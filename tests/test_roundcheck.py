"""Round-evidence tooling: the roundcheck artifact and where its lanes write."""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _root_files() -> dict:
    """Digest of every regular file in the repo root (where the lanes used to
    write), by name."""
    out = {}
    for name in sorted(os.listdir(REPO_ROOT)):
        path = os.path.join(REPO_ROOT, name)
        if os.path.isfile(path):
            with open(path, "rb") as f:
                out[name] = hashlib.sha256(f.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def round_run(tmp_path_factory):
    """One roundcheck run with the lanes tier-1 can afford (sim, serving,
    tenbps, supervision), shared by the tests below."""
    out = tmp_path_factory.mktemp("round") / "ROUNDCHECK.json"
    root_before = _root_files()
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO_ROOT, "tools", "roundcheck.py"),
            "--skip-tests",
            # the mesh lanes re-trace the verify ladder in fresh subprocesses
            # (minutes on CPU) — they get their own roundcheck run per round,
            # not a seat inside the tier-1 fast lane; same for the chaos
            # sustain run (three full replays of a hostile workload)
            # and the obs lane (traced 24-block replay plus a tracing-off
            # overhead A/B whose 2% gate is noise under suite load)
            "--skip-mesh",
            "--skip-chaos",
            "--skip-obs",
            # and the fabric drill (a verifyd subprocess + three replays)
            "--skip-fabric",
            # and the ingest lane (an identity-check subprocess + a 24-block
            # tx-flood sustain replay)
            "--skip-ingest",
            # and the brownout ramp drill (another 24-block flood replay)
            "--skip-overload",
            # and the swarm drill (three live nodes over loopback sockets
            # running a full partition/heal/late-join scenario — minutes
            # of wall; it gets its own `roundcheck --only swarm` run)
            "--skip-swarm",
            # and the serving latency observatory (a 50k-virtual-subscriber
            # ramp + overhead A/B, minutes of wall and timing-sensitive —
            # it gets its own `roundcheck --only serving_load` run)
            "--skip-serving_load",
            # and the lint lane: the v2 gate runs the gated kernel-shape
            # audit (real eval_shape traces, ~50 s on CPU) — it gets its
            # own `roundcheck --only lint` acceptance run
            "--skip-lint",
            "--blocks",
            "8",
            "--out",
            str(out),
        ],
        cwd=REPO_ROOT,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        timeout=300,
    )
    return proc, out, root_before, _root_files()


def test_roundcheck_writes_round_evidence(round_run):
    proc, out, _before, _after = round_run
    assert proc.returncode == 0, proc.stdout
    evidence = json.loads(out.read_text())
    assert evidence["ok"] is True
    sim = evidence["sections"]["sim"]
    assert sim["ok"] and sim["result"]["blocks"] == 8
    assert "created" in evidence


def test_roundcheck_lanes_write_beside_out(round_run):
    """The lanes' own artifacts land in the directory of --out, and the repo
    root (where they are tracked files) is as it was."""
    proc, out, before, after = round_run
    assert proc.returncode == 0, proc.stdout
    assert "supervision" in json.loads(out.read_text())["sections"]
    assert json.loads((out.parent / "SUSTAIN_WEDGE.json").read_text())
    assert after == before


def test_roundcheck_only_selector(tmp_path):
    """--only SECTION runs exactly the named sections (skip flags ignored)
    and every section records its own wall_seconds in the artifact."""
    out = tmp_path / "RC.json"
    proc = subprocess.run(
        [
            sys.executable, os.path.join(REPO_ROOT, "tools", "roundcheck.py"),
            "--only", "sim", "--skip-sim", "--blocks", "8", "--out", str(out),
        ],
        cwd=REPO_ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout
    evidence = json.loads(out.read_text())
    assert list(evidence["sections"]) == ["sim"]
    assert evidence["sections"]["sim"]["wall_seconds"] >= 0
    # unknown section names fail fast instead of silently running nothing
    bad = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "roundcheck.py"), "--only", "nope"],
        cwd=REPO_ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, timeout=60,
    )
    assert bad.returncode != 0 and "unknown --only" in bad.stdout
