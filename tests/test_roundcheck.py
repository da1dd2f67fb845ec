"""Round-evidence tooling: roundcheck artifact + bench probe / no-TPU behaviour."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_roundcheck_writes_round_evidence(tmp_path):
    out = tmp_path / "ROUNDCHECK.json"
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO_ROOT, "tools", "roundcheck.py"),
            "--skip-tests",
            "--skip-bench",
            # the mesh lanes re-trace the verify ladder in fresh subprocesses
            # (minutes on CPU) — they get their own roundcheck run per round,
            # not a seat inside the tier-1 fast lane; same for the chaos
            # sustain run (three full replays of a hostile workload) and the
            # coalesced-dispatch throughput lane (bench child + dual replay)
            # and the obs lane (traced 24-block replay plus a tracing-off
            # overhead A/B whose 2% gate is noise under suite load)
            "--skip-mesh",
            "--skip-chaos",
            "--skip-dispatch",
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
            # and the aggregated-verify lane: its bench child traces BOTH
            # verify lanes from a cold process (minutes of XLA compile on
            # CPU, ~5x everything else in this run combined) — it gets its
            # own `roundcheck --only aggregate` acceptance run
            "--skip-aggregate",
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
    assert proc.returncode == 0, proc.stdout
    evidence = json.loads(out.read_text())
    assert evidence["ok"] is True
    sim = evidence["sections"]["sim"]
    assert sim["ok"] and sim["result"]["blocks"] == 8
    assert "created" in evidence


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


def _run_bench(extra_env: dict, argv=()):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py"), *argv],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True, timeout=300,
    )


@pytest.mark.parametrize(
    "extra_env,argv",
    [
        ({"KASPA_TPU_BENCH_CHILD": "1", "KASPA_TPU_BENCH_B": "8"}, ()),  # the measuring child itself
        ({}, ()),  # the jax-free parent, headline
        ({}, ("--sweep",)),  # ... and the sweep
    ],
    ids=["child", "parent", "sweep"],
)
def test_bench_exits_nonzero_without_tpu(extra_env, argv):
    """bench.py measures a TPU or nothing: on the CPU backend it exits
    non-zero and no line it prints carries a value — there is no CPU lane
    whose number could be read beside a device metric."""
    proc = _run_bench(extra_env, argv)
    assert proc.returncode != 0
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert lines, "bench.py must say why it measured nothing"
    for obj in lines:
        assert "value" not in obj and "cpu_fallback_value" not in obj
    last = lines[-1]
    assert "tpu" in (last.get("error") or last.get("child_error") or "").lower()


def test_bench_probe_mode_emits_json_line():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["KASPA_TPU_BENCH_CHILD"] = "1"
    env["KASPA_TPU_BENCH_MODE"] = "probe"
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py")],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        text=True,
        timeout=180,
    )
    line = [ln for ln in proc.stdout.strip().splitlines() if ln.startswith("{")][-1]
    obj = json.loads(line)
    assert obj["probe_ok"] is True and proc.returncode == 0
    assert obj["platform"] == "cpu" and obj["device_platform"] == "cpu"
