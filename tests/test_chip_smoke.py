"""chip_smoke.py off the chip: it must fail without an accelerator, and its
phase functions — the same ones ``main`` runs at full size on the TPU — must
pass their own device-work checks at tiny sizes on the CPU backend, and fail
them when a device fault pushes work onto the host lane.

The platform check lives in the script's child entry, not in the phase
functions, so these tests set it aside by calling the functions directly;
no option or environment variable of the script relaxes it.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", os.path.join(REPO_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json_lines(text: str) -> list:
    return [json.loads(ln) for ln in text.splitlines() if ln.startswith("{")]


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_smoke_fails_fast_without_accelerator(argv):
    """conftest holds every child to JAX_PLATFORMS=cpu: the first child's
    platform check fails, no later phase starts, the last line says so."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py"), *argv],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True, timeout=120, cwd=REPO_ROOT,
    )
    assert proc.returncode != 0
    lines = _json_lines(proc.stdout)
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    phases = [ln for ln in lines if "phase" in ln]
    assert len(phases) == 1 and phases[0]["ok"] is False and "no accelerator" in phases[0]["error"]


def test_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program beside it: non-zero, no result."""
    import shutil

    shutil.copy(os.path.join(REPO_ROOT, "chip_smoke.py"), tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, timeout=120, cwd=tmp_path, env=env,
    )
    assert proc.returncode != 0
    assert not any(ln.get("ok") for ln in _json_lines(proc.stdout))


_TINY_REPLAY = dict(
    blocks=24, tpb=4, coalesce=64, pretrace_buckets=(8,), min_super_bucket=8,
    need_muhash_device=False, expect_kernels=("schnorr",),
)


@pytest.mark.slow
def test_phases_pass_their_device_checks_at_tiny_size(smoke):
    """kernels + replay + daemon on the CPU backend: the XLA ladder is the
    device lane here, so the expected kernel names differ from the chip's."""
    k = smoke.phase_kernels(top=16, bottom=8, muhash_sizes=(64,), oracle_lanes=16, expect_kernels=("schnorr", "ecdsa"))
    assert k["counters"]["secp_device_jobs"] == 48 and k["counters"]["secp_degraded_jobs"] == 0
    assert k["counters"]["muhash_device_dispatches"] == {"64": 1}
    r = smoke.phase_replay(**_TINY_REPLAY)
    assert r["pipelined"] == r["reference"] and r["reference"]["sink"] == r["sim_sink"]
    assert r["counters"]["secp_device_jobs"] == sum(r["counters"]["txscript_batch_jobs"].values()) > 0
    d = smoke.phase_daemon(spends=3, expect_kernels=("schnorr",))
    assert d["spends_confirmed"] == 3 and d["counters"]["secp_degraded_jobs"] == 0
    assert d["device"]["platform"] == "cpu"  # what the child entry would refuse


def test_injected_device_fault_fails_the_smoke_not_the_fingerprints(smoke):
    """One ``device.verify`` fault: the breaker's host lane answers that
    batch bit-identically, so every fingerprint still matches — and the
    smoke must fail all the same, on the degraded counters."""
    from kaspa_tpu.resilience.breaker import device_breaker
    from kaspa_tpu.resilience.faults import FAULTS

    FAULTS.configure({"device.verify": {"mode": "error", "hits": [3]}})
    try:
        with pytest.raises(smoke.SmokeFailure, match="host degraded lane") as failed:
            smoke.phase_replay(**_TINY_REPLAY)
    finally:
        FAULTS.clear()
        device_breaker().reset()
    ev = failed.value.evidence
    assert ev["pipelined"] == ev["reference"] and ev["reference"]["sink"] == ev["sim_sink"]
    assert ev["counters"]["secp_degraded_jobs"] > 0
    assert ev["counters"]["secp_device_jobs"] + ev["counters"]["secp_degraded_jobs"] == sum(
        ev["counters"]["txscript_batch_jobs"].values()
    )


@pytest.mark.parametrize("kind", ["schnorr", "ecdsa"])
def test_spoiled_batches_mean_what_they_say(kind):
    """The smoke's expected masks come from construction: hold the
    construction itself to the host oracle, every invalid class included."""
    from kaspa_tpu.crypto import eclib
    from kaspa_tpu.sim import sigbatch

    items = (sigbatch.schnorr_items if kind == "schnorr" else sigbatch.ecdsa_items)(20, seed=99)
    host = eclib.schnorr_verify if kind == "schnorr" else eclib.ecdsa_verify
    assert all(host(*it) for it in items)  # distinct, valid lanes to begin with
    assert len({it[0] for it in items}) == 20
    spoiled, expect, classes = sigbatch.spoil(kind, items, every=2, seed=3)
    assert {c for c in classes if c} == set(sigbatch.INVALID_CLASSES)
    assert expect == [c is None for c in classes]
    assert [bool(host(*it)) for it in spoiled] == expect
