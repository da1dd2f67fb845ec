"""Device-runtime supervision: watchdog, hang requeue, canary re-probe,
warm-kernel manifest, and bounded dispatcher shutdown.

The invariants under test: a hung device call costs one watchdog deadline
of latency, never a lost or double-resolved batch (the host degraded lane
answers bit-identically and any late device result is discarded); breaker
HALF_OPEN probes come only from the background canary, never from a live
super-batch; and a wedged dispatcher thread cannot block daemon shutdown.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from kaspa_tpu.crypto import eclib, secp
from kaspa_tpu.ops import dispatch
from kaspa_tpu.resilience import breaker as breaker_mod
from kaspa_tpu.resilience import supervisor
from kaspa_tpu.resilience.breaker import CLOSED, HALF_OPEN, HUNG, OPEN, CircuitBreaker
from kaspa_tpu.resilience.faults import FAULTS


@pytest.fixture(autouse=True)
def _clean_supervision():
    """Every test starts and ends disarmed, unmanaged, breaker CLOSED."""
    FAULTS.clear()
    breaker_mod.device_breaker().reset()
    yield
    FAULTS.clear()
    breaker_mod.device_breaker().reset()
    breaker_mod.device_breaker().set_managed(False)


def _poll(pred, timeout_s: float = 5.0) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return pred()


# --- watchdog worker pool -------------------------------------------------


def test_supervised_passthrough_result_and_exception():
    assert supervisor.run_supervised(lambda: 41 + 1) == 42

    def boom():
        raise ValueError("device said no")

    with pytest.raises(ValueError, match="device said no"):
        supervisor.run_supervised(boom)


def test_watchdog_timeout_abandons_and_discards_late_result():
    pool = supervisor.WorkerPool()
    release = threading.Event()

    def slow():
        release.wait(5.0)
        return "late"

    with pytest.raises(supervisor.DeviceHangError) as ei:
        pool.run(slow, 0.1, "dispatch", kernel="k", jobs=3)
    assert ei.value.tier == "dispatch" and ei.value.jobs == 3
    snap = pool.snapshot()
    assert snap["timeouts"] == {"dispatch": 1} and snap["abandoned_threads"] == 1

    # the abandoned worker finishes later: its result is discarded (late),
    # and a fresh worker serves the next call untouched
    release.set()
    assert _poll(lambda: pool.snapshot()["late_results"] == 1, 2.0)
    assert pool.run(lambda: "ok", 1.0, "dispatch") == "ok"
    assert pool.snapshot()["completed"] == 1
    pool.shutdown()


def test_deadline_overrides_scoped_and_restored():
    base = supervisor.deadline_s("dispatch")
    with supervisor.deadline_overrides(dispatch_s=0.5):
        assert supervisor.deadline_s("dispatch") == 0.5
        with supervisor.deadline_overrides(compile_s=1.5):
            assert supervisor.deadline_s("dispatch") == 0.5
            assert supervisor.deadline_s("compile") == 1.5
        assert supervisor.deadline_s("dispatch") == 0.5
    assert supervisor.deadline_s("dispatch") == base


# --- hung dispatch -> host requeue, bit-identical -------------------------


def _signed_items(n: int, seed: int = 11) -> list:
    sk = (seed * 2 + 1) % eclib.N or 1
    pub = eclib.schnorr_pubkey(sk)
    items = []
    for i in range(n):
        msg = bytes([i]) * 32
        items.append((pub, msg, eclib.schnorr_sign(msg, sk)))
    return items


def test_hung_dispatch_requeues_bit_identical_and_trips_hung():
    items = _signed_items(3)
    # corrupt one signature: the mask must stay the exact eclib oracle
    pub, msg, sig = items[1]
    items[1] = (pub, msg, sig[:40] + bytes([sig[40] ^ 1]) + sig[41:])
    oracle = [eclib.schnorr_verify(p, m, s) for p, m, s in items]
    secp.schnorr_verify_batch(items)  # warm the bucket: tier stays "dispatch"

    br = breaker_mod.device_breaker()
    before = supervisor.verdict()["requeued"]["batches"]
    late_before = supervisor._POOL.snapshot()["late_results"]
    FAULTS.configure({"device.hang": {"mode": "wedge", "delay": 0.8, "hits": [1]}}, seed=0)
    with supervisor.deadline_overrides(dispatch_s=0.2):
        t0 = time.monotonic()
        mask = np.asarray(secp.schnorr_verify_batch(items))
        waited = time.monotonic() - t0

    assert mask.tolist() == oracle  # host lane answered, bit-identical
    assert waited < 0.8  # one deadline of stall, not the full hang
    assert br.state == OPEN and br.last_trip_cause == HUNG  # immediate trip
    assert supervisor.verdict()["requeued"]["batches"] == before + 1
    # the wedged worker unblocks at 0.8s; its outcome must be discarded
    assert _poll(lambda: supervisor._POOL.snapshot()["late_results"] > late_before, 3.0)


def test_compile_stall_requeues_and_leaves_shape_cold():
    from kaspa_tpu.resilience.sustain import _compile_stall_drill

    res = _compile_stall_drill(seed=3, stall_delay_s=0.6, compile_deadline_s=0.15)
    assert res["injected"] == 1
    assert res["all_valid"]  # host lane verified every triple correctly
    # the abandoned compile must not leave the shape marked warm
    assert res["shape_left_cold"]
    assert breaker_mod.device_breaker().last_trip_cause == HUNG


# --- canary prober --------------------------------------------------------


def test_hung_trip_recovers_via_injected_canary():
    br = breaker_mod.device_breaker()
    probes = []
    supervisor.install(pretrace=False, probe_fn=lambda: probes.append(1) or True)
    try:
        assert supervisor.installed()
        br.record_failure(cause=HUNG)
        assert br.state == OPEN
        # managed: live dispatches stay degraded even after the backoff
        assert br.allow() is False
        assert _poll(lambda: br.state == CLOSED, 10.0), br.snapshot()
        assert probes and br.recoveries >= 1
    finally:
        supervisor.shutdown()
    assert not supervisor.installed()


def test_canary_probe_cannot_race_live_dispatch():
    br = CircuitBreaker("race-test", failure_threshold=1, backoff_base=0.01)
    br.set_managed(True)
    br.record_failure(cause=HUNG)
    time.sleep(0.05)  # backoff elapsed: legacy allow() would go HALF_OPEN
    assert br.reopen_due()

    denied = []

    def hammer():
        denied.extend(br.allow() for _ in range(50))

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not any(denied)  # no live dispatch ever claimed the probe slot
    assert br.state == OPEN

    assert br.allow(probe=True) is True  # the canary's slot, exactly one
    assert br.state == HALF_OPEN
    assert br.allow(probe=True) is False  # second probe: already in flight
    br.record_success()
    assert br.state == CLOSED
    assert br.allow(probe=True) is False  # nothing to probe when CLOSED
    tos = [t["to"] for t in br.snapshot()["transitions"]]
    assert tos == [OPEN, HALF_OPEN, CLOSED]  # observable via the collector


# --- dispatcher shutdown under a hung device thread -----------------------


def _blocking_verify(monkeypatch):
    entered, release = threading.Event(), threading.Event()

    def fake(items):
        entered.set()
        release.wait(10.0)
        return np.ones(len(items), dtype=bool)

    monkeypatch.setattr(secp, "schnorr_verify_batch", fake)
    return entered, release


def test_close_abandons_hung_device_thread(monkeypatch):
    entered, release = _blocking_verify(monkeypatch)
    eng = dispatch.CoalescingDispatcher(64, 0.01)
    ticket = eng.submit("schnorr", _signed_items(2))
    assert entered.wait(5.0)  # dispatcher thread is wedged inside the call

    assert eng.close(timeout=0.2) is False  # bounded: did not join the hang
    stats = eng.stats()
    assert stats["abandoned"] and stats["unresolved_chunks"] == 0
    with pytest.raises(dispatch.DispatchAbandoned):
        ticket.wait(1.0)

    # the wedged thread finishes later: first resolution wins, the late
    # mask is discarded and the verdict does not flip
    release.set()
    time.sleep(0.1)
    with pytest.raises(dispatch.DispatchAbandoned):
        ticket.wait(0.1)


def test_dispatch_timeout_names_super_batch_and_verdict(monkeypatch):
    entered, release = _blocking_verify(monkeypatch)
    eng = dispatch.CoalescingDispatcher(64, 0.01)
    ticket = eng.submit("schnorr", _signed_items(2))
    assert entered.wait(5.0)
    with pytest.raises(dispatch.DispatchTimeout) as ei:
        ticket.wait(0.3)
    e = ei.value
    assert isinstance(e, TimeoutError)  # infrastructure, not consensus
    assert e.kind == "schnorr" and e.jobs == 2
    assert e.super_id is not None  # the super-batch had formed
    assert e.verdict["watchdog"] in ("on", "off")
    release.set()
    assert np.asarray(ticket.wait(5.0)).tolist() == [True, True]
    assert eng.close(timeout=5.0) is True


# --- warm-kernel manifest -------------------------------------------------


def test_warm_manifest_roundtrip(monkeypatch, tmp_path):
    path = tmp_path / "warm_manifest.json"
    monkeypatch.setenv("KASPA_TPU_WARM_MANIFEST", str(path))
    supervisor.note_shape("schnorr_verify", 8)
    supervisor.note_shape("schnorr_verify", 8)  # dedup
    supervisor.note_shape("ecdsa_verify", 16)
    assert len(supervisor.load_warm_entries()) == 2

    # an entry compiled under another backend must not be pretraced here
    import json

    doc = json.loads(path.read_text())
    doc["entries"].append({"kernel": "schnorr_verify", "bucket": 32, "mesh": 1,
                           "backend": "tpu-v6", "jax_version": "0.0.0"})
    path.write_text(json.dumps(doc))
    rep = supervisor.cache_report()
    assert rep["manifest_path"] == str(path)
    assert rep["entries_total"] == 3 and len(rep["entries"]) == 2

    traced = []
    monkeypatch.setattr(secp, "pretrace_bucket", lambda k, b: traced.append((k, b)) or "traced")
    rows = supervisor.pretrace_warm()
    assert traced == [("schnorr_verify", 8), ("ecdsa_verify", 16)]  # smallest first
    assert [r["status"] for r in rows] == ["traced", "traced"]
    assert all(r["seconds"] >= 0 for r in rows)

    rows = supervisor.pretrace_warm(budget_s=-1.0)  # exhausted budget
    assert [r["status"] for r in rows] == ["skipped:budget"] * 2


def test_warm_manifest_of_an_older_tree(monkeypatch, tmp_path):
    """A manifest an older tree wrote: every row carries a ``family``, and one
    names a kernel this tree no longer has.  Boot's pretrace raises nothing,
    answers that row as it answers any unknown kernel, and still warms the
    others; a row with the key and ours without it are one shape."""
    import json

    path = tmp_path / "warm_manifest.json"
    monkeypatch.setenv("KASPA_TPU_WARM_MANIFEST", str(path))
    env = supervisor._env_key()
    path.write_text(json.dumps({"entries": [
        {"kernel": "schnorr_aggregate", "bucket": 16, "family": "aggregate", **env},
        {"kernel": "schnorr_verify", "bucket": 8, "family": "ladder", **env},
    ]}))
    # the real pretrace_bucket: bucket 8 marked compiled, so nothing compiles
    monkeypatch.setattr(secp, "_seen_shapes", {("schnorr_verify", 8, env["mesh"])})
    rows = supervisor.pretrace_warm()
    assert [(r["kernel"], r["bucket"], r["status"]) for r in rows] == [
        ("schnorr_verify", 8, "warm"),
        ("schnorr_aggregate", 16, "error:unknown schnorr_aggregate/16"),
    ]
    supervisor.note_shape("schnorr_verify", 8)
    assert supervisor.cache_report()["entries_total"] == 2


def test_pretrace_bucket_rejects_unknown():
    assert supervisor.run_supervised(lambda: None) is None  # smoke: pool alive
    assert secp.pretrace_bucket("no_such_kernel", 8).startswith("error:")
    assert secp.pretrace_bucket("schnorr_verify", 4).startswith("error:")


# --- the wedge drill, tier-1-fast variant ---------------------------------


def test_mini_wedge_drill_bit_identical(tmp_path):
    """End-to-end drill on a tiny hostile DAG: compile stall injected
    mid-run, canary-driven recovery, bit-identity against the fault-free
    replay, and exact requeue/ticket accounting.  (The 24-block variant
    with live dispatch hangs is tools/roundcheck.py's supervision lane.)"""
    from kaspa_tpu.resilience.sustain import run_wedge_drill
    from kaspa_tpu.sim.simulator import SimConfig

    cfg = SimConfig(bps=2, delay=2.0, num_miners=2, num_blocks=6,
                    txs_per_block=2, seed=5, hostile=True)
    report = run_wedge_drill(
        cfg, seed=5, out=str(tmp_path / "SUSTAIN_WEDGE.json"),
        hang_delay_s=1.5, dispatch_deadline_s=2.0,
        stall_delay_s=1.0, compile_deadline_s=0.3,
        hang_hits=(1,), recovery_timeout_s=15.0,
    )
    det, sup = report["deterministic"], report["supervisor"]
    assert det["matches_fault_free"], det
    assert sup["requeue_matches_injected"], sup
    assert sup["recovered"], sup
    assert report["compile_stall"]["all_valid"]
    assert report["compile_stall"]["shape_left_cold"]
    assert report["tickets"]["ok"], report["tickets"]
    assert report["breaker"]["managed"] is True
    assert (tmp_path / "SUSTAIN_WEDGE.json").exists()
