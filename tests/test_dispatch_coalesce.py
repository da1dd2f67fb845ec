"""Cross-block coalescing verify dispatch (ops/dispatch.py).

The contract under test: routing signature jobs through the coalescing
queue is invisible in results — masks and BatchScriptChecker decisions
are bit-identical to per-block blocking dispatch (verify masks are
per-lane functions of each triple; batch composition cannot change
them) — while jobs from multiple submitters merge into one super-batch.

Shape discipline: every device call here lands in the same padded
bucket-8 shape the other verify tests use (each new bucket costs a
fresh XLA compile on CPU, minutes of tier-1 budget).
"""

import hashlib
import random
import threading
import time

import numpy as np
import pytest

from kaspa_tpu.observability.core import REGISTRY
from kaspa_tpu.ops import dispatch as coalesce


@pytest.fixture(autouse=True)
def _coalesce_off_after():
    yield
    coalesce.configure(0)


def _schnorr_items(n: int, corrupt_every: int = 4):
    from kaspa_tpu.crypto import eclib

    items = []
    for i in range(n):
        sk = i + 1
        msg = hashlib.sha256(bytes([i, n])).digest()
        sig = eclib.schnorr_sign(msg, sk)
        if corrupt_every and i % corrupt_every == corrupt_every - 1:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        items.append((eclib.schnorr_pubkey(sk), msg, sig))
    return items


# --- configuration ----------------------------------------------------------


def test_configure_modes():
    assert coalesce.configure(0) == 0
    assert coalesce.active() is None
    assert coalesce.drain() is True  # no-op when disabled
    assert coalesce.configure("off") == 0
    assert coalesce.configure(16) == 16
    assert coalesce.active() is not None and coalesce.active().target == 16
    assert coalesce.configure(2) == 8  # clamps up to the min bucket
    assert coalesce.configure(1 << 20) == 16384  # clamps down to the max
    state = REGISTRY.snapshot()["dispatch"]
    assert state["enabled"] and state["target"] == 16384
    assert coalesce.configure(None) == 0  # env default: off
    assert REGISTRY.snapshot()["dispatch"]["enabled"] is False


def test_configure_auto_is_the_default_target_and_opens_no_file(monkeypatch):
    import builtins

    opened = []
    real_open = builtins.open
    monkeypatch.setattr(builtins, "open", lambda *a, **k: opened.append(a) or real_open(*a, **k))
    assert coalesce.configure("auto") == coalesce.DEFAULT_TARGET == 1024
    assert opened == []


def test_dead_verify_switches_change_nothing(monkeypatch):
    """The two variables that used to pick another Schnorr formulation are
    set on purpose: a mixed batch still gets the oracle's mask from one pass
    of the Schnorr builder and one dispatch of kernel ``schnorr``."""
    from kaspa_tpu.crypto import eclib, secp
    from kaspa_tpu.observability import trace

    monkeypatch.setenv("KASPA_TPU_VERIFY_MODE", "aggregate")
    monkeypatch.setenv("KASPA_TPU_GLV", "1")
    items = _schnorr_items(7, corrupt_every=3)
    items[4] = (items[4][0], items[4][1], b"\xff" * 32 + items[4][2][32:])  # r >= p: refused on the host
    want = [bool(eclib.schnorr_verify(*it)) for it in items]
    assert any(want) and not all(want)
    secp.verify_batch("schnorr", items)  # bucket 8 is warm from here on
    trace.set_capture(1 << 12)
    trace.drain()
    before = REGISTRY.snapshot()["counters"].get("secp_device_dispatches", {})
    try:
        mask = secp.verify_batch("schnorr", items)
        spans = trace.drain()
    finally:
        trace.set_capture(0)
    assert np.asarray(mask).tolist() == want
    after = REGISTRY.snapshot()["counters"]["secp_device_dispatches"]
    assert {k: v - before.get(k, 0) for k, v in after.items() if v - before.get(k, 0)} == {"schnorr": 1}
    assert [s["attrs"]["kernel"] for s in spans if s["name"] == "secp.host_prepare"] == ["schnorr_verify"]


# --- engine mechanics -------------------------------------------------------


def test_empty_submit_resolves_immediately():
    coalesce.configure(16)
    t = coalesce.active().submit("schnorr", [])
    assert t.done() and list(t.wait(1.0)) == []


def test_chunks_coalesce_into_one_super_batch(monkeypatch):
    """Three chunks from one submitter, age parked high: nothing flushes
    until the first wait() nudges — then all three go out as ONE
    super-batch, sliced back per-ticket bit-identically to a direct
    batched call over the same items."""
    from kaspa_tpu.crypto import secp

    monkeypatch.setenv("KASPA_TPU_COALESCE_AGE_MS", "10000")
    coalesce.configure(16)
    eng = coalesce.active()

    items = _schnorr_items(7)
    direct = np.asarray(secp.schnorr_verify_batch(items)).tolist()
    before = REGISTRY.snapshot()["counters"].get("dispatch_flushes", {})

    t1 = eng.submit("schnorr", items[:2])
    t2 = eng.submit("schnorr", items[2:4])
    t3 = eng.submit("schnorr", items[4:])
    got = [bool(v) for t in (t1, t2, t3) for v in t.wait(300.0)]
    assert got == direct
    assert not all(got) and any(got)  # mixed validity actually exercised

    snap = REGISTRY.snapshot()
    flushes = snap["counters"]["dispatch_flushes"]
    assert flushes.get("nudge", 0) == before.get("nudge", 0) + 1
    assert sum(flushes.values()) == sum(before.values()) + 1  # exactly one flush
    assert snap["counters"]["dispatch_coalesced_jobs"]["schnorr"] >= 7
    assert snap["histograms"]["dispatch_coalesce_depth"]["count"] >= 1


def test_drain_resolves_everything(monkeypatch):
    monkeypatch.setenv("KASPA_TPU_COALESCE_AGE_MS", "10000")
    coalesce.configure(16)
    eng = coalesce.active()
    items = _schnorr_items(7)
    tickets = [eng.submit("schnorr", items[:3]), eng.submit("schnorr", items[3:])]
    assert coalesce.drain(timeout=300.0) is True
    assert all(t.done() for t in tickets)
    assert eng.stats()["unresolved_chunks"] == 0


def test_kernel_error_surfaces_on_ticket():
    coalesce.configure(16)
    t = coalesce.active().submit("schnorr", [(None, None, None)])
    with pytest.raises(TypeError):
        t.wait(300.0)


# --- close() racing an in-flight job ----------------------------------------


def _hold_kernel(monkeypatch):
    """Replace the schnorr kernel with one that parks inside the device
    call until released, so a super-batch can be held in flight while the
    test races close() against it."""
    from kaspa_tpu.crypto import secp

    entered, release = threading.Event(), threading.Event()
    real = secp.schnorr_verify_batch

    def slow(items):
        entered.set()
        release.wait(30.0)
        return real(items)

    monkeypatch.setattr(secp, "schnorr_verify_batch", slow)
    return entered, release, real


def _count_resolves(monkeypatch):
    counts: dict[int, int] = {}
    orig = coalesce.Ticket._resolve

    def counting(self, mask, error):
        counts[id(self)] = counts.get(id(self), 0) + 1
        return orig(self, mask, error)

    monkeypatch.setattr(coalesce.Ticket, "_resolve", counting)
    return counts


def test_close_waits_out_in_flight_job(monkeypatch):
    """close() while a super-batch is mid-device-call and the call
    finishes inside the drain window: the ticket resolves exactly once,
    with its real mask — close never clobbers a job that is about to
    complete."""
    entered, release, real = _hold_kernel(monkeypatch)
    counts = _count_resolves(monkeypatch)
    coalesce.configure(16)
    eng = coalesce.active()

    items = _schnorr_items(7)
    direct = np.asarray(real(items)).tolist()
    t = eng.submit("schnorr", items)
    eng.nudge()
    assert entered.wait(30.0)  # the chunk is now inside the kernel

    threading.Timer(0.3, release.set).start()
    assert eng.close(timeout=30.0) is True
    assert [bool(v) for v in t.wait(1.0)] == direct
    assert counts[id(t)] == 1


def test_close_timeout_abandons_in_flight_job_exactly_once(monkeypatch):
    """close() whose drain window expires while the job is still wedged
    in the device call: the ticket fails with DispatchAbandoned, and the
    late result the hung thread eventually produces is discarded at the
    chunk layer — the ticket resolves exactly once, never a second time."""
    entered, release, _ = _hold_kernel(monkeypatch)
    counts = _count_resolves(monkeypatch)
    coalesce.configure(16)
    eng = coalesce.active()

    finishes: list[bool] = []
    orig_finish = eng._finish

    def recording_finish(chunk, mask, error):
        r = orig_finish(chunk, mask, error)
        finishes.append(r)
        return r

    monkeypatch.setattr(eng, "_finish", recording_finish)

    t = eng.submit("schnorr", _schnorr_items(7))
    eng.nudge()
    assert entered.wait(30.0)

    assert eng.close(timeout=0.2) is False  # drain expires, job still wedged
    assert t.done()
    with pytest.raises(coalesce.DispatchAbandoned):
        t.wait(1.0)
    assert eng.stats()["abandoned"] is True
    assert finishes == [True]  # the abandon resolution

    # let the wedged kernel call complete; its late result must be
    # discarded (finish returns False), not resolved into the ticket
    release.set()
    deadline = time.monotonic() + 30.0
    while len(finishes) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert finishes == [True, False]
    assert counts[id(t)] == 1
    with pytest.raises(coalesce.DispatchAbandoned):
        t.wait(1.0)  # still the abandonment, not the late mask


# --- the production path ----------------------------------------------------


def _p2pk_tx(seed: int, corrupt: bool):
    from kaspa_tpu.consensus import hashing as chash
    from kaspa_tpu.consensus.model import (
        SUBNETWORK_ID_NATIVE,
        ComputeCommit,
        Transaction,
        TransactionInput,
        TransactionOutpoint,
        TransactionOutput,
        UtxoEntry,
    )
    from kaspa_tpu.crypto import eclib
    from kaspa_tpu.txscript import standard

    rng = random.Random(seed)
    sk = rng.randrange(1, eclib.N)
    pub = eclib.schnorr_pubkey(sk)
    spk = standard.pay_to_pub_key(pub)
    entry = UtxoEntry(10_000, spk, 5, False)
    tx = Transaction(
        0,
        [TransactionInput(TransactionOutpoint(bytes([seed]) * 32, 0), b"", 0, ComputeCommit.sigops(1))],
        [TransactionOutput(9_000, spk)], 0, SUBNETWORK_ID_NATIVE, 0, b"",
    )
    reused = chash.SigHashReusedValues()
    msg = chash.calc_schnorr_signature_hash(tx, [entry], 0, chash.SIG_HASH_ALL, reused)
    sig = eclib.schnorr_sign(msg, sk, rng.randbytes(32))
    if corrupt:
        sig = sig[:9] + bytes([sig[9] ^ 1]) + sig[10:]
    tx.inputs[0].signature_script = standard.schnorr_signature_script(sig, chash.SIG_HASH_ALL)
    return tx, [entry]


def _run_checker(txs):
    from kaspa_tpu.txscript.batch import BatchScriptChecker
    from kaspa_tpu.txscript.caches import SigCache

    checker = BatchScriptChecker(SigCache())  # fresh cache: no cross-run skips
    for token, (tx, entries) in enumerate(txs):
        checker.collect_tx(token, tx, entries)
    return {
        t: None if e is None else (getattr(e, "input_index", None), str(e))
        for t, e in checker.dispatch().items()
    }


def test_checker_decisions_identical_coalesced_vs_legacy():
    """BatchScriptChecker fast-path decisions must be bit-identical with
    the coalescing queue on vs off (the acceptance criterion's unit-level
    form; the sim replay covers the full-block form)."""
    txs = [_p2pk_tx(seed, corrupt=(seed % 3 == 0)) for seed in range(40, 47)]
    coalesce.configure(0)
    legacy = _run_checker(txs)
    coalesce.configure(16)
    coalesced = _run_checker(txs)
    assert legacy == coalesced
    assert any(v is not None for v in legacy.values()) and any(v is None for v in legacy.values())


def test_dispatch_async_detaches_the_handle():
    """dispatch_async() snapshots the collected jobs: jobs collected
    afterwards belong to the NEXT dispatch, and result() is idempotent."""
    from kaspa_tpu.txscript.batch import BatchScriptChecker
    from kaspa_tpu.txscript.caches import SigCache

    coalesce.configure(16)
    txs = [_p2pk_tx(seed, corrupt=(seed == 51)) for seed in range(50, 53)]
    checker = BatchScriptChecker(SigCache())
    checker.collect_tx(0, *txs[0])
    checker.collect_tx(1, *txs[1])
    handle = checker.dispatch_async()
    checker.collect_tx(2, *txs[2])  # lands in the next dispatch, not this one

    first = handle.result()
    assert set(first) == {0, 1}
    assert first[0] is None and first[1] is not None
    assert handle.result() is first  # idempotent

    second = checker.dispatch()
    assert set(second) == {2} and second[2] is None


def test_dispatch_async_works_with_coalescing_off():
    coalesce.configure(0)
    txs = [_p2pk_tx(seed, corrupt=(seed == 61)) for seed in range(60, 63)]
    from kaspa_tpu.txscript.batch import BatchScriptChecker
    from kaspa_tpu.txscript.caches import SigCache

    checker = BatchScriptChecker(SigCache())
    for token, (tx, entries) in enumerate(txs):
        checker.collect_tx(token, tx, entries)
    res = checker.dispatch_async().result()
    assert res[0] is None and res[1] is not None and res[2] is None


# --- full-replay bit-identity (slow lane; roundcheck's dispatch section
# carries the fast per-round evidence) ---------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("mesh_n", [1, 8])
def test_sim_replay_identical_coalesced_vs_legacy(mesh_n, on_mesh_devices):
    """Same simulated DAG, coalescing off vs on: sink + utxo_commitment
    must be byte-identical, on single-device and 8-way mesh dispatch."""
    from kaspa_tpu.ops import mesh
    from kaspa_tpu.sim.simulator import SimConfig, replay, simulate

    res = simulate(SimConfig(bps=2, delay=2.0, num_miners=4, num_blocks=64, txs_per_block=4, seed=42))
    assert res.total_txs > 0  # real signature batches actually flow

    mesh.configure(mesh_n)
    try:
        with on_mesh_devices(*(("schnorr",) if mesh_n > 1 else ())):
            coalesce.configure(0)
            _, legacy = replay(res)
            sink_l = legacy.sink()
            commit_l = legacy.multisets[sink_l].finalize().hex()

            coalesce.configure(64)
            _, co = replay(res)
            sink_c = co.sink()
            commit_c = co.multisets[sink_c].finalize().hex()
    finally:
        mesh.configure(1)

    assert sink_l == sink_c
    assert commit_l == commit_c
