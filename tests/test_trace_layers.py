"""PR 26's spans and counters, on the CPU: a small signed DAG replayed through
``ConsensusPipeline`` with span capture on (the XLA ladder at bucket 8 is the
device lane here), then what each new span and counter says about it.

One simulated DAG and one captured replay serve the whole module; nothing
timed here is a device number."""

import collections
import threading

import numpy as np
import pytest

from kaspa_tpu.observability import flight, trace
from kaspa_tpu.observability.core import REGISTRY
from kaspa_tpu.ops import dispatch as coalesce

FLUSH_REASONS = {"nudge", "age", "size", "drain"}


def _counters():
    return REGISTRY.snapshot()["counters"]


def _moved(after, before, name):
    a, b = after.get(name, 0), before.get(name, 0)
    if isinstance(a, dict):
        return {k: v - (b or {}).get(k, 0) for k, v in a.items() if v - (b or {}).get(k, 0)}
    return a - b


def _inside(inner, outer):
    return outer["start_ns"] <= inner["start_ns"] and inner["end_ns"] <= outer["end_ns"]


@pytest.fixture(scope="module")
def sim():
    from kaspa_tpu.sim.simulator import SimConfig, simulate

    res = simulate(SimConfig(bps=2, num_blocks=30, txs_per_block=3, seed=11))
    assert res.total_txs > 0
    return res


@pytest.fixture(scope="module")
def replay(sim):
    """(spans, counter readings before/after, block hashes as hex) of one
    pipelined replay with capture on, the recorder off, coalescing at 64."""
    from kaspa_tpu.sim.simulator import replay_pipelined

    assert not flight.enabled()
    coalesce.configure(64)
    trace.set_capture(1 << 16)
    trace.drain()
    before = _counters()
    try:
        replay_pipelined(sim)
        after = _counters()
        spans = trace.drain()
    finally:
        trace.set_capture(0)
        coalesce.configure(0)
    return spans, before, after, {b.hash.hex() for b in sim.blocks}


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


# --- Part 1: the verify round trip ------------------------------------------


def test_host_prepare_once_per_super_batch(replay):
    spans = replay[0]
    prepares, supers = _named(spans, "secp.host_prepare"), _named(spans, "dispatch.super_batch")
    assert supers and len(prepares) == len(supers)
    for p in prepares:
        assert p["attrs"]["kernel"] == "schnorr_verify" and p["attrs"]["jobs"] > 0
        assert any(_inside(p, s) and s["attrs"]["jobs"] == p["attrs"]["jobs"] for s in supers)


def test_host_prepare_stays_one_span_a_batch_with_the_native_lift(replay):
    """The batched lift_x call runs inside ``secp.host_prepare``: the span
    keeps its two attributes, nothing per job is opened or labelled, and the
    two lift counters account for every job the spans prepared."""
    from kaspa_tpu.crypto import hostcrypto

    spans, before, after, _ = replay
    prepares = _named(spans, "secp.host_prepare")
    assert prepares and all(set(p["attrs"]) == {"kernel", "jobs"} for p in prepares)
    assert not [s["name"] for s in spans if "lift" in s["name"]]
    assert len(prepares) < sum(p["attrs"]["jobs"] for p in prepares)  # a span a batch, not a job
    lifted = _moved(after, before, "secp_host_lift_jobs")
    assert isinstance(lifted, int) and lifted == sum(p["attrs"]["jobs"] for p in prepares)
    native = _moved(after, before, "secp_native_lift_jobs")
    assert isinstance(native, int) and native == (lifted if hostcrypto.lib() is not None else 0)


def test_device_call_and_readback_once_per_device_dispatch(replay):
    spans = replay[0]
    dispatches = _named(spans, "secp.device_dispatch")
    calls, reads, marshals = (_named(spans, n) for n in ("secp.device_call", "secp.readback", "secp.host_marshal"))
    assert dispatches and len(calls) == len(reads) == len(dispatches)
    for d in dispatches:
        inner = [s for s in calls + reads if s["thread"] == d["thread"] and _inside(s, d)]
        assert sorted(s["name"] for s in inner) == ["secp.device_call", "secp.readback"]
        call = next(s for s in inner if s["name"] == "secp.device_call")
        b = d["attrs"]["batch"]
        # three [b,16] limb planes, two [b,64] digit planes (int32), b flags
        assert call["attrs"] == {"kernel": "schnorr", "lanes": b, "bytes": (3 * 16 + 2 * 64) * 4 * b + b}
    # one marshal a batch, by the lane that runs it: _Batch.run hands its
    # byte columns over and packs nothing itself
    assert len(marshals) == len(dispatches)
    assert all(m["attrs"]["kernel"] == "schnorr" and {"batch", "lanes"} <= set(m["attrs"]) for m in marshals)


def test_wait_dispatch_names_what_flushed_the_queue(replay):
    waits = _named(replay[0], "wait.dispatch")
    assert waits and all(w["attrs"].get("reason") in FLUSH_REASONS for w in waits)
    assert sum(_moved(replay[2], replay[1], "dispatch_flushes").values()) > 0


def test_secp_device_lanes_counts_the_launched_width(replay):
    _, before, after, _ = replay
    buckets = _moved(after, before, "secp_device_buckets")
    jobs, lanes = _moved(after, before, "secp_device_jobs"), _moved(after, before, "secp_device_lanes")
    # on the XLA lane the program is as wide as the bucket
    assert lanes == sum(int(b) * n for b, n in buckets.items()) and 0 < jobs <= lanes


@pytest.mark.parametrize("b, lanes", [(1, 256), (16, 256), (256, 256), (257, 512), (1000, 1024)])
def test_pallas_launched_lanes_are_whole_blocks(b, lanes):
    from kaspa_tpu.ops.secp256k1 import ladder_pallas

    assert ladder_pallas.launched_lanes(b) == lanes


def test_pallas_programs_are_named_on_the_device():
    from kaspa_tpu.ops.secp256k1 import ladder_pallas

    names = {ladder_pallas._kernel_name(e) for e in (False, True)}
    assert names == {"secp256k1_ladder_schnorr", "secp256k1_ladder_ecdsa"}


def test_padded_lanes_help_says_bucket_not_device():
    from kaspa_tpu.crypto import secp

    assert "bucket" in secp._PADDED_LANES.help and "wasted" not in secp._PADDED_LANES.help
    assert "_bucket" in secp._OCCUPANCY.help


# --- Part 2: the muhash round trip ------------------------------------------


def _preimages(n, tag):
    return [bytes([tag, i]) * 20 for i in range(n)]


def _captured(call):
    """(result, spans, counters moved) of one call with span capture on."""
    trace.set_capture(1 << 10)
    trace.drain()
    before = _counters()
    try:
        got = call()
        spans = trace.drain()
    finally:
        trace.set_capture(0)
    after = _counters()
    return got, spans, lambda name: _moved(after, before, name)


@pytest.mark.parametrize("n, dispatches, pad", [(40, 1, 40), (200, 4, 8)])
def test_muhash_product_over_the_threshold_counts_device_elements(n, dispatches, pad):
    """However many chunks a product has, they are launched together and read
    back in one wait: one ``muhash.device_dispatch`` span, the padding outside it."""
    from kaspa_tpu.crypto import muhash

    got, spans, moved = _captured(lambda: muhash.bulk_element_product(_preimages(n, 1)))
    assert moved("muhash_device_elements") == n
    assert moved("muhash_device_dispatches") == {"64": dispatches}
    assert moved("muhash_device_waits") == 1
    assert moved("muhash_host_elements") == 0
    assert got == muhash.bulk_element_product(_preimages(n, 1), use_device=False)
    phases = [(s["attrs"]["phase"], s["attrs"]["elements"]) for s in _named(spans, "muhash.host_prepare")]
    assert phases == [("elements", n), ("pad", pad)]
    (dispatch,) = _named(spans, "muhash.device_dispatch")
    assert dispatch["attrs"] == {"bucket": 64, "elements": n, "dispatches": dispatches}
    assert not any(_inside(p, dispatch) for p in _named(spans, "muhash.host_prepare"))


def test_muhash_commit_with_two_device_products_waits_once(monkeypatch):
    """The numerator's chunks are on the device while the denominator's
    elements are derived: one wait and one span for the commit, and the
    second product's preparation lies inside that span."""
    from kaspa_tpu.crypto import muhash

    monkeypatch.setattr(muhash, "_tx_element_preimages", lambda adds, removes, daa: (adds, removes))
    adds, removes = _preimages(100, 3), _preimages(70, 4)
    committed = muhash.MuHash()
    _, spans, moved = _captured(lambda: committed.add_transactions_batch([(adds, removes, 0)]))
    assert moved("muhash_device_dispatches") == {"64": 4}
    assert moved("muhash_device_elements") == 170
    assert moved("muhash_device_waits") == 1
    on_host = muhash.MuHash()
    on_host.add_transactions_batch([(adds, removes, 0)], use_device=False)
    assert (committed.numerator, committed.denominator) == (on_host.numerator, on_host.denominator)
    (dispatch,) = _named(spans, "muhash.device_dispatch")
    assert dispatch["attrs"] == {"bucket": 64, "elements": 170, "dispatches": 4}
    prepares = [(s["attrs"]["phase"], s["attrs"]["elements"], _inside(s, dispatch)) for s in _named(spans, "muhash.host_prepare")]
    assert prepares == [
        ("preimages", 170, False), ("elements", 100, False), ("pad", 36, False), ("elements", 70, True), ("pad", 6, True),
    ]
    (commit,) = _named(spans, "muhash.commit")
    assert dispatch["parent"] == commit["span"] and _inside(dispatch, commit)


def _recording_tree_product(monkeypatch):
    """``_tree_product`` replaced by a host fake whose results say when they
    are read: the returned list holds ("launch", i) / ("read", i) in order."""
    from kaspa_tpu.ops import bigint as bi
    from kaspa_tpu.ops import muhash_ops

    events = []

    class Result:
        def __init__(self, i, limbs):
            self.i, self.limbs = i, limbs

        def __array__(self, *args, **kwargs):
            events.append(("read", self.i))
            return self.limbs

    def fake(x, levels):
        rows = np.asarray(x)
        assert rows.shape == (1 << levels, muhash_ops.F.W) and rows.dtype == np.int32
        product = 1
        for row in rows:
            product = product * bi.limbs_to_int(row) % muhash_ops.F.modulus
        i = sum(kind == "launch" for kind, _ in events)
        events.append(("launch", i))
        return Result(i, bi.ints_to_limbs([product], muhash_ops.F.W)[0].astype(np.int32))

    monkeypatch.setattr(muhash_ops, "_tree_product", fake)
    return events


def test_every_launch_of_a_group_precedes_its_first_read(monkeypatch):
    from kaspa_tpu.crypto import muhash

    events = _recording_tree_product(monkeypatch)
    lists = [_preimages(200, 5), _preimages(10, 6), _preimages(70, 7)]  # 4 chunks, host, 2 chunks
    got = muhash.bulk_element_products(lists)
    assert [e[0] for e in events] == ["launch"] * 6 + ["read"] * 6
    assert got == muhash.bulk_element_products(lists, use_device=False)


def test_in_flight_bound_reads_a_group_back_before_the_next_launch(monkeypatch):
    import random

    from kaspa_tpu.ops import muhash_ops

    rng = random.Random(12)
    vals = [rng.randrange(muhash_ops.F.modulus) for _ in range(300)]  # 4 x 64 + 44: five chunks
    expected = 1
    for v in vals:
        expected = expected * v % muhash_ops.F.modulus
    monkeypatch.setattr(muhash_ops, "MAX_IN_FLIGHT", 2)
    got, spans, moved = _captured(lambda: muhash_ops.batch_product_ints(vals))
    assert got == expected
    assert moved("muhash_device_dispatches") == {"64": 5} and moved("muhash_device_waits") == 3
    dispatches = _named(spans, "muhash.device_dispatch")
    assert [(d["attrs"]["dispatches"], d["attrs"]["elements"]) for d in dispatches] == [(2, 128), (2, 128), (1, 44)]
    assert all(a["end_ns"] <= b["start_ns"] for a, b in zip(dispatches, dispatches[1:]))
    # and with the fake: never more than two launched and unread
    events = _recording_tree_product(monkeypatch)
    assert muhash_ops.batch_product_ints(vals) == expected
    assert [e[0] for e in events] == ["launch", "launch", "read", "read"] * 2 + ["launch", "read"]


def test_muhash_product_under_the_threshold_counts_host_elements():
    from kaspa_tpu.crypto import muhash

    before = _counters()
    muhash.bulk_element_product(_preimages(10, 2))
    after = _counters()
    assert _moved(after, before, "muhash_host_elements") == 10
    assert _moved(after, before, "muhash_device_elements") == 0
    assert _moved(after, before, "muhash_device_dispatches") == {}


def test_muhash_commit_opens_one_preimage_span(replay):
    spans = replay[0]
    commits = _named(spans, "muhash.commit")
    assert commits
    for c in commits:
        inner = [s for s in _named(spans, "muhash.host_prepare") if s["thread"] == c["thread"] and _inside(s, c)]
        assert [s["attrs"]["phase"] for s in inner if s["attrs"]["phase"] == "preimages"] == ["preimages"]
        assert all(isinstance(s["attrs"]["elements"], int) for s in inner)
    _, before, after, _ = replay
    assert _moved(after, before, "muhash_host_elements") + _moved(after, before, "muhash_device_elements") > 0


# --- Part 3: the virtual stage outside its round trips ------------------------


def test_script_collect_span_counts_the_jobs_it_staged(replay):
    spans, before, after, _ = replay
    collects = _named(spans, "txscript.collect")
    assert collects and all({"txs", "jobs", "speculative"} <= set(c["attrs"]) for c in collects)
    assert {c["attrs"]["speculative"] for c in collects} <= {True, False}
    assert all(0 <= c["attrs"]["jobs"] for c in collects) and any(c["attrs"]["jobs"] > 0 for c in collects)
    # every job staged under a collect span was queued, and the device answered every queued job
    queued = sum(_moved(after, before, "txscript_batch_jobs").values())
    assert sum(c["attrs"]["jobs"] for c in collects) == queued == _moved(after, before, "secp_device_jobs")
    # the span closes before the round trip starts
    waits = _named(spans, "txscript.dispatch_wait")
    assert not any(_inside(w, c) for c in collects for w in waits if w["thread"] == c["thread"])


def test_move_position_span_says_how_far_it_walked(replay):
    moves = _named(replay[0], "virtual.move_position")
    assert moves
    for m in moves:
        assert m["attrs"]["unapplied"] >= 0 and m["attrs"]["applied"] >= 0
        assert m["attrs"]["unapplied"] + m["attrs"]["applied"] > 0  # an unmoved position opens no span


# --- Part 4: a block's spans share its id ------------------------------------


def test_every_span_of_a_block_carries_its_hash(replay):
    spans, _, _, hashes = replay
    under_root = [s for s in spans if s["path"].startswith("pipeline.block")]
    assert under_root and all(s["trace"] in hashes for s in under_root)
    # what has no block is what several blocks share: the dispatcher's
    # super-batch and the supervised worker under it, on their own threads
    for s in spans:
        if s["trace"] is None:
            assert not s["thread"].startswith("kaspa-"), s
    for name in ("wait.stage", "wait.virtual", "pipeline.stage", "pipeline.virtual", "wait.dispatch"):
        assert all(s["trace"] in hashes for s in _named(spans, name)), name


def test_one_root_span_per_block_from_submit_to_resolution(replay):
    spans, _, _, hashes = replay
    roots = _named(spans, "pipeline.block")
    assert collections.Counter(r["trace"] for r in roots) == collections.Counter(hashes)
    by_trace = {r["trace"]: r for r in roots}
    for r in roots:
        assert r["parent"] == 0 and r["attrs"]["status"] in ("utxo_valid", "utxo_pending")
    for s in _named(spans, "pipeline.stage") + _named(spans, "wait.stage"):
        root = by_trace[s["trace"]]
        assert s["parent"] == root["span"] and _inside(s, root)


def _submit_one(sim):
    """The pipeline's task for one submitted block, once it resolved."""
    from kaspa_tpu.consensus.consensus import Consensus
    from kaspa_tpu.pipeline.pipeline import ConsensusPipeline

    pipe = ConsensusPipeline(Consensus(sim.params), workers=1)
    seen = []
    register = pipe.deps.register
    pipe.deps.register = lambda h, task: (seen.append(task), register(h, task))[1]
    try:
        assert pipe.submit(sim.blocks[0]).result(timeout=60) in ("utxo_valid", "utxo_pending")
    finally:
        pipe.shutdown()
    return seen[0]


def test_with_no_sink_a_task_has_no_context(sim):
    assert not trace.sinks_active()
    assert _submit_one(sim).ctx is None


def test_with_the_recorder_on_the_block_root_is_the_recorders(sim):
    flight.enable(ring=8)
    trace.set_capture(1 << 12)
    trace.drain()
    try:
        ctx = _submit_one(sim).ctx
        spans = trace.drain()
        done = flight.traces()
    finally:
        trace.set_capture(0)
        flight.disable()
        flight.reset()
    assert ctx.trace_id == sim.blocks[0].hash.hex() and ctx.path.startswith("block:")
    assert not _named(spans, "pipeline.block")
    assert [t["trace"] for t in done] == [sim.blocks[0].hash.hex()]
    assert [s["name"] for s in done[0]["spans"] if s["parent"] == 0] == ["block"]


def test_critical_path_of_a_fixed_span_set_is_unchanged():
    """The recorder's walk over a block whose virtual cycle holds the new
    spans: the same numbers as before they existed, by hand."""
    def s(name, sid, parent, t0, t1):
        return {"name": name, "span": sid, "parent": parent, "start_ns": t0, "end_ns": t1}

    spans = [
        s("block", 1, 0, 0, 1000),
        s("wait.stage", 2, 1, 0, 100),
        s("pipeline.stage", 3, 1, 100, 300),
        s("wait.virtual", 4, 1, 300, 400),
        s("pipeline.virtual", 5, 1, 400, 980),
        s("txscript.collect", 6, 5, 420, 500),
        s("txscript.dispatch_wait", 7, 5, 500, 800),
        s("wait.dispatch", 8, 7, 500, 560),
        s("dispatch.device", 9, 7, 560, 790),
        s("muhash.commit", 10, 5, 810, 900),
        s("muhash.host_prepare", 11, 10, 810, 850),
        s("virtual.move_position", 12, 5, 900, 940),
    ]
    cp = flight.critical_path(spans, 1)
    assert cp["total_ns"] == 1000 and cp["attributed_ns"] == 980
    assert cp["stages"] == {
        "block": 20, "wait.stage": 100, "pipeline.stage": 200, "wait.virtual": 100,
        "pipeline.virtual": 20 + 10 + 40, "txscript.collect": 80, "txscript.dispatch_wait": 10,
        "wait.dispatch": 60, "dispatch.device": 230, "muhash.commit": 50, "muhash.host_prepare": 40,
        "virtual.move_position": 40,
    }


# --- the tracer's two additions -----------------------------------------------


def test_span_set_adds_attributes_known_only_afterwards():
    trace.set_capture(16)
    trace.drain()
    try:
        with trace.span("t.region", a=1) as sp:
            sp.set(b=2)
        (rec,) = trace.drain()
    finally:
        trace.set_capture(0)
    assert rec["attrs"] == {"a": 1, "b": 2}
    trace.disable()
    try:
        with trace.span("t.region") as sp:
            sp.set(b=2)  # the shared no-op span takes it and keeps nothing
    finally:
        trace.enable()


def test_root_context_and_record_root_share_one_id():
    trace.set_capture(16)
    trace.drain()
    try:
        ctx = trace.root_context("ab" * 32, "pipeline.block")
        done = threading.Event()

        def child():
            with trace.span("t.child", parent=ctx):
                pass
            done.set()

        threading.Thread(target=child).start()
        assert done.wait(10)
        trace.record_root(ctx, 100, 50, status="ok")  # an end before the start is clamped
        child_rec, root = trace.drain()
    finally:
        trace.set_capture(0)
    assert root["name"] == root["path"] == "pipeline.block" and root["span"] == ctx.span_id and root["parent"] == 0
    assert root["dur_us"] == 0.0 and root["attrs"] == {"status": "ok"}
    assert child_rec["parent"] == root["span"] and child_rec["trace"] == root["trace"] == "ab" * 32
    assert child_rec["path"] == "pipeline.block/t.child"
    trace.record_root(ctx, 0, 10)  # no sink: the histogram only
    assert trace.drain() == []


def test_mesh_product_counts_its_elements(on_mesh_devices):
    from kaspa_tpu.crypto import muhash
    from kaspa_tpu.ops import mesh

    mesh.configure(2)
    try:
        before = _counters()
        got = muhash.bulk_element_product(_preimages(40, 3))
        after = _counters()
    finally:
        mesh.configure(1)
    assert _moved(after, before, "muhash_device_elements") == 40
    assert got == muhash.bulk_element_product(_preimages(40, 3), use_device=False)


def test_tree_product_lowers_under_its_named_scope():
    """The scope reaches the HLO's op metadata (what a profile's metadata
    plane and an HLO dump show); lowering only, nothing is compiled."""
    import jax

    from kaspa_tpu.ops import muhash_ops

    x = jax.ShapeDtypeStruct((2, muhash_ops.F.W), np.int32)
    text = muhash_ops._tree_product.lower(x, levels=1).as_text(debug_info=True)
    assert "muhash_tree_product" in text
