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
from kaspa_tpu.txscript.caches import VerdictMemo

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


# --- PR 37: a multi-block virtual cycle, measured from inside -----------------------


def _replay_in_one_cycle(dag, traced: bool, memo=None):
    """Every block of ``dag`` queued while the commit lock is held, so that one
    cycle absorbs them all; with capture on, or with tracing disabled, and
    over ``memo`` as the script-verdict memo where one is given.  Beside
    the consensus and the statuses: the spans, the counters' movement, and for
    each ``precompute_chain`` call the blocks it computed and those it published."""
    from kaspa_tpu.consensus.consensus import Consensus
    from kaspa_tpu.pipeline.pipeline import ConsensusPipeline

    consensus = Consensus(dag.params)
    if memo is not None:
        consensus.transaction_validator.tx_memo = memo
    pipe = ConsensusPipeline(consensus, workers=2)
    verifier, segments = consensus.speculative, []
    replay, publish, precompute = consensus._calculate_utxo_state, verifier._publish, verifier.precompute_chain

    def spy_replay(*args, **kwargs):
        if kwargs.get("cause") == "segment":
            segments[-1][0].append(kwargs["token_ns"])
        return replay(*args, **kwargs)

    def spy_publish(entry):
        if threading.current_thread().name == "kaspa-virtual":
            segments[-1][1].append(entry.block)
        return publish(entry)

    def spy_precompute(chain):
        segments.append(([], []))
        return precompute(chain)

    consensus._calculate_utxo_state, verifier._publish, verifier.precompute_chain = spy_replay, spy_publish, spy_precompute
    coalesce.configure(64)
    if traced:
        trace.set_capture(1 << 16)
        trace.drain()
    else:
        trace.disable()
    before = _counters()
    try:
        with pipe._lock:  # nothing commits until every block is queued
            futures = [pipe.submit(b) for b in dag.blocks]
        statuses = [f.result(timeout=300) for f in futures]
        after = _counters()
        spans = trace.drain()
    finally:
        pipe.shutdown()
        trace.set_capture(0)
        trace.enable()
        coalesce.configure(0)
    return consensus, statuses, spans, lambda name: _moved(after, before, name), segments


def _simpa_toy_dag():
    """A toy DAG of simpa's shape: about four blocks wide, two late siblings
    with a failed spend, 64 blocks."""
    from benchmarks import harness

    network = {"bps": 4, "delay_s": 1.0, "miners": 1, "own_blocks_delayed": True, "ghostdag_k": 55}
    workload = {"config": "toy", "mode": "catchup", "tx_shape": "fanout-then-1to1", "window_blocks": 24, "sig_samples": 0,
                "tx_per_block": 3, "spoiled_blocks": 2, "pool_factor": 8, "gap_stratum_blocks": 4}
    assert not flight.enabled()
    coalesce.configure(0)  # the build is the in-order run
    dag = harness.build_dag(workload, {"name": "toy", "network": network, "pipeline": {"coalesce": 64, "stage_workers": 2}}, 36, lambda _m: None)
    assert len(dag.blocks) <= 64 and len(dag.spoiled) == 2
    return dag


@pytest.fixture(scope="module")
def cycle():
    """The toy DAG of simpa's shape resolved in one virtual cycle."""
    dag = _simpa_toy_dag()
    return dag, _replay_in_one_cycle(dag, traced=True)


def test_one_cycle_absorbed_every_block_and_counted_its_candidates(cycle):
    dag, (_, _, spans, moved, _) = cycle
    assert moved("pipeline_virtual_cycles") == 1 and moved("pipeline_virtual_cycle_blocks") == len(dag.blocks)
    (virtual,) = [s for s in _named(spans, "pipeline.virtual") if not s["attrs"].get("shared")]
    assert virtual["attrs"]["batch"] == len(dag.blocks)
    assert 0 < virtual["attrs"]["candidates"] == moved("virtual_chain_blocks_verified") < len(dag.blocks)


def test_chain_verifications_are_hits_plus_misses(cycle):
    _, (_, _, spans, moved, _) = cycle
    hits, misses = moved("speculative_hits"), moved("speculative_misses")
    assert hits > 0 and misses > 0 and hits + misses == moved("virtual_chain_blocks_verified")
    verifies = _named(spans, "virtual.chain_verify")
    assert sum(v["attrs"]["hits"] for v in verifies) == hits and sum(v["attrs"]["misses"] for v in verifies) == misses
    for v in verifies:  # nothing is disqualified on a tip's chain here: the failed spends sit in merged siblings
        assert v["attrs"]["qualified"] == v["attrs"]["hits"] + v["attrs"]["misses"] == v["attrs"]["blocks"]


def test_one_chain_commit_span_a_verified_block_with_its_source(cycle):
    _, (_, _, spans, moved, _) = cycle
    commits = _named(spans, "virtual.chain_commit")
    assert len(commits) == moved("virtual_chain_blocks_verified")
    by_source = collections.Counter(c["attrs"]["source"] for c in commits)
    assert by_source == {"cache": moved("speculative_hits"), "sync": moved("speculative_misses")}
    assert all(c["attrs"]["ok"] is True for c in commits)
    verifies = _named(spans, "virtual.chain_verify")
    assert all(any(_inside(c, v) for v in verifies) for c in commits)


def test_one_mergeset_replay_span_a_call_with_its_cause(cycle):
    _, (_, _, spans, moved, segments) = cycle
    replays = _named(spans, "virtual.mergeset_replay")
    by_cause = collections.Counter(r["attrs"]["cause"] for r in replays)
    assert set(by_cause) == {"stage", "segment", "fallback", "virtual"}
    assert by_cause["fallback"] == moved("speculative_misses")  # a miss is a replay made again
    assert by_cause["segment"] == sum(len(computed) for computed, _ in segments) == moved("speculative_chain_blocks_computed")
    assert by_cause["virtual"] == 1  # the virtual's own mergeset, once a cycle
    for r in replays:
        assert r["attrs"]["fallback"] is (r["attrs"]["cause"] == "fallback")
        assert r["attrs"]["blocks"] >= 1 and r["attrs"]["txs"] >= 0
    # where each is opened: the stage workers' under their own span, a segment's under precompute_chain's
    outer = {"stage": "speculative.precompute", "segment": "speculative.chain_precompute", "fallback": "virtual.chain_verify", "virtual": "virtual.commit"}
    for r in replays:
        assert outer[r["attrs"]["cause"]] in r["path"].split("/"), r["path"]


def test_a_segment_publishes_a_prefix_and_discards_from_the_failed_block_on(cycle):
    dag, (consensus, _, spans, moved, segments) = cycle
    computed, discarded = moved("speculative_chain_blocks_computed"), moved("speculative_chain_blocks_discarded")
    published = sum(len(p) for _, p in segments)
    assert computed == published + discarded and 0 < discarded < computed
    precomputes = _named(spans, "speculative.chain_precompute")
    assert len(precomputes) == len(segments)
    for span, (seg_computed, seg_published) in zip(precomputes, segments):
        attrs = span["attrs"]
        assert (attrs["computed"], attrs["published"]) == (len(seg_computed), len(seg_published))
        assert attrs["computed"] + attrs["reused"] <= attrs["blocks"] and attrs["jobs"] >= 0
        assert seg_published == seg_computed[: len(seg_published)]  # a prefix, in chain order
        if len(seg_published) < len(seg_computed):
            failed = seg_computed[len(seg_published)]  # the first discarded: its mergeset holds a failed spend
            assert set(consensus.storage.ghostdag.get(failed).unordered_mergeset()) & set(dag.spoiled)
    assert moved("speculative_invalidations").get("script", 0) == sum(len(p) < len(c) for c, p in segments)
    # what was discarded was computed again: every discarded block is a miss
    assert discarded <= moved("speculative_misses")


def test_collected_transactions_are_the_collect_spans(cycle):
    _, (_, _, spans, moved, _) = cycle
    collects = _named(spans, "txscript.collect")
    assert moved("txscript_collected_txs") == sum(c["attrs"]["txs"] for c in collects) > 0
    assert moved("txscript_sync_collected_txs") == sum(c["attrs"]["txs"] for c in collects if not c["attrs"]["speculative"]) > 0
    # the memo is asked in collect_tx only: a selected parent's transactions and one with a missing input never reach it
    assert moved("txscript_collected_txs") >= moved("txscript_tx_memo_lookups") > 0


def test_commit_lock_wait_says_who_waited(cycle):
    dag, (_, _, spans, _, _) = cycle
    waits = _named(spans, "wait.consensus-commit_lock")
    by_who = collections.Counter(w["attrs"]["who"] for w in waits)
    assert by_who == {"stage": len(dag.blocks), "speculate": len(dag.blocks), "virtual": 1}
    hashes = {b.hash.hex() for b in dag.blocks}
    assert all(w["trace"] in hashes for w in waits)
    # the stage workers queued behind the test's hold of the lock: their wait is time, not a stamp
    assert max(w["dur_us"] for w in waits if w["attrs"]["who"] == "stage") > 0


def test_removed_spans_are_gone(cycle):
    names = {s["name"] for s in cycle[1][2]}
    assert "pipeline.precompute" not in names and "speculative.begin" not in names
    assert {"pipeline.stage", "speculative.precompute", "speculative.wait"} <= names


def test_tracing_disabled_leaves_the_same_state(cycle):
    dag, (traced, statuses, _, _, _) = cycle
    plain, plain_statuses, spans, moved, _ = _replay_in_one_cycle(dag, traced=False)
    assert spans == [] and plain_statuses == statuses
    assert moved("speculative_hits") + moved("speculative_misses") == moved("virtual_chain_blocks_verified") > 0  # counters need no tracer
    sink = traced.sink()
    assert plain.sink() == sink == dag.sinks[-1]
    assert plain.multisets[sink].finalize() == traced.multisets[sink].finalize()
    assert plain.get_virtual_daa_score() == traced.get_virtual_daa_score()
    assert plain.virtual_state.parents == traced.virtual_state.parents
    assert plain.virtual_state.accepted_tx_ids == traced.virtual_state.accepted_tx_ids
    for b in dag.blocks:
        assert plain.storage.statuses.get(b.hash) == traced.storage.statuses.get(b.hash)
        assert plain.acceptance_data.get(b.hash) == traced.acceptance_data.get(b.hash)


def _reader_span(name, start_us, end_us, **attrs):
    return {"name": name, "start_ns": start_us * 1000, "end_ns": end_us * 1000, "dur_us": float(end_us - start_us), "attrs": attrs}


@pytest.mark.parametrize(
    "spans, expected",
    [
        # the longest of the named spans, in the metric's unit; the shared copy of a cycle and other names left out
        ([_reader_span("cycle", 0, 12_000), _reader_span("cycle", 20_000, 27_500), _reader_span("cycle", 0, 90_000, shared=True),
          _reader_span("other", 0, 10**6)], 12.0),
        ([_reader_span("cycle", 5, 10)], 0.005),
        ([_reader_span("cycle", 0, 90_000, shared=True)], None),  # every one excluded: nothing to read
        ([_reader_span("other", 0, 10)], None),  # the parent's program has no such span
        ([], None),
    ],
)
def test_span_max_reads_the_longest_of_the_named_spans(spans, expected):
    from benchmarks.readers import span_max

    source = {"reader": "span_max", "span": "cycle", "exclude_attrs": {"shared": True}, "unit_scale": 0.001}
    got = span_max.read(source, {"spans": spans, "window": {"blocks": 3}})
    assert got == expected if expected is None else abs(got - expected) < 1e-12


PR37_METRICS = [
    "chain_candidates_per_block", "chain_verify_fallback_pct", "chain_precompute_discarded_pct", "script_collected_txs_per_block",
    "script_collected_sync_pct", "chain_recompute_ms_per_block.catchup", "mergeset_replay_uncovered_ms_per_block.catchup",
    "chain_commit_ms_per_block.catchup", "virtual_sink_search_uncovered_ms_per_block.catchup", "virtual_cycle_max_ms.catchup",
    "commit_lock_wait_ms_per_block.catchup", "verify_calls_per_block",
]


@pytest.mark.parametrize("metric", PR37_METRICS)
def test_each_new_metric_reads_the_captured_cycle_and_nothing_from_a_bare_program(metric, cycle):
    """``benchmarks/tests/test_benchmark_files.py``'s checks for the twelve new
    files, and each metric read through its reader from the captured cycle; from
    a window with neither the spans nor the counters it reads nothing and does
    not raise (what the parent commit gives)."""
    import importlib
    import os

    from benchmarks import harness

    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    spec = harness.load_json(os.path.join(harness.HERE, "metrics", f"{metric}.json"))
    assert spec["name"] == metric and spec["bench_source"] == entry["source"] and spec["note"]
    assert all(spec[k] == entry[k] for k in ("layer", "unit", "better", "moves"))
    assert entry["moves"] == "catchup_blocks_per_s" and len(entry["workloads"]) == 4 and all("catchup" in c for c in entry["workloads"])
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"] if m["name"] not in PR37_METRICS}  # a layer the benchmark already names
    reader = importlib.import_module(f"benchmarks.readers.{spec['source']['reader']}")
    dag, (_, _, spans, moved, _) = cycle
    names = ("virtual_chain_blocks_verified", "pipeline_virtual_cycle_blocks", "speculative_misses", "speculative_chain_blocks_computed",
             "speculative_chain_blocks_discarded", "txscript_collected_txs", "txscript_sync_collected_txs", "secp_device_dispatches")
    ctx = {"spans": spans, "counters": {n: moved(n) for n in names}, "window": {"blocks": len(dag.blocks)}}
    value = reader.read(spec["source"], ctx)
    assert value is not None and value > 0
    if spec["unit"] == "%":
        assert value <= 100.0
    assert reader.read(spec["source"], {"spans": [], "counters": {}, "window": {"blocks": len(dag.blocks)}}) is None


# --- the script-verdict memo's running epoch over the same cycle ---------------------


class _RandomEvictionMemo(VerdictMemo):
    """The memo without epochs: every insert evicts at random at the bound, as the signature cache does."""

    def insert(self, key, value, protect=False):
        super().insert(key, value)

    def protect(self, keys):
        pass


def test_a_cycle_past_the_memo_bound_leaves_the_state_of_a_memo_that_never_evicts(cycle):
    """The toy cycle asks ≈ 100 memo keys ≈ 300 times; at a bound of 32 the
    running epoch holds them all until the cycle ends, and random eviction
    loses most of them between two asks."""
    dag = cycle[0]
    runs = {}
    for name, memo in (
        ("never_evicts", VerdictMemo(size=1 << 30)),
        ("epochs", VerdictMemo(size=32, seed=40)),
        ("random_eviction", _RandomEvictionMemo(size=32, seed=40)),
    ):
        consensus, statuses, _, moved, _ = _replay_in_one_cycle(dag, traced=False, memo=memo)
        assert moved("pipeline_virtual_cycles") == 1
        sink = consensus.sink()
        consensus._move_utxo_position(sink)
        state = {
            "statuses": statuses,
            "final_statuses": [consensus.storage.statuses.get(b.hash) for b in dag.blocks],
            "sink": sink,
            "utxo_set": {(op.transaction_id, op.index): e for op, e in consensus.utxo_set.items()},
            "commitment": consensus.multisets[sink].finalize(),
        }
        runs[name] = state, memo.hits / (memo.hits + memo.misses), moved("txscript_tx_memo_over_bound"), len(memo._map)
    never, epochs, random_eviction = runs["never_evicts"], runs["epochs"], runs["random_eviction"]
    assert epochs[0] == never[0] and random_eviction[0] == never[0] and never[0]["sink"] == dag.sinks[-1]
    assert epochs[1] > random_eviction[1] + 0.1
    assert epochs[2] > 0 and epochs[3] == 32  # past the bound while the cycle ran, trimmed to it at its end
    assert never[2] == random_eviction[2] == 0 and random_eviction[3] == 32
