"""Concurrent pipeline tests: the port of the reference's
consensus_pipeline_tests.rs (test_concurrent_pipeline /
test_concurrent_pipeline_random) plus deps-manager unit coverage.

Blocks are real (built against a scratch consensus), then submitted to a
fresh pipelined consensus concurrently / out of order / in duplicate —
results must match a sequential replay bit-for-bit and the reachability
intervals must stay valid.
"""

import random
import threading

import pytest

from kaspa_tpu.consensus.consensus import Consensus
from kaspa_tpu.consensus.params import simnet_params
from kaspa_tpu.consensus.processes.coinbase import MinerData
from kaspa_tpu.consensus.model import ScriptPublicKey
from kaspa_tpu.pipeline import BlockTaskDependencyManager, ConsensusPipeline

MINER = MinerData(ScriptPublicKey(0, b"\x20" + b"\x07" * 32 + b"\xac"))


def _build_dag(topology):
    """topology: list of (name, [parent names]); returns (params, blocks in
    topology order) built/validated on a scratch consensus."""
    params = simnet_params()
    scratch = Consensus(params)
    by_name = {"G": params.genesis.hash}
    blocks = []
    for i, (name, parent_names) in enumerate(topology):
        parents = [by_name[p] for p in parent_names]
        blk = scratch.build_block_with_parents(parents, MINER)
        blk.header.nonce = i + 1  # disambiguate same-parent siblings
        blk.header.invalidate_cache()
        scratch.validate_and_insert_block(blk)
        by_name[name] = blk.hash
        blocks.append(blk)
    return params, blocks, by_name


TOPOLOGY = [
    ("2", ["G"]),
    ("3", ["G"]),
    ("4", ["2", "3"]),
    ("5", ["4"]),
    ("6", ["G"]),
    ("7", ["5", "6"]),
    ("8", ["G"]),
    ("9", ["G"]),
    ("10", ["7", "8", "9"]),
    ("11", ["G"]),
    ("12", ["11", "10"]),
]


def test_concurrent_pipeline():
    """Reference: consensus_pipeline_tests.rs test_concurrent_pipeline —
    every block submitted twice concurrently; reachability relations and
    intervals must come out exact."""
    params, blocks, names = _build_dag(TOPOLOGY)
    consensus = Consensus(params)
    pipe = ConsensusPipeline(consensus, workers=3)
    try:
        for blk in blocks:
            f1 = pipe.submit(blk)
            f2 = pipe.submit(blk)  # duplicate: absorbed by the task group
            assert f1.result(timeout=60) in ("utxo_valid", "utxo_pending")
            assert f2.result(timeout=60) in ("utxo_valid", "utxo_pending")
    finally:
        pipe.shutdown()

    reach = consensus.reachability
    reach.validate_intervals()
    g = params.genesis.hash
    for name in [t[0] for t in TOPOLOGY]:
        assert reach.is_dag_ancestor_of(g, names[name])

    in_past = lambda a, b: reach.is_dag_ancestor_of(names[a], names[b]) and names[a] != names[b]
    anticone = lambda a, b: not reach.is_dag_ancestor_of(names[a], names[b]) and not reach.is_dag_ancestor_of(
        names[b], names[a]
    )
    assert in_past("2", "4") and in_past("2", "5") and in_past("2", "7")
    assert in_past("5", "10") and in_past("6", "10")
    assert in_past("10", "12") and in_past("11", "12")
    assert anticone("2", "3") and anticone("2", "6") and anticone("3", "6")
    assert anticone("5", "6") and anticone("3", "8")
    assert anticone("11", "2") and anticone("11", "4") and anticone("11", "6") and anticone("11", "9")


def test_concurrent_pipeline_random_waves():
    """Reference: test_concurrent_pipeline_random — Poisson waves of
    sibling blocks submitted concurrently without awaiting; the pipelined
    result must equal a sequential replay."""
    rng = random.Random(42)
    params = simnet_params()
    scratch = Consensus(params)
    tips = [params.genesis.hash]
    all_blocks = []
    total = 120
    while total > 0:
        v = min(params.max_block_parents, max(1, int(rng.gauss(3, 1.5))))
        v = min(v, total)
        total -= v
        new_tips = []
        for _ in range(v):
            blk = scratch.build_block_with_parents(list(tips), MINER)
            blk.header.nonce = rng.getrandbits(48)
            blk.header.invalidate_cache()
            scratch.validate_and_insert_block(blk)
            new_tips.append(blk.hash)
            all_blocks.append(blk)
        tips = new_tips

    consensus = Consensus(params)
    pipe = ConsensusPipeline(consensus, workers=3)
    try:
        futures = [pipe.submit(b) for b in all_blocks]  # whole DAG in flight
        for f in futures:
            f.result(timeout=120)
    finally:
        pipe.shutdown()

    consensus.reachability.validate_intervals()
    assert consensus.sink() == scratch.sink()
    assert consensus.get_virtual_daa_score() == scratch.get_virtual_daa_score()
    for blk in all_blocks:
        # consensus data must be bit-identical; statuses may differ only in
        # that drained-batch resolution leaves side blocks utxo_pending
        # (the reference's virtual processor batches the same way)
        assert consensus.storage.ghostdag.get_blue_work(blk.hash) == scratch.storage.ghostdag.get_blue_work(blk.hash)
        assert consensus.storage.ghostdag.get(blk.hash).mergeset_blues == scratch.storage.ghostdag.get(blk.hash).mergeset_blues
        status = consensus.storage.statuses.get(blk.hash)
        ref_status = scratch.storage.statuses.get(blk.hash)
        assert status == ref_status or (status == "utxo_pending" and ref_status == "utxo_valid")
    # every selected-chain ancestor of the sink is fully UTXO-verified
    cur = consensus.sink()
    while cur != params.genesis.hash:
        assert consensus.storage.statuses.get(cur) == "utxo_valid"
        cur = consensus.storage.ghostdag.get_selected_parent(cur)


def test_pipeline_out_of_order_chain():
    """A linear chain submitted all at once: children park on pending
    parents in the deps manager and complete once released."""
    topo = [(str(i), [str(i - 1)] if i > 2 else ["G"]) for i in range(2, 22)]
    params, blocks, _ = _build_dag(topo)
    consensus = Consensus(params)
    pipe = ConsensusPipeline(consensus, workers=2)
    try:
        futures = [pipe.submit(b) for b in blocks]
        statuses = [f.result(timeout=120) for f in futures]
    finally:
        pipe.shutdown()
    assert statuses[-1] == "utxo_valid"
    assert consensus.sink() == blocks[-1].hash


def test_pipeline_missing_parent_errors():
    params, blocks, _ = _build_dag([("2", ["G"]), ("3", ["2"])])
    consensus = Consensus(params)
    pipe = ConsensusPipeline(consensus)
    try:
        fut = pipe.submit(blocks[1])  # parent never submitted nor known
        with pytest.raises(Exception, match="missing parent"):
            fut.result(timeout=30)
    finally:
        pipe.shutdown()


def test_deps_manager_parking_and_groups():
    dm = BlockTaskDependencyManager()

    class T:
        def __init__(self, h, parents):
            self.h, self.parents = h, parents

    a, b = b"\xaa" * 32, b"\xbb" * 32
    ta, tb = T(a, []), T(b, [a])
    assert dm.register(a, ta) is True
    assert dm.register(b, tb) is True
    assert dm.register(b, tb) is False  # duplicate absorbed

    parents_of = lambda t: t.parents
    # b parks under pending a
    assert dm.try_begin(b, parents_of) is None
    assert dm.try_begin(a, parents_of) is ta
    released = dm.end(a)
    assert released == [b]
    assert dm.try_begin(b, parents_of) is tb
    # first b ends -> same hash requeued for the duplicate
    assert dm.end(b) == [b]
    assert dm.try_begin(b, parents_of) is tb
    assert dm.end(b) == []
    assert dm.wait_for_idle(1.0)


def test_pipeline_wait_for_idle_and_counters():
    topo = [(str(i), [str(i - 1)] if i > 2 else ["G"]) for i in range(2, 8)]
    params, blocks, _ = _build_dag(topo)
    consensus = Consensus(params)
    pipe = ConsensusPipeline(consensus)
    try:
        for b in blocks:
            pipe.submit(b)
        pipe.wait_for_idle()
        snap = consensus.counters.snapshot()
        assert snap.body_counts == len(blocks)
    finally:
        pipe.shutdown()


def test_channel_drain_cap():
    """Channel.drain(max_items) takes at most that many, FIFO, leaving the
    rest queued — the primitive under the virtual worker's batch bound."""
    from kaspa_tpu.utils.sync import Channel

    ch = Channel()
    for i in range(10):
        ch.send(i)
    assert ch.drain(3) == [0, 1, 2]
    assert ch.drain(0) == []
    assert ch.drain(None) == [3, 4, 5, 6, 7, 8, 9]
    assert ch.drain(5) == []


def test_virtual_batch_cap(monkeypatch):
    """KASPA_TPU_VIRTUAL_BATCH_MAX bounds blocks absorbed per virtual
    cycle; a capped pipeline must still absorb every block (the feed stays
    honest, the batches just get smaller)."""
    from kaspa_tpu.pipeline.pipeline import _VIRT_BATCH

    monkeypatch.setenv("KASPA_TPU_VIRTUAL_BATCH_MAX", "2")
    topo = [(str(i), [str(i - 1)] if i > 2 else ["G"]) for i in range(2, 18)]
    params, blocks, _ = _build_dag(topo)
    consensus = Consensus(params)
    count0, max0 = _VIRT_BATCH.count, _VIRT_BATCH.max
    pipe = ConsensusPipeline(consensus, workers=2)
    assert pipe._virtual_batch_max == 2
    try:
        futures = [pipe.submit(b) for b in blocks]
        statuses = [f.result(timeout=120) for f in futures]
    finally:
        pipe.shutdown()
    assert statuses[-1] == "utxo_valid"
    assert consensus.sink() == blocks[-1].hash
    # the histogram recorded this run's cycles, none above the cap
    assert _VIRT_BATCH.count > count0
    if _VIRT_BATCH.max > max0:
        assert _VIRT_BATCH.max <= 2


def test_relay_out_of_order_parks_on_inflight_parent():
    """round-3 review item #3 'done' criterion: a relayed child whose parent is
    still IN FLIGHT inside the pipeline must park in the deps manager (not
    orphan out), and both must land — overlapped header/body/virtual
    processing across relay arrivals."""
    import random
    import threading
    import time

    from kaspa_tpu.p2p.node import Node, connect

    params = simnet_params(bps=2)
    scratch = Consensus(params)
    node = Node(Consensus(params), "ooo-relay")

    # build parent + child on a scratch consensus
    parent = scratch.build_block_template(MINER, [])
    scratch.validate_and_insert_block(parent)
    child = scratch.build_block_template(MINER, [])

    # hold the pipeline's commit lock so the parent stays in flight while
    # the child arrives over relay
    gate = node.pipeline._lock
    release = threading.Event()

    def hold():
        with gate:
            release.wait(10)

    holder = threading.Thread(target=hold, daemon=True)
    holder.start()
    time.sleep(0.1)

    parent_fut = node.pipeline.submit(parent)
    time.sleep(0.2)  # stage worker now blocked on the held lock
    assert node.pipeline.deps.is_pending(parent.hash)

    peer_node = Node(Consensus(params), "ooo-peer")
    pa, pb = connect(node, peer_node)

    done = []

    def relay_child():
        # _on_relay_block must treat the in-flight parent as present
        with node.lock:
            node._on_relay_block(pb.remote, child)
        done.append(True)

    relayer = threading.Thread(target=relay_child, daemon=True)
    relayer.start()
    time.sleep(0.2)
    assert child.hash not in node.orphan_blocks, "child wrongly orphaned"
    release.set()
    relayer.join(30)
    assert done, "relay did not complete"
    assert parent_fut.result(30) in ("utxo_valid", "utxo_pending")
    assert node.consensus.storage.statuses.get(child.hash) == "utxo_valid"
    assert node.consensus.sink() == child.hash


# ----------------------------------------------------------------------
# when a virtual cycle starts (pipeline.py module docstring): once the cap is
# reached, or once nothing is ready or inside a stage worker
# ----------------------------------------------------------------------

_TOY_DAGS = {
    # one miner that sees its own block at once: a chain
    "chain": ({"bps": 2, "delay_s": 1.0, "miners": 1}, {"tx_per_block": 2, "spoiled_blocks": 0, "pool_factor": 3}),
    # simpa's network at toy size: one miner whose own blocks reach it after the
    # delay too, about delay x bps = 4 blocks wide; two late siblings carry a failed spend
    "wide": (
        {"bps": 4, "delay_s": 1.0, "miners": 1, "own_blocks_delayed": True, "ghostdag_k": 55},
        {"tx_per_block": 3, "spoiled_blocks": 2, "pool_factor": 8, "gap_stratum_blocks": 4},
    ),
}


def _cycle_counters():
    from kaspa_tpu.pipeline.pipeline import _VIRT_CYCLE_BLOCKS, _VIRT_CYCLES

    return _VIRT_CYCLES.value, _VIRT_CYCLE_BLOCKS.value


def _spy_cycles(consensus, on_cycle=lambda: None):
    """Blocks absorbed by each cycle, counted where the virtual worker works:
    one ``_update_tips`` a block, then one ``_resolve_virtual`` a cycle."""
    sizes, n = [], [0]
    update_tips, resolve = consensus._update_tips, consensus._resolve_virtual

    def spy_tips(h):
        n[0] += 1
        return update_tips(h)

    def spy_resolve():
        sizes.append(n[0])
        n[0] = 0
        on_cycle()
        return resolve()

    consensus._update_tips, consensus._resolve_virtual = spy_tips, spy_resolve
    return sizes


def _settled(pipe, timeout=10.0):
    """The ready-or-staging count once the stage workers are through (a future
    resolves a few instructions before its task leaves the count)."""
    import time

    deadline = time.monotonic() + timeout
    while pipe._staging and time.monotonic() < deadline:
        time.sleep(0.001)
    return pipe._staging


@pytest.mark.parametrize("shape", ["chain", "wide"])
def test_cycle_absorbs_what_is_ready(shape, monkeypatch):
    """N >= 40 blocks queued while the commit lock is held: the cycles absorb
    what is staged (more than the two blocks two stage workers hand over, never
    more than the cap), and the state is the block-by-block replay's: sink,
    ``utxo_commitment``, and every block's status on the sink's chain.  Off that
    chain a batched resolve leaves ``utxo_pending`` what the replay, in which
    every block was once a tip, qualified or disqualified (the reference's
    virtual processor batches the same way); it never reaches another verdict."""
    from benchmarks import harness
    from kaspa_tpu.ops import dispatch as coalescing

    cap = 16
    monkeypatch.setenv("KASPA_TPU_VIRTUAL_BATCH_MAX", str(cap))
    coalescing.configure(0)
    network, traffic = _TOY_DAGS[shape]
    workload = {"config": "toy", "mode": "catchup", "tx_shape": "fanout-then-1to1", "window_blocks": 24, "sig_samples": 0, **traffic}
    dag = harness.build_dag(workload, {"name": "toy", "network": network, "pipeline": {"coalesce": 64, "stage_workers": 2}}, 36, lambda _m: None)
    blocks = dag.blocks
    assert len(blocks) >= 40 and len(dag.spoiled) == traffic["spoiled_blocks"]
    if shape == "wide":
        assert dag.facts["mean_window_parents"] >= 4

    replay = Consensus(dag.params)
    for b in blocks:
        replay.validate_and_insert_block(b)

    consensus = Consensus(dag.params)
    pipe = ConsensusPipeline(consensus, workers=2)
    sizes = _spy_cycles(consensus)
    cycles0, blocks0 = _cycle_counters()
    try:
        with pipe._lock:  # nothing commits until every block is queued
            futures = [pipe.submit(b) for b in blocks]
        statuses = [f.result(timeout=300) for f in futures]
    finally:
        pipe.shutdown()
    cycles1, blocks1 = _cycle_counters()
    assert cycles1 - cycles0 == len(sizes) and blocks1 - blocks0 == sum(sizes) == len(blocks)
    assert max(sizes) > 2 and max(sizes) <= cap, sizes
    assert len(sizes) < len(blocks) / 2, sizes  # not one or two blocks a cycle
    assert pipe._staging == 0

    sink = consensus.sink()
    assert sink == replay.sink() == dag.sinks[-1]
    assert consensus.multisets[sink].finalize() == replay.multisets[sink].finalize()
    assert consensus.get_virtual_daa_score() == replay.get_virtual_daa_score()
    chain, cur = set(), sink
    while cur != dag.params.genesis.hash:
        chain.add(cur)
        cur = consensus.storage.ghostdag.get_selected_parent(cur)
    for b, said in zip(blocks, statuses):
        got, want = consensus.storage.statuses.get(b.hash), replay.storage.statuses.get(b.hash)
        if b.hash in chain:
            assert got == want == "utxo_valid"
            assert consensus.acceptance_data.get(b.hash) == replay.acceptance_data.get(b.hash)
        else:
            assert got == want or got == "utxo_pending", (got, want)
        assert said == got or said == "utxo_pending"  # what the future said, before a later cycle qualified the block
        assert (want == "disqualified") == (b.hash in dag.spoiled)


def test_lone_block_cycle_starts_at_once():
    """A lone block (every paced and relayed one) finds nothing else ready or
    staging when it is handed over: its cycle starts with the count at zero
    and absorbs that block alone."""
    params, blocks, _ = _build_dag([("2", ["G"]), ("3", ["2"])])
    consensus = Consensus(params)
    pipe = ConsensusPipeline(consensus, workers=2)
    counts = []
    sizes = _spy_cycles(consensus, on_cycle=lambda: counts.append(pipe._staging))
    cycles0, blocks0 = _cycle_counters()
    try:
        for blk in blocks:
            assert pipe.submit(blk).result(timeout=60) == "utxo_valid"
    finally:
        pipe.shutdown()
    assert sizes == [1, 1] and counts == [0, 0]
    assert _cycle_counters() == (cycles0 + 2, blocks0 + 2)


def test_every_stage_exit_leaves_the_count():
    """A stage error, a duplicate (one absorbed by its group, one of a block
    already stored) and a header-only task each leave the ready-or-staging
    count at zero, and the next block resolves."""
    params, blocks, _ = _build_dag([("2", ["G"]), ("3", ["2"]), ("4", ["3"]), ("5", ["4"]), ("6", ["5"])])
    consensus = Consensus(params)
    pipe = ConsensusPipeline(consensus, workers=2)
    try:
        with pytest.raises(Exception, match="missing parent"):
            pipe.submit(blocks[2]).result(timeout=30)  # stage error: its parent was never seen
        assert _settled(pipe) == 0
        assert pipe.submit(blocks[0]).result(timeout=30) == "utxo_valid"

        with pipe._lock:  # the second submission joins the first one's group
            twice = [pipe.submit(blocks[1]), pipe.submit(blocks[1])]
        # the group's second task is answered from the store as soon as the first has staged
        assert twice[0].result(timeout=30) == "utxo_valid" and twice[1].result(timeout=30) in ("utxo_valid", "utxo_pending")
        assert pipe.submit(blocks[1]).result(timeout=30) == "utxo_valid"  # already stored: no reprocessing
        assert _settled(pipe) == 0
        assert pipe.submit(blocks[2]).result(timeout=30) == "utxo_valid"

        assert pipe.submit(blocks[3], header_only=True).result(timeout=30) == "header_only"
        assert _settled(pipe) == 0
        assert pipe.submit(blocks[3]).result(timeout=30) == "utxo_valid"
        assert pipe.submit(blocks[4]).result(timeout=30) == "utxo_valid"
        pipe.wait_for_idle(10)
        assert _settled(pipe) == 0
    finally:
        pipe.shutdown()
    assert consensus.sink() == blocks[4].hash


def test_shutdown_wakes_a_waiting_virtual_worker():
    """``shutdown()`` returns though the virtual worker is waiting for a stage
    task that never ends (a leaked count), and what was handed over resolves."""
    import time

    params, blocks, _ = _build_dag([("2", ["G"])])
    consensus = Consensus(params)
    pipe = ConsensusPipeline(consensus, workers=2)
    with pipe._idle_mu:
        pipe._staging += 1  # a stage task that never leaves
    fut = pipe.submit(blocks[0])
    deadline = time.monotonic() + 10
    while not len(pipe._virtual_q) and time.monotonic() < deadline:
        time.sleep(0.001)
    assert len(pipe._virtual_q) == 1 and pipe._staging == 1 and not fut.done()  # handed over; the cycle waits
    t0 = time.monotonic()
    pipe.shutdown()
    assert time.monotonic() - t0 < 5
    assert not pipe._virtual_worker_t.is_alive()
    assert fut.result(timeout=1) == "utxo_valid"


def test_staging_count_under_stress():
    """More stage workers than cores, a shortened switch interval and four
    submitters racing the same DAG, each in its own order: a lost update of
    the ready-or-staging count would leave it off zero (a wedged or an early
    cycle); every future resolves, the count ends at zero and the cycles
    absorbed each block exactly once."""
    import sys

    rng = random.Random(36)
    topo, levels, level, n = [], [], ["G"], 2
    while n < 90:
        width = rng.randint(1, 4)
        names = [str(n + k) for k in range(width)]
        topo += [(name, list(level)) for name in names]
        levels.append(range(n - 2, n - 2 + width))
        level, n = names, n + width
    params, blocks, _ = _build_dag(topo)
    consensus = Consensus(params)
    pipe = ConsensusPipeline(consensus, workers=16)
    sizes = _spy_cycles(consensus)
    futures, mu = [], threading.Lock()

    def submitter(seed):
        # every thread its own order inside a level, no thread ahead of its own
        # parents: a child finds them stored or in flight (then it parks)
        order = random.Random(seed)
        for lvl in levels:
            for i in order.sample(lvl, len(lvl)):
                f = pipe.submit(blocks[i])
                with mu:
                    futures.append(f)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=submitter, args=(s,), daemon=True) for s in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
        statuses = [f.result(timeout=120) for f in futures]
        assert _settled(pipe) == 0
    finally:
        sys.setswitchinterval(interval)
        pipe.shutdown()
    assert len(statuses) == 4 * len(blocks) and set(statuses) <= {"utxo_valid", "utxo_pending"}
    assert sum(sizes) == len(blocks) and max(sizes) <= pipe._virtual_batch_max
    assert not pipe._virtual_worker_t.is_alive() and pipe._staging == 0
    tips = [b.hash for b in blocks if b.hash not in {p for x in blocks for p in x.header.direct_parents()}]
    assert consensus.sink() in tips
