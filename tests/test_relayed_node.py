"""A live node that sees its transactions before its blocks (ISSUE 33):
``p2p.Node`` over a consensus and pipeline it is handed, the ingest tier, the
mempool and the signature cache, on a toy DAG of the benchmark's generator,
held against ``benchmarks/reference_mempool.py`` (which imports nothing of
the program) and against the same DAG replayed without a mempool.  CPU, XLA
ladder at bucket 8; nothing here is a device number."""

import itertools
import json
import time

import pytest

from benchmarks import harness
from benchmarks import reference_mempool as refpool
from kaspa_tpu.consensus.consensus import Consensus
from kaspa_tpu.ingest import SOURCE_RPC, SOURCE_UNORPHAN
from kaspa_tpu.observability import trace
from kaspa_tpu.observability.core import REGISTRY
from kaspa_tpu.ops import dispatch as coalescing
from kaspa_tpu.p2p.node import Node, Peer
from kaspa_tpu.pipeline.pipeline import ConsensusPipeline

CELL = "testnet12-rothschild.paced-10tpb-relayed"
WORKLOAD = {
    "config": "toy", "mode": "paced_relayed", "tx_per_block": 4, "tx_shape": "fanout-then-1to1",
    "window_blocks": 24, "spoiled_blocks": 2, "pool_factor": 3, "grace_seconds": 30, "sig_samples": 4,
    "relayed_share": 1.0, "tx_lead_s": "0.5-1.5", "pretrace": {"schnorr_verify": [8]}, "trace_seconds": 1.0,
    "idle_gap_spans": ["ingest.wave", "mempool.handle_block", "wait.node_lock", "pipeline.virtual"],
}
EARLY = 30  # the fan-out's last blocks: the outputs the window's first spends consume are made in them
CONFIG = {"name": "toy", "network": {"bps": 2, "delay_s": 1.0, "miners": 4}, "pipeline": {"coalesce": 64, "stage_workers": 2}}


@pytest.fixture(scope="module")
def dag():
    coalescing.configure(0)
    return harness.build_dag(WORKLOAD, CONFIG, 33, lambda _m: None)


def _replayed(dag, upto: int, one_by_one_from: int | None = None):
    """A fresh consensus behind its pipeline with ``blocks[:upto]`` in: the
    first ones overlapping, those from ``one_by_one_from`` on each after the
    one before it has resolved (the order a node's lock gives them)."""
    consensus = Consensus(dag.params)
    pipe = ConsensusPipeline(consensus, workers=2)
    split = upto if one_by_one_from is None else one_by_one_from
    for f in [pipe.submit(b) for b in dag.blocks[:split]]:
        f.result(timeout=600)
    for b in dag.blocks[split:upto]:
        pipe.validate_and_insert_block(b)
    return consensus, pipe


def _state(consensus, blocks) -> dict:
    sink = consensus.sink()
    consensus._move_utxo_position(sink)
    return {
        "sink": sink,
        "utxo_commitment": consensus.multisets[sink].finalize(),
        "utxo_set": {(op.transaction_id, op.index): (e.amount, e.script_public_key.script, e.block_daa_score, e.is_coinbase)
                     for op, e in consensus.utxo_set.items()},
        # of the statuses, what the order of arrival alone decides (a side block is
        # utxo_pending or utxo_valid by whether the sink search ever walked it)
        "disqualified": [consensus.storage.statuses.get(b.hash) == "disqualified" for b in blocks],
        "sink_status": consensus.storage.statuses.get(sink),
    }


def _relayed_replay(dag) -> dict:
    """The node comes up ``EARLY`` blocks before the ramp ends and is handed
    every window spend at once (whatever spends an output of those last ramp
    blocks is parked), then the blocks one by one, each under the node lock:
    the log on a logical clock, the final pools, the spans and the counters."""
    coalescing.configure(64)
    first = dag.ramp - EARLY
    consensus, pipe = _replayed(dag, first)
    node = Node(consensus, name="relayed", mempool_seed=1, pipeline=pipe)
    mempool = node.mining.mempool
    clock = itertools.count()
    tick = lambda: float(next(clock))  # noqa: E731
    relay = refpool.Relay(dag.blocks, first, len(dag.blocks) - first, set(dag.spoiled))
    trace.set_capture(1 << 16)
    trace.drain()
    before = REGISTRY.snapshot()["counters"]
    log, block_in, in_pool_after_parent = [], {}, {}
    try:
        for i in range(dag.ramp, len(dag.blocks)):
            for tx in dag.blocks[i].transactions[1:]:
                t0 = tick()
                ticket = node.ingest.admit(tx, SOURCE_RPC)  # no worker: the submitter pumps its own wave
                log.append((tx.id(), t0, tick(), ticket.status))
        parked = {txid: relay.creator[txid] for txid, _a, _b, outcome in log if outcome == refpool.ORPHANED}
        for i in range(first, len(dag.blocks)):
            t0 = tick()
            with node.lock.locked_for("block"):
                node.submit_block(dag.blocks[i])
            block_in[i] = (t0, tick())
            for txid, made in parked.items():
                if made == i:
                    in_pool_after_parent[txid] = txid in mempool.pool
        spans = trace.drain()
        state = _state(consensus, dag.blocks)
    finally:
        trace.set_capture(0)
        coalescing.drain()
        pipe.shutdown()
        coalescing.configure(0)
    after = REGISTRY.snapshot()["counters"]
    moved = {k: after[k] - before.get(k, 0) for k in after if not isinstance(after[k], dict)}
    for family in ("ingest_submitted", "ingest_outcomes"):
        moved[family] = {k: v - before[family].get(k, 0) for k, v in after[family].items()}
    return {
        "relay": relay, "log": log, "block_in": block_in, "parked": parked, "in_pool_after_parent": in_pool_after_parent,
        "spans": spans, "moved": moved, "pool": set(mempool.pool), "orphans": set(mempool.orphans),
        "stats": node.ingest.stats(), "state": state,
    }


def _compare(dag, run: dict) -> dict:
    spoiled = {s["txid"] for s in dag.spoiled.values()}
    rejected = {s[0] for s in run["log"] if s[3] == refpool.REJECTED}
    invalid = {txid for txid in spoiled | rejected if not run["relay"].valid(txid)}
    assert invalid == spoiled  # the reference's own verdicts: the construction's spoiled spends, and only they
    handed_back = run["stats"]["submitted"] - len(run["log"])
    return refpool.compare(run["relay"], run["log"], run["block_in"], run["pool"], run["orphans"], handed_back, invalid)


@pytest.fixture(scope="module")
def relayed(dag):
    return _relayed_replay(dag)


def test_node_adopts_the_pipeline_it_is_given(dag):
    consensus = Consensus(dag.params)
    pipe = ConsensusPipeline(consensus, workers=2)
    try:
        node = Node(consensus, pipeline=pipe)
        assert node.pipeline is pipe and node.ingest.lock is node.lock
        with pytest.raises(ValueError):
            Node(Consensus(dag.params), pipeline=pipe)  # a pipeline over another consensus
    finally:
        pipe.shutdown()
    own = Node(Consensus(dag.params))
    try:
        assert own.pipeline is not pipe and own.pipeline.consensus is own.consensus
    finally:
        own.shutdown()


def test_outcomes_and_final_pools_agree_with_the_reference(dag, relayed):
    assert _compare(dag, relayed) == {"ticket_outcomes_vs_reference": 0, "mempool_vs_reference": 0, "lost_tickets": 0}
    assert relayed["stats"]["lost"] == 0 and not relayed["pool"] and not relayed["orphans"]


def test_a_dropped_handback_is_counted_by_the_reference(dag):
    """The fault of ``benchmarks/control_relayed.py``: the orphans a block gave
    parents are never handed back (the node before ISSUE 33).  The final
    pools are as empty as a sound run's; the count of hand-backs is not."""
    from benchmarks import control_relayed

    with control_relayed.drop_unorphan_handback():
        run = _relayed_replay(dag)
    assert run["parked"] and run["stats"]["submitted"] == len(run["log"])
    assert not any(run["in_pool_after_parent"].values())
    checks = _compare(dag, run)
    assert checks["mempool_vs_reference"] >= 1 and checks["ticket_outcomes_vs_reference"] == 0


def test_a_spoiled_spend_is_never_accepted(dag, relayed):
    by_id = {s[0]: s[3] for s in relayed["log"]}
    spoiled = [s["txid"] for s in dag.spoiled.values()]
    # parked while its input does not exist, refused as soon as it does (at once, or when handed back)
    assert all(by_id[txid] in (refpool.REJECTED, refpool.ORPHANED) for txid in spoiled)
    assert relayed["moved"]["ingest_outcomes"].get(refpool.REJECTED, 0) == len(spoiled)
    assert not any(relayed["in_pool_after_parent"].get(txid) for txid in spoiled)


def test_an_early_submission_is_parked_and_readmitted_by_its_parents_block(dag, relayed):
    parked, landed = relayed["parked"], relayed["in_pool_after_parent"]
    assert parked and set(landed) == set(parked)
    spoiled_blocks = {s["index"] for s in dag.spoiled.values()}
    spoiled = {s["txid"] for s in dag.spoiled.values()}
    # handed back and validated in the same lock section as its parents' block:
    # in the pool at once, unless it is wrongly signed or that block is one the virtual does not merge
    honest = [txid for txid, made in parked.items() if made not in spoiled_blocks and txid not in spoiled]
    assert honest and all(landed[txid] for txid in honest)
    assert not any(landed[txid] for txid in parked if txid in spoiled)
    assert relayed["moved"]["ingest_submitted"][SOURCE_UNORPHAN] == len(parked)


def test_relayed_replay_leaves_the_state_of_a_plain_replay(dag, relayed):
    consensus, pipe = _replayed(dag, len(dag.blocks), one_by_one_from=dag.ramp - EARLY)
    try:
        plain = _state(consensus, dag.blocks)
    finally:
        pipe.shutdown()
    assert relayed["state"] == plain
    assert plain["sink"] == dag.sinks[-1]


def test_blocks_find_their_signatures_in_the_cache(dag, relayed):
    moved = relayed["moved"]
    asked, hits = moved["txscript_sig_cache_block_lookups"], moved["txscript_sig_cache_block_hits"]
    assert asked > 0 and moved["txscript_sig_cache_tx_lookups"] >= len(relayed["log"])
    # what a block still had to ask the device: only spends admission had not decided before it
    undecided = relayed["relay"].unverified_at_block(relayed["log"], relayed["block_in"])
    assert asked - hits <= 4 * undecided
    assert hits >= 0.9 * asked


@pytest.mark.parametrize("name,attrs", [
    ("wait.node_lock", {"who": "block"}),
    ("wait.node_lock", {"who": "ingest"}),
    ("ingest.wave", {"size", "orphans", "rejected"}),
    ("mempool.handle_block", {"txs", "unorphaned"}),
])
def test_spans_of_the_relayed_path(relayed, name, attrs):
    found = [s for s in relayed["spans"] if s["name"] == name]
    if isinstance(attrs, dict):
        found = [s for s in found if all(s["attrs"].get(k) == v for k, v in attrs.items())]
        assert found
    else:
        assert found and all(attrs <= set(s["attrs"]) for s in found)


def test_wave_and_handback_counts_add_up(relayed):
    moved, spans = relayed["moved"], relayed["spans"]
    waves = [s for s in spans if s["name"] == "ingest.wave"]
    assert moved["ingest_waves"] == len(waves) == relayed["stats"]["waves"]
    assert moved["ingest_wave_txs"] == sum(s["attrs"]["size"] for s in waves) == relayed["stats"]["submitted"]
    handled = [s for s in spans if s["name"] == "mempool.handle_block"]
    assert len(handled) == len(relayed["block_in"])
    assert sum(s["attrs"]["unorphaned"] for s in handled) == len(relayed["parked"])
    assert sum(s["attrs"]["orphans"] for s in waves) >= len(relayed["parked"])


def _orphan_case(dag):
    """(index of an honest block, a later block's spend of one of its
    outputs): what a node parks when the spend arrives first."""
    spoiled_blocks = {s["index"] for s in dag.spoiled.values()}
    spoiled = {s["txid"] for s in dag.spoiled.values()}
    relay = refpool.Relay(dag.blocks, dag.ramp, len(dag.blocks) - dag.ramp, set(dag.spoiled))
    for txid, made in sorted(relay.creator.items(), key=lambda kv: -kv[1]):
        if made not in spoiled_blocks and txid not in spoiled:
            return made, relay.txs[txid]
    raise AssertionError("the toy DAG holds no honest spend")


@pytest.mark.parametrize("caller", ["node_submit_block", "node_relay_block", "rpc_service_without_a_node"])
def test_every_caller_of_handle_new_block_transactions_readmits_the_orphans(dag, caller):
    from kaspa_tpu.mempool.mining_manager import MiningManager
    from kaspa_tpu.rpc.service import RpcCoreService

    made, tx = _orphan_case(dag)
    consensus, pipe = _replayed(dag, made)
    try:
        if caller == "rpc_service_without_a_node":
            mining = MiningManager(consensus)
            service = RpcCoreService(consensus, mining)
            mining.validate_and_insert_transaction(tx)
            deliver = lambda: service.submit_block(dag.blocks[made])  # noqa: E731
        else:
            node = Node(consensus, pipeline=pipe)
            mining = node.mining
            assert node.ingest.admit(tx, SOURCE_RPC).status == refpool.ORPHANED
            if caller == "node_submit_block":
                deliver = lambda: node.submit_block(dag.blocks[made])  # noqa: E731
            else:
                deliver = lambda: node._on_relay_block(Peer(node), dag.blocks[made])  # noqa: E731
        assert tx.id() in mining.mempool.orphans and tx.id() not in mining.mempool.pool
        deliver()
        assert tx.id() in mining.mempool.pool and tx.id() not in mining.mempool.orphans
    finally:
        pipe.shutdown()


def test_tier_worker_takes_the_handback_as_a_wave(dag):
    """With the tier's worker running (no daemon starts one today; the tier
    offers it) the block only queues the orphans; the worker admits them
    after the lock is free."""
    made, tx = _orphan_case(dag)
    consensus, pipe = _replayed(dag, made)
    node = Node(consensus, pipeline=pipe)
    node.ingest.start()
    try:
        ticket = node.ingest.submit(tx, SOURCE_RPC)
        assert ticket.wait(60) and ticket.status == refpool.ORPHANED
        with node.lock.locked_for("block"):
            node.submit_block(dag.blocks[made])
        deadline = time.monotonic() + 60
        while tx.id() not in node.mining.mempool.pool and time.monotonic() < deadline:
            time.sleep(0.01)
        assert tx.id() in node.mining.mempool.pool
    finally:
        node.ingest.stop()
        pipe.shutdown()
    assert node.ingest.stats()["lost"] == 0


def test_run_cell_in_mode_paced_relayed(dag):
    """The cell's own mode through ``harness.run_cell`` at toy size: pacer,
    feeder, the handler threads in ``Node.submit_transaction``, the
    comparison and the ``relay`` line."""
    bench = harness.load_json(harness.os.path.join(harness.ROOT, "BENCHMARK.json"))
    lines = []
    out = harness.run_cell(WORKLOAD, CONFIG, bench, CELL, seed=33, seconds=4.0, trace=False,
                           process_start=time.perf_counter(), log=lines.append, dag=dag)
    assert out["correct"] is True, {k: v for k, v in out["checks"].items() if v[0] != v[1]}
    assert out["failed"] == 0 and out["attempted"] == 8 + 8 * 4  # 8 blocks due in 4 s at 2 a second, 4 spends each
    assert set(out["metrics"]) == {"setup_s", "commit_p50_ms", "commit_p95_ms"}
    relay = json.loads(next(ln for ln in lines if ln.startswith("relay ")).split(" ", 1)[1])
    for count in ("mempool_vs_reference", "ticket_outcomes_vs_reference", "lost_tickets", "sigcache_vs_reference"):
        assert relay[count] == 0, relay
    assert relay["submitted"] == 32 and relay["pool_at_end"] == 0 and relay["orphans_at_end"] == 0
    assert relay["waves"] >= 1 and relay["burst_txs"] >= 1 and relay["handler_threads"] == 4


def test_seeded_early_submissions_precede_the_block_that_creates_their_input(dag):
    """On every seed the schedule hands a few spends in just before the block
    that creates their input, so that the node parks them and that block has
    to hand them back; every other spend keeps its lead before its own block."""
    import types

    from benchmarks.modes import paced_relayed

    # the toy window's spends consume what the fan-out's last blocks made: the schedule starts with those
    dag = types.SimpleNamespace(blocks=dag.blocks, ramp=dag.ramp - EARLY, spoiled=dag.spoiled)
    n_due = len(dag.blocks) - dag.ramp
    due_rel = [1.0 + 0.5 * k for k in range(n_due)]
    subs = paced_relayed.submissions(dag, n_due, due_rel, (0.5, 1.5), 33)
    spends = [tx.id() for b in dag.blocks[dag.ramp:] for tx in b.transactions[1:]]
    assert sorted(tx.id() for _due, _idx, tx in subs) == sorted(spends)
    assert [s[0] for s in subs] == sorted(s[0] for s in subs)
    relay = refpool.Relay(dag.blocks, dag.ramp, n_due, set(dag.spoiled))
    spoiled_blocks = {s["index"] for s in dag.spoiled.values()}
    early = [(due, idx, tx) for due, idx, tx in subs if due < due_rel[idx - dag.ramp] - 1.5]
    assert len(early) == paced_relayed.EARLY_SUBMISSIONS
    lo, hi = paced_relayed.EARLY_LEAD_S
    for due, idx, tx in early:
        made = relay.creator[tx.id()]
        assert dag.ramp <= made < idx and not {made, idx} & spoiled_blocks
        assert lo <= due_rel[made - dag.ramp] - due <= hi
    assert subs == paced_relayed.submissions(dag, n_due, due_rel, (0.5, 1.5), 33)
