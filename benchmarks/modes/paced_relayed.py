"""Paced, relayed: a live node that sees its transactions before its blocks.

The timed path is ``kaspa_tpu.p2p.node.Node`` over the ramp-replayed
consensus and its pipeline, as ``node/daemon.py`` builds it, driven through
what the daemon's handlers call and nothing else:

- blocks through ``Node.submit_block`` under the node lock, one at a time, as
  the relay reader thread and the RPC dispatch do: ``paced.py``'s schedule and
  its definition of latency (block due -> ``submit_block`` returned; a block
  not taken in ``grace_seconds`` after the window = failed and worst);
- transactions through ``Node.submit_transaction``, the blocking call the
  daemon's ``submitTransaction`` handler makes outside the node lock, from
  ``handler_threads`` threads (the configuration's ``intake``: the RPC
  server's handler threads behind the generator's connections).  The daemon
  starts no tier worker: a handler queues its transaction and pumps the
  queue itself, so a wave holds what the handlers queued together, and the
  orphans a block hands back are admitted inside ``submit_block``, under the
  lock.  One feeder thread offers the load open loop: every spend of every
  due block is due ``tx_lead_s`` (uniform, from the seed) before its block;
  what is due before the window opens is one burst after the ramp, answered
  before t0.  ``EARLY_SUBMISSIONS`` seeded spends are instead due just
  before the block that *creates their input*, as a wallet's chained spend
  reaches a node ahead of its parent's block: on every seed the node parks
  some, and their parents' blocks must hand them back.

An answer is read as ``RpcCoreService`` reads it: an exception is
``rejected``; else the transaction is ``orphaned`` if the orphan pool holds
it when the call returns, ``accepted`` if not.  The mode returns when every
call has returned and the tier's queue is empty, so the ledger's job counts
are exact.  The mempool's side is held against ``reference_mempool.py``: its
counts of disagreement are logged under their own names on the ``relay``
line and added to ``unresolved`` (the check ``unresolved_blocks``, limit 0),
because ``harness.run_cell`` takes its checks from ``compare.py`` and the
ledger only.
"""

from __future__ import annotations

import json
import queue
import random
import threading
import time

from benchmarks import reference_mempool as refpool
from benchmarks.modes.paced import percentile, schedule

# a spend the mempool had not decided when its block came is looked up by
# the speculative stage, the chain verification and the virtual's mergeset
# before the first answer is cached: at most this many device jobs each
UNDECIDED_LOOKUPS = 4
# spends handed in ahead of the block that creates their input, as many as
# the construction spoils blocks, and how long ahead of that block (seconds)
EARLY_SUBMISSIONS = 4
EARLY_LEAD_S = (0.05, 0.25)
HANDLER_THREADS = 4  # where the configuration states no intake


def submissions(dag, n_due: int, due_rel: list, lead: tuple, seed: int) -> list:
    """(due time relative to t0, block index, tx) of every spend of the due
    blocks, by due time: each ``lead`` seconds before its block, but for
    ``EARLY_SUBMISSIONS`` seeded ones, each ``EARLY_LEAD_S`` before the
    block that creates its input.  Those are drawn from the honest spends of
    honest blocks whose input an honest block creates inside the window."""
    rng = random.Random(seed ^ 0x7E1A)
    out = []
    made_in = {}  # id of a due block's spend -> the block's place in the schedule
    for k in range(n_due):
        for tx in dag.blocks[dag.ramp + k].transactions[1:]:
            out.append([due_rel[k] - rng.uniform(*lead), dag.ramp + k, tx])
            made_in[tx.id()] = k
    spoiled_blocks = {s["index"] for s in dag.spoiled.values()}
    spoiled_spends = {s["txid"] for s in dag.spoiled.values()}
    chained = []  # (place in `out`, place in the schedule of the block that creates its input)
    for n, (_due, idx, tx) in enumerate(out):
        made = [made_in.get(inp.previous_outpoint.transaction_id) for inp in tx.inputs]
        if None in made or idx in spoiled_blocks or tx.id() in spoiled_spends:
            continue
        if max(made) < idx - dag.ramp and due_rel[max(made)] > EARLY_LEAD_S[1] and not {dag.ramp + k for k in made} & spoiled_blocks:
            chained.append((n, max(made)))
    early = random.Random(seed ^ 0xEA71)
    for n, k in early.sample(chained, min(EARLY_SUBMISSIONS, len(chained))):
        out[n][0] = due_rel[k] - early.uniform(*EARLY_LEAD_S)
    out.sort(key=lambda s: s[0])
    return [tuple(s) for s in out]


def _span_totals() -> dict:
    """{span name: (count, seconds)} from the program's always-on span histogram."""
    from kaspa_tpu.observability.core import REGISTRY

    family = REGISTRY.snapshot()["histograms"].get("span_duration_seconds", {})
    return {name: (h["count"], h["sum"]) for name, h in family.items()}


def _check_intake(stated: dict | None, tier_config, sig_cache) -> None:
    """The intake the node came up with is what the configuration states."""
    for key in ("queue_capacity", "batch_max", "max_wait_ms") if stated else ():
        if getattr(tier_config, key) != stated[key]:
            raise RuntimeError(f"config states intake {key}={stated[key]}, the node's tier has {getattr(tier_config, key)}")
    if stated and sig_cache.size != stated["sig_cache_entries"]:
        raise RuntimeError(f"config states a signature cache of {stated['sig_cache_entries']}, the validator's holds {sig_cache.size}")


def run(setup, consensus, pipe, seconds: float) -> dict:
    from benchmarks import ledger
    from kaspa_tpu.consensus.processes.transaction_validator import TxRuleError
    from kaspa_tpu.mempool.mempool import MempoolError
    from kaspa_tpu.p2p.node import Node

    dag, wl = setup.dag, setup.workload
    grace = float(wl.get("grace_seconds", 5.0))
    lo, hi = (float(x) for x in str(wl["tx_lead_s"]).split("-"))
    if float(wl.get("relayed_share", 1.0)) != 1.0:
        raise ValueError("paced_relayed submits every spend of every due block: relayed_share must be 1.0")
    n_want = max(1, round(float(setup.config["network"]["bps"]) * seconds))
    n_due = min(n_want, len(dag.blocks) - dag.ramp)
    if n_due < n_want:
        setup.log(f"paced_relayed: the DAG holds {n_due} window blocks, {n_want} are due in {seconds} s: the DAG is too short")
    due_rel = schedule(n_due, seconds, setup.seed)
    subs = submissions(dag, n_due, due_rel, (lo, hi), setup.seed)

    node = Node(consensus, name="bench", mempool_seed=setup.seed, pipeline=pipe)
    tier, mempool = node.ingest, node.mining.mempool
    intake = setup.config.get("intake")
    _check_intake(intake, tier.config, consensus.transaction_validator.sig_cache)
    counters0, spans0 = ledger.counters(), _span_totals()

    answers: list = []  # (txid, t_call, t_returned, outcome or None, seconds after due), as the handlers got them
    errors: list = []
    work: queue.SimpleQueue = queue.SimpleQueue()  # (tx, when it was due) for the handlers; None: no more

    def handle() -> None:
        """One RPC handler thread: the daemon's ``submitTransaction`` without the socket."""
        while (item := work.get()) is not None:
            tx, due_at = item
            t_call = time.perf_counter()
            outcome = None
            try:
                node.submit_transaction(tx)
                outcome = refpool.ORPHANED if tx.id() in mempool.orphans else refpool.ACCEPTED
            except (MempoolError, TxRuleError):
                outcome = refpool.REJECTED
            except Exception as e:  # noqa: BLE001 - a call that broke is a lost answer, counted below
                errors.append(f"submit_transaction {tx.id().hex()[:16]}: {type(e).__name__}: {e}")
            answers.append((tx.id(), t_call, time.perf_counter(), outcome, t_call - due_at))

    n_handlers = int((intake or {}).get("handler_threads", HANDLER_THREADS))
    handlers = [threading.Thread(target=handle, name=f"bench-rpc-{n}", daemon=True) for n in range(n_handlers)]
    for h in handlers:
        h.start()
    try:
        # ---- what is due before the window opens: one burst, answered before t0
        n_burst = sum(1 for s in subs if s[0] < 0.0)
        t_burst = time.perf_counter()
        for _due, _idx, tx in subs[:n_burst]:
            work.put((tx, t_burst))
        while len(answers) < n_burst:
            if time.perf_counter() - t_burst > 120.0:
                raise RuntimeError("the burst before the window was not answered")
            time.sleep(0.001)
        burst_s = time.perf_counter() - t_burst
        pool_at_t0 = len(mempool.pool)

        t0 = time.perf_counter()
        end = t0 + seconds
        give_up = end + grace

        def feed() -> None:
            try:
                for due, _idx, tx in subs[n_burst:]:
                    wait = t0 + due - time.perf_counter()
                    if wait > 0:
                        time.sleep(wait)
                    work.put((tx, t0 + due))
            finally:
                for _h in handlers:
                    work.put(None)

        feeder = threading.Thread(target=feed, name="bench-feed", daemon=True)
        feeder.start()

        # ---- the pacer: blocks in mining order, each under the node lock
        block_in: dict = {}  # block index -> (handed in, taken in)
        statuses, late = {}, []
        for k in range(n_due):
            due = t0 + due_rel[k]
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            idx = dag.ramp + k
            t_in = time.perf_counter()
            late.append(t_in - due)
            if t_in > give_up:
                break
            try:
                with node.lock.locked_for("block"):
                    statuses[idx] = node.submit_block(dag.blocks[idx])
                block_in[idx] = (t_in, time.perf_counter())
            except Exception as e:  # noqa: BLE001 - a failed block is counted, not raised
                errors.append(f"block {idx}: {type(e).__name__}: {e}")
        feeder.join(max(0.0, give_up - time.perf_counter()) + 1.0)
        for h in handlers:
            h.join(max(0.0, give_up - time.perf_counter()) + 1.0)
    finally:
        tier.stop()  # no worker to stop: whatever a handler left queued is admitted here
    t_stop = time.perf_counter()
    counters = ledger.delta(ledger.counters(), counters0)
    spans = {name: (n - spans0.get(name, (0, 0.0))[0], t - spans0.get(name, (0, 0.0))[1]) for name, (n, t) in _span_totals().items()}
    answers = list(answers)  # a handler still in its call past the grace adds nothing from here on

    t1 = max([end] + [t for _t, t in block_in.values()])
    latencies = []
    for k in range(n_due):
        idx = dag.ramp + k
        due = t0 + due_rel[k]
        latencies.append((block_in[idx][1] - due) if idx in block_in else (give_up - due))
    for e in errors[:5]:
        setup.log("error " + e)
    # what the tail is made of: the slowest blocks, each with how late it was handed in
    # (the node was still taking in the block before it) and how long the node then took
    worst = sorted(range(len(late)), key=lambda k: -latencies[k])[:12]
    setup.log("relay_slowest_blocks " + json.dumps([
        {"k": k, "ms": round(latencies[k] * 1e3, 2), "late_ms": round(late[k] * 1e3, 2),
         "gap_ms": round((due_rel[k] - due_rel[k - 1]) * 1e3, 2) if k else None} for k in worst
    ]))

    # ---- the mempool's side against the reference
    relay = refpool.Relay(dag.blocks, dag.ramp, n_due, set(dag.spoiled))
    log = [(txid, t_call, t_returned if outcome is not None else None, outcome) for txid, t_call, t_returned, outcome, _late in answers]
    answered = {a[0] for a in answers}
    log += [(tx.id(), t_stop, None, None) for _due, _idx, tx in subs if tx.id() not in answered]  # still in its call: lost
    to_verify = {s[0] for s in log if s[3] == refpool.REJECTED} | {s["txid"] for s in dag.spoiled.values()}
    to_verify |= {s[1] for s in dag.sig_samples}
    invalid = {txid for txid in to_verify & set(relay.txs) if not relay.valid(txid)}
    stats = tier.stats()
    handed_back = stats["submitted"] - len(answers)
    checks = refpool.compare(relay, log, block_in, set(mempool.pool), set(mempool.orphans), handed_back, invalid)
    checks["lost_tickets"] += max(0, stats["lost"])
    misses = counters.get("txscript_sig_cache_block_lookups", 0) - counters.get("txscript_sig_cache_block_hits", 0)
    checks["sigcache_vs_reference"] = max(0, misses - UNDECIDED_LOOKUPS * relay.unverified_at_block(log, block_in))
    in_window = answers[n_burst:]
    waits = [t_returned - t_call for _id, t_call, t_returned, outcome, _late in in_window if outcome is not None]
    tx_late = [a[4] for a in in_window]
    outcomes: dict = {}
    for s in log:
        outcomes[str(s[3])] = outcomes.get(str(s[3]), 0) + 1
    setup.log("relay " + json.dumps({
        **checks, "submitted": len(answers), "outcomes": outcomes, "handler_threads": n_handlers,
        "burst_txs": n_burst, "burst_s": burst_s,
        "submit_to_answer_p50_ms": percentile(waits, 0.50) * 1e3 if waits else None,
        "submit_to_answer_p95_ms": percentile(waits, 0.95) * 1e3 if waits else None,
        "tx_late_p95_ms": percentile(tx_late, 0.95) * 1e3 if tx_late else None,
        "waves": stats["waves"], "mean_wave_txs": stats["submitted"] / stats["waves"] if stats["waves"] else None,
        "orphans_readmitted": handed_back, "block_path_cache_misses": misses,
        "pool_at_t0": pool_at_t0, "pool_at_end": len(mempool.pool), "orphans_at_end": len(mempool.orphans),
        "drain_s": t_stop - end,
    }))

    n_blocks = len(block_in)
    # not metrics: what the program's spans summed to over the run, burst included, with the profiler off
    setup.log("relay_spans_ms_per_block " + json.dumps({
        name: [n, round(1e3 * t / max(1, n_blocks), 4)] for name, (n, t) in sorted(spans.items(), key=lambda kv: -kv[1][1]) if n
    }))
    disagreements = sum(checks.values())
    e2e = {}
    if latencies:
        e2e = {"commit_p50_ms": percentile(latencies, 0.50) * 1e3, "commit_p95_ms": percentile(latencies, 0.95) * 1e3}
    return {
        "attempted": n_due + len(subs),
        "failed": (n_due - n_blocks) + disagreements,  # a submission without an answer is among them: lost_tickets
        "unresolved": (n_due - n_blocks) + disagreements,
        "blocks": n_blocks,
        "seconds": t1 - t0,
        "passes": [{"consensus": consensus, "prefix": dag.ramp + n_due, "statuses": statuses}],
        "end_to_end": e2e,
        "harness": {"pacer_late_p95_ms": percentile(late, 0.95) * 1e3 if late else None,
                    "commit_max_ms": max(latencies) * 1e3 if latencies else None},
        "exhausted": int(n_due < n_want),  # the DAG ended inside the window: less load than the cell states
        "facts": {"due_blocks": n_due, "offered_blocks_per_s": n_due / seconds, "offered_tx_per_s": len(subs) / seconds},
    }
