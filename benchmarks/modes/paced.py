"""Paced: an open loop at the protocol's rate.  ``bps x seconds`` window
blocks are submitted, in mining order, from one pacer thread that never waits
for a result; latency runs from the block's *due* time to the moment its
future resolved (the virtual stage absorbed it), so a stall charges every
block that was due meanwhile.  A block unresolved ``grace_seconds`` after the
window counts as failed and as the worst latency.

Arrivals are Poisson in shape and the same work for every seed: the gaps are
the n quantiles of the exponential distribution at the protocol's rate (bursts
and lulls included), scaled to fill the window exactly, in an order drawn from
the seed.  (Taking each block's own simulated mining time offered 9.1 to 11.3
blocks/s from seed to seed and moved the tails with it.)
"""

from __future__ import annotations

import math
import random
import time


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile over all the values (no interpolation)."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def schedule(n: int, seconds: float, seed: int) -> list:
    """Due times of n arrivals in [0, seconds): exponential-quantile gaps,
    shuffled by the seed, the first arrival at 0."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    random.Random(seed ^ 0x9ACE).shuffle(gaps)
    scale = seconds / sum(gaps)
    due, t = [], 0.0
    for g in gaps:
        due.append(t)
        t += g * scale
    return due


def run(setup, consensus, pipe, seconds: float) -> dict:
    dag = setup.dag
    grace = float(setup.workload.get("grace_seconds", 5.0))
    n_want = max(1, round(float(setup.config["network"]["bps"]) * seconds))
    n_due = min(n_want, len(dag.blocks) - dag.ramp)
    if n_due < n_want:
        setup.log(f"paced: the DAG holds {n_due} window blocks, {n_want} are due in {seconds} s: the DAG is too short")
    due_rel = schedule(n_due, seconds, setup.seed)
    done_at: dict = {}
    futures, late = {}, []

    t0 = time.perf_counter()
    for k in range(n_due):
        due = t0 + due_rel[k]
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        idx = dag.ramp + k
        f = pipe.submit(dag.blocks[idx])
        late.append(time.perf_counter() - due)
        f.add_done_callback(lambda _f, idx=idx: done_at.setdefault(idx, time.perf_counter()))
        futures[idx] = f
    end = t0 + seconds
    statuses, errors = {}, []
    for idx, f in futures.items():
        try:
            statuses[idx] = f.result(timeout=max(0.0, end + grace - time.perf_counter()))
            done_at.setdefault(idx, time.perf_counter())  # result() can return before the callback has run
        except Exception as e:  # noqa: BLE001 - a failed block is counted, not raised
            errors.append(f"block {idx}: {type(e).__name__}: {e}")
    t1 = max([end] + list(done_at.values()))
    latencies = []
    for k in range(n_due):
        idx = dag.ramp + k
        due = t0 + due_rel[k]
        latencies.append((done_at[idx] - due) if idx in statuses else (end + grace - due))
    for e in errors[:5]:
        setup.log("error " + e)
    e2e = {}
    if latencies:
        e2e = {"commit_p50_ms": percentile(latencies, 0.50) * 1e3, "commit_p95_ms": percentile(latencies, 0.95) * 1e3}
    return {
        "attempted": n_due,
        "failed": n_due - len(statuses),
        "unresolved": n_due - len(statuses),
        "blocks": len(statuses),
        "seconds": t1 - t0,
        "passes": [{"consensus": consensus, "prefix": dag.ramp + n_due, "statuses": statuses}],
        "end_to_end": e2e,
        "harness": {"pacer_late_p95_ms": percentile(late, 0.95) * 1e3 if late else None,
                    "commit_max_ms": max(latencies) * 1e3 if latencies else None},
        "exhausted": int(n_due < n_want),  # the DAG ended inside the window: less load than the cell states
        "facts": {"due_blocks": n_due, "offered_blocks_per_s": n_due / seconds},
    }
