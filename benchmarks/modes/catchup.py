"""Catch-up: the window's blocks as fast as the pipeline takes them, at most
``max_in_flight`` unresolved at a time (the reference node's IBD batch), the
next submitted as one resolves.  Submission stops at ``seconds``; the drain
that follows is timed work.

``catchup_blocks_per_s`` = window blocks whose future resolved / (t1 - t0),
t0 = first submit after the ramp, t1 = last in-flight future resolved: all the
work over all the time.  If the DAG runs out before ``seconds`` (a later,
faster program), a second pass into a fresh consensus starts inside the same
window; its ramp is replayed inside the window and only its window blocks
count, so the rate can only read low.
"""

from __future__ import annotations

import threading
import time


def run(setup, consensus, pipe, seconds: float) -> dict:
    dag = setup.dag
    limit = int(setup.workload.get("max_in_flight", 99))
    cv = threading.Condition()
    state = {"inflight": 0, "last_done": 0.0}
    done_at: list = []  # when each future resolved: the rate by quarter of the window goes to the log
    passes, errors = [], []
    attempted = resolved = 0

    def on_done(_f):
        now = time.perf_counter()
        with cv:
            state["inflight"] -= 1
            state["last_done"] = now
            done_at.append(now)
            cv.notify()

    t0 = time.perf_counter()
    deadline = t0 + seconds
    first_pass = True
    while True:
        futures = {}
        start = dag.ramp if first_pass else 0
        i = start
        while i < len(dag.blocks) and time.perf_counter() < deadline:
            with cv:
                while state["inflight"] >= limit:
                    cv.wait(0.5)
                state["inflight"] += 1
            f = pipe.submit(dag.blocks[i])
            f.add_done_callback(on_done)
            futures[i] = f
            i += 1
        statuses = {}
        for idx, f in futures.items():
            try:
                statuses[idx] = f.result(timeout=120)
            except Exception as e:  # noqa: BLE001 - a failed block is counted, not raised
                errors.append(f"block {idx}: {type(e).__name__}: {e}")
        window_idx = [k for k in futures if k >= dag.ramp]
        attempted += len(window_idx)
        resolved += sum(1 for k in window_idx if k in statuses)
        passes.append({"consensus": consensus, "prefix": i, "statuses": statuses})
        if i < len(dag.blocks) or time.perf_counter() >= deadline:
            break
        setup.log(f"second pass: the DAG ran out after {time.perf_counter() - t0:.2f} s of {seconds} s")
        first_pass = False
        consensus, pipe = setup.fresh_pipeline()
    with cv:
        t1 = max(state["last_done"], t0)
    elapsed = t1 - t0
    for e in errors[:5]:
        setup.log("error " + e)
    return {
        "attempted": attempted,
        "failed": attempted - resolved,
        "unresolved": attempted - resolved,
        "blocks": resolved,
        "seconds": elapsed,
        "passes": passes,
        "end_to_end": {"catchup_blocks_per_s": resolved / elapsed} if elapsed > 0 and resolved else {},
        "harness": {},
        "facts": {"passes": len(passes), "submit_seconds": seconds, "drain_seconds": max(0.0, t1 - deadline),
                  "resolved_by_quarter": [sum(1 for t in done_at if t <= t0 + q * seconds / 4) for q in (1, 2, 3, 4)]},
    }
