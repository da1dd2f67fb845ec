#!/usr/bin/env python3
"""The control and the fault of the relayed cell, beside ``control.py``'s
three: the timed path broken underneath, to show which number says so.  A
benchmark run never enters here.

    python benchmarks/control_relayed.py --workload testnet12-rothschild.paced-10tpb-relayed --seeds 1,2 --seconds 6

- ``sigcache_holds_nothing`` — the control of ``sigcache_block_hit_pct``: the
  signature cache answers no lookup (it is as good as emptied before every
  block and every wave), so each block asks the device again for what
  admission had decided.  The state stays right; the share of block-path
  lookups the cache answered reads 0 and ``sigcache_vs_reference`` counts the
  jobs the reference says no block had to send.
- ``drop_unorphan_handback`` — a fault: the orphans a block gave parents are
  taken out of the orphan pool and never handed back to admission (what the
  node did before ISSUE 33).  ``mempool_vs_reference`` counts it.

Each seed runs the honest window and then every break over one DAG build, as
``control.py`` does; a line a run, with the ``relay`` line's counts beside
the failing checks.
"""

from __future__ import annotations

import contextlib
import time


@contextlib.contextmanager
def sigcache_holds_nothing():
    from kaspa_tpu.txscript.caches import SigCache

    real = SigCache.get
    SigCache.get = lambda self, key: None
    try:
        yield
    finally:
        SigCache.get = real


@contextlib.contextmanager
def drop_unorphan_handback():
    from kaspa_tpu.ingest.tier import IngestTier

    real = IngestTier.resubmit
    IngestTier.resubmit = lambda self, txs: []
    try:
        yield
    finally:
        IngestTier.resubmit = real


def breaks() -> dict:
    from benchmarks import control

    return {**control.BREAKS, "sigcache_holds_nothing": sigcache_holds_nothing, "drop_unorphan_handback": drop_unorphan_handback}


def run_break(bench, cell: str, workload: dict, config: dict, seed: int, seconds: float, wrap, dag) -> dict:
    """One window with ``wrap`` around it: the failing checks, the ``relay``
    line's counts and the block path's share of cache hits."""
    import json

    from benchmarks import harness

    lines: list = []
    out = harness.run_cell(workload, config, bench, cell, seed=seed, seconds=seconds, trace=False,
                           process_start=time.perf_counter(), log=lines.append, wrap_window=wrap, dag=dag)
    relay = next((json.loads(ln.split(" ", 1)[1]) for ln in lines if ln.startswith("relay ")), {})
    moved = next((json.loads(ln.split(" ", 1)[1]) for ln in lines if ln.startswith("counters ")), {})
    asked = moved.get("txscript_sig_cache_block_lookups", 0)
    return {
        "correct": out["correct"], "attempted": out["attempted"], "failed": out["failed"],
        "failing": {k: v[0] for k, v in out["checks"].items() if v[0] != v[1]},
        "relay": {k: relay.get(k) for k in ("mempool_vs_reference", "ticket_outcomes_vs_reference", "lost_tickets",
                                            "sigcache_vs_reference", "orphans_readmitted", "outcomes")},
        "sigcache_block_hit_pct": 100.0 * moved.get("txscript_sig_cache_block_hits", 0) / asked if asked else None,
    }


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    names = breaks()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--breaks", default=",".join(names))
    args = ap.parse_args(argv)

    from benchmarks import harness
    from kaspa_tpu.ops import dispatch as coalescing
    from kaspa_tpu.utils import jax_setup

    jax_setup.setup()
    bench, _entry, workload, config = harness.load_cell(args.workload)
    info = harness.device_info()
    if info["platform"] != "tpu":
        print(f"benchmarks/control_relayed.py: needs a TPU, JAX found {info}", file=sys.stderr)
        return 3
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        coalescing.configure(0)
        harness._pretrace(workload, lambda _m: None)
        dag = harness.build_dag(workload, config, seed, lambda _m: None)  # one build, every window
        for name in ["honest"] + args.breaks.split(","):
            row = run_break(bench, args.workload, workload, config, seed, args.seconds, names.get(name), dag)
            print(json.dumps({"seed": seed, "run": name, **row}), flush=True)
            ok = ok and (row["correct"] == (name == "honest"))
    print(json.dumps({"control_ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
