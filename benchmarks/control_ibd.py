#!/usr/bin/env python3
"""The faults of the wire cell, beside ``control.py``'s and
``control_relayed.py``'s: the donor broken, to show which number says so.  A
benchmark run never enters here.

    python benchmarks/control_ibd.py --workload crescendo-10bps-ibd.catchup-10tpb-wire --seeds 1,2 --seconds 6

- ``withhold_one_block`` — the donor leaves one block out of its second
  chunk.  Every later block that descends from it is refused for a missing
  parent: ``ibd_blocks_missing`` counts the positions of acknowledged chunks
  the syncee does not hold, ``p2p_ibd_blocks_rejected`` the refusals.
- ``flip_one_signature_byte`` — one byte of one spend's signature script is
  flipped on the wire, in a block of the second chunk: the block's merkle
  root no longer matches and its body is refused, its header stays.  As the
  program stands the virtual stage then meets a mergeset block without a
  body, every future of that cycle fails with a ``KeyError``, the reader
  drops the peer and the rest of the chunk is abandoned: ``hung_up`` and the
  blocks served and not held say so (PERF.md section 7).
- ``serve_a_chunk_twice`` — the donor answers the request after its first
  chunk with the first chunk again.  The state stays right (the pipeline
  answers a duplicate with the status it has); the syncee then asks for the
  same continuation a second time, which ``ibd_rerequests`` counts.

Each seed runs the honest window and then every break over one DAG build, as
``control.py`` does; a line a run, with the ``ibd`` line's counts beside the
failing checks.
"""

from __future__ import annotations

import contextlib
import time

# the block a fault touches: late enough in its chunk that the blocks before it show the chunk was otherwise taken in
FAULT_BLOCK = 5


def _donor_fault(fault: dict):
    @contextlib.contextmanager
    def wrap():
        from benchmarks.modes import ibd_wire

        ibd_wire.FAULT = fault
        try:
            yield
        finally:
            ibd_wire.FAULT = None

    return wrap


def breaks(chunk: int = 1) -> dict:
    """The three breaks, the first two in chunk ``chunk`` (the second one on
    the chip, so that a sound chunk goes first; the toy tests take the first,
    which every window serves)."""
    return {
        "withhold_one_block": _donor_fault({"kind": "withhold", "chunk": chunk, "block": FAULT_BLOCK}),
        "flip_one_signature_byte": _donor_fault({"kind": "flip_sigscript", "chunk": chunk, "block": FAULT_BLOCK}),
        "serve_a_chunk_twice": _donor_fault({"kind": "repeat_chunk", "chunk": 0}),
    }


def run_break(bench, cell: str, workload: dict, config: dict, seed: int, seconds: float, wrap, dag) -> dict:
    """One window with ``wrap`` around it: the failing checks and the ``ibd``
    line's counts."""
    import json

    from benchmarks import harness

    lines: list = []
    out = harness.run_cell(workload, config, bench, cell, seed=seed, seconds=seconds, trace=False,
                           process_start=time.perf_counter(), log=lines.append, wrap_window=wrap, dag=dag)
    ibd = next((json.loads(ln.split(" ", 1)[1]) for ln in lines if ln.startswith("ibd ")), {})
    moved = next((json.loads(ln.split(" ", 1)[1]) for ln in lines if ln.startswith("counters ")), {})
    return {
        "correct": out["correct"], "attempted": out["attempted"], "failed": out["failed"],
        "failing": {k: v[0] for k, v in out["checks"].items() if v[0] != v[1]},
        "ibd": {k: ibd.get(k) for k in ("ibd_blocks_missing", "ibd_blocks_unsent_held", "ibd_rerequests",
                                        "ibd_bad_continuations", "hung_up", "served", "held", "drained")},
        "p2p_ibd_blocks_rejected": moved.get("p2p_ibd_blocks_rejected", 0),
    }


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=6.0)
    names = breaks()
    ap.add_argument("--breaks", default=",".join(names))
    args = ap.parse_args(argv)

    from benchmarks import harness
    from kaspa_tpu.ops import dispatch as coalescing
    from kaspa_tpu.utils import jax_setup

    jax_setup.setup()
    bench, _entry, workload, config = harness.load_cell(args.workload)
    info = harness.device_info()
    if info["platform"] != "tpu":
        print(f"benchmarks/control_ibd.py: needs a TPU, JAX found {info}", file=sys.stderr)
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
