#!/usr/bin/env python3
"""The control and the faults: the timed path broken underneath, to show that
``correct`` comes out false.  A benchmark run never enters here.

    python benchmarks/control.py --workload <cell> --seeds 1,2,3 --seconds 6

For each seed it runs the cell's window three more times in the same process
as the honest run (one DAG build per seed, the same ``harness.run_cell``):

- ``accept_every_signature`` — the control.  The system states no numeric
  precision; its configuration states guarantees.  The control breaks the one
  a later PR would be tempted by: every signature job is answered "valid"
  without the ladder deciding it (what skipping or trusting verification
  would do).  The spoiled spends are then accepted, the UTXO commitment leaves
  the headers' and honest blocks are disqualified.
- ``flip_one_answer`` — a fault: one lane of one device mask altered where it
  is produced (an honest spend refused).
- ``host_lane`` — a fault: a device dispatch fails and the bit-identical host
  lane answers.  Sink and commitment stay right; only the guarded-dispatch
  numbers (``degraded_jobs``) show it.

On a TPU it needs the chip like ``run.py``; ``tests/test_control.py`` calls
the same context managers at toy size on the CPU.
"""

from __future__ import annotations

import contextlib
import functools
import time


@contextlib.contextmanager
def _patched_schnorr(alter):
    """``crypto/secp.py`` looks ``schnorr_verify`` up at every dispatch: wrap
    it so that ``alter(mask, valid_in)`` is what the program gets back."""
    from kaspa_tpu.crypto import secp

    real = secp.schnorr_verify

    @functools.wraps(real)  # the kernel's __name__ keys the warm-shape table
    def altered(px, py, rc, k1, k2, valid_in):
        import numpy as np

        return alter(np.asarray(real(px, py, rc, k1, k2, valid_in)).copy(), np.asarray(valid_in))

    secp.schnorr_verify = altered
    try:
        yield
    finally:
        secp.schnorr_verify = real


def accept_every_signature():
    def alter(mask, valid_in):
        mask[: len(valid_in)] = valid_in  # every lane that passed the host's range checks
        return mask

    return _patched_schnorr(alter)


def flip_one_answer():
    state = {"done": False}

    def alter(mask, valid_in):
        if not state["done"] and mask.any():
            mask[int(mask.argmax())] = False  # the first lane the device found valid
            state["done"] = True
        return mask

    return _patched_schnorr(alter)


@contextlib.contextmanager
def host_lane():
    from kaspa_tpu.resilience.faults import FAULTS

    FAULTS.configure({"device.verify": {"mode": "error", "hits": [2]}})
    try:
        yield
    finally:
        FAULTS.clear()


@contextlib.contextmanager
def wrong_selected_parent():
    """A fault of the header stage: GHOSTDAG takes the parent of *least* blue
    work as the selected parent.  Kept for the tests (the measured consensus
    then refuses the blocks, whose headers carry the right scores)."""
    from kaspa_tpu.consensus.processes.ghostdag import GhostdagManager

    real = GhostdagManager.find_selected_parent
    GhostdagManager.find_selected_parent = lambda self, parents: min(
        parents, key=lambda p: (self.ghostdag_store.get_blue_work(p), p)
    )
    try:
        yield
    finally:
        GhostdagManager.find_selected_parent = real


BREAKS = {"accept_every_signature": accept_every_signature, "flip_one_answer": flip_one_answer, "host_lane": host_lane}


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--breaks", default=",".join(BREAKS))
    args = ap.parse_args(argv)

    from benchmarks import harness
    from kaspa_tpu.utils import jax_setup

    jax_setup.setup()
    bench, entry, workload, config = harness.load_cell(args.workload)
    info = harness.device_info()
    if info["platform"] != "tpu":
        print(f"benchmarks/control.py: needs a TPU, JAX found {info}", file=sys.stderr)
        return 3
    ok = True
    from kaspa_tpu.ops import dispatch as coalescing

    for seed in (int(s) for s in args.seeds.split(",")):
        dag = None
        for name in ["honest"] + args.breaks.split(","):
            if dag is None:
                coalescing.configure(0)
                harness._pretrace(workload, lambda _m: None)
                dag = harness.build_dag(workload, config, seed, lambda _m: None)  # one build, every window
            out = harness.run_cell(
                workload, config, bench, args.workload, seed=seed, seconds=args.seconds, trace=False,
                process_start=time.perf_counter(), log=lambda _m: None, wrap_window=BREAKS.get(name), dag=dag,
            )
            failing = {k: v[0] for k, v in out["checks"].items() if v[0] != v[1]}
            print(json.dumps({"seed": seed, "run": name, "correct": out["correct"], "failing": failing,
                              "attempted": out["attempted"]}), flush=True)
            ok = ok and (out["correct"] == (name == "honest"))
    print(json.dumps({"control_ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
