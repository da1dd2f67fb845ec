#!/usr/bin/env python3
"""One run of one benchmark cell, in the one process that holds the chip.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's DAG from ``--seed``, warms the cell's own shapes, replays
the ramp, measures for ``--seconds`` and prints one JSON object as the last
line of standard output.  Earlier lines carry what that line may not: DAG
facts, super-batches by bucket, compile-cache hits and misses, the set-up
split, whether a second pass started.  Every number compared for ``correct``
is printed beside its limit as the last lines of standard error and under
``checks``, the result line's last key.

It fails — non-zero, no result line — unless ``jax.devices()[0].platform ==
"tpu"`` with as many chips as the cell asks for; nothing relaxes that.
``BENCH_RUN`` in the environment is ignored.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _log(msg: str) -> None:
    print(msg, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "kaspa_tpu")):
        print("benchmarks/run.py: the program (kaspa_tpu/) is not beside the benchmark", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    from benchmarks import harness

    bench, entry, workload, config = harness.load_cell(args.workload)

    from kaspa_tpu.utils import jax_setup

    jax_setup.setup()  # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    import jax

    try:
        info = harness.device_info()
    except RuntimeError as e:
        print(f"benchmarks/run.py: no device: {e}", file=sys.stderr)
        return 3
    if info["platform"] != "tpu" or info["count"] < int(entry["chips"]):
        print(f"benchmarks/run.py: needs {entry['chips']} TPU chip(s), JAX found {info}", file=sys.stderr)
        return 3
    _log("device " + json.dumps(info) + f" jax {jax.__version__} cache {jax_setup.cache_dir()}")

    out = harness.run_cell(
        workload, config, bench, args.workload, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        process_start=_PROCESS_START, log=_log,
    )
    sys.stdout.flush()
    for name, (value, limit) in out["checks"].items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
