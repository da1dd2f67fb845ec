"""The benchmark's own tests run by hand, on the CPU, at toy sizes:

    python -m pytest benchmarks/tests -q -p no:cacheprovider

They hold JAX to the CPU before its first import; nothing they time or count
is ever written under a device metric's name.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _jax_cache():
    from kaspa_tpu.utils import jax_setup

    jax_setup.setup()


TOY_NETWORK = {"bps": 2, "delay_s": 1.0, "miners": 4}
# simpa's network at toy size: one miner whose own blocks reach it after the
# delay too, so the DAG is about delay x bps = 4 blocks wide; mining gaps in
# seed-shuffled strata of one delay's blocks, as the real cell has them
TOY_WIDE = {  # keywords of toy_cell; pool: a spend's output is spendable about 4 blocks later
    "network": {"bps": 4, "delay_s": 1.0, "miners": 1, "own_blocks_delayed": True, "ghostdag_k": 55},
    "pool_factor": 8, "window_blocks": 40, "gap_stratum_blocks": 4,
    "tx_per_block": 6,  # a mergeset of 4-5 blocks then holds over 32 muhash elements: the device product's threshold
}


def toy_cell(mode: str, tx_per_block: int = 4, window_blocks: int = 24, network: dict = TOY_NETWORK,
             pool_factor: int = 3, gap_stratum_blocks: int = 0) -> tuple[dict, dict]:
    """A toy traffic file and configuration: the same keys as the real ones,
    sizes a CPU run can hold (one verify bucket, XLA ladder)."""
    workload = {
        "config": "toy", "mode": mode, "tx_per_block": tx_per_block, "tx_shape": "fanout-then-1to1",
        "window_blocks": window_blocks, "spoiled_blocks": 2, "pool_factor": pool_factor, "max_in_flight": 99,
        "grace_seconds": 30, "sig_samples": 4, "pretrace": {"schnorr_verify": [8]}, "trace_seconds": 1.0,
        "idle_gap_spans": ["txscript.dispatch_wait", "pipeline.virtual", "pipeline.body", "pipeline.header"],
    }
    if gap_stratum_blocks:
        workload["gap_stratum_blocks"] = gap_stratum_blocks
    config = {"name": "toy", "network": dict(network), "pipeline": {"coalesce": 64, "stage_workers": 2}}
    return workload, config
