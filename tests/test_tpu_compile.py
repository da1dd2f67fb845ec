"""Ask the TPU's compiler for the main path's kernels at real widths.

The chip is described, not attached (on-chip-measurement guide, section 2):
a compile that passes here is not a chip run, but what the chip's compiler
refuses — a tiling it cannot do, too much fast memory, a kernel it cannot
partition — shows here at no chip time.  This is the only test file that
describes the chip, and it does so inside a module-scoped fixture: only one
process at a time may load the TPU library, and every xdist worker imports
every test file.  The remaining shapes — the other padded widths,
bucket-1024 muhash, and the 4-device shard_map ladder (over
a minute per compile, which would push this file past two) — are in
``tools/tpu_rehearse.py``, which also owns the case builders used here.
"""

import importlib.util
import os

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_rehearse():
    spec = importlib.util.spec_from_file_location("tpu_rehearse", os.path.join(REPO_ROOT, "tools", "tpu_rehearse.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def rehearse():
    return _load_rehearse()


@pytest.fixture(scope="module")
def topo(rehearse):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = rehearse.describe_topology()
    except Exception as e:  # noqa: BLE001 - no TPU compiler reachable from this process
        pytest.skip(f"no {rehearse.TOPOLOGY} topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but can
    # never be read back without a chip: keep these out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kind,n_padded", [("schnorr", 256), ("ecdsa", 1024)])
def test_pallas_ladder_compiles_for_v5e(rehearse, topo, kind, n_padded):
    """The default single-chip path: the fused Mosaic ladder, smallest and
    largest padded width, one signature kind each."""
    rep = rehearse.report(rehearse.pallas_ladder(topo, kind, n_padded))
    assert rep["mosaic_kernel"], "no tpu_custom_call in the compiled program"
    assert rep["code_bytes"] > 1_000_000  # the 64-window ladder is ~3 MB of code
    # one packed byte array in (five 32-byte fields and the valid byte a
    # lane; the device layout pads the rows), one int32 row out
    assert 161 * n_padded <= rep["argument_bytes"] < 2 * 161 * n_padded
    assert rep["output_bytes"] == n_padded * 4


def test_muhash_tree_compiles_for_v5e(rehearse, topo):
    """The muhash tree product at the bucket a block's UTXO diff reaches."""
    rep = rehearse.report(rehearse.muhash_tree(topo, 64))
    assert not rep["mosaic_kernel"]  # plain XLA
    # the device layout pads: at least the logical bytes, not exactly them
    assert rep["argument_bytes"] >= 64 * 192 * 4
    assert rep["output_bytes"] >= 192 * 4
    assert rep["code_bytes"] > 1_000_000 and rep["temp_bytes"] > 0
