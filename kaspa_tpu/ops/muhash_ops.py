"""Batched U3072 product on device: the muhash bulk-diff kernel.

The reference reduces muhash over txs with a rayon map-reduce
(consensus/src/pipeline/virtual_processor/utxo_validation.rs:334-363,
crypto/muhash/src/lib.rs:87-90 `combine`).  Here the monoid product of a
batch of 3072-bit field elements is a jax.lax tree reduction (log2(N)
levels of pairwise modular multiplies) — the multiplies vectorise over the
shrinking batch, keeping the VPU busy at every level.

Elements enter as [N, 192] int32 limb arrays (see ops/bigint.int_to_limbs);
N is padded to a power of two with ones (the monoid identity).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from kaspa_tpu.observability import trace
from kaspa_tpu.observability.core import REGISTRY
from kaspa_tpu.ops import bigint as bi

F = bi.F3072

DEVICE_DISPATCHES = REGISTRY.counter_family(
    "muhash_device_dispatches", "bucket",
    help="tree-product dispatches answered by the device, by per-device bucket",
)


# the useful elements of those dispatches: over bucket x dispatches this is
# the tree's lane occupancy, and the unit of work its roofline share counts
DEVICE_ELEMENTS = REGISTRY.counter(
    "muhash_device_elements", help="field elements multiplied by the device tree product (padding not counted)"
)


# Fixed batch buckets: one jit compile per bucket size (the 3072-bit mul
# body is large, so unbounded shape-polymorphism would hammer compile time).
BUCKETS = (64, 1024)


@functools.partial(jax.jit, static_argnames=("levels",))
def _tree_product(x, levels: int):
    with jax.named_scope("muhash_tree_product"):
        for _ in range(levels):
            half = x.shape[0] // 2
            x = bi.mul(F, x[:half], x[half:])
        return bi.canon(F, x[0])


def batch_product_device(elements: np.ndarray) -> int:
    """[N, 192] int32 limbs -> product mod 2**3072 - 1103717 (python int).

    Batches larger than the biggest bucket are reduced bucket-by-bucket with
    the partial products combined on host (cheap: one 3072-bit mul each).
    With a configured device mesh (> 1) the whole reduction shards over the
    mesh instead — same result (the monoid product is association-free),
    one compiled shape per (mesh, bucket).
    """
    n = elements.shape[0]
    if n == 0:
        return 1
    from kaspa_tpu.ops import mesh

    if mesh.active_size() > 1:
        return mesh.dispatch_tree_product(elements)
    result = 1
    pos = 0
    while pos < n:
        remaining = n - pos
        # largest bucket that fits the remainder, else the smallest bucket
        # (padded with identity) — keeps the set of compiled shapes tiny
        fitting = [b for b in BUCKETS if b <= remaining]
        bucket = fitting[-1] if fitting else BUCKETS[0]
        chunk = elements[pos : pos + min(bucket, remaining)]
        levels = bucket.bit_length() - 1
        with trace.span("muhash.host_prepare", phase="pad", elements=chunk.shape[0]):
            padded = np.tile(np.asarray(F.one, dtype=np.int32), (bucket, 1))
            padded[: chunk.shape[0]] = chunk
        with trace.span("muhash.device_dispatch", bucket=bucket, elements=chunk.shape[0]):
            out = np.asarray(_tree_product(jnp.asarray(padded), levels))
        DEVICE_DISPATCHES.inc(str(bucket))
        DEVICE_ELEMENTS.inc(chunk.shape[0])
        _note_bucket(bucket)
        result = result * bi.limbs_to_int(out) % F.modulus
        pos += chunk.shape[0]
    return result


# warm-manifest integration: first dispatch of each bucket this process
# records the shape so a restart can pretrace it (once per bucket — the
# manifest write is file io, not something to pay per reduction)
_noted_buckets: set[int] = set()


def _note_bucket(bucket: int) -> None:
    if bucket in _noted_buckets:
        return
    _noted_buckets.add(bucket)
    try:
        from kaspa_tpu.resilience import supervisor

        supervisor.note_shape("muhash_tree", bucket)
    except Exception:  # noqa: BLE001 - the manifest is an optimization
        pass


def pretrace_bucket(bucket: int) -> str:
    """Compile the tree-product kernel at one bucket shape ahead of
    traffic (warm-manifest restart path): an all-identity batch, so the
    product is 1 and the compile is the only work."""
    if bucket not in BUCKETS:
        return f"error:unknown muhash_tree/{bucket}"
    if bucket in _noted_buckets:
        return "warm"
    padded = np.tile(np.asarray(F.one, dtype=np.int32), (bucket, 1))
    jax.block_until_ready(_tree_product(jnp.asarray(padded), bucket.bit_length() - 1))
    _noted_buckets.add(bucket)
    return "traced"


def ints_to_elements(vals: list[int]) -> np.ndarray:
    return bi.ints_to_limbs(vals, F.W).astype(np.int32)


def batch_product_ints(vals: list[int]) -> int:
    return batch_product_device(ints_to_elements(vals))
