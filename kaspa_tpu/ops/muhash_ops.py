"""Batched U3072 product on device: the muhash bulk-diff kernel.

The reference reduces muhash over txs with a rayon map-reduce
(consensus/src/pipeline/virtual_processor/utxo_validation.rs:334-363,
crypto/muhash/src/lib.rs:87-90 `combine`).  Here the monoid product of a
batch of 3072-bit field elements is a jax.lax tree reduction (log2(N)
levels of pairwise modular multiplies) — the multiplies vectorise over the
shrinking batch, keeping the VPU busy at every level.

Elements enter as [N, 192] int32 limb arrays (see ops/bigint.int_to_limbs).
A product goes out in chunks of the two compiled widths (``BUCKETS``), the
last one padded with ones (the monoid identity).  Every chunk of a
``ProductGroup`` - one product, or the two of a muhash commit - is launched
before any result is read back, so the host waits for the device once a
group and not once a chunk; the chunks' partial products are then combined
on the host (one 3072-bit multiply each).
"""

from __future__ import annotations

import functools
from time import perf_counter_ns

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

# dispatches over waits = how many launches share one blocking read-back
DEVICE_WAITS = REGISTRY.counter(
    "muhash_device_waits", help="times the host blocked for tree-product results (one a ProductGroup read-back)"
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


# Launched-and-unread chunks a group may hold: 32 x 1,024 x 192 x 4 B = 25 MB
# of limbs on the device.  A product with more chunks (a UTXO-set import of
# a million elements) reads a group back before it launches the next.
MAX_IN_FLIGHT = 32


def _chunks(n: int) -> list[tuple[int, int, int]]:
    """(pos, elements, bucket) of each dispatch of an n-element product: the
    largest bucket that fits the remainder, else the smallest bucket (padded
    with identity) - keeps the set of compiled shapes tiny."""
    chunks = []
    pos = 0
    while pos < n:
        remaining = n - pos
        fitting = [b for b in BUCKETS if b <= remaining]
        bucket = fitting[-1] if fitting else BUCKETS[0]
        take = min(bucket, remaining)
        chunks.append((pos, take, bucket))
        pos += take
    return chunks


class ProductGroup:
    """Tree products launched on the device and not yet read back.

    ``launch`` cuts one product into chunks by the bucket rule and hands
    every chunk to the device without waiting for any (JAX dispatch is
    asynchronous); ``finish`` fetches what is unread together and returns
    one python int per ``launch``, in order.  Products launched into one
    group (a muhash commit's numerator and denominator) share its wait: the
    host blocks on the device once a group, however many chunks, and
    whatever it does between two launches runs while the device works.  A
    group holds at most ``MAX_IN_FLIGHT`` unread chunks; a product with
    more reads them back before it launches the next.

    A full chunk goes up as its own slice of ``elements`` and the ragged
    tail in a buffer of its own: the transfer is asynchronous too, so the
    caller leaves ``elements`` alone until ``finish``, and nothing here is
    a scratch array filled twice.
    """

    def __init__(self):
        self._products: list[int] = []  # one per launch(); partial until finish()
        self._unread: list[tuple[int, int, jax.Array]] = []  # (slot in _products, bucket, [192] limbs on the device)
        self._t0_ns = 0  # first launch among the unread chunks
        self._elements = 0  # useful elements of the unread chunks

    def launch(self, elements: np.ndarray) -> None:
        """[N, 192] int32 limbs: start their product mod 2**3072 - 1103717.

        With a configured device mesh (> 1) the whole reduction shards over
        the mesh instead and is read back here - same result (the monoid
        product is association-free), one compiled shape per (mesh, bucket).
        """
        slot = len(self._products)
        self._products.append(1)
        n = elements.shape[0]
        if n == 0:
            return
        from kaspa_tpu.ops import mesh

        if mesh.active_size() > 1:
            self._products[slot] = mesh.dispatch_tree_product(elements)
            return
        elements = np.ascontiguousarray(elements, dtype=np.int32)
        chunks = _chunks(n)
        pos, take, bucket = chunks[-1]
        tail = None
        if take < bucket:
            with trace.span("muhash.host_prepare", phase="pad", elements=take):
                tail = np.tile(np.asarray(F.one, dtype=np.int32), (bucket, 1))
                tail[:take] = elements[pos:]
        for pos, take, bucket in chunks:
            if len(self._unread) >= MAX_IN_FLIGHT:
                self._read_back()
            if not self._unread:
                self._t0_ns = perf_counter_ns()
            host = elements[pos : pos + take] if take == bucket else tail
            self._unread.append((slot, bucket, _tree_product(jnp.asarray(host), bucket.bit_length() - 1)))
            self._elements += take
            DEVICE_DISPATCHES.inc(str(bucket))
            DEVICE_ELEMENTS.inc(take)
            _note_bucket(bucket)

    def finish(self) -> list[int]:
        """The product of every ``launch`` so far, in order."""
        self._read_back()
        return self._products

    def _read_back(self) -> None:
        """One wait: every unread result to the host, then each folded into
        its product (cheap: one 3072-bit multiply a chunk)."""
        if not self._unread:
            return
        outs = jax.device_get([out for _, _, out in self._unread])
        DEVICE_WAITS.inc()
        # one span a wait, from the group's first launch to its last result
        # on the host: what the host spent on the device product, and what
        # it prepared for a later product of the group meanwhile
        trace.record_span(
            "muhash.device_dispatch", trace.context(), self._t0_ns, perf_counter_ns(),
            bucket=max(bucket for _, bucket, _ in self._unread), dispatches=len(outs), elements=self._elements,
        )
        for (slot, _, _), out in zip(self._unread, outs):
            self._products[slot] = self._products[slot] * bi.limbs_to_int(out) % F.modulus
        self._unread.clear()
        self._elements = 0


def batch_product_device(elements: np.ndarray) -> int:
    """[N, 192] int32 limbs -> product mod 2**3072 - 1103717 (python int):
    one ``ProductGroup`` launched and finished."""
    group = ProductGroup()
    group.launch(elements)
    return group.finish()[0]


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
