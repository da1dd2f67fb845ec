"""Mesh execution layer: shard_map dispatch for the production batch kernels.

The 8-device dryrun (`__graft_entry__.dryrun_multichip`) proved the verify
kernels and the muhash tree product shard bit-identically over a 1-D device
mesh; this module makes that the *production* path.  `configure("--mesh N")`
selects a mesh size once per process (``auto`` = every visible device), and
the batch front-ends (`ops/secp256k1/verify.py`, `ops/muhash_ops.py`) route
through here whenever the active size is > 1:

- inputs are padded to a shard multiple (invalid lanes for verify, the
  monoid identity for muhash) and results unpadded, so callers keep their
  exact single-device shapes and semantics;
- one jit entry is cached per (kernel, mesh size) — the shard_map trace
  sees the per-shard local shape, so the compiled artifact set stays as
  small as the single-device bucket scheme;
- per-shard observability (occupancy, padding waste, local batch sizes,
  dispatch counts) lands in the global registry next to the secp batch
  telemetry, surfacing through ``get_metrics`` and the Prometheus text.

CPU-mesh testing recipe (no TPU needed, what the test suite does):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python -m kaspa_tpu.sim --blocks 32 --mesh 8

Sharding layout: pure batch-dim data parallelism for the verify kernels
(no collectives — each shard verifies its slice and returns its mask
slice); the muhash tree product reduces each shard's slice to one U3072
partial product on device and combines the <= mesh-size partials on host
(one cheap 3072-bit multiply each), which keeps the result bit-identical
to any other association order of the commutative monoid product.

2-D hybrid mesh (the verify-fabric substrate): ``configure("RxC")``
arranges the devices as R slices of C devices each — on a multi-host
deployment via ``create_hybrid_device_mesh`` (slices map to hosts, the
fast intra-host links carry the "shard" axis), on a single host by
reshaping the local devices (the CPU test topology).  A fabric slice
worker pins itself with ``slice_lane(i)`` so its dispatches run on slice
i's devices only; unpinned dispatches shard over the whole grid.  All
in/out specs derive from the regex partition-rule registry, and every
path — 1-D, full grid, single slice — is batch-dim data parallelism over
the same kernels, so masks stay bit-identical across layouts.
"""

from __future__ import annotations

import contextlib
import functools
import os
import re
import threading

from kaspa_tpu.utils.sync import ranked_lock

import numpy as np

from kaspa_tpu.observability import trace
from kaspa_tpu.observability.core import PERCENT_BUCKETS, REGISTRY, SIZE_BUCKETS

# --- per-shard observability ----------------------------------------------
# occupancy is per SHARD (not per batch): contiguous padding concentrates
# waste in the tail shards, and a starved tail shard is pure bubble on that
# device — the first thing to look at when mesh throughput disappoints
_SHARD_OCCUPANCY = REGISTRY.histogram(
    "mesh_shard_occupancy_pct", PERCENT_BUCKETS,
    help="useful (non-pad) lanes per shard / shard width * 100, one observation per shard per dispatch",
)
_SHARD_BATCH = REGISTRY.histogram(
    "mesh_shard_batch_size", SIZE_BUCKETS, help="per-shard local batch width of mesh dispatches"
)
_PAD_WASTE = REGISTRY.histogram(
    "mesh_padding_waste_pct", PERCENT_BUCKETS,
    help="pad lanes added by the mesh layer / padded total * 100, per dispatch",
)
_PADDED_LANES = REGISTRY.counter("mesh_padded_lanes", help="device lanes wasted on pad-to-shard-multiple")
_DISPATCHES = REGISTRY.counter_family(
    "mesh_dispatches", "kernel", help="sharded dispatches by kernel (schnorr/ecdsa/muhash)"
)

_SLICE_DISPATCHES = REGISTRY.counter_family(
    "mesh_slice_dispatches", "slice", help="slice-pinned verify dispatches by mesh slice"
)
_SLICE_JOBS = REGISTRY.counter_family(
    "mesh_slice_jobs", "slice", help="verify jobs dispatched per mesh slice (pre-padding)"
)

_lock = ranked_lock("mesh.config")
_configured: str | int | None = None  # raw spec, resolved lazily
_active: int | None = None  # resolved mesh size
_grid: tuple[int, int] | None = None  # (slices, shards-per-slice) for "RxC" specs
_slice_tls = threading.local()  # slice_lane() pin: route dispatches to one slice


def _mesh_state() -> dict:
    n = active_size()
    return {
        "configured": str(_configured) if _configured is not None else "",
        "size": n,
        "grid": "x".join(map(str, _grid)) if _grid else "",
        "slices": slice_count(),
    }


REGISTRY.register_collector("mesh", _mesh_state)


def configure(spec: int | str | None) -> int:
    """Select the process-wide mesh size; returns the resolved size.

    ``spec``: an int, a decimal string, ``"auto"`` (every visible device),
    an ``"RxC"`` grid (R slices of C devices — the 2-D hybrid mesh), or
    None (fall back to the KASPA_TPU_MESH env var, default 1).  An
    explicit size above the visible device count raises ValueError (only
    ``auto`` adapts to what is visible) and leaves the previous
    configuration in place; <= 1 disables mesh dispatch.
    """
    global _configured, _active, _grid
    raw = spec if spec is not None else os.environ.get("KASPA_TPU_MESH", 1)
    active, grid_ = _resolve(raw)
    with _lock:
        _configured, _active, _grid = raw, active, grid_
    return active


def active_size() -> int:
    """Resolved mesh size (1 = mesh dispatch disabled)."""
    global _configured, _active, _grid
    if _active is None:
        with _lock:
            if _active is None:
                spec = _configured if _configured is not None else os.environ.get("KASPA_TPU_MESH", 1)
                _configured = spec
                _active, _grid = _resolve(spec)
    return _active


def grid() -> tuple[int, int] | None:
    """The resolved (slices, shards-per-slice) grid, or None in 1-D mode."""
    active_size()
    return _grid


def slice_count() -> int:
    """Mesh slices of the active grid (1 in 1-D / disabled mode)."""
    g = grid()
    return g[0] if g else 1


def slice_width() -> int:
    """Devices per slice of the active grid (= mesh size in 1-D mode)."""
    g = grid()
    return g[1] if g else active_size()


def _resolve(spec: int | str) -> tuple[int, int | None]:
    import jax

    ndev = len(jax.devices())
    if isinstance(spec, str):
        spec = spec.strip().lower()
        if "x" in spec:
            r_s, _, c_s = spec.partition("x")
            r, c = max(1, int(r_s or 1)), max(1, int(c_s or 1))
            if r * c > ndev:
                raise ValueError(f"mesh {r}x{c} needs {r * c} devices, {ndev} visible")
            if r <= 1:
                return (c if c > 1 else 1), None
            return r * c, (r, c)
        if spec in ("auto", "all"):
            n = ndev
        else:
            n = int(spec or 1)
    else:
        n = int(spec)
    if n <= 1:
        return 1, None
    if n > ndev:
        # never clamp: a four-chip deployment that silently runs on one
        # chip measures (and serves) something else than was asked for
        raise ValueError(f"mesh {n} needs {n} devices, {ndev} visible")
    return n, None


@contextlib.contextmanager
def slice_lane(idx: int | None):
    """Pin this thread's verify dispatches to mesh slice ``idx`` (no-op
    when no 2-D grid is configured or ``idx`` is None) — the fabric slice
    workers wrap their device calls in this so concurrent slices run on
    disjoint devices."""
    if idx is None or _grid is None:
        yield
        return
    prev = getattr(_slice_tls, "idx", None)
    _slice_tls.idx = idx % _grid[0]
    try:
        yield
    finally:
        _slice_tls.idx = prev


@functools.lru_cache(maxsize=None)
def _mesh(n: int):
    import jax
    from jax.sharding import Mesh

    devices = np.array(jax.devices()[:n])
    assert len(devices) == n, f"mesh size {n} exceeds visible devices {len(jax.devices())}"
    return Mesh(devices, axis_names=("shard",))


@functools.lru_cache(maxsize=None)
def _device_grid(r: int, c: int) -> np.ndarray:
    """[r, c] device array for the hybrid mesh: `create_hybrid_device_mesh`
    when the process set actually spans hosts (slices ride the slow DCN
    axis, shards the fast ICI axis), plain local reshape otherwise (the
    single-host / CPU-test topology, where the hybrid helper has no slice
    metadata to work with)."""
    import jax

    if jax.process_count() > 1:
        try:
            from jax.experimental.mesh_utils import create_hybrid_device_mesh

            return np.asarray(
                create_hybrid_device_mesh((1, c), (r, 1), devices=jax.devices())
            ).reshape(r, c)
        except Exception:  # noqa: BLE001 - topology metadata absent: fall back
            pass
    return np.array(jax.devices()[: r * c]).reshape(r, c)


@functools.lru_cache(maxsize=None)
def _mesh2d(r: int, c: int):
    from jax.sharding import Mesh

    return Mesh(_device_grid(r, c), axis_names=("slice", "shard"))


@functools.lru_cache(maxsize=None)
def _slice_mesh(r: int, c: int, idx: int):
    """1-D mesh over slice ``idx``'s row of the grid — slice-pinned
    dispatches reuse the plain ("shard",) kernel entries on it."""
    from jax.sharding import Mesh

    return Mesh(_device_grid(r, c)[idx], axis_names=("shard",))


# --- partition-rule registry ------------------------------------------------
# regex -> PartitionSpec axes, first match wins (the t5x/EasyLM registry
# idiom): verify/muhash operands are pure batch-dim data parallelism, so
# the batch axis shards over every mesh axis and everything else
# replicates.  register_partition_rule() lets a new kernel claim a layout
# without touching the dispatch plumbing.
DEFAULT_PARTITION_RULES: tuple = (
    (r"(px|py|rc|.*digits|elements)$", (("slice", "shard"), None)),
    (r"(valid_in|mask)$", (("slice", "shard"),)),
    (r".*", ()),  # replicate
)

_partition_rules: list = list(DEFAULT_PARTITION_RULES)


def register_partition_rule(pattern: str, axes: tuple) -> None:
    """Prepend one (regex, PartitionSpec axes) rule (first match wins)."""
    _partition_rules.insert(0, (pattern, axes))


def _axes_for_1d(axes: tuple) -> tuple:
    """Project a 2-D rule onto a 1-D ("shard",) mesh: the composite
    ("slice", "shard") batch axis collapses to "shard"."""
    return tuple("shard" if isinstance(a, tuple) else a for a in axes)


def partition_spec_for(name: str, *, flat: bool = False):
    """PartitionSpec for a named operand per the registry; ``flat=True``
    projects onto the 1-D mesh axis."""
    from jax.sharding import PartitionSpec as P

    for pattern, axes in _partition_rules:
        if re.fullmatch(pattern, name):
            return P(*(_axes_for_1d(axes) if flat else axes))
    return P()


def match_partition_rules(rules, tree: dict) -> dict:
    """Map a (possibly nested) dict of named arrays to PartitionSpecs by
    first-matching regex on the '/'-joined path — the SNIPPETS registry
    shape, usable for any future parameter pytree."""
    from jax.sharding import PartitionSpec as P

    def walk(prefix: str, node):
        if isinstance(node, dict):
            return {k: walk(f"{prefix}/{k}" if prefix else k, v) for k, v in node.items()}
        for pattern, axes in rules:
            if re.search(pattern, prefix):
                return P(*axes)
        return P()

    return walk("", tree)


def constrain(x, name: str):
    """`with_sharding_constraint` under the registry's spec for ``name`` —
    a no-op on CPU or when no 2-D grid is configured (the SNIPPETS [3]
    CPU-fallback contract), so call sites never need backend guards.
    Inside ``shard_map`` the mesh axes are manual and jax refuses the
    constraint with ValueError: the value is already laid out, identity."""
    g = grid()
    if g is None:
        return x
    import jax

    if jax.default_backend() == "cpu":
        return x
    from jax.sharding import NamedSharding

    try:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(_mesh2d(*g), partition_spec_for(name))
        )
    except ValueError:  # manual (shard_map) axes: nothing to constrain
        return x


def _sharded_jit(fn, mesh, in_specs, out_specs):
    """The one place a kernel meets the mesh: ``jax.jit(jax.shard_map(...))``
    with the varying-axes check left on, so a kernel whose loop carries do
    not type under the mesh fails at trace time instead of miscompiling."""
    import jax

    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs))


def _pad_rows(arr: np.ndarray, m: int) -> np.ndarray:
    """Zero-pad the leading (batch) axis of `arr` to m rows."""
    arr = np.asarray(arr)
    if arr.shape[0] == m:
        return arr
    out = np.zeros((m,) + arr.shape[1:], dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def _observe(kernel: str, logical: int, padded: int, n: int) -> None:
    _DISPATCHES.inc(kernel)
    _PADDED_LANES.inc(padded - logical)
    _PAD_WASTE.observe(100.0 * (padded - logical) / padded)
    width = padded // n
    for shard in range(n):
        useful = min(max(logical - shard * width, 0), width)
        _SHARD_OCCUPANCY.observe(100.0 * useful / width)
        _SHARD_BATCH.observe(width)


# --- batched signature verification ---------------------------------------


_VERIFY_ARG_NAMES = ("px", "py", "rc", "d1_digits", "d2_digits", "valid_in")


def _verify_kernel(kind: str):
    from kaspa_tpu.ops.secp256k1 import verify as v

    return (v.schnorr_verify_kernel if kind == "schnorr" else v.ecdsa_verify_kernel).__wrapped__


@functools.lru_cache(maxsize=None)
def _verify_entry(kind: str, n: int):
    """Cached shard_map-jitted verify kernel for one (kind, mesh size);
    in/out specs come from the partition-rule registry projected onto the
    1-D ("shard",) axis."""
    in_specs = tuple(partition_spec_for(nm, flat=True) for nm in _VERIFY_ARG_NAMES)
    out_specs = partition_spec_for("mask", flat=True)
    return _sharded_jit(_verify_kernel(kind), _mesh(n), in_specs, out_specs)


@functools.lru_cache(maxsize=None)
def _verify_entry_2d(kind: str, r: int, c: int):
    """Full-grid entry: batch axis sharded over ("slice", "shard") — the
    same per-device local shapes (and thus the same trace cost and
    bit-identical masks) as the 1-D entry of size r*c."""
    in_specs = tuple(partition_spec_for(nm) for nm in _VERIFY_ARG_NAMES)
    out_specs = partition_spec_for("mask")

    kernel = _verify_kernel(kind)

    def wrapped(*args):
        return constrain(kernel(*args), "mask")

    return _sharded_jit(wrapped, _mesh2d(r, c), in_specs, out_specs)


@functools.lru_cache(maxsize=None)
def _verify_entry_slice(kind: str, r: int, c: int, idx: int):
    """Slice-pinned entry: the 1-D kernel over slice ``idx``'s devices, so
    concurrent fabric slice workers occupy disjoint hardware."""
    in_specs = tuple(partition_spec_for(nm, flat=True) for nm in _VERIFY_ARG_NAMES)
    out_specs = partition_spec_for("mask", flat=True)
    return _sharded_jit(_verify_kernel(kind), _slice_mesh(r, c, idx), in_specs, out_specs)


def _verify_shards() -> int:
    """Shards this thread's verify dispatch runs over: the pinned slice's
    width inside ``slice_lane``, else the whole mesh."""
    g = _grid
    return g[1] if g is not None and getattr(_slice_tls, "idx", None) is not None else active_size()


def padded_lanes(b: int) -> int:
    """Rows a verify batch of ``b`` is padded to: a shard multiple."""
    n = _verify_shards()
    return -(-b // n) * n


def dispatch_verify(kind: str, px, py, rc, d1_digits, d2_digits, valid_in) -> np.ndarray:
    """Batch-dim sharded verify: pads to a shard multiple, dispatches the
    cached shard_map entry, unpads the mask.  Pad lanes carry zeroed limbs
    and ``valid_in=False`` so they can never contribute a True.

    With a 2-D grid configured, a thread inside ``slice_lane(i)`` runs on
    slice i's devices only; unpinned threads shard over the full grid.
    """
    import jax

    from kaspa_tpu.resilience.faults import FAULTS

    # mesh-specific fault point (a single wedged shard kills the whole
    # shard_map dispatch); propagates into the device breaker like any
    # other dispatch failure
    FAULTS.fire("device.mesh.dispatch")
    n = _verify_shards()
    g = _grid
    pin = getattr(_slice_tls, "idx", None) if g else None
    if g is None:
        entry = _verify_entry(kind, n)
    elif pin is not None:
        entry = _verify_entry_slice(kind, g[0], g[1], pin)
    else:
        entry = _verify_entry_2d(kind, g[0], g[1])
    px = np.asarray(px)
    b = px.shape[0]
    if b == 0:
        return np.zeros(0, dtype=bool)
    m = -(-b // n) * n  # ceil to shard multiple (padded_lanes)
    args = (
        _pad_rows(px, m),
        _pad_rows(py, m),
        _pad_rows(rc, m),
        _pad_rows(d1_digits, m),
        _pad_rows(d2_digits, m),
        _pad_rows(np.asarray(valid_in, dtype=bool), m),
    )
    kernel = f"{kind}_mesh"
    with trace.span("secp.device_call", kernel=kernel, lanes=m, bytes=sum(a.nbytes for a in args)):
        out = entry(*args)
        out.copy_to_host_async()  # queued behind the kernel, as np.asarray alone would
        jax.block_until_ready(out)
    with trace.span("secp.readback", kernel=kernel):
        mask = np.asarray(out)[:b]
    _observe(kind, b, m, n)
    if pin is not None:
        _SLICE_DISPATCHES.inc(str(pin))
        _SLICE_JOBS.inc(str(pin), b)
    return mask


# --- muhash tree product ---------------------------------------------------


def _local_tree(levels: int):
    """Per-shard tree product: a [2**levels, 192] slice -> one canonical
    U3072 element ([1, 192])."""
    from kaspa_tpu.ops import bigint as bi

    F = bi.F3072

    def local_tree(x):
        for _ in range(levels):
            half = x.shape[0] // 2
            x = bi.mul(F, x[:half], x[half:])
        return bi.canon(F, x[0])[None, :]

    return local_tree


@functools.lru_cache(maxsize=None)
def _tree_entry(n: int, levels: int):
    """Cached shard_map-jitted local tree product: each shard reduces its
    [bucket, 192] slice to one canonical U3072 element ([1, 192])."""
    from jax.sharding import PartitionSpec as P

    return _sharded_jit(_local_tree(levels), _mesh(n), P("shard", None), P("shard", None))


def dispatch_tree_product(elements: np.ndarray) -> int:
    """Sharded U3072 product: [N, 192] int32 limbs -> python int mod the
    muhash prime.  Mirrors `muhash_ops.batch_product_device`'s bucket
    policy per shard (one compiled shape per (mesh, bucket)); each shard's
    partial product combines on host with one 3072-bit multiply.
    """
    from kaspa_tpu.ops import bigint as bi
    from kaspa_tpu.ops.muhash_ops import BUCKETS, DEVICE_DISPATCHES, DEVICE_ELEMENTS

    F = bi.F3072
    n = active_size()
    elements = np.asarray(elements)
    total = elements.shape[0]
    if total == 0:
        return 1
    result = 1
    pos = 0
    while pos < total:
        remaining = total - pos
        per_shard = -(-remaining // n)
        # largest bucket that fits the per-shard remainder, else the
        # smallest bucket (identity-padded) — same shape discipline as the
        # single-device path, scaled by the mesh
        fitting = [bk for bk in BUCKETS if bk <= per_shard]
        bucket = fitting[-1] if fitting else BUCKETS[0]
        take = min(bucket * n, remaining)
        chunk = elements[pos : pos + take]
        padded = np.tile(np.asarray(F.one, dtype=np.int32), (bucket * n, 1))
        padded[: chunk.shape[0]] = chunk
        partials = np.asarray(_tree_entry(n, bucket.bit_length() - 1)(padded))
        DEVICE_DISPATCHES.inc(str(bucket))
        DEVICE_ELEMENTS.inc(take)
        for row in partials:
            result = result * bi.limbs_to_int(row) % F.modulus
        _observe("muhash", take, bucket * n, n)
        pos += take
    return result
