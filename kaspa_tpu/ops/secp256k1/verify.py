"""Jitted batched Schnorr/ECDSA verification kernels (device side).

The host (kaspa_tpu/crypto/secp.py) parses/validates encodings, lifts
pubkeys to affine coordinates, computes challenge scalars, and extracts
4-bit window digits; the device does the heavy dual-scalar ladder and the
final affine checks, returning a validity bitmask — the layout prescribed
by the north star (BASELINE.json): triples in, bitmask out.
"""

from __future__ import annotations

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from kaspa_tpu.observability import trace
from kaspa_tpu.observability.core import REGISTRY
from kaspa_tpu.ops import bigint as bi
from kaspa_tpu.ops.secp256k1 import points as pt
from kaspa_tpu.resilience.faults import FAULTS

FP = bi.FP
FN = bi.FN


def _jit_compile_counts() -> dict:
    """Actual jit cache sizes of the verify kernels — one entry per
    (shape, backend) compilation.  When the round-5 style "0.0
    verifies/sec" failure recurs, this says whether the device ever
    finished a compile at all."""
    out = {}
    pairs = [("schnorr", schnorr_verify_kernel), ("ecdsa", ecdsa_verify_kernel)]
    try:  # the aggregate lane's two kernels, when the module has loaded
        from kaspa_tpu.ops.secp256k1 import aggregate as _agg

        pairs.append(("aggregate_partials", _agg.aggregate_partials_kernel))
        pairs.append(("aggregate_finish", _agg.aggregate_reduce_finish_kernel))
    except Exception:  # noqa: BLE001
        pass
    for name, fn in pairs:
        try:
            out[name] = int(fn._cache_size())
        except Exception:  # noqa: BLE001 - jax internals may shift
            pass
    # the fused ladder builds one Mosaic call per (padded width, kind); the
    # module only loads on a TPU backend
    lp = sys.modules.get("kaspa_tpu.ops.secp256k1.ladder_pallas")
    if lp is not None:
        out["pallas_plain"] = lp._build_call_plain.cache_info().currsize
        out["pallas_glv"] = lp._build_call.cache_info().currsize
    mo = sys.modules.get("kaspa_tpu.ops.muhash_ops")
    if mo is not None:
        out["muhash_tree"] = int(mo._tree_product._cache_size())
    return {"jit_compiles": out}


REGISTRY.register_collector("secp", _jit_compile_counts)


def _use_pallas() -> bool:
    """The fused Mosaic ladder runs on real TPU backends; the XLA
    formulation remains the portable path (CPU mesh tests, fallback)."""
    if os.environ.get("KASPA_TPU_NO_PALLAS"):
        return False
    return jax.default_backend() == "tpu"


def _scalars_to_digits(ks, b: int) -> np.ndarray:
    """Host: scalars -> [b, 64] MSB-first 4-bit window digits (padded).

    Elements are python ints or already-canonical 32-byte big-endian
    strings (the schnorr s column ships ``sig[32:]`` straight through,
    skipping the int round trip entirely); everything downstream of the
    single join is np.frombuffer bulk work.  Measured at B=8..16384
    against a log-depth shift-or bigint tree and a uint64-decompose numpy
    path: the one-join form is ~2-3x faster than either (CPython's
    to_bytes C path wins), and dropping the old loop's per-item ``int()``
    coercion is another 1.4-1.7x.  Shared by the ladder lane (s/e, u1/u2)
    and the aggregate lane's weight/combined-challenge digits.
    """
    out = np.zeros((b, 64), np.int32)
    if ks:
        raw = b"".join([k if type(k) is bytes else k.to_bytes(32, "big") for k in ks])
        arr = np.frombuffer(raw, dtype=np.uint8).reshape(len(ks), 32)
        dig = np.empty((len(ks), 64), np.uint8)
        dig[:, 0::2] = arr >> 4
        dig[:, 1::2] = arr & 0x0F
        out[: len(ks)] = dig
    return out


# what the device actually answered: a batch counts here only after its
# mask came back, so "the chip did the work" is readable from the registry
# (the degraded host lane in crypto/secp.py never passes through here)
_DEVICE_DISPATCHES = REGISTRY.counter_family(
    "secp_device_dispatches", "kernel",
    help="verify batches answered by the device, by formulation "
    "(<kind>_pallas fused ladder / <kind> XLA ladder / <kind>_mesh sharded XLA ladder)",
)
_DEVICE_BUCKETS = REGISTRY.counter_family(
    "secp_device_buckets", "bucket", help="verify batches answered by the device, by padded bucket width"
)
# the width the device actually ran, which the bucket understates on the
# Pallas path (whole 256-lane blocks): secp_device_jobs over this is the
# occupancy of the launched program
_DEVICE_LANES = REGISTRY.counter(
    "secp_device_lanes", help="lanes of the verify programs the device ran (bucket padded on to the launched width)"
)


def _verify(kind: str, px, py, rc, k1, k2, valid_in) -> np.ndarray:
    """Backend-dispatching batched verify shared by both signature kinds.

    px/py/rc: [B, 16] limb arrays; k1/k2: scalar sequences already reduced
    mod n (s/e for Schnorr, u1/u2 for ECDSA); valid_in: [B] bool.
    """
    # raise/wedge/slow the whole batch here — above every backend path, so
    # the breaker in crypto/secp.py sees the failure whichever way it routes
    FAULTS.fire("device.verify")
    # separate point for supervised-hang drills: mode "hang" sleeps past the
    # watchdog deadline then completes, "wedge" sleeps then dies — either
    # way the batch must already have been requeued on the host lane
    FAULTS.fire("device.hang")
    from kaspa_tpu.ops import mesh

    n_mesh = mesh.active_size()
    b = np.asarray(px).shape[0]
    if n_mesh == 1 and _use_pallas():
        from kaspa_tpu.ops.secp256k1.ladder_pallas import launched_lanes, verify_batch_pallas

        kernel = f"{kind}_pallas"
        lanes = launched_lanes(b)
        with trace.span("secp.device_dispatch", kernel=kernel, batch=b):
            mask = verify_batch_pallas(px, py, rc, k1, k2, valid_in, ecdsa=kind == "ecdsa")
    else:
        # host marshal vs device dispatch split: when throughput collapses,
        # this localizes the stall to python packing or the XLA round trip
        with trace.span("secp.host_marshal", kernel=kind, batch=b, lanes=b):
            d1 = _scalars_to_digits(k1, b)
            d2 = _scalars_to_digits(k2, b)
        if n_mesh > 1:
            # mesh > 1 rides the portable XLA formulation sharded over the
            # device mesh (the fused Mosaic ladder stays the single-chip path)
            kernel = f"{kind}_mesh"
            lanes = mesh.padded_lanes(b)
            with trace.span("secp.device_dispatch", kernel=kernel, batch=b, mesh=n_mesh):
                mask = mesh.dispatch_verify(kind, px, py, rc, d1, d2, valid_in)
        else:
            kernel = kind
            lanes = b
            xla_kernel = schnorr_verify_kernel if kind == "schnorr" else ecdsa_verify_kernel
            with trace.span("secp.device_dispatch", kernel=kernel, batch=b):
                with trace.span("secp.device_call", kernel=kernel, lanes=lanes):
                    out = xla_kernel(px, py, rc, d1, d2, valid_in)
                    out.copy_to_host_async()  # queued behind the kernel, as np.asarray alone would
                    jax.block_until_ready(out)
                with trace.span("secp.readback", kernel=kernel):
                    mask = np.asarray(out)
    _DEVICE_DISPATCHES.inc(kernel)
    _DEVICE_BUCKETS.inc(str(b))
    _DEVICE_LANES.inc(lanes)
    return mask


def schnorr_verify(px, py, r_canon, s_scalars, e_scalars, valid_in) -> np.ndarray:
    """Batched Schnorr verify (see _verify): s/e scalars, r canonical mod p."""
    return _verify("schnorr", px, py, r_canon, s_scalars, e_scalars, valid_in)


def ecdsa_verify(px, py, r_n_canon, u1_scalars, u2_scalars, valid_in) -> np.ndarray:
    """Batched ECDSA verify (see _verify): u1/u2 scalars, r canonical mod n."""
    return _verify("ecdsa", px, py, r_n_canon, u1_scalars, u2_scalars, valid_in)


@jax.jit
def schnorr_verify_kernel(px, py, r_canon, s_digits, e_digits, valid_in):
    """BIP340: R = s*G + e*(-P); valid iff R finite, even-y, x(R) == r.

    px/py: [B, 16] limbs of lifted pubkey (even y);  r_canon: [B, 16]
    canonical limbs of sig r;  s_digits/e_digits: [B, 64] int32 4-bit MSB
    windows;  valid_in: [B] bool (host-side encoding checks).
    """
    py_neg = bi.neg(FP, py)
    r = pt.dual_scalar_mul_base(px, py_neg, s_digits, e_digits)
    # batch-affine Montgomery inversion: one Fermat ladder per batch
    # instead of one per lane (see points.to_affine_batch)
    xa, ya, inf = pt.to_affine_batch(r)
    ok = ~inf
    ok &= jnp.all(xa == r_canon, axis=-1)
    ok &= (ya[..., 0] & 1) == 0
    return ok & valid_in


@jax.jit
def ecdsa_verify_kernel(px, py, r_n_canon, u1_digits, u2_digits, valid_in):
    """ECDSA: R = u1*G + u2*P; valid iff R finite and x(R) mod n == r.

    u1 = z*s^-1 mod n, u2 = r*s^-1 mod n are computed host-side (cheap,
    n-field inversions are per-signature scalars).
    """
    r = pt.dual_scalar_mul_base(px, py, u1_digits, u2_digits)
    xa, _ya, inf = pt.to_affine_batch(r)
    x_mod_n = bi.canon(FN, xa)  # x < p < 2**256: reinterpret limbs mod n
    ok = ~inf
    ok &= jnp.all(x_mod_n == r_n_canon, axis=-1)
    return ok & valid_in
