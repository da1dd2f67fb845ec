"""Jitted batched Schnorr/ECDSA verification kernels (device side).

The host (kaspa_tpu/crypto/secp.py) parses/validates encodings, lifts
pubkeys to affine coordinates and computes challenge scalars, and hands the
batch over as byte columns; the device does the heavy dual-scalar ladder and
the final affine checks, returning a validity bitmask — the layout
prescribed by the north star (BASELINE.json): triples in, bitmask out.

`_verify` marshals for the lane it takes.  The fused Pallas ladder (a TPU,
mesh 1) gets the columns as one packed byte array and lays limbs and window
digits out on the device (ladder_pallas.pack_lanes / unpack_lanes).  The XLA
ladder (CPU, KASPA_TPU_NO_PALLAS) and the mesh ladder get `marshal_limbs`:
[B, 16] 2**16-radix limbs and [B, 64] 4-bit window digits built on the host.
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
    for name, fn in (("schnorr", schnorr_verify_kernel), ("ecdsa", ecdsa_verify_kernel)):
        try:
            out[name] = int(fn._cache_size())
        except Exception:  # noqa: BLE001 - jax internals may shift
            pass
    # the fused ladder builds one Mosaic call per (padded width, kind); the
    # module only loads on a TPU backend
    lp = sys.modules.get("kaspa_tpu.ops.secp256k1.ladder_pallas")
    if lp is not None:
        out["pallas"] = lp._build_call.cache_info().currsize
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


def _be32_to_limbs(col, b):
    """[N x 32-byte big-endian] -> [bucket, 16] int32 LE 16-bit limbs (vectorised)."""
    out = np.zeros((b, FP.W), np.int32)
    if col:
        arr = np.frombuffer(b"".join(col), dtype=np.uint8).reshape(len(col), 32)
        out[: len(col)] = arr[:, ::-1].copy().view("<u2").astype(np.int32)
    return out


def _scalars_to_digits(ks, b: int) -> np.ndarray:
    """Host: scalars -> [b, 64] MSB-first 4-bit window digits (padded).

    Elements are python ints or already-canonical 32-byte big-endian
    strings (the schnorr s column ships ``sig[32:]`` straight through,
    skipping the int round trip entirely); everything downstream of the
    single join is np.frombuffer bulk work.  Measured at B=8..16384
    against a log-depth shift-or bigint tree and a uint64-decompose numpy
    path: the one-join form is ~2-3x faster than either (CPython's
    to_bytes C path wins), and dropping the old loop's per-item ``int()``
    coercion is another 1.4-1.7x.
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


def marshal_limbs(px, py, rc, k1, k2, valid_in) -> tuple:
    """Host marshal of the XLA and mesh ladders: byte columns and scalars of
    the jobs -> the kernels' six arrays at the width of ``valid_in``."""
    b = len(valid_in)
    return (
        _be32_to_limbs(px, b),
        _be32_to_limbs(py, b),
        _be32_to_limbs(rc, b),
        _scalars_to_digits(k1, b),
        _scalars_to_digits(k2, b),
        valid_in,
    )


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
# 1 a call on the fused Pallas ladder (the packed lanes), 6 on the XLA and
# mesh ladders: over secp_device_dispatches it says which marshal engaged
_DEVICE_UPLOADS = REGISTRY.counter(
    "secp_device_uploads", help="host arrays handed to the device by verify calls it answered"
)


def _verify(kind: str, px, py, rc, k1, k2, valid_in) -> np.ndarray:
    """Backend-dispatching batched verify shared by both signature kinds.

    px/py/rc: the jobs' 32-byte big-endian columns (one string a job); k1/k2:
    one scalar a job, already reduced mod n (s/e for Schnorr, u1/u2 for
    ECDSA; python ints or canonical 32-byte strings); valid_in: [B] bool at
    the bucket width B >= jobs.  -> [B] bool.
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
    b = len(valid_in)
    if n_mesh == 1 and _use_pallas():
        from kaspa_tpu.ops.secp256k1.ladder_pallas import launched_lanes, verify_batch_pallas

        kernel = f"{kind}_pallas"
        lanes = launched_lanes(b)
        with trace.span("secp.device_dispatch", kernel=kernel, batch=b):
            mask, uploads = verify_batch_pallas(px, py, rc, k1, k2, valid_in, ecdsa=kind == "ecdsa")
    else:
        # host marshal vs device dispatch split: when throughput collapses,
        # this localizes the stall to python packing or the XLA round trip
        with trace.span("secp.host_marshal", kernel=kind, batch=b, lanes=b):
            args = marshal_limbs(px, py, rc, k1, k2, valid_in)
        uploads = len(args)
        if n_mesh > 1:
            # mesh > 1 rides the portable XLA formulation sharded over the
            # device mesh (the fused Mosaic ladder stays the single-chip path)
            kernel = f"{kind}_mesh"
            lanes = mesh.padded_lanes(b)
            with trace.span("secp.device_dispatch", kernel=kernel, batch=b, mesh=n_mesh):
                mask = mesh.dispatch_verify(kind, *args)
        else:
            kernel = kind
            lanes = b
            xla_kernel = schnorr_verify_kernel if kind == "schnorr" else ecdsa_verify_kernel
            with trace.span("secp.device_dispatch", kernel=kernel, batch=b):
                with trace.span("secp.device_call", kernel=kernel, lanes=lanes, bytes=sum(a.nbytes for a in args)):
                    out = xla_kernel(*args)
                    out.copy_to_host_async()  # queued behind the kernel, as np.asarray alone would
                    jax.block_until_ready(out)
                with trace.span("secp.readback", kernel=kernel):
                    mask = np.asarray(out)
    _DEVICE_DISPATCHES.inc(kernel)
    _DEVICE_BUCKETS.inc(str(b))
    _DEVICE_LANES.inc(lanes)
    _DEVICE_UPLOADS.inc(uploads)
    return mask


def schnorr_verify(px, py, r_canon, s_scalars, e_scalars, valid_in) -> np.ndarray:
    """Batched Schnorr verify (see _verify): byte columns, s/e scalars, r canonical mod p."""
    return _verify("schnorr", px, py, r_canon, s_scalars, e_scalars, valid_in)


def ecdsa_verify(px, py, r_n_canon, u1_scalars, u2_scalars, valid_in) -> np.ndarray:
    """Batched ECDSA verify (see _verify): byte columns, u1/u2 scalars, r canonical mod n."""
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
