"""Batched complete projective point arithmetic on secp256k1 (a=0, b=7).

Points are pytrees ``(X, Y, Z)`` of int32 lazy limbs [..., 16] (see
ops/bigint.py) in homogeneous projective coordinates; the identity is
(0 : 1 : 0) and needs no flag.  Formulas are the *complete* addition laws of
Renes–Costello–Batina 2016 (algorithms 7/8/9 for a=0), valid for ALL input
pairs on a prime-order curve — including P == Q, P == -Q and the identity.
Completeness matters doubly here: consensus demands exactness under
adversarial inputs (a wrong validity bit is a chain split), and branch-free
total functions are exactly what XLA wants.

Replaces the EC internals of libsecp256k1 used by the reference's signature
checks (crypto/txscript/src/lib.rs:885-935).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from kaspa_tpu.ops import bigint as bi

FP = bi.FP
B3 = 21  # 3*b for y^2 = x^3 + 7

GX = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
GY = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8
G_AFFINE = (GX, GY)

WINDOW = 4
N_WINDOWS = 256 // WINDOW  # 64 windows of 4 bits, MSB-first


def point_identity(shape_prefix, like=None):
    """(0 : 1 : 0) broadcast to ``shape_prefix``; ``like`` is the operand a
    loop carry seeded with it will be combined with (bigint.like_varying)."""
    zero = jnp.zeros((*shape_prefix, FP.W), dtype=jnp.int32)
    one = jnp.broadcast_to(jnp.asarray(FP.one), zero.shape).astype(jnp.int32)
    if like is not None:
        zero, one = bi.like_varying(zero, like), bi.like_varying(one, like)
    return (zero, one, zero)


def point_double(p):
    """RCB alg. 9 (a=0): 3M + 2S + 1*b3; complete."""
    x, y, z = p
    t0 = bi.sqr(FP, y)
    z3 = bi.mul_small(FP, t0, 8)
    t1 = bi.mul(FP, y, z)
    t2 = bi.mul_small(FP, bi.sqr(FP, z), B3)
    x3 = bi.mul(FP, t2, z3)
    y3 = bi.add(FP, t0, t2)
    z3 = bi.mul(FP, t1, z3)
    t0 = bi.sub(FP, t0, bi.mul_small(FP, t2, 3))
    y3 = bi.add(FP, x3, bi.mul(FP, t0, y3))
    x3 = bi.mul_small(FP, bi.mul(FP, t0, bi.mul(FP, x, y)), 2)
    return (x3, y3, z3)


def point_add(p, q):
    """RCB alg. 7 (a=0): 12M + 2*b3; complete for all inputs."""
    x1, y1, z1 = p
    x2, y2, z2 = q
    t0 = bi.mul(FP, x1, x2)
    t1 = bi.mul(FP, y1, y2)
    t2 = bi.mul(FP, z1, z2)
    t3 = bi.mul(FP, bi.add(FP, x1, y1), bi.add(FP, x2, y2))
    t3 = bi.sub(FP, t3, bi.add(FP, t0, t1))
    t4 = bi.mul(FP, bi.add(FP, y1, z1), bi.add(FP, y2, z2))
    t4 = bi.sub(FP, t4, bi.add(FP, t1, t2))
    x3 = bi.mul(FP, bi.add(FP, x1, z1), bi.add(FP, x2, z2))
    y3 = bi.sub(FP, x3, bi.add(FP, t0, t2))
    t0 = bi.mul_small(FP, t0, 3)
    t2 = bi.mul_small(FP, t2, B3)
    z3 = bi.add(FP, t1, t2)
    t1 = bi.sub(FP, t1, t2)
    y3 = bi.mul_small(FP, y3, B3)
    x3_out = bi.sub(FP, bi.mul(FP, t3, t1), bi.mul(FP, t4, y3))
    y3_out = bi.add(FP, bi.mul(FP, t1, z3), bi.mul(FP, y3, t0))
    z3_out = bi.add(FP, bi.mul(FP, z3, t4), bi.mul(FP, t0, t3))
    return (x3_out, y3_out, z3_out)


def point_add_mixed(p, q_affine):
    """RCB alg. 8 (a=0, Z2=1): 11M + 2*b3; complete except Q == identity
    (unrepresentable in affine — callers select around digit==0)."""
    x1, y1, z1 = p
    x2, y2 = q_affine
    t0 = bi.mul(FP, x1, x2)
    t1 = bi.mul(FP, y1, y2)
    t3 = bi.mul(FP, bi.add(FP, x2, y2), bi.add(FP, x1, y1))
    t3 = bi.sub(FP, t3, bi.add(FP, t0, t1))
    t4 = bi.add(FP, bi.mul(FP, y2, z1), y1)
    y3 = bi.add(FP, bi.mul(FP, x2, z1), x1)
    t0 = bi.mul_small(FP, t0, 3)
    t2 = bi.mul_small(FP, z1, B3)
    z3 = bi.add(FP, t1, t2)
    t1 = bi.sub(FP, t1, t2)
    y3 = bi.mul_small(FP, y3, B3)
    x3_out = bi.sub(FP, bi.mul(FP, t3, t1), bi.mul(FP, t4, y3))
    y3_out = bi.add(FP, bi.mul(FP, t1, z3), bi.mul(FP, y3, t0))
    z3_out = bi.add(FP, bi.mul(FP, z3, t4), bi.mul(FP, t0, t3))
    return (x3_out, y3_out, z3_out)


def _g_multiples_table():
    """Host-precomputed affine multiples 1..15 of G (python ints).

    Entry 0 is a placeholder (G) — the ladder selects around digit == 0.
    """
    from kaspa_tpu.crypto import eclib

    pts = []
    acc = None
    for _ in range(15):
        acc = eclib.point_add(acc, (GX, GY))
        pts.append(acc)
    pts = [pts[0]] + pts  # index 0 placeholder
    xs = bi.ints_to_limbs([q[0] for q in pts], FP.W)
    ys = bi.ints_to_limbs([q[1] for q in pts], FP.W)
    return xs, ys


_GTAB_X, _GTAB_Y = _g_multiples_table()


def _build_p_table(px, py):
    """Per-batch projective multiples 0..15 of P. Returns [B, 16, W] arrays.

    Entry 0 is the true identity (0:1:0) — complete addition handles it.
    Entries 2..15 come from one lax.scan'd point_add rather than a fully
    unrolled chain: 14 adds in the jaxpr made XLA:CPU compile time grow
    superlinearly with the op count (tens of seconds per bucket), while
    the rolled form traces one add and compiles flat.  Identical math,
    identical limbs out."""
    one = bi.like_varying(jnp.broadcast_to(jnp.asarray(FP.one), px.shape).astype(jnp.int32), px)
    p1 = (px, py, one)
    ident = point_identity(px.shape[:-1])

    def step(acc, _):
        nxt = point_add(acc, p1)
        return nxt, nxt

    _, rest = jax.lax.scan(step, p1, None, length=14)  # 2P..15P, [14, B, W]
    cols = []
    for i in range(3):
        head = jnp.stack([ident[i], p1[i]], axis=-2)  # [B, 2, W]
        tail = jnp.moveaxis(rest[i], 0, -2)  # [B, 14, W]
        cols.append(jnp.concatenate([head, tail], axis=-2))  # [B, 16, W]
    return tuple(cols)


def _gather_tab(tab, digit):
    """Select table entry per batch element. digit: [B] int32 in [0,16)."""
    idx = digit[..., None, None]
    return tuple(jnp.take_along_axis(a, idx, axis=-2)[..., 0, :] for a in tab)


def dual_scalar_mul_base(px, py, g_digits, p_digits):
    """R = a*G + b*P with 4-bit MSB-first window digits of a and b.

    px, py: [B, W] limbs of P (affine, on-curve — host-validated);
    g_digits, p_digits: [B, 64] int32.  Shamir's trick: one shared doubling
    chain, two table additions per window (G mixed-affine, P projective).
    Returns projective (X, Y, Z); identity <=> Z == 0 (mod p).
    """
    ptab = _build_p_table(px, py)
    gtx = jnp.asarray(_GTAB_X)
    gty = jnp.asarray(_GTAB_Y)

    r0 = point_identity(px.shape[:-1], like=px)

    def body(w, r):
        for _ in range(WINDOW):
            r = point_double(r)
        gd = jax.lax.dynamic_slice_in_dim(g_digits, w, 1, axis=-1)[..., 0]
        pd = jax.lax.dynamic_slice_in_dim(p_digits, w, 1, axis=-1)[..., 0]
        ra = point_add_mixed(r, (gtx[gd], gty[gd]))
        sel = (gd == 0)[..., None]
        r = tuple(jnp.where(sel, a, b) for a, b in zip(r, ra))
        r = point_add(r, _gather_tab(ptab, pd))
        return r

    return jax.lax.fori_loop(0, N_WINDOWS, body, r0)


def to_affine(p):
    """Projective -> canonical affine limbs (x, y, is_identity)."""
    x, y, z = p
    zi = bi.inv(FP, z)
    xa = bi.canon(FP, bi.mul(FP, x, zi))
    ya = bi.canon(FP, bi.mul(FP, y, zi))
    inf = bi.is_zero(FP, z)
    return xa, ya, inf


def to_affine_batch(p):
    """Projective -> canonical affine limbs for a batch (leading axis).

    Same contract as ``to_affine``, but all Z inversions share one
    batch-affine Montgomery inversion (bigint.inv_batch): ~3(B-1) modular
    multiplies plus a single Fermat ladder instead of B ladders — the
    dominant per-element saving in the portable XLA verify lane.  Identity
    lanes (Z ≡ 0) keep zi == 0, matching ``inv``'s inv(0) == 0, so the
    returned (x, y) are (0, 0) there exactly as in the per-lane path.
    """
    x, y, z = p
    inf = bi.is_zero(FP, z)
    zi = bi.inv_batch(FP, z, zero_mask=inf)
    xa = bi.canon(FP, bi.mul(FP, x, zi))
    ya = bi.canon(FP, bi.mul(FP, y, zi))
    return xa, ya, inf


def scalar_digits_msb(k: int) -> np.ndarray:
    """Host: scalar -> 64 MSB-first 4-bit window digits."""
    return np.array([(k >> (256 - WINDOW * (i + 1))) & 0xF for i in range(N_WINDOWS)], dtype=np.int32)
