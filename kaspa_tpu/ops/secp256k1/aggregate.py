"""Aggregated random-linear-combination Schnorr verification kernel.

Instead of B independent dual-scalar ladders (points.dual_scalar_mul_base,
~2 scalar muls x 64 windows per signature), the aggregate lane checks ONE
combined equation over the whole batch.  With per-signature random weights
a_i (host-derived, ChaCha-seeded from the batch transcript — see
crypto/secp.py), each BIP340 equation R_i = s_i*G - e_i*P_i folds into

    T  =  u*G  +  sum_i c_i*(-P_i)  +  sum_i a_i*(-R_i)          (== O)

where u = sum_i a_i*s_i mod n is a single host-side scalar, c_i = a_i*e_i
mod n, and R_i = lift_x(r_i) (even y).  All B signatures are valid iff T
is the identity; random 128-bit weights bound the probability that a set
of invalid signatures conspires to cancel at 2^-128 (the FPGA
ECDSA-engine batching trick, mapped onto this repo's windowed ladder).

Multi-scalar shape (Strauss with a shared doubling chain): every lane
gathers its window summand from its own 16-entry table
(points._build_p_table — entry 0 is the true identity, so a zero digit
contributes nothing), the per-window summands tree-reduce across the
batch axis with the *complete* addition law, and one final 64-window
Horner pass (4 doublings + one add per window, plus the mixed-affine u*G
add) collapses the window sums.  Field-mul count per lane: 2 tables
(~336M) + the a/c gathers' adds (~1.5 adds/window amortized) versus the
ladder's ~43M/window — the doubling chain, previously paid per lane, is
paid once per *batch*.

The weights are 128-bit, so their 4-bit MSB-first digit columns 0..31 are
statically zero: the R-term gathers and adds run only for windows 32..63
(`A_WINDOWS`), saving half the R-side work.

Sharding: `aggregate_partials_kernel` maps cleanly onto the mesh — each
shard reduces its lanes to one [64] window-sum vector, and the [n, 64]
stack reduces + Horner-finishes in `aggregate_reduce_finish_kernel`
(tiny, runs unsharded).  `ops/mesh.py:dispatch_aggregate_partials` owns
the shard_map plumbing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from kaspa_tpu.ops import bigint as bi
from kaspa_tpu.ops.secp256k1 import points as pt

FP = bi.FP

# weight scalars are 128-bit -> only the low 32 of the 64 MSB-first 4-bit
# windows can be non-zero; the host ships a_digits already sliced to these
A_WINDOWS = pt.N_WINDOWS // 2  # windows 32..63


def _gather_window_points(tab, digits):
    """Per-lane per-window table select.

    tab: (xs, ys, zs) each [B, 16, W];  digits: [B, K] int32 in [0, 16).
    Returns (X, Y, Z) each [B, K, W] — lane b's window w summand.  Digit 0
    selects the table's true-identity entry, so zero-weight (pad/invalid)
    lanes contribute nothing anywhere.
    """
    idx = digits[..., None, None]  # [B, K, 1, 1] -> broadcasts over W
    return tuple(
        jnp.take_along_axis(a[:, None, :, :], idx, axis=-2)[..., 0, :] for a in tab
    )


def _tree_reduce_lanes(p):
    """Sum a [B, K, W] point batch over the lane axis with the complete
    addition law: log2(B) levels of halved point_adds -> [K, W].  Odd
    levels pad with the identity (complete addition absorbs it).  The
    graph holds one point_add per level, so keep B small here (shard
    stacks, scan-group remainders) — big lane axes go through
    _scan_reduce_lanes."""
    x, y, z = p
    while x.shape[0] > 1:
        if x.shape[0] % 2:
            ident = pt.point_identity(x.shape[1:-1])
            x = jnp.concatenate([x, ident[0][None]], axis=0)
            y = jnp.concatenate([y, ident[1][None]], axis=0)
            z = jnp.concatenate([z, ident[2][None]], axis=0)
        h = x.shape[0] // 2
        x, y, z = pt.point_add((x[:h], y[:h], z[:h]), (x[h:], y[h:], z[h:]))
    return x[0], y[0], z[0]


# lane-fold accumulator width: wide enough to keep the per-step point_add
# vectorized (8 lanes x 64 windows = 512 parallel adds), short enough that
# the trailing unrolled tree is 3 levels
_SCAN_GROUP = 8


def _scan_reduce_lanes(p):
    """Sum a [B, K, W] point batch over the lane axis -> [K, W], with a
    graph whose size does NOT grow with B.

    A fully unrolled binary tree puts log2(B) distinct point_adds in the
    jaxpr and XLA:CPU compile time blows up superlinearly in the bucket
    (measured: ~45s at B=8 -> ~4m50s at B=16).  Instead the lanes fold
    into _SCAN_GROUP parallel accumulators through ONE lax.scan'd
    complete point_add, then a 3-level tree collapses the group.  Runtime
    work is B + G - 2 lane-adds vs the tree's B - 1 — noise — and the
    compile cost is flat across buckets.
    """
    x, y, z = p
    b = x.shape[0]
    g = min(b, _SCAN_GROUP)
    if b % g:  # pad to a whole number of scan steps; identity lanes absorb
        pad = g - b % g
        ident = pt.point_identity((pad,) + x.shape[1:-1])
        x = jnp.concatenate([x, ident[0]], axis=0)
        y = jnp.concatenate([y, ident[1]], axis=0)
        z = jnp.concatenate([z, ident[2]], axis=0)
    xs = tuple(a.reshape(-1, g, *a.shape[1:]) for a in (x, y, z))
    acc = pt.point_identity((g,) + x.shape[1:-1], like=x)

    def step(acc, lanes):
        return pt.point_add(acc, lanes), None

    acc, _ = jax.lax.scan(step, acc, xs)
    return _tree_reduce_lanes(acc)


@jax.jit
def aggregate_partials_kernel(pxn, pyn, rxn, ryn, c_digits, a_digits):
    """Per-window multi-scalar partial sums for one (shard's) lane slice.

    pxn/pyn: [B, W] limbs of -P_i (negated lifted pubkey);
    rxn/ryn: [B, W] limbs of -R_i (negated lift_x(r_i));
    c_digits: [B, 64] digits of c_i = a_i*e_i mod n;
    a_digits: [B, 32] digits of a_i (windows 32..63 only — see A_WINDOWS).
    Invalid/pad lanes carry zero digits (their garbage tables are never
    selected).  Returns (Sx, Sy, Sz) each [64, W]: window w's summand sum.
    """
    ptab = pt._build_p_table(pxn, pyn)
    rtab = pt._build_p_table(rxn, ryn)
    cx, cy, cz = _gather_window_points(ptab, c_digits)  # [B, 64, W]
    ar = _gather_window_points(rtab, a_digits)  # [B, 32, W]
    lo = (cx[:, :A_WINDOWS], cy[:, :A_WINDOWS], cz[:, :A_WINDOWS])
    hi = pt.point_add((cx[:, A_WINDOWS:], cy[:, A_WINDOWS:], cz[:, A_WINDOWS:]), ar)
    per_lane = tuple(jnp.concatenate([a, b], axis=1) for a, b in zip(lo, hi))
    return _scan_reduce_lanes(per_lane)


@jax.jit
def aggregate_reduce_finish_kernel(sx, sy, sz, u_digits):
    """Combine shard partials and run the shared Horner chain.

    sx/sy/sz: [n, 64, W] stacked per-shard window sums (n == 1 off-mesh);
    u_digits: [64] int32 digits of u = sum a_i*s_i mod n.  Returns a
    scalar bool: True iff  u*G + sum_w 16^(63-w) * S_w  is the identity —
    i.e. every aggregated signature equation holds.
    """
    s = _tree_reduce_lanes((sx, sy, sz))  # [64, W] triple
    sxw, syw, szw = s
    gtx = jnp.asarray(pt._GTAB_X)
    gty = jnp.asarray(pt._GTAB_Y)
    r0 = pt.point_identity(())

    def body(w, r):
        for _ in range(pt.WINDOW):
            r = pt.point_double(r)
        gd = jax.lax.dynamic_slice_in_dim(u_digits, w, 1, axis=-1)[..., 0]
        ra = pt.point_add_mixed(r, (gtx[gd], gty[gd]))
        sel = (gd == 0)[..., None]
        r = tuple(jnp.where(sel, a, b) for a, b in zip(r, ra))
        sw = tuple(
            jax.lax.dynamic_slice_in_dim(a, w, 1, axis=0)[0] for a in (sxw, syw, szw)
        )
        return pt.point_add(r, sw)

    t = jax.lax.fori_loop(0, pt.N_WINDOWS, body, r0)
    # identity <=> Z == 0 mod p; no affine lift needed for the yes/no check
    return bi.is_zero(FP, t[2])


def aggregate_check(pxn, pyn, rxn, ryn, c_digits, a_digits, u_digits) -> bool:
    """Single-dispatch aggregate check for one device batch (mesh-aware).

    The mesh path ships the partials kernel through shard_map (each shard
    reduces its lane slice) and finishes on the [n, 64] stack; off-mesh the
    same two kernels run back to back with n == 1, so masks and compile
    shapes stay uniform across layouts.
    """
    from kaspa_tpu.ops import mesh

    if mesh.active_size() > 1:
        sx, sy, sz = mesh.dispatch_aggregate_partials(
            pxn, pyn, rxn, ryn, c_digits, a_digits
        )
    else:
        sx, sy, sz = aggregate_partials_kernel(pxn, pyn, rxn, ryn, c_digits, a_digits)
        sx, sy, sz = sx[None], sy[None], sz[None]
    return bool(aggregate_reduce_finish_kernel(sx, sy, sz, u_digits))
