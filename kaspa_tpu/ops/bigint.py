"""Width-generic fixed-size big-integer modular arithmetic on int32 limbs.

This is the TPU-native replacement for the reference's CPU big-int stacks:
`math/src/uint.rs` (Uint256/Uint3072 limbed ints) and the field arithmetic
inside libsecp256k1 (C) / `crypto/muhash/src/u3072.rs`.  Design notes:

- Values are arrays of shape ``[..., W]`` (int32), little-endian limbs in a
  2**16 radix.  Limb values are *lazy*: any int32 in ``(-2**18, 2**18)`` is
  legal between operations; the represented integer is ``sum(l[i] << 16*i)``.
  Signed lazy limbs make subtraction carry-free and avoid sequential borrow
  ripple on the VPU (there is no widening 32x32 multiply on TPU, so the radix
  is chosen such that all partial products and column sums stay inside int32).
- Multiplication splits limbs into 8-bit half-limbs so that schoolbook
  partial products (<= 2**20) summed over a column (<= 2*W terms) stay below
  2**31 for every width used here (W=16 for secp256k1, W=192 for muhash).
- All moduli are of the special form ``m = 2**(16*W) - c`` with small-ish
  ``c`` (secp256k1 p and n, muhash's 2**3072 - 1103717), so reduction is a
  fold: ``hi * c + lo``, iterated until the value fits W limbs.
- Everything is branch-free / fixed-shape: jit- and vmap-safe, identical
  semantics on CPU and TPU.

Canonicalisation (exact carry propagation + range reduction into [0, m)) is
only needed at equality tests and outputs; it uses short unrolled scans.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

RADIX_BITS = 16
RADIX = 1 << RADIX_BITS
RADIX_MASK = RADIX - 1


def int_to_limbs(v: int, w: int) -> np.ndarray:
    """Host: python int -> W int32 limbs (little-endian, 16-bit radix)."""
    if v < 0:
        raise ValueError("int_to_limbs expects non-negative")
    out = np.zeros(w, dtype=np.int32)
    for i in range(w):
        out[i] = v & RADIX_MASK
        v >>= RADIX_BITS
    if v:
        raise ValueError("value does not fit in width")
    return out

def ints_to_limbs(vs, w: int) -> np.ndarray:
    """Host: iterable of python ints -> [N, W] int32 limb array."""
    rows = [int_to_limbs(v, w) for v in vs]
    if not rows:
        return np.zeros((0, w), dtype=np.int32)
    return np.stack(rows)

def limbs_to_int(arr) -> int:
    """Host: limb array (possibly lazy/signed) -> python int."""
    arr = np.asarray(arr)
    v = 0
    for i in range(arr.shape[-1]):
        v += int(arr[..., i]) << (RADIX_BITS * i)
    return v

def limbs_to_ints(arr):
    """Host: [N, W] limb array -> list of python ints."""
    arr = np.asarray(arr)
    return [limbs_to_int(arr[i]) for i in range(arr.shape[0])]


class FieldCtx:
    """Static context for a special-form prime field m = 2**(16W) - c."""

    def __init__(self, name: str, bits: int, modulus: int):
        assert bits % RADIX_BITS == 0
        self.name = name
        self.bits = bits
        self.W = bits // RADIX_BITS
        self.modulus = modulus
        self.c = (1 << bits) - modulus
        assert 0 < self.c < (1 << (bits - RADIX_BITS)), "modulus not special-form"
        # 8-bit digits of c (little-endian), python ints
        c8 = []
        c = self.c
        while c:
            c8.append(c & 0xFF)
            c >>= 8
        self.c8 = tuple(c8)
        self.c_limbs16 = int_to_limbs(self.c, (len(c8) + 1) // 2)
        self.m_limbs = int_to_limbs(modulus, self.W)
        self.zero = np.zeros(self.W, dtype=np.int32)
        self.one = int_to_limbs(1, self.W)

    # like _const below: a first access inside shard_map's check_rep rewrite
    # trace yields a RewriteTracer, which must not be cached on the ctx

    @property
    def m_limbs_dev(self):
        if not hasattr(self, "_m_limbs_dev"):
            with jax.ensure_compile_time_eval():
                out = jnp.asarray(self.m_limbs)
            if isinstance(out, jax.core.Tracer):
                return out
            self._m_limbs_dev = out
        return self._m_limbs_dev

    @property
    def c_limbs16_dev(self):
        if not hasattr(self, "_c_limbs16_dev"):
            with jax.ensure_compile_time_eval():
                out = jnp.asarray(self.c_limbs16)
            if isinstance(out, jax.core.Tracer):
                return out
            self._c_limbs16_dev = out
        return self._c_limbs16_dev

    def __repr__(self):
        return f"FieldCtx({self.name}, {self.bits}b)"


# ---------------------------------------------------------------------------
# lazy-limb primitives
# ---------------------------------------------------------------------------

def _split8(x):
    """[..., K] limbs -> [..., 2K] 8-bit half-limbs (even in [0,256), odd signed)."""
    lo = x & 0xFF
    hi = x >> 8  # arithmetic shift: value-preserving for signed lazy limbs
    return jnp.stack([lo, hi], axis=-1).reshape(*x.shape[:-1], 2 * x.shape[-1])


def _carry_round(cols):
    """One vectorised carry round in the 2**16 radix; widens by one limb."""
    limb = cols & RADIX_MASK
    carry = cols >> RADIX_BITS
    out = jnp.concatenate([limb, jnp.zeros_like(limb[..., :1])], axis=-1)
    return out.at[..., 1:].add(carry)


def _carry_rounds(cols, n=2):
    for _ in range(n):
        cols = _carry_round(cols)
    return cols


@functools.lru_cache(maxsize=None)
def _conv_matrix_np(k: int):
    """[k*k, 2k] one-hot anti-diagonal collector: (i,j) -> column i+j."""
    m = np.zeros((k * k, 2 * k), np.int32)
    for i in range(k):
        for j in range(k):
            m[i * k + j, i + j] = 1
    return m


_CONST_CACHE: dict = {}


def _const(arr_factory_key):
    """Memoized device constants: avoids re-running numpy->jax conversion for
    the large one-hot matrices on every traced multiply (a dominant share of
    trace/lowering time for fresh batch shapes).

    ensure_compile_time_eval makes the conversion concrete when the first
    call happens inside a plain jit trace, but inside shard_map's check_rep
    rewrite interpreter it still yields a RewriteTracer — memoizing that
    poisons every later trace in the process, so tracers are returned
    uncached and only concrete arrays enter the cache."""
    hit = _CONST_CACHE.get(arr_factory_key)
    if hit is not None:
        return hit
    kind, arg = arr_factory_key
    with jax.ensure_compile_time_eval():
        if kind == "conv":
            out = jnp.asarray(_conv_matrix_np(arg))
        elif kind == "collect":
            out = jnp.asarray(_block_collect_np(arg))
        elif kind == "cmat":
            c8, k = arg
            out = jnp.asarray(_c_matrix_np(c8, k))
        else:
            raise KeyError(kind)
    if not isinstance(out, jax.core.Tracer):
        _CONST_CACHE[arr_factory_key] = out
    return out


@functools.lru_cache(maxsize=None)
def _block_collect_np(nb: int):
    """[nb*nb, 2nb-1] one-hot: block pair (i,j) -> result block i+j."""
    m = np.zeros((nb * nb, 2 * nb - 1), np.int32)
    for i in range(nb):
        for j in range(nb):
            m[i * nb + j, i + j] = 1
    return m


_BLK = 32  # 8-bit limbs per block in the blocked schoolbook


def _poly_mul8(a8, b8):
    """Schoolbook column products of two 8-bit-split operands.

    [..., K] x [..., K] -> [..., 2K (+pad)] columns in the 2**8 radix.
    Column magnitudes < 2K * 2**20 < 2**31 for K <= 512.

    Small widths contract the outer-product against a one-hot matrix in a
    single dot (XLA fuses the product into the matmul operand, minimising
    HBM round-trips and HLO size).  Large widths (muhash U3072) use a
    blocked schoolbook: all nb*nb block pairs go through the same 32-wide
    contraction in one shot, then a second one-hot dot collects block pairs
    into result blocks — two fat ops instead of K dynamic-slice updates.
    """
    k = a8.shape[-1]
    if k <= 64:
        m = _const(("conv", k))
        p = (a8[..., :, None] * b8[..., None, :]).reshape(*a8.shape[:-1], k * k)
        return jax.lax.dot_general(
            p, m, (((p.ndim - 1,), (0,)), ((), ())), preferred_element_type=jnp.int32
        )
    assert k % _BLK == 0, "large operands must be a multiple of the block size"
    nb = k // _BLK
    lead = a8.shape[:-1]
    ab = a8.reshape(*lead, nb, _BLK)
    bb = b8.reshape(*lead, nb, _BLK)
    # all block-pair products through one 32-wide contraction
    m = _const(("conv", _BLK))  # [blk*blk, 2blk]
    p = (ab[..., :, None, :, None] * bb[..., None, :, None, :]).reshape(*lead, nb * nb, _BLK * _BLK)
    c = jax.lax.dot_general(p, m, (((p.ndim - 1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    # collect pair results into blocks k = i + j  (sums of <= nb products:
    # per-column bound nb * blk * 2**20 <= 2**31 for nb <= 16, blk = 32
    # ... tighter: blk*2**20 per pair, nb pairs -> nb*2**25; nb<=12 ok)
    coll = _const(("collect", nb))
    d = jax.lax.dot_general(
        c, coll, (((c.ndim - 2,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )  # [..., 2blk, 2nb-1]
    d = jnp.moveaxis(d, -1, -2)  # [..., 2nb-1, 2blk]
    # overlap-add the two halves of each block result (phase offset blk)
    out = jnp.zeros((*lead, 2 * nb + 1, _BLK), dtype=jnp.int32)
    out = out.at[..., : 2 * nb - 1, :].add(d[..., :_BLK])
    out = out.at[..., 1 : 2 * nb, :].add(d[..., _BLK:])
    return out.reshape(*lead, (2 * nb + 1) * _BLK)


def _pair_columns(cols8):
    """Columns in 2**8 radix [..., 2K] -> columns in 2**16 radix [..., K+1]."""
    if cols8.shape[-1] % 2:
        cols8 = jnp.concatenate([cols8, jnp.zeros_like(cols8[..., :1])], axis=-1)
    even = cols8[..., 0::2]
    odd = cols8[..., 1::2]
    out = even + ((odd & 0xFF) << 8)
    hi = odd >> 8
    out = out.at[..., 1:].add(hi[..., :-1])
    return jnp.concatenate([out, hi[..., -1:]], axis=-1)


@functools.lru_cache(maxsize=None)
def _c_matrix_np(c8: tuple, k: int):
    """[k, k + len(c8)] banded matrix: multiply an 8-bit-split value by c."""
    m = np.zeros((k, k + len(c8)), np.int32)
    for j, d in enumerate(c8):
        for i in range(k):
            m[i, i + j] = d
    return m


def _mul_by_c(ctx: FieldCtx, x):
    """x * c where c = 2**(16W) - m, via 8-bit digits of c. Input any width."""
    x8 = _split8(x)
    k = x8.shape[-1]
    m = _const(("cmat", (ctx.c8, k)))
    out = jax.lax.dot_general(
        x8, m, (((x8.ndim - 1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    return _carry_rounds(_pair_columns(out), 2)


def _fold(ctx: FieldCtx, x):
    """Reduce an arbitrary-width lazy value into width W (value mod m preserved)."""
    w = ctx.W
    while x.shape[-1] > w:
        lo, hi = x[..., :w], x[..., w:]
        prod = _mul_by_c(ctx, hi)  # hi * c  == hi * 2**(16W) (mod m)
        if prod.shape[-1] <= w:
            x = lo.at[..., : prod.shape[-1]].add(prod) if prod.shape[-1] < w else lo + prod
        else:
            x = prod.at[..., :w].add(lo)
    return x


def tighten(ctx: FieldCtx, x):
    """Re-establish the lazy-limb bound (|limb| < ~2**17) after adds."""
    return _fold(ctx, _carry_rounds(x, 2))


# ---------------------------------------------------------------------------
# public modular ops (all shapes [..., W] int32, lazy limbs)
# ---------------------------------------------------------------------------

def mul(ctx: FieldCtx, a, b):
    cols = _poly_mul8(_split8(a), _split8(b))
    x = _carry_rounds(_pair_columns(cols), 2)
    x = _fold(ctx, x)
    return _fold(ctx, _carry_rounds(x, 2))

def sqr(ctx: FieldCtx, a):
    return mul(ctx, a, a)

def add(ctx: FieldCtx, a, b):
    return tighten(ctx, a + b)

def sub(ctx: FieldCtx, a, b):
    return tighten(ctx, a - b)

def mul_small(ctx: FieldCtx, a, k: int):
    assert -(1 << 12) < k < (1 << 12)
    return tighten(ctx, a * k)

def neg(ctx: FieldCtx, a):
    return tighten(ctx, -a)


def _scan_carry(x):
    """Exact sequential carry: [..., W] lazy -> ([..., W] canonical limbs, top).

    value == sum(base[i] << 16i) + top << 16W, with base limbs in [0, 2**16).
    Unrolled: W is small (16) or used rarely (192, finalize-only).
    """
    carry = jnp.zeros(x.shape[:-1], dtype=jnp.int32)
    outs = []
    for i in range(x.shape[-1]):
        v = x[..., i] + carry
        outs.append(v & RADIX_MASK)
        carry = v >> RADIX_BITS
    return jnp.stack(outs, axis=-1), carry


def _cond_sub_m(ctx: FieldCtx, x):
    """x in [0, 2**16W) canonical -> subtract m once if x >= m."""
    m = ctx.m_limbs_dev
    d, top = _scan_carry(x - m)
    take = top >= 0  # no borrow => x >= m
    return jnp.where(take[..., None], d, x)


def canon(ctx: FieldCtx, x):
    """Full canonicalisation into [0, m) with limbs in [0, 2**16).

    Repeatedly substitutes the top carry t (value == base + t*2**16W) with
    t*c, which preserves the value mod m since 2**16W == c (mod m).  After
    three substitutions the top carry is provably zero; a final conditional
    subtract brings the value into [0, m).
    """
    c16 = ctx.c_limbs16_dev
    nc = ctx.c_limbs16.shape[0]
    base, t = _scan_carry(x)  # |t| <= 4 given lazy-limb bounds
    for _ in range(3):
        y = base.at[..., :nc].add(t[..., None] * c16)  # |t*c16| < 2**19: ok
        base, t = _scan_carry(y)
    # By range analysis: after the second substitution the value lies in
    # (-c, 2**16W + c), so the third lands in [0, 2**16W) with t == 0.
    out = _cond_sub_m(ctx, base)
    return _cond_sub_m(ctx, out)


def is_zero(ctx: FieldCtx, x):
    """Canonical zero test (x ≡ 0 mod m)."""
    return jnp.all(canon(ctx, x) == 0, axis=-1)

def eq(ctx: FieldCtx, a, b):
    return jnp.all(canon(ctx, a) == canon(ctx, b), axis=-1)

def eq_canonical(ctx: FieldCtx, a, b_canon):
    """Compare against an already-canonical value."""
    return jnp.all(canon(ctx, a) == b_canon, axis=-1)

def is_odd(ctx: FieldCtx, x):
    return (canon(ctx, x)[..., 0] & 1) == 1


def like_varying(c, like):
    """Give the constant ``c`` the varying-manual-axes type of ``like``.

    Under ``jax.shard_map`` a loop carry must enter with the type it leaves
    with: a broadcast constant is replicated while anything computed from a
    sharded operand is varying, so a constant that seeds a scan/fori carry
    is cast here.  Outside shard_map ``like`` varies over nothing and ``c``
    comes back untouched — the traced kernel is the same either way."""
    vma = getattr(jax.typeof(like), "vma", None)
    return jax.lax.pcast(c, tuple(vma), to="varying") if vma else c


def exp_const(ctx: FieldCtx, x, e: int):
    """x**e mod m for a *static* python-int exponent (square-and-multiply).

    Uses lax.fori_loop over the fixed bit string to keep the HLO small.
    """
    nbits = e.bit_length()
    bits = np.array([(e >> (nbits - 1 - i)) & 1 for i in range(nbits)], dtype=np.int32)
    bits_d = jnp.asarray(bits)
    one = like_varying(jnp.broadcast_to(jnp.asarray(ctx.one), x.shape).astype(jnp.int32), x)

    def body(i, acc):
        acc = sqr(ctx, acc)
        withx = mul(ctx, acc, x)
        return jnp.where(bits_d[i][..., None], withx, acc)

    return jax.lax.fori_loop(0, nbits, body, one)


def inv(ctx: FieldCtx, x):
    """Modular inverse via Fermat (m prime). inv(0) == 0."""
    return exp_const(ctx, x, ctx.modulus - 2)


def inv_batch(ctx: FieldCtx, x, zero_mask=None):
    """Batch-affine Montgomery inversion along the leading axis.

    Replaces B independent Fermat ladders with ~3(B-1) modular multiplies
    plus ONE Fermat inversion of the running product:

        inv(x_i) == prefix_{i-1} * suffix_{i+1} * inv(prod_j x_j)

    Prefix/suffix products are two O(log B)-depth associative scans —
    modular multiplication is associative, so the scan's reassociation is
    exact (lazy-limb representations may differ; values mod m cannot).
    Zeros would poison the shared product, so zero lanes are substituted
    with 1 through the chain and masked back to 0 on output, preserving
    ``inv``'s inv(0) == 0 convention.

    x: [B, ..., W] lazy limbs; zero_mask: optional [B, ...] bool marking
    canonical zeros (computed here when absent). Returns lazy limbs.
    """
    b = x.shape[0]
    if b == 0:
        return x
    if zero_mask is None:
        zero_mask = is_zero(ctx, x)
    one = jnp.broadcast_to(jnp.asarray(ctx.one), x.shape).astype(jnp.int32)
    u = jnp.where(zero_mask[..., None], one, x)
    if b == 1:
        return jnp.where(zero_mask[..., None], jnp.zeros_like(x), inv(ctx, u))

    def mulfn(p, q):
        return mul(ctx, p, q)

    pre = jax.lax.associative_scan(mulfn, u, axis=0)  # pre[i] = u_0 .. u_i
    suf = jax.lax.associative_scan(mulfn, u, axis=0, reverse=True)
    total_inv = inv(ctx, pre[-1])  # the single Fermat ladder
    left = jnp.concatenate([one[:1], pre[:-1]], axis=0)  # prod of lanes < i
    right = jnp.concatenate([suf[1:], one[:1]], axis=0)  # prod of lanes > i
    out = mul(ctx, mul(ctx, left, right), jnp.broadcast_to(total_inv, x.shape))
    return jnp.where(zero_mask[..., None], jnp.zeros_like(x), out)


# ---------------------------------------------------------------------------
# field contexts used by the framework
# ---------------------------------------------------------------------------

SECP_P = 2**256 - 2**32 - 977
SECP_N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
MUHASH_M = 2**3072 - 1103717  # crypto/muhash/src/u3072.rs:22 (PRIME_DIFF)

FP = FieldCtx("secp256k1_p", 256, SECP_P)
FN = FieldCtx("secp256k1_n", 256, SECP_N)
F3072 = FieldCtx("muhash_u3072", 3072, MUHASH_M)
