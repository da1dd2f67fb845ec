"""Operations and bytes the *algorithm* needs, from shapes alone — whatever
kernel formulation runs, the share is of the same work.

One BIP340/ECDSA verification is one dual-scalar multiplication
``s*G + e*P`` over secp256k1 with 4-bit fixed windows, then an affine check:

- 64 windows x 4 doublings                = 256 point doublings
- per window one addition from G's table (mixed: the table is affine)
  and one from P's table (full: the table is projective)
                                           = 64 mixed + 64 full additions
- P's table: 1 doubling + 13 additions    =   1 doubling + 13 full additions
  (2P, then 3P..15P; G's table is a constant)
- affine check: one field inversion by Fermat, a 256-bit addition chain
  (255 squarings + 15 multiplications), and 2 multiplications.

Field multiplications per point operation (a = 0 short Weierstrass, Jacobian
/ complete-projective formulas as commonly costed; a squaring counts as a
multiplication): doubling 8 (4M + 4S), mixed addition 11 (7M + 4S), full
addition 16 (11M + 5S, rounded to the 12M + 4S of the EFD add-2007-bl form).

A 256-bit field multiplication on a machine whose widest published integer
unit multiplies 8-bit operands is 32 x 32 limb multiply-adds = 1,024 MACs =
2,048 integer operations (reduction modulo p is linear in the limbs and is
not counted: the count is a floor on the work, so the share is a floor too).
"""

from __future__ import annotations

FIELD_MUL_OPS = 32 * 32 * 2  # 8-bit limb multiply-adds of one 256x256-bit product, x2 (mul + add)

DOUBLINGS = 64 * 4 + 1
MIXED_ADDS = 64
FULL_ADDS = 64 + 13
INVERSION_MULS = 255 + 15
CHECK_MULS = 2
COST = {"double": 8, "mixed_add": 11, "full_add": 16}


def verify_field_muls() -> int:
    """Field multiplications of one verification: 4,264."""
    return (
        DOUBLINGS * COST["double"] + MIXED_ADDS * COST["mixed_add"] + FULL_ADDS * COST["full_add"]
        + INVERSION_MULS + CHECK_MULS
    )


def verify_ops() -> int:
    """Integer operations of one verification: 4,264 x 2,048 = 8,732,672."""
    return verify_field_muls() * FIELD_MUL_OPS


def verify_bytes() -> int:
    """Bytes one verification moves over HBM: px, py, r (3 x 32), two
    scalars as 64 4-bit digits in int32 (2 x 256), a validity flag in and a
    verdict out (2 x 4): 616."""
    return 3 * 32 + 2 * 64 * 4 + 2 * 4


def muhash_element_ops() -> int:
    """Integer operations of folding one 3072-bit element into a product:
    one 3072 x 3072-bit multiplication = 384 x 384 eight-bit limb
    multiply-adds x 2 = 294,912 (the reduction by the sparse prime is linear
    and not counted)."""
    return 384 * 384 * 2


def muhash_element_bytes() -> int:
    """Bytes of one element in and its share of a product out: 384 + 384."""
    return 2 * 384


def roofline_seconds(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound gives it."""
    t_ops = ops / peak["int8_ops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
