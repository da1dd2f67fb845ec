"""Seeded signature batches for harnesses (chip_smoke.py).

Every lane is a DISTINCT (pubkey, message, signature) triple, made cheaply:
P_i = P_{i-1} + G and R_i = R_{i-1} + G cost two point_adds per lane
instead of two full scalar ladders in the pure-Python oracle.  ``spoil``
then turns a chosen share of lanes invalid, cycling through every class the
host prechecks and the device decide between them, and returns the mask
those lanes must get *by construction* — the harness compares it with the
device mask and, on a sample, with ``eclib``.
"""

from __future__ import annotations

import random

from kaspa_tpu.crypto import eclib
from kaspa_tpu.crypto.secp import schnorr_challenge

# every way a lane is made invalid; the first three are decided by the
# host prechecks (never reach a useful device lane), the last two by the
# device's ladder + affine check
INVALID_CLASSES = ("r_ge_p", "s_ge_n", "r_off_curve", "flipped_sig_byte", "wrong_message")


def schnorr_points(b: int, seed: int = 2026) -> list:
    """b distinct BIP340 lanes as (P_point, pubkey32, msg32, sig64)."""
    rng = random.Random(seed)
    sk0 = rng.randrange(1, eclib.N - b)
    k0 = rng.randrange(1, eclib.N - b)
    P = eclib.point_mul(eclib.G, sk0)
    R = eclib.point_mul(eclib.G, k0)
    triples = []
    for i in range(b):
        sk, k = sk0 + i, k0 + i
        # BIP340 key/nonce negation for even-y points
        d = sk if P[1] % 2 == 0 else eclib.N - sk
        pub = P[0].to_bytes(32, "big")
        kk = k if R[1] % 2 == 0 else eclib.N - k
        r = R[0].to_bytes(32, "big")
        msg = rng.getrandbits(256).to_bytes(32, "big")
        e = schnorr_challenge(r, pub, msg)
        s = (kk + e * d) % eclib.N
        triples.append((P, pub, msg, r + s.to_bytes(32, "big")))
        P = eclib.point_add(P, eclib.G)
        R = eclib.point_add(R, eclib.G)
    return triples


def ecdsa_points(b: int, seed: int = 2027) -> list:
    """b distinct ECDSA lanes as (P_point, msg32, low-S sig64) with known
    nonces k_i = k0 + i (one cheap modular inverse per lane)."""
    rng = random.Random(seed)
    sk0 = rng.randrange(1, eclib.N - b)
    k0 = rng.randrange(1, eclib.N - b)
    P = eclib.point_mul(eclib.G, sk0)
    R = eclib.point_mul(eclib.G, k0)
    triples = []
    for i in range(b):
        sk, k = sk0 + i, k0 + i
        r = R[0] % eclib.N
        msg = rng.getrandbits(256).to_bytes(32, "big")
        z = int.from_bytes(msg, "big") % eclib.N
        s = pow(k, -1, eclib.N) * (z + r * sk) % eclib.N
        if s > eclib.N // 2:
            s = eclib.N - s  # low-S, like the signing front-end
        triples.append((P, msg, r.to_bytes(32, "big") + s.to_bytes(32, "big")))
        P = eclib.point_add(P, eclib.G)
        R = eclib.point_add(R, eclib.G)
    return triples


def schnorr_items(b: int, seed: int = 2026) -> list:
    """b valid (pubkey32, msg32, sig64) items for ``schnorr_verify_batch``."""
    return [(pub, msg, sig) for _P, pub, msg, sig in schnorr_points(b, seed)]


def ecdsa_items(b: int, seed: int = 2027) -> list:
    """b valid (pubkey33, msg32, sig64) items for ``ecdsa_verify_batch``."""
    return [
        (bytes([2 + (P[1] & 1)]) + P[0].to_bytes(32, "big"), msg, sig)
        for P, msg, sig in ecdsa_points(b, seed)
    ]


def _off_curve_x(start: int) -> int:
    """Smallest x >= start (mod p) that is no point's x-coordinate."""
    x = start % eclib.P
    while eclib.lift_x(x) is not None:
        x = (x + 1) % eclib.P
    return x


def spoil(kind: str, items: list, every: int = 4, seed: int = 7) -> tuple[list, list, list]:
    """Make lanes 0, every, 2*every, ... invalid, cycling INVALID_CLASSES.

    Returns (items, expected_mask, classes) where classes[i] is the class
    lane i was spoiled with, or None.  ``kind`` is "schnorr" or "ecdsa":
    the out-of-range values differ (ECDSA's r is reduced mod n, so its
    "r_ge_p" lane carries r >= n, and "r_off_curve" an r that is in range
    but is not the nonce point's x — the device, not the host, rejects it).
    """
    rng = random.Random(seed)
    out = list(items)
    expect = [True] * len(out)
    classes: list = [None] * len(out)
    for n, i in enumerate(range(0, len(out), every)):
        cls = INVALID_CLASSES[n % len(INVALID_CLASSES)]
        pub, msg, sig = out[i]
        r, s = sig[:32], sig[32:]
        if cls == "r_ge_p":
            bound = eclib.P if kind == "schnorr" else eclib.N
            r = (bound + rng.randrange(1000)).to_bytes(32, "big")
        elif cls == "s_ge_n":
            s = (eclib.N + rng.randrange(1000)).to_bytes(32, "big")
        elif cls == "r_off_curve":
            r = _off_curve_x(int.from_bytes(r, "big") + 1).to_bytes(32, "big")
        elif cls == "flipped_sig_byte":
            j = 32 + rng.randrange(32)  # in s: stays in range with near-certainty
            flipped = sig[:j] + bytes([sig[j] ^ (1 + rng.randrange(255))]) + sig[j + 1 :]
            r, s = flipped[:32], flipped[32:]
        else:  # wrong_message
            msg = bytes([msg[0] ^ 0x01]) + msg[1:]
        out[i] = (pub, msg, r + s)
        expect[i] = False
        classes[i] = cls
    return out, expect, classes
