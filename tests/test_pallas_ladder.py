"""Interpret-mode validation of the fused Pallas verification ladder.

The Mosaic kernel (ops/secp256k1/ladder_pallas.py) is the TPU fast path for
batched Schnorr/ECDSA; on the CPU test mesh we run it through the Pallas
interpreter and check the validity mask bit-for-bit against the pure-python
oracle (eclib) — same strategy as the XLA kernel's oracle tests.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from kaspa_tpu.crypto import eclib
from kaspa_tpu.crypto.secp import schnorr_challenge
from kaspa_tpu.ops.secp256k1 import points as pt
from kaspa_tpu.ops.secp256k1.ladder_pallas import verify_batch_pallas

pytestmark = pytest.mark.slow

B = 8


@pytest.fixture(scope="module")
def keys():
    random.seed(7)
    sk = [random.randrange(1, eclib.N) for _ in range(B)]
    return sk


def _col(vals):
    """The byte column secp._Batch holds: one 32-byte big-endian string a job."""
    return [v.to_bytes(32, "big") for v in vals]


def test_schnorr_pallas_interpret(keys):
    sk = keys
    pubs = [eclib.schnorr_pubkey(k) for k in sk]
    pks = [eclib.lift_x(int.from_bytes(p, "big")) for p in pubs]
    msgs = [random.randbytes(32) for _ in range(B)]
    sigs = [eclib.schnorr_sign(m, k, b"\x07" * 32) for m, k in zip(msgs, sk)]
    expect = [True] * B
    for i in (1, 5):  # corrupt s
        sigs[i] = sigs[i][:40] + bytes([sigs[i][40] ^ 1]) + sigs[i][41:]
        expect[i] = False
    # corrupt r on one more lane
    sigs[6] = bytes([sigs[6][0] ^ 1]) + sigs[6][1:]
    expect[6] = False

    px = _col([p[0] for p in pks])
    py = _col([p[1] for p in pks])
    rc = _col([int.from_bytes(s[:32], "big") for s in sigs])
    sd = [int.from_bytes(s[32:], "big") for s in sigs]
    ed = [schnorr_challenge(s[:32], pubs[i], msgs[i]) for i, s in enumerate(sigs)]
    ok = np.ones(B, dtype=bool)
    ok[3] = False  # host-side encoding rejection must mask through
    expect[3] = False

    mask, uploads = verify_batch_pallas(px, py, rc, sd, ed, ok, ecdsa=False, interpret=True)
    assert mask.tolist() == expect and uploads == 1

    # oracle cross-check on the uncorrupted lanes
    for i in (0, 2, 4, 7):
        assert eclib.schnorr_verify(pubs[i], msgs[i], sigs[i])


def test_ecdsa_pallas_interpret(keys):
    sk = keys
    pks = [eclib.point_mul(eclib.G, k) for k in sk]
    msgs = [random.randbytes(32) for _ in range(B)]
    sigs_b = [eclib.ecdsa_sign(m, k, 10_007 + i) for i, (m, k) in enumerate(zip(msgs, sk))]
    rs = [(int.from_bytes(s[:32], "big"), int.from_bytes(s[32:], "big")) for s in sigs_b]
    expect = [True] * B
    rs[2] = (rs[2][0], rs[2][1] ^ 2)  # corrupt s
    expect[2] = False

    u1, u2 = [], []
    for m, (r, s) in zip(msgs, rs):
        z = int.from_bytes(m, "big") % eclib.N
        si = pow(s, -1, eclib.N)
        u1.append(z * si % eclib.N)
        u2.append(r * si % eclib.N)

    px = _col([p[0] for p in pks])
    py = _col([p[1] for p in pks])
    rn = _col([r % eclib.N for r, _ in rs])
    ok = np.ones(B, dtype=bool)

    mask, uploads = verify_batch_pallas(px, py, rn, u1, u2, ok, ecdsa=True, interpret=True)
    assert mask.tolist() == expect and uploads == 1
