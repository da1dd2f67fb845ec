"""Batched TPU Schnorr/ECDSA kernels vs the pure-python oracle.

Mirrors the signature-check semantics of the reference
(crypto/txscript/src/lib.rs:885-935): BIP340 x-only Schnorr and compact
ECDSA with high-S rejection.  Adversarial cases included — wrong message,
corrupted sigs, invalid pubkeys, out-of-range r/s.
"""

import random

import numpy as np
import pytest

from kaspa_tpu.crypto import eclib, secp

pytestmark = pytest.mark.slow


def _schnorr_cases(n=16, seed=11):
    rng = random.Random(seed)
    items, expect = [], []
    for i in range(n):
        sk = rng.randrange(1, eclib.N)
        msg = rng.randbytes(32)
        pub = eclib.schnorr_pubkey(sk)
        sig = eclib.schnorr_sign(msg, sk, rng.randbytes(32))
        kind = i % 8
        if kind == 1:
            sig = sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]  # corrupt s
        elif kind == 2:
            msg = rng.randbytes(32)  # wrong message
        elif kind == 3:
            sig = bytes([sig[0] ^ 1]) + sig[1:]  # corrupt r
        elif kind == 4:
            pub = rng.randbytes(32)  # likely not a valid x (or wrong key)
        elif kind == 5:
            sig = sig[:32] + (eclib.N + 5).to_bytes(32, "big")  # s >= n
        elif kind == 6:
            sig = (eclib.P + 1).to_bytes(32, "big") + sig[32:]  # r >= p
        items.append((pub, msg, sig))
        expect.append(eclib.schnorr_verify(pub, msg, sig))
    return items, expect


def test_schnorr_batch_matches_oracle():
    items, expect = _schnorr_cases()
    mask = secp.schnorr_verify_batch(items)
    assert list(mask) == expect
    assert any(expect) and not all(expect)  # mix of valid/invalid exercised


def _ecdsa_cases(n=16, seed=12):
    rng = random.Random(seed)
    items, expect = [], []
    for i in range(n):
        sk = rng.randrange(1, eclib.N)
        msg = rng.randbytes(32)
        pub = eclib.ecdsa_pubkey(sk)
        sig = eclib.ecdsa_sign(msg, sk, rng.randrange(1, eclib.N))
        kind = i % 8
        if kind == 1:
            sig = sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]
        elif kind == 2:
            msg = rng.randbytes(32)
        elif kind == 3:  # high-S: must be rejected (libsecp256k1 semantics)
            s = int.from_bytes(sig[32:], "big")
            sig = sig[:32] + (eclib.N - s).to_bytes(32, "big")
        elif kind == 4:
            pub = bytes([9]) + pub[1:]  # bad prefix byte
        elif kind == 5:
            sig = sig[:32] + b"\x00" * 32  # s == 0
        elif kind == 6:
            pub = bytes([pub[0] ^ 1]) + pub[1:]  # flipped parity (2 <-> 3): valid encoding, wrong key
        items.append((pub, msg, sig))
        expect.append(eclib.ecdsa_verify(pub, msg, sig))
    return items, expect


def test_ecdsa_batch_matches_oracle():
    items, expect = _ecdsa_cases()
    mask = secp.ecdsa_verify_batch(items)
    assert list(mask) == expect
    assert any(expect) and not all(expect)


def test_point_ladder_vs_oracle():
    """dual_scalar_mul against python scalar multiplication, incl. edge scalars."""
    import jax.numpy as jnp

    from kaspa_tpu.ops import bigint as bi
    from kaspa_tpu.ops.secp256k1 import points as pt

    rng = random.Random(13)
    sk = rng.randrange(1, eclib.N)
    P = eclib.point_mul(eclib.G, sk)
    cases = [
        (0, 0),
        (1, 0),
        (0, 1),
        (5, 7),
        (eclib.N - 1, 1),
        (rng.randrange(eclib.N), rng.randrange(eclib.N)),
        (rng.randrange(eclib.N), rng.randrange(eclib.N)),
        (1, eclib.N - 1),
    ]
    b = len(cases)
    px = np.tile(bi.int_to_limbs(P[0], 16), (b, 1)).astype(np.int32)
    py = np.tile(bi.int_to_limbs(P[1], 16), (b, 1)).astype(np.int32)
    gd = np.stack([pt.scalar_digits_msb(a) for a, _ in cases])
    pd = np.stack([pt.scalar_digits_msb(c) for _, c in cases])
    import jax

    ladder = jax.jit(lambda *a: pt.to_affine(pt.dual_scalar_mul_base(*a)))
    xa, ya, inf = ladder(jnp.asarray(px), jnp.asarray(py), jnp.asarray(gd), jnp.asarray(pd))
    for i, (a, c) in enumerate(cases):
        exp = eclib.point_add(eclib.point_mul(eclib.G, a), eclib.point_mul(P, c))
        if exp is None:
            assert bool(inf[i])
        else:
            assert not bool(inf[i])
            assert bi.limbs_to_int(np.asarray(xa)[i]) == exp[0]
            assert bi.limbs_to_int(np.asarray(ya)[i]) == exp[1]


def test_cold_bucket_split(monkeypatch):
    """A batch whose padded bucket was never compiled must split into
    sub-dispatches at the largest warm bucket instead of paying the cold
    jit inline; masks reassemble in order, and the cold shape is never
    recorded as compiled."""
    import numpy as np

    from kaspa_tpu.crypto import secp

    calls = []

    def fake_kernel(px, py, rc, d1, d2, ok):
        calls.append(len(ok))
        out = np.asarray(ok, dtype=bool).copy()
        return out

    fake_kernel.__name__ = "fake_kernel"
    monkeypatch.setattr(secp, "_seen_shapes", {("fake_kernel", 8, 1)})
    monkeypatch.delenv("KASPA_TPU_COLD_BUCKET_SPLIT", raising=False)

    batch = secp._Batch()
    for i in range(10):
        if i == 3:
            batch.push_invalid()
        else:
            batch.push(1, 2, 3, 4, 5)
    mask = batch.run(fake_kernel)
    # two warm bucket-8 dispatches, bucket 16 never compiled
    assert calls == [8, 8]
    assert ("fake_kernel", 16, 1) not in secp._seen_shapes
    assert mask.tolist() == [True] * 3 + [False] + [True] * 6

    # disabled: pad up into the cold bucket as before
    calls.clear()
    monkeypatch.setenv("KASPA_TPU_COLD_BUCKET_SPLIT", "0")
    batch2 = secp._Batch()
    for _ in range(10):
        batch2.push(1, 2, 3, 4, 5)
    mask2 = batch2.run(fake_kernel)
    assert calls == [16]
    assert ("fake_kernel", 16, 1) in secp._seen_shapes
    assert mask2.tolist() == [True] * 10
