"""Ad-hoc kernel-variant bench: times the plain vs GLV Pallas ladder on
the live device (run from the repo root).  Not part of the driver bench."""
import random, time
import numpy as np
from kaspa_tpu.utils import jax_setup
jax_setup.setup()
from kaspa_tpu.crypto import eclib
from kaspa_tpu.crypto.secp import schnorr_challenge
from kaspa_tpu.ops.secp256k1.ladder_pallas import verify_batch_pallas

B = 16384
UNIQUE = 32
random.seed(2026)
sk = random.randrange(1, eclib.N)
pub = eclib.schnorr_pubkey(sk)
pk = eclib.lift_x(int.from_bytes(pub, "big"))
msgs = [random.randbytes(32) for _ in range(UNIQUE)]
sigs = [eclib.schnorr_sign(m, sk, b"\x05" * 32) for m in msgs]
expect = [True] * UNIQUE
for i in range(0, UNIQUE, 4):
    sigs[i] = sigs[i][:40] + bytes([sigs[i][40] ^ 1]) + sigs[i][41:]
    expect[i] = False
reps = B // UNIQUE
px = [pk[0].to_bytes(32, "big")] * B
py = [pk[1].to_bytes(32, "big")] * B
rc = [s[:32] for s in sigs] * reps
s_ints = [int.from_bytes(s[32:], "big") % eclib.N for s in sigs] * reps
e_ints = [schnorr_challenge(s[:32], pub, msgs[i]) for i, s in enumerate(sigs)] * reps
ok = np.ones(B, dtype=bool)
for glv in (False,):
    mask, _ = verify_batch_pallas(px, py, rc, s_ints, e_ints, ok, ecdsa=False, glv=glv)
    assert mask.tolist() == expect * reps, "MISMATCH glv=%s" % glv
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        out, _ = verify_batch_pallas(px, py, rc, s_ints, e_ints, ok, ecdsa=False, glv=glv)
        best = min(best, time.perf_counter() - t0)
    print("glv=%s: %.1f verifies/sec" % (glv, B / best))
