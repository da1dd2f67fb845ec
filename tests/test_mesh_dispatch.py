"""Mesh-sharded dispatch correctness (ops/mesh.py).

conftest forces 8 CPU host devices (XLA_FLAGS
--xla_force_host_platform_device_count=8), so every mesh size up to 8 is a
real sharded execution here, through the same shard_map entries production
uses.  The contract under test: mesh size is invisible in results — masks,
muhash digests, and BatchScriptChecker decisions are bit-identical to
single-device dispatch, for any batch size (divisible or not, empty,
single job).
"""

import hashlib
import random

import numpy as np
import pytest

from kaspa_tpu.observability.core import REGISTRY
from kaspa_tpu.ops import mesh


@pytest.fixture(autouse=True)
def _mesh_off_after():
    yield
    mesh.configure(1)


def test_configure_resolution():
    assert mesh.configure(1) == 1
    assert mesh.configure(0) == 1  # <= 1 disables
    assert mesh.configure(8) == 8
    assert mesh.configure("auto") == 8  # conftest forces 8 host devices
    with pytest.raises(ValueError, match="64 devices, 8 visible"):
        mesh.configure(64)  # an explicit size never clamps to what is visible
    assert mesh.active_size() == 8  # ... and the failed call changed nothing
    assert mesh.configure("3") == 3
    state = REGISTRY.snapshot()["mesh"]
    assert state["size"] == 3 and state["configured"] == "3"


# --- muhash -----------------------------------------------------------------


def _muhash_vals(n: int, seed: int = 0):
    from kaspa_tpu.ops import muhash_ops as mo

    rng = random.Random(seed)
    return [rng.getrandbits(3072) % mo.F.modulus for _ in range(n)]


@pytest.mark.parametrize("n", [0, 1, 7, 64, 200])
def test_muhash_product_identical_across_mesh(n, on_mesh_devices):
    from kaspa_tpu.ops import muhash_ops as mo

    vals = _muhash_vals(n, seed=n)
    oracle = 1
    for v in vals:
        oracle = oracle * v % mo.F.modulus
    mesh.configure(1)
    assert mo.batch_product_ints(vals) == oracle
    sharded = ("muhash",) if n else ()  # the empty product dispatches nothing
    mesh.configure(8)
    with on_mesh_devices(*sharded):
        assert mo.batch_product_ints(vals) == oracle
    # non-pow2 mesh: per-shard padding with the monoid identity
    mesh.configure(3)
    with on_mesh_devices(*sharded):
        assert mo.batch_product_ints(vals) == oracle


# --- batched signature verification ----------------------------------------


def _schnorr_items(n: int, corrupt_every: int = 4):
    from kaspa_tpu.crypto import eclib

    items = []
    for i in range(n):
        sk = i + 1
        msg = hashlib.sha256(bytes([i, n])).digest()
        sig = eclib.schnorr_sign(msg, sk)
        if corrupt_every and i % corrupt_every == corrupt_every - 1:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        items.append((eclib.schnorr_pubkey(sk), msg, sig))
    return items


def test_schnorr_mask_identical_mesh1_vs_mesh8(on_mesh_devices):
    from kaspa_tpu.crypto import secp

    # 7 items -> bucket 8, 1 lane/shard on the 8-mesh.  Deliberately the same
    # padded shape as the other schnorr tests here: one shard_map trace of
    # the verify ladder serves the whole file (each extra shape costs
    # minutes of trace time on CPU and would blow the tier-1 budget).
    items = _schnorr_items(7)
    mesh.configure(1)
    m1 = np.asarray(secp.schnorr_verify_batch(items))
    mesh.configure(8)
    with on_mesh_devices("schnorr"):
        m8 = np.asarray(secp.schnorr_verify_batch(items))
    assert m1.tolist() == m8.tolist()
    assert not m1.all() and m1.any()  # mixed validity actually exercised


def test_dispatch_verify_padding_edges(on_mesh_devices):
    """Direct mesh-layer edges: empty batch, single job (7 pad lanes on an
    8-mesh), and a batch not divisible by the shard count."""
    from kaspa_tpu.crypto import secp

    mesh.configure(8)
    assert secp.schnorr_verify_batch([]).shape == (0,)
    with on_mesh_devices("schnorr"):
        single = np.asarray(secp.schnorr_verify_batch(_schnorr_items(1, corrupt_every=0)))
    assert single.tolist() == [True]
    with on_mesh_devices("schnorr"):
        bad_single = np.asarray(secp.schnorr_verify_batch(_schnorr_items(1, corrupt_every=1)))
    assert bad_single.tolist() == [False]


def test_mesh_metrics_surface():
    from kaspa_tpu.crypto import secp
    from kaspa_tpu.ops import muhash_ops as mo

    mesh.configure(8)
    secp.schnorr_verify_batch(_schnorr_items(3))
    mo.batch_product_ints(_muhash_vals(10, seed=99))
    snap = REGISTRY.snapshot()
    assert snap["counters"]["mesh_dispatches"]["schnorr"] >= 1
    assert snap["counters"]["mesh_dispatches"]["muhash"] >= 1
    occ = snap["histograms"]["mesh_shard_occupancy_pct"]
    assert occ["count"] >= 8  # one observation per shard per dispatch
    assert snap["histograms"]["mesh_padding_waste_pct"]["count"] >= 1
    assert snap["mesh"]["size"] == 8


def test_batch_checker_decisions_identical_mesh1_vs_mesh8(on_mesh_devices):
    """The production path: BatchScriptChecker fast-path decisions must be
    bit-identical across mesh sizes (the acceptance criterion's unit-level
    form; the sim replay covers the full-block form)."""
    from kaspa_tpu.consensus import hashing as chash
    from kaspa_tpu.consensus.model import (
        SUBNETWORK_ID_NATIVE,
        ComputeCommit,
        Transaction,
        TransactionInput,
        TransactionOutpoint,
        TransactionOutput,
        UtxoEntry,
    )
    from kaspa_tpu.crypto import eclib
    from kaspa_tpu.txscript import standard
    from kaspa_tpu.txscript.batch import BatchScriptChecker
    from kaspa_tpu.txscript.caches import SigCache

    def p2pk_tx(seed, corrupt):
        rng = random.Random(seed)
        sk = rng.randrange(1, eclib.N)
        pub = eclib.schnorr_pubkey(sk)
        spk = standard.pay_to_pub_key(pub)
        entry = UtxoEntry(10_000, spk, 5, False)
        tx = Transaction(
            0,
            [TransactionInput(TransactionOutpoint(bytes([seed]) * 32, 0), b"", 0, ComputeCommit.sigops(1))],
            [TransactionOutput(9_000, spk)], 0, SUBNETWORK_ID_NATIVE, 0, b"",
        )
        reused = chash.SigHashReusedValues()
        msg = chash.calc_schnorr_signature_hash(tx, [entry], 0, chash.SIG_HASH_ALL, reused)
        sig = eclib.schnorr_sign(msg, sk, rng.randbytes(32))
        if corrupt:
            sig = sig[:9] + bytes([sig[9] ^ 1]) + sig[10:]
        tx.inputs[0].signature_script = standard.schnorr_signature_script(sig, chash.SIG_HASH_ALL)
        return tx, [entry]

    txs = [p2pk_tx(seed, corrupt=(seed % 3 == 0)) for seed in range(40, 47)]

    def run():
        checker = BatchScriptChecker(SigCache())  # fresh cache: no cross-run skips
        for token, (tx, entries) in enumerate(txs):
            checker.collect_tx(token, tx, entries)
        return {
            t: None if e is None else (getattr(e, "input_index", None), str(e))
            for t, e in checker.dispatch().items()
        }

    mesh.configure(1)
    r1 = run()
    mesh.configure(8)
    with on_mesh_devices("schnorr"):
        r8 = run()
    assert r1 == r8
    assert any(v is not None for v in r1.values()) and any(v is None for v in r1.values())
