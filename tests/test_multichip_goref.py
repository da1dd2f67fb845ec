"""Sharded verification of a real goref block batch on the CPU mesh.

The round-1 review asked for multi-chip evidence beyond identical tiled lanes:
this replays a prefix of the golden tx DAG, captures the exact
(pubkey, sighash, sig) triples the consensus validator dispatched, then
re-runs them through the Schnorr kernel jitted over an 8-device mesh with
batch-dim sharding — the mask must match both the single-device dispatch
and the scalar eclib oracle, lane for lane.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kaspa_tpu.crypto import eclib, secp
from kaspa_tpu.ops.secp256k1 import points as pt
from kaspa_tpu.ops.secp256k1.verify import schnorr_verify_kernel
from kaspa_tpu.sim.goref import replay_goref

pytestmark = pytest.mark.slow

TX_DAG = (
    "/root/reference/testing/integration/testdata/dags_for_json_tests/"
    "goref-1060-tx-265-blocks/blocks.json.gz"
)


@pytest.mark.skipif(not os.path.exists(TX_DAG), reason="reference testdata not mounted")
def test_goref_block_batch_sharded_over_mesh(monkeypatch):
    captured = []
    real_batch = secp.schnorr_verify_batch

    def capturing_batch(items):
        items = list(items)
        captured.extend(items)
        return real_batch(items)

    # txscript.batch resolves secp.schnorr_verify_batch at call time on this
    # same module object, so one patch covers the validator's dispatch too
    monkeypatch.setattr(secp, "schnorr_verify_batch", capturing_batch)
    replay_goref(TX_DAG)  # txs appear late in this DAG: replay in full
    assert len(captured) >= 64, f"expected real sig jobs in the tx DAG, got {len(captured)}"

    triples = captured[:256]
    host_mask = np.asarray(real_batch(triples))
    oracle = np.array(
        [len(p) == 32 and len(s) == 64 and eclib.schnorr_verify(p, m, s) for p, m, s in triples]
    )
    assert (host_mask == oracle).all()

    devices = np.array(jax.devices()[:8])
    mesh = Mesh(devices, axis_names=("batch",))

    def sharded_verify(px, py, rc, s_scalars, e_scalars, valid_in):
        assert len(valid_in) % 8 == 0  # secp buckets are powers of two >= 8
        from kaspa_tpu.ops.secp256k1.verify import marshal_limbs

        lane = NamedSharding(mesh, P("batch", None))
        flat = NamedSharding(mesh, P("batch"))
        args = [
            jax.device_put(np.asarray(a), s)
            for a, s in zip(
                marshal_limbs(px, py, rc, s_scalars, e_scalars, valid_in),
                (lane, lane, lane, lane, lane, flat),
            )
        ]
        fn = jax.jit(
            schnorr_verify_kernel.__wrapped__,
            in_shardings=(lane,) * 5 + (flat,),
            out_shardings=flat,
        )
        return np.asarray(fn(*args))

    monkeypatch.setattr(secp, "schnorr_verify", sharded_verify)
    sharded_mask = np.asarray(real_batch(triples))
    assert (sharded_mask == host_mask).all(), "mesh-sharded mask diverges from single-device dispatch"
    assert sharded_mask.all(), "golden DAG signatures must all verify"

    # and with adversarial lanes mixed in: corrupted copies of real triples
    bad = [(p, m, bytes([s[0] ^ 0xFF]) + s[1:]) for p, m, s in triples[:16]]
    mixed = triples[:48] + bad
    mixed_mask = np.asarray(real_batch(mixed))
    assert mixed_mask[:48].all() and not mixed_mask[48:].any()
