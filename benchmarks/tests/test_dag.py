"""The generator at toy size on the CPU: window blocks carry exactly
``tx_per_block`` spends, every signature verifies under ``eclib`` (and the
spoiled ones do not), spoiled spends are never accepted, and the same seed
gives the same block hashes."""

import pytest

from benchmarks import dag as dagmod
from benchmarks import reference


@pytest.fixture(scope="module")
def toy():
    from kaspa_tpu.crypto import secp
    from kaspa_tpu.ops import dispatch

    dispatch.configure(0)
    assert not secp.pretrace_bucket("schnorr_verify", 8).startswith("error")
    spec = dagmod.DagSpec(bps=2, delay=1.0, miners=4, tx_per_block=4, window_blocks=20, seed=5, spoiled_blocks=2, sig_samples=6)
    return spec, dagmod.build(spec)


def test_window_blocks_carry_exactly_tx_per_block(toy):
    spec, d = toy
    assert len(d.blocks) - d.ramp == spec.window_blocks
    assert all(len(b.transactions) - 1 == spec.tx_per_block for b in d.blocks[d.ramp :])
    assert all(len(tx.inputs) == 1 and len(tx.outputs) == 1 for b in d.blocks[d.ramp :] for tx in b.transactions[1:])
    assert d.ramp > 0 and len(d.sinks) == len(d.blocks)


def _sig_of(tx):
    script = tx.inputs[0].signature_script
    assert script[0] == 65
    return script[1:65]


def test_every_window_signature_verifies_under_eclib_but_the_spoiled(toy):
    from kaspa_tpu.consensus import hashing as chash
    from kaspa_tpu.crypto import eclib

    _spec, d = toy
    spoiled_txids = {s["txid"] for s in d.spoiled.values()}
    assert len(spoiled_txids) == 2 and {s["cls"] for s in d.spoiled.values()} == set(dagmod.SPOIL_CLASSES)
    # rebuild each spend's sighash from the output it spends
    outputs = {}
    for b in d.blocks:
        for tx in b.transactions:
            for j, out in enumerate(tx.outputs):
                outputs[(tx.id(), j)] = out
    from kaspa_tpu.consensus.model.tx import UtxoEntry

    checked = 0
    for b in d.blocks[d.ramp :]:
        for tx in b.transactions[1:]:
            op = tx.inputs[0].previous_outpoint
            out = outputs[(op.transaction_id, op.index)]
            entry = UtxoEntry(out.value, out.script_public_key, 0, False)
            msg = chash.calc_schnorr_signature_hash(tx, [entry], 0, chash.SIG_HASH_ALL, chash.SigHashReusedValues())
            pub = out.script_public_key.script[1:33]
            assert eclib.schnorr_verify(pub, msg, _sig_of(tx)) == (tx.id() not in spoiled_txids)
            # the plain reference works the message and the verdict out from the transaction alone
            spk = out.script_public_key
            assert reference.p2pk_spend_verdict(tx, out.value, spk.version, spk.script) == (tx.id() not in spoiled_txids, msg)
            checked += 1
    assert checked == 20 * 4
    # the benchmark's own verifier agrees on the sample it will use
    # (and a sample is the signature its block carries: one from a template
    # that was discarded would be held against a spend mined again, honestly)
    for i, txid, pub, msg, sig, valid in d.sig_samples:
        assert reference.bip340_verify(pub, msg, sig) == valid == eclib.schnorr_verify(pub, msg, sig)
        assert [_sig_of(tx) for tx in d.blocks[i].transactions[1:] if tx.id() == txid] == [sig]


def test_spoiled_blocks_are_rejected_and_the_reference_agrees_with_the_headers(toy):
    _spec, d = toy
    by_hash = {b.hash: b for b in d.blocks}
    spoiled_txids = {s["txid"] for s in d.spoiled.values()}
    sink = d.sinks[-1]
    assert sink not in d.spoiled
    genesis = d.params.genesis.hash

    def ghostdag(blocks):
        return reference.Ghostdag(blocks, genesis, d.params.genesis.bits, d.params.ghostdag_k)

    gd = ghostdag(d.blocks)
    # the reference's GHOSTDAG gives the scores the program wrote into the headers
    assert all((b.header.blue_score, b.header.blue_work, b.header.daa_score)
               == (gd.blue_score[b.hash], gd.blue_work[b.hash], gd.daa_score[b.hash]) for b in d.blocks)
    utxos, chain, accepted, _merged = reference.expected_utxo_set(d.blocks, gd, sink, genesis, spoiled_txids)
    assert not (accepted & spoiled_txids)
    assert not any(txid in spoiled_txids for txid, _j in utxos)
    assert not (set(chain) & set(d.spoiled))
    # the header of the sink commits to the reference's UTXO set, at several prefixes
    for n in (len(d.blocks), len(d.blocks) - 3, d.ramp + 2):
        s = d.sinks[n - 1]
        u, *_ = reference.expected_utxo_set(d.blocks[:n], ghostdag(d.blocks[:n]), s, genesis, spoiled_txids)
        assert reference.muhash_commitment(u) == by_hash[s].header.utxo_commitment
    # accepting the spoiled spends would give another commitment
    u, *_ = reference.expected_utxo_set(d.blocks, gd, sink, genesis, set())
    assert reference.muhash_commitment(u) != by_hash[sink].header.utxo_commitment


def test_same_seed_same_hashes(toy):
    spec, d = toy
    again = dagmod.build(spec)
    assert [b.hash for b in again.blocks] == [b.hash for b in d.blocks]
    other = dagmod.build(dagmod.DagSpec(**{**spec.__dict__, "seed": spec.seed + 1}))
    assert [b.hash for b in other.blocks[:5]] != [b.hash for b in d.blocks[:5]]
