"""The generator at toy size on the CPU: window blocks carry exactly
``tx_per_block`` spends, every signature verifies under ``eclib`` (and the
spoiled ones do not), spoiled spends are never accepted, and the same seed
gives the same block hashes.  With ``own_blocks_delayed`` (ISSUE 28) one miner
builds simpa's wide DAG; with the key absent no DAG moves."""

import hashlib

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


# the toy seed's DAG as the tree before ISSUE 28 built it (git archive of
# c5047af, same spec): block count, ramp, first and last hash, digest of all
PINNED = (58, 38, "879056dd51ba1218a07f4f7eaa8e02599269fda73196455d1726f5a35ddd4bc0",
          "6722a013c6e9f3e6622dc623190241b1ba76755245af477b3c90fcf9a05b3381",
          "20f183beedfe6eae8a273d6d4e762bdc406ecf2b449c9d2680557a76cfd2f08e")


def test_with_the_key_absent_the_dag_is_the_parents(toy):
    spec, d = toy
    assert not spec.own_blocks_delayed
    got = (len(d.blocks), d.ramp, d.blocks[0].hash.hex(), d.blocks[-1].hash.hex(),
           hashlib.sha256(b"".join(b.hash for b in d.blocks)).hexdigest())
    assert got == PINNED


WIDE = dict(bps=4, delay=1.0, miners=1, tx_per_block=4, window_blocks=40, seed=11, spoiled_blocks=2, sig_samples=6,
            pool_factor=8, own_blocks_delayed=True)


@pytest.fixture(scope="module")
def wide(toy):  # after `toy`: the bucket is warm and coalescing is off
    spec = dagmod.DagSpec(**WIDE)
    return spec, dagmod.build(spec)


def test_own_blocks_delayed_makes_one_miner_build_a_wide_dag(wide):
    spec, d = wide
    window = d.blocks[d.ramp :]
    assert len(window) == spec.window_blocks and all(len(b.transactions) - 1 == spec.tx_per_block for b in window)
    assert d.facts["miners"] == 1 and d.facts["ghostdag_k"] == 55  # simnet_params(bps=4)
    mean_parents = sum(len(b.header.direct_parents()) for b in window) / len(window)
    assert mean_parents == d.facts["mean_window_parents"]
    # a miner's tips are the blocks of the last-but-one delay (delay x bps of
    # them) and each older block until one a delay younger is seen (one more)
    width = spec.delay * spec.bps + 1
    assert 0.65 * width <= mean_parents <= 1.35 * width, mean_parents
    # in the window nothing is a parent before the delay has passed, the
    # miner's own previous block included (a block's timestamp is its mining
    # time in ms, plus 1; the spoiled blocks are late siblings over parents
    # seen earlier still); the ramp before it is a chain: the fan-out's
    # coinbase outputs exist on one selected chain only
    stamp = {b.hash: b.header.timestamp for b in d.blocks}
    delay_ms = int(spec.delay * 1000)
    for i in range(d.ramp, len(d.blocks)):
        prev, b = d.blocks[i - 1], d.blocks[i]
        assert all(stamp[p] + delay_ms <= stamp[b.hash] + 1 for p in b.header.direct_parents())
        if stamp[b.hash] - stamp[prev.hash] < delay_ms - 1:
            assert prev.hash not in b.header.direct_parents()
    assert all(len(b.header.direct_parents()) == 1 for b in d.blocks[: d.ramp // 2])
    # every honest window spend is accepted on the final chain: no output of the pool was lost to another chain
    gd = reference.Ghostdag(d.blocks, d.params.genesis.hash, d.params.genesis.bits, d.params.ghostdag_k)
    spoiled_txids = {s["txid"] for s in d.spoiled.values()}
    _u, _chain, accepted, merged = reference.expected_utxo_set(d.blocks, gd, d.sinks[-1], d.params.genesis.hash, spoiled_txids)
    refused = [tx.id() for b in d.blocks if b.hash in merged for tx in b.transactions[1:]
               if tx.id() not in accepted and tx.id() not in spoiled_txids]
    assert not refused
    # spoiled spends are still never accepted on the wide DAG
    assert len(d.spoiled) == 2 and d.sinks[-1] not in d.spoiled


def test_wide_same_seed_same_hashes(wide):
    spec, d = wide
    again = dagmod.build(spec)
    assert [b.hash for b in again.blocks] == [b.hash for b in d.blocks]
    chain = dagmod.build(dagmod.DagSpec(**{**WIDE, "own_blocks_delayed": False, "pool_factor": 3}))
    assert chain.facts["mean_window_parents"] < 1.2  # without the rule one miner builds a chain (a spoiled sibling is merged)


def test_gap_source_without_strata_is_the_rngs_own_draw():
    import random

    spec = dagmod.DagSpec(**{**WIDE, "own_blocks_delayed": False})
    draw = dagmod.gap_source(spec, random.Random(3), 4.0, 0)
    twin = random.Random(3)
    assert [draw() for _ in range(5)] == [twin.expovariate(4.0) for _ in range(5)]


def test_gap_strata_are_the_same_arrivals_in_another_order():
    import random

    def strata(seed, midx=0):
        spec = dagmod.DagSpec(**{**WIDE, "seed": seed, "gap_stratum_blocks": 16})
        rng = random.Random(seed)
        draw = dagmod.gap_source(spec, rng, 8.0, midx)
        got = [[draw() for _ in range(16)] for _ in range(3)]
        assert rng.random() == random.Random(seed).random()  # the shuffle draws nothing from the generator's rng
        return got

    a, b = strata(11), strata(2**31 + 12)
    for s in a + b:
        assert abs(sum(s) - 16 / 8.0) < 1e-9  # a stratum spans exactly the time the rate gives it
        assert sorted(s) == sorted(a[0])  # the same set of gaps: the exponential's quantiles
    assert max(a[0]) / min(a[0]) > 50  # bursts and lulls are still there
    assert a[0] != a[1] and a[0] != b[0] and a == strata(11) and a != strata(11, midx=1)


def test_wide_dag_with_gap_strata(wide):
    """With a stratum of delay x bps gaps every delay holds as many blocks:
    the window's span and width no longer follow the seed."""
    spec, _d = wide
    facts = []
    for seed in (11, 12):
        s = dagmod.DagSpec(**{**WIDE, "seed": seed, "gap_stratum_blocks": 4})
        d = dagmod.build(s)
        if seed == 11:
            assert [b.hash for b in dagmod.build(s).blocks] == [b.hash for b in d.blocks]
        window = d.blocks[d.ramp :]
        assert len(window) == s.window_blocks and all(len(b.transactions) - 1 == s.tx_per_block for b in window)
        facts.append(d.facts)
    for f in facts:
        # 39 gaps of the window: whole strata of 1 s each but for the two ends
        assert abs(f["window_vtime_s"] - 39 / spec.bps) <= 1.0, f["window_vtime_s"]
        assert 0.65 * 5 <= f["mean_window_parents"] <= 1.35 * 5
    assert abs(facts[0]["mean_window_parents"] - facts[1]["mean_window_parents"]) < 0.5
