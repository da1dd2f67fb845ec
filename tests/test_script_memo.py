"""The script-verdict memo over the signature cache (ISSUE 34, txscript/batch.py).

A transaction whose every input took a batch lane and was answered valid is
remembered, with its signature scripts, over the amounts and scripts of the
outputs it spent, and is not collected again.  These tests pin that a replay
with the memo leaves the state of a replay without it, what the key holds and
what it leaves out, which transactions are never remembered, that the checks
which read the caller's context run on a hit, who owns the memo, and what its
hits do to the cache counters.  CPU, XLA ladder at bucket 8; nothing here is a
device number.
"""

import dataclasses

import pytest

from benchmarks import control_relayed
from kaspa_tpu.consensus.consensus import Consensus
from kaspa_tpu.consensus.model import ComputeCommit
from kaspa_tpu.consensus.processes.transaction_validator import (
    SEQUENCE_LOCK_TIME_DISABLED,
    TransactionValidator,
    TxRuleError,
)
from kaspa_tpu.observability import trace
from kaspa_tpu.ops import dispatch as coalesce
from kaspa_tpu.pipeline.pipeline import ConsensusPipeline
from kaspa_tpu.sim.simulator import SimConfig, simulate
from kaspa_tpu.txscript import batch, standard
from kaspa_tpu.txscript.caches import SigCache
from tests.test_txscript_multisig_batch import (  # noqa: F401 - _sync_device_lane is that file's autouse fixture
    KEYS,
    PARAMS,
    _counters,
    _mixed_tx,
    _moved,
    _msg,
    _multisig_spend,
    _sync_device_lane,
    _tx,
)

POV = 10


def _verdicts(tv, txs, pov=POV, traffic_class=None):
    """token -> None | message, through one checker of ``tv`` and one dispatch."""
    checker = tv.new_checker(traffic_class)
    for token, (tx, entries) in enumerate(txs):
        checker.collect_tx(token, tx, entries, pov_daa_score=pov)
    return {t: None if e is None else str(e) for t, e in checker.dispatch().items()}


def _held(tv) -> int:
    return len(tv.tx_memo._map)


VM_REDEEM = bytes([0x51, 0x87])  # OP_1 OP_EQUAL behind a hash: no batch lane takes it, the host VM does
VM_SPK = standard.pay_to_script_hash_script(VM_REDEEM)
VM_SIGNATURE_SCRIPT = bytes([0x51, len(VM_REDEEM)]) + VM_REDEEM


def _vm_spend(beside_p2pk: bool):
    """A sound spend with an input in the host VM's lane, alone or after a P2PK input."""
    tx, entries = _tx(([(standard.pay_to_pub_key(KEYS[3].pub32), 1)] if beside_p2pk else []) + [(VM_SPK, 0)])
    tx.inputs[-1].signature_script = VM_SIGNATURE_SCRIPT
    if beside_p2pk:
        tx.inputs[0].signature_script = standard.schnorr_signature_script(KEYS[3].schnorr(_msg(tx, entries, 0, False)), 1)
    return tx, entries


# --------------------------------------------------------------------------
# (a) equivalence: a wide simulated DAG replayed with the memo and without
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def wide_dag():
    """8 blocks/s x 2 s of delay, 8 miners: up to 8 parents a block, and a sink
    that hops between chains, so mergesets are collected again and again."""
    coalesce.configure(0)
    return simulate(SimConfig(bps=8, delay=2.0, num_miners=8, num_blocks=48, txs_per_block=3, seed=34))


def _replayed_state(res, pipelined: bool, memo_answers: bool) -> dict:
    consensus = Consensus(res.params)
    if not memo_answers:
        consensus.transaction_validator.tx_memo.get = lambda key: None
    before = _counters()
    if pipelined:
        pipe = ConsensusPipeline(consensus, workers=2)
        try:
            statuses = [f.result(timeout=600) for f in [pipe.submit(b) for b in res.blocks]]
        finally:
            pipe.shutdown()
    else:
        statuses = [consensus.validate_and_insert_block(b) for b in res.blocks]
    sink = consensus.sink()
    consensus._move_utxo_position(sink)
    chain = [b.hash for b in res.blocks if consensus.reachability.is_chain_ancestor_of(b.hash, sink)]
    return {
        # pipelined, a side block is utxo_pending or utxo_valid by whether the sink search ever walked it:
        # of the statuses, what the order of arrival alone decides
        "final_statuses": [s == "disqualified" if pipelined else s for s in (consensus.storage.statuses.get(b.hash) for b in res.blocks)],
        "sink_status": consensus.storage.statuses.get(sink),
        "sink": sink,
        "utxo_set": {(op.transaction_id, op.index): e for op, e in consensus.utxo_set.items()},
        "accepted_ids": {b: consensus.acceptance_data.get(b) for b in chain},
        "commitments": {b: consensus.multisets[b].finalize() for b in chain},
        "returned": statuses if not pipelined else None,  # a pipelined status is by the moment it is read
        "memo_hits": _moved(before, "txscript_tx_memo_hits"),
    }


@pytest.fixture(scope="module")
def in_order_with_memo(wide_dag):
    return _replayed_state(wide_dag, pipelined=False, memo_answers=True)


@pytest.mark.parametrize("pipelined", [False, True], ids=["in_order", "pipelined"])
def test_replay_with_the_memo_leaves_the_state_of_a_replay_without(wide_dag, in_order_with_memo, pipelined):
    with_memo = dict(_replayed_state(wide_dag, pipelined, memo_answers=True) if pipelined else in_order_with_memo)
    without = _replayed_state(wide_dag, pipelined, memo_answers=False)
    assert with_memo.pop("memo_hits") > 0 and without.pop("memo_hits") == 0
    assert with_memo == without
    assert with_memo["sink"] == wide_dag.sink and len(with_memo["accepted_ids"]) > 1


# --------------------------------------------------------------------------
# (b) the key: what a verdict is a function of, and nothing else
# --------------------------------------------------------------------------


def _copy(tx):
    return dataclasses.replace(tx, inputs=[dataclasses.replace(i) for i in tx.inputs])


def _other_signature_script(tx, entries):
    """The same id under a malleated signature script: a push of the same 65
    bytes through OP_PUSHDATA1, which the P2PK lane does not take for canonical."""
    tx = _copy(tx)
    tx.inputs[0].signature_script = bytes([0x4C]) + tx.inputs[0].signature_script
    return tx, entries


def _other_commit(tx, entries):
    tx = _copy(tx)
    tx.inputs[0].compute_commit = ComputeCommit.sigops(2)
    return tx, entries


def _other_entry(**changed):
    return lambda tx, entries: (tx, [dataclasses.replace(entries[0], **changed)] + entries[1:])


KEY_CASES = {
    "the_same_again": (lambda tx, entries: (tx, entries), True),
    "another_signature_script_same_id": (_other_signature_script, False),
    "another_sig_op_commit_same_id": (_other_commit, False),
    "another_amount_spent": (_other_entry(amount=10_001), False),
    "another_script_public_key_spent": (_other_entry(script_public_key=standard.pay_to_pub_key(KEYS[4].pub32)), False),
    "another_daa_score_on_the_spent_output": (_other_entry(block_daa_score=3), True),
    "spent_output_of_a_coinbase": (_other_entry(is_coinbase=True), True),
}


@pytest.mark.parametrize("case", sorted(KEY_CASES))
def test_key_holds_what_the_verdict_reads_and_nothing_else(case):
    change, hits = KEY_CASES[case]
    tv = TransactionValidator(PARAMS)
    tx, entries = _mixed_tx(["schnorr", "ecdsa"])
    assert _verdicts(tv, [(tx, entries)]) == {0: None} and _held(tv) == 1
    again, entries_again = change(tx, entries)
    assert again.id() == tx.id()
    before = _counters()
    verdict = _verdicts(tv, [(again, entries_again)])[0]
    assert _moved(before, "txscript_tx_memo_lookups") == 1
    assert _moved(before, "txscript_tx_memo_hits") == (1 if hits else 0)
    # a miss is collected as ever: what changed was under a signature, so it is refused
    assert (verdict is None) == hits


# --------------------------------------------------------------------------
# (c) scope: which transactions are never remembered
# --------------------------------------------------------------------------


def _rerun_that_accepts(monkeypatch):
    """A sound 2-of-3 whose walk is made to refuse: the host VM re-runs the
    input and accepts it, reading the caller's context as it does."""
    monkeypatch.setattr(batch._MultisigInput, "accepted", lambda self: False)
    return _multisig_spend(2, 3, False, [0, 2])


SCOPE_CASES = {
    "an_input_in_the_host_vm": (lambda mp: _vm_spend(beside_p2pk=False), True),
    "a_host_vm_input_beside_a_batch_input": (lambda mp: _vm_spend(beside_p2pk=True), True),
    "a_multisig_the_vm_re_ran": (_rerun_that_accepts, True),
    "a_multisig_the_vm_re_ran_and_refused": (lambda mp: _multisig_spend(2, 3, False, [0, 2], spoil="flipped_byte"), False),
    "an_invalid_signature": (lambda mp: _mixed_tx(["schnorr", "ecdsa"], bad=(1,)), False),
    "a_signature_script_refused_at_collect": (lambda mp: _other_signature_script(*_mixed_tx(["schnorr"])), False),
}


@pytest.mark.parametrize("case", sorted(SCOPE_CASES))
def test_what_the_memo_never_holds(case, monkeypatch):
    make, valid = SCOPE_CASES[case]
    tv = TransactionValidator(PARAMS)
    spend = make(monkeypatch)
    before = _counters()
    for _ in range(3):  # collected anew, and answered the same, every time
        verdict = _verdicts(tv, [spend])[0]
        assert (verdict is None) == valid
        assert _held(tv) == 0
    assert _moved(before, "txscript_tx_memo_lookups") == 3 and _moved(before, "txscript_tx_memo_hits") == 0


def test_a_refused_transaction_does_not_keep_its_neighbours_out():
    tv = TransactionValidator(PARAMS)
    block = [_mixed_tx(["schnorr", "multisig"]), _mixed_tx(["ecdsa"], bad=(0,)), _multisig_spend(2, 3, True, [1, 2])]
    first = _verdicts(tv, block)
    assert first[0] is None and first[1] == "invalid signature" and first[2] is None
    assert _held(tv) == 2
    before = _counters()
    assert _verdicts(tv, block) == {**first, 1: "invalid signature (cached)"}
    assert _moved(before, "txscript_tx_memo_hits") == 2 and _moved(before, "txscript_batch_sigcache_skips") == 1


# --------------------------------------------------------------------------
# (d) what reads the caller's context runs on a hit
# --------------------------------------------------------------------------

MATURITY = PARAMS.coinbase_maturity
CONTEXT_CASES = {
    # name: (change to the spent output, sequence of the input, pov that passes, pov that fails, the rule's words)
    "immature_coinbase_spend": ({"is_coinbase": True, "block_daa_score": 50}, SEQUENCE_LOCK_TIME_DISABLED, 50 + MATURITY, 50 + MATURITY - 1, "immature coinbase"),
    "unmet_sequence_lock": ({"block_daa_score": 50}, 20, 70, 69, "sequence lock"),
}


@pytest.mark.parametrize("case", sorted(CONTEXT_CASES))
def test_context_checks_run_although_the_memo_holds_the_transaction(case):
    changed, sequence, pov_ok, pov_fails, words = CONTEXT_CASES[case]
    tv = TransactionValidator(PARAMS)
    tx, entries = _tx([(standard.pay_to_pub_key(KEYS[3].pub32), 1)])
    tx.inputs[0].sequence = sequence
    entries = [dataclasses.replace(entries[0], **changed)]
    tx.inputs[0].signature_script = standard.schnorr_signature_script(KEYS[3].schnorr(_msg(tx, entries, 0, False)), 1)
    tx.storage_mass = tv.mass_calculator.calc_contextual_masses(tx, entries)

    def validate(pov):
        checker = tv.new_checker()
        fee = tv.validate_populated_transaction_and_get_fee(tx, entries, pov, checker=checker, token=0)
        return fee, checker.memo_hits(), checker.dispatch()

    assert validate(pov_ok) == (1_000, 0, {0: None}) and _held(tv) == 1
    assert validate(pov_ok) == (1_000, 1, {0: None})
    with pytest.raises(TxRuleError, match=words):
        validate(pov_fails)
    # the same outpoint under the score another chain gave it: the memo answers, the context decides
    entries = [dataclasses.replace(entries[0], block_daa_score=entries[0].block_daa_score + 1)]
    with pytest.raises(TxRuleError, match=words):
        validate(pov_ok)
    assert validate(pov_ok + 1) == (1_000, 1, {0: None})


def test_wrong_mass_commitment_is_refused_on_a_hit():
    tv = TransactionValidator(PARAMS)
    tx, entries = _mixed_tx(["schnorr"])
    tx.storage_mass = tv.mass_calculator.calc_contextual_masses(tx, entries)
    assert _verdicts(tv, [(tx, entries)]) == {0: None}
    tx.storage_mass += 1  # neither in the id nor under a signature: the memo still holds the transaction
    checker = tv.new_checker()
    with pytest.raises(TxRuleError, match="wrong mass commitment"):
        tv.validate_populated_transaction_and_get_fee(tx, entries, POV, checker=checker, token=0)


# --------------------------------------------------------------------------
# (e) ownership: the validator's, and nothing on the transaction objects
# --------------------------------------------------------------------------


def test_a_fresh_consensus_over_the_same_transactions_starts_cold(wide_dag, in_order_with_memo):
    """The benchmark's build, its replay and a second pass are separate
    ``Consensus`` instances over the *same* block objects."""
    first = in_order_with_memo
    assert first["memo_hits"] > 0
    consensus = Consensus(wide_dag.params)
    tv = consensus.transaction_validator
    assert _held(tv) == 0 and tv.tx_memo is not Consensus(wide_dag.params).transaction_validator.tx_memo
    before = _counters()
    asked_cold = False
    for block in wide_dag.blocks:
        consensus.validate_and_insert_block(block)
        if not asked_cold and _moved(before, "txscript_tx_memo_lookups"):
            # the first transactions this instance was asked about: none was known to it
            asked_cold = _moved(before, "txscript_tx_memo_hits") == 0 and tv.tx_memo.hits == 0
            assert asked_cold
    assert asked_cold and _moved(before, "txscript_tx_memo_hits") == first["memo_hits"] == tv.tx_memo.hits
    fields = {f.name for f in dataclasses.fields(wide_dag.blocks[-1].transactions[0])}
    assert fields == {"version", "inputs", "outputs", "lock_time", "subnetwork_id", "gas", "payload", "storage_mass", "_id_cache"}


def test_the_memo_is_the_bounded_map_the_signature_cache_is():
    tv = TransactionValidator(PARAMS)
    assert type(tv.tx_memo) is type(tv.sig_cache) is SigCache and tv.tx_memo.size == tv.sig_cache.size == 10_000
    checker = tv.new_checker()
    assert checker.tx_memo is tv.tx_memo and checker.sig_cache is tv.sig_cache
    tv.tx_memo = SigCache(size=2, seed=1)
    spends = [_mixed_tx(classes) for classes in (["schnorr"], ["ecdsa"], ["multisig"], ["schnorr", "ecdsa"])]
    assert _verdicts(tv, spends) == dict.fromkeys(range(4))
    assert _held(tv) == 2  # the cache's random eviction, at the cache's bound


# --------------------------------------------------------------------------
# (f) the counters and the span
# --------------------------------------------------------------------------

COUNTER_CASES = {
    # name: (classes of the inputs, signature checks the verdict stands for, traffic class)
    "p2pk_on_the_block_path": (["schnorr", "ecdsa"], 2, None),
    "multisig_pairs_on_the_block_path": (["multisig", "schnorr"], 4 + 1, None),
    "p2pk_in_an_ingest_wave": (["schnorr"], 1, "standalone_tx"),
}


@pytest.mark.parametrize("case", sorted(COUNTER_CASES))
def test_a_hit_counts_as_the_signature_checks_it_answered(case):
    classes, checks, traffic_class = COUNTER_CASES[case]
    path, other = ("block", "tx") if traffic_class is None else ("tx", "block")
    tv = TransactionValidator(PARAMS)
    spend = _mixed_tx(classes)
    before = _counters()
    assert _verdicts(tv, [spend], traffic_class=traffic_class) == {0: None}
    assert _moved(before, f"txscript_sig_cache_{path}_lookups") == checks and _moved(before, f"txscript_sig_cache_{path}_hits") == 0
    assert _moved(before, "txscript_batch_jobs") == checks
    before = _counters()
    assert _verdicts(tv, [spend], traffic_class=traffic_class) == {0: None}
    # the relayed cell's reading: every check of the block asked, every one answered without the device
    assert _moved(before, f"txscript_sig_cache_{path}_lookups") == _moved(before, f"txscript_sig_cache_{path}_hits") == checks
    assert _moved(before, f"txscript_sig_cache_{other}_lookups") == 0
    assert _moved(before, "txscript_tx_memo_lookups") == _moved(before, "txscript_tx_memo_hits") == 1
    assert _moved(before, "txscript_batch_jobs") == 0 and _moved(before, "secp_device_jobs") == 0


def test_a_wave_fills_the_memo_for_the_block_that_follows():
    tv = TransactionValidator(PARAMS)
    spend = _mixed_tx(["schnorr", "multisig"])
    assert _verdicts(tv, [spend], traffic_class="standalone_tx") == {0: None}
    before = _counters()
    assert _verdicts(tv, [spend]) == {0: None}
    assert _moved(before, "txscript_sig_cache_block_lookups") == _moved(before, "txscript_sig_cache_block_hits") == 5
    assert _moved(before, "txscript_tx_memo_hits") == 1


def test_emptying_the_signature_cache_empties_the_memo_with_it():
    """The premise of ``control_relayed.sigcache_holds_nothing``: with
    ``SigCache.get`` answering nothing, no memo answers in the cache's place."""
    tv = TransactionValidator(PARAMS)
    spend = _mixed_tx(["schnorr", "multisig"])
    assert _verdicts(tv, [spend]) == {0: None} and _held(tv) == 1
    before = _counters()
    with control_relayed.sigcache_holds_nothing():
        assert _verdicts(tv, [spend]) == {0: None}
    assert _moved(before, "txscript_tx_memo_lookups") == 1 and _moved(before, "txscript_tx_memo_hits") == 0
    assert _moved(before, "txscript_sig_cache_block_lookups") == 5 and _moved(before, "txscript_sig_cache_block_hits") == 0
    assert _moved(before, "txscript_batch_jobs") == 5 == _moved(before, "secp_device_jobs")
    before = _counters()
    assert _verdicts(tv, [spend]) == {0: None}
    assert _moved(before, "txscript_tx_memo_hits") == 1 and _moved(before, "txscript_batch_jobs") == 0


def test_collect_span_carries_the_memo_hits(wide_dag):
    trace.set_capture(1 << 16)
    trace.drain()
    try:
        state = _replayed_state(wide_dag, pipelined=False, memo_answers=True)
        spans = [s for s in trace.drain() if s["name"] == "txscript.collect"]
    finally:
        trace.set_capture(0)
    assert spans and all({"txs", "jobs", "multisig", "memo_hits"} <= set(s["attrs"]) for s in spans)
    assert sum(s["attrs"]["memo_hits"] for s in spans) == state["memo_hits"] > 0
    assert all(s["attrs"]["memo_hits"] + s["attrs"]["jobs"] <= s["attrs"]["txs"] for s in spans)  # 1 -> 1 P2PK spends


def test_get_metrics_shows_the_memo_beside_the_signature_cache(wide_dag):
    from kaspa_tpu.p2p import Node
    from kaspa_tpu.rpc import RpcCoreService

    node = Node(Consensus(wide_dag.params), "memo-test")
    try:
        for block in wide_dag.blocks:
            node.submit_block(block)
        memo = node.consensus.transaction_validator.tx_memo
        metrics = RpcCoreService(node.consensus, node.mining, address_prefix="kaspasim").get_metrics()
    finally:
        node.shutdown()
    assert metrics["tx_memo_hits"] == memo.hits and metrics["tx_memo_lookups"] == memo.hits + memo.misses > 0
    assert "sig_cache_hits" in metrics


def test_checkers_on_many_threads_share_the_memo():
    """Stage workers, the virtual thread and RPC handlers each make checkers
    of the one validator: the verdicts are those of one thread, nothing
    refused is ever held, and every lookup is counted once."""
    import sys
    import threading

    tv = TransactionValidator(PARAMS)
    block = [_mixed_tx(["schnorr", "multisig"]), _mixed_tx(["ecdsa"], bad=(0,)), _mixed_tx(["ecdsa", "schnorr"]), _vm_spend(beside_p2pk=True)]
    expected = {t: v is None for t, v in _verdicts(TransactionValidator(PARAMS), block).items()}
    threads, rounds, seen, errors = 8, 5, [], []

    def work():
        try:
            for _ in range(rounds):
                seen.append({t: v is None for t, v in _verdicts(tv, block).items()})
        except Exception as e:  # noqa: BLE001 - reported by the assertion below
            errors.append(e)

    before, interval = _counters(), sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=work) for _ in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=300)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(w.is_alive() for w in workers)
    assert seen == [expected] * (threads * rounds) and expected == {0: True, 1: False, 2: True, 3: True}
    assert _held(tv) == 2  # the two sound transactions of batch lanes alone
    lookups, hits = _moved(before, "txscript_tx_memo_lookups"), _moved(before, "txscript_tx_memo_hits")
    assert lookups == 4 * threads * rounds and hits == tv.tx_memo.hits and lookups == tv.tx_memo.hits + tv.tx_memo.misses
    assert hits >= 2 * threads * (rounds - 1)  # from a thread's second round on, both are answered
