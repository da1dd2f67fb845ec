"""What decides ``correct``: the state the measured consensus holds after the
window against ``reference.py`` (which knows nothing of the program) and
against the in-order run of the build (the program's other path, a second
witness).  Every number is a count of disagreements and its limit is 0: the
arithmetic is exact integer.
"""

from __future__ import annotations

from benchmarks import reference

HONEST = ("utxo_valid", "utxo_pending")
SPOILED = ("disqualified", "utxo_pending")  # never utxo_valid: its own spend fails


def compare_pass(dag, consensus, prefix: int, statuses: dict) -> dict:
    """``prefix`` blocks of the DAG went through ``consensus`` and resolved;
    ``statuses`` maps block index -> what its future said."""
    blocks = dag.blocks[:prefix]
    by_hash = {b.hash: b for b in blocks}
    genesis = dag.params.genesis.hash
    spoiled_hashes = {h for h in dag.spoiled if h in by_hash}
    spoiled_txids = {s["txid"] for s in dag.spoiled.values()}

    # GHOSTDAG by the reference, from parents and bits alone
    gd = reference.Ghostdag(blocks, genesis, dag.params.genesis.bits, dag.params.ghostdag_k)

    # the sink: the block of most blue work whose chain holds no failed spend
    ref_sink = max((b.hash for b in blocks if b.hash not in spoiled_hashes), key=lambda h: (gd.blue_work[h], h))
    utxos, chain, accepted, merged = reference.expected_utxo_set(blocks, gd, ref_sink, genesis, spoiled_txids)
    ref_commitment = reference.muhash_commitment(utxos)
    inorder_sink = dag.sinks[prefix - 1]

    sink = consensus.sink()
    commitment = consensus.multisets[sink].finalize()
    out = {
        "sink_vs_reference": int(sink != ref_sink),
        "sink_vs_inorder_run": int(sink != inorder_sink),
        "utxo_commitment_vs_reference": int(commitment != ref_commitment),
        "utxo_commitment_vs_inorder_run": int(commitment != by_hash[inorder_sink].header.utxo_commitment),
    }

    # the UTXO set itself, entry by entry
    consensus._move_utxo_position(sink)
    held = {
        (op.transaction_id, op.index): (
            e.amount, e.script_public_key.version, e.script_public_key.script, e.block_daa_score, e.is_coinbase
        )
        for op, e in consensus.utxo_set.items()
    }
    out["utxo_entries_vs_reference"] = len(set(held.items()) ^ set(utxos.items()))

    # which spends were accepted, over the whole selected chain
    got = set()
    for c in chain:
        got.update(consensus.acceptance_data.get(c, ()))
    got &= {tx.id() for b in blocks for tx in b.transactions}  # genesis' own coinbase is not among the blocks
    out["accepted_ids_vs_reference"] = len(got ^ accepted)

    # statuses: what the construction says of every block
    chain_set = set(chain)
    bad = 0
    for i, b in enumerate(blocks):
        final = consensus.storage.statuses.get(b.hash)
        allowed = SPOILED if b.hash in spoiled_hashes else HONEST
        if final not in allowed or (b.hash in chain_set and final != "utxo_valid"):
            bad += 1
        elif i in statuses and statuses[i] not in allowed:
            bad += 1
    out["bad_status_blocks"] = bad

    # GHOSTDAG: what the measured consensus stored for every block, and what the
    # headers carry (the in-order run's), against the reference's colouring
    store = consensus.storage.ghostdag
    wrong_stored = wrong_header = 0
    for b in blocks:
        h, got_gd = b.hash, store.get(b.hash) if store.has(b.hash) else None
        blues = [m for m in gd.mergeset[h] if m not in gd.reds[h]]
        if got_gd is None or (got_gd.selected_parent, got_gd.blue_score, got_gd.blue_work, list(got_gd.mergeset_blues), set(got_gd.mergeset_reds)) != (
            gd.selected_parent[h], gd.blue_score[h], gd.blue_work[h], blues, gd.reds[h]
        ):
            wrong_stored += 1
        if (b.header.blue_score, b.header.blue_work, b.header.daa_score) != (gd.blue_score[h], gd.blue_work[h], gd.daa_score[h]):
            wrong_header += 1
    out["ghostdag_vs_reference"] = wrong_stored
    out["header_scores_vs_reference"] = wrong_header

    # signatures: the reference's sighash and verdict on a seeded sample of
    # spends and on every spoiled spend, from the transaction and the output it
    # spends, against the program's sighash, the construction and what was accepted
    outputs = {(tx.id(), j): o for b in blocks for tx in b.transactions for j, o in enumerate(tx.outputs)}
    wrong = wrong_msg = 0
    for index, txid, _pub, msg, _sig, constructed_valid in dag.sig_samples:
        if index >= prefix:
            continue
        tx = next(t for t in blocks[index].transactions[1:] if t.id() == txid)
        op = tx.inputs[0].previous_outpoint
        spent = outputs[(op.transaction_id, op.index)]
        verdict, ref_msg = reference.p2pk_spend_verdict(
            tx, spent.value, spent.script_public_key.version, spent.script_public_key.script
        )
        wrong_msg += int(ref_msg != msg)
        if verdict != constructed_valid:
            wrong += 1
        elif blocks[index].hash in merged and (txid in got) != verdict:
            wrong += 1
    out["sighash_vs_reference"] = wrong_msg
    out["signature_verdicts_vs_reference"] = wrong
    return out
