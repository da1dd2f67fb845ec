"""The plain reference against the program's own implementations, where the
program's are golden-tested against the upstream node's vectors: GHOSTDAG on
random DAGs with a k small enough to colour blocks red, work from compact
targets.  (The sighash and the verdicts are held against the program's in
``test_dag.py``, on the generator's own spends.)"""

import random
from types import SimpleNamespace

import pytest

from benchmarks import reference

BITS = 0x207FFFFF


def _random_dag(seed: int, n: int, width: int):
    """[(hash, parents)]: each block points at 1-3 of the blocks that were
    tips ``width`` blocks ago (so anticones of about ``width`` blocks)."""
    rng = random.Random(seed)
    genesis = b"\x01" + bytes(31)
    blocks, tips_then = [], [[genesis]]
    tips = {genesis}
    for i in range(n):
        seen = tips_then[max(0, len(tips_then) - 1 - rng.randrange(width))]
        parents = rng.sample(sorted(seen), min(len(seen), rng.randint(1, 3)))
        h = rng.randbytes(32)
        blocks.append((h, parents))
        tips = (tips - set(parents)) | {h}
        tips_then.append(sorted(tips))
    return genesis, blocks


def _wide_dag(seed: int, n: int, width: int):
    """[(hash, parents)] as one miner with delayed own blocks builds it: every
    block points at *all* the tips of the DAG as it stood ``width`` blocks
    ago (at most 16), so anticones are ``width`` blocks wide throughout."""
    rng = random.Random(seed)
    genesis = b"\x01" + bytes(31)
    blocks, tips_then = [], [[genesis]]
    tips = {genesis}
    for i in range(n):
        parents = tips_then[max(0, len(tips_then) - width)][:16]
        h = rng.randbytes(32)
        blocks.append((h, parents))
        tips = (tips - set(parents)) | {h}
        tips_then.append(sorted(tips))
    return genesis, blocks


def _program_ghostdag(genesis, blocks, k):
    from kaspa_tpu.consensus.model.header import Header
    from kaspa_tpu.consensus.processes.ghostdag import GhostdagManager
    from kaspa_tpu.consensus.reachability import ORIGIN, ReachabilityService
    from kaspa_tpu.consensus.stores import ConsensusStorage

    def header(block_hash, parents):
        hd = Header(version=1, parents_by_level=[parents], hash_merkle_root=bytes(32), accepted_id_merkle_root=bytes(32),
                    utxo_commitment=bytes(32), timestamp=0, bits=BITS, nonce=0, daa_score=0, blue_work=0, blue_score=0,
                    pruning_point=bytes(32))
        hd._hash_cache = block_hash
        return hd

    storage, reach = ConsensusStorage(), ReachabilityService()
    mgr = GhostdagManager(genesis, k, storage.ghostdag, storage.relations, storage.headers, reach)
    storage.relations.insert(genesis, [ORIGIN])
    storage.headers.insert(header(genesis, [ORIGIN]))
    storage.ghostdag.insert(genesis, mgr.genesis_ghostdag_data())
    reach.add_block(genesis, ORIGIN, [], [ORIGIN])
    out = {}
    for h, parents in blocks:
        data = mgr.ghostdag(parents)
        storage.relations.insert(h, parents)
        storage.headers.insert(header(h, parents))
        storage.ghostdag.insert(h, data)
        reach.add_block(h, data.selected_parent, data.unordered_mergeset_without_selected_parent(), parents)
        out[h] = data
    return out


def _disagreements(gd, program, blocks):
    wrong = 0
    for h, _parents in blocks:
        d = program[h]
        blues = [m for m in gd.mergeset[h] if m not in gd.reds[h]]
        wrong += (d.selected_parent, d.blue_score, d.blue_work, list(d.mergeset_blues), set(d.mergeset_reds)) != (
            gd.selected_parent[h], gd.blue_score[h], gd.blue_work[h], blues, gd.reds[h]
        )
    return wrong


@pytest.mark.parametrize("seed,k,width,make", [
    (1, 2, 5, _random_dag), (2, 3, 8, _random_dag), (3, 1, 4, _random_dag), (4, 18, 6, _random_dag),
    (5, 2, 6, _wide_dag), (6, 3, 8, _wide_dag),  # simpa's shape; no block is red at its own k=102
])
def test_ghostdag_equals_the_program_on_random_dags(seed, k, width, make):
    genesis, blocks = make(seed, 400, width)
    as_blocks = [SimpleNamespace(hash=h, header=SimpleNamespace(bits=BITS, direct_parents=lambda p=p: p)) for h, p in blocks]
    gd = reference.Ghostdag(as_blocks, genesis, BITS, k)
    program = _program_ghostdag(genesis, blocks, k)
    if k < 18:
        assert sum(len(d.mergeset_reds) for d in program.values()) > 20  # the rule was really exercised
    assert _disagreements(gd, program, blocks) == 0
    # and a reference that colours by another k does not agree: the number separates
    if k < 18:
        assert _disagreements(reference.Ghostdag(as_blocks, genesis, BITS, k + 1), program, blocks) > 0


def test_work_of_equals_the_program():
    from kaspa_tpu.consensus.difficulty import calc_work

    for bits in (BITS, 0x207FFFFE, 0x1E7FFFFF, 0x1D00FFFF, 0x1B0404CB, 0x1C123456, 0x1A7FFFFF):
        assert reference.work_of(bits) == calc_work(bits), hex(bits)
