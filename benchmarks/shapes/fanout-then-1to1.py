"""Tx shape ``fanout-then-1to1``: coinbase outputs fan out 1->25 until every
miner holds ``pool_factor * tx_per_block`` outputs; from then on (*steady*)
every spend is 1->1 with a small fee (storage mass 0, compute mass 1,624).

(ISSUE 25's 1->10-then-1->2 plan assumed 5e10-sompi coinbase outputs;
simnet's are 5.5e9 at 8 BPS, so one wider fan-out replaces the split stage.
KIP-9 storage mass lets a block create about 275 such outputs.)

A coinbase output exists only on the chain of the block that paid it, and
simnet's maturity (8) is shorter than the DAG's width, so a coinbase output is
spent only ``COINBASE_DEPTH_DELAYS`` propagation delays deep: a spend of one
that a reorganisation took away would be refused with everything that descends
from it.  An output that stays invisible that long is dropped.

A shape is a class ``Shape(spec, params, miners, mass_calc, rng, samples)``
with ``steady``, ``select``, ``discarded``, ``mined`` and ``is_window_block``,
as ``dag.build`` calls them; another shape is another file here.
"""

from __future__ import annotations

from collections import deque

from benchmarks.dag import MassBudget, spend

FANOUT = 25
FEE = 2000  # sompi
COINBASE_DEPTH_DELAYS = 3.0


class Shape:
    def __init__(self, spec, params, miners, mass_calc, rng, samples: list):
        self.spec, self.params, self.miners, self.mass_calc, self.rng, self.samples = spec, params, miners, mass_calc, rng, samples
        self.tpb = spec.tx_per_block
        self.pool_target = spec.pool_factor * self.tpb
        self.sample_p = min(1.0, 8.0 * spec.sig_samples / max(1, spec.window_blocks * self.tpb))
        self.depth = max(params.coinbase_maturity, int(COINBASE_DEPTH_DELAYS * spec.delay * spec.bps) + 16)
        self.steady = False  # every pool full: every spend is 1->1 (off again only below 2 blocks' worth)
        self.seen_coinbases: set = set()

    def select(self, miner, view, pov_daa_score: int, n_blocks: int, spoil_cls, made: list) -> None:
        """Fill ``made`` with (tx, spent outpoint, spoil class or None, block
        index the outpoint was made in) for the template of block ``n_blocks``."""
        tpb, budget = self.tpb, MassBudget(self.params, self.mass_calc)
        # steady is the whole network's: a miner whose pool is full waits
        # (empty blocks) until every pool is, so the ramp stays cheap
        if all(len(m.pool) >= self.pool_target for m in self.miners):
            self.steady = True
        elif any(len(m.pool) < 2 * tpb for m in self.miners):
            self.steady = False

        if not self.steady:
            # ramp: fan mature coinbase outputs out first
            keep = deque()
            while miner.coinbases and len(made) < tpb:
                outpoint, paid_at = miner.coinbases.popleft()
                entry = view.get(outpoint)
                if entry is None or entry.block_daa_score + self.depth > pov_daa_score:
                    if pov_daa_score - paid_at < 3 * self.depth:
                        keep.append((outpoint, paid_at))  # not on this chain, or not deep enough yet
                    continue
                tx, _msg, _sig = spend(miner, outpoint, entry, FANOUT, FEE, self.mass_calc, None, self.rng)
                if not budget.fits(tx):
                    keep.append((outpoint, paid_at))
                    break
                made.append((tx, outpoint, None, -1))
            miner.coinbases.extendleft(reversed(keep))
        skipped = deque()
        while miner.pool and len(made) < tpb:
            outpoint, born = miner.pool.popleft()
            entry = view.get(outpoint)
            if entry is None:  # its block is not in this block's past yet
                if n_blocks - born < self.depth:
                    skipped.append((outpoint, born))
                continue
            if not self.steady:
                skipped.append((outpoint, born))  # the ramp only fans out
                break
            cls = spoil_cls if spoil_cls and not any(m[2] for m in made) else None
            tx, msg, sig = spend(miner, outpoint, entry, 1, FEE, self.mass_calc, cls, self.rng)
            if not budget.fits(tx):
                skipped.append((outpoint, born))
                break
            made.append((tx, outpoint, cls, born))
            if cls is not None or (self.steady and self.rng.random() < self.sample_p):
                self.samples.append((n_blocks, tx.id(), miner.pubkey, msg, sig, cls is None))
        miner.pool.extendleft(reversed(skipped))

    def discarded(self, miner, made: list) -> None:
        """The template was not mined: its outputs go back to the pool."""
        miner.pool.extendleft((m[1], m[3]) for m in reversed(made))

    def mined(self, miner, block, made: list, index: int) -> None:
        """Block ``index`` is in: its spends' outputs and (on the ramp) its
        coinbase's become spendable."""
        from kaspa_tpu.consensus.model.tx import TransactionOutpoint

        for tx, _outpoint, cls, _born in made:
            if cls is None:  # a spoiled spend's outputs never exist
                txid = tx.id()
                miner.pool.extend((TransactionOutpoint(txid, j), index) for j in range(len(tx.outputs)))
        coinbase = block.transactions[0]
        # sibling blocks of one miner over the same parents carry the same coinbase
        if not self.steady and coinbase.id() not in self.seen_coinbases:
            self.seen_coinbases.add(coinbase.id())
            for j, out in enumerate(coinbase.outputs):
                for m in self.miners:
                    if out.script_public_key == m.spk:
                        m.coinbases.append((TransactionOutpoint(coinbase.id(), j), block.header.daa_score))

    def is_window_block(self, block, made: list) -> bool:
        return len(block.transactions) - 1 == self.tpb and all(len(m[0].outputs) == 1 for m in made)
