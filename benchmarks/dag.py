"""The benchmark's DAG generator: ``kaspa_tpu.sim.simulator.simulate`` copied
with six named edits (ISSUE 25; the fifth and sixth ISSUE 28), so that blocks fill to
a cell's ``tx_per_block`` and a few-thousand-block DAG builds inside set-up.

1. Signer: per miner a fixed key and a running nonce point (``k += 1``,
   ``R += G``): a valid BIP340 signature costs one point addition, not a
   pure-Python scalar ladder (``sim/sigbatch.py``'s trick with a fixed key).
2. Tx shape: which spends fill a block is the traffic file's ``tx_shape``,
   a module of ``shapes/`` found by that name (``shapes/fanout-then-1to1.py``
   is the one ISSUE 25 describes).  The blocks before the first one from which
   every block is what the shape calls a window block (exactly
   ``tx_per_block`` steady spends) are the *ramp*.
3. A few seeded window blocks carry one spend whose signature is spoiled
   (flipped byte of s / signed over another message — the two classes only
   the device decides).  Such a block is a late sibling of its miner's
   previous block and is never offered as a selected parent, so it is merged
   by honest blocks, its spoiled spend is never accepted and the DAG goes on.
4. A tip frontier per miner and an output pool per miner replace the scans
   over every mined block and the whole UTXO view.
5. ``own_blocks_delayed`` (a key of the configuration's ``network``; absent =
   false): a miner's own block reaches it after ``delay`` like anyone else's,
   as in simpa's network, where one aggregated miner therefore builds a DAG
   about ``delay * bps`` blocks wide.  Without it a miner sees its own block
   at once and one miner builds a chain.  The rule holds from the moment the
   shape is steady: the fan-out before it stays a chain, because a coinbase
   output exists on one selected chain only and the wide DAG's tips follow
   some 16 interleaved chains that do not meet for hundreds of blocks (with
   the rule on from genesis over half of the pool descended from coinbases
   the final chain never had, and was refused).  The blocks of the first two
   delays after the switch, while the DAG widens, count as ramp.  The rule
   draws nothing from the ``rng``: with the key absent every seed's DAG is
   what it was.
6. ``gap_stratum_blocks`` (a key of the traffic file; absent = 0 = every gap
   drawn from the ``rng`` as before): each miner's mining gaps come in strata
   of that many: the stratum's exponential quantiles, scaled to span exactly
   the time the rate gives them, in an order shuffled by the seed.  Every
   seed then has the same set of arrivals in another order, and with a
   stratum of ``delay * bps`` blocks every delay holds about as many blocks:
   the width of ``own_blocks_delayed``'s DAG no longer follows the seed
   (drawn freely, mean parents read 12.7-15.7 over a 150-block window and the
   rate followed them).  The shuffle has an ``rng`` of its own.

The consensus the DAG is built against runs in order with coalescing off: the
build *is* the in-order run of the program, and records the sink after every
block, so the in-order witness for any prefix the window gets through costs
no extra set-up.
"""

from __future__ import annotations

import heapq
import importlib
import math
import random
import time
from collections import deque
from dataclasses import dataclass, field

SPOIL_CLASSES = ("flipped_sig_byte", "wrong_message")


MAX_RAMP_BLOCKS = 600  # a ramp that has not settled by then never will


@dataclass
class DagSpec:
    bps: int
    delay: float
    miners: int
    tx_per_block: int
    window_blocks: int
    seed: int
    tx_shape: str = "fanout-then-1to1"
    spoiled_blocks: int = 0
    pool_factor: int = 3
    sig_samples: int = 24
    coinbase_maturity: int | None = None  # None: what simnet_params gives
    own_blocks_delayed: bool = False  # simpa's network: a miner's own block reaches it after `delay` too
    gap_stratum_blocks: int = 0  # > 0: mining gaps are seed-shuffled strata of that many exponential quantiles


@dataclass
class Dag:
    params: object
    blocks: list
    ramp: int  # blocks[:ramp] are replayed during set-up
    sinks: list  # sinks[i]: the in-order run's sink after blocks[:i + 1]
    spoiled: dict  # block hash -> {"txid", "cls", "index"}
    sig_samples: list  # (block index, txid, pubkey32, msg32, sig64, valid by construction)
    facts: dict = field(default_factory=dict)


class _Miner:
    def __init__(self, idx: int, rng: random.Random):
        from kaspa_tpu.consensus.processes.coinbase import MinerData
        from kaspa_tpu.crypto import eclib
        from kaspa_tpu.txscript import standard

        d = rng.randrange(1, eclib.N)
        pub = eclib.point_mul(eclib.G, d)
        self.d = d if pub[1] % 2 == 0 else eclib.N - d  # BIP340: even-y key
        self.pubkey = pub[0].to_bytes(32, "big")
        self.k = rng.randrange(1, eclib.N >> 1)
        self.R = eclib.point_mul(eclib.G, self.k)
        self.idx = idx
        self.spk = standard.pay_to_pub_key(self.pubkey)
        self.miner_data = MinerData(self.spk, extra_data=f"miner-{idx}".encode())
        self.pool: deque = deque()  # (outpoint, index of the block that made it), oldest first
        self.coinbases: deque = deque()  # (outpoint, daa score of the paying block)
        self.known: set = set()
        self.tips: set = set()
        self.parent_history: deque = deque(maxlen=2)  # parents of its last honest blocks
        self.prev_spoiled = False

    def sign(self, msg: bytes) -> bytes:
        """BIP340 signature over ``msg`` with the next nonce point."""
        from kaspa_tpu.crypto import eclib
        from kaspa_tpu.crypto.secp import schnorr_challenge

        self.k += 1
        self.R = eclib.point_add(self.R, eclib.G)
        kk = self.k if self.R[1] % 2 == 0 else eclib.N - self.k
        r = self.R[0].to_bytes(32, "big")
        e = schnorr_challenge(r, self.pubkey, msg)
        return r + ((kk + e * self.d) % eclib.N).to_bytes(32, "big")

    def learn(self, block_hash: bytes, parents) -> None:
        if block_hash in self.known:
            return
        self.known.add(block_hash)
        self.tips.difference_update(parents)
        self.tips.add(block_hash)


def spend(miner: _Miner, outpoint, entry, n_out: int, fee: int, mass_calc, spoil: str | None, rng):
    """One signed P2PK spend of ``entry`` into ``n_out`` equal outputs back
    to the miner.  Returns (tx, msg, sig)."""
    from kaspa_tpu.consensus import hashing as chash
    from kaspa_tpu.consensus.model import Transaction, TransactionInput, TransactionOutput
    from kaspa_tpu.consensus.model.tx import SUBNETWORK_ID_NATIVE, ComputeCommit
    from kaspa_tpu.txscript import standard

    share = (entry.amount - fee) // n_out
    outputs = [TransactionOutput(share, miner.spk) for _ in range(n_out)]
    outputs[0] = TransactionOutput(entry.amount - fee - share * (n_out - 1), miner.spk)
    tx = Transaction(
        0, [TransactionInput(outpoint, b"", 0, ComputeCommit.sigops(1))], outputs, 0, SUBNETWORK_ID_NATIVE, 0, b""
    )
    tx.storage_mass = mass_calc.calc_contextual_masses(tx, [entry])
    msg = chash.calc_schnorr_signature_hash(tx, [entry], 0, chash.SIG_HASH_ALL, chash.SigHashReusedValues())
    if spoil == "wrong_message":
        sig = miner.sign(bytes([msg[0] ^ 0x01]) + msg[1:])
    else:
        sig = miner.sign(msg)
        if spoil == "flipped_sig_byte":
            j = 32 + rng.randrange(32)
            sig = sig[:j] + bytes([sig[j] ^ (1 + rng.randrange(255))]) + sig[j + 1 :]
    tx.inputs[0].signature_script = standard.schnorr_signature_script(sig, chash.SIG_HASH_ALL)
    tx._id_cache = None
    return tx, msg, sig


class MassBudget:
    """What still fits into one block template under the block mass limit."""

    def __init__(self, params, mass_calc):
        from kaspa_tpu.consensus.mass import BlockMassLimits

        self.limits, self.mass_calc = BlockMassLimits.with_shared_limit(params.max_block_mass), mass_calc
        self.compute = self.transient = self.storage = 0

    def fits(self, tx) -> bool:
        """Take ``tx`` into the template if it fits."""
        from kaspa_tpu.consensus.mass import NonContextualMasses

        nc = self.mass_calc.calc_non_contextual_masses(tx)
        totals = NonContextualMasses(self.compute + nc.compute_mass, self.transient + nc.transient_mass)
        if not self.limits.would_fit(totals, self.storage + tx.storage_mass):
            return False
        self.compute, self.transient, self.storage = totals.compute_mass, totals.transient_mass, self.storage + tx.storage_mass
        return True


def gap_source(spec: DagSpec, rng: random.Random, lam: float, midx: int):
    """The next mining gap of one miner, as a function.  Without strata: the
    shared ``rng``'s next exponential draw, as ``simulate()`` has it."""
    n = spec.gap_stratum_blocks
    if not n:
        return lambda: rng.expovariate(lam)
    quantiles = [-math.log(1.0 - (j + 0.5) / n) for j in range(n)]
    scale = n / (lam * sum(quantiles))  # a stratum spans exactly n / lam seconds
    order = random.Random((spec.seed ^ 0x6A95) + midx)
    pending: list = []

    def draw() -> float:
        if not pending:
            pending.extend(q * scale for q in quantiles)
            order.shuffle(pending)
        return pending.pop()

    return draw


def build(spec: DagSpec, log=None) -> Dag:
    """Build the DAG against one authoritative in-order consensus."""
    from kaspa_tpu.consensus.consensus import Consensus
    from kaspa_tpu.consensus.params import simnet_params

    t_start = time.perf_counter()
    rng = random.Random(spec.seed)
    params = simnet_params(bps=spec.bps)
    if spec.coinbase_maturity is not None and spec.coinbase_maturity != params.coinbase_maturity:
        raise ValueError(f"coinbase_maturity {spec.coinbase_maturity} is not what simnet_params gives")
    consensus = Consensus(params)
    mass_calc = consensus.transaction_validator.mass_calculator
    ghostdag = consensus.storage.ghostdag
    miners = [_Miner(i, rng) for i in range(spec.miners)]
    genesis = params.genesis.hash
    for m in miners:
        m.learn(genesis, ())

    events = []
    seq = 0
    lam = spec.bps / spec.miners
    gaps = [gap_source(spec, rng, lam, m.idx) for m in miners]
    for m in miners:
        events.append((gaps[m.idx](), seq, m.idx))
        seq += 1
    heapq.heapify(events)

    tpb = spec.tx_per_block
    samples: list = []  # (block index, txid, pubkey32, msg32, sig64, valid by construction)
    shape = importlib.import_module(f"benchmarks.shapes.{spec.tx_shape}").Shape(spec, params, miners, mass_calc, rng, samples)
    blocks, vtimes, sinks, owners, parent_lists = [], [], [], [], []
    arrival = [0] * spec.miners  # per miner: next index of `blocks` to become visible
    spoiled: dict = {}
    spoiled_hashes: set = set()
    window_start = 0
    spoil_at: list = []  # window positions still to spoil, ascending
    total_txs = discarded = stalled = 0
    wide_since = None  # own_blocks_delayed: the mining time from which the rule has held without a break

    while len(blocks) - window_start < spec.window_blocks:
        if len(blocks) - spec.window_blocks > MAX_RAMP_BLOCKS:
            raise RuntimeError(
                f"the ramp did not settle: {len(blocks)} blocks built, last thin block at {window_start}"
            )
        vtime, _, midx = heapq.heappop(events)
        miner = miners[midx]
        delayed = spec.own_blocks_delayed and shape.steady
        if not delayed:
            wide_since = None
        elif wide_since is None:
            wide_since = vtime
        # blocks propagate after `delay` (a miner's own only where the
        # deployment says so); mining times only grow, so one index per miner
        # walks the list once
        i = arrival[midx]
        while i < len(blocks) and vtimes[i] + spec.delay <= vtime:
            if delayed or owners[i] != midx:
                miner.learn(blocks[i].hash, parent_lists[i])
            i += 1
        arrival[midx] = i

        n_window = len(blocks) - window_start
        spoil_cls = None
        if spoil_at and n_window >= spoil_at[0] and shape.steady and not miner.prev_spoiled and len(miner.parent_history) == miner.parent_history.maxlen:
            spoil_at.pop(0)
            spoil_cls = SPOIL_CLASSES[len(spoiled) % len(SPOIL_CLASSES)]
            parents = list(miner.parent_history[-1])  # a late sibling of its own previous block
        else:
            tips = sorted(miner.tips, key=lambda h: (ghostdag.get_blue_work(h), h), reverse=True)
            # a spoiled block is merged, never followed: it is not offered as
            # the selected parent (the parent of most blue work)
            while len(tips) > 1 and tips[0] in spoiled_hashes:
                tips.pop(0)
            parents = tips[: params.max_block_parents]
        made: list = []  # (tx, spent outpoint, spoil class or None, block index the outpoint was made in)
        n_samples = len(samples)

        def tx_selector(view, pov_daa_score, miner=miner, spoil_cls=spoil_cls, made=made):
            shape.select(miner, view, pov_daa_score, len(blocks), spoil_cls, made)
            return [m[0] for m in made]

        block = consensus.build_block_with_parents(
            parents, miner.miner_data, timestamp=int(vtime * 1000) + 1, tx_selector=tx_selector
        )
        if shape.steady and len(made) != tpb:
            # a miner without a full template waits: in the steady state a
            # block that cannot carry tx_per_block visible outputs is not mined
            # (its outputs go back to the pool; rare: the pool is sized for it)
            shape.discarded(miner, made)
            del samples[n_samples:]  # its signatures are in no block: the outpoints are spent again, honestly
            discarded += 1
            if spoil_cls is not None:
                spoil_at.insert(0, n_window + 1)  # an honest block first, then try again
            stalled += 1
            if stalled > 200:
                raise RuntimeError(f"{stalled} blocks in a row could not be filled at block {len(blocks)}")
            heapq.heappush(events, (vtime + gaps[midx](), seq, midx))
            seq += 1
            continue
        status = consensus.validate_and_insert_block(block)
        # a block whose own spend fails is disqualified from the chain as soon
        # as the virtual stage weighs it as a tip; honest blocks merge it
        if status not in ("utxo_valid", "utxo_pending") and not (spoil_cls and status == "disqualified"):
            raise RuntimeError(f"built block {len(blocks)} rejected: {status}")
        h = block.hash
        n_spends = len(block.transactions) - 1
        for tx, _outpoint, cls, _born in made:
            if cls is not None:  # its outputs never exist; its input is never offered again
                spoiled[h] = {"txid": tx.id(), "cls": cls, "index": len(blocks)}
                spoiled_hashes.add(h)
        shape.mined(miner, block, made, len(blocks))
        steady_block = shape.is_window_block(block, made) and (
            not spec.own_blocks_delayed or (wide_since is not None and vtime >= wide_since + 2 * spec.delay)
        )
        if not steady_block:
            window_start = len(blocks) + 1
            # spoiled blocks sit at seeded places in the first quarter of the
            # window: whatever prefix a run gets through holds them
            margin = min(4, spec.window_blocks // 4)
            hi = max(margin + spec.spoiled_blocks, spec.window_blocks // 4)
            spoil_at = sorted(random.Random(spec.seed ^ 0x5EED).sample(range(margin, hi), spec.spoiled_blocks))
        blocks.append(block)
        vtimes.append(vtime)
        owners.append(midx)
        parent_lists.append(tuple(parents))
        sinks.append(consensus.sink())
        total_txs += n_spends
        stalled = 0
        miner.prev_spoiled = spoil_cls is not None
        if spoil_cls is None:
            miner.parent_history.append(tuple(parents))
        if not delayed:
            miner.learn(h, parents)  # a miner sees its own block at once
        heapq.heappush(events, (vtime + gaps[midx](), seq, midx))
        seq += 1
        if log is not None and len(blocks) % 100 == 0:
            log(f"dag: {len(blocks)} blocks, window from {window_start}, pools {[len(m.pool) for m in miners]}, {time.perf_counter() - t_start:.1f} s")

    in_window = [s for s in spoiled.values() if s["index"] >= window_start]
    if len(in_window) < spec.spoiled_blocks:
        raise RuntimeError(f"placed {len(in_window)} of {spec.spoiled_blocks} spoiled blocks in the window")
    rng_s = random.Random(spec.seed ^ 0xA11CE)
    valid = sorted(s for s in samples if s[5] and s[0] >= window_start)
    picked = [s for s in samples if not s[5]] + rng_s.sample(valid, min(spec.sig_samples, len(valid)))
    window = blocks[window_start:]
    facts = {
        "blocks": len(blocks),
        "ramp_blocks": window_start,
        "window_blocks": len(window),
        "txs": total_txs,
        "window_txs": sum(len(b.transactions) - 1 for b in window),
        "widest_block_txs": max(len(b.transactions) - 1 for b in blocks),
        "spoiled_blocks": sorted(s["index"] for s in spoiled.values()),
        "window_vtime_s": vtimes[-1] - vtimes[window_start],
        "miners": spec.miners,
        "mean_window_parents": sum(len(p) for p in parent_lists[window_start:]) / len(window),
        "ghostdag_k": params.ghostdag_k,
        "max_block_parents": params.max_block_parents,
        "mergeset_size_limit": params.mergeset_size_limit,
        "coinbase_maturity": params.coinbase_maturity,
        "max_block_mass": params.max_block_mass,
        "discarded_thin_blocks": discarded,
        "build_seconds": time.perf_counter() - t_start,
    }
    return Dag(params, blocks, window_start, sinks, spoiled, picked, facts)
