"""Tx shape ``checkscripts-mix``: the grid of rusty-kaspa's
``consensus/benches/check_scripts.rs`` (one transaction of 2, 5, 10, 25, 50 or
100 signed inputs, every signature unique) as the steady traffic of a block,
over the three standard script classes.

Steady state, every block of every miner: **6 merges**, one of each grid size
g -> 1 output, and **6 splits** 1 -> g that re-mint what merges consume: 12
transactions, 198 signed inputs, 198 outputs, the UTXO set flat.  All inputs of
a merge are of one class and its output is of the same class; a split's g
outputs are of the class of its input: every (grid size, class) is a loop of
its own (g small outputs -> merge -> one large output -> split -> g small
outputs).  The class of grid size g in a miner's t-th steady block follows
``GROUPS``: multisig = ``GROUPS[t % 4]``, ECDSA = ``GROUPS[(t + 1) % 4]``,
Schnorr the other two groups, so over any 4 consecutive blocks of a miner
every grid size is merged twice from Schnorr pay-to-pubkey outputs, once from
ECDSA pay-to-pubkey outputs and once from 2-of-3 Schnorr multisig
pay-to-script-hash outputs: by input 1/2, 1/4, 1/4.  A multisig input is
signed by two of its three keys, ``SIGNERS[j % 4]`` for input j, and commits
the sig ops its script executes (2 when the first two keys sign, else 3): with
3 committed on every input a block whose 100-input merge is multisig would
weigh 398,000 g of sig ops alone and not fit the 500,000 g block.

KIP-9 storage mass prices every new output at 10**12 / value: the 192 small
outputs a block creates fit 500,000 g from 3.84e8 sompi each, so a small
output is worth ``VALUE`` = 4.2e8 and the ramp has to bring each miner 810 of
them (about 77 coinbases' worth; over 300 blocks for the four): coinbases are
fanned out to the three classes of the miner's own wallet first, then of the
wallet that lacks most; a wallet that is full merges half of it in two
*priming* blocks; a miner that is primed goes on fanning out for the others
until every miner is.

Spoiled blocks (``dag.build`` asks for one spoiled spend in each of 4 seeded
blocks) spoil one input of one merge, in turn: a Schnorr input that is not
input 0; an ECDSA input; the second signature of a multisig input; a multisig
input one of whose signatures is by a key outside its redeem script.  A
spoiled merge is never accepted, so its large output is missing two blocks
later: only grid sizes of ``SPARE`` carry one spare large output a miner, and a
spoiled spend is placed there (a template that cannot place it is given up,
and ``dag.build`` tries again after the miner's next honest block).

``compare.py`` asks ``reference.p2pk_spend_verdict`` about input 0 of every
sample, so ``samples`` holds honest spends whose input 0 is Schnorr
pay-to-pubkey only.  The other classes are held here: for every spoiled spend
and a seeded sample of honest ones ``reference_scripts.py`` gives its verdict
while the DAG is built, and a verdict that differs from the construction
raises.  And the shape *probes the program* when it is constructed: one 2-of-3
spend through ``BatchScriptChecker`` with the VM lane wired must leave
``txscript_vm_fallbacks`` where it was.  A program that sends multisig to the
host VM (37 ms a signature check, in Python) would take hours to build this
DAG, and would time the wrong thing.
"""

from __future__ import annotations

import random
from collections import deque

from benchmarks import reference_scripts
from benchmarks.dag import MassBudget

GRID = (2, 5, 10, 25, 50, 100)
GROUPS = ((100, 2), (50, 5), (25,), (10,))
SPARE = (2, 5, 10, 25)  # grid sizes with one spare large output a miner: where spoiled spends go
SIGNERS = ((0, 1), (0, 1), (0, 2), (1, 2))  # which two of the three keys sign multisig input j (j % 4)
CLASSES = ("schnorr", "ecdsa", "multisig")
VALUE = 420_000_000  # sompi of a small output
FEE = 2000  # sompi a transaction
COINBASE_DEPTH_DELAYS = 3.0
SPOILS = ("schnorr_input_not_first", "ecdsa_input", "multisig_second_signature", "multisig_outside_key")
SPOIL_CLASS = {SPOILS[0]: "schnorr", SPOILS[1]: "ecdsa", SPOILS[2]: "multisig", SPOILS[3]: "multisig"}
REFERENCE_SAMPLES = 8  # honest spends a build asks the reference about, beside every spoiled one


def class_of(g: int, t: int) -> str:
    """The class of grid size ``g`` in a miner's block ``t``."""
    if g in GROUPS[t % 4]:
        return "multisig"
    return "ecdsa" if g in GROUPS[(t + 1) % 4] else "schnorr"


class Key:
    """A secp256k1 key with a running nonce point (k += 1, R += G): a
    signature costs one point addition (``dag._Miner``'s trick)."""

    def __init__(self, rng: random.Random):
        from kaspa_tpu.crypto import eclib

        self.d = rng.randrange(1, eclib.N)
        pub = eclib.point_mul(eclib.G, self.d)
        self.pub33 = bytes([2 + (pub[1] & 1)]) + pub[0].to_bytes(32, "big")
        self.pub32 = pub[0].to_bytes(32, "big")
        self.d_even = self.d if pub[1] % 2 == 0 else eclib.N - self.d  # BIP340 signs with the even-y key
        self.k = rng.randrange(1, eclib.N >> 1)
        self.R = eclib.point_mul(eclib.G, self.k)

    def _next_nonce(self) -> None:
        from kaspa_tpu.crypto import eclib

        self.k += 1
        self.R = eclib.point_add(self.R, eclib.G)

    def schnorr(self, msg: bytes) -> bytes:
        from kaspa_tpu.crypto import eclib
        from kaspa_tpu.crypto.secp import schnorr_challenge

        self._next_nonce()
        kk = self.k if self.R[1] % 2 == 0 else eclib.N - self.k
        r = self.R[0].to_bytes(32, "big")
        return r + ((kk + schnorr_challenge(r, self.pub32, msg) * self.d_even) % eclib.N).to_bytes(32, "big")

    def ecdsa(self, msg: bytes) -> bytes:
        from kaspa_tpu.crypto import eclib

        self._next_nonce()
        r = self.R[0] % eclib.N
        s = pow(self.k, -1, eclib.N) * (int.from_bytes(msg, "big") + r * self.d) % eclib.N
        return r.to_bytes(32, "big") + min(s, eclib.N - s).to_bytes(32, "big")  # low s


def _flip(sig: bytes, rng: random.Random) -> bytes:
    j = 32 + rng.randrange(32)
    return sig[:j] + bytes([sig[j] ^ (1 + rng.randrange(255))]) + sig[j + 1 :]


class Wallet:
    """One miner's keys of the three classes and what it holds of each."""

    def __init__(self, rng: random.Random):
        from kaspa_tpu.txscript import standard

        self.schnorr_key, self.ecdsa_key = Key(rng), Key(rng)
        self.multisig_keys, self.outsider = [Key(rng) for _ in range(3)], Key(rng)
        self.redeem = standard.multisig_redeem_script([k.pub32 for k in self.multisig_keys], 2)
        self.spk = {
            "schnorr": standard.pay_to_pub_key(self.schnorr_key.pub32),
            "ecdsa": standard.pay_to_pub_key_ecdsa(self.ecdsa_key.pub33),
            "multisig": standard.pay_to_script_hash_script(self.redeem),
        }
        self.loose = {c: deque() for c in CLASSES}  # the ramp's small outputs: (outpoint, amount, block index)
        self.ready = {(g, c): deque() for g in GRID for c in CLASSES}  # sets of g small outputs: [(outpoint, amount)]
        self.large = {g: deque() for g in GRID}  # merged outputs, oldest first: (outpoint, amount, class)
        self.spare_sets = deque()  # priming: (g, set) merged into the spare large outputs
        self.spare_large = {g: deque() for g in GRID}  # a large output held back: stands in for the one a spoiled merge never made
        self.t = 2  # the two priming blocks are t = 2 and 3, the first steady block t = 4
        self.phase = "fill"  # -> "prime" -> "primed"

    def needs(self) -> dict:
        """Small outputs of each class the ramp still has to bring: four
        blocks' worth of sets and the spare large outputs' worth."""
        if self.phase != "fill":
            return {c: 0 for c in CLASSES}
        want = {"schnorr": 2 * sum(GRID) + sum(SPARE), "ecdsa": sum(GRID), "multisig": sum(GRID)}
        return {c: want[c] - len(self.loose[c]) for c in CLASSES}

    def settle(self, view, n_blocks: int, depth: int) -> None:
        """The fan-out has brought what the wallet needs: once every loose
        output is in ``view``, they become one set a (grid size, block of the
        rotation) and the spare sets.  A fan-out of a coinbase that the chain
        has since left behind is never accepted: an output still invisible
        ``depth`` blocks on is dropped, and the fan-out goes on."""
        waiting = False
        for c in CLASSES:
            seen = [view.get(op) is not None for op, _a, _born in self.loose[c]]
            waiting = waiting or any(not ok and n_blocks - born < depth for ok, (_op, _a, born) in zip(seen, self.loose[c]))
            self.loose[c] = deque(item for ok, item in zip(seen, self.loose[c]) if ok or n_blocks - item[2] < depth)
        if waiting or any(n > 0 for n in self.needs().values()):
            return
        for t in range(4):
            for g in GRID:
                c = class_of(g, t)
                self.ready[(g, c)].append([self.loose[c].popleft()[:2] for _ in range(g)])
        for g in SPARE:
            self.spare_sets.append((g, [self.loose["schnorr"].popleft()[:2] for _ in range(g)]))
        self.phase = "prime"

    def sign_input(self, cls: str, tx, entries, i: int, reused, spoil: str | None, rng) -> tuple:
        """Sign input ``i`` (of class ``cls``); returns (the message, the
        signature: of a multisig input the list of its two)."""
        from kaspa_tpu.consensus import hashing as chash
        from kaspa_tpu.txscript import standard
        from kaspa_tpu.txscript.script_builder import ScriptBuilder

        hash_all = bytes([chash.SIG_HASH_ALL])
        if cls == "multisig":
            msg = chash.calc_schnorr_signature_hash(tx, entries, i, chash.SIG_HASH_ALL, reused)
            first, second = SIGNERS[i % 4]
            sigs = [self.multisig_keys[first].schnorr(msg), self.multisig_keys[second].schnorr(msg)]
            if spoil == "multisig_second_signature":
                sigs[1] = _flip(sigs[1], rng)
            elif spoil == "multisig_outside_key":
                sigs[1] = self.outsider.schnorr(msg)
            b = ScriptBuilder()
            for sig in sigs:
                b.add_data(sig + hash_all)
            tx.inputs[i].signature_script = b.add_data(self.redeem).drain()
            return msg, sigs
        if cls == "ecdsa":
            msg = chash.calc_ecdsa_signature_hash(tx, entries, i, chash.SIG_HASH_ALL, reused)
            sig = self.ecdsa_key.ecdsa(msg)
            tx.inputs[i].signature_script = standard.ecdsa_signature_script(_flip(sig, rng) if spoil else sig, chash.SIG_HASH_ALL)
            return msg, sig
        msg = chash.calc_schnorr_signature_hash(tx, entries, i, chash.SIG_HASH_ALL, reused)
        sig = self.schnorr_key.schnorr(msg)
        tx.inputs[i].signature_script = standard.schnorr_signature_script(_flip(sig, rng) if spoil else sig, chash.SIG_HASH_ALL)
        return msg, sig


def sig_ops(cls: str, i: int) -> int:
    """What input ``i`` of class ``cls`` commits: the sig ops its script executes."""
    return 1 if cls != "multisig" else SIGNERS[i % 4][1] + 1


def probe_program() -> None:
    """One canonical 2-of-3 spend through the program's batch checker, VM
    lane wired: it must be accepted and must not have gone to the host VM."""
    from kaspa_tpu.consensus import hashing as chash
    from kaspa_tpu.consensus.model import Transaction, TransactionInput, TransactionOutpoint, TransactionOutput, UtxoEntry
    from kaspa_tpu.consensus.model.tx import SUBNETWORK_ID_NATIVE, ComputeCommit
    from kaspa_tpu.consensus.params import simnet_params
    from kaspa_tpu.consensus.processes.transaction_validator import TransactionValidator
    from kaspa_tpu.observability.core import REGISTRY

    wallet, rng = Wallet(random.Random(0x2F3)), random.Random(0)
    entries = [UtxoEntry(VALUE, wallet.spk["multisig"], 5, False)]
    tx = Transaction(
        0, [TransactionInput(TransactionOutpoint(bytes([0x2F]) * 32, 0), b"", 0, ComputeCommit.sigops(sig_ops("multisig", 0)))],
        [TransactionOutput(VALUE - FEE, wallet.spk["schnorr"])], 0, SUBNETWORK_ID_NATIVE, 0, b"",
    )
    wallet.sign_input("multisig", tx, entries, 0, chash.SigHashReusedValues(), None, rng)
    checker = TransactionValidator(simnet_params()).new_checker()

    def to_vm() -> int:
        return REGISTRY.snapshot()["counters"].get("txscript_vm_fallbacks", 0)

    before = to_vm()
    checker.collect_tx(0, tx, entries, pov_daa_score=5)
    if to_vm() != before:
        raise RuntimeError(
            "this program sends multisig to the host VM: a canonical 2-of-3 pay-to-script-hash spend moved "
            "txscript_vm_fallbacks; checkscripts-mix needs the m-of-n batch path of txscript/batch.py"
        )
    err = checker.dispatch()[0]
    if err is not None:
        raise RuntimeError(f"the program refused a canonical 2-of-3 spend: {err}")


class Shape:
    def __init__(self, spec, params, miners, mass_calc, rng, samples: list):
        self.spec, self.params, self.miners, self.mass_calc, self.rng, self.samples = spec, params, miners, mass_calc, rng, samples
        self.tpb = spec.tx_per_block
        if self.tpb != 2 * len(GRID):
            raise ValueError(f"checkscripts-mix fills a block with {2 * len(GRID)} transactions, the cell asks for {self.tpb}")
        probe_program()
        self.wallets = [Wallet(rng) for _ in miners]
        self.depth = max(params.coinbase_maturity, int(COINBASE_DEPTH_DELAYS * spec.delay * spec.bps) + 16)
        # about 8 x sig_samples spends are offered to dag.build's pick (half of a block's spends start with a Schnorr input)
        self.sample_p = min(1.0, 8.0 * spec.sig_samples / max(1, spec.window_blocks * len(GRID)))
        self.reference_p = min(1.0, REFERENCE_SAMPLES / max(1, spec.window_blocks * self.tpb))
        self.reference_rng = random.Random(spec.seed ^ 0x5C817)
        self.steady = False  # every miner primed: every block is 6 merges and 6 splits
        self.spoiled_mined = 0
        self.seen_coinbases: set = set()

    # ------------------------------------------------------------- building
    def _spend_sets(self, wallet: Wallet, view, sources: list, outputs: list, spoil_at: int | None, spoil: str | None):
        """One transaction over ``sources`` [(outpoint, class)] paying
        ``outputs`` [(amount, class)], signed; None if a source is not in
        ``view`` yet.  Returns (tx, entries, msg and sig of input 0)."""
        from kaspa_tpu.consensus import hashing as chash
        from kaspa_tpu.consensus.model import Transaction, TransactionInput, TransactionOutput
        from kaspa_tpu.consensus.model.tx import SUBNETWORK_ID_NATIVE, ComputeCommit

        entries = [view.get(op) for op, _c in sources]
        if any(e is None for e in entries):
            return None
        tx = Transaction(
            0, [TransactionInput(op, b"", 0, ComputeCommit.sigops(sig_ops(c, i))) for i, (op, c) in enumerate(sources)],
            [TransactionOutput(amount, wallet.spk[c]) for amount, c in outputs], 0, SUBNETWORK_ID_NATIVE, 0, b"",
        )
        tx.storage_mass = self.mass_calc.calc_contextual_masses(tx, entries)
        reused, first = chash.SigHashReusedValues(), None
        for i, (_op, c) in enumerate(sources):
            signed = wallet.sign_input(c, tx, entries, i, reused, spoil if i == spoil_at else None, self.rng)
            first = first or signed
        tx._id_cache = None
        return tx, entries, first

    def _merge(self, wallet: Wallet, view, g: int, cls: str, aset: list, spoil: str | None = None):
        # a transaction's id leaves its signatures out: the spoiled merge pays one sompi more in fee, so that the
        # honest merge of the same outputs, four blocks later, is another transaction
        paid = sum(amount for _op, amount in aset) - FEE - (1 if spoil else 0)
        return self._spend_sets(wallet, view, [(op, cls) for op, _a in aset], [(paid, cls)], 1 if spoil else None, spoil)

    def _split(self, wallet: Wallet, view, g: int, large: tuple):
        op, amount, cls = large
        share = (amount - FEE) // g
        outputs = [(amount - FEE - share * (g - 1), cls)] + [(share, cls)] * (g - 1)
        return self._spend_sets(wallet, view, [(op, cls)], outputs, None, None)

    def _ask_reference(self, tx, entries, spoiled_input: int | None) -> None:
        """The reference's verdict on ``tx`` against the construction."""
        spent = [(e.amount, e.script_public_key.version, e.script_public_key.script) for e in entries]
        if spoiled_input is None:
            verdicts = [reference_scripts.spend_verdict(tx, spent)]
            expected = [True]
        else:  # the spoiled input alone fails; the inputs around it hold
            around = [i for i in (0, spoiled_input, len(spent) - 1) if 0 <= i < len(spent)]
            verdicts = [reference_scripts.input_verdict(tx, i, *spent[i]) for i in around]
            expected = [i != spoiled_input for i in around]
        if verdicts != expected:
            raise RuntimeError(f"the reference's verdict {verdicts} on spend {tx.id().hex()} is not the construction's {expected}")

    # ------------------------------------------------------------ templates
    def select(self, miner, view, pov_daa_score: int, n_blocks: int, spoil_cls, made: list) -> None:
        """Fill ``made`` with (tx, what it consumed, spoil class or None,
        block index) for the template of block ``n_blocks``."""
        wallet = self.wallets[miner.idx]
        if not self.steady and all(w.phase == "primed" for w in self.wallets):
            self.steady = True
        budget = MassBudget(self.params, self.mass_calc)
        if wallet.phase == "fill" and all(n <= 0 for n in wallet.needs().values()):
            wallet.settle(view, n_blocks, self.depth)
        if self.steady:
            self._steady_block(wallet, view, n_blocks, spoil_cls, made, budget)
        elif wallet.phase == "prime":
            self._priming_block(wallet, view, n_blocks, made, budget)
        else:
            self._fan_out(miner, view, pov_daa_score, made, budget)

    def _fan_out(self, miner, view, pov_daa_score: int, made: list, budget) -> None:
        """Ramp: mature coinbase outputs become small outputs of the classes
        that are lacking: the miner's own wallet first, then whichever lacks
        most, so that the ramp ends when the network has mined what all the
        wallets need and not when its unluckiest miner has."""
        needs = {(w, c): n for w, wallet in enumerate(self.wallets) for c, n in wallet.needs().items()}
        keep = deque()
        while miner.coinbases and any(n > 0 for n in needs.values()):
            outpoint, paid_at = miner.coinbases.popleft()
            entry = view.get(outpoint)
            if entry is None or entry.block_daa_score + self.depth > pov_daa_score:
                if pov_daa_score - paid_at < 3 * self.depth:
                    keep.append((outpoint, paid_at))  # not on this chain, or not deep enough yet
                continue
            n_out = entry.amount // VALUE
            if n_out == 0:
                continue
            paid_to = []
            for _ in range(n_out):
                to = max(needs, key=lambda k: (needs[k] > 0 and k[0] == miner.idx, needs[k]))
                needs[to] -= 1
                paid_to.append(to)
            tx = self._fan_out_tx(miner, outpoint, entry, [self.wallets[w].spk[c] for w, c in paid_to])
            if not budget.fits(tx):
                keep.append((outpoint, paid_at))
                break
            made.append((tx, ("fan_out", outpoint, paid_at, paid_to), None, -1))
        miner.coinbases.extendleft(reversed(keep))

    def _fan_out_tx(self, miner, outpoint, entry, spks: list):
        """The coinbase output ``entry`` (the miner's own pay-to-pubkey) in
        equal shares to ``spks``, signed by the miner."""
        from kaspa_tpu.consensus import hashing as chash
        from kaspa_tpu.consensus.model import Transaction, TransactionInput, TransactionOutput
        from kaspa_tpu.consensus.model.tx import SUBNETWORK_ID_NATIVE, ComputeCommit
        from kaspa_tpu.txscript import standard

        share = (entry.amount - FEE) // len(spks)
        outputs = [TransactionOutput(share, spk) for spk in spks]
        outputs[0] = TransactionOutput(entry.amount - FEE - share * (len(spks) - 1), spks[0])
        tx = Transaction(0, [TransactionInput(outpoint, b"", 0, ComputeCommit.sigops(1))], outputs, 0, SUBNETWORK_ID_NATIVE, 0, b"")
        tx.storage_mass = self.mass_calc.calc_contextual_masses(tx, [entry])
        msg = chash.calc_schnorr_signature_hash(tx, [entry], 0, chash.SIG_HASH_ALL, chash.SigHashReusedValues())
        tx.inputs[0].signature_script = standard.schnorr_signature_script(miner.sign(msg), chash.SIG_HASH_ALL)
        tx._id_cache = None
        return tx

    def _priming_block(self, wallet: Wallet, view, n_blocks: int, made: list, budget) -> None:
        """The merges of block t of the rotation without the splits (t = 2,
        then 3), and with the first of them the spare large outputs."""
        jobs = [(g, class_of(g, wallet.t), wallet.ready[(g, class_of(g, wallet.t))][0]) for g in GRID]
        if wallet.t == 2:
            jobs += [(g, "schnorr", aset) for g, aset in wallet.spare_sets]
        for g, cls, aset in jobs:
            built = self._merge(wallet, view, g, cls, aset)
            if built is None or not budget.fits(built[0]):
                del made[:]  # all of it or nothing: the next block tries again
                return
            made.append((built[0], ("merge", g, cls, aset), None, n_blocks))

    def _steady_block(self, wallet: Wallet, view, n_blocks: int, spoil_cls, made: list, budget) -> None:
        t = wallet.t
        spoil = spoil_at_g = None
        if spoil_cls:
            spoil = SPOILS[self.spoiled_mined % len(SPOILS)]
            spoil_at_g = next((g for g in SPARE if class_of(g, t) == SPOIL_CLASS[spoil] and wallet.spare_large[g]), None)
            if spoil_at_g is None:
                return  # no grid size of that class here can lose its large output: dag.build tries again later
        for g in GRID:
            cls = class_of(g, t)
            if not wallet.ready[(g, cls)]:
                return
            aset = wallet.ready[(g, cls)][0]
            this_spoil = spoil if g == spoil_at_g else None
            built = self._merge(wallet, view, g, cls, aset, this_spoil)
            if built is None or not budget.fits(built[0]):
                return
            tx, entries, (msg, sig) = built
            made.append((tx, ("merge", g, cls, aset), this_spoil, n_blocks))
            self._sample(tx, entries, cls, msg, sig, this_spoil, n_blocks, wallet)
        for g in GRID:
            # a large output is split two blocks after its merge (a late sibling of the miner's last block
            # does not see that block's outputs); where a spoiled merge left a gap the spare one steps in
            queue = wallet.large[g] if len(wallet.large[g]) >= 2 or not wallet.spare_large[g] else wallet.spare_large[g]
            if not queue:
                return
            large = queue[0]
            built = self._split(wallet, view, g, large)
            if built is None or not budget.fits(built[0]):
                return
            tx, entries, (msg, sig) = built
            made.append((tx, ("split", g, large), None, n_blocks))
            self._sample(tx, entries, large[2], msg, sig, None, n_blocks, wallet)

    def _sample(self, tx, entries, cls: str, msg: bytes, sig: bytes, spoil, n_blocks: int, wallet: Wallet) -> None:
        if spoil is not None:
            self._ask_reference(tx, entries, 1)
        else:
            if self.reference_rng.random() < self.reference_p:
                self._ask_reference(tx, entries, None)
            # compare.py asks the plain reference about input 0 of these: Schnorr pay-to-pubkey only
            if cls == "schnorr" and self.rng.random() < self.sample_p:
                self.samples.append((n_blocks, tx.id(), wallet.schnorr_key.pub32, msg, sig, True))

    # ------------------------------------------------------ what dag.build reports back
    def discarded(self, miner, made: list) -> None:
        """The template was not mined: nothing was consumed (a template takes
        from the fronts of the queues without removing), but for the ramp's coinbases."""
        for _tx, what, _cls, _born in reversed(made):
            if what[0] == "fan_out":
                miner.coinbases.appendleft((what[1], what[2]))

    def mined(self, miner, block, made: list, index: int) -> None:
        """Block ``index`` is in: what it consumed leaves the queues, what it
        made joins them."""
        from kaspa_tpu.consensus.model.tx import TransactionOutpoint

        wallet = self.wallets[miner.idx]
        rotated = False
        for tx, what, spoil, _born in made:
            txid = tx.id()
            if what[0] == "fan_out":
                for j, (w, c) in enumerate(what[3]):
                    self.wallets[w].loose[c].append((TransactionOutpoint(txid, j), tx.outputs[j].value, index))
            elif what[0] == "merge":
                _kind, g, cls, aset = what
                rotated = True
                large = (TransactionOutpoint(txid, 0), tx.outputs[0].value, cls)
                if wallet.spare_sets and wallet.spare_sets[0][1] is aset:
                    wallet.spare_sets.popleft()
                    wallet.spare_large[g].append(large)
                elif spoil is None:
                    wallet.ready[(g, cls)].popleft()
                    wallet.large[g].append(large)
                else:  # never accepted: its inputs stay where they were, its large output never exists
                    self.spoiled_mined += 1
            else:
                _kind, g, large = what
                (wallet.large[g] if wallet.large[g] and wallet.large[g][0] is large else wallet.spare_large[g]).popleft()
                wallet.ready[(g, large[2])].append([(TransactionOutpoint(txid, j), o.value) for j, o in enumerate(tx.outputs)])
        if rotated:
            wallet.t += 1
            if wallet.phase == "prime" and wallet.t == 4:
                wallet.phase = "primed"
        coinbase = block.transactions[0]
        # sibling blocks of one miner over the same parents carry the same coinbase
        if not self.steady and coinbase.id() not in self.seen_coinbases:
            self.seen_coinbases.add(coinbase.id())
            for j, out in enumerate(coinbase.outputs):
                for m in self.miners:
                    if out.script_public_key == m.spk:
                        m.coinbases.append((TransactionOutpoint(coinbase.id(), j), block.header.daa_score))

    def is_window_block(self, block, made: list) -> bool:
        return len(made) == self.tpb and sum(1 for m in made if m[1][0] == "split") == len(GRID)
