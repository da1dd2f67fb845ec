"""The plain reference of a node's transaction intake: what a Kaspa node that
is handed transactions before their blocks may answer, and what it may hold
afterwards.  Standard library and ``reference.py`` only; it imports nothing
of ``kaspa_tpu`` and reads block *data* through plain attribute access.

The deployment: transactions are submitted to the node one by one, blocks are
handed in one at a time in the list's (topological) order, and the two
streams exclude each other (a node serialises intake and block handling).
The run is described by its *log*, on one clock:

- ``block_in[i] = (t0, t1)``: block ``i`` of the list was handed in between
  these two instants and was taken in (no entry: it never was);
- per submission ``(tx, t_submit, t_resolved, outcome)``: the node looked at
  the transaction at some instant between the two, and the outcome is what
  the caller read when the call returned.

The rules (mining/src/manager.rs:296-421 and mempool/handle_new_block_
transactions.rs as recalled, in this deployment's terms):

- a transaction is validated against the virtual's UTXO view.  An output is
  in that view once a block that holds its creating transaction in its past
  (or is it) *and whose own spends all verify* has been taken in: a block
  with a failed spend is a tip the virtual does not merge, so its other
  transactions' outputs appear only when an honest block merges it;
- input visible and signature valid -> ``accepted`` (into the pool); input
  visible and signature invalid -> ``rejected``; input not visible ->
  ``orphaned`` (parked; signature not looked at yet);
- once the block that carries a transaction has been taken in, the
  transaction is known (pool, orphan pool and the accepted-ids cache): a
  later submission of it is ``rejected`` as a duplicate, whatever its
  signature;
- when a block is taken in, every transaction it carries leaves the pool and
  the orphan pool, and every parked transaction that spends an output of one
  of the block's transactions is handed back to admission once.

Where the log leaves the order of two events open (they overlap), both
answers are allowed: the reference never guesses.
"""

from __future__ import annotations

from benchmarks import reference

ACCEPTED, ORPHANED, REJECTED = "accepted", "orphaned", "rejected"


class Relay:
    """The blocks' side of the schedule: for every spend of ``blocks[first:
    first + count]`` which block carries it, which block created its input
    and from which block on that input is visible."""

    def __init__(self, blocks: list, first: int, count: int, spoiled_blocks: set):
        self.first = first
        pos = {b.hash: i for i, b in enumerate(blocks)}
        made_in: dict = {}  # txid -> index of the block that carries it
        self.outputs: dict = {}  # (txid, index) -> the output
        past = []  # past[i]: bitset of the listed blocks in block i's past
        for i, b in enumerate(blocks):
            seen = 0
            for p in b.header.direct_parents():
                j = pos.get(p)
                if j is not None:  # genesis is not listed
                    seen |= past[j] | (1 << j)
            past.append(seen)
            for tx in b.transactions:
                made_in.setdefault(tx.id(), i)  # sibling blocks of one miner may carry one coinbase
                for k, out in enumerate(tx.outputs):
                    self.outputs[(tx.id(), k)] = out
        honest = [b.hash not in spoiled_blocks for b in blocks]
        # the first honest block that is block j or has it in its past
        self.visible_from = list(range(len(blocks)))
        for j in range(len(blocks)):
            if not honest[j]:
                self.visible_from[j] = next(
                    (k for k in range(j + 1, len(blocks)) if honest[k] and past[k] >> j & 1), None
                )
        self.carrier: dict = {}
        self.creator: dict = {}
        self.txs: dict = {}
        for i in range(first, first + count):
            for tx in blocks[i].transactions[1:]:
                txid = tx.id()
                self.txs[txid], self.carrier[txid] = tx, i
                self.creator[txid] = max(made_in[inp.previous_outpoint.transaction_id] for inp in tx.inputs)

    def valid(self, txid: bytes) -> bool:
        """BIP340 over the reference's own sighash (a one-input pay-to-pubkey spend)."""
        tx = self.txs[txid]
        op = tx.inputs[0].previous_outpoint
        spent = self.outputs[(op.transaction_id, op.index)]
        return reference.p2pk_spend_verdict(tx, spent.value, spent.script_public_key.version, spent.script_public_key.script)[0]

    def allowed(self, txid: bytes, t_submit: float, t_resolved: float, block_in: dict, valid: bool) -> set:
        """The outcomes the rules allow for one submission, given the log."""
        own = self.carrier[txid]
        sight = self.visible_from[self.creator[txid]]
        own_surely_in = own in block_in and block_in[own][1] <= t_submit
        own_maybe_in = own in block_in and block_in[own][0] < t_resolved
        if sight is None or sight < self.first:
            input_surely_visible, input_maybe_visible = sight is not None, sight is not None
        else:
            input_surely_visible = sight in block_in and block_in[sight][1] <= t_submit
            input_maybe_visible = sight in block_in and block_in[sight][0] < t_resolved
        out = set()
        if own_maybe_in:
            out.add(REJECTED)  # a duplicate of what a block already brought
        if not own_surely_in:
            # an input that only the transaction's own block (or a later one) brings into view is
            # never seen by admission: by then the transaction is a duplicate
            if input_maybe_visible and (sight is None or sight < own):
                out.add(ACCEPTED if valid else REJECTED)
            if not input_surely_visible:
                out.add(ORPHANED)
        return out

    def handed_back(self, submissions: list, block_in: dict) -> tuple[int, int]:
        """(fewest, most) parked transactions that blocks hand back to
        admission over the run: one for each ``orphaned`` submission that was
        parked before the block carrying its input's creator was taken in.
        An ``accepted`` one whose call overlaps that block's intake may have
        been parked and handed back before its answer was read: it counts
        towards the most."""
        lo = hi = 0
        for txid, t_submit, t_resolved, outcome in submissions:
            made = self.creator[txid]
            if made not in block_in:
                continue
            t0, t1 = block_in[made]
            if outcome == ORPHANED:
                lo += int(t_resolved <= t0)
                hi += int(t_submit < t1)
            elif outcome == ACCEPTED:
                hi += int(t_submit < t1 and t0 < t_resolved)
        return lo, hi

    def unverified_at_block(self, submissions: list, block_in: dict) -> int:
        """Spends whose signature admission had perhaps not decided when their
        block was handed in, so that the block had to ask the device: every
        one not ``accepted`` or ``rejected`` strictly before."""
        decided = {
            txid for txid, _t, t_resolved, outcome in submissions
            if outcome in (ACCEPTED, REJECTED) and self.carrier[txid] in block_in and t_resolved <= block_in[self.carrier[txid]][0]
        }
        return sum(1 for txid, own in self.carrier.items() if own in block_in and txid not in decided)

    def must_be_gone(self, block_in: dict) -> set:
        """Ids that neither the pool nor the orphan pool may hold after the
        run: every transaction of every block that was taken in."""
        return {txid for txid, own in self.carrier.items() if own in block_in}


def compare(relay: Relay, submissions: list, block_in: dict, pool: set, orphans: set, handed_back: int,
            invalid: set) -> dict:
    """Counts of disagreement between a run's log and final pools and the
    rules; every one is 0 in a sound run.  ``submissions`` holds (txid,
    t_submit, t_resolved or None, outcome or None); ``invalid`` the ids the
    reference found wrongly signed (``relay.valid`` over whatever the caller
    had it look at: every rejected one and every one the construction spoiled,
    at the least)."""
    resolved = [s for s in submissions if s[3] is not None]
    wrong = sum(
        1 for txid, t_submit, t_resolved, outcome in resolved
        if outcome not in relay.allowed(txid, t_submit, t_resolved, block_in, txid not in invalid)
    )
    gone = relay.must_be_gone(block_in)
    lo, hi = relay.handed_back(resolved, block_in)
    return {
        "ticket_outcomes_vs_reference": wrong,
        # every hand-back the rules demand and the node did not make, or made and the rules do not know, is one
        "mempool_vs_reference": len((pool | orphans) & gone) + len(pool & invalid) + max(0, lo - handed_back) + max(0, handed_back - hi),
        "lost_tickets": len(submissions) - len(resolved),
    }
