"""``reference_mempool.py`` on hand-made schedules: a five-block DAG of plain
objects (nothing of the program), a log written by hand, and the outcomes,
hand-backs and final pools the rules allow.  No JAX."""

from types import SimpleNamespace as NS

import pytest

from benchmarks import reference_mempool as rp

A, O, R = rp.ACCEPTED, rp.ORPHANED, rp.REJECTED


def _tx(name: str, spends: str | None):
    inputs = [NS(previous_outpoint=NS(transaction_id=spends.encode(), index=0))] if spends else []
    return NS(id=lambda: name.encode(), inputs=inputs, outputs=[NS()])


def _block(name: str, parents: list, txs: list):
    return NS(hash=name.encode(), header=NS(direct_parents=lambda: [p.encode() for p in parents]),
              transactions=[_tx("cb-" + name, None)] + txs)


def _dag():
    """b0 (the ramp) makes p0.  b1 carries t1 (spends p0).  b2, a sibling of
    b1, is spoiled and carries t2 (spends p0x, honest) beside its failed
    spend bad.  b3 merges both and carries t3 (spends t1) and t4 (spends t2).
    b4 carries t5 (spends t3)."""
    b0 = _block("b0", ["genesis"], [_tx("p0", "cb-b0"), _tx("p0x", "cb-b0"), _tx("p0y", "cb-b0")])
    b1 = _block("b1", ["b0"], [_tx("t1", "p0")])
    b2 = _block("b2", ["b0"], [_tx("t2", "p0x"), _tx("bad", "p0y")])
    b3 = _block("b3", ["b1", "b2"], [_tx("t3", "t1"), _tx("t4", "t2")])
    b4 = _block("b4", ["b3"], [_tx("t5", "t3")])
    return rp.Relay([b0, b1, b2, b3, b4], first=1, count=4, spoiled_blocks={b"b2"})


# every block handed in for one second, ten seconds apart: block i over [10 i, 10 i + 1]
ALL_IN = {i: (10.0 * i, 10.0 * i + 1) for i in (1, 2, 3, 4)}


def test_carriers_creators_and_sight():
    relay = _dag()
    assert relay.carrier == {b"t1": 1, b"t2": 2, b"bad": 2, b"t3": 3, b"t4": 3, b"t5": 4}
    assert relay.creator == {b"t1": 0, b"t2": 0, b"bad": 0, b"t3": 1, b"t4": 2, b"t5": 3}
    # an honest block shows its own outputs; the spoiled b2's appear with b3, the first honest block above it
    assert relay.visible_from == [0, 1, 3, 3, 4]


@pytest.mark.parametrize("txid,t_submit,t_resolved,valid,allowed", [
    (b"t1", 2.0, 3.0, True, {A}),  # input from the ramp, own block not yet in
    (b"t1", 12.0, 13.0, True, {R}),  # own block already in: a duplicate
    (b"t1", 9.5, 10.5, True, {A, R}),  # resolved while its own block was handed in
    (b"t3", 2.0, 3.0, True, {O}),  # before the block that makes its input
    (b"t3", 12.0, 13.0, True, {A}),  # after it
    (b"t3", 10.5, 10.7, True, {A, O}),  # while it was handed in
    (b"t4", 22.0, 23.0, True, {O}),  # its input is in the spoiled b2: not visible until b3, its own block, merges it
    (b"t4", 29.5, 30.5, True, {O, R}),
    (b"bad", 2.0, 3.0, False, {R}),  # input visible, signature wrong
    (b"t5", 2.0, 3.0, False, {O}),  # a wrong signature is not looked at while the input is missing
    (b"t5", 32.0, 33.0, False, {R}),
    (b"t5", 42.0, 43.0, True, {R}),
])
def test_allowed_outcomes(txid, t_submit, t_resolved, valid, allowed):
    assert _dag().allowed(txid, t_submit, t_resolved, ALL_IN, valid) == allowed


def test_a_block_that_was_never_taken_in_shows_nothing():
    relay = _dag()
    block_in = {1: ALL_IN[1]}  # b3 never came: t1's output exists, t3's never will
    assert relay.allowed(b"t3", 12.0, 13.0, block_in, True) == {A}
    assert relay.allowed(b"t5", 50.0, 51.0, block_in, True) == {O}
    assert relay.must_be_gone(block_in) == {b"t1"}


@pytest.mark.parametrize("subs,bounds", [
    ([(b"t3", 2.0, 3.0, O)], (1, 1)),  # parked before b1: handed back by it
    ([(b"t3", 9.9, 10.5, O)], (0, 1)),  # parked while b1 was handed in: either
    ([(b"t4", 22.0, 23.0, O)], (0, 0)),  # parked after b2 (whose outputs b3 shows): nobody hands it back
    ([(b"t4", 2.0, 3.0, O), (b"t3", 2.0, 3.0, O), (b"t5", 2.0, 3.0, O), (b"t1", 2.0, 3.0, A)], (3, 3)),
    ([(b"t3", 9.9, 10.5, A)], (0, 1)),  # read as accepted after b1 was handed in: it may have been parked and handed back first
    ([(b"t3", 12.0, 13.0, A)], (0, 0)),  # accepted after b1: never parked
])
def test_hand_backs(subs, bounds):
    assert _dag().handed_back(subs, ALL_IN) == bounds


def test_what_a_block_still_has_to_ask_the_device():
    relay = _dag()
    log = [(b"t1", 2.0, 3.0, A), (b"t2", 2.0, 3.0, A), (b"bad", 2.0, 3.0, R), (b"t3", 2.0, 3.0, O),
           (b"t4", 2.0, 3.0, O), (b"t5", 39.5, 40.5, A)]
    # t3 and t4 were parked (whatever decided them later is not in the log), t5 was decided after b4 began
    assert relay.unverified_at_block(log, ALL_IN) == 3
    assert relay.unverified_at_block([], ALL_IN) == 6


def _sound_log():
    return [(b"t1", 2.0, 3.0, A), (b"t2", 2.0, 3.0, A), (b"bad", 2.0, 3.0, R), (b"t3", 4.0, 5.0, O),
            (b"t4", 4.0, 5.0, O), (b"t5", 32.0, 33.0, A)]


def test_a_sound_run_counts_nothing():
    got = rp.compare(_dag(), _sound_log(), ALL_IN, pool=set(), orphans=set(), handed_back=2, invalid={b"bad"})
    assert got == {"ticket_outcomes_vs_reference": 0, "mempool_vs_reference": 0, "lost_tickets": 0}


@pytest.mark.parametrize("change,count,value", [
    ({"pool": {b"t1"}}, "mempool_vs_reference", 1),  # left in the pool after its block
    ({"orphans": {b"t4"}}, "mempool_vs_reference", 1),  # left in the orphan pool after its block
    ({"handed_back": 0}, "mempool_vs_reference", 2),  # the orphans b1 and b2 gave parents were dropped: one each
    ({"handed_back": 1}, "mempool_vs_reference", 1),
    ({"handed_back": 3}, "mempool_vs_reference", 1),
    ({"log": [(b"bad", 2.0, 3.0, A)], "handed_back": 0}, "ticket_outcomes_vs_reference", 1),  # a wrong signature let in
    ({"pool": {b"bad"}}, "mempool_vs_reference", 2),  # and held: wrongly signed, and of a block that was taken in
    ({"log": [(b"t1", 2.0, 3.0, R)], "handed_back": 0}, "ticket_outcomes_vs_reference", 1),  # an honest spend refused
    ({"log": [(b"t1", 2.0, None, None)], "handed_back": 0}, "lost_tickets", 1),
])
def test_each_fault_is_counted(change, count, value):
    args = {"log": _sound_log(), "pool": set(), "orphans": set(), "handed_back": 2, **change}
    got = rp.compare(_dag(), args["log"], ALL_IN, args["pool"], args["orphans"], args["handed_back"], {b"bad"})
    assert got[count] == value
