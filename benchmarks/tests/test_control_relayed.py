"""The relayed cell's control and fault through ``control_relayed.run_break``
(the same ``harness.run_cell`` as a run), toy size, CPU.  The fault's own
count needs a parked transaction, which the toy window never has (its spends
consume the ramp's outputs): ``tests/test_relayed_node.py`` holds that case
on a node that comes up before the ramp ends; here the break is shown to
take the hand-back away and to give it back."""

import os

import pytest

from benchmarks import control_relayed, harness
from benchmarks.tests.conftest import ROOT, toy_cell

CELL = "testnet12-rothschild.paced-10tpb-relayed"


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _run(bench, wrap):
    workload, config = toy_cell("paced_relayed")
    workload.update(relayed_share=1.0, tx_lead_s="0.5-1.5")
    return control_relayed.run_break(bench, CELL, workload, config, seed=21, seconds=3.0, wrap=wrap, dag=None)


def test_honest_run_is_correct_and_the_cache_answers_the_blocks(bench):
    row = _run(bench, None)
    assert row["correct"] is True and row["failing"] == {}
    # on the CPU a wave's verify takes longer than some spends' lead, so a block may meet a spend still in flight
    assert row["sigcache_block_hit_pct"] >= 90.0
    assert {row["relay"][k] for k in ("mempool_vs_reference", "ticket_outcomes_vs_reference", "lost_tickets", "sigcache_vs_reference")} == {0}


def test_a_cache_that_holds_nothing_reads_zero_and_is_not_correct(bench):
    row = _run(bench, control_relayed.sigcache_holds_nothing)
    assert row["sigcache_block_hit_pct"] == 0.0
    assert row["correct"] is False and set(row["failing"]) == {"unresolved_blocks"}  # the state itself stays right
    assert row["relay"]["sigcache_vs_reference"] == row["failing"]["unresolved_blocks"] > 0


def test_the_accepting_control_lets_a_wrong_signature_into_the_pool(bench):
    row = _run(bench, control_relayed.breaks()["accept_every_signature"])
    assert row["correct"] is False
    assert row["relay"]["ticket_outcomes_vs_reference"] >= 1  # a spoiled spend's ticket said accepted
    assert set(row["failing"]) & {"utxo_commitment_vs_reference", "accepted_ids_vs_reference", "bad_status_blocks"}


def test_the_fault_takes_the_handback_away_and_gives_it_back():
    from kaspa_tpu.ingest.tier import IngestTier

    real = IngestTier.resubmit
    with control_relayed.drop_unorphan_handback():
        assert IngestTier.resubmit is not real and IngestTier.resubmit(None, [object()]) == []
    assert IngestTier.resubmit is real
    assert set(control_relayed.breaks()) >= {"accept_every_signature", "flip_one_answer", "host_lane",
                                             "sigcache_holds_nothing", "drop_unorphan_handback"}
