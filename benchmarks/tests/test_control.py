"""The rest of a run with the timed path broken underneath: ``correct`` must
come out false, each time by the numbers that fault is another number's to
catch.  Same ``harness.run_cell`` as a run, toy size, CPU."""

import os
import time

import pytest

from benchmarks import control, harness
from benchmarks.tests.conftest import ROOT, toy_cell


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _failing(bench, mode, wrap):
    workload, config = toy_cell(mode)
    out = harness.run_cell(workload, config, bench, "crescendo-10bps.catchup-10tpb", seed=21, seconds=3.0, trace=False,
                           process_start=time.perf_counter(), log=lambda _m: None, wrap_window=wrap)
    return out["correct"], {k for k, v in out["checks"].items() if v[0] != v[1]}


def test_honest_run_is_correct(bench):
    assert _failing(bench, "catchup", None) == (True, set())


def test_control_accepting_every_signature_is_not_correct(bench):
    correct, failing = _failing(bench, "catchup", control.accept_every_signature)
    assert correct is False
    # the spoiled spends were accepted: the state left the reference's
    assert failing & {"utxo_commitment_vs_reference", "accepted_ids_vs_reference", "bad_status_blocks", "sink_vs_reference"}


def test_one_altered_answer_is_not_correct(bench):
    correct, failing = _failing(bench, "catchup", control.flip_one_answer)
    assert correct is False
    assert failing & {"accepted_ids_vs_reference", "utxo_entries_vs_reference", "bad_status_blocks"}


def test_host_lane_is_not_correct_though_the_state_is_right(bench):
    correct, failing = _failing(bench, "paced", control.host_lane)
    assert correct is False
    assert "degraded_jobs" in failing and "utxo_commitment_vs_reference" not in failing


def test_a_wrong_selected_parent_is_not_correct(bench):
    correct, failing = _failing(bench, "catchup", control.wrong_selected_parent)
    assert correct is False
    assert "ghostdag_vs_reference" in failing
