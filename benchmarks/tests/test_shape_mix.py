"""The ``checkscripts-mix`` shape at toy size on the CPU: a four-row grid
(2, 3, 4, 5 inputs) stands in for 2 ... 100 so that the XLA ladder at bucket 8
can answer a block, everything else is the shape as the cell runs it.  The
grid, the shares and the rotation of the real constants are held against the
configuration's ``transactions``."""

import importlib
import json
import os
import time
from collections import Counter

import pytest

from benchmarks import control, harness
from benchmarks import dag as dagmod
from benchmarks.tests.conftest import ROOT, TOY_NETWORK

mix = importlib.import_module("benchmarks.shapes.checkscripts-mix")
CELL = "checkscripts-mix.catchup-12tpb"
TOY_GRID = {"GRID": (2, 3, 4, 5), "GROUPS": ((5,), (4,), (3,), (2,)), "SPARE": (2, 3, 4, 5)}
REAL_GRID = {k: getattr(mix, k) for k in TOY_GRID}  # as the cell runs it


def _class(script: bytes) -> str:
    if len(script) == 34:
        return "schnorr"
    return "ecdsa" if script[0] == 33 else "multisig"


@pytest.fixture(scope="module")
def toy_grid():
    for k, v in TOY_GRID.items():
        setattr(mix, k, v)
    yield
    for k, v in REAL_GRID.items():
        setattr(mix, k, v)


@pytest.fixture
def real_grid(monkeypatch):
    """The module's constants as the cell runs them, whatever ``toy_grid`` (of
    module scope) has set meanwhile."""
    for k, v in REAL_GRID.items():
        monkeypatch.setattr(mix, k, v)


def _toy_cell():
    workload = {
        "config": "toy", "mode": "catchup", "tx_per_block": 8, "tx_shape": "checkscripts-mix", "window_blocks": 24,
        "spoiled_blocks": 4, "pool_factor": 3, "max_in_flight": 99, "sig_samples": 6,
        "pretrace": {"schnorr_verify": [8], "ecdsa_verify": [8]}, "trace_seconds": 1.0,
        "idle_gap_spans": ["txscript.multisig_resolve", "txscript.dispatch_wait", "pipeline.virtual", "pipeline.body", "pipeline.header"],
    }
    config = {"name": "toy", "network": dict(TOY_NETWORK), "pipeline": {"coalesce": 64, "stage_workers": 2}}
    return workload, config


@pytest.fixture(scope="module")
def toy(toy_grid):
    """(workload, config, DAG): one build for every case of this file."""
    from kaspa_tpu.ops import dispatch

    workload, config = _toy_cell()
    dispatch.configure(0)
    harness._pretrace(workload, lambda _m: None)
    return workload, config, harness.build_dag(workload, config, 5, lambda _m: None)


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _run(bench, toy, wrap=None, trace=False, lines=None):
    workload, config, dag = toy
    return harness.run_cell(workload, config, bench, CELL, seed=5, seconds=3.0, trace=trace, process_start=time.perf_counter(),
                            log=(lines.append if lines is not None else lambda _m: None), wrap_window=wrap, dag=dag)


def test_toy_cell_is_correct_with_all_21_counts_zero(bench, toy):
    lines = []
    out = _run(bench, toy, lines=lines)
    assert len(out["checks"]) == 21 and all(v == [0, 0] for v in out["checks"].values()), out["checks"]
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"catchup_blocks_per_s", "setup_s"}
    d = json.loads(next(ln for ln in lines if ln.startswith("counters ")).split(" ", 1)[1])
    # every multisig input of the window took the batch path; the host VM ran for the spoiled spends alone
    assert d["txscript_multisig_inputs"] == d["txscript_p2sh_inputs"]["batch"] and "vm" not in d["txscript_p2sh_inputs"]
    assert d["txscript_multisig_pairs"] == 4 * d["txscript_multisig_inputs"]
    assert "txscript_vm_fallbacks" not in d and 1 <= d["txscript_multisig_vm_reruns"] == d["txscript_vm_executions"]
    assert d["secp_device_ecdsa_jobs"] == d["txscript_batch_jobs"]["ecdsa"] > 0
    assert not any(ln.startswith("second pass") for ln in lines)


def test_traced_toy_cell_reads_the_new_program_metrics(bench, toy):
    out = _run(bench, toy, trace=True)
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["multisig_pairs_per_input"]["value"] == 4.0
    assert out["metrics"]["multisig_batched_inputs_pct"]["value"] == 100.0
    assert out["metrics"]["ecdsa_host_prepare_ms_per_block.catchup"]["value"] > 0
    assert "ecdsa_kernel_roofline" not in out["metrics"]  # no device plane on the CPU: left out, never 0


@pytest.mark.parametrize("break_name,numbers", [
    ("accept_every_signature", {"utxo_commitment_vs_reference", "accepted_ids_vs_reference", "bad_status_blocks", "sink_vs_reference"}),
    ("flip_one_answer", {"accepted_ids_vs_reference", "utxo_entries_vs_reference", "bad_status_blocks"}),
])
def test_control_and_fault_come_out_not_correct(bench, toy, break_name, numbers):
    out = _run(bench, toy, wrap=control.BREAKS[break_name])
    assert out["correct"] is False
    assert {k for k, v in out["checks"].items() if v[0] != v[1]} & numbers


def test_window_blocks_are_the_grid(toy):
    _w, _c, d = toy
    outputs = {(tx.id(), j): o for b in d.blocks for tx in b.transactions for j, o in enumerate(tx.outputs)}
    grid = sorted(mix.GRID)
    for b in d.blocks[d.ramp :]:
        txs = b.transactions[1:]
        assert sorted(len(tx.inputs) for tx in txs if len(tx.outputs) == 1) == grid  # the merges
        assert sorted(len(tx.outputs) for tx in txs if len(tx.inputs) == 1) == grid  # the splits
        for tx in txs:  # all inputs of a transaction, and its outputs, are of one class
            spent = {_class(outputs[(i.previous_outpoint.transaction_id, i.previous_outpoint.index)].script_public_key.script) for i in tx.inputs}
            assert len(spent) == 1 and {_class(o.script_public_key.script) for o in tx.outputs} == spent
    assert {s["cls"] for s in d.spoiled.values()} == set(mix.SPOILS)
    assert d.sig_samples and all(valid for *_rest, valid in d.sig_samples)


def test_spoiled_spends_and_a_sample_of_honest_ones_went_to_the_reference(toy_grid):
    from kaspa_tpu.ops import dispatch

    dispatch.configure(0)
    asked = Counter()
    real = mix.Shape._ask_reference

    def counting(self, tx, entries, spoiled_input):
        real(self, tx, entries, spoiled_input)
        asked["honest" if spoiled_input is None else "spoiled"] += 1

    mix.Shape._ask_reference = counting
    try:
        spec = dagmod.DagSpec(bps=2, delay=1.0, miners=4, tx_per_block=8, window_blocks=12, seed=6, tx_shape="checkscripts-mix", spoiled_blocks=4)
        d = dagmod.build(spec)
    finally:
        mix.Shape._ask_reference = real
    assert len(d.spoiled) == 4 and asked["spoiled"] >= 4 and asked["honest"] >= 1


def test_a_verdict_that_differs_from_the_construction_ends_the_build(toy_grid, monkeypatch):
    from kaspa_tpu.ops import dispatch

    dispatch.configure(0)
    monkeypatch.setattr(mix.reference_scripts, "input_verdict", lambda *a: True)  # a reference that accepts the spoiled input
    spec = dagmod.DagSpec(bps=2, delay=1.0, miners=4, tx_per_block=8, window_blocks=12, seed=6, tx_shape="checkscripts-mix", spoiled_blocks=4)
    with pytest.raises(RuntimeError, match="the reference's verdict"):
        dagmod.build(spec)


def test_probe_raises_when_the_batch_path_is_stubbed_out(monkeypatch):
    from kaspa_tpu.txscript.batch import BatchScriptChecker

    mix.probe_program()  # this program: accepted, nothing sent to the VM
    monkeypatch.setattr(BatchScriptChecker, "_collect_multisig", lambda self, *a, **k: None)
    with pytest.raises(RuntimeError, match="sends multisig to the host VM"):
        mix.probe_program()


def test_the_cell_must_ask_for_a_block_of_the_grid(real_grid):
    spec = dagmod.DagSpec(bps=2, delay=1.0, miners=4, tx_per_block=10, window_blocks=12, seed=6, tx_shape="checkscripts-mix")
    with pytest.raises(ValueError, match="12 transactions"):
        mix.Shape(spec, None, [], None, None, [])


# ---- the real constants against the configuration's ``transactions``
def test_grid_shares_and_rotation_equal_the_configuration(real_grid):
    t = harness.load_json(os.path.join(ROOT, "benchmarks", "configs", "checkscripts-mix.json"))["transactions"]
    grid = list(mix.GRID)
    assert grid == t["merges"]["inputs"] == t["splits"]["outputs"] == [2, 5, 10, 25, 50, 100]
    assert t["per_block"] == 2 * len(grid) == t["merges"]["a_block"] + t["splits"]["a_block"]
    assert t["signed_inputs_per_block"] == sum(grid) + len(grid) == t["outputs_per_block"] == 198
    assert sorted(g for group in mix.GROUPS for g in group) == grid and len(mix.GROUPS) == 4
    assert [list(p) for p in mix.SIGNERS] == t["multisig"]["signers_of_input_j"]
    assert (mix.VALUE, mix.FEE, t["non_uniq"]) == (t["small_output_sompi"], t["fee_sompi"], 0)
    # over any 4 consecutive blocks of a miner every grid size meets multisig once, ECDSA once, Schnorr twice;
    # a split spends the merged output of two blocks before, of that block's class
    for start in range(4):
        by_input = Counter()
        for tt in range(start, start + 4):
            for g in grid:
                by_input[mix.class_of(g, tt)] += g  # the merge's inputs
                by_input[mix.class_of(g, tt - 2)] += 1  # the split's
        total = sum(by_input.values())
        assert total == 4 * 198
        shares = t["class_share_by_input"]
        assert by_input["schnorr"] / total == shares["schnorr_p2pk"] == 0.5
        assert by_input["ecdsa"] / total == shares["ecdsa_p2pk"] == 0.25
        assert by_input["multisig"] / total == shares["p2sh_multisig_2of3_schnorr"] == 0.25
        for g in grid:
            assert Counter(mix.class_of(g, tt) for tt in range(start, start + 4)) == {"schnorr": 2, "ecdsa": 1, "multisig": 1}
    # every class has a grid size with a spare large output in every block: a spoiled spend can always be placed
    assert all({mix.class_of(g, tt) for g in mix.SPARE} == set(mix.CLASSES) for tt in range(4))
    # the widest tickets a block sends: Schnorr inputs and four pairs a multisig input (splits included), ECDSA inputs
    for tt in range(4):
        jobs = Counter()
        for g in grid:
            for cls, n in ((mix.class_of(g, tt), g), (mix.class_of(g, tt - 2), 1)):
                jobs["ecdsa" if cls == "ecdsa" else "schnorr"] += 4 * n if cls == "multisig" else n
        assert jobs["schnorr"] <= 512 and jobs["ecdsa"] <= 256


def test_the_traffic_file_warms_what_a_block_sends():
    w = harness.load_json(os.path.join(ROOT, "benchmarks", "workloads", f"{CELL}.json"))
    assert max(w["pretrace"]["schnorr_verify"]) == 512 and max(w["pretrace"]["ecdsa_verify"]) == 256
    assert min(w["pretrace"]["schnorr_verify"]) == 8 == min(w["pretrace"]["ecdsa_verify"])
    assert w["tx_per_block"] == 12 and w["max_in_flight"] == 99 and w["spoiled_blocks"] == 4 and "gap_stratum_blocks" not in w
    spans = w["idle_gap_spans"]
    assert spans[spans.index("txscript.collect") + 1 :][:2] == ["txscript.multisig_resolve", "txscript.fallback_join"]
