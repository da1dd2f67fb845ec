"""The wire cell's own mode end to end (ISSUE 35): ``benchmarks/modes/ibd_wire.py``
through the same ``harness.run_cell`` as a run, the donor in a process of its
own, toy size, ``IBD_BATCH_SIZE`` patched to 16; then ``control_ibd.py``'s
three breaks of the donor, each of which must come out not correct.  CPU, XLA
ladder at bucket 8; nothing here is a device number."""

import json
import os
import time

import pytest

from benchmarks import harness
from kaspa_tpu.ops import dispatch as coalescing
from kaspa_tpu.p2p import node as node_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "crescendo-10bps-ibd.catchup-10tpb-wire"
CHUNK = 16
SECONDS = 0.5  # the donor offers for half a second: the first chunk goes out, the request after it (seconds later on a CPU) is answered with ``done``
WORKLOAD = {
    "config": "toy", "mode": "ibd_wire", "tx_per_block": 2, "tx_shape": "fanout-then-1to1",
    "window_blocks": 48, "spoiled_blocks": 2, "pool_factor": 3, "grace_seconds": 60, "sig_samples": 4,
    "pretrace": {"schnorr_verify": [8]}, "trace_seconds": 1.0,
    "idle_gap_spans": ["p2p.decode", "wait.p2p_frame", "wait.node_lock", "pipeline.virtual", "ibd.insert_batch"],
}
CONFIG = {"name": "toy", "network": {"bps": 2, "delay_s": 1.0, "miners": 4}, "pipeline": {"coalesce": 64, "stage_workers": 2},
          "p2p": {"wire": "proto"}}
COUNTS = ("ibd_blocks_missing", "ibd_blocks_unsent_held", "ibd_rerequests", "ibd_bad_continuations")
NEW_METRICS = ("wire_decode_ms_per_block.ibd", "wire_wait_ms_per_block.ibd", "ibd_insert_ms_per_block",
               "ibd_blocks_per_chunk", "wire_bytes_per_block.ibd")


@pytest.fixture(scope="module")
def dag():
    coalescing.configure(0)
    return harness.build_dag(WORKLOAD, CONFIG, 35, lambda _m: None)



def test_the_mode_end_to_end_traced(dag, monkeypatch):
    monkeypatch.setattr(node_mod, "IBD_BATCH_SIZE", CHUNK)
    lines: list = []
    out = harness.run_cell(WORKLOAD, CONFIG, BENCH, CELL, seed=35, seconds=SECONDS, trace=True,
                           process_start=time.perf_counter(), log=lines.append, dag=dag)
    assert out["correct"] is True, {k: v for k, v in out["checks"].items() if v[0] != v[1]}
    assert out["attempted"] == 16 and out["failed"] == 0
    ibd = json.loads(next(ln for ln in lines if ln.startswith("ibd ")).split(" ", 1)[1])
    assert {k: ibd[k] for k in COUNTS} == dict.fromkeys(COUNTS, 0) and ibd["chunk_blocks"] == CHUNK and ibd["passes"] == 1
    assert ibd["pulls"][0]["chunks"] == 1 and ibd["pulls"][0]["resubmitted"] == 0
    window = json.loads(next(ln for ln in lines if ln.startswith("window ")).split(" ", 1)[1])
    assert window["blocks"] == 16 and window["end_to_end"]["catchup_blocks_per_s"] > 0
    # the five new metrics read what the path opened; a CPU run has no device plane, so no idle share and no roofline
    assert set(NEW_METRICS) <= set(out["metrics"]), sorted(out["metrics"])
    assert out["metrics"]["ibd_blocks_per_chunk"]["value"] == 16.0
    assert not any("idle" in k or "roofline" in k for k in out["metrics"])
    assert {"pipeline_virtual_ms_per_block.catchup", "script_collect_ms_per_block.catchup"} <= set(out["metrics"])


@pytest.mark.parametrize("name", ["withhold_one_block", "flip_one_signature_byte", "serve_a_chunk_twice"])
def test_control_ibd_breaks_are_not_correct(dag, monkeypatch, name):
    from benchmarks import control_ibd
    from benchmarks.modes import ibd_wire

    monkeypatch.setattr(node_mod, "IBD_BATCH_SIZE", CHUNK)
    row = control_ibd.run_break(BENCH, CELL, WORKLOAD, CONFIG, seed=35, seconds=SECONDS, wrap=control_ibd.breaks(chunk=0)[name], dag=dag)
    assert ibd_wire.FAULT is None  # the break gives the donor back
    assert row["correct"] is False and "unresolved_blocks" in row["failing"]
    if name == "serve_a_chunk_twice":
        assert row["ibd"]["ibd_rerequests"] >= 1 and row["ibd"]["ibd_blocks_missing"] == 0
        assert set(row["failing"]) == {"unresolved_blocks"}  # the state itself stays right
    elif name == "withhold_one_block":
        # the children of the block that never came are refused for a missing parent, and counted where the loop used to pass
        assert row["ibd"]["ibd_blocks_missing"] >= 1 and row["p2p_ibd_blocks_rejected"] >= 1
    else:
        # the body is refused and the header stays: the virtual stage then fails on a mergeset block without a body,
        # the reader drops the peer and the chunk is never acknowledged
        assert row["ibd"]["hung_up"] == 1 and row["ibd"]["held"] < row["ibd"]["served"] == 16
