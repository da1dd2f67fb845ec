"""Every per-layer metric that reads program spans or counters finds
something to read in a CPU rehearsal of its cell (toy sizes, the XLA ladder at
bucket 8): a span or counter renamed in the program shows here, not first on
the chip.  The quantile readers need 20 blocks and the device-trace readers a
device plane, so both may read nothing here; no number of these runs is a
device number."""

import os

import pytest

from benchmarks import harness
from benchmarks.tests.conftest import ROOT, TOY_WIDE
from benchmarks.tests.test_rehearse import _check_line, _run

READS_ON_CPU = {"span_sum", "span_uncovered", "counter_ratio", "harness_value"}


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("cell, mode, kw", [
    ("crescendo-10bps.catchup-10tpb", "catchup", {}),
    ("crescendo-10bps.paced-10tpb", "paced", {}),
    ("simpa-8bps.catchup-200tpb", "catchup", TOY_WIDE),
])
def test_span_and_counter_metrics_read_a_number(bench, cell, mode, kw):
    lines = []
    out = _run(bench, cell, mode, True, lines, **kw)
    _check_line(out, bench, cell, True)
    expected = set()
    for m in bench["per_layer"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        spec = harness.load_json(os.path.join(ROOT, "benchmarks", "metrics", f"{m['name']}.json"))
        if spec["source"]["reader"] in READS_ON_CPU:
            expected.add(m["name"])
    assert expected and expected <= set(out["metrics"]), sorted(expected - set(out["metrics"]))
    # the inner spans lie inside the outer ones they open
    v = {k: m["value"] for k, m in out["metrics"].items()}
    if mode == "paced":
        assert v["dispatch_wait_uncovered_ms_per_block.paced"] <= v["dispatch_wait_ms_per_block.paced"]
        assert v["dispatch_queue_wait_ms_per_block.paced"] <= v["dispatch_wait_ms_per_block.paced"]
    else:
        assert v["virtual_uncovered_ms_per_block.catchup"] <= v["pipeline_virtual_ms_per_block.catchup"]
        assert v["verify_padded_lane_occupancy_pct"] <= v["verify_lane_occupancy_pct"]
        assert 0 < v["muhash_lane_occupancy_pct"] <= 100
        # the price of a reorganisation (ROADMAP S3) is read in both catch-up cells
        assert 0 < v["virtual_move_position_ms_per_block.catchup"] <= v["pipeline_virtual_ms_per_block.catchup"]
