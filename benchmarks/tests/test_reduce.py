"""The reducer on known intervals: busy/idle union, per-name sums, gap
attribution and the clock offset — and on a small trace recorded on the chip
(``fixtures/trace_v5e_small.json``: device events and program spans of one
traced run, cut to a few hundred events, numbers checked by hand)."""

import json
import os

import pytest

from benchmarks import reduce

US = 1000  # the trace's clock is in nanoseconds


def ev(name, start_us, dur_us):
    return (name, start_us * US, dur_us * US)


EVENTS = [
    ev("ladder", 0, 100), ev("ladder", 50, 100),  # overlap: union 0..150
    ev("tree", 300, 50),
    ev("tree", 900, 200),  # runs past the window's end at 1000
]


def test_union_merges_overlaps_and_keeps_order():
    assert reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_busy_is_the_union_clipped_to_the_window():
    assert reduce.busy_seconds(EVENTS, 0, 1000 * US) == pytest.approx((150 + 50 + 100) * 1e-6)


def test_per_name_sums_count_overlap_twice_and_clip():
    names = reduce.by_name(EVENTS, 0, 1000 * US)
    assert names["ladder"] == [pytest.approx(200e-6), 2]
    assert names["tree"] == [pytest.approx(150e-6), 2]


def test_gaps_longest_first():
    g = reduce.gaps(EVENTS, 0, 1000 * US)
    assert g == [(350 * US, 900 * US), (150 * US, 300 * US)]


def test_idle_time_goes_to_the_innermost_span_that_covers_it():
    offset = 10_000 * US  # perf_counter reads 10 ms less than the trace's clock
    spans = [
        {"name": "pipeline.virtual", "start_ns": (340 - 10_000) * US, "end_ns": (860 - 10_000) * US},
        {"name": "store.flush", "start_ns": (800 - 10_000) * US, "end_ns": (850 - 10_000) * US},  # inside virtual
        {"name": "unlisted", "start_ns": (150 - 10_000) * US, "end_ns": (300 - 10_000) * US},
    ]
    rows = dict(reduce.idle_by_span(EVENTS, 0, 1000 * US, spans, offset, ("store.flush", "pipeline.virtual")))
    # idle: 150..300 and 350..900
    assert rows["store.flush"] == pytest.approx(50e-6)  # the inner span wins where both cover
    assert rows["pipeline.virtual"] == pytest.approx((450 + 10) * 1e-6)
    assert rows["no span"] == pytest.approx((150 + 40) * 1e-6)
    assert sum(rows.values()) == pytest.approx(1000e-6 - reduce.busy_seconds(EVENTS, 0, 1000 * US))


def test_interval_arithmetic():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (45, 60)]
    assert reduce.intersect(a, b) == [(5, 10), (20, 25), (45, 50)]
    assert reduce.subtract(a, b) == [(0, 5), (25, 30), (40, 45)]
    assert reduce.subtract(a, []) == a and reduce.intersect(a, []) == []


def test_a_big_trace_reduces_in_seconds():
    import time

    events = [("op", i * 10 * US, 4 * US) for i in range(200_000)]
    spans = [{"name": "pipeline.virtual" if i % 2 else "muhash.commit", "start_ns": i * 40 * US, "end_ns": (i * 40 + 25) * US}
             for i in range(50_000)]
    t0 = time.perf_counter()
    rows = dict(reduce.idle_by_span(events, 0, 2_000_000 * US, spans, 0, ("muhash.commit", "pipeline.virtual")))
    assert time.perf_counter() - t0 < 10
    assert sum(rows.values()) == pytest.approx(2.0 - 0.8)


def test_reduce_trace_puts_the_window_on_the_traces_clock():
    xp = {"devices": {"/device:TPU:0": EVENTS}, "lines": {}, "anchor_ns": 5_000 * US}
    # the window opened at perf_counter 1,000 us, which the anchor says is 5,000 us on the trace
    shifted = {"devices": {"/device:TPU:0": [(n, s + 5_000 * US, d) for n, s, d in EVENTS]}, "lines": {}, "anchor_ns": 5_000 * US}
    out = reduce.reduce_trace(shifted, (1_000 * US, 2_000 * US), [], 1_000 * US, ())
    assert out["window_s"] == pytest.approx(1e-3)
    assert out["busy_s"] == pytest.approx(300e-6)
    assert out["device_ops"][0][0] == "ladder"
    assert out["clock_offset_known"] is True
    # without an anchor the window is the extent of the device events
    out = reduce.reduce_trace(dict(xp, anchor_ns=None), (0, 1), [], None, ())
    assert out["window_s"] == pytest.approx(1100e-6) and out["clock_offset_known"] is False


def test_no_device_plane_reads_nothing():
    out = reduce.reduce_trace({"devices": {}, "lines": {}, "anchor_ns": None}, None, [], None, ())
    assert out["busy_s"] == 0.0 and out["device_ops"] == []
    from benchmarks.readers import trace_idle

    assert trace_idle.read({}, {"trace": out}) is None  # never 100% idle from an empty trace


FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "trace_v5e_small.json")


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace fixture")
def test_recorded_trace():
    rec = json.load(open(FIXTURE))
    events = [tuple(e) for e in rec["events"]]
    lo, hi = rec["window_ns"]
    assert reduce.busy_seconds(events, lo, hi) == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    names = reduce.by_name(events, lo, hi)
    for name, secs in rec["expect"]["by_name"].items():
        assert names[name][0] == pytest.approx(secs, rel=1e-9)
    assert reduce.busy_seconds(events, lo, hi) <= (hi - lo) * 1e-9
    idle = (hi - lo) * 1e-9 - reduce.busy_seconds(events, lo, hi)
    assert sum(e - s for s, e in reduce.gaps(events, lo, hi)) * 1e-9 == pytest.approx(idle)
    # the spans were recorded on the trace's clock: every idle second is given to something
    from benchmarks.harness import HERE, load_json

    span_names = tuple(load_json(os.path.join(HERE, "workloads", "crescendo-10bps.catchup-10tpb.json"))["idle_gap_spans"])
    rows = reduce.idle_by_span(events, lo, hi, [dict(s) for s in rec["spans_on_trace_clock"]], 0, span_names, top=99)
    assert sum(v for _k, v in rows) == pytest.approx(idle)
    assert rows[0][0] in span_names  # the host was inside a program span for most of it
