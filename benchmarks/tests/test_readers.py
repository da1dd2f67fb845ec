"""The two span readers of PR 26 on known intervals (no chip, no program)."""

from benchmarks.readers import span_quantile, span_uncovered


def _span(name, start_us, end_us, thread="t0", trace=None, **attrs):
    return {"name": name, "start_ns": start_us * 1000, "end_ns": end_us * 1000, "dur_us": float(end_us - start_us),
            "thread": thread, "trace": trace, "attrs": attrs}


UNCOVERED = {"reader": "span_uncovered", "span": "outer", "exclude_attrs": {"shared": True},
             "covered_by": ["a", "b"], "per": "blocks", "unit_scale": 0.001}


def test_span_uncovered_over_two_threads_with_overlapping_children():
    spans = [
        _span("outer", 0, 100),                       # 100 us on the caller's thread
        _span("a", 10, 40, thread="worker"),          # another thread: covers 10-40
        _span("b", 30, 60, thread="worker-2"),        # overlaps a: the union covers 10-60, not 30 + 30
        _span("a", 90, 120, thread="worker"),         # runs past outer's end: only 90-100 counts
        _span("c", 60, 90),                           # not listed: covers nothing
        _span("outer", 200, 300, shared=True),        # the synthetic copy of a shared cycle: left out
        _span("outer", 400, 450),                     # a second cycle, wholly uncovered
    ]
    # (100 - 50 - 10) + 50 = 90 us over 2 blocks = 0.045 ms a block
    got = span_uncovered.read(UNCOVERED, {"spans": spans, "window": {"blocks": 2}})
    assert abs(got - 0.045) < 1e-12


def test_span_uncovered_overlapping_outer_spans_count_once():
    spans = [_span("outer", 0, 100), _span("outer", 50, 150, thread="t1"), _span("a", 0, 150, thread="w")]
    assert span_uncovered.read(UNCOVERED, {"spans": spans, "window": {"blocks": 1}}) == 0.0


def test_span_uncovered_reads_nothing_without_the_span_or_the_count():
    assert span_uncovered.read(UNCOVERED, {"spans": [_span("a", 0, 10)], "window": {"blocks": 3}}) is None
    assert span_uncovered.read(UNCOVERED, {"spans": [_span("outer", 0, 10)], "window": {"blocks": 0}}) is None
    assert span_uncovered.read(UNCOVERED, {"spans": [], "window": {"blocks": 3}}) is None


QUANTILE = {"reader": "span_quantile", "span": "wait", "q": 0.95, "group_by": "trace", "unit_scale": 0.001}


def test_span_quantile_sums_the_spans_of_one_trace():
    # 19 blocks of 1,000 us each, and one block whose wait came in two spans of 3,000 + 4,000 us
    spans = [_span("wait", 0, 1000, trace=f"{i:02x}") for i in range(19)]
    spans += [_span("wait", 0, 3000, trace="ff"), _span("wait", 5000, 9000, trace="ff"), _span("other", 0, 10**6, trace="ff")]
    ctx = {"spans": spans, "window": {}}
    assert span_quantile.read(dict(QUANTILE, q=1.0), ctx) == 7.0   # the two spans of ff are one block
    assert span_quantile.read(QUANTILE, ctx) == 1.0                # nearest rank: the 19th of 20
    assert span_quantile.read(dict(QUANTILE, q=0.5), ctx) == 1.0


def test_span_quantile_takes_a_span_with_no_trace_as_a_group_of_its_own():
    spans = [_span("wait", 0, 100 * (i + 1)) for i in range(20)]  # no trace id on any
    assert abs(span_quantile.read(QUANTILE, {"spans": spans, "window": {}}) - 1.9) < 1e-12  # 19th of 20 groups


def test_span_quantile_reads_nothing_under_twenty_groups():
    spans = [_span("wait", 0, 1000, trace=f"{i:02x}") for i in range(19)]
    assert span_quantile.read(QUANTILE, {"spans": spans, "window": {}}) is None
    assert span_quantile.read(QUANTILE, {"spans": [], "window": {}}) is None
    spans.append(_span("wait", 0, 1000, trace="13"))  # the twentieth group
    assert span_quantile.read(QUANTILE, {"spans": spans, "window": {}}) == 1.0
