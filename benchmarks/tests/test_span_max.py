"""``readers/span_max.py`` (PR 37) on known intervals (no chip, no program).
Beside ``test_readers.py`` and not inside it: a PR that is not a ``benchmark``
PR edits no file the benchmark has."""

from benchmarks.readers import span_max


def _span(name, start_us, end_us, thread="t0", **attrs):
    return {"name": name, "start_ns": start_us * 1000, "end_ns": end_us * 1000, "dur_us": float(end_us - start_us),
            "thread": thread, "trace": None, "attrs": attrs}


MAX = {"reader": "span_max", "span": "cycle", "exclude_attrs": {"shared": True}, "unit_scale": 0.001}


def test_span_max_is_the_longest_of_the_named_spans():
    spans = [
        _span("cycle", 0, 12_000),                    # 12 ms: the longest that counts
        _span("cycle", 20_000, 27_500, thread="t1"),  # on whatever thread
        _span("cycle", 0, 90_000, shared=True),       # the synthetic copy of a shared cycle: left out
        _span("other", 0, 10**6),                     # another name
    ]
    assert span_max.read(MAX, {"spans": spans, "window": {}}) == 12.0
    assert span_max.read(dict(MAX, exclude_attrs={}), {"spans": spans, "window": {}}) == 90.0
    assert span_max.read(dict(MAX, unit_scale=1.0), {"spans": spans[1:2], "window": {}}) == 7500.0


def test_span_max_reads_nothing_without_the_span():
    assert span_max.read(MAX, {"spans": [], "window": {}}) is None
    assert span_max.read(MAX, {"spans": [_span("other", 0, 10)], "window": {}}) is None
    assert span_max.read(MAX, {"spans": [_span("cycle", 0, 10, shared=True)], "window": {}}) is None
