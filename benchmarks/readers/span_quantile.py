"""A quantile, over the blocks of the window, of the time one named program
span took for each: the span's durations are summed per ``trace`` id (the
block's hash, which every span of a block carries while spans are captured)
and the nearest-rank quantile is taken over those sums, as
``modes/paced.percentile`` takes the end-to-end one.  A span with no trace id
is a group of its own.  Under 20 groups (at q = 0.95 nothing lies beyond the
quantile) there is nothing to read.

source: {"reader": "span_quantile", "span": name, "q": 0.95, "group_by": "trace", "unit_scale": 0.001}
``unit_scale`` turns microseconds into the metric's unit (0.001: ms).
"""

MIN_GROUPS = 20


def read(source: dict, ctx: dict):
    from benchmarks.modes.paced import percentile

    key = source.get("group_by", "trace")
    groups: dict = {}
    for i, s in enumerate(ctx["spans"]):
        if s["name"] == source["span"]:
            g = s.get(key)
            g = ("", i) if g is None else g
            groups[g] = groups.get(g, 0.0) + s["dur_us"]
    if len(groups) < MIN_GROUPS:
        return None
    return percentile(list(groups.values()), float(source["q"])) * float(source.get("unit_scale", 0.001))
