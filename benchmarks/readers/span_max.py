"""The longest single occurrence of one named program span in the window: what
the slowest cycle, hold or wait cost, where there are too few of them for a
quantile (``span_quantile`` reads nothing under 20 groups).

source: {"reader": "span_max", "span": name, "exclude_attrs": {k: v}, "unit_scale": 0.001}
``unit_scale`` turns microseconds into the metric's unit (0.001: ms).
"""


def read(source: dict, ctx: dict):
    skip = source.get("exclude_attrs", {})
    durations = [
        s["dur_us"] for s in ctx["spans"]
        if s["name"] == source["span"] and not any((s.get("attrs") or {}).get(k) == v for k, v in skip.items())
    ]
    if not durations:
        return None
    return max(durations) * float(source.get("unit_scale", 0.001))
