"""Sum of the durations of one named program span over the window, divided
by a named count of the window.

source: {"reader": "span_sum", "span": name, "exclude_attrs": {k: v}, "per": "blocks", "unit_scale": 0.001}
``unit_scale`` turns microseconds into the metric's unit (0.001: ms).
"""


def read(source: dict, ctx: dict):
    skip = source.get("exclude_attrs", {})
    total_us, n = 0.0, 0
    for s in ctx["spans"]:
        if s["name"] != source["span"]:
            continue
        attrs = s.get("attrs") or {}
        if any(attrs.get(k) == v for k, v in skip.items()):
            continue
        total_us += s["dur_us"]
        n += 1
    per = ctx["window"].get(source.get("per", "blocks"))
    if n == 0 or not per:
        return None
    return total_us * float(source.get("unit_scale", 0.001)) / per
