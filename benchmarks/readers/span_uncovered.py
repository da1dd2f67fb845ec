"""What of one named program span no listed span covers: the union of its
intervals minus the union of the intervals of every ``covered_by`` span, on
whatever thread those ran, divided by a named count of the window.  Self time
by intervals rather than by parent ids, so it survives the hand-offs between
threads (the dispatcher, the supervised worker) that parent ids do not.

source: {"reader": "span_uncovered", "span": name, "exclude_attrs": {k: v},
         "covered_by": [names], "per": "blocks", "unit_scale": 0.001}
``unit_scale`` turns microseconds into the metric's unit (0.001: ms).
"""


def read(source: dict, ctx: dict):
    from benchmarks import reduce

    skip, inner = source.get("exclude_attrs", {}), set(source["covered_by"])
    own, cover = [], []
    for s in ctx["spans"]:
        if s["name"] == source["span"]:
            attrs = s.get("attrs") or {}
            if not any(attrs.get(k) == v for k, v in skip.items()):
                own.append((s["start_ns"], s["end_ns"]))
        elif s["name"] in inner:
            cover.append((s["start_ns"], s["end_ns"]))
    per = ctx["window"].get(source.get("per", "blocks"))
    if not own or not per:
        return None
    left = reduce.subtract(reduce.union(own), reduce.union(cover))
    return sum(e - s for s, e in left) * 1e-3 * float(source.get("unit_scale", 0.001)) / per
