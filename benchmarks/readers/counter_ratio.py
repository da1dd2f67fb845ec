"""Ratio of two registry counters (or counter families) over the window.

source: {"reader": "counter_ratio", "numerator": term, "denominator": term, "scale": 100}
term:   {"counter": name} | {"family": name} (sum of its labels)
        | {"family": name, "weight": "label"} (sum of label-as-number x count)
"""


def _term(term: dict, counters: dict):
    if "counter" in term:
        return counters.get(term["counter"])
    fam = counters.get(term["family"])
    if fam is None:
        return None
    if term.get("weight") == "label":
        return sum(float(label) * n for label, n in fam.items())
    return sum(fam.values())


def read(source: dict, ctx: dict):
    num, den = _term(source["numerator"], ctx["counters"]), _term(source["denominator"], ctx["counters"])
    if not num or not den:
        return None
    return float(source.get("scale", 1)) * num / den
