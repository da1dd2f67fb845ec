"""From a profiler trace and the program's spans to numbers: device busy and
idle time, device time per event name, and the longest idle gaps with what the
host was doing in each.

``load_xplane`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
into plain lists; everything else here is arithmetic on intervals, so it is
checked on known intervals in ``tests/test_reduce.py`` without a chip.

Clocks: device events carry the profiler's nanoseconds.  The harness opens
one ``TraceAnnotation("bench.window")`` beside a ``perf_counter_ns()``
reading; the annotation's start in the trace minus that reading is the
offset that puts the program's spans (perf_counter_ns) on the trace's clock.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# the line of a device plane that holds one event per executed operation; the
# others ("XLA Modules", "Async XLA Ops", ...) wrap or repeat these
OP_LINES = ("XLA Ops",)
ANCHOR = "bench.window"


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load_xplane(path: str) -> dict:
    """{"devices": {plane: [(name, start_ns, dur_ns)]}, "lines": {plane: {line: count}},
    "anchor_ns": start of the bench.window annotation or None}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, lines, anchor = {}, {}, None
    for plane in data.planes:
        counts = lines.setdefault(plane.name, {})
        is_device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            n = 0
            keep = is_device and line.name in OP_LINES
            for ev in line.events:
                n += 1
                if keep:
                    devices.setdefault(plane.name, []).append((ev.name, int(ev.start_ns), int(ev.duration_ns)))
                elif not is_device and anchor is None and ev.name == ANCHOR:
                    anchor = int(ev.start_ns)
            counts[line.name] = n
    return {"devices": devices, "lines": lines, "anchor_ns": anchor}


def union(intervals: list) -> list:
    """Merge (start, end) intervals; returns disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: list, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_seconds(events: list, lo: int, hi: int) -> float:
    """Seconds of [lo, hi) in which at least one event ran."""
    merged = union(clip([(s, s + d) for _n, s, d in events], lo, hi))
    return sum(e - s for s, e in merged) * 1e-9


def by_name(events: list, lo: int, hi: int) -> dict:
    """Device seconds and count per event name inside [lo, hi): {name: [seconds, count]}."""
    out: dict = {}
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            row = out.setdefault(name, [0.0, 0])
            row[0] += (b - a) * 1e-9
            row[1] += 1
    return out


def gaps(events: list, lo: int, hi: int) -> list:
    """Idle (start, end) intervals of [lo, hi), longest first."""
    merged = union(clip([(s, s + d) for _n, s, d in events], lo, hi))
    out, cur = [], lo
    for s, e in merged:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def intersect(a: list, b: list) -> list:
    """Intersection of two sorted lists of disjoint (start, end) intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: list, b: list) -> list:
    """What of the sorted disjoint intervals ``a`` lies outside ``b``."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def idle_by_span(events: list, lo: int, hi: int, spans: list, offset_ns: int, names: tuple, top: int = 10) -> list:
    """[[what the host was doing, idle seconds]], largest first.  Every
    idle nanosecond of [lo, hi) goes to the first of ``names`` (innermost
    first) whose program span covers it, else to "no span".  ``spans`` carry
    perf_counter ``start_ns``/``end_ns``; ``offset_ns`` puts them on the
    trace's clock.  One sweep per name: linear in events and spans."""
    out, left = {}, sorted(gaps(events, lo, hi))
    for name in names:
        cover = union(clip(
            [(sp["start_ns"] + offset_ns, sp["end_ns"] + offset_ns) for sp in spans if sp["name"] == name], lo, hi
        ))
        got = intersect(left, cover)
        if got:
            out[name] = sum(b - a for a, b in got) * 1e-9
            left = subtract(left, cover)
    rest = sum(b - a for a, b in left) * 1e-9
    if rest > 0:
        out["no span"] = rest
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:top]]


def short_name(name: str) -> str:
    """An XLA op event is named by its whole HLO text: keep the op's id, its
    result shape and its kind ("%fusion.61 = s32[32,23,64] fusion")."""
    m = re.match(r"(%[^ ]+) = ([a-z0-9]+\[[0-9,]*\])[^ ]* ([a-z\-]+)\(", name)
    return f"{m.group(1)} = {m.group(2)} {m.group(3)}" if m else name[:96]


def reduce_trace(xp: dict, window_ns: tuple | None, spans: list, perf_anchor_ns: int | None, span_names: tuple) -> dict:
    """Everything the trace readers need.  ``window_ns`` is (lo, hi) on the
    perf_counter clock, or None for the extent of the device events."""
    if not xp["devices"]:
        return {"busy_s": 0.0, "window_s": 0.0, "per_device_busy_s": {}, "by_name": {}, "device_ops": [], "idle_gaps": [],
                "clock_offset_known": False}
    offset = (xp["anchor_ns"] - perf_anchor_ns) if xp["anchor_ns"] is not None and perf_anchor_ns is not None else None
    every = [ev for evs in xp["devices"].values() for ev in evs]
    if window_ns is not None and offset is not None:
        lo, hi = window_ns[0] + offset, window_ns[1] + offset
    else:
        lo, hi = min(s for _n, s, _d in every), max(s + d for _n, s, d in every)
    per_device = {plane: busy_seconds(evs, lo, hi) for plane, evs in sorted(xp["devices"].items())}
    names: dict = {}
    for evs in xp["devices"].values():
        for name, (secs, count) in by_name(evs, lo, hi).items():
            row = names.setdefault(name, [0.0, 0])
            row[0] += secs
            row[1] += count
    first = next(iter(sorted(xp["devices"])))
    top_ops = sorted(names.items(), key=lambda kv: -kv[1][0])[:10]
    return {
        "busy_s": sum(per_device.values()) / len(per_device),
        "window_s": (hi - lo) * 1e-9,
        "per_device_busy_s": per_device,
        "by_name": names,
        "device_ops": [[short_name(n), v[0]] for n, v in top_ops],
        "idle_gaps": idle_by_span(xp["devices"][first], lo, hi, spans, offset or 0, span_names if offset is not None else ()),
        "clock_offset_known": offset is not None,
    }
