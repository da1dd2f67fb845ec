#!/usr/bin/env python
"""Replay a captured span log into a per-stage flame summary.

Input: a JSONL span log written by ``kaspa_tpu.observability.trace.dump``
(one span dict per line: name/path/start_us/dur_us/thread/depth/attrs), or
a JSON document embedding such a list under an ``observability`` /
``spans`` key — e.g. a chip_smoke.py phase line — or a flight-recorder dump
(``kaspa_tpu.observability.flight.dump``: per-block span trees with
critical-path attribution).

Output: a path-aggregated flame table (total vs self time, counts,
mean/max) plus the slowest individual spans — enough to answer "which
stage stalled" when a run reports 0.0 verifies/sec.  Flight dumps
additionally get a per-block critical-path table, and export to the
Chrome trace-event format that ui.perfetto.dev / chrome://tracing load:

    python tools/trace_report.py /tmp/spans.jsonl
    python tools/trace_report.py bench_line.json --top 15
    python tools/trace_report.py FLIGHT.json --critical-path
    python tools/trace_report.py FLIGHT.json --perfetto trace.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _flight_module():
    """Import kaspa_tpu.observability.flight, tolerating a bare checkout
    (tools/ run from anywhere without the package installed)."""
    try:
        from kaspa_tpu.observability import flight
    except ImportError:
        sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        from kaspa_tpu.observability import flight
    return flight


def load_flight(path: str) -> dict | None:
    """Return the parsed flight dump if ``path`` holds one, else None."""
    with open(path) as f:
        head = f.read(256)
    if '"kaspa-flight"' not in head:
        return None
    with open(path) as f:
        doc = json.load(f)
    if doc.get("format") != "kaspa-flight":
        return None
    return doc


def _find_spans(obj) -> list | None:
    """Depth-first hunt for a list of span dicts inside a JSON document."""
    if isinstance(obj, list):
        if obj and isinstance(obj[0], dict) and "dur_us" in obj[0] and ("path" in obj[0] or "name" in obj[0]):
            return obj
        for item in obj:
            found = _find_spans(item)
            if found is not None:
                return found
        return None
    if isinstance(obj, dict):
        for key in ("spans", "observability", "tail"):
            if key in obj:
                found = _find_spans(obj[key])
                if found is not None:
                    return found
        for v in obj.values():
            found = _find_spans(v)
            if found is not None:
                return found
    return None


def load_spans(path: str) -> list[dict]:
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
    if doc is not None:
        spans = _find_spans(doc)
        if spans is None:
            raise SystemExit(f"{path}: JSON document contains no span list")
        return spans
    spans = []
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        spans.append(json.loads(line))
    return spans


def aggregate(spans: list[dict]) -> dict[str, dict]:
    """Per-path totals; self time = total minus direct children's total."""
    agg: dict[str, dict] = {}
    for s in spans:
        path = s.get("path") or s.get("name", "?")
        a = agg.setdefault(path, {"count": 0, "total_us": 0.0, "max_us": 0.0})
        dur = float(s.get("dur_us", 0.0))
        a["count"] += 1
        a["total_us"] += dur
        if dur > a["max_us"]:
            a["max_us"] = dur
    for path, a in agg.items():
        child_total = sum(
            other["total_us"]
            for opath, other in agg.items()
            if opath.startswith(path + "/") and "/" not in opath[len(path) + 1 :]
        )
        a["self_us"] = max(0.0, a["total_us"] - child_total)
    return agg


def _ms(us: float) -> str:
    return f"{us / 1000.0:10.3f}"


def render_report(spans: list[dict], top: int = 10) -> str:
    if not spans:
        return "no spans in input\n"
    agg = aggregate(spans)
    lines = [f"{len(spans)} spans over {len(agg)} stages", ""]
    lines.append(f"{'stage (path)':<52} {'count':>7} {'total ms':>10} {'self ms':>10} {'mean ms':>9} {'max ms':>10}")
    lines.append("-" * 102)
    # flame ordering: depth-first by path so children sit under parents,
    # roots sorted by total time descending
    roots = sorted(
        (p for p in agg if "/" not in p), key=lambda p: -agg[p]["total_us"]
    )

    def emit(path: str, indent: int) -> None:
        a = agg[path]
        label = ("  " * indent) + path.rsplit("/", 1)[-1]
        mean = a["total_us"] / a["count"]
        lines.append(
            f"{label:<52} {a['count']:>7} {_ms(a['total_us'])} {_ms(a['self_us'])} "
            f"{mean / 1000.0:>9.3f} {_ms(a['max_us'])}"
        )
        children = sorted(
            (
                p
                for p in agg
                if p.startswith(path + "/") and "/" not in p[len(path) + 1 :]
            ),
            key=lambda p: -agg[p]["total_us"],
        )
        for c in children:
            emit(c, indent + 1)

    for r in roots:
        emit(r, 0)
    lines.append("")
    lines.append(f"slowest {top} spans:")
    slowest = sorted(spans, key=lambda s: -float(s.get("dur_us", 0.0)))[:top]
    for s in slowest:
        attrs = s.get("attrs") or {}
        attr_txt = " ".join(f"{k}={v}" for k, v in attrs.items())
        lines.append(
            f"  {float(s.get('dur_us', 0.0)) / 1000.0:10.3f} ms  {s.get('path', s.get('name', '?')):<40}"
            f" [{s.get('thread', '?')}] {attr_txt}"
        )
    return "\n".join(lines) + "\n"


def render_by_shard(spans: list[dict], top: int = 10) -> str:
    """Flame table grouped by the ``shard`` attr serving spans carry
    (``serving.fanout`` / ``serving.deliver`` / ``wait.serving_queue``):
    per-shard totals first, then each shard's stage breakdown.  Spans
    without a shard tag (the single-fanout path, consensus stages) group
    under ``unsharded``."""
    if not spans:
        return "no spans in input\n"
    groups: dict[str, list[dict]] = {}
    for s in spans:
        shard = (s.get("attrs") or {}).get("shard")
        key = f"shard {shard}" if shard is not None else "unsharded"
        groups.setdefault(key, []).append(s)
    lines = [f"{len(spans)} spans over {len(groups)} shard groups", ""]
    lines.append(f"{'group':<14} {'spans':>7} {'total ms':>10} {'max ms':>10}")
    lines.append("-" * 44)
    order = sorted(
        groups, key=lambda g: -sum(float(s.get("dur_us", 0.0)) for s in groups[g])
    )
    for g in order:
        durs = [float(s.get("dur_us", 0.0)) for s in groups[g]]
        lines.append(
            f"{g:<14} {len(durs):>7} {_ms(sum(durs))} {_ms(max(durs))}"
        )
    for g in order:
        lines.append("")
        lines.append(f"== {g} ==")
        lines.append(render_report(groups[g], top=top).rstrip())
    return "\n".join(lines) + "\n"


def render_critical_path(doc: dict, top: int = 10) -> str:
    """Per-block critical-path table + aggregate stage attribution for a
    flight dump (recomputed from the span trees, so dumps predating the
    embedded summary still work)."""
    flight = _flight_module()
    traces = doc.get("traces", [])
    if not traces:
        return "no traces in flight dump\n"
    lines = [f"{len(traces)} block traces (dump reason: {doc.get('reason', '?')})", ""]
    lines.append(f"{'block':<18} {'spans':>6} {'threads':>8} {'wall ms':>9} {'attrib %':>9}  top stages")
    lines.append("-" * 100)
    agg: dict[str, float] = {}
    fractions = []
    for t in traces:
        spans = t["spans"]
        root = next((s for s in spans if s["name"] == "block"), spans[0])
        cp = flight.critical_path(spans, root["span"])
        fractions.append(cp["fraction"])
        stages = sorted(cp["stages"].items(), key=lambda kv: -kv[1])
        for name, ns in stages:
            agg[name] = agg.get(name, 0.0) + ns
        top3 = " ".join(f"{n}={ns / 1e6:.1f}ms" for n, ns in stages[:3] if n != "block")
        lines.append(
            f"{t['label'][:16]:<18} {len(spans):>6} {len({s['thread'] for s in spans}):>8} "
            f"{cp['total_ns'] / 1e6:>9.2f} {cp['fraction'] * 100:>8.1f}%  {top3}"
        )
    lines.append("")
    lines.append(f"min/mean attribution: {min(fractions) * 100:.1f}% / {sum(fractions) / len(fractions) * 100:.1f}%")
    lines.append("")
    lines.append("aggregate critical-path time by stage:")
    total = sum(agg.values()) or 1.0
    for name, ns in sorted(agg.items(), key=lambda kv: -kv[1])[:top]:
        lines.append(f"  {name:<28} {ns / 1e6:>10.2f} ms  {ns / total * 100:>5.1f}%")
    return "\n".join(lines) + "\n"


def export_perfetto(doc: dict, out_path: str) -> str:
    """Write the Chrome trace-event JSON for a flight dump; load the file
    at ui.perfetto.dev or chrome://tracing."""
    flight = _flight_module()
    chrome = flight.chrome_trace(doc.get("traces", []))
    with open(out_path, "w") as f:
        json.dump(chrome, f)
    return out_path


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description="per-stage flame summary from a span log")
    ap.add_argument("log", help="span JSONL file, JSON document embedding a span list, or flight dump")
    ap.add_argument("--top", type=int, default=10, help="slowest individual spans to list")
    ap.add_argument(
        "--perfetto", default=None, metavar="OUT",
        help="convert a flight dump to Chrome trace-event JSON at OUT (open in ui.perfetto.dev)",
    )
    ap.add_argument(
        "--critical-path", action="store_true",
        help="per-block critical-path attribution table (flight dumps only)",
    )
    ap.add_argument(
        "--by-shard", action="store_true",
        help="group the flame table by the serving tier's shard span tag "
        "(untagged spans group under 'unsharded')",
    )
    args = ap.parse_args(argv)
    doc = load_flight(args.log)
    if args.by_shard:
        spans = (
            [s for t in doc.get("traces", []) for s in t["spans"]]
            if doc is not None
            else load_spans(args.log)
        )
        sys.stdout.write(render_by_shard(spans, top=args.top))
        return
    if args.perfetto or args.critical_path:
        if doc is None:
            raise SystemExit(f"{args.log}: not a flight-recorder dump (need format=kaspa-flight)")
        if args.perfetto:
            path = export_perfetto(doc, args.perfetto)
            n = sum(len(t["spans"]) for t in doc.get("traces", []))
            sys.stdout.write(f"wrote {path}: {len(doc.get('traces', []))} block traces, {n} spans\n")
        if args.critical_path:
            sys.stdout.write(render_critical_path(doc, top=args.top))
        return
    if doc is not None:
        spans = [s for t in doc.get("traces", []) for s in t["spans"]]
        sys.stdout.write(render_report(spans, top=args.top))
        sys.stdout.write("\n")
        sys.stdout.write(render_critical_path(doc, top=args.top))
        return
    sys.stdout.write(render_report(load_spans(args.log), top=args.top))


if __name__ == "__main__":
    main()
