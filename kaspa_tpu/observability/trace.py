"""Low-overhead span tracer: ``trace.span("stage", **attrs)``.

Spans time a code region on the monotonic clock (perf_counter_ns), nest
through a thread-local stack, and report their duration into a per-stage
latency histogram in the global registry.  Optionally a bounded in-memory
span log captures every completed span (name, path, start, duration,
thread, attrs) for offline replay by ``tools/trace_report.py``.

Cross-thread causality (flight recorder substrate): every recorded span
carries Dapper-style identity — ``trace`` (the block hash that owns it),
``span`` (a process-unique id), ``parent`` (the enclosing span's id).
Within a thread the ids flow through the TLS stack as before; across a
queue boundary the producer captures ``trace.context()`` (a small
immutable ``TraceContext``) and the consumer reopens the tree with
``trace.span("stage", parent=ctx)`` or records an already-elapsed
interval with ``trace.record_span(...)`` (queue waits, fan-back device
spans).  ``kaspa_tpu.observability.flight`` installs ``_flight_sink`` to
collect per-trace span sets into the ring buffer.

Cost model (the contract tests/test_observability.py asserts loosely):
- tracing disabled: ``span()`` returns a shared no-op object — well under
  a microsecond per use;
- tracing enabled: one small-object allocation, two clock reads, one
  histogram observe and a stack push/pop — single-digit microseconds.

Exception safety: ``__exit__`` always pops the stack and always records
the span (tagging ``error`` with the exception type); the exception
propagates unchanged.
"""

from __future__ import annotations

import itertools
import threading
from collections import deque
from time import perf_counter_ns

from kaspa_tpu.observability.core import DEFAULT_LATENCY_BUCKETS, REGISTRY

# per-stage latency: the "per-stage latency histograms" surface of
# RpcCoreService.get_metrics()["observability"]["histograms"]
SPAN_HIST = REGISTRY.histogram_family(
    "span_duration_seconds", "stage", DEFAULT_LATENCY_BUCKETS,
    help="wall time of traced spans by stage name",
)

_tls = threading.local()
_enabled = True
_capture: deque | None = None  # bounded span log for trace_report replay
_flight_sink = None  # set by observability.flight when the recorder is on
_next_id = itertools.count(1).__next__  # process-unique span ids


class TraceContext:
    """Immutable handle passed across thread/queue boundaries.

    ``trace_id`` is the owning block hash (hex), ``span_id`` the producer
    span to parent on, ``path`` the slash-joined ancestry so flame paths
    stay connected in trace_report across threads.
    """

    __slots__ = ("trace_id", "span_id", "path")

    def __init__(self, trace_id: str | None, span_id: int, path: str):
        self.trace_id = trace_id
        self.span_id = span_id
        self.path = path

    def __repr__(self):  # debugging aid only
        return f"TraceContext({self.trace_id!r}, {self.span_id}, {self.path!r})"


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NOOP = _NoopSpan()


class Span:
    __slots__ = ("name", "attrs", "path", "_t0", "trace_id", "span_id", "parent_id", "_parent")

    def __init__(self, name: str, attrs: dict, parent: TraceContext | None = None):
        self.name = name
        self.attrs = attrs
        self.path = name
        self._t0 = 0
        self.trace_id = None
        self.span_id = 0
        self.parent_id = 0
        self._parent = parent

    def __enter__(self):
        st = _stack()
        if st:
            top = st[-1]
            self.path = top.path + "/" + self.name
            self.trace_id = top.trace_id
            self.parent_id = top.span_id
        elif self._parent is not None:
            p = self._parent
            self.path = p.path + "/" + self.name
            self.trace_id = p.trace_id
            self.parent_id = p.span_id
        self.span_id = _next_id()
        st.append(self)
        self._t0 = perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = perf_counter_ns()
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        SPAN_HIST.observe(self.name, (t1 - self._t0) * 1e-9)
        if _capture is not None or _flight_sink is not None:
            if exc_type is not None:
                self.attrs["error"] = exc_type.__name__
            _sink(
                {
                    "name": self.name,
                    "path": self.path,
                    "trace": self.trace_id,
                    "span": self.span_id,
                    "parent": self.parent_id,
                    "start_us": self._t0 // 1000,
                    "dur_us": (t1 - self._t0) / 1000.0,
                    "start_ns": self._t0,
                    "end_ns": t1,
                    "thread": threading.current_thread().name,
                    "depth": len(st),
                    "attrs": self.attrs,
                }
            )
        return False  # never swallow the exception

    def context(self) -> TraceContext:
        """Handle for parenting work handed to another thread/queue."""
        return TraceContext(self.trace_id, self.span_id, self.path)

    def set(self, **attrs) -> None:
        """Attributes known only once the region has run (counts)."""
        self.attrs.update(attrs)


def _sink(rec: dict) -> None:
    cap = _capture
    if cap is not None:
        cap.append(rec)
    fs = _flight_sink
    if fs is not None:
        fs(rec)


def span(name: str, parent: TraceContext | None = None, **attrs) -> Span | _NoopSpan:
    """Open a timed span; use as ``with trace.span("stage", key=val):``.

    ``parent`` (a TraceContext) grafts this span onto a tree started on
    another thread; it only applies when this thread's span stack is
    empty — an enclosing local span always wins.
    """
    if not _enabled:
        return _NOOP
    return Span(name, attrs, parent)


def record_span(
    name: str,
    parent: TraceContext | None,
    t0_ns: int,
    t1_ns: int,
    **attrs,
) -> TraceContext | None:
    """Record an already-elapsed interval (queue wait, fan-back device
    span) retroactively: the producer stamped ``t0_ns`` (perf_counter_ns)
    when it enqueued, the consumer calls this at pickup.  Returns the new
    span's context so callers can parent further children on it."""
    if not _enabled:
        return None
    if t1_ns < t0_ns:
        t1_ns = t0_ns
    SPAN_HIST.observe(name, (t1_ns - t0_ns) * 1e-9)
    if _capture is None and _flight_sink is None:
        return None
    trace_id = parent.trace_id if parent is not None else None
    parent_id = parent.span_id if parent is not None else 0
    path = (parent.path + "/" + name) if parent is not None else name
    return _record(name, trace_id, _next_id(), parent_id, path, t0_ns, t1_ns, attrs)


def _record(name, trace_id, sid, parent_id, path, t0_ns, t1_ns, attrs) -> TraceContext:
    _sink(
        {
            "name": name,
            "path": path,
            "trace": trace_id,
            "span": sid,
            "parent": parent_id,
            "start_us": t0_ns // 1000,
            "dur_us": (t1_ns - t0_ns) / 1000.0,
            "start_ns": t0_ns,
            "end_ns": t1_ns,
            "thread": threading.current_thread().name,
            "depth": 0,
            "attrs": attrs,
        }
    )
    return TraceContext(trace_id, sid, path)


def root_context(trace_id: str, name: str) -> TraceContext:
    """Context of a root span that ``record_root`` will close later: work
    handed across queues parents on it (and carries ``trace_id``) before
    the root's extent is known.  An object only: no ring, no lock."""
    return TraceContext(trace_id, _next_id(), name)


def record_root(ctx: TraceContext, t0_ns: int, t1_ns: int, **attrs) -> None:
    """Close the root span ``root_context`` opened, under the id its
    children already name as their parent."""
    if not _enabled:
        return
    t1_ns = max(t1_ns, t0_ns)
    SPAN_HIST.observe(ctx.path, (t1_ns - t0_ns) * 1e-9)
    if _capture is not None or _flight_sink is not None:
        _record(ctx.path, ctx.trace_id, ctx.span_id, 0, ctx.path, t0_ns, t1_ns, attrs)


def context() -> TraceContext | None:
    """TraceContext of this thread's innermost open span (None outside)."""
    st = getattr(_tls, "stack", None)
    return st[-1].context() if st else None


def enabled() -> bool:
    return _enabled


def sinks_active() -> bool:
    """True when completed spans actually land somewhere (capture log or
    flight ring).  Ultra-hot paths use this to skip building retroactive
    spans nobody would collect."""
    return _capture is not None or _flight_sink is not None


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def current_path() -> str:
    """Slash-joined path of the active span stack on this thread."""
    st = getattr(_tls, "stack", None)
    return st[-1].path if st else ""


def set_capture(maxlen: int = 65536) -> None:
    """Turn the bounded span log on (maxlen > 0) or off (maxlen == 0)."""
    global _capture
    _capture = deque(maxlen=maxlen) if maxlen > 0 else None


def drain() -> list[dict]:
    """Return and clear the captured span log (oldest first)."""
    cap = _capture
    if cap is None:
        return []
    out = []
    while cap:
        try:
            out.append(cap.popleft())
        except IndexError:  # racing producer threads; good enough
            break
    return out


def dump(path: str) -> int:
    """Write the captured span log as JSONL for tools/trace_report.py."""
    import json

    spans = drain()
    with open(path, "w") as f:
        for s in spans:
            f.write(json.dumps(s) + "\n")
    return len(spans)
