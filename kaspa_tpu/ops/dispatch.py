"""Asynchronous verify-dispatch engine: cross-block batch coalescing.

`BatchScriptChecker.dispatch()` historically blocked per block: every
block's handful of signature jobs paid full jit-dispatch latency at low
device occupancy.  This module owns a process-wide coalescing queue in
front of the batched verify kernels (`crypto/secp.py`): signature jobs
from *concurrent* callers — pipeline stage workers, mempool checks, RPC
validators — accumulate into device-sized super-batches and are flushed
by a dedicated dispatcher thread:

- **size**: a kind's pending jobs reach the target (``DEFAULT_TARGET``
  unless the caller pins one);
- **age**: the oldest queued chunk exceeds the flush age
  (``KASPA_TPU_COALESCE_AGE_MS``, default 2 ms);
- **nudge**: a caller blocks on its ticket — the queue flushes as soon
  as the dispatcher is idle, so a serial caller sees near-zero added
  latency and *bit-identical* results (verify masks are per-lane
  functions of each triple; batch composition cannot change them);
- **drain/barrier**: shutdown or an explicit `drain()` flushes
  everything and blocks until every callback has resolved.

Double buffering: the staging buffer is swapped out wholesale under the
lock (the host keeps collecting/sighashing block N+1 into the fresh
buffer) while the dispatcher marshals the taken chunks and runs the
device kernel — the taken arrays are *donated* to the dispatch in the
sense that no host reference mutates them afterwards, so XLA is free to
alias them.  The mesh path (`ops/mesh.py`) pads once per super-batch
instead of once per block.

Consensus note: `_calculate_utxo_state` consumes each merged block's
script results before building the next block's UTXO view, so the
production consensus path keeps its synchronous `dispatch()` semantics
(submit + nudge).  Coalescing wins come from jobs that arrive while the
device is busy — concurrent pipeline stages, the mempool lane — and from
callers that use `dispatch_async()` to overlap their own host work.

Traffic classes: a kind may be class-qualified (``"standalone_tx:schnorr"``)
to give a workload its own batch-size dynamics without a second queue.
Standalone-transaction admission (the ingest tier) arrives in small
concurrent bursts rather than block-sized slabs, so the ``standalone_tx``
class carries its own coalesce target (``KASPA_TPU_TX_COALESCE``, default
256) and flush age (``KASPA_TPU_TX_COALESCE_AGE_MS``, default 5 ms);
flush triggers, chunk packing, and span/counter attribution all key on
the full qualified kind, while the device call maps back to the base
kind — so the fabric balancer, breaker degradation, and host fallback are
inherited unchanged.
"""

from __future__ import annotations

import itertools
import os
import threading

from kaspa_tpu.utils.sync import ranked_lock
import time
from dataclasses import dataclass, field
from time import perf_counter_ns

import numpy as np

from kaspa_tpu.observability import trace
from kaspa_tpu.observability.core import REGISTRY, SIZE_BUCKETS

# shared id stamped on every fan-back span of one device dispatch, so a
# trace viewer can correlate the N per-ticket child spans that rode the
# same super-batch
_super_ids = itertools.count(1)

DEFAULT_TARGET = 1024
_TARGET_MIN, _TARGET_MAX = 8, 16384
_WAIT_CAP_S = 600.0  # ticket.wait() hard cap: covers a cold ladder compile

# standalone-transaction admission traffic class (the ingest tier's lane)
TX_CLASS = "standalone_tx"
DEFAULT_TX_TARGET = 256


def base_kind(kind: str) -> str:
    """Strip a traffic-class qualifier: "standalone_tx:schnorr" -> "schnorr"."""
    return kind.split(":", 1)[1] if ":" in kind else kind


def traffic_class(kind: str) -> str:
    """The traffic class of a (possibly qualified) kind; "block" default."""
    return kind.split(":", 1)[0] if ":" in kind else "block"

_COALESCE_DEPTH = REGISTRY.histogram(
    "dispatch_coalesce_depth", SIZE_BUCKETS,
    help="caller chunks merged into one super-batch, per dispatch",
)
_SUPER_BATCH = REGISTRY.histogram(
    "dispatch_super_batch_size", SIZE_BUCKETS,
    help="verify jobs per coalesced super-batch dispatch",
)
_QUEUE_AGE = REGISTRY.histogram(
    "dispatch_queue_age_seconds",
    (0.0005, 0.001, 0.002, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 1.0, 5.0),
    help="oldest chunk's queue residency at flush time",
)
_FLUSHES = REGISTRY.counter_family(
    "dispatch_flushes", "reason", help="super-batch flushes by trigger (size/age/nudge/drain)"
)
_COALESCED_JOBS = REGISTRY.counter_family(
    "dispatch_coalesced_jobs", "kind", help="verify jobs routed through the coalescing queue"
)
from kaspa_tpu.observability.shed import SHED as _SHED


class DispatchTimeout(TimeoutError):
    """A ticket wait expired.  Carries the chunk's identity (kind, job
    count, super_id once assigned) and the supervision verdict, so the
    error names the wedged super-batch instead of an opaque timeout."""

    def __init__(self, kind: str, jobs: int, super_id: int | None, waited_s: float, verdict: dict):
        sup = f"super_id={super_id}" if super_id is not None else "not yet super-batched"
        super().__init__(
            f"verify dispatch ticket timed out after {waited_s:g}s "
            f"(kind={kind}, jobs={jobs}, {sup}; supervisor: {verdict})"
        )
        self.kind = kind
        self.jobs = jobs
        self.super_id = super_id
        self.waited_s = waited_s
        self.verdict = verdict


class DispatchAbandoned(RuntimeError):
    """The dispatcher was abandoned (hung device thread at shutdown)
    before this chunk resolved; the caller must treat it as unverified."""


class Ticket:
    """Per-chunk completion handle: resolves to the [n] bool validity mask
    for exactly the items submitted (super-batch slicing is internal)."""

    __slots__ = ("_engine", "_event", "_mask", "_error", "kind", "jobs", "super_id")

    def __init__(self, engine: "CoalescingDispatcher | None", kind: str = "", jobs: int = 0):
        self._engine = engine
        self._event = threading.Event()
        self._mask: np.ndarray | None = None
        self._error: Exception | None = None
        self.kind = kind
        self.jobs = jobs
        self.super_id: int | None = None  # stamped when the super-batch forms

    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: float | None = None) -> np.ndarray:
        """Block for this chunk's mask; nudges the queue so a lone waiter
        never sits out the full flush age."""
        if not self._event.is_set():
            if self._engine is not None:
                self._engine.nudge()
            waited = timeout if timeout is not None else _WAIT_CAP_S
            if not self._event.wait(waited):
                from kaspa_tpu.resilience import supervisor  # deferred: import DAG

                raise DispatchTimeout(self.kind, self.jobs, self.super_id, waited, supervisor.verdict())
        if self._error is not None:
            raise self._error
        return self._mask

    def _resolve(self, mask: np.ndarray | None, error: Exception | None) -> None:
        if self._event.is_set():
            return  # first resolution wins (late results from an abandoned
            # dispatcher thread are discarded, never merged)
        self._mask = mask
        self._error = error
        self._event.set()


@dataclass
class _Chunk:
    kind: str  # "schnorr" | "ecdsa", optionally class-qualified ("standalone_tx:schnorr")
    items: list  # [(pubkey, msg, sig), ...] — ownership donated on submit
    ticket: Ticket
    enqueued_at: float = field(default_factory=time.monotonic)
    # producer's TraceContext + enqueue stamp: the dispatcher thread fans
    # the one device span back into each submitting block's trace
    ctx: object = None
    enqueued_ns: int = 0
    resolved: bool = False  # guarded by the engine lock: first finish wins
    deferred: bool = False  # held back at least once by class-yield scheduling


class CoalescingDispatcher:
    """Cross-caller coalescing queue in front of secp's batched kernels."""

    def __init__(self, target: int, max_age_s: float, class_specs: dict | None = None):
        self.target = max(_TARGET_MIN, min(_TARGET_MAX, int(target)))
        self.max_age_s = max_age_s
        # traffic class -> (target, max_age_s): per-class batch dynamics for
        # class-qualified kinds; unqualified kinds use the defaults above
        self.class_specs = {
            cls: (max(_TARGET_MIN, min(_TARGET_MAX, int(t))), float(age))
            for cls, (t, age) in (class_specs or {}).items()
        }
        self._lock = ranked_lock("dispatch.queue", reentrant=False)
        self._wake = self._lock.condition()
        self._idle = self._lock.condition()
        # class-yield brownout seam: traffic classes in this set are held
        # back from flushes while non-yield work is pending, each chunk for
        # at most _starvation_s (the starvation bound) — the overload
        # controller points this at TX_CLASS under pressure so block-verify
        # super-batches keep the device to themselves
        self._yield_classes: frozenset[str] = frozenset()
        self._starvation_s = 0.25
        self._pending: list[_Chunk] = []  # staging buffer (swapped at flush)
        self._inflight: list[_Chunk] = []  # swapped out, not yet resolved
        self._urgent = False
        self._unresolved = 0  # chunks submitted but not yet resolved
        self._closed = False
        self._abandoned = False
        self._thread: threading.Thread | None = None

    # -- producer side ------------------------------------------------------

    def submit(self, kind: str, items: list) -> Ticket:
        """Queue one chunk of (pubkey, msg, sig) triples; the caller must
        not mutate `items` afterwards (donated to the dispatcher)."""
        ticket = Ticket(self, kind, len(items))
        if not items:
            ticket._resolve(np.zeros(0, dtype=bool), None)
            return ticket
        _COALESCED_JOBS.inc(kind, len(items))
        with self._lock:
            if self._closed:
                raise RuntimeError("verify dispatcher is shut down")
            self._pending.append(
                _Chunk(kind, items, ticket, ctx=trace.context(), enqueued_ns=perf_counter_ns())
            )
            self._unresolved += 1
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="verify-dispatch", daemon=True
                )
                self._thread.start()
            self._wake.notify()
        return ticket

    def nudge(self) -> None:
        """Request an immediate flush (a caller is blocked on a ticket)."""
        with self._lock:
            self._urgent = True
            self._wake.notify()

    def drain(self, timeout: float = 10.0) -> bool:
        """Flush everything and block until every submitted chunk has
        resolved (True) or the timeout expires (False)."""
        deadline = time.monotonic() + timeout
        with self._lock:
            self._urgent = True
            self._wake.notify()
            while self._unresolved > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._idle.wait(remaining)
        return True

    def close(self, timeout: float = 10.0, abandon: bool = True) -> bool:
        """Drain, then stop accepting work and retire the thread.

        When the drain times out — the dispatcher thread is wedged inside
        a device call — ``abandon=True`` (the default) bounds shutdown:
        every unresolved ticket is failed with DispatchAbandoned and the
        hung thread is left behind as a daemon, so daemon exit never
        blocks on a dead device."""
        drained = self.drain(timeout)
        if not drained and abandon:
            self.abandon("close timeout: device thread hung")
            return False
        with self._lock:
            self._closed = True
            self._wake.notify()
        return drained

    def abandon(self, reason: str) -> int:
        """Fail every unresolved chunk (queued or in flight) with
        DispatchAbandoned and stop accepting work; returns the number of
        chunks abandoned.  The wedged dispatcher thread is not joined —
        any result it later produces hits resolved chunks and is
        discarded."""
        err = DispatchAbandoned(f"verify dispatcher abandoned: {reason}")
        with self._lock:
            self._closed = True
            self._abandoned = True
            victims = [c for c in self._pending + self._inflight if not c.resolved]
            self._pending = []
            self._wake.notify_all()
        for c in victims:
            self._finish(c, None, err)
        return len(victims)

    def set_class_yield(self, classes, starvation_s: float = 0.25) -> None:
        """Make the given traffic classes yield to other pending work.
        A yielded chunk is excluded from flush decisions while non-yield
        chunks are pending, but never for longer than ``starvation_s``
        (the starvation bound) — block floods cannot starve txs forever.
        Empty/None restores plain FIFO coalescing."""
        with self._lock:
            self._yield_classes = frozenset(classes or ())
            self._starvation_s = max(0.0, float(starvation_s))
            self._wake.notify()

    def pressure(self) -> dict:
        """Per-traffic-class backlog snapshot for the overload controller:
        pending+inflight job counts and the oldest pending chunk age."""
        with self._lock:
            now = time.monotonic()
            per: dict[str, dict] = {}
            for c in self._pending:
                d = per.setdefault(traffic_class(c.kind), {"jobs": 0, "oldest_age_s": 0.0})
                d["jobs"] += len(c.items)
                d["oldest_age_s"] = max(d["oldest_age_s"], now - c.enqueued_at)
            for c in self._inflight:
                d = per.setdefault(traffic_class(c.kind), {"jobs": 0, "oldest_age_s": 0.0})
                d["jobs"] += len(c.items)
            return per

    def stats(self) -> dict:
        with self._lock:
            return {
                "target": self.target,
                "max_age_ms": round(self.max_age_s * 1000, 3),
                "classes": {
                    cls: {"target": t, "max_age_ms": round(age * 1000, 3)}
                    for cls, (t, age) in self.class_specs.items()
                },
                "pending_chunks": len(self._pending),
                "inflight_chunks": len(self._inflight),
                "unresolved_chunks": self._unresolved,
                "abandoned": self._abandoned,
                "yield_classes": sorted(self._yield_classes),
                "starvation_ms": round(self._starvation_s * 1000, 3),
            }

    # -- dispatcher thread ---------------------------------------------------

    def _target_for(self, kind: str) -> int:
        spec = self.class_specs.get(traffic_class(kind))
        return spec[0] if spec is not None else self.target

    def _age_for(self, kind: str) -> float:
        spec = self.class_specs.get(traffic_class(kind))
        return spec[1] if spec is not None else self.max_age_s

    def _eligible_locked(self, now: float) -> tuple[list[_Chunk], list[_Chunk]]:
        """Split staged chunks into (eligible, held) under class-yield.
        A chunk is held only while (a) its traffic class yields, (b) some
        non-yield chunk is pending (otherwise there is nothing to yield
        to), and (c) it is younger than the starvation bound.  Drain
        bypasses yielding entirely — shutdown flushes everything."""
        if not self._yield_classes or self._closed:
            return self._pending, []
        if not any(traffic_class(c.kind) not in self._yield_classes for c in self._pending):
            return self._pending, []
        eligible: list[_Chunk] = []
        held: list[_Chunk] = []
        for c in self._pending:
            if (
                traffic_class(c.kind) in self._yield_classes
                and now - c.enqueued_at < self._starvation_s
            ):
                held.append(c)
            else:
                eligible.append(c)
        return eligible, held

    def _flush_reason_locked(self, now: float, eligible: list[_Chunk]) -> str | None:
        if not eligible:
            return None
        if self._closed:
            return "drain"
        if self._urgent:
            return "nudge"
        per_kind: dict[str, int] = {}
        for c in eligible:
            per_kind[c.kind] = per_kind.get(c.kind, 0) + len(c.items)
        if any(n >= self._target_for(k) for k, n in per_kind.items()):
            return "size"
        if any(now - c.enqueued_at >= self._age_for(c.kind) for c in eligible):
            return "age"
        return None

    def _next_age_deadline_locked(self, now: float, held: list[_Chunk]) -> float:
        """Seconds until the earliest chunk becomes actionable (the sleep
        bound).  A held chunk's deadline is its starvation bound, not its
        flush age — otherwise an expired flush age on a held chunk makes
        this 0 and the loop busy-spins until the starvation bound."""
        held_ids = {id(c) for c in held}  # _Chunk is unhashable (dataclass eq)
        deadlines = [
            (self._starvation_s if id(c) in held_ids else self._age_for(c.kind))
            - (now - c.enqueued_at)
            for c in self._pending
        ]
        return max(0.0, min(deadlines))

    def _run(self) -> None:
        while True:
            with self._lock:
                while True:
                    if self._abandoned:
                        return
                    now = time.monotonic()
                    if not self._pending:
                        # a stale nudge with nothing queued must not force
                        # the next lone chunk into a depth-1 flush
                        self._urgent = False
                    eligible, held = self._eligible_locked(now)
                    reason = self._flush_reason_locked(now, eligible)
                    if reason is not None:
                        break
                    if self._closed and not self._pending:
                        return
                    if self._pending:
                        # sleep only until the earliest chunk is actionable
                        self._wake.wait(self._next_age_deadline_locked(now, held))
                    else:
                        self._wake.wait()
                # double-buffer swap: donate the eligible chunks to this
                # flush cycle (held chunks stay staged for a later flush);
                # producers refill a fresh buffer while XLA runs below
                for c in held:
                    if not c.deferred:
                        c.deferred = True
                        _SHED.inc("dispatch_yield")
                taken = eligible
                self._pending = held
                self._inflight.extend(taken)
                self._urgent = False
            self._dispatch(taken, reason)

    def _dispatch(self, chunks: list[_Chunk], reason: str) -> None:
        _FLUSHES.inc(reason)
        now = time.monotonic()
        by_kind: dict[str, list[_Chunk]] = {}
        for c in chunks:
            by_kind.setdefault(c.kind, []).append(c)
        for kind, group in by_kind.items():
            # greedy whole-chunk packing into <= target super-batches (a
            # single chunk larger than the target still goes out in one)
            target = self._target_for(kind)
            i = 0
            while i < len(group):
                batch, jobs = [], 0
                while i < len(group) and (not batch or jobs + len(group[i].items) <= target):
                    batch.append(group[i])
                    jobs += len(group[i].items)
                    i += 1
                self._run_super_batch(kind, batch, jobs, now, reason)

    def _run_super_batch(self, kind: str, batch: list[_Chunk], jobs: int, now: float, reason: str) -> None:
        from kaspa_tpu.crypto import secp  # deferred: keeps import DAG acyclic

        _COALESCE_DEPTH.observe(len(batch))
        _SUPER_BATCH.observe(jobs)
        _QUEUE_AGE.observe(now - min(c.enqueued_at for c in batch))
        sid = next(_super_ids)
        for c in batch:
            c.ticket.super_id = sid  # a timeout now names the super-batch
        items = [it for c in batch for it in c.items]
        try:
            t0 = perf_counter_ns()
            # class-qualified kinds map to their base kernel here, keeping
            # the fabric/breaker behavior identical per class
            with trace.span("dispatch.super_batch", kind=kind, jobs=jobs, chunks=len(batch)):
                mask = np.asarray(secp.verify_batch(base_kind(kind), items))
            t1 = perf_counter_ns()
        except Exception as e:  # noqa: BLE001 - surfaced on every waiting ticket
            t1 = perf_counter_ns()
            self._fan_back(kind, batch, jobs, sid, t1, t1, reason, error=type(e).__name__)
            for c in batch:
                self._finish(c, None, e)
            return
        self._fan_back(kind, batch, jobs, sid, t0, t1, reason)
        pos = 0
        for c in batch:
            self._finish(c, mask[pos : pos + len(c.items)], None)
            pos += len(c.items)

    def _fan_back(
        self, kind: str, batch: list[_Chunk], jobs: int, sid: int, t0: int, t1: int, reason: str, **extra
    ) -> None:
        """Fan the single device dispatch back into each submitting block's
        trace: a retroactive ``wait.dispatch`` (enqueue -> kernel start,
        with what flushed the queue: nudge / age / size / drain) plus a
        ``dispatch.device`` child covering the device interval, stamped
        with a shared super_id so Perfetto can correlate them."""
        for c in batch:
            if c.ctx is None:
                continue
            trace.record_span("wait.dispatch", c.ctx, c.enqueued_ns, t0, reason=reason)
            trace.record_span(
                "dispatch.device", c.ctx, t0, t1,
                kind=kind, jobs=len(c.items), super_jobs=jobs,
                chunks=len(batch), super_id=sid, **extra,
            )

    def _finish(self, chunk: _Chunk, mask, error) -> bool:
        """Resolve one chunk exactly once; False = already resolved (a
        late result from an abandoned dispatcher thread, discarded)."""
        with self._lock:
            if chunk.resolved:
                return False
            chunk.resolved = True
            try:
                self._inflight.remove(chunk)
            except ValueError:
                pass  # abandoned straight from the staging buffer
            self._unresolved -= 1
            if self._unresolved == 0:
                self._idle.notify_all()
        chunk.ticket._resolve(mask, error)
        return True


# --- process-wide configuration (mirrors ops/mesh.py) -----------------------

_cfg_lock = ranked_lock("dispatch.config")
_configured: str | int | None = None
_engine: CoalescingDispatcher | None = None

def _flush_age_s() -> float:
    return float(os.environ.get("KASPA_TPU_COALESCE_AGE_MS", "2")) / 1000.0


def _tx_class_spec(block_target: int) -> tuple[int, float]:
    """(target, age) for the standalone_tx class.  Admission batches are
    built from concurrent submitters, not block-sized slabs: the default
    target is smaller than the block-replay target and the flush age a bit
    longer, so a burst of independent submitters coalesces while a lone
    submitter still resolves within single-digit milliseconds."""
    raw = os.environ.get("KASPA_TPU_TX_COALESCE", "")
    target = int(raw) if raw else min(block_target, DEFAULT_TX_TARGET)
    age = float(os.environ.get("KASPA_TPU_TX_COALESCE_AGE_MS", "5")) / 1000.0
    return max(_TARGET_MIN, min(_TARGET_MAX, target)), age


def configure(spec: int | str | None) -> int:
    """Select the process-wide coalescing mode; returns the resolved
    super-batch target (0 = disabled, the default).

    spec: None/0/"off" disable; "auto" is DEFAULT_TARGET; an integer pins
    the target.  With no explicit spec
    the KASPA_TPU_COALESCE env var is consulted the same way.
    """
    global _configured, _engine
    with _cfg_lock:
        raw = spec if spec is not None else os.environ.get("KASPA_TPU_COALESCE", "0")
        _configured = raw
        old, _engine = _engine, None
    if old is not None:
        old.close(timeout=10.0)
    if raw in (0, "0", "", "off", None):
        return 0
    target = DEFAULT_TARGET if raw == "auto" else int(raw)
    target = max(_TARGET_MIN, min(_TARGET_MAX, target))
    with _cfg_lock:
        _engine = CoalescingDispatcher(
            target, _flush_age_s(), class_specs={TX_CLASS: _tx_class_spec(target)}
        )
    return target


def install(engine) -> None:
    """Install a custom dispatch engine as the process-wide verify engine
    (the fabric balancer uses this to become what `active()` returns, so
    BatchScriptChecker / the pipeline / daemon shutdown pick it up
    unchanged).  Any engine exposing the CoalescingDispatcher surface —
    submit/nudge/drain/close/abandon/stats — qualifies; a previously live
    engine is retired first."""
    global _configured, _engine
    with _cfg_lock:
        old, _engine = _engine, engine
        _configured = getattr(engine, "label", type(engine).__name__)
    if old is not None and old is not engine:
        old.close(timeout=10.0)


def active() -> CoalescingDispatcher | None:
    """The live engine, or None when coalescing is disabled."""
    return _engine


def drain(timeout: float = 10.0) -> bool:
    """Flush + resolve everything in flight (daemon-shutdown barrier).
    No-op True when coalescing is disabled."""
    eng = _engine
    return eng.drain(timeout) if eng is not None else True


def shutdown(timeout: float = 10.0) -> bool:
    """Daemon-stop barrier: drain and retire the engine, abandoning it if
    the device thread is hung so process exit stays bounded.  True = clean
    drain; False = tickets were failed with DispatchAbandoned."""
    global _engine
    with _cfg_lock:
        eng, _engine = _engine, None
    return eng.close(timeout, abandon=True) if eng is not None else True


def _dispatch_state() -> dict:
    eng = _engine
    if eng is None:
        return {"enabled": False, "configured": str(_configured) if _configured is not None else ""}
    out = {"enabled": True, "configured": str(_configured)}
    out.update(eng.stats())
    return out


REGISTRY.register_collector("dispatch", _dispatch_state)
