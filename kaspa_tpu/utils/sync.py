"""Concurrency primitives: channels, reader-writer lock, lock-order debug.

The runtime counterpart of the reference's kaspa-utils sync layer
(utils/src/channel.rs, utils/src/sync/rwlock.rs, utils/src/sync/
semaphore.rs).  Python-runtime notes baked into the design:

- Channels are closeable MPMC queues (async_channel semantics): `send`
  after close raises, receivers drain remaining items then see `Closed`.
- LockCtx is the race/deadlock *detection* strategy (SURVEY §5): with
  KASPA_TPU_LOCK_DEBUG=1 every guarded acquisition records a per-thread
  held-set and asserts a global partial order over lock ranks — a cycle
  (deadlock candidate) fails loudly in tests instead of hanging a node.
"""

from __future__ import annotations

import collections
import os
import threading
import time


class Closed(Exception):
    """Channel closed and drained."""


class Channel:
    """Closeable MPMC FIFO channel (utils/src/channel.rs semantics)."""

    def __init__(self, maxsize: int = 0):
        self._q: collections.deque = collections.deque()
        self._maxsize = maxsize
        self._mu = threading.Lock()
        self._not_empty = threading.Condition(self._mu)
        self._not_full = threading.Condition(self._mu)
        self._closed = False

    def send(self, item) -> None:
        with self._mu:
            if self._closed:
                raise Closed("send on closed channel")
            while self._maxsize and len(self._q) >= self._maxsize:
                self._not_full.wait()
                if self._closed:
                    raise Closed("send on closed channel")
            self._q.append(item)
            self._not_empty.notify()

    def recv(self, timeout: float | None = None):
        with self._mu:
            while not self._q:
                if self._closed:
                    raise Closed
                if not self._not_empty.wait(timeout):
                    raise TimeoutError
            item = self._q.popleft()
            self._not_full.notify()
            return item

    def drain(self, max_items: int | None = None) -> list:
        """Atomically take everything currently queued (up to ``max_items``)."""
        with self._mu:
            if max_items is None or max_items >= len(self._q):
                items = list(self._q)
                self._q.clear()
            elif max_items <= 0:
                return []
            else:
                items = [self._q.popleft() for _ in range(max_items)]
            self._not_full.notify_all()
            return items

    def close(self) -> None:
        with self._mu:
            self._closed = True
            self._not_empty.notify_all()
            self._not_full.notify_all()

    def __iter__(self):
        while True:
            try:
                yield self.recv()
            except Closed:
                return

    def __len__(self) -> int:
        with self._mu:
            return len(self._q)


# ----------------------------------------------------------------------
# lock-order debugging (deadlock detection strategy)
# ----------------------------------------------------------------------

_LOCK_DEBUG = bool(os.environ.get("KASPA_TPU_LOCK_DEBUG"))
_held = threading.local()
# per-lock contention/hold aggregates under debug: the runtime analog of
# the reference's semaphore trace feature (utils/src/sync/semaphore.rs
# trace-enabled acquisition accounting)
_trace_mu = threading.Lock()
_trace: dict[str, list] = {}  # name -> [acquisitions, total_hold_s, max_hold_s]


def set_lock_debug(on: bool) -> None:
    """Toggle lock-order checking + hold tracing (tests; env is read once)."""
    global _LOCK_DEBUG
    _LOCK_DEBUG = bool(on)


def lock_trace_snapshot() -> dict:
    """{lock name: {acquisitions, total_hold_s, max_hold_s}} accumulated
    while debug is on — contention hunting without a profiler attached."""
    with _trace_mu:
        return {
            name: {"acquisitions": c, "total_hold_s": round(t, 6), "max_hold_s": round(m, 6)}
            for name, (c, t, m) in _trace.items()
        }


def _trace_record(name: str, held_s: float) -> None:
    with _trace_mu:
        entry = _trace.setdefault(name, [0, 0.0, 0.0])
        entry[0] += 1
        entry[1] += held_s
        entry[2] = max(entry[2], held_s)


# The global lock rank table: locks may only be acquired in strictly
# ascending rank order on one thread (same-instance re-entry excepted).
# Every production LockCtx takes its rank from here via ranked_lock() so
# the whole-node partial order is reviewable in one place.  Rationale for
# the ordering (outer → inner as ranks ascend):
#
#   node / ingest.state sit at the outside: RPC and P2P entry points take
#   them first, then descend into consensus commit, then into the leaf
#   queues/stats.  Wire/service/stats locks are leaves — nothing else is
#   acquired while they are held — so they rank highest.  daemon.upnp is
#   a pure leaf around a blocking-free socket probe.
RANKS: dict[str, int] = {
    "service.shutdown": 3,     # core/service.py — held across service.stop() fan-out
    "node": 5,                 # p2p/node.py — outermost node state
    "ingest.state": 7,         # ingest/tier.py — mempool admission state
    "overload.state": 8,       # resilience/overload.py — controller level state
    "consensus-commit": 10,    # pipeline/pipeline.py — UTXO commit section
    "pipeline.deps": 20,       # pipeline/deps_manager.py — orphan/deps graph
    "fabric.config": 25,       # fabric/balancer.py — process-wide balancer slot
    "fabric.balancer": 30,     # fabric/balancer.py — slice table + breaker state
    "dispatch.config": 35,     # ops/dispatch.py — process-wide dispatcher slot
    "mesh.config": 38,         # ops/mesh.py — mesh/topology (re)configuration
    "dispatch.queue": 40,      # ops/dispatch.py — verify coalescing queue
    "ingest.queue": 45,        # ingest/queue.py — tx admission queue
    "serving.shards": 49,      # serving/shards.py — sharded-fanout facade (event refs)
    "serving.broadcaster": 50, # serving/broadcaster.py — subscriber table
    "serving.shard": 51,       # serving/shards.py — per-shard scope index + membership
    # (serving/pool.py's ready queue is a stdlib Queue — its internal lock
    # is a leaf taken between broadcaster(50)/shard(51) and subscriber(55)
    # acquisitions, never while either ranked lock is held)
    "serving.subscriber": 55,  # serving/broadcaster.py — per-subscriber buffer
    "pipeline.idle": 60,       # pipeline/pipeline.py — idle/backlog condvar
    "pipeline.speculative": 65,# pipeline/speculative.py — prefetch results
    "fabric.wire": 70,         # fabric/client.py — per-connection write lock
    "fabric.service": 75,      # fabric/service.py — verifyd slice state
    "ingest.stats": 80,        # ingest/tier.py — admission counters (leaf)
    "daemon.upnp": 85,         # node/daemon.py — UPnP probe guard (leaf)
    # leaves (nothing ranked is ever acquired while holding these)
    "p2p.addressbook": 86,     # p2p/address_manager.py — address-book state
    "p2p.connmgr": 87,         # p2p/address_manager.py — dial bookkeeping
    "p2p.links": 88,           # resilience/faults.py — LINKS drop ledger (frame send path)
    "breaker.slot": 89,        # resilience/breaker.py — device-breaker slot swap
    "supervisor.install": 90,  # resilience/supervisor.py — install/shutdown slot
    "supervisor.manifest": 91, # resilience/supervisor.py — warm-manifest file io
    "watchdog.pool": 92,       # resilience/supervisor.py — worker freelist
    "watchdog.task": 93,       # resilience/supervisor.py — per-job result latch
    "watchdog.stats": 94,      # resilience/supervisor.py — requeue counters
    "txscript.pool": 96,       # txscript/batch.py — VM fallback pool slot
    "txscript.inflight": 97,   # txscript/batch.py — drain accounting
    "txscript.cache": 98,      # txscript/caches.py — sighash/sig cache
    "mining.stats": 99,        # mining/rule_engine.py — sync-rate window
    "stratum.stats": 100,      # bridge/stratum.py — per-worker vardiff stats
    "stratum.shares": 101,     # bridge/stratum.py — job ring + share dedup
    "service.list": 102,       # core/service.py — bound-services list
    "wrpc.ids": 104,           # rpc/wrpc.py — client request-id counter
    "storage.build": 105,      # storage/kv.py — one-shot native build guard
    "chacha.build": 106,       # crypto/hostcrypto.py — one-shot native build guard (chacha + lift_x)
    "observability.registry": 110,  # observability/core.py — metric registration (innermost)
}


def ranked_lock(name: str, reentrant: bool = True) -> "LockCtx":
    """A LockCtx whose rank comes from the RANKS table (KeyError on an
    undeclared name — adding a lock means declaring its place in the
    global order first)."""
    return LockCtx(name, RANKS[name], reentrant=reentrant)


class LockCtx:
    """Ranked lock wrapper: acquiring a lock with rank <= any currently
    held rank (on the same thread) is an ordering violation — the static
    discipline that makes the pipeline deadlock-free.  Zero overhead
    unless KASPA_TPU_LOCK_DEBUG is set.

    ``condition()`` builds a threading.Condition over the *underlying*
    lock, so condvar users keep the rank bookkeeping of ``with ctx:``
    while wait/notify release and reacquire the raw lock underneath.
    Note: under debug, a hold that spans ``cv.wait()`` is traced as one
    long hold (the stack entry stays while the raw lock is released —
    the parked thread cannot acquire anything, so order checking is
    unaffected, but hold-time aggregates include wait time).
    """

    def __init__(self, name: str, rank: int, lock=None, reentrant: bool = True):
        self.name = name
        self.rank = rank
        self._wait_span = f"wait.{name}_lock"  # what locked_for() records the wait under
        if lock is not None:
            self._lock = lock
        else:
            self._lock = threading.RLock() if reentrant else threading.Lock()

    def condition(self) -> threading.Condition:
        """A Condition bound to this lock; use inside ``with ctx:``."""
        return threading.Condition(self._lock)

    def locked_for(self, who: str, parent=None) -> "_LockedFor":
        """``with lock.locked_for(who):`` is ``with lock:`` whose wait for
        the lock is a ``wait.<name>_lock`` span carrying ``who`` (a block
        behind an admission wave, a wave behind a block).  ``parent`` (a
        TraceContext) is the span's parent where the thread has no span
        open, as ``trace.span`` takes it."""
        return _LockedFor(self, who, parent)

    def __enter__(self):
        tracked = _LOCK_DEBUG
        if tracked:
            stack = getattr(_held, "stack", None)
            if stack is None:
                stack = _held.stack = []
            if stack and stack[-1][1] >= self.rank and stack[-1][0] is not self:
                raise AssertionError(
                    f"lock-order violation: acquiring {self.name}(rank {self.rank}) "
                    f"while holding {stack[-1][2]}(rank {stack[-1][1]})"
                )
        self._lock.acquire()
        if tracked:
            # timestamp AFTER acquire: the trace measures hold time, not
            # wait+hold (contention shows as many short holds, not one long)
            stack.append((self, self.rank, self.name, time.perf_counter()))
        return self

    def __exit__(self, *exc):
        self._lock.release()
        # pop-if-ours regardless of the current debug flag: a debug toggle
        # while locks are held must neither pop a foreign/missing entry nor
        # leave a stale one behind (set_lock_debug races are test-only, but
        # corruption here would surface as false ordering violations)
        stack = getattr(_held, "stack", None)
        if stack and stack[-1][0] is self:
            entry = stack.pop()
            _trace_record(self.name, time.perf_counter() - entry[3])
        return False


_spans = None  # kaspa_tpu.observability.trace, from the first locked_for() on: observability.core imports this module


class _LockedFor:
    """What ``LockCtx.locked_for`` hands back: the lock's own enter and exit,
    with the wait for the lock timed as a span."""

    __slots__ = ("_ctx", "_who", "_parent")

    def __init__(self, ctx: LockCtx, who: str, parent=None):
        self._ctx = ctx
        self._who = who
        self._parent = parent

    def __enter__(self) -> LockCtx:
        global _spans
        if _spans is None:
            from kaspa_tpu.observability import trace as _spans
        ctx = self._ctx
        with _spans.span(ctx._wait_span, parent=self._parent, who=self._who):
            ctx.__enter__()
        return ctx

    def __exit__(self, *exc):
        return self._ctx.__exit__(*exc)
