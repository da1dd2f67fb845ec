"""Bounded ingest queue with per-source fairness.

Two front doors feed transaction admission — RPC ``submitTransaction``
callers and P2P tx relay — and a flood on one must not starve the other.
Each source gets its own FIFO lane with its own capacity; a wave pop
round-robins across lanes (preserving per-source arrival order) so a P2P
orphan storm and a legitimate RPC submitter share the batcher fairly.
``put`` never blocks: a full lane sheds load immediately (the caller
turns that into an ``ingest-backpressure`` rejection), which keeps the
admission path's worst-case memory bounded under hostile floods.
"""

from __future__ import annotations

import threading

from kaspa_tpu.utils.sync import ranked_lock
from collections import deque

from kaspa_tpu.observability.core import REGISTRY

SOURCE_RPC = "rpc"
SOURCE_P2P = "p2p"
SOURCE_UNORPHAN = "unorphan"  # a block handing back the orphans it gave parents (its lane appears on first use)

_SUBMITTED = REGISTRY.counter_family(
    "ingest_submitted", "source", help="transactions offered to the ingest queue, by source"
)
_BACKPRESSURE = REGISTRY.counter_family(
    "ingest_backpressure", "source", help="transactions shed by a full ingest lane, by source"
)


class IngestQueue:
    """Per-source bounded FIFO lanes under one lock + condition.

    ``capacity`` bounds each lane independently (a hostile source fills
    only its own lane).  ``pop_wave`` blocks up to ``wait_s`` for the
    first item, then drains up to ``max_items`` alternating lanes from a
    persistent round-robin cursor.
    """

    def __init__(self, capacity: int = 10_000, sources: tuple[str, ...] = (SOURCE_RPC, SOURCE_P2P)):
        self.capacity = capacity
        self._limit: int | None = None  # overload clamp (see set_capacity_limit)
        self._lanes: dict[str, deque] = {s: deque() for s in sources}  # graftlint: allow(unbounded-queue) -- lanes are capacity-bounded by the put() check below
        self._order: tuple[str, ...] = tuple(sources)
        self._next = 0  # round-robin cursor into _order
        self._mu = ranked_lock("ingest.queue", reentrant=False)
        self._nonempty = self._mu.condition()

    def set_capacity_limit(self, limit: int | None) -> None:
        """Overload clamp: shrink the effective per-lane bound below the
        configured capacity (None restores it).  Items already queued
        above a new lower limit stay queued — the clamp sheds new
        arrivals, it never drops accepted work."""
        with self._mu:
            self._limit = max(1, int(limit)) if limit is not None else None

    def effective_capacity(self) -> int:
        limit = self._limit
        return min(self.capacity, limit) if limit is not None else self.capacity

    def put(self, source: str, item) -> bool:
        """Enqueue on the source's lane; False (shed) when that lane is full."""
        _SUBMITTED.inc(source)
        with self._mu:
            lane = self._lanes.get(source)
            if lane is None:
                lane = self._lanes[source] = deque()  # graftlint: allow(unbounded-queue) -- bounded by the effective-capacity check below
                self._order = self._order + (source,)
            if len(lane) >= self.effective_capacity():
                _BACKPRESSURE.inc(source)
                return False
            lane.append(item)
            self._nonempty.notify()
            return True

    def pop_wave(self, max_items: int, wait_s: float = 0.0) -> list:
        """Dequeue up to ``max_items`` round-robin across lanes.

        Blocks up to ``wait_s`` for the first item; returns [] on timeout.
        Within one source the FIFO order is preserved; across sources the
        cursor alternates so neither can monopolize a wave.
        """
        with self._mu:
            if wait_s > 0 and not any(self._lanes.values()):
                self._nonempty.wait_for(lambda: any(self._lanes.values()), timeout=wait_s)
            out: list = []
            order = self._order
            n = len(order)
            misses = 0
            while len(out) < max_items and misses < n:
                lane = self._lanes[order[self._next % n]]
                self._next = (self._next + 1) % n
                if lane:
                    out.append(lane.popleft())
                    misses = 0
                else:
                    misses += 1
            return out

    def depth(self, source: str | None = None) -> int:
        with self._mu:
            if source is not None:
                lane = self._lanes.get(source)
                return len(lane) if lane is not None else 0
            return sum(len(lane) for lane in self._lanes.values())

    def stats(self) -> dict:
        with self._mu:
            return {
                "capacity": self.capacity,
                "effective_capacity": self.effective_capacity(),
                "depth": {s: len(lane) for s, lane in self._lanes.items()},
            }
