"""Processing counters + monitor (observability).

Reference: consensus/core/src/api/counters.rs (ProcessingCounters atomics)
and consensus/src/pipeline/monitor.rs (ConsensusMonitor logging rolling
block/header/tx throughput).  Python ints under the GIL stand in for the
atomics.  Snapshots are surfaced through RpcCoreService.get_metrics
(process_counters field); ConsensusMonitor turns snapshot deltas into
rolling rates for operator logging.
"""

from __future__ import annotations

import time
from dataclasses import asdict, dataclass, field


@dataclass
class ProcessingCountersSnapshot:
    blocks_submitted: int = 0
    header_counts: int = 0
    body_counts: int = 0
    txs_counts: int = 0
    chain_block_counts: int = 0
    chain_disqualified_counts: int = 0
    mass_counts: int = 0
    dep_counts: int = 0

    def __sub__(self, other: "ProcessingCountersSnapshot") -> "ProcessingCountersSnapshot":
        return ProcessingCountersSnapshot(
            **{k: v - getattr(other, k) for k, v in asdict(self).items()}
        )


class ProcessingCounters:
    def __init__(self):
        self._s = ProcessingCountersSnapshot()

    def inc_blocks_submitted(self, n=1):
        self._s.blocks_submitted += n

    def inc_headers(self, n=1):
        self._s.header_counts += n

    def inc_bodies(self, n=1):
        self._s.body_counts += n

    def inc_txs(self, n=1):
        self._s.txs_counts += n

    def inc_chain_blocks(self, n=1):
        self._s.chain_block_counts += n

    def inc_chain_disqualified(self, n=1):
        self._s.chain_disqualified_counts += n

    def chain_verified(self) -> int:
        """Chain candidates verified so far: those committed and those disqualified."""
        return self._s.chain_block_counts + self._s.chain_disqualified_counts

    def snapshot(self) -> ProcessingCountersSnapshot:
        return ProcessingCountersSnapshot(**asdict(self._s))


class ConsensusMonitor:
    """Rolling throughput from counter deltas (pipeline/monitor.rs)."""

    def __init__(self, counters: ProcessingCounters):
        self.counters = counters
        self._last = counters.snapshot()
        self._last_time = time.monotonic()

    def tick(self) -> dict:
        now = time.monotonic()
        snapshot = self.counters.snapshot()
        delta = snapshot - self._last
        elapsed = max(now - self._last_time, 1e-9)
        self._last, self._last_time = snapshot, now
        return {
            "blocks_per_sec": delta.blocks_submitted / elapsed,
            "headers_per_sec": delta.header_counts / elapsed,
            "txs_per_sec": delta.txs_counts / elapsed,
            "chain_blocks_per_sec": delta.chain_block_counts / elapsed,
            "disqualified": delta.chain_disqualified_counts,
            "window_secs": elapsed,
        }
