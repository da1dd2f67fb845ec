"""Micro-batched transaction admission on the verify plane.

Concurrent entrants from the ingest queue are grouped into *waves*: the
worker pops the first entrant, lingers ``max_wait_ms`` for company, and
admits the whole wave through the split mempool intake
(``MiningManager.prepare_transaction`` / ``finish_transaction``):

- **phase 1 (on the mempool lock, arrival order)**: contextual
  pre-checks — isolation, gas cap, header context, the virtual-UTXO view
  lookup (missing inputs park the tx as an orphan right here), fee/mass
  population — with every entrant's signature/script jobs collected into
  ONE shared ``BatchScriptChecker``;
- **phase 2 (off the lock)**: a single ``dispatch_async`` rides the
  verify plane under the ``standalone_tx`` traffic class, so a wave of N
  transactions pays one coalesced device dispatch instead of N;
- **phase 3 (on the lock, arrival order)**: per-entrant verdicts feed
  ``finish_transaction`` — duplicate/double-spend/RBF/fee-floor/full
  resolve at insert exactly as the per-tx path would have resolved them.

Because every state-dependent step runs in arrival order under the same
lock, batched admission is state-identical to calling
``validate_and_insert_transaction`` per entrant (the roundcheck
``ingest`` section asserts this bit-for-bit).  Each entrant gets an
``AdmissionTicket`` resolved when its wave completes; no ticket is ever
lost — every accepted submission resolves exactly once, even on
``stop()``.
"""

from __future__ import annotations

import threading

from kaspa_tpu.utils.sync import ranked_lock
import time
from dataclasses import dataclass

from kaspa_tpu.ingest.queue import SOURCE_RPC, SOURCE_UNORPHAN, IngestQueue
from kaspa_tpu.mempool.mempool import MempoolError
from kaspa_tpu.observability import trace
from kaspa_tpu.observability.core import REGISTRY, SIZE_BUCKETS
from kaspa_tpu.ops.dispatch import TX_CLASS

_WAVE_SIZE = REGISTRY.histogram(
    "ingest_wave_size", SIZE_BUCKETS, help="transactions admitted per ingest wave"
)
_WAVE_MS = REGISTRY.histogram(
    "ingest_wave_ms",
    (0.25, 0.5, 1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 1000.0),
    help="wall time per ingest wave (prepare + verify + finish), milliseconds",
)
_OUTCOMES = REGISTRY.counter_family(
    "ingest_outcomes", "outcome", help="admission verdicts (accepted/orphaned/rejected)"
)
_WAVES = REGISTRY.counter("ingest_waves", help="admission waves run")
_WAVE_TXS = REGISTRY.counter("ingest_wave_txs", help="transactions those waves carried")
from kaspa_tpu.observability.shed import SHED as _SHED  # noqa: E402  (family declared once there)

ACCEPTED = "accepted"
ORPHANED = "orphaned"
REJECTED = "rejected"


@dataclass
class IngestConfig:
    queue_capacity: int = 10_000  # per-source lane bound
    batch_max: int = 256  # wave ceiling (matches the standalone_tx coalesce default)
    max_wait_ms: float = 2.0  # linger after the first entrant before admitting


class AdmissionTicket:
    """One entrant's admission future.

    Resolves exactly once with status accepted / orphaned / rejected;
    ``raise_for_status`` replays the per-tx call's contract (raise the
    stored MempoolError/TxRuleError, else return the RBF-evicted txids).
    """

    __slots__ = ("tx", "source", "status", "evicted", "error", "_done")

    def __init__(self, tx, source: str):
        self.tx = tx
        self.source = source
        self.status: str | None = None
        self.evicted: list[bytes] = []
        self.error: Exception | None = None
        self._done = threading.Event()

    def _resolve(self, status: str, evicted=None, error=None) -> None:
        self.status = status
        if evicted:
            self.evicted = evicted
        self.error = error
        _OUTCOMES.inc(status)
        self._done.set()

    def wait(self, timeout: float | None = None) -> bool:
        return self._done.wait(timeout)

    def raise_for_status(self) -> list[bytes]:
        assert self._done.is_set(), "ticket not yet resolved"
        if self.error is not None:
            raise self.error
        return self.evicted


class IngestTier:
    """The admission front door: queue + worker + wave batcher.

    ``lock`` serializes mempool/consensus access; the daemon passes the
    node lock so admission interleaves safely with block processing.
    Standalone use (sim, tests) defaults to a private RLock.
    """

    def __init__(self, mining, lock=None, config: IngestConfig | None = None):
        self.mining = mining
        self.lock = lock if lock is not None else ranked_lock("ingest.state")
        self.config = config or IngestConfig()
        self.queue = IngestQueue(self.config.queue_capacity)
        self._worker: threading.Thread | None = None
        self._stop = threading.Event()
        # lost = submitted - resolved must be 0 after drain (roundcheck gate)
        self._submitted = 0
        self._resolved = 0
        self._waves = 0
        self._mu = ranked_lock("ingest.stats", reentrant=False)
        # overload brownout state (set by resilience/overload.py): when
        # active, new submissions are rejected up-front with the stable
        # node-overloaded code + a retry-after hint.  Already-queued
        # tickets still admit normally — shed new work, never accepted work.
        self._overload_active = False
        self._overload_retry_ms = 0

    def set_overload(self, active: bool, retry_after_ms: int = 0) -> None:
        """Brownout seam: reject new submissions with ``node-overloaded``
        (+ retry hint) while active.  Every rejected tx still resolves its
        AdmissionTicket — the lost==0 invariant is untouched."""
        with self._mu:
            self._overload_active = bool(active)
            self._overload_retry_ms = int(retry_after_ms)

    # -- lifecycle ------------------------------------------------------

    def start(self) -> None:
        if self._worker is not None:
            return
        self._stop.clear()
        self._worker = threading.Thread(target=self._run, name="tx-ingest", daemon=True)
        self._worker.start()

    def stop(self, timeout: float = 10.0) -> None:
        """Drain the queue, resolve every outstanding ticket, stop the worker."""
        self._stop.set()
        worker = self._worker
        if worker is not None:
            worker.join(timeout)
            self._worker = None
        # the worker exits only after draining, but a stop() without start()
        # (sync mode) may still hold queued tickets
        self.pump()

    # -- submission -----------------------------------------------------

    def submit(self, tx, source: str = SOURCE_RPC) -> AdmissionTicket:
        """Enqueue one transaction; returns its ticket immediately.

        A full lane resolves the ticket rejected right away (bounded
        memory under floods) instead of blocking the submitter.
        """
        ticket = AdmissionTicket(tx, source)
        with self._mu:
            self._submitted += 1
            overloaded, retry_ms = self._overload_active, self._overload_retry_ms
        if overloaded:
            _SHED.inc("ingest_shed")
            self._finish_ticket(
                ticket,
                REJECTED,
                error=MempoolError(
                    "node overloaded, retry later",
                    code="node-overloaded",
                    retry_after_ms=retry_ms or None,
                ),
            )
            return ticket
        if not self.queue.put(source, ticket):
            self._finish_ticket(
                ticket,
                REJECTED,
                error=MempoolError(
                    f"ingest queue full for source {source!r}", code="ingest-backpressure"
                ),
            )
        return ticket

    def resubmit(self, txs) -> list[AdmissionTicket]:
        """Hand transactions back for admission together: the orphans whose
        parents a block just created (the reference's unorphan step of
        ``on_new_block``).  A running worker takes them as its next wave;
        without one the caller pumps here and now, which is safe under
        ``self.lock`` (re-entrant), as the relay handler's ``admit`` is."""
        tickets = [self.submit(tx, SOURCE_UNORPHAN) for tx in txs]
        if self._worker is None:
            self.pump()
        return tickets

    def pump(self) -> int:
        """Synchronously drain the queue in waves; returns txs admitted.

        The deterministic path for sim/roundcheck: no worker thread, no
        timing dependence — every queued entrant is admitted now.
        """
        total = 0
        while True:
            wave = self.queue.pop_wave(self.config.batch_max)
            if not wave:
                return total
            self._admit_wave(wave)
            total += len(wave)

    def admit(self, tx, source: str = SOURCE_RPC) -> AdmissionTicket:
        """Submit + combining pump: the caller-thread batching front door.

        Without a worker, the submitter drains the queue itself — and since
        the queue is shared, it admits every *concurrent* entrant queued
        behind the lock in the same wave (the combining-lock pattern:
        batching emerges exactly when submissions contend).  Our own ticket
        may have been popped by a concurrent pumper whose wave is still in
        flight, so wait for resolution either way.  With a worker running,
        this just blocks on the ticket — do not call it while holding
        ``self.lock`` in that mode (the worker needs the lock to resolve).
        """
        ticket = self.submit(tx, source)
        if self._worker is None:
            self.pump()
        ticket.wait(600.0)
        return ticket

    # -- worker ---------------------------------------------------------

    def _run(self) -> None:
        linger = self.config.max_wait_ms / 1000.0
        while True:
            wave = self.queue.pop_wave(1, wait_s=0.25)
            if wave:
                if linger > 0 and len(wave) < self.config.batch_max:
                    time.sleep(linger)  # let concurrent entrants join the wave
                wave.extend(self.queue.pop_wave(self.config.batch_max - len(wave)))
                try:
                    self._admit_wave(wave)
                except Exception:  # noqa: BLE001 - tickets already resolved rejected
                    pass
            elif self._stop.is_set():
                if self.queue.depth() == 0:
                    return
            # else: idle poll; loop back to the blocking pop

    # -- wave admission -------------------------------------------------

    def _admit_wave(self, tickets: list[AdmissionTicket]) -> None:
        t0 = time.perf_counter()
        try:
            with trace.span("ingest.wave", size=len(tickets)) as sp:
                checker = self.mining.consensus.transaction_validator.new_checker(
                    traffic_class=TX_CLASS
                )
                prepared: dict[int, object] = {}
                # phase 1: contextual pre-checks in arrival order, on the lock
                with self.lock.locked_for("ingest"):
                    for i, t in enumerate(tickets):
                        try:
                            prepared[i] = self.mining.prepare_transaction(t.tx, checker, token=i)
                        except Exception as e:  # noqa: BLE001 - verdict, not crash
                            self._finish_ticket(t, REJECTED, error=e)
                # phase 2: one batched verify for the whole wave, off the lock
                errs = checker.dispatch_async().result() if prepared else {}
                # phase 3: verdicts + inserts in arrival order, on the lock
                with self.lock.locked_for("ingest"):
                    for i, t in enumerate(tickets):
                        p = prepared.get(i)
                        if p is None:
                            continue  # rejected in phase 1
                        try:
                            evicted = self.mining.finish_transaction(p, errs.get(i))
                        except Exception as e:  # noqa: BLE001
                            self._finish_ticket(t, REJECTED, error=e)
                            continue
                        self._finish_ticket(t, ORPHANED if p.orphan else ACCEPTED, evicted=evicted)
                sp.set(
                    orphans=sum(t.status == ORPHANED for t in tickets),
                    rejected=sum(t.status == REJECTED for t in tickets),
                )
        finally:
            # no ticket ever leaks unresolved: a wave-level failure (device
            # dispatch error, unexpected crash between phases) rejects every
            # still-pending entrant instead of stranding its waiter
            for t in tickets:
                if not t._done.is_set():
                    self._finish_ticket(
                        t, REJECTED, error=MempoolError("ingest wave failed", code="ingest-internal")
                    )
        with self._mu:
            self._waves += 1
        _WAVES.inc()
        _WAVE_TXS.inc(len(tickets))
        _WAVE_SIZE.observe(len(tickets))
        _WAVE_MS.observe((time.perf_counter() - t0) * 1000.0)

    def _finish_ticket(self, ticket: AdmissionTicket, status: str, evicted=None, error=None) -> None:
        ticket._resolve(status, evicted=evicted, error=error)
        with self._mu:
            self._resolved += 1

    # -- telemetry ------------------------------------------------------

    def stats(self) -> dict:
        with self._mu:
            submitted, resolved, waves = self._submitted, self._resolved, self._waves
            overloaded = self._overload_active
        out = _OUTCOMES.snapshot()
        return {
            "overload_active": overloaded,
            "submitted": submitted,
            "resolved": resolved,
            "lost": submitted - resolved - self.queue.depth(),
            "waves": waves,
            "accepted": out.get(ACCEPTED, 0),
            "orphaned": out.get(ORPHANED, 0),
            "rejected": out.get(REJECTED, 0),
            "queue": self.queue.stats(),
        }
