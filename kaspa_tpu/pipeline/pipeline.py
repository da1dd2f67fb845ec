"""Concurrent consensus pipeline: staged workers over one consensus core.

Re-design of the reference's 4-processor pipeline (consensus/src/pipeline/:
header/body/virtual processors connected by channels, backed by a block
task dependency manager) for the Python+TPU runtime:

- An intake that registers submissions with the dependency manager, so
  blocks may arrive out of order and duplicates collapse into task groups
  (deps_manager.rs semantics, ported in pipeline/deps_manager.py).
- A pool of stage workers running header+body validation.  The
  GIL-releasing parts — header/tx hashing (hashlib), batch marshalling
  (numpy), device dispatch (XLA) — overlap across threads; the
  pure-Python consensus math serializes under one ranked commit lock
  (an honest mapping of the reference's rayon pools onto the Python
  runtime; see utils/sync.py LockCtx for the deadlock-detection story).
- A single virtual worker (the reference also serializes virtual state):
  it absorbs every staged block each cycle, updates tips for all of them,
  then resolves virtual once — so chain verification sees whole segments
  (one `precompute_chain` dispatch a segment), the virtual's own mergeset
  is replayed once a cycle and the UTXO position hops once a tip, not
  once a block (virtual_processor/processor.rs:267-271 task batching).

When a cycle starts.  The virtual worker keeps absorbing staged tasks and
starts its cycle when (a) the batch holds ``_virtual_batch_max`` tasks, or
(b) no task is ready or inside a stage worker, i.e. nothing more can reach
it without the virtual stage itself running.  The condition is observed,
never timed: ``_staging`` counts the tasks that sit in ``_ready`` or
between ``try_begin`` and the end of ``_stage_worker``'s body.  A task
parked under a pending parent is not counted; its parent is, until
``deps.end`` has re-queued the child, so the count cannot touch zero
between the two.  A lone block (every paced and relayed one) finds the
count at zero when it is handed over: its cycle starts at once.  Without
this the stage workers, which commit under the same lock as the cycle,
hand over one block each between two cycles and the batch is bound by the
number of workers, however long the queue behind them.

The invariant that makes the wait deadlock-free: **stage completion never
depends on the virtual stage**.  A stage worker hands its task over, ends
it in the deps manager, re-queues the dependents and leaves the count,
and on none of these steps does it wait for a cycle, a future or anything
else the virtual worker does; the commit lock it needs is free while the
virtual worker waits.  So the count always reaches zero (or the batch its
cap) by the stage workers' progress alone.

``submit`` returns a Future resolving to the block's status after the
virtual stage absorbed it (the reference's virtual_state_task).
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import Future
from dataclasses import dataclass
from time import perf_counter_ns

from kaspa_tpu.consensus.stores import StatusesStore
from kaspa_tpu.observability import flight, trace
from kaspa_tpu.observability.core import DEFAULT_LATENCY_BUCKETS, REGISTRY, SIZE_BUCKETS
from kaspa_tpu.pipeline.deps_manager import BlockTaskDependencyManager
from kaspa_tpu.pipeline.speculative import SpeculativeVerifier
from kaspa_tpu.utils.sync import Channel, Closed, LockCtx, ranked_lock

# queue wait vs execute split per stage — the question the round-5 bench
# failure could not answer ("which stage stalled?")
_Q_WAIT = REGISTRY.histogram_family(
    "pipeline_queue_wait_seconds", "stage", DEFAULT_LATENCY_BUCKETS,
    help="time a task sat queued before a worker picked it up",
)
_LOCK_WAIT = REGISTRY.histogram(
    "pipeline_commit_lock_wait_seconds", DEFAULT_LATENCY_BUCKETS,
    help="time stage workers waited on the ranked commit lock",
)
_VIRT_BATCH = REGISTRY.histogram(
    "pipeline_virtual_batch_size", SIZE_BUCKETS,
    help="blocks absorbed per virtual-resolution cycle",
)
_VIRT_CYCLES = REGISTRY.counter("pipeline_virtual_cycles", help="virtual-resolution cycles run")
_VIRT_CYCLE_BLOCKS = REGISTRY.counter(
    "pipeline_virtual_cycle_blocks", help="blocks absorbed by those cycles (over pipeline_virtual_cycles: blocks a cycle)"
)
_SUBMITTED = REGISTRY.counter("pipeline_tasks_submitted", help="blocks entered into the pipeline")


@dataclass
class _Task:
    block: object  # Block (or header-only Block with empty txs)
    header_only: bool
    future: Future
    enqueue_ns: int = 0  # set at submit / virtual hand-off for queue-wait spans
    ctx: object = None  # root TraceContext: the flight recorder's, or the capture log's (None with no sink)


class ConsensusPipeline:
    def __init__(self, consensus, workers: int = 2, speculative: bool | None = None):
        self.consensus = consensus
        self.deps = BlockTaskDependencyManager()
        self._ready = Channel()
        self._virtual_q = Channel()
        self._lock = LockCtx("consensus-commit", rank=10)
        # a cap, not a target: a cycle absorbs every staged block there is
        # (see _next_batch: it starts once nothing more is ready or inside a
        # stage worker) and a deep IBD burst reaches this bound, so that it
        # cannot collapse into one giant resolve with unbounded commit latency
        self._virtual_batch_max = max(1, int(os.environ.get("KASPA_TPU_VIRTUAL_BATCH_MAX", "64")))
        if speculative is None:
            speculative = os.environ.get("KASPA_TPU_SPECULATIVE", "1") not in ("0", "off", "false")
        self.speculative = SpeculativeVerifier(consensus, self._lock) if speculative else None
        consensus.speculative = self.speculative
        self._inflight = 0
        # tasks in _ready or inside a stage worker: what can still reach
        # _virtual_q without the virtual stage running (module docstring)
        self._staging = 0
        self._closed = False
        self._idle_mu = ranked_lock("pipeline.idle", reentrant=False)
        self._idle_cv = self._idle_mu.condition()
        self._cycle_cv = self._idle_mu.condition()
        self._workers = [
            threading.Thread(target=self._stage_worker, name=f"kaspa-stage-{i}", daemon=True)
            for i in range(max(1, workers))
        ]
        self._virtual_worker_t = threading.Thread(
            target=self._virtual_worker, name="kaspa-virtual", daemon=True
        )
        for t in self._workers:
            t.start()
        self._virtual_worker_t.start()

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def submit(self, block, header_only: bool = False) -> Future:
        """Queue a block for full processing; returns a Future[str status].

        Out-of-order safe: if a direct parent is itself in flight, this
        task parks until the parent completes.  Duplicate submissions of
        the same hash are absorbed into one task group and each receives
        its own result.
        """
        fut: Future = Future()
        task = _Task(block, header_only, fut, enqueue_ns=perf_counter_ns())
        # flight recorder: the block's trace starts at intake and is sealed
        # when the future resolves (after virtual absorption); a duplicate
        # submission re-joins the existing open trace
        recorder = flight.enabled()
        if recorder:
            task.ctx = flight.begin(block.hash)
        elif trace.sinks_active():
            # span capture without the recorder: the block's spans share its
            # hash as their trace id, under a root that closes at resolution
            # (recorded before _on_done, so an idle pipeline has all its roots)
            task.ctx = trace.root_context(block.hash.hex(), "pipeline.block")
            fut.add_done_callback(
                lambda f, ctx=task.ctx, t0=task.enqueue_ns: trace.record_root(
                    ctx, t0, perf_counter_ns(), status="error" if f.exception() else str(f.result())
                )
            )
        _SUBMITTED.inc()
        with self._idle_mu:
            self._inflight += 1
        fut.add_done_callback(self._on_done)
        if recorder and task.ctx is not None:
            fut.add_done_callback(
                lambda f, h=block.hash: flight.end(h, "error" if f.exception() else "ok")
            )
        if self.deps.register(block.hash, task):
            self._send_ready(block.hash)
        return fut

    def validate_and_insert_block(self, block) -> str:
        """Synchronous submission (raises the pipeline error, if any)."""
        return self.submit(block).result()

    def wait_for_idle(self, timeout: float | None = 60.0) -> None:
        with self._idle_mu:
            self._idle_cv.wait_for(lambda: self._inflight == 0, timeout)

    def shutdown(self) -> None:
        self._ready.close()
        for t in self._workers:
            t.join(timeout=10)
        with self._idle_mu:
            # wakes a virtual worker still waiting for the stage workers
            self._closed = True
            self._cycle_cv.notify()
        self._virtual_q.close()
        self._virtual_worker_t.join(timeout=10)
        # detach: direct (serial) callers of _verify_chain_block after
        # shutdown must not consume stale entries
        self.consensus.speculative = None

    # ------------------------------------------------------------------
    # stage workers: header + body
    # ------------------------------------------------------------------

    def _on_done(self, _fut) -> None:
        with self._idle_mu:
            self._inflight -= 1
            if self._inflight == 0:
                self._idle_cv.notify_all()

    def _send_ready(self, task_id: bytes) -> None:
        with self._idle_mu:
            self._staging += 1
        try:
            self._ready.send(task_id)
        except Closed:
            # shutdown with tasks in flight: fail the (parked) group so no
            # caller hangs on an unresolved future
            self._leave_stage()
            self._fail_group(task_id, RuntimeError("pipeline shut down"))

    def _leave_stage(self) -> None:
        """One task left _ready and the stage workers, whichever way."""
        with self._idle_mu:
            self._staging -= 1
            if self._staging == 0 or len(self._virtual_q) >= self._virtual_batch_max:
                self._cycle_cv.notify()

    def _requeue(self, ids) -> None:
        for dep in ids:
            self._send_ready(dep)

    def _fail_group(self, task_id: bytes, err: Exception) -> None:
        with self.deps._mu:
            group = self.deps._pending.pop(task_id, None)
            if group is None:
                return
            tasks, dependents = list(group.tasks), list(group.dependent_tasks)
            if not self.deps._pending:
                self.deps._idle.notify_all()
        for t in tasks:
            if not t.future.done():
                t.future.set_exception(err)
        for dep in dependents:
            self._fail_group(dep, err)

    def _stage_worker(self) -> None:
        for task_id in self._ready:
            try:
                self._stage(task_id)
            finally:
                # every exit of a stage task leaves the count: parked, error,
                # duplicate, header-only, Closed; and only after its dependents
                # were re-queued (counted), so the count does not touch zero
                # between a parent and its child
                self._leave_stage()

    def _stage(self, task_id: bytes) -> None:
        consensus = self.consensus
        task = self.deps.try_begin(task_id, lambda t: t.block.header.direct_parents())
        if task is None:
            return  # parked under a pending parent, which is counted
        now = perf_counter_ns()
        _Q_WAIT.observe("stage", (now - task.enqueue_ns) * 1e-9)
        # queue wait as a first-class span so critical-path attribution
        # names the handoff latency instead of losing it to root self-time
        trace.record_span("wait.stage", task.ctx, task.enqueue_ns, now)
        duplicate_status = None
        err = None
        try:
            with trace.span("pipeline.stage", parent=task.ctx):
                # GIL-releasing precompute outside the commit lock: header
                # hash + merkle leaves hash concurrently across workers
                blk = task.block
                _ = blk.hash
                if not task.header_only:
                    for tx in blk.transactions:
                        tx.id()
                t_lock = perf_counter_ns()
                with self._lock.locked_for("stage"):
                    _LOCK_WAIT.observe((perf_counter_ns() - t_lock) * 1e-9)
                    with trace.span("pipeline.commit"):
                        existing = consensus.storage.statuses.get(blk.hash)
                        if existing is not None and (
                            task.header_only or existing != StatusesStore.STATUS_HEADER_ONLY
                        ):
                            duplicate_status = existing  # no reprocessing
                        else:
                            with trace.span("pipeline.header"):
                                if consensus._process_header(blk.header):
                                    consensus.counters.inc_headers()
                            if task.header_only:
                                consensus.storage.flush()
                            else:
                                consensus.counters.inc_blocks_submitted()
                                with trace.span("pipeline.body"):
                                    consensus._process_body(blk)
                                consensus.counters.inc_bodies()
                                consensus.counters.inc_txs(len(blk.transactions))
        except Exception as e:
            err = e
        # on success, hand the task to the virtual queue BEFORE releasing
        # dependents: a child finishing its stages can then never overtake
        # its parent into tips/virtual resolution
        if err is None and duplicate_status is None and not task.header_only:
            # speculative chain-state precompute runs BEFORE the virtual
            # hand-off, so by the time the virtual worker verifies this
            # block its (block, selected_parent) entry is already cached;
            # device waits happen here, off the commit lock, coalescing
            # with other speculating workers' script batches
            if self.speculative is not None:
                self.speculative.run(blk.hash, task.ctx)
            try:
                task.enqueue_ns = perf_counter_ns()
                self._virtual_q.send(task)
            except Closed:
                err = RuntimeError("pipeline shut down")
        self._requeue(self.deps.end(task_id))
        if err is not None:
            task.future.set_exception(err)
        elif duplicate_status is not None:
            task.future.set_result(duplicate_status)
        elif task.header_only:
            task.future.set_result(consensus.storage.statuses.get(blk.hash))

    # ------------------------------------------------------------------
    # virtual worker
    # ------------------------------------------------------------------

    def _next_batch(self) -> list:
        """Block until a cycle is due (module docstring: the cap is reached,
        or nothing is ready or inside a stage worker); its tasks, none once
        the pipeline is shut down and drained.  Waiting here cannot deadlock:
        stage completion never depends on the virtual stage."""
        q, cap = self._virtual_q, self._virtual_batch_max
        with self._idle_mu:
            self._cycle_cv.wait_for(lambda: len(q) >= cap or (self._staging == 0 and len(q)) or self._closed)
            return q.drain(cap)

    def _virtual_worker(self) -> None:
        consensus = self.consensus
        while True:
            batch = self._next_batch()
            if not batch:
                return
            now = perf_counter_ns()
            _VIRT_BATCH.observe(len(batch))
            _VIRT_CYCLES.inc()
            _VIRT_CYCLE_BLOCKS.inc(len(batch))
            for task in batch:
                _Q_WAIT.observe("virtual", (now - task.enqueue_ns) * 1e-9)
                trace.record_span("wait.virtual", task.ctx, task.enqueue_ns, now)
            t_lock = perf_counter_ns()
            with self._lock.locked_for("virtual", parent=batch[0].ctx):
                _LOCK_WAIT.observe((perf_counter_ns() - t_lock) * 1e-9)
                try:
                    # the TLS span parents on the first task's trace: muhash /
                    # store.flush / utxoindex children nest there; every other
                    # task in the batch gets a synthetic same-interval span so
                    # its trace still owns the shared virtual-cycle time
                    t_v0 = perf_counter_ns()
                    with trace.span("pipeline.virtual", parent=batch[0].ctx, batch=len(batch)) as sp:
                        verified0 = consensus.counters.chain_verified()
                        for task in batch:
                            consensus.notification_root.notify_block_added(task.block, task.ctx)
                            consensus._update_tips(task.block.hash)
                        # one virtual resolution absorbs the whole cycle: chain
                        # verification batches signatures across these blocks
                        # graftlint: allow(blocking-under-lock) -- the virtual cycle's device work runs under the pipeline lock by design: the pipeline thread is the sole consumer and the watchdog monitors progress
                        consensus._resolve_virtual()
                        consensus.storage.flush()
                        # chain blocks the cycle verified (committed or disqualified)
                        sp.set(candidates=consensus.counters.chain_verified() - verified0)
                    t_v1 = perf_counter_ns()
                    for task in batch[1:]:
                        trace.record_span(
                            "pipeline.virtual", task.ctx, t_v0, t_v1,
                            batch=len(batch), shared=True,
                        )
                except Exception as e:
                    for task in batch:
                        if not task.future.done():
                            task.future.set_exception(e)
                    continue
                for task in batch:
                    status = consensus.storage.statuses.get(task.block.hash)
                    if not task.future.done():
                        task.future.set_result(status)
