"""Batched script checking: the TPU offload point.

The reference validates scripts per input inside rayon par_iter
(tx_validation_in_utxo_context.rs:206-223); here the per-input signature
checks of an entire block/mergeset are *collected* into one device batch:

    collect phase  : classify each (input, utxo) pair, compute its sighash
                     (host, memoized per tx), queue (pubkey, msg, sig)
    dispatch phase : one batched Schnorr kernel call + one ECDSA call,
                     overlapped with the host-VM fallback lane
    resolve phase  : validity bitmask mapped back to per-input results

Consensus equivalence: only canonical standard P2PK spends take the batch
path; anything else routes to the host VM (txscript.vm) — same acceptance
decisions as running the reference's engine per input.

The VM fallback lane is *deferred and parallel*: nonstandard inputs are
queued at collect time and executed at dispatch on a bounded thread pool,
concurrently with the device batches (the device dispatch releases the GIL
while XLA runs, so a multisig/P2SH-heavy block no longer serializes the
fallback work behind — or in front of — the device lane).  Failure
precedence matches the serial path exactly: VM failures apply first, in
collect order, then device-batch failures in queue order, so the
(token -> first error) mapping is bit-identical to serial execution.
"""

from __future__ import annotations

import functools
import os
import threading

from kaspa_tpu.utils.sync import ranked_lock
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from time import perf_counter_ns

from kaspa_tpu.consensus import hashing as chash
from kaspa_tpu.crypto import secp
from kaspa_tpu.observability import trace
from kaspa_tpu.observability.core import REGISTRY, SIZE_BUCKETS
from kaspa_tpu.resilience.faults import FAULTS, FaultInjected
from kaspa_tpu.txscript import standard
from kaspa_tpu.txscript.caches import SigCache

# fast-path vs fallback mix: a fallback-heavy workload starves the device
# batch, which is the first thing to check when occupancy drops
_JOBS = REGISTRY.counter_family("txscript_batch_jobs", "kind", help="signature jobs queued for device dispatch")
_SIGCACHE_SKIPS = REGISTRY.counter("txscript_batch_sigcache_skips", help="jobs answered by the sig cache pre-dispatch")
_VM_FALLBACKS = REGISTRY.counter("txscript_vm_fallbacks", help="inputs routed to the host VM instead of the batch")
_FALLBACK_BATCH = REGISTRY.histogram(
    "txscript_fallback_batch_size", SIZE_BUCKETS, help="deferred VM fallback jobs per dispatch"
)
_VM_RETRIES = REGISTRY.counter(
    "txscript_vm_fault_retries", help="VM fallback jobs retried after an injected transient fault"
)


def _default_fallback_workers() -> int:
    """Bounded pool width for the VM fallback lane (0/1 = serial)."""
    raw = os.environ.get("KASPA_TPU_VM_FALLBACK_WORKERS")
    if raw is not None:
        return max(0, int(raw))
    return max(2, min(8, os.cpu_count() or 2))


_pool_lock = ranked_lock("txscript.pool")
_pool: ThreadPoolExecutor | None = None


def _fallback_pool() -> ThreadPoolExecutor:
    """Shared bounded executor (threads are reused across dispatches and
    across checkers; daemonized so interpreter shutdown never hangs)."""
    global _pool
    if _pool is None:
        with _pool_lock:
            if _pool is None:
                _pool = ThreadPoolExecutor(
                    max_workers=_default_fallback_workers() or 1, thread_name_prefix="vm-fallback"
                )
    return _pool


class ScriptCheckError(Exception):
    def __init__(self, msg: str, input_index: int | None = None):
        super().__init__(msg)
        self.input_index = input_index


@dataclass
class _Job:
    kind: str  # "schnorr" | "ecdsa"
    pubkey: bytes
    msg: bytes
    sig: bytes
    cache_key: tuple
    callback: object  # fn(bool)


@dataclass
class _FallbackJob:
    token: int
    input_index: int
    run: object  # fn() -> None, raises on invalid script
    # collector's TraceContext + enqueue stamp: pool threads re-attach the
    # VM execution (and its queue wait) to the owning block's trace
    ctx: object = None
    enqueued_ns: int = 0


def _run_fallback(job: _FallbackJob) -> Exception | None:
    """Execute one deferred VM job; returns the failure (or None).

    Runs on pool threads: the engine instance is job-local; the shared
    SigCache is internally locked; SigHashReusedValues memoization races
    are benign (idempotent writes of identical digests).

    An injected ``vm.fallback.exec`` fault is a *transient infrastructure*
    failure, not a script verdict: the job retries, so fault schedules can
    never flip a consensus decision (the sustain run's sink-identity check
    depends on this).
    """
    t0 = perf_counter_ns()
    if job.enqueued_ns:
        trace.record_span("wait.vm", job.ctx, job.enqueued_ns, t0)
    with trace.span("vm.fallback", parent=job.ctx, input=job.input_index):
        while True:
            try:
                FAULTS.fire("vm.fallback.exec")
                job.run()
                return None
            except FaultInjected:
                _VM_RETRIES.inc()
                continue
            except Exception as e:  # noqa: BLE001 - VM raises on invalid script
                return e


# in-flight accounting for the shared pool so daemon shutdown can drain
# the deferred VM lane instead of abandoning futures mid-dispatch
_inflight_lock = ranked_lock("txscript.inflight")
_inflight = 0
_inflight_zero = threading.Event()
_inflight_zero.set()


def _submit_tracked(pool: ThreadPoolExecutor, job: _FallbackJob):
    global _inflight
    with _inflight_lock:
        _inflight += 1
        _inflight_zero.clear()

    def run():
        global _inflight
        try:
            return _run_fallback(job)
        finally:
            with _inflight_lock:
                _inflight -= 1
                if _inflight == 0:
                    _inflight_zero.set()

    return pool.submit(run)


def drain_fallback_pool(timeout: float = 10.0) -> bool:
    """Block until every in-flight deferred VM job has resolved (True) or
    the timeout expires (False).  Dispatchers joining their own futures is
    the common case; this is the daemon-shutdown barrier."""
    return _inflight_zero.wait(timeout)


def shutdown_fallback_pool(timeout: float = 10.0) -> bool:
    """Drain, then retire the shared executor (a later dispatch lazily
    rebuilds it).  Returns whether the drain completed in time."""
    global _pool
    drained = drain_fallback_pool(timeout)
    with _pool_lock:
        pool, _pool = _pool, None
    if pool is not None:
        pool.shutdown(wait=False)
    return drained


class BatchScriptChecker:
    """Collects signature-check jobs across many txs, dispatches once.

    ``fallback_workers``: width of the VM fallback lane (None = shared
    default pool, sized by KASPA_TPU_VM_FALLBACK_WORKERS or cpu count;
    0/1 = serial execution at dispatch — same results either way).

    ``traffic_class``: coalescing-queue traffic class for this checker's
    device submissions (e.g. ``"standalone_tx"`` for the ingest tier's
    admission batches).  Class-qualified kinds get their own coalesce
    target/age and counters in ops/dispatch; results are bit-identical.
    """

    def __init__(
        self,
        sig_cache: SigCache | None = None,
        vm_fallback=None,
        fallback_workers: int | None = None,
        traffic_class: str | None = None,
    ):
        self.sig_cache = sig_cache if sig_cache is not None else SigCache()
        # contract: fn(tx, entries, input_index, reused, pov_daa_score) — the
        # daa score drives fork-activation gating inside the engine
        self.vm_fallback = vm_fallback
        self.fallback_workers = fallback_workers
        self.traffic_class = traffic_class
        self._jobs: list[_Job] = []
        self._fallbacks: list[_FallbackJob] = []
        self._results: dict[int, Exception | None] = {}

    def collect_tx(self, token: int, tx, utxo_entries, reused=None, pov_daa_score=None, seq_commit_accessor=None) -> None:
        """Queue all input script checks of `tx`; result under `token`.
        ``pov_daa_score`` feeds fork-activation gating in the VM fallback;
        ``seq_commit_accessor`` backs OpChainblockSeqCommit post-Toccata."""
        if reused is None:
            reused = chash.SigHashReusedValues()
        self._results.setdefault(token, None)
        for i, (inp, entry) in enumerate(zip(tx.inputs, utxo_entries)):
            try:
                self._collect_input(token, tx, utxo_entries, i, inp, entry, reused, pov_daa_score, seq_commit_accessor)
            except ScriptCheckError as e:
                self._fail(token, e)

    def _fail(self, token: int, err: Exception) -> None:
        if self._results.get(token) is None:
            self._results[token] = err

    def _collect_input(self, token, tx, utxo_entries, i, inp, entry, reused, pov_daa_score=None, seq_commit_accessor=None):
        cls = standard.classify_script(entry.script_public_key)
        if cls in (standard.ScriptClass.PUB_KEY, standard.ScriptClass.PUB_KEY_ECDSA):
            # runtime sig-op parity with the engine path (lib.rs:545 + :898):
            # the single CheckSig consumes one committed sig op
            commit = inp.compute_commit
            if commit.sig_op_count() is not None and commit.sig_op_count() < 1:
                raise ScriptCheckError("exceeded sig op limit of 0", i)
        if cls == standard.ScriptClass.PUB_KEY:
            data = standard.parse_single_push(inp.signature_script)
            if data is None or len(data) == 0:
                raise ScriptCheckError("signature script is not a canonical single push", i)
            if len(data) != 65:
                raise ScriptCheckError(f"invalid schnorr signature length {len(data) - 1}", i)
            sig, hash_type = data[:64], data[64]
            if hash_type not in chash.ALLOWED_SIG_HASH_TYPES:
                raise ScriptCheckError(f"invalid hash type {hash_type}", i)
            pubkey = entry.script_public_key.script[1:33]
            msg = chash.calc_schnorr_signature_hash(tx, utxo_entries, i, hash_type, reused)
            self._queue(token, "schnorr", pubkey, msg, sig, i)
        elif cls == standard.ScriptClass.PUB_KEY_ECDSA:
            data = standard.parse_single_push(inp.signature_script)
            if data is None or len(data) == 0:
                raise ScriptCheckError("signature script is not a canonical single push", i)
            if len(data) != 65:
                raise ScriptCheckError(f"invalid ecdsa signature length {len(data) - 1}", i)
            sig, hash_type = data[:64], data[64]
            if hash_type not in chash.ALLOWED_SIG_HASH_TYPES:
                raise ScriptCheckError(f"invalid hash type {hash_type}", i)
            pubkey = entry.script_public_key.script[1:34]
            msg = chash.calc_ecdsa_signature_hash(tx, utxo_entries, i, hash_type, reused)
            self._queue(token, "ecdsa", pubkey, msg, sig, i)
        else:
            # non-fast-path scripts defer to the host VM lane (executed at
            # dispatch, concurrently with the device batches)
            if self.vm_fallback is None:
                raise ScriptCheckError(f"unsupported script class {cls.value} (VM fallback not wired)", i)
            _VM_FALLBACKS.inc()
            self._fallbacks.append(
                _FallbackJob(
                    token,
                    i,
                    functools.partial(
                        self.vm_fallback, tx, utxo_entries, i, reused, pov_daa_score,
                        seq_commit_accessor=seq_commit_accessor,
                    ),
                    ctx=trace.context(),
                    enqueued_ns=perf_counter_ns(),
                )
            )

    def _queue(self, token, kind, pubkey, msg, sig, input_index):
        cache_key = (kind, sig, msg, pubkey)
        cached = self.sig_cache.get(cache_key)
        if cached is not None:
            _SIGCACHE_SKIPS.inc()
            if not cached:
                self._fail(token, ScriptCheckError("invalid signature (cached)", input_index))
            return
        _JOBS.inc(kind)

        # `fail` is supplied at resolve time: dispatch_async detaches the
        # results dict into its handle, so the callback must not close over
        # the checker's (reusable) live state
        def cb(ok: bool, fail, token=token, input_index=input_index):
            if not ok:
                fail(token, ScriptCheckError("invalid signature", input_index))

        self._jobs.append(_Job(kind, pubkey, msg, sig, cache_key, cb))

    def queued_jobs(self) -> int:
        """Signature jobs staged for the device lane since the last dispatch."""
        return len(self._jobs)

    def _effective_workers(self, jobs: int) -> int:
        w = self.fallback_workers if self.fallback_workers is not None else _default_fallback_workers()
        return min(w, jobs)

    def dispatch(self) -> dict[int, Exception | None]:
        """Run all queued checks: the VM fallback lane on the bounded pool
        overlapped with (at most) two device batches; returns
        token -> None (valid) | Exception (first failure)."""
        return self.dispatch_async().result()

    def dispatch_async(self) -> "DispatchHandle":
        """Submit all queued checks without blocking and detach the
        checker's state into the returned handle: the VM fallback lane
        goes to the bounded pool, the device lane to the cross-block
        coalescing queue (`ops/dispatch.py`) when enabled.  The checker is
        immediately reusable for the next collect round; the handle's
        ``result()`` yields the same token -> first-error mapping — and
        the same failure precedence — as the synchronous path."""
        fallbacks, self._fallbacks = self._fallbacks, []
        jobs, self._jobs = self._jobs, []
        results, self._results = self._results, {}

        pending = None
        if fallbacks:
            _FALLBACK_BATCH.observe(len(fallbacks))
            if self._effective_workers(len(fallbacks)) > 1:
                pool = _fallback_pool()
                pending = [_submit_tracked(pool, j) for j in fallbacks]

        schnorr = [j for j in jobs if j.kind == "schnorr"]
        ecdsa = [j for j in jobs if j.kind == "ecdsa"]
        from kaspa_tpu.ops import dispatch as coalesce

        engine = coalesce.active()
        tickets = None
        if engine is not None:
            # chunk ownership is donated to the coalescing queue: the item
            # lists are never touched again from this side.  A traffic class
            # qualifies the kind so the queue applies per-class batch
            # dynamics; the device call maps back to the base kernel.
            prefix = f"{self.traffic_class}:" if self.traffic_class else ""
            tickets = {}
            if schnorr:
                tickets["schnorr"] = engine.submit(
                    f"{prefix}schnorr", [(j.pubkey, j.msg, j.sig) for j in schnorr]
                )
            if ecdsa:
                tickets["ecdsa"] = engine.submit(
                    f"{prefix}ecdsa", [(j.pubkey, j.msg, j.sig) for j in ecdsa]
                )
        return DispatchHandle(self.sig_cache, fallbacks, pending, schnorr, ecdsa, tickets, results)


class DispatchHandle:
    """In-flight dispatch: owns the detached jobs/results of one round."""

    def __init__(self, sig_cache, fallbacks, pending, schnorr, ecdsa, tickets, results):
        self.sig_cache = sig_cache
        self._fallbacks = fallbacks
        self._pending = pending
        self._schnorr = schnorr
        self._ecdsa = ecdsa
        self._tickets = tickets  # None = coalescing disabled (sync device lane)
        self._results = results
        self._resolved = False

    def _fail(self, token: int, err: Exception) -> None:
        if self._results.get(token) is None:
            self._results[token] = err

    def result(self) -> dict[int, Exception | None]:
        """Join every lane; token -> None (valid) | Exception (first
        failure), bit-identical to the legacy synchronous dispatch."""
        if self._resolved:
            return self._results
        self._resolved = True
        schnorr_mask = ecdsa_mask = None
        if self._tickets is None:
            # legacy synchronous device lane (coalescing disabled)
            if self._schnorr:
                with trace.span("txscript.dispatch", kind="schnorr", jobs=len(self._schnorr)):
                    # verify_batch (not schnorr_verify_batch): the sync lane
                    # honors --verify-mode aggregate/auto like the coalesced one
                    schnorr_mask = secp.verify_batch("schnorr", [(j.pubkey, j.msg, j.sig) for j in self._schnorr])
            if self._ecdsa:
                with trace.span("txscript.dispatch", kind="ecdsa", jobs=len(self._ecdsa)):
                    ecdsa_mask = secp.verify_batch("ecdsa", [(j.pubkey, j.msg, j.sig) for j in self._ecdsa])

        # fallback lane resolution BEFORE the device callbacks: the serial
        # path ran the VM at collect time, so VM failures must win the
        # first-error slot over same-token batch failures, in collect order
        if self._fallbacks:
            with trace.span("txscript.fallback_join", jobs=len(self._fallbacks), parallel=self._pending is not None):
                errors = (
                    [f.result() for f in self._pending]
                    if self._pending is not None
                    else [_run_fallback(j) for j in self._fallbacks]
                )
            for job, err in zip(self._fallbacks, errors):
                if err is not None:
                    self._fail(job.token, ScriptCheckError(str(err), job.input_index))

        if self._tickets is not None:
            # coalesced device lane: block on this round's tickets (wait()
            # nudges the queue, so a serial caller flushes immediately)
            with trace.span("txscript.dispatch_wait", kinds=",".join(sorted(self._tickets))):
                try:
                    if "schnorr" in self._tickets:
                        schnorr_mask = self._tickets["schnorr"].wait()
                    if "ecdsa" in self._tickets:
                        ecdsa_mask = self._tickets["ecdsa"].wait()
                except TimeoutError as e:
                    # infrastructure failure, not a consensus verdict: keep
                    # the TimeoutError type but attach this handle's view
                    if hasattr(e, "add_note"):
                        e.add_note(
                            "batch handle: "
                            f"schnorr_jobs={len(self._schnorr)} ecdsa_jobs={len(self._ecdsa)} "
                            f"fallback_jobs={len(self._fallbacks)} tokens={len(self._results)}"
                        )
                    raise

        for jobs, mask in ((self._schnorr, schnorr_mask), (self._ecdsa, ecdsa_mask)):
            if mask is not None:
                for j, ok in zip(jobs, mask):
                    self.sig_cache.insert(j.cache_key, bool(ok))
                    j.callback(bool(ok), self._fail)
        return self._results
