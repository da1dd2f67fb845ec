"""Batched script checking: the TPU offload point.

The reference validates scripts per input inside rayon par_iter
(tx_validation_in_utxo_context.rs:206-223); here the per-input signature
checks of an entire block/mergeset are *collected* into one device batch:

    collect phase  : classify each (input, utxo) pair, compute its sighash
                     (host, memoized per tx), queue (pubkey, msg, sig)
    dispatch phase : one batched Schnorr kernel call + one ECDSA call,
                     overlapped with the host-VM fallback lane
    resolve phase  : validity bitmask mapped back to per-input results

Over the signature cache sits the script-verdict memo (Bitcoin Core's
script-execution cache, validation.cpp CheckInputScripts): a transaction whose
every input took a batch lane and was answered valid is remembered with its
signature scripts and the amounts and scripts of the outputs it spent, and is
not collected again while the memo holds it.  The sink of a wide DAG hops
between chains that each ask for the same spends; the memo answers before a
script is classified, parsed or hashed.

Consensus equivalence: three script forms take the batch path, chosen by what
the script is: canonical P2PK Schnorr, canonical P2PK ECDSA, and a P2SH spend
whose redeem script is the canonical m-of-n multisig
(``standard.parse_multisig_redeem``) behind a push-only signature script of
exactly m 65-byte signatures.  Anything else routes to the host VM
(txscript.vm) — same acceptance decisions as running the reference's engine
per input.

The multisig lane: signatures follow key order, so signature i can only match
keys i .. i + (n - m); those m * (n - m + 1) candidate (signature, key) pairs
ask the signature cache and then join the device batch as ordinary ``schnorr``
/ ``ecdsa`` jobs.  At resolve every answer goes into the signature cache and
the engine's key-order walk is replayed over them.  The lane only ever
*accepts*: an input whose walk does not end in success (or would charge more
sig ops than the input committed, or meets a key that is no curve point) is
run through the host VM, which finds the device's answers in the cache and
supplies the verdict and the message.

The VM fallback lane is *deferred and parallel*: nonstandard inputs are
queued at collect time and executed at dispatch on a bounded thread pool,
concurrently with the device batches (the device dispatch releases the GIL
while XLA runs, so a multisig/P2SH-heavy block no longer serializes the
fallback work behind — or in front of — the device lane).  Failure
precedence matches the serial path exactly: VM failures apply first, in
collect order (a multisig input the VM re-ran holds the place it was
collected in), then device-batch failures in queue order, so the
(token -> first error) mapping is bit-identical to serial execution.
"""

from __future__ import annotations

import functools
import hashlib
import os
import threading

from kaspa_tpu.utils.sync import ranked_lock
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from time import perf_counter_ns

from kaspa_tpu.consensus import hashing as chash
from kaspa_tpu.crypto import eclib, secp
from kaspa_tpu.observability import trace
from kaspa_tpu.observability.core import REGISTRY, SIZE_BUCKETS
from kaspa_tpu.resilience.faults import FAULTS, FaultInjected
from kaspa_tpu.txscript import standard
from kaspa_tpu.txscript.caches import SigCache
from kaspa_tpu.txscript.vm import MAX_SCRIPT_ELEMENT_SIZE

# fast-path vs fallback mix: a fallback-heavy workload starves the device
# batch, which is the first thing to check when occupancy drops
_JOBS = REGISTRY.counter_family("txscript_batch_jobs", "kind", help="signature jobs queued for device dispatch")
_SIGCACHE_SKIPS = REGISTRY.counter("txscript_batch_sigcache_skips", help="jobs answered by the sig cache pre-dispatch")
# signature checks asked of the caches and answered without the device, by the
# checker's traffic class (one increment a dispatch round): a block whose
# transactions came through the mempool first reads hits == lookups and waits
# for no device.  A transaction the verdict memo answers counts as the checks
# its verdict stood for, asked and answered, whichever cache held them
_BLOCK_CACHE_LOOKUPS = REGISTRY.counter("txscript_sig_cache_block_lookups", help="signature checks block-path checkers asked of the caches (a verdict-memo hit counts as the checks it stood for)")
_BLOCK_CACHE_HITS = REGISTRY.counter("txscript_sig_cache_block_hits", help="of the block path's checks, those a cache answered without the device (either verdict; a verdict-memo hit counts as the checks it stood for)")
_TX_CACHE_LOOKUPS = REGISTRY.counter("txscript_sig_cache_tx_lookups", help="signature checks asked of the caches by checkers of a traffic class (the ingest tier's waves), counted as the block path's are")
_TX_CACHE_HITS = REGISTRY.counter("txscript_sig_cache_tx_hits", help="of the waves' checks, those a cache answered without the device, counted as the block path's are")
# the verdict memo, asked once a transaction ahead of the signature cache
_MEMO_LOOKUPS = REGISTRY.counter("txscript_tx_memo_lookups", help="transactions asked of the script-verdict memo at collect")
_MEMO_HITS = REGISTRY.counter("txscript_tx_memo_hits", help="of those, transactions the memo answered valid: none of their scripts was collected")
_VM_FALLBACKS = REGISTRY.counter("txscript_vm_fallbacks", help="inputs routed to the host VM instead of the batch")
_FALLBACK_BATCH = REGISTRY.histogram(
    "txscript_fallback_batch_size", SIZE_BUCKETS, help="deferred VM fallback jobs per dispatch"
)
_P2SH_INPUTS = REGISTRY.counter_family("txscript_p2sh_inputs", "lane", help="pay-to-script-hash inputs by the lane that took them (batch | vm)")
_MULTISIG_INPUTS = REGISTRY.counter("txscript_multisig_inputs", help="canonical m-of-n multisig inputs that took the batch path")
_MULTISIG_PAIRS = REGISTRY.counter(
    "txscript_multisig_pairs", help="candidate (signature, key) pairs of batch-path multisig inputs, cache-answered ones included"
)
_MULTISIG_RERUNS = REGISTRY.counter(
    "txscript_multisig_vm_reruns", help="batch-path multisig inputs the walk did not accept, re-run through the host VM"
)
_VM_RETRIES = REGISTRY.counter(
    "txscript_vm_fault_retries", help="VM fallback jobs retried after an injected transient fault"
)


def _memo_key(tx, utxo_entries) -> tuple:
    """What the batch lanes' verdict on a transaction is a function of, and
    nothing else: the id, the signature scripts and compute commits the id
    leaves out (a malleated signature script under the same id must miss), and
    of each spent output the two fields the sighash and the lanes read.  Not
    its ``block_daa_score``: one outpoint carries another score on every chain
    that accepted its creator, which is exactly where the memo is asked."""
    return (
        tx.id(),
        tuple([(inp.signature_script, inp.compute_commit) for inp in tx.inputs]),
        tuple([(entry.amount, entry.script_public_key) for entry in utxo_entries]),
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
    seq: int = 0  # place among the checker's VM-lane inputs, in collect order


@dataclass
class _MultisigInput:
    """One batch-path m-of-n input: the answers to its candidate pairs arrive
    from the cache at collect and from the device at resolve."""

    vm: _FallbackJob  # the same input through the host VM, run only if the walk does not accept
    m: int
    keys: list
    ecdsa: bool
    sig_op_limit: int
    answers: dict  # (signature index, key index) -> bool

    def accepted(self) -> bool:
        """The engine's key-order walk (vm._op_checkmultisig_impl) over the
        answers.  False wherever the engine would fail *or raise*."""
        n, key_pos = len(self.keys), 0
        for sig_idx in range(self.m):
            while True:
                if n - key_pos < self.m - sig_idx or key_pos >= self.sig_op_limit:
                    return False  # fewer keys than signatures left, or the next check exceeds the commit
                key = self.keys[key_pos]
                key_pos += 1
                if self.answers[(sig_idx, key_pos - 1)]:
                    break
                # the engine raises on a key that is no curve point before it
                # looks at the signature; the device just says False
                if not _is_curve_point(key, self.ecdsa):
                    return False
        return True


@functools.lru_cache(maxsize=4096)
def _is_curve_point(key: bytes, ecdsa: bool) -> bool:
    """A modular square root a key: remembered, because a wallet's keys come
    back with every input it spends."""
    return (eclib.parse_compressed(key) if ecdsa else eclib.lift_x(int.from_bytes(key, "big"))) is not None


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

    ``tx_memo``: the script-verdict memo, a second ``SigCache`` keyed by
    ``_memo_key``; its value is the number of signature checks the verdict
    stood for.  A checker made without one remembers nothing past itself.
    """

    def __init__(
        self,
        sig_cache: SigCache | None = None,
        vm_fallback=None,
        fallback_workers: int | None = None,
        traffic_class: str | None = None,
        tx_memo: SigCache | None = None,
    ):
        self.sig_cache = sig_cache if sig_cache is not None else SigCache()
        self.tx_memo = tx_memo if tx_memo is not None else SigCache()
        # contract: fn(tx, entries, input_index, reused, pov_daa_score) — the
        # daa score drives fork-activation gating inside the engine
        self.vm_fallback = vm_fallback
        self.fallback_workers = fallback_workers
        self.traffic_class = traffic_class
        self._jobs: list[_Job] = []
        self._fallbacks: list[_FallbackJob] = []
        self._multisigs: list[_MultisigInput] = []
        self._results: dict[int, Exception | None] = {}
        self._cache_lookups = self._cache_hits = 0  # since the last dispatch
        self._memo_lookups = self._memo_hits = 0  # since the last dispatch
        # (token, memo key, signature checks) of each transaction collected
        # wholly into the batch lanes: the memo's entries if all answer valid
        self._memo_candidates: list[tuple] = []

    def collect_tx(self, token: int, tx, utxo_entries, reused=None, pov_daa_score=None, seq_commit_accessor=None) -> None:
        """Queue all input script checks of `tx`; result under `token`.
        ``pov_daa_score`` feeds fork-activation gating in the VM fallback;
        ``seq_commit_accessor`` backs OpChainblockSeqCommit post-Toccata."""
        self._results.setdefault(token, None)
        key = _memo_key(tx, utxo_entries)
        checks = self.tx_memo.get(key)
        self._memo_lookups += 1
        if checks is not None:
            # the collection below, done before: it asked `checks` signature
            # checks, every one was answered valid, and no lane read the
            # caller's context (``pov_daa_score``, the seq-commit accessor)
            self._memo_hits += 1
            self._cache_lookups += checks
            self._cache_hits += checks
            return
        if reused is None:
            reused = chash.SigHashReusedValues()
        lookups0, vm0 = self._cache_lookups, len(self._fallbacks)
        for i, (inp, entry) in enumerate(zip(tx.inputs, utxo_entries)):
            try:
                self._collect_input(token, tx, utxo_entries, i, inp, entry, reused, pov_daa_score, seq_commit_accessor)
            except ScriptCheckError as e:
                self._fail(token, e)
        if len(self._fallbacks) == vm0:  # no input went to the host VM, which reads the caller's context
            self._memo_candidates.append((token, key, self._cache_lookups - lookups0))

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
            vm_job = _FallbackJob(
                token,
                i,
                functools.partial(
                    self.vm_fallback, tx, utxo_entries, i, reused, pov_daa_score,
                    seq_commit_accessor=seq_commit_accessor,
                ),
                ctx=trace.context(),
                seq=len(self._fallbacks) + len(self._multisigs),
            )
            if cls == standard.ScriptClass.SCRIPT_HASH:
                multisig = self._collect_multisig(tx, utxo_entries, i, inp, entry, reused, vm_job)
                _P2SH_INPUTS.inc("vm" if multisig is None else "batch")
                if multisig is not None:
                    self._multisigs.append(multisig)
                    return
            _VM_FALLBACKS.inc()
            vm_job.enqueued_ns = perf_counter_ns()  # it waits for a pool thread from here
            self._fallbacks.append(vm_job)

    def _collect_multisig(self, tx, utxo_entries, i, inp, entry, reused, vm_job) -> _MultisigInput | None:
        """The batch-path form of a P2SH input, its candidate pairs queued, or
        None: whatever is not the canonical spend of a canonical m-of-n redeem
        script is the host VM's, as it always was."""
        limit = inp.compute_commit.sig_op_count()
        pushes = standard.parse_canonical_pushes(inp.signature_script)
        if limit is None or not pushes:
            return None
        redeem, blobs = pushes[-1], pushes[:-1]
        if len(redeem) > MAX_SCRIPT_ELEMENT_SIZE or hashlib.blake2b(redeem, digest_size=32).digest() != entry.script_public_key.script[2:34]:
            return None
        parsed = standard.parse_multisig_redeem(redeem)
        if parsed is None:
            return None
        m, keys, ecdsa = parsed
        if len(blobs) != m or any(len(b) != 65 or b[64] not in chash.ALLOWED_SIG_HASH_TYPES for b in blobs):
            return None
        kind, sighash = ("ecdsa", chash.calc_ecdsa_signature_hash) if ecdsa else ("schnorr", chash.calc_schnorr_signature_hash)
        multisig = _MultisigInput(vm_job, m, keys, ecdsa, limit, {})
        msgs: dict = {}  # one sighash per hash type of this input
        for sig_idx, blob in enumerate(blobs):
            sig, hash_type = blob[:64], blob[64]
            if hash_type not in msgs:
                msgs[hash_type] = sighash(tx, utxo_entries, i, hash_type, reused)
            # signatures follow key order: signature sig_idx can only match keys sig_idx .. sig_idx + (n - m)
            for key_idx in range(sig_idx, sig_idx + len(keys) - m + 1):
                self._queue_pair(kind, keys[key_idx], msgs[hash_type], sig, multisig.answers, (sig_idx, key_idx))
        _MULTISIG_INPUTS.inc()
        _MULTISIG_PAIRS.inc(m * (len(keys) - m + 1))
        return multisig

    def _queue_pair(self, kind, pubkey, msg, sig, answers: dict, slot) -> None:
        """One candidate pair of a multisig input: its answer lands in
        ``answers[slot]``, from the cache now or from the device at resolve."""
        cached = self._ask(kind, pubkey, msg, sig, lambda ok, _fail: answers.__setitem__(slot, ok))
        if cached is not None:
            answers[slot] = cached

    def _queue(self, token, kind, pubkey, msg, sig, input_index):
        # `fail` is supplied at resolve time: dispatch_async detaches the
        # results dict into its handle, so the callback must not close over
        # the checker's (reusable) live state
        def cb(ok: bool, fail, token=token, input_index=input_index):
            if not ok:
                fail(token, ScriptCheckError("invalid signature", input_index))

        if self._ask(kind, pubkey, msg, sig, cb) is False:
            self._fail(token, ScriptCheckError("invalid signature (cached)", input_index))

    def _ask(self, kind, pubkey, msg, sig, callback) -> bool | None:
        """The signature cache's answer to one check, or None once the check
        is queued as a device job that ends in ``callback(ok, fail)``."""
        cache_key = (kind, sig, msg, pubkey)
        cached = self.sig_cache.get(cache_key)
        self._cache_lookups += 1
        if cached is not None:
            self._cache_hits += 1
            _SIGCACHE_SKIPS.inc()
            return cached
        _JOBS.inc(kind)
        self._jobs.append(_Job(kind, pubkey, msg, sig, cache_key, callback))
        return None

    def queued_jobs(self) -> int:
        """Signature jobs staged for the device lane since the last dispatch."""
        return len(self._jobs)

    def queued_multisig_inputs(self) -> int:
        """Multisig inputs that took the batch path since the last dispatch."""
        return len(self._multisigs)

    def memo_hits(self) -> int:
        """Transactions the verdict memo answered since the last dispatch."""
        return self._memo_hits

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
        multisigs, self._multisigs = self._multisigs, []
        jobs, self._jobs = self._jobs, []
        results, self._results = self._results, {}
        memo_candidates, self._memo_candidates = self._memo_candidates, []
        if self._memo_lookups:
            _MEMO_LOOKUPS.inc(self._memo_lookups)
            _MEMO_HITS.inc(self._memo_hits)
            self._memo_lookups = self._memo_hits = 0
        if self._cache_lookups:
            lookups, hits = (
                (_BLOCK_CACHE_LOOKUPS, _BLOCK_CACHE_HITS) if self.traffic_class is None else (_TX_CACHE_LOOKUPS, _TX_CACHE_HITS)
            )
            lookups.inc(self._cache_lookups)
            hits.inc(self._cache_hits)
            self._cache_lookups = self._cache_hits = 0

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
        return DispatchHandle(
            self.sig_cache, fallbacks, pending, schnorr, ecdsa, tickets, results, multisigs, self.tx_memo, memo_candidates
        )


class DispatchHandle:
    """In-flight dispatch: owns the detached jobs/results of one round."""

    def __init__(self, sig_cache, fallbacks, pending, schnorr, ecdsa, tickets, results, multisigs, tx_memo, memo_candidates):
        self.sig_cache = sig_cache
        self.tx_memo = tx_memo
        self._memo_candidates = memo_candidates
        self._fallbacks = fallbacks
        self._multisigs = multisigs
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
                    schnorr_mask = secp.verify_batch("schnorr", [(j.pubkey, j.msg, j.sig) for j in self._schnorr])
            if self._ecdsa:
                with trace.span("txscript.dispatch", kind="ecdsa", jobs=len(self._ecdsa)):
                    ecdsa_mask = secp.verify_batch("ecdsa", [(j.pubkey, j.msg, j.sig) for j in self._ecdsa])

        # fallback lane resolution BEFORE the device callbacks: the serial
        # path ran the VM at collect time, so VM failures must win the
        # first-error slot over same-token batch failures, in collect order
        vm_failures = []  # (job, error) of the VM lane
        if self._fallbacks:
            with trace.span("txscript.fallback_join", jobs=len(self._fallbacks), parallel=self._pending is not None):
                errors = (
                    [f.result() for f in self._pending]
                    if self._pending is not None
                    else [_run_fallback(j) for j in self._fallbacks]
                )
            vm_failures = [(job, err) for job, err in zip(self._fallbacks, errors) if err is not None]

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

        # the device's answers go into the signature cache first: a multisig
        # input the walk does not accept is re-run through the host VM, which
        # reads them there and does no curve arithmetic of its own
        batch_failures: list = []  # (token, error) of the P2PK jobs, in queue order
        reruns: list = []  # the VM jobs of multisig inputs the walk did not accept
        for jobs, mask in ((self._schnorr, schnorr_mask), (self._ecdsa, ecdsa_mask)):
            if mask is not None:
                for j, ok in zip(jobs, mask):
                    self.sig_cache.insert(j.cache_key, bool(ok))
                    j.callback(bool(ok), lambda token, err: batch_failures.append((token, err)))
        if self._multisigs:
            with trace.span("txscript.multisig_resolve", inputs=len(self._multisigs)) as sp:
                reruns = [ms.vm for ms in self._multisigs if not ms.accepted()]
                _MULTISIG_RERUNS.inc(len(reruns))
                for job in reruns:
                    err = _run_fallback(job)
                    if err is not None:
                        vm_failures.append((job, err))
                sp.set(vm_reruns=len(reruns))
            vm_failures.sort(key=lambda f: f[0].seq)
        for job, err in vm_failures:
            self._fail(job.token, ScriptCheckError(str(err), job.input_index))
        for token, err in batch_failures:
            self._fail(token, err)
        # every lane has resolved: a transaction collected wholly into the
        # batch lanes whose token stands at None had every script answered
        # valid, whoever asked (block path, speculative worker, ingest wave);
        # not one the VM re-ran, whose verdict reads the caller's context
        rerun_tokens = {job.token for job in reruns}
        for token, key, checks in self._memo_candidates:
            if self._results[token] is None and token not in rerun_tokens:
                self.tx_memo.insert(key, checks)
        return self._results
