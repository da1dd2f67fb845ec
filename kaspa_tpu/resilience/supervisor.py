"""Device-runtime supervision: the hang-proof verify plane.

The breaker (`resilience/breaker.py`) counts *errors*; it is blind to
*hangs*: a jit compile or device call that never returns, pinning whichever
thread dispatched it (the CoalescingDispatcher thread in production).
This module closes that hole with three cooperating pieces:

**Watchdog** — ``run_supervised(fn, tier=...)`` executes the device call
on a disposable worker thread and waits with a hard deadline (env knobs
``KASPA_TPU_WATCHDOG_DISPATCH_S`` / ``_COMPILE_S``; compile gets a far
longer tier because a cold XLA trace legitimately takes minutes).  On
deadline the worker is *abandoned-and-replaced*: the caller gets
``DeviceHangError`` immediately (so the batch requeues onto the
bit-identical host degraded lane and the breaker trips with cause
``hung``), the wedged thread is left to die on its own, and any result it
produces later is discarded — a job-level lock makes timeout-vs-complete
atomic, so a batch is never lost and never double-resolved.

**Canary prober** — with the breaker in *managed* mode (``install()``),
live dispatches while OPEN always take the degraded lane; HALF_OPEN
probes are driven exclusively by a background thread dispatching a tiny
known-answer batch (fault-injection suppressed, so drills stay
deterministic).  Recovery is automatic and never stalls a live block.

**Warm-kernel manifest** — a JSON sidecar next to the persistent XLA
compilation cache recording every (kernel, bucket, mesh, backend,
jax_version) shape this machine has compiled.  ``pretrace_warm()``
re-traces those shapes in a background thread at daemon start, off the
commit lock, so a restart after a wedge comes back warm.  Honesty note,
measured on this repo's kernels: the XLA disk cache removes the *compile*
but not the *trace/lower* wall, and on the CPU backend executable
deserialization costs about as much as compiling — so ``auto`` pretraces
only on non-CPU backends.
"""

from __future__ import annotations

import itertools
import json
import os
import queue
import threading

from kaspa_tpu.utils.sync import ranked_lock
import time
from contextlib import contextmanager

from kaspa_tpu.observability import trace
from kaspa_tpu.observability.core import REGISTRY
from kaspa_tpu.resilience import faults as faults_mod
from kaspa_tpu.resilience.breaker import CLOSED, device_breaker

_TIMEOUTS = REGISTRY.counter_family(
    "secp_watchdog_timeouts", "tier", help="supervised device calls that exceeded their watchdog deadline"
)
_REQUEUED = REGISTRY.counter(
    "secp_watchdog_requeued_total", help="hung device batches requeued onto the host degraded lane"
)
_REQUEUED_JOBS = REGISTRY.counter(
    "secp_watchdog_requeued_jobs", help="verify jobs inside watchdog-requeued batches"
)
_ABANDONED = REGISTRY.counter(
    "secp_watchdog_abandoned_threads", help="wedged device worker threads abandoned-and-replaced"
)
_LATE = REGISTRY.counter(
    "secp_watchdog_late_results", help="results from abandoned workers that arrived after requeue (discarded)"
)
_CANARY = REGISTRY.counter_family(
    "secp_watchdog_canary_probes", "result", help="background canary re-probe dispatches by outcome"
)

_DEADLINE_DEFAULTS = {"dispatch": 60.0, "compile": 900.0}
_overrides: dict[str, float] = {}


class DeviceHangError(RuntimeError):
    """A supervised device call blew its watchdog deadline.

    The call may still be running on the abandoned worker; the caller
    must treat the batch as *unresolved* and requeue it on the host lane
    (any late device result is discarded, never merged)."""

    def __init__(self, tier: str, deadline_s: float, kernel: str = "", jobs: int = 0):
        super().__init__(
            f"device {tier} exceeded the {deadline_s:g}s watchdog deadline "
            f"(kernel={kernel or '?'}, jobs={jobs}); batch requeued on the host lane"
        )
        self.tier = tier
        self.deadline_s = deadline_s
        self.kernel = kernel
        self.jobs = jobs


def watchdog_enabled() -> bool:
    return os.environ.get("KASPA_TPU_WATCHDOG", "1") not in ("0", "off", "false")


def deadline_s(tier: str) -> float:
    ov = _overrides.get(tier)
    if ov is not None:
        return ov
    env = os.environ.get(f"KASPA_TPU_WATCHDOG_{tier.upper()}_S")
    if env:
        return float(env)
    return _DEADLINE_DEFAULTS.get(tier, _DEADLINE_DEFAULTS["dispatch"])


@contextmanager
def deadline_overrides(dispatch_s: float | None = None, compile_s: float | None = None):
    """Scoped deadline overrides (process-global; drills and tests use
    this to make hangs observable in fractions of a second)."""
    prev = dict(_overrides)
    if dispatch_s is not None:
        _overrides["dispatch"] = float(dispatch_s)
    if compile_s is not None:
        _overrides["compile"] = float(compile_s)
    try:
        yield
    finally:
        _overrides.clear()
        _overrides.update(prev)


# --- the watchdogged worker pool ------------------------------------------


class _Job:
    __slots__ = ("fn", "event", "lock", "result", "error", "abandoned")

    def __init__(self, fn):
        self.fn = fn
        self.event = threading.Event()
        self.lock = ranked_lock("watchdog.task")
        self.result = None
        self.error: BaseException | None = None
        self.abandoned = False


class _Worker(threading.Thread):
    _ids = itertools.count(1)

    def __init__(self, pool: "WorkerPool"):
        super().__init__(name=f"secp-supervised-{next(self._ids)}", daemon=True)
        self._pool = pool
        self._q: queue.SimpleQueue = queue.SimpleQueue()  # graftlint: allow(unbounded-queue) -- one job in flight per supervised worker by construction (submit awaits the verdict)

    def submit(self, job: _Job) -> None:
        self._q.put(job)

    def retire(self) -> None:
        self._q.put(None)

    def run(self) -> None:
        while True:
            job = self._q.get()
            if job is None:
                return
            try:
                r, e = job.fn(), None
            except BaseException as ex:  # noqa: BLE001 - surfaced on the caller
                r, e = None, ex
            with job.lock:
                late = job.abandoned
                if not late:
                    job.result, job.error = r, e
                    job.event.set()
            if late:
                # the caller gave up on this job long ago: discard the
                # result and retire — a replacement worker already exists
                self._pool._note_late()
                return


class WorkerPool:
    """Disposable device-call workers with a small idle free-list.

    Concurrency is caller-driven (each ``run`` occupies one worker for
    its duration), so pipelined dispatch keeps overlapping exactly as it
    did without the watchdog."""

    def __init__(self, max_idle: int = 2):
        self._lock = ranked_lock("watchdog.pool")
        self._free: list[_Worker] = []
        self._max_idle = max_idle
        self.completed = 0
        self.timeouts: dict[str, int] = {}
        self.abandoned = 0
        self.late = 0

    def _get(self) -> _Worker:
        with self._lock:
            if self._free:
                return self._free.pop()
        w = _Worker(self)
        w.start()
        return w

    def _put(self, w: _Worker) -> None:
        with self._lock:
            if len(self._free) < self._max_idle:
                self._free.append(w)
                return
        w.retire()

    def _note_late(self) -> None:
        _LATE.inc()
        with self._lock:
            self.late += 1

    def run(self, fn, deadline: float, tier: str, kernel: str = "", jobs: int = 0):
        job = _Job(fn)
        w = self._get()
        w.submit(job)
        if not job.event.wait(deadline):
            with job.lock:
                if not job.event.is_set():
                    # timeout-vs-complete decided atomically: from here the
                    # worker's eventual result is late and gets discarded
                    job.abandoned = True
            if job.abandoned:
                _TIMEOUTS.inc(tier)
                _ABANDONED.inc()
                with self._lock:
                    self.timeouts[tier] = self.timeouts.get(tier, 0) + 1
                    self.abandoned += 1
                raise DeviceHangError(tier, deadline, kernel, jobs)
        self._put(w)
        with self._lock:
            self.completed += 1
        if job.error is not None:
            raise job.error
        return job.result

    def shutdown(self) -> None:
        with self._lock:
            free, self._free = self._free, []
        for w in free:
            w.retire()

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "completed": self.completed,
                "timeouts": dict(self.timeouts),
                "abandoned_threads": self.abandoned,
                "late_results": self.late,
                "idle_workers": len(self._free),
            }


_POOL = WorkerPool()

_stats_lock = ranked_lock("watchdog.stats")
_REQUEUE_STATS = {"batches": 0, "jobs": 0}


def run_supervised(fn, *, tier: str = "dispatch", kernel: str = "", jobs: int = 0):
    """Run one device call under the watchdog; returns its result, raises
    its exception, or raises DeviceHangError on deadline.  With the
    watchdog disabled (KASPA_TPU_WATCHDOG=0) this is a plain call."""
    if not watchdog_enabled():
        return fn()
    d = deadline_s(tier)
    ctx = trace.context()

    def _on_worker():
        # umbrella span re-attaches the worker's device spans (host
        # marshal, jit compile, device dispatch) to the caller's trace
        with trace.span("supervisor.worker", parent=ctx, kernel=kernel, tier=tier, jobs=jobs):
            return fn()

    with trace.span("supervisor.dispatch", kernel=kernel, tier=tier, jobs=jobs, deadline_s=d):
        return _POOL.run(_on_worker, d, tier, kernel, jobs)


def note_requeue(jobs: int) -> None:
    """Record one hung batch requeued onto the host degraded lane."""
    _REQUEUED.inc()
    _REQUEUED_JOBS.inc(jobs)
    with _stats_lock:
        _REQUEUE_STATS["batches"] += 1
        _REQUEUE_STATS["jobs"] += jobs


def verdict() -> dict:
    """Compact supervision verdict attached to dispatch-timeout errors."""
    p = _POOL.snapshot()
    try:
        state = device_breaker().state
    except Exception:  # noqa: BLE001 - verdict is best-effort diagnostics
        state = "?"
    with _stats_lock:
        requeued = dict(_REQUEUE_STATS)
    return {
        "watchdog": "on" if watchdog_enabled() else "off",
        "installed": _install_count > 0,
        "breaker": state,
        "timeouts": p["timeouts"],
        "abandoned_threads": p["abandoned_threads"],
        "late_results": p["late_results"],
        "requeued": requeued,
    }


# --- warm-kernel manifest (persistent compiled-kernel cache index) --------

_manifest_lock = ranked_lock("supervisor.manifest")
_pretrace_report: list | None = None


def manifest_path() -> str:
    p = os.environ.get("KASPA_TPU_WARM_MANIFEST")
    if p:
        return p
    from kaspa_tpu.utils import jax_setup

    return os.path.join(jax_setup.cache_dir(), "warm_manifest.json")


def _env_key() -> dict:
    import jax

    from kaspa_tpu.ops import mesh

    return {"mesh": mesh.active_size(), "backend": jax.default_backend(), "jax_version": jax.__version__}


def _read_manifest(path: str) -> list[dict]:
    try:
        with open(path) as f:
            doc = json.load(f)
        entries = doc.get("entries")
        if not isinstance(entries, list):
            return []
        out = []
        for e in entries:
            if isinstance(e, dict):
                # older trees wrote a "family" beside the kernel name, which
                # says the same: without it their rows dedup against ours
                e.pop("family", None)
                out.append(e)
        return out
    except (OSError, ValueError):
        return []


def note_shape(kernel_name: str, bucket: int) -> None:
    """Record a freshly compiled (kernel, bucket) shape in the manifest,
    keyed by the current mesh/backend/jax version.  Write-through on new
    shapes only (rare); never allowed to fail a dispatch."""
    try:
        path = manifest_path()
        entry = {"kernel": str(kernel_name), "bucket": int(bucket), **_env_key()}
        with _manifest_lock:
            entries = _read_manifest(path)
            if entry in entries:
                return
            entries.append(entry)
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"entries": entries}, f, indent=2, sort_keys=True)
            os.replace(tmp, path)
    except Exception:  # noqa: BLE001 - the manifest is an optimization
        pass


def load_warm_entries() -> list[dict]:
    """Manifest entries compiled under the *current* (mesh, backend,
    jax_version) — the only ones a pretrace can actually reuse."""
    try:
        key = _env_key()
        return [
            e
            for e in _read_manifest(manifest_path())
            if all(e.get(k) == v for k, v in key.items())
        ]
    except Exception:  # noqa: BLE001
        return []


def pretrace_warm(budget_s: float | None = None) -> list[dict]:
    """Pre-trace every matching manifest shape (smallest buckets first so
    a budget cut keeps the most common shapes warm).  Returns per-shape
    timing — the measured warm-start jit cost."""
    from kaspa_tpu.crypto import secp  # deferred: secp imports this module

    out: list[dict] = []
    t_all = time.monotonic()
    for e in sorted(load_warm_entries(), key=lambda e: (e.get("bucket", 0), e.get("kernel", ""))):
        row = {"kernel": e.get("kernel"), "bucket": e.get("bucket")}
        if budget_s is not None and time.monotonic() - t_all > budget_s:
            row["status"] = "skipped:budget"
            out.append(row)
            continue
        t0 = time.monotonic()
        row["status"] = secp.pretrace_bucket(e.get("kernel", ""), int(e.get("bucket", 0)))
        row["seconds"] = round(time.monotonic() - t0, 3)
        out.append(row)
    global _pretrace_report
    _pretrace_report = out
    return out


def cache_report() -> dict:
    """Persistent-kernel-cache evidence for dossiers and drills."""
    report: dict = {"manifest_path": manifest_path()}
    try:
        from kaspa_tpu.utils import jax_setup

        report["xla_cache_dir"] = jax_setup.cache_dir()
    except Exception:  # noqa: BLE001
        pass
    try:
        report["env"] = _env_key()
        report["entries"] = load_warm_entries()
    except Exception:  # noqa: BLE001
        report["entries"] = []
    report["entries_total"] = len(_read_manifest(report["manifest_path"]))
    if _pretrace_report is not None:
        report["pretrace"] = _pretrace_report
    return report


# --- the canary prober ----------------------------------------------------


class CanaryProber(threading.Thread):
    """Drives breaker HALF_OPEN off the critical path.

    Woken by the breaker's trip listener; once the backoff window
    elapses it claims the probe slot (``allow(probe=True)`` — the only
    path that transitions a *managed* breaker to HALF_OPEN, so a live
    super-batch can never race it) and dispatches a tiny known-answer
    batch with fault injection suppressed."""

    def __init__(self, breaker, probe_fn=None, poll_s: float = 0.05):
        super().__init__(name="canary-prober", daemon=True)
        self._breaker = breaker
        self._probe_fn = probe_fn
        self._poll_s = poll_s
        self._wake = threading.Event()
        self._stop = threading.Event()
        self.probes = 0
        self.ok = 0
        self.failed = 0
        breaker.add_trip_listener(self._wake.set)

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()

    def snapshot(self) -> dict:
        return {"probes": self.probes, "ok": self.ok, "failed": self.failed, "alive": self.is_alive()}

    def run(self) -> None:
        while not self._stop.is_set():
            self._wake.wait(0.5)
            if self._stop.is_set():
                return
            self._wake.clear()
            br = self._breaker
            while br.state != CLOSED and not self._stop.is_set():
                if not br.reopen_due() or not br.allow(probe=True):
                    self._stop.wait(self._poll_s)
                    continue
                self.probes += 1
                if self._run_probe():
                    self.ok += 1
                    _CANARY.inc("ok")
                    br.record_success()
                else:
                    self.failed += 1
                    _CANARY.inc("failed")
                    br.record_failure(cause="canary")

    def _run_probe(self) -> bool:
        fn = self._probe_fn
        if fn is None:
            from kaspa_tpu.crypto.secp import canary_probe as fn  # deferred: import cycle
        try:
            with faults_mod.suppress():
                with trace.span("supervisor.canary"):
                    return bool(fn())
        except Exception:  # noqa: BLE001 - a failed probe just re-opens
            return False


# --- install / shutdown ---------------------------------------------------

_install_lock = ranked_lock("supervisor.install")
_install_count = 0
_prober: CanaryProber | None = None


def _should_pretrace(flag: bool | None) -> bool:
    if flag is not None:
        return flag
    env = os.environ.get("KASPA_TPU_PRETRACE", "auto")
    if env in ("1", "on", "true"):
        return True
    if env in ("0", "off", "false"):
        return False
    # auto: on CPU the XLA cache's executable deserialization costs about
    # as much as compiling, so a background pretrace only burns cores; on
    # a real accelerator it is the restart-warmth mechanism
    try:
        import jax

        return jax.default_backend() != "cpu" and bool(load_warm_entries())
    except Exception:  # noqa: BLE001
        return False


def installed() -> bool:
    return _install_count > 0


def install(pretrace: bool | None = None, probe_fn=None) -> None:
    """Activate supervision: managed breaker + canary prober, and (backend
    permitting) a background warm-manifest pretrace off the commit lock.
    Refcounted — concurrent daemons in one process share one prober."""
    global _install_count, _prober
    with _install_lock:
        _install_count += 1
        if _install_count > 1:
            return
        br = device_breaker()
        br.set_managed(True)
        _prober = CanaryProber(br, probe_fn=probe_fn)
        _prober.start()
    if _should_pretrace(pretrace):
        budget = float(os.environ.get("KASPA_TPU_PRETRACE_BUDGET_S", "600"))
        threading.Thread(
            target=lambda: pretrace_warm(budget_s=budget), name="kernel-pretrace", daemon=True
        ).start()


def shutdown() -> None:
    """Release one install ref; the last one stops the prober and returns
    the breaker to legacy (unmanaged) probing."""
    global _install_count, _prober
    with _install_lock:
        if _install_count == 0:
            return
        _install_count -= 1
        if _install_count > 0:
            return
        prober, _prober = _prober, None
    if prober is not None:
        prober.stop()
    try:
        device_breaker().set_managed(False)
    except Exception:  # noqa: BLE001
        pass


def _state() -> dict:
    out = {
        "watchdog": watchdog_enabled(),
        "installed": _install_count > 0,
        "deadlines": {t: deadline_s(t) for t in ("dispatch", "compile")},
        "pool": _POOL.snapshot(),
    }
    with _stats_lock:
        out["requeued"] = dict(_REQUEUE_STATS)
    p = _prober
    if p is not None:
        out["canary"] = p.snapshot()
    return out


REGISTRY.register_collector("supervisor", _state)
