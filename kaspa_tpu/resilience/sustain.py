"""Hostile-load sustain run: the chaos-engineering acceptance harness.

Builds a hostile workload (multisig/P2SH script mix that bypasses the
device fast path, plus an attacker side-DAG forking from genesis with
~1.5x the block count — a deep reorg when its heavier chain lands), then
replays it twice into fresh consensus instances:

  1. fault-free, in build order — the baseline fingerprints
  2. under a seeded fault schedule, delivered in shuffled windows through
     an orphan-tolerant queue (blocks held until their parents arrive)

and asserts the post-recovery end state (sink, utxo_commitment,
virtual_daa_score) is identical.  Every injected fault is transient
infrastructure noise — a device dispatch that errors into the breaker's
degraded lane, a VM fallback job that retries — so the faulted run must
converge to the byte-identical fault-free state; ``matches_fault_free``
in SUSTAIN.json is the acceptance bit.

The report splits deterministic data (fault event log, fingerprints)
from wall-clock data (throughput, breaker recovery latencies, lock-hold
traces): two runs of the same workload + schedule + seed produce
byte-identical ``deterministic`` sections.
"""

from __future__ import annotations

import json
import platform
import random
import sys
import time
from dataclasses import asdict, replace

import os

from kaspa_tpu.consensus.consensus import Consensus
from kaspa_tpu.observability.core import REGISTRY
from kaspa_tpu.resilience import supervisor
from kaspa_tpu.resilience.breaker import CLOSED, device_breaker
from kaspa_tpu.resilience.faults import FAULTS
from kaspa_tpu.sim.simulator import SimConfig, simulate
from kaspa_tpu.utils.sync import lock_trace_snapshot, set_lock_debug

# metric counters whose faulted-replay deltas land in SUSTAIN.json
_DELTA_COUNTERS = (
    "secp_degraded_dispatches",
    "secp_degraded_jobs",
    "txscript_vm_fault_retries",
    "kv_journal_repairs",
)


def default_schedule() -> dict:
    """The stock hostile schedule: four consecutive device-verify errors
    (trips the breaker, then fails its first probe — exercising trip,
    degraded lane, backoff doubling, and recovery) plus every-5th VM
    fallback job erroring (exercising the retry lane), capped at 8."""
    return {
        "device.verify": {"mode": "error", "hits": [2, 3, 4, 5]},
        "vm.fallback.exec": {"mode": "error", "every": 5, "max": 8},
    }


def build_workload(cfg: SimConfig) -> dict:
    """Hostile main DAG plus an attacker fork from the same genesis.

    The attacker sim runs with seed+1 (distinct miners/keys) and ~1.5x
    the blocks, so once its blocks are all delivered its chain carries
    more blue work and the virtual reorgs deep past the main DAG."""
    main = simulate(cfg)
    attacker = simulate(
        replace(cfg, num_blocks=max(cfg.num_blocks * 3 // 2, cfg.num_blocks + 1), seed=cfg.seed + 1)
    )
    return {"cfg": cfg, "main": main, "attacker": attacker, "blocks": main.blocks + attacker.blocks}


def _fingerprints(consensus: Consensus) -> dict:
    sink = consensus.sink()
    return {
        "sink": sink.hex(),
        "utxo_commitment": consensus.multisets[sink].finalize().hex(),
        "virtual_daa_score": consensus.get_virtual_daa_score(),
    }


def _insert(consensus: Consensus, block) -> None:
    status = consensus.validate_and_insert_block(block)
    assert status in ("utxo_valid", "utxo_pending"), f"sustain replay rejected block: {status}"


def _orphan_tolerant_replay(consensus: Consensus, blocks: list, seed: int, window: int = 8) -> None:
    """Deliver ``blocks`` in deterministically shuffled windows; a block
    whose parents have not arrived is parked and flushed once they do —
    the orphan-pool discipline a real node applies to out-of-order
    gossip, here driving the faulted run's out-of-order stress."""
    rng = random.Random(seed ^ 0x5EED)
    order: list = []
    for i in range(0, len(blocks), window):
        chunk = list(blocks[i : i + window])
        rng.shuffle(chunk)
        order.extend(chunk)

    def ready(b) -> bool:
        return all(consensus.storage.headers.has(p) for p in b.header.direct_parents())

    pending: dict[bytes, object] = {}
    for b in order:
        if not ready(b):
            pending[b.hash] = b
            continue
        _insert(consensus, b)
        progress = True
        while progress:
            progress = False
            for h, pb in list(pending.items()):
                if ready(pb):
                    del pending[h]
                    _insert(consensus, pb)
                    progress = True
    assert not pending, f"{len(pending)} orphans never became insertable"


def run_meta(wall: dict | None = None) -> dict:
    """Volatile per-run facts (timestamp, host, interpreter, wall-clock
    telemetry), quarantined under ONE artifact key so diffing two runs of
    the same workload+schedule+seed (``stable_view``) ignores them
    wholesale instead of chasing churn field by field."""
    return {
        "created_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "host": platform.node(),
        "python": sys.version.split()[0],
        "wall": wall or {},
    }


def stable_view(report: dict) -> dict:
    """The diffable surface of a SUSTAIN-family artifact: everything but
    ``run_meta``.  (``metrics`` stays — its throughput numbers are the
    run's headline, reviewed rather than diffed.)"""
    return {k: v for k, v in report.items() if k != "run_meta"}


def _split_breaker(snapshot: dict) -> tuple[dict, dict]:
    """(stable fields, volatile fields) of a breaker snapshot: recovery
    latencies and timestamped transition records differ every run and
    belong under ``run_meta.wall``."""
    snap = dict(snapshot)
    wall = {k: snap.pop(k) for k in ("recovery_latency_seconds", "transitions") if k in snap}
    return snap, wall


def _counter_value(counters: dict, name: str):
    v = counters.get(name, 0)
    return dict(v) if isinstance(v, dict) else v


def _delta(before: dict, after: dict, name: str):
    b, a = _counter_value(before, name), _counter_value(after, name)
    if isinstance(a, dict):
        b = b if isinstance(b, dict) else {}
        return {k: a[k] - b.get(k, 0) for k in sorted(a) if a[k] - b.get(k, 0)}
    return a - (b if isinstance(b, (int, float)) else 0)


def run_sustain(
    cfg: SimConfig,
    schedule: dict | None = None,
    seed: int = 0,
    out: str | None = None,
    workload: dict | None = None,
) -> dict:
    """Run the hostile sustain benchmark; returns (and optionally writes
    to ``out``) the SUSTAIN.json report dict."""
    schedule = default_schedule() if schedule is None else schedule
    wl = workload if workload is not None else build_workload(cfg)
    blocks = wl["blocks"]

    # fault-free baseline first, while nothing is armed
    FAULTS.clear()
    baseline = Consensus(wl["main"].params)
    for b in blocks:
        _insert(baseline, b)
    base_fp = _fingerprints(baseline)

    breaker = device_breaker()
    breaker.reset()
    set_lock_debug(True)
    before = REGISTRY.snapshot()["counters"]
    FAULTS.configure(schedule, seed)
    try:
        faulted = Consensus(wl["main"].params)
        t0 = time.perf_counter()
        _orphan_tolerant_replay(faulted, blocks, seed)
        elapsed = time.perf_counter() - t0
        events = FAULTS.events()
    finally:
        FAULTS.clear()
        set_lock_debug(False)
    after = REGISTRY.snapshot()["counters"]
    fp = _fingerprints(faulted)

    brk_stable, brk_wall = _split_breaker(breaker.snapshot())
    report = {
        "config": {**asdict(cfg), "fault_seed": seed, "schedule": schedule},
        "deterministic": {
            "blocks": len(blocks),
            "events": events,
            "fingerprints": fp,
            "fault_free_fingerprints": base_fp,
            "matches_fault_free": fp == base_fp,
        },
        "breaker": brk_stable,
        "metrics": {
            "replay_seconds": round(elapsed, 3),
            "blocks_per_sec": round(len(blocks) / elapsed, 2) if elapsed else None,
            "fault_injections": _delta(before, after, "fault_injections"),
            **{name: _delta(before, after, name) for name in _DELTA_COUNTERS},
        },
        "run_meta": run_meta(wall={"breaker": brk_wall, "lock_traces": lock_trace_snapshot()}),
    }
    if out:
        with open(out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")
    return report


# --- the wedge drill ------------------------------------------------------


def _await_recovery(breaker, timeout_s: float) -> bool:
    """Poll until the canary prober re-arms the breaker (CLOSED)."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if breaker.state == CLOSED:
            return True
        time.sleep(0.02)
    return breaker.state == CLOSED


def _await_late_results(expected: int, before: int, timeout_s: float) -> int:
    """Wait for abandoned workers to finish and discard their results, so
    the accounting in the report is complete (best-effort: a wedged real
    device might never finish — the drill's fakes always do)."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        seen = supervisor._POOL.snapshot()["late_results"] - before
        if seen >= expected:
            return seen
        time.sleep(0.05)
    return supervisor._POOL.snapshot()["late_results"] - before


def _compile_stall_drill(seed: int, stall_delay_s: float, compile_deadline_s: float) -> dict:
    """Micro-phase for the compile tier: wedge the first compile of a
    genuinely cold (schnorr, bucket) shape and assert the watchdog
    requeues it onto the host lane with the shape left cold.

    The injected wedge raises *before* the kernel call, so no real XLA
    compile runs — the phase costs ~compile_deadline_s, not minutes."""
    from kaspa_tpu.crypto import eclib, secp

    bucket = 8
    while secp._shape_key("schnorr_verify", bucket) in secp._seen_shapes:
        bucket <<= 1
    count = bucket // 2 + 1  # pads to exactly `bucket`
    seckey = (seed * 2 + 1) % eclib.N or 1
    pub = eclib.schnorr_pubkey(seckey)
    items = []
    for i in range(count):
        msg = bytes([i & 0xFF]) * 32
        items.append((pub, msg, eclib.schnorr_sign(msg, seckey)))

    prev_split = os.environ.get("KASPA_TPU_COLD_BUCKET_SPLIT")
    os.environ["KASPA_TPU_COLD_BUCKET_SPLIT"] = "0"  # hit the cold shape head-on
    FAULTS.configure({"device.jit_compile": {"mode": "wedge", "delay": stall_delay_s, "hits": [1]}}, seed)
    try:
        with supervisor.deadline_overrides(compile_s=compile_deadline_s):
            mask = secp.schnorr_verify_batch(items)
        events = FAULTS.events()
    finally:
        FAULTS.clear()
        if prev_split is None:
            os.environ.pop("KASPA_TPU_COLD_BUCKET_SPLIT", None)
        else:
            os.environ["KASPA_TPU_COLD_BUCKET_SPLIT"] = prev_split
    # the abandoned worker un-marks the shape when its wedge finally fires
    # (after stall_delay_s, well past our deadline) — wait for it so the
    # cold-shape assertion doesn't race the cleanup
    deadline = time.monotonic() + stall_delay_s + 5.0
    while secp._shape_key("schnorr_verify", bucket) in secp._seen_shapes and time.monotonic() < deadline:
        time.sleep(0.02)
    return {
        "bucket": bucket,
        "jobs": count,
        "injected": len(events),
        "events": events,
        "all_valid": bool(mask.all()) and len(mask) == count,
        "shape_left_cold": secp._shape_key("schnorr_verify", bucket) not in secp._seen_shapes,
    }


def run_wedge_drill(
    cfg: SimConfig,
    seed: int = 0,
    out: str | None = None,
    *,
    hang_delay_s: float = 8.0,
    dispatch_deadline_s: float = 5.0,
    stall_delay_s: float = 4.0,
    compile_deadline_s: float = 1.0,
    hang_hits: tuple = (2, 4, 6),
    recovery_timeout_s: float = 30.0,
) -> dict:
    """The supervision acceptance drill: wedge the device mid-replay and
    prove the node degrades instead of dying.

    Phase A replays the hostile workload fault-free (warming every device
    shape) and fingerprints the end state.  Phase B installs supervision
    (managed breaker + canary prober) and arms ``device.hang`` in mode
    "hang": the scheduled dispatches sleep past the watchdog deadline and
    then *complete* — the hardest case, because the late result must be
    discarded after the batch already resolved via the host lane.  Phase C
    replays out-of-order under those hangs.  Phase D is the compile-tier
    micro-drill (a wedged cold-bucket jit).  The report's gates: bitwise
    fingerprint identity, ``requeued == injected``, zero unresolved
    tickets, breaker recovered to CLOSED by the canary alone.
    """
    wl = build_workload(cfg)
    blocks = wl["blocks"]

    # A: fault-free baseline — also warms every (kernel, bucket) shape so
    # the hang phase exercises steady-state dispatch, not compiles
    FAULTS.clear()
    baseline = Consensus(wl["main"].params)
    for b in blocks:
        _insert(baseline, b)
    base_fp = _fingerprints(baseline)

    # B: supervision on.  Warm the canary's own (schnorr, bucket-8) shape
    # first — the hostile script mix may never dispatch that shape, and a
    # canary that compiles under a drill-shortened deadline would read as
    # a recovery failure that is really a cold jit
    from kaspa_tpu.crypto import secp

    breaker = device_breaker()
    breaker.reset()
    t_warm = time.perf_counter()
    canary_warm = secp.canary_probe()
    canary_warm_s = round(time.perf_counter() - t_warm, 3)
    before = REGISTRY.snapshot()["counters"]
    pool_before = supervisor._POOL.snapshot()
    supervisor.install(pretrace=False)
    schedule = {
        "device.hang": {
            "mode": "hang",
            "delay": hang_delay_s,
            "hits": list(hang_hits),
            "max": len(hang_hits),
        }
    }
    try:
        # C: out-of-order replay under dispatch hangs
        FAULTS.configure(schedule, seed)
        faulted = Consensus(wl["main"].params)
        t0 = time.perf_counter()
        with supervisor.deadline_overrides(
            dispatch_s=dispatch_deadline_s,
            compile_s=max(30.0, 6.0 * dispatch_deadline_s),
        ):
            _orphan_tolerant_replay(faulted, blocks, seed)
            hang_events = FAULTS.events()
            FAULTS.clear()
            recovered_after_hangs = _await_recovery(breaker, recovery_timeout_s)

            # D: compile-tier stall on a cold bucket
            compile_stall = _compile_stall_drill(seed, stall_delay_s, compile_deadline_s)
            recovered = _await_recovery(breaker, recovery_timeout_s)
        elapsed = time.perf_counter() - t0
        fp = _fingerprints(faulted)

        injected = len(hang_events) + compile_stall["injected"]
        late_seen = _await_late_results(
            injected, pool_before["late_results"], timeout_s=hang_delay_s + 10.0
        )
        # snapshot while supervision (managed) is live
        brk_stable, brk_wall = _split_breaker(breaker.snapshot())
    finally:
        FAULTS.clear()
        supervisor.shutdown()
    after = REGISTRY.snapshot()["counters"]
    pool_after = supervisor._POOL.snapshot()

    requeued = _delta(before, after, "secp_watchdog_requeued_total")
    from kaspa_tpu.ops import dispatch as coalesce

    eng = coalesce.active()
    tickets = {"coalescing": eng is not None}
    if eng is not None:
        tickets.update(eng.stats())
    unresolved = int(tickets.get("unresolved_chunks", 0))
    tickets["ok"] = unresolved == 0 and not tickets.get("abandoned", False)

    report = {
        "config": {
            **asdict(cfg),
            "fault_seed": seed,
            "schedule": schedule,
            "hang_delay_s": hang_delay_s,
            "dispatch_deadline_s": dispatch_deadline_s,
            "stall_delay_s": stall_delay_s,
            "compile_deadline_s": compile_deadline_s,
        },
        "deterministic": {
            "blocks": len(blocks),
            "events": hang_events,
            "fingerprints": fp,
            "fault_free_fingerprints": base_fp,
            "matches_fault_free": fp == base_fp,
        },
        "supervisor": {
            "injected_hangs": injected,
            "hang_phase_events": len(hang_events),
            "canary_warm": canary_warm,
            "canary_warm_seconds": canary_warm_s,
            "requeued_total": requeued,
            "requeue_matches_injected": requeued == injected,
            "requeued_jobs": _delta(before, after, "secp_watchdog_requeued_jobs"),
            "watchdog_timeouts": _delta(before, after, "secp_watchdog_timeouts"),
            "abandoned_threads": pool_after["abandoned_threads"] - pool_before["abandoned_threads"],
            "late_results": late_seen,
            "canary_probes": _delta(before, after, "secp_watchdog_canary_probes"),
            "recovered_after_hangs": recovered_after_hangs,
            "recovered": recovered,
            "verdict": supervisor.verdict(),
        },
        "compile_stall": compile_stall,
        "tickets": tickets,
        "breaker": brk_stable,
        "kernel_cache": supervisor.cache_report(),
        "metrics": {
            "replay_seconds": round(elapsed, 3),
            "blocks_per_sec": round(len(blocks) / elapsed, 2) if elapsed else None,
            "fault_injections": _delta(before, after, "fault_injections"),
            **{name: _delta(before, after, name) for name in _DELTA_COUNTERS},
        },
        "run_meta": run_meta(wall={"breaker": brk_wall}),
    }
    if out:
        with open(out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")
    return report
