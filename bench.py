#!/usr/bin/env python
"""Headline benchmark: batched Schnorr-secp256k1 verification throughput.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Baseline: 50_000 verifies/sec on a single TPU v5e chip (BASELINE.json
north star; the reference does this on CPU via libsecp256k1 + rayon,
consensus/src/processes/transaction_validator/tx_validation_in_utxo_context.rs:206-223).

A chip belongs to one process at a time and a wedged PJRT client poisons
its whole process, so this script is a jax-free PARENT that runs the real
workload in ONE FRESH CHILD with a staged in-child device probe (fail fast
on a dead backend) and a hard parent-side timeout (kill on a hung one).
The headline and the sweep measure a TPU or nothing: with no answering TPU
the script exits non-zero and prints no value — there is no CPU lane.  The
``dispatch`` / ``aggregate`` / ``probe`` child modes are CPU-runnable
identity/ratio checks that tools/roundcheck.py drives directly.

Every lane verifies a DISTINCT (pubkey, message, signature) triple —
no tiling — and the batch mixes valid and invalid signatures: the device
mask must match the pure-python oracle expectation exactly.

``--sweep`` runs the kernel x batch-size x mesh-size grid instead of the
headline number (one fresh child per cell, mesh via KASPA_TPU_MESH) and
writes best-per-config to BENCH_SWEEP.json; ``--probe`` just reports
backend liveness + device count.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

BASELINE = 50_000.0  # verifies/sec/chip target
B = int(os.environ.get("KASPA_TPU_BENCH_B", "16384"))

METRIC = "schnorr_secp256k1_batch_verify_throughput"
UNIT = "verifies/sec/chip"

# -- parent-side tunables (env-overridable for local experiments) ----------
TOTAL_BUDGET_S = float(os.environ.get("KASPA_TPU_BENCH_BUDGET_S", "1500"))
ATTEMPT_TIMEOUT_S = float(os.environ.get("KASPA_TPU_BENCH_ATTEMPT_S", "420"))
PROBE_TIMEOUT_S = float(os.environ.get("KASPA_TPU_BENCH_PROBE_S", "90"))


# ==========================================================================
# child: the actual device workload (runs in a fresh interpreter per try)
# ==========================================================================


def _compile_events(spans: list) -> list:
    """Filter a drained span list down to jit/compile events (the
    ``bench.jit_compile`` probe span, secp's per-shape ``secp.jit_compile``,
    mesh shard_map traces) — how far each compile got before a stall."""
    out = []
    for s in spans or []:
        name = str(s.get("path") or s.get("name") or "")
        if "jit" in name or "compile" in name:
            out.append(s)
    return out


def _child_probe(timeout_s: float) -> bool:
    """True if the device answers a trivial jit within the timeout.

    Runs in a daemon thread so a wedged compile RPC can't hang the child
    past the deadline — the child reports and exits, and the parent
    retries in another fresh process (fresh PJRT client).
    """
    import threading

    ok = []

    def probe():
        import jax
        import jax.numpy as jnp

        from kaspa_tpu.observability import trace

        # span the first-call compile so a failed probe's line shows how far
        # the backend got (span present+closed = compile finished; capture
        # empty = it never came back)
        with trace.span("bench.jit_compile", kernel="probe_add1", batch=8):
            y = jax.jit(lambda v: v + 1)(jnp.ones((8,), jnp.int32))
            y.block_until_ready()
        ok.append(True)

    t = threading.Thread(target=probe, daemon=True)
    t.start()
    t.join(timeout_s)
    return bool(ok)


def _child_probe_main() -> None:
    """Probe-only child (KASPA_TPU_BENCH_MODE=probe): one trivial jit,
    one JSON line, exit 0/3.  The parent's session-start probe and
    tools/roundcheck.py both run this in a fresh interpreter so a wedged
    PJRT client dies with the child, never with the caller."""
    from kaspa_tpu.utils import jax_setup

    jax_setup.setup()

    from kaspa_tpu.observability import trace

    trace.set_capture(64)
    t0 = time.perf_counter()
    ok = _child_probe(PROBE_TIMEOUT_S)
    devices = 0
    device_platform = ""
    if ok:
        import jax

        devices = len(jax.devices())  # the sweep's mesh column source
        device_platform = jax.devices()[0].platform
    # persistent-kernel-cache status: a warm manifest means the heavy secp
    # shapes need no re-trace — the probe reuses (and reports) that cache
    # instead of proving compilation from scratch
    cache: dict = {}
    if ok:
        try:
            from kaspa_tpu.resilience import supervisor

            rep = supervisor.cache_report()
            entries = rep.get("entries") or []
            cache = {
                "manifest_path": rep.get("manifest_path"),
                "xla_cache_dir": rep.get("xla_cache_dir"),
                "warm_entries": len(entries),
                # aggregate-RLC kernels warm in this env (family column in
                # the manifest schema): 0 means the first --verify-mode
                # aggregate dispatch pays a cold compile
                "aggregate_warm_entries": sum(1 for e in entries if e.get("family") == "aggregate"),
                "entries_total": rep.get("entries_total", 0),
            }
        except Exception:  # noqa: BLE001 - cache evidence is best-effort
            pass
    print(
        json.dumps(
            {
                "probe_ok": ok,
                "elapsed_s": round(time.perf_counter() - t0, 3),
                "platform": os.environ.get("JAX_PLATFORMS", ""),
                "devices": devices,
                "device_platform": device_platform,
                # jit/compile span evidence (how far a stalled probe got)
                "jit_compile_events": _compile_events(trace.drain()),
                "kernel_cache": cache,
            }
        )
    )
    sys.stdout.flush()
    os._exit(0 if ok else 3)


def _child_ecdsa_main(obs_fn) -> None:
    """ECDSA sweep lane: mirrors the Schnorr child (distinct triples, a
    corrupted quarter, host-side validity checks matching secp.py's
    front-end, device mask asserted against the oracle expectation)."""
    import random

    import numpy as np

    from kaspa_tpu.crypto import eclib
    from kaspa_tpu.ops import mesh
    from kaspa_tpu.ops.secp256k1.verify import ecdsa_verify

    from kaspa_tpu.sim import sigbatch

    triples = sigbatch.ecdsa_points(B)
    for i in (0, 1, B // 2, B - 1):
        Pt, msg, sig = triples[i]
        pub33 = bytes([2 + (Pt[1] & 1)]) + Pt[0].to_bytes(32, "big")
        assert eclib.ecdsa_verify(pub33, msg, sig), "generator produced bad ecdsa sig"

    expect = [True] * B
    rng = random.Random(11)
    sigs = [t[2] for t in triples]
    for i in range(0, B, 4):  # corrupt a quarter of the batch
        j = rng.randrange(64)
        sigs[i] = sigs[i][:j] + bytes([sigs[i][j] ^ (1 + rng.randrange(255))]) + sigs[i][j + 1 :]
        expect[i] = False

    half_n = eclib.N // 2
    # byte columns, as secp._Batch hands them over: the backend (pallas or
    # XLA) derives its own layout — the e2e path includes that marshalling
    zero32 = bytes(32)
    px = [zero32] * B
    py = [zero32] * B
    rc = [zero32] * B
    u1 = [0] * B
    u2 = [0] * B
    ok = np.zeros(B, dtype=bool)
    for i, ((x, y), msg, _orig) in enumerate(triples):
        r = int.from_bytes(sigs[i][:32], "big")
        s = int.from_bytes(sigs[i][32:], "big")
        # same validity gate as secp.ecdsa_verify_batch (corrupt r/s can
        # fail by encoding before ever reaching the device)
        if not (1 <= r < eclib.N) or not (1 <= s < eclib.N) or s > half_n:
            continue
        z = int.from_bytes(msg, "big") % eclib.N
        si = pow(s, -1, eclib.N)
        px[i] = x.to_bytes(32, "big")
        py[i] = y.to_bytes(32, "big")
        rc[i] = r.to_bytes(32, "big")
        u1[i] = z * si % eclib.N
        u2[i] = r * si % eclib.N
        ok[i] = True

    mask = np.asarray(ecdsa_verify(px, py, rc, u1, u2, ok))  # compile + warmup
    assert mask.tolist() == expect, "BENCH CORRECTNESS FAILURE: ecdsa mask != oracle"

    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        out = np.asarray(ecdsa_verify(px, py, rc, u1, u2, ok))
        best = min(best, time.perf_counter() - t0)
    assert out.tolist() == expect

    value = B / best
    print(
        json.dumps(
            {
                "metric": "ecdsa_secp256k1_batch_verify_throughput",
                "value": round(value, 1),
                "unit": UNIT,
                "vs_baseline": round(value / BASELINE, 4),
                "batch": B,
                "mesh": mesh.active_size(),
                "observability": obs_fn(),
            }
        )
    )
    sys.stdout.flush()
    os._exit(0)


def _child_dispatch_main(obs_fn) -> None:
    """Dispatch-layer lane (KASPA_TPU_BENCH_MODE=dispatch): coalesced
    cross-block dispatch vs legacy per-block dispatch over the SAME jobs
    and the SAME device kernel, so the delta isolates the dispatch layer.

    Legacy = one blocking device call per chunk (what per-block
    ``BatchScriptChecker.dispatch`` does); coalesced = every chunk
    submitted to the CoalescingDispatcher up front, masks collected from
    tickets.  Both lanes are oracle-checked before timing.
    """
    import random

    from kaspa_tpu.crypto import secp
    from kaspa_tpu.ops import dispatch as coalesce
    from kaspa_tpu.ops import mesh
    from kaspa_tpu.sim import sigbatch

    total = int(os.environ.get("KASPA_TPU_BENCH_DISPATCH_B", "512"))
    chunk = int(os.environ.get("KASPA_TPU_BENCH_CHUNK", "16"))
    passes = int(os.environ.get("KASPA_TPU_BENCH_DISPATCH_PASSES", "2"))
    kind = os.environ.get("KASPA_TPU_BENCH_KERNEL", "schnorr")
    # deterministic flush behavior while timing: size-triggered flushes plus
    # one final nudge, with the age timer parked out of the way
    os.environ.setdefault("KASPA_TPU_COALESCE_AGE_MS", "500")
    target = coalesce.configure(os.environ.get("KASPA_TPU_COALESCE") or min(total, 256))

    if kind == "ecdsa":
        raw = sigbatch.ecdsa_points(total)
        items = [(bytes([2 + (P[1] & 1)]) + P[0].to_bytes(32, "big"), msg, sig) for P, msg, sig in raw]
        batch_fn = secp.ecdsa_verify_batch
    else:
        raw = sigbatch.schnorr_points(total)
        items = [(pub, msg, sig) for _P, pub, msg, sig in raw]
        batch_fn = secp.schnorr_verify_batch
    expect = [True] * total
    rng = random.Random(13)
    for i in range(0, total, 4):  # corrupt a quarter of the jobs
        pub, msg, sig = items[i]
        j = rng.randrange(64)
        items[i] = (pub, msg, sig[:j] + bytes([sig[j] ^ (1 + rng.randrange(255))]) + sig[j + 1 :])
        expect[i] = False
    chunks = [items[i : i + chunk] for i in range(0, total, chunk)]

    engine = coalesce.active()
    assert engine is not None, "coalescing engine failed to configure"

    def run_legacy() -> list:
        out = []
        for ch in chunks:
            out.extend(bool(v) for v in batch_fn(ch))
        return out

    def run_coalesced() -> list:
        tickets = [engine.submit(kind, list(ch)) for ch in chunks]
        out = []
        for t in tickets:
            out.extend(bool(v) for v in t.wait())
        return out

    # compile + warmup both shapes, oracle-checked
    assert run_legacy() == expect, "BENCH CORRECTNESS FAILURE: legacy mask != oracle"
    assert run_coalesced() == expect, "BENCH CORRECTNESS FAILURE: coalesced mask != oracle"

    legacy_best = coalesced_best = float("inf")
    for _ in range(passes):
        t0 = time.perf_counter()
        out = run_legacy()
        legacy_best = min(legacy_best, time.perf_counter() - t0)
        assert out == expect
        t0 = time.perf_counter()
        out = run_coalesced()
        coalesced_best = min(coalesced_best, time.perf_counter() - t0)
        assert out == expect

    legacy_vps = total / legacy_best
    coalesced_vps = total / coalesced_best
    result = {
        "metric": "verify_dispatch_coalescing",
        "value": round(coalesced_vps, 1),
        "unit": UNIT,
        "legacy_vps": round(legacy_vps, 1),
        "coalesced_vps": round(coalesced_vps, 1),
        "speedup": round(coalesced_vps / legacy_vps, 3),
        "batch": total,
        "chunk": chunk,
        "coalesce_target": target,
        "passes": passes,
        "kernel": kind,
        "mesh": mesh.active_size(),
    }

    # optional end-to-end identity check: replay the same simulated DAG with
    # coalescing off and on; sink + utxo_commitment must be bit-identical
    replay_blocks = int(os.environ.get("KASPA_TPU_BENCH_DISPATCH_REPLAY", "0"))
    if replay_blocks:
        from kaspa_tpu.sim.simulator import SimConfig, replay, simulate

        cfg = SimConfig(
            bps=2, delay=2.0, num_miners=4, num_blocks=replay_blocks, txs_per_block=4, seed=42
        )
        res = simulate(cfg)
        coalesce.configure(0)
        _, fresh_legacy = replay(res)
        sink_l = fresh_legacy.sink()
        commit_l = fresh_legacy.multisets[sink_l].finalize().hex()
        coalesce.configure(target)
        _, fresh_co = replay(res)
        sink_c = fresh_co.sink()
        commit_c = fresh_co.multisets[sink_c].finalize().hex()
        result.update(
            replay_blocks=replay_blocks,
            replay_txs=res.total_txs,  # must be > 0 for the check to mean anything
            replay_identical=bool(sink_l == sink_c and commit_l == commit_c),
            sink=sink_c.hex(),
            utxo_commitment=commit_c,
        )

    coalesce.drain(timeout=10.0)
    print(json.dumps({**result, "observability": obs_fn()}))
    sys.stdout.flush()
    os._exit(0)


def _child_aggregate_main(obs_fn) -> None:
    """Aggregate-RLC lane (KASPA_TPU_BENCH_MODE=aggregate): ONE combined
    multi-scalar check vs per-signature dual ladders over the SAME items on
    the SAME backend — the delta is the tentpole speedup (the shared
    doubling chain amortized over the batch instead of paid per lane).

    Correctness before timing: an all-valid batch must come back all-True
    on both lanes, and a small corrupted batch must bisect to the oracle
    mask through the aggregate lane (the falsification path the tests pin).
    """
    from kaspa_tpu.crypto import eclib, secp
    from kaspa_tpu.ops import mesh
    from kaspa_tpu.sim import sigbatch

    total = int(os.environ.get("KASPA_TPU_BENCH_AGG_B", "512"))
    passes = int(os.environ.get("KASPA_TPU_BENCH_AGG_PASSES", "2"))
    check_b = int(os.environ.get("KASPA_TPU_BENCH_AGG_CHECK_B", "8"))
    raw = sigbatch.schnorr_points(total + check_b)
    items = [(pub, msg, sig) for _P, pub, msg, sig in raw[:total]]

    # bisection correctness on a small corrupted batch (small on purpose:
    # each recursion bucket is a fresh ~1min XLA compile on a cold CPU
    # backend, so the falsification check must not walk a deep bucket chain)
    bad = [(pub, msg, sig) for _P, pub, msg, sig in raw[total:]]
    k = len(bad) // 2
    bad[k] = (bad[k][0], bad[k][1], bad[k][2][:32] + ((int.from_bytes(bad[k][2][32:], "big") + 1) % eclib.N).to_bytes(32, "big"))
    expect_bad = [eclib.schnorr_verify(*it) for it in bad]
    assert expect_bad.count(False) == 1
    got_bad = [bool(v) for v in secp.schnorr_verify_batch_aggregate(bad)]
    assert got_bad == expect_bad, "BENCH CORRECTNESS FAILURE: aggregate bisect mask != oracle"

    # warm both lanes on the timing shape, all-valid masks oracle-checked
    assert all(bool(v) for v in secp.schnorr_verify_batch_aggregate(items)), (
        "BENCH CORRECTNESS FAILURE: aggregate rejected a valid batch"
    )
    assert all(bool(v) for v in secp.schnorr_verify_batch(items))

    agg_best = ladder_best = float("inf")
    for _ in range(passes):
        t0 = time.perf_counter()
        out = secp.schnorr_verify_batch_aggregate(items)
        agg_best = min(agg_best, time.perf_counter() - t0)
        assert all(bool(v) for v in out)
        t0 = time.perf_counter()
        out = secp.schnorr_verify_batch(items)
        ladder_best = min(ladder_best, time.perf_counter() - t0)
        assert all(bool(v) for v in out)

    agg_vps = total / agg_best
    ladder_vps = total / ladder_best
    print(
        json.dumps(
            {
                "metric": "schnorr_aggregate_verify_throughput",
                "value": round(agg_vps, 1),
                "unit": UNIT,
                "aggregate_vps": round(agg_vps, 1),
                "ladder_vps": round(ladder_vps, 1),
                "speedup": round(agg_vps / ladder_vps, 3),
                "batch": total,
                "passes": passes,
                "mesh": mesh.active_size(),
                "observability": obs_fn(),
            }
        )
    )
    sys.stdout.flush()
    os._exit(0)


def _child_main() -> None:
    """Generate the batch, verify on device, print the JSON result line.

    Exits via os._exit so jax's atexit teardown can't block on a sick
    PJRT client after the result is already out.
    """
    import random

    import numpy as np

    from kaspa_tpu.utils import jax_setup

    jax_setup.setup()

    # span capture + metric registry ride the result line (success AND
    # failure): when the backend wedges, the tail shows exactly which spans
    # ever completed (host marshal? device dispatch?) and what compiled
    from kaspa_tpu.observability import snapshot as obs_snapshot
    from kaspa_tpu.observability import trace

    trace.set_capture(512)

    def _obs() -> dict:
        # the supervisor verdict rides every result line (success AND
        # failure): watchdog escalations + host-lane requeue counts are the
        # first evidence to read after a stall
        from kaspa_tpu.resilience import supervisor

        return {
            "metrics": obs_snapshot(),
            "spans": trace.drain(),
            "supervisor": supervisor.verdict(),
        }

    if not _child_probe(PROBE_TIMEOUT_S):
        print(json.dumps({"child_error": "probe_timeout", "observability": _obs()}))
        sys.stdout.flush()
        os._exit(3)

    if os.environ.get("KASPA_TPU_BENCH_MODE") == "dispatch":
        _child_dispatch_main(_obs)
        return  # unreachable (child exits)

    if os.environ.get("KASPA_TPU_BENCH_MODE") == "aggregate":
        _child_aggregate_main(_obs)
        return  # unreachable (child exits)

    # everything below is a device metric: it is measured on a TPU or not
    # at all (the dispatch/aggregate modes above are ratio/identity checks
    # that roundcheck runs on CPU)
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(json.dumps({"child_error": f"no_tpu: platform={platform}"}))
        sys.stdout.flush()
        os._exit(3)

    if os.environ.get("KASPA_TPU_BENCH_KERNEL", "schnorr") == "ecdsa":
        _child_ecdsa_main(_obs)
        return  # unreachable (child exits)

    from kaspa_tpu.crypto import eclib
    from kaspa_tpu.crypto.secp import schnorr_challenge
    from kaspa_tpu.ops.secp256k1.verify import schnorr_verify

    from kaspa_tpu.sim import sigbatch

    triples = sigbatch.schnorr_points(B)
    # spot-check the generator against the reference verifier
    for i in (0, 1, B // 2, B - 1):
        P, pub, msg, sig = triples[i]
        assert eclib.schnorr_verify(pub, msg, sig), "generator produced bad sig"

    expect = [True] * B
    rng = random.Random(7)
    sigs = [t[3] for t in triples]
    for i in range(0, B, 4):  # corrupt a quarter of the batch
        j = rng.randrange(64)
        sigs[i] = sigs[i][:j] + bytes([sigs[i][j] ^ (1 + rng.randrange(255))]) + sigs[i][j + 1 :]
        expect[i] = False

    # byte columns, as secp._Batch hands them over: the backend (pallas or
    # XLA) derives its own layout — the e2e path includes that marshalling
    px = [t[0][0].to_bytes(32, "big") for t in triples]
    # lifted pubkey (even y): negate odd-y points host-side like secp.py does
    py = [(t[0][1] if t[0][1] % 2 == 0 else eclib.P - t[0][1]).to_bytes(32, "big") for t in triples]
    rc = [s[:32] for s in sigs]
    s_ints = [int.from_bytes(s[32:], "big") % eclib.N for s in sigs]
    e_ints = [schnorr_challenge(s[:32], t[1], t[2]) for s, t in zip(sigs, triples)]
    # host-side encoding validity: r must be a canonical field element and
    # on-curve (lift_x); corrupted r bytes can make lanes invalid-by-encoding
    ok = np.ones(B, dtype=bool)
    for i in range(0, B, 4):
        r_int = int.from_bytes(sigs[i][:32], "big")
        if r_int >= eclib.P or eclib.lift_x(r_int) is None:
            ok[i] = False
        if int.from_bytes(sigs[i][32:], "big") >= eclib.N:
            ok[i] = False

    mask = np.asarray(schnorr_verify(px, py, rc, s_ints, e_ints, ok))  # compile + warmup
    assert mask.tolist() == expect, "BENCH CORRECTNESS FAILURE: mask != oracle"

    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        out = np.asarray(schnorr_verify(px, py, rc, s_ints, e_ints, ok))
        best = min(best, time.perf_counter() - t0)
    assert out.tolist() == expect

    from kaspa_tpu.ops import mesh

    value = B / best
    print(
        json.dumps(
            {
                "metric": METRIC,
                "value": round(value, 1),
                "unit": UNIT,
                "vs_baseline": round(value / BASELINE, 4),
                "batch": B,
                "mesh": mesh.active_size(),
                "observability": _obs(),
            }
        )
    )
    sys.stdout.flush()
    os._exit(0)


# ==========================================================================
# parent: jax-free orchestration — fresh subprocess per attempt
# ==========================================================================


def _run_attempt(timeout_s: float) -> tuple[dict | None, str, dict | None]:
    """The one fresh-subprocess measurement.
    Returns (result_json | None, note, observability | None) — the obs tail
    comes back even from a failed child so the error line can carry the
    last evidence of what the device did before it stopped."""
    env = dict(os.environ)
    env["KASPA_TPU_BENCH_CHILD"] = "1"
    # the headline measures one fixed kernel shape; warm-bucket splitting
    # would silently substitute smaller dispatches for it
    env.setdefault("KASPA_TPU_COLD_BUCKET_SPLIT", "0")
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        text=True,
        env=env,
    )
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        try:
            proc.communicate(timeout=10)
        except Exception:
            pass
        return None, f"attempt timeout after {timeout_s:.0f}s (killed)", None
    for line in reversed((out or "").strip().splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if obj.get("metric") == METRIC and obj.get("value", 0) > 0:
            return obj, "ok", obj.get("observability")
        if "child_error" in obj:
            return None, f"child: {obj['child_error']}", obj.get("observability")
    return None, f"child exited rc={proc.returncode} without a result line", None


def _utc_stamp(compact: bool = True) -> str:
    fmt = "%Y%m%dT%H%M%SZ" if compact else "%Y-%m-%dT%H:%M:%SZ"
    return time.strftime(fmt, time.gmtime())


def _run_json_child(env_extra: dict, timeout_s: float) -> tuple[dict | None, str]:
    """Fresh subprocess -> last JSON line on stdout (None on hang/garbage)."""
    env = dict(os.environ)
    env.update(env_extra)
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        text=True,
        env=env,
    )
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        try:
            proc.communicate(timeout=10)
        except Exception:
            pass
        return None, f"killed after {timeout_s:.0f}s"
    for line in reversed((out or "").strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line), f"rc={proc.returncode}"
            except json.JSONDecodeError:
                continue
    return None, f"rc={proc.returncode}, no JSON line"


def _session_probe(log: list) -> bool:
    """Session-start device probe: trivial jit in a fresh child, hard
    parent-side timeout.  Every step lands in ``log`` with a UTC stamp so a
    wedge leaves a trail instead of a silent death."""
    timeout_s = PROBE_TIMEOUT_S + 30  # child gets PROBE_TIMEOUT_S; +30 for interpreter spin-up
    log.append({"t": _utc_stamp(), "event": "session_probe_start", "timeout_s": timeout_s})
    obj, note = _run_json_child(
        {"KASPA_TPU_BENCH_CHILD": "1", "KASPA_TPU_BENCH_MODE": "probe"}, timeout_s
    )
    ok = bool(obj and obj.get("probe_ok"))
    log.append({"t": _utc_stamp(), "event": "session_probe_result", "ok": ok, "note": note, "child": obj})
    return ok


def _sweep(probe_log: list, devices: int) -> None:
    """ROADMAP item-1 sweep: kernel x batch-size x mesh-size grid, one
    fresh child per cell, best-per-(kernel, mesh) config into the sweep
    JSON.  Reuses the headline machinery: each cell still probes in-child
    and dies alone on a wedged backend; the parent just records the hole.
    """
    batches = [
        int(b) for b in os.environ.get("KASPA_TPU_BENCH_SWEEP_BATCHES", "1024,4096,16384").split(",") if b.strip()
    ]
    meshes = [1] + ([devices] if devices > 1 else [])
    deadline = time.monotonic() + TOTAL_BUDGET_S
    cells = []
    for kernel in ("schnorr", "ecdsa"):
        for mesh_n in meshes:
            for b in batches:
                cell = {"kernel": kernel, "batch": b, "mesh": mesh_n}
                remaining = deadline - time.monotonic()
                if remaining <= 30:
                    cell.update(value=0.0, note="sweep budget exhausted")
                    cells.append(cell)
                    continue
                obj, note = _run_json_child(
                    {
                        "KASPA_TPU_BENCH_CHILD": "1",
                        "KASPA_TPU_BENCH_B": str(b),
                        "KASPA_TPU_BENCH_KERNEL": kernel,
                        "KASPA_TPU_MESH": str(mesh_n),
                        # cells measure this exact bucket shape: no
                        # warm-bucket substitution
                        "KASPA_TPU_COLD_BUCKET_SPLIT": "0",
                    },
                    min(ATTEMPT_TIMEOUT_S, remaining),
                )
                if obj is not None and obj.get("value", 0) > 0:
                    cell.update(value=obj["value"], unit=obj.get("unit", UNIT), note="ok")
                else:
                    err = (obj or {}).get("child_error", note)
                    cell.update(value=0.0, note=f"failed: {err}")
                cells.append(cell)
    # coalesce-depth column: dispatch-layer throughput (cross-block
    # coalescing vs per-block dispatch over the same chunked jobs), one
    # dispatch-mode child per depth — measures the layer the kernel cells
    # can't see
    depths = [
        int(d) for d in os.environ.get("KASPA_TPU_BENCH_SWEEP_DEPTHS", "4,16").split(",") if d.strip()
    ]
    chunk = int(os.environ.get("KASPA_TPU_BENCH_CHUNK", "16"))
    for kernel in ("schnorr", "ecdsa"):
        for mesh_n in meshes:
            for depth in depths:
                target = depth * chunk
                cell = {"kernel": kernel, "batch": target, "mesh": mesh_n, "coalesce_depth": depth}
                remaining = deadline - time.monotonic()
                if remaining <= 30:
                    cell.update(value=0.0, note="sweep budget exhausted")
                    cells.append(cell)
                    continue
                obj, note = _run_json_child(
                    {
                        "KASPA_TPU_BENCH_CHILD": "1",
                        "KASPA_TPU_BENCH_MODE": "dispatch",
                        "KASPA_TPU_BENCH_KERNEL": kernel,
                        "KASPA_TPU_BENCH_DISPATCH_B": str(target * 2),
                        "KASPA_TPU_BENCH_CHUNK": str(chunk),
                        "KASPA_TPU_COALESCE": str(target),
                        "KASPA_TPU_MESH": str(mesh_n),
                    },
                    min(ATTEMPT_TIMEOUT_S, remaining),
                )
                if obj is not None and obj.get("coalesced_vps", 0) > 0:
                    cell.update(
                        value=obj["coalesced_vps"],
                        speedup=obj.get("speedup"),
                        legacy_vps=obj.get("legacy_vps"),
                        unit=obj.get("unit", UNIT),
                        note="ok",
                    )
                else:
                    err = (obj or {}).get("child_error", note)
                    cell.update(value=0.0, note=f"failed: {err}")
                cells.append(cell)
    # aggregate-RLC column: combined multi-scalar check vs per-signature
    # ladders at each batch size; the smallest batch where the aggregate
    # lane wins becomes the recorded crossover that --verify-mode auto
    # reads back from this file (ops/dispatch._aggregate_crossover)
    agg_batches = [
        int(b) for b in os.environ.get("KASPA_TPU_BENCH_AGG_BATCHES", "64,256,1024").split(",") if b.strip()
    ]
    agg_cells: list = []
    for b in agg_batches:
        cell = {"lane": "aggregate", "kernel": "schnorr", "batch": b, "mesh": 1}
        remaining = deadline - time.monotonic()
        if remaining <= 30:
            cell.update(value=0.0, note="sweep budget exhausted")
            agg_cells.append(cell)
            continue
        obj, note = _run_json_child(
            {
                "KASPA_TPU_BENCH_CHILD": "1",
                "KASPA_TPU_BENCH_MODE": "aggregate",
                "KASPA_TPU_BENCH_AGG_B": str(b),
                # cells measure this exact bucket shape, like the kernel grid
                "KASPA_TPU_COLD_BUCKET_SPLIT": "0",
            },
            min(ATTEMPT_TIMEOUT_S, remaining),
        )
        if obj is not None and obj.get("aggregate_vps", 0) > 0:
            cell.update(
                value=obj["aggregate_vps"],
                ladder_vps=obj.get("ladder_vps"),
                aggregate_speedup=obj.get("speedup"),
                unit=obj.get("unit", UNIT),
                note="ok",
            )
        else:
            err = (obj or {}).get("child_error", note)
            cell.update(value=0.0, note=f"failed: {err}")
        agg_cells.append(cell)
    cells.extend(agg_cells)
    agg_crossover = None
    for c in sorted(agg_cells, key=lambda c: c["batch"]):
        if (c.get("aggregate_speedup") or 0) >= 1.0:
            agg_crossover = c["batch"]
            break
    best: dict = {}
    for c in cells:
        if c.get("lane") == "aggregate":
            key = f"{c['kernel']}/mesh{c['mesh']}/aggregate"
            if c["value"] > best.get(key, {}).get("value", 0.0):
                best[key] = {
                    "batch": c["batch"], "value": c["value"], "speedup": c.get("aggregate_speedup"),
                }
            continue
        if "coalesce_depth" in c:
            key = f"{c['kernel']}/mesh{c['mesh']}/coalesce"
            if c["value"] > best.get(key, {}).get("value", 0.0):
                best[key] = {"batch": c["batch"], "depth": c["coalesce_depth"], "value": c["value"]}
            continue
        key = f"{c['kernel']}/mesh{c['mesh']}"
        if c["value"] > best.get(key, {}).get("value", 0.0):
            best[key] = {"batch": c["batch"], "value": c["value"]}
    out_path = os.environ.get("KASPA_TPU_BENCH_SWEEP_PATH", "BENCH_SWEEP.json")
    doc = {
        "created": _utc_stamp(compact=False),
        "devices": devices,
        "batches": batches,
        "meshes": meshes,
        "cells": cells,
        "best": best,
        # --verify-mode auto reads crossover_batch from here; cells above
        # carry the full aggregate_speedup column
        "aggregate": {"crossover_batch": agg_crossover, "batches": agg_batches},
        "probe_log": probe_log,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    print(json.dumps({"sweep": out_path, "devices": devices, "best": best}))


def _fail(error: str, **extra) -> None:
    """No TPU measurement: say why, print no value, exit non-zero."""
    print(json.dumps({"metric": METRIC, "unit": UNIT, "error": error, **extra}))
    sys.exit(1)


def main() -> None:
    if os.environ.get("KASPA_TPU_BENCH_CHILD"):
        if os.environ.get("KASPA_TPU_BENCH_MODE") == "probe":
            _child_probe_main()
        else:
            _child_main()
        return  # unreachable (child exits)

    # session-start probe: a dead backend is diagnosed in ~2 min instead of
    # burning the whole attempt timeout first
    probe_log: list = []
    probe_ok = _session_probe(probe_log)
    if "--probe" in sys.argv[1:]:
        print(json.dumps({"probe_ok": probe_ok, "log": probe_log}))
        sys.exit(0 if probe_ok else 1)
    if not probe_ok:
        _fail("device probe did not answer", probe_log=probe_log)
    child = probe_log[-1].get("child") or {}
    if child.get("device_platform") != "tpu":
        _fail(f"no TPU: platform={child.get('device_platform')!r} (this benchmark has no CPU lane)")

    if "--sweep" in sys.argv[1:]:
        _sweep(probe_log, int(child.get("devices", 0) or 0))
        return

    result, note, obs = _run_attempt(ATTEMPT_TIMEOUT_S)
    if result is None:
        _fail(f"no measurement: {note}", observability=obs)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
