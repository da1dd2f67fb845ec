#!/usr/bin/env python
"""Chip smoke: the block-validation path on a TPU, through the entry points
a user calls, refusing to pass through anything that hides the device.

    python chip_smoke.py             # one chip: kernels, replay, daemon
    python chip_smoke.py --chips 4   # the mesh path and what it is compared with

Every earlier line is one JSON object per phase; the last line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the exit code is 0 only then.  A chip belongs to one process at a
time, so this parent never initialises a JAX backend: each phase runs in a
child, one after another, and the ``daemon`` phase's child is an RPC
client that starts ``python -m kaspa_tpu.node`` as the one process holding
the chip.  Children share the persistent compile cache
(``kaspa_tpu.utils.jax_setup``).  A child fails unless
``jax.devices()[0].platform == "tpu"``; nothing here sets or defaults
``JAX_PLATFORMS`` and no option relaxes that check.

Each phase proves the device did the work from the program's own counters
and spans (``DeviceLedger``): device-dispatch spans of the expected kernel
formulation present, every submitted job answered by the device lane or
the signature cache, nothing on the host degraded lane, no watchdog
timeout, no breaker trip.  The safety lane itself is untouched — the
smoke only refuses to pass *through* it.

The phases are plain functions of their sizes: ``main`` calls them at full
size; ``tests/test_chip_smoke.py`` calls the same functions at tiny sizes
on the CPU backend.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# sizes of the full run.  The replay is the sim's mainnet-Crescendo setting
# (--bps 10 --delay 0.3 --miners 4 derive k=124, 16 parents, mergeset 248)
# with simpa's --tpb 200; only the block count is cut.
TOP_BUCKET, BOTTOM_BUCKET = 1024, 8  # ends of kernel_catalog.VERIFY_BUCKETS
MUHASH_SIZES = (64, 1024)  # kernel_catalog.MUHASH_BUCKETS
REPLAY = {"bps": 10, "delay": 0.3, "miners": 4, "tpb": 200, "coalesce": 1024}
REPLAY_BLOCKS = 96
MESH_REPLAY_BLOCKS = 40
DAEMON_SPENDS = 36
PHASE_TIMEOUT_S = 1100  # the whole run has 1200 s


class SmokeFailure(AssertionError):
    """A phase's own check failed; carries the evidence printed with it."""

    def __init__(self, message: str, evidence: dict | None = None):
        super().__init__(message)
        self.evidence = evidence or {}


def _emit(obj: dict) -> None:
    print(json.dumps(obj, sort_keys=True), flush=True)


# ==========================================================================
# the device-work ledger: what the program's own counters and spans say
# ==========================================================================

_FAMILIES = (
    "secp_device_dispatches", "secp_device_buckets", "muhash_device_dispatches",
    "txscript_batch_jobs", "secp_watchdog_timeouts", "breaker_trips", "mesh_dispatches",
)
_SCALARS = (
    "secp_device_jobs", "secp_degraded_jobs", "secp_degraded_dispatches",
    "txscript_batch_sigcache_skips",
)


def _delta(after: dict, before: dict) -> dict:
    """Counter movement between two ``counters`` snapshots, zeros dropped."""
    out = {}
    for name in _SCALARS:
        out[name] = after.get(name, 0) - before.get(name, 0)
    for name in _FAMILIES:
        a, b = after.get(name, {}), before.get(name, {})
        out[name] = {k: v - b.get(k, 0) for k, v in a.items() if v - b.get(k, 0)}
    return out


def device_work_failures(delta: dict, dispatch_kernels, expect_kernels, submitted: int | None) -> list:
    """Why the counters do NOT prove that the device did the work."""
    bad = []
    for k in expect_kernels:
        if not delta["secp_device_dispatches"].get(k):
            bad.append(f"no device dispatch counted for kernel {k}")
        if dispatch_kernels is not None and k not in dispatch_kernels:
            bad.append(f"no secp.device_dispatch span with kernel={k}")
    if delta["secp_degraded_dispatches"] or delta["secp_degraded_jobs"]:
        bad.append(
            f"{delta['secp_degraded_jobs']} jobs in {delta['secp_degraded_dispatches']} "
            "batches ran on the host degraded lane"
        )
    if delta["secp_watchdog_timeouts"]:
        bad.append(f"watchdog timeouts: {delta['secp_watchdog_timeouts']}")
    if delta["breaker_trips"]:
        bad.append(f"breaker trips: {delta['breaker_trips']}")
    if submitted is not None and delta["secp_device_jobs"] != submitted:
        bad.append(f"{submitted} jobs submitted, {delta['secp_device_jobs']} answered by the device lane")
    return bad


# JAX's own compile-cache events, tallied process-wide from the first
# ledger on (jax.monitoring has no public unregister, so: one listener pair,
# registered once, and each ledger reads the tally before and after)
_JAX_EVENTS = {"hits": 0, "misses": 0, "backend_compiles": []}


def _tally_jax_events() -> None:
    if "listening" in _JAX_EVENTS:
        return
    import jax.monitoring as monitoring

    def on_event(name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            _JAX_EVENTS["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            _JAX_EVENTS["misses"] += 1

    def on_duration(name, secs, **_kw):
        # a cache hit never reaches the backend compiler
        if name == "/jax/core/compile/backend_compile_duration":
            _JAX_EVENTS["backend_compiles"].append(secs)

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    _JAX_EVENTS["listening"] = True


class DeviceLedger:
    """In-process ledger around one phase: registry counters before/after,
    the captured spans, the breaker's state, and the compile-cache tally
    (hits, misses, and every backend compile of a second or more)."""

    def __init__(self):
        from kaspa_tpu.observability import trace
        from kaspa_tpu.observability.core import REGISTRY

        self._registry, self._trace = REGISTRY, trace
        _tally_jax_events()
        self._events0 = (_JAX_EVENTS["hits"], _JAX_EVENTS["misses"], len(_JAX_EVENTS["backend_compiles"]))
        trace.set_capture(1 << 18)
        trace.drain()
        self._before = REGISTRY.snapshot()["counters"]

    def so_far(self) -> dict:
        """Counter movement since the ledger was opened."""
        return _delta(self._registry.snapshot()["counters"], self._before)

    def close(self) -> dict:
        """Freeze the ledger; returns the evidence a phase prints."""
        from kaspa_tpu.resilience.breaker import device_breaker

        snap = self._registry.snapshot()
        self.delta = _delta(snap["counters"], self._before)
        self.dispatch_kernels: dict = {}
        setup = []
        for s in self._trace.drain():
            attrs = s.get("attrs") or {}
            if s["name"] == "secp.device_dispatch" and "error" not in attrs:
                k = attrs.get("kernel")
                self.dispatch_kernels[k] = self.dispatch_kernels.get(k, 0) + 1
            elif s["name"] == "secp.jit_compile":
                setup.append(
                    {"kernel": attrs.get("kernel"), "bucket": attrs.get("bucket"), "seconds": round(s["dur_us"] / 1e6, 2)}
                )
        self.breaker = device_breaker().state
        hits0, misses0, compiles0 = self._events0
        compiles = _JAX_EVENTS["backend_compiles"][compiles0:]
        return {
            "counters": self.delta,
            "device_dispatch_spans": self.dispatch_kernels,
            "first_dispatch_setup": setup,
            "jit_compiles": snap.get("secp", {}).get("jit_compiles", {}),
            "breaker": self.breaker,
            "compile_cache": {
                "hits": _JAX_EVENTS["hits"] - hits0,
                "misses": _JAX_EVENTS["misses"] - misses0,
                "backend_compile_seconds": round(sum(compiles), 1),
                "backend_compiles_over_1s": [round(c, 1) for c in compiles if c >= 1.0],
            },
        }

    def failures(self, expect_kernels, submitted: int | None) -> list:
        bad = device_work_failures(self.delta, self.dispatch_kernels, expect_kernels, submitted)
        if self.breaker != "closed":
            bad.append(f"device breaker is {self.breaker} at exit")
        return bad


def _require(bad: list, evidence: dict) -> None:
    if bad:
        raise SmokeFailure("; ".join(bad), evidence)


def _max_bucket(family: dict) -> int:
    return max((int(b) for b in family), default=0)


# ==========================================================================
# phase: kernels — the public batch APIs at the ends of the bucket ladder
# ==========================================================================


def _verify_cases(top: int, bottom: int, seed: int):
    """(kind, size, spoiled items, expected mask, invalid class per lane)."""
    from kaspa_tpu.sim import sigbatch

    cases = []
    for kind, gen, kseed in (("schnorr", sigbatch.schnorr_items, seed), ("ecdsa", sigbatch.ecdsa_items, seed + 1)):
        items = gen(top + bottom, kseed)
        # the top bucket first: crypto/secp.py splits a cold bucket into
        # sub-batches of the largest warm one, so bottom-first would never
        # dispatch the top shape
        for size, chunk in ((top, items[:top]), (bottom, items[top:])):
            spoiled, expect, classes = sigbatch.spoil(kind, chunk, every=4, seed=seed + size)
            cases.append((kind, size, spoiled, expect, classes))
    return cases


def _oracle_lanes(classes: list, want: int) -> list:
    """Lane indices for the eclib cross-check: every invalid class at
    least once, then the leading lanes (every fourth one spoiled) up to
    ``want``."""
    seen, picked = set(), []
    for i, c in enumerate(classes):
        if c is not None and c not in seen:
            seen.add(c)
            picked.append(i)
    for i in range(len(classes)):
        if len(picked) >= want:
            break
        if i not in picked:
            picked.append(i)
    return sorted(picked)


def phase_kernels(
    top: int = TOP_BUCKET,
    bottom: int = BOTTOM_BUCKET,
    muhash_sizes=MUHASH_SIZES,
    oracle_lanes: int = 64,
    expect_kernels=("schnorr_pallas", "ecdsa_pallas"),
    seed: int = 2026,
) -> dict:
    import random

    from kaspa_tpu.crypto import eclib, muhash, secp
    from kaspa_tpu.sim import sigbatch

    batch_fn = {"schnorr": secp.schnorr_verify_batch, "ecdsa": secp.ecdsa_verify_batch}
    host_fn = {"schnorr": eclib.schnorr_verify, "ecdsa": eclib.ecdsa_verify}
    ledger = DeviceLedger()
    bad, submitted, rows = [], 0, []
    for kind, size, items, expect, classes in _verify_cases(top, bottom, seed):
        t0 = time.perf_counter()
        mask = [bool(x) for x in batch_fn[kind](items)]
        secs = round(time.perf_counter() - t0, 2)
        submitted += len(items)
        if mask != expect:
            wrong = [i for i, (m, e) in enumerate(zip(mask, expect)) if m != e][:8]
            bad.append(f"{kind}/{size}: device mask differs from construction at lanes {wrong}")
        lanes = _oracle_lanes(classes, min(oracle_lanes, size))
        oracle_bad = [i for i in lanes if bool(host_fn[kind](*items[i])) != mask[i]]
        if oracle_bad:
            bad.append(f"{kind}/{size}: device mask differs from eclib at lanes {oracle_bad[:8]}")
        rows.append(
            {
                "kind": kind, "jobs": size, "valid": sum(mask), "first_call_seconds": secs,
                "eclib_lanes": len(lanes),
                "invalid_classes": sorted({c for c in classes if c}),
            }
        )
        missing = set(sigbatch.INVALID_CLASSES) - {c for c in classes if c}
        if size >= 4 * len(sigbatch.INVALID_CLASSES) and missing:
            bad.append(f"{kind}/{size}: invalid classes never generated: {sorted(missing)}")

    rng = random.Random(seed)
    products = []
    for n in muhash_sizes:
        preimages = [rng.randbytes(40) for _ in range(n)]
        t0 = time.perf_counter()
        got = muhash.bulk_element_product(preimages)
        secs = round(time.perf_counter() - t0, 2)
        host = 1
        for e in muhash.elements_from_preimages(preimages):
            host = host * e % muhash.PRIME
        if got != host:
            bad.append(f"muhash/{n}: device product differs from the host big-int product")
        products.append({"elements": n, "first_call_seconds": secs, "matches_host": got == host})

    evidence = {"verify": rows, "muhash": products, **ledger.close()}
    bad += ledger.failures(expect_kernels, submitted)
    for n in muhash_sizes:
        if not ledger.delta["muhash_device_dispatches"].get(str(n)):
            bad.append(f"no muhash device dispatch at bucket {n}")
    if _max_bucket(ledger.delta["secp_device_buckets"]) < top:
        bad.append(f"verify never dispatched at bucket {top}: {ledger.delta['secp_device_buckets']}")
    _require(bad, evidence)
    return evidence


# ==========================================================================
# phase: replay — the sim's own path against the in-order reference
# ==========================================================================


def _fingerprint(consensus) -> dict:
    sink = consensus.sink()
    return {"sink": sink.hex(), "utxo_commitment": consensus.multisets[sink].finalize().hex()}


def _pretrace(kernel: str, buckets) -> list:
    """Warm the served shapes ahead of the window, as a daemon restart does
    from the warm manifest; the seconds are set-up time, not replay time."""
    from kaspa_tpu.crypto import secp

    rows = []
    for b in buckets:
        t0 = time.perf_counter()
        status = secp.pretrace_bucket(kernel, b)
        rows.append({"kernel": kernel, "bucket": b, "status": status, "seconds": round(time.perf_counter() - t0, 2)})
        if status.startswith("error"):
            raise SmokeFailure(f"pretrace of {kernel}/{b} failed: {status}", {"pretrace": rows})
    return rows


def phase_replay(
    blocks: int = REPLAY_BLOCKS,
    tpb: int = REPLAY["tpb"],
    coalesce: int = REPLAY["coalesce"],
    bps: int = REPLAY["bps"],
    delay: float = REPLAY["delay"],
    miners: int = REPLAY["miners"],
    pretrace_buckets=(8, 16, 32, 64, 128, 256, 512, 1024),
    min_super_bucket: int | None = None,
    need_muhash_device: bool = True,
    expect_kernels=("schnorr_pallas",),
    seed: int = 42,
) -> dict:
    """``simulate`` -> ``replay_pipelined`` (what ``python -m kaspa_tpu.sim
    --pipeline --coalesce N`` runs) compared with the in-order,
    non-speculative ``replay`` of the same DAG and the simulator's own sink.

    ``min_super_bucket`` None asks for what shows that coalescing merged
    blocks: one pipelined super-batch in a bucket above the widest block's
    own.  (The sim's generator thins blocks out under KIP-9 storage mass —
    about 45 spends at most at these parameters — and the chip answers in
    milliseconds, so super-batches stay near 100-250 jobs; bucket 1024 is
    the ``kernels`` phase's job.)"""
    from kaspa_tpu.crypto.secp import _bucket
    from kaspa_tpu.ops import dispatch as coalescing
    from kaspa_tpu.sim.simulator import SimConfig, replay, replay_pipelined, simulate

    cfg = SimConfig(bps=bps, delay=delay, num_miners=miners, num_blocks=blocks, txs_per_block=tpb, seed=seed)
    ledger = DeviceLedger()
    pretrace = _pretrace("schnorr_verify", pretrace_buckets)
    target = coalescing.configure(coalesce)
    try:
        res = simulate(cfg)
        built = ledger.so_far()
        piped_seconds, piped = replay_pipelined(res)
        after_piped = ledger.so_far()
        coalescing.configure(0)
        ref_seconds, ref = replay(res)
    finally:
        coalescing.shutdown()
    fp_piped, fp_ref = _fingerprint(piped), _fingerprint(ref)
    p = res.params
    widest = max(len(b.transactions) - 1 for b in res.blocks)
    super_buckets = {
        b: n - built["secp_device_buckets"].get(b, 0)
        for b, n in after_piped["secp_device_buckets"].items()
        if n - built["secp_device_buckets"].get(b, 0)
    }
    evidence = {
        "deployment": {
            "bps": bps, "delay": delay, "miners": miners, "tpb_cap": tpb, "coalesce": target,
            "ghostdag_k": p.ghostdag_k, "max_block_parents": p.max_block_parents,
            "mergeset_size_limit": p.mergeset_size_limit, "coinbase_maturity": p.coinbase_maturity,
        },
        "cut": {"blocks": blocks, "of": "the block count only"},
        "txs": res.total_txs,
        "widest_block_txs": widest,
        "build_seconds": round(res.build_seconds, 1),
        "pipelined_seconds": round(piped_seconds, 2),
        "reference_seconds": round(ref_seconds, 2),
        "pipelined": fp_piped,
        "reference": fp_ref,
        "sim_sink": res.sink.hex(),
        "pipelined_super_batches_by_bucket": super_buckets,
        "pretrace": pretrace,
        **ledger.close(),
    }
    bad = []
    if fp_piped != fp_ref or fp_ref["sink"] != res.sink.hex():
        bad.append("pipelined replay, in-order reference and simulator disagree on sink/utxo_commitment")
    d = ledger.delta
    queued = sum(d["txscript_batch_jobs"].values())
    if queued == 0:
        bad.append("no signature job was queued: the DAG carried no spend")
    bad += ledger.failures(expect_kernels, queued)
    if min_super_bucket is None:
        min_super_bucket = 2 * _bucket(widest)
    if _max_bucket(super_buckets) < min_super_bucket:
        bad.append(
            f"largest pipelined verify super-batch reached bucket {_max_bucket(super_buckets)}, "
            f"need {min_super_bucket}: {super_buckets}"
        )
    if need_muhash_device and not d["muhash_device_dispatches"]:
        bad.append("no muhash device dispatch in the replay")
    _require(bad, evidence)
    return evidence


# ==========================================================================
# phase: daemon — the node as the one process that holds the chip
# ==========================================================================


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@contextlib.contextmanager
def _running_daemon(appdir: str, bps: int, extra=()):
    """``python -m kaspa_tpu.node`` the documented way; stopped on exit."""
    from kaspa_tpu.node.daemon import rpc_call

    addr = f"127.0.0.1:{_free_port()}"
    log = open(os.path.join(appdir, "daemon.log"), "w")
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.Popen(
        [sys.executable, "-m", "kaspa_tpu.node", "--appdir", appdir, "--rpclisten", addr,
         "--listen", f"127.0.0.1:{_free_port()}", "--bps", str(bps), *extra],
        stdout=log, stderr=subprocess.STDOUT, env=env, cwd=REPO,
    )
    try:
        deadline = time.monotonic() + 180
        while True:
            if proc.poll() is not None:
                raise SmokeFailure(f"daemon exited rc={proc.returncode} before serving", {"log": _tail(log.name)})
            try:
                rpc_call(addr, "getServerInfo", timeout=5.0)
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise SmokeFailure("daemon did not serve within 180 s", {"log": _tail(log.name)})
                time.sleep(0.5)
        yield addr, log.name
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)
        log.close()


def _tail(path: str, n: int = 2000) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()[-n:]
    except OSError:
        return ""


def phase_daemon(
    spends: int = DAEMON_SPENDS,
    bps: int = 10,
    expect_kernels=("schnorr_pallas",),
    seed: int = 7,
) -> dict:
    """Drive the daemon over its RPC: mine past coinbase maturity, submit
    signed spends, mine them in, query.  This process is only an RPC
    client (pure-Python signing); it must never initialise a JAX backend,
    because the daemon child needs the chip."""
    from kaspa_tpu.node.daemon import rpc_call
    from kaspa_tpu.tools.rothschild import Rothschild
    from kaspa_tpu.wallet import Account
    from kaspa_tpu.wallet.__main__ import _RemoteIndex, tx_to_wire

    account = Account.from_seed(seed.to_bytes(32, "big"), prefix="kaspasim")
    pay = account.addresses()[0]

    with tempfile.TemporaryDirectory(prefix="chip_smoke_daemon_") as appdir, _running_daemon(appdir, bps) as (addr, log):

        stale_templates = 0

        def mine(n: int) -> None:
            nonlocal stale_templates
            mined = 0
            while mined < n:
                t = rpc_call(addr, "getBlockTemplate", {"payAddress": pay}, timeout=600.0)
                try:
                    r = rpc_call(addr, "submitBlockByTemplateHash", {"hash": t["block_hash"]}, timeout=600.0)
                except RuntimeError as e:
                    # a template lives one second (mempool TemplateCache): a
                    # daemon busy warming kernels can answer later than that,
                    # and a miner then simply asks again
                    stale_templates += 1
                    if "template not cached" not in str(e) or stale_templates > 50:
                        raise
                    continue
                if r["status"] not in ("utxo_valid", "utxo_pending"):
                    raise SmokeFailure(f"mined block rejected: {r}", {"log": _tail(log)})
                mined += 1

        info = rpc_call(addr, "getServerInfo")
        maturity = info.get("coinbase_maturity", 200)
        before = rpc_call(addr, "getMetrics")["observability"]["counters"]
        t0 = time.perf_counter()
        mine(spends + maturity + 2)
        mined_seconds = time.perf_counter() - t0

        daa = rpc_call(addr, "getServerInfo")["virtual_daa_score"]
        spam = Rothschild(account)
        spam.seed_utxos(
            (op, entry) for op, entry, _key in account.spendable_utxos(_RemoteIndex(addr, "kaspasim"), daa, maturity)
        )
        txids, t0 = [], time.perf_counter()
        for _ in range(spends):
            tx = spam._build_self_spend()
            if tx is None:
                raise SmokeFailure(f"only {len(txids)} mature outputs to spend, wanted {spends}", {})
            # the first submit may pay a kernel compile inside the daemon
            txids.append(rpc_call(addr, "submitTransaction", {"tx": tx_to_wire(tx)}, timeout=900.0))
        submit_seconds = time.perf_counter() - t0
        pooled = len(rpc_call(addr, "getMempoolEntries"))
        mine(3)  # one block takes them, its descendants merge it into the UTXO state
        left = len(rpc_call(addr, "getMempoolEntries"))

        dag = rpc_call(addr, "getBlockDagInfo")
        utxos = rpc_call(addr, "getUtxosByAddresses", {"addresses": [pay]})
        confirmed = {u["outpoint"]["transaction_id"] for u in utxos} & set(txids)
        metrics = rpc_call(addr, "getMetrics")
        delta = _delta(metrics["observability"]["counters"], before)
        resilience = metrics["observability"].get("resilience", {}).get("device_verify", {})

    evidence = {
        "blocks": dag["block_count"],
        "spends_submitted": len(txids),
        "spends_pooled": pooled,
        "spends_left_in_pool": left,
        "spends_confirmed": len(confirmed),
        "paid_address_utxos": len(utxos),
        "stale_templates_retried": stale_templates,
        "mine_seconds": round(mined_seconds, 1),
        "submit_seconds": round(submit_seconds, 1),
        "device": metrics.get("device"),
        "counters": delta,
        "jit_compiles": metrics["observability"].get("secp", {}).get("jit_compiles", {}),
        "breaker": resilience.get("state"),
    }
    bad = []
    if pooled != spends or left != 0 or len(confirmed) != spends:
        bad.append(f"{spends} spends: {pooled} accepted, {left} left in the pool, {len(confirmed)} confirmed")
    # the daemon's spans stay in the daemon; its counters are the evidence
    bad += device_work_failures(delta, None, expect_kernels, sum(delta["txscript_batch_jobs"].values()))
    if delta["secp_device_jobs"] < spends:
        bad.append(f"{delta['secp_device_jobs']} device jobs for {spends} submitted spends")
    if resilience.get("state") != "closed":
        bad.append(f"daemon's device breaker is {resilience.get('state')}")
    _require(bad, evidence)
    return evidence


# ==========================================================================
# phase: mesh (--chips N) — sharded dispatch against mesh 1, one process
# ==========================================================================


def phase_mesh(
    chips: int = 4,
    top: int = TOP_BUCKET,
    muhash_elements: int = 1024,
    blocks: int = MESH_REPLAY_BLOCKS,
    tpb: int = REPLAY["tpb"],
    coalesce: int = REPLAY["coalesce"],
    mesh1_kernels=("schnorr_pallas", "ecdsa_pallas"),
    seed: int = 2026,
) -> dict:
    """The ``kernels`` batches at the top bucket and a shortened ``replay``
    under ``mesh.configure(chips)``; masks and fingerprints must equal the
    mesh-1 run of the same inputs in this same process.  Mesh > 1 rides the
    shard_map-wrapped XLA ladder, not the fused Pallas ladder of mesh 1."""
    import random

    import jax

    from kaspa_tpu.crypto import muhash, secp
    from kaspa_tpu.ops import dispatch as coalescing
    from kaspa_tpu.ops import mesh
    from kaspa_tpu.sim.simulator import SimConfig, replay_pipelined, simulate

    batch_fn = {"schnorr": secp.schnorr_verify_batch, "ecdsa": secp.ecdsa_verify_batch}
    cases = [c for c in _verify_cases(top, BOTTOM_BUCKET, seed) if c[1] == top]
    rng = random.Random(seed)
    preimages = [rng.randbytes(40) for _ in range(muhash_elements)]
    host_product = 1
    for e in muhash.elements_from_preimages(preimages):
        host_product = host_product * e % muhash.PRIME
    cfg = SimConfig(
        bps=REPLAY["bps"], delay=REPLAY["delay"], num_miners=REPLAY["miners"],
        num_blocks=blocks, txs_per_block=tpb, seed=42,
    )

    def run(n_mesh: int, res=None):
        """Top-bucket batches + pipelined replay under one mesh width; the
        DAG is simulated once (at mesh 1) and replayed under both."""
        mesh.configure(n_mesh)
        ledger = DeviceLedger()
        masks = {kind: [bool(x) for x in batch_fn[kind](items)] for kind, _s, items, _e, _c in cases}
        product = muhash.bulk_element_product(preimages) if n_mesh > 1 else None
        coalescing.configure(coalesce)
        try:
            res = res or simulate(cfg)
            _secs, piped = replay_pipelined(res)
        finally:
            coalescing.shutdown()
        fp = dict(_fingerprint(piped), sim_sink=res.sink.hex(), txs=res.total_txs)
        report = ledger.close()
        submitted = sum(len(c[2]) for c in cases) + sum(ledger.delta["txscript_batch_jobs"].values())
        return {"masks": masks, "product": product, "fp": fp, "ledger": ledger, "report": report,
                "submitted": submitted, "res": res}

    try:
        one = run(1)
        many = run(chips, one["res"])
        # where the shards physically sit: the committed output of one more
        # dispatch of the very entry (and shape) the top-bucket batches used
        probe = mesh._verify_entry("schnorr", chips)(*_all_invalid_batch(top))
        devices_holding_shards = sorted(str(d) for d in probe.sharding.device_set)
        shapes_compiled = {
            kind: int(mesh._verify_entry(kind, chips)._cache_size()) for kind in ("schnorr", "ecdsa")
        }
    finally:
        mesh.configure(1)
    m1, mN, fp1, fpN, ledN = one["masks"], many["masks"], one["fp"], many["fp"], many["ledger"]

    from kaspa_tpu.observability.core import REGISTRY

    occupancy = REGISTRY.snapshot()["histograms"]["mesh_shard_occupancy_pct"]["count"]
    evidence = {
        "chips": chips,
        "visible_devices": len(jax.devices()),
        "formulation": {
            "mesh1": "fused Pallas ladder (ladder_pallas._build_call)",
            f"mesh{chips}": "shard_map-wrapped XLA ladder (verify.schnorr_verify_kernel / ecdsa_verify_kernel)",
        },
        "mesh1": {"fingerprint": fp1, **one["report"]},
        f"mesh{chips}": {"fingerprint": fpN, **many["report"]},
        "xla_ladder_shapes_compiled": shapes_compiled,
        "masks_equal": m1 == mN,
        "expected_masks_equal": all(mN[kind] == expect for kind, _s, _i, expect, _c in cases),
        "muhash_matches_host": many["product"] == host_product,
        "shard_occupancy_observations": occupancy,
        "devices_holding_shards": devices_holding_shards,
    }
    bad = []
    if m1 != mN or not evidence["expected_masks_equal"]:
        bad.append(f"mesh-{chips} masks differ from mesh-1 or from construction")
    if fp1 != fpN or fpN["sink"] != fpN["sim_sink"]:
        bad.append(f"mesh-{chips} replay fingerprints differ from mesh-1: {fp1} vs {fpN}")
    if many["product"] != host_product:
        bad.append("sharded muhash product differs from the host big-int product")
    bad += [f"mesh1: {b}" for b in one["ledger"].failures(mesh1_kernels, one["submitted"])]
    bad += [f"mesh{chips}: {b}" for b in ledN.failures(("schnorr_mesh", "ecdsa_mesh"), many["submitted"])]
    for k in ("schnorr", "ecdsa", "muhash"):
        if not ledN.delta["mesh_dispatches"].get(k):
            bad.append(f"mesh_dispatches{{{k}}} did not move under mesh {chips}")
    if len(devices_holding_shards) != chips:
        bad.append(f"shards sit on {len(devices_holding_shards)} devices, not {chips}: {devices_holding_shards}")
    if occupancy < chips:
        bad.append(f"mesh_shard_occupancy_pct has {occupancy} observations")
    _require(bad, evidence)
    return evidence


def _all_invalid_batch(b: int):
    """Arguments of a verify entry: ``b`` zeroed lanes with valid_in False."""
    import numpy as np

    z = lambda w: np.zeros((b, w), np.int32)  # noqa: E731
    return z(16), z(16), z(16), z(64), z(64), np.zeros(b, dtype=bool)


# ==========================================================================
# children and the parent
# ==========================================================================

PHASES = {"kernels": phase_kernels, "replay": phase_replay, "daemon": phase_daemon, "mesh": phase_mesh}


def _engines() -> dict:
    """Which storage and host-crypto engines are live (the keystream and the
    verify builders' lift_x share one library): both fall back to
    Python without a word when g++ fails, and both build from ``native/``
    as checked out (utils/nativebuild.py names the library after a digest
    of its sources, so a stale ignored ``*.so`` is never what runs)."""
    from kaspa_tpu.crypto import hostcrypto
    from kaspa_tpu.storage import kv

    with tempfile.TemporaryDirectory(prefix="chip_smoke_kv_") as d:
        store = kv.open_store(os.path.join(d, "probe"))
        storage = type(store).__name__
        store.close()
    return {"keystream": "native" if hostcrypto.lib() is not None else "python", "storage": storage}


def _device() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def _child(phase: str, chips: int) -> int:
    """One phase in this process.  The chip-holding phases check the
    platform first; the daemon phase holds no chip — its daemon does, and
    reports the device it runs on through getMetrics."""
    from kaspa_tpu.utils import jax_setup

    jax_setup.setup()
    t0 = time.perf_counter()
    row: dict = {"phase": phase, "cache_dir": jax_setup.cache_dir(), "engines": _engines()}
    try:
        if phase != "daemon":
            row["device"] = _device()
            if row["device"]["platform"] != "tpu":
                raise SmokeFailure(f"no accelerator: JAX runs on {row['device']['platform']}")
            if row["device"]["count"] != chips:
                raise SmokeFailure(f"{row['device']['count']} devices visible, this run needs {chips}")
        evidence = PHASES[phase](chips=chips) if phase == "mesh" else PHASES[phase]()
        if phase == "daemon":
            row["device"] = evidence.pop("device")
            if (row["device"] or {}).get("platform") != "tpu":
                raise SmokeFailure(f"the daemon does not run on a TPU: {row['device']}", evidence)
        row.update(ok=True, **evidence)
    except SmokeFailure as e:
        row.update(ok=False, error=str(e), **e.evidence)
    except Exception as e:  # noqa: BLE001 - a phase that raised has failed; say how
        import traceback

        row.update(ok=False, error=f"{type(e).__name__}: {e}", traceback=traceback.format_exc()[-3000:])
    row["seconds"] = round(time.perf_counter() - t0, 1)
    _emit(row)
    return 0 if row["ok"] else 1


def _run_phase(phase: str, chips: int) -> dict | None:
    """Run one phase as a child; echo its lines; return its phase object."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", phase, "--chips", str(chips)],
        stdout=subprocess.PIPE, text=True, cwd=REPO, start_new_session=True,
    )
    try:
        out, _ = proc.communicate(timeout=PHASE_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)  # the child and whatever it started (the daemon)
        out, _ = proc.communicate()
        _emit({"phase": phase, "ok": False, "error": f"killed after {PHASE_TIMEOUT_S} s"})
        return None
    row = None
    for line in out.splitlines():
        print(line, flush=True)
        if line.startswith("{"):
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if obj.get("phase") == phase:
                row = obj
    if row is not None and proc.returncode != 0:
        row["ok"] = False
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run the mesh path and what it is compared with, and no other phase")
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)  # a child of this script
    args = ap.parse_args(argv)
    if args.phase:
        return _child(args.phase, args.chips)

    t0 = time.perf_counter()
    device, ok = None, True
    for phase in ("mesh",) if args.chips > 1 else ("kernels", "replay", "daemon"):
        row = _run_phase(phase, args.chips)
        ok = bool(row and row.get("ok"))
        if row and row.get("device"):
            d = row["device"]
            device = {"platform": d["platform"], "kind": d["kind"], "count": d["count"]}
        if not ok:
            break  # fail fast: the platform check is the first thing the first child does
    _emit({"smoke_seconds": round(time.perf_counter() - t0, 1), "chips": args.chips})
    # the driver reads exactly these keys from the last line
    print(json.dumps({"ok": ok and device is not None, "device": device}), flush=True)
    return 0 if ok and device is not None else 1


if __name__ == "__main__":
    sys.exit(main())
