"""One run of one cell: set-up, the measured window, the comparison, the
metrics.  ``run.py`` calls ``run_cell`` on a TPU at the cell's own sizes;
``tests/test_rehearse.py`` calls the same function on the CPU at toy sizes.

Everything that belongs to one configuration, one traffic mix, one per-layer
metric, one reader kind or one mode lives in a file of its own, found here by
name: ``configs/<config>.json``, ``workloads/<cell>.json``,
``metrics/<metric>.json``, ``readers/<kind>.py``, ``modes/<mode>.py``,
``shapes/<tx_shape>.py``, ``peaks/<device kind>.json``.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import shutil
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(HERE, "_trace")  # git-ignored; emptied by every traced run


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(BENCHMARK.json, its entry for the cell, the traffic file, the config file)."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    workload = load_json(os.path.join(HERE, "workloads", f"{name}.json"))
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    if workload["config"] != entry["config"]:
        raise SystemExit(f"{name}: traffic file names config {workload['config']!r}, BENCHMARK.json {entry['config']!r}")
    return bench, entry, workload, config


class Setup:
    """What set-up hands to a mode: the DAG, a way to make a fresh measured
    consensus with its pipeline, and the clock's origin."""

    def __init__(self, dag, workload: dict, config: dict, seed: int, log):
        self.dag, self.workload, self.config, self.seed, self.log = dag, workload, config, seed, log
        self.pipes: list = []

    def fresh_pipeline(self):
        """A fresh ``Consensus`` behind a ``ConsensusPipeline`` — what
        ``python -m kaspa_tpu.sim --pipeline --coalesce N`` replays into."""
        from kaspa_tpu.consensus.consensus import Consensus
        from kaspa_tpu.pipeline.pipeline import ConsensusPipeline

        consensus = Consensus(self.dag.params)
        pipe = ConsensusPipeline(consensus, workers=int(self.config["pipeline"]["stage_workers"]))
        self.pipes.append(pipe)
        return consensus, pipe

    def replay_ramp(self, pipe) -> None:
        """The thin blocks before the window, through the same entry."""
        ramp = self.dag.blocks[: self.dag.ramp]
        futures = [pipe.submit(b) for b in ramp]
        for b, f in zip(ramp, futures):
            status = f.result(timeout=600)
            # a spoiled block placed before the ramp settled stays in the ramp
            if status not in ("utxo_valid", "utxo_pending") and b.hash not in self.dag.spoiled:
                raise RuntimeError(f"ramp block rejected: {status}")

    def shutdown(self) -> None:
        for pipe in self.pipes:
            pipe.shutdown()
        self.pipes.clear()


def _pretrace(workload: dict, log) -> list:
    """Warm this cell's shapes, and no others, ahead of the build and the window."""
    from kaspa_tpu.crypto import secp

    rows = []
    for kernel, buckets in workload["pretrace"].items():
        for b in buckets:
            t0 = time.perf_counter()
            status = secp.pretrace_bucket(kernel, int(b))
            rows.append({"kernel": kernel, "bucket": int(b), "status": status, "seconds": time.perf_counter() - t0})
            if status.startswith("error"):
                raise RuntimeError(f"pretrace of {kernel}/{b} failed: {status}")
    log("pretrace " + json.dumps(rows))
    return rows


def _check_network(config: dict, params) -> None:
    """The derived network parameters are what the configuration states."""
    net = config["network"]
    for key in ("ghostdag_k", "max_block_parents", "mergeset_size_limit", "max_block_mass", "coinbase_maturity"):
        if key in net and getattr(params, key) != net[key]:
            raise RuntimeError(f"config states {key}={net[key]}, simnet_params(bps={net['bps']}) gives {getattr(params, key)}")


def build_dag(workload: dict, config: dict, seed: int, log):
    """The cell's DAG from the seed: the one general generator reads the
    traffic file's parameters (its ``tx_shape`` names a file of ``shapes/``)
    and the configuration's network."""
    from benchmarks import dag as dagmod

    net = config["network"]
    spec = dagmod.DagSpec(
        bps=int(net["bps"]), delay=float(net["delay_s"]), miners=int(net["miners"]),
        tx_per_block=int(workload["tx_per_block"]), window_blocks=int(workload["window_blocks"]), seed=seed,
        tx_shape=workload["tx_shape"], spoiled_blocks=int(workload.get("spoiled_blocks", 0)),
        pool_factor=int(workload.get("pool_factor", 3)), sig_samples=int(workload.get("sig_samples", 24)),
        coinbase_maturity=net.get("coinbase_maturity"), own_blocks_delayed=bool(net.get("own_blocks_delayed", False)),
        gap_stratum_blocks=int(workload.get("gap_stratum_blocks", 0)),
    )
    dag = dagmod.build(spec, log=log)
    _check_network(config, dag.params)
    log("dag " + json.dumps(dag.facts))
    return dag


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_cell(workload: dict, config: dict, bench: dict, cell: str, seed: int, seconds: float, trace: bool,
             process_start: float, log, wrap_window=None, dag=None) -> dict:
    """Set-up, window, comparison, metrics.  ``wrap_window`` is a context
    manager factory entered around the window alone: the control and the
    fault tests break the timed path with it, a benchmark run passes none;
    they may also hand in the DAG of an earlier run of the same seed."""
    from benchmarks import compare as cmp
    from benchmarks import ledger
    from kaspa_tpu.observability import trace as ptrace
    from kaspa_tpu.ops import dispatch as coalescing

    ledger.tally_jax_events()
    coalescing.configure(0)  # the build is the in-order run: coalescing off
    t0 = time.perf_counter()
    _pretrace(workload, log)
    t_pretrace = time.perf_counter() - t0

    t0 = time.perf_counter()
    if dag is None:
        dag = build_dag(workload, config, seed, log)
    t_build = time.perf_counter() - t0
    tpb = int(workload["tx_per_block"])

    target = coalescing.configure(int(config["pipeline"]["coalesce"]))
    setup = Setup(dag, workload, config, seed, log)
    mode = importlib.import_module(f"benchmarks.modes.{workload['mode']}")
    try:
        t0 = time.perf_counter()
        consensus, pipe = setup.fresh_pipeline()
        setup.replay_ramp(pipe)
        t_ramp = time.perf_counter() - t0

        ptrace.set_capture(1 << 20 if trace else 0)
        ptrace.drain()
        before, compiles0 = ledger.counters(), ledger.compile_tally()
        tracer = _Tracer(float(workload.get("trace_seconds", 4.0)), tuple(workload.get("idle_gap_spans", ()))) if trace else None
        setup_s = time.perf_counter() - process_start
        log("setup " + json.dumps({"setup_s": setup_s, "pretrace_s": t_pretrace, "build_s": t_build, "ramp_s": t_ramp,
                                   "coalesce": target, "compile_cache": compiles0}))
        if tracer:
            tracer.start()
        try:
            with (wrap_window or contextlib.nullcontext)():
                window = mode.run(setup, consensus, pipe, seconds)
        finally:
            if tracer:
                tracer.finish()
        after, compiles1 = ledger.counters(), ledger.compile_tally()
        spans = ptrace.drain() if trace else []
        ptrace.set_capture(0)
        coalescing.drain()
        breaker = ledger.breaker_state()
        mem_peak = memory_peak_bytes()
        d = ledger.delta(after, before)
        cd = ledger.compile_delta(compiles1, compiles0)
        log("window " + json.dumps({k: v for k, v in window.items() if k in ("attempted", "blocks", "seconds", "facts", "end_to_end", "harness")}))
        log("counters " + json.dumps(d))
        log("compile_cache_in_window " + json.dumps(cd))

        # ---- the comparison: once the window has closed and the peak is read
        t0 = time.perf_counter()
        checks = {}
        for p in window["passes"]:
            for name, value in cmp.compare_pass(dag, p["consensus"], p["prefix"], p["statuses"]).items():
                checks[name] = checks.get(name, 0) + value
        # a prefix that holds none of the spoiled blocks has shown nothing about the masks
        first_spoiled = min((s["index"] for s in dag.spoiled.values()), default=None)
        checks["no_spoiled_block_compared"] = int(
            first_spoiled is not None and all(p["prefix"] <= first_spoiled for p in window["passes"])
        )
        checks.update(ledger.device_work_checks(d, breaker))
        checks["compiles_in_window"] = cd["backend_compiles"] + cd["misses"]
        checks["unresolved_blocks"] = window["unresolved"]
        checks["traffic_exhausted"] = window.get("exhausted", 0)
        checks["thin_window_blocks"] = sum(
            1 for b in dag.blocks[dag.ramp :] if len(b.transactions) - 1 != tpb
        )
        t_compare = time.perf_counter() - t0
        log(f"compare {t_compare:.2f} s")
    finally:
        setup.shutdown()
        coalescing.shutdown()

    correct = all(v == 0 for v in checks.values())
    metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
    if trace:
        reduced = tracer.reduce(spans)
        ctx = {
            "spans": spans, "counters": d, "trace": reduced, "window": window, "tracer": tracer,
            "peak": lambda: _peak(device_info()["kind"]),
        }
        if reduced:
            log("trace " + json.dumps({k: reduced[k] for k in ("busy_s", "window_s", "per_device_busy_s", "clock_offset_known", "lines")}))
            log("trace_device_ops " + json.dumps(sorted(([n, v[0], v[1]] for n, v in reduced["by_name"].items()), key=lambda r: -r[1])[:25]))
            log("trace_idle_gaps " + json.dumps(reduced["idle_gaps"]))
        metrics = read_per_layer(bench, cell, ctx, log)
    else:
        for m in bench["end_to_end"]:
            if m["name"] in window["end_to_end"]:
                metrics[m["name"]] = {"value": window["end_to_end"][m["name"]], "unit": m["unit"]}
    device = dict(device_info(), memory_peak_bytes=mem_peak)
    if trace and reduced:
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
    failed = window["failed"] + checks.get("bad_status_blocks", 0)
    out = {"correct": correct, "attempted": window["attempted"], "failed": failed, "metrics": metrics, "device": device}
    if trace and reduced:
        out["breakdown"] = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = {k: [v, 0] for k, v in checks.items()}
    return out


def _peak(kind: str) -> dict:
    """The chip's published peaks: ``peaks/<device kind, spaces as _>.json``."""
    path = os.path.join(HERE, "peaks", kind.replace(" ", "_") + ".json")
    if not os.path.exists(path):
        raise RuntimeError(f"device kind {kind!r} has no file {os.path.relpath(path, ROOT)}")
    return load_json(path)


def read_per_layer(bench: dict, cell: str, ctx: dict, log) -> dict:
    """Every per-layer metric that lists this cell, through the reader its
    file names.  A reader that finds nothing returns None and the metric is
    left out of the line."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        spec = load_json(os.path.join(HERE, "metrics", f"{m['name']}.json"))
        reader = importlib.import_module(f"benchmarks.readers.{spec['source']['reader']}")
        value = reader.read(spec["source"], ctx)
        if value is None:
            log(f"metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class _Tracer:
    """The profiler over the first ``trace_seconds`` of the window, stopped
    from a timer thread so that the window itself is not held up."""

    def __init__(self, trace_seconds: float, gap_spans: tuple):
        self.trace_seconds = trace_seconds
        self.gap_spans = gap_spans  # the traffic file's ``idle_gap_spans``: innermost first
        self.counters_at_stop = None
        self.counters_at_start = None
        self.perf_anchor_ns = None
        self.stop_ns = None
        self._timer = None
        self._stopped = threading.Event()
        self._lock = threading.Lock()

    def start(self) -> None:
        import jax

        from benchmarks import ledger

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.makedirs(TRACE_DIR, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self._anchor = jax.profiler.TraceAnnotation("bench.window")
        self.perf_anchor_ns = time.perf_counter_ns()
        self._anchor.__enter__()
        self.counters_at_start = ledger.counters()
        self._timer = threading.Timer(self.trace_seconds, self._stop)
        self._timer.daemon = True
        self._timer.start()

    def _stop(self) -> None:
        import jax

        from benchmarks import ledger

        with self._lock:
            if self._stopped.is_set():
                return
            self.counters_at_stop = ledger.counters()
            self.stop_ns = time.perf_counter_ns()
            self._anchor.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._stopped.set()

    def finish(self) -> None:
        self._timer.cancel()
        self._stop()

    def reduce(self, spans: list) -> dict | None:
        from benchmarks import reduce

        path = reduce.find_xplane(TRACE_DIR)
        if path is None:
            return None
        xp = reduce.load_xplane(path)
        out = reduce.reduce_trace(xp, (self.perf_anchor_ns, self.stop_ns), spans, self.perf_anchor_ns, self.gap_spans)
        out["lines"] = xp["lines"]
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return out
