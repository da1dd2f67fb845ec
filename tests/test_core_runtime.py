"""Core/Service runtime, tick, logging, metrics core, sysinfo, DB tool.

Reference shapes: core/src/{core,service}.rs (ordered lifecycle),
core/src/task/tick.rs, metrics/core/src/data.rs (snapshot rates),
database/rocknroll (DB admin tooling).
"""

import threading
import time

import pytest

from kaspa_tpu.core import Core, Service, TickService
from kaspa_tpu.core.log import get_logger, init_logger
from kaspa_tpu.metrics.core import METRIC_GROUPS, MetricsData, MetricsSnapshot


class _Recorder(Service):
    def __init__(self, name, events):
        self._name = name
        self.events = events

    def ident(self):
        return self._name

    def start(self, core):
        self.events.append(("start", self._name))
        t = threading.Thread(target=lambda: None, daemon=True)
        t.start()
        return [t]

    def stop(self):
        self.events.append(("stop", self._name))


def test_core_lifecycle_ordering():
    events = []
    core = Core()
    for name in ("db", "consensus", "rpc"):
        core.bind(_Recorder(name, events))
    workers = core.start()
    assert [e for e in events if e[0] == "start"] == [("start", "db"), ("start", "consensus"), ("start", "rpc")]
    core.join(workers, timeout=5)
    core.shutdown()
    # reverse bind order: dependents stop before their dependencies
    assert [e for e in events if e[0] == "stop"] == [("stop", "rpc"), ("stop", "consensus"), ("stop", "db")]
    # idempotent
    core.shutdown()
    assert len([e for e in events if e[0] == "stop"]) == 3
    assert core.find("consensus") is not None and core.find("nope") is None


def test_core_stop_failure_does_not_strand_others():
    events = []
    core = Core()

    class Bad(Service):
        def stop(self):
            raise RuntimeError("boom")

    core.bind(_Recorder("a", events))
    core.bind(Bad())
    core.bind(_Recorder("b", events))
    core.start()
    core.shutdown()
    assert ("stop", "a") in events and ("stop", "b") in events


def test_tick_service_fires_and_stops_fast():
    ticks = []
    svc = TickService()
    svc.register(0.02, lambda: ticks.append(time.monotonic()))
    core = Core()
    core.bind(svc)
    core.start()
    time.sleep(0.15)
    t0 = time.monotonic()
    core.shutdown()
    assert time.monotonic() - t0 < 1.0  # shutdown doesn't wait out intervals
    assert len(ticks) >= 3


def test_logger_filter_spec():
    init_logger("warn,consensus=trace")
    import logging

    assert logging.getLogger("kaspa").level == logging.WARNING
    assert logging.getLogger("kaspa.consensus").level == 5  # trace
    log = get_logger("consensus")
    log.trace("trace message works")  # must not raise
    init_logger("info")  # restore


def test_metrics_rates_from_snapshot_deltas():
    data = MetricsData()
    s1 = MetricsSnapshot(unixtime_millis=1_000, values={"node_total_bytes_tx": 0, "node_total_bytes_rx": 100})
    s2 = MetricsSnapshot(unixtime_millis=3_000, values={"node_total_bytes_tx": 4000, "node_total_bytes_rx": 300})
    data.push(s1)
    assert s1.values["node_total_bytes_tx_per_second"] == 0.0  # no prior sample
    data.push(s2)
    assert s2.values["node_total_bytes_tx_per_second"] == 2000.0
    assert s2.values["node_total_bytes_rx_per_second"] == 100.0
    # groups index into the same value space
    assert "node_cpu_usage" in METRIC_GROUPS["system"]
    assert set(s2.group("bandwidth")) == set(METRIC_GROUPS["bandwidth"])


def test_sysinfo_and_build_info():
    from kaspa_tpu.utils.sysinfo import build_info, system_info

    info = system_info()
    assert info["cpu_physical_cores"] >= 1
    assert info["total_memory"] > 0
    assert info["fd_limit"] > 0
    assert len(info["system_id"]) == 32
    assert build_info()["version"]
    assert info["git_hash"]  # live repo


def test_db_tool_stats_verify_compact(tmp_path):
    from kaspa_tpu.consensus.consensus import Consensus
    from kaspa_tpu.consensus.params import simnet_params
    from kaspa_tpu.consensus.processes.coinbase import MinerData
    from kaspa_tpu.consensus.model import ScriptPublicKey
    from kaspa_tpu.storage import __main__ as dbtool
    from kaspa_tpu.storage.kv import KvStore

    db_path = tmp_path / "consensus.db"
    db = KvStore(str(db_path))
    c = Consensus(simnet_params(), db=db)
    miner = MinerData(ScriptPublicKey(0, b"\x20" + b"\x07" * 32 + b"\xac"))
    for i in range(4):
        b = c.build_block_with_parents(list(c.tips), miner)
        b.header.nonce = i + 1
        b.header.invalidate_cache()
        c.validate_and_insert_block(b)
    db.close()

    assert dbtool.resolve_active_db(str(tmp_path)) == str(db_path)
    store = KvStore(str(db_path))
    try:
        assert dbtool.cmd_stats(store) == 0
        assert dbtool.cmd_verify(store) == 0
        assert dbtool.cmd_compact(store) == 0
    finally:
        store.close()
    # post-compact: the DB still replays into a working consensus
    db2 = KvStore(str(db_path))
    c2 = Consensus(simnet_params(), db=db2)
    assert c2.get_virtual_daa_score() == c.get_virtual_daa_score()
    assert c2.sink() == c.sink()
    db2.close()


def test_daemon_metrics_snapshot_over_wire(tmp_path):
    from kaspa_tpu.node.daemon import Daemon, parse_args, rpc_call

    args = parse_args(["--appdir", str(tmp_path), "--rpclisten", "127.0.0.1:0", "--no-persist"])
    daemon = Daemon(args)
    try:
        addr = daemon.start()
        # force one sample through the tick body
        daemon.metrics_data.push(
            __import__("kaspa_tpu.metrics.core", fromlist=["collect_snapshot"]).collect_snapshot(
                daemon.consensus, daemon.mining, daemon.perf_monitor, p2p_node=daemon.node
            )
        )
        m = rpc_call(addr, "getMetrics")
        assert m["snapshot"] is not None
        assert m["snapshot"]["node_database_headers_count"] >= 1
        si = rpc_call(addr, "getSystemInfo")
        assert si["cpu_physical_cores"] >= 1 and si["version"]
    finally:
        daemon.stop()


# --- jax_setup: the compile cache is placed from outside ---------------------


@pytest.mark.parametrize("from_env", [True, False], ids=["JAX_COMPILATION_CACHE_DIR", "in-checkout"])
def test_jax_setup_cache_placement(tmp_path, from_env):
    """With JAX_COMPILATION_CACHE_DIR set the program sets no directory in
    code (JAX reads the variable itself) and cache_dir() — which the warm
    manifest hangs off — returns it; unset, both are the one fixed
    git-ignored directory in the checkout.  In a subprocess each, so the
    session's own setup is not disturbed."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "outside")
    code = (
        "import json, jax\n"
        "from kaspa_tpu.utils import jax_setup\n"
        "from kaspa_tpu.resilience import supervisor\n"
        "jax_setup.setup()\n"
        "print(json.dumps({'config': jax.config.jax_compilation_cache_dir,"
        " 'cache_dir': jax_setup.cache_dir(), 'manifest': supervisor.manifest_path()}))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=str(tmp_path),
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True, timeout=120,
    )
    assert proc.returncode == 0
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    want = str(tmp_path / "outside") if from_env else os.path.join(repo, ".jax_cache")
    assert os.path.realpath(got["config"]) == os.path.realpath(want)
    assert os.path.realpath(got["cache_dir"]) == os.path.realpath(want)
    assert os.path.dirname(os.path.realpath(got["manifest"])) == os.path.realpath(want)
    if from_env:
        assert not (tmp_path / "outside").exists()  # set from outside, not created in code
