"""Both modes end to end on the CPU at toy size, through the same
``harness.run_cell`` that ``run.py`` calls, and ``run.py`` itself without a
TPU.  The XLA ladder at bucket 8 is the device lane here; nothing these runs
time is a device number."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import harness
from benchmarks.tests.conftest import ROOT, TOY_WIDE, toy_cell

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _run(bench, cell, mode, trace, lines, **kw):
    import time

    workload, config = toy_cell(mode, **kw)
    return harness.run_cell(workload, config, bench, cell, seed=9, seconds=3.0, trace=trace,
                            process_start=time.perf_counter(), log=lines.append)


def _check_line(out, bench, cell, trace):
    assert KEYS <= set(out) and list(out)[-1] == "checks"
    json.loads(json.dumps(out))  # the line is JSON
    assert out["correct"] is True, {k: v for k, v in out["checks"].items() if v[0] != v[1]}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] >= 1
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in bench[kind] if "workloads" not in m or cell in m["workloads"]}
    assert set(out["metrics"]) <= allowed
    for m in out["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]
    return allowed


def test_catchup_end_to_end(bench):
    cell, lines = "crescendo-10bps.catchup-10tpb", []
    out = _run(bench, cell, "catchup", False, lines)
    allowed = _check_line(out, bench, cell, False)
    assert set(out["metrics"]) == allowed == {"catchup_blocks_per_s", "setup_s"}
    heads = {ln.split(" ", 1)[0] for ln in lines}
    assert {"pretrace", "dag", "setup", "window", "counters", "compile_cache_in_window"} <= heads


def test_wide_catchup_end_to_end(bench):
    """The wide toy cell (one miner, own blocks delayed): the same entry, mode
    and comparison; every count 0 though the virtual reorganises."""
    cell, lines = "simpa-8bps.catchup-200tpb", []
    out = _run(bench, cell, "catchup", False, lines, **TOY_WIDE)
    allowed = _check_line(out, bench, cell, False)
    assert set(out["metrics"]) == allowed == {"catchup_blocks_per_s", "setup_s"}
    assert all(v == [0, 0] for v in out["checks"].values()), out["checks"]
    facts = json.loads(next(ln for ln in lines if ln.startswith("dag ")).split(" ", 1)[1])
    assert facts["miners"] == 1 and facts["mean_window_parents"] > 2
    # the traffic file's gap strata reach the generator: 39 gaps at 4 blocks/s, whole 1 s strata but for the ends
    assert abs(facts["window_vtime_s"] - 39 / 4) <= 1.0
    assert not any(ln.startswith("second pass") for ln in lines)


def test_paced_traced(bench):
    cell, lines = "crescendo-10bps.paced-10tpb", []
    out = _run(bench, cell, "paced", True, lines)
    _check_line(out, bench, cell, True)
    # program spans and the pacer are readable anywhere; device-trace metrics
    # find no device plane on the CPU and are left out, never 0
    assert {"pipeline_virtual_ms_per_block.paced", "pacer_late_p95_ms"} <= set(out["metrics"])
    assert not any("idle" in k or "roofline" in k for k in out["metrics"])
    assert "breakdown" in out


def test_second_pass_starts_when_the_dag_runs_out(bench):
    cell, lines = "crescendo-10bps.catchup-10tpb", []
    out = _run(bench, cell, "catchup", False, lines, window_blocks=6, tx_per_block=2)
    assert any(ln.startswith("second pass") for ln in lines)
    assert out["correct"] is True, out["checks"]


def test_run_py_refuses_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", "crescendo-10bps.catchup-10tpb",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, timeout=300, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())
    assert "TPU" in proc.stderr


def test_run_py_alone_in_a_directory_fails(tmp_path):
    import shutil

    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "_trace"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "crescendo-10bps.catchup-10tpb", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, timeout=120, cwd=tmp_path, env=env,
    )
    assert proc.returncode != 0 and not any(ln.startswith("{") for ln in proc.stdout.splitlines())
