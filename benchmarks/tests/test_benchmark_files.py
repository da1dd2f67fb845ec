"""``BENCHMARK.json`` against the files it names: every cell, configuration
and per-layer metric has its data file, and every name, ``why`` and
``source`` is inside the contract's limits.  No JAX; one case a row, so that
a file gone missing says which."""

import os
import re

import pytest

from benchmarks.harness import HERE, ROOT
from benchmarks.harness import load_json as _load

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")

BENCH = _load(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = {w["name"]: w for w in BENCH["workloads"]}
CONFIGS = {c["name"]: c for c in BENCH["configs"]}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_has_its_traffic_and_config_file(cell):
    entry = CELLS[cell]
    traffic = _load(os.path.join(HERE, "workloads", f"{cell}.json"))
    assert cell == f"{entry['config']}.{entry['traffic']}"
    assert traffic["config"] == entry["config"] and entry["config"] in CONFIGS
    assert traffic["why"] == entry["why"] and 1 <= len(entry["why"]) <= 200
    assert entry["chips"] in (1, 4)
    for key in (entry["name"], entry["config"], entry["traffic"]):
        assert NAME.fullmatch(key), key
    assert os.path.exists(os.path.join(HERE, "modes", traffic["mode"] + ".py"))
    assert os.path.exists(os.path.join(HERE, "shapes", traffic["tx_shape"] + ".py"))
    # every cell reports setup_s, one more end-to-end metric and a per-layer metric
    listed = lambda m: "workloads" not in m or cell in m["workloads"]  # noqa: E731
    assert len([m for m in BENCH["end_to_end"] if listed(m)]) >= 2
    assert any(listed(m) for m in BENCH["per_layer"])


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_config_has_its_file(config):
    entry = CONFIGS[config]
    cfg = _load(os.path.join(ROOT, entry["file"]))
    assert entry["file"].startswith("benchmarks/configs/") and cfg["name"] == config
    assert cfg["source"] == entry["source"] and cfg["reduced"] == entry["reduced"]
    assert 1 <= len(entry["source"]) <= 200 and 1 <= len(entry["why"]) <= 200
    assert NAME.fullmatch(config) and all(NAME.fullmatch(k) for k in entry["reduced"])
    assert any(w["config"] == config for w in BENCH["workloads"])  # each configuration is used by some cell
    assert len(cfg["guarantees"]) == 4 and set(cfg["reduced"]) <= set(cfg["published"])


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_has_its_file_reader_and_cells(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    spec = _load(os.path.join(HERE, "metrics", f"{metric}.json"))
    assert NAME.fullmatch(metric) and spec["name"] == metric
    for key in ("layer", "unit", "better", "moves"):
        assert spec[key] == entry[key], key
    assert spec["bench_source"] == entry["source"]
    assert os.path.exists(os.path.join(HERE, "readers", spec["source"]["reader"] + ".py"))
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == entry["moves"])
    assert entry["workloads"] and set(entry["workloads"]) <= set(CELLS)
    # each listed cell reports the end-to-end metric this one should move
    assert all("workloads" not in moved or c in moved["workloads"] for c in entry["workloads"])


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"]])
def test_end_to_end_metric_is_inside_the_contract(metric):
    entry = next(m for m in BENCH["end_to_end"] if m["name"] == metric)
    assert NAME.fullmatch(metric) and entry["better"] in ("lower", "higher")
    assert 0.01 <= entry["bound"] <= 0.25 and entry["source"] in ("host_clock", "device_trace")
    assert set(entry.get("workloads", ())) <= set(CELLS)
    assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", entry["unit"])
