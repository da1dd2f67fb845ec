"""A late joiner pulls its blocks from one peer over the socket (ISSUE 35):
``connect_outbound`` -> ``WirePeer._reader_loop`` -> ``Node._handle`` ->
``_insert_ibd_batch`` on a toy DAG of the benchmark's generator, from
``benchmarks/donor.py`` (in a thread here, a process in the cell) on both
wires, held against ``benchmarks/reference.py`` through ``compare.py`` and
against ``benchmarks/reference_ibd.py`` (which imports nothing of the
program).  CPU, XLA ladder at bucket 8; nothing here is a device number.
Every wait on a socket or a thread carries a limit of its own."""

import os
import time

import pytest

from benchmarks import compare, donor as donor_mod, harness, reference_ibd
from kaspa_tpu.consensus.consensus import Consensus
from kaspa_tpu.observability import trace
from kaspa_tpu.observability.core import REGISTRY
from kaspa_tpu.ops import dispatch as coalescing
from kaspa_tpu.p2p import node as node_mod
from kaspa_tpu.p2p import transport
from kaspa_tpu.pipeline.pipeline import ConsensusPipeline

CELL = "crescendo-10bps-ibd.catchup-10tpb-wire"
CHUNK = 16  # blocks a chunk here, so that several chunks pass
LIMIT = 240.0  # seconds any one sync may take on this CPU
WORKLOAD = {
    "config": "toy", "mode": "ibd_wire", "tx_per_block": 3, "tx_shape": "fanout-then-1to1",
    "window_blocks": 64, "spoiled_blocks": 2, "pool_factor": 3, "grace_seconds": 60, "sig_samples": 4,
    "pretrace": {"schnorr_verify": [8]}, "trace_seconds": 1.0,
    "idle_gap_spans": ["p2p.decode", "wait.p2p_frame", "wait.node_lock", "pipeline.virtual", "ibd.insert_batch"],
}
CONFIG = {"name": "toy", "network": {"bps": 2, "delay_s": 1.0, "miners": 4}, "pipeline": {"coalesce": 64, "stage_workers": 2},
          "p2p": {"wire": "proto"}}
COUNTS = ("ibd_blocks_missing", "ibd_blocks_unsent_held", "ibd_rerequests", "ibd_bad_continuations")


@pytest.fixture(scope="module")
def dag():
    coalescing.configure(0)
    return harness.build_dag(WORKLOAD, CONFIG, 35, lambda _m: None)


def _sync(dag, wire: str) -> dict:
    """The ramp replayed into a fresh consensus behind its pipeline, a ``Node``
    over them, the donor in a thread, one ``ibd_from``: what the syncee holds,
    the donor's log, the spans and the counters that moved."""
    coalescing.configure(64)
    consensus = Consensus(dag.params)
    pipe = ConsensusPipeline(consensus, workers=2)
    window = dag.blocks[dag.ramp:]
    last = dag.sinks[-1]
    donor = donor_mod.Donor(
        **donor_mod.split(window, CHUNK), sink=last, sink_blue_work=next(b for b in window if b.hash == last).header.blue_work,
        pruning_point=dag.params.genesis.hash, network=dag.params.name, codec_name=wire, chunk_blocks=CHUNK,
    )
    peer = None
    try:
        for f in [pipe.submit(b) for b in dag.blocks[: dag.ramp]]:
            f.result(timeout=LIMIT)
        node = node_mod.Node(consensus, name="syncee", mempool_seed=1, pipeline=pipe)
        assert donor.wait_first_frame(LIMIT)
        donor.serve(seconds=LIMIT)
        trace.set_capture(1 << 16)
        trace.drain()
        before = REGISTRY.snapshot()["counters"]
        peer = transport.connect_outbound(node, donor.address, codec=transport.get_codec(wire))
        with node.lock.locked_for("ibd_from"):
            node.ibd_from(peer)
        assert donor.finished.wait(LIMIT), "the donor never sent its last chunk"
        deadline = time.monotonic() + LIMIT
        while node._sync_peer is not None and peer.alive and time.monotonic() < deadline:
            time.sleep(0.01)  # the reader thread is still inserting the last chunk
        assert node._sync_peer is None or not peer.alive, "the last chunk was not taken in"
        spans = trace.drain()
        after = REGISTRY.snapshot()["counters"]
        store = consensus.storage.statuses
        held = {b.hash: store.get(b.hash) for b in window if store.get(b.hash) is not None}
        log = list(donor.log)
        served = sorted({i for e in log if e["event"] == "chunk" for i in e["sent"]})
        prefix = dag.ramp + (max(served) + 1 if served else 0)
        checks = compare.compare_pass(dag, consensus, prefix, {})
    finally:
        trace.set_capture(0)
        if peer is not None:
            peer.close()
        donor.close()
        coalescing.drain()
        pipe.shutdown()
        coalescing.configure(0)
    return {
        "log": log, "held": held, "spans": spans, "checks": checks, "served": served,
        "moved": {k: after[k] - before.get(k, 0) for k in after if not isinstance(after[k], dict)},
        "counts": reference_ibd.check([b.hash for b in window], dag.sinks[dag.ramp - 1], log, held,
                                      ("utxo_valid", "utxo_pending", "disqualified")),
    }


@pytest.fixture(scope="module", params=["proto", "custom"])
def sound(request, dag):
    return request.param, _sync(dag, request.param)


def test_the_syncee_ends_where_the_reference_and_the_in_order_run_do(dag, sound):
    _wire, run = sound
    assert len(run["served"]) == len(dag.blocks) - dag.ramp == 64 and len(run["held"]) == 64
    # sink, commitment, UTXO set, accepted ids, statuses, GHOSTDAG, signatures: every count of disagreement 0
    assert run["checks"] and all(v == 0 for v in run["checks"].values()), run["checks"]


def test_a_sound_pull_reads_zero_on_every_count_of_the_reference(dag, sound):
    _wire, run = sound
    assert run["counts"] == dict.fromkeys(COUNTS, 0)
    chunks = [e for e in run["log"] if e["event"] == "chunk"]
    assert [len(e["sent"]) for e in chunks] == [16, 16, 16, 16] and [e["done"] for e in chunks] == [False] * 3 + [True]
    requests = [e["msg"] for e in run["log"] if e["event"] == "request"]
    assert requests == ["requestibdchaininfo", "ibdblocklocator"] + ["requestantipast"] * 3  # a chunk only when asked


def test_spans_and_counters_of_the_path(sound):
    wire, run = sound
    by_name: dict = {}
    for s in run["spans"]:
        by_name.setdefault(s["name"], []).append(s)
    inserts = by_name["ibd.insert_batch"]
    assert [s["attrs"] for s in inserts] == [{"blocks": n, "rejected": 0} for n in (16, 16, 16, 16)]  # one a chunk
    decodes = {s["attrs"]["msg"]: s["attrs"]["bytes"] for s in by_name["p2p.decode"]}
    assert {"version", "verack", "ibdchaininfo", "ibdblocks"} <= set(decodes) and decodes["ibdblocks"] > 1000
    assert len([s for s in by_name["p2p.decode"] if s["attrs"]["msg"] == "ibdblocks"]) == 4  # one a frame, never one a block
    # the reader's waits on the socket are spans while the sync is in hand: one a chunk (the wait for the chain info
    # began before ``ibd_from`` was called)
    assert len(by_name["wait.p2p_frame"]) == 4
    assert {s["attrs"]["who"] for s in by_name["wait.node_lock"]} >= {"ibdblocks", "ibdchaininfo"}
    assert (run["moved"]["p2p_ibd_chunks_rx"], run["moved"]["p2p_ibd_blocks_rx"], run["moved"]["p2p_ibd_blocks_rejected"]) == (4, 64, 0)
    assert run["moved"]["p2p_bytes_rx"] > sum(e["bytes"] for e in run["log"] if e["event"] == "chunk")


# ---- reference_ibd.py on logs written by hand: standard library only

H = [bytes([i]) * 32 for i in range(12)]  # a window of twelve blocks, chunks of four
SINK = b"\xaa" * 32
ALL_HELD = dict.fromkeys(H, "utxo_valid")


def _log(requests_and_chunks: list) -> list:
    out = [{"t": 0.0, "event": "request", "msg": "requestibdchaininfo"}]
    for item in requests_and_chunks:
        if item[0] == "locator":
            out.append({"t": 0.0, "event": "request", "msg": "ibdblocklocator", "locator": item[1]})
        elif item[0] == "low":
            out.append({"t": 0.0, "event": "request", "msg": "requestantipast", "low": item[1]})
        else:
            first, last, sent, done = item[1:]
            out.append({"t": 0.0, "event": "chunk", "first": first, "last": last, "sent": sent, "bytes": 1, "done": done})
    return out


SOUND = [("locator", [SINK, b"\x00" * 32]), ("chunk", 0, 3, [0, 1, 2, 3], False), ("low", H[3]),
         ("chunk", 4, 7, [4, 5, 6, 7], False), ("low", H[7]), ("chunk", 8, 11, [8, 9, 10, 11], True)]


def _faulty(name: str):
    steps, held = list(SOUND), dict(ALL_HELD)
    if name == "a withheld block":
        steps[3] = ("chunk", 4, 7, [4, 6, 7], False)
        held = {h: s for h, s in held.items() if h not in (H[5], H[6], H[7])}  # what descends from it is refused too
    elif name == "a repeated request":
        steps[4:4] = [("low", H[3]), ("chunk", 4, 7, [4, 5, 6, 7], False)]
    elif name == "a wrong continuation":
        steps[2] = ("low", H[2])
    elif name == "a locator above another sink":
        steps[0] = ("locator", [H[0], SINK])
    elif name == "a block nobody sent":
        steps = steps[:4]
        held = {h: "utxo_valid" for h in H[:9]}
    elif name == "an unfinished last chunk":
        held[H[11]] = "header_only"
    return steps, held


@pytest.mark.parametrize("name,count", [
    ("a withheld block", "ibd_blocks_missing"), ("a repeated request", "ibd_rerequests"),
    ("a wrong continuation", "ibd_bad_continuations"), ("a locator above another sink", "ibd_bad_continuations"),
    ("a block nobody sent", "ibd_blocks_unsent_held"), ("an unfinished last chunk", "ibd_blocks_missing"),
])
def test_reference_ibd_counts_what_is_wrong_and_nothing_else(name, count):
    final = ("utxo_valid", "utxo_pending", "disqualified")
    assert reference_ibd.check(H, SINK, _log(SOUND), ALL_HELD, final) == dict.fromkeys(COUNTS, 0)
    steps, held = _faulty(name)
    got = reference_ibd.check(H, SINK, _log(steps), held, final)
    assert got[count] >= 1, got
    if name == "a withheld block":
        assert got["ibd_blocks_missing"] == 3  # the acknowledged chunk is read back, position by position


def test_reference_ibd_imports_nothing_of_the_program():
    import ast

    tree = ast.parse(open(reference_ibd.__file__).read())
    names = [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    names += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    assert set(names) == {"__future__"}


# ---- the new files parse and name each other (``benchmarks/tests/test_benchmark_files.py``'s checks, for them)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NEW_METRICS = ("wire_decode_ms_per_block.ibd", "wire_wait_ms_per_block.ibd", "ibd_insert_ms_per_block",
               "ibd_blocks_per_chunk", "wire_bytes_per_block.ibd")


def test_the_cell_its_traffic_and_its_config_name_each_other():
    _bench, entry, workload, config = harness.load_cell(CELL)
    assert CELL == f"{entry['config']}.{entry['traffic']}" and entry["chips"] == 1
    assert workload["why"] == entry["why"] and 1 <= len(entry["why"]) <= 200
    cfg_entry = next(c for c in BENCH["configs"] if c["name"] == "crescendo-10bps-ibd")
    assert config["name"] == cfg_entry["name"] and config["source"] == cfg_entry["source"] and len(config["source"]) <= 200
    assert config["reduced"] == cfg_entry["reduced"] == ["run_length"] and set(config["reduced"]) <= set(config["published"])
    thin = harness.load_json(os.path.join(ROOT, "benchmarks", "configs", "crescendo-10bps.json"))
    assert config["network"] == thin["network"] and config["pipeline"] == thin["pipeline"]  # the same deployment but for the path
    assert config["guarantees"][:4] == thin["guarantees"] and len(config["guarantees"]) == 5 and "read back" in config["guarantees"][4]
    assert config["p2p"]["wire"] in ("proto", "custom") and config["p2p"]["flow"] == "ibd_from"
    for name in ("modes/ibd_wire.py", "donor.py", "reference_ibd.py", "control_ibd.py", f"shapes/{workload['tx_shape']}.py"):
        assert os.path.exists(os.path.join(ROOT, "benchmarks", name)), name
    thin_traffic = harness.load_json(os.path.join(ROOT, "benchmarks", "workloads", "crescendo-10bps.catchup-10tpb.json"))
    for key in ("tx_per_block", "tx_shape", "pool_factor", "spoiled_blocks", "sig_samples"):  # the thin catch-up cell's construction
        assert workload[key] == thin_traffic[key], key
    assert workload["window_blocks"] % node_mod.IBD_BATCH_SIZE == 0 and "gap_stratum_blocks" not in workload
    assert {"p2p.decode", "wait.p2p_frame", "ibd.insert_batch", "wait.node_lock"} <= set(workload["idle_gap_spans"])
    assert CELL in next(m for m in BENCH["end_to_end"] if m["name"] == "catchup_blocks_per_s")["workloads"]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_per_layer_metric_has_its_file_and_reader(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    spec = harness.load_json(os.path.join(ROOT, "benchmarks", "metrics", f"{metric}.json"))
    assert spec["name"] == metric and entry["workloads"] == [CELL] and entry["moves"] == "catchup_blocks_per_s"
    for key in ("layer", "unit", "better", "moves"):
        assert spec[key] == entry[key], key
    assert spec["bench_source"] == entry["source"] and entry["layer"] in ("p2p wire", "IBD flow")
    assert os.path.exists(os.path.join(ROOT, "benchmarks", "readers", spec["source"]["reader"] + ".py"))
