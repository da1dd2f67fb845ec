"""Observability layer: span tracer, histograms, registry sinks, and the
four hardening fixes that rode along (wire truncation, reject flush,
store miss-sentinel guard, sub-1-BPS activation gate)."""

import random
import socket
import threading
import time

import pytest

from kaspa_tpu.observability import prom, trace
from kaspa_tpu.observability.core import (
    REGISTRY,
    Counter,
    CounterFamily,
    Histogram,
    Registry,
    _derive_rates,
    _merge_numeric,
)

# --- span tracer ----------------------------------------------------------


def test_span_nesting_paths():
    trace.set_capture(256)
    try:
        with trace.span("outer"):
            assert trace.current_path() == "outer"
            with trace.span("inner", key=1):
                assert trace.current_path() == "outer/inner"
            assert trace.current_path() == "outer"
        assert trace.current_path() == ""
        got = trace.drain()
        assert [s["path"] for s in got] == ["outer/inner", "outer"]
        assert got[0]["attrs"] == {"key": 1}
        assert got[0]["dur_us"] >= 0
        assert got[1]["name"] == "outer"
    finally:
        trace.set_capture(0)


def test_span_exception_safety():
    trace.set_capture(256)
    try:
        with pytest.raises(ValueError):
            with trace.span("bad"):
                raise ValueError("boom")
        # stack unwound: a fresh span is a root again
        with trace.span("after"):
            assert trace.current_path() == "after"
        got = trace.drain()
        assert got[0]["name"] == "bad"
        assert got[0]["attrs"]["error"] == "ValueError"
        assert got[1]["path"] == "after"
    finally:
        trace.set_capture(0)


def test_span_disabled_is_noop():
    trace.disable()
    try:
        s = trace.span("anything", a=1)
        assert s is trace.span("other")  # the shared no-op singleton
        with s:
            assert trace.current_path() == ""
    finally:
        trace.enable()


def test_span_overhead_budget():
    """Loose ceilings (CI machines vary): disabled ~0.2µs, enabled ~2µs
    measured locally; budgets 2µs / 10µs."""

    def per_use_us(n=20_000, trials=5):
        best = float("inf")
        for _ in range(trials):
            t0 = time.perf_counter()
            for _ in range(n):
                with trace.span("bench"):
                    pass
            best = min(best, time.perf_counter() - t0)
        return best / n * 1e6

    trace.set_capture(0)
    trace.disable()
    try:
        disabled = per_use_us()
    finally:
        trace.enable()
    enabled = per_use_us()
    assert disabled < 2.0, f"disabled span costs {disabled:.2f}µs"
    assert enabled < 10.0, f"enabled span costs {enabled:.2f}µs"


# --- histograms / counters ------------------------------------------------


def test_histogram_bucket_edges():
    h = Histogram("h", buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 1.5, 2.0, 5.0, 7.0):
        h.observe(v)
    snap = h.snapshot()
    # le semantics: value lands in the first bucket whose edge >= value
    assert snap["buckets"] == [[1.0, 2], [2.0, 2], [5.0, 1], ["+Inf", 1]]
    assert snap["count"] == 6
    assert snap["sum"] == pytest.approx(17.0)
    assert snap["min"] == 0.5 and snap["max"] == 7.0
    assert snap["p50"] == 2.0  # 3rd of 6 observations sits in the le=2 bucket


def test_histogram_quantile_edge_cases():
    # empty histogram: 0.0, explicitly — not NaN, not a stale max
    h = Histogram("h", buckets=(1.0, 2.0))
    assert h.quantile(0.5) == 0.0
    assert h.snapshot()["count"] == 0 and "p50" not in h.snapshot()
    # every observation in the +Inf overflow bucket: the edges carry no
    # upper bound, so the estimate is inf — the observed max would
    # understate the tail the caller asked about
    for v in (10.0, 20.0):
        h.observe(v)
    assert h.quantile(0.5) == float("inf")
    assert h.quantile(0.99) == float("inf")
    assert h.snapshot()["p99"] == float("inf")
    # mixed: quantiles below the overflow mass still resolve to edges
    for _ in range(6):
        h.observe(0.5)
    assert h.quantile(0.5) == 1.0
    assert h.quantile(0.99) == float("inf")
    # single observation on an exact edge
    g = Histogram("g", buckets=(1.0, 2.0))
    g.observe(1.0)
    assert g.quantile(0.5) == 1.0 and g.quantile(1.0) == 1.0


def test_counter_snapshot_deterministic():
    r = Registry()
    fam = r.counter_family("jobs", "kind")
    fam.inc("zeta", 3)
    fam.inc("alpha")
    r.counter("plain").inc(7)
    s1, s2 = r.snapshot(), r.snapshot()
    assert s1 == s2  # no mutation between snapshots -> identical trees
    assert list(s1["counters"]["jobs"].keys()) == ["alpha", "zeta"]  # sorted
    assert s1["counters"]["plain"] == 7
    import json

    json.dumps(s1)  # JSON-serializable end to end


def test_registry_collector_merge_and_rates():
    r = Registry()

    class Owner:
        def stats(self):
            return {"store": {"hits": 8, "misses": 2}}

    a, b = Owner(), Owner()
    r.register_collector("caches", a.stats)
    r.register_collector("caches", b.stats)
    snap = r.snapshot()
    assert snap["caches"]["store"]["hits"] == 16  # merged by sum
    assert snap["caches"]["store"]["hit_rate"] == pytest.approx(0.8)
    # dead owners are pruned, not crashed on
    del a, b
    import gc

    gc.collect()
    assert r.snapshot()["caches"] == {}


def test_merge_and_rates_helpers():
    d = _merge_numeric({"a": {"x": 1}}, {"a": {"x": 2, "y": 3}})
    assert d == {"a": {"x": 3, "y": 3}}
    t = {"c": {"hits": 0, "misses": 0}}
    _derive_rates(t)
    assert t["c"]["hit_rate"] == 0.0


# --- prometheus exporter --------------------------------------------------


def test_prom_render_cumulative_buckets():
    r = Registry()
    h = r.histogram("lat", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    r.counter("reqs", help="requests").inc(4)
    fam = r.counter_family("bykind", "kind")
    fam.inc('we"ird\\', 2)  # label escaping
    text = prom.render(r)
    lines = text.splitlines()
    assert '# TYPE kaspa_lat histogram' in lines
    assert 'kaspa_lat_bucket{le="0.1"} 1' in lines
    assert 'kaspa_lat_bucket{le="1.0"} 2' in lines  # cumulative
    assert 'kaspa_lat_bucket{le="+Inf"} 3' in lines  # == _count
    assert 'kaspa_lat_count 3' in lines
    assert 'kaspa_reqs_total 4' in lines
    assert 'kaspa_bykind_total{kind="we\\"ird\\\\"} 2' in lines
    # every sample line is "name{labels} value" with a float-parseable value
    for ln in lines:
        if ln and not ln.startswith("#"):
            float(ln.rsplit(" ", 1)[1])


def test_prom_renders_global_registry_collectors():
    # the global registry always carries span_duration_seconds; rendering
    # must produce valid text even with collector gauge trees attached
    with trace.span("prom.check"):
        pass
    text = prom.render()
    assert "kaspa_span_duration_seconds" in text


def test_prom_family_headers_exactly_once():
    r = Registry()
    # a family with several cells must emit one header block, not one per
    # cell; distinct raw names folding to the same exposition name
    # ("a.b" and "a:b" both sanitize their dots) must not duplicate either
    fam = r.counter_family("jobs", "kind", help="job counts")
    fam.inc("alpha")
    fam.inc("beta")
    hfam = r.histogram_family("lat", "stage", (0.1, 1.0), help="latency")
    hfam.observe("x", 0.05)
    hfam.observe("y", 0.5)
    r.counter("dup.name", help="first").inc(1)
    r.counter("dup name", help="second").inc(2)  # same sanitized name
    lines = prom.render(r).splitlines()
    for needle in ("# TYPE kaspa_jobs counter", "# TYPE kaspa_lat histogram"):
        assert lines.count(needle) == 1
    type_names = [ln.split()[2] for ln in lines if ln.startswith("# TYPE ")]
    assert len(type_names) == len(set(type_names)), "duplicate # TYPE family"
    help_names = [ln.split()[2] for ln in lines if ln.startswith("# HELP ")]
    assert len(help_names) == len(set(help_names)), "duplicate # HELP family"
    # both dup counters still contribute their samples
    assert lines.count("kaspa_dup_name_total 1") == 1
    assert lines.count("kaspa_dup_name_total 2") == 1


def test_prom_help_text_escaped():
    r = Registry()
    r.counter("tricky", help="line one\nline two \\ backslash").inc(3)
    text = prom.render(r)
    # exposition 0.0.4: HELP escapes newline and backslash; the rendered
    # output must stay one physical line per comment
    assert "# HELP kaspa_tricky line one\\nline two \\\\ backslash" in text.splitlines()


def test_prom_full_live_registry_parses():
    """Parse-validate the ENTIRE live global registry (flight recorder,
    dispatch, serving, pipeline families all registered by import time):
    every non-comment line is ``name[{labels}] value`` with a
    float-parseable value, every # TYPE appears exactly once per family,
    and every typed sample's name resolves to its family via the
    histogram/counter suffix rules."""
    import re as _re

    from kaspa_tpu.observability import flight  # noqa: F401 - registers families

    with trace.span("prom.live"):
        pass
    lines = prom.render().splitlines()
    assert lines, "empty exposition"
    sample_re = _re.compile(r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (\S+)$')
    types: dict[str, str] = {}
    for ln in lines:
        if not ln:
            continue
        if ln.startswith("# TYPE "):
            _, _, name, mtype = ln.split(" ", 3)
            assert name not in types, f"duplicate # TYPE for {name}"
            assert mtype in ("counter", "histogram")
            types[name] = mtype
            continue
        if ln.startswith("# HELP "):
            assert "\n" not in ln  # escaped, single physical line
            continue
        m = sample_re.match(ln)
        assert m, f"unparseable sample line: {ln!r}"
        float(m.group(3))  # value must parse
    assert types, "no typed families rendered"
    # suffix rules: histogram samples are _bucket/_sum/_count, counter
    # samples are _total; every sample that wears a typed family's name
    # must agree with that family's declared type
    for ln in lines:
        if not ln or ln.startswith("#"):
            continue
        name = sample_re.match(ln).group(1)
        for fam, mtype in types.items():
            if name.startswith(fam + "_") or name == fam:
                suffix = name[len(fam):]
                allowed = ("_bucket", "_sum", "_count") if mtype == "histogram" else ("_total",)
                assert suffix in allowed, f"{name} disagrees with # TYPE {fam} {mtype}"
    # the always-present span family renders real samples on the page
    assert types.get("kaspa_span_duration_seconds") == "histogram"
    assert any(ln.startswith("kaspa_span_duration_seconds_bucket{") for ln in lines)
    # the flight recorder's histogram family is part of the live page
    assert types.get("kaspa_block_critical_path_ms") == "histogram"


# --- get_metrics sink -----------------------------------------------------


def test_get_metrics_observability_section():
    from kaspa_tpu.consensus.consensus import Consensus
    from kaspa_tpu.consensus.params import simnet_params
    from kaspa_tpu.p2p import Node
    from kaspa_tpu.rpc import RpcCoreService
    from kaspa_tpu.sim.simulator import Miner

    node = Node(Consensus(simnet_params(bps=2)), "obs-test")
    service = RpcCoreService(node.consensus, node.mining, address_prefix="kaspasim")
    miner = Miner(0, random.Random(5))
    for _ in range(6):
        node.submit_block(node.consensus.build_block_template(miner.miner_data, []))
    obs = service.get_metrics()["observability"]
    # per-stage span latencies: block intake runs through the pipeline
    spans = obs["histograms"]["span_duration_seconds"]
    assert "pipeline.stage" in spans and spans["pipeline.stage"]["count"] >= 6
    assert obs["histograms"]["pipeline_queue_wait_seconds"]["stage"]["count"] >= 6
    assert obs["counters"]["pipeline_tasks_submitted"] >= 6
    # store cache hit rates from the ConsensusStorage collector
    headers = obs["store_cache"]["headers"]
    assert headers["hits"] > 0 and "hit_rate" in headers
    # prometheus endpoint renders the same registry
    text = service.get_metrics_prometheus()
    assert 'kaspa_span_duration_seconds_bucket{stage="pipeline.stage"' in text
    node.pipeline.shutdown()


# --- trace_report CLI -----------------------------------------------------


def test_trace_report_aggregation(tmp_path):
    trace.set_capture(1024)
    try:
        with trace.span("root"):
            with trace.span("child"):
                pass
            with trace.span("child"):
                pass
        log = tmp_path / "spans.jsonl"
        n = trace.dump(str(log))
        assert n == 3
    finally:
        trace.set_capture(0)
    import sys

    sys.path.insert(0, "tools")
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    spans = trace_report.load_spans(str(log))
    agg = trace_report.aggregate(spans)
    assert agg["root/child"]["count"] == 2
    assert agg["root"]["count"] == 1
    # self time excludes direct children
    assert agg["root"]["self_us"] <= agg["root"]["total_us"]
    report = trace_report.render_report(spans)
    assert "child" in report and "slowest" in report


# --- satellite: wire truncation hardening ---------------------------------


def test_wire_truncated_frames_raise():
    from kaspa_tpu.p2p import wire

    with pytest.raises(wire.WireError):
        wire._dec_smt_request(b"\x00" * 16)  # pp hash cut short
    # a valid smt chunk, then truncated at every prefix length
    full = wire._enc_smt_chunk(
        {
            "active": True,
            "meta": {
                "lanes_root": b"\x01" * 32, "pcd": b"\x02" * 32,
                "parent_seq_commit": b"\x03" * 32, "shortcut_block": b"\x04" * 32,
                "inactivity_shortcut": b"\x05" * 32,
            },
            "offset": 1,
            "lanes": [(b"\x06" * 32, b"\x07" * 32, 9)],
            "segment": [],
            "done": True,
        }
    )
    assert wire._dec_smt_chunk(full)["lanes"][0][2] == 9
    for cut in (0, 1, 40, 170, len(full) - 1):
        with pytest.raises(wire.WireError):
            wire._dec_smt_chunk(full[:cut])
    # bodies: hash cut short must not silently yield a 20-byte "hash"
    bodies = wire._enc_bodies([(b"\x08" * 32, [])])
    with pytest.raises(wire.WireError):
        wire._dec_bodies(bodies[:-12])


# --- satellite: reject frame flushed before close -------------------------


def _tcp_pair():
    """Loopback TCP pair (WirePeer wants a real getpeername address)."""
    lsock = socket.socket()
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)
    client = socket.create_connection(lsock.getsockname())
    server, _ = lsock.accept()
    lsock.close()
    return server, client


def test_reject_frame_delivered_before_close():
    from kaspa_tpu.p2p import wire
    from kaspa_tpu.p2p.node import MSG_REJECT, ProtocolError
    from kaspa_tpu.p2p.transport import WirePeer
    from kaspa_tpu.utils.sync import LockCtx

    class StubNode:
        def __init__(self):
            self.lock = LockCtx("node", rank=5)  # what Node.lock is: the reader takes it through locked_for()
            self.peers = []

        def _handle(self, peer, msg_type, payload):
            raise ProtocolError("you are misbehaving")

    server_sock, client_sock = _tcp_pair()
    node = StubNode()
    peer = WirePeer(node, server_sock, outbound=False)
    node.peers.append(peer)
    peer.start()
    client_sock.sendall(wire.encode_frame(wire.MSG_PING, 1))

    def read_exactly(n):
        buf = b""
        while len(buf) < n:
            chunk = client_sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("closed before reject arrived")
            buf += chunk
        return buf

    client_sock.settimeout(5.0)
    msg_type, payload = wire.read_message(read_exactly)
    assert msg_type == MSG_REJECT
    assert "misbehaving" in payload
    client_sock.close()


def test_transport_flush_returns_false_when_dead():
    from kaspa_tpu.p2p.transport import WirePeer

    class StubNode:
        lock = threading.Lock()
        peers = []

    a, b = _tcp_pair()
    peer = WirePeer(StubNode(), a, outbound=False)
    peer.close()
    assert peer.flush(timeout=0.1) is False
    b.close()


# --- satellite: store cache miss-sentinel guard ---------------------------


def test_store_cache_rejects_none_values(tmp_path):
    from kaspa_tpu.consensus.stores import CachedDbAccess, ConsensusStorage
    from kaspa_tpu.storage.kv import KvStore

    storage = ConsensusStorage(db=KvStore(str(tmp_path / "t.db")))
    with pytest.raises(AssertionError):
        storage.ghostdag._access.write(b"\x01" * 32, None)
    # a decoder returning None must fail loudly, not loop as eternal misses
    acc = CachedDbAccess(storage, b"ZZ", lambda v: v, lambda b: None, budget=4)
    acc.write(b"\x02" * 32, b"payload")
    storage.flush()
    acc.clear_cache()
    with pytest.raises(AssertionError):
        acc.try_get(b"\x02" * 32)


def test_store_cache_stats_counts(tmp_path):
    from kaspa_tpu.consensus.stores import CachedDbAccess, ConsensusStorage
    from kaspa_tpu.storage.kv import KvStore

    storage = ConsensusStorage(db=KvStore(str(tmp_path / "t.db")))
    acc = CachedDbAccess(storage, b"ZZ", lambda v: v, lambda b: b, budget=2)
    for i in range(4):
        acc.write(bytes([i]) * 32, b"v%d" % i)
    storage.flush()  # unpins; evictions bring the cache back to budget
    assert acc._evictions >= 2
    acc.try_get(b"\x03" * 32)
    base_hits = acc._hits
    acc.try_get(b"\x03" * 32)
    assert acc._hits == base_hits + 1
    acc.try_get(b"\xee" * 32)  # absent everywhere
    assert acc._misses >= 1
    stats = storage.cache_stats()["ZZ"]
    assert stats["hits"] == acc._hits and stats["evictions"] == acc._evictions


# --- satellite: sub-1-BPS activation gate ---------------------------------


def test_activation_gate_blocks_sub_one_bps():
    from kaspa_tpu.p2p.node import _activation_gate_blocks

    assert _activation_gate_blocks(1000) == 86_400  # 1 BPS: one day of blocks
    assert _activation_gate_blocks(100) == 864_000  # 10 BPS
    # sub-1-BPS: the old round(1000/target) factor collapsed to 1 here,
    # inflating the one-day gate to ten days
    assert _activation_gate_blocks(10_000) == 8_640
    assert _activation_gate_blocks(500) == 172_800
