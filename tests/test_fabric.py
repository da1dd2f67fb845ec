"""Verify fabric (kaspa_tpu/fabric/): wire format, verifyd service,
cross-host balancer, and the 2-D hybrid mesh spec/partition registry.

The contract under test: routing verify chunks over the fabric is
invisible in results — masks are bit-identical to direct batched
dispatch — while slice failures (send faults, corrupted frames, a
stopped server) fail over to the next slice or the bit-identical host
degraded lane without ever losing a ticket.

Shape discipline: every device call here lands in the same padded
bucket-8 shape the other verify tests use (each new bucket costs a
fresh XLA compile on CPU, minutes of tier-1 budget).  The degraded-lane
and stop-race tests never touch the device at all (host oracle lane).
"""

import hashlib

import numpy as np
import pytest

from kaspa_tpu.fabric import wire
from kaspa_tpu.fabric.balancer import FabricBalancer
from kaspa_tpu.fabric.service import VerifyService
from kaspa_tpu.observability.core import REGISTRY
from kaspa_tpu.ops import dispatch as coalesce
from kaspa_tpu.ops import mesh
from kaspa_tpu.p2p.proto.wire_format import ProtoWireError
from kaspa_tpu.resilience.faults import FAULTS


@pytest.fixture(autouse=True)
def _clean_after():
    yield
    FAULTS.clear()
    coalesce.configure(0)
    mesh.configure(1)


def _schnorr_items(n: int, corrupt_every: int = 4):
    from kaspa_tpu.crypto import eclib

    items = []
    for i in range(n):
        sk = i + 1
        msg = hashlib.sha256(bytes([i, n])).digest()
        sig = eclib.schnorr_sign(msg, sk)
        if corrupt_every and i % corrupt_every == corrupt_every - 1:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        items.append((eclib.schnorr_pubkey(sk), msg, sig))
    return items


# --- wire format -------------------------------------------------------------


def test_wire_hello_roundtrip():
    mtype, msg = wire.decode(wire.encode_hello(4))
    assert mtype == wire.HELLO
    assert msg == {"proto": wire.PROTO_VERSION, "slices": 4, "modes": 0}
    # an older verifyd set capability bit 0x01: the varint still decodes, and
    # nothing reads it
    assert wire.decode(wire.encode_hello(4, modes=0x01)) == (wire.HELLO, {**msg, "modes": 0x01})


def test_wire_hello_proto1_compat():
    # a proto-1 HELLO has no trailing modes varint; decode defaults modes=0
    mtype, msg = wire.decode(wire.encode_hello(2, proto=1)[: 1 + 1 + 1])
    assert mtype == wire.HELLO
    assert msg == {"proto": 1, "slices": 2, "modes": 0}


def test_wire_verify_req_roundtrip():
    items = [(b"\x02" * 32, b"\xaa" * 32, b"\x0f" * 64), (b"\x03" * 33, b"\xbb" * 32, b"\x10" * 65)]
    payload = wire.encode_verify_req(7, "ecdsa", 3, "trace-1", items)
    mtype, msg = wire.decode(payload)
    assert mtype == wire.VERIFY_REQ
    assert msg["req_id"] == 7 and msg["kind"] == "ecdsa" and msg["slice"] == 3
    assert msg["trace_id"] == "trace-1"
    assert msg["items"] == items
    # absent trace id decodes to None, not ""
    _, msg2 = wire.decode(wire.encode_verify_req(8, "schnorr", 0, None, []))
    assert msg2["trace_id"] is None and msg2["items"] == []


@pytest.mark.parametrize("lanes", [1, 7, 8, 9, 64])
def test_wire_mask_roundtrip_at_byte_edges(lanes):
    mask = np.array([i % 3 != 1 for i in range(lanes)], dtype=bool)
    _, msg = wire.decode(wire.encode_verify_resp(5, mask, 123, 456, 2))
    assert msg["ok"] is True
    assert msg["mask"].tolist() == mask.tolist()
    assert (msg["queue_ns"], msg["verify_ns"], msg["inflight"]) == (123, 456, 2)


def test_wire_error_and_status_roundtrip():
    _, err = wire.decode(wire.encode_error_resp(9, "kaboom"))
    assert err == {"req_id": 9, "ok": False, "error": "kaboom"}
    _, st = wire.decode(wire.encode_status_resp(11, [(1, 0), (0, 5)]))
    assert st == {"req_id": 11, "slices": [(1, 0), (0, 5)]}
    mtype, req = wire.decode(wire.encode_status_req(11))
    assert mtype == wire.STATUS_REQ and req == {"req_id": 11}


def test_wire_rejects_malformed():
    with pytest.raises(ProtoWireError):
        wire.decode(b"")
    with pytest.raises(ProtoWireError):
        wire.decode(bytes([0x7F]))  # unknown message type
    good = wire.encode_verify_req(1, "schnorr", 0, None, [(b"\x02" * 32, b"\xaa" * 32, b"\x0f" * 64)])
    with pytest.raises(ProtoWireError):
        wire.decode(good[: len(good) // 2])  # truncated mid-item
    # a decodable-but-lying mask length must not produce a short mask
    resp = bytearray(wire.encode_verify_resp(2, np.ones(8, dtype=bool), 0, 0, 0))
    resp[2] = 16  # claim 16 lanes, still 1 packed byte
    with pytest.raises(ProtoWireError):
        wire.decode(bytes(resp))


# --- service + balancer ------------------------------------------------------


def _serve(slices: int = 2):
    svc = VerifyService("127.0.0.1:0", slices=slices)
    host, port = svc.start()
    return svc, f"{host}:{port}"


def test_remote_verify_bit_identical():
    """One chunk over a real socket to an in-process verifyd: the mask
    must be byte-identical to direct batched dispatch, resolved remotely
    with zero lost tickets."""
    from kaspa_tpu.crypto import secp

    items = _schnorr_items(7)
    direct = np.asarray(secp.schnorr_verify_batch(items)).tolist()  # warms the kernel too

    svc, addr = _serve(slices=2)
    bal = FabricBalancer([addr], deadline_s=120.0)
    try:
        got = [bool(v) for v in bal.submit("schnorr", items).wait(120.0)]
        assert got == direct
        assert not all(got) and any(got)  # mixed validity actually exercised
        st = bal.stats()
        assert st["remote"] == 1 and st["degraded"] == 0 and st["lost"] == 0
        assert len(st["slices"]) == 2  # one routable lane per server slice
    finally:
        bal.close(timeout=5.0)
        svc.stop()
    snap = REGISTRY.snapshot()
    assert sum(snap["counters"].get("fabric_remote_jobs", {}).values()) >= 7
    assert sum(snap["counters"].get("fabric_service_requests", {}).values()) >= 1


def test_degraded_lane_when_no_slice_reachable():
    """Nothing listening on any address: every chunk lands on the host
    degraded lane (eclib oracle — no device), bit-identical, lost == 0."""
    items = _schnorr_items(7)
    bal = FabricBalancer(["127.0.0.1:1"], deadline_s=30.0)
    try:
        got = [bool(v) for v in bal.submit("schnorr", items).wait(30.0)]
        assert got == [i % 4 != 3 for i in range(7)]
        st = bal.stats()
        assert st["remote"] == 0 and st["degraded"] == 1 and st["lost"] == 0
    finally:
        bal.close(timeout=5.0)


def test_send_fault_fails_over_to_next_slice():
    """An injected fabric.send error on the first attempt: the chunk is
    re-routed (failover) and still resolves remotely, bit-identically."""
    from kaspa_tpu.crypto import secp

    items = _schnorr_items(7)
    direct = np.asarray(secp.schnorr_verify_batch(items)).tolist()

    svc, addr = _serve(slices=2)
    bal = FabricBalancer([addr], deadline_s=120.0)
    try:
        FAULTS.configure({"fabric.send": {"mode": "error", "hits": [1]}}, seed=0)
        got = [bool(v) for v in bal.submit("schnorr", items).wait(120.0)]
        assert got == direct
        st = bal.stats()
        assert st["failovers"] >= 1 and st["remote"] == 1 and st["lost"] == 0
    finally:
        FAULTS.clear()
        bal.close(timeout=5.0)
        svc.stop()


def test_truncated_frame_hangs_then_degrades():
    """A truncated request frame leaves the server reader blocked
    mid-frame: the request can never be answered, the balancer's deadline
    trips the slice as hung, and with no other slice the chunk resolves
    on the degraded lane — never lost, never wrong."""
    items = _schnorr_items(7)
    svc, addr = _serve(slices=1)
    bal = FabricBalancer([addr], deadline_s=2.0)
    try:
        FAULTS.configure({"fabric.send": {"mode": "truncate", "hits": [1]}}, seed=3)
        got = [bool(v) for v in bal.submit("schnorr", items).wait(30.0)]
        assert got == [i % 4 != 3 for i in range(7)]
        st = bal.stats()
        assert st["degraded"] == 1 and st["lost"] == 0
        assert sum(s["trips"] for s in st["slices"]) >= 1  # the hung verdict
    finally:
        FAULTS.clear()
        bal.close(timeout=5.0)
        svc.stop()


def test_server_stop_races_submit_without_losing_tickets():
    """stop() the service under a connected balancer, then submit: the
    dead link must route the chunk to the degraded lane, resolved exactly
    once (the fabric smoke's kill drill, at unit scale and device-free)."""
    items = _schnorr_items(7)
    svc, addr = _serve(slices=2)
    bal = FabricBalancer([addr], deadline_s=5.0)
    try:
        assert any(s.conn.alive for s in bal._slices)
        svc.stop()
        t = bal.submit("schnorr", items)
        got = [bool(v) for v in t.wait(30.0)]
        assert got == [i % 4 != 3 for i in range(7)]
        st = bal.stats()
        assert st["submitted"] == 1 and st["degraded"] == 1 and st["lost"] == 0
    finally:
        bal.close(timeout=5.0)


# --- 2-D hybrid mesh ---------------------------------------------------------


def test_mesh_2d_spec_parsing():
    # conftest forces 8 CPU host devices
    assert mesh.configure("2x4") == 8
    assert mesh.grid() == (2, 4)
    assert mesh.slice_count() == 2 and mesh.slice_width() == 4
    # a grid larger than the visible devices is an error, never a clamp
    with pytest.raises(ValueError, match="16 devices, 8 visible"):
        mesh.configure("4x4")
    assert mesh.grid() == (2, 4)
    assert mesh.configure("4x2") == 8
    assert mesh.grid() == (4, 2)
    # a single slice degenerates to the 1-D mesh
    assert mesh.configure("1x8") == 8
    assert mesh.grid() is None
    # plain integers never form a grid
    assert mesh.configure(8) == 8
    assert mesh.grid() is None and mesh.slice_count() == 1
    state = REGISTRY.snapshot()["mesh"]
    assert state["grid"] == "" and state["size"] == 8


def test_partition_rule_registry():
    from jax.sharding import PartitionSpec as P

    mesh.configure("2x4")
    assert mesh.partition_spec_for("px") == P(("slice", "shard"), None)
    assert mesh.partition_spec_for("valid_in") == P(("slice", "shard"))
    assert mesh.partition_spec_for("anything_else") == P()
    # 1-D projection collapses the composite batch axis onto "shard"
    assert mesh.partition_spec_for("px", flat=True) == P("shard", None)
    # registration is first-match-wins at the head of the registry
    before = list(mesh._partition_rules)
    try:
        mesh.register_partition_rule(r"px", ("shard",))
        assert mesh.partition_spec_for("px") == P("shard")
    finally:
        mesh._partition_rules[:] = before
    tree = {"layer": {"px": 1, "bias": 2}}
    specs = mesh.match_partition_rules(mesh.DEFAULT_PARTITION_RULES, tree)
    assert specs["layer"]["px"] == P(("slice", "shard"), None)
    assert specs["layer"]["bias"] == P()


def test_schnorr_mask_identical_1d_vs_2x4_grid(on_mesh_devices):
    """The full 2-D grid (both mesh axes, no slice pinning) must be
    bit-identical to single-device dispatch — same bucket-8 shape as the
    1-D mesh tests, so the grid entry's local computation is served by
    the persistent compilation cache."""
    from kaspa_tpu.crypto import secp

    items = _schnorr_items(7)
    mesh.configure(1)
    m1 = np.asarray(secp.schnorr_verify_batch(items))
    mesh.configure("2x4")
    with on_mesh_devices("schnorr"):
        m2d = np.asarray(secp.schnorr_verify_batch(items))
    assert m1.tolist() == m2d.tolist()
    assert not m1.all() and m1.any()
    snap = REGISTRY.snapshot()
    assert snap["mesh"]["grid"] == "2x4"
