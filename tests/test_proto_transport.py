"""Two in-process nodes over the protobuf/gRPC wire: handshake + relay.

The same scenario runs over the custom frame codec and the protobuf codec;
the resulting app-level state (negotiated tier, sink, DAA score, block
availability) must be identical — the wire is a pluggable serialization,
never a behavior change.
"""

from __future__ import annotations

import random
import time

import pytest

from kaspa_tpu.consensus.consensus import Consensus
from kaspa_tpu.consensus.params import simnet_params
from kaspa_tpu.consensus.processes.coinbase import MinerData
from kaspa_tpu.p2p.node import Node
from kaspa_tpu.p2p.transport import P2PServer, connect_outbound, get_codec
from kaspa_tpu.sim.simulator import Miner


def _mine(node: Node, n: int, t0: int = 10_000) -> list:
    miner = Miner(0, random.Random(5))
    out = []
    for i in range(n):
        with node.lock:
            t = node.consensus.build_block_template(
                MinerData(miner.spk, b""), [], timestamp=t0 + 600 * i
            )
            node.submit_block(t)
        out.append(t)
    return out


def _wait(predicate, timeout: float = 20.0) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.02)
    return False


def _run_scenario(codec_name: str) -> dict:
    """Handshake two socket-connected nodes, relay blocks, snapshot state."""
    params = simnet_params(bps=2)
    a = Node(Consensus(params), f"donor-{codec_name}")
    b = Node(Consensus(params), f"joiner-{codec_name}")
    server = P2PServer(a, port=0, codec=get_codec(codec_name))
    server.start()
    try:
        out_peer = connect_outbound(b, server.address, codec=get_codec(codec_name))
        assert _wait(lambda: a.peers and a.peers[0].handshaken), "inbound handshake"
        in_peer = a.peers[0]

        blocks = _mine(a, 6)
        want_sink = blocks[-1].hash

        def synced():
            with b.lock:
                return b.consensus.sink() == want_sink

        assert _wait(synced), f"block relay over {codec_name} wire did not converge"

        with b.lock:
            state = {
                "tier_out": out_peer.protocol_version,
                "tier_in": in_peer.protocol_version,
                "sink": b.consensus.sink(),
                "daa": b.consensus.get_virtual_daa_score(),
                "has_blocks": [b.consensus.reachability.has(blk.hash) for blk in blocks],
            }
        return state
    finally:
        server.stop()
        for peer in list(a.peers) + list(b.peers):
            peer.close()


@pytest.mark.parametrize("codec_name", ["custom", "proto"])
def test_handshake_and_block_relay(codec_name):
    state = _run_scenario(codec_name)
    assert state["tier_out"] == 10 and state["tier_in"] == 10
    assert all(state["has_blocks"])


def test_proto_wire_state_identical_to_custom_wire():
    """The acceptance bar: the proto transport produces bit-identical
    app-level state to the custom wire for the same scenario."""
    assert _run_scenario("custom") == _run_scenario("proto")


def test_codec_selector_rejects_unknown_wire():
    with pytest.raises(ValueError):
        get_codec("carrier-pigeon")


def test_daemon_flag_selects_proto_wire(tmp_path):
    """Two OS-process daemons both launched with --p2p-proto handshake and
    relay over the protobuf wire — the flag is runtime wire selection."""
    import os
    import subprocess
    import sys

    from kaspa_tpu.node.daemon import rpc_call
    from kaspa_tpu.wallet.account import Account

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def free_ports(n):
        import socket

        socks = [socket.socket() for _ in range(n)]
        for s in socks:
            s.bind(("127.0.0.1", 0))
        ports = [s.getsockname()[1] for s in socks]
        for s in socks:
            s.close()
        return ports

    def spawn(name, rpc_port, p2p_port, connect=None):
        env = dict(os.environ)
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        argv = [
            sys.executable, "-m", "kaspa_tpu.node",
            "--appdir", str(tmp_path / name),
            "--rpclisten", f"127.0.0.1:{rpc_port}",
            "--listen", f"127.0.0.1:{p2p_port}",
            "--bps", "2",
            "--p2p-proto",
        ]
        if connect:
            argv += ["--connect", connect]
        return subprocess.Popen(argv, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)

    def wait_rpc(addr, timeout=90.0):
        deadline = time.monotonic() + timeout
        last = None
        while time.monotonic() < deadline:
            try:
                return rpc_call(addr, "getServerInfo")
            except Exception as e:  # noqa: BLE001
                last = e
                time.sleep(0.3)
        raise TimeoutError(f"rpc at {addr} not up: {last}")

    rpc_a, p2p_a, rpc_b = free_ports(3)
    addr_a, addr_b = f"127.0.0.1:{rpc_a}", f"127.0.0.1:{rpc_b}"
    pay = Account.from_seed(b"\x02" * 32, prefix="kaspasim").addresses()[0]
    proc_a = proc_b = None
    try:
        proc_a = spawn("a", rpc_a, p2p_a)
        wait_rpc(addr_a)
        for _ in range(4):
            t = rpc_call(addr_a, "getBlockTemplate", {"payAddress": pay})
            rpc_call(addr_a, "submitBlockByTemplateHash", {"hash": t["block_hash"]})
        sink_a = rpc_call(addr_a, "getBlockDagInfo")["sink"]

        proc_b = spawn("b", rpc_b, 0, connect=f"127.0.0.1:{p2p_a}")
        wait_rpc(addr_b)
        assert _wait(
            lambda: rpc_call(addr_b, "getBlockDagInfo")["sink"] == sink_a, timeout=120
        ), "IBD over --p2p-proto wire did not converge"

        # relay direction B -> A over the proto wire
        t = rpc_call(addr_b, "getBlockTemplate", {"payAddress": pay})
        rpc_call(addr_b, "submitBlockByTemplateHash", {"hash": t["block_hash"]})
        sink_b = rpc_call(addr_b, "getBlockDagInfo")["sink"]
        assert _wait(
            lambda: rpc_call(addr_a, "getBlockDagInfo")["sink"] == sink_b, timeout=60
        ), "relay over --p2p-proto wire failed"
    finally:
        for proc in (proc_a, proc_b):
            if proc is not None:
                proc.terminate()
                try:
                    proc.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    proc.kill()
