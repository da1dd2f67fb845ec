"""Two OS processes forming a network over the binary P2P wire.

The round-1 gap this closes: P2P existed only as in-process objects.  Here
two real daemon processes handshake over TCP (version/verack), the second
catches up via IBD, and subsequent blocks propagate by inv/request relay —
the integration shape of the reference's testing/integration daemon tests
over protocol/p2p's gRPC wire.
"""

import os
import subprocess
import sys
import time

import pytest

from kaspa_tpu.node.daemon import rpc_call

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn_daemon(tmp_path, name, rpc_port, p2p_port, connect=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    argv = [
        sys.executable, "-m", "kaspa_tpu.node",
        "--appdir", str(tmp_path / name),
        "--rpclisten", f"127.0.0.1:{rpc_port}",
        "--listen", f"127.0.0.1:{p2p_port}",
        "--bps", "2",
    ]
    if connect:
        argv += ["--connect", connect]
    proc = subprocess.Popen(argv, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    return proc


def _wait_rpc(addr, timeout=90.0):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            return rpc_call(addr, "getServerInfo")
        except Exception as e:  # noqa: BLE001
            last = e
            time.sleep(0.3)
    raise TimeoutError(f"rpc at {addr} not up: {last}")


def _free_ports(n):
    import socket

    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports



def test_two_process_network_converges(tmp_path):
    from kaspa_tpu.wallet.account import Account

    rpc_a, p2p_a, rpc_b, p2p_b = _free_ports(4)
    addr_a, addr_b = f"127.0.0.1:{rpc_a}", f"127.0.0.1:{rpc_b}"
    pay = Account.from_seed(b"\x02" * 32, prefix="kaspasim").addresses()[0]

    proc_a = proc_b = None
    try:
        proc_a = _spawn_daemon(tmp_path, "a", rpc_a, p2p_a)
        _wait_rpc(addr_a)
        # seed node A with a chain over its own RPC wire
        for _ in range(8):
            t = rpc_call(addr_a, "getBlockTemplate", {"payAddress": pay})
            rpc_call(addr_a, "submitBlockByTemplateHash", {"hash": t["block_hash"]})
        dag_a = rpc_call(addr_a, "getBlockDagInfo")
        assert dag_a["virtual_daa_score"] == 8

        # node B dials A and IBDs the chain
        proc_b = _spawn_daemon(tmp_path, "b", rpc_b, p2p_b, connect=f"127.0.0.1:{p2p_a}")
        _wait_rpc(addr_b)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            dag_b = rpc_call(addr_b, "getBlockDagInfo")
            if dag_b["sink"] == dag_a["sink"]:
                break
            time.sleep(0.5)
        assert dag_b["sink"] == dag_a["sink"], f"IBD did not converge: {dag_b} vs {dag_a}"

        # mine on B; the block must relay to A over the wire
        t = rpc_call(addr_b, "getBlockTemplate", {"payAddress": pay})
        rpc_call(addr_b, "submitBlockByTemplateHash", {"hash": t["block_hash"]})
        sink_b = rpc_call(addr_b, "getBlockDagInfo")["sink"]
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if rpc_call(addr_a, "getBlockDagInfo")["sink"] == sink_b:
                break
            time.sleep(0.3)
        assert rpc_call(addr_a, "getBlockDagInfo")["sink"] == sink_b, "relay A<-B failed"

        # and the reverse direction
        t = rpc_call(addr_a, "getBlockTemplate", {"payAddress": pay})
        rpc_call(addr_a, "submitBlockByTemplateHash", {"hash": t["block_hash"]})
        sink_a = rpc_call(addr_a, "getBlockDagInfo")["sink"]
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if rpc_call(addr_b, "getBlockDagInfo")["sink"] == sink_a:
                break
            time.sleep(0.3)
        assert rpc_call(addr_b, "getBlockDagInfo")["sink"] == sink_a, "relay B<-A failed"
    finally:
        for proc in (proc_a, proc_b):
            if proc is not None:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()


def test_wire_codec_roundtrip():
    import random

    from kaspa_tpu.p2p import wire
    from kaspa_tpu.p2p.node import (
        MSG_BLOCK,
        MSG_INV_BLOCK,
        MSG_INV_TXS,
        MSG_VERSION,
    )
    from tests.test_serde import _rand_header, _rand_tx

    rng = random.Random(3)

    def roundtrip(msg_type, payload):
        frame = wire.encode_frame(msg_type, payload)
        pos = [0]

        def read_exactly(n):
            out = frame[pos[0] : pos[0] + n]
            assert len(out) == n
            pos[0] += n
            return out

        name, decoded = wire.read_message(read_exactly)
        assert name == msg_type
        assert pos[0] == len(frame)
        return decoded

    v = {"protocol_version": 7, "network": "kaspa-simnet", "listen_port": 16111, "id": 99}
    assert roundtrip(MSG_VERSION, v) == v
    h = rng.randbytes(32)
    assert roundtrip(MSG_INV_BLOCK, h) == h
    ids = [rng.randbytes(32) for _ in range(5)]
    assert roundtrip(MSG_INV_TXS, ids) == ids
    from kaspa_tpu.consensus.model.block import Block

    blk = Block(_rand_header(rng), [_rand_tx(rng) for _ in range(3)])
    out = roundtrip(MSG_BLOCK, blk)
    assert out.header == blk.header and out.transactions == blk.transactions

    # adversarial: bad magic / unknown type / oversized must raise WireError
    import pytest as _pytest

    with _pytest.raises(wire.WireError):
        wire.decode_frame(b"XX\x00\x00\x00\x00\x00")
    with _pytest.raises(wire.WireError):
        wire.decode_frame(wire.MAGIC + b"\xff\x00\x00\x00\x00")
    with _pytest.raises(wire.WireError):
        wire.decode_frame(wire.MAGIC + b"\x02\xff\xff\xff\xff")
