"""Two-OS-process pruning-proof IBD over the binary wire.

The donor daemon mines past its (scaled-down) pruning depth so deep
history is actually deleted; a fresh joiner daemon then dials it and must
converge via proof + trusted data + PP-UTXO chunks + block sync, across
real sockets — the full trustless-join path end to end.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

import pytest

from kaspa_tpu.node.daemon import rpc_call

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

OVERRIDES = [
    "--override-pruning-depth", "60",
    "--override-finality-depth", "30",
    "--override-merge-depth", "15",
    "--override-proof-m", "10",
    "--override-window-scale", "12",
]


def _spawn(tmp_path, name, rpc_port, p2p_port, connect=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    argv = [
        sys.executable, "-m", "kaspa_tpu.node",
        "--appdir", str(tmp_path / name),
        "--rpclisten", f"127.0.0.1:{rpc_port}",
        "--listen", f"127.0.0.1:{p2p_port}",
        "--bps", "2",
        *OVERRIDES,
    ]
    if connect:
        argv += ["--connect", connect]
    return subprocess.Popen(argv, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


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


def test_two_process_proof_ibd(tmp_path):
    from kaspa_tpu.crypto.addresses import Address

    addr = Address("kaspasim", 0, bytes(32)).to_string()
    r1, p1, r2, p2 = _free_ports(4)
    donor = _spawn(tmp_path, "donor", r1, p1)
    joiner = None
    try:
        _wait_rpc(f"127.0.0.1:{r1}")
        for _ in range(160):
            t = rpc_call(f"127.0.0.1:{r1}", "getBlockTemplate", {"payAddress": addr})
            rpc_call(f"127.0.0.1:{r1}", "submitBlockByTemplateHash", {"hash": t["block_hash"]})
        dag = rpc_call(f"127.0.0.1:{r1}", "getBlockDagInfo")
        donor_sink = rpc_call(f"127.0.0.1:{r1}", "getSink")
        # pruning actually happened donor-side
        counts = rpc_call(f"127.0.0.1:{r1}", "getBlockCount")
        assert counts["block_count"] < 160, counts

        joiner = _spawn(tmp_path, "joiner", r2, p2, connect=f"127.0.0.1:{p1}")
        _wait_rpc(f"127.0.0.1:{r2}")
        deadline = time.monotonic() + 120
        sink2 = None
        while time.monotonic() < deadline:
            try:
                sink2 = rpc_call(f"127.0.0.1:{r2}", "getSink")
                if sink2 == donor_sink:
                    break
            except Exception:  # noqa: BLE001
                pass
            time.sleep(0.5)
        assert sink2 == donor_sink, f"joiner never converged: {sink2} vs {donor_sink}"
        # mine on the joiner; block must relay back to the donor
        t = rpc_call(f"127.0.0.1:{r2}", "getBlockTemplate", {"payAddress": addr})
        rpc_call(f"127.0.0.1:{r2}", "submitBlockByTemplateHash", {"hash": t["block_hash"]})
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if rpc_call(f"127.0.0.1:{r1}", "getSink") == rpc_call(f"127.0.0.1:{r2}", "getSink"):
                break
            time.sleep(0.5)
        assert rpc_call(f"127.0.0.1:{r1}", "getSink") == rpc_call(f"127.0.0.1:{r2}", "getSink")
    finally:
        for proc, name in ((donor, "donor"), (joiner, "joiner")):
            if proc is None:
                continue
            proc.terminate()
            try:
                out = proc.communicate(timeout=10)[0]
            except subprocess.TimeoutExpired:
                proc.kill()
                out = proc.communicate()[0]
            if out and ("Traceback" in out or "Error" in out):
                print(f"--- {name} output tail ---\n{out[-1500:]}")
