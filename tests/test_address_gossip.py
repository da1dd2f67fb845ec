"""Three-node address bootstrap + locator sync (round-3 review item #7).

Reference: protocol/flows/src/v7/address.rs (RequestAddresses /
SendAddresses) + connectionmanager: node C explicitly connects only to B,
learns A's listen address through B's gossip, dials A via its connection
manager, and — after B goes away — still receives A's new branch, which it
can only do because of the gossip bootstrap.  Block transfer along the way
runs the exponential block-locator negotiation (sync/mod.rs), not a full
inventory exchange.
"""

import os
import subprocess
import sys
import time

from kaspa_tpu.node.daemon import rpc_call

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def _wait(cond, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.4)
    raise AssertionError(f"timed out waiting for {what}")


def _mine(addr, pay, n):
    for _ in range(n):
        t = rpc_call(addr, "getBlockTemplate", {"payAddress": pay})
        rpc_call(addr, "submitBlockByTemplateHash", {"hash": t["block_hash"]})


def test_three_node_gossip_bootstrap(tmp_path):
    from kaspa_tpu.wallet.account import Account

    import socket

    socks, ports = [], []
    for _ in range(6):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    rpc_a, p2p_a, rpc_b, p2p_b, rpc_c, p2p_c = ports
    addr_a, addr_b, addr_c = (f"127.0.0.1:{p}" for p in (rpc_a, rpc_b, rpc_c))
    pay = Account.from_seed(b"\x03" * 32, prefix="kaspasim").addresses()[0]

    procs = {}
    try:
        procs["a"] = _spawn(tmp_path, "a", rpc_a, p2p_a)
        _wait_rpc(addr_a)
        _mine(addr_a, pay, 8)
        sink_a = rpc_call(addr_a, "getBlockDagInfo")["sink"]

        procs["b"] = _spawn(tmp_path, "b", rpc_b, p2p_b, connect=f"127.0.0.1:{p2p_a}")
        _wait_rpc(addr_b)
        _wait(lambda: rpc_call(addr_b, "getBlockDagInfo")["sink"] == sink_a, 90, "B<-A IBD")

        # C connects ONLY to B; gossip must teach it A's address
        procs["c"] = _spawn(tmp_path, "c", rpc_c, p2p_c, connect=f"127.0.0.1:{p2p_b}")
        _wait_rpc(addr_c)
        _wait(lambda: rpc_call(addr_c, "getBlockDagInfo")["sink"] == sink_a, 90, "C<-B locator sync")
        # A's listen address arrived via B's SendAddresses
        _wait(
            lambda: f"127.0.0.1:{p2p_a}" in rpc_call(addr_c, "getPeerAddresses")["known_addresses"],
            60,
            "C learning A's address via gossip",
        )
        # C's connection manager dials A from the gossiped address book
        _wait(
            lambda: any(
                p["address"] == f"127.0.0.1:{p2p_a}"
                for p in rpc_call(addr_c, "getConnectedPeerInfo")
            ),
            60,
            "C dialing A from the address book",
        )

        # partition: B leaves; A extends the chain; C must still follow via
        # its gossip-learned connection to A
        procs.pop("b").terminate()
        _mine(addr_a, pay, 4)
        sink_a2 = rpc_call(addr_a, "getBlockDagInfo")["sink"]
        assert sink_a2 != sink_a
        _wait(lambda: rpc_call(addr_c, "getBlockDagInfo")["sink"] == sink_a2, 90, "C following A's branch")
    finally:
        for proc in procs.values():
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def test_dns_seeding(tmp_path):
    """--dnsseed resolves hostnames into the address book at startup
    (flow_context dnsseed bootstrap)."""
    from kaspa_tpu.node.daemon import Daemon, parse_args

    args = parse_args(
        ["--appdir", str(tmp_path), "--rpclisten", "127.0.0.1:0", "--no-persist",
         "--dnsseed", "localhost:16333", "--dnsseed", "no-such-host.invalid"]
    )
    d = Daemon(args)
    d.start()
    try:
        # seeding runs on a background thread so startup never blocks on DNS
        _wait(
            lambda: "127.0.0.1:16333" in [str(a) for a in d.address_manager.get_all_addresses()],
            10,
            "dns seed resolution",
        )
        known = [str(a) for a in d.address_manager.get_all_addresses()]
        assert not any("invalid" in a for a in known)  # failures skipped
    finally:
        d.stop()
