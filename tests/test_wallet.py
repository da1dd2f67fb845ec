"""Wallet tests: BIP32 golden vectors + account send round-trip."""

import pytest

from kaspa_tpu.wallet import Account, ExtendedKey


def test_bip32_vector1():
    """BIP32 test vector 1 (seed 000102...0f): checked via public keys,
    which pin down the full (key, chain code) derivation state."""
    seed = bytes.fromhex("000102030405060708090a0b0c0d0e0f")
    m = ExtendedKey.from_seed(seed)
    assert m.public_key().hex() == "0339a36013301597daef41fbe593a02cc513d0b55527ec2df1050e2e8ff49c85c2"
    # m/0'
    m0h = m.derive_path("m/0'")
    assert m0h.public_key().hex() == "035a784662a4a20a65bf6aab9ae98a6c068a81c52e4b032c0fb5400c706cfccc56"
    # m/0'/1
    m0h1 = m0h.derive_child(1)
    assert m0h1.public_key().hex() == "03501e454bf00751f24b1b489aa925215d66af2234e3891c3b21a52bedb3cd711c"
    # m/0'/1/2'/2/1000000000
    deep = m.derive_path("m/0'/1/2'/2/1000000000")
    assert deep.public_key().hex() == "022a471424da5e657499d1ff51cb43c47481a03b1e77f951fe64cec9f5a48f7011"


def test_bip32_vector2_deep():
    seed = bytes.fromhex(
        "fffcf9f6f3f0edeae7e4e1dedbd8d5d2cfccc9c6c3c0bdbab7b4b1aeaba8a5a29f9c999693908d8a8784817e7b7875726f6c696663605d5a5754514e4b484542"
    )
    m = ExtendedKey.from_seed(seed)
    assert m.public_key().hex() == "03cbcaa9c98c877a26977d00825c956a238e8dddfbd322cce4f74b0b5bd6ace4a7"
    node = m.derive_path("m/0/2147483647'/1/2147483646'/2")
    assert node.public_key().hex() == "024d902e1a2fc7a8755ab5b694c575fce742c48d9ff192e63df5193e4c7afe1f9c"


def test_account_send_roundtrip():
    """Mine to a wallet address, then send with change and confirm balances."""
    import random

    from kaspa_tpu.consensus.consensus import Consensus
    from kaspa_tpu.consensus.params import simnet_params
    from kaspa_tpu.consensus.processes.coinbase import MinerData
    from kaspa_tpu.index import UtxoIndex
    from kaspa_tpu.mempool import MiningManager

    params = simnet_params(bps=2)
    c = Consensus(params)
    index = UtxoIndex(c)
    mgr = MiningManager(c)

    wallet = Account.from_seed(b"test seed for round trip", prefix="kaspasim")
    recv = wallet.receive_keys[0]
    miner_data = MinerData(recv.spk, b"wallet-miner")
    for _ in range(12):  # mature some rewards (simnet maturity = 8)
        blk = mgr.get_block_template(miner_data)
        c.validate_and_insert_block(blk)
        mgr.handle_new_block_transactions(blk.transactions, c.get_virtual_daa_score())
        mgr.template_cache.clear()
    index.resync()
    balance = wallet.balance(index)
    assert balance > 0

    # send to a freshly derived second address
    dest = wallet.derive_receive_address()
    send_amount = balance // 4
    tx = wallet.build_send(
        index, dest.address.to_string(), send_amount, fee=2000,
        virtual_daa_score=c.get_virtual_daa_score(), coinbase_maturity=params.coinbase_maturity,
    )
    mgr.validate_and_insert_transaction(tx)
    blk = mgr.get_block_template(miner_data)
    assert any(t.id() == tx.id() for t in blk.transactions[1:])
    c.validate_and_insert_block(blk)
    mgr.handle_new_block_transactions(blk.transactions, c.get_virtual_daa_score())
    # a block's txs enter the chain UTXO state when a descendant merges it
    nxt = mgr.get_block_template(miner_data)
    c.validate_and_insert_block(nxt)
    index.resync()
    assert index.get_balance_by_script(dest.spk.script) == send_amount
    # insufficient funds raises
    from kaspa_tpu.wallet.account import WalletError

    with pytest.raises(WalletError):
        wallet.build_send(index, dest.address.to_string(), 10**18, fee=0,
                          virtual_daa_score=c.get_virtual_daa_score(), coinbase_maturity=params.coinbase_maturity)


def test_wallet_interactive_terminal(tmp_path):
    """The interactive terminal (reference cli/): a scripted session over a
    live daemon — help, addresses, node info, balance, live monitor of a
    mined coinbase, derived address, clean exit."""
    import random
    import subprocess
    import sys
    import threading
    import time

    from kaspa_tpu.node.daemon import Daemon, parse_args, rpc_call

    seed = tmp_path / "seed.bin"
    seed.write_bytes(b"\x5a" * 32)
    from kaspa_tpu.wallet import Account

    acct = Account.from_seed(b"\x5a" * 32, prefix="kaspasim")
    pay = acct.addresses()[0]

    args = parse_args(["--appdir", str(tmp_path / "node"), "--rpclisten", "127.0.0.1:0", "--bps", "2"])
    d = Daemon(args)
    addr = d.start()
    try:
        import os

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.Popen(
            [sys.executable, "-m", "kaspa_tpu.wallet", "--rpc", addr, "--seed-file", str(seed), "repl"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
        )

        def mine_soon():
            time.sleep(3)
            for _ in range(2):
                t = rpc_call(addr, "getBlockTemplate", {"payAddress": pay})
                rpc_call(addr, "submitBlockByTemplateHash", {"hash": t["block_hash"]})
                d.mining.template_cache.clear()

        miner = threading.Thread(target=mine_soon, daemon=True)
        miner.start()
        script = (
            f"help\naddress\nnode\ndag\nbalance\nutxos\nfee-rates\n"
            f"estimate {pay} 1\nmonitor 12\nnew-address\nbadcmd\nexit\n"
        )
        out, _ = proc.communicate(script, timeout=120)
        assert proc.returncode == 0
        assert "commands:" in out
        assert pay in out
        assert "network simnet" in out
        assert "sompi" in out
        assert "monitor done" in out and "pending=" in out
        assert "blocks " in out and "pruning-point" in out  # dag
        assert "spendable utxos" in out  # utxos listing
        assert "sompi/g" in out  # fee-rates buckets
        # estimate prints mass/fee pricing (or a clean insufficient-funds
        # message before any coinbase matured)
        assert ("relay fee floor" in out) or ("insufficient funds" in out)
        # the monitored coinbase arrived as a live pending event
        assert "[pending]" in out or "mature=" in out
        assert "unknown command 'badcmd'" in out
    finally:
        d.stop()
