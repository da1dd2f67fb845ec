"""``reference_scripts.py`` against the program's host VM (golden-tested
against the upstream node's vectors) on seeded spends of every class the
``checkscripts-mix`` shape signs, spoiled ones included, and on the ECDSA
multisig opcode, which the cell leaves to the CPU tests."""

import importlib
import random

import pytest

from benchmarks import reference_scripts

mix = importlib.import_module("benchmarks.shapes.checkscripts-mix")


def _spend(wallet, classes, spoil_at=None, spoil=None, seed=1):
    """A transaction whose input i is of ``classes[i]``, signed by ``wallet``."""
    from kaspa_tpu.consensus import hashing as chash
    from kaspa_tpu.consensus.model import Transaction, TransactionInput, TransactionOutpoint, TransactionOutput, UtxoEntry
    from kaspa_tpu.consensus.model.tx import SUBNETWORK_ID_NATIVE, ComputeCommit

    rng = random.Random(seed)
    entries = [UtxoEntry(mix.VALUE + i, wallet.spk[c], 5, False) for i, c in enumerate(classes)]
    tx = Transaction(
        0, [TransactionInput(TransactionOutpoint(rng.randbytes(32), i), b"", 0, ComputeCommit.sigops(mix.sig_ops(c, i))) for i, c in enumerate(classes)],
        [TransactionOutput(sum(e.amount for e in entries) - mix.FEE, wallet.spk[classes[0]])], 0, SUBNETWORK_ID_NATIVE, 0, b"",
    )
    reused = chash.SigHashReusedValues()
    msgs = [wallet.sign_input(c, tx, entries, i, reused, spoil if i == spoil_at else None, rng)[0] for i, c in enumerate(classes)]
    return tx, entries, msgs


def _spent(entries):
    return [(e.amount, e.script_public_key.version, e.script_public_key.script) for e in entries]


def _vm_accepts(tx, entries, i) -> bool:
    from kaspa_tpu.consensus import hashing as chash
    from kaspa_tpu.consensus.params import simnet_params
    from kaspa_tpu.consensus.processes.transaction_validator import TransactionValidator

    try:
        TransactionValidator(simnet_params()).vm_fallback(tx, entries, i, chash.SigHashReusedValues(), 5)
    except Exception:  # noqa: BLE001 - the VM raises on an invalid script
        return False
    return True


@pytest.fixture(scope="module")
def wallet():
    return mix.Wallet(random.Random(0x5EED))


CASES = {  # name: (classes, spoiled input, spoil kind)
    "schnorr_merge": (["schnorr"] * 3, None, None),
    "ecdsa_merge": (["ecdsa"] * 3, None, None),
    "multisig_merge_every_signer_pair": (["multisig"] * 4, None, None),
    "mixed_classes": (["multisig", "ecdsa", "schnorr", "multisig"], None, None),
    "schnorr_input_not_first": (["schnorr"] * 3, 1, "schnorr_input_not_first"),
    "ecdsa_input": (["ecdsa"] * 2, 1, "ecdsa_input"),
    "multisig_second_signature": (["multisig"] * 3, 2, "multisig_second_signature"),
    "multisig_outside_key": (["multisig"] * 2, 1, "multisig_outside_key"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_sighash_and_verdict_against_the_programs_vm(wallet, case):
    classes, spoil_at, spoil = CASES[case]
    tx, entries, msgs = _spend(wallet, classes, spoil_at, spoil, seed=len(case))
    spent = _spent(entries)
    for i, c in enumerate(classes):
        sighash = reference_scripts.ecdsa_sighash if c == "ecdsa" else reference_scripts.reference.sighash
        assert sighash(tx, i, *spent[i]) == msgs[i]  # the program's own (chash), which signed it
        verdict = reference_scripts.input_verdict(tx, i, *spent[i])
        assert verdict == _vm_accepts(tx, entries, i) == (i != spoil_at)
    assert reference_scripts.spend_verdict(tx, spent) == (spoil_at is None)


def _ecdsa_multisig(wallet, signers, m=2, high_s=False):
    """One input spending an m-of-3 ECDSA multisig output of the wallet's keys."""
    from kaspa_tpu.consensus import hashing as chash
    from kaspa_tpu.consensus.model import Transaction, TransactionInput, TransactionOutpoint, TransactionOutput, UtxoEntry
    from kaspa_tpu.consensus.model.tx import SUBNETWORK_ID_NATIVE, ComputeCommit
    from kaspa_tpu.crypto import eclib
    from kaspa_tpu.txscript import standard
    from kaspa_tpu.txscript.script_builder import ScriptBuilder

    keys = wallet.multisig_keys
    redeem = standard.multisig_redeem_script_ecdsa([k.pub33 for k in keys], m)
    entries = [UtxoEntry(mix.VALUE, standard.pay_to_script_hash_script(redeem), 5, False)]
    tx = Transaction(0, [TransactionInput(TransactionOutpoint(bytes([9]) * 32, 0), b"", 0, ComputeCommit.sigops(3))],
                     [TransactionOutput(mix.VALUE - mix.FEE, wallet.spk["schnorr"])], 0, SUBNETWORK_ID_NATIVE, 0, b"")
    msg = chash.calc_ecdsa_signature_hash(tx, entries, 0, chash.SIG_HASH_ALL, chash.SigHashReusedValues())
    b = ScriptBuilder()
    for k in signers:
        sig = (wallet.outsider if k == "outsider" else keys[k]).ecdsa(msg)
        if high_s:
            sig = sig[:32] + (eclib.N - int.from_bytes(sig[32:], "big")).to_bytes(32, "big")
        b.add_data(sig + bytes([chash.SIG_HASH_ALL]))
    tx.inputs[0].signature_script = b.add_data(redeem).drain()
    return tx, entries, msg


@pytest.mark.parametrize("signers,m,high_s,valid", [
    ([0, 1], 2, False, True), ([0, 2], 2, False, True), ([1, 2], 2, False, True), ([2], 1, False, True), ([0, 1, 2], 3, False, True),
    ([1, 0], 2, False, False),  # out of key order
    ([0, "outsider"], 2, False, False),
    ([0, 1], 2, True, False),  # the low-s rule
    ([0], 2, False, False),  # a signature short
], ids=["keys_0_1", "keys_0_2", "keys_1_2", "one_of_3", "three_of_3", "out_of_order", "outside_key", "high_s", "one_short"])
def test_ecdsa_multisig_walk(wallet, signers, m, high_s, valid):
    tx, entries, msg = _ecdsa_multisig(wallet, signers, m, high_s)
    spent = _spent(entries)
    assert reference_scripts.ecdsa_sighash(tx, 0, *spent[0]) == msg
    assert reference_scripts.input_verdict(tx, 0, *spent[0]) == _vm_accepts(tx, entries, 0) == valid


def test_ecdsa_verify_against_eclib(wallet):
    from kaspa_tpu.crypto import eclib

    rng = random.Random(7)
    for _ in range(4):
        msg = rng.randbytes(32)
        sig = wallet.ecdsa_key.ecdsa(msg)
        flipped = sig[:40] + bytes([sig[40] ^ 4]) + sig[41:]
        for s in (sig, flipped, bytes(32) + sig[32:], sig[:32] + bytes(32)):
            assert reference_scripts.ecdsa_verify(wallet.ecdsa_key.pub33, msg, s) == eclib.ecdsa_verify(wallet.ecdsa_key.pub33, msg, s)
        assert reference_scripts.ecdsa_verify(wallet.ecdsa_key.pub33, msg, sig)
    assert not reference_scripts.ecdsa_verify(b"\x04" + wallet.ecdsa_key.pub33[1:], msg, sig)


def test_what_is_no_standard_spend_is_an_error(wallet):
    tx, entries, _ = _spend(wallet, ["schnorr"])
    spent = _spent(entries)
    with pytest.raises(ValueError):
        reference_scripts.input_verdict(tx, 0, spent[0][0], 0, b"\x51")  # an output of no standard class
    tx.inputs[0].signature_script = b"\x51"
    with pytest.raises(ValueError):
        reference_scripts.input_verdict(tx, 0, *spent[0])  # a signature script that is no data push


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import os

    tree = ast.parse(open(os.path.join(os.path.dirname(reference_scripts.__file__), "reference_scripts.py")).read())
    names = [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)] + \
            [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    assert names and not any(n.split(".")[0] == "kaspa_tpu" for n in names)
    assert set(n.split(".")[0] for n in names) <= {"__future__", "hashlib", "benchmarks"}
