"""m-of-n multisig on the batch path (txscript/batch.py, the third lane).

A P2SH input whose redeem script is the canonical ``<m> <key>*n <n>
OpCheckMultiSig[ECDSA]`` has its candidate (signature, key) pairs verified in
the device batch (here: the XLA ladder at bucket 8 on the CPU) and the
engine's key-order walk replayed over the answers.  These tests pin that the
token -> first error mapping, message included, is what the serial host VM
gives, that canonical valid inputs never reach the VM lane, and that the
counters and the span of the lane move as documented.
"""

import random

import pytest

from kaspa_tpu.consensus import hashing as chash
from kaspa_tpu.consensus.model import (
    SUBNETWORK_ID_NATIVE,
    ComputeCommit,
    Transaction,
    TransactionInput,
    TransactionOutpoint,
    TransactionOutput,
    UtxoEntry,
)
from kaspa_tpu.consensus.params import simnet_params
from kaspa_tpu.consensus.processes.transaction_validator import TransactionValidator
from kaspa_tpu.crypto import eclib
from kaspa_tpu.crypto.secp import schnorr_challenge
from kaspa_tpu.observability import trace
from kaspa_tpu.observability.core import REGISTRY
from kaspa_tpu.ops import dispatch as coalesce
from kaspa_tpu.txscript import standard
from kaspa_tpu.txscript.caches import VerdictMemo
from kaspa_tpu.txscript.script_builder import ScriptBuilder

PARAMS = simnet_params()
ALL = chash.SIG_HASH_ALL


class _Key:
    """A key with a running nonce point (k += 1, R += G): a signature costs
    one point addition, not a pure-Python scalar ladder."""

    def __init__(self, rng):
        d = rng.randrange(1, eclib.N)
        pub = eclib.point_mul(eclib.G, d)
        self.d_ecdsa, self.pub33 = d, bytes([2 + (pub[1] & 1)]) + pub[0].to_bytes(32, "big")
        self.d = d if pub[1] % 2 == 0 else eclib.N - d  # BIP340: even-y key
        self.pub32 = pub[0].to_bytes(32, "big")
        self.k = rng.randrange(1, eclib.N >> 1)
        self.R = eclib.point_mul(eclib.G, self.k)

    def _next(self):
        self.k += 1
        self.R = eclib.point_add(self.R, eclib.G)

    def schnorr(self, msg: bytes) -> bytes:
        self._next()
        kk = self.k if self.R[1] % 2 == 0 else eclib.N - self.k
        r = self.R[0].to_bytes(32, "big")
        return r + ((kk + schnorr_challenge(r, self.pub32, msg) * self.d) % eclib.N).to_bytes(32, "big")

    def ecdsa(self, msg: bytes) -> bytes:
        self._next()
        r = self.R[0] % eclib.N
        s = pow(self.k, -1, eclib.N) * (int.from_bytes(msg, "big") + r * self.d_ecdsa) % eclib.N
        return r.to_bytes(32, "big") + min(s, eclib.N - s).to_bytes(32, "big")  # low s

    def sign(self, msg: bytes, ecdsa: bool) -> bytes:
        return self.ecdsa(msg) if ecdsa else self.schnorr(msg)

    def pub(self, ecdsa: bool) -> bytes:
        return self.pub33 if ecdsa else self.pub32


_RNG = random.Random(0x29)
KEYS = [_Key(_RNG) for _ in range(6)]  # 0..4 sit in redeem scripts, 5 is the outsider


@pytest.fixture(autouse=True)
def _sync_device_lane():
    coalesce.configure(0)
    yield
    coalesce.configure(0)


def _counters():
    return REGISTRY.snapshot()["counters"]


def _moved(before, name, label=None):
    """Movement of a counter (a family: of one label, or of all of them)."""
    def read(snapshot):
        v = snapshot.get(name, 0)
        if isinstance(v, dict):
            return v.get(label, 0) if label is not None else sum(v.values())
        return v

    return read(_counters()) - read(before)


def _sig_script(blobs, redeem: bytes) -> bytes:
    b = ScriptBuilder()
    for blob in blobs:
        b.add_data(blob)
    return b.add_data(redeem).drain()


def _tx(inputs):
    """inputs: [(spk, commit)] -> (tx with empty signature scripts, entries)."""
    entries = [UtxoEntry(10_000 + i, spk, 5, False) for i, (spk, _c) in enumerate(inputs)]
    tx = Transaction(
        0,
        [TransactionInput(TransactionOutpoint(bytes([7, i]) * 16, i), b"", 0, ComputeCommit.sigops(c)) for i, (_s, c) in enumerate(inputs)],
        [TransactionOutput(9_000, standard.pay_to_pub_key(KEYS[0].pub32))], 0, SUBNETWORK_ID_NATIVE, 0, b"",
    )
    return tx, entries


def _msg(tx, entries, i, ecdsa, hash_type=ALL):
    fn = chash.calc_ecdsa_signature_hash if ecdsa else chash.calc_schnorr_signature_hash
    return fn(tx, entries, i, hash_type, chash.SigHashReusedValues())


def _redeem(m, n, ecdsa):
    pubs = [k.pub(ecdsa) for k in KEYS[:n]]
    return standard.multisig_redeem_script_ecdsa(pubs, m) if ecdsa else standard.multisig_redeem_script(pubs, m)


def _multisig_spend(m, n, ecdsa, signers, commit=None, spoil=None):
    """One-input spend of an m-of-n P2SH output signed by ``signers`` (key
    indices, in the order their signatures are pushed)."""
    redeem = _redeem(m, n, ecdsa)
    spk = standard.pay_to_script_hash_script(redeem)
    tx, entries = _tx([(spk, n if commit is None else commit)])
    hash_type = 0x55 if spoil == "hash_type" else ALL
    msg = _msg(tx, entries, 0, ecdsa)
    blobs = [KEYS[k].sign(msg, ecdsa) + bytes([hash_type]) for k in signers]
    if spoil == "flipped_byte":
        blobs[-1] = blobs[-1][:40] + bytes([blobs[-1][40] ^ 0x20]) + blobs[-1][41:]
    elif spoil == "empty_signature":
        blobs[0] = b""
    script = _sig_script(blobs, redeem)
    if spoil == "noncanonical_push":  # the first signature behind OP_PUSHDATA1, not a direct push
        script = bytes([0x4C, 65]) + blobs[0] + _sig_script(blobs[1:], redeem)
    elif spoil == "redeem_hash":
        script = _sig_script(blobs, _redeem(m, n, ecdsa)[:-1] + b"\xac")  # another script than the output commits to
    tx.inputs[0].signature_script = script
    return tx, entries


def _batch(txs, tv=None):
    """token -> None | (input index, message) through BatchScriptChecker with
    the validator's own VM lane wired and a fresh signature cache."""
    checker = (tv or TransactionValidator(PARAMS)).new_checker()
    for token, (tx, entries) in enumerate(txs):
        checker.collect_tx(token, tx, entries, pov_daa_score=10)
    return {t: None if e is None else (e.input_index, str(e)) for t, e in checker.dispatch().items()}


def _vm_error(tx, entries, i, tv=None):
    """What the host VM alone says of input ``i``: None | (i, message)."""
    try:
        (tv or TransactionValidator(PARAMS)).vm_fallback(tx, entries, i, chash.SigHashReusedValues(), 10)
    except Exception as e:  # noqa: BLE001 - the VM raises on an invalid script
        return (i, str(e))
    return None


def _serial_vm(txs):
    """The same through the host VM alone, input by input."""
    tv = TransactionValidator(PARAMS)
    return {
        token: next((err for err in (_vm_error(tx, entries, i, tv) for i in range(len(tx.inputs))) if err), None)
        for token, (tx, entries) in enumerate(txs)
    }


M_OF_N = [(m, n) for n in range(1, 6) for m in range(1, n + 1)]


@pytest.mark.parametrize("ecdsa", [False, True], ids=["schnorr", "ecdsa"])
@pytest.mark.parametrize("m,n", M_OF_N, ids=[f"{m}of{n}" for m, n in M_OF_N])
def test_valid_m_of_n_takes_the_batch_path(m, n, ecdsa):
    rng = random.Random(1000 * n + 10 * m + ecdsa)
    signers = sorted(rng.sample(range(n), m))
    spend = _multisig_spend(m, n, ecdsa, signers)
    before = _counters()
    assert _batch([spend]) == {0: None}
    pairs = m * (n - m + 1)
    assert _moved(before, "txscript_vm_fallbacks") == 0
    assert _moved(before, "txscript_multisig_vm_reruns") == 0
    assert _moved(before, "txscript_batch_jobs", "ecdsa" if ecdsa else "schnorr") == pairs
    assert _moved(before, "txscript_batch_jobs") == pairs == _moved(before, "secp_device_jobs")
    assert _moved(before, "secp_device_ecdsa_jobs") == (pairs if ecdsa else 0)
    assert _moved(before, "txscript_multisig_inputs") == 1 and _moved(before, "txscript_multisig_pairs") == pairs
    assert _moved(before, "txscript_p2sh_inputs", "batch") == 1 and _moved(before, "txscript_p2sh_inputs", "vm") == 0
    assert _moved(before, "secp_degraded_jobs") == 0
    assert _serial_vm([spend]) == {0: None}


# kind -> (keywords of _multisig_spend for a 2-of-3, does the input still take the batch path)
REJECTED = {
    "out_of_key_order": ({"signers": [2, 0]}, True),
    "key_outside_the_script": ({"signers": [0, 5]}, True),
    "flipped_signature_byte": ({"signers": [0, 2], "spoil": "flipped_byte"}, True),
    "sig_op_commit_one_short": ({"signers": [0, 2], "commit": 2}, True),
    "empty_signature": ({"signers": [0, 1], "spoil": "empty_signature"}, False),
    "hash_type_not_allowed": ({"signers": [0, 1], "spoil": "hash_type"}, False),
    "noncanonical_push": ({"signers": [0, 1], "spoil": "noncanonical_push"}, False),
    "redeem_hash_differs": ({"signers": [0, 1], "spoil": "redeem_hash"}, False),
}


@pytest.mark.parametrize("ecdsa", [False, True], ids=["schnorr", "ecdsa"])
@pytest.mark.parametrize("kind", sorted(REJECTED))
def test_rejection_is_the_serial_vms(kind, ecdsa):
    kw, batched = REJECTED[kind]
    spend = _multisig_spend(2, 3, ecdsa, **kw)
    before = _counters()
    got = _batch([spend])
    assert got[0] is not None and got[0][0] == 0
    assert got == _serial_vm([spend])
    assert _moved(before, "txscript_p2sh_inputs", "batch" if batched else "vm") == 1
    assert _moved(before, "txscript_vm_fallbacks") == (0 if batched else 1)
    assert _moved(before, "txscript_multisig_vm_reruns") == (1 if batched else 0)
    assert _moved(before, "txscript_multisig_pairs") == (4 if batched else 0)


def test_commit_that_covers_the_walk_is_accepted():
    """Signed by the first two keys, a 2-of-3 charges two sig ops: a commit
    of 2 covers it; signed by the first and the last it charges three."""
    assert _batch([_multisig_spend(2, 3, False, [0, 1], commit=2)]) == {0: None}
    short = _multisig_spend(2, 3, False, [0, 2], commit=2)
    assert _batch([short]) == _serial_vm([short]) == {0: (0, "exceeded sig op limit of 2")}


def test_key_that_is_no_curve_point_is_the_vms_to_refuse():
    """The engine raises on an invalid key it walks past; the device only
    says False, so the walk hands the input to the VM."""
    bad = next(x.to_bytes(32, "big") for x in range(2, 50) if eclib.lift_x(x) is None)
    redeem = standard.multisig_redeem_script([KEYS[0].pub32, bad, KEYS[2].pub32], 2)
    tx, entries = _tx([(standard.pay_to_script_hash_script(redeem), 3)])
    msg = _msg(tx, entries, 0, False)
    tx.inputs[0].signature_script = _sig_script([KEYS[k].schnorr(msg) + bytes([ALL]) for k in (0, 2)], redeem)
    before = _counters()
    got = _batch([(tx, entries)])
    assert got == _serial_vm([(tx, entries)]) == {0: (0, "invalid public key")}
    assert _moved(before, "txscript_multisig_vm_reruns") == 1 and _moved(before, "txscript_vm_fallbacks") == 0


def _p2pk_input(ecdsa):
    key = KEYS[3]
    return standard.pay_to_pub_key_ecdsa(key.pub33) if ecdsa else standard.pay_to_pub_key(key.pub32)


def _mixed_tx(classes, bad=()):
    """A transaction whose inputs are of ``classes`` ("schnorr" | "ecdsa" |
    "multisig"); inputs listed in ``bad`` carry a signature with one flipped
    byte (the multisig's second one)."""
    redeem = _redeem(2, 3, False)
    spks = {"schnorr": _p2pk_input(False), "ecdsa": _p2pk_input(True), "multisig": standard.pay_to_script_hash_script(redeem)}
    tx, entries = _tx([(spks[c], 3 if c == "multisig" else 1) for c in classes])
    for i, c in enumerate(classes):
        msg = _msg(tx, entries, i, c == "ecdsa")
        flip = (lambda s: s[:50] + bytes([s[50] ^ 1]) + s[51:]) if i in bad else (lambda s: s)
        if c == "multisig":
            blobs = [KEYS[0].schnorr(msg) + bytes([ALL]), flip(KEYS[2].schnorr(msg)) + bytes([ALL])]
            tx.inputs[i].signature_script = _sig_script(blobs, redeem)
        else:
            tx.inputs[i].signature_script = standard.schnorr_signature_script(flip(KEYS[3].sign(msg, c == "ecdsa")), ALL)
    return tx, entries


def test_mixed_block_keeps_the_serial_order():
    """All three classes in one block, failures in two transactions.  The
    serial path ran the VM at collect time and the device batch at dispatch:
    a VM-lane failure owns the first-error slot over a batch failure of the
    same transaction, batch failures go Schnorr first, then ECDSA."""
    block = [
        _mixed_tx(["schnorr", "ecdsa", "multisig"]),
        _mixed_tx(["schnorr", "multisig", "ecdsa"], bad=(0, 1)),  # the multisig (input 1) wins over the P2PK (input 0)
        _mixed_tx(["multisig", "multisig", "schnorr"]),
        _mixed_tx(["ecdsa", "schnorr", "multisig"], bad=(0, 1)),  # no VM-lane failure: Schnorr (input 1) before ECDSA (input 0)
        _mixed_tx(["ecdsa", "multisig"]),
    ]
    before = _counters()
    got = _batch(block)
    assert got[0] is None and got[2] is None and got[4] is None
    assert got[1] == _vm_error(*block[1], 1)  # the VM's verdict and message on input 1
    assert got[1][0] == 1 and "not all signatures empty" in got[1][1]
    assert got[3] == (1, "invalid signature")
    assert _moved(before, "txscript_vm_fallbacks") == 0 and _moved(before, "txscript_multisig_vm_reruns") == 1
    assert _moved(before, "txscript_multisig_inputs") == 6 and _moved(before, "txscript_multisig_pairs") == 24
    # Σ txscript_batch_jobs = secp_device_jobs: what the benchmark's ledger holds at limit 0
    assert _moved(before, "txscript_batch_jobs") == _moved(before, "secp_device_jobs") == 24 + 8
    assert _moved(before, "secp_device_ecdsa_jobs") == _moved(before, "txscript_batch_jobs", "ecdsa") == 4


def test_mixed_block_identical_with_coalescing_on():
    block = [_mixed_tx(["schnorr", "multisig", "ecdsa"], bad=(1,)), _mixed_tx(["multisig", "ecdsa"])]
    legacy = _batch(block)
    coalesce.configure(16)
    assert _batch(block) == legacy
    assert legacy[0][0] == 1 and legacy[1] is None


def test_cache_answers_are_not_sent_again():
    """A second validation of the same spend through the same validator (as
    the virtual stage does after a stage worker) sends nothing: the verdict
    memo answers the transaction, and where the memo no longer holds it the
    signature cache is asked for every pair."""
    tv = TransactionValidator(PARAMS)
    spend = _multisig_spend(2, 3, False, [0, 2])
    assert _batch([spend], tv) == {0: None}
    before = _counters()
    assert _batch([spend], tv) == {0: None}
    assert _moved(before, "txscript_batch_jobs") == 0 and _moved(before, "secp_device_jobs") == 0
    assert _moved(before, "txscript_tx_memo_hits") == 1 and _moved(before, "txscript_multisig_inputs") == 0
    assert _moved(before, "txscript_sig_cache_block_lookups") == _moved(before, "txscript_sig_cache_block_hits") == 4
    tv.tx_memo = VerdictMemo()  # the memo evicted it
    before = _counters()
    assert _batch([spend], tv) == {0: None}
    assert _moved(before, "txscript_batch_jobs") == 0 and _moved(before, "secp_device_jobs") == 0
    assert _moved(before, "txscript_batch_sigcache_skips") == 4 and _moved(before, "txscript_tx_memo_hits") == 0
    assert _moved(before, "txscript_multisig_inputs") == 1 and _moved(before, "txscript_multisig_pairs") == 4


def test_flipped_device_answer_refuses_the_honest_spend():
    """The device's answer stays the authority: with one True lane of the
    mask altered (benchmarks/control.py's flip_one_answer) the VM re-run reads
    the altered answer in the cache and refuses the spend."""
    import functools

    import numpy as np

    from kaspa_tpu.crypto import secp

    real = secp.schnorr_verify

    @functools.wraps(real)
    def altered(px, py, rc, k1, k2, valid_in):
        mask = np.asarray(real(px, py, rc, k1, k2, valid_in)).copy()
        mask[int(mask.argmax())] = False
        return mask

    spend = _multisig_spend(2, 3, False, [0, 1])
    secp.schnorr_verify = altered
    try:
        got = _batch([spend])
    finally:
        secp.schnorr_verify = real
    assert got[0] is not None and "not all signatures empty" in got[0][1]


def test_spans_of_the_lane():
    trace.set_capture(1 << 12)
    trace.drain()
    try:
        _batch([_multisig_spend(2, 3, False, [0, 1]), _multisig_spend(2, 3, True, [1, 0])])
        spans = [s for s in trace.drain() if s["name"] == "txscript.multisig_resolve"]
    finally:
        trace.set_capture(0)
    assert len(spans) == 1  # one a dispatch round, never one a job
    assert spans[0]["attrs"]["inputs"] == 2 and spans[0]["attrs"]["vm_reruns"] == 1


def test_collect_span_counts_multisig_inputs():
    """``txscript.collect`` (consensus._validate_transactions) carries the
    batch-path multisig inputs of the block beside its jobs."""
    checker = TransactionValidator(PARAMS).new_checker()
    assert checker.queued_multisig_inputs() == 0
    checker.collect_tx(0, *_mixed_tx(["multisig", "schnorr", "multisig"]), pov_daa_score=10)
    assert checker.queued_multisig_inputs() == 2 and checker.queued_jobs() == 9
    assert checker.dispatch() == {0: None}
    assert checker.queued_multisig_inputs() == 0


@pytest.mark.parametrize("script,parsed", [
    (standard.multisig_redeem_script([k.pub32 for k in KEYS[:3]], 2), (2, [k.pub32 for k in KEYS[:3]], False)),
    (standard.multisig_redeem_script_ecdsa([k.pub33 for k in KEYS[:2]], 1), (1, [k.pub33 for k in KEYS[:2]], True)),
    (standard.multisig_redeem_script([k.pub32 for k in KEYS[:3]], 2) + b"\x51", None),  # something after the check opcode
    (standard.multisig_redeem_script([k.pub32 for k in KEYS[:3]], 2)[:-1] + b"\xac", None),  # another last opcode
    (b"\x53" + standard.multisig_redeem_script([k.pub32 for k in KEYS[:2]], 2)[1:], None),  # m > n
    (b"\x00" + standard.multisig_redeem_script([k.pub32 for k in KEYS[:2]], 1)[1:], None),  # m = 0
    (bytes([0x51, 0x21]) + KEYS[0].pub33 + bytes([0x51, 0xAE]), None),  # a 33-byte key under the Schnorr opcode
    (b"", None),
], ids=["schnorr_2of3", "ecdsa_1of2", "trailing_opcode", "checksig", "m_over_n", "m_zero", "key_width", "empty"])
def test_parse_multisig_redeem(script, parsed):
    assert standard.parse_multisig_redeem(script) == parsed


@pytest.mark.parametrize("script,items", [
    (ScriptBuilder().add_data(b"a" * 65).add_data(b"b" * 102).add_data(b"c" * 300).drain(), [b"a" * 65, b"b" * 102, b"c" * 300]),
    (bytes([0x4C, 65]) + b"a" * 65, None),  # OP_PUSHDATA1 where a direct push is minimal
    (bytes([0x4D, 100, 0]) + b"a" * 100, None),  # OP_PUSHDATA2 where OP_PUSHDATA1 is minimal
    (bytes([65]) + b"a" * 64, None),  # truncated
    (bytes([0x00]), None),  # OP_0: an empty item is the engine's business
    (bytes([0x51]), None),  # a small-integer opcode is no data push
    (bytes([2, 1, 2, 0xAC]), None),  # not push only
    (b"", []),
], ids=["minimal", "pushdata1_short", "pushdata2_short", "truncated", "op_0", "op_1", "not_push_only", "empty"])
def test_parse_canonical_pushes(script, items):
    assert standard.parse_canonical_pushes(script) == items
