"""The plain reference for the script classes of ``checkscripts-mix``: is a
spend of a pay-to-pubkey-ECDSA output, or of a pay-to-script-hash output whose
redeem script is an m-of-n multisig, validly signed?  Standard library only;
it imports ``benchmarks.reference`` (its ``sighash``, ``bip340_verify`` and
curve arithmetic) and nothing of ``kaspa_tpu``.  From the published rules:

- ``ecdsa_sighash``: what an ECDSA signature signs: SHA-256, prefixed with
  SHA-256("TransactionSigningHashECDSA"), over the Schnorr signing hash.
- ``ecdsa_verify``: ECDSA over secp256k1 on a 33-byte compressed key and a
  64-byte compact signature (r, s), with the low-s rule (s <= n / 2) that
  libsecp256k1's verification of a non-normalised signature enforces.
- pay-to-script-hash: the last item the signature script pushes is the redeem
  script, and its Blake2b-256 is the hash in the output.
- ``multisig_walk``: OpCheckMultiSig[ECDSA]: signatures in key order, each key
  tried at most once, failure as soon as fewer keys than signatures remain.

``spend_verdict`` puts them together for every input of a transaction;
``benchmarks/shapes/checkscripts-mix.py`` asks it, while it builds, for the
verdict on every spoiled spend and on a seeded sample of honest ones.

Where this departs from the reference node's engine (each departure narrows
what is answered, none changes an answer): only SIGHASH_ALL on version-0
transactions (``reference.sighash``'s own limit); a signature script is read
as data pushes only and anything else is a ``ValueError``, as is any output
script but the three standard classes and any redeem script but a multisig
(the engine runs whatever it is given); no sig-op or script-unit metering
(the construction commits what its scripts execute; the program's own tests
hold the metering); a multisig that fails with a non-empty signature is an
*error* in the engine (NULLFAIL) and a key that is no curve point is an error
too: both are "not validly signed" here.
"""

from __future__ import annotations

import hashlib

from benchmarks import reference
from benchmarks.reference import G, N, P, SIG_HASH_ALL

_ECDSA_DOMAIN = hashlib.sha256(b"TransactionSigningHashECDSA").digest()
OP_CHECKSIG, OP_CHECKSIG_ECDSA, OP_CHECKMULTISIG, OP_CHECKMULTISIG_ECDSA = 0xAC, 0xAB, 0xAE, 0xA9
OP_BLAKE2B, OP_EQUAL, OP_PUSHDATA1, OP_PUSHDATA2 = 0xAA, 0x87, 0x4C, 0x4D


def ecdsa_sighash(tx, input_index: int, spent_amount: int, spent_spk_version: int, spent_script: bytes) -> bytes:
    inner = reference.sighash(tx, input_index, spent_amount, spent_spk_version, spent_script)
    return hashlib.sha256(_ECDSA_DOMAIN + inner).digest()


def _decompress(pubkey33: bytes):
    if len(pubkey33) != 33 or pubkey33[0] not in (2, 3):
        return None
    x = int.from_bytes(pubkey33[1:], "big")
    if x >= P:
        return None
    y2 = (pow(x, 3, P) + 7) % P
    y = pow(y2, (P + 1) // 4, P)
    if y * y % P != y2:
        return None
    return x, (y if y & 1 == pubkey33[0] & 1 else P - y)


def ecdsa_verify(pubkey33: bytes, msg32: bytes, sig64: bytes) -> bool:
    q = _decompress(pubkey33)
    if q is None or len(sig64) != 64 or len(msg32) != 32:
        return False
    r, s = int.from_bytes(sig64[:32], "big"), int.from_bytes(sig64[32:], "big")
    if not (1 <= r < N and 1 <= s <= N // 2):  # the low-s rule
        return False
    w = pow(s, -1, N)
    point = reference._add(reference._mul(G, int.from_bytes(msg32, "big") % N * w % N), reference._mul(q, r * w % N))
    return point is not None and point[0] % N == r


def pushed_items(script: bytes) -> list:
    """The data items of a script made of data pushes alone."""
    out, i = [], 0
    while i < len(script):
        op = script[i]
        if 1 <= op <= 75:
            start, ln = i + 1, op
        elif op == OP_PUSHDATA1:
            start, ln = i + 2, script[i + 1]
        elif op == OP_PUSHDATA2:
            start, ln = i + 3, int.from_bytes(script[i + 1 : i + 3], "little")
        else:
            raise ValueError(f"opcode {op:#x} is no data push")
        if start + ln > len(script):
            raise ValueError("truncated push")
        out.append(script[start : start + ln])
        i = start + ln
    return out


def parse_multisig(redeem: bytes) -> tuple[int, list, bool]:
    """(m, keys, ecdsa) of ``<m> <key>... <n> OpCheckMultiSig[ECDSA]``."""
    if len(redeem) < 4 or redeem[-1] not in (OP_CHECKMULTISIG, OP_CHECKMULTISIG_ECDSA):
        raise ValueError("not a multisig redeem script")
    ecdsa = redeem[-1] == OP_CHECKMULTISIG_ECDSA
    m, n = redeem[0] - 0x50, redeem[-2] - 0x50  # Op1..Op16 push 1..16
    keys = pushed_items(redeem[1:-2])
    if not (1 <= m <= n <= 16) or len(keys) != n or any(len(k) != (33 if ecdsa else 32) for k in keys):
        raise ValueError("not a multisig redeem script")
    return m, keys, ecdsa


def multisig_walk(signatures: list, keys: list, verify) -> bool:
    """Every signature, in order, must verify under a key later than the one
    the signature before it used; a key that was tried is not tried again."""
    key_pos = 0
    for done, sig in enumerate(signatures):
        while True:
            if len(keys) - key_pos < len(signatures) - done:
                return False
            key_pos += 1
            if verify(keys[key_pos - 1], sig):
                break
    return True


def _signature(blob: bytes) -> bytes:
    if len(blob) != 65 or blob[64] != SIG_HASH_ALL:
        raise ValueError("not a 64-byte signature with SIGHASH_ALL")
    return blob[:64]


def input_verdict(tx, input_index: int, spent_amount: int, spent_spk_version: int, spent_script: bytes) -> bool:
    """Is input ``input_index`` of ``tx`` validly signed for the output it spends?"""
    items = pushed_items(tx.inputs[input_index].signature_script)
    s = spent_script
    spent = (spent_amount, spent_spk_version, spent_script)
    if len(s) == 34 and s[0] == 32 and s[33] == OP_CHECKSIG:
        if len(items) != 1:
            raise ValueError("a pay-to-pubkey spend pushes one signature")
        return reference.bip340_verify(s[1:33], reference.sighash(tx, input_index, *spent), _signature(items[0]))
    if len(s) == 35 and s[0] == 33 and s[34] == OP_CHECKSIG_ECDSA:
        if len(items) != 1:
            raise ValueError("a pay-to-pubkey spend pushes one signature")
        return ecdsa_verify(s[1:34], ecdsa_sighash(tx, input_index, *spent), _signature(items[0]))
    if len(s) == 35 and s[0] == OP_BLAKE2B and s[1] == 32 and s[34] == OP_EQUAL:
        if not items or hashlib.blake2b(items[-1], digest_size=32).digest() != s[2:34]:
            return False  # the redeem script is not the one the output commits to
        m, keys, ecdsa = parse_multisig(items[-1])
        signatures = [_signature(b) for b in items[:-1]]
        if len(signatures) != m:
            return False  # too few fail the walk's count, one too many stays on the stack
        if ecdsa:
            msg = ecdsa_sighash(tx, input_index, *spent)
            return multisig_walk(signatures, keys, lambda key, sig: ecdsa_verify(key, msg, sig))
        msg = reference.sighash(tx, input_index, *spent)
        return multisig_walk(signatures, keys, lambda key, sig: reference.bip340_verify(key, msg, sig))
    raise ValueError("not an output of a standard class")


def spend_verdict(tx, spent: list) -> bool:
    """``spent[i]`` = (amount, script version, script) of the output that
    input i spends: is every input validly signed?"""
    return all(input_verdict(tx, i, *spent[i]) for i in range(len(tx.inputs)))
