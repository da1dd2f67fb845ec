"""Standard script classes and builders.

Reference: crypto/txscript/src/{script_class.rs,standard.rs}.
"""

from __future__ import annotations

import hashlib
from enum import Enum

from kaspa_tpu.consensus.model import ScriptPublicKey

# opcode bytes (crypto/txscript/src/opcodes/mod.rs codes)
OP_DATA_32 = 0x20
OP_DATA_33 = 0x21
OP_DATA_65 = 0x41
OP_PUSHDATA1 = 0x4C
OP_PUSHDATA2 = 0x4D
OP_1 = 0x51
OP_EQUAL = 0x87
OP_CHECKMULTISIG_ECDSA = 0xA9
OP_BLAKE2B = 0xAA
OP_CHECKSIG_ECDSA = 0xAB
OP_CHECKSIG = 0xAC
OP_CHECKMULTISIG = 0xAE

MAX_SCRIPT_PUBLIC_KEY_VERSION = 0


class ScriptClass(Enum):
    NON_STANDARD = "nonstandard"
    PUB_KEY = "pubkey"
    PUB_KEY_ECDSA = "pubkeyecdsa"
    SCRIPT_HASH = "scripthash"


def is_pay_to_pubkey(script: bytes) -> bool:
    return len(script) == 34 and script[0] == OP_DATA_32 and script[33] == OP_CHECKSIG


def is_pay_to_pubkey_ecdsa(script: bytes) -> bool:
    return len(script) == 35 and script[0] == OP_DATA_33 and script[34] == OP_CHECKSIG_ECDSA


def is_pay_to_script_hash(script: bytes) -> bool:
    return len(script) == 35 and script[0] == OP_BLAKE2B and script[1] == OP_DATA_32 and script[34] == OP_EQUAL


def classify_script(spk: ScriptPublicKey) -> ScriptClass:
    if spk.version != MAX_SCRIPT_PUBLIC_KEY_VERSION:
        return ScriptClass.NON_STANDARD
    if is_pay_to_pubkey(spk.script):
        return ScriptClass.PUB_KEY
    if is_pay_to_pubkey_ecdsa(spk.script):
        return ScriptClass.PUB_KEY_ECDSA
    if is_pay_to_script_hash(spk.script):
        return ScriptClass.SCRIPT_HASH
    return ScriptClass.NON_STANDARD


def pay_to_pub_key(pubkey32: bytes) -> ScriptPublicKey:
    assert len(pubkey32) == 32
    return ScriptPublicKey(0, bytes([OP_DATA_32]) + pubkey32 + bytes([OP_CHECKSIG]))


def pay_to_pub_key_ecdsa(pubkey33: bytes) -> ScriptPublicKey:
    assert len(pubkey33) == 33
    return ScriptPublicKey(0, bytes([OP_DATA_33]) + pubkey33 + bytes([OP_CHECKSIG_ECDSA]))


def pay_to_script_hash_script(redeem_script: bytes) -> ScriptPublicKey:
    h = hashlib.blake2b(redeem_script, digest_size=32).digest()
    return ScriptPublicKey(0, bytes([OP_BLAKE2B, OP_DATA_32]) + h + bytes([OP_EQUAL]))


def schnorr_signature_script(sig64: bytes, hash_type: int) -> bytes:
    """Signature script for P2PK: a single push of sig||hash_type."""
    assert len(sig64) == 64
    return bytes([OP_DATA_65]) + sig64 + bytes([hash_type])


def ecdsa_signature_script(sig64: bytes, hash_type: int) -> bytes:
    assert len(sig64) == 64
    return bytes([OP_DATA_65]) + sig64 + bytes([hash_type])


def parse_single_push(script: bytes) -> bytes | None:
    """Parse a signature script that is exactly one canonical data push.

    Standard P2PK spends push one 65-byte blob (sig64 + hashtype).  Returns
    the pushed data or None if the script isn't a single plain push
    (1 <= opcode <= 75 direct-data form).
    """
    if not script:
        return None
    op = script[0]
    if 1 <= op <= 75 and len(script) == 1 + op:
        return script[1:]
    return None


def parse_canonical_pushes(script: bytes) -> list[bytes] | None:
    """The data items of a script that is nothing but minimal data pushes of
    two bytes or more (direct 2..75, OP_PUSHDATA1 76..255, OP_PUSHDATA2
    256..65535), else None.  What the engine's minimal-push rule would make
    of shorter items or other opcodes is the engine's business."""
    out, i, n = [], 0, len(script)
    while i < n:
        op = script[i]
        if 2 <= op <= 75:
            start, ln = i + 1, op
        elif op == OP_PUSHDATA1 and i + 2 <= n and script[i + 1] > 75:
            start, ln = i + 2, script[i + 1]
        elif op == OP_PUSHDATA2 and i + 3 <= n and script[i + 1] | script[i + 2] << 8 > 255:
            start, ln = i + 3, script[i + 1] | script[i + 2] << 8
        else:
            return None
        if start + ln > n:
            return None
        out.append(script[start : start + ln])
        i = start + ln
    return out


def parse_multisig_redeem(script: bytes) -> tuple[int, list[bytes], bool] | None:
    """(m, keys, ecdsa) of exactly the scripts ``multisig_redeem_script`` and
    ``multisig_redeem_script_ecdsa`` build with small-integer opcodes:
    ``<m> <key>*n <n> OpCheckMultiSig[ECDSA]``, 1 <= m <= n <= 16 (inside the
    engine's MAX_PUB_KEYS_PER_MULTISIG), every key a direct push of 32
    (Schnorr) or 33 (ECDSA) bytes, nothing after the check opcode.  Anything
    else: None."""
    if len(script) < 4:
        return None
    m, n, check = script[0] - OP_1 + 1, script[-2] - OP_1 + 1, script[-1]
    if check == OP_CHECKMULTISIG:
        ecdsa, key_len = False, 32
    elif check == OP_CHECKMULTISIG_ECDSA:
        ecdsa, key_len = True, 33
    else:
        return None
    if not (1 <= m <= n <= 16) or len(script) != 3 + n * (1 + key_len):
        return None
    keys = []
    for i in range(1, len(script) - 2, 1 + key_len):
        if script[i] != key_len:
            return None
        keys.append(script[i + 1 : i + 1 + key_len])
    return m, keys, ecdsa


def _multisig_script(pub_keys: list[bytes], required: int, check_op: int) -> bytes:
    from kaspa_tpu.txscript.script_builder import ScriptBuilder

    if not pub_keys:
        raise ValueError("provided public keys should not be empty")
    if not (1 <= required <= len(pub_keys)):
        raise ValueError(f"invalid required signatures {required} for {len(pub_keys)} keys")
    b = ScriptBuilder().add_i64(required)
    for k in pub_keys:
        b.add_data(k)
    b.add_i64(len(pub_keys))
    b.add_op(check_op)
    return b.drain()


def multisig_redeem_script(pub_keys32: list[bytes], required: int) -> bytes:
    """m-of-n schnorr multisig redeem script (standard/multisig.rs:18):
    <m> <key1> ... <keyn> <n> OpCheckMultiSig."""
    return _multisig_script(pub_keys32, required, OP_CHECKMULTISIG)


def multisig_redeem_script_ecdsa(pub_keys33: list[bytes], required: int) -> bytes:
    """ECDSA variant (standard/multisig.rs:44)."""
    return _multisig_script(pub_keys33, required, OP_CHECKMULTISIG_ECDSA)
