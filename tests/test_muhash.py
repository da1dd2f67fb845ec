"""MuHash golden vectors + device tree-product equivalence.

Vectors from crypto/muhash/src/lib.rs tests (EMPTY_MUHASH, the three
UTXO-style vectors with cumulative combination, pre-computed set hash) —
validates the Blake2b element hash, the rand_chacha-compatible ChaCha20
expansion, and the GF(2**3072 - 1103717) arithmetic end to end.
"""

import random

import numpy as np
import pytest

from kaspa_tpu.crypto.muhash import EMPTY_MUHASH, PRIME, MuHash, data_to_element

V1 = bytes(
    [152, 32, 81, 253, 30, 75, 167, 68, 187, 190, 104, 14, 31, 238, 20, 103, 123, 161, 163, 195, 84, 11, 247, 177, 205,
     182, 6, 232, 87, 35, 62, 14, 0, 0, 0, 0, 1, 0, 0, 0, 1, 0, 242, 5, 42, 1, 0, 0, 0, 67, 65, 4, 150, 181, 56, 232, 83,
     81, 156, 114, 106, 44, 145, 230, 30, 193, 22, 0, 174, 19, 144, 129, 58, 98, 124, 102, 251, 139, 231, 148, 123, 230,
     60, 82, 218, 117, 137, 55, 149, 21, 212, 224, 166, 4, 248, 20, 23, 129, 230, 34, 148, 114, 17, 102, 191, 98, 30, 115,
     168, 44, 191, 35, 66, 200, 88, 238, 172]
)
V2 = bytes(
    [213, 253, 204, 84, 30, 37, 222, 28, 122, 90, 221, 237, 242, 72, 88, 184, 187, 102, 92, 159, 54, 239, 116, 78, 228, 44,
     49, 96, 34, 201, 15, 155, 0, 0, 0, 0, 2, 0, 0, 0, 1, 0, 242, 5, 42, 1, 0, 0, 0, 67, 65, 4, 114, 17, 168, 36, 245, 91,
     80, 82, 40, 228, 195, 213, 25, 76, 31, 207, 170, 21, 164, 86, 171, 223, 55, 249, 185, 217, 122, 64, 64, 175, 192, 115,
     222, 230, 200, 144, 100, 152, 79, 3, 56, 82, 55, 217, 33, 103, 193, 62, 35, 100, 70, 180, 23, 171, 121, 160, 252, 174,
     65, 42, 227, 49, 107, 119, 172]
)
V3 = bytes(
    [68, 246, 114, 34, 96, 144, 216, 93, 185, 169, 242, 251, 254, 95, 15, 150, 9, 179, 135, 175, 123, 229, 183, 251, 183,
     161, 118, 124, 131, 28, 158, 153, 0, 0, 0, 0, 3, 0, 0, 0, 1, 0, 242, 5, 42, 1, 0, 0, 0, 67, 65, 4, 148, 185, 211, 231,
     108, 91, 22, 41, 236, 249, 127, 255, 149, 215, 164, 187, 218, 200, 124, 194, 96, 153, 173, 162, 128, 102, 198, 255,
     30, 185, 25, 18, 35, 205, 137, 113, 148, 160, 141, 12, 39, 38, 197, 116, 127, 29, 180, 158, 140, 249, 14, 117, 220,
     62, 53, 80, 174, 155, 48, 8, 111, 60, 213, 170, 172]
)

MULTISET = [
    "2c379620fdf4ec0ac253cbe4ba82c2bbdc0fedac7fe0e452957d93757bbff5c1",
    "668bb292ef152c54db0f5714bf45ff8da7b1d41c0c5026ad655b2f9e1be67e21",
    "f40b20bdc43ef2f01a173b767cb9c6b8db5602eb535fcb9827385f9b0e3afaf4",
]
CUMULATIVE = [
    "2c379620fdf4ec0ac253cbe4ba82c2bbdc0fedac7fe0e452957d93757bbff5c1",
    "b15bd1124a6b52e64eda3c3023c587e455a79e748c8c954dd7411d0dbd973863",
    "e69c6e050410761648ce6276a81c8044b9efb1715ea6f6fb9f8cf7a8c1e80396",
]


def test_empty_muhash():
    assert EMPTY_MUHASH.hex() == "544eb3142c000f0ad2c76ac41f4222abbababed830eeafee4b6dc56b52d5cac0"


def test_golden_vectors():
    acc = MuHash()
    for i, data in enumerate([V1, V2, V3]):
        single = MuHash()
        single.add_element(data)
        assert single.finalize().hex() == MULTISET[i]
        acc.add_element(data)
        assert acc.finalize().hex() == CUMULATIVE[i]


def test_add_remove_commutes():
    rng = random.Random(4)
    datas = [rng.randbytes(40) for _ in range(6)]
    m = MuHash()
    for d in datas:
        m.add_element(d)
    for d in reversed(datas):
        m.remove_element(d)
    assert m.finalize() == EMPTY_MUHASH
    # order independence
    a = MuHash()
    b = MuHash()
    for d in datas:
        a.add_element(d)
    for d in reversed(datas):
        b.add_element(d)
    assert a.finalize() == b.finalize()


def test_combine_and_serialize_roundtrip():
    a = MuHash()
    a.add_element(V1)
    b = MuHash()
    b.add_element(V2)
    b.remove_element(V3)
    a.combine(b)
    ser = a.serialize()
    back = MuHash.deserialize(ser)
    assert back.finalize() == a.finalize()


# every size goes out on the bucket-64 program (one XLA-CPU compile; 1,024
# takes minutes there): a ragged single chunk, a full one, a full one and a
# rest, several chunks launched before the one read-back
@pytest.mark.parametrize("n", [3, 64, 70, 200, 449])
def test_device_tree_product_matches_host(n):
    from kaspa_tpu.ops.muhash_ops import batch_product_ints

    rng = random.Random(5 + n)
    vals = [rng.randrange(PRIME) for _ in range(n)]
    exp = 1
    for v in vals:
        exp = exp * v % PRIME
    assert batch_product_ints(vals) == exp


def test_commit_with_two_device_products_matches_the_host_commit():
    """Adds and removes both past DEVICE_BATCH_THRESHOLD: the commit launches
    both products before it reads either, and holds what use_device=False holds."""
    from kaspa_tpu.consensus.model import (
        ScriptPublicKey, Transaction, TransactionInput, TransactionOutpoint, TransactionOutput, UtxoEntry,
    )
    from kaspa_tpu.crypto.muhash import DEVICE_BATCH_THRESHOLD

    rng = random.Random(9)
    spk = ScriptPublicKey(0, b"\x20" + bytes(32) + b"\xac")
    items = []
    for t in range(6):
        inputs = [TransactionInput.new(TransactionOutpoint(rng.randbytes(32), i), b"", 0, 1) for i in range(7)]
        outputs = [TransactionOutput(1000 + i, spk) for i in range(12)]
        entries = [UtxoEntry(5000 + i, spk, 77 + t, False) for i in range(7)]
        items.append((Transaction(0, inputs, outputs, 0, bytes(20), 0, b""), entries, 100 + t))
    assert 6 * 7 >= DEVICE_BATCH_THRESHOLD and 6 * 12 >= DEVICE_BATCH_THRESHOLD
    on_device, on_host, one_by_one = MuHash(), MuHash(), MuHash()
    on_device.add_transactions_batch(items)
    on_host.add_transactions_batch(items, use_device=False)
    for tx, entries, daa in items:
        one_by_one.add_transaction(tx, entries, daa)
    assert (on_device.numerator, on_device.denominator) == (on_host.numerator, on_host.denominator)
    assert (on_host.numerator, on_host.denominator) == (one_by_one.numerator, one_by_one.denominator)
    assert on_device.denominator != 1 != on_device.numerator


def test_utxo_element_serialization():
    from kaspa_tpu.consensus.model import ScriptPublicKey, TransactionOutpoint, UtxoEntry
    from kaspa_tpu.crypto.muhash import serialize_utxo

    op = TransactionOutpoint(bytes(range(32)), 7)
    entry = UtxoEntry(1234, ScriptPublicKey(0, b"\xaa\xbb"), 999, True)
    data = serialize_utxo(op, entry)
    # outpoint(32+4) + daa(8) + amount(8) + coinbase(1) + spk ver(2) + len(8) + script(2)
    assert len(data) == 32 + 4 + 8 + 8 + 1 + 2 + 8 + 2
    m = MuHash()
    m.add_element(data)
    m.remove_utxo(op, entry)
    assert m.finalize() == EMPTY_MUHASH
