"""MuHash: homomorphic multiset hash for UTXO commitments.

Re-implementation of the reference's kaspa-muhash (crypto/muhash/src/lib.rs,
u3072.rs) + the consensus extensions (consensus/core/src/muhash.rs):

- element = Blake2b("MuHashElement") -> ChaCha20 keystream (384 bytes) ->
  3072-bit little-endian integer in GF(2**3072 - 1103717)
- add = numerator *= elem; remove = denominator *= elem; combine = pairwise
- finalize = normalize (denominator inverse) -> 384-byte LE ->
  Blake2b("MuHashFinalize")

The host object keeps exact python-int accumulators (cheap at 3072 bits).
Bulk diffs — ``add_transactions_batch``, the call the consensus virtual
processor makes per mergeset — derive all element preimages at once
(native-vectorised ChaCha20) and, from ``DEVICE_BATCH_THRESHOLD``
elements, reduce the products through the device U3072 tree-product kernel
(ops/muhash_ops.ProductGroup).  A commit waits for the device once: the
numerator's chunks are launched, the denominator's digests, keystream and
limbs are derived on the host while the device works, its chunks are
launched too, and only then are both products read back; each combines
into the accumulator with one host multiply.
"""

from __future__ import annotations

import numpy as np

from kaspa_tpu.crypto import chacha
from kaspa_tpu.crypto import hashing as h
from kaspa_tpu.observability import trace
from kaspa_tpu.observability.core import REGISTRY

ELEMENT_BYTE_SIZE = 384
PRIME = 2**3072 - 1103717  # u3072.rs:22


def element_hashes_to_ints(hashes: np.ndarray) -> list[int]:
    """[N, 32] uint8 element hashes -> N field elements (vectorised chacha)."""
    ks = chacha.keystream(hashes, ELEMENT_BYTE_SIZE)
    return [int.from_bytes(ks[i].tobytes(), "little") % PRIME for i in range(ks.shape[0])]


def data_to_element(data: bytes) -> int:
    return element_hashes_to_ints(_digests([data]))[0]


def _digests(preimages: list[bytes]) -> np.ndarray:
    """[N, 32] uint8 MuHashElement digests of the preimages."""
    out = np.empty((len(preimages), 32), dtype=np.uint8)
    for i, p in enumerate(preimages):
        hasher = h.MuHashElementHash()
        hasher.update(p)
        out[i] = np.frombuffer(hasher.digest(), dtype=np.uint8)
    return out


# Bulk products with at least this many elements go through the device
# tree-product kernel; smaller ones multiply on host (dispatch overhead of a
# padded 64-wide bucket isn't worth it below this).
DEVICE_BATCH_THRESHOLD = 32

# the other side of muhash_device_elements (ops/muhash_ops.py): which way a
# commit's elements went
_HOST_ELEMENTS = REGISTRY.counter(
    "muhash_host_elements", help="field elements multiplied on the host (bulk product under DEVICE_BATCH_THRESHOLD)"
)


def elements_from_preimages(preimages: list[bytes]) -> list[int]:
    """Batch preimage -> field-element derivation (vectorised keystream)."""
    if not preimages:
        return []
    return element_hashes_to_ints(_digests(preimages))


def bulk_element_products(batches: list[list[bytes]], use_device: bool = True) -> list[int]:
    """Product mod PRIME of the field elements of each list of preimages.

    A list of at least ``DEVICE_BATCH_THRESHOLD`` goes through the device
    tree-product kernel: its chunks are launched and the next list is
    prepared (or multiplied, under the threshold) on the host while the
    device works; every launched product is read back in one wait at the
    end.  The device path views the raw keystream bytes as 16-bit limbs
    directly - values in [PRIME, 2**3072) are legal lazy-limb inputs that
    the kernel's final canon reduces - so no per-element host bigint
    conversion happens."""
    products = [1] * len(batches)
    group = None
    launched: list[int] = []  # the batches whose product the group holds, in launch order
    for i, preimages in enumerate(batches):
        if not preimages:
            continue
        if use_device and len(preimages) >= DEVICE_BATCH_THRESHOLD:
            from kaspa_tpu.ops import muhash_ops

            with trace.span("muhash.host_prepare", phase="elements", elements=len(preimages)):
                ks = chacha.keystream(_digests(preimages), ELEMENT_BYTE_SIZE)
                limbs = ks.view(np.dtype("<u2")).astype(np.int32)  # [N, 192]
            group = group or muhash_ops.ProductGroup()
            group.launch(limbs)
            launched.append(i)
        else:
            with trace.span("muhash.host_prepare", phase="elements", elements=len(preimages)):
                elements = elements_from_preimages(preimages)
            _HOST_ELEMENTS.inc(len(preimages))
            for e in elements:
                products[i] = products[i] * e % PRIME
    if group is not None:
        for i, product in zip(launched, group.finish()):
            products[i] = product
    return products


def bulk_element_product(preimages: list[bytes], use_device: bool = True) -> int:
    """Product of the field elements of `preimages` mod PRIME."""
    return bulk_element_products([preimages], use_device)[0]


def serialize_utxo(outpoint, entry) -> bytes:
    """Element preimage for a UTXO (consensus/core/src/muhash.rs write_utxo)."""
    out = bytearray()
    out += outpoint.transaction_id
    out += outpoint.index.to_bytes(4, "little")
    out += entry.block_daa_score.to_bytes(8, "little")
    out += entry.amount.to_bytes(8, "little")
    out += b"\x01" if entry.is_coinbase else b"\x00"
    out += entry.script_public_key.version.to_bytes(2, "little")
    out += len(entry.script_public_key.script).to_bytes(8, "little")
    out += entry.script_public_key.script
    if entry.covenant_id is not None:
        out += entry.covenant_id
    return bytes(out)


class MuHash:
    __slots__ = ("numerator", "denominator")

    def __init__(self, numerator: int = 1, denominator: int = 1):
        self.numerator = numerator
        self.denominator = denominator

    def add_element(self, data: bytes) -> None:
        self.numerator = self.numerator * data_to_element(data) % PRIME

    def remove_element(self, data: bytes) -> None:
        self.denominator = self.denominator * data_to_element(data) % PRIME

    def combine(self, other: "MuHash") -> None:
        self.numerator = self.numerator * other.numerator % PRIME
        self.denominator = self.denominator * other.denominator % PRIME

    def normalize(self) -> None:
        if self.denominator != 1:
            self.numerator = self.numerator * pow(self.denominator, -1, PRIME) % PRIME
            self.denominator = 1

    def serialize(self) -> bytes:
        self.normalize()
        return self.numerator.to_bytes(ELEMENT_BYTE_SIZE, "little")

    @staticmethod
    def deserialize(data: bytes) -> "MuHash":
        assert len(data) == ELEMENT_BYTE_SIZE
        v = int.from_bytes(data, "little")
        if v >= PRIME:
            raise OverflowError("Overflow in the MuHash field")
        return MuHash(v)

    def finalize(self) -> bytes:
        hasher = h.MuHashFinalizeHash()
        hasher.update(self.serialize())
        return hasher.digest()

    def clone(self) -> "MuHash":
        return MuHash(self.numerator, self.denominator)

    # --- consensus extensions (consensus/core/src/muhash.rs) ---

    def add_utxo(self, outpoint, entry) -> None:
        self.add_element(serialize_utxo(outpoint, entry))

    def remove_utxo(self, outpoint, entry) -> None:
        self.remove_element(serialize_utxo(outpoint, entry))

    def add_transaction(self, tx, utxo_entries, block_daa_score: int) -> None:
        """Remove spent entries, add created outputs (muhash.rs:16-34)."""
        adds, removes = _tx_element_preimages(tx, utxo_entries, block_daa_score)
        for p in removes:
            self.remove_element(p)
        for p in adds:
            self.add_element(p)

    def add_transactions_batch(self, items, use_device: bool = True) -> None:
        """Bulk `add_transaction` over ``[(tx, utxo_entries, daa_score)]``.

        All element preimages of the batch are derived together and the two
        monoid products (created outputs -> numerator, spent entries ->
        denominator) reduce through the device kernel from the threshold,
        launched one after the other and read back together.
        Equivalent to calling add_transaction per item, in any order — the
        multiset hash is commutative (reference rayon map-reduce:
        consensus/src/pipeline/virtual_processor/utxo_validation.rs:334-363).
        """
        with trace.span("muhash.commit", txs=len(items)):
            adds: list[bytes] = []
            removes: list[bytes] = []
            with trace.span("muhash.host_prepare", phase="preimages") as sp:
                for tx, entries, daa in items:
                    a, r = _tx_element_preimages(tx, entries, daa)
                    adds += a
                    removes += r
                sp.set(elements=len(adds) + len(removes))
            added, removed = bulk_element_products([adds, removes], use_device)
            if adds:
                self.numerator = self.numerator * added % PRIME
            if removes:
                self.denominator = self.denominator * removed % PRIME


def _tx_element_preimages(tx, utxo_entries, block_daa_score: int):
    """(added_preimages, removed_preimages) for one populated transaction."""
    from kaspa_tpu.consensus.model import TransactionOutpoint, UtxoEntry

    tx_id = tx.id()
    removes = [serialize_utxo(inp.previous_outpoint, entry) for inp, entry in zip(tx.inputs, utxo_entries)]
    adds = []
    for i, output in enumerate(tx.outputs):
        outpoint = TransactionOutpoint(tx_id, i)
        entry = UtxoEntry(
            output.value,
            output.script_public_key,
            block_daa_score,
            tx.is_coinbase(),
            output.covenant.covenant_id if output.covenant is not None else None,
        )
        adds.append(serialize_utxo(outpoint, entry))
    return adds, removes


EMPTY_MUHASH = MuHash().finalize()
