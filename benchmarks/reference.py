"""The plain reference: what a Kaspa validator must hold after a set of
blocks, worked out from the blocks alone with the standard library and numpy.

It imports nothing of ``kaspa_tpu``.  It reads block *data* (header parents
and ``bits``; transaction ids, inputs, outputs) through plain attribute
access, and answers five questions:

- ``ghostdag``: which parent is selected, which mergeset blocks are blue and
  which red, and the blue score, blue work and DAA score of every block?
  (GHOSTDAG's k-cluster rule by its definition, on ancestor bitsets; work from
  the compact target.)  The header stage's layer.
- ``sighash``: which 32 bytes does a spend's signature sign?  (Kaspa's
  ``TransactionSigningHash`` for version-0 transactions under SIGHASH_ALL, from
  the transaction's fields and the output it spends.)
- ``bip340_verify``: is this Schnorr signature valid?  (BIP340, affine
  big-int arithmetic.)  The verify kernels' layer.
- ``expected_utxo_set``: which outputs exist at the sink?  The selected
  chain and every mergeset's order are ``ghostdag``'s; each chain block accepts
  its selected parent's coinbase and every spend of its mergeset whose inputs
  exist, except those the construction spoiled.  The UTXO semantics.
- ``muhash_commitment``: the UTXO commitment of that set (MuHash over
  GF(2**3072 - 1103717): Blake2b element hash -> ChaCha20 keystream ->
  big-int product -> Blake2b finalize).  The muhash layer.

``compare.py`` turns the five into the numbers a run prints beside their
limits; every limit is 0 (the arithmetic is exact integer).
"""

from __future__ import annotations

import hashlib

import numpy as np

# ---------------------------------------------------------------- secp256k1
P = 2**256 - 2**32 - 977
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
G = (
    0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798,
    0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8,
)


def _add(a, b):
    if a is None:
        return b
    if b is None:
        return a
    if a[0] == b[0]:
        if (a[1] + b[1]) % P == 0:
            return None
        lam = 3 * a[0] * a[0] * pow(2 * a[1], -1, P) % P
    else:
        lam = (b[1] - a[1]) * pow(b[0] - a[0], -1, P) % P
    x = (lam * lam - a[0] - b[0]) % P
    return x, (lam * (a[0] - x) - a[1]) % P


def _mul(pt, k):
    acc = None
    while k:
        if k & 1:
            acc = _add(acc, pt)
        pt = _add(pt, pt)
        k >>= 1
    return acc


def bip340_verify(pubkey32: bytes, msg: bytes, sig64: bytes) -> bool:
    if len(pubkey32) != 32 or len(sig64) != 64:
        return False
    x = int.from_bytes(pubkey32, "big")
    r, s = int.from_bytes(sig64[:32], "big"), int.from_bytes(sig64[32:], "big")
    if x >= P or r >= P or s >= N:
        return False
    y2 = (pow(x, 3, P) + 7) % P
    y = pow(y2, (P + 1) // 4, P)
    if y * y % P != y2:
        return False
    if y & 1:
        y = P - y
    tag = hashlib.sha256(b"BIP0340/challenge").digest()
    e = int.from_bytes(hashlib.sha256(tag + tag + sig64[:32] + pubkey32 + msg).digest(), "big") % N
    rp = _add(_mul(G, s), _mul((x, P - y), e))
    return rp is not None and rp[1] % 2 == 0 and rp[0] == r


# ------------------------------------------------------------------- muhash
MUHASH_PRIME = 2**3072 - 1103717
_CHACHA_CONST = np.frombuffer(b"expand 32-byte k", dtype="<u4")


def _chacha20_keystream(keys: np.ndarray, n_bytes: int) -> np.ndarray:
    """[n, 32] uint8 keys -> [n, n_bytes] keystream, nonce 0, counter from 0."""
    n = keys.shape[0]
    words = np.ascontiguousarray(keys).view("<u4").reshape(n, 8).astype(np.uint32)
    out = np.empty((n, -(-n_bytes // 64) * 64), dtype=np.uint8)

    def rotl(v, c):
        return (v << np.uint32(c)) | (v >> np.uint32(32 - c))

    def quarter(s, a, b, c, d):
        s[a] += s[b]
        s[d] = rotl(s[d] ^ s[a], 16)
        s[c] += s[d]
        s[b] = rotl(s[b] ^ s[c], 12)
        s[a] += s[b]
        s[d] = rotl(s[d] ^ s[a], 8)
        s[c] += s[d]
        s[b] = rotl(s[b] ^ s[c], 7)

    with np.errstate(over="ignore"):
        for blk in range(out.shape[1] // 64):
            init = np.zeros((16, n), dtype=np.uint32)
            init[0:4] = _CHACHA_CONST[:, None]
            init[4:12] = words.T
            init[12] = blk
            s = init.copy()
            for _ in range(10):
                quarter(s, 0, 4, 8, 12), quarter(s, 1, 5, 9, 13), quarter(s, 2, 6, 10, 14), quarter(s, 3, 7, 11, 15)
                quarter(s, 0, 5, 10, 15), quarter(s, 1, 6, 11, 12), quarter(s, 2, 7, 8, 13), quarter(s, 3, 4, 9, 14)
            s += init
            out[:, blk * 64 : (blk + 1) * 64] = np.ascontiguousarray(s.T, dtype="<u4").view(np.uint8).reshape(n, 64)
    return out[:, :n_bytes]


def _utxo_preimage(txid: bytes, index: int, amount: int, spk_version: int, script: bytes, daa: int, coinbase: bool):
    return (
        txid + index.to_bytes(4, "little") + daa.to_bytes(8, "little") + amount.to_bytes(8, "little")
        + (b"\x01" if coinbase else b"\x00") + spk_version.to_bytes(2, "little")
        + len(script).to_bytes(8, "little") + script
    )


def muhash_commitment(utxos: dict) -> bytes:
    """utxos: {(txid, index): (amount, spk version, script, daa score, is coinbase)}."""
    acc = 1
    if utxos:
        digests = np.empty((len(utxos), 32), dtype=np.uint8)
        for i, ((txid, index), e) in enumerate(utxos.items()):
            d = hashlib.blake2b(_utxo_preimage(txid, index, *e), key=b"MuHashElement", digest_size=32).digest()
            digests[i] = np.frombuffer(d, dtype=np.uint8)
        ks = _chacha20_keystream(digests, 384)
        for i in range(ks.shape[0]):
            acc = acc * (int.from_bytes(ks[i].tobytes(), "little") % MUHASH_PRIME) % MUHASH_PRIME
    return hashlib.blake2b(acc.to_bytes(384, "little"), key=b"MuHashFinalize", digest_size=32).digest()


# ----------------------------------------------------------------- GHOSTDAG
def _bits_of(v: int):
    """Indices of the set bits of ``v``, ascending."""
    while v:
        low = v & -v
        yield low.bit_length() - 1
        v ^= low


def work_of(bits: int) -> int:
    """Work of a block from its compact target: 2**256 // (target + 1)."""
    exponent, mantissa, shift = bits >> 24, bits & 0xFFFFFF, 0
    if exponent <= 3:
        mantissa >>= 8 * (3 - exponent)
    else:
        shift = 8 * (exponent - 3)
    # the mantissa is signed and may not be negative
    target = 0 if mantissa > 0x7FFFFF else (mantissa << shift) % (1 << 256)
    return ((1 << 256) - 1 - target) // (target + 1) + 1


class Ghostdag:
    """GHOSTDAG over ``blocks`` (in any topological order), by definition.

    The selected parent of a block is its parent of most blue work (ties by
    hash).  Its mergeset is its past without the selected parent's past, in
    ascending (blue work, hash).  Its blue set starts as the selected parent's
    blue set and the selected parent; a mergeset block joins it iff the set
    stays a k-cluster (no member with more than k members in its anticone) and
    the block has at most k + 1 mergeset blues, else it is red.  Blue score
    and blue work add the mergeset blues' count and work to the selected
    parent's; the DAA score adds the whole mergeset's count, genesis left out
    (no mergeset block of these DAGs is older than the difficulty window).

    Pasts are bitsets over the blocks' positions, so "is a an ancestor of b"
    is one bit test.  Per hash: ``selected_parent``, ``blue_score``,
    ``blue_work``, ``daa_score``, ``mergeset`` (selected parent first, then
    ascending), ``reds`` (a set).
    """

    def __init__(self, blocks: list, genesis: bytes, genesis_bits: int, k: int):
        hashes, pos = [genesis], {genesis: 0}
        past, blue_set = [0], [0]  # blue_set[i]: every blue block down block i's selected chain, i excluded
        work, blue_work, blue_score, daa = [work_of(genesis_bits)], [0], [0], [0]
        self.selected_parent, self.blue_score, self.blue_work, self.daa_score = {}, {genesis: 0}, {genesis: 0}, {genesis: 0}
        self.mergeset, self.reds = {}, {}
        for b in blocks:
            parents = [pos[p] for p in b.header.direct_parents()]
            sp = max(parents, key=lambda j: (blue_work[j], hashes[j]))
            seen = 0
            for j in parents:
                seen |= past[j] | (1 << j)
            candidates = sorted(_bits_of(seen & ~past[sp] & ~(1 << sp)), key=lambda j: (blue_work[j], hashes[j]))
            blues, mergeset_blues, reds = blue_set[sp] | (1 << sp), [sp], []
            for c in candidates:
                # a blue block is never in the future of c: it is in the selected
                # parent's past (c is not), or an earlier candidate (less blue work)
                anticone = blues & ~past[c]
                blue = len(mergeset_blues) <= k and anticone.bit_count() <= k
                if blue:
                    for x in _bits_of(anticone):
                        outside = blues & ~past[x] & ~(1 << x)
                        in_future = sum(1 for y in _bits_of(outside >> (x + 1) << (x + 1)) if past[y] >> x & 1)
                        if outside.bit_count() - in_future + 1 > k:
                            blue = False
                            break
                if blue:
                    blues |= 1 << c
                    mergeset_blues.append(c)
                else:
                    reds.append(c)
            pos[b.hash] = len(hashes)
            hashes.append(b.hash)
            past.append(seen)
            blue_set.append(blues)
            work.append(work_of(b.header.bits))
            blue_score.append(blue_score[sp] + len(mergeset_blues))
            blue_work.append(blue_work[sp] + sum(work[j] for j in mergeset_blues))
            daa.append(daa[sp] + len(candidates) + (1 if sp else 0))  # genesis is not counted
            self.selected_parent[b.hash] = hashes[sp]
            self.blue_score[b.hash], self.blue_work[b.hash], self.daa_score[b.hash] = blue_score[-1], blue_work[-1], daa[-1]
            self.mergeset[b.hash] = [hashes[sp]] + [hashes[j] for j in candidates]
            self.reds[b.hash] = {hashes[j] for j in reds}

    def selected_chain(self, sink: bytes, genesis: bytes) -> list:
        """Genesis-exclusive selected chain up to ``sink``."""
        chain, cur = [], sink
        while cur != genesis:
            chain.append(cur)
            cur = self.selected_parent[cur]
        chain.reverse()
        return chain


# ------------------------------------------------------------------ sighash
SIG_HASH_ALL = 1
_NATIVE_SUBNETWORK = bytes(20)


def sighash(tx, input_index: int, spent_amount: int, spent_spk_version: int, spent_script: bytes) -> bytes:
    """What input ``input_index`` of a version-0 transaction signs under
    SIGHASH_ALL: Blake2b-256 keyed "TransactionSigningHash" over the version,
    the hashes of all outpoints, sequences and sig-op counts, this input's
    outpoint, the output it spends, its sequence and sig-op count, the hash of
    all outputs, lock time, subnetwork, gas, the payload's hash and the type."""
    if tx.version != 0:
        raise ValueError("the reference signs version-0 transactions only")

    def hasher():
        return hashlib.blake2b(key=b"TransactionSigningHash", digest_size=32)

    def u(n: int, width: int) -> bytes:
        return n.to_bytes(width, "little")

    def outpoint(i) -> bytes:
        return i.previous_outpoint.transaction_id + u(i.previous_outpoint.index, 4)

    def spk(version: int, script: bytes) -> bytes:
        return u(version, 2) + u(len(script), 8) + script

    outpoints, sequences, sig_ops, outputs = hasher(), hasher(), hasher(), hasher()
    for i in tx.inputs:
        outpoints.update(outpoint(i))
        sequences.update(u(i.sequence, 8))
        sig_ops.update(u(i.compute_commit.value, 1))
    for o in tx.outputs:
        outputs.update(u(o.value, 8) + spk(o.script_public_key.version, o.script_public_key.script))
    if tx.subnetwork_id == _NATIVE_SUBNETWORK and not tx.payload:
        payload = bytes(32)
    else:
        payload = hashlib.blake2b(u(len(tx.payload), 8) + tx.payload, key=b"TransactionSigningHash", digest_size=32).digest()
    me = tx.inputs[input_index]
    h = hasher()
    h.update(u(tx.version, 2) + outpoints.digest() + sequences.digest() + sig_ops.digest())
    h.update(outpoint(me) + spk(spent_spk_version, spent_script) + u(spent_amount, 8))
    h.update(u(me.sequence, 8) + u(me.compute_commit.value, 1) + outputs.digest())
    h.update(u(tx.lock_time, 8) + tx.subnetwork_id + u(tx.gas, 8) + payload + u(SIG_HASH_ALL, 1))
    return h.digest()


def p2pk_spend_verdict(tx, spent_amount: int, spent_spk_version: int, spent_script: bytes) -> tuple[bool, bytes]:
    """(is the one-input pay-to-pubkey spend ``tx`` validly signed, the
    message it had to sign), from the transaction and the output it spends."""
    script = tx.inputs[0].signature_script
    if len(spent_script) != 34 or spent_script[0] != 32 or spent_script[33] != 0xAC:
        raise ValueError("not a pay-to-pubkey output")
    if len(script) != 66 or script[0] != 65 or script[65] != SIG_HASH_ALL:
        raise ValueError("not a 64-byte signature pushed with SIGHASH_ALL")
    msg = sighash(tx, 0, spent_amount, spent_spk_version, spent_script)
    return bip340_verify(spent_script[1:33], msg, script[1:65]), msg


# ------------------------------------------------------------ UTXO semantics
def expected_utxo_set(blocks: list, gd: Ghostdag, sink: bytes, genesis: bytes, spoiled_txids: set) -> tuple[dict, list, set, set]:
    """(the UTXO set at ``sink``, its selected chain, the ids of every accepted
    transaction, the blocks merged so far), from the blocks and ``gd`` alone."""
    by_hash = {b.hash: b for b in blocks}
    chain = gd.selected_chain(sink, genesis)
    utxos: dict = {}
    accepted: set = set()
    merged = {genesis}
    for c in chain:
        daa = gd.daa_score[c]
        prev = gd.selected_parent[c]
        if prev != genesis:
            cb = by_hash[prev].transactions[0]
            accepted.add(cb.id())
            for j, out in enumerate(cb.outputs):
                utxos[(cb.id(), j)] = (out.value, out.script_public_key.version, out.script_public_key.script, daa, True)
        for h in gd.mergeset[c]:  # the selected parent first, then ascending blue work
            merged.add(h)
            if h == genesis:
                continue
            for tx in by_hash[h].transactions[1:]:
                if tx.id() in spoiled_txids:
                    continue
                keys = [(i.previous_outpoint.transaction_id, i.previous_outpoint.index) for i in tx.inputs]
                if any(k not in utxos for k in keys):
                    continue
                for k in keys:
                    del utxos[k]
                accepted.add(tx.id())
                for j, out in enumerate(tx.outputs):
                    spk = out.script_public_key
                    utxos[(tx.id(), j)] = (out.value, spk.version, spk.script, daa, False)
    return utxos, chain, accepted, merged
