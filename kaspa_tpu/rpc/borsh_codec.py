"""Borsh wRPC encoding: the binary counterpart of the JSON WebSocket RPC.

Payload layouts are byte-exact ports of the reference's versioned
`Serializer` impls over borsh primitives (rpc/core/src/model/message.rs,
block.rs, header.rs, tx.rs — each codec cites its source): little-endian
fixed-width ints, `bool` as one byte, `Vec`/`String` with a u32 length,
`Option` with a one-byte tag, `Hash` as 32 raw bytes, `SubnetworkId` as 20
raw bytes, `Uint192` blue work as 24 bytes LE
(math/src/lib.rs construct_uint!(Uint192, 3)).

The outer frame is NOT the reference's: its wRPC rides the external
workflow-rpc crate whose Borsh framing is not vendored here, so this module
defines an explicit documented frame instead:

    kind(u8: 0=request 1=response 2=notification 3=error)
    | id(u64 LE; requests/responses only)
    | op(u32 LE, RpcApiOps discriminants from rpc/core/src/api/ops.rs)
    | payload (reference-exact message encoding)

Ops used: Subscribe=3, SubmitBlock=117, GetInfo=141,
BlockAddedNotification=60 (ops.rs:28,74,122,48).
"""

from __future__ import annotations

import io
import struct

# --- RpcApiOps discriminants (rpc/core/src/api/ops.rs) ---
OP_SUBSCRIBE = 3
OP_BLOCK_ADDED_NOTIFICATION = 60
OP_SUBMIT_BLOCK = 117
OP_GET_INFO = 141
# serving-tier methods: this frame's op assignment (the reference numbers
# them inside the external workflow-rpc crate); pinned by the golden
# fixtures under tests/fixtures/borsh/
OP_GET_UTXOS_BY_ADDRESSES = 145
OP_GET_BALANCE_BY_ADDRESS = 146
OP_GET_COIN_SUPPLY = 147
# notification ops follow the EVENT_TYPES order from the block-added base:
# op = 60 + EVENT_TYPES.index(event) (ops.rs keeps notifications contiguous)
OP_UTXOS_CHANGED_NOTIFICATION = 64

KIND_REQUEST = 0
KIND_RESPONSE = 1
KIND_NOTIFICATION = 2
KIND_ERROR = 3


# ---------------------------------------------------------------------------
# borsh primitives
# ---------------------------------------------------------------------------

def w_u8(w, v):
    w.write(struct.pack("<B", v))


def w_u16(w, v):
    w.write(struct.pack("<H", v))


def w_u32(w, v):
    w.write(struct.pack("<I", v))


def w_u64(w, v):
    w.write(struct.pack("<Q", v))


def w_f64(w, v):
    w.write(struct.pack("<d", v))


def w_bool(w, v):
    w.write(b"\x01" if v else b"\x00")


def w_bytes(w, b):
    w_u32(w, len(b))
    w.write(b)


def w_string(w, s):
    w_bytes(w, s.encode("utf-8"))


def w_hash(w, h):
    assert len(h) == 32
    w.write(h)


def w_uint192(w, v):
    w.write(v.to_bytes(24, "little"))


def _rd(r, n):
    b = r.read(n)
    if len(b) != n:
        raise EOFError(f"truncated borsh read: wanted {n}, got {len(b)}")
    return b


def r_u8(r):
    return struct.unpack("<B", _rd(r, 1))[0]


def r_u16(r):
    return struct.unpack("<H", _rd(r, 2))[0]


def r_u32(r):
    return struct.unpack("<I", _rd(r, 4))[0]


def r_u64(r):
    return struct.unpack("<Q", _rd(r, 8))[0]


def r_f64(r):
    return struct.unpack("<d", _rd(r, 8))[0]


def r_bool(r):
    return _rd(r, 1) == b"\x01"


def r_bytes(r):
    return _rd(r, r_u32(r))


def r_string(r):
    return r_bytes(r).decode("utf-8")


def r_hash(r):
    return _rd(r, 32)


def r_uint192(r):
    return int.from_bytes(_rd(r, 24), "little")


# ---------------------------------------------------------------------------
# message payload codecs (reference-exact)
# ---------------------------------------------------------------------------

def encode_get_info_request(w) -> None:
    """message.rs:250-254."""
    w_u16(w, 1)


def decode_get_info_request(r) -> dict:
    r_u16(r)
    return {}


def encode_get_info_response(w, info: dict) -> None:
    """message.rs:276-286: struct version + 2 strings, u64, 4 bools."""
    w_u16(w, 1)
    w_string(w, info["p2p_id"])
    w_u64(w, info["mempool_size"])
    w_string(w, info["server_version"])
    w_bool(w, info["is_utxo_indexed"])
    w_bool(w, info["is_synced"])
    w_bool(w, info["has_notify_command"])
    w_bool(w, info["has_message_id"])


def decode_get_info_response(r) -> dict:
    r_u16(r)
    return {
        "p2p_id": r_string(r),
        "mempool_size": r_u64(r),
        "server_version": r_string(r),
        "is_utxo_indexed": r_bool(r),
        "is_synced": r_bool(r),
        "has_notify_command": r_bool(r),
        "has_message_id": r_bool(r),
    }


def encode_outpoint(w, op) -> None:
    """tx.rs:128-135: u8 version, TransactionId hash, u32 index."""
    w_u8(w, 1)
    w_hash(w, op.transaction_id)
    w_u32(w, op.index)


def decode_outpoint(r):
    from kaspa_tpu.consensus.model import TransactionOutpoint

    r_u8(r)
    return TransactionOutpoint(r_hash(r), r_u32(r))


def encode_tx_input(w, inp) -> None:
    """tx.rs:194-205 (struct version 2 carries the compute budget)."""
    w_u8(w, 2)
    encode_outpoint(w, inp.previous_outpoint)
    w_bytes(w, inp.signature_script)
    w_u64(w, inp.sequence)
    cc = inp.compute_commit
    w_u8(w, cc.value if cc.kind == "sigops" else 0)  # sig_op_count
    w_u8(w, 0)  # Option<RpcTransactionInputVerboseData>: None
    w_u16(w, cc.value if cc.kind == "budget" else 0)  # compute_budget


def decode_tx_input(r, tx_version: int = 0):
    from kaspa_tpu.consensus.model import ComputeCommit, TransactionInput

    version = r_u8(r)
    op = decode_outpoint(r)
    script = r_bytes(r)
    seq = r_u64(r)
    sig_ops = r_u8(r)
    if r_u8(r) == 1:  # verbose data present: struct is empty + u8 version
        r_u8(r)
    budget = r_u16(r) if version > 1 else 0
    # the TRANSACTION version selects the commit variant (model/tx.py:64,
    # mirroring the reference's versioned sighash field selection) — a
    # nonzero-budget heuristic would flip budget(0) into sigops(0)
    if ComputeCommit.version_expects_compute_budget_field(tx_version):
        cc = ComputeCommit.budget(budget)
    else:
        cc = ComputeCommit.sigops(sig_ops)
    return TransactionInput(op, script, seq, cc)


def encode_tx_output(w, out) -> None:
    """tx.rs:268-276 (struct version 2 carries the covenant binding)."""
    w_u8(w, 2)
    w_u64(w, out.value)
    w_u16(w, out.script_public_key.version)  # RpcScriptPublicKey borsh:
    w_bytes(w, out.script_public_key.script)  # u16 version + Vec<u8> script
    w_u8(w, 0)  # Option<RpcTransactionOutputVerboseData>: None
    cov = out.covenant
    if cov is None:
        w_u8(w, 0)
    else:
        w_u8(w, 1)
        w_u8(w, 1)  # RpcCovenantBinding struct version (tx.rs:319-325)
        w_u16(w, cov.authorizing_input)
        w_hash(w, cov.covenant_id)


def decode_tx_output(r):
    from kaspa_tpu.consensus.model import Covenant, ScriptPublicKey, TransactionOutput

    version = r_u8(r)
    value = r_u64(r)
    spk = ScriptPublicKey(r_u16(r), r_bytes(r))
    if r_u8(r) == 1:  # verbose data: skip (version u8 + script class str + addr str)
        r_u8(r)
        r_string(r)
        r_string(r)
    cov = None
    if version > 1 and r_u8(r) == 1:
        r_u8(r)
        cov = Covenant(r_u16(r), r_hash(r))
    return TransactionOutput(value, spk, cov)


def encode_tx(w, tx) -> None:
    """tx.rs:478-493."""
    w_u16(w, 1)
    w_u16(w, tx.version)
    w_u32(w, len(tx.inputs))
    for inp in tx.inputs:
        encode_tx_input(w, inp)
    w_u32(w, len(tx.outputs))
    for out in tx.outputs:
        encode_tx_output(w, out)
    w_u64(w, tx.lock_time)
    w.write(tx.subnetwork_id)  # RpcSubnetworkId: 20 raw bytes
    w_u64(w, tx.gas)
    w_bytes(w, tx.payload)
    w_u64(w, tx.storage_mass)
    w_u8(w, 0)  # Option<RpcTransactionVerboseData>: None


def decode_tx(r):
    from kaspa_tpu.consensus.model import Transaction

    r_u16(r)
    version = r_u16(r)
    inputs = [decode_tx_input(r, version) for _ in range(r_u32(r))]
    outputs = [decode_tx_output(r) for _ in range(r_u32(r))]
    lock_time = r_u64(r)
    subnetwork = _rd(r, 20)
    gas = r_u64(r)
    payload = r_bytes(r)
    storage_mass = r_u64(r)
    if r_u8(r) == 1:  # verbose data: u8 version + txid hash + u64 compute mass
        r_u8(r)
        r_hash(r)
        r_u64(r)
    return Transaction(version, inputs, outputs, lock_time, subnetwork, gas, payload, storage_mass)


def _encode_header_fields(w, h) -> None:
    w_u16(w, h.version)
    w_u32(w, len(h.parents_by_level))
    for level in h.parents_by_level:
        w_u32(w, len(level))
        for p in level:
            w_hash(w, p)
    w_hash(w, h.hash_merkle_root)
    w_hash(w, h.accepted_id_merkle_root)
    w_hash(w, h.utxo_commitment)
    w_u64(w, h.timestamp)
    w_u32(w, h.bits)
    w_u64(w, h.nonce)
    w_u64(w, h.daa_score)
    w_uint192(w, h.blue_work)
    w_u64(w, h.blue_score)
    w_hash(w, h.pruning_point)


def _decode_header_fields(r) -> dict:
    version = r_u16(r)
    parents = []
    for _ in range(r_u32(r)):
        parents.append([r_hash(r) for _ in range(r_u32(r))])
    return {
        "version": version,
        "parents_by_level": parents,
        "hash_merkle_root": r_hash(r),
        "accepted_id_merkle_root": r_hash(r),
        "utxo_commitment": r_hash(r),
        "timestamp": r_u64(r),
        "bits": r_u32(r),
        "nonce": r_u64(r),
        "daa_score": r_u64(r),
        "blue_work": r_uint192(r),
        "blue_score": r_u64(r),
        "pruning_point": r_hash(r),
    }


def encode_raw_header(w, h) -> None:
    """header.rs:286-305 (RpcRawHeader: no hash field)."""
    w_u16(w, 1)
    _encode_header_fields(w, h)


def decode_raw_header(r):
    from kaspa_tpu.consensus.model import Header

    r_u16(r)
    f = _decode_header_fields(r)
    return Header(**f)


def encode_header(w, h) -> None:
    """header.rs:148-167 (RpcHeader: leads with the block hash)."""
    w_u16(w, 1)
    w_hash(w, h.hash)
    _encode_header_fields(w, h)


def encode_submit_block_request(w, block, allow_non_daa_blocks: bool = False) -> None:
    """message.rs:34-41: struct version + RpcRawBlock + bool."""
    w_u16(w, 1)
    w_u16(w, 1)  # RpcRawBlock struct version (block.rs:45-52)
    encode_raw_header(w, block.header)
    w_u32(w, len(block.transactions))
    for tx in block.transactions:
        encode_tx(w, tx)
    w_bool(w, allow_non_daa_blocks)


def decode_submit_block_request(r):
    from kaspa_tpu.consensus.model.block import Block

    r_u16(r)
    r_u16(r)  # raw block struct version
    header = decode_raw_header(r)
    txs = [decode_tx(r) for _ in range(r_u32(r))]
    allow_non_daa = r_bool(r)
    return Block(header, txs), allow_non_daa


# SubmitBlockRejectReason discriminants (message.rs:54-60, use_discriminant)
REJECT_BLOCK_INVALID = 1
REJECT_IS_IN_IBD = 2
REJECT_ROUTE_IS_FULL = 3


def encode_submit_block_response(w, reject_reason: int | None) -> None:
    """message.rs:98-103; SubmitBlockReport borsh enum: 0=Success,
    1=Reject(reason) (message.rs:82-85)."""
    w_u16(w, 1)
    if reject_reason is None:
        w_u8(w, 0)
    else:
        w_u8(w, 1)
        w_u8(w, reject_reason)


def decode_submit_block_response(r) -> int | None:
    r_u16(r)
    if r_u8(r) == 0:
        return None
    return r_u8(r)


def encode_block_added_notification(w, block, verbose: dict) -> None:
    """message.rs:2991-2996 wrapping RpcBlock (block.rs:23-31) with its
    verbose data (block.rs:80-92)."""
    w_u16(w, 1)
    w_u16(w, 1)  # RpcBlock struct version
    encode_header(w, block.header)
    w_u32(w, len(block.transactions))
    for tx in block.transactions:
        encode_tx(w, tx)
    w_u8(w, 1)  # Option<RpcBlockVerboseData>: Some
    w_u8(w, 1)  # verbose struct version
    w_hash(w, block.hash)
    w_f64(w, verbose.get("difficulty", 0.0))
    w_hash(w, verbose.get("selected_parent_hash", bytes(32)))
    ids = [tx.id() for tx in block.transactions]
    w_u32(w, len(ids))
    for i in ids:
        w_hash(w, i)
    w_bool(w, verbose.get("is_header_only", False))
    w_u64(w, verbose.get("blue_score", block.header.blue_score))
    for key in ("children_hashes", "merge_set_blues_hashes", "merge_set_reds_hashes"):
        hs = verbose.get(key, [])
        w_u32(w, len(hs))
        for h in hs:
            w_hash(w, h)
    w_bool(w, verbose.get("is_chain_block", False))


# ---------------------------------------------------------------------------
# serving-tier payloads: UTXO queries + UtxosChanged (message.rs
# GetUtxosByAddresses*/GetBalanceByAddress*/GetCoinSupply*/UtxosChanged*)
# ---------------------------------------------------------------------------

def encode_utxo_entry_rpc(w, e) -> None:
    """RpcUtxoEntry (tx.rs:361-370): amount, spk, daa score, coinbase flag,
    plus the version-2 Option<covenant id> this consensus carries."""
    w_u16(w, 2)
    w_u64(w, e.amount)
    w_u16(w, e.script_public_key.version)
    w_bytes(w, e.script_public_key.script)
    w_u64(w, e.block_daa_score)
    w_bool(w, e.is_coinbase)
    if e.covenant_id is None:
        w_u8(w, 0)
    else:
        w_u8(w, 1)
        w_hash(w, e.covenant_id)


def decode_utxo_entry_rpc(r):
    from kaspa_tpu.consensus.model import ScriptPublicKey, UtxoEntry

    r_u16(r)
    amount = r_u64(r)
    spk = ScriptPublicKey(r_u16(r), r_bytes(r))
    daa = r_u64(r)
    coinbase = r_bool(r)
    cov = r_hash(r) if r_u8(r) == 1 else None
    return UtxoEntry(amount, spk, daa, coinbase, cov)


def encode_utxos_by_addresses_entry(w, address: str | None, outpoint, entry) -> None:
    """RpcUtxosByAddressesEntry (message.rs:1764-1771): Option<address>
    (None for scripts with no standard address form) + outpoint + entry."""
    w_u16(w, 1)
    if address is None:
        w_u8(w, 0)
    else:
        w_u8(w, 1)
        w_string(w, address)
    encode_outpoint(w, outpoint)
    encode_utxo_entry_rpc(w, entry)


def decode_utxos_by_addresses_entry(r):
    r_u16(r)
    address = r_string(r) if r_u8(r) == 1 else None
    return address, decode_outpoint(r), decode_utxo_entry_rpc(r)


def encode_get_utxos_by_addresses_request(w, addresses: list[str]) -> None:
    w_u16(w, 1)
    w_u32(w, len(addresses))
    for a in addresses:
        w_string(w, a)


def decode_get_utxos_by_addresses_request(r) -> list[str]:
    r_u16(r)
    return [r_string(r) for _ in range(r_u32(r))]


def encode_get_utxos_by_addresses_response(w, entries) -> None:
    """entries: (address|None, outpoint, UtxoEntry) triples."""
    w_u16(w, 1)
    w_u32(w, len(entries))
    for address, outpoint, entry in entries:
        encode_utxos_by_addresses_entry(w, address, outpoint, entry)


def decode_get_utxos_by_addresses_response(r):
    r_u16(r)
    return [decode_utxos_by_addresses_entry(r) for _ in range(r_u32(r))]


def encode_get_balance_by_address_request(w, address: str) -> None:
    w_u16(w, 1)
    w_string(w, address)


def decode_get_balance_by_address_request(r) -> str:
    r_u16(r)
    return r_string(r)


def encode_get_balance_by_address_response(w, balance: int) -> None:
    w_u16(w, 1)
    w_u64(w, balance)


def decode_get_balance_by_address_response(r) -> int:
    r_u16(r)
    return r_u64(r)


# consensus/core/src/constants.rs MAX_SOMPI: 29B KAS in sompi
MAX_SOMPI = 29_000_000_000 * 100_000_000


def encode_get_coin_supply_request(w) -> None:
    w_u16(w, 1)


def decode_get_coin_supply_request(r) -> dict:
    r_u16(r)
    return {}


def encode_get_coin_supply_response(w, circulating_sompi: int, max_sompi: int = MAX_SOMPI) -> None:
    """message.rs GetCoinSupplyResponse: max then circulating."""
    w_u16(w, 1)
    w_u64(w, max_sompi)
    w_u64(w, circulating_sompi)


def decode_get_coin_supply_response(r) -> dict:
    r_u16(r)
    return {"max_sompi": r_u64(r), "circulating_sompi": r_u64(r)}


def encode_utxos_changed_notification(w, added, removed, address_prefix: str | None = None) -> None:
    """message.rs:3127-3133 UtxosChangedNotification: added/removed entry
    vecs.  ``added``/``removed`` are (outpoint, UtxoEntry) pairs; addresses
    are recovered from the script pubkey (None when nonstandard)."""
    w_u16(w, 1)
    for pairs in (added, removed):
        w_u32(w, len(pairs))
        for outpoint, entry in pairs:
            address = None
            if address_prefix is not None:
                from kaspa_tpu.crypto.addresses import extract_script_pub_key_address

                try:
                    address = extract_script_pub_key_address(entry.script_public_key, address_prefix).to_string()
                except Exception:  # noqa: BLE001 - nonstandard script: no address form
                    address = None
            encode_utxos_by_addresses_entry(w, address, outpoint, entry)


def decode_utxos_changed_notification(r) -> dict:
    r_u16(r)
    added = [decode_utxos_by_addresses_entry(r) for _ in range(r_u32(r))]
    removed = [decode_utxos_by_addresses_entry(r) for _ in range(r_u32(r))]
    return {"added": added, "removed": removed}


def encode_subscribe_request(w, event_op: int, addresses: list[str] | None = None) -> None:
    """Subscribe payload: the notification op, plus (UtxosChanged only) the
    bech32 address scope — an empty vec subscribes to all addresses."""
    w_u32(w, event_op)
    if event_op == OP_UTXOS_CHANGED_NOTIFICATION:
        addrs = addresses or []
        w_u32(w, len(addrs))
        for a in addrs:
            w_string(w, a)


# ---------------------------------------------------------------------------
# framing + dispatch
# ---------------------------------------------------------------------------

def encode_frame(kind: int, op: int, payload: bytes, msg_id: int | None = None) -> bytes:
    w = io.BytesIO()
    w_u8(w, kind)
    if kind in (KIND_REQUEST, KIND_RESPONSE, KIND_ERROR):
        w_u64(w, msg_id or 0)
    w_u32(w, op)
    w.write(payload)
    return w.getvalue()


def decode_frame(data: bytes):
    r = io.BytesIO(data)
    kind = r_u8(r)
    msg_id = r_u64(r) if kind in (KIND_REQUEST, KIND_RESPONSE, KIND_ERROR) else None
    op = r_u32(r)
    return kind, msg_id, op, r


def handle_frame(daemon, data: bytes, notification_sink=None, subscriber_ref=None, stop=None) -> bytes:
    """Dispatch one Borsh wRPC request frame; returns the response frame.

    The server side of the reference's Borsh-encoding wRPC endpoint
    (rpc/wrpc/server/src/server.rs) over this module's documented frame.
    ``subscriber_ref`` is the connection's one-slot serving Subscriber cell
    (created lazily on first subscribe, torn down by the transport).
    """
    msg_id = 0
    try:
        kind, msg_id, op, r = decode_frame(data)
        if kind != KIND_REQUEST:
            raise ValueError(f"unexpected frame kind {kind}")
        if op == OP_GET_INFO:
            decode_get_info_request(r)
            info = daemon.dispatch("getInfo", {})
            w = io.BytesIO()
            encode_get_info_response(w, info)
            return encode_frame(KIND_RESPONSE, op, w.getvalue(), msg_id)
        if op == OP_SUBMIT_BLOCK:
            from kaspa_tpu.consensus.consensus import RuleError
            from kaspa_tpu.core.log import get_logger

            block, _allow_non_daa = decode_submit_block_request(r)
            w = io.BytesIO()
            try:
                with daemon._dispatch_lock.locked_for("block"):
                    # graftlint: allow(blocking-under-lock) -- borsh submit serializes with the RPC mutation path under the dispatch lock; insert+unorphan device waits are deliberate
                    daemon.node.submit_block(block)
                encode_submit_block_response(w, None)
            except (RuleError, ValueError) as e:
                # consensus rejection: the typed reject report
                get_logger("wrpc.borsh").info("block %s rejected: %s", block.hash.hex()[:16], e)
                encode_submit_block_response(w, REJECT_BLOCK_INVALID)
            # internal failures propagate to the KIND_ERROR frame below —
            # a miner must not read a node bug as "your block was invalid"
            return encode_frame(KIND_RESPONSE, op, w.getvalue(), msg_id)
        if op == OP_GET_UTXOS_BY_ADDRESSES:
            from kaspa_tpu.crypto.addresses import Address, pay_to_address_script

            addresses = decode_get_utxos_by_addresses_request(r)
            entries = []
            with daemon._dispatch_lock:
                index = daemon.rpc._require_index()
                for a in addresses:
                    spk = pay_to_address_script(Address.from_string(a))
                    utxos = index.get_utxos_by_script(spk.script)
                    for outpoint in sorted(utxos, key=lambda o: (o.transaction_id, o.index)):
                        entries.append((a, outpoint, utxos[outpoint]))
            w = io.BytesIO()
            encode_get_utxos_by_addresses_response(w, entries)
            return encode_frame(KIND_RESPONSE, op, w.getvalue(), msg_id)
        if op == OP_GET_BALANCE_BY_ADDRESS:
            address = decode_get_balance_by_address_request(r)
            with daemon._dispatch_lock:
                balance = daemon.rpc.get_balance_by_address(address)
            w = io.BytesIO()
            encode_get_balance_by_address_response(w, balance)
            return encode_frame(KIND_RESPONSE, op, w.getvalue(), msg_id)
        if op == OP_GET_COIN_SUPPLY:
            decode_get_coin_supply_request(r)
            with daemon._dispatch_lock:
                supply = daemon.rpc.get_coin_supply()["circulating_sompi"]
            w = io.BytesIO()
            encode_get_coin_supply_response(w, supply)
            return encode_frame(KIND_RESPONSE, op, w.getvalue(), msg_id)
        if op == OP_SUBSCRIBE:
            event_op = r_u32(r)
            scripts = None
            if event_op == OP_BLOCK_ADDED_NOTIFICATION:
                event = "block-added"
            elif event_op == OP_UTXOS_CHANGED_NOTIFICATION:
                event = "utxos-changed"
                addrs = [r_string(r) for _ in range(r_u32(r))]
                if addrs:
                    from kaspa_tpu.crypto.addresses import Address, pay_to_address_script

                    scripts = {pay_to_address_script(Address.from_string(a)).script for a in addrs}
            else:
                raise ValueError(f"unsupported subscription op {event_op}")
            # route through the serving broadcaster: one lazily-created
            # Borsh subscriber per connection, bounded queue + dedicated
            # sender thread so the full-block/diff encode never runs on the
            # consensus thread publishing the event
            with daemon._dispatch_lock:
                if subscriber_ref[0] is None:
                    subscriber_ref[0] = daemon.broadcaster.register(
                        daemon.make_borsh_subscriber(notification_sink, stop)
                    )
                daemon.broadcaster.subscribe(subscriber_ref[0], event, scripts)
            return encode_frame(KIND_RESPONSE, op, b"", msg_id)
        raise ValueError(f"unsupported borsh op {op}")
    except Exception as e:  # noqa: BLE001 - wire boundary
        w = io.BytesIO()
        w_string(w, str(e))
        return encode_frame(KIND_ERROR, 0, w.getvalue(), msg_id or 0)


def make_block_added_frame(block, verbose: dict | None = None) -> bytes:
    w = io.BytesIO()
    encode_block_added_notification(w, block, verbose or {})
    return encode_frame(KIND_NOTIFICATION, OP_BLOCK_ADDED_NOTIFICATION, w.getvalue())


def make_utxos_changed_frame(n, address_prefix: str | None = None) -> bytes:
    w = io.BytesIO()
    encode_utxos_changed_notification(w, n.data.get("added", []), n.data.get("removed", []), address_prefix)
    return encode_frame(KIND_NOTIFICATION, OP_UTXOS_CHANGED_NOTIFICATION, w.getvalue())


def encode_notification(n, address_prefix: str | None = None) -> bytes | None:
    """Serving-tier encoder: one Notification -> one Borsh frame, or None
    when this encoding has no codec for the event (the subscriber skips
    it).  Runs on the subscriber's sender thread."""
    if n.event_type == "block-added":
        return make_block_added_frame(n.data["block"])
    if n.event_type == "utxos-changed":
        return make_utxos_changed_frame(n, address_prefix)
    return None
