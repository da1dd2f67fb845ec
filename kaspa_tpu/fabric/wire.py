"""Verify-fabric wire format.

Every message rides the PR 2 gRPC Length-Prefixed-Message framing
(`p2p/proto/framing.py`: flag byte + 4-byte big-endian length); the
payload is a 1-byte message type followed by varint/length-delimited
fields (`p2p/proto/wire_format.py` primitives) — no schema compiler, no
new dependency, same bounds discipline as the P2P wire.

    HELLO        server -> client on accept: proto version, slice count,
                 capability mode flags (proto >= 2; none defined)
    VERIFY_REQ   req_id, kind, target slice, trace id, [(pub,msg,sig)...]
    VERIFY_RESP  req_id, status; ok: packed mask + server-side timings +
                 the slice's post-completion inflight count (the load
                 signal the balancer routes on); err: utf-8 message
    STATUS_REQ   req_id — the balancer's liveness/occupancy probe
    STATUS_RESP  req_id, per-slice (inflight, queue depth)

Verify masks are bit-packed (numpy packbits order) with an explicit lane
count, so a 1024-job super-batch answers in ~128 bytes + framing.
"""

from __future__ import annotations

import numpy as np

from kaspa_tpu.p2p.proto.framing import encode_grpc_frame, read_grpc_frame
from kaspa_tpu.p2p.proto.wire_format import ProtoWireError, decode_varint, encode_varint

PROTO_VERSION = 2

HELLO = 0x01
VERIFY_REQ = 0x02
VERIFY_RESP = 0x03
STATUS_REQ = 0x04
STATUS_RESP = 0x05

STATUS_OK = 0
STATUS_ERR = 1

KINDS = ("schnorr", "ecdsa")

MAX_ITEMS = 1 << 20  # one super-batch; far above any sane coalesce target


def _pb(data: bytes) -> bytes:
    return encode_varint(len(data)) + data


def _read_bytes(buf: bytes, pos: int) -> tuple[bytes, int]:
    n, pos = decode_varint(buf, pos)
    if pos + n > len(buf):
        raise ProtoWireError(f"truncated length-delimited field ({n} bytes past end)")
    return buf[pos : pos + n], pos + n


def encode_hello(slices: int, proto: int = PROTO_VERSION, modes: int = 0) -> bytes:
    # the modes capability varint is appended after the proto-1 fields:
    # old decoders read exactly two varints and ignore trailing bytes, so
    # a v2 HELLO stays backward compatible on the wire
    return bytes([HELLO]) + encode_varint(proto) + encode_varint(slices) + encode_varint(modes)


def encode_verify_req(req_id: int, kind: str, slice_idx: int, trace_id: str | None, items) -> bytes:
    out = [bytes([VERIFY_REQ]), encode_varint(req_id), encode_varint(KINDS.index(kind)),
           encode_varint(slice_idx), _pb((trace_id or "").encode()), encode_varint(len(items))]
    for pub, msg, sig in items:
        out.append(_pb(pub))
        out.append(_pb(msg))
        out.append(_pb(sig))
    return b"".join(out)


def encode_verify_resp(req_id: int, mask, queue_ns: int, verify_ns: int, inflight: int) -> bytes:
    mask = np.asarray(mask, dtype=bool)
    return (
        bytes([VERIFY_RESP]) + encode_varint(req_id) + encode_varint(STATUS_OK)
        + encode_varint(int(mask.shape[0])) + _pb(np.packbits(mask).tobytes())
        + encode_varint(max(0, int(queue_ns))) + encode_varint(max(0, int(verify_ns)))
        + encode_varint(max(0, int(inflight)))
    )


def encode_error_resp(req_id: int, message: str) -> bytes:
    return (
        bytes([VERIFY_RESP]) + encode_varint(req_id) + encode_varint(STATUS_ERR)
        + _pb(message.encode("utf-8", "replace")[:1024])
    )


def encode_status_req(req_id: int) -> bytes:
    return bytes([STATUS_REQ]) + encode_varint(req_id)


def encode_status_resp(req_id: int, slices) -> bytes:
    out = [bytes([STATUS_RESP]), encode_varint(req_id), encode_varint(len(slices))]
    for inflight, depth in slices:
        out.append(encode_varint(max(0, int(inflight))))
        out.append(encode_varint(max(0, int(depth))))
    return b"".join(out)


def decode(message: bytes) -> tuple[int, dict]:
    """One framed payload -> (msg type, fields dict); raises ProtoWireError
    on any truncation/overrun (the transport treats that as a dead peer)."""
    if not message:
        raise ProtoWireError("empty fabric message")
    mtype, pos = message[0], 1
    if mtype == HELLO:
        proto, pos = decode_varint(message, pos)
        slices, pos = decode_varint(message, pos)
        modes = 0
        if pos < len(message):  # proto-1 peers send no capability flags
            modes, pos = decode_varint(message, pos)
        return mtype, {"proto": proto, "slices": slices, "modes": modes}
    if mtype == VERIFY_REQ:
        req_id, pos = decode_varint(message, pos)
        kind_idx, pos = decode_varint(message, pos)
        if kind_idx >= len(KINDS):
            raise ProtoWireError(f"unknown verify kind {kind_idx}")
        slice_idx, pos = decode_varint(message, pos)
        tid, pos = _read_bytes(message, pos)
        count, pos = decode_varint(message, pos)
        if count > MAX_ITEMS:
            raise ProtoWireError(f"oversized verify batch ({count} items)")
        items = []
        for _ in range(count):
            pub, pos = _read_bytes(message, pos)
            msg, pos = _read_bytes(message, pos)
            sig, pos = _read_bytes(message, pos)
            items.append((pub, msg, sig))
        return mtype, {
            "req_id": req_id, "kind": KINDS[kind_idx], "slice": slice_idx,
            "trace_id": tid.decode("utf-8", "replace") or None, "items": items,
        }
    if mtype == VERIFY_RESP:
        req_id, pos = decode_varint(message, pos)
        status, pos = decode_varint(message, pos)
        if status != STATUS_OK:
            emsg, pos = _read_bytes(message, pos)
            return mtype, {"req_id": req_id, "ok": False, "error": emsg.decode("utf-8", "replace")}
        count, pos = decode_varint(message, pos)
        if count > MAX_ITEMS:
            raise ProtoWireError(f"oversized verify mask ({count} lanes)")
        packed, pos = _read_bytes(message, pos)
        if len(packed) != (count + 7) // 8:
            raise ProtoWireError(f"mask length mismatch ({len(packed)} bytes for {count} lanes)")
        mask = np.unpackbits(np.frombuffer(packed, dtype=np.uint8), count=count).astype(bool)
        queue_ns, pos = decode_varint(message, pos)
        verify_ns, pos = decode_varint(message, pos)
        inflight, pos = decode_varint(message, pos)
        return mtype, {
            "req_id": req_id, "ok": True, "mask": mask,
            "queue_ns": queue_ns, "verify_ns": verify_ns, "inflight": inflight,
        }
    if mtype == STATUS_REQ:
        req_id, pos = decode_varint(message, pos)
        return mtype, {"req_id": req_id}
    if mtype == STATUS_RESP:
        req_id, pos = decode_varint(message, pos)
        n, pos = decode_varint(message, pos)
        if n > 4096:
            raise ProtoWireError(f"implausible slice count {n}")
        slices = []
        for _ in range(n):
            inflight, pos = decode_varint(message, pos)
            depth, pos = decode_varint(message, pos)
            slices.append((inflight, depth))
        return mtype, {"req_id": req_id, "slices": slices}
    raise ProtoWireError(f"unknown fabric message type {mtype:#x}")


def frame(message: bytes) -> bytes:
    """Payload -> on-the-wire bytes (the shared gRPC length prefix)."""
    return encode_grpc_frame(message)


def read_message(read_exactly) -> tuple[int, dict]:
    """Read + decode one framed message via ``read_exactly(n) -> bytes``."""
    return decode(read_grpc_frame(read_exactly))
