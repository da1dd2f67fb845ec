"""P2P socket transport: framed binary messages between OS processes.

The reference's connection layer (protocol/p2p/src/core/connection_handler.rs
over tonic gRPC streams + Router per peer) as a thread-per-connection TCP
server speaking the frames of p2p/wire.py.  The flow logic stays in
p2p/node.Node — a WirePeer exposes the same ``send(msg_type, payload)``
surface as the in-process Peer, so every handler runs unchanged over the
wire.

Concurrency: each connection gets a reader thread and a writer thread; all
flow handling is serialized through ``node.lock`` (the node objects are
single-writer, the discipline the reference gets from consensus sessions +
the tokio runtime).  Sends only *enqueue* — socket writes happen on the
writer thread so a handler never blocks on peer backpressure while holding
``node.lock`` (two nodes serving each other large IBD payloads would
otherwise deadlock once both TCP buffers filled).  Mirrors the reference
Router's bounded mpsc outgoing lane (p2p/src/core/router.rs); a peer whose
queue overflows is dropped as too-slow.
"""

from __future__ import annotations

import os
import queue
import socket
import threading
from time import perf_counter_ns

_SEND_QUEUE_LIMIT = 4096  # frames; overflow => drop the peer (slow consumer)

from kaspa_tpu.observability import trace
from kaspa_tpu.observability.core import REGISTRY
from kaspa_tpu.p2p import wire
from kaspa_tpu.p2p.node import MIN_PROTOCOL_VERSION, MSG_VERSION, Node, ProtocolError
from kaspa_tpu.resilience import faults as fault_mod
from kaspa_tpu.resilience.faults import FAULTS, FaultInjected


class WireMetrics:
    """The transport's instrument set, bound to one registry (or scope).

    Codec cost only (socket IO excluded): encode is timed around
    codec.encode in send(), decode around codec.decode in the reader loop —
    blocking recv time would otherwise swamp the histogram.  Both wire
    implementations (custom frames and protobuf/gRPC) feed the SAME
    instruments so dashboards compare codecs without relabeling.

    One process-global default instance serves the daemon (one node per
    process).  A multi-node host — the swarm drill — hangs a scoped
    instance on ``node.wire_metrics`` so each node's relay accounting
    (``p2p_msgs_rx`` per message type, the amplification budget's input)
    lands in its own namespace instead of one shared counter.
    """

    __slots__ = ("enc_time", "dec_time", "frames_tx", "frames_rx", "bytes_tx", "bytes_rx", "msgs_tx", "msgs_rx")

    def __init__(self, registry=REGISTRY):
        self.enc_time = registry.histogram("p2p_frame_encode_seconds", help="wire frame encode time (codec only)")
        self.dec_time = registry.histogram("p2p_frame_decode_seconds", help="wire payload decode time (codec only)")
        self.frames_tx = registry.counter("p2p_frames_tx", help="frames enqueued for send")
        self.frames_rx = registry.counter("p2p_frames_rx", help="frames received and decoded")
        self.bytes_tx = registry.counter("p2p_bytes_tx", help="frame bytes enqueued for send")
        self.bytes_rx = registry.counter("p2p_bytes_rx", help="frame bytes received (incl. headers)")
        self.msgs_tx = registry.counter_family("p2p_msgs_tx", "type", help="messages sent by flow message type")
        self.msgs_rx = registry.counter_family("p2p_msgs_rx", "type", help="messages received by flow message type")


_DEFAULT_METRICS = WireMetrics(REGISTRY)


def wire_metrics_for(node) -> WireMetrics:
    """The node's own instrument set if it carries one, else the global."""
    m = getattr(node, "wire_metrics", None)
    return m if m is not None else _DEFAULT_METRICS


class CustomWireCodec:
    """The canonical serde wire of p2p/wire.py (magic|type|len|payload)."""

    name = "custom"

    def encode(self, msg_type: str, payload) -> bytes:
        return wire.encode_frame(msg_type, payload)

    def read_frame(self, read_exactly) -> tuple[object, bytes, int]:
        """Blocking read of one frame -> (decode meta, body, wire bytes).

        Kept separate from :meth:`decode` so the reader loop can time codec
        work alone — socket waits never enter the decode histogram."""
        type_id, plen = wire.decode_frame(read_exactly(7))
        return type_id, read_exactly(plen), 7 + plen

    def decode(self, meta, body: bytes) -> tuple[str, object]:
        return wire.decode_payload(meta, body)


class GrpcProtoCodec:
    """Reference-compatible wire: KaspadMessage protobuf in gRPC framing.

    Byte-compatible with what the reference's tonic stack writes inside
    HTTP/2 DATA frames (p2p/proto/framing.py has the layout); the payload
    bytes are the vendored KaspadMessage schema.  Same reader/writer
    machinery, same flow layer — only the bytes on the socket change.
    """

    name = "proto"

    def __init__(self):
        # deferred import: kaspa_tpu.p2p.proto.codec imports node constants,
        # and transport is imported early by the daemon
        from kaspa_tpu.p2p.proto import framing
        from kaspa_tpu.p2p.proto import codec as proto_codec

        self._framing = framing
        self._codec = proto_codec

    def encode(self, msg_type: str, payload) -> bytes:
        return self._framing.encode_grpc_frame(self._codec.encode_kaspad_message(msg_type, payload))

    def read_frame(self, read_exactly) -> tuple[object, bytes, int]:
        n = self._framing.decode_grpc_prefix(read_exactly(self._framing.GRPC_FRAME_OVERHEAD))
        return None, read_exactly(n), self._framing.GRPC_FRAME_OVERHEAD + n

    def decode(self, _meta, body: bytes) -> tuple[str, object]:
        return self._codec.decode_kaspad_message(body)


def get_codec(name: str):
    """Wire selector for the daemon's ``--p2p-proto`` flag."""
    if name == "custom":
        return CustomWireCodec()
    if name == "proto":
        return GrpcProtoCodec()
    raise ValueError(f"unknown p2p wire codec {name!r} (expected 'custom' or 'proto')")


class WirePeer:
    """Router endpoint over a socket (p2p/src/core/router.rs)."""

    def __init__(self, node: Node, sock: socket.socket, outbound: bool, codec=None):
        self.node = node
        self.sock = sock
        self.outbound = outbound
        self.codec = codec if codec is not None else CustomWireCodec()
        self.metrics = wire_metrics_for(node)
        # the remote's version-handshake identity nonce (node._handle sets
        # it on VERSION receipt); the LINKS partition plane keys on it
        self.remote_id = None
        try:
            ip, port = sock.getpeername()[:2]
            from kaspa_tpu.p2p.address_manager import NetAddress

            self.peer_address = NetAddress(ip, port)
        except OSError:
            self.peer_address = None
        self.version_sent = outbound  # inbound reciprocates on VERSION receipt
        self.handshaken = False
        self.misbehavior_score = 0
        # a half-open socket (SYN accepted, VERSION never arrives) must not
        # pin a reader thread forever; after the handshake the read deadline
        # relaxes to read_timeout (0 = disabled — block indefinitely)
        self.handshake_timeout = float(os.environ.get("KASPA_TPU_P2P_HANDSHAKE_TIMEOUT", "15"))
        self.read_timeout = float(os.environ.get("KASPA_TPU_P2P_READ_TIMEOUT", "0"))
        # tier floor until the handshake negotiates (node._handle sets it)
        self.protocol_version = MIN_PROTOCOL_VERSION
        self.known_blocks: set = set()
        self.known_txs: set = set()
        self.alive = True
        self._outq: queue.Queue = queue.Queue(maxsize=_SEND_QUEUE_LIMIT)
        self._thread: threading.Thread | None = None
        self._writer: threading.Thread | None = None

    def send(self, msg_type: str, payload) -> None:
        if not self.alive:
            return
        links = fault_mod.LINKS
        if links.active and links.drop(getattr(self.node, "id", None), self.remote_id):
            # severed link: the frame is black-holed before it is even
            # encoded — the sender's relay state (known_blocks dedup)
            # still believes it left, exactly like real packet loss
            FAULTS.fire("p2p.partition")
            return
        t0 = perf_counter_ns()
        frame = self.codec.encode(msg_type, payload)
        self.metrics.enc_time.observe((perf_counter_ns() - t0) * 1e-9)
        act = FAULTS.fire("p2p.send")
        if act is not None:
            if act.mode == "disconnect":
                self.close()
                return
            frame = fault_mod.mangle_frame(frame, act)
            if frame is None:  # drop: the frame silently never leaves
                return
        self.metrics.frames_tx.inc()
        self.metrics.bytes_tx.inc(len(frame))
        self.metrics.msgs_tx.inc(msg_type)
        try:
            self._outq.put_nowait(frame)
        except queue.Full:
            self.close()

    def flush(self, timeout: float = 1.0) -> bool:
        """Block until every frame enqueued so far has hit the socket.

        Implemented as a sentinel Event that rides the FIFO behind the
        pending frames; the writer thread sets it once everything ahead of
        it has been sendall()'d.  Bounded wait: a wedged peer must not be
        able to pin the caller (returns False on timeout/overflow)."""
        if not self.alive:
            return False
        done = threading.Event()
        try:
            self._outq.put_nowait(done)
        except queue.Full:
            return False
        return done.wait(timeout)

    def _writer_loop(self) -> None:
        try:
            while True:
                frame = self._outq.get()
                if frame is None:
                    return
                if isinstance(frame, threading.Event):
                    frame.set()  # flush barrier: everything ahead is on the wire
                    continue
                self.sock.sendall(frame)
        except OSError:
            pass
        finally:
            self.close()

    def _score(self, peer, reason: str, points: int) -> bool:
        # test doubles and minimal node stubs don't carry the misbehavior
        # ledger; treat them as never banning
        score = getattr(self.node, "score_misbehavior", None)
        return bool(score(peer, reason, points)) if score is not None else False

    def _read_exactly(self, n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("peer closed")
            buf += chunk
        return buf

    def _reader_loop(self) -> None:
        try:
            # handshake deadline: socket.timeout is an OSError subclass, so
            # an expired deadline lands in the handler below and closes the
            # peer — the reference's handshake timeout in connection_handler
            self.sock.settimeout(self.handshake_timeout or None)
            steady = False
            while self.alive:
                act = FAULTS.fire("p2p.recv")
                if act is not None and act.mode == "disconnect":
                    raise ConnectionError("injected disconnect")
                # frame read and payload decode are split so only codec work
                # is timed — the header/body reads block on the peer.  While
                # this peer is the one the node syncs from, the read is a
                # span: request out -> whole frame in
                if getattr(self.node, "_sync_peer", None) is self:
                    with trace.span("wait.p2p_frame"):
                        meta, body, nbytes = self.codec.read_frame(self._read_exactly)
                else:
                    meta, body, nbytes = self.codec.read_frame(self._read_exactly)
                t0 = perf_counter_ns()
                try:
                    with trace.span("p2p.decode", bytes=nbytes) as sp:
                        msg_type, payload = self.codec.decode(meta, body)
                        sp.set(msg=msg_type)
                except Exception:  # noqa: BLE001 - body didn't decode but the
                    # frame header did, so the stream is still in sync: score
                    # the peer and keep reading.  A repeat offender crosses
                    # the ban threshold and is dropped + address-banned.
                    if self._score(self, "malformed_frame", 40):
                        raise ConnectionError("peer banned for malformed frames") from None
                    continue
                self.metrics.dec_time.observe((perf_counter_ns() - t0) * 1e-9)
                self.metrics.frames_rx.inc()
                self.metrics.bytes_rx.inc(nbytes)
                self.metrics.msgs_rx.inc(msg_type)
                with self.node.lock.locked_for(msg_type):
                    # graftlint: allow(blocking-under-lock) -- every p2p message is handled under the node lock (the node's serialization point); IBD batch inserts legitimately wait on verify futures there
                    self.node._handle(self, msg_type, payload)
                if self.handshaken and not steady:
                    steady = True
                    self.sock.settimeout(self.read_timeout or None)
        except (ConnectionError, OSError):
            pass
        except ProtocolError as e:
            # protocol violations score per the error's own weight (benign
            # handshake mismatches carry 0), and the peer is told WHY
            # before dropping it (p2p.proto RejectMessage)
            points = getattr(e, "points", 100)
            if points:
                self._score(self, "protocol_error", points)
            from kaspa_tpu.p2p.node import MSG_REJECT

            try:
                self.send(MSG_REJECT, str(e))
                # the finally-close below would otherwise race the writer
                # thread and RST the socket before the reject frame leaves
                self.flush()
            except Exception:  # noqa: BLE001 - socket may already be gone
                pass
        except Exception:  # noqa: BLE001 - wire boundary: malformed frames,
            # codec decode errors, or consensus rejections from adversarial
            # payloads all mean "drop the peer", with misbehavior points so
            # a repeat offender graduates to a ban
            self._score(self, "malformed_frame", 40)
        finally:
            self.close()

    def start(self) -> None:
        self._thread = threading.Thread(target=self._reader_loop, daemon=True, name="p2p-reader")
        self._thread.start()
        self._writer = threading.Thread(target=self._writer_loop, daemon=True, name="p2p-writer")
        self._writer.start()

    def close(self) -> None:
        if not self.alive:
            return
        self.alive = False
        try:
            self._outq.put_nowait(None)  # unblock the writer thread
        except queue.Full:
            pass  # writer will hit the closed socket and exit
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self.sock.close()
        with self.node.lock:
            if self in self.node.peers:
                self.node.peers.remove(self)

    def wait_handshaken(self, timeout: float = 10.0) -> bool:
        import time

        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.handshaken:
                return True
            if not self.alive:
                return False  # peer rejected us (e.g. self-connection)
            time.sleep(0.01)
        return False


class P2PServer:
    """Listener accepting inbound peers (connection_handler.rs serve)."""

    def __init__(self, node: Node, host: str = "127.0.0.1", port: int = 0, address_manager=None, codec=None):
        self.node = node
        self.address_manager = address_manager  # inbound ban enforcement
        self.codec = codec if codec is not None else CustomWireCodec()
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen()
        self.address = f"{host}:{self._sock.getsockname()[1]}"
        self._accept_thread: threading.Thread | None = None
        self._running = False

    def start(self) -> None:
        self._running = True
        self._accept_thread = threading.Thread(target=self._accept_loop, daemon=True, name="p2p-accept")
        self._accept_thread.start()

    def _accept_loop(self) -> None:
        while self._running:
            try:
                sock, addr = self._sock.accept()
            except OSError:
                return
            if self.address_manager is not None and self.address_manager.is_banned(addr[0]):
                sock.close()
                continue
            # codecs are stateless; the server's instance is shared by peers
            peer = WirePeer(self.node, sock, outbound=False, codec=self.codec)
            with self.node.lock:
                self.node.peers.append(peer)
            peer.start()

    def stop(self) -> None:
        self._running = False
        try:
            self._sock.close()
        except OSError:
            pass


def connect_outbound(node: Node, address: str, timeout: float = 10.0, codec=None) -> WirePeer:
    """Dial a peer, run the version/verack handshake, return the live peer.

    Both ends must speak the same wire (``codec``): like the reference,
    wire selection is deployment configuration, not negotiated in-band —
    the version handshake only negotiates the flow tier."""
    host, port = address.rsplit(":", 1)
    try:
        # injected dial failure (mode "error"): presents as the failure the
        # caller already handles so the connect-retry path absorbs it
        FAULTS.fire("p2p.link_drop")
    except FaultInjected as e:
        raise ConnectionError(f"injected link drop dialing {address}") from e
    sock = socket.create_connection((host, int(port)), timeout=timeout)
    # the reader loop owns the socket deadline from here (handshake_timeout,
    # then read_timeout once handshaken)
    peer = WirePeer(node, sock, outbound=True, codec=codec)
    with node.lock:
        node.peers.append(peer)
    peer.start()
    peer.send(
        MSG_VERSION,
        {
            "protocol_version": node.protocol_version,
            "network": node.consensus.params.name,
            "listen_port": node.listen_port,
            "id": node.id,
        },
    )
    if not peer.wait_handshaken(timeout):
        peer.close()
        raise ConnectionError(f"handshake with {address} timed out")
    return peer
