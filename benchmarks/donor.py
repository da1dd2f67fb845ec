"""The donor of the wire cell: a generator that speaks the IBD side of the
protocol, as rothschild is the generator of the relayed cell.  It holds the
window's blocks as encoded frames and answers the four messages a syncing
node sends; it validates nothing and is no node.

It is handed the blocks after the ramp in mining order, the last sink with
its blue work and the pruning point (genesis); listens on ``127.0.0.1:0``;
answers

- ``version`` with its own ``version`` and a ``verack``;
- ``requestibdchaininfo`` with the sink, its blue work and the pruning
  point, and starts its clock: a request that arrives more than ``seconds``
  after it is answered with ``done`` and no blocks;
- ``ibdblocklocator`` with the first chunk (the syncee holds every block
  before the ramp's end, so position 0 is where it starts);
- ``requestantipast`` with the chunk after the block the request names.

A chunk is ``chunk_blocks`` blocks by position, the last one shorter if the
list does not divide; its ``continuation`` is its last block's hash and
``done`` is set on the list's last chunk.  A chunk is sent only when asked
for, and encoded ahead of the request for it (a thread of the donor's runs
through the list), with the program's own codec, as ``dag.py`` builds with
the program's ``Consensus``.  Every request and every chunk goes to the log:
time since the clock started, what was named, first and last position,
blocks, bytes.

``python -m benchmarks.donor`` is the donor as a process of its own, which
is how the mode runs it: another machine's CPU is not the syncee's
interpreter.  **It never touches the chip**: the parent starts it with
``JAX_PLATFORMS=cpu`` (importing the codec imports the consensus model, and
that imports JAX) and it initialises no backend.  Commands and answers are
pickles, one after another, on its standard input and on what was its standard
output (anything a library prints goes to standard error).

``fault`` (``control_ibd.py`` and the tests; a benchmark run passes none):
``{"kind": "withhold", "chunk": c, "block": k}`` leaves one block out of one
chunk; ``{"kind": "flip_sigscript", "chunk": c, "block": k}`` flips one byte
of the first spend's signature script in that block's bytes on the wire;
``{"kind": "repeat_chunk", "chunk": c}`` answers the request after chunk
``c`` with chunk ``c`` again.
"""

from __future__ import annotations

import os
import pickle
import socket
import sys
import threading
import time


def split(blocks: list, chunk_blocks: int, pickled: bool = False) -> dict:
    """The first two arguments of ``Donor``: the blocks' hashes by position and
    the blocks by chunk, each chunk a list or (for the way into the donor's
    process) that list pickled, so that the donor unpickles a chunk when it
    encodes it and not all of them before the first."""
    chunks = [blocks[i : i + chunk_blocks] for i in range(0, len(blocks), chunk_blocks)]
    if pickled:
        chunks = [pickle.dumps(c, protocol=pickle.HIGHEST_PROTOCOL) for c in chunks]
    return {"hashes": [b.hash for b in blocks], "chunks": chunks}


class Donor:
    def __init__(self, hashes: list, chunks: list, sink: bytes, sink_blue_work: int, pruning_point: bytes, network: str,
                 codec_name: str, chunk_blocks: int, fault: dict | None = None):
        from kaspa_tpu.p2p import node as msgs
        from kaspa_tpu.p2p.transport import get_codec

        self.msgs, self.codec = msgs, get_codec(codec_name)
        self.hashes, self.chunks, self.network, self.fault = hashes, chunks, network, fault or {}
        self.chain_info = {"sink": sink, "sink_blue_work": sink_blue_work, "pruning_point": pruning_point}
        self.chunk_blocks = int(chunk_blocks)
        self.position = {h: i for i, h in enumerate(hashes)}
        self.n_chunks = len(chunks)
        self.frames: list = [None] * self.n_chunks  # (frame bytes, positions sent)
        self._encoded = [threading.Event() for _ in range(self.n_chunks)]
        self.seconds = 0.0
        self.log: list = []
        self.finished = threading.Event()  # a ``done`` chunk went out, or the connection ended
        self._t0 = None
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.bind(("127.0.0.1", 0))
        self._sock.listen()
        self.address = "127.0.0.1:%d" % self._sock.getsockname()[1]
        self._conn = None
        threading.Thread(target=self._encode_all, name="donor-encode", daemon=True).start()

    # ---- frames, ahead of the requests for them

    def _encode_all(self) -> None:
        for c in range(self.n_chunks):
            blocks = self.chunks[c]
            if isinstance(blocks, bytes):
                blocks = pickle.loads(blocks)
            lo = c * self.chunk_blocks
            positions = list(range(lo, lo + len(blocks)))
            fault = self.fault if self.fault.get("chunk") == c else {}
            if fault.get("kind") == "withhold":
                del positions[fault["block"]]
            payload = {"blocks": [blocks[i - lo] for i in positions], "done": c == self.n_chunks - 1,
                       "continuation": blocks[-1].hash}
            frame = self.codec.encode(self.msgs.MSG_IBD_BLOCKS, payload)
            if fault.get("kind") == "flip_sigscript":
                script = blocks[fault["block"]].transactions[1].inputs[0].signature_script
                at = frame.index(script) + len(script) // 2
                frame = frame[:at] + bytes([frame[at] ^ 0x01]) + frame[at + 1:]
            self.frames[c] = (frame, positions)
            self.chunks[c] = None  # the frame is what is kept
            self._encoded[c].set()

    def wait_first_frame(self, timeout: float = 120.0) -> bool:
        return self._encoded[0].wait(timeout)

    # ---- one session: one connection served to its end

    def serve(self, seconds: float) -> None:
        """Take one connection and answer it until a ``done`` chunk went out
        or it closed; the log is this session's."""
        self.seconds, self.log, self._t0 = float(seconds), [], None
        self.finished.clear()
        threading.Thread(target=self._session, name="donor-session", daemon=True).start()

    def _now(self) -> float:
        return 0.0 if self._t0 is None else time.perf_counter() - self._t0

    def _send(self, msg_type: str, payload) -> None:
        self._conn.sendall(self.codec.encode(msg_type, payload))

    def _read_exactly(self, n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            got = self._conn.recv(n - len(buf))
            if not got:
                raise ConnectionError("syncee closed")
            buf += got
        return buf

    def _session(self) -> None:
        m = self.msgs
        try:
            self._sock.settimeout(120.0)
            self._conn, _addr = self._sock.accept()
            self._conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            last = None  # the chunk the last answer carried
            while True:
                meta, body, _n = self.codec.read_frame(self._read_exactly)
                msg_type, payload = self.codec.decode(meta, body)
                if msg_type == m.MSG_VERSION:
                    self._send(m.MSG_VERSION, {"protocol_version": m.PROTOCOL_VERSION, "network": self.network,
                                               "listen_port": 0, "id": (int(payload["id"]) ^ 0x5D0A0B) or 1})
                    self._send(m.MSG_VERACK, m.PROTOCOL_VERSION)
                elif msg_type == m.MSG_REQUEST_IBD_CHAIN_INFO:
                    self._t0 = time.perf_counter()
                    self.log.append({"t": 0.0, "event": "request", "msg": msg_type})
                    self._send(m.MSG_IBD_CHAIN_INFO, self.chain_info)
                elif msg_type in (m.MSG_IBD_BLOCK_LOCATOR, m.MSG_REQUEST_ANTIPAST):
                    t = self._now()
                    if msg_type == m.MSG_IBD_BLOCK_LOCATOR:
                        self.log.append({"t": t, "event": "request", "msg": msg_type, "locator": list(payload)})
                        start = 0
                    else:
                        self.log.append({"t": t, "event": "request", "msg": msg_type, "low": payload})
                        start = self.position.get(payload, len(self.hashes) - 1) + 1
                    c = start // self.chunk_blocks
                    if self.fault.get("kind") == "repeat_chunk" and last == self.fault["chunk"]:
                        c, self.fault = last, {}  # a donor at fault repeats itself whatever the clock says
                    elif t > self.seconds or start >= len(self.hashes) or start % self.chunk_blocks:
                        # past the deadline it was told, past the list's end, or a block no chunk ends on: nothing more
                        self._send(m.MSG_IBD_BLOCKS, {"blocks": [], "done": True, "continuation": self.hashes[start - 1] if start else self.chain_info["sink"]})
                        self.log.append({"t": self._now(), "event": "chunk", "first": None, "last": None, "sent": [], "bytes": 0, "done": True})
                        break
                    self._encoded[c].wait()
                    frame, positions = self.frames[c]
                    waited = self._now() - t  # > 0 only if the encoder was behind the request
                    self._conn.sendall(frame)
                    lo = c * self.chunk_blocks
                    self.log.append({"t": self._now(), "event": "chunk", "first": lo, "last": min(lo + self.chunk_blocks, len(self.hashes)) - 1,
                                     "sent": positions, "bytes": len(frame), "done": c == self.n_chunks - 1, "encode_wait_s": waited})
                    last = c
                    if c == self.n_chunks - 1:
                        break
                # a verack, or anything else a node may say: nothing to answer
        except (OSError, ConnectionError) as e:
            self.log.append({"t": self._now(), "event": "closed", "why": f"{type(e).__name__}: {e}"})
        finally:
            self.finished.set()

    def close(self) -> None:
        for s in (self._conn, self._sock):
            try:
                if s is not None:
                    s.close()
            except OSError:
                pass


# ---- the donor as a process: pickles on stdin / stdout


def send_msg(stream, obj) -> None:
    pickle.dump(obj, stream, protocol=pickle.HIGHEST_PROTOCOL)
    stream.flush()


def recv_msg(stream):
    return pickle.load(stream)  # EOFError once the other side has closed


def main() -> int:
    out = os.fdopen(os.dup(1), "wb")
    os.dup2(2, 1)  # whatever a library prints goes to standard error, not into the channel
    inp = sys.stdin.buffer
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    t0 = time.perf_counter()
    from kaspa_tpu.p2p import transport  # noqa: F401 - the codecs and what they import, before the first command
    from kaspa_tpu.p2p.proto import codec  # noqa: F401

    send_msg(out, {"ready": True, "import_s": time.perf_counter() - t0, "pid": os.getpid()})
    donor = None
    try:
        while True:
            cmd = recv_msg(inp)
            if cmd["cmd"] == "load":
                t0 = time.perf_counter()
                if donor is not None:
                    donor.close()
                donor = Donor(**cmd["donor"])
                ok = donor.wait_first_frame()
                send_msg(out, {"address": donor.address, "chunk_blocks": donor.chunk_blocks, "chunks": donor.n_chunks,
                               "first_frame": ok, "load_s": time.perf_counter() - t0})
            elif cmd["cmd"] == "serve":
                donor.serve(cmd["seconds"])
                send_msg(out, {"serving": True})
            elif cmd["cmd"] == "wait":
                finished = donor.finished.wait(cmd["timeout"])
                send_msg(out, {"finished": finished, "log": list(donor.log)})
            elif cmd["cmd"] == "exit":
                return 0
    except EOFError:
        return 0
    finally:
        if donor is not None:
            donor.close()


if __name__ == "__main__":
    raise SystemExit(main())
