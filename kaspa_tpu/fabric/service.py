"""verifyd: the verify-fabric server.

Accepts verify super-batch jobs over the length-prefixed wire
(`fabric/wire.py`) and runs them on per-slice worker lanes:

- each connection gets a reader thread (same discipline as
  `p2p/transport.py`): one `VERIFY_REQ` frame -> one job queued onto the
  slice the client addressed;
- each slice worker pins its device dispatches with `mesh.slice_lane(i)`
  (disjoint devices when a 2-D grid is configured, no-op otherwise) and
  feeds the local CoalescingDispatcher when one is active — remote chunks
  coalesce with local traffic into the same super-batches — else calls
  the batched verify front-end directly;
- responses carry the server-side queue/verify nanoseconds and the
  slice's post-completion inflight count, so the client can graft remote
  spans into the block's flight trace and route by real occupancy.

Runnable standalone (the two-process quickstart / roundcheck fabric
drill):

    python -m kaspa_tpu.fabric.service --listen 127.0.0.1:0 --slices 2

prints one JSON line ``{"fabric_listen": "host:port", ...}`` once bound.
"""

from __future__ import annotations

import json
import os
import queue
import socket
import threading

from kaspa_tpu.utils.sync import ranked_lock
from time import perf_counter_ns

from kaspa_tpu.fabric import wire
from kaspa_tpu.observability import trace
from kaspa_tpu.observability.core import REGISTRY
from kaspa_tpu.resilience.faults import FAULTS

_REQS = REGISTRY.counter_family("fabric_service_requests", "slice", help="verify requests served per fabric slice")
_JOBS = REGISTRY.counter_family("fabric_service_jobs", "slice", help="verify jobs served per fabric slice")
_ERRORS = REGISTRY.counter("fabric_service_errors", help="verify requests answered with an error status")


class _Conn:
    """One accepted client: socket + write lock (slice workers interleave
    responses on the same stream)."""

    def __init__(self, sock: socket.socket, peer: str):
        self.sock = sock
        self.peer = peer
        self._wlock = ranked_lock("fabric.wire", reentrant=False)
        self.alive = True

    def read_exactly(self, n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError(f"fabric peer {self.peer} closed mid-frame")
            buf += chunk
        return buf

    def send(self, payload: bytes) -> None:
        with self._wlock:
            self.sock.sendall(wire.frame(payload))

    def close(self) -> None:
        self.alive = False
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self.sock.close()


class VerifyService:
    """The verifyd server; `start()` binds and returns (host, port)."""

    def __init__(self, listen: str = "127.0.0.1:0", slices: int | None = None):
        host, _, port = listen.rpartition(":")
        self.host = host or "127.0.0.1"
        self.port = int(port or 0)
        if slices is None:
            from kaspa_tpu.ops import mesh

            slices = mesh.slice_count()
        self.slices = max(1, int(slices))
        self._queues: list[queue.Queue] = [queue.Queue() for _ in range(self.slices)]  # graftlint: allow(unbounded-queue) -- per-slice dispatch handoff; producers are the bounded wire readers, a maxsize here would deadlock the service loop
        self._inflight = [0] * self.slices
        self._served = [0] * self.slices
        self._lock = ranked_lock("fabric.service", reentrant=False)
        self._listener: socket.socket | None = None
        self._conns: list[_Conn] = []
        self._threads: list[threading.Thread] = []
        self._stopped = threading.Event()
        REGISTRY.register_collector("fabric_service", self._state)

    def _state(self) -> dict:
        with self._lock:
            return {
                "listen": f"{self.host}:{self.port}",
                "slices": [
                    {"inflight": self._inflight[i], "queue_depth": self._queues[i].qsize(),
                     "served": self._served[i]}
                    for i in range(self.slices)
                ],
                "connections": sum(1 for c in self._conns if c.alive),
            }

    def start(self) -> tuple[str, int]:
        ls = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        ls.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        ls.bind((self.host, self.port))
        ls.listen(64)
        self.port = ls.getsockname()[1]
        self._listener = ls
        for i in range(self.slices):
            t = threading.Thread(target=self._slice_worker, args=(i,), name=f"fabric-slice-{i}", daemon=True)
            t.start()
            self._threads.append(t)
        t = threading.Thread(target=self._accept_loop, name="fabric-accept", daemon=True)
        t.start()
        self._threads.append(t)
        return self.host, self.port

    def stop(self) -> None:
        self._stopped.set()
        if self._listener is not None:
            self._listener.close()
        with self._lock:
            conns, self._conns = self._conns, []
        for c in conns:
            c.close()
        for q in self._queues:
            q.put(None)  # slice-worker sentinel

    # --- connection handling ------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._stopped.is_set():
            try:
                sock, addr = self._listener.accept()
            except OSError:
                return
            if self._stopped.is_set():
                # stop() closed the listener while we were blocked in
                # accept(); the in-flight syscall keeps the kernel socket
                # alive, so a reconnect racing the shutdown can still land
                # here — drop it before HELLO so the dialer fails over
                sock.close()
                return
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            conn = _Conn(sock, f"{addr[0]}:{addr[1]}")
            with self._lock:
                self._conns.append(conn)
            try:
                conn.send(wire.encode_hello(self.slices))
            except OSError:
                conn.close()
                continue
            threading.Thread(
                target=self._reader, args=(conn,), name=f"fabric-read-{conn.peer}", daemon=True
            ).start()

    def _reader(self, conn: _Conn) -> None:
        try:
            while conn.alive:
                mtype, msg = wire.read_message(conn.read_exactly)
                if mtype == wire.VERIFY_REQ:
                    self._queues[msg["slice"] % self.slices].put((conn, msg, perf_counter_ns()))  # graftlint: allow(trace-ctx-handoff) -- remote span grafting rides msg['trace_id']; the server has no local parent ctx to attach
                elif mtype == wire.STATUS_REQ:
                    with self._lock:
                        per_slice = [
                            (self._inflight[i], self._queues[i].qsize()) for i in range(self.slices)
                        ]
                    conn.send(wire.encode_status_resp(msg["req_id"], per_slice))
                # anything else from a client is ignored (forward compat)
        except (OSError, ConnectionError, wire.ProtoWireError):
            pass
        finally:
            conn.close()
            with self._lock:
                if conn in self._conns:
                    self._conns.remove(conn)

    # --- slice workers ------------------------------------------------------

    def _slice_worker(self, idx: int) -> None:
        from kaspa_tpu.ops import dispatch as coalesce
        from kaspa_tpu.ops import mesh

        while True:
            job = self._queues[idx].get()
            if job is None:
                return
            conn, msg, t_recv = job
            with self._lock:
                self._inflight[idx] += 1
            try:
                # the slice-hang drill point: "slow"/"hang" stalls this lane
                # past the client deadline; its breaker must trip as `hung`
                # while the other slices keep serving
                FAULTS.fire("fabric.slice_hang")
                t0 = perf_counter_ns()
                mask = self._verify(idx, msg["kind"], msg["items"], msg["trace_id"], coalesce, mesh)
                t1 = perf_counter_ns()
                with self._lock:
                    self._inflight[idx] -= 1
                    self._served[idx] += 1
                    inflight = self._inflight[idx]
                resp = wire.encode_verify_resp(msg["req_id"], mask, t0 - t_recv, t1 - t0, inflight)
            except Exception as e:  # noqa: BLE001 - answered, never crashes the lane
                with self._lock:
                    self._inflight[idx] -= 1
                _ERRORS.inc()
                resp = wire.encode_error_resp(msg["req_id"], f"{type(e).__name__}: {e}")
            _REQS.inc(str(idx))
            _JOBS.inc(str(idx), len(msg["items"]))
            try:
                conn.send(resp)
            except OSError:
                conn.close()

    def _verify(self, idx: int, kind: str, items: list, trace_id, coalesce, mesh):
        with trace.span("fabric.slice_verify", slice=idx, kind=kind, jobs=len(items),
                        remote_trace=trace_id or ""):
            with mesh.slice_lane(idx):
                eng = coalesce.active()
                # feed the *local* coalescing dispatcher only: when this
                # process also runs a fabric balancer (colocated client +
                # server), dispatching back into it would loop the job
                # straight out over the wire again
                if isinstance(eng, coalesce.CoalescingDispatcher):
                    return eng.submit(kind, items).wait()
                from kaspa_tpu.crypto import secp  # deferred: jax import

                return secp.verify_batch(kind, items)


def main(argv=None) -> int:
    import argparse
    import signal

    ap = argparse.ArgumentParser(description="kaspa-tpu verify-fabric server (verifyd)")
    ap.add_argument("--listen", default="127.0.0.1:0", help="HOST:PORT to bind (port 0 = ephemeral)")
    ap.add_argument("--slices", type=int, default=None,
                    help="slice worker lanes (default: mesh slice count)")
    ap.add_argument("--mesh", default=None, help="device mesh spec (N | auto | RxC)")
    # graftlint: allow(env-knob) -- verifyd exists to batch: its CLI default is auto, deliberately diverging from the in-node default of off
    ap.add_argument("--coalesce", default=os.environ.get("KASPA_TPU_COALESCE", "auto"),
                    help="local coalescing target feeding the slices (N | auto | off)")
    args = ap.parse_args(argv)

    from kaspa_tpu.utils import jax_setup

    jax_setup.setup()
    from kaspa_tpu.ops import dispatch as coalesce
    from kaspa_tpu.ops import mesh

    if args.mesh is not None:
        mesh.configure(args.mesh)
    coalesce.configure(args.coalesce)

    svc = VerifyService(args.listen, slices=args.slices)
    host, port = svc.start()
    print(json.dumps({
        "fabric_listen": f"{host}:{port}", "slices": svc.slices,
        "mesh": mesh.active_size(), "pid": os.getpid(),
    }), flush=True)

    done = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: done.set())
    done.wait()
    svc.stop()
    coalesce.shutdown(timeout=5.0)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
