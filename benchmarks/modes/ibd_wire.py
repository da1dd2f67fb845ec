"""IBD over the wire: a late joiner pulls the window's blocks from one peer
through a socket, as ``node/daemon.py`` wires it.

The syncee (the system under test) is ``kaspa_tpu.p2p.node.Node`` over the
ramp-replayed consensus and its pipeline, ``transport.connect_outbound`` to
the donor with the codec the configuration's ``p2p.wire`` names, and
``Node.ibd_from(peer)``.  From there every block comes through
``WirePeer._reader_loop`` -> ``Node._handle`` -> ``_insert_ibd_batch``; the
mode submits nothing after the ramp.  The donor (``benchmarks/donor.py``) is
a process of its own, held to the CPU.  It is started **and waited for when
this module is imported**: ``run_cell`` imports the mode before the ramp is
replayed and before the profiler starts, so the child's ≈ 3.4 s of imports
(the program's codec imports the consensus model, and that JAX) are set-up
and not the first seconds of the traced window; the ramp replay alone
(0.1 s) would hide none of them, and a traced run whose first 4 s hold no
sync has no device plane to read (PERF.md section 6, PR 35).  The window's
blocks are handed over when ``run`` starts: pickled a chunk at a time, the
first chunk unpickled and encoded before t0, the others ahead of their
requests.

t0 = the call of ``ibd_from`` once the handshake is done.  The donor stops
offering at the first request that reaches it more than ``seconds`` after
the sync's first message.  t1 = the last window block's future resolved,
seen by a done-callback on what the node's pipeline hands back (the node
keeps no futures; the tap submits nothing).  ``catchup_blocks_per_s`` =
window blocks the syncee's store holds with a final status / (t1 - t0): all
the work over all the time, the drain after the last request included.  If
the DAG runs out inside ``seconds`` a second pass starts as in
``catchup.py``: a fresh consensus and pipeline, the ramp replayed inside the
window, a fresh ``Node`` and connection; only window blocks count.

The mode never waits without a limit: ``grace_seconds`` after the window it
closes the peer and reports what is unresolved.

The pull's own side of ``correct`` is ``reference_ibd.py``'s: its four
counts, and ``hung_up`` (a connection that ended before the donor's last
chunk), are logged under their own names on the ``ibd`` line and added to
``unresolved`` (the check ``unresolved_blocks``), because
``harness.run_cell`` takes its checks from ``compare.py`` and the ledger
only.
"""

from __future__ import annotations

import atexit
import json
import os
import subprocess
import sys
import threading
import time

from benchmarks import donor as donor_mod
from benchmarks import reference_ibd
from benchmarks.harness import ROOT

FINAL = ("utxo_valid", "utxo_pending", "disqualified")  # what a block that went through the virtual stage is left with
FAULT = None  # ``control_ibd.py`` and the tests set a donor fault here around a window; a benchmark run leaves None


class _Child:
    """The donor's process: started with the CPU as its only platform, so
    that importing the program's codec can never reach for the chip."""

    def __init__(self):
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "benchmarks.donor"], stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=ROOT,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        self.ready = None

    def call(self, cmd: dict | None) -> dict:
        if cmd is not None:
            donor_mod.send_msg(self.proc.stdin, cmd)
        return donor_mod.recv_msg(self.proc.stdout)

    def wait_ready(self) -> dict:
        if self.ready is None:
            self.ready = self.call(None)
        return self.ready

    def stop(self) -> None:
        try:
            donor_mod.send_msg(self.proc.stdin, {"cmd": "exit"})
            self.proc.stdin.close()
        except (OSError, ValueError):
            pass
        try:
            self.proc.wait(5)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait(5)


_children: list = []


def _spawn() -> _Child:
    child = _Child()
    _children.append(child)
    return child


def _stop_children() -> None:
    while _children:
        _children.pop().stop()


atexit.register(_stop_children)
_prestarted = [_spawn()]
_prestarted[0].wait_ready()  # set-up time, on purpose: see the module's docstring


class _Tap:
    """When each block the node submitted resolved: a done-callback on the
    futures the pipeline hands back.  It submits nothing itself."""

    def __init__(self, pipe):
        self.submitted = 0
        self.done_at: list = []  # when each future resolved, in order
        self._cv = threading.Condition()
        real = pipe.submit

        def submit(block, *a, **kw):
            with self._cv:
                self.submitted += 1
            f = real(block, *a, **kw)
            f.add_done_callback(self._on_done)
            return f

        pipe.submit = submit

    def _on_done(self, _f) -> None:
        now = time.perf_counter()
        with self._cv:
            self.done_at.append(now)
            self._cv.notify_all()

    def wait_resolved(self, n: int, until: float) -> bool:
        with self._cv:
            while len(self.done_at) < n:
                left = until - time.perf_counter()
                if left <= 0:
                    return False
                self._cv.wait(min(left, 0.5))
        return True


def _pull(setup, consensus, pipe, child: _Child, seconds: float, grace: float, chunk_blocks: int) -> dict:
    """One sync of one fresh node from the donor: connect, ``ibd_from``, wait
    for the donor's last chunk and for the blocks it carried, at most
    ``seconds`` + ``grace`` from t0."""
    from kaspa_tpu.p2p import transport
    from kaspa_tpu.p2p.node import Node

    dag = setup.dag
    tap = _Tap(pipe)
    node = Node(consensus, name="bench", mempool_seed=setup.seed, pipeline=pipe)
    child.call({"cmd": "serve", "seconds": seconds})
    peer = None
    try:
        peer = transport.connect_outbound(node, child.address, codec=transport.get_codec(setup.config["p2p"]["wire"]))
        t0 = time.perf_counter()
        give_up = t0 + seconds + grace
        with node.lock.locked_for("ibd_from"):
            node.ibd_from(peer)
        answer = child.call({"cmd": "wait", "timeout": max(0.0, give_up - time.perf_counter())})
        log = answer["log"]
        sent = sum(len(e["sent"]) for e in log if e["event"] == "chunk")
        drained = answer["finished"] and tap.wait_resolved(sent, give_up)
    finally:
        if peer is not None:
            # ``close`` takes the node lock, which a reader wedged inside a chunk would hold: no wait without a limit
            closer = threading.Thread(target=peer.close, name="bench-close", daemon=True)
            closer.start()
            closer.join(5.0)
        del pipe.submit  # the tap comes off
    served = sorted({i for e in log if e["event"] == "chunk" for i in e["sent"]})
    n_served = (max(served) + 1) if served else 0  # a mining-order prefix of the window, by the donor's construction
    store = consensus.storage.statuses
    status_of = {i: store.get(b.hash) for i, b in enumerate(dag.blocks)}
    statuses = {i: s for i, s in status_of.items() if s is not None and i < dag.ramp + n_served}
    held = {dag.blocks[i].hash: s for i, s in status_of.items() if s is not None and i >= dag.ramp}
    window_hashes = [b.hash for b in dag.blocks[dag.ramp:]]
    counts = reference_ibd.check(window_hashes, dag.sinks[dag.ramp - 1], log, held, FINAL)
    n_held = sum(1 for i in served if status_of[dag.ramp + i] in FINAL)
    chunks = [e for e in log if e["event"] == "chunk" and e["sent"]]
    for e in chunks:
        if len(e["sent"]) != chunk_blocks and not e["done"] and FAULT is None:
            raise RuntimeError(f"the donor sent a chunk of {len(e['sent'])} blocks, the program's IBD_BATCH_SIZE is {chunk_blocks}")
    return {
        "t0": t0, "t1": max(tap.done_at[-1:] + [t0]), "served": len(served), "n_served": n_served, "held": n_held,
        "drained": bool(drained), "statuses": statuses, "counts": counts, "done_at": tap.done_at,
        "whole_dag": n_served == len(window_hashes), "consensus": consensus,
        "log": {"requests": sum(1 for e in log if e["event"] == "request"), "chunks": len(chunks),
                "bytes": sum(e["bytes"] for e in chunks), "chunk_sent_at": [round(e["t"], 3) for e in chunks],
                "encode_wait_s": round(sum(e.get("encode_wait_s", 0.0) for e in chunks), 4),
                "closed": [e["why"] for e in log if e["event"] == "closed"], "resubmitted": tap.submitted - len(served)},
    }


def run(setup, consensus, pipe, seconds: float) -> dict:
    from kaspa_tpu.p2p import node as node_mod

    dag, wl = setup.dag, setup.workload
    grace = float(wl.get("grace_seconds", 30.0))
    chunk_blocks = int(node_mod.IBD_BATCH_SIZE)
    window = dag.blocks[dag.ramp:]
    last_sink = dag.sinks[-1]

    # ---- before t0: the donor gets its blocks and encodes the first frame
    t_run = time.perf_counter()
    child = _prestarted.pop() if _prestarted else _spawn()
    handed = donor_mod.split(window, chunk_blocks, pickled=True)  # while the child may still be importing
    ready = child.wait_ready()
    loaded = child.call({"cmd": "load", "donor": {
        **handed, "sink": last_sink, "sink_blue_work": next(b for b in reversed(window) if b.hash == last_sink).header.blue_work,
        "pruning_point": dag.params.genesis.hash, "network": dag.params.name, "codec_name": setup.config["p2p"]["wire"],
        "chunk_blocks": chunk_blocks, "fault": FAULT,
    }})
    if loaded["chunk_blocks"] != chunk_blocks or not loaded["first_frame"]:
        raise RuntimeError(f"the donor chunks by {loaded['chunk_blocks']}, the program's IBD_BATCH_SIZE is {chunk_blocks}")
    child.address = loaded["address"]
    handover_s = time.perf_counter() - t_run

    pulls, errors = [], []
    try:
        first = _pull(setup, consensus, pipe, child, seconds, grace, chunk_blocks)
        pulls.append(first)
        deadline = first["t0"] + seconds
        # the DAG ran out inside the window: a second pass, the ramp replayed inside the window
        while pulls[-1]["whole_dag"] and pulls[-1]["drained"] and time.perf_counter() < deadline:
            setup.log(f"second pass: the DAG ran out after {time.perf_counter() - first['t0']:.2f} s of {seconds} s")
            consensus, pipe = setup.fresh_pipeline()
            setup.replay_ramp(pipe)
            again = _pull(setup, consensus, pipe, child, max(0.0, deadline - time.perf_counter()), grace, chunk_blocks)
            if not again["served"]:
                break  # it asked after the deadline: a pass that pulled nothing is no pass
            pulls.append(again)
    except Exception as e:  # noqa: BLE001 - a later pass that broke is counted, not raised
        if not pulls:
            raise
        errors.append(f"{type(e).__name__}: {e}")
    finally:
        child.stop()
        _children.remove(child)

    t0, t1 = first["t0"], max(p["t1"] for p in pulls)
    elapsed = t1 - t0
    attempted = sum(p["served"] for p in pulls)
    held = sum(p["held"] for p in pulls)
    counts = {k: sum(p["counts"][k] for p in pulls) for k in first["counts"]}
    counts["hung_up"] = sum(len(p["log"]["closed"]) for p in pulls)  # a connection that ended before the donor's last chunk
    for e in errors[:5]:
        setup.log("error " + e)
    done_at = [t for p in pulls for t in p["done_at"]]
    setup.log("ibd " + json.dumps({
        **counts, "chunk_blocks": chunk_blocks, "wire": setup.config["p2p"]["wire"], "passes": len(pulls),
        "served": attempted, "held": held, "drained": all(p["drained"] for p in pulls),
        "donor_import_s": ready["import_s"], "donor_load_s": loaded["load_s"], "handover_s": handover_s,
        "pulls": [p["log"] for p in pulls],
    }))
    disagreements = sum(counts.values()) + sum(1 for p in pulls if not p["drained"]) + len(errors)
    return {
        "attempted": attempted,
        "failed": (attempted - held) + disagreements,
        "unresolved": (attempted - held) + disagreements,
        "blocks": held,
        "seconds": elapsed,
        "passes": [{"consensus": p["consensus"], "prefix": dag.ramp + p["n_served"], "statuses": p["statuses"]} for p in pulls],
        "end_to_end": {"catchup_blocks_per_s": held / elapsed} if elapsed > 0 and held else {},
        "harness": {},
        "facts": {"passes": len(pulls), "submit_seconds": seconds, "drain_seconds": max(0.0, t1 - deadline),
                  "before_t0_seconds": t0 - t_run, "chunks": sum(p["log"]["chunks"] for p in pulls),
                  "resolved_by_quarter": [sum(1 for t in done_at if t <= t0 + q * seconds / 4) for q in (1, 2, 3, 4)]},
    }
