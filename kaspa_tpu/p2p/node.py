"""In-process P2P: routers, flows, and block/tx relay between nodes.

Reference: protocol/p2p (Adaptor/Router/Hub over tonic gRPC, ~60 payload
types) and protocol/flows (one task per flow per peer: handshake, block
relay with orphan resolution, tx relay, IBD).  This round models the flow
layer over an in-process transport — the same peer/message/flow shapes,
synchronous delivery — matching the reference's own in-process daemon
integration strategy (testing/integration/src/common/daemon.rs).  The
tonic-equivalent wire transport (C++ gRPC/asio) binds underneath in a
later milestone without changing the flow logic.

Messages are (type, payload) tuples; types mirror p2p.proto payload names.
"""

from __future__ import annotations

import os
from collections import deque
from dataclasses import dataclass, field
from time import monotonic as _monotonic

from kaspa_tpu.consensus.consensus import Consensus, RuleError
from kaspa_tpu.consensus.stores import StatusesStore
from kaspa_tpu.consensus.model.block import Block
from kaspa_tpu.mempool import MiningManager
from kaspa_tpu.mempool.mempool import MempoolError
from kaspa_tpu.observability import flight, trace
from kaspa_tpu.observability.core import REGISTRY
from kaspa_tpu.utils.sync import LockCtx

# p2p.proto payload types modeled this round
MSG_VERSION = "version"
MSG_VERACK = "verack"
MSG_INV_BLOCK = "invrelayblock"
MSG_REQUEST_BLOCK = "requestrelayblocks"
MSG_BLOCK = "block"
MSG_INV_TXS = "invtransactions"
MSG_REQUEST_TXS = "requesttransactions"
MSG_TX = "transaction"
MSG_IBD_BLOCKS = "ibdblocks"
# proof-based IBD (flows/src/ibd/flow.rs negotiate + headers-proof path)
MSG_REQUEST_IBD_CHAIN_INFO = "requestibdchaininfo"
MSG_IBD_CHAIN_INFO = "ibdchaininfo"
MSG_REQUEST_PRUNING_PROOF = "requestpruningpointproof"
MSG_PRUNING_PROOF = "pruningpointproof"
MSG_REQUEST_TRUSTED_DATA = "requestpruningpointtrusteddata"
MSG_TRUSTED_DATA = "pruningpointtrusteddata"
MSG_REQUEST_PP_UTXOS = "requestpruningpointutxoset"
MSG_PP_UTXO_CHUNK = "pruningpointutxosetchunk"
# KIP-21 lane-state sync (flows/src/ibd/flow.rs:145-150 sync_new_smt_state)
MSG_REQUEST_PP_SMT = "requestpruningpointsmtstate"
MSG_PP_SMT_CHUNK = "pruningpointsmtstatechunk"
# locator sync negotiation (flows/src/ibd/negotiate.rs + sync/mod.rs)
MSG_IBD_BLOCK_LOCATOR = "ibdblocklocator"
MSG_REQUEST_ANTIPAST = "requestantipast"

IBD_BATCH_SIZE = 512  # blocks per IBD chunk (ibd/flow.rs IBD_BATCH_SIZE shape)
# address exchange (flows/src/v7/address.rs)
MSG_REQUEST_ADDRESSES = "requestaddresses"
MSG_ADDRESSES = "addresses"

PP_UTXO_CHUNK_SIZE = 4096  # entries per chunk (ibd/flow.rs utxo chunking)
PP_SMT_CHUNK_SIZE = 4096  # lanes/anchors per chunk (ibd SMT_CHUNK_SIZE role)

# v8 body-only sync (flows/src/v8/request_block_bodies.rs): bodies for
# blocks whose headers the requester already holds
MSG_REQUEST_BLOCK_BODIES = "requestblockbodies"
MSG_BLOCK_BODIES = "blockbodies"
# headers-first sync (request_headers.rs RequestHeaders/BlockHeaders):
# stream headers above a chain anchor, bodies follow via the v8 flow
MSG_REQUEST_HEADERS = "requestheaders"
MSG_HEADERS = "blockheaders"
# typed pre-disconnect diagnostic (p2p.proto RejectMessage)
MSG_REJECT = "reject"

# Protocol-version tiers (flows/src/{v7,v8,v10}/mod.rs + flow_context.rs:63):
# v7 = base flow set, v8/v9 = + block-body requests (body-only IBD),
# v10 = + pruning-point SMT state (Toccata).  The handshake negotiates
# min(local, peer) and flows outside the negotiated tier are refused.
PROTOCOL_VERSION = 10
MIN_PROTOCOL_VERSION = 7
_MSG_MIN_VERSION = {
    MSG_REQUEST_BLOCK_BODIES: 8,
    MSG_BLOCK_BODIES: 8,
    MSG_REQUEST_HEADERS: 8,  # headers-first rides the body-only tier
    MSG_HEADERS: 8,
    MSG_REQUEST_PP_SMT: 10,
    MSG_PP_SMT_CHUNK: 10,
}
# one day before Toccata activation upgraded nodes stop accepting outdated
# peers (flow_context.rs:827-838)
_ACTIVATION_GATE_SECONDS = 24 * 60 * 60

# peer misbehavior accounting (flows ProtocolError + the reference's
# ban-score ladder): repeat offenses accumulate per connection; crossing
# the threshold bans the peer's IP in the address manager, which both
# refuses future inbound accepts and stops outbound redials
PEER_BAN_SCORE = int(os.environ.get("KASPA_TPU_BAN_SCORE", "100"))
# tx-relay hygiene ladder: sustained hostility crosses the ban threshold,
# honest noise (the odd orphan, a lost RBF race) never does
TX_ORPHAN_POINTS = 2  # orphan storm: ban after ~50 parentless relays
TX_DOUBLE_SPEND_POINTS = 5  # double-spend/RBF-churn chains: ban after ~20
TX_INVALID_POINTS = 30  # invalid signature/script: outright hostile
# an INV larger than this is a flood, not gossip (the reference bounds
# inv batching at MAX_INV_PER_TX_INV_MSG)
MAX_INV_PER_MSG = 512
# a requested txid the peer never delivered stops shadowing re-requests
# after this long
TX_REQUEST_TTL_SECONDS = 30.0
# an IBD donor that stops making progress (no message advancing the sync
# for this long) is abandoned — the one-active-sync slot must not be
# wedgeable by a stalled or malicious peer
IBD_DEADLINE_SECONDS = float(os.environ.get("KASPA_TPU_IBD_DEADLINE", "120"))

_MISBEHAVIOR_POINTS = REGISTRY.counter_family(
    "p2p_misbehavior_points", "reason", help="misbehavior points assessed, by offense"
)
_PEERS_BANNED = REGISTRY.counter("p2p_peers_banned", help="peers that crossed the ban-score threshold")
_IBD_TIMEOUTS = REGISTRY.counter("p2p_ibd_timeouts", help="in-flight syncs abandoned for lack of progress")
_IBD_CHUNKS_RX = REGISTRY.counter("p2p_ibd_chunks_rx", help="IBD batches that carried blocks and went through the pipeline")
_IBD_BLOCKS_RX = REGISTRY.counter("p2p_ibd_blocks_rx", help="blocks of those batches")
_IBD_BLOCKS_REJECTED = REGISTRY.counter("p2p_ibd_blocks_rejected", help="blocks of those batches the pipeline refused (RuleError), skipped")
from kaspa_tpu.observability.shed import SHED as _SHED  # noqa: E402  (family declared once there)

# serve-side SMT snapshot lifetime (prune_caches): a snapshot nobody has
# requested for the TTL is dead weight (it holds the full lane/segment
# export); one whose anchor the local pruning point has moved past gets a
# shorter grace so a receiver mid-page (which refreshes last-use every
# chunk request) can finish, but an abandoned transfer cannot pin it
SMT_SNAPSHOT_TTL_SECONDS = 300.0
SMT_SNAPSHOT_STALE_GRACE_SECONDS = 60.0


def _activation_gate_blocks(target_time_per_block_ms: int) -> int:
    """DAA-score horizon equal to one day of blocks.  Division before
    rounding: the old per-second blocks-rate factor collapsed to 1 for any
    target slower than 1 BPS (round(1000/10000) == 0 → clamped to 1), which
    turned the one-day gate into ten days on sub-1-BPS networks."""
    return round(_ACTIVATION_GATE_SECONDS * 1000 / target_time_per_block_ms)


class ProtocolError(Exception):
    """Peer misbehavior that warrants disconnect/ban (flows ProtocolError).

    ``points`` is the misbehavior score the reader loop assesses before
    dropping the connection.  Handshake outcomes that reflect OUR state or
    a misconfiguration rather than hostility (self-connection via our own
    gossiped address, wrong network, version mismatch, busy sync slot) set
    0 — banning by IP on those would take out every co-hosted node behind
    the same address."""

    def __init__(self, msg: str, points: int = 100):
        super().__init__(msg)
        self.points = points


@dataclass
class Peer:
    """Router endpoint for one connection (p2p/src/core/router.rs)."""

    node: "Node"
    remote: "Peer | None" = None
    handshaken: bool = False
    # negotiated protocol tier: min(our version, peer's advertised
    # version); floored until the handshake so pre-handshake messages
    # from later tiers are refused, not served
    protocol_version: int = MIN_PROTOCOL_VERSION
    inbox: deque = field(default_factory=deque)
    known_blocks: set = field(default_factory=set)
    known_txs: set = field(default_factory=set)
    # the remote node's identity nonce (learned from its version message);
    # link-level fault planes key partitions on (our id, remote_id)
    remote_id: int | None = None

    def send(self, msg_type: str, payload) -> None:
        """Enqueue on the remote peer's inbox and drain it (sync transport)."""
        self.remote.inbox.append((msg_type, payload))
        self.remote.node._drain(self.remote)


class Node:
    """A full node instance: consensus + mempool + flow handlers + hub."""

    def __init__(
        self,
        consensus: Consensus,
        name: str = "node",
        mempool_seed: int | None = None,
        template_debounce: float = 0.0,
        ident: int | None = None,
        pipeline=None,
    ):
        from kaspa_tpu.consensus.manager import ConsensusManager
        from kaspa_tpu.pipeline import ConsensusPipeline

        from kaspa_tpu.ingest import IngestTier

        self.name = name
        self.cmgr = ConsensusManager(consensus)
        # deterministic template-selection sampling: the same seed makes
        # frontier weighted sampling (and thus SUSTAIN fingerprints)
        # byte-reproducible across runs and across the consensus swaps below
        self.mempool_seed = mempool_seed
        # tx-churn template rebuilds collapse to one per debounce window
        # (0 = rebuild on next request, the historical behavior)
        self.template_debounce = template_debounce
        self.mining = MiningManager(consensus, seed=mempool_seed, template_debounce=template_debounce)
        # requested-but-undelivered txids: txid -> request time.  Shared
        # across peers so N connections advertising the same flood tx cost
        # one request, not N (flowcontext transactions_spread dedup role)
        self._tx_requested: dict[bytes, float] = {}
        # requested-but-undelivered relay blocks: in a mesh of N peers the
        # same INV arrives from every neighbor while the first copy is
        # still in flight or mid-validation; without this ledger each
        # arrival re-requests the block and one INV burst amplifies into
        # O(peers) block transfers per node (the swarm drill's
        # relay-amplification budget measures exactly this)
        self._block_requested: dict[bytes, float] = {}
        # wired by the daemon; None in bare in-process tests (flows no-op)
        self.address_manager = None
        self.listen_port = 0  # advertised in the version handshake
        import secrets

        # per-node identity nonce (the reference's version message peer id):
        # a version carrying OUR id is a self-connection and is dropped.
        # ``ident`` pins it (swarm drills: link-level partitions key on it
        # and the event log must be byte-reproducible); default stays random
        self.id = secrets.randbits(64) if ident is None else int(ident)
        # advertised protocol tier; tests cap this to simulate old peers
        self.protocol_version = PROTOCOL_VERSION
        self.cmgr.on_swap(self._on_consensus_swap)
        self.peers: list = []  # the Hub (p2p/src/core/hub.rs)
        self.orphan_blocks: dict[bytes, Block] = {}  # flowcontext/orphans.rs
        self._ibd: dict = {}  # proof-IBD state machine (one active sync)
        # the peer ibd_from() asked, until its last chunk is in or it has
        # nothing to give: while set, that peer's reader records its waits
        # on the socket as wait.p2p_frame spans
        self._sync_peer = None
        # single-writer discipline: wire reader threads and RPC dispatch all
        # serialize consensus/mempool access through this lock.  Ranked
        # BELOW the pipeline's consensus-commit lock (rank 10): handlers
        # take node -> commit, never the inverse (LockCtx asserts this
        # under KASPA_TPU_LOCK_DEBUG)
        self.lock = LockCtx("node", rank=5)
        # the concurrent pipeline IS the block intake — relay, RPC submit and
        # IBD all flow through it (the reference runs its 4-processor
        # pipeline always, consensus/src/consensus/mod.rs:369-401; there is
        # no synchronous alternative path).  A consensus that already runs
        # behind a pipeline (one that replayed a chain into it before the
        # node came up) hands that pipeline over: a second one over the same
        # consensus would be a second virtual worker
        if pipeline is not None and pipeline.consensus is not consensus:
            raise ValueError("the pipeline handed over runs another consensus")
        self.pipeline = pipeline if pipeline is not None else ConsensusPipeline(consensus, workers=2)
        # batched admission front door (kaspa_tpu/ingest/): RPC submits and
        # P2P relay enqueue tickets; whoever pumps under the node lock
        # admits every concurrently-queued entrant in one wave with a single
        # coalesced verify dispatch (the standalone_tx traffic class)
        self.ingest = IngestTier(self.mining, lock=self.lock)
        # INV-relay damping (resilience/overload.py brownout seam): while
        # set, outbound tx INVs are suppressed — peers re-learn the pool
        # from post-recovery gossip; block relay is never damped
        self.relay_damping = False

    def set_relay_damping(self, active: bool) -> None:
        self.relay_damping = bool(active)

    @property
    def consensus(self) -> Consensus:
        return self.cmgr.consensus

    def _on_consensus_swap(self, new_consensus) -> None:
        """Staging commit: rebuild the mempool facade on the new consensus
        (pending txs are dropped — they reference the stale DAG)."""
        from kaspa_tpu.pipeline import ConsensusPipeline

        self.mining = MiningManager(
            new_consensus, seed=self.mempool_seed, template_debounce=self.template_debounce
        )
        self.ingest.mining = self.mining  # queued entrants admit against the new DAG
        self._drop_ibd_pipeline()
        old = self.pipeline
        self.pipeline = ConsensusPipeline(new_consensus, workers=2)
        old.shutdown()

    def _drop_ibd_pipeline(self) -> None:
        cached = getattr(self, "_ibd_pipeline", None)
        if cached is not None:
            self._ibd_pipeline = None
            cached[1].shutdown()

    def shutdown(self) -> None:
        """Tear down one node instance cleanly: close every peer link and
        stop the worker pools.  Multi-instance hosts (swarm drills spin up
        N nodes in one process) call this per node so the fleet's threads
        and sockets don't outlive the run."""
        for peer in list(self.peers):
            if hasattr(peer, "close"):
                try:
                    peer.close()
                except Exception:
                    pass
        self.peers.clear()
        self._drop_ibd_pipeline()
        self.pipeline.shutdown()

    def prune_caches(self, now: float | None = None) -> None:
        """Drop serve-side IBD snapshots that outlived their usefulness.

        Called under ``self.lock`` (SMT request handler + the daemon's
        metrics tick).  The SMT snapshot ``(anchor_pp, state, last_use)``
        dies when idle past SMT_SNAPSHOT_TTL_SECONDS, or — once the local
        pruning point has advanced past its anchor — after the shorter
        stale grace (an active receiver refreshes last_use every chunk
        request and finishes; an abandoned transfer cannot pin the export
        forever).  The UTXO snapshot is keyed to the live pruning point
        only, so it drops as soon as the anchor moves.
        """
        now = _monotonic() if now is None else now
        if self._ibd:
            # IBD progress deadline: _handle refreshes last_progress on
            # every message from the donor; a donor that goes quiet past
            # the deadline loses the (single) sync slot and the connection
            last = self._ibd.setdefault("last_progress", now)
            if now - last > IBD_DEADLINE_SECONDS:
                stalled, self._ibd = self._ibd, {}
                _IBD_TIMEOUTS.inc()
                staging = stalled.get("staging")
                if staging is not None:
                    staging.cancel()
                self._drop_ibd_pipeline()
                donor = stalled.get("peer")
                self.score_misbehavior(donor, "ibd_stall", 40)
                if donor is not None and hasattr(donor, "close"):
                    donor.close()
        pp = self.consensus.pruning_processor.pruning_point
        snap = getattr(self, "_pp_smt_snapshot", None)
        if snap is not None:
            # tests prime bare (pp, state) snapshots; treat those as fresh
            anchor, last_use = snap[0], (snap[2] if len(snap) > 2 else now)
            limit = SMT_SNAPSHOT_TTL_SECONDS if anchor == pp else SMT_SNAPSHOT_STALE_GRACE_SECONDS
            if now - last_use > limit:
                self._pp_smt_snapshot = None
        usnap = getattr(self, "_pp_utxo_snapshot", None)
        if usnap is not None and usnap[0] != pp:
            self._pp_utxo_snapshot = None

    def score_misbehavior(self, peer, reason: str, points: int) -> bool:
        """Assess misbehavior points against ``peer``; True once banned.

        Per-connection accumulator with an IP-level consequence: crossing
        PEER_BAN_SCORE bans the address in the address manager (inbound
        accepts refused, outbound dials stopped, gossip filtered).  Callers
        decide whether to also close the connection — the reader loop is
        usually already unwinding it.
        """
        if peer is None:
            return False
        score = getattr(peer, "misbehavior_score", 0) + points
        peer.misbehavior_score = score
        _MISBEHAVIOR_POINTS.inc(reason, points)
        if score < PEER_BAN_SCORE:
            return False
        _PEERS_BANNED.inc()
        addr = getattr(peer, "peer_address", None)
        if self.address_manager is not None and addr is not None:
            self.address_manager.ban(addr.ip)
        return True

    # --- hub / relay (flow_context.rs on_new_block -> broadcast) ---

    def broadcast_block(self, block: Block) -> None:
        # snapshot: a failed send self-removes the peer from self.peers
        for peer in list(self.peers):
            if block.hash not in peer.known_blocks:
                peer.known_blocks.add(block.hash)
                peer.send(MSG_INV_BLOCK, block.hash)

    def broadcast_tx(self, tx) -> None:
        if self.relay_damping:
            if self.peers:
                _SHED.inc("inv_damping")
            return
        for peer in list(self.peers):
            if tx.id() not in peer.known_txs:
                peer.known_txs.add(tx.id())
                peer.send(MSG_INV_TXS, [tx.id()])

    def submit_block(self, block: Block) -> str:
        status = self.pipeline.validate_and_insert_block(block)
        self._on_new_block_transactions(block)
        self._try_unorphan(block.hash)
        self.broadcast_block(block)
        return status

    def _on_new_block_transactions(self, block: Block) -> None:
        """The mempool's half of ``on_new_block`` (flow_context.rs): the
        block's transactions leave the pool, and the orphans it gave parents
        go back through admission together (mining manager.rs
        ``handle_new_block_transactions`` + ``revalidate``)."""
        unorphaned = self.mining.handle_new_block_transactions(
            block.transactions, self.consensus.get_virtual_daa_score()
        )
        if unorphaned:
            self.ingest.resubmit([entry.tx for entry in unorphaned])

    def submit_transaction(self, tx) -> list[bytes]:
        """RPC-facing admission through the batched ingest tier.

        Same contract as the old direct call — raises on rejection, parks
        orphans silently, returns RBF-evicted txids — but concurrent
        submitters now share one verify wave, and the relay only carries
        txs that actually entered a pool."""
        from kaspa_tpu.ingest import SOURCE_RPC

        ticket = self.ingest.admit(tx, SOURCE_RPC)
        evicted = ticket.raise_for_status()
        self.broadcast_tx(tx)
        return evicted

    # --- flow handlers (protocol/flows/src/v7/) ---

    def _drain(self, peer: Peer) -> None:
        # re-entrancy guard: a handler that triggers a send back to this
        # peer (chunked IBD ping-pong) must ENQUEUE, not recurse — the
        # outer drain loop picks the message up iteratively
        if getattr(peer, "_draining", False):
            return
        peer._draining = True
        try:
            while peer.inbox:
                msg_type, payload = peer.inbox.popleft()
                self._handle(peer, msg_type, payload)
        finally:
            peer._draining = False

    def _handle(self, peer: Peer, msg_type: str, payload) -> None:
        # any message from the active IBD donor counts as sync progress
        # (the deadline in prune_caches fires on silence, not slowness)
        if self._ibd and self._ibd.get("peer") is peer:
            self._ibd["last_progress"] = _monotonic()
        # tier gate: flows introduced in a later protocol version than the
        # negotiated one are refused (the reference simply never registers
        # them for the old tier, flow_context.rs:837-852)
        min_v = _MSG_MIN_VERSION.get(msg_type)
        if min_v is not None and peer.protocol_version < min_v:
            raise ProtocolError(
                f"message {msg_type} requires protocol v{min_v} but v{peer.protocol_version} was negotiated"
            )
        if msg_type == MSG_VERSION:
            # handshake.rs: version negotiation incl. network match
            if isinstance(payload, dict) and payload.get("network", self.consensus.params.name) != self.consensus.params.name:
                raise ProtocolError(f"network mismatch: {payload.get('network')}", points=0)
            peer_pv = payload.get("protocol_version", MIN_PROTOCOL_VERSION) if isinstance(payload, dict) else MIN_PROTOCOL_VERSION
            if peer_pv < MIN_PROTOCOL_VERSION:
                raise ProtocolError(
                    f"protocol version mismatch: ours {self.protocol_version}, peer {peer_pv}", points=0
                )
            # one day before Toccata activation, refuse pre-Toccata tiers:
            # a v<10 peer cannot serve/receive lane state and would fork
            # (flow_context.rs:827-841)
            params = self.consensus.params
            gate_daa = self.consensus.get_virtual_daa_score() + _activation_gate_blocks(
                params.target_time_per_block
            )
            if params.toccata_active(gate_daa) and peer_pv < 10:
                raise ProtocolError(
                    f"protocol v10 required near Toccata activation (peer advertises v{peer_pv})", points=0
                )
            peer.protocol_version = min(self.protocol_version, peer_pv)
            if isinstance(payload, dict) and payload.get("id"):
                # link identity for the partition fault plane (swarm drills)
                peer.remote_id = payload["id"]
            if isinstance(payload, dict) and payload.get("id") and payload["id"] == self.id:
                # gossip taught us our own address and we dialed ourselves;
                # scrub the LISTEN address (what gossip stored), not the
                # dialing socket's ephemeral source address
                if self.address_manager is not None and getattr(peer, "peer_address", None):
                    from kaspa_tpu.p2p.address_manager import NetAddress

                    self.address_manager.remove(peer.peer_address)
                    if payload.get("listen_port"):
                        self.address_manager.remove(
                            NetAddress(peer.peer_address.ip, payload["listen_port"])
                        )
                if hasattr(peer, "close"):
                    peer.close()
                raise ProtocolError("self-connection detected (matching version id)", points=0)
            # record the peer's advertised listen address for gossip
            # (flow_context.rs registers it with the address manager)
            if (
                isinstance(payload, dict)
                and payload.get("listen_port")
                and getattr(peer, "peer_address", None) is not None
            ):
                from kaspa_tpu.p2p.address_manager import NetAddress

                # remember the peer's listen identity on the peer itself so
                # the connection manager never back-dials a live inbound peer
                peer.advertised_address = NetAddress(peer.peer_address.ip, payload["listen_port"])
                if self.address_manager is not None:
                    self.address_manager.add_address(peer.advertised_address)
            if not getattr(peer, "version_sent", True):
                # inbound wire peer: reciprocate with our own version
                peer.version_sent = True
                peer.send(
                    MSG_VERSION,
                    {
                        "protocol_version": self.protocol_version,
                        "network": self.consensus.params.name,
                        "listen_port": self.listen_port,
                        "id": self.id,
                    },
                )
            peer.send(MSG_VERACK, self.protocol_version)
        elif msg_type == MSG_VERACK:
            peer.handshaken = True
            if self.address_manager is not None:
                peer.send(MSG_REQUEST_ADDRESSES, {})
        elif msg_type == MSG_REQUEST_ADDRESSES:
            peers = []
            if self.address_manager is not None:
                import itertools

                peers = [
                    str(a)
                    for a in itertools.islice(
                        self.address_manager.iterate_prioritized_random_addresses(), 256
                    )
                ]
            peer.send(MSG_ADDRESSES, peers)
        elif msg_type == MSG_ADDRESSES:
            # gossip intake: feed the address manager (ban-filtered there)
            if self.address_manager is not None:
                from kaspa_tpu.p2p.address_manager import NetAddress

                for a in payload[:256]:
                    try:
                        self.address_manager.add_address(NetAddress.parse(a))
                    except ValueError:
                        continue
        elif msg_type == "ping":
            peer.send("pong", payload)
        elif msg_type == "pong":
            pass
        elif msg_type == MSG_INV_BLOCK:
            # blockrelay/flow.rs: request unknown relay blocks — but only
            # once per block fleet-wide: in a mesh every neighbor relays
            # the same INV while the first copy is still in flight, and
            # re-requesting from each would amplify one burst into
            # O(peers) transfers per node (see _block_requested)
            now = _monotonic()
            if self._block_requested:
                self._block_requested = {
                    h: ts for h, ts in self._block_requested.items()
                    if now - ts < TX_REQUEST_TTL_SECONDS
                }
            if (
                not self.consensus.storage.statuses.is_valid(payload)
                and payload not in self.orphan_blocks
                and payload not in self._block_requested
                and not self.pipeline.deps.is_pending(payload)
            ):
                self._block_requested[payload] = now
                peer.send(MSG_REQUEST_BLOCK, [payload])
        elif msg_type == MSG_REQUEST_BLOCK:
            for h in payload:
                if self.consensus.storage.block_transactions.has(h):
                    header = self.consensus.storage.headers.get(h)
                    txs = self.consensus.storage.block_transactions.get(h)
                    peer.send(MSG_BLOCK, Block(header, txs))
        elif msg_type == MSG_BLOCK:
            self._on_relay_block(peer, payload)
        elif msg_type == MSG_INV_TXS:
            if len(payload) > MAX_INV_PER_MSG:
                # inventory flood: refuse the oversized frame, charge the
                # sender, and don't fan N*request traffic out of it
                self.score_misbehavior(peer, "inv_flood", 20)
                return
            now = _monotonic()
            # expire requests a peer never answered so re-advertisement works
            if self._tx_requested:
                self._tx_requested = {
                    t: ts for t, ts in self._tx_requested.items()
                    if now - ts < TX_REQUEST_TTL_SECONDS
                }
            mempool = self.mining.mempool
            unknown = [
                t
                for t in payload
                if not mempool.has(t) and t not in mempool.accepted and t not in self._tx_requested
            ]
            if unknown:
                for t in unknown:
                    self._tx_requested[t] = now
                peer.send(MSG_REQUEST_TXS, unknown)
        elif msg_type == MSG_REQUEST_TXS:
            for txid in payload[:MAX_INV_PER_MSG]:
                entry = self.mining.mempool.get(txid)
                if entry is not None:
                    peer.known_txs.add(txid)
                    peer.send(MSG_TX, entry.tx)
        elif msg_type == MSG_TX:
            self._on_relay_tx(peer, payload)
        elif msg_type == MSG_IBD_BLOCK_LOCATOR:
            # negotiate.rs donor side: highest locator entry we know anchors
            # the antipast query; unknown locator => serve from our pruning
            # point (the syncer should have proof-synced first)
            reach = self.consensus.reachability
            sink = self.consensus.sink()
            # only a chain ancestor of our sink anchors the walk safely:
            # retained anticone blocks near the retention boundary may have
            # had their selected-parent chain pruned underneath them
            common = next(
                (h for h in payload if reach.has(h) and reach.is_chain_ancestor_of(h, sink)),
                None,
            )
            if common is None:
                common = self.consensus.pruning_processor.pruning_point
            self._serve_antipast_chunk(peer, common)
        elif msg_type == MSG_REQUEST_ANTIPAST:
            # continuation request: low is the highest chain block the
            # previous chunk reached (flow.rs IBD batching).  Re-apply the
            # same pruning-safe anchoring as the locator path, and ALWAYS
            # reply — a silently dropped continuation would wedge the
            # syncer's _ibd state forever
            reach = self.consensus.reachability
            sink = self.consensus.sink()
            low = payload
            if not (reach.has(low) and reach.is_chain_ancestor_of(low, sink)):
                low = self.consensus.pruning_processor.pruning_point
            self._serve_antipast_chunk(peer, low)
        elif msg_type == MSG_IBD_BLOCKS:
            staging = self._ibd.get("staging") if self._ibd.get("peer") is peer else None
            target = staging.consensus if staging is not None else self.consensus
            self._insert_ibd_batch(target, payload["blocks"])
            if not payload["done"]:
                # bounded chunks: pull the next batch from where we stopped
                peer.send(MSG_REQUEST_ANTIPAST, payload["continuation"])
                return
            if self._sync_peer is peer:
                self._sync_peer = None
            if staging is not None:
                self._finalize_proof_ibd(staging)
        elif msg_type == MSG_REQUEST_IBD_CHAIN_INFO:
            sink = self.consensus.sink()
            peer.send(
                MSG_IBD_CHAIN_INFO,
                {
                    "sink": sink,
                    "sink_blue_work": self.consensus.storage.ghostdag.get_blue_work(sink),
                    "pruning_point": self.consensus.pruning_processor.pruning_point,
                },
            )
        elif msg_type == MSG_IBD_CHAIN_INFO:
            self._on_chain_info(peer, payload)
        elif msg_type == MSG_REQUEST_PRUNING_PROOF:
            peer.send(MSG_PRUNING_PROOF, self.consensus.pruning_proof_manager.build_proof())
        elif msg_type == MSG_PRUNING_PROOF:
            if self._ibd.get("peer") is peer and self._ibd.get("phase") == "proof":
                # early tier gate: the proof's claimed PP header reveals a
                # post-Toccata bootstrap before the (much larger) trusted
                # data + UTXO set are transferred; the authoritative check
                # after proof validation remains in _on_pp_utxo_chunk
                if payload and payload[0] and peer.protocol_version < 10:
                    claimed_pp = payload[0][-1]
                    if self.consensus.params.toccata_active(claimed_pp.daa_score):
                        self._ibd = {}
                        raise ProtocolError(
                            "peer protocol tier too old for a post-Toccata bootstrap (needs v10)"
                        )
                self._ibd["proof"] = payload
                self._ibd["phase"] = "trusted"
                peer.send(MSG_REQUEST_TRUSTED_DATA, {})
        elif msg_type == MSG_REQUEST_TRUSTED_DATA:
            peer.send(MSG_TRUSTED_DATA, self.consensus.pruning_proof_manager.get_trusted_data())
        elif msg_type == MSG_TRUSTED_DATA:
            if self._ibd.get("peer") is peer and self._ibd.get("phase") == "trusted":
                self._ibd["trusted"] = payload
                self._ibd["phase"] = "utxos"
                self._ibd["utxo"] = {}
                peer.send(MSG_REQUEST_PP_UTXOS, 0)
        elif msg_type == MSG_REQUEST_PP_UTXOS:
            # snapshot the sorted item list once per pruning point — chunk
            # requests must not re-sort the whole set under the node lock
            pp = self.consensus.pruning_processor.pruning_point
            cached = getattr(self, "_pp_utxo_snapshot", None)
            if cached is None or cached[0] != pp:
                items = sorted(
                    self.consensus.pruning_processor.pruning_utxo_set.items(),
                    key=lambda kv: (kv[0].transaction_id, kv[0].index),
                )
                self._pp_utxo_snapshot = cached = (pp, items)
            items = cached[1]
            start = int(payload)
            chunk = items[start : start + PP_UTXO_CHUNK_SIZE]
            peer.send(
                MSG_PP_UTXO_CHUNK,
                {"offset": start, "pairs": chunk, "done": start + len(chunk) >= len(items)},
            )
        elif msg_type == MSG_PP_UTXO_CHUNK:
            self._on_pp_utxo_chunk(peer, payload)
        elif msg_type == MSG_REQUEST_PP_SMT:
            # the request pins the pruning point (RequestPruningPointSmtState
            # carries pruning_point_hash in the reference, ibd/flow.rs:714):
            # a mid-IBD local pruning advance must not switch snapshots under
            # a receiver still paging the old state
            req_pp = payload["pp"]
            self.prune_caches()  # expired snapshots never serve another chunk
            cached = getattr(self, "_pp_smt_snapshot", None)
            if cached is None or cached[0] != req_pp:
                if req_pp != self.consensus.pruning_processor.pruning_point:
                    # neither the cached snapshot nor our live PP: cannot serve
                    peer.send(
                        MSG_PP_SMT_CHUNK,
                        {"active": False, "meta": None, "offset": 0, "lanes": [], "segment": [], "done": True},
                    )
                    return
                cached = (req_pp, self.consensus.export_pp_lane_state(), _monotonic())
            else:
                cached = (cached[0], cached[1], _monotonic())  # refresh last-use
            self._pp_smt_snapshot = cached
            state = cached[1]
            if state is None:
                peer.send(
                    MSG_PP_SMT_CHUNK,
                    {"active": False, "meta": None, "offset": 0, "lanes": [], "segment": [], "done": True},
                )
            else:
                meta, lanes, segment = state
                start = int(payload["offset"])
                lane_part = lanes[start : start + PP_SMT_CHUNK_SIZE]
                rem = PP_SMT_CHUNK_SIZE - len(lane_part)
                seg_start = max(0, start - len(lanes))
                seg_part = segment[seg_start : seg_start + rem] if rem > 0 else []
                total = len(lanes) + len(segment)
                sent = start + len(lane_part) + len(seg_part)
                peer.send(
                    MSG_PP_SMT_CHUNK,
                    {
                        "active": True,
                        "meta": meta if start == 0 else None,
                        "offset": start,
                        "lanes": lane_part,
                        "segment": seg_part,
                        "done": sent >= total,
                    },
                )
        elif msg_type == MSG_PP_SMT_CHUNK:
            self._on_pp_smt_chunk(peer, payload)
        elif msg_type == MSG_REQUEST_HEADERS:
            # serve one bounded chunk of headers above `low` along the
            # antipast walk (request_headers.rs).  A known off-chain anchor
            # is fine: antipast_hashes_between resolves it to the common
            # chain block; only an UNKNOWN anchor falls back pruning-safe
            low = payload
            if not self.consensus.reachability.has(low):
                low = self.consensus.pruning_processor.pruning_point
            self._serve_antipast_chunk(peer, low, headers_only=True)
        elif msg_type == MSG_HEADERS:
            if not getattr(peer, "_headers_first", False):
                return  # unsolicited headers stream
            statuses = self.consensus.storage.statuses
            bodies = self.consensus.storage.block_transactions
            need_bodies = []
            for h in payload["headers"]:
                h.invalidate_cache()  # wire-decoded cache is untrusted
                status = statuses.get(h.hash)
                if status is None:
                    try:
                        self.consensus.validate_and_insert_header(h)
                    except RuleError:
                        continue
                    status = statuses.get(h.hash)
                # fetch bodies only for header-only blocks we lack — never
                # for already-complete or known-invalid ones
                if status == StatusesStore.STATUS_HEADER_ONLY and not bodies.has(h.hash):
                    need_bodies.append(h.hash)
            for i in range(0, len(need_bodies), IBD_BATCH_SIZE):
                self.request_bodies(peer, need_bodies[i : i + IBD_BATCH_SIZE])
            if not payload["done"]:
                peer.send(MSG_REQUEST_HEADERS, payload["continuation"])
            else:
                peer._headers_first = False
        elif msg_type == MSG_REJECT:
            # peer-reported protocol rejection: log and let the connection
            # wind down (p2p.proto RejectMessage semantics)
            from kaspa_tpu.core.log import get_logger

            get_logger("p2p").warn("peer rejected us: %s", payload)
            if hasattr(peer, "close"):
                peer.close()
        elif msg_type == MSG_REQUEST_BLOCK_BODIES:
            # v8 body-only serving (request_block_bodies.rs): bodies for
            # blocks the requester holds headers for
            out = []
            # bounded like the chunked IBD path: a peer cannot make the
            # server materialize its whole body store in one frame
            for h in payload[:IBD_BATCH_SIZE]:
                if self.consensus.storage.block_transactions.has(h):
                    out.append((h, self.consensus.storage.block_transactions.get(h)))
            peer.send(MSG_BLOCK_BODIES, out)
        elif msg_type == MSG_BLOCK_BODIES:
            # attach received bodies to header-only blocks and run them
            # through the normal intake pipeline
            blocks = []
            for h, txs in payload:
                if not self.consensus.storage.headers.has(h):
                    continue
                if self.consensus.storage.block_transactions.has(h):
                    continue  # already have the body
                blocks.append(Block(self.consensus.storage.headers.get(h), list(txs)))
            if blocks:
                self._insert_ibd_batch(self.consensus, blocks)

    def _insert_ibd_batch(self, target: Consensus, blocks) -> None:
        """Bulk intake through the concurrent pipeline: the whole batch goes
        in flight at once (children park on pending parents in the deps
        manager), stage workers overlap hashing/device dispatch, and the
        virtual worker drains multiple blocks per resolution — the IBD
        analog of the reference's pipelined block processing
        (flows/src/ibd/flow.rs feeding consensus's pipeline).  The wire
        reader holds the node lock throughout, so no RPC reader observes
        intermediate virtual state.  One pipeline is kept per sync target
        (not per message) so a chunked IBD doesn't churn threads."""
        from kaspa_tpu.pipeline import ConsensusPipeline

        if not blocks:
            return  # the empty last chunk of a sync: nothing to insert, nothing counted
        if target is self.consensus:
            pipe = self.pipeline  # plain IBD rides the steady-state pipeline
        else:
            cached = getattr(self, "_ibd_pipeline", None)
            if cached is None or cached[0] is not target:
                if cached is not None:
                    cached[1].shutdown()
                cached = (target, ConsensusPipeline(target, workers=2))
                self._ibd_pipeline = cached
            pipe = cached[1]
        rejected = 0
        with trace.span("ibd.insert_batch", blocks=len(blocks)) as sp:
            futures = [pipe.submit(b) for b in blocks]
            for f in futures:
                try:
                    f.result(timeout=600)
                except RuleError:
                    rejected += 1  # invalid blocks within an IBD batch are skipped, and counted
            sp.set(rejected=rejected)
        _IBD_CHUNKS_RX.inc()
        _IBD_BLOCKS_RX.inc(len(blocks))
        if rejected:
            _IBD_BLOCKS_REJECTED.inc(rejected)

    def _on_relay_tx(self, peer: Peer, tx) -> None:
        """Tx-relay intake with flood hygiene (flows/src/v7/txrelay/flow.rs).

        Admission rides the batched ingest tier (source ``p2p``).  The
        verdict feeds the misbehavior ladder: parentless relays (orphan
        storms) and double-spend/RBF-churn chains accumulate points until
        the peer crosses the ban score; invalid signatures/scripts are
        charged hard.  Honest outcomes — duplicates from gossip races, a
        fee floor, our own backpressure — are free.  Only txs that entered
        the live pool are rebroadcast (orphans would propagate the storm).
        """
        from kaspa_tpu.consensus.processes.transaction_validator import TxRuleError
        from kaspa_tpu.ingest import SOURCE_P2P

        txid = tx.id()
        peer.known_txs.add(txid)
        self._tx_requested.pop(txid, None)
        ticket = self.ingest.admit(tx, SOURCE_P2P)
        if ticket.status == "accepted":
            self.broadcast_tx(tx)
            return
        banned = False
        if ticket.status == "orphaned":
            banned = self.score_misbehavior(peer, "tx_orphan", TX_ORPHAN_POINTS)
        elif isinstance(ticket.error, TxRuleError):
            banned = self.score_misbehavior(peer, "invalid_tx", TX_INVALID_POINTS)
        elif isinstance(ticket.error, MempoolError) and ticket.error.code in (
            "tx-double-spend",
            "tx-rbf-rejected",
        ):
            banned = self.score_misbehavior(peer, "tx_double_spend", TX_DOUBLE_SPEND_POINTS)
        # everything else — including code "node-overloaded" (OUR brownout
        # shed the relay, the peer did nothing wrong) — stays unscored
        # alongside duplicates, fee floors and ingest backpressure
        if banned and hasattr(peer, "close"):
            peer.close()

    def _on_relay_block(self, peer: Peer, block: Block) -> None:
        # flight trace starts at the wire: the pipeline's own begin() on
        # submit is idempotent and re-joins this root, so the recorded
        # block time includes the p2p intake hop
        ctx = flight.begin(block.hash) if flight.enabled() else None
        with trace.span("p2p.block_receive", parent=ctx):
            self._block_requested.pop(block.hash, None)  # delivered: allow re-request if invalid
            peer.known_blocks.add(block.hash)  # sender has it: don't echo the inv back
            parents = block.header.direct_parents()
            # a parent already in flight inside the pipeline counts as present:
            # the deps manager parks the child until the parent commits (the
            # reference's out-of-order intake, deps_manager.rs) — only parents
            # neither stored nor in flight make this an orphan
            missing = [
                p
                for p in parents
                if not self.consensus.storage.headers.has(p) and not self.pipeline.deps.is_pending(p)
            ]
            if missing:
                # orphan: request missing ancestors (orphan resolution, flow.rs)
                self.orphan_blocks[block.hash] = block
                peer.send(MSG_REQUEST_BLOCK, missing)
        if missing:
            return
        try:
            self.pipeline.validate_and_insert_block(block)
        except RuleError:
            # invalid relay blocks are an offense, not an instant ban: an
            # honest peer can relay a block it hasn't fully validated, but
            # a stream of them crosses the threshold
            if self.score_misbehavior(peer, "invalid_block", 40) and hasattr(peer, "close"):
                peer.close()
            return
        self._on_new_block_transactions(block)
        self._try_unorphan(block.hash)
        self.broadcast_block(block)

    def _try_unorphan(self, new_hash: bytes) -> None:
        """revalidate_orphans: process orphans whose parents arrived.

        Each round submits EVERY ready orphan to the pipeline at once —
        siblings overlap their header/body stages — then collects results."""
        progress = True
        while progress:
            progress = False
            ready = [
                (h, block)
                for h, block in list(self.orphan_blocks.items())
                if all(self.consensus.storage.headers.has(p) for p in block.header.direct_parents())
            ]
            futures = []
            for h, block in ready:
                del self.orphan_blocks[h]
                futures.append((block, self.pipeline.submit(block)))
            for block, fut in futures:
                try:
                    fut.result()
                    self.broadcast_block(block)
                    progress = True
                except RuleError:
                    pass

    def _serve_antipast_chunk(self, peer: Peer, low: bytes, headers_only: bool = False) -> None:
        """One bounded IBD batch above ``low`` plus the continuation point
        (flow.rs streams IBD_BATCH_SIZE chunks; the syncer requests the
        next batch from ``continuation``).  ``headers_only`` serves the v8
        headers-first stream over the same walk/batching discipline."""
        from kaspa_tpu.consensus.processes.sync import SyncManager

        sm = SyncManager(self.consensus)
        sink = self.consensus.sink()
        hashes, highest = sm.antipast_hashes_between(low, sink, max_blocks=IBD_BATCH_SIZE)
        bts = self.consensus.storage.block_transactions
        hdrs = self.consensus.storage.headers
        done = highest == sink or not hashes
        if headers_only:
            headers = [hdrs.get(h) for h in hashes if hdrs.has(h)]
            peer.send(MSG_HEADERS, {"headers": headers, "done": done, "continuation": highest})
            return
        blocks = [Block(hdrs.get(h), bts.get(h)) for h in hashes if bts.has(h)]
        peer.send(
            MSG_IBD_BLOCKS,
            {"blocks": blocks, "done": done, "continuation": highest},
        )

    def _send_locator(self, peer: Peer, consensus: Consensus) -> None:
        from kaspa_tpu.consensus.processes.sync import SyncManager

        sm = SyncManager(consensus)
        locator = sm.create_block_locator_from_pruning_point(
            consensus.sink(), consensus.pruning_processor.pruning_point
        )
        peer.send(MSG_IBD_BLOCK_LOCATOR, locator)

    def ibd_from(self, peer: Peer) -> None:
        """IBD negotiation (ibd/flow.rs determine_ibd_type): ask for the
        peer's chain info, then either relay-style catch-up (peer's pruning
        point known locally) or a pruning-proof sync into a staging
        consensus."""
        self._sync_peer = peer
        peer.send(MSG_REQUEST_IBD_CHAIN_INFO, {})

    def _on_chain_info(self, peer: Peer, info: dict) -> None:
        peer_pp = info["pruning_point"]
        sink = self.consensus.sink()
        our_work = self.consensus.storage.ghostdag.get_blue_work(sink)
        if info["sink_blue_work"] <= our_work or self._ibd:
            # nothing to gain from this peer, or one sync at a time (an
            # in-flight staging is not abandoned): no sync follows this answer
            if self._sync_peer is peer:
                self._sync_peer = None
            return
        if (
            self.consensus.reachability.has(peer_pp)
            and (
                self.consensus.reachability.is_dag_ancestor_of(
                    self.consensus.pruning_processor.pruning_point, peer_pp
                )
                or peer_pp == self.consensus.pruning_processor.pruning_point
            )
        ):
            # peer's pruning point is connected within our known history
            # (header-only proof remnants without reachability do NOT count):
            # negotiate with an exponential block locator instead of a full
            # inventory (sync/mod.rs create_block_locator_from_pruning_point)
            self._send_locator(peer, self.consensus)
            return
        # too far behind: headers-proof sync (ibd/flow.rs IbdType::DownloadHeadersProof)
        self._ibd = {"peer": peer, "phase": "proof"}
        peer.send(MSG_REQUEST_PRUNING_PROOF, {})

    def request_bodies(self, peer: Peer, hashes: list[bytes]) -> None:
        """v8 body-only fetch for blocks we hold headers for
        (request_block_bodies.rs client side; requires tier >= 8)."""
        if peer.protocol_version < 8:
            raise ProtocolError("peer protocol tier does not support body requests (needs v8)")
        peer.send(MSG_REQUEST_BLOCK_BODIES, hashes)

    def headers_first_sync(self, peer: Peer) -> None:
        """v8 headers-first catch-up: stream headers above our sink anchor,
        then fetch just the bodies (ibd body_only_ibd_permitted mode)."""
        if peer.protocol_version < 8:
            raise ProtocolError("peer protocol tier does not support headers-first sync (needs v8)")
        if self._ibd:
            # one sync at a time: never race an in-flight (possibly staging)
            # IBD with a second header stream into the same consensus
            raise ProtocolError("a sync is already in flight", points=0)
        peer._headers_first = True
        peer.send(MSG_REQUEST_HEADERS, self.consensus.sink())

    def _on_pp_utxo_chunk(self, peer: Peer, payload: dict) -> None:
        from kaspa_tpu.consensus.processes.pruning_proof import ProofError
        from kaspa_tpu.consensus.utxo import UtxoCollection

        if self._ibd.get("peer") is not peer or self._ibd.get("phase") != "utxos":
            return
        for op, entry in payload["pairs"]:
            self._ibd["utxo"][op] = entry
        if not payload["done"]:
            if not payload["pairs"]:
                self._ibd = {}
                raise ProtocolError("peer sent an empty non-final UTXO chunk (no progress)")
            peer.send(MSG_REQUEST_PP_UTXOS, payload["offset"] + len(payload["pairs"]))
            return
        # all trust material in hand: bootstrap a staging consensus and sync
        # the post-pruning-point history into it; the swap happens only when
        # the staging chain actually carries more blue work than the active
        # one (staging_consensus.rs commit discipline)
        staging = self.cmgr.new_staging()
        try:
            active_ppm = self.consensus.pruning_proof_manager
            staging.consensus.pruning_proof_manager.import_pruning_data(
                self._ibd["proof"],
                self._ibd["trusted"],
                UtxoCollection(self._ibd["utxo"]),
                defender_proof=active_ppm.build_proof(),
            )
        except ProofError as e:
            self._ibd = {}
            staging.cancel()
            raise ProtocolError(f"invalid pruning proof data from peer: {e}") from e
        # KIP-21: a post-Toccata pruning point needs its lane state before
        # any post-PP chain block can be seq-commit-verified
        # (flows/src/ibd/flow.rs:145-150); pre-Toccata starts empty
        sc = staging.consensus
        pp = sc.pruning_processor.pruning_point
        pp_hdr = sc.storage.headers.get(pp)
        if sc.params.toccata_active(pp_hdr.daa_score) and pp != sc.params.genesis.hash:
            if peer.protocol_version < 10:
                # the donor cannot speak the SMT flow: a post-Toccata
                # bootstrap from it would start without lane state and fork
                self._ibd = {}
                staging.cancel()
                raise ProtocolError(
                    "peer protocol tier too old for a post-Toccata bootstrap (needs v10)"
                )
            self._ibd = {
                "peer": peer, "phase": "smt", "staging": staging, "smt_pp": pp,
                "smt_meta": None, "smt_lanes": [], "smt_seg": [],
            }
            peer.send(MSG_REQUEST_PP_SMT, {"pp": pp, "offset": 0})
            return
        self._ibd = {"peer": peer, "phase": "blocks", "staging": staging}
        self._send_locator(peer, staging.consensus)

    def _on_pp_smt_chunk(self, peer: Peer, payload: dict) -> None:
        from kaspa_tpu.consensus.smt_processor import LaneStateError

        if self._ibd.get("peer") is not peer or self._ibd.get("phase") != "smt":
            return
        staging = self._ibd["staging"]
        if not payload.get("active", True):
            # we only request lane state for a post-Toccata PP, so a donor
            # claiming there is none cannot seed a verifiable bootstrap
            self._ibd = {}
            staging.cancel()
            raise ProtocolError("peer cannot serve lane state for a post-Toccata pruning point")
        if payload.get("meta") is not None:
            self._ibd["smt_meta"] = payload["meta"]
        self._ibd["smt_lanes"].extend(payload["lanes"])
        self._ibd["smt_seg"].extend(payload["segment"])
        if not payload["done"]:
            if not payload["lanes"] and not payload["segment"]:
                self._ibd = {}
                staging.cancel()
                raise ProtocolError("peer sent an empty non-final SMT chunk (no progress)")
            peer.send(
                MSG_REQUEST_PP_SMT,
                {
                    "pp": self._ibd["smt_pp"],
                    "offset": payload["offset"] + len(payload["lanes"]) + len(payload["segment"]),
                },
            )
            return
        try:
            staging.consensus.import_pp_lane_state(
                self._ibd["smt_meta"], self._ibd["smt_lanes"], self._ibd["smt_seg"]
            )
        except (LaneStateError, KeyError, TypeError) as e:
            self._ibd = {}
            staging.cancel()
            raise ProtocolError(f"invalid pruning point SMT state from peer: {e}") from e
        self._ibd = {"peer": peer, "phase": "blocks", "staging": staging}
        self._send_locator(peer, staging.consensus)

    def _finalize_proof_ibd(self, staging) -> None:
        self._ibd = {}
        self._drop_ibd_pipeline()
        new_sink = staging.consensus.sink()
        new_work = staging.consensus.storage.ghostdag.get_blue_work(new_sink)
        cur_work = self.consensus.storage.ghostdag.get_blue_work(self.consensus.sink())
        if new_work > cur_work:
            staging.commit()
        else:
            staging.cancel()
            raise ProtocolError("proof-IBD peer failed to deliver the promised chain work")


def connect(a: Node, b: Node) -> tuple[Peer, Peer]:
    """Wire two nodes with a bidirectional in-process connection + handshake."""
    pa = Peer(node=a)  # a's endpoint talking to b
    pb = Peer(node=b)
    pa.remote = pb
    pb.remote = pa
    a.peers.append(pa)
    b.peers.append(pb)
    pa.send(MSG_VERSION, {"protocol_version": a.protocol_version, "network": a.consensus.params.name, "listen_port": 0, "id": a.id})
    pb.send(MSG_VERSION, {"protocol_version": b.protocol_version, "network": b.consensus.params.name, "listen_port": 0, "id": b.id})
    return pa, pb
