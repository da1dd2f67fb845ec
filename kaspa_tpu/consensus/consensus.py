"""The consensus engine: header/body/virtual processing over the block DAG.

Re-design of the reference's 4-stage pipeline (consensus/src/pipeline/) as
explicit processing stages sharing a ConsensusStorage.  This module is the
host-side control path; batchable crypto goes to the device through the
batch layers — signature/script checks via txscript.batch (every chain
block), muhash element products via MuHash.add_transactions_batch, which
tree-reduces on device above its element-count threshold.

Stage semantics follow the reference call stack (SURVEY.md §3.2):
- header stage: in-isolation checks -> parent relations -> GHOSTDAG ->
  difficulty/DAA window checks -> PoW -> median time, mergeset limit,
  blue score/work -> commit (header_processor/processor.rs:296-313)
- body stage: merkle root, coinbase form, tx in-isolation checks
  (body_processor/)
- virtual stage: sink search, chain-block UTXO verification with muhash
  commitments, virtual resolution (virtual_processor/processor.rs:261-384,
  utxo_validation.rs)
"""

from __future__ import annotations

from dataclasses import dataclass, field

from kaspa_tpu.consensus import hashing as chash
from kaspa_tpu.consensus import serde
from kaspa_tpu.consensus.model import (
    SUBNETWORK_ID_COINBASE,
    Header,
    ScriptPublicKey,
    Transaction,
    TransactionOutpoint,
)
from kaspa_tpu.consensus.mass import BlockMassLimits
from kaspa_tpu.consensus.model.block import Block
from kaspa_tpu.consensus.params import Params
from kaspa_tpu.consensus.processes.coinbase import BlockRewardData, CoinbaseData, CoinbaseManager, MinerData
from kaspa_tpu.consensus.processes.block_depth import BlockDepthManager
from kaspa_tpu.consensus.processes.ghostdag import GhostdagManager
from kaspa_tpu.consensus.processes.pruning import PruningPointManager
from kaspa_tpu.consensus.processes.transaction_validator import (
    FLAG_FULL,
    FLAG_SKIP_SCRIPTS,
    TransactionValidator,
    TxRuleError,
)
from kaspa_tpu.consensus.processes.window import DIFFICULTY_WINDOW, MEDIAN_TIME_WINDOW, SampledWindowManager
from kaspa_tpu.consensus.reachability import ORIGIN, ReachabilityService
from kaspa_tpu.consensus.stores import (
    ConsensusStorage,
    GhostdagData,
    StatusesStore,
)
from kaspa_tpu.consensus.utxo import UtxoDiff, UtxoView, apply_diff, unapply_diff
from kaspa_tpu.crypto import merkle
from kaspa_tpu.crypto.muhash import MuHash
from kaspa_tpu.observability import flight, trace
from kaspa_tpu.observability.core import REGISTRY

# counted where the virtual stage does the work, whichever cycle or thread it
# runs in: over pipeline_virtual_cycle_blocks they are candidates and collected
# transactions a block
_CHAIN_VERIFIED = REGISTRY.counter(
    "virtual_chain_blocks_verified",
    help="chain candidates passed through _verify_chain_block, from the cache or recomputed (= speculative_hits + speculative_misses where a verifier is attached)",
)
_COLLECTED_TXS = REGISTRY.counter(
    "txscript_collected_txs",
    help="non-coinbase transactions handed to _validate_transactions, one increment a merged block (= the txs of the txscript.collect spans)",
)
_COLLECTED_TXS_SYNC = REGISTRY.counter(
    "txscript_sync_collected_txs",
    help="of txscript_collected_txs, those collected with no shared checker: a blocking dispatch under the commit lock follows",
)


class RuleError(Exception):
    pass


def _neg_bytes(b: bytes) -> bytes:
    """Lexicographic inversion so a min-heap orders hashes descending."""
    return bytes(255 - x for x in b)


def _encode_anchor_segment(segment: list) -> bytes:
    """[(blue_score, block_hash)] — the bootstrap shortcut-anchor chain
    persisted under the meta column (headers live in the header store)."""
    import struct as _struct

    out = [_struct.pack("<I", len(segment))]
    for bs, blk in segment:
        out.append(_struct.pack("<Q", bs) + blk)
    return b"".join(out)


def _decode_anchor_segment(raw: bytes) -> list:
    import struct as _struct

    (n,) = _struct.unpack_from("<I", raw, 0)
    off = 4
    out = []
    for _ in range(n):
        (bs,) = _struct.unpack_from("<Q", raw, off)
        out.append((bs, raw[off + 8 : off + 40]))
        off += 40
    return out


def _FinalityConflictNotification(tip: bytes, finality_point: bytes):
    from kaspa_tpu.notify.notifier import Notification

    return Notification(
        "finality-conflict",
        {"violating_tip": tip.hex(), "finality_point": finality_point.hex()},
    )


@dataclass
class VirtualState:
    """reference: consensus/src/model/stores/virtual_state.rs"""

    parents: list[bytes]
    ghostdag_data: GhostdagData
    daa_score: int
    bits: int
    past_median_time: int
    accepted_tx_ids: list[bytes]
    mergeset_rewards: dict
    mergeset_non_daa: set


class Consensus:
    def __init__(self, params: Params, db=None, cache_policy=None):
        """``db``: optional storage.kv.KvStore — attaches crash-safe
        persistence (bounded read-through caches + atomic batch flush per
        block).  A non-empty DB restores the consensus state (restart-resume)
        with O(tips + caches) work; an empty one is initialized with genesis.
        ``cache_policy``: stores.CachePolicy bounding per-store decode caches
        (defaults applied when a DB is attached)."""
        self.params = params
        self.storage = ConsensusStorage(db, cache_policy)
        self.reachability = ReachabilityService()
        # reachability rides every flush batch: dirty nodes are staged so a
        # kill -9 restart decodes the RN column instead of rebuilding
        self.storage.pre_flush_hooks.append(self._stage_reachability_dirty)
        self.ghostdag_manager = GhostdagManager(
            params.genesis.hash,
            params.ghostdag_k,
            self.storage.ghostdag,
            self.storage.relations,
            self.storage.headers,
            self.reachability,
        )
        self.window_manager = SampledWindowManager(
            params.genesis.hash,
            params.genesis.bits,
            params.genesis.timestamp,
            self.storage.ghostdag,
            self.storage.headers,
            params.max_difficulty_target,
            params.target_time_per_block,
            params.difficulty_window_size,
            params.min_difficulty_window_size,
            params.difficulty_sample_rate,
            params.past_median_time_window_size,
            params.past_median_time_sample_rate,
        )
        self.coinbase_manager = CoinbaseManager(
            max_coinbase_payload_len=params.max_coinbase_payload_len,
            deflationary_phase_daa_score=params.deflationary_phase_daa_score,
            pre_deflationary_phase_base_subsidy=params.pre_deflationary_phase_base_subsidy,
            bps=params.bps,
        )
        self.transaction_validator = TransactionValidator(params)
        self.depth_manager = BlockDepthManager(
            params.merge_depth, params.finality_depth, params.genesis.hash, self.storage.ghostdag,
            self.reachability, self.storage.depth,
        )
        self.pruning_point_manager = PruningPointManager(
            params.pruning_depth, params.finality_depth, params.genesis.hash, self.storage.headers,
            self.storage.pruning_samples,
        )
        from kaspa_tpu.consensus.processes.parents_builder import ParentsManager

        self.storage.headers.max_block_level = params.max_block_level
        self.parents_manager = ParentsManager(
            params.max_block_level,
            params.genesis.hash,
            self.storage.headers,
            self.reachability,
            self.storage.relations,
        )
        from kaspa_tpu.consensus.processes.pruning_processor import PruningProcessor

        self.pruning_processor = PruningProcessor(self, is_archival=getattr(params, "is_archival", False))
        from kaspa_tpu.consensus.processes.pruning_proof import PruningProofManager

        self.pruning_proof_manager = PruningProofManager(self)
        from kaspa_tpu.notify.notifier import ConsensusNotificationRoot

        self.notification_root = ConsensusNotificationRoot()
        from kaspa_tpu.consensus.counters import ProcessingCounters

        self.counters = ProcessingCounters()

        # speculative chain-state precompute (pipeline/speculative.py):
        # attached by ConsensusPipeline when enabled; None = synchronous
        # chain verification only (serial replay, tests, direct callers)
        self.speculative = None

        # virtual/UTXO state.  The per-block columns live in ConsensusStorage
        # as bounded read-through caches (CachedDbAccess); these attributes
        # alias them so processing code reads naturally.
        self.tips: set[bytes] = set()
        self.utxo_set = self.storage.utxo_set  # positioned at self.utxo_position
        self.utxo_position: bytes = params.genesis.hash
        self.utxo_diffs = self.storage.utxo_diffs  # chain-validated block -> diff vs selected parent position
        self.multisets = self.storage.multisets
        self.acceptance_data = self.storage.acceptance
        self.virtual_state: VirtualState | None = None
        self.daa_excluded = self.storage.daa_excluded
        # net UTXO delta accumulated between virtual resolutions (reorg-safe):
        # emitted as one UtxosChanged per resolve
        self._acc_added: dict = {}
        self._acc_removed: dict = {}
        self.reach_mergesets = self.storage.reach_mergesets

        # finality conflicts observed (tips heavier than the sink that
        # exclude the finality point): tip -> "active" | "resolved".
        # Entries are never dropped while the tip remains heavier, so an
        # acknowledged conflict is not re-notified every resolve cycle
        self._finality_conflicts: dict[bytes, str] = {}

        # KIP-21: materialized lane state + selected-chain index, both moved
        # in lock-step with utxo_position (smt-store / selected_chain_store)
        from kaspa_tpu.consensus.smt_processor import LaneTracker

        self.lane_tracker = LaneTracker(self.storage, params.finality_depth, params.genesis.hash)
        self.selected_chain: list[tuple[int, bytes]] = [(0, params.genesis.hash)]
        # chain linkage for below-pruning-point anchor-segment blocks whose
        # ghostdag records do not exist (proof bootstrap) or were re-rooted
        # by pruning: block -> selected parent.  Their headers live in the
        # ordinary header store.
        self._segment_prev: dict[bytes, bytes] = {}

        if self.storage.is_initialized():
            self._load_state()
        else:
            self._insert_genesis()

    # ------------------------------------------------------------------
    # genesis
    # ------------------------------------------------------------------

    def _insert_genesis(self):
        g = self.params.genesis
        override = self.params.genesis_override
        if override is not None:
            header = override.header
            genesis_txs = list(override.transactions)
        else:
            header = Header(
                version=g.version,
                parents_by_level=[[]],
                hash_merkle_root=b"\x00" * 32,
                accepted_id_merkle_root=b"\x00" * 32,
                utxo_commitment=MuHash().finalize(),
                timestamp=g.timestamp,
                bits=g.bits,
                nonce=0,
                daa_score=g.daa_score,
                blue_work=0,
                blue_score=0,
                pruning_point=g.hash,
            )
            header._hash_cache = g.hash
            genesis_txs = [
                Transaction(
                    0, [], [], 0, SUBNETWORK_ID_COINBASE, 0,
                    self.coinbase_manager.serialize_coinbase_payload(CoinbaseData(0, 0, MinerData(ScriptPublicKey(0, b"")))),
                )
            ]
        self.storage.headers.insert(header)
        self.storage.relations.insert(g.hash, [ORIGIN])
        self.storage.ghostdag.insert(g.hash, self.ghostdag_manager.genesis_ghostdag_data())
        self.reachability.add_block(g.hash, ORIGIN, [], [ORIGIN])
        self._set_reach_mergeset(g.hash, [])
        self.storage.block_transactions.insert(g.hash, genesis_txs)
        self.storage.statuses.set(g.hash, StatusesStore.STATUS_UTXO_VALID)
        self._set_multiset(g.hash, MuHash())
        self._set_utxo_diff(g.hash, UtxoDiff())
        self._set_daa_excluded(g.hash, set())
        self.tips = {g.hash}
        self._persist_tips()
        self.storage.put_meta(b"init", b"1")
        self._resolve_virtual()
        self.storage.flush()

    # ------------------------------------------------------------------
    # persistence (stage aux state alongside the write-through stores;
    # reference: consensus/src/consensus/storage.rs + database/src/access.rs)
    # ------------------------------------------------------------------

    def _set_multiset(self, block: bytes, ms: MuHash) -> None:
        self.multisets[block] = ms

    def _set_utxo_diff(self, block: bytes, diff: UtxoDiff) -> None:
        self.utxo_diffs[block] = diff

    def _set_acceptance(self, block: bytes, accepted_ids: list[bytes]) -> None:
        self.acceptance_data[block] = accepted_ids

    def _set_daa_excluded(self, block: bytes, excluded: set) -> None:
        self.daa_excluded[block] = excluded

    def _set_reach_mergeset(self, block: bytes, mergeset: list[bytes]) -> None:
        """Persist the exact mergeset registered with reachability, so the
        load-time rebuild replays identical FCS state even after pruning
        filtered the ghostdag data (the blues[0]==sp invariant no longer
        holds for blocks whose selected parent was pruned)."""
        self.reach_mergesets[block] = mergeset

    def _rebind_reachability(self) -> None:
        """Point every manager at a replacement ReachabilityService
        (snapshot-recovery path)."""
        self.ghostdag_manager.reachability = self.reachability
        self.depth_manager.reachability = self.reachability
        self.parents_manager.reachability = self.reachability

    def _stage_reachability_dirty(self) -> None:
        """Stage the reachability nodes mutated since the last flush into
        the RN column (pre-flush hook: the records join the same atomic
        batch as the block state that produced them).  This keeps the
        persistent reachability index the source of truth — crash restarts
        decode it instead of rebuilding, matching the reference's
        store-backed design (processes/reachability/)."""
        from kaspa_tpu.consensus.stores import PREFIX_REACH_NODE

        r = self.reachability
        if self.storage.db is None or (not r._dirty and not r._deleted):
            return
        for h in r._deleted:
            self.storage.stage(PREFIX_REACH_NODE + h, None)
        for h in r._dirty:
            self.storage.stage(PREFIX_REACH_NODE + h, serde.encode_reach_node(r, h))
        self.storage.put_meta(b"reach_reindex_root", r._reindex_root)
        r._dirty.clear()
        r._deleted.clear()

    def save_reachability_snapshot(self) -> None:
        """Orderly-shutdown persistence.  With the incremental RN column the
        crash and clean paths are identical — this just flushes any staged
        remainder (kept for API compatibility with earlier DB layouts)."""
        if self.storage.db is None:
            return
        self.storage.flush()

    def _persist_tips(self) -> None:
        if self.storage.db is not None:
            self.storage.put_meta(b"tips", serde.encode_hash_list(sorted(self.tips)))

    def _persist_utxo_position(self) -> None:
        if self.storage.db is not None:
            self.storage.put_meta(b"utxo_position", self.utxo_position)

    # ------------------------------------------------------------------
    # KIP-21 lane-state transfer (IBD / trusted bootstrap)
    # ------------------------------------------------------------------

    def _chain_parent(self, block: bytes) -> bytes | None:
        """Selected parent along the final (pruned-history) chain.

        The anchor archive takes precedence: pruning re-roots surviving
        ghostdag records whose parents were deleted to ORIGIN, while the
        archive records the true chain linkage before deletion (history
        below the pruning point is final, so archived links never go
        stale).  Above the archive, live ghostdag is authoritative."""
        sp = self._segment_prev.get(block)
        if sp is not None:
            return sp
        if self.storage.ghostdag.has(block):
            sp = self.storage.ghostdag.get_selected_parent(block)
            if sp != ORIGIN:
                return sp
        return None

    def export_pp_lane_state(self):
        """Lane state at the pruning point, for IBD serving — the donor side
        of flows/src/ibd/flow.rs:145-150 sync_new_smt_state.

        Returns None when the PP is pre-Toccata (the receiver starts empty,
        mirroring the reference's set_pruning_smt_stable fast path), else
        ``(meta, lanes, segment)``:

        - meta: {lanes_root, pcd, parent_seq_commit, shortcut_block,
          inactivity_shortcut} — the reference's 96-byte SmtMetadata plus
          the shortcut identity;
        - lanes: sorted [(lane_key, tip, blue_score)] at the PP;
        - segment: the selected-chain HEADERS from the PP's
          inactivity-shortcut block up to the PP itself — the receiver's
          shortcut anchors for the first finality-window of post-bootstrap
          chain blocks.  Whole headers, not bare value pairs: each is bound
          to the proof-validated PP by the parent-hash chain, so a peer
          cannot substitute anchor values without mining real alternative
          headers in the PP's past.  (The reference reads the same data
          from headers it retains below the PP.)
        """
        from kaspa_tpu.consensus.smt_processor import ZERO_HASH

        pp = self.pruning_processor.pruning_point
        if pp == self.params.genesis.hash:
            return None
        hdr = self.storage.headers.get(pp)
        if not self.params.toccata_active(hdr.daa_score):
            return None
        build = self.lane_tracker.builds.try_get(pp)
        if build is None:
            return None

        # rewind the materialized lane tips from the current UTXO position
        # back to the PP by applying per-chain-block undo records (the
        # in-RAM selected_chain index is trimmed, so walk storage)
        tips = dict(self.lane_tracker.lane_tips)
        cur = self.utxo_position
        while cur != pp:
            b = self.lane_tracker.builds.try_get(cur)
            if b is not None:
                for lk, prev in b.undo.items():
                    if prev is None:
                        tips.pop(lk, None)
                    else:
                        tips[lk] = prev
            cur = self._chain_parent(cur)
            if cur is None:
                return None  # chain walk left our materialized history

        if not self.storage.headers.has(build.shortcut_block):
            return None  # anchor headers not retained (pre-upgrade DB)
        sc_hdr = self.storage.headers.get(build.shortcut_block)
        inactivity = (
            sc_hdr.accepted_id_merkle_root
            if self.params.toccata_active(sc_hdr.daa_score)
            else ZERO_HASH
        )
        # the seq-commit chains from the GHOSTDAG selected parent (which the
        # post-Toccata chain rule also pins as direct_parents()[0])
        parent = (
            self.storage.ghostdag.get_selected_parent(pp)
            if self.storage.ghostdag.has(pp)
            else hdr.direct_parents()[0]
        )
        meta = {
            "lanes_root": build.lanes_root,
            "pcd": build.payload_ctx_digest,
            "parent_seq_commit": self.storage.headers.get(parent).accepted_id_merkle_root,
            "shortcut_block": build.shortcut_block,
            "inactivity_shortcut": inactivity,
        }

        # anchor segment: chain headers from shortcut(pp) to pp inclusive
        segment = []
        cur = pp
        while True:
            if not self.storage.headers.has(cur):
                return None
            segment.append(self.storage.headers.get(cur))
            if cur == build.shortcut_block or cur == self.params.genesis.hash:
                break
            cur = self._chain_parent(cur)
            if cur is None:
                return None
        segment.reverse()
        lanes = sorted((lk, tip, bs) for lk, (tip, bs) in tips.items())
        return meta, lanes, segment

    def import_pp_lane_state(self, meta: dict, lanes: list, segment: list) -> None:
        """Install a transferred pruning-point lane state into this (freshly
        proof-bootstrapped) consensus — the receiving side of
        sync_new_smt_state / import_pruning_point_smt.

        The lane set and metadata are verified against the proof-validated
        PP header's sequencing commitment (verify_lane_state), and the
        anchor-segment headers are verified as a parent-hash chain ending
        at the PP: header[i].hash must appear in header[i+1]'s direct
        parents and the last header must BE the proven PP header, so every
        anchor's (daa_score, accepted_id_merkle_root, blue_score) is bound
        through block hashes to the proof.
        """
        from kaspa_tpu.consensus.smt_processor import LaneStateError, ZERO_HASH, verify_lane_state

        pp = self.pruning_processor.pruning_point
        hdr = self.storage.headers.get(pp)
        # wire-decoded headers carry a cached hash restored from peer bytes;
        # recompute so every hash-binding check below is over real contents
        for h in segment:
            h.invalidate_cache()
        if not segment or segment[-1].hash != pp:
            raise LaneStateError("anchor segment must end at the pruning point")
        if segment[0].hash != meta["shortcut_block"]:
            raise LaneStateError("anchor segment must start at the shortcut block")
        for a, b in zip(segment, segment[1:]):
            # post-Toccata chain blocks pin the selected parent as the FIRST
            # direct parent (utxo_validation.rs:219-238), which rules out a
            # donor routing the segment through non-selected parents; for
            # pre-Toccata hops membership is the strongest header-level
            # check, and such anchors fold to ZERO regardless
            if self.params.toccata_active(b.daa_score):
                if b.direct_parents()[0] != a.hash:
                    raise LaneStateError("anchor segment hop is not the selected parent")
            elif a.hash not in b.direct_parents():
                raise LaneStateError("anchor segment headers do not form a parent chain")
            if b.blue_score <= a.blue_score:
                raise LaneStateError("anchor segment blue scores must strictly ascend")
        if len(segment) > 1 and self.storage.ghostdag.has(pp):
            if self.storage.ghostdag.get_selected_parent(pp) != segment[-2].hash:
                raise LaneStateError("anchor segment disagrees with the PP's selected parent")
        # the seq-commit chains from the GHOSTDAG selected parent
        # (smt_processor.compute); trusted ghostdag gives it for the PP
        par = (
            self.storage.ghostdag.get_selected_parent(pp)
            if self.storage.ghostdag.has(pp)
            else hdr.direct_parents()[0]
        )
        if self.storage.headers.has(par):
            if meta["parent_seq_commit"] != self.storage.headers.get(par).accepted_id_merkle_root:
                raise LaneStateError("metadata parent commitment contradicts the PP parent header")
        # the claimed folded shortcut value must equal what the (now hash-
        # bound) shortcut header itself folds to
        sc_hdr = segment[0]
        expected_fold = (
            sc_hdr.accepted_id_merkle_root
            if self.params.toccata_active(sc_hdr.daa_score)
            else ZERO_HASH
        )
        if meta["inactivity_shortcut"] != expected_fold:
            raise LaneStateError("metadata inactivity shortcut contradicts the shortcut header")
        verify_lane_state(hdr, meta, lanes)

        self.lane_tracker.import_state(pp, hdr, meta, lanes)
        pairs = []
        for i, h in enumerate(segment):
            if not self.storage.headers.has(h.hash):
                self.storage.headers.insert(h)
                self.storage.statuses.set(h.hash, StatusesStore.STATUS_HEADER_ONLY)
            if i > 0:
                self._segment_prev[h.hash] = segment[i - 1].hash
            pairs.append((h.blue_score, h.hash))
        self.selected_chain = pairs
        if self.storage.db is not None:
            self.storage.put_meta(b"lane_anchor_segment", _encode_anchor_segment(pairs))
        self.storage.flush()

    def _load_state(self) -> None:
        """Restore consensus state from the attached DB.

        Every store column is read-through (nothing is bulk-decoded at
        startup); the only O(retained-history) work is rebuilding the
        in-memory reachability index — a keys-only relations scan plus one
        transient ghostdag decode per block for the topological order.
        Ascending (blue_work, hash) is a total topological order of the DAG
        — every ancestor has strictly smaller blue work — and unlike a Kahn
        walk over relations it stays valid when pruning removed intermediate
        blocks (a kept block's mergeset members always sort before it)."""
        from kaspa_tpu.consensus.stores import PREFIX_GHOSTDAG, PREFIX_RELATIONS

        self.utxo_position = self.storage.get_meta(b"utxo_position") or self.params.genesis.hash
        self.tips = set(serde.decode_hash_list_bytes(self.storage.get_meta(b"tips")))
        self.pruning_processor.load()

        engine = self.storage.db.engine
        g = self.params.genesis.hash
        restored = False
        # primary path: the incrementally-persisted RN column — written at
        # every flush, so crash and clean restarts are both O(decode)
        from kaspa_tpu.consensus.stores import PREFIX_REACH_NODE

        try:
            n_nodes = 0
            for key, raw in engine.items_prefix(PREFIX_REACH_NODE):
                serde.decode_reach_node(self.reachability, key, raw)
                n_nodes += 1
            if n_nodes:
                root = self.storage.get_meta(b"reach_reindex_root")
                if root is not None:
                    self.reachability._reindex_root = root
                # the column IS the persisted state: nothing is dirty
                self.reachability._dirty.clear()
                restored = True
        except Exception:  # noqa: BLE001 - corrupt column must not brick startup
            self.reachability = ReachabilityService()
            self._rebind_reachability()
            # purge the corrupt column so the rebuild's rewrite converges
            # (stale orphan records would otherwise throw on every restart)
            for key in list(engine.keys_prefix(PREFIX_REACH_NODE)):
                self.storage.stage(PREFIX_REACH_NODE + key, None)
            restored = False
        if not restored:
            # legacy clean-shutdown blob (pre-RN-column DBs)
            snapshot = self.storage.get_meta(b"reach_snapshot")
            if snapshot is not None and self.storage.get_meta(b"reach_clean") == b"1":
                try:
                    serde.decode_reachability(snapshot, self.reachability)
                    # migrate: everything is dirty so the next flush writes
                    # the whole RN column; drop the legacy blob
                    self.reachability._dirty = set(self.reachability._interval.keys())
                    restored = True
                except Exception:  # noqa: BLE001 - corrupt/skewed snapshot
                    self.reachability = ReachabilityService()
                    self._rebind_reachability()
                from kaspa_tpu.consensus.stores import PREFIX_META

                self.storage.stage(PREFIX_META + b"reach_snapshot", None)
                self.storage.put_meta(b"reach_clean", b"0")
        if not restored:
            # transient (blue_work, hash, selected_parent) triples: one
            # ghostdag decode per block — the walk needs only selected_parent
            order = []
            for blk in engine.keys_prefix(PREFIX_RELATIONS):
                raw = engine.get(PREFIX_GHOSTDAG + blk)
                if raw:
                    gd = serde.decode_ghostdag(raw)
                    order.append((gd.blue_work, blk, gd.selected_parent))
                else:
                    order.append((0, blk, ORIGIN))
            order.sort()
            live = {blk for _, blk, _sp in order}
            for _, blk, sp in order:
                if blk == g:
                    self.reachability.add_block(blk, ORIGIN, [], [ORIGIN])
                else:
                    parents = self.storage.relations.get_parents(blk)
                    live_parents = [p for p in parents if p in live] or [sp]
                    self.reachability.add_block(
                        blk, sp, self.reach_mergesets.get(blk, []), live_parents
                    )
        # KIP-21 lane state resumes lazily from its persisted snapshot
        self.lane_tracker.load()
        # selected-chain index: only the finality window is ever queried
        # (inactivity-shortcut anchors reach back finality_depth+1 at most)
        chain = []
        cur = self.utxo_position
        limit = self.params.finality_depth + 1025
        while self.storage.ghostdag.has(cur) and len(chain) <= limit:
            chain.append((self.storage.ghostdag.get_blue_score(cur), cur))
            if cur == g:
                break
            cur = self.storage.ghostdag.get_selected_parent(cur)
        self.selected_chain = chain[::-1]
        # prepend the bootstrap anchor segment (below-PP shortcut anchors
        # whose headers were imported with the lane state) where it reaches
        # below the rebuilt chain's base
        raw_seg = self.storage.get_meta(b"lane_anchor_segment")
        if raw_seg:
            # defensively truncate a stale blob at the first missing header:
            # filtering interior holes would splice non-parents together in
            # _segment_prev and poison future exports
            decoded = _decode_anchor_segment(raw_seg)
            first_live = next(
                (i for i, (_, blk) in enumerate(decoded) if self.storage.headers.has(blk)),
                len(decoded),
            )
            entries = decoded[first_live:]
            if any(not self.storage.headers.has(blk) for _, blk in entries):
                entries = []  # interior hole: unusable without false links
            for i, (bs, blk) in enumerate(entries):
                if i > 0:
                    self._segment_prev[blk] = entries[i - 1][1]
            base_bs = self.selected_chain[0][0] if self.selected_chain else None
            prefix = [(bs, blk) for bs, blk in entries if base_bs is None or bs < base_bs]
            self.selected_chain = prefix + self.selected_chain

        self._resolve_virtual()
        # the load-time resolve may reposition the UTXO set; flush that
        self.storage.flush()

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def validate_and_insert_block(self, block: Block) -> str:
        """Full pipeline for one block; returns the resulting block status.

        Synchronous path (serial replay, tests, direct callers); the
        concurrent pipeline inlines these stages in its own workers and
        never enters here, so both paths can own their block's flight
        trace without double-recording."""
        existing = self.storage.statuses.get(block.hash)
        if existing is not None and existing != StatusesStore.STATUS_HEADER_ONLY:
            return existing  # duplicate submission: no reprocessing, no events
        ctx = flight.begin(block.hash) if flight.enabled() else None
        try:
            with trace.span("consensus.validate", parent=ctx):
                self.counters.inc_blocks_submitted()
                if self._process_header(block.header):
                    self.counters.inc_headers()
                self._process_body(block)
                self.counters.inc_bodies()
                self.counters.inc_txs(len(block.transactions))
                self.notification_root.notify_block_added(block)
                self._update_tips(block.hash)
                self._resolve_virtual()
                status = self.storage.statuses.get(block.hash)
                self.storage.flush()
        except BaseException:
            if ctx is not None:
                flight.end(block.hash, "error")
            raise
        if ctx is not None:
            flight.end(block.hash, "ok")
        return status

    def validate_and_insert_header(self, header) -> str:
        """Headers-first intake (IBD): header validation + commit without a
        body; the block completes later via validate_and_insert_block."""
        existing = self.storage.statuses.get(header.hash)
        if existing is not None:
            return existing
        self._process_header(header)
        self.counters.inc_headers()
        self.storage.flush()
        return self.storage.statuses.get(header.hash)

    def sink(self) -> bytes:
        return self.virtual_state.ghostdag_data.selected_parent

    def get_virtual_daa_score(self) -> int:
        return self.virtual_state.daa_score

    # ------------------------------------------------------------------
    # header stage (pipeline/header_processor/)
    # ------------------------------------------------------------------

    def _process_header(self, header: Header) -> bool:
        """Returns True if the header was newly processed (False if known)."""
        block_hash = header.hash
        if self.storage.headers.has(block_hash) and self.storage.statuses.get(block_hash) is not None:
            return False  # known
        parents = header.direct_parents()

        # in isolation (pre_ghostdag_validation.rs)
        if not parents:
            raise RuleError("block has no parents")
        if len(parents) > self.params.max_block_parents:
            raise RuleError(f"too many parents {len(parents)}")
        if len(set(parents)) != len(parents):
            raise RuleError("duplicate parents")

        # parent relations
        for p in parents:
            if not self.storage.headers.has(p):
                raise RuleError(f"missing parent {p.hex()}")
            if self.storage.statuses.get(p) == StatusesStore.STATUS_INVALID:
                raise RuleError("invalid parent")

        # GHOSTDAG
        gd = self.ghostdag_manager.ghostdag(parents)

        # difficulty & DAA (pre_pow_validation.rs)
        daa_window = self.window_manager.block_daa_window(gd)
        expected_bits = self.window_manager.calculate_difficulty_bits(gd, daa_window)
        if header.bits != expected_bits:
            raise RuleError(f"unexpected difficulty bits {header.bits:#x} != {expected_bits:#x}")
        if header.daa_score != daa_window.daa_score:
            raise RuleError(f"unexpected daa score {header.daa_score} != {daa_window.daa_score}")
        # header version in context (post_pow_validation.rs:105-111 WrongBlockVersion):
        # the expected version is fork-activation-dependent, so it's checked against
        # the contextually-validated daa score rather than in isolation
        expected_version = self.params.block_version(header.daa_score)
        if header.version != expected_version:
            raise RuleError(f"wrong block version {header.version} != {expected_version}")

        # PoW (consensus/pow): gated by skip_proof_of_work (test/sim configs)
        if not self.params.skip_proof_of_work:
            from kaspa_tpu.crypto.powhash import check_pow

            if not check_pow(header):
                raise RuleError("invalid proof of work")

        # post-pow (post_pow_validation.rs)
        pmt, _w = self.window_manager.calc_past_median_time(gd)
        if header.timestamp <= pmt:
            raise RuleError(f"timestamp {header.timestamp} not later than past median time {pmt}")
        if gd.mergeset_size() > self.params.mergeset_size_limit:
            raise RuleError(f"mergeset size {gd.mergeset_size()} above limit")
        if header.blue_score != gd.blue_score:
            raise RuleError(f"blue score mismatch {header.blue_score} != {gd.blue_score}")
        if header.blue_work != gd.blue_work:
            raise RuleError(f"blue work mismatch {header.blue_work} != {gd.blue_work}")
        # bounded merge depth (post_pow_validation.rs check_bounded_merge_depth)
        try:
            mdr, fp = self.depth_manager.check_bounded_merge_depth(gd, self.pruning_processor.pruning_point)
        except Exception as e:
            raise RuleError(f"violating bounded merge depth: {e}") from e

        # commit (header_processor/processor.rs:361)
        self.storage.headers.insert(header)
        self.storage.relations.insert(block_hash, parents)
        self.storage.ghostdag.insert(block_hash, gd)
        reach_mergeset = list(gd.unordered_mergeset_without_selected_parent())
        self.reachability.add_block(block_hash, gd.selected_parent, reach_mergeset, parents)
        self._set_reach_mergeset(block_hash, reach_mergeset)
        self._set_daa_excluded(block_hash, daa_window.mergeset_non_daa)
        self.depth_manager.store(block_hash, mdr, fp)
        self.window_manager.cache_block_window(block_hash, DIFFICULTY_WINDOW, daa_window.window)
        # cache the median-time window too: children (and every virtual
        # resolve whose sink is this block) then extend it incrementally
        # instead of re-walking the selected chain from scratch
        self.window_manager.cache_block_window(block_hash, MEDIAN_TIME_WINDOW, _w)
        self.storage.statuses.set(block_hash, StatusesStore.STATUS_HEADER_ONLY)
        return True

    # ------------------------------------------------------------------
    # body stage (pipeline/body_processor/)
    # ------------------------------------------------------------------

    def _process_body(self, block: Block) -> None:
        txs = block.transactions
        if not txs:
            raise RuleError("block has no transactions (header-only unsupported in this path)")
        # merkle root (body_validation_in_isolation.rs)
        computed = merkle.calc_hash_merkle_root(txs)
        if computed != block.header.hash_merkle_root:
            raise RuleError("bad merkle root")
        if not txs[0].is_coinbase():
            raise RuleError("first tx is not coinbase")
        for tx in txs[1:]:
            if tx.is_coinbase():
                raise RuleError("second coinbase")
        coinbase_data = self.coinbase_manager.deserialize_coinbase_payload(txs[0].payload)
        gd = self.storage.ghostdag.get(block.hash)
        if coinbase_data.blue_score != gd.blue_score:
            raise RuleError("coinbase blue score mismatch")
        # per-dimension block mass limits (body_validation_in_isolation.rs
        # check_block_mass): compute/transient from the calculator, storage
        # from the miner commitments
        limits = BlockMassLimits.with_shared_limit(self.params.max_block_mass)
        total_compute = total_transient = total_storage = 0
        # KIP-21 lane limits (body_validation_in_isolation.rs:100-121): cap
        # occupied subnetwork lanes per block and summed gas per lane.
        # Applied unconditionally — pre-Toccata valid blocks contain only
        # native zero-gas non-coinbase txs, so the caps are vacuous there.
        lanes: dict[bytes, int] = {}  # lane (subnetwork id) -> summed gas
        for tx in txs:
            nc = self.transaction_validator.mass_calculator.calc_non_contextual_masses(tx)
            total_compute += nc.compute_mass
            total_transient += nc.transient_mass
            total_storage += tx.storage_mass
            if total_compute > limits.compute:
                raise RuleError(f"exceeds compute mass limit: {total_compute} > {limits.compute}")
            if total_transient > limits.transient:
                raise RuleError(f"exceeds transient mass limit: {total_transient} > {limits.transient}")
            if total_storage > limits.storage:
                raise RuleError(f"exceeds storage mass limit: {total_storage} > {limits.storage}")
            if not tx.is_coinbase():
                lane = tx.subnetwork_id
                if lane in lanes:
                    gas = lanes[lane] = min(lanes[lane] + tx.gas, (1 << 64) - 1)
                else:
                    if len(lanes) >= self.params.lanes_per_block:
                        raise RuleError(
                            f"exceeds lanes-per-block limit: {len(lanes) + 1} > {self.params.lanes_per_block}"
                        )
                    gas = lanes[lane] = tx.gas
                if gas > self.params.gas_per_lane:
                    raise RuleError(
                        f"exceeds gas-per-lane limit on lane {lane.hex()}: {gas} > {self.params.gas_per_lane}"
                    )
        seen_ids = set()
        seen_outpoints = set()
        created_outpoints = set()
        for tx in txs:
            self.transaction_validator.validate_tx_in_isolation(tx)
            txid = tx.id()
            if txid in seen_ids:
                raise RuleError("duplicate transactions")
            seen_ids.add(txid)
            for inp in tx.inputs:
                # body_validation_in_isolation.rs check_block_double_spends
                if inp.previous_outpoint in seen_outpoints:
                    raise RuleError(f"double spend in same block: {inp.previous_outpoint}")
                seen_outpoints.add(inp.previous_outpoint)
        # check_no_chained_transactions: a tx may not spend an output created
        # in the same block (keeps in-block txs independent -> parallelizable)
        for tx in txs:
            for i in range(len(tx.outputs)):
                created_outpoints.add(TransactionOutpoint(tx.id(), i))
        for op in seen_outpoints:
            if op in created_outpoints:
                raise RuleError(f"chained transaction spending in-block output {op}")
        # in-context: tx lock times vs this block's context
        pmt, _ = self.window_manager.calc_past_median_time(gd)
        hdr = block.header
        for tx in txs[1:]:
            self.transaction_validator.validate_tx_in_header_context(tx, hdr.daa_score, pmt)
        self.storage.block_transactions.insert(block.hash, txs)
        self.storage.statuses.set(block.hash, StatusesStore.STATUS_UTXO_PENDING_VERIFICATION)

    def _update_tips(self, new_block: bytes) -> None:
        parents = set(self.storage.relations.get_parents(new_block))
        self.tips = (self.tips - parents) | {new_block}
        self._persist_tips()

    # ------------------------------------------------------------------
    # virtual stage (pipeline/virtual_processor/)
    # ------------------------------------------------------------------

    def _resolve_virtual(self) -> None:
        # sink search: max blue-work candidate whose chain UTXO-verifies,
        # descending into parents of disqualified candidates
        # (virtual_processor/processor.rs sink_search_algorithm)
        import heapq as _hq

        heap = []  # max-heap via negated key
        seen = set()
        # blue-work sort keys fetched once per candidate: the finality
        # filter, the heap and the virtual-parent sort all reuse them
        blue_work: dict[bytes, int] = {}

        def bw(h):
            w = blue_work.get(h)
            if w is None:
                w = blue_work[h] = self.storage.ghostdag.get_blue_work(h)
            return w

        def push(h):
            if h not in seen:
                seen.add(h)
                _hq.heappush(heap, ((-bw(h), _neg_bytes(h)), h))

        with trace.span("virtual.sink_search"):
            # finality filter (processor.rs:296-316): only tips in the future
            # of the virtual finality point can become the sink; a heavier tip
            # on the wrong side is a FINALITY CONFLICT — surface it, never
            # adopt it
            finality_point = None
            if self.virtual_state is not None:
                pp = self.pruning_processor.pruning_point
                fp = self.depth_manager.calc_finality_point(self.virtual_state.ghostdag_data, pp)
                # virtual_finality_point (processor.rs:386-391): the finality
                # point only anchors when it sits on the pruning point's chain;
                # otherwise the pruning point itself is the anchor (e.g. right
                # after a trusted proof import, where the computed point falls
                # into pruned/disconnected history)
                if (
                    fp != ORIGIN
                    and self.reachability.has(fp)
                    and self.reachability.is_chain_ancestor_of(pp, fp)
                ):
                    finality_point = fp
                elif self.reachability.has(pp):
                    finality_point = pp
            allowed_tips = []
            for t in self.tips:
                if finality_point is not None and not self.reachability.is_dag_ancestor_of(finality_point, t):
                    if t not in self._finality_conflicts and bw(t) > bw(self.sink()):
                        # a chain heavier than ours that excludes our finality
                        # point: requires manual intervention (flow_context.rs
                        # on_finality_conflict -> FinalityConflict notification)
                        self._finality_conflicts[t] = "active"
                        self.notification_root.notify(
                            _FinalityConflictNotification(t, finality_point)
                        )
                    continue
                allowed_tips.append(t)
                push(t)
            sink = None
            while heap:
                _, cand = _hq.heappop(heap)
                st = self.storage.statuses.get(cand)
                if st == StatusesStore.STATUS_UTXO_VALID or (
                    st != StatusesStore.STATUS_DISQUALIFIED and self._ensure_chain_utxo_valid(cand)
                ):
                    sink = cand
                    break
                for p in self.storage.relations.get_parents(cand):
                    if p != ORIGIN:
                        push(p)
            assert sink is not None, "no valid sink found"
            prev_sink = (
                self.virtual_state.ghostdag_data.selected_parent if self.virtual_state is not None else None
            )
            # advance the reachability reindex root toward the agreed chain
            # (inquirer.rs hint_virtual_selected_parent)
            self.reachability.hint_virtual_selected_parent(sink)

            # virtual parents: bounded count of chain-qualified tips from the
            # finality-filtered set, sink first (pick_virtual_parents,
            # processor.rs:1013-1146) — virtual must never merge a tip that
            # excludes the finality point.  Tips already UTXO_VALID skip the
            # requalification walk entirely
            others = sorted(
                (
                    t
                    for t in allowed_tips
                    if t != sink
                    and (
                        self.storage.statuses.get(t) == StatusesStore.STATUS_UTXO_VALID
                        or self._ensure_chain_utxo_valid(t)
                    )
                ),
                key=lambda h: (bw(h), h),
                reverse=True,
            )
            virtual_parents = [sink] + others[: self.params.max_block_parents - 1]
            vgd = self.ghostdag_manager.ghostdag(virtual_parents)
            assert vgd.selected_parent == sink, "virtual selected parent must be the sink"

        with trace.span("virtual.window"):
            # virtual window state: both windows extend the sink's cached
            # windows (difficulty + median-time are cached at header commit),
            # so this is an incremental mergeset merge, not a chain walk
            daa_window = self.window_manager.block_daa_window(vgd)
            bits = self.window_manager.calculate_difficulty_bits(vgd, daa_window)
            pmt, _ = self.window_manager.calc_past_median_time(vgd)

        with trace.span("virtual.commit"):
            # virtual UTXO state: replay virtual mergeset over sink position.
            # The virtual multiset is never read (only chain blocks commit to
            # a utxo_commitment), so skip its device product outright
            self._move_utxo_position(sink)
            ctx = self._calculate_utxo_state(vgd, daa_window.daa_score, need_multiset=False, cause="virtual")
            self.virtual_utxo_diff = ctx["mergeset_diff"]
            prev_state = self.virtual_state
            self.virtual_state = VirtualState(
                parents=virtual_parents,
                ghostdag_data=vgd,
                daa_score=daa_window.daa_score,
                bits=bits,
                past_median_time=pmt,
                accepted_tx_ids=ctx["accepted_tx_ids"],
                mergeset_rewards=ctx["mergeset_rewards"],
                mergeset_non_daa=daa_window.mergeset_non_daa,
            )
            # emit score notifications on every resolve; one net UtxosChanged
            # only when the chain state actually moved
            if prev_state is not None:
                self.notification_root.notify_virtual_change(
                    self.virtual_state, list(self._acc_added.items()), list(self._acc_removed.items())
                )
                if prev_sink is not None and prev_sink != sink:
                    self._notify_chain_changed(prev_sink, sink)
            self._acc_added = {}
            self._acc_removed = {}
            # pruning executor: advance the pruning point + delete stale
            # history (pipeline/pruning_processor/processor.rs worker)
            if prev_state is not None:
                self.pruning_processor.advance_if_possible(self.storage.ghostdag.get(sink))

    def _notify_chain_changed(self, prev_sink: bytes, sink: bytes) -> None:
        """VirtualChainChanged (notify/events.rs): the selected-chain path
        delta between resolves, with acceptance data for added blocks.
        The payload is only assembled when someone is subscribed — during
        IBD this would otherwise hex-encode the entire synced history."""
        from kaspa_tpu.notify.notifier import Notification

        if not self.notification_root.has_subscribers("virtual-chain-changed"):
            return
        # single walk down prev_sink's chain to the first block on sink's
        # chain collects `removed` and the common ancestor together
        removed = []
        cur = prev_sink
        while not (self.reachability.has(cur) and self.reachability.is_chain_ancestor_of(cur, sink)):
            removed.append(cur)
            cur = self.storage.ghostdag.get_selected_parent(cur)
        added = list(self.reachability.forward_chain_iterator(cur, sink))
        self.notification_root.notify(
            Notification(
                "virtual-chain-changed",
                {
                    "added_chain_block_hashes": [h.hex() for h in added],
                    "removed_chain_block_hashes": [h.hex() for h in removed],
                    "accepted_transaction_ids": {
                        h.hex(): [t.hex() for t in self.acceptance_data.get(h, [])] for h in added
                    },
                },
            )
        )

    def _ensure_chain_utxo_valid(self, block: bytes) -> bool:
        """Verify the selected chain up to `block` is UTXO valid; disqualify on failure."""
        # collect unverified chain ancestors
        chain = []
        cur = block
        while self.storage.statuses.get(cur) != StatusesStore.STATUS_UTXO_VALID:
            if self.storage.statuses.get(cur) == StatusesStore.STATUS_DISQUALIFIED:
                return False
            chain.append(cur)
            cur = self.storage.ghostdag.get_selected_parent(cur)
        if not chain:
            return True
        chain.reverse()
        with trace.span("virtual.chain_verify", blocks=len(chain)) as sp:
            # batch every cache-missing segment member's context into one
            # coalesced device dispatch before the serial verify loop —
            # k misses cost one script round-trip instead of k
            if self.speculative is not None and len(chain) > 1:
                self.speculative.precompute_chain(chain)
            served = {"cache": 0, "sync": 0}  # chain blocks by where their context came from
            ok = True
            for c in chain:
                ok = self._verify_chain_block(c, served)
                if not ok:
                    self.storage.statuses.set(c, StatusesStore.STATUS_DISQUALIFIED)
                    self.counters.inc_chain_disqualified()
                    break
            sp.set(hits=served["cache"], misses=served["sync"], qualified=served["cache"] + served["sync"] - (not ok))
        return ok

    def _verify_chain_block(self, block: bytes, served: dict | None = None) -> bool:
        """verify_expected_utxo_state for one chain-candidate block.

        The expensive half — mergeset replay, script batch, muhash product
        (`_calculate_utxo_state`) — is served from the speculative
        precompute cache when a stage worker already ran it for this
        (block, selected_parent) position; the checks + commit half always
        runs here, so hit and miss paths write identical state.  ``served``
        (the caller's tally) counts the block under where its context came
        from: ``cache`` or ``sync``."""
        gd = self.storage.ghostdag.get(block)
        header = self.storage.headers.get(block)
        self._move_utxo_position(gd.selected_parent)
        _CHAIN_VERIFIED.inc()
        entry = None
        if self.speculative is not None:
            entry = self.speculative.take(block, gd.selected_parent)
        source = "cache" if entry is not None else "sync"
        if served is not None:
            served[source] += 1
        ctx = entry.ctx if entry is not None else self._calculate_utxo_state(gd, header.daa_score, cause="fallback")
        with trace.span("virtual.chain_commit", source=source) as sp:
            ok = self._check_and_commit_chain_block(block, gd, header, ctx)
            sp.set(ok=ok)
        return ok

    def _check_and_commit_chain_block(self, block: bytes, gd: GhostdagData, header, ctx: dict) -> bool:
        """The five verify_expected_utxo_state checks + the chain commit,
        over an already-computed UTXO context (requires utxo_position ==
        gd.selected_parent).  Check order and side effects are identical
        whether ctx came from the synchronous path or the speculative
        cache."""
        # 1. utxo commitment
        multiset = ctx["multiset"]
        if multiset.finalize() != header.utxo_commitment:
            return False
        # 2. accepted id merkle root: KIP-15 two-level pre-Toccata, the
        # KIP-21 sequencing commitment after activation
        # (utxo_validation.rs:211-217)
        toccata = self.params.toccata_active(header.daa_score)
        build = None
        if toccata:
            # chain-qualification rule: first parent must be the selected
            # parent (utxo_validation.rs:219-238)
            if header.parents_by_level[0][0] != gd.selected_parent:
                return False
            build = self.lane_tracker.compute(
                gd,
                header.daa_score,
                ctx["mergeset_acceptance"],
                self.storage.headers,
                self.params.toccata_active,
                self._selected_chain_block_at,
            )
            expected_root = build.seq_commit
        else:
            sp_header = self.storage.headers.get(gd.selected_parent)
            expected_root = merkle.merkle_hash(
                sp_header.accepted_id_merkle_root, merkle.calc_merkle_root(ctx["accepted_tx_ids"])
            )
        if expected_root != header.accepted_id_merkle_root:
            return False
        # 3. header pruning point (verify_header_pruning_point: chain rule)
        reply = self.pruning_point_manager.expected_header_pruning_point(gd)
        if reply.pruning_point != header.pruning_point:
            return False
        self.pruning_point_manager.store_pruning_sample(block, reply.pruning_sample)
        # 4. coinbase
        txs = self.storage.block_transactions.get(block)
        if not self._verify_coinbase_transaction(txs[0], header.daa_score, gd, ctx["mergeset_rewards"], self.daa_excluded[block]):
            return False
        # 5. own txs valid in own utxo view
        own_view = UtxoView(self.utxo_set, ctx["mergeset_diff"])
        validated = self._validate_transactions(
            txs, own_view, header.daa_score, FLAG_FULL
        )
        if len(validated) < len(txs) - 1:
            return False

        # commit: store diff/multiset/acceptance, apply position
        self._set_multiset(block, multiset)
        self._set_utxo_diff(block, ctx["mergeset_diff"])
        self._set_acceptance(block, ctx["accepted_tx_ids"])
        self._apply_chain_diff(ctx["mergeset_diff"])
        if build is not None:
            self.lane_tracker.commit(block, build)
        self.selected_chain.append((gd.blue_score, block))
        # bound the in-RAM chain index to the queried window (finality+margin;
        # _selected_chain_block_at raises loudly if this ever proves too tight)
        limit = self.params.finality_depth + 1025
        if len(self.selected_chain) > limit + 256:
            del self.selected_chain[: len(self.selected_chain) - limit]
        self.utxo_position = block
        self._persist_utxo_position()
        self.storage.statuses.set(block, StatusesStore.STATUS_UTXO_VALID)
        self.counters.inc_chain_blocks()
        return True

    def _apply_chain_diff(self, diff: UtxoDiff) -> None:
        # the UtxoSetStore stages its own write-through ops per mutation
        apply_diff(self.utxo_set, diff)
        for op, entry in diff.remove.items():
            if op in self._acc_added:
                del self._acc_added[op]
            else:
                self._acc_removed[op] = entry
        for op, entry in diff.add.items():
            if op in self._acc_removed:
                del self._acc_removed[op]
            else:
                self._acc_added[op] = entry

    def _unapply_chain_diff(self, diff: UtxoDiff) -> None:
        unapply_diff(self.utxo_set, diff)
        for op, entry in diff.add.items():
            if op in self._acc_added:
                del self._acc_added[op]
            else:
                self._acc_removed[op] = entry
        for op, entry in diff.remove.items():
            if op in self._acc_removed:
                del self._acc_removed[op]
            else:
                self._acc_added[op] = entry

    def _selected_chain_block_at(self, target_bs: int) -> bytes:
        """Highest selected-chain block (<= utxo_position) with
        blue_score <= target_bs (processor.rs:790 shortcut anchor)."""
        import bisect

        i = bisect.bisect_right(self.selected_chain, (target_bs, b"\xff" * 32)) - 1
        if i < 0:
            # Target below our chain base.  If the base block is itself
            # pre-Toccata, it is a valid anchor: the reference's backward
            # walk stops at the first pre-Toccata ancestor and folds the
            # shortcut to ZERO (processor.rs:890-905) — any deeper true
            # anchor is also pre-Toccata and folds identically.  This is
            # the bootstrap-from-a-pre-Toccata-PP case, where no anchor
            # segment below the PP exists.
            base = self.selected_chain[0][1]
            base_hdr = self.storage.headers.get(base)
            if not self.params.toccata_active(base_hdr.daa_score):
                return base
            # otherwise selected_chain retention must reach
            # finality_depth+1 below the tip; a miss means pruning trimmed
            # too close — fail loudly rather than anchor the inactivity
            # shortcut wrongly
            raise RuleError(
                f"selected-chain retention violated: no entry with blue_score <= {target_bs}"
            )
        return self.selected_chain[i][1]

    def _verify_coinbase_transaction(self, coinbase, daa_score, gd, mergeset_rewards, non_daa) -> bool:
        miner_data = self.coinbase_manager.deserialize_coinbase_payload(coinbase.payload).miner_data
        expected = self.coinbase_manager.expected_coinbase_transaction(
            daa_score, miner_data, gd, mergeset_rewards, non_daa
        )
        return chash.tx_hash(coinbase) == chash.tx_hash(expected)

    def _calculate_utxo_state(
        self,
        gd: GhostdagData,
        pov_daa_score: int,
        need_multiset: bool = True,
        base=None,
        seed_multiset: MuHash | None = None,
        checker=None,
        token_ns=None,
        cause: str = "virtual",
    ) -> dict:
        """utxo_validation.rs calculate_utxo_state relative to current position
        (must equal gd.selected_parent).

        One ``virtual.mergeset_replay`` span a call, with the merged ``blocks``
        (the selected parent included), the accepted ``txs`` (its coinbase
        not), and who asked as ``cause``: ``stage`` (a stage worker's
        speculation), ``segment`` (`precompute_chain`), ``fallback``
        (`_verify_chain_block` with no cached context: the replay made again
        under the commit lock, or the only one where no verifier is
        attached), ``virtual`` (the virtual's own mergeset), ``build`` (a
        block under construction); ``fallback`` says the same as a boolean.

        ``need_multiset=False`` skips the muhash device product entirely —
        the virtual resolve never reads it (only chain blocks commit to a
        utxo_commitment).

        Speculative mode (``checker`` given): UTXO reads go through ``base``
        (the caller's frozen view of the selected-parent position) instead of
        the live set, the multiset seeds from ``seed_multiset`` and its device
        batch is deferred (returned under ``multiset_items``), and script
        checks are staged *optimistically* on the shared checker — every
        staged tx is treated as accepted, with the staged tokens returned
        under ``staged_tokens`` so the caller can discard the whole context
        if any check fails after the async dispatch resolves."""
        with trace.span("virtual.mergeset_replay", cause=cause, fallback=cause == "fallback") as sp:
            speculative = checker is not None
            if not speculative:
                assert self.utxo_position == gd.selected_parent
            if base is None:
                base = self.utxo_set
            mergeset_diff = UtxoDiff()
            multiset = None
            if need_multiset:
                seed = seed_multiset if seed_multiset is not None else self.multisets[gd.selected_parent]
                multiset = seed.clone()
            accepted_tx_ids: list[bytes] = []
            mergeset_rewards: dict[bytes, BlockRewardData] = {}

            sp_txs = self.storage.block_transactions.get(gd.selected_parent)
            coinbase = sp_txs[0]
            coinbase_entries: list = []
            mergeset_diff.add_transaction(coinbase, coinbase_entries, pov_daa_score)
            accepted_tx_ids.append(coinbase.id())
            # multiset updates accumulate across the whole mergeset and reduce in
            # one batch below (the product is commutative) — this is what routes
            # the muhash work through the device tree-product kernel
            multiset_items: list = [(coinbase, coinbase_entries, pov_daa_score)]
            # per-merged-block acceptance (KIP-21 lane activity source):
            # (merged_block, coinbase payload, [accepted txs in block order])
            mergeset_acceptance: list = []
            staged_tokens: list = []

            ordered = [(gd.selected_parent, sp_txs)] + [
                (b, self.storage.block_transactions.get(b)) for b in gd.ascending_mergeset_without_selected_parent(self.storage.ghostdag)
            ]
            for i, (merged_block, txs) in enumerate(ordered):
                composed = UtxoView(base, mergeset_diff)
                is_selected_parent = i == 0
                flags = FLAG_SKIP_SCRIPTS if is_selected_parent else FLAG_FULL
                if speculative:
                    # token_ns keeps tokens collision-free when several blocks
                    # share one checker (the in-cycle chain precompute)
                    staged = self._validate_transactions(
                        txs, composed, pov_daa_score, flags,
                        checker=checker,
                        token_tag=("ms", i) if token_ns is None else ("ms", token_ns, i),
                        position_anchor=gd.selected_parent,
                    )
                    staged_tokens.extend(t for t, _tx, _e, _f in staged)
                    validated = [(tx, entries, fee) for _t, tx, entries, fee in staged]
                else:
                    validated = self._validate_transactions(txs, composed, pov_daa_score, flags)
                block_fee = 0
                accepted_here = [coinbase] if is_selected_parent else []
                for tx, entries, fee in validated:
                    mergeset_diff.add_transaction(tx, entries, pov_daa_score)
                    multiset_items.append((tx, entries, pov_daa_score))
                    accepted_tx_ids.append(tx.id())
                    accepted_here.append(tx)
                    block_fee += fee
                cb_data = self.coinbase_manager.deserialize_coinbase_payload(txs[0].payload)
                mergeset_rewards[merged_block] = BlockRewardData(cb_data.subsidy, block_fee, cb_data.miner_data.script_public_key)
                mergeset_acceptance.append((merged_block, txs[0].payload, accepted_here))
            if need_multiset and not speculative:
                multiset.add_transactions_batch(multiset_items)

            ctx = {
                "mergeset_diff": mergeset_diff,
                "multiset": multiset,
                "accepted_tx_ids": accepted_tx_ids,
                "mergeset_rewards": mergeset_rewards,
                "mergeset_acceptance": mergeset_acceptance,
            }
            if speculative:
                ctx["multiset_items"] = multiset_items
                ctx["staged_tokens"] = staged_tokens
            sp.set(blocks=len(ordered), txs=len(accepted_tx_ids) - 1)
        return ctx

    def _validate_transactions(
        self, txs, utxo_view, pov_daa_score, flags, checker=None, token_tag=None, position_anchor=None
    ):
        """validate_transactions_in_parallel: returns [(tx, entries, fee)] of
        valid non-coinbase txs; script checks batched on device.

        With a shared ``checker`` (speculative mode) nothing is dispatched
        here: the staged list [(token, tx, entries, fee)] is returned with
        tokens namespaced by ``token_tag``, and the caller joins the async
        handle and maps failures back.  ``position_anchor`` pins the
        seq-commit accessor to the position the synchronous path would have
        (it calls ``_move_utxo_position`` first; speculation does not)."""
        shared = checker is not None
        if not shared:
            checker = self.transaction_validator.new_checker()
        accessor = None
        if self.params.toccata_active(pov_daa_score):
            from kaspa_tpu.consensus.smt_processor import ConsensusSeqCommitAccessor

            accessor = ConsensusSeqCommitAccessor(
                position_anchor if position_anchor is not None else self.utxo_position,
                self.reachability,
                self.storage.headers,
                self.params.toccata_active,
                self.params.finality_depth,
            )
        staged = []
        collected = len(txs) - 1  # the coinbase is not collected
        _COLLECTED_TXS.inc(collected)
        if not shared:
            _COLLECTED_TXS_SYNC.inc(collected)
        # UTXO population, sighash and job staging of one merged block: what
        # the virtual stage does for its scripts before the round trip
        with trace.span("txscript.collect", txs=collected, speculative=shared) as sp:
            jobs0, multisig0, memo0 = checker.queued_jobs(), checker.queued_multisig_inputs(), checker.memo_hits()
            for i, tx in enumerate(txs):
                if i == 0:
                    continue  # coinbase
                entries = []
                missing = False
                for inp in tx.inputs:
                    entry = utxo_view.get(inp.previous_outpoint)
                    if entry is None:
                        missing = True
                        break
                    entries.append(entry)
                if missing:
                    continue
                token = (token_tag, i) if shared else i
                try:
                    fee = self.transaction_validator.validate_populated_transaction_and_get_fee(
                        tx, entries, pov_daa_score, flags, checker=checker, token=token,
                        seq_commit_accessor=accessor,
                    )
                except TxRuleError:
                    continue
                staged.append((token, tx, entries, fee))
            sp.set(
                jobs=checker.queued_jobs() - jobs0, multisig=checker.queued_multisig_inputs() - multisig0,
                memo_hits=checker.memo_hits() - memo0,
            )
        if shared:
            return staged
        script_results = checker.dispatch()
        out = []
        for i, tx, entries, fee in staged:
            if script_results.get(i) is None:
                out.append((tx, entries, fee))
        return out

    # ------------------------------------------------------------------
    # block building (test_consensus.rs build_*_with_parents + the
    # template path of virtual_processor/processor.rs:1351-1510)
    # ------------------------------------------------------------------

    def build_block_with_parents(
        self,
        parents: list[bytes],
        miner_data: MinerData,
        txs: list[Transaction] | None = None,
        timestamp: int | None = None,
        tx_selector=None,
    ) -> Block:
        """Builds a fully valid block merging `parents` (any known tips).

        Computes GHOSTDAG, window state and the UTXO commitments exactly as a
        validator will, so the result passes validate_and_insert_block.
        ``tx_selector(utxo_view, pov_daa_score) -> [Transaction]`` selects
        transactions against the block's own UTXO context (the template
        path's validate_block_template_transactions discipline).
        """
        gd = self.ghostdag_manager.ghostdag(parents)
        if not self._ensure_chain_utxo_valid(gd.selected_parent):
            raise RuleError("selected parent chain is disqualified")
        daa_window = self.window_manager.block_daa_window(gd)
        if self.params.toccata_active(daa_window.daa_score):
            # KIP-21 chain rule: the selected parent leads the parent list
            parents = [gd.selected_parent] + [p for p in parents if p != gd.selected_parent]
        bits = self.window_manager.calculate_difficulty_bits(gd, daa_window)
        pmt, _ = self.window_manager.calc_past_median_time(gd)
        self._move_utxo_position(gd.selected_parent)
        ctx = self._calculate_utxo_state(gd, daa_window.daa_score, cause="build")
        if tx_selector is not None:
            assert txs is None
            txs = tx_selector(UtxoView(self.utxo_set, ctx["mergeset_diff"]), daa_window.daa_score)
        txs = txs or []

        # mergeset rewards only cover merged blocks; txs of THIS block are
        # rewarded by the block that merges it
        coinbase = self.coinbase_manager.expected_coinbase_transaction(
            daa_window.daa_score, miner_data, gd, ctx["mergeset_rewards"], daa_window.mergeset_non_daa
        )
        all_txs = [coinbase] + list(txs)

        sp_header = self.storage.headers.get(gd.selected_parent)
        if self.params.toccata_active(daa_window.daa_score):
            accepted_root = self.lane_tracker.compute(
                gd,
                daa_window.daa_score,
                ctx["mergeset_acceptance"],
                self.storage.headers,
                self.params.toccata_active,
                self._selected_chain_block_at,
            ).seq_commit
        else:
            accepted_root = merkle.merkle_hash(
                sp_header.accepted_id_merkle_root, merkle.calc_merkle_root(ctx["accepted_tx_ids"])
            )
        header = Header(
            version=self.params.block_version(daa_window.daa_score),
            parents_by_level=self.parents_manager.calc_block_parents(
                self.pruning_processor.pruning_point, list(parents)
            ),
            hash_merkle_root=merkle.calc_hash_merkle_root(all_txs),
            accepted_id_merkle_root=accepted_root,
            utxo_commitment=ctx["multiset"].finalize(),
            timestamp=timestamp if timestamp is not None else pmt + 1,
            bits=bits,
            nonce=0,
            daa_score=daa_window.daa_score,
            blue_work=gd.blue_work,
            blue_score=gd.blue_score,
            pruning_point=self.pruning_point_manager.expected_header_pruning_point(gd).pruning_point,
        )
        if header.timestamp <= pmt:
            header.timestamp = pmt + 1
            header.invalidate_cache()
        return Block(header, all_txs)

    def build_block_template(self, miner_data: MinerData, txs: list[Transaction], timestamp: int | None = None) -> Block:
        """Template on top of the current virtual (mining path)."""
        return self.build_block_with_parents(self.virtual_state.parents, miner_data, txs, timestamp)

    def get_virtual_utxo_view(self) -> UtxoView:
        """UTXO view of the current virtual (for tx selection/mempool)."""
        self._move_utxo_position(self.sink())
        return UtxoView(self.utxo_set, self.virtual_utxo_diff)

    def _move_utxo_position(self, target: bytes) -> None:
        """Reposition the materialized UTXO set along the selected chain."""
        if self.utxo_position == target:
            return
        with trace.span("virtual.move_position") as sp:
            unapplied, applied = self._walk_utxo_position(target)
            sp.set(unapplied=unapplied, applied=applied)

    def _walk_utxo_position(self, target: bytes) -> tuple[int, int]:
        """Unapply chain diffs down to a chain ancestor of ``target``, apply
        up to it; returns the chain blocks walked each way."""
        # walk current position down to a chain ancestor of target
        back_path = []
        cur = self.utxo_position
        while not self.reachability.is_chain_ancestor_of(cur, target):
            back_path.append(cur)
            cur = self.storage.ghostdag.get_selected_parent(cur)
        # walk target down to cur, collecting forward path
        fwd_path = []
        t = target
        while t != cur:
            fwd_path.append(t)
            t = self.storage.ghostdag.get_selected_parent(t)
        for b in back_path:
            self._unapply_chain_diff(self.utxo_diffs[b])
            self.lane_tracker.retreat(b)
            assert self.selected_chain[-1][1] == b
            self.selected_chain.pop()
        for b in reversed(fwd_path):
            self._apply_chain_diff(self.utxo_diffs[b])
            self.lane_tracker.advance(b)
            self.selected_chain.append((self.storage.ghostdag.get_blue_score(b), b))
        self.utxo_position = target
        self._persist_utxo_position()
        return len(back_path), len(fwd_path)
