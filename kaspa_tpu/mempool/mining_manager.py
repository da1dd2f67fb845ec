"""MiningManager: the mempool facade + block-template pipeline.

Reference: mining/src/manager.rs (validate_and_insert_transaction,
get_block_template with cache, handle_new_block_transactions) and
mining/src/block_template/builder.rs.  Tx validation against the virtual
UTXO view routes through the consensus validator (scripts batched on
device); templates come from Consensus.build_block_template.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

from kaspa_tpu.consensus.consensus import Consensus
from kaspa_tpu.consensus.model import Transaction
from kaspa_tpu.consensus.model.block import Block
from kaspa_tpu.consensus.processes.coinbase import MinerData
from kaspa_tpu.consensus.processes.transaction_validator import TxRuleError
from kaspa_tpu.mempool.mempool import Mempool, MempoolConfig, MempoolError, MempoolTx
from kaspa_tpu.observability import trace
from kaspa_tpu.observability.core import REGISTRY

_TEMPLATE_REBUILD_MS = REGISTRY.histogram(
    "mempool_template_rebuild_ms",
    (0.5, 1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0, 1000.0, 2500.0),
    help="block-template rebuild latency (frontier selection + build), milliseconds",
)
from kaspa_tpu.observability.shed import SHED as _SHED  # noqa: E402  (family declared once there)


@dataclass
class TemplateCache:
    """block_template cache (mining/src/cache.rs): short-lived reuse window.

    Tx-intake invalidation can be *debounced*: a new pool entry makes the
    cached template stale-but-still-mineable (it just misses the newest
    txs), so ``mark_dirty`` keeps serving it until ``debounce`` seconds
    after the last rebuild — a tx flood then costs one rebuild per debounce
    window instead of one per transaction.  The default debounce of 0 keeps
    the historical rebuild-on-next-request behavior; the daemon and the
    tx-flood harness opt in.  Block acceptance calls ``clear`` (the cached
    template may now be *invalid*), which drops it unconditionally.
    """

    template: Block | None = None
    created: float = 0.0
    lifetime: float = 1.0  # seconds
    debounce: float = 0.0  # min seconds between tx-churn-driven rebuilds
    dirty: bool = False
    # CRITICAL-brownout deferral: extra grace past lifetime/debounce during
    # which a stale-but-mineable template keeps serving instead of paying a
    # rebuild (bounded staleness: hard ceiling lifetime + defer_grace).
    # clear() is unaffected — an *invalid* template never survives.
    defer_grace: float = 0.0

    def get(self):
        if self.template is None:
            return None
        age = time.monotonic() - self.created
        if age >= self.lifetime + self.defer_grace:
            return None
        if age >= self.lifetime or (self.dirty and age >= self.debounce):
            if self.defer_grace > 0.0:
                _SHED.inc("template_deferral")
                return self.template
            return None
        return self.template

    def set(self, template: Block):
        self.template = template
        self.created = time.monotonic()
        self.dirty = False

    def mark_dirty(self):
        self.dirty = True

    def clear(self):
        self.template = None
        self.dirty = False


@dataclass
class PreparedTx:
    """One entrant past the contextual pre-checks, its signature/script jobs
    collected into a shared checker, awaiting the batched verify verdict.
    ``entry is None`` means the tx was parked in the orphan pool during
    prepare (missing inputs) and needs no finish step."""

    tx: Transaction
    token: int
    entry: MempoolTx | None

    @property
    def orphan(self) -> bool:
        return self.entry is None


class MiningManager:
    def __init__(
        self,
        consensus: Consensus,
        config: MempoolConfig | None = None,
        seed: int | None = None,
        template_debounce: float = 0.0,
    ):
        self.consensus = consensus
        params = consensus.params
        self.mempool = Mempool(
            config,
            target_time_per_block_seconds=params.target_time_per_block / 1000.0,
            seed=seed,
        )
        self.template_cache = TemplateCache(debounce=template_debounce)

    def set_template_deferral(self, grace_s: float) -> None:
        """Brownout seam: serve stale-but-mineable templates for up to
        ``grace_s`` past their normal rebuild point (0 restores normal
        rebuild behavior).  Block acceptance still clears unconditionally."""
        self.template_cache.defer_grace = max(0.0, float(grace_s))

    # --- fee estimation (manager.rs get_realtime_feerate_estimations) ---

    def get_fee_estimate(self):
        from kaspa_tpu.mempool.feerate import FeerateEstimatorArgs

        params = self.consensus.params
        args = FeerateEstimatorArgs(
            network_blocks_per_second=max(1, round(1000 / params.target_time_per_block)),
            maximum_mass_per_block=params.max_block_mass,
        )
        estimator = self.mempool.build_feerate_estimator(args)
        return estimator.calc_estimations(minimum_standard_feerate=1.0)

    # --- tx intake (manager.rs:296-421) ---

    def validate_and_insert_transaction(self, tx: Transaction) -> list[bytes]:
        """Validate against the virtual UTXO view and insert; returns RBF-evicted
        txids.  Raises MempoolError/TxRuleError on rejection; parks txs with
        missing inputs in the orphan pool.

        The batched ingest tier (kaspa_tpu/ingest/) runs the same two
        halves — ``prepare_transaction`` per entrant in arrival order, one
        shared checker dispatch, then ``finish_transaction`` in the same
        order — so batched admission is state-identical to this per-tx path.
        """
        checker = self.consensus.transaction_validator.new_checker()
        prepared = self.prepare_transaction(tx, checker, token=0)
        err = checker.dispatch().get(0)
        return self.finish_transaction(prepared, err)

    def prepare_transaction(self, tx: Transaction, checker, token: int) -> PreparedTx:
        """Contextual pre-checks + signature-job collection for one entrant.

        Runs everything that must see mempool/consensus state in arrival
        order: isolation + gas-cap + header-context checks, the virtual
        UTXO view lookup (missing inputs park the tx in the orphan pool
        immediately), and fee/mass population — collecting the tx's
        signature/script jobs into ``checker`` under ``token`` instead of
        verifying inline.  Raises MempoolError/TxRuleError on pre-check
        rejection."""
        validator = self.consensus.transaction_validator
        validator.validate_tx_in_isolation(tx)
        # per-tx gas cap (mining/src/mempool/check_transaction_limits.rs:19
        # RejectGas): a tx whose gas alone exceeds the per-lane cap can never
        # be mined, so it must not enter the pool
        if tx.gas > self.consensus.params.gas_per_lane:
            raise MempoolError(
                f"transaction gas {tx.gas} exceeds the per-lane cap {self.consensus.params.gas_per_lane}",
                code="tx-gas",
            )
        virtual = self.consensus.virtual_state
        validator.validate_tx_in_header_context(tx, virtual.daa_score, virtual.past_median_time)

        view = self.consensus.get_virtual_utxo_view()
        entries = []
        missing = False
        for inp in tx.inputs:
            entry = view.get(inp.previous_outpoint)
            if entry is None:
                missing = True
                break
            entries.append(entry)
        if missing:
            nc = self._masses(tx)
            entry = MempoolTx(tx, fee=0, mass=nc.compute_mass, added_daa_score=virtual.daa_score, transient_mass=nc.transient_mass)
            self.mempool.insert(entry, orphan=True)
            return PreparedTx(tx, token, None)

        accessor = None
        if self.consensus.params.toccata_active(virtual.daa_score):
            # mempool/consensus acceptance parity for OpChainblockSeqCommit
            # (validate_block_template_transaction passes the same accessor)
            from kaspa_tpu.consensus.smt_processor import ConsensusSeqCommitAccessor

            accessor = ConsensusSeqCommitAccessor(
                self.consensus.sink(),
                self.consensus.reachability,
                self.consensus.storage.headers,
                self.consensus.params.toccata_active,
                self.consensus.params.finality_depth,
            )
        fee = validator.validate_populated_transaction_and_get_fee(
            tx, entries, virtual.daa_score, checker=checker, token=token, seq_commit_accessor=accessor
        )
        nc = self._masses(tx)
        return PreparedTx(
            tx, token, MempoolTx(tx, fee, nc.compute_mass, virtual.daa_score, nc.transient_mass)
        )

    def finish_transaction(self, prepared: PreparedTx, err) -> list[bytes]:
        """Second half of admission: consume the verify verdict for one
        prepared entrant and insert on success.  ``err`` is the checker's
        per-token result (None = all signatures/scripts valid)."""
        if prepared.entry is None:
            return []  # parked as orphan during prepare
        if err is not None:
            raise TxRuleError(str(err))
        evicted = self.mempool.insert(prepared.entry)
        self.template_cache.mark_dirty()
        return evicted

    def _masses(self, tx: Transaction):
        return self.consensus.transaction_validator.mass_calculator.calc_non_contextual_masses(tx)

    # --- block templates (manager.rs:94-215) ---

    def get_block_template(self, miner_data: MinerData, timestamp: int | None = None) -> Block:
        cached = self.template_cache.get()
        if cached is not None:
            return cached
        if timestamp is None:
            # real templates carry wall-clock time (clamped to pmt+1 by the
            # builder) — sync-state gating reads sink recency off these
            import time as _time

            timestamp = int(_time.time() * 1000)
        from kaspa_tpu.consensus.mass import BlockLaneLimits, BlockMassLimits

        params = self.consensus.params
        limits = BlockMassLimits.with_shared_limit(params.max_block_mass)
        lane_limits = BlockLaneLimits(params.lanes_per_block, params.gas_per_lane)
        t0 = time.perf_counter()
        selected = self.mempool.select_transactions(mass_limits=limits, lane_limits=lane_limits)
        template = self.consensus.build_block_template(miner_data, [e.tx for e in selected], timestamp)
        _TEMPLATE_REBUILD_MS.observe((time.perf_counter() - t0) * 1000.0)
        self.template_cache.set(template)
        return template

    # --- new-block notification (manager.rs:605 handle_new_block_transactions) ---

    def _notify_new_template(self) -> None:
        from kaspa_tpu.notify.notifier import Notification

        self.consensus.notification_root.notify(Notification("new-block-template", {}))

    def handle_new_block_transactions(self, block_txs: list[Transaction], daa_score: int) -> list[MempoolTx]:
        """The mempool's half of ``on_new_block``.  Returns the orphans whose
        parents the block just created, taken out of the orphan pool: the
        caller hands them back to admission (they are revalidated there)."""
        with trace.span("mempool.handle_block", txs=len(block_txs)) as sp:
            accepted_ids = [tx.id() for tx in block_txs]
            self.mempool.handle_accepted_transactions(accepted_ids, daa_score)
            spent = [inp.previous_outpoint for tx in block_txs for inp in tx.inputs]
            self.mempool.remove_conflicting(spent)
            self.mempool.expire(daa_score)
            self.template_cache.clear()
            # a fresh template is now available (notify/events.rs NewBlockTemplate)
            self._notify_new_template()
            unorphaned = self.mempool.unorphan_candidates(set(accepted_ids))
            sp.set(unorphaned=len(unorphaned))
        return unorphaned
