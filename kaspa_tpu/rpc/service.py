"""RpcCoreService: the RPC API implementation over consensus/mempool/indexes.

Reference: rpc/core/src/api/rpc.rs (the ~45-method RpcApi trait) implemented
by rpc/service/src/service.rs against consensus sessions, the mining
manager, and the utxoindex.  This module is the transport-independent core:
the gRPC/wRPC server stacks (rpc/grpc, rpc/wrpc) bind these methods to the
wire in a later milestone; notifications flow through the same
kaspa_tpu.notify chain the reference threads through RpcCoreService.

Methods mirror the reference's names (get_block, get_block_dag_info,
submit_block, submit_transaction, get_utxos_by_addresses, ...) and return
plain dict/dataclass models (the Rpc* mirror types of rpc/core/src/model).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

from kaspa_tpu.consensus.consensus import Consensus, RuleError
from kaspa_tpu.consensus.model.block import Block
from kaspa_tpu.crypto.addresses import Address, extract_script_pub_key_address, pay_to_address_script
from kaspa_tpu.index import UtxoIndex
from kaspa_tpu.mempool import MiningManager
from kaspa_tpu.mempool.mempool import MempoolError
from kaspa_tpu.metrics import PerfMonitor
from kaspa_tpu.notify.notifier import Notifier
from kaspa_tpu.observability import snapshot as observability_snapshot
from kaspa_tpu.utils.sync import lock_trace_snapshot as _lock_trace_snapshot


class RpcError(Exception):
    """RPC-level rejection.  ``code`` is a stable machine-readable
    identifier forwarded on the wire (rpc.rs RpcError submit categories):
    clients branch on tx-orphan / tx-duplicate / tx-rbf-rejected /
    tx-fee-too-low / tx-double-spend / mempool-full / tx-gas / tx-invalid /
    node-overloaded without parsing prose.  ``node-overloaded`` (a brownout
    shed, not a verdict on the tx) additionally carries ``retry_after_ms``,
    forwarded on the wire as ``retryAfterMs`` — the client should back off
    and resubmit the identical tx."""

    def __init__(self, message: str, code: str = "rpc-error", retry_after_ms: int | None = None):
        super().__init__(message)
        self.code = code
        self.retry_after_ms = retry_after_ms


@dataclass
class ServerInfo:
    rpc_api_version: int = 1
    server_version: str = "kaspa-tpu/0.1"
    network_id: str = ""
    has_utxo_index: bool = True
    is_synced: bool = True
    virtual_daa_score: int = 0


class RpcCoreService:
    def __init__(
        self,
        consensus: Consensus,
        mining: MiningManager,
        utxoindex: UtxoIndex | None = None,
        address_prefix: str = "kaspasim",
        p2p_node=None,
        address_manager=None,
        connection_manager=None,
        shutdown_fn=None,
    ):
        self.consensus = consensus
        # the formal consensus boundary (consensus/core/src/api/mod.rs):
        # all consensus reads route through the facade, so staging swaps
        # can never race readers against internal stores
        from kaspa_tpu.consensus.api import ConsensusApi

        self.api = ConsensusApi(consensus)
        self.mining = mining
        # None => run without an index: address-based queries unavailable
        self.utxoindex = utxoindex
        self.address_prefix = address_prefix
        # p2p wiring (None => peer methods report unavailability)
        self.p2p_node = p2p_node
        self.address_manager = address_manager
        self.connection_manager = connection_manager
        self.shutdown_fn = shutdown_fn
        # daemon-installed: () -> metrics.core.MetricsSnapshot | None
        self.metrics_provider = None
        # rpc-level notifier chained onto the consensus root (the reference's
        # consensus -> notify -> index -> rpc chain)
        self.notifier = Notifier("rpc-core", parent=consensus.notification_root)
        self.perf_monitor = PerfMonitor()
        self.start_time = time.time()

    # --- node / dag info ---

    def get_server_info(self) -> ServerInfo:
        return ServerInfo(
            network_id=self.consensus.params.name,
            virtual_daa_score=self.api.get_virtual_daa_score(),
        )

    def get_block_dag_info(self) -> dict:
        return {
            "network": self.consensus.params.name,
            "block_count": self.api.get_block_count(),
            "tip_hashes": [h.hex() for h in self.api.get_tips()],
            "virtual_parent_hashes": [h.hex() for h in self.api.get_virtual_parents_ordered()],
            "difficulty_bits": self.api.get_virtual_bits(),
            "past_median_time": self.api.get_virtual_past_median_time(),
            "virtual_daa_score": self.api.get_virtual_daa_score(),
            "sink": self.api.get_sink().hex(),
            "pruning_point": self.api.pruning_point().hex(),
        }

    def get_sink(self) -> bytes:
        return self.api.get_sink()

    def get_sink_blue_score(self) -> int:
        return self.api.get_sink_blue_score()

    def get_virtual_chain_from_block(self, low: bytes) -> dict:
        """Selected-chain path from `low` to the sink + acceptance data."""
        if not self.api.block_exists(low):
            raise RpcError(f"block {low.hex()} not found")
        from kaspa_tpu.consensus.api import ConsensusError

        try:
            chain = self.api.get_virtual_chain_from_block(low)["added"]
        except ConsensusError as e:
            raise RpcError(str(e)) from e
        return {
            "added_chain_blocks": [h.hex() for h in chain],
            "accepted_transaction_ids": {
                h.hex(): [t.hex() for t in self.api.get_accepted_transaction_ids(h)] for h in chain
            },
        }

    # --- blocks ---

    def get_block(self, block_hash: bytes, include_transactions: bool = True) -> dict:
        if not self.api.block_exists(block_hash):
            raise RpcError(f"block {block_hash.hex()} not found")
        header = self.api.get_header(block_hash)
        out = {
            "hash": block_hash.hex(),
            "header": {
                "version": header.version,
                "parents_by_level": [[p.hex() for p in lvl] for lvl in header.parents_by_level],
                "hash_merkle_root": header.hash_merkle_root.hex(),
                "accepted_id_merkle_root": header.accepted_id_merkle_root.hex(),
                "utxo_commitment": header.utxo_commitment.hex(),
                "timestamp": header.timestamp,
                "bits": header.bits,
                "nonce": header.nonce,
                "daa_score": header.daa_score,
                "blue_work": hex(header.blue_work),
                "blue_score": header.blue_score,
                "pruning_point": header.pruning_point.hex(),
            },
            "verbose": {
                "status": self.api.get_block_status(block_hash),
                "is_chain_block": self.api.is_chain_block(block_hash),
            },
        }
        if include_transactions and self.api.has_block_body(block_hash):
            out["transactions"] = [self._tx_to_rpc(tx) for tx in self.api.get_block_transactions(block_hash)]
        return out

    def get_blocks(self, low_hash: bytes | None = None, include_transactions: bool = False) -> list[dict]:
        """Blocks in the future of `low_hash` (inclusive), or all blocks."""
        hashes = list(self.api.iter_block_hashes())
        if low_hash is not None:
            if not self.api.block_exists(low_hash):
                raise RpcError(f"block {low_hash.hex()} not found")
            hashes = [h for h in hashes if self.api.is_dag_ancestor_of(low_hash, h)]
        return [self.get_block(h, include_transactions) for h in hashes]

    def submit_block(self, block: Block) -> str:
        try:
            if self.p2p_node is not None:
                # the node path runs the concurrent pipeline + orphan/relay
                return self.p2p_node.submit_block(block)
            status = self.api.validate_and_insert_block(block)
        except RuleError as e:
            raise RpcError(f"block rejected: {e}") from e
        # no node, so no ingest tier: the orphans this block gave parents are
        # revalidated one by one (their verdicts are nobody's to hear)
        from kaspa_tpu.consensus.processes.transaction_validator import TxRuleError

        for entry in self.mining.handle_new_block_transactions(block.transactions, self.api.get_virtual_daa_score()):
            try:
                self.mining.validate_and_insert_transaction(entry.tx)
            except (MempoolError, TxRuleError):
                pass
        return status

    def get_block_template(self, pay_address: str, extra_data: bytes = b"") -> Block:
        from kaspa_tpu.consensus.processes.coinbase import MinerData

        # MiningRuleEngine gate (rule_engine.rs should_mine): templates are
        # refused while the node is unsynced/disconnected, unless the
        # sync-rate rule determined the network itself stalled
        engine = getattr(self, "rule_engine", None)
        if engine is not None:
            sink_ts = self.api.get_sink_timestamp()
            if not engine.should_mine(sink_ts):
                raise RpcError("node is not synced: block templates unavailable")
        addr = Address.from_string(pay_address)
        spk = pay_to_address_script(addr)
        return self.mining.get_block_template(MinerData(spk, extra_data))

    # --- transactions ---

    def _admit_transaction(self, tx) -> list[bytes]:
        """Shared admission for submit/replacement: through the node's
        batched ingest tier when p2p is wired (concurrent submitters share
        a verify wave; accepted txs are relayed), direct otherwise.  Maps
        rejections to RpcError with the mempool's stable code, and reports
        an orphan park explicitly — the reference's submit rejects orphans
        unless allow_orphan, and a caller must be able to tell a parked tx
        from a pooled one (rpc.rs RejectedTransactionIsAnOrphan)."""
        from kaspa_tpu.consensus.processes.transaction_validator import TxRuleError

        try:
            if self.p2p_node is not None:
                evicted = self.p2p_node.submit_transaction(tx)
            else:
                evicted = self.mining.validate_and_insert_transaction(tx)
        except MempoolError as e:
            raise RpcError(
                f"transaction rejected: {e}",
                code=e.code,
                retry_after_ms=getattr(e, "retry_after_ms", None),
            ) from e
        except TxRuleError as e:
            raise RpcError(f"transaction rejected: {e}", code="tx-invalid") from e
        if tx.id() in self.mining.mempool.orphans:
            raise RpcError(
                f"transaction {tx.id().hex()} is an orphan (missing inputs); "
                "it was parked in the orphan pool awaiting its parents",
                code="tx-orphan",
            )
        return evicted

    def submit_transaction(self, tx) -> bytes:
        self._admit_transaction(tx)
        return tx.id()

    def get_mempool_entries(self, include_orphan_pool: bool = True) -> list[dict]:
        out = [
            {"transaction_id": txid.hex(), "fee": e.fee, "mass": e.mass, "is_orphan": False}
            for txid, e in self.mining.mempool.pool.items()
        ]
        if include_orphan_pool:
            out.extend(
                {"transaction_id": txid.hex(), "fee": e.fee, "mass": e.mass, "is_orphan": True}
                for txid, e in self.mining.mempool.orphans.items()
            )
        return out

    def get_mempool_entry(self, txid: bytes) -> dict:
        e = self.mining.mempool.get(txid)
        if e is not None:
            return {"transaction_id": txid.hex(), "fee": e.fee, "mass": e.mass, "is_orphan": False}
        e = self.mining.mempool.orphans.get(txid)
        if e is not None:
            return {"transaction_id": txid.hex(), "fee": e.fee, "mass": e.mass, "is_orphan": True}
        raise RpcError(f"transaction {txid.hex()} not in mempool")

    # --- utxos / balances (utxoindex-backed, rpc.rs get_utxos_by_addresses) ---

    def _require_index(self):
        if self.utxoindex is None:
            raise RpcError("method unavailable without --utxoindex")
        return self.utxoindex

    def get_utxos_by_addresses(self, addresses: list[str]) -> list[dict]:
        self._require_index()
        out = []
        for s in addresses:
            addr = Address.from_string(s)
            spk = pay_to_address_script(addr)
            for outpoint, entry in self.utxoindex.get_utxos_by_script(spk.script).items():
                out.append(
                    {
                        "address": s,
                        "outpoint": {"transaction_id": outpoint.transaction_id.hex(), "index": outpoint.index},
                        "utxo_entry": {
                            "amount": entry.amount,
                            "block_daa_score": entry.block_daa_score,
                            "is_coinbase": entry.is_coinbase,
                        },
                    }
                )
        return out

    def get_balance_by_address(self, address: str) -> int:
        spk = pay_to_address_script(Address.from_string(address))
        return self._require_index().get_balance_by_script(spk.script)

    def get_coin_supply(self) -> dict:
        return {"circulating_sompi": self._require_index().get_circulating_supply()}

    # --- subscriptions (notify_* RPCs) ---

    def register_listener(self, callback) -> int:
        return self.notifier.register(callback)

    def start_notify(self, listener_id: int, event_type: str, addresses: list[str] | None = None) -> None:
        spks = None
        if addresses is not None:
            spks = {pay_to_address_script(Address.from_string(a)).script for a in addresses}
        self.notifier.start_notify(listener_id, event_type, spks)

    def stop_notify(self, listener_id: int, event_type: str) -> None:
        self.notifier.stop_notify(listener_id, event_type)

    # --- metrics (rpc.rs get_metrics -> metrics/core MetricsSnapshot) ---

    def get_metrics(self) -> dict:
        from dataclasses import asdict

        import jax

        sc = self.consensus.transaction_validator.sig_cache
        memo = self.consensus.transaction_validator.tx_memo
        obs = observability_snapshot()
        devs = jax.devices()
        return {
            "uptime_seconds": time.time() - self.start_time,
            # what this node's verify/muhash kernels run on, as JAX reports it
            "device": {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)},
            "block_count": self.api.get_block_count(),
            "tip_count": self.api.get_tips_len(),
            "mempool_size": len(self.mining.mempool),
            "virtual_daa_score": self.api.get_virtual_daa_score(),
            "sig_cache_hits": sc.hits,
            "sig_cache_misses": sc.misses,
            # the script-verdict memo over it: transactions asked, and those not collected again
            "tx_memo_hits": memo.hits,
            "tx_memo_lookups": memo.hits + memo.misses,
            # what the memo is asked from: transactions handed to script collection
            # (process-wide), and those of them collected for a blocking dispatch
            "collected_txs": obs["counters"]["txscript_collected_txs"],
            "collected_txs_sync": obs["counters"]["txscript_sync_collected_txs"],
            "process_counters": asdict(self.consensus.counters.snapshot()),
            "process_metrics": asdict(self.perf_monitor.sample()),
            # per-lock acquisition/hold aggregates when KASPA_TPU_LOCK_DEBUG
            # is on (the reference's semaphore-trace analog); {} otherwise
            "lock_trace": _lock_trace_snapshot(),
            # grouped snapshot with derived rates (metrics/core/src/data.rs),
            # sampled by the daemon's tick service
            "snapshot": (
                {"unixtime_millis": snap.unixtime_millis, **snap.values}
                if self.metrics_provider is not None and (snap := self.metrics_provider()) is not None
                else None
            ),
            # span/histogram/counter registry (observability/core): per-stage
            # pipeline latencies, secp batch occupancy, jit compile counts,
            # store cache hit rates — the same tree prom.render() exports
            "observability": obs,
            # serving-plane latency observatory (the Broadcaster collector):
            # fanout state + per-stage block-accept -> wire lag quantiles
            # (serving_lag_ms), surfaced top-level so dashboards don't dig
            "serving": obs.get("serving", {}),
        }

    def get_metrics_prometheus(self) -> str:
        """The observability registry in Prometheus text exposition format
        (the reference daemon's --prometheus endpoint analog)."""
        from kaspa_tpu.observability import prom

        return prom.render()

    def get_traces(self, limit: int = 32, verbose: bool = False) -> dict:
        """Flight-recorder surface: recent completed block traces with
        their critical-path attribution.  ``verbose`` returns the full
        span trees (trace_report.py / Perfetto input); the default is the
        per-block summary (spans, threads, wall ms, top stages)."""
        from kaspa_tpu.observability import flight

        out = {"enabled": flight.enabled(), "traces": flight.summaries(limit=limit)}
        if verbose:
            out["full"] = flight.traces()[-limit:]
        return out

    # --- node info / misc (rpc.rs ping/get_info/get_current_network/...) ---

    def ping(self) -> dict:
        return {}

    def get_current_network(self) -> str:
        return self.consensus.params.name

    def get_info(self) -> dict:
        return {
            "p2p_id": self.consensus.params.name,
            "mempool_size": len(self.mining.mempool),
            "server_version": "kaspa-tpu/0.2",
            "is_utxo_indexed": self.utxoindex is not None,
            "is_synced": True,
            "has_notify_command": True,
            "has_message_id": True,
        }

    def get_block_count(self) -> dict:
        n = self.api.get_block_count()
        return {"header_count": n, "block_count": n}

    def get_sync_status(self) -> bool:
        return True

    def get_system_info(self) -> dict:
        from kaspa_tpu.utils.sysinfo import system_info

        return system_info()

    def shutdown(self) -> dict:
        if self.shutdown_fn is None:
            raise RpcError("shutdown is not wired on this node")
        self.shutdown_fn()
        return {}

    def get_subnetwork(self, subnetwork_id: str) -> dict:
        raise RpcError(f"subnetwork {subnetwork_id} not found")

    def get_seq_commit_lane_proof(self, *_args) -> dict:
        raise RpcError("seq-commit lanes are not active (pre-Toccata ruleset)")

    # --- headers / chain queries ---

    def get_headers(self, start_hash: bytes, limit: int = 100, is_ascending: bool = True) -> list[dict]:
        if not self.api.block_exists(start_hash):
            raise RpcError(f"block {start_hash.hex()} not found")
        out = []
        cur = start_hash
        if is_ascending:
            # follow the selected chain toward the sink
            sink = self.api.get_sink()
            if not self.api.is_chain_ancestor_of(cur, sink):
                raise RpcError("start hash is not on the selected chain")
            while len(out) < limit:
                out.append(self.get_block(cur, include_transactions=False)["header"] | {"hash": cur.hex()})
                if cur == sink:
                    break
                cur = self.api.get_next_chain_ancestor(sink, cur)
        else:
            genesis = self.consensus.params.genesis.hash
            while len(out) < limit:
                out.append(self.get_block(cur, include_transactions=False)["header"] | {"hash": cur.hex()})
                if cur == genesis:
                    break
                cur = self.api.get_selected_parent(cur)
        return out

    def get_current_block_color(self, block_hash: bytes) -> dict:
        """Blue/red of `block_hash` from the virtual's perspective (rpc.rs
        get_current_block_color -> ConsensusApi get_current_block_color)."""
        from kaspa_tpu.consensus.api import ConsensusError

        if not self.api.block_exists(block_hash):
            raise RpcError(f"block {block_hash.hex()} not found")
        try:
            return {"blue": self.api.get_current_block_color(block_hash)}
        except ConsensusError as e:
            raise RpcError(str(e)) from e

    def get_daa_score_timestamp_estimate(self, daa_scores: list[int]) -> list[int]:
        """Timestamps of the selected-chain blocks nearest each DAA score."""
        chain = []
        cur = self.api.get_sink()
        genesis = self.consensus.params.genesis.hash
        while True:
            chain.append(cur)
            if cur == genesis:
                break
            cur = self.api.get_selected_parent(cur)
        chain.reverse()
        scores = [self.api.get_daa_score(h) for h in chain]
        import bisect

        out = []
        for q in daa_scores:
            i = min(bisect.bisect_left(scores, q), len(chain) - 1)
            out.append(self.api.get_block_timestamp(chain[i]))
        return out

    def estimate_network_hashes_per_second(self, window_size: int = 1000, start_hash: bytes | None = None) -> int:
        """Σ chain-block work over the window / elapsed time (rpc.rs) —
        delegated to the ConsensusApi estimator."""
        from kaspa_tpu.consensus.api import ConsensusError

        try:
            return self.api.estimate_network_hashes_per_second(start_hash, window_size)
        except ConsensusError as e:
            raise RpcError(str(e)) from e

    def get_block_reward_info(self, block_hash: bytes | None = None) -> dict:
        h = block_hash if block_hash is not None else self.api.get_sink()
        if not self.api.block_exists(h):
            raise RpcError(f"block {h.hex()} not found")
        daa = self.api.get_daa_score(h)
        subsidy = self.consensus.coinbase_manager.calc_block_subsidy(daa)
        return {"block_hash": h.hex(), "daa_score": daa, "subsidy": subsidy}

    def resolve_finality_conflict(self, finality_block_hash: bytes) -> dict:
        """Operator acknowledgement of a finality conflict (rpc.rs
        resolve_finality_conflict): clears the tracked conflicts and emits
        FinalityConflictResolved; adopting the competing chain requires a
        resync from a peer carrying it (the reference likewise requires
        manual intervention)."""
        acked = self.api.acknowledge_finality_conflicts()
        if not acked:
            raise RpcError("no active finality conflict to resolve")
        from kaspa_tpu.notify.notifier import Notification

        self.consensus.notification_root.notify(
            Notification(
                "finality-conflict-resolved",
                {"finality_block_hash": finality_block_hash.hex()},
            )
        )
        return {}

    _RETURN_ADDRESS_DAA_SLACK = 2_000  # search radius around the claimed score

    def get_utxo_return_address(self, txid: bytes, accepting_block_daa_score: int) -> str:
        """Source address of a tx's first input (rpc.rs get_utxo_return_address).

        The accepting DAA score narrows the search to nearby accepting chain
        blocks; the funding output is then resolved from bodies in the
        accepting block's past within the same bounded window (the reference
        resolves it via its tx-index; pruned or out-of-window history raises)."""
        lo = accepting_block_daa_score - self._RETURN_ADDRESS_DAA_SLACK
        hi = accepting_block_daa_score + self._RETURN_ADDRESS_DAA_SLACK
        src_tx = None
        for bh, txids in self.api.iter_acceptance():
            daa = self.api.get_daa_score(bh)
            if accepting_block_daa_score and not (lo <= daa <= hi):
                continue
            if txid not in txids:
                continue
            # scan the merged blocks' bodies for the tx
            for cand in [bh, *self.api.get_ghostdag_data(bh).unordered_mergeset()]:
                if not self.api.has_block_body(cand):
                    continue
                for tx in self.api.get_block_transactions(cand):
                    if tx.id() == txid:
                        src_tx = tx
                        break
            if src_tx is not None:
                break
        if src_tx is None:
            raise RpcError("transaction not found in accepted history near the given DAA score")
        if not src_tx.inputs:
            raise RpcError("transaction is coinbase; no return address")
        prev = src_tx.inputs[0].previous_outpoint
        spk = self._find_output_script(prev, hi)
        if spk is None:
            raise RpcError("source output unavailable (pruned or beyond search window)")
        return extract_script_pub_key_address(spk, self.address_prefix).to_string()

    def _find_output_script(self, outpoint, max_daa: int):
        """Bounded body search for a funding output: only blocks below the
        acceptance window's upper DAA bound are scanned."""
        return self.api.find_output_script(outpoint, max_daa)

    # --- fees ---

    def get_fee_estimate(self) -> dict:
        est = self.mining.get_fee_estimate()
        bucket = lambda b: {"feerate": b.feerate, "estimated_seconds": b.estimated_seconds}  # noqa: E731
        return {
            "priority_bucket": bucket(est.priority_bucket),
            "normal_buckets": [bucket(b) for b in est.normal_buckets],
            "low_buckets": [bucket(b) for b in est.low_buckets],
        }

    def get_fee_estimate_experimental(self, verbose: bool = False) -> dict:
        out = {"estimate": self.get_fee_estimate()}
        if verbose:
            mp = self.mining.mempool
            out["verbose"] = {
                "mempool_ready_transactions_count": len(mp.frontier),
                "mempool_ready_transactions_total_mass": mp.frontier.total_mass,
                "network_mass_per_second": self.consensus.params.max_block_mass
                * max(1, round(1000 / self.consensus.params.target_time_per_block)),
            }
        return out

    def submit_transaction_replacement(self, tx) -> dict:
        """RBF submission: returns the replaced txid (rpc.rs)."""
        evicted = self._admit_transaction(tx)
        return {
            "transaction_id": tx.id().hex(),
            "replaced_transaction_ids": [t.hex() for t in evicted],
        }

    # --- addresses / balances (plural + mempool-by-address) ---

    def get_balances_by_addresses(self, addresses: list[str]) -> list[dict]:
        return [
            {"address": a, "balance": self.get_balance_by_address(a)} for a in addresses
        ]

    def get_mempool_entries_by_addresses(self, addresses: list[str]) -> list[dict]:
        spk_to_addr = {
            pay_to_address_script(Address.from_string(a)).script: a for a in addresses
        }
        out = {a: {"address": a, "sending": [], "receiving": []} for a in addresses}
        pool = self.mining.mempool.pool
        view = self.api.get_virtual_utxo_view()
        for txid, e in pool.items():
            for o in e.tx.outputs:
                a = spk_to_addr.get(o.script_public_key.script)
                if a is not None:
                    out[a]["receiving"].append(txid.hex())
            for inp in e.tx.inputs:
                # resolve the spent output's script: virtual UTXO set first,
                # then an in-pool parent's outputs (chained spend)
                op = inp.previous_outpoint
                entry = view.get(op)
                if entry is not None:
                    spk = entry.script_public_key.script
                else:
                    parent = pool.get(op.transaction_id)
                    if parent is None or op.index >= len(parent.tx.outputs):
                        continue
                    spk = parent.tx.outputs[op.index].script_public_key.script
                a = spk_to_addr.get(spk)
                if a is not None:
                    out[a]["sending"].append(txid.hex())
        return list(out.values())

    # --- peers (addressmanager/connectionmanager-backed) ---

    def _require_p2p(self):
        if self.p2p_node is None:
            raise RpcError("p2p methods unavailable: node runs without a P2P stack")
        return self.p2p_node

    def add_peer(self, address: str, is_permanent: bool = False) -> dict:
        self._require_p2p()
        if self.connection_manager is None:
            raise RpcError("connection manager not wired")
        from kaspa_tpu.p2p.address_manager import NetAddress

        na = NetAddress.parse(address)
        if self.address_manager is not None:
            self.address_manager.add_address(na)
        self.connection_manager.add_connection_request(na, is_permanent)
        return {}

    def get_connected_peer_info(self) -> list[dict]:
        node = self._require_p2p()
        out = []
        for peer in list(node.peers):
            addr = getattr(peer, "peer_address", None)
            out.append(
                {
                    "id": hex(id(peer) & 0xFFFFFFFF),
                    "address": str(addr) if addr else "in-process",
                    "is_outbound": getattr(peer, "outbound", False),
                    "handshaken": getattr(peer, "handshaken", True),
                }
            )
        return out

    def get_connections(self) -> dict:
        node = self._require_p2p()
        peers = list(node.peers)
        return {
            "clients": 0,
            "peers": len(peers),
            "outbound": sum(1 for p in peers if getattr(p, "outbound", False)),
        }

    def get_peer_addresses(self) -> dict:
        if self.address_manager is None:
            raise RpcError("address manager not wired")
        return {
            "known_addresses": [str(a) for a in self.address_manager.get_all_addresses()],
            "banned_addresses": self.address_manager.get_all_banned_addresses(),
        }

    def ban(self, ip: str) -> dict:
        if self.address_manager is None:
            raise RpcError("address manager not wired")
        self.address_manager.ban(ip)
        node = self.p2p_node
        if node is not None:
            for peer in list(node.peers):
                addr = getattr(peer, "peer_address", None)
                if addr is not None and addr.ip == ip and hasattr(peer, "close"):
                    peer.close()
        return {}

    def unban(self, ip: str) -> dict:
        if self.address_manager is None:
            raise RpcError("address manager not wired")
        self.address_manager.unban(ip)
        return {}

    def unregister_listener(self, listener_id: int) -> None:
        self.notifier.unregister(listener_id)

    # --- helpers ---

    def _tx_to_rpc(self, tx) -> dict:
        d = {
            "transaction_id": tx.id().hex(),
            "version": tx.version,
            "lock_time": tx.lock_time,
            "gas": tx.gas,
            "payload": tx.payload.hex(),
            "inputs": [
                {
                    "previous_outpoint": {
                        "transaction_id": i.previous_outpoint.transaction_id.hex(),
                        "index": i.previous_outpoint.index,
                    },
                    "signature_script": i.signature_script.hex(),
                    "sequence": i.sequence,
                }
                for i in tx.inputs
            ],
            "outputs": [],
        }
        for o in tx.outputs:
            entry = {"amount": o.value, "script_public_key": o.script_public_key.script.hex()}
            try:
                entry["address"] = extract_script_pub_key_address(o.script_public_key, self.address_prefix).to_string()
            except Exception:
                pass
            d["outputs"].append(entry)
        return d
