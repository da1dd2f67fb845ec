"""Node assembly: the kaspad-equivalent daemon.

Reference: kaspad/src/{main,daemon,args}.rs — parse args, assemble the
service stack (consensus, mining manager, utxoindex, notification chain,
RPC), and serve RPC on a socket.  The wire protocol here is line-delimited
JSON-RPC over TCP (the gRPC/wRPC codec stacks bind to the same
RpcCoreService in a later milestone); P2P connections use the in-process
flow layer and can be bridged over sockets the same way.

Run: ``python -m kaspa_tpu.node --appdir /tmp/kaspa --rpclisten 127.0.0.1:16110``
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import socketserver
import threading

from kaspa_tpu.consensus.consensus import Consensus
from kaspa_tpu.consensus.params import Params, simnet_params
from kaspa_tpu.index import UtxoIndex
from kaspa_tpu.mempool import MiningManager
from kaspa_tpu.observability.core import REGISTRY
from kaspa_tpu.p2p import Node
from kaspa_tpu.rpc import RpcCoreService
from kaspa_tpu.utils.sync import ranked_lock

# per-encoding request counters (rpc/wrpc/server metrics): line-json is the
# TCP transport, json/borsh are the WebSocket text/binary frame paths
_RPC_BY_ENCODING = REGISTRY.counter_family(
    "rpc_requests_by_encoding", "encoding", help="RPC requests served, by wire encoding"
)


class DaemonArgs(argparse.Namespace):
    pass


def parse_args(argv=None) -> DaemonArgs:
    """kaspad/src/args.rs equivalent (the subset meaningful this round)."""
    p = argparse.ArgumentParser(prog="kaspa-tpu-node", description="kaspa-tpu full node")
    p.add_argument("--appdir", default=os.path.expanduser("~/.kaspa-tpu"), help="data directory")
    p.add_argument("--rpclisten", default="127.0.0.1:16110", help="host:port for JSON-RPC")
    p.add_argument("--rpclisten-wrpc", default=None, help="host:port for the WebSocket JSON wRPC server (omit to disable)")
    p.add_argument(
        "--network", default="simnet", choices=["simnet", "mainnet", "testnet", "devnet"],
        help="network preset (real genesis for mainnet/testnet/devnet; simnet uses the fast test params)",
    )
    p.add_argument("--bps", type=int, default=2, help="simnet blocks per second")
    p.add_argument("--utxoindex", action=argparse.BooleanOptionalAction, default=True, help="maintain the UTXO index")
    p.add_argument(
        "--seed", type=int, default=None,
        help="deterministic seed for mempool template-selection sampling "
        "(byte-reproducible template choice under congestion; default: fixed internal seed)",
    )
    p.add_argument(
        "--template-debounce-ms", type=float, default=250.0,
        help="serve a stale-but-mineable cached template for up to this long "
        "after tx churn, so a tx flood costs one rebuild per window instead "
        "of one per transaction (0 = rebuild on next request)",
    )
    p.add_argument(
        "--fanout-queue", type=int, default=1024,
        help="per-subscriber bounded notification queue length (serving tier backpressure)",
    )
    p.add_argument(
        "--fanout-policy", default="drop-oldest", choices=["drop-oldest", "disconnect"],
        help="subscriber queue overflow policy: evict the oldest event, or tear the connection down",
    )
    p.add_argument("--address-prefix", default=None, help="bech32 prefix (defaults per network)")
    p.add_argument(
        "--persist",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="crash-safe consensus persistence under <appdir>/consensus.db (restart resumes)",
    )
    p.add_argument("--listen", default=None, help="host:port for the P2P wire (omit to disable inbound P2P)")
    p.add_argument(
        "--p2p-proto",
        action="store_true",
        help="speak the reference-compatible protobuf/gRPC P2P wire instead of the custom frame codec "
        "(both ends of a connection must use the same wire)",
    )
    p.add_argument("--upnp", action="store_true", help="map the P2P listen port on the internet gateway via UPnP")
    p.add_argument("--stratum", default=None, help="host:port for the stratum bridge (omit to disable)")
    p.add_argument("--stratum-pay-address", default=None, help="address stratum block templates pay to")
    p.add_argument(
        "--enable-unsynced-mining", action=argparse.BooleanOptionalAction, default=None,
        help="serve block templates while unsynced (defaults on for simnet, off otherwise; args.rs enable_unsynced_mining)",
    )
    p.add_argument("--connect", action="append", default=[], help="peer host:port to dial (repeatable); IBD runs on connect")
    p.add_argument("--dnsseed", action="append", default=[], help="seed hostname[:port] resolved into the address book (repeatable)")
    def _ram_scale(v: str) -> float:
        import math

        x = float(v)
        # args.rs bounds the flag at parse time; 0/negative/inf/nan would
        # silently floor every cache or crash the policy scaler
        if not math.isfinite(x) or not (0.1 <= x <= 10.0):
            raise argparse.ArgumentTypeError("--ram-scale must be a finite value in [0.1, 10]")
        return x

    p.add_argument("--ram-scale", type=_ram_scale, default=1.0,
                   help="scale all store cache budgets, 0.1-10 (cache_policy_builder.rs --ram-scale)")
    p.add_argument(
        "--mesh", default=None, metavar="N",
        help="shard batch signature verify + muhash over N devices via shard_map "
        "(default 1 = single device; 'auto' = every visible device; "
        "CPU testing: XLA_FLAGS=--xla_force_host_platform_device_count=8)",
    )
    p.add_argument(
        "--coalesce", default=None, metavar="N",
        help="coalesce signature verify jobs across blocks into super-batches of "
        "up to N jobs before device dispatch (default off; 'auto' = 1024; "
        "flush age via KASPA_TPU_COALESCE_AGE_MS)",
    )
    p.add_argument(
        "--fabric", nargs="+", default=None, metavar=("MODE", "ADDR"),
        help="verify fabric: 'serve [HOST:PORT]' runs a verifyd slice server "
        "inside this node (default 127.0.0.1:18500, port 0 = ephemeral); "
        "'connect ADDR[,ADDR...]' routes batch signature verification to "
        "remote verifyd slices — least-loaded routing, per-slice breakers, "
        "bit-identical host degraded lane when every slice is down",
    )
    p.add_argument(
        "--serving-pool", type=int,
        default=int(os.environ.get("KASPA_TPU_SERVING_POOL", "0")),
        metavar="N",
        help="drain serving-tier subscribers with a shared crew of N sender "
        "threads instead of one thread per subscriber (0 = per-subscriber "
        "threads, the historical shape; the 50k-subscriber load harness "
        "runs pooled)",
    )
    p.add_argument(
        "--fanout-shards", type=int,
        default=int(os.environ.get("KASPA_TPU_FANOUT_SHARDS", "1")),
        metavar="N",
        help="partition the serving fanout across N shard workers with a "
        "scope-pushdown inverted index (subscribers hash-partitioned by "
        "connection id; 1 = the single-fanout broadcaster, bit-identical "
        "delivered streams either way; with --serving-pool the crew splits "
        "into per-shard pools)",
    )
    p.add_argument(
        "--flight", action=argparse.BooleanOptionalAction, default=False,
        help="per-block flight recorder: cross-thread span trees for every "
        "validated block in a bounded ring, served over getTraces and dumped "
        "to <appdir>/flight-*.json on demand, crash, or breaker-open "
        "(tools/trace_report.py --perfetto renders the dump)",
    )
    # consensus-parameter overrides (kaspad exposes these for testnets;
    # primarily for pruning/IBD integration tests at small scale)
    p.add_argument("--override-pruning-depth", type=int, default=None)
    p.add_argument("--override-finality-depth", type=int, default=None)
    p.add_argument("--override-merge-depth", type=int, default=None)
    p.add_argument("--override-proof-m", type=int, default=None)
    p.add_argument("--override-window-scale", type=int, default=None,
                   help="shrink difficulty/median windows to this sampled size")
    return p.parse_args(argv, namespace=DaemonArgs())


def _apply_param_overrides(params: Params, args: DaemonArgs) -> Params:
    if getattr(args, "override_pruning_depth", None):
        params.pruning_depth = args.override_pruning_depth
    if getattr(args, "override_finality_depth", None):
        params.finality_depth = args.override_finality_depth
    if getattr(args, "override_merge_depth", None):
        params.merge_depth = args.override_merge_depth
    if getattr(args, "override_proof_m", None):
        params.pruning_proof_m = args.override_proof_m
    ws = getattr(args, "override_window_scale", None)
    if ws:
        params.difficulty_window_size = ws
        params.min_difficulty_window_size = min(5, ws)
        params.difficulty_sample_rate = 2
        params.past_median_time_window_size = ws
        params.past_median_time_sample_rate = 2
    return params


def _json_notification_line(n) -> bytes:
    """Serving-tier JSON encoder: one Notification -> one wire line.  Runs
    on the subscriber's sender thread, never on the consensus thread."""
    return (
        json.dumps({"notification": {"event": n.event_type, "data": _serialize_notification(n)}}) + "\n"
    ).encode()


def _serialize_notification(n) -> dict:
    """Wire shapes for streamed notifications (rpc/grpc/server's
    notification message bodies, JSON-ified)."""
    if n.event_type == "block-added":
        blk = n.data["block"]
        return {
            "hash": blk.hash.hex(),
            "daa_score": blk.header.daa_score,
            "blue_score": blk.header.blue_score,
            "timestamp": blk.header.timestamp,
            "tx_count": len(blk.transactions),
        }
    if n.event_type == "utxos-changed":
        def pairs(key):
            return [
                {
                    "outpoint": {"transaction_id": op.transaction_id.hex(), "index": op.index},
                    "utxo_entry": {
                        "amount": e.amount,
                        "block_daa_score": e.block_daa_score,
                        "is_coinbase": e.is_coinbase,
                        "script_public_key": {
                            "version": e.script_public_key.version,
                            "script": e.script_public_key.script.hex(),
                        },
                    },
                }
                for op, e in n.data.get(key, [])
            ]

        return {"added": pairs("added"), "removed": pairs("removed")}
    if n.event_type == "new-block-template":
        return {}
    if n.event_type == "virtual-chain-changed":
        return dict(n.data)  # already JSON-shaped (hex lists + txid map)
    # score changes and the rest carry plain JSON-able payloads
    return {k: v for k, v in n.data.items() if isinstance(v, (int, str, bool, float, list))}


class ConnectionPump:
    """Per-connection outbound pump shared by every RPC transport (line-
    JSON and WebSocket): a bounded queue drained by a dedicated writer
    thread (notify/src/broadcaster.rs role) so a slow consumer can never
    stall the consensus thread publishing an event — overflow drops, never
    blocks — plus the subscription-listener lifecycle."""

    def __init__(self, daemon: "Daemon", wfile, name: str, encoding: str = "line-json"):
        import queue as _queue

        self.daemon = daemon
        self.outq: _queue.Queue = _queue.Queue(maxsize=4096)
        self.stop = threading.Event()
        self.subscriber_ref = [None]  # one serving Subscriber per connection
        self.encoding = encoding
        self._wfile = wfile
        self._queue_mod = _queue
        self._writer = threading.Thread(target=self._writer_loop, daemon=True, name=name)
        self._writer.start()

    def _writer_loop(self):
        # drain until the sentinel: queued responses still flush after
        # stop is set (half-close clients must get their last reply);
        # a dead socket or stop+empty ends the thread
        while True:
            try:
                item = self.outq.get(timeout=0.5)
            except self._queue_mod.Empty:
                if self.stop.is_set():
                    return
                continue
            if item is None:
                return
            if callable(item):
                # deferred encoding: expensive serialization (e.g. Borsh
                # full-block notifications) runs on this writer thread, not
                # on the consensus thread that published the event
                try:
                    item = item()
                except Exception:  # noqa: BLE001 - encoding failure drops the frame
                    from kaspa_tpu.core.log import get_logger

                    get_logger("rpc.pump").exception("deferred notification encoding failed")
                    continue
            try:
                self._wfile.write(item)
                self._wfile.flush()
            except (OSError, ValueError):  # ValueError: write on a closed file object
                self.stop.set()
                return

    def send(self, data: bytes) -> None:
        self.outq.put(data)

    def handle_request(self, payload: bytes, notification_sink=None) -> bytes:
        """Dispatch one JSON request; returns the encoded response line.
        ``notification_sink``: queue-like receiving notification lines
        (defaults to the raw outq — the line-JSON transport)."""
        req_id = None
        _RPC_BY_ENCODING.inc(self.encoding)
        try:
            req = json.loads(payload)
            req_id = req.get("id")
            method = req.get("method", "")
            params = req.get("params", {})
            if method in ("subscribe", "unsubscribe"):
                result = self.daemon.handle_subscription(
                    method, params, notification_sink or self.outq, self.subscriber_ref, self.stop
                )
            else:
                result = self.daemon.dispatch(method, params)
            resp = {"id": req_id, "result": result}
        except Exception as e:  # noqa: BLE001 - wire boundary
            resp = {"id": req_id, "error": str(e)}
            # stable machine-readable rejection code (RpcError.code):
            # clients branch on tx-orphan/tx-duplicate/... without parsing
            code = getattr(e, "code", None)
            if code:
                resp["error_code"] = code
            # node-overloaded brownout sheds carry a resubmission hint
            retry_ms = getattr(e, "retry_after_ms", None)
            if retry_ms:
                resp["retryAfterMs"] = int(retry_ms)
        return (json.dumps(resp) + "\n").encode()

    def close(self) -> None:
        sub = self.subscriber_ref[0]
        if sub is not None:
            self.subscriber_ref[0] = None
            with self.daemon._dispatch_lock:
                self.daemon.broadcaster.unregister(sub)
            sub.close()  # join the sender thread outside the lock
        self.stop.set()
        try:
            self.outq.put_nowait(None)
        except self._queue_mod.Full:
            pass  # writer exits via stop+empty / OSError


class _RpcHandler(socketserver.StreamRequestHandler):
    """One connection: request/response lines plus, after a `subscribe`,
    interleaved `{"notification": ...}` lines over the shared pump."""

    def handle(self):
        daemon: Daemon = self.server.daemon  # type: ignore[attr-defined]
        pump = ConnectionPump(daemon, self.wfile, "rpc-notify-writer")
        try:
            for line in self.rfile:
                line = line.strip()
                if not line:
                    continue
                pump.send(pump.handle_request(line))
        finally:
            pump.close()


DB_VERSION = 1
# version -> upgrade fn(engine) bringing a DB from `version` to `version+1`
# (daemon.rs:441-522 upgrade machinery; populated as formats evolve)
DB_UPGRADES: dict = {}

_NETWORK_PREFIX = {"simnet": "kaspasim", "mainnet": "kaspa", "testnet": "kaspatest", "devnet": "kaspadev"}


def _network_params_for(args: DaemonArgs) -> Params:
    if args.network == "simnet":
        return simnet_params(bps=args.bps)
    from kaspa_tpu.consensus import networks

    return {
        "mainnet": networks.mainnet_params,
        "testnet": networks.testnet_params,
        "devnet": networks.devnet_params,
    }[args.network]()


class Daemon:
    """create_core_with_runtime equivalent: wire every service together."""

    def __init__(self, args: DaemonArgs, params: Params | None = None):
        self.args = args
        os.makedirs(args.appdir, exist_ok=True)
        if getattr(args, "address_prefix", None) is None:
            args.address_prefix = _NETWORK_PREFIX.get(args.network, "kaspasim")
        self.params = _apply_param_overrides(
            params if params is not None else _network_params_for(args), args
        )
        from kaspa_tpu.ops import dispatch as verify_dispatch
        from kaspa_tpu.ops import mesh as mesh_dispatch

        # process-wide: every batch verify/muhash call in this daemon routes
        # through the mesh once configured (> 1)
        self.mesh_size = mesh_dispatch.configure(getattr(args, "mesh", None))
        # process-wide: verify jobs coalesce across blocks/callers into
        # super-batches once configured (> 0)
        self.coalesce_target = verify_dispatch.configure(getattr(args, "coalesce", None))
        fab = getattr(args, "fabric", None) or []
        self.fabric_mode = fab[0] if fab else None
        if self.fabric_mode not in (None, "serve", "connect"):
            raise SystemExit(f"--fabric mode must be serve|connect, got {self.fabric_mode!r}")
        if self.fabric_mode == "connect" and len(fab) < 2:
            raise SystemExit("--fabric connect requires ADDR[,ADDR...]")
        self._fabric_arg = fab[1] if len(fab) > 1 else None
        self.fabric_service = None
        self.fabric_addr = None
        if getattr(args, "flight", False):
            from kaspa_tpu.observability import flight

            # breaker-open and crash paths dump into the appdir unprompted;
            # getTraces serves the live ring
            flight.enable(dump_dir=args.appdir)
        self.db = None
        if getattr(args, "persist", False):
            from kaspa_tpu.storage.kv import KvStore

            # ACTIVE meta file points at the live db (staging swaps rotate it)
            active = "consensus.db"
            active_path = os.path.join(args.appdir, "ACTIVE")
            if os.path.exists(active_path):
                with open(active_path) as f:
                    name = f.read().strip()
                # a truncated pointer (crash mid-replace) must not silently
                # reset to genesis: only honor names whose db file exists
                if name and os.path.exists(os.path.join(args.appdir, name)):
                    active = name
            # retire staging leftovers from aborted swaps
            for fn in os.listdir(args.appdir):
                if fn.startswith("consensus-staging-") and fn != active:
                    try:
                        os.remove(os.path.join(args.appdir, fn))
                    except OSError:
                        pass
            self.db = KvStore(os.path.join(args.appdir, active))
            self._check_db_version(self.db)
        from kaspa_tpu.consensus.stores import CachePolicy

        self.cache_policy = CachePolicy().scaled(getattr(args, "ram_scale", 1.0))
        self.consensus = Consensus(self.params, db=self.db, cache_policy=self.cache_policy)
        self.node = Node(
            self.consensus,
            name="daemon",
            mempool_seed=getattr(args, "seed", None),
            template_debounce=getattr(args, "template_debounce_ms", 0.0) / 1000.0,
        )
        self.node.cmgr._factory = self._staging_factory
        self.node.cmgr.on_swap(self._on_consensus_swap)
        self.mining = self.node.mining
        import itertools

        self._fanout_queue = getattr(args, "fanout_queue", None) or 1024
        self._fanout_policy = getattr(args, "fanout_policy", None) or "drop-oldest"
        # shared sender crew (--serving-pool / KASPA_TPU_SERVING_POOL):
        # None keeps the historical thread-per-subscriber shape.  With
        # --fanout-shards > 1 the crew is owned per shard instead (the
        # ShardedBroadcaster builds one pool per shard from the same
        # worker budget), so no shared pool is created here.
        pool_workers = int(getattr(args, "serving_pool", 0) or 0)
        self._fanout_shards = max(1, int(getattr(args, "fanout_shards", 1) or 1))
        if pool_workers > 0 and self._fanout_shards <= 1:
            from kaspa_tpu.serving import SenderPool

            self.serving_pool = SenderPool(workers=pool_workers)
        else:
            self.serving_pool = None
        self._serving_pool_workers = pool_workers
        self._sub_seq = itertools.count(1)
        self.utxoindex = self._make_utxoindex(self.consensus) if args.utxoindex else None
        from kaspa_tpu.p2p.address_manager import AddressManager, ConnectionManager

        self.address_manager = AddressManager(seed=getattr(args, "seed", None))
        self.connection_manager = ConnectionManager(
            self.node, self.address_manager, tick_seconds=5.0, seed=getattr(args, "seed", None)
        )
        self.node.address_manager = self.address_manager
        self.rpc = RpcCoreService(
            self.consensus,
            self.mining,
            self.utxoindex,
            args.address_prefix,
            p2p_node=self.node,
            address_manager=self.address_manager,
            connection_manager=self.connection_manager,
            shutdown_fn=lambda: threading.Thread(target=self.stop, daemon=True).start(),
        )
        # serving tier: the async fanout stage between the rpc notifier and
        # every remote subscriber.  Bound to the notifier OBJECT, which
        # survives consensus staging swaps via rebind_parent, so the
        # broadcaster (and its wildcard listener id) lives daemon-long.
        # --fanout-shards N > 1 swaps in the subscriber-partitioned tier
        # behind the same surface (bit-identical delivered streams).
        from kaspa_tpu.serving.broadcaster import tune_gil_switch_interval

        tune_gil_switch_interval()
        if self._fanout_shards > 1:
            from kaspa_tpu.serving import ShardedBroadcaster

            per_shard = (
                max(1, -(-self._serving_pool_workers // self._fanout_shards))
                if self._serving_pool_workers > 0
                else 0
            )
            self.broadcaster = ShardedBroadcaster(
                self.rpc.notifier,
                shards=self._fanout_shards,
                pool_workers=per_shard,
            )
        else:
            from kaspa_tpu.serving import Broadcaster

            self.broadcaster = Broadcaster(self.rpc.notifier)
        # node-wide overload-control plane (resilience/overload.py): samples
        # pressure on its own ticker, engages brownout actions through the
        # subsystem seams.  The mining facade is rebuilt on consensus
        # staging swaps, so signals/actions reach it through a live proxy
        # instead of capturing the bootstrap instance.
        from kaspa_tpu.resilience.overload import build_controller

        daemon_self = self

        class _MiningProxy:
            @property
            def mempool(self):
                return daemon_self.node.mining.mempool

            def set_template_deferral(self, grace_s: float) -> None:
                daemon_self.node.mining.set_template_deferral(grace_s)

        self.overload = build_controller(
            mining=_MiningProxy(),
            tier=self.node.ingest,
            broadcaster=self.broadcaster,
            node=self.node,
        )
        from kaspa_tpu.mining import MiningRuleEngine

        allow_unsynced = getattr(args, "enable_unsynced_mining", None)
        if allow_unsynced is None:
            allow_unsynced = args.network == "simnet"
        self.rule_engine = MiningRuleEngine(
            lambda: self.consensus, self.params, lambda: bool(self.node.peers),
            allow_unsynced=allow_unsynced,
        )
        self.rpc.rule_engine = self.rule_engine
        # consensus/mempool objects are single-writer: RPC dispatch and P2P
        # reader threads all serialize through the node lock (the reference
        # takes consensus sessions; an RW split can come later)
        self._dispatch_lock = self.node.lock
        self._server: socketserver.ThreadingTCPServer | None = None
        self._thread: threading.Thread | None = None
        self.p2p_server = None
        self.p2p_wire = "proto" if getattr(args, "p2p_proto", False) else "custom"

        # service runtime (core/src/core.rs): ordered start, reverse-order
        # stop, periodic metrics sampling on the tick service
        from kaspa_tpu.core import Core, TickService
        from kaspa_tpu.core.log import get_logger
        from kaspa_tpu.core.service import CallbackService
        from kaspa_tpu.metrics.core import MetricsData, collect_snapshot
        from kaspa_tpu.metrics.perf_monitor import PerfMonitor

        self.log = get_logger("daemon")
        if self.mesh_size > 1:
            self.log.info("mesh dispatch enabled over %d devices", self.mesh_size)
        if self.coalesce_target:
            self.log.info("verify coalescing enabled, super-batch target %d", self.coalesce_target)
        self.core = Core()
        self.perf_monitor = PerfMonitor()
        self.metrics_data = MetricsData()
        self.tick = TickService()

        # prometheus text rendered on the metrics tick (not per scrape):
        # rendering walks the whole registry, so it rides the existing
        # 10s cadence and getMetricsPrometheus serves the cached page
        self.prom_text = ""

        def sample_metrics():
            with self._dispatch_lock:
                self.metrics_data.push(
                    collect_snapshot(self.consensus, self.mining, self.perf_monitor, p2p_node=self.node)
                )
                # piggyback cache hygiene on the metrics cadence: drops the
                # pruning-point SMT snapshot once stale (anchor moved or idle)
                self.node.prune_caches()
            from kaspa_tpu.observability import prom

            self.prom_text = prom.render()

        self.tick.register(10.0, sample_metrics)

        def sample_rule_engine():
            with self._dispatch_lock:
                self.rule_engine.sample()

        from kaspa_tpu.mining.rule_engine import SNAPSHOT_INTERVAL

        self.tick.register(float(SNAPSHOT_INTERVAL), sample_rule_engine)
        self.rpc.metrics_provider = lambda: self.metrics_data.last
        self.core.bind(self.tick)
        self.core.bind(CallbackService("rpc-server", on_start=self._start_rpc_service, on_stop=self._stop_rpc_service))
        self.core.bind(CallbackService("p2p-server", on_start=self._start_p2p_service, on_stop=self._stop_p2p_service))
        if self.fabric_mode:
            self.core.bind(
                CallbackService("fabric", on_start=self._start_fabric_service, on_stop=self._stop_fabric_service)
            )
        self.wrpc_server = None
        if getattr(args, "rpclisten_wrpc", None):
            self.core.bind(
                CallbackService("wrpc-server", on_start=self._start_wrpc_service, on_stop=self._stop_wrpc_service)
            )
        self.stratum_server = None
        if getattr(args, "stratum", None):
            self.core.bind(
                CallbackService("stratum", on_start=self._start_stratum_service, on_stop=self._stop_stratum_service)
            )

    def _check_db_version(self, db) -> None:
        """Stamp fresh DBs; refuse (or upgrade, when a hook exists) stale
        ones instead of silently misreading a foreign format
        (daemon.rs:441-522)."""
        key = b"MTdb_version"
        net_key = b"MTdb_network"
        raw = db.engine.get(key)
        if raw is None:
            if len(db.engine) > 0:
                raise SystemExit(
                    "consensus DB has no version stamp (pre-versioning format); "
                    "delete the datadir or run the DB tooling to migrate"
                )
            db.engine.put(key, str(DB_VERSION).encode())
            db.engine.put(net_key, self.params.name.encode())
            return
        stamped_net = (db.engine.get(net_key) or b"").decode()
        if stamped_net and stamped_net != self.params.name:
            raise SystemExit(
                f"consensus DB belongs to network {stamped_net!r}, not {self.params.name!r}; "
                "use a separate --appdir per network"
            )
        version = int(raw)
        while version < DB_VERSION:
            upgrade = DB_UPGRADES.get(version)
            if upgrade is None:
                raise SystemExit(
                    f"consensus DB version {version} is older than {DB_VERSION} "
                    "and no upgrade path exists; delete the datadir to resync"
                )
            upgrade(db.engine)
            version += 1
            db.engine.put(key, str(version).encode())
        if version > DB_VERSION:
            raise SystemExit(
                f"consensus DB version {version} is newer than this binary supports ({DB_VERSION})"
            )

    def _make_utxoindex(self, consensus) -> UtxoIndex:
        """Persistent (journaled KV under <appdir>/utxoindex.db) when the
        node persists; the in-memory index otherwise."""
        db_path = None
        if getattr(self.args, "persist", False):
            db_path = os.path.join(self.args.appdir, "utxoindex.db")
        return UtxoIndex(consensus, db_path=db_path)

    # --- serving-tier subscribers (one per connection, lazily created) ---

    def _subscriber_placement(self, name: str):
        """(pool, shard) a new subscriber must be built with: its shard's
        sender crew under --fanout-shards, the shared pool (or None)
        otherwise."""
        bc = self.broadcaster
        if bc is not None and hasattr(bc, "sender_pool_for"):
            return bc.sender_pool_for(name), bc.shard_of(name)
        return self.serving_pool, None

    def make_json_subscriber(self, sink, stop=None):
        from kaspa_tpu.serving import Subscriber

        name = f"json-{next(self._sub_seq)}"
        pool, shard = self._subscriber_placement(name)
        return Subscriber(
            name,
            _json_notification_line,
            sink,
            encoding="json",
            maxlen=self._fanout_queue,
            policy=self._fanout_policy,
            on_disconnect=stop.set if stop is not None else None,
            pool=pool,
            shard=shard,
        )

    def make_borsh_subscriber(self, sink, stop=None):
        from kaspa_tpu.rpc import borsh_codec
        from kaspa_tpu.serving import Subscriber

        prefix = self.args.address_prefix
        name = f"borsh-{next(self._sub_seq)}"
        pool, shard = self._subscriber_placement(name)
        return Subscriber(
            name,
            lambda n: borsh_codec.encode_notification(n, prefix),
            sink,
            encoding="borsh",
            maxlen=self._fanout_queue,
            policy=self._fanout_policy,
            on_disconnect=stop.set if stop is not None else None,
            pool=pool,
            shard=shard,
        )

    # --- staging consensus (proof IBD) ---

    def _staging_factory(self):
        db = None
        if getattr(self.args, "persist", False):
            import time as _time

            from kaspa_tpu.storage.kv import KvStore

            self._staging_db_name = f"consensus-staging-{int(_time.time() * 1000)}.db"
            db = KvStore(os.path.join(self.args.appdir, self._staging_db_name))
        return Consensus(self.params, db=db, cache_policy=self.cache_policy)

    def _on_consensus_swap(self, new_consensus) -> None:
        """Rebind every consensus-holding service after a staging commit
        (Node already rebuilt its MiningManager)."""
        old_db = self.db
        old_notifier = self.rpc.notifier
        self.consensus = new_consensus
        self.mining = self.node.mining
        if self.utxoindex is not None:
            # the persistent index owns <appdir>/utxoindex.db: close it
            # (listener + db handle) before the replacement reopens the path
            self.utxoindex.close()
        self.utxoindex = self._make_utxoindex(new_consensus) if self.args.utxoindex else None
        self.rpc = RpcCoreService(
            new_consensus,
            self.mining,
            self.utxoindex,
            self.args.address_prefix,
            p2p_node=self.node,
            address_manager=self.address_manager,
            connection_manager=self.connection_manager,
            shutdown_fn=self.rpc.shutdown_fn,
        )
        self.rpc.metrics_provider = lambda: self.metrics_data.last
        self.rpc.rule_engine = self.rule_engine
        # live wire subscriptions must survive the swap: keep the old
        # notifier object (listener ids intact) and re-chain it onto the
        # new consensus root
        old_notifier.rebind_parent(new_consensus.notification_root)
        self.rpc.notifier = old_notifier
        if new_consensus.storage.db is not None:
            # atomic pointer rotation: tmp + rename so a crash mid-write
            # cannot leave a truncated ACTIVE behind
            active_path = os.path.join(self.args.appdir, "ACTIVE")
            with open(active_path + ".tmp", "w") as f:
                f.write(self._staging_db_name)
                f.flush()
                os.fsync(f.fileno())
            os.replace(active_path + ".tmp", active_path)
            self.db = new_consensus.storage.db
        if old_db is not None and old_db is not self.db:
            old_db.close()

    # --- rpc wire dispatch ---

    _METHODS = {
        "getServerInfo": lambda rpc, p: {**rpc.get_server_info().__dict__, "coinbase_maturity": rpc.consensus.params.coinbase_maturity},
        "getBlockDagInfo": lambda rpc, p: rpc.get_block_dag_info(),
        "getBlock": lambda rpc, p: rpc.get_block(bytes.fromhex(p["hash"]), p.get("includeTransactions", True)),
        "getSinkBlueScore": lambda rpc, p: rpc.get_sink_blue_score(),
        "getVirtualChainFromBlock": lambda rpc, p: rpc.get_virtual_chain_from_block(bytes.fromhex(p["startHash"])),
        "getMempoolEntries": lambda rpc, p: rpc.get_mempool_entries(),
        "getUtxosByAddresses": lambda rpc, p: rpc.get_utxos_by_addresses(p["addresses"]),
        "getBalanceByAddress": lambda rpc, p: rpc.get_balance_by_address(p["address"]),
        "getCoinSupply": lambda rpc, p: rpc.get_coin_supply(),
        "getMetrics": lambda rpc, p: rpc.get_metrics(),
        "getMetricsPrometheus": lambda rpc, p: rpc.get_metrics_prometheus(),
        "getTraces": lambda rpc, p: rpc.get_traces(
            int(p.get("limit", 32)), bool(p.get("verbose", False))
        ),
        "ping": lambda rpc, p: rpc.ping(),
        "getCurrentNetwork": lambda rpc, p: rpc.get_current_network(),
        "getInfo": lambda rpc, p: rpc.get_info(),
        "getBlockCount": lambda rpc, p: rpc.get_block_count(),
        "getSyncStatus": lambda rpc, p: rpc.get_sync_status(),
        "getSystemInfo": lambda rpc, p: rpc.get_system_info(),
        "getSink": lambda rpc, p: rpc.get_sink().hex(),
        "getHeaders": lambda rpc, p: rpc.get_headers(
            bytes.fromhex(p["startHash"]), p.get("limit", 100), p.get("isAscending", True)
        ),
        "getCurrentBlockColor": lambda rpc, p: rpc.get_current_block_color(bytes.fromhex(p["hash"])),
        "getDaaScoreTimestampEstimate": lambda rpc, p: rpc.get_daa_score_timestamp_estimate(p["daaScores"]),
        "estimateNetworkHashesPerSecond": lambda rpc, p: rpc.estimate_network_hashes_per_second(
            p.get("windowSize", 1000),
            bytes.fromhex(p["startHash"]) if p.get("startHash") else None,
        ),
        "getBlockRewardInfo": lambda rpc, p: rpc.get_block_reward_info(
            bytes.fromhex(p["hash"]) if p.get("hash") else None
        ),
        "getFeeEstimate": lambda rpc, p: rpc.get_fee_estimate(),
        "getFeeEstimateExperimental": lambda rpc, p: rpc.get_fee_estimate_experimental(p.get("verbose", False)),
        "getBalancesByAddresses": lambda rpc, p: rpc.get_balances_by_addresses(p["addresses"]),
        "getMempoolEntriesByAddresses": lambda rpc, p: rpc.get_mempool_entries_by_addresses(p["addresses"]),
        "getConnections": lambda rpc, p: rpc.get_connections(),
        "getConnectedPeerInfo": lambda rpc, p: rpc.get_connected_peer_info(),
        "getPeerAddresses": lambda rpc, p: rpc.get_peer_addresses(),
        "addPeer": lambda rpc, p: rpc.add_peer(p["address"], p.get("isPermanent", False)),
        "ban": lambda rpc, p: rpc.ban(p["ip"]),
        "unban": lambda rpc, p: rpc.unban(p["ip"]),
        "getUtxoReturnAddress": lambda rpc, p: rpc.get_utxo_return_address(
            bytes.fromhex(p["txid"]), p.get("acceptingBlockDaaScore", 0)
        ),
    }

    def handle_subscription(self, method: str, params: dict, sink, subscriber_ref, stop) -> str:
        """subscribe/unsubscribe verbs for one connection.

        params: {"event": <EVENT_TYPES name>, "addresses": [bech32...]?}.
        The connection's serving Subscriber (bounded queue + sender thread)
        is created lazily on first subscribe and registered on the
        broadcaster; the UtxosChanged address scope is pushed down so
        filtering happens once per event at the fanout stage."""
        from kaspa_tpu.notify.notifier import EVENT_TYPES

        event = params.get("event")
        if event not in EVENT_TYPES:
            raise ValueError(f"unknown event type {event!r}")
        scripts = None
        addresses = params.get("addresses")
        if addresses:
            from kaspa_tpu.crypto.addresses import Address, pay_to_address_script

            scripts = {pay_to_address_script(Address.from_string(a)).script for a in addresses}
        with self._dispatch_lock:
            if subscriber_ref[0] is None:
                subscriber_ref[0] = self.broadcaster.register(self.make_json_subscriber(sink, stop))
            if method == "subscribe":
                self.broadcaster.subscribe(subscriber_ref[0], event, scripts)
            else:
                self.broadcaster.unsubscribe(subscriber_ref[0], event)
        return "ok"

    def dispatch(self, method: str, params: dict):
        if method == "submitTransaction":
            # deliberately NOT under the dispatch lock: admission rides the
            # batched ingest tier, whose waves take the node lock internally
            # — concurrent submitters therefore queue up and coalesce into
            # one verify wave instead of serializing one-by-one here
            from kaspa_tpu.wallet.__main__ import wire_to_tx

            tx = wire_to_tx(params["tx"])
            txid = self.rpc.submit_transaction(tx)
            return txid.hex()
        with self._dispatch_lock.locked_for(method):
            # graftlint: allow(blocking-under-lock) -- RPC mutation path serializes consensus work by design; device round trips run under the dispatch lock deliberately
            return self._dispatch(method, params)

    def _dispatch(self, method: str, params: dict):
        if method == "getBlockTemplate":
            block = self.rpc.get_block_template(params["payAddress"], bytes.fromhex(params.get("extraData", "")))
            return {"block_hash": block.hash.hex(), "transactions": len(block.transactions)}
        if method == "submitBlockByTemplateHash":
            # in-process miner convenience: submit the cached template
            cached = self.mining.template_cache.get()
            if cached is None or cached.hash.hex() != params["hash"]:
                raise ValueError("template not cached")
            status = self.node.submit_block(cached)  # insert + unorphan + relay
            return {"status": status}
        fn = self._METHODS.get(method)
        if fn is None:
            raise ValueError(f"unknown method {method}")
        return fn(self.rpc, params)

    # --- lifecycle (core/src/core.rs run/shutdown shape) ---

    def _start_rpc_service(self, _core) -> list:
        host, port = self.args.rpclisten.rsplit(":", 1)
        srv = socketserver.ThreadingTCPServer((host, int(port)), _RpcHandler, bind_and_activate=False)
        srv.allow_reuse_address = True
        srv.daemon_threads = True
        srv.server_bind()
        srv.server_activate()
        srv.daemon = self  # type: ignore[attr-defined]
        self._server = srv
        self._thread = threading.Thread(target=srv.serve_forever, daemon=True)
        self._thread.start()
        self._rpc_addr = f"{host}:{srv.server_address[1]}"
        self.log.info("RPC listening on %s", self._rpc_addr)
        return [self._thread]

    def _stop_rpc_service(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None

    def _start_p2p_service(self, _core) -> list:
        if getattr(self.args, "listen", None):
            from kaspa_tpu.p2p.transport import P2PServer, get_codec

            lhost, lport = self.args.listen.rsplit(":", 1)
            self.p2p_server = P2PServer(
                self.node, lhost, int(lport), address_manager=self.address_manager, codec=get_codec(self.p2p_wire)
            )
            self.p2p_server.start()
            self.node.listen_port = int(self.p2p_server.address.rsplit(":", 1)[1])
            self.log.info("P2P listening on %s (%s wire)", self.p2p_server.address, self.p2p_wire)
            if getattr(self.args, "upnp", False):
                self._start_upnp(self.node.listen_port)
        self.connection_manager.start()
        return []

    def _start_upnp(self, listen_port: int) -> None:
        """Map the P2P listen port on the internet gateway and keep the
        lease alive (addressmanager configure_port_mapping + the
        port_mapping_extender service).  Discovery runs off-thread and the
        whole feature fails soft — no cooperative gateway, no mapping."""

        def run():
            import http.client as _http_client

            from kaspa_tpu.p2p.upnp import UpnpError, configure_port_mapping

            try:
                external_ip, extender = configure_port_mapping(listen_port)
            except (UpnpError, OSError, _http_client.HTTPException) as e:
                self.log.info("UPnP unavailable: %s", e)
                return
            stale = None
            with self._upnp_lock:
                if self._upnp_stopped:
                    # the daemon shut down while discovery was in flight:
                    # tear the fresh mapping down instead of leaking it
                    stale = extender
                else:
                    self.upnp_extender = extender
            if stale is not None:
                # outside the lock: stop() joins the renewal thread, and a
                # join under daemon.upnp would stall the shutdown path
                stale.stop()
                return
            if self.address_manager is not None:
                from kaspa_tpu.p2p.address_manager import NetAddress

                # gossiped to peers, excluded from our own outbound dials
                self.address_manager.add_local_address(NetAddress(external_ip, listen_port))
            self.log.info("publicly routable address %s:%d registered", external_ip, listen_port)

        self._upnp_lock = ranked_lock("daemon.upnp", reentrant=False)
        self._upnp_stopped = False
        threading.Thread(target=run, daemon=True, name="upnp-setup").start()

    def _stop_p2p_service(self) -> None:
        self.connection_manager.stop()
        if getattr(self, "_upnp_lock", None) is not None:
            with self._upnp_lock:
                self._upnp_stopped = True
                extender = getattr(self, "upnp_extender", None)
                self.upnp_extender = None
            if extender is not None:
                extender.stop()
        if self.p2p_server is not None:
            self.p2p_server.stop()
            self.p2p_server = None
        for peer in list(self.node.peers):
            if hasattr(peer, "close"):
                peer.close()

    def _start_fabric_service(self, _core) -> list:
        if self.fabric_mode == "serve":
            from kaspa_tpu.fabric.service import VerifyService

            self.fabric_service = VerifyService(self._fabric_arg or "127.0.0.1:18500")
            host, port = self.fabric_service.start()
            self.fabric_addr = f"{host}:{port}"
            self.log.info(
                "verify fabric serving on %s (%d slices)", self.fabric_addr, self.fabric_service.slices
            )
        else:
            from kaspa_tpu.fabric import balancer as fabric_balancer

            bal = fabric_balancer.configure(self._fabric_arg)
            live = sum(1 for s in bal.stats()["slices"] if s["alive"])
            self.log.info("verify fabric balancer over %s (%d live slices)", self._fabric_arg, live)
        return []

    def _stop_fabric_service(self) -> None:
        # only the serve side stops here (reverse bind order): the connect-
        # side balancer must outlive the pipeline drain in stop(), so its
        # tickets keep resolving until validation work is idle
        if self.fabric_service is not None:
            self.fabric_service.stop()
            self.fabric_service = None

    def _start_wrpc_service(self, _core) -> list:
        from kaspa_tpu.rpc.wrpc import WrpcServer

        host, port = self.args.rpclisten_wrpc.rsplit(":", 1)
        self.wrpc_server = WrpcServer(self, host, int(port))
        self.wrpc_server.start()
        return []

    def _stop_wrpc_service(self) -> None:
        if self.wrpc_server is not None:
            self.wrpc_server.stop()
            self.wrpc_server = None

    def _start_stratum_service(self, _core) -> list:
        from kaspa_tpu.bridge.stratum import StratumBridge, StratumServer
        from kaspa_tpu.consensus.processes.coinbase import MinerData
        from kaspa_tpu.crypto.addresses import Address, pay_to_address_script

        pay = getattr(self.args, "stratum_pay_address", None)
        if not pay:
            raise ValueError("--stratum requires --stratum-pay-address")
        spk = pay_to_address_script(Address.from_string(pay))
        miner_data = MinerData(spk, b"")

        from kaspa_tpu.consensus.api import ConsensusApi

        def template_source():
            with self._dispatch_lock:
                # same sync gate as the RPC path (rule_engine.rs should_mine):
                # stratum miners must not burn hashrate on a stale tip.
                # self.consensus re-resolves per call: staging swaps rebind it
                sink_ts = ConsensusApi(self.consensus).get_sink_timestamp()
                if not self.rule_engine.should_mine(sink_ts):
                    raise ValueError("node is not synced: block templates unavailable")
                # graftlint: allow(blocking-under-lock) -- template build runs consensus (and its device waves) under the dispatch lock by design, same gate as the RPC path
                return self.mining.get_block_template(miner_data)

        def submit(block):
            with self._dispatch_lock.locked_for("block"):
                # graftlint: allow(blocking-under-lock) -- stratum submit serializes with the RPC mutation path; insert+unorphan device waits are the locked section's job
                return self.node.submit_block(block)

        bridge = StratumBridge(template_source, submit)
        host, port = self.args.stratum.rsplit(":", 1)
        self.stratum_server = StratumServer(bridge, host, int(port))
        self.stratum_server.start()
        self.log.info("stratum bridge on %s", self.stratum_server.address)
        return []

    def _stop_stratum_service(self) -> None:
        if self.stratum_server is not None:
            self.stratum_server.stop()
            self.stratum_server = None

    def start(self) -> str:
        # device supervision up before any traffic: managed breaker, canary
        # prober, and (on warm non-CPU backends) the background pretrace of
        # manifest shapes — off the commit lock, the restart-warmth path
        from kaspa_tpu.resilience import supervisor

        supervisor.install()
        self._supervised = True
        self.overload.start(interval_s=0.5)
        self.core.start()
        seeds = getattr(self.args, "dnsseed", []) or []
        if seeds:
            # resolver latency must not block startup (a dead seed hangs
            # getaddrinfo for its full timeout, serially per seed)
            def _seed():
                n = self.address_manager.dns_seed(seeds, default_port=16111)
                self.log.info("dns seeding added %d addresses from %d seeds", n, len(seeds))

            threading.Thread(target=_seed, daemon=True, name="dnsseed").start()
        for peer_addr in getattr(self.args, "connect", []) or []:
            self.connect_peer(peer_addr)
        return self._rpc_addr

    def connect_peer(self, address: str):
        """Dial a peer over the wire and catch up from it (IBD).

        The dial retries with deterministic exponential backoff
        (KASPA_TPU_CONNECT_RETRIES attempts, default 5): a --connect seed
        peer that comes up moments after us — the normal case when a swarm
        starts N nodes in one burst, and common enough on real restarts —
        should not cost the only startup dial we'd otherwise make."""
        import time as _time

        from kaspa_tpu.p2p.address_manager import NetAddress
        from kaspa_tpu.p2p.transport import connect_outbound, get_codec

        attempts = max(1, int(os.environ.get("KASPA_TPU_CONNECT_RETRIES", "5")))
        peer = None
        for attempt in range(attempts):
            try:
                peer = connect_outbound(self.node, address, codec=get_codec(self.p2p_wire))
                break
            except (OSError, ConnectionError):
                if attempt == attempts - 1:
                    raise
                # deterministic (no jitter): 0.25s, 0.5s, 1s, 2s, capped 4s
                _time.sleep(min(0.25 * (2.0 ** attempt), 4.0))
        # register the RESOLVED address (getpeername) so the connection
        # manager's connected-set comparison matches and never re-dials
        na = getattr(peer, "peer_address", None)
        if na is not None:
            self.address_manager.add_address(na)
            self.address_manager.mark_connection_success(na)
        # connect-path IBD kick: ibd_from only sends the chain-info request
        # (no consensus access), so it needs no lock — the response flows
        # run under the reader thread's node-lock acquisition
        self.node.ibd_from(peer)
        return peer

    def stop(self) -> None:
        # overload ticker first: brownout actions must not re-engage while
        # the subsystems they reach into are being torn down below
        if getattr(self, "overload", None) is not None:
            self.overload.shutdown()
        self.core.shutdown()  # reverse bind order: p2p, rpc, tick (blocks
        # until services are down, even when another thread began the stop)
        # drain asynchronous validation work before the db handle goes away:
        # blocks in flight inside the pipeline and script jobs on the VM
        # fallback lane both write through consensus stores — killing them
        # mid-commit is exactly the torn state the journal exists to absorb,
        # so an ORDERLY stop should not manufacture one
        try:
            self.node.pipeline.wait_for_idle(timeout=30.0)
        except Exception:  # noqa: BLE001 - drain is best-effort on the way down
            pass
        self.node._drop_ibd_pipeline()
        self.node.pipeline.shutdown()
        from kaspa_tpu.ops import dispatch as verify_dispatch
        from kaspa_tpu.txscript import batch as script_batch

        script_batch.drain_fallback_pool(timeout=10.0)
        if self.fabric_mode == "connect":
            # the balancer drains (remote + degraded lanes) before the
            # generic dispatch shutdown below closes whatever engine remains
            from kaspa_tpu.fabric import balancer as fabric_balancer

            fabric_balancer.shutdown(timeout=10.0)
        # same barrier for the async coalescing queue: flush staged verify
        # chunks and block until every callback has resolved — tickets
        # resolving after the db handle closes would write sig-cache entries
        # for a consensus object that is already torn down.  shutdown()
        # (vs drain) bounds the wait: if the dispatcher thread is wedged
        # inside a hung device call, remaining tickets fail with
        # DispatchAbandoned instead of blocking process exit
        verify_dispatch.shutdown(timeout=10.0)
        from kaspa_tpu.resilience import supervisor

        with self._dispatch_lock:
            # stop() may race itself; release the supervision ref once
            was_supervised, self._supervised = getattr(self, "_supervised", False), False
        if was_supervised:
            supervisor.shutdown()
        # serving tier down before the stores: the broadcaster detaches from
        # the notifier (no new fanout), then the index unhooks its listener
        # and closes its own db.  Snapshot-and-null under the lock, close
        # outside it: broadcaster.close() joins the fanout thread, and a
        # racing stop() sees None instead of double-closing
        with self._dispatch_lock:
            bc = getattr(self, "broadcaster", None)
            self.broadcaster = None
            pool, self.serving_pool = getattr(self, "serving_pool", None), None
            ui, self.utxoindex = self.utxoindex, None
        if bc is not None:
            bc.close()
        if pool is not None:
            pool.close()
        if ui is not None:
            ui.close()
        # quiesce dispatch before closing the native handle: an in-flight
        # handler finishes under the lock; later ones see db == None and
        # stage() no-ops (server is already down, nothing new arrives).
        # db re-checked under the lock: stop() may race itself (shutdown
        # RPC thread vs main's wait_for_shutdown path).
        with self._dispatch_lock:
            if self.db is not None:
                # orderly shutdown: snapshot reachability for the fast
                # restart path (crashes skip this and rebuild instead);
                # its flush also commits any other pending ops
                self.consensus.save_reachability_snapshot()
                self.consensus.storage.db = None
                self.db.close()
                self.db = None


class NotificationClient:
    """Persistent RPC connection with notification streaming (the
    rpc/grpc/client + notify subscriber pair).  ``call`` issues regular
    requests on the same socket; streamed ``{"notification": ...}`` lines
    land in ``self.notifications`` (a Queue) as (event, data) tuples."""

    def __init__(self, addr: str, timeout: float = 30.0):
        import queue as _queue

        host, port = addr.rsplit(":", 1)
        self._sock = socket.create_connection((host, int(port)), timeout=timeout)
        self._rfile = self._sock.makefile("rb")
        self._timeout = timeout
        # graftlint: allow(unbounded-queue) -- client-side helper; one request in flight, reader thread drains
        self._responses: _queue.Queue = _queue.Queue()
        self.notifications: _queue.Queue = _queue.Queue()  # graftlint: allow(unbounded-queue) -- client-side helper for tests/CLI; consumer polls per scripted step
        self._next_id = 0
        self._closed = False
        self._reader = threading.Thread(target=self._read_loop, daemon=True, name="rpc-notify-reader")
        self._reader.start()

    def _read_loop(self):
        try:
            for line in self._rfile:
                msg = json.loads(line)
                if "notification" in msg:
                    n = msg["notification"]
                    self.notifications.put((n["event"], n["data"]))
                else:
                    self._responses.put(msg)
        except (OSError, ValueError):
            pass
        self._responses.put(None)  # connection closed

    def call(self, method: str, params: dict | None = None):
        import queue as _queue
        import time as _time

        self._next_id += 1
        req_id = self._next_id
        self._sock.sendall(
            (json.dumps({"id": req_id, "method": method, "params": params or {}}) + "\n").encode()
        )
        deadline = _time.monotonic() + self._timeout
        while True:
            remaining = deadline - _time.monotonic()
            if remaining <= 0:
                raise TimeoutError(f"rpc call {method} timed out after {self._timeout}s")
            try:
                resp = self._responses.get(timeout=remaining)
            except _queue.Empty:
                raise TimeoutError(f"rpc call {method} timed out after {self._timeout}s") from None
            if resp is None:
                raise ConnectionError("connection closed")
            if resp.get("id") != req_id:
                continue  # stale response from an earlier timed-out call
            if "error" in resp:
                raise RuntimeError(resp["error"])
            return resp["result"]

    def subscribe(self, event: str, addresses: list[str] | None = None):
        params = {"event": event}
        if addresses:
            params["addresses"] = addresses
        return self.call("subscribe", params)

    def unsubscribe(self, event: str, addresses: list[str] | None = None):
        params = {"event": event}
        if addresses:
            params["addresses"] = addresses
        return self.call("unsubscribe", params)

    def next_notification(self, timeout: float = 30.0):
        return self.notifications.get(timeout=timeout)

    def close(self):
        self._closed = True
        try:
            self._sock.close()
        except OSError:
            pass


def rpc_call(addr: str, method: str, params: dict | None = None, timeout: float = 30.0):
    """Minimal line-JSON-RPC client (rpc/grpc/client equivalent)."""
    host, port = addr.rsplit(":", 1)
    with socket.create_connection((host, int(port)), timeout=timeout) as s:
        s.sendall((json.dumps({"id": 1, "method": method, "params": params or {}}) + "\n").encode())
        buf = b""
        while not buf.endswith(b"\n"):
            chunk = s.recv(65536)
            if not chunk:
                raise ConnectionError(f"connection closed mid-response ({len(buf)} bytes buffered)")
            buf += chunk
    resp = json.loads(buf)
    if "error" in resp:
        raise RuntimeError(resp["error"])
    return resp["result"]


def main(argv=None) -> None:
    from kaspa_tpu.core.log import init_logger

    args = parse_args(argv)
    os.makedirs(args.appdir, exist_ok=True)
    init_logger(log_file=os.path.join(args.appdir, "kaspad.log"))
    daemon = Daemon(args)
    daemon.core.install_signal_handlers()  # SIGINT/SIGTERM -> ordered stop
    addr = daemon.start()
    print(f"kaspa-tpu node listening on {addr} (network {daemon.params.name})")
    try:
        daemon.core.wait_for_shutdown()
        daemon.stop()
    except BaseException:
        # crash path: the flight ring is the black box — flush it beside the
        # log before the interpreter unwinds (no-op when --flight is off)
        if getattr(args, "flight", False):
            from kaspa_tpu.observability import flight

            try:
                flight.dump(reason="crash")
            except Exception:  # noqa: BLE001 - never mask the original crash
                pass
        raise


if __name__ == "__main__":
    main()
