"""simpa-equivalent CLI (reference: simpa/src/main.rs).

Builds a virtual-time multi-miner DAG with signed transactions, then
replays it into a fresh consensus and reports validation throughput:

    python -m kaspa_tpu.sim --bps 2 --blocks 100 --miners 4 --tpb 4

Mesh replay (sharded batch verify + muhash over N devices; CPU recipe):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python -m kaspa_tpu.sim --blocks 32 --mesh 8 --json
"""

import argparse
import json

from kaspa_tpu.utils import jax_setup

jax_setup.setup()

from kaspa_tpu.observability import flight, trace
from kaspa_tpu.ops import dispatch as coalesce
from kaspa_tpu.ops import mesh
from kaspa_tpu.sim.simulator import SimConfig, replay, replay_pipelined, simulate


def main() -> None:
    p = argparse.ArgumentParser(prog="kaspa-tpu-sim", description="DAG simulation + validation replay benchmark")
    p.add_argument("--bps", type=int, default=2, help="target blocks per second")
    p.add_argument("--delay", type=float, default=2.0, help="simulated propagation delay (seconds)")
    p.add_argument("--miners", type=int, default=4, help="number of miners")
    p.add_argument("--blocks", type=int, default=64, help="blocks to produce")
    p.add_argument("--tpb", type=int, default=8, help="transactions per block")
    p.add_argument("--seed", type=int, default=42)
    p.add_argument(
        "--mesh", default=None, metavar="N",
        help="shard the replay's batch verify + muhash over N devices ('auto' = all visible)",
    )
    p.add_argument(
        "--coalesce", default=None, metavar="N",
        help="route the replay's verify batches through the cross-block coalescing "
        "queue with super-batch target N ('auto' = 1024; "
        "default off — results are bit-identical either way)",
    )
    p.add_argument(
        "--fabric", default=None, metavar="ADDR[,ADDR...]",
        help="route the replay's verify batches to remote verifyd slices "
        "(`python -m kaspa_tpu.fabric.service`) through the cross-host "
        "balancer; results stay bit-identical (host degraded lane on slice "
        "loss) and the JSON report gains a 'fabric' stats block",
    )
    p.add_argument("--json", action="store_true", help="emit one JSON line")
    p.add_argument(
        "--pipeline", action="store_true",
        help="replay through the concurrent ConsensusPipeline (stage workers + "
        "virtual worker) instead of the serial loop",
    )
    p.add_argument(
        "--no-spec", action="store_true",
        help="disable the speculative chain-state precompute in --pipeline replays "
        "(bit-identity baseline; results must match speculation-on exactly)",
    )
    p.add_argument(
        "--trace", default=None, metavar="PATH",
        help="enable the per-block flight recorder during the replay and dump "
        "the completed-trace ring to PATH (tools/trace_report.py input)",
    )
    p.add_argument(
        "--notrace", action="store_true",
        help="disable span tracing entirely for the replay (overhead baseline)",
    )
    p.add_argument(
        "--hostile", action="store_true",
        help="hostile-load sustain run: multisig/P2SH fast-path-bypass script mix, "
        "attacker-fork deep reorg, out-of-order delivery; writes SUSTAIN.json",
    )
    p.add_argument(
        "--txflood", action="store_true",
        help="tx-flood sustain run: flood the batched ingest tier with clean spends, "
        "double-spend chains, RBF churn and orphan storms between paced block "
        "deliveries under the chaos schedule; adds the 'ingest' block to SUSTAIN.json "
        "(combine with --hostile for the fast-path-bypass script mix)",
    )
    p.add_argument(
        "--txflood-rates", default=None, metavar="JSON",
        help="override TxFloodConfig fields for --txflood, "
        "e.g. '{\"clean_per_block\": 12, \"rbf_chain\": 5}'",
    )
    p.add_argument(
        "--overload", action="store_true",
        help="with --txflood: ramp the flood rate (warm -> linear ramp -> hold at "
        "peak -> cooldown) against a live overload controller wired to the run's "
        "mining/ingest tier; gates on shed>0, SATURATED reached, cadence within "
        "1.5x of nominal, and recovery to NOMINAL; adds the 'overload' block to "
        "the sustain report",
    )
    p.add_argument(
        "--overload-config", default=None, metavar="JSON",
        help="override OverloadRampConfig fields for --overload, e.g. "
        "'{\"peak_scale\": 6, \"thresholds\": {\"mempool\": [15, 40, 120]}, "
        "\"expire_daa\": 6}'",
    )
    p.add_argument(
        "--no-pace", action="store_true",
        help="with --txflood: deliver blocks as fast as possible instead of the "
        "true --bps wall-clock cadence",
    )
    p.add_argument(
        "--faults", default="default", metavar="SPEC",
        help="fault schedule for --hostile: 'default', 'none', inline JSON, or @/path/to/schedule.json",
    )
    p.add_argument(
        "--sustain-out", default="SUSTAIN.json", metavar="PATH",
        help="where --hostile writes its report (default SUSTAIN.json)",
    )
    p.add_argument(
        "--wedge-drill", action="store_true",
        help="with --hostile: run the device-supervision wedge drill instead of the "
        "stock sustain schedule — inject dispatch hangs + a compile stall mid-replay "
        "and gate on bit-identity, requeue accounting, and canary recovery",
    )
    p.add_argument(
        "--swarm", type=int, default=None, metavar="N",
        help="swarm drill: N in-process nodes over the real P2P wire driven by a "
        "seeded scenario (partition/heal, deep attacker reorg, late-join IBD, "
        "relay-storm budget); writes SWARM.json and exits non-zero unless all "
        "nodes converge bit-identically to the fault-free replay (--blocks sets "
        "the base-chain length, --seed the schedule seed)",
    )
    p.add_argument(
        "--swarm-scenario", default=None, metavar="JSON|@PATH",
        help="override the stock swarm schedule: inline JSON or @/path/to/scenario.json "
        "(a list of {'op': mine|txs|partition|heal|converge|join, ...} steps)",
    )
    p.add_argument(
        "--swarm-out", default="SWARM.json", metavar="PATH",
        help="where --swarm writes its report (default SWARM.json)",
    )
    args = p.parse_args()

    if args.swarm is not None:
        _run_swarm(args)
        return

    mesh_size = mesh.configure(args.mesh)
    if args.overload and args.coalesce is None:
        # the dispatch_yield brownout action needs a live coalescing engine
        # to act on — overload runs default it on rather than silently
        # exercising a no-op action
        args.coalesce = "auto"
    coalesce_target = coalesce.configure(args.coalesce)
    fabric_bal = None
    if args.fabric:
        from kaspa_tpu.fabric import balancer as fabric_balancer

        fabric_bal = fabric_balancer.configure(args.fabric)
    cfg = SimConfig(
        bps=args.bps, delay=args.delay, num_miners=args.miners,
        num_blocks=args.blocks, txs_per_block=args.tpb, seed=args.seed,
        hostile=args.hostile,
    )
    if args.txflood:
        _run_txflood(cfg, args)
        return
    if args.hostile:
        if args.wedge_drill:
            _run_wedge(cfg, args)
        else:
            _run_hostile(cfg, args)
        return
    res = simulate(cfg)
    if args.notrace:
        trace.disable()
    if args.trace:
        flight.enable(ring=max(2 * args.blocks, 64))
        flight.reset()
    if args.pipeline:
        # traced replays attach the serving fanout so block traces cover
        # the full production thread topology (stage/virtual/dispatch/serving)
        elapsed, fresh = replay_pipelined(
            res, fanout=bool(args.trace), speculative=False if args.no_spec else None
        )
    else:
        elapsed, fresh = replay(res)
    sink = fresh.sink()
    out = {
        "blocks": len(res.blocks),
        "txs": res.total_txs,
        "build_seconds": round(res.build_seconds, 2),
        "replay_seconds": round(elapsed, 2),
        "replay_blocks_per_sec": round(len(res.blocks) / elapsed, 2),
        "bps_target": args.bps,
        "realtime_factor": round(len(res.blocks) / args.bps / elapsed, 2),
        "mesh": mesh_size,
        "coalesce": coalesce_target,
        # end-state fingerprints: identical across --mesh/--coalesce values
        # is the bit-identity acceptance check for the sharded dispatch
        "sink": sink.hex(),
        "utxo_commitment": fresh.multisets[sink].finalize().hex(),
        "pipeline": bool(args.pipeline),
        "tracing": not args.notrace,
    }
    if fabric_bal is not None:
        from kaspa_tpu.fabric import balancer as fabric_balancer

        fabric_bal.drain(timeout=30.0)
        out["fabric"] = fabric_bal.stats()
        fabric_balancer.shutdown(timeout=10.0)
    if args.pipeline:
        from kaspa_tpu.pipeline.speculative import SpeculativeVerifier

        out["speculative"] = SpeculativeVerifier.snapshot()
        out["speculative"]["enabled"] = not args.no_spec
    if args.trace:
        path = flight.dump(args.trace, reason="sim-replay")
        out["trace_path"] = path
        out["traces"] = len(flight.traces())
        flight.disable()
    if args.json:
        print(json.dumps(out))
    else:
        print(f"built {out['blocks']} blocks / {out['txs']} txs in {out['build_seconds']}s")
        print(
            f"replayed in {out['replay_seconds']}s = {out['replay_blocks_per_sec']} blocks/s "
            f"({out['realtime_factor']}x the {args.bps}-BPS real-time rate, mesh {mesh_size})"
        )


def _parse_schedule(spec: str):
    from kaspa_tpu.resilience.sustain import default_schedule

    if spec == "default":
        return default_schedule()
    if spec == "none":
        return {}
    if spec.startswith("@"):
        with open(spec[1:]) as f:
            return json.load(f)
    return json.loads(spec)


def _run_hostile(cfg, args) -> None:
    from kaspa_tpu.resilience.sustain import run_sustain

    report = run_sustain(cfg, schedule=_parse_schedule(args.faults), seed=args.seed, out=args.sustain_out)
    det, brk = report["deterministic"], report["breaker"]
    summary = {
        "blocks": det["blocks"],
        "matches_fault_free": det["matches_fault_free"],
        "fault_events": len(det["events"]),
        "breaker_trips": brk["trips"],
        "breaker_recoveries": brk["recoveries"],
        "degraded_dispatches": report["metrics"]["secp_degraded_dispatches"],
        "replay_seconds": report["metrics"]["replay_seconds"],
        "sink": det["fingerprints"]["sink"],
        "utxo_commitment": det["fingerprints"]["utxo_commitment"],
        "sustain_out": args.sustain_out,
    }
    if args.json:
        print(json.dumps(summary))
    else:
        print(
            f"sustain: {det['blocks']} blocks, {len(det['events'])} faults injected, "
            f"breaker trips={brk['trips']} recoveries={brk['recoveries']}, "
            f"matches_fault_free={det['matches_fault_free']} -> {args.sustain_out}"
        )
    if not det["matches_fault_free"]:
        raise SystemExit(2)


def _run_txflood(cfg, args) -> None:
    from kaspa_tpu.resilience.txflood import (
        OverloadRampConfig,
        TxFloodConfig,
        run_txflood_sustain,
    )

    flood = TxFloodConfig()
    if args.txflood_rates:
        for k, v in json.loads(args.txflood_rates).items():
            if not hasattr(flood, k):
                raise SystemExit(f"unknown txflood rate field: {k}")
            setattr(flood, k, v)
    ramp = None
    if args.overload:
        ramp = OverloadRampConfig()
        if args.overload_config:
            for k, v in json.loads(args.overload_config).items():
                if not hasattr(ramp, k):
                    raise SystemExit(f"unknown overload config field: {k}")
                setattr(ramp, k, v)
    report = run_txflood_sustain(
        cfg,
        flood_cfg=flood,
        schedule=_parse_schedule(args.faults),
        seed=args.seed,
        out=args.sustain_out,
        pace=not args.no_pace,
        overload=ramp,
    )
    det, ing = report["deterministic"], report["ingest"]
    summary = {
        "blocks": det["blocks"],
        "matches_fault_free": det["matches_fault_free"],
        "fault_events": len(det["events"]),
        "txs_submitted": ing["flood"]["submitted"],
        "tx_acceptance_rate": ing["tx_acceptance_rate"],
        "template_rebuilds": ing["template_rebuilds"],
        "template_rebuild_p50_ms": ing["template_rebuild_p50_ms"],
        "template_rebuild_p99_ms": ing["template_rebuild_p99_ms"],
        "peak_mempool_occupancy": ing["peak_mempool_occupancy"],
        "lost_tickets": ing["lost_tickets"],
        "waves": ing["waves"],
        "actual_bps": ing["actual_bps"],
        "sink": det["fingerprints"]["sink"],
        "sustain_out": args.sustain_out,
    }
    ov_ok = True
    if ramp is not None:
        ov = report["overload"]
        ratio = ov["cadence"]["saturated_over_nominal"]
        ov_ok = (
            ov["levels"]["max"] in ("SATURATED", "CRITICAL")
            and sum(ov["shed"].values()) > 0
            and ov["recovered"]
            and ratio is not None
            and ratio <= 1.5
        )
        summary.update(
            {
                "overload_max_level": ov["levels"]["max"],
                "overload_recovered": ov["recovered"],
                "overload_shed": sum(ov["shed"].values()),
                "overload_rejected": ov["overload_rejected"],
                "cadence_saturated_over_nominal": ratio,
                "overload_ok": ov_ok,
            }
        )
    if args.json:
        print(json.dumps(summary))
    else:
        print(
            f"txflood: {det['blocks']} blocks at {ing['actual_bps']} BPS "
            f"(target {ing['bps_target']}), {ing['flood']['submitted']} txs flooded, "
            f"clean acceptance {ing['tx_acceptance_rate']}, "
            f"rebuilds={ing['template_rebuilds']} p50={ing['template_rebuild_p50_ms']}ms "
            f"p99={ing['template_rebuild_p99_ms']}ms, "
            f"peak pool={ing['peak_mempool_occupancy']}, lost={ing['lost_tickets']}, "
            f"matches_fault_free={det['matches_fault_free']} -> {args.sustain_out}"
        )
        if ramp is not None:
            ov = report["overload"]
            print(
                f"overload: max={ov['levels']['max']} final={ov['levels']['final']} "
                f"shed={ov['shed']} "
                f"cadence sat/nom={ov['cadence']['saturated_over_nominal']} "
                f"recovered={ov['recovered']} ok={ov_ok}"
            )
    if not det["matches_fault_free"] or ing["lost_tickets"] != 0 or not ov_ok:
        raise SystemExit(2)


def _run_swarm(args) -> None:
    from kaspa_tpu.resilience.swarm import gates, run_swarm

    report = run_swarm(
        args.swarm,
        seed=args.seed,
        scenario=args.swarm_scenario,
        blocks=args.blocks,
        bps=args.bps,
        out=args.swarm_out,
    )
    det, fleet = report["deterministic"], report["fleet"]
    g = gates(report)
    summary = {
        "nodes": args.swarm,
        "blocks": det["blocks"],
        "converged": g["converged"],
        "matches_fault_free": g["matches_fault_free"],
        "lost_tickets": fleet["lost_tickets"],
        "amplification": fleet["relay"]["amplification"],
        "amp_ok": g["amp_ok"],
        "wall_seconds": report["metrics"]["wall_seconds"],
        "sink": det["fingerprints"]["node0"]["sink"],
        "utxo_commitment": det["fingerprints"]["node0"]["utxo_commitment"],
        "swarm_out": args.swarm_out,
    }
    if args.json:
        print(json.dumps(summary))
    else:
        print(
            f"swarm: {args.swarm} nodes, {det['blocks']} blocks mined, "
            f"converged={g['converged']} matches_fault_free={g['matches_fault_free']} "
            f"lost={fleet['lost_tickets']} amplification={fleet['relay']['amplification']} "
            f"in {summary['wall_seconds']}s -> {args.swarm_out}"
        )
    if not all(g.values()):
        raise SystemExit(2)


def _run_wedge(cfg, args) -> None:
    from kaspa_tpu.resilience.sustain import run_wedge_drill

    report = run_wedge_drill(cfg, seed=args.seed, out=args.sustain_out)
    det, sup, brk = report["deterministic"], report["supervisor"], report["breaker"]
    summary = {
        "blocks": det["blocks"],
        "matches_fault_free": det["matches_fault_free"],
        "injected_hangs": sup["injected_hangs"],
        "requeued_total": sup["requeued_total"],
        "requeue_matches_injected": sup["requeue_matches_injected"],
        "late_results_discarded": sup["late_results"],
        "compile_stall_ok": report["compile_stall"]["all_valid"] and report["compile_stall"]["shape_left_cold"],
        "tickets_ok": report["tickets"]["ok"],
        "breaker_trips": brk["trips"],
        "breaker_recoveries": brk["recoveries"],
        "recovered": sup["recovered"],
        "replay_seconds": report["metrics"]["replay_seconds"],
        "sink": det["fingerprints"]["sink"],
        "utxo_commitment": det["fingerprints"]["utxo_commitment"],
        "sustain_out": args.sustain_out,
    }
    if args.json:
        print(json.dumps(summary))
    else:
        print(
            f"wedge drill: {det['blocks']} blocks, {sup['injected_hangs']} hangs injected, "
            f"requeued={sup['requeued_total']} (match={sup['requeue_matches_injected']}), "
            f"trips={brk['trips']} recovered={sup['recovered']}, "
            f"matches_fault_free={det['matches_fault_free']} -> {args.sustain_out}"
        )
    ok = (
        det["matches_fault_free"]
        and sup["requeue_matches_injected"]
        and sup["injected_hangs"] > 0
        and summary["compile_stall_ok"]
        and summary["tickets_ok"]
        and sup["recovered"]
    )
    if not ok:
        raise SystemExit(2)


if __name__ == "__main__":
    main()
