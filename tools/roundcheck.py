#!/usr/bin/env python
"""One-command round evidence: graftlint + fast-lane tests + sim replay
+ multichip dryrun + mesh smoke + flight-recorder trace + chaos sustain.

Runs the repo's tier-1 fast lane, a short simulator replay, the sharded
multichip dryrun (on every visible device, forced-CPU), a `--mesh 8` sim
smoke replay, the flight-recorder lane (a
traced 24-block pipelined replay whose dump must hold one connected
>=4-thread span tree per block with >= 90% critical-path attribution and
a valid Perfetto export, plus a tracing-off-within-2% overhead gate),
the hostile-load chaos sustain run (seeded fault schedule; the faulted
replay must converge to the bit-identical fault-free end state), the
device-supervision wedge drill (injected dispatch hangs + a compile
stall; watchdog requeue accounting + canary recovery, bit-identity
gated), the ingest lane (batched-vs-per-tx mempool-admission
identity plus a short tx-flood sustain; clean acceptance >= 0.99 and
zero lost tickets), and the overload lane (a tx-flood replay with the
adaptive brownout ramp; the controller must reach SATURATED, shed load
with zero lost tickets, hold cadence within 1.5x of nominal, and settle
back to NOMINAL), and the swarm lane (three real in-process nodes over
loopback sockets: partition/heal with a deep attacker reorg and a
late-join IBD, gated on fleet-wide bit-identity, fault-free match, zero
lost tickets and a relay-amplification budget), then writes a single
round-evidence JSON (ROUNDCHECK.json)
summarizing them — the artifact a driver round or a reviewer reads
instead of eight scrollback logs.  Every lane's own artifact
(SUSTAIN*.json, FLIGHT*.json, SERVING_LOAD.json, SWARM.json) is written
beside ``--out``, whose default is the repo root.

    python tools/roundcheck.py                     # everything
    python tools/roundcheck.py --only tier1        # just one section
    python tools/roundcheck.py --only sim --only fabric
    python tools/roundcheck.py --skip-mesh         # no multichip/mesh lanes
    python tools/roundcheck.py --skip-obs          # no flight-recorder lane
    python tools/roundcheck.py --skip-chaos        # no fault-injection sustain
    python tools/roundcheck.py --skip-supervision  # no wedge drill
    python tools/roundcheck.py --skip-fabric       # no two-process fabric drill
    python tools/roundcheck.py --skip-ingest       # no tx-ingest admission lane
    python tools/roundcheck.py --skip-overload     # no brownout ramp drill
    python tools/roundcheck.py --skip-lint         # no graftlint static-analysis gate
    python tools/roundcheck.py --skip-serving_load # no 50k-subscriber latency observatory run
    python tools/roundcheck.py --skip-swarm        # no multi-node partition/heal swarm drill
    python tools/roundcheck.py --out my.json       # custom artifact path

``--only SECTION`` (repeatable, or comma-separated) runs exactly the
named sections and ignores the skip flags; section names are the keys in
ROUNDCHECK.json (lint, tier1, sim, multichip, mesh_smoke, serving,
serving_load, obs, tenbps, chaos, supervision, fabric, ingest, overload,
swarm).  Every section records its own
``wall_seconds`` in the artifact.

Exit code 0 iff every section that ran passed.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the tier1 section shells out to the pre-PR gate script so roundcheck
# and a bare `bash tools/ci_fastlane.sh` can never disagree on what
# "tier-1 green" means (fast-lane pytest + proto/borsh wire-freeze checks)
FASTLANE_CMD = ["bash", os.path.join(REPO_ROOT, "tools", "ci_fastlane.sh")]


def _utc() -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


def _run(cmd: list[str], timeout_s: float, env_extra: dict | None = None) -> dict:
    """Run one section command, capture tail + rc + wall time."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    t0 = time.monotonic()
    try:
        proc = subprocess.run(
            cmd, cwd=REPO_ROOT, env=env, timeout=timeout_s,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        rc, out = proc.returncode, proc.stdout or ""
    except subprocess.TimeoutExpired as e:
        rc = -9
        out = (e.stdout.decode() if isinstance(e.stdout, bytes) else e.stdout or "") + "\n[roundcheck] TIMEOUT"
    return {
        "cmd": " ".join(cmd),
        "rc": rc,
        "seconds": round(time.monotonic() - t0, 1),
        "tail": out.strip().splitlines()[-12:],
    }


def _last_json_line(section: dict) -> dict | None:
    for line in reversed(section["tail"]):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def _validate_flight(path: str) -> dict:
    """Schema + connectivity validation for a flight-recorder dump: every
    block trace must form a single connected span tree (exactly one root,
    zero orphan spans), cross >= 4 threads, and carry >= 90% critical-path
    attribution.  Returns the verdict + the aggregate top-3 stages."""
    with open(path) as f:
        doc = json.load(f)
    out: dict = {"path": path, "ok": False}
    if doc.get("format") != "kaspa-flight" or "traces" not in doc:
        out["error"] = "not a kaspa-flight dump"
        return out
    traces = doc["traces"]
    if not traces:
        out["error"] = "dump holds zero traces"
        return out
    bad_tree = bad_threads = bad_frac = 0
    thread_counts, fractions = [], []
    stage_ns: dict[str, float] = {}
    for t in traces:
        spans = t["spans"]
        ids = {s["span"] for s in spans}
        roots = [s for s in spans if s["parent"] not in ids]
        if len(roots) != 1 or roots[0]["name"] != "block":
            bad_tree += 1
        threads = {s["thread"] for s in spans}
        thread_counts.append(len(threads))
        if len(threads) < 4:
            bad_threads += 1
        cp = t.get("critical_path", {})
        frac = float(cp.get("fraction", 0.0))
        fractions.append(frac)
        if frac < 0.90:
            bad_frac += 1
        for name, ms in cp.get("stages_ms", {}).items():
            if name != "block":
                stage_ns[name] = stage_ns.get(name, 0.0) + ms
    top3 = sorted(stage_ns.items(), key=lambda kv: -kv[1])[:3]
    out.update(
        traces=len(traces),
        orphan_trees=bad_tree,
        under_4_threads=bad_threads,
        under_90pct_attribution=bad_frac,
        min_threads=min(thread_counts),
        min_fraction=round(min(fractions), 4),
        mean_fraction=round(sum(fractions) / len(fractions), 4),
        top_stages=[{"stage": n, "total_ms": round(ms, 2)} for n, ms in top3],
        ok=bad_tree == 0 and bad_threads == 0 and bad_frac == 0,
    )
    return out


def _validate_chrome(path: str) -> dict:
    """Minimal Chrome trace-event schema check on the exported Perfetto
    JSON: complete events carry ts/dur/pid/tid, flow events pair up."""
    with open(path) as f:
        doc = json.load(f)
    events = doc.get("traceEvents")
    if not isinstance(events, list) or not events:
        return {"path": path, "ok": False, "error": "no traceEvents"}
    complete = flows_out = flows_in = malformed = 0
    for e in events:
        if not isinstance(e.get("pid"), int) or not isinstance(e.get("tid"), int):
            malformed += 1
            continue
        ph = e.get("ph")
        if ph == "X":
            complete += 1
            if "ts" not in e or "dur" not in e or "name" not in e:
                malformed += 1
        elif ph == "s":
            flows_out += 1
        elif ph == "f":
            flows_in += 1
    return {
        "path": path,
        "events": len(events),
        "complete_spans": complete,
        "flow_edges": flows_out,
        "malformed": malformed,
        "ok": malformed == 0 and complete > 0 and flows_out == flows_in,
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--skip-tests", action="store_true", help="skip the tier-1 fast lane")
    ap.add_argument("--skip-sim", action="store_true", help="skip the simulator replay")
    ap.add_argument("--skip-mesh", action="store_true", help="skip the multichip dryrun + mesh smoke replay")
    ap.add_argument("--skip-chaos", action="store_true", help="skip the hostile-load chaos sustain run")
    ap.add_argument("--skip-serving", action="store_true", help="skip the serving-tier dual-encoding + kill -9 lane")
    ap.add_argument("--skip-obs", action="store_true", help="skip the flight-recorder traced-replay lane")
    ap.add_argument("--skip-tenbps", action="store_true", help="skip the 10-BPS speculative-pipeline lane")
    ap.add_argument("--skip-supervision", action="store_true", help="skip the device-supervision wedge drill")
    ap.add_argument("--skip-fabric", action="store_true", help="skip the two-process verify-fabric drill")
    ap.add_argument("--skip-ingest", action="store_true", help="skip the tx-ingest admission lane")
    ap.add_argument("--skip-overload", action="store_true", help="skip the brownout ramp drill")
    ap.add_argument("--skip-swarm", action="store_true", help="skip the multi-node swarm partition/heal drill")
    ap.add_argument("--skip-lint", action="store_true", help="skip the graftlint static-analysis gate")
    ap.add_argument("--skip-serving_load", action="store_true",
                    help="skip the 50k-virtual-subscriber serving latency observatory run")
    ap.add_argument("--serving-load-subscribers", type=int, default=50_000,
                    help="final population for the serving_load section")
    ap.add_argument(
        "--only", action="append", default=None, metavar="SECTION",
        help="run only the named section(s); repeatable or comma-separated, "
        "overrides every --skip-* flag",
    )
    ap.add_argument("--chaos-blocks", type=int, default=24, help="chaos sustain main-DAG length")
    # long enough that coinbase maturity passes and real signature batches
    # flow through the sharded verify path (a 12-block replay carries 0 txs)
    ap.add_argument("--mesh-blocks", type=int, default=48, help="mesh smoke replay length")
    ap.add_argument("--blocks", type=int, default=64, help="sim replay length")
    ap.add_argument("--test-timeout", type=float, default=900.0)
    ap.add_argument("--out", default=os.path.join(REPO_ROOT, "ROUNDCHECK.json"))
    args = ap.parse_args(argv)
    out_dir = os.path.dirname(os.path.abspath(args.out))

    # forced 8 CPU host devices: the mesh lanes must work on any box the
    # round runs on, with or without a real accelerator
    mesh_env = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}

    def _sect_lint() -> dict:
        t0 = time.monotonic()
        sect = _run([sys.executable, os.path.join(REPO_ROOT, "tools", "lint.py"), "-q", "--ratchet"], 120.0)
        sect["wall_s"] = round(time.monotonic() - t0, 2)
        report = None
        try:
            with open(os.path.join(REPO_ROOT, "LINT.json")) as f:
                report = json.load(f)
        except (OSError, json.JSONDecodeError):
            pass
        sect["findings"] = len(report["findings"]) if report else None
        sect["suppressed"] = len(report["suppressed"]) if report else None
        sect["files"] = report["files"] if report else None
        sect["engine"] = report.get("engine") if report else None
        sect["callgraph"] = report.get("callgraph") if report else None
        sect["ratchet"] = report.get("ratchet") if report else None
        sect["ok"] = (
            sect["rc"] == 0
            and report is not None
            and report["ok"]
            and report.get("engine") == "v2"
        )
        return sect

    def _sect_tier1() -> dict:
        sect = _run(FASTLANE_CMD, args.test_timeout, {"JAX_PLATFORMS": "cpu"})
        summary = next((ln for ln in reversed(sect["tail"]) if "passed" in ln), "")
        sect["summary"] = summary.strip()
        sect["ok"] = sect["rc"] == 0
        return sect

    def _sect_sim() -> dict:
        sect = _run(
            [sys.executable, "-m", "kaspa_tpu.sim", "--bps", "2", "--blocks", str(args.blocks), "--json"],
            300.0,
            {"JAX_PLATFORMS": "cpu"},
        )
        result = _last_json_line(sect)
        sect["result"] = result
        sect["ok"] = sect["rc"] == 0 and result is not None
        return sect

    def _sect_multichip() -> dict:
        # multichip dryrun: masks + muhash product checked against host
        # oracles on every visible device (round evidence for item 6)
        sect = _run(
            [
                sys.executable, "-c",
                "import json, jax, __graft_entry__ as g; n = len(jax.devices()); "
                "g.dryrun_multichip(n); print(json.dumps({'devices': n, 'dryrun_ok': True}))",
            ],
            600.0,
            mesh_env,
        )
        result = _last_json_line(sect)
        sect["result"] = result
        sect["ok"] = sect["rc"] == 0 and bool(result and result.get("dryrun_ok"))
        return sect

    def _sect_mesh_smoke() -> dict:
        # mesh smoke: the production batch path (BatchScriptChecker +
        # muhash) sharded over 8 host devices for a short replay — the
        # tier-1 fast lane exercises sharded dispatch at least once a round
        sect = _run(
            [
                sys.executable, "-m", "kaspa_tpu.sim",
                "--bps", "2", "--blocks", str(args.mesh_blocks), "--mesh", "8", "--json",
            ],
            # budget covers the one-time shard_map trace of the verify ladder
            # (~3-4 min/process on CPU; the XLA compile itself is served by
            # the persistent cache after the first round)
            900.0,
            mesh_env,
        )
        result = _last_json_line(sect)
        sect["result"] = result
        sect["ok"] = sect["rc"] == 0 and bool(result) and result.get("mesh") == 8
        return sect

    def _sect_serving() -> dict:
        # serving tier: one persistent daemon, one JSON + one Borsh client
        # on the same UtxosChanged scope — the streams must be identical —
        # then kill -9 and a reopen that reconciles (journal rewind /
        # chain-diff catch-up), never a full resync.  Subscriber-lag
        # histograms and per-encoding request counters land in the evidence.
        sect = _run(
            [sys.executable, os.path.join(REPO_ROOT, "tools", "serving_check.py"), "--blocks", "10"],
            600.0,
            {"JAX_PLATFORMS": "cpu"},
        )
        result = _last_json_line(sect)
        sect["result"] = result
        sect["ok"] = sect["rc"] == 0 and bool(result and result.get("serving_ok"))
        return sect

    def _sect_serving_load() -> dict:
        # serving latency observatory (tools/serving_load.py): first the
        # sharded-vs-single fanout identity harness (delivered streams
        # must be bit-identical at shards=4), then a ramped >=50k-virtual-
        # subscriber run of BOTH legs — the single-fanout baseline curve
        # and the sharded (--shards 4) curve, the latter gated against the
        # committed PR 16 baseline (saturation >= 1.5x, paced p99 <= 0.5x,
        # zero drops/disconnects) on top of the historical gates (drained,
        # bounded p99, tracing-off overhead).  Evidence: SERVING_LOAD.json.
        ident = _run(
            [sys.executable, "-m", "kaspa_tpu.serving.check", "--shards", "4"],
            300.0,
            {"JAX_PLATFORMS": "cpu"},
        )
        ident["result"] = _last_json_line(ident)
        sect = _run(
            [
                sys.executable, os.path.join(REPO_ROOT, "tools", "serving_load.py"),
                "--subscribers", str(args.serving_load_subscribers),
                "--shards", "4",
                "--out", os.path.join(out_dir, "SERVING_LOAD.json"),
            ],
            1500.0,
            {"JAX_PLATFORMS": "cpu"},
        )
        result = _last_json_line(sect)
        sect["identity"] = ident
        sect["result"] = result
        identity_ok = ident["rc"] == 0 and bool(
            ident["result"] and ident["result"].get("serving_identity_ok")
        )
        sect["ok"] = (
            identity_ok
            and sect["rc"] == 0
            and bool(result and result.get("serving_load_ok"))
        )
        return sect

    def _sect_obs() -> dict:
        # flight-recorder lane: a traced 24-block pipelined + coalesced
        # replay (the full production thread topology: stage workers,
        # virtual worker, verify-dispatch, serving fanout) must produce a
        # dump where every block is a single connected span tree crossing
        # >= 4 threads with >= 90% critical-path attribution, the Perfetto
        # export must be valid Chrome trace JSON, and the tracing-disabled
        # replay must stay within 2% of the default (PR 5 baseline) replay.
        flight_path = os.path.join(out_dir, "FLIGHT.json")
        perfetto_path = os.path.join(out_dir, "FLIGHT.perfetto.json")
        sect = _run(
            [
                sys.executable, "-m", "kaspa_tpu.sim",
                "--bps", "2", "--blocks", "24", "--tpb", "4",
                "--pipeline", "--coalesce", "64", "--trace", flight_path, "--json",
            ],
            600.0,
            {"JAX_PLATFORMS": "cpu"},
        )
        sect["result"] = _last_json_line(sect)
        traced_ok = sect["rc"] == 0 and bool(sect["result"])
        if traced_ok:
            sect["flight"] = _validate_flight(flight_path)
            conv = _run(
                [sys.executable, os.path.join(REPO_ROOT, "tools", "trace_report.py"),
                 flight_path, "--perfetto", perfetto_path],
                120.0,
                {"JAX_PLATFORMS": "cpu"},
            )
            sect["perfetto"] = (
                _validate_chrome(perfetto_path) if conv["rc"] == 0
                else {"ok": False, "error": "trace_report --perfetto failed", "tail": conv["tail"]}
            )
        # overhead gate: serial replay as in PR 5 (default tracing, no
        # flight recorder) vs the same replay with tracing disabled —
        # best-of-2 each to keep run-to-run noise out of the 2% budget
        base_cmd = [
            sys.executable, "-m", "kaspa_tpu.sim",
            "--bps", "2", "--blocks", "24", "--tpb", "4", "--json",
        ]
        def _best_bps(cmd):
            best, tails = 0.0, []
            for _ in range(2):
                r = _run(cmd, 300.0, {"JAX_PLATFORMS": "cpu"})
                tails.append(r["tail"][-1:])
                j = _last_json_line(r)
                if r["rc"] == 0 and j:
                    best = max(best, float(j.get("replay_blocks_per_sec", 0.0)))
            return best, tails
        base_bps, _ = _best_bps(base_cmd)
        off_bps, _ = _best_bps(base_cmd + ["--notrace"])
        sect["overhead"] = {
            "baseline_bps": base_bps,
            "tracing_off_bps": off_bps,
            "ratio": round(off_bps / base_bps, 4) if base_bps else 0.0,
            "ok": base_bps > 0 and off_bps >= 0.98 * base_bps,
        }
        sect["ok"] = (
            traced_ok
            and sect.get("flight", {}).get("ok", False)
            and sect.get("perfetto", {}).get("ok", False)
            and sect["overhead"]["ok"]
        )
        return sect

    def _sect_tenbps() -> dict:
        # 10-BPS lane (ROADMAP item 2): a pipelined replay of a 10-BPS DAG
        # with the chaos schedule off, speculation on — records the
        # realtime_factor and the speculative hit-rate — gated on the
        # speculation-disabled replay of the same DAG reaching the
        # bit-identical sink + utxo_commitment (the hit path must be
        # indistinguishable from the honest path)
        tenbps_cmd = [
            sys.executable, "-m", "kaspa_tpu.sim",
            "--bps", "10", "--blocks", "24", "--tpb", "4", "--pipeline", "--json",
        ]
        sect = _run(tenbps_cmd, 600.0, {"JAX_PLATFORMS": "cpu"})
        spec_on = _last_json_line(sect)
        off = _run(tenbps_cmd + ["--no-spec"], 600.0, {"JAX_PLATFORMS": "cpu"})
        spec_off = _last_json_line(off)
        identical = bool(
            spec_on and spec_off
            and spec_on["sink"] == spec_off["sink"]
            and spec_on["utxo_commitment"] == spec_off["utxo_commitment"]
        )
        sect["result"] = spec_on
        sect["no_spec_result"] = spec_off
        sect["identical_to_no_spec"] = identical
        if spec_on:
            sect["realtime_factor"] = spec_on.get("realtime_factor")
            sect["speculative"] = spec_on.get("speculative")
        sect["ok"] = sect["rc"] == 0 and off["rc"] == 0 and identical
        return sect

    def _sect_chaos() -> dict:
        # chaos sustain: seeded fault schedule under hostile script mix +
        # attacker-fork reorg; the acceptance bit is the faulted run
        # converging to the byte-identical fault-free end state with the
        # breaker demonstrably tripping and recovering (round evidence for
        # ROADMAP item 5)
        sect = _run(
            [
                sys.executable, "-m", "kaspa_tpu.sim",
                "--hostile", "--faults", "default", "--blocks", str(args.chaos_blocks),
                "--tpb", "4", "--seed", "7", "--json",
                "--sustain-out", os.path.join(out_dir, "SUSTAIN.json"),
            ],
            900.0,
            {"JAX_PLATFORMS": "cpu"},
        )
        result = _last_json_line(sect)
        sect["result"] = result
        sect["ok"] = (
            sect["rc"] == 0
            and bool(result)
            and bool(result.get("matches_fault_free"))
            and result.get("breaker_trips", 0) >= 1
        )
        return sect

    def _sect_supervision() -> dict:
        # supervision wedge drill: dispatch hangs + a compile stall injected
        # mid-replay; the watchdog reroutes every wedged super-batch to the
        # host degraded lane and the canary prober recovers the breaker —
        # gated on bit-identity with the fault-free replay plus exact
        # requeue accounting (no ticket lost, none double-resolved)
        sect = _run(
            [
                sys.executable, "-m", "kaspa_tpu.sim",
                "--hostile", "--wedge-drill", "--blocks", "24",
                "--tpb", "4", "--seed", "7", "--coalesce", "256", "--json",
                "--sustain-out", os.path.join(out_dir, "SUSTAIN_WEDGE.json"),
            ],
            1200.0,
            {"JAX_PLATFORMS": "cpu"},
        )
        result = _last_json_line(sect)
        sect["result"] = result
        sect["ok"] = (
            sect["rc"] == 0
            and bool(result)
            and bool(result.get("matches_fault_free"))
            and bool(result.get("requeue_matches_injected"))
            and result.get("injected_hangs", 0) > 0
            and bool(result.get("compile_stall_ok"))
            and bool(result.get("tickets_ok"))
            and bool(result.get("recovered"))
        )
        return sect

    def _sect_fabric() -> dict:
        # verify fabric: spawn a real verifyd (second process), replay over
        # the wire and gate on bit-identity with the local-only replay, then
        # SIGKILL the server mid-replay and gate on the degraded-lane
        # failover losing zero tickets (ISSUE acceptance: fabric smoke +
        # slice-kill drill)
        sect = _run(
            [sys.executable, os.path.join(REPO_ROOT, "tools", "fabric_check.py"), "--blocks", "24"],
            900.0,
            {"JAX_PLATFORMS": "cpu"},
        )
        result = _last_json_line(sect)
        sect["result"] = result
        sect["ok"] = sect["rc"] == 0 and bool(result and result.get("fabric_ok"))
        return sect

    def _sect_ingest() -> dict:
        # ingest lane (ISSUE 12): (a) batched waves on the verify plane vs
        # one-at-a-time validate_and_insert over the same hostile flood in
        # the same arrival order must leave the mempool, orphan pool and a
        # fixed-timestamp template bit-identical; (b) a short tx-flood
        # sustain run must keep consensus bit-identical to the fault-free
        # replay with clean acceptance >= 0.99 and zero lost tickets
        sect = _run(
            [sys.executable, "-m", "kaspa_tpu.ingest.check", "--blocks", "24", "--tpb", "4", "--slots", "6"],
            600.0,
            {"JAX_PLATFORMS": "cpu"},
        )
        identity = _last_json_line(sect)
        sect["result"] = identity
        flood = _run(
            [
                sys.executable, "-m", "kaspa_tpu.sim",
                "--txflood", "--no-pace", "--blocks", "24", "--tpb", "4",
                "--seed", "7", "--json",
                "--sustain-out", os.path.join(out_dir, "SUSTAIN_TXFLOOD.json"),
            ],
            900.0,
            {"JAX_PLATFORMS": "cpu"},
        )
        j_flood = _last_json_line(flood)
        sect["flood_cmd"] = flood["cmd"]
        sect["flood_tail"] = flood["tail"]
        sect["flood_result"] = j_flood
        sect["ok"] = (
            sect["rc"] == 0
            and bool(identity and identity.get("ingest_ok"))
            and flood["rc"] == 0
            and bool(j_flood)
            and bool(j_flood.get("matches_fault_free"))
            and j_flood.get("tx_acceptance_rate", 0.0) >= 0.99
            and j_flood.get("lost_tickets", 1) == 0
        )
        return sect

    def _sect_overload() -> dict:
        # overload lane (ISSUE 14): a tx-flood replay with the adaptive
        # brownout ramp engaged — flood scale climbs past the pressure
        # thresholds, the controller must reach SATURATED, every brownout
        # seam sheds observably (zero lost tickets — every shed tx still
        # resolves its admission ticket), block cadence under SATURATED
        # stays within 1.5x of loaded-nominal, and the controller settles
        # back to NOMINAL once the flood drains.  The late ramp fractions
        # leave the 24-block warm phase long enough for coinbase maturity,
        # so the NOMINAL cadence baseline carries real flood traffic.
        sect = _run(
            [
                sys.executable, "-m", "kaspa_tpu.sim",
                "--txflood", "--overload", "--no-pace", "--blocks", "24",
                "--tpb", "4", "--seed", "7", "--json",
                "--overload-config", '{"warm_frac": 0.5, "ramp_frac": 0.2, "hold_frac": 0.2}',
                "--sustain-out", os.path.join(out_dir, "SUSTAIN_OVERLOAD.json"),
            ],
            900.0,
            {"JAX_PLATFORMS": "cpu"},
        )
        result = _last_json_line(sect)
        sect["result"] = result
        sect["ok"] = (
            sect["rc"] == 0
            and bool(result)
            and bool(result.get("matches_fault_free"))
            and result.get("lost_tickets", 1) == 0
            and result.get("overload_max_level") in ("SATURATED", "CRITICAL")
            and result.get("overload_shed", 0) > 0
            and bool(result.get("overload_recovered"))
            and bool(result.get("overload_ok"))
        )
        return sect

    def _sect_swarm() -> dict:
        # swarm drill (ISSUE 19): three real in-process nodes over loopback
        # sockets run the seeded default scenario — partition into
        # {attacker} x {honest}, divergent mining on both sides, heal with
        # a deep attacker reorg, post-heal relay round, then a late joiner
        # IBDs the whole DAG.  Gated on every node converging bit-identical
        # (sink + utxo commitment), the run matching the fault-free replay,
        # zero lost admission tickets fleet-wide, and block-relay traffic
        # staying under the O(N * blocks) amplification budget.
        sect = _run(
            [
                sys.executable, "-m", "kaspa_tpu.sim",
                "--swarm", "3", "--blocks", "24", "--seed", "7", "--json",
                "--swarm-out", os.path.join(out_dir, "SWARM.json"),
            ],
            900.0,
            {"JAX_PLATFORMS": "cpu"},
        )
        result = _last_json_line(sect)
        sect["result"] = result
        sect["ok"] = (
            sect["rc"] == 0
            and bool(result)
            and bool(result.get("converged"))
            and bool(result.get("matches_fault_free"))
            and result.get("lost_tickets", 1) == 0
            and bool(result.get("amp_ok"))
        )
        return sect

    sections: list[tuple[str, bool, object]] = [
        ("lint", not args.skip_lint, _sect_lint),
        ("tier1", not args.skip_tests, _sect_tier1),
        ("sim", not args.skip_sim, _sect_sim),
        ("multichip", not args.skip_mesh, _sect_multichip),
        ("mesh_smoke", not args.skip_mesh, _sect_mesh_smoke),
        ("serving", not args.skip_serving, _sect_serving),
        ("serving_load", not args.skip_serving_load, _sect_serving_load),
        ("obs", not args.skip_obs, _sect_obs),
        ("tenbps", not args.skip_tenbps, _sect_tenbps),
        ("chaos", not args.skip_chaos, _sect_chaos),
        ("supervision", not args.skip_supervision, _sect_supervision),
        ("fabric", not args.skip_fabric, _sect_fabric),
        ("ingest", not args.skip_ingest, _sect_ingest),
        ("overload", not args.skip_overload, _sect_overload),
        ("swarm", not args.skip_swarm, _sect_swarm),
    ]
    only: set[str] | None = None
    if args.only:
        only = {name.strip() for spec in args.only for name in spec.split(",") if name.strip()}
        known = {name for name, _, _ in sections}
        unknown = only - known
        if unknown:
            ap.error(f"unknown --only section(s) {sorted(unknown)}; known: {sorted(known)}")

    evidence: dict = {"created": _utc(), "sections": {}}
    ok = True
    for name, enabled, fn in sections:
        if only is not None:
            if name not in only:
                continue
        elif not enabled:
            continue
        t0 = time.monotonic()
        sect = fn()
        # wall_seconds covers the whole section (some run several commands;
        # each command's own time stays in its "seconds")
        sect["wall_seconds"] = round(time.monotonic() - t0, 1)
        evidence["sections"][name] = sect
        ok &= sect["ok"]

    evidence["ok"] = ok
    with open(args.out, "w") as f:
        json.dump(evidence, f, indent=2)
        f.write("\n")
    print(f"[roundcheck] {'PASS' if ok else 'FAIL'} -> {args.out}")
    for name, sect in evidence["sections"].items():
        print(f"  {name:12s} {'ok' if sect['ok'] else 'FAIL':4s} rc={sect['rc']} {sect['wall_seconds']}s")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
