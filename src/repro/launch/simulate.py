"""S-RAPS CLI (the paper's ``main.py`` equivalent).

  python -m repro.launch.simulate --system marconi100 -t 61000 -ff 4381000 \\
      --scheduler default --policy fcfs --backfill easy -o out/

Options mirror the paper's artifact: --system selects the dataloader,
--policy/--backfill the built-in scheduler, --scheduler external couples an
event-based external simulator (fastsim | scheduleflow), --accounts tracks
account ledgers, --accounts-json reloads them (incentive redeeming),
--sweep runs several policies in one compiled batch.

Subcommand ``train`` closes the ML scheduling loop (repro.ml.train,
docs/ml-scheduling.md): ES-optimize the scoring alpha against batched twin
rollouts, e.g. ``python -m repro.launch.simulate train --smoke``. A trained
checkpoint feeds back into evaluation via ``--policy ml --ml-alpha
<checkpoint.json or comma floats>``.

Real traces (repro.traces, docs/datasets.md): ``--trace`` ingests a
published job table or a joblive/jobprofile telemetry dump in place of
the synthetic dataset, ``--replay-power`` plays measured power back
verbatim, ``--weather-trace`` drives the cooling tower from recorded
ambient conditions, and subcommand ``calibrate`` fits the cooling-plant
parameters to recorded facility telemetry.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import secrets
import time
import types

import numpy as np

import dataclasses

from repro.core import accounts as acct_mod
from repro.core import engine as eng
from repro.core import external as ext
from repro.core import stats as stats_mod
from repro.core import types as T
from repro.datasets import loaders
from repro.launch import env as launch_env
from repro.ml.pipeline import MLSchedulerModel, attach_scores
from repro.systems.config import FacilityTopology, get_system


def _parse_time(s: str) -> float:
    units = {"s": 1.0, "m": 60.0, "h": 3600.0, "d": 86400.0}
    if s and s[-1] in units:
        return float(s[:-1]) * units[s[-1]]
    return float(s)


def build_system(name: str, scale: int = 0, halls: int = 0):
    """Resolve a system config with optional node scaling and a hall
    split (capacity-preserving re-rate so every hall gets >= 1 CDU
    group and >= 1 tower cell). Shared by the CLI entry points."""
    sys_ = get_system(name)
    if scale:
        sys_ = sys_.scaled(scale)
    if halls:
        cool = sys_.cooling
        # every hall needs >= 1 CDU group and >= 1 tower cell: re-rate the
        # fleet capacity-preservingly (more, smaller cells/CDUs — total
        # rated heat, flow, pump power and HX conductance unchanged) when
        # a scaled config is too coarse for the requested hall count
        cells = max(cool.n_tower_cells, halls)
        groups = max(cool.n_groups, halls)
        cell_k = cool.n_tower_cells / cells
        group_k = cool.n_groups / groups
        sys_ = dataclasses.replace(
            sys_, cooling=dataclasses.replace(
                cool,
                n_groups=groups,
                mdot_kg_s=cool.mdot_kg_s * group_k,
                ua_w_k=cool.ua_w_k * group_k,
                pump_w_per_group=cool.pump_w_per_group * group_k,
                n_tower_cells=cells,
                cell_rated_heat_w=cool.cell_rated_heat_w * cell_k,
                fan_rated_w=cool.fan_rated_w * cell_k,
                topology=FacilityTopology(n_halls=halls)))
    return sys_


def main(argv=None) -> int:
    """Run the CLI; returns the process exit code."""
    import sys as _sys
    argv = list(_sys.argv[1:]) if argv is None else list(argv)
    if argv[:1] == ["train"]:
        # policy-training subcommand (repro.ml.train): ES over batched
        # twin rollouts; everything after "train" is its own arg set
        from repro.ml import train as ml_train
        ml_train.main(argv[1:])
        return 0
    if argv[:1] == ["serve"]:
        # twin-as-a-service (repro.serve, docs/serving.md): persistent
        # session with snapshot/fork branching over a socket
        from repro.serve import cli as serve_cli
        return serve_cli.main(argv[1:])
    if argv[:1] == ["calibrate"]:
        # cooling-plant calibration against recorded telemetry
        # (repro.traces.calibrate, docs/datasets.md)
        from repro.traces import calibrate as calibrate_cli
        return calibrate_cli.main(argv[1:])
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--system", default="marconi100")
    ap.add_argument("--scheduler", default="default",
                    choices=["default", "experimental", "fastsim",
                             "scheduleflow"])
    ap.add_argument("--policy", default="replay")
    ap.add_argument("--backfill", default=None,
                    help="backfill mode (default: none for built-in "
                         "schedulers, firstfit for external peers; an "
                         "explicit value always wins)")
    ap.add_argument("-ff", "--fastforward", default="0", type=str,
                    help="simulation start offset (s/m/h/d suffix)")
    ap.add_argument("-t", "--time", default="6h", type=str,
                    help="simulated duration")
    ap.add_argument("--jobs", type=int, default=1000)
    ap.add_argument("--days", type=float, default=None,
                    help="dataset horizon to generate (days)")
    # real-trace ingestion (repro.traces, docs/datasets.md)
    ap.add_argument("--trace", nargs="+", default=None, metavar="PATH",
                    help="replace the synthetic --system dataset with a "
                         "real trace: one job table (.parquet/.csv), one "
                         "cached trace .npz, or a joblive dir followed by "
                         "a jobprofile dir (RAPS-style telemetry)")
    ap.add_argument("--trace-cache", default=None, metavar="DIR",
                    help="content-addressed NPZ cache directory for "
                         "parsed telemetry (repeat runs skip the CSVs)")
    ap.add_argument("--replay-power", action="store_true",
                    help="replay measured per-node power profiles from "
                         "the trace instead of the power model (jobs "
                         "without a measurement keep the model)")
    ap.add_argument("--weather-trace", default=None, metavar="FILE",
                    help="measured weather CSV/NPZ (timestamp + wet-bulb "
                         "or dry-bulb/RH) driving the cooling tower "
                         "ambient (repro.traces.weather)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int, default=0,
                    help="scale the system to N nodes (CPU-friendly)")
    ap.add_argument("--halls", type=int, default=0,
                    help="split the cooling plant into N halls "
                         "(FacilityTopology; per-hall towers/basins)")
    ap.add_argument("--cells-offline", default=None,
                    help="tower cells out for maintenance: a number "
                         "(every hall) or comma list (per hall), e.g. "
                         "'2,0,0,0'")
    # stochastic failure + demand-response layer (repro.events)
    ap.add_argument("--failure-rate", type=float, default=None,
                    help="per-node failure hazard (failures per node-DAY); "
                         "enables the stochastic failure layer")
    ap.add_argument("--cdu-failure-rate", type=float, default=None,
                    help="per-CDU-group failure hazard (per group-day)")
    ap.add_argument("--cell-failure-rate", type=float, default=None,
                    help="per-tower-cell failure hazard (per cell-day)")
    ap.add_argument("--failure-corr", type=float, default=0.0,
                    help="correlated common-cause scale in [0,1]: one "
                         "per-hall draw takes the hall's CDU groups "
                         "down together")
    ap.add_argument("--failure-seed", type=int, default=0,
                    help="failure-universe seed (deterministic draws)")
    ap.add_argument("--repair", default="1h", type=str,
                    help="mean repair time (s/m/h/d suffix)")
    ap.add_argument("--no-requeue", action="store_true",
                    help="killed jobs are dismissed instead of requeued")
    ap.add_argument("--dr-announce", default=None, type=str,
                    help="demand-response event: announcement time into "
                         "the run (s/m/h/d suffix); enables the DR layer")
    ap.add_argument("--dr-notice", default="30m", type=str,
                    help="notice window between announcement and the cap "
                         "engaging")
    ap.add_argument("--dr-duration", default="1h", type=str,
                    help="how long the DR cap holds")
    ap.add_argument("--dr-cap-mw", type=float, default=0.0,
                    help="DR cap level (MW)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: scale to 64 nodes, <=48 jobs, "
                         "30 minutes simulated")
    ap.add_argument("--external-cmd", default=None,
                    help="couple an out-of-process scheduler: spawn this "
                         "command as a subprocess peer (NDJSON socket "
                         "wire protocol, docs/external-scheduling.md), "
                         "e.g. 'python -m tools.reference_peer'")
    ap.add_argument("--external-socket", default=None,
                    help="couple a peer already listening at unix:/path "
                         "or host:port (see tools/reference_peer.py "
                         "--listen)")
    ap.add_argument("--external-mode", default="plugin",
                    choices=["plugin", "sequential"],
                    help="coupling mode for --external-cmd/--external-"
                         "socket (paper §4.2: per-step polling vs "
                         "schedule-then-replay)")
    ap.add_argument("--external-wire", default="auto",
                    choices=("auto", "ndjson", "binary"),
                    help="wire dialect for the external peer: auto "
                         "upgrades to binary frames when the peer "
                         "advertises the capability, ndjson pins the "
                         "legacy dialect, binary demands it (fails the "
                         "handshake on a legacy peer)")
    ap.add_argument("--external-timeout", type=float, default=30.0,
                    help="per-poll wall budget (s) for the external "
                         "bridge; also the socket recv timeout")
    ap.add_argument("--accounts", action="store_true")
    ap.add_argument("--accounts-json", default=None)
    ap.add_argument("--ml-alpha", default=None,
                    help="scoring alpha for --policy ml: a training "
                         "checkpoint JSON (repro.ml.train) or comma "
                         "floats, e.g. '1.2,0.8,1.1,0.3'")
    ap.add_argument("--sweep", nargs="*", default=None,
                    help="policy[:backfill] list to run as one batch")
    ap.add_argument("-o", "--output", default=None, nargs="?",
                    const="simulation_results")
    # flight recorder (docs/observability.md)
    ap.add_argument("--manifest", default=None, metavar="FILE",
                    help="write a schema-versioned run manifest JSON")
    ap.add_argument("--events", default=None, metavar="FILE",
                    help="write lifecycle events (compile/scan/checkpoint/"
                         "respawn) as NDJSON")
    ap.add_argument("--metrics", default=None, metavar="TARGET",
                    help="stream per-interval telemetry as NDJSON frames "
                         "to a file path, tcp:host:port, or unix:/path")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the run into DIR")
    from repro.obs.reporter import add_output_flags
    add_output_flags(ap)
    args = ap.parse_args(argv)

    if args.smoke:
        args.scale = args.scale or 64
        args.jobs = min(args.jobs, 48)
        args.time = "30m"
    sys_ = build_system(args.system, args.scale, args.halls)
    cells_offline = 0.0
    if args.cells_offline:
        parts = [float(x) for x in args.cells_offline.split(",")]
        cells_offline = parts[0] if len(parts) == 1 else tuple(parts)
    t0 = _parse_time(args.fastforward)
    t1 = t0 + _parse_time(args.time)
    days = args.days or max((t1 / 86400.0) * 1.25, 0.5)
    if args.trace:
        js = loaders.load_trace(args.trace, prof_dt=sys_.prof_dt,
                                cache_dir=args.trace_cache)
    else:
        # sized for the machine actually simulated (--scale), so a
        # replayed schedule is feasible on it
        js = loaders.load(args.system, n_jobs=args.jobs, days=days,
                          seed=args.seed, system=sys_)
    weather = None
    if args.weather_trace:
        from repro.traces.weather import load_weather
        n_steps = int(round((t1 - t0) / sys_.dt))
        weather = load_weather(args.weather_trace, n_steps, sys_.dt, t0=t0)
    if args.policy == "ml":
        alpha = None
        if args.ml_alpha:
            if pathlib.Path(args.ml_alpha).exists():
                from repro.ml.train import load_alpha
                alpha = load_alpha(args.ml_alpha)
            else:
                alpha = np.asarray(
                    [float(x) for x in args.ml_alpha.split(",")],
                    np.float32)
        # trained or default alpha is baked into the static score, so
        # every engine path (static / sweep / traced) ranks identically
        model = MLSchedulerModel.fit(js, k=5, alpha=alpha)
        attach_scores(js, model)
    js.assign_prepop_placement(t0, sys_.n_nodes)
    table = js.to_table(replay_power=args.replay_power)

    accounts = None
    if args.accounts_json:
        accounts = acct_mod.load_json(args.accounts_json)

    from repro import obs
    rep = obs.Reporter.from_flags(args)
    recorder = None
    if args.manifest or args.events:
        recorder = obs.RunRecorder(manifest_path=args.manifest,
                                   events_path=args.events)
        recorder.begin(
            sys_, command="sweep" if args.sweep else "simulate", argv=argv,
            scenario={"policy": args.policy,
                      "backfill": args.backfill or "none",
                      "scheduler": args.scheduler, "sweep": args.sweep,
                      "external_cmd": args.external_cmd,
                      "external_socket": args.external_socket,
                      "external_mode": args.external_mode,
                      "external_wire": args.external_wire,
                      "halls": args.halls,
                      "cells_offline": args.cells_offline,
                      "failure_rate_per_day": args.failure_rate,
                      "failure_seed": args.failure_seed,
                      "dr_cap_mw": args.dr_cap_mw,
                      "trace": args.trace,
                      "replay_power": args.replay_power,
                      "weather_trace": args.weather_trace,
                      "t0_s": t0, "duration_s": t1 - t0},
            seed=args.seed, jobs=js,
            extra={"env_preset": launch_env.report(
                "sweep" if args.sweep else "throughput"),
                   # content digests pin exactly which trace bytes
                   # produced this run (repro.traces provenance)
                   **_trace_digests(args)})
        recorder.event("run_start")
    timer = obs.SpanTimer(listener=recorder.span_listener
                          if recorder else None)
    import jax
    if args.profile:
        jax.profiler.start_trace(args.profile)

    wall0 = time.perf_counter()
    with obs.use(timer):
        runs, bridge = _run(args, sys_, js, table, accounts, t0, t1,
                            cells_offline, recorder, weather)
        # the engine returns once the device has its work: the wait for
        # the results is the scan's time (engine.launch holds the compile)
        with obs.span("engine.scan"):
            jax.block_until_ready(runs)
    wall = time.perf_counter() - wall0
    if args.profile:
        jax.profiler.stop_trace()
        rep.info(f"profiler trace -> {args.profile}")

    sink = obs.MetricsSink(args.metrics) if args.metrics else None
    summaries = {}
    for (p, b), final, hist in runs:
        s = stats_mod.summarize(sys_, table, final, hist)
        label = f"{p}:{b}"
        summaries[label] = s
        if sink is not None:
            obs.stream_history(sink, recorder.run_id if recorder
                               else "anonymous", sys_, table, final, hist,
                               label=label, summary=s)
        rep.result(f"=== {args.system} policy={p} backfill={b} "
                   f"(sim {t1 - t0:.0f}s in {wall:.1f}s wall, "
                   f"{(t1 - t0) / wall:.0f}x realtime) ===\n" +
                   stats_mod.format_stats(s),
                   key=label, value=s)
        if args.output:
            out = pathlib.Path(args.output) / secrets.token_hex(4)
            out.mkdir(parents=True, exist_ok=True)
            np.savez(out / "history.npz",
                     **{k: np.asarray(getattr(hist, k))
                        for k in vars(hist) if not k.startswith("_")})
            (out / "stats.out").write_text(stats_mod.format_stats(s))
            with open(out / "job_history.csv", "w") as f:
                f.write("job,submit,start,end,nodes,account,state\n")
                st_ = np.asarray(final.start)
                en_ = np.asarray(final.end)
                js_ = np.asarray(final.jstate)
                for j in range(len(js)):
                    f.write(f"{j},{js.submit[j]:.0f},{st_[j]:.0f},"
                            f"{en_[j]:.0f},{js.nodes[j]},{js.account[j]},"
                            f"{js_[j]}\n")
            if args.accounts:
                acct_mod.save_json(final.accounts,
                                   str(out / "accounts.json"))
            rep.info(f"output -> {out}")
            rep.result_json("output_dir", str(out))
    if sink is not None:
        sink.close()
        rep.info(f"metrics: {sink.n_frames} frames -> {args.metrics}")
    if bridge is not None:
        rep.result_json("bridge", bridge.stats())
    if recorder is not None:
        recorder.event("run_end", wall_s=wall)
        counters = {"sweep_cache": dict(eng.SWEEP_CACHE_STATS),
                    "compiles": dict(obs.timing.COMPILE_STATS)}
        if bridge is not None:
            counters["bridge"] = bridge.stats()
        if sink is not None:
            counters["metrics_frames"] = sink.n_frames
        recorder.finalize(spans=timer.summary(), counters=counters,
                          wall_s=wall, summaries=summaries)
        rep.info(f"manifest -> {args.manifest}" if args.manifest
                 else f"events -> {args.events}")
    rep.flush_json()
    return 0


def _trace_digests(args) -> dict:
    """Content digests of any real traces feeding this run, for the
    manifest — empty when the run is fully synthetic."""
    from repro.traces import source_digest
    out = {}
    if args.trace:
        out["trace_digest"] = source_digest(*args.trace)
    if args.weather_trace:
        out["weather_trace_digest"] = source_digest(args.weather_trace)
    return out


def _failure_kwargs(args, t0):
    """Scenario knob kwargs for the failure/DR layer from CLI flags.

    Empty dict = the layer is off. CLI hazard rates are per entity-DAY
    (operator-friendly MTBF units); Scenario knobs are hazards in 1/s.
    ``--dr-announce`` is relative to the run start, the Scenario knob is
    absolute sim time."""
    per_day = 1.0 / 86400.0
    kw = {}
    if args.failure_rate is not None:
        kw["node_fail_rate"] = args.failure_rate * per_day
    if args.cdu_failure_rate is not None:
        kw["cdu_fail_rate"] = args.cdu_failure_rate * per_day
    if args.cell_failure_rate is not None:
        kw["cell_fail_rate"] = args.cell_failure_rate * per_day
    if kw:
        kw["failure_corr"] = args.failure_corr
        kw["failure_seed"] = float(args.failure_seed)
        kw["repair_s"] = _parse_time(args.repair)
    if args.dr_announce is not None and args.dr_cap_mw > 0:
        kw["dr_announce_s"] = t0 + _parse_time(args.dr_announce)
        kw["dr_notice_s"] = _parse_time(args.dr_notice)
        kw["dr_duration_s"] = _parse_time(args.dr_duration)
        kw["dr_cap_w"] = args.dr_cap_mw * 1e6
    return kw


def _run(args, sys_, js, table, accounts, t0, t1, cells_offline, recorder,
         weather=None):
    """Dispatch one CLI invocation to the right engine path.

    Returns (runs, bridge): ``runs`` is a list of ((policy, backfill),
    final, hist) and ``bridge`` the SchedulerBridge when an external
    coupling ran in plugin mode (its counters feed the manifest).
    ``weather`` (a measured trace, --weather-trace) reaches every
    compiled path; the external-scheduler bridges do not model ambient
    conditions, so combining them is a loud error rather than a
    silently-ignored flag."""
    backfill_cli = args.backfill or "none"
    bridge = None
    if weather is not None and (args.external_cmd or args.external_socket
                                or args.scheduler in ("fastsim",
                                                      "scheduleflow")):
        raise SystemExit("--weather-trace is not supported with external "
                         "scheduler coupling")
    fail_kw = _failure_kwargs(args, t0)
    events_cfg = None
    dr_signals = None
    if fail_kw:
        from repro.events import EventConfig
        events_cfg = EventConfig(requeue=not args.no_requeue)
        if "dr_cap_w" in fail_kw:
            # demand-response rides the grid-cap machinery: inject
            # neutral signals (zero carbon/price, uncapped) when no grid
            # trace drives the run
            from repro.grid import signals as gsig
            dr_signals = gsig.neutral(int(round((t1 - t0) / sys_.dt)))
    if args.external_cmd or args.external_socket:
        from repro.core import transport as tr
        policy = args.policy if args.policy != "replay" else "fcfs"
        # an explicit --backfill (including "none") reaches the peer;
        # only the unset default maps to FastSimLike's firstfit
        backfill = args.backfill or "firstfit"
        if args.external_cmd:
            peer = tr.SubprocessPeer(cmd=args.external_cmd, policy=policy,
                                     backfill=backfill,
                                     timeout_s=args.external_timeout,
                                     wire=args.external_wire)
        else:
            peer = tr.SocketPeer(address=args.external_socket,
                                 policy=policy, backfill=backfill,
                                 timeout_s=args.external_timeout,
                                 wire=args.external_wire)
        ext_scen = T.Scenario.make("replay", cells_offline=cells_offline)
        on_event = recorder.span_listener if recorder else None
        try:
            if args.external_mode == "sequential":
                # one-shot coupling: the peer is driven directly (the
                # bridge's poll retry policy has nothing to wrap here)
                final, hist = ext.run_sequential_mode(sys_, js, peer,
                                                      t0, t1, scen=ext_scen)
            else:
                bridge = ext.SchedulerBridge(
                    peer, ext.BridgeConfig(timeout_s=args.external_timeout),
                    on_event=on_event)
                final, hist, _ = ext.run_plugin_mode(sys_, js, bridge,
                                                     t0, t1, scen=ext_scen)
        finally:
            peer.close()
        if isinstance(hist, dict):  # plugin mode returns a dict of arrays
            hist = types.SimpleNamespace(**hist)
        runs = [((policy, f"external:{args.external_mode}"), final, hist)]
    elif args.scheduler in ("fastsim", "scheduleflow"):
        ext_scen = T.Scenario.make("replay", cells_offline=cells_offline)
        if args.scheduler == "fastsim":
            sched = ext.FastSimLike(policy=args.policy
                                    if args.policy != "replay" else "fcfs")
            final, hist = ext.run_sequential_mode(sys_, js, sched, t0, t1,
                                                  scen=ext_scen)
        else:
            # explicit bridge so its poll counters reach the manifest
            bridge = ext.SchedulerBridge(
                ext.ScheduleFlowLike(),
                on_event=recorder.span_listener if recorder else None)
            final, hist = ext.run_plugin_mode(sys_, js, bridge, t0, t1,
                                              scen=ext_scen)[:2]
        if isinstance(hist, dict):  # plugin mode returns a dict of arrays
            hist = types.SimpleNamespace(**hist)
        runs = [((args.policy, "external"), final, hist)]
    elif args.sweep:
        specs = []
        for s in args.sweep:
            p, _, b = s.partition(":")
            specs.append((p, b or "none"))
        scens = [T.Scenario.make(p, b, cells_offline=cells_offline,
                                 **fail_kw)
                 for p, b in specs]
        # shards the scenario axis over the visible devices (shard_map);
        # exactly simulate_sweep when only one device is present
        finals, hists = eng.simulate_sweep_sharded(sys_, table, scens,
                                                   t0, t1, accounts,
                                                   signals=dr_signals,
                                                   weather=weather,
                                                   events=events_cfg)
        import jax
        runs = [((p, b),
                 jax.tree_util.tree_map(lambda x, i=i: x[i], finals),
                 jax.tree_util.tree_map(lambda x, i=i: x[i], hists))
                for i, (p, b) in enumerate(specs)]
    elif fail_kw:
        # stochastic failures / demand-response: traced-scenario engine
        # with the event layer enabled (repro.events)
        scen = T.Scenario.make(args.policy, backfill_cli,
                               cells_offline=cells_offline, **fail_kw)
        final, hist = eng.simulate(sys_, table, scen, t0, t1, accounts,
                                   signals=dr_signals, weather=weather,
                                   events=events_cfg)
        runs = [((args.policy, backfill_cli), final, hist)]
    elif args.cells_offline:
        # maintenance knob is traced: run the traced-scenario engine
        scen = T.Scenario.make(args.policy, backfill_cli,
                               cells_offline=cells_offline)
        final, hist = eng.simulate(sys_, table, scen, t0, t1, accounts,
                                   weather=weather)
        runs = [((args.policy, backfill_cli), final, hist)]
    else:
        # single-policy runs take the static fast path (policy/backfill are
        # compile-time constants; docs/architecture.md)
        final, hist = eng.simulate_static(sys_, table, args.policy,
                                          backfill_cli, t0, t1, accounts,
                                          weather=weather)
        runs = [((args.policy, backfill_cli), final, hist)]
    return runs, bridge


if __name__ == "__main__":
    # the process entry point, not main(): in-process callers keep their
    # own cache settings
    launch_env.enable_compile_cache()
    raise SystemExit(main())
