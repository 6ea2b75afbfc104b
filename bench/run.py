#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``configs/<name>.json``: the machine and its job backlog)
and a traffic mix (``traffic/<name>.json``: the what-if study, S
scenarios over a horizon). Its limits are ``limits/<cell>.json`` and each
per-layer metric is read by ``metrics/<metric>.py``; everything is found
by name.

Set-up makes the backlog from ``--seed``, puts the job table on the
device once and runs one whole study, which compiles (or loads from the
persistent cache) every program a study uses. The window then runs whole
studies back to back until ``--seconds`` have passed; the study under
way finishes. ``sim_speedup`` is the simulated scenario-seconds of every
completed study over the window's wall time. With ``--trace 1`` the
window is two studies, and the profiler traces their boundary: the end
of one study's scan, its results and summaries, the next dispatch and
the start of the next scan (a whole scan holds more device events than
the profiler keeps); the line carries the per-layer metrics.
After the window, rows drawn from the seed are compared with the plain
reference (``check.py``).

The last line of stdout is one JSON object; the numbers compared, each
beside its limit, are the last lines of stderr. Without an accelerator,
or with fewer devices than the cell asks for, it exits 2 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import concurrent.futures  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
CACHE_DIR = BENCH / ".jax_cache"
TRACE_DIR = BENCH / ".traces"
LEAD_S = 2.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(name: str) -> dict:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = by_name(spec["workloads"], name, "workload")
    conf = by_name(spec["configs"], cell["config"], "config")
    read = lambda p: json.loads(p.read_text())
    return dict(
        spec=spec, cell=cell,
        config=read(ROOT / conf["file"]),
        traffic=read(BENCH / "traffic" / f"{cell['traffic']}.json"),
        limits=read(BENCH / "limits" / f"{name}.json"))


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts the executables JAX builds or loads from its persistent
    cache (one ``backend_compile_duration`` event each), and the seconds
    spent building programs: tracing, lowering and compiling."""

    BUILD = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration",
             "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.n, self.s, self.build_s = 0, 0.0, 0.0

        def on(event, duration, **_):
            if event == self.BUILD[-1]:
                self.n += 1
                self.s += duration
            if event in self.BUILD:
                self.build_s += duration
        jax.monitoring.register_event_duration_secs_listener(on)


def run_study(jax, study):
    """One study: dispatch, wait for the device, collect. Returns (host
    (final, history), {"dispatch", "wait", "collect"} seconds)."""
    t0 = time.perf_counter()
    out = study.dispatch()
    t1 = time.perf_counter()
    jax.block_until_ready(out)
    t2 = time.perf_counter()
    final, hist, _ = study.collect(out)
    t3 = time.perf_counter()
    return (final, hist), dict(dispatch=t1 - t0, wait=t2 - t1,
                               collect=t3 - t2)


def traced_studies(jax, study, run_s: float):
    """Two studies, B and C, with the profiler on around their boundary:
    from about ``LEAD_S`` before B's results are ready (``run_s`` is the
    warm-up's dispatch and wait, less the time spent building programs)
    until ``LEAD_S`` after C is dispatched. The whole scan of a study
    holds more device events than the profiler keeps. A worker thread
    dispatches each study and waits for it, since one entry
    (``simulate_static``) returns only when its scan has ended. Returns
    ([B, C] host outputs, the window's seconds, and B's seconds from
    dispatch to results on the device and to results summarised)."""
    span = jax.profiler.TraceAnnotation
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    shutil.rmtree(TRACE_DIR, ignore_errors=True)

    def until_ready():
        return jax.block_until_ready(study.dispatch())

    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        t0 = time.perf_counter()
        fut_b = pool.submit(until_ready)
        time.sleep(max(run_s - LEAD_S, 0.0))
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
        with span("bench.window"):
            with span("bench.wait"):
                out_b = fut_b.result()
            t_ready = time.perf_counter()
            with span("bench.collect"):
                final, hist, _ = study.collect(out_b)
            t_done = time.perf_counter()
            with span("bench.dispatch"):
                fut_c = pool.submit(until_ready)
                time.sleep(LEAD_S)
        jax.profiler.stop_trace()
        b = (final, hist)
        final, hist, _ = study.collect(fut_c.result())
    return ([b, (final, hist)], time.perf_counter() - t0, t_ready - t0,
            t_done - t0)


def main(argv=None, require_accelerator: bool = True,
         loaded: dict | None = None, cache_dir: str | None = None) -> int:
    """``loaded`` stands in for what ``load_cell`` reads (tests run a
    small cell with it); ``require_accelerator=False`` lets a run go on
    without a chip (tests on the CPU only). ``cache_dir`` turns on JAX's
    persistent compilation cache there, with no minimum compile time."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    c = loaded or load_cell(args.workload)
    cell, config, traffic = c["cell"], c["config"], c["traffic"]

    import jax
    if cache_dir:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if require_accelerator and (devices[0].platform == "cpu" or
                                len(devices) < int(cell["chips"])):
        log(f"run.py: cell {cell['name']} needs {cell['chips']} "
            f"accelerator chip(s); JAX found {len(devices)} "
            f"{devices[0].platform} device(s)")
        return 2
    compiles = CompileCounter(jax)
    t_import = time.perf_counter() - T_START

    import check
    import program
    import workload
    t = time.perf_counter()
    jobs = workload.make_jobs(config, args.seed)
    study = program.Study(config, traffic, jobs)
    jax.block_until_ready(study.table)
    t_data = time.perf_counter() - t
    t = time.perf_counter()
    warm, warm_t = run_study(jax, study)
    t_warm = time.perf_counter() - t
    setup_s = time.perf_counter() - T_START
    log(f"setup: import {t_import:.3f} s, data {t_data:.3f} s, compile "
        f"{compiles.s:.3f} s ({compiles.n} executables; with tracing and "
        f"lowering {compiles.build_s:.3f} s), warm-up study "
        f"{t_warm - compiles.build_s:.3f} s, total {setup_s:.3f} s")

    S = len(study.rows)
    n_before = compiles.n
    studies = [warm]
    if args.trace:
        window, elapsed, ready_s, cycle_s = traced_studies(
            jax, study, warm_t["dispatch"] + warm_t["wait"] -
            compiles.build_s)
        studies += window
    else:
        t0 = time.perf_counter()
        while True:
            out, _ = run_study(jax, study)
            studies.append(out)
            elapsed = time.perf_counter() - t0
            if elapsed >= args.seconds:
                break
    n_studies = len(studies) - 1
    in_window = compiles.n - n_before
    log(f"window: {n_studies} studies of {S} scenarios x "
        f"{study.n_steps} steps in {elapsed:.3f} s; compilations in the "
        f"window: {in_window}")
    stats = [d.memory_stats() or {} for d in devices]
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}

    result = {}
    if args.trace:
        ctx = dict(ready_s=ready_s, cycle_s=cycle_s,
                   rows_per_device=S / len(devices), n_steps=study.n_steps)
        result.update(traced(c, ctx, device))
    else:
        result["metrics"] = {
            "sim_speedup": {"value": n_studies * S * study.horizon /
                            elapsed, "unit": "sim-s/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    del study
    t = time.perf_counter()
    nums, picked, failed = check.check(config, traffic, jobs, studies,
                                       args.seed, c["limits"])
    correct = all(v <= lim for v, lim in nums.values() if lim is not None)
    correct = correct and in_window == 0
    log(f"check: rows {picked} of {S} in {time.perf_counter() - t:.3f} s; "
        f"compilations in the window {in_window} (limit 0)")
    for k, (v, lim) in nums.items():
        log(f"check {k} = {v!r} (limit {lim!r})")
    line = {"correct": bool(correct), "attempted": n_studies * S,
            "failed": int(failed), "metrics": result.pop("metrics"),
            "device": device}
    line.update(result)
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in nums.items()}
    line["checks"]["window_compiles"] = {"value": in_window, "limit": 0}
    print(json.dumps(line), flush=True)
    return 0


def traced(c, ctx, device) -> dict:
    """Per-layer metrics and the breakdown from the traced window;
    ``ctx`` holds the host-clock numbers the readers need."""
    import trace_reduce as tr
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(tr.latest_xplane(str(TRACE_DIR)))
    spans = tr.host_spans(profile)
    win = [s for s in spans if s[0] == "bench.window"]
    lo, hi = (win[0][1], win[0][2]) if win else (0.0, float("inf"))
    red = tr.reduce(profile, lo, hi)
    del profile
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    devs = red["devices"]
    ctx = dict(ctx, trace=red, spans=spans)
    metrics = {}
    for m in c["spec"]["per_layer"]:
        if c["cell"]["name"] not in m.get("workloads", [c["cell"]["name"]]):
            continue
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device["busy_s"] = (sum(d["busy_s"] for d in devs) / len(devs)
                        if devs else 0.0)
    device["window_s"] = red["window_s"]
    ops, codes = {}, {}
    for d in devs:
        for name, s in d["op_s"].items():
            ops[name] = ops.get(name, 0.0) + s / len(devs)
        for name, s in d["opcode_s"].items():
            codes[name] = codes.get(name, 0.0) + s / len(devs)
    scans = [(d["long_module_busy_s"], d["long_module_s"], d["scan_steps"])
             for d in devs]
    bounds = [(d["boundary_busy_s"], d["boundary_s"]) for d in devs]
    log(f"trace: window {red['window_s']!r} s, busy per device "
        f"{[d['busy_s'] for d in devs]}, scans (busy, length, steps) "
        f"{scans}, boundary (busy, length) {bounds}, opcodes "
        f"{sorted(codes.items(), key=lambda x: -x[1])[:12]}")
    gaps = devs[0]["gaps"] if devs else []
    return dict(metrics=metrics, breakdown={
        "device_ops": sorted(([k, v] for k, v in ops.items()),
                             key=lambda x: -x[1])[:10],
        "idle_gaps": tr.name_gaps(gaps, spans)})


if __name__ == "__main__":
    raise SystemExit(main(cache_dir=os.environ.get(
        "JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)))
