#!/usr/bin/env python3
"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds <n> \
        --control-seeds <m> --first-seed <s> [--out <file>]

In one process (one set-up on the chip) it runs one study of the cell
for each of ``n`` seeds and compares every row with the plain reference:
the largest of each number over these sound runs is the lower reading.
Then the control, the reference computed in bfloat16 and put in the
program's place, is compared in the same way on ``m`` seeds: the
smallest of each number over the control's rows is the upper reading.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import concurrent.futures as cf
import multiprocessing as mp
import json
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

import numpy as np  # noqa: E402

import check  # noqa: E402
import reference  # noqa: E402
import workload  # noqa: E402

CONTROL_ROUNDING = "bfloat16"


def control_outputs(system: dict, jobs: dict, row: dict,
                    horizon_s: float) -> dict:
    """What the bfloat16 reference reports in the program's place."""
    import ml_dtypes  # registers bfloat16 with numpy
    del ml_dtypes
    out = reference.run(system, jobs, row["policy"], row["backfill"],
                        horizon_s, row["cells_offline"],
                        rounding=CONTROL_ROUNDING)
    h = out["hist"]
    return dict(start=out["start"], jstate=out["jstate"],
                node_job=out["node_job"], free_count=out["free_count"],
                completed=float(out["completed"]),
                energy_total=out["energy_total"],
                power_it=h["power_it"], power_cooling=h["power_cooling"],
                power_total=h["power_total"])


def _control_row(args):
    system, jobs, row, horizon = args
    got = control_outputs(system, jobs, row, horizon)
    return check.compare_row(system, jobs, row, horizon, got)


def _program_row(args):
    return check.compare_row(*args)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import run
    c = run.load_cell(args.workload)
    config, traffic = c["config"], c["traffic"]
    system = config["system"]
    horizon = float(traffic["horizon_s"])
    import program
    rows = program.rows_of(traffic)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    cseeds = [args.first_seed + 104729 * (i + 1)
              for i in range(args.control_seeds)]
    report = {"workload": args.workload, "program": [], "control": []}
    # spawned workers import only numpy code: they never touch the chip
    pool = cf.ProcessPoolExecutor(max_workers=max(os.cpu_count() - 1, 1),
                                  mp_context=mp.get_context("spawn"))
    if args.seeds:
        import jax
        jax.config.update("jax_compilation_cache_dir", os.environ.get(
            "JAX_COMPILATION_CACHE_DIR") or str(run.CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        if jax.devices()[0].platform == "cpu":
            print("calibrate.py: needs an accelerator", file=sys.stderr)
            return 2
        for seed in seeds:
            t = time.perf_counter()
            jobs = workload.make_jobs(config, seed)
            study = program.Study(config, traffic, jobs)
            (final, hist), _ = run.run_study(jax, study)
            t_study = time.perf_counter() - t
            jobs_np = {k: np.asarray(v) for k, v in jobs.items()}
            tasks = [(system, jobs_np, rows[i], horizon,
                      program.row_outputs(final, hist, i))
                     for i in range(len(rows))]
            res = list(pool.map(_program_row, tasks))
            for i, r in enumerate(res):
                report["program"].append(dict(seed=seed, row=i, **r))
            print(f"program seed {seed}: study {t_study:.3f} s, worst "
                  f"{_worst(res)}", flush=True)
    for seed in cseeds:
        jobs = workload.make_jobs(config, seed)
        cpick = check.sample_rows(len(rows), int(traffic["check_strata"]),
                                  seed)
        res = list(pool.map(_control_row, [(system, jobs, rows[i], horizon)
                                           for i in cpick]))
        for i, r in zip(cpick, res):
            report["control"].append(dict(seed=seed, row=i, **r))
        print(f"control seed {seed}: least {_least(res)}", flush=True)
    pool.shutdown()
    summary = {"lower": _worst(report["program"]),
               "upper": _least(report["control"])}
    report["summary"] = summary
    print(json.dumps(summary), flush=True)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(report, indent=1))
    return 0


def _worst(rs):
    return {k: max(r[k] for r in rs) for k in check.NAMES[:-1]} if rs \
        else {}


def _least(rs):
    return {k: min(r[k] for r in rs) for k in check.NAMES[:-1]} if rs \
        else {}


if __name__ == "__main__":
    raise SystemExit(main())
