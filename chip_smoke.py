#!/usr/bin/env python
"""Run the twin on one TPU chip through its normal entry points, at
Frontier's published size (9,600 nodes), and check what comes out.

    python chip_smoke.py               # one chip: simulate, sweep, serve
    python chip_smoke.py --four-chips  # four chips: the sharded sweep only

Everything runs in this one process, which holds the chip. The phases:

* **simulate** — the ``simulate`` CLI over a day of Frontier (5,760
  steps), once under ``--policy replay`` and once under ``--policy fcfs
  --backfill easy``. Replay starts every in-window job within one step of
  its recorded start; the energy ledger equals the integral of facility
  power; the free-node count matches the node map.
* **sweep** — the CLI's ``--sweep`` of eight policy:backfill pairs; each
  row matches a single-scenario ``engine.simulate`` of its pair.
* **serve** — an in-process ``TwinServer`` over a Frontier
  ``TwinSession``, driven by the stdlib ``tools.twin_client``: advance,
  neutral fork, advance both branches, fetch, shutdown. The neutral
  fork's telemetry equals its parent's.
* **four chips** (``--four-chips`` only, nothing else runs) — the CLI's
  ``--sweep`` sharded over four chips, each row matched against the same
  sweep as one vmapped program on device 0.

Lines starting ``smoke observation:`` are what this run saw (compile
seconds, steps per second): observations of one run, not records. Any
failed check raises, and the process exits non-zero. The last line of
stdout is the device report, on success only. Without a TPU the script
exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import engine as eng  # noqa: E402
from repro.core import types as T  # noqa: E402
from repro.datasets import loaders  # noqa: E402
from repro.launch import env as launch_env  # noqa: E402
from repro.launch import simulate as cli  # noqa: E402
from repro.obs import sink as obs_sink  # noqa: E402
from repro.serve.server import TwinServer  # noqa: E402
from repro.serve.session import TwinSession  # noqa: E402
from repro.systems.config import get_system  # noqa: E402

SWEEP_PAIRS = ("fcfs:none", "fcfs:easy", "fcfs:first-fit", "sjf:first-fit",
               "sjf:easy", "ljf:easy", "priority:easy", "replay:none")
# Frontier excerpt at ``load_frontier()`` density: 1,238 jobs over a day
DATASET = ("--jobs", "1238", "--days", "1")


class CheckFailed(AssertionError):
    """A smoke check did not hold."""


def observe(name: str, value) -> None:
    print(f"smoke observation: {name} = {value}", flush=True)


def check(name: str, ok: bool, detail: str) -> None:
    print(f"check {name}: {'ok' if ok else 'FAILED'} ({detail})",
          flush=True)
    if not ok:
        raise CheckFailed(f"{name}: {detail}")


@contextlib.contextmanager
def capture(name: str):
    """Record (args, result) of every ``engine.<name>`` call made inside
    the block: what the CLI computed, for the checks to read."""
    calls = []
    orig = getattr(eng, name)

    def spy(*args, **kwargs):
        out = orig(*args, **kwargs)
        calls.append((args, out))
        return out
    setattr(eng, name, spy)
    try:
        yield calls
    finally:
        setattr(eng, name, orig)


def _frontier_argv(scale: int, hours: float) -> list:
    argv = ["--system", "frontier", "-t", f"{hours:g}h", *DATASET, "--quiet"]
    return argv + (["--scale", str(scale)] if scale else [])


def _run_cli(argv: list) -> None:
    rc = cli.main(argv)
    check("cli exit code", rc == 0, f"simulate {' '.join(argv[:6])} ... "
          f"returned {rc}")


def _rows_match(name: str, got_hist, got_final, want_hist, want_final):
    got_p = np.asarray(got_hist.power_it)
    want_p = np.asarray(want_hist.power_it)
    err = float(np.max(np.abs(got_p - want_p) /
                       np.maximum(np.abs(want_p), 1e-30)))
    check(f"{name} power_it", got_p.shape == want_p.shape and
          np.allclose(got_p, want_p, rtol=1e-5, atol=0.0),
          f"max rel err {err:.3g} over {got_p.shape} (rtol 1e-5)")
    got_c, want_c = float(got_final.completed), float(want_final.completed)
    check(f"{name} completed", got_c == want_c,
          f"{got_c:g} vs {want_c:g}")


def _row(tree, i):
    return jax.tree_util.tree_map(lambda x: x[i], tree)


def _observe_distinct(name: str, hists) -> None:
    # a row-by-row match can only catch swapped rows where rows differ
    rows = np.asarray(hists.power_it)
    observe(f"{name} distinct power_it rows",
            f"{len({r.tobytes() for r in rows})} of {rows.shape[0]}")


def phase_simulate(scale: int = 0, hours: float = 24.0) -> None:
    """The CLI's static single-scenario path, replay then fcfs+EASY."""
    for policy in (["--policy", "replay"],
                   ["--policy", "fcfs", "--backfill", "easy"]):
        label = ":".join(policy[1::2])
        with tempfile.TemporaryDirectory() as tmp, \
                capture("simulate_static") as calls:
            manifest = pathlib.Path(tmp) / "run.json"
            _run_cli(_frontier_argv(scale, hours) + policy +
                     ["--manifest", str(manifest)])
            spans = json.loads(manifest.read_text())["spans"]["spans"]
        (system, table, _, _, t0, t1, *_), (final, hist) = calls[0]
        n_steps = int(round((t1 - t0) / system.dt))
        scan_s = spans["engine.scan"]["total_s"]
        observe(f"simulate {label} nodes", system.n_nodes)
        observe(f"simulate {label} steps", n_steps)
        observe(f"simulate {label} compile_s",
                spans["engine.compile"]["total_s"])
        observe(f"simulate {label} steady_steps_per_s", n_steps / scan_s)

        power = np.asarray(hist.power_total, np.float64)
        check(f"simulate {label} telemetry", power.shape == (n_steps,)
              and bool(np.isfinite(power).all()),
              f"power_total shape {power.shape}, all finite")
        e_hist = power.sum() * system.dt
        e_led = float(final.energy_total)
        check(f"simulate {label} energy", np.isclose(e_hist, e_led,
                                                     rtol=1e-4),
              f"sum(power*dt) {e_hist:.9g} J vs energy_total "
              f"{e_led:.9g} J (rtol 1e-4)")
        node_job = np.asarray(final.node_job)
        free = int(final.free_count)
        check(f"simulate {label} free_count", free == int(
            (node_job < 0).sum()), f"free_count {free} vs "
            f"{int((node_job < 0).sum())} free entries in node_job")
        if label == "replay":
            valid = np.asarray(table.valid)
            rec = np.asarray(table.rec_start, np.float64)[valid]
            wall = np.asarray(table.wall, np.float64)[valid]
            start = np.asarray(final.start, np.float64)[valid]
            jstate = np.asarray(final.jstate)[valid]
            started = (jstate == T.RUNNING) | (jstate == T.DONE)
            in_window = (rec + wall > t0) & (rec < t1 - system.dt)
            err = np.abs(start - rec)[started & in_window]
            check("simulate replay schedule",
                  bool(started[in_window].all()) and err.size > 0 and
                  float(err.max()) <= system.dt + 1e-3,
                  f"{int(in_window.sum())} in-window jobs, "
                  f"{int(started[in_window].sum())} started, max |start - "
                  f"recorded| {float(err.max()) if err.size else -1:g} s "
                  f"(dt {system.dt:g} s)")


def phase_sweep(scale: int = 0, hours: float = 24.0) -> None:
    """The CLI's --sweep: eight pairs as one program, row by row against
    single-scenario runs."""
    t = time.perf_counter()
    with capture("simulate_sweep_sharded") as calls:
        _run_cli(_frontier_argv(scale, hours) + ["--sweep", *SWEEP_PAIRS])
    observe("sweep wall_s (compile + run)", time.perf_counter() - t)
    (system, table, scens, t0, t1, *_), (finals, hists) = calls[0]
    _observe_distinct("sweep", hists)
    t = time.perf_counter()
    for i, pair in enumerate(SWEEP_PAIRS):
        final, hist = eng.simulate(system, table, scens[i], t0, t1)
        _rows_match(f"sweep row {pair}", _row(hists, i), _row(finals, i),
                    hist, final)
    observe("sweep single-scenario reference wall_s",
            time.perf_counter() - t)


def phase_serve(scale: int = 0, hours: float = 2.0,
                interval_steps: int = 120) -> None:
    """An in-process TwinServer driven by the stdlib client."""
    from tools.twin_client import TwinClient

    system = get_system("frontier")
    system = system.scaled(scale) if scale else system
    js = loaders.load("frontier", system=system)
    js.assign_prepop_placement(0.0, system.n_nodes)
    session = TwinSession(system, js.to_table(),
                          T.Scenario.make("fcfs", "easy"), 0.0,
                          hours * 3600.0, interval_steps=interval_steps)
    t = time.perf_counter()
    with TwinServer(session, "127.0.0.1:0") as srv, \
            TwinClient(srv.address, timeout_s=900.0) as client:
        def ok(reply, kind):
            check(f"serve {kind} reply", reply.get("kind") == f"{kind}_ok",
                  f"kind {reply.get('kind')!r}")
            return reply
        ok(client.advance(0, 2), "advance")
        fork = ok(client.fork(0), "fork")
        child, born = fork["branch"], fork["born_step"]
        ok(client.advance(0, 2), "advance")
        ok(client.advance(child, 2), "advance")
        stop = born + 2 * interval_steps
        parent_rows = ok(client.fetch(0, born, stop), "fetch")["rows"]
        child_rows = ok(client.fetch(child, born, stop), "fetch")["rows"]
        ok(client.shutdown(), "shutdown")
    observe("serve nodes", system.n_nodes)
    observe("serve wall_s (compile + 6 advanced intervals)",
            time.perf_counter() - t)
    fields = ("t",) + obs_sink.SCALAR_FIELDS
    got = np.asarray([[r[k] for k in fields] for r in child_rows])
    want = np.asarray([[r[k] for k in fields] for r in parent_rows])
    check("serve neutral fork", got.shape == want.shape ==
          (2 * interval_steps, len(fields)) and
          np.allclose(got, want, rtol=1e-5, atol=0.0),
          f"{got.shape[0]} rows x {len(fields)} fields vs parent "
          f"(rtol 1e-5)")


def phase_four_chips(scale: int = 0, hours: float = 24.0) -> None:
    """The CLI's --sweep sharded over four chips, against the same sweep
    vmapped on device 0."""
    check("four chips present", len(jax.devices()) >= 4,
          f"{len(jax.devices())} devices")
    t = time.perf_counter()
    with capture("simulate_sweep_sharded") as calls:
        _run_cli(_frontier_argv(scale, hours) + ["--sweep", *SWEEP_PAIRS])
    observe("four-chip sweep wall_s (compile + run)",
            time.perf_counter() - t)
    (system, table, scens, t0, t1, *_), (finals, hists) = calls[0]
    n_dev = len(hists.power_it.sharding.device_set)
    check("four-chip sweep sharded", n_dev == len(jax.devices()),
          f"telemetry spread over {n_dev} devices")
    _observe_distinct("four-chip sweep", hists)
    t = time.perf_counter()
    ref_finals, ref_hists = eng.simulate_sweep(system, table, scens, t0, t1)
    observe("device-0 vmapped sweep wall_s (compile + run)",
            time.perf_counter() - t)
    check("reference on device 0",
          ref_hists.power_it.sharding.device_set == {jax.devices()[0]},
          f"{ref_hists.power_it.sharding}")
    for i, pair in enumerate(SWEEP_PAIRS):
        _rows_match(f"four-chip row {pair}", _row(hists, i), _row(finals, i),
                    _row(ref_hists, i), _row(ref_finals, i))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded sweep on four chips and its "
                         "device-0 reference")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    launch_env.enable_compile_cache()
    cache = {"requests": 0, "hits": 0}

    def count(event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            cache["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
    jax.monitoring.register_event_listener(count)
    t = time.perf_counter()
    if args.four_chips:
        phase_four_chips()
    else:
        phase_simulate()
        phase_sweep()
        phase_serve()
    observe("total wall_s", time.perf_counter() - t)
    observe("persistent compile cache hits / lookups",
            f"{cache['hits']} / {cache['requests']}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
