"""The comparison catches what it must, on the CPU at 64 nodes.

A whole run of a small cell goes through ``run.main`` with the look for a
chip skipped: sound, it reads correct; with the timed path broken
underneath, it reads not correct. The faults a study can have: a step
that returns its state unchanged, half of the scenario batch left out
(its rows copied from the other half), and an answer altered where it is
produced (one job's start moved by a step in every row), each planted in
every cell that can have it (a one-row study has no half to leave out).
Scenario rows never exchange data between chips, so there is no
exchange to leave out.
The control, the reference computed in bfloat16 in the program's place,
fails the committed limits of every cell.
"""
import collections
import contextlib
import io
import json

import jax
import numpy as np
import pytest

import bench_small as bs
import calibrate
import check
import program
import run
import workload
from repro.core import engine

SPEC = json.loads((bs.ROOT / "BENCHMARK.json").read_text())


def small(config="frontier", mix="policy16", limits="frontier.policy16"):
    return dict(spec=SPEC,
                cell=dict(name=limits, config=config, traffic=mix, chips=1),
                config=bs.scaled(bs.load("configs", config), 64, 300,
                                 6 * 3600.0),
                traffic=bs.traffic(mix, 4 * 3600.0),
                limits=bs.load("limits", limits))


def run_small(loaded, seed=20261016, trace=0):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", "small", "--seed", str(seed),
                       "--seconds", "0", "--trace", str(trace)],
                      require_accelerator=False, loaded=loaded)
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1])


CELLS = [w["name"] for w in SPEC["workloads"]]
SWEEPS = [c for c in CELLS
          if run.load_cell(c)["traffic"]["entry"] == "sweep"]


def small_cell(cell):
    c = run.load_cell(cell)
    return small(c["cell"]["config"], c["cell"]["traffic"], cell)


def test_sound_run_is_correct():
    line = run_small(small())
    assert line["correct"] and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert line["metrics"]["sim_speedup"]["value"] > 0


def test_traced_run_is_correct_and_reads_its_window():
    line = run_small(small(mix="replay1", limits="frontier.replay1"),
                     trace=1)
    assert line["correct"] and line["attempted"] == 2
    assert list(line)[-1] == "checks"
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert line["device"]["window_s"] > run.LEAD_S
    assert 0 < line["metrics"]["host_summary_share"]["value"] < 1
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell", CELLS)
def test_state_left_unchanged_is_caught(cell, monkeypatch):
    real = engine.engine_step

    def frozen(system, table, st, *a, **k):
        _, rec = real(system, table, st, *a, **k)
        return st, rec
    monkeypatch.setattr(engine, "engine_step", frozen)
    monkeypatch.setattr(engine, "_SWEEP_CACHE", collections.OrderedDict())
    monkeypatch.setattr(engine, "_STATIC_CACHE", {})
    line = run_small(small_cell(cell))
    assert not line["correct"]
    assert line["checks"]["wrong_steps"]["value"] > 0


@pytest.mark.parametrize("cell", SWEEPS)
def test_half_the_batch_left_out_is_caught(cell, monkeypatch):
    real = program.Study.dispatch

    def half(self):
        final, hist = real(self)
        S = len(self.rows)
        idx = np.arange(S) % (S // 2)
        return jax.tree_util.tree_map(lambda x: x[idx], (final, hist))
    monkeypatch.setattr(program.Study, "dispatch", half)
    line = run_small(small_cell(cell))
    assert not line["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_an_altered_start_is_caught(cell, monkeypatch):
    real = program.Study.dispatch

    def altered(self):
        final, hist = real(self)
        start = np.asarray(final.start)
        j = int(np.argmax(np.isfinite(start[0]) & (start[0] > 0)))
        final.start = final.start.at[:, j].add(self.system.dt)
        return final, hist
    monkeypatch.setattr(program.Study, "dispatch", altered)
    line = run_small(small_cell(cell))
    assert not line["correct"]
    assert line["checks"]["wrong_steps"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_the_bfloat16_control_fails_every_cell(cell):
    c = run.load_cell(cell)
    loaded = small_cell(cell)
    config, traffic = loaded["config"], loaded["traffic"]
    rows = program.rows_of(traffic)
    for seed in (1, 2, 3):
        jobs = workload.make_jobs(config, seed)
        row = rows[check.sample_rows(len(rows), 4, seed)[0]]
        got = calibrate.control_outputs(config["system"], jobs, row,
                                        traffic["horizon_s"])
        nums = check.compare_row(config["system"], jobs, row,
                                 traffic["horizon_s"], got)
        assert any(nums[k] > v for k, v in c["limits"].items()
                   if k in nums), nums
