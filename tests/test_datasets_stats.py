"""Dataloaders, SWF round-trip, generator calibration, stats summaries."""
import os
import tempfile

import numpy as np
import pytest

from repro.core import engine as eng
from repro.core import stats as stats_mod
from repro.core import types as T
from repro.datasets import loaders
from repro.datasets.swf import read_swf, write_swf
from repro.datasets.synthetic import WorkloadSpec, event_schedule, generate
from repro.systems.config import SYSTEMS, get_system


def test_all_loaders_produce_valid_jobsets():
    for name in ("frontier", "marconi100", "fugaku", "lassen", "adastra"):
        js = loaders.load(name, n_jobs=50, days=0.25)
        sys_ = get_system(name if name != "adastra" else "adastraMI250")
        assert len(js) == 50
        assert (js.nodes >= 1).all() and (js.nodes <= sys_.n_nodes).all()
        assert (js.wall > 0).all()
        assert (js.limit >= js.wall).all()
        assert np.isfinite(js.rec_start).all()
        # trace datasets carry time series; summary datasets scalars
        if sys_.has_traces:
            assert js.power_prof.shape[1] > 1
        else:
            assert js.power_prof.shape[1] == 1
        # recorded schedule is capacity-feasible: never more nodes in use
        # than the system has
        t_grid = np.arange(0.0, js.rec_end.max(), sys_.dt * 20)
        for t in t_grid[:30]:
            running = (js.rec_start <= t) & (js.rec_end > t)
            assert js.nodes[running].sum() <= sys_.n_nodes


def test_event_schedule_respects_capacity_and_order():
    rng = np.random.default_rng(0)
    n, N = 30, 16
    submit = np.sort(rng.uniform(0, 600, n))
    wall = np.maximum(np.round(rng.uniform(60, 600, n) / 30), 1) * 30
    nodes = rng.integers(1, N + 1, n)
    start = event_schedule(submit, wall * 2, wall, nodes, N, 30.0)
    assert np.isfinite(start).all()
    assert (start >= np.ceil(submit / 30) * 30 - 1e-6).all()
    ends = start + wall
    for t in np.unique(np.concatenate([start, ends])):
        running = (start <= t) & (ends > t)
        assert nodes[running].sum() <= N


def test_swf_roundtrip():
    js = loaders.load("lassen", n_jobs=20, days=0.2)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "w.swf")
        write_swf(js, path)
        back = read_swf(path)
        assert len(back) == 20
        np.testing.assert_allclose(back.wall, np.round(js.wall), atol=1.0)
        np.testing.assert_allclose(back.nodes, js.nodes)
        np.testing.assert_allclose(back.rec_start,
                                   np.round(js.rec_start), atol=2.0)


def test_generator_hits_target_load():
    sys_ = get_system("marconi100").scaled(128)
    spec = WorkloadSpec(n_jobs=400, duration_s=86400.0, load=0.7, seed=0,
                        trace_len=1)
    js = generate(sys_, spec)
    offered = (js.nodes * js.wall).sum() / (sys_.n_nodes * 86400.0)
    assert 0.4 < offered < 1.0


def test_stats_summary_fields(small_system, small_table):
    final, hist = eng.simulate(small_system, small_table,
                               T.Scenario.make("fcfs", "first-fit"),
                               0.0, 3600.0)
    s = stats_mod.summarize(small_system, small_table, final, hist)
    for k in ("jobs_completed", "avg_wait_s", "avg_turnaround_s", "awrt_s",
              "psrt_s", "avg_system_power_mw", "avg_pue",
              "total_energy_mwh", "power_swing_mw", "hist_small"):
        assert k in s
        assert np.isfinite(s[k])
    assert s["avg_pue"] > 1.0
    assert s["power_efficiency"] <= 1.0
    out = stats_mod.format_stats(s)
    assert "avg_pue" in out


def test_stats_empty_job_set(small_system, small_jobs):
    """An all-padding table (zero real jobs) summarizes to finite zeros."""
    empty = small_jobs.select(np.zeros(len(small_jobs), dtype=bool))
    table = empty.to_table(16)
    final, hist = eng.simulate(small_system, table,
                               T.Scenario.make("fcfs", "first-fit"),
                               0.0, 10 * small_system.dt)
    s = stats_mod.summarize(small_system, table, final, hist)
    assert s["jobs_completed"] == 0.0
    assert s["avg_wait_s"] == 0.0 and s["avg_turnaround_s"] == 0.0
    assert s["hist_small"] + s["hist_medium"] + s["hist_large"] == 0
    for v in s.values():
        assert np.isfinite(v)


def test_stats_all_unfinished_jobs(small_system, small_table):
    """A window shorter than any job's runtime: nothing completes, and
    the per-job means must not divide by an empty set."""
    final, hist = eng.simulate(small_system, small_table,
                               T.Scenario.make("fcfs", "first-fit"),
                               0.0, 2 * small_system.dt)
    s = stats_mod.summarize(small_system, small_table, final, hist)
    assert s["jobs_completed"] == 0.0
    assert s["avg_job_energy_j"] == 0.0 and s["avg_job_nodes"] == 0.0
    assert s["edp"] == 0.0
    assert s["avg_system_power_mw"] >= 0.0
    for v in s.values():
        assert np.isfinite(v)


def test_stats_single_interval_run(small_system, small_table):
    """One engine step: telemetry reductions over a length-1 history."""
    final, hist = eng.simulate(small_system, small_table,
                               T.Scenario.make("fcfs", "first-fit"),
                               0.0, small_system.dt)
    assert np.asarray(hist.power_total).shape[-1] == 1
    s = stats_mod.summarize(small_system, small_table, final, hist)
    assert s["power_swing_mw"] == 0.0  # max == min over one sample
    assert s["throughput_per_hour"] >= 0.0
    for v in s.values():
        assert np.isfinite(v)


def test_lm_workload_from_roofline_artifacts():
    """The AI-workload dataset: per-node power comes from each (arch x
    shape) cell's utilisation in the analytic table, within the
    system's idle..peak envelope, and the twin schedules it."""
    from repro.core import engine as eng
    from repro.core import types as T
    from repro.datasets.lmjobs import generate_lm_workload

    sys_ = get_system("frontier").scaled(256)
    js = generate_lm_workload(sys_, n_jobs=60, duration_s=6 * 3600.0, seed=3)
    assert len(js) == 60
    assert (js.power_prof >= sys_.power.idle_node_w - 1e-3).all()
    assert (js.power_prof <= sys_.power.peak_node_w + 1e-3).all()
    assert len(js.arch_ids) == 60
    final, hist = eng.simulate(sys_, js.to_table(64),
                               T.Scenario.make("fcfs", "first-fit"),
                               0.0, 4 * 3600.0)
    assert float(final.completed) > 0
    assert np.isfinite(np.asarray(hist.power_total)).all()
