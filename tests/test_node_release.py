"""Node release by the per-node end time, against the gather oracle.

The engine frees a node when ``t >= node_end[n]``, the end time written
beside the node map wherever a job gains nodes, instead of gathering the
per-job completion flag onto every node (``rm.release_done_gather``).
That is exact only while ``node_end[n]`` is the very f32 value of its
job's ``end``, which DVFS breaks on the grid path: there the engine keeps
the gather. Each placement writer of ``test_group_occupancy`` runs at 64
nodes step by step; after every step the per-node end times must equal
the jobs' end times (bound them on the grid path), and the step must
leave the same node map and job state as the same step released through
the gather.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_signals
from repro.core import engine as eng
from repro.core import resource_manager as rm
from repro.core import types as T
from repro.obs import timing
from test_group_occupancy import (CASES, N_STEPS, T0, _external_ids,
                                  _system, _table)

RESULT = ("node_job", "jstate", "start", "end", "free_count")


def _step_fn(case, system, table, signals, events):
    """Jitted (state -> state) of the case's writer, traced on call."""
    _, policy, backfill, grid_admission, _ = CASES[case]
    if policy is None:
        ext = eng.external_step.__wrapped__        # a fresh trace each time
        return jax.jit(lambda s, ids: ext(system, table, s, ids)[0])
    scen = T.Scenario.make(policy, backfill, node_fail_rate=(
        2e-4 if events else 0.0), repair_s=600.0, failure_seed=3.0)
    step = functools.partial(
        eng.engine_step, system, table, scen=scen,
        signals=signals if grid_admission else None, events=events)
    return jax.jit(lambda s: step(s)[0])


def _compiled(fn, st, ids):
    args = (st,) if ids is None else (st, ids)
    return fn.lower(*args).compile()


def assert_node_end_matches_jobs(st, exact, what):
    node_job = np.asarray(st.node_job)
    occupied = node_job >= 0
    got = np.asarray(st.node_end)[occupied]
    want = np.asarray(st.end)[node_job[occupied]]
    if exact:
        np.testing.assert_array_equal(got, want, err_msg=what)
    else:
        assert (got <= want).all(), what
    return int((got < want).sum())


@pytest.mark.parametrize("case", sorted(CASES))
def test_node_end_release_matches_gather_at_every_step(case, monkeypatch):
    halls, policy, _, grid_admission, events = CASES[case]
    system = _system(halls)
    table = _table(system)
    signals = jax.tree_util.tree_map(jnp.asarray,
                                     make_signals(system, N_STEPS + 1))
    st = eng.init_state(system, table, T0, T0 + N_STEPS * system.dt,
                        num_accounts=8, events=events)
    ids = _external_ids(table, st) if policy is None else None

    # (c) prepopulated nodes carry their jobs' recorded end
    node_job = np.asarray(st.node_job)
    assert (node_job >= 0).any(), "nothing prepopulated"
    rec_end = np.asarray(table.rec_start) + np.asarray(table.wall)
    np.testing.assert_array_equal(np.asarray(st.node_end)[node_job >= 0],
                                  rec_end[node_job[node_job >= 0]])

    before = dict(timing.RELEASE_STATS)
    step = _compiled(_step_fn(case, system, table, signals, events), st, ids)
    took = "job_gather" if grid_admission else "node_end"
    assert timing.RELEASE_STATS[took] == before[took] + 1
    assert sum(timing.RELEASE_STATS.values()) == sum(before.values()) + 1

    original = eng._prepare_and_arrivals
    monkeypatch.setattr(eng, "_prepare_and_arrivals",
                        lambda system_, table_, st_, has_grid_:
                        original(system_, table_, st_, True))
    oracle = _compiled(_step_fn(case, system, table, signals, events), st,
                       ids)
    monkeypatch.undo()

    freed = stretched = 0
    for k in range(N_STEPS + 1):
        if k:
            args = (st,) if ids is None else (st, ids)
            want = oracle(*args)
            got = step(*args)
            for name in RESULT:
                np.testing.assert_array_equal(
                    np.asarray(getattr(got, name)),
                    np.asarray(getattr(want, name)),
                    err_msg=f"{case} step {k}: {name}")
            freed += int(np.sum((np.asarray(st.node_job) >= 0)
                                & (np.asarray(got.node_job) == -1)))
            st = got
            if policy is None:
                ids = _external_ids(table, st)
        # (a) every occupied node carries its job's end (a lower bound
        # of it where DVFS stretches the end)
        stretched += assert_node_end_matches_jobs(
            st, exact=not grid_admission, what=f"{case} step {k}")
    assert freed > 0, "no node was released"
    if grid_admission:
        assert stretched > 0, "the cap never stretched a running job"


def test_release_compare_equals_gather_on_consistent_maps():
    """Unit check at Frontier's width: free (-1), down (-2) and occupied
    nodes, end times equal to, just above and just below ``t``."""
    rng = np.random.default_rng(0)
    J, N = 1238, 9600
    t = np.float32(43215.0)
    end = (t + rng.choice([-15.0, 0.0, np.spacing(t), 15.0, np.inf], J)
           ).astype(np.float32)
    running = rng.random(J) < 0.8
    node_job = rng.choice(np.concatenate([[-2, -1], np.arange(J)]),
                          N).astype(np.int32)
    node_job = np.where((node_job >= 0) & ~running[np.maximum(node_job, 0)],
                        -1, node_job)
    node_end = np.where(node_job >= 0, end[np.maximum(node_job, 0)],
                        np.float32(-7.0))          # never read off a job
    done_now = running & (t >= end)
    got = rm.release_done(jnp.asarray(node_job), jnp.asarray(node_end),
                          jnp.float32(t))
    want = rm.release_done_gather(jnp.asarray(node_job),
                                  jnp.asarray(done_now))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert 0 < int(np.sum(np.asarray(got) != node_job)) < (node_job >= 0).sum()
