"""Unit tests for policy keys, queue ordering, EASY shadow machinery."""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import engine as eng
from repro.core import scheduler as sched
from repro.core import types as T
from repro.datasets.synthetic import WorkloadSpec, generate
from repro.systems.config import get_system


@pytest.fixture(scope="module")
def system():
    return get_system("marconi100").scaled(32)


def make_table(system, **kw):
    spec = WorkloadSpec(n_jobs=40, duration_s=7200.0, trace_len=4,
                        seed=kw.pop("seed", 1), **kw)
    return generate(system, spec).to_table()


def test_policy_key_orderings(system):
    table = make_table(system)
    accounts = T.AccountStats.zeros(64)
    for name, expect in [
        ("fcfs", np.asarray(table.submit)),
        ("sjf", np.asarray(table.limit)),
        ("ljf", -np.asarray(table.nodes, np.float64)),
        ("priority", -np.asarray(table.priority)),
    ]:
        scen = T.Scenario.make(name)
        key = np.asarray(sched.policy_key(table, accounts, scen))
        np.testing.assert_allclose(key, expect.astype(np.float32), rtol=1e-6)


def test_queue_order_puts_eligible_first(system):
    table = make_table(system)
    st = eng.init_state(system, table, 0.0, 7200.0)
    # force a known time so some jobs are queued
    st = dataclasses.replace(st, t=jnp.float32(1800.0),
                             jstate=jnp.where(table.submit <= 1800.0,
                                              T.QUEUED, T.PENDING))
    order, elig = sched.queue_order(table, st, st.accounts,
                                    T.Scenario.make("fcfs"))
    order = np.asarray(order)
    elig = np.asarray(elig)
    n = elig.sum()
    assert elig[order[:n]].all()
    assert not elig[order[n:]].any()
    submits = np.asarray(table.submit)[order[:n]]
    assert (np.diff(submits) >= 0).all()


def test_shadow_time_computation(system):
    """Craft a running set and verify the EASY shadow: 3 running jobs
    releasing 8 nodes each at t=100/200/300; free=4. A job needing 16 nodes
    waits until t=200 (4+8+8 >= 16); extra = 4."""
    table = make_table(system)
    J = table.num_jobs
    jstate = jnp.full((J,), T.DISMISSED, jnp.int32)
    end = jnp.full((J,), jnp.inf, jnp.float32)
    nodes = np.asarray(table.nodes).copy()
    limit = np.asarray(table.limit).copy()
    for i, e in enumerate([100.0, 200.0, 300.0]):
        jstate = jstate.at[i].set(T.RUNNING)
        end = end.at[i].set(e)
        nodes[i] = 8
    table2 = T.JobTable(**{**vars(table),
                           "nodes": jnp.asarray(nodes, jnp.int32),
                           "limit": jnp.asarray(limit)})
    st = eng.init_state(system, table2, 0.0, 7200.0)
    # only the release profile is read: the node map and its per-group
    # summary (``job_group_nodes``) stay as init_state left them
    st = dataclasses.replace(st, jstate=jstate,
                             start=jnp.where(end < jnp.inf, 0.0, jnp.inf),
                             end=end)
    # release profile uses start+limit as the EASY estimate; set limit=end
    limit[:3] = [100.0, 200.0, 300.0]
    table3 = T.JobTable(**{**vars(table2), "limit": jnp.asarray(
        limit, jnp.float32)})
    end_sorted, cum = sched.release_profile(table3, st)
    shadow_t, extra = sched.shadow_for(end_sorted, cum, jnp.int32(4),
                                       jnp.int32(16))
    assert float(shadow_t) == 200.0
    assert int(extra) == 4


def test_easy_never_delays_head_job(system):
    """The head job's start under fcfs-easy must not be later than under
    fcfs-nobf (EASY's defining property, given truthful limits)."""
    spec = WorkloadSpec(n_jobs=60, duration_s=7200.0, load=1.8, trace_len=4,
                        mean_wall_s=1800.0, seed=5, max_frac_nodes=0.6)
    js = generate(system, spec)
    # truthful limits: EASY's no-delay guarantee assumes limit == wall
    js.limit = js.wall.copy()
    table = js.to_table()
    f_none, _ = eng.simulate(system, table, T.Scenario.make("fcfs", "none"),
                             0.0, 7200.0)
    f_easy, _ = eng.simulate(system, table, T.Scenario.make("fcfs", "easy"),
                             0.0, 7200.0)
    s_none = np.asarray(f_none.start)
    s_easy = np.asarray(f_easy.start)
    started_both = np.isfinite(s_none) & np.isfinite(s_easy)
    # identify head jobs: in FCFS order, jobs that were delayed by capacity
    # under no-backfill. EASY must start them no later.
    assert (s_easy[started_both] <= s_none[started_both] + 1e-3).all()


def test_account_policy_uses_ledger(system):
    table = make_table(system)
    accounts = T.AccountStats.zeros(64)
    # account 0: high power, account 1: low power
    accounts = T.AccountStats(**{**vars(accounts),
                                 "power_sum": accounts.power_sum.at[0]
                                 .set(1000.0).at[1].set(10.0),
                                 "jobs_done": accounts.jobs_done.at[0]
                                 .set(1.0).at[1].set(1.0)})
    scen_hi = T.Scenario.make("acct_avg_power")
    scen_lo = T.Scenario.make("acct_low_avg_power")
    k_hi = np.asarray(sched.policy_key(table, accounts, scen_hi))
    k_lo = np.asarray(sched.policy_key(table, accounts, scen_lo))
    acct = np.asarray(table.account)
    if (acct == 0).any() and (acct == 1).any():
        j0 = np.nonzero(acct == 0)[0][0]
        j1 = np.nonzero(acct == 1)[0][0]
        assert k_hi[j0] < k_hi[j1]   # high-power account first
        assert k_lo[j1] < k_lo[j0]   # low-power account first
