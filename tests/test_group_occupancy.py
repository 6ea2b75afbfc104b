"""Per-CDU power from the per-group occupancy, against the per-node oracle.

The engine keeps ``SimState.job_group_nodes`` (nodes each job holds in
each CDU group) and sums power per group from it, never forming per-node
power. Every writer of the node map must keep it true, and the sums must
equal the per-node path's: ``node_power`` + ``step_from_node_power`` on
the plain plant, ``enforce_cap(node_pw)`` under a cap. Each case runs one
placement writer at 64 nodes step by step and, after every step, checks
the occupancy against the node map and the engine's tick against the
oracle on the same state (1e-6 relative: the sums differ by f32
reordering only).
"""
import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import make_signals, with_topology
from repro.cooling import model as cooling
from repro.core import engine as eng
from repro.core import resource_manager as rm
from repro.core import types as T
from repro.datasets.synthetic import WorkloadSpec, generate
from repro.events import EventConfig
from repro.grid import powercap
from repro.grid import signals as gsig
from repro.kernels.power_topo.ref import group_ids, group_power_ref
from repro.power import model as pmodel
from repro.systems.config import get_system

T0 = 1800.0          # jobs already running here are prepopulated
N_STEPS = 90
RTOL = 1e-6


def _system(n_halls):
    base = get_system("marconi100").scaled(64)
    if n_halls == 4:
        # a soft band just above the setpoint: the halls' cooling pressure
        # differs from step to step, so the preference order of the halls
        # (and of their groups) keeps changing
        cfg = with_topology(base.cooling, 4, n_groups=8, n_cells=4,
                            t_return_limit_c=25.5, thermal_margin_c=0.5)
    else:
        # 6 groups over 64 nodes: spans of 11 and a ragged last one (9)
        cfg = dataclasses.replace(base.cooling, n_groups=6)
    return dataclasses.replace(base, cooling=cfg)


def _table(system):
    js = generate(system, WorkloadSpec(
        n_jobs=60, duration_s=3 * 3600.0, load=1.4, trace_len=8,
        n_accounts=8, mean_wall_s=300.0, seed=5))
    js.assign_prepop_placement(T0, system.n_nodes)
    return js.to_table(72)


def _close(got, want, what):
    want = np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=RTOL,
                               atol=RTOL * scale, err_msg=what)


def assert_occupancy_matches_node_map(system, table, st):
    """Every RUNNING job's column equals its nodes per group in the node
    map, and sums to the job's node count."""
    G = system.cooling.n_groups
    gid = group_ids(system.n_nodes, G)
    node_job = np.asarray(st.node_job)
    occ = np.asarray(st.job_group_nodes)
    nodes = np.asarray(table.nodes)
    for j in np.flatnonzero(np.asarray(st.jstate) == T.RUNNING):
        want = np.bincount(gid[node_job == j], minlength=G)
        np.testing.assert_array_equal(occ[:, j], want, err_msg=f"job {j}")
        assert occ[:, j].sum() == nodes[j]


@functools.partial(jax.jit, static_argnums=0)
def _tick_pairs(system, table, st, grid):
    """(engine, oracle) pairs for one tick from ``st``: the plain plant,
    and a cap halfway through the dynamic draw."""
    cfg = system.cooling
    idle = system.power.idle_node_w
    job_pw = pmodel.job_node_power_elapsed(table, st.jstate, st.progress,
                                           system.prof_dt)
    node_pw = pmodel.node_power(system, table, st.node_job, job_pw)
    occ = pmodel.group_occupancy(st.job_group_nodes, st.jstate)
    new, rec = eng._tick(system, table, st, None, None)
    want_st, want, p_it = cooling.step_from_node_power(cfg, st.cooling,
                                                       node_pw, system.dt)
    cap = jnp.sum(jnp.minimum(node_pw, idle)) + \
        0.5 * jnp.sum(jnp.maximum(node_pw - idle, 0.0))
    got_cap = powercap.enforce_cap_groups(
        system, *powercap.group_split(system, occ, job_pw), cap)
    want_cap = powercap.enforce_cap(system, node_pw, cap)
    new_c, rec_c = eng._tick(system, table, st, grid, cap)
    _, want_c = cooling.step(cfg, st.cooling, want_cap.group_heat, system.dt)
    return {
        "group heat": (pmodel.group_power(system, occ, job_pw),
                       group_power_ref(node_pw, cfg.n_groups)),
        "p_it": (rec.power_it, p_it),
        "hall sums": (rec.power_it_hall, want.q_hall_w),
        "cdu supply": (new.cooling.t_supply, want_st.t_supply),
        "cap c": (got_cap.c, want_cap.c),
        "cap group heat": (got_cap.group_heat, want_cap.group_heat),
        "cap p_it": (rec_c.power_it, want_cap.p_it),
        "cap throttle": (rec_c.throttle_frac, 1.0 - want_cap.c),
        "cap hall sums": (rec_c.power_it_hall, want_c.q_hall_w),
    }


def firstfree_mask(node_job, need):
    """The first ``need`` free nodes in node order (a -2 down node is not
    free): the selection one placement made before placements were
    written from free ranks after the loop."""
    free = node_job == -1
    return free & (jnp.cumsum(free.astype(jnp.int32)) <= need)


def firstfree_mask_ordered(node_job, need, order):
    """``firstfree_mask`` taken in the preference order ``order`` (i32[N]
    permutation of node indices; identity reproduces it exactly)."""
    free = node_job == -1
    free_o = free[order]
    sel_o = free_o & (jnp.cumsum(free_o.astype(jnp.int32)) <= need)
    return jnp.zeros_like(free).at[order].set(sel_o)


def _external_ids(table, st, k=8):
    """The first ``k`` queued jobs by submit time, padded with -1: a
    stand-in for an external scheduler's decisions."""
    queued = np.flatnonzero(np.asarray(st.jstate) == T.QUEUED)
    queued = queued[np.argsort(np.asarray(table.submit)[queued],
                               kind="stable")][:k]
    return jnp.asarray(np.pad(queued, (0, k - len(queued)),
                              constant_values=-1), jnp.int32)


CASES = {
    # name: (halls, policy, backfill, grid admission, events)
    "first-fit": (1, "fcfs", "first-fit", False, None),
    "easy": (1, "fcfs", "easy", False, None),
    "replay": (1, "replay", "none", False, None),
    "halls4": (4, "fcfs", "first-fit", False, None),
    "grid-cap": (1, "fcfs", "easy", True, None),
    "events-requeue": (1, "fcfs", "first-fit", False,
                       EventConfig(requeue=True)),
    "external": (1, None, None, False, None),
    "external-halls4": (4, None, None, False, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_group_power_matches_node_oracle_at_every_step(case):
    halls, policy, backfill, grid_admission, events = CASES[case]
    system = _system(halls)
    table = _table(system)
    signals = jax.tree_util.tree_map(jnp.asarray,
                                     make_signals(system, N_STEPS + 1))
    st = eng.init_state(system, table, T0, T0 + N_STEPS * system.dt,
                        num_accounts=8, events=events)
    assert (np.asarray(st.jstate) == T.RUNNING).any(), "nothing prepopulated"
    if policy is None:
        ext = jax.jit(eng.external_step, static_argnums=0)
        step = lambda s: ext(system, table, s, _external_ids(table, s))
    else:
        scen = T.Scenario.make(policy, backfill, node_fail_rate=(
            2e-4 if events else 0.0), repair_s=600.0, failure_seed=3.0)
        step = jax.jit(functools.partial(
            eng.engine_step, system, table, scen=scen,
            signals=signals if grid_admission else None, events=events))
    for k in range(N_STEPS + 1):
        if k:
            st, _ = step(st)
        assert_occupancy_matches_node_map(system, table, st)
        pairs = _tick_pairs(system, table, st, gsig.at_step(signals, st.step))
        for what, (got, want) in pairs.items():
            _close(got, want, f"{case} step {k}: {what}")
    started = np.isfinite(np.asarray(st.start)) & \
        (np.asarray(st.start) > T0 - 1.0)
    assert started.sum() >= 5, "too few placements to exercise the writer"
    if events is not None:
        assert float(st.events.jobs_requeued) > 0, "no job was killed"


@pytest.mark.parametrize("ordered", [False, True], ids=["flat", "ordered"])
@pytest.mark.parametrize("n_nodes,n_groups",
                         [(64, 2), (64, 6), (64, 25), (9600, 25)])
def test_placement_counts_match_successive_selections(n_nodes, n_groups,
                                                      ordered):
    """The counts derived after a loop equal each selection's nodes per
    group, for first-free in node order and in an order that visits whole
    groups shuffled (as the hall plan does), with down nodes (-2), jobs
    that did not fit, and empty trailing groups (64 nodes in 25 groups)."""
    rng = np.random.default_rng(n_nodes + n_groups + ordered)
    gid = group_ids(n_nodes, n_groups)
    node_job = rng.choice([-2, -1, -1, 3], size=n_nodes).astype(np.int32)
    free0 = rm.group_counts(jnp.asarray(node_job == -1), n_groups)
    if ordered:
        perm = rng.permutation(n_groups)
        order = np.concatenate([np.flatnonzero(gid == g) for g in perm])
        group_pos = np.full(n_groups, n_nodes, np.int32) + np.arange(n_groups)
        for g in range(n_groups):
            if (gid == g).any():
                group_pos[g] = np.flatnonzero(order == np.flatnonzero(
                    gid == g)[0])[0]
    else:
        order, group_pos = np.arange(n_nodes), np.arange(n_groups)
    K = 8
    need = np.zeros(K, np.int32)
    want = np.zeros((K, n_groups), np.int64)
    for k, ask in enumerate(rng.integers(0, n_nodes // 4, K)):
        free = int((node_job == -1).sum())
        if ask == 0 or ask > free:
            continue                      # not placed: this slot is empty
        sel = np.asarray(firstfree_mask_ordered(
            jnp.asarray(node_job), jnp.int32(ask), jnp.asarray(order)))
        want[k] = np.bincount(gid[sel], minlength=n_groups)
        need[k] = ask
        node_job[sel] = 10 + k
    got = rm.placement_counts(free0, jnp.asarray(group_pos, jnp.int32),
                              jnp.asarray(need))
    np.testing.assert_array_equal(np.asarray(got), want)
    assert need.any()


@pytest.mark.parametrize("n_nodes,n_groups", [(64, 6), (9600, 25)])
def test_prepopulated_groups_match_prepopulated_node_map(n_nodes, n_groups):
    rng = np.random.default_rng(n_groups)
    cuts = np.sort(rng.choice(np.arange(1, n_nodes), 9, replace=False))
    first = np.concatenate([[0], cuts[:-1]]).astype(np.int32)
    nodes = (cuts - first).astype(np.int32)
    running = rng.random(9) < 0.7
    node_job = np.asarray(rm.prepopulate(n_nodes, jnp.asarray(first),
                                         jnp.asarray(nodes),
                                         jnp.asarray(running)))
    occ = np.asarray(rm.prepopulate_groups(
        n_nodes, n_groups, jnp.asarray(first), jnp.asarray(nodes),
        jnp.asarray(running)))
    gid = group_ids(n_nodes, n_groups)
    for j in range(9):
        want = np.bincount(gid[node_job == j], minlength=n_groups)
        np.testing.assert_array_equal(occ[:, j], want, err_msg=f"job {j}")
