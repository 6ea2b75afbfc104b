"""Admission decided on scalars and placed once, against the per-pass loop.

``scheduler.schedule_step`` and ``engine.external_step`` decide each
candidate on scalars (free counts, the power projection, the EASY flags)
and write the placement once after the loop, from one rank of the free
nodes (``resource_manager.commit_placements``). The oracle here is the
loop they replace: each pass takes its own first-free mask over the node
map, writes the node map, ``node_end`` and the job's lifecycle, and the
per-group occupancy is read off after the loop. Every placement writer
runs at 64 nodes step by step from the same state through both; the
node map, per-node end times, lifecycle, start and end times, free count
and occupancy must be equal bit for bit after every step.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_signals
from repro.core import engine as eng
from repro.core import resource_manager as rm
from repro.core import scheduler as sched
from repro.core import types as T
from repro.cooling import model as cooling
from repro.events import EventConfig
from repro.kernels.power_topo.ref import segment_dot
from repro.obs import timing
from test_group_occupancy import (N_STEPS, T0, _external_ids, _system,
                                  _table, firstfree_mask,
                                  firstfree_mask_ordered)

RESULT = ("node_job", "node_end", "jstate", "start", "end", "free_count",
          "job_group_nodes")


# ---------------------------------------------------------------------------
# The oracle: the admission loop that placed inside every pass.
# ---------------------------------------------------------------------------
def _place(node_job, node_end, sel, jid, end_t, do_place):
    m = sel & do_place
    return jnp.where(m, jid, node_job), jnp.where(m, end_t, node_end)


def _record_placements(job_group_nodes, node_job, group_pos, cand,
                       jstate_before, jstate_after, job_nodes):
    """Placed jobs read off QUEUED -> RUNNING over the candidates (at a
    repeat's first occurrence)."""
    K = cand.shape[0]
    k = jnp.arange(K)
    repeat = jnp.any((cand[:, None] == cand[None, :])
                     & (k[:, None] > k[None, :]), axis=1)
    safe = jnp.maximum(cand, 0)
    started = (cand >= 0) & ~repeat & \
        (jstate_before[safe] == T.QUEUED) & (jstate_after[safe] == T.RUNNING)
    counts = rm.placement_counts(
        rm.group_counts(node_job == -1, group_pos.shape[0]), group_pos,
        jnp.where(started, job_nodes[safe], 0))
    J = job_group_nodes.shape[-1]
    hit = started[:, None] & (cand[:, None] == jnp.arange(J)[None, :])
    cols = segment_dot(counts.T.astype(jnp.float32), hit.astype(jnp.float32))
    return jnp.where(jnp.any(hit, axis=0)[None, :],
                     cols.astype(jnp.int32), job_group_nodes)


def oracle_schedule_step(system, table, st, scen, grid=None, proj_pw=None,
                         thermal=None, dr=None):
    has_grid = grid is not None
    is_replay = scen.policy == T.POLICY_REPLAY
    hall_aware = thermal is not None and system.cooling.n_halls > 1
    if hall_aware:
        order_nodes, node_ok, free_ok0, group_pos = \
            sched.hall_placement_plan(system, st, thermal, is_replay)
    else:
        free_ok0 = st.free_count
        group_pos = jnp.arange(system.cooling.n_groups, dtype=jnp.int32)
    thermal_ok = jnp.bool_(True) if thermal is None else ~thermal.overheat
    if has_grid:
        cap_active = grid.cap_w * scen.cap_scale
        if dr is not None:
            cap_active = jnp.minimum(cap_active, dr.cap_now_w)
        est_add_pw = jnp.maximum(
            table.power_prof[:, 0] - system.power.idle_node_w, 0.0) * \
            table.nodes.astype(jnp.float32)
    if proj_pw is None:
        proj_pw = jnp.float32(0.0)
    order, _ = sched.queue_order(table, st, st.accounts, scen, grid, thermal)
    static = isinstance(scen.backfill, int)
    if static and scen.backfill != T.BF_EASY:
        end_sorted = jnp.zeros((1,), jnp.float32)
        cum_nodes = jnp.zeros((1,), jnp.int32)
    else:
        end_sorted, cum_nodes = sched.release_profile(table, st)
    t = st.t

    def body(i, carry):
        (node_job, node_end, jstate, start, end, free_count, free_ok, proj,
         blocked_any, head_blocked, head_capped,
         shadow_t, shadow_extra) = carry
        j = order[i]
        valid = jstate[j] == T.QUEUED
        valid &= jnp.where(is_replay, table.rec_start[j] <= t, True)
        need = table.nodes[j]
        if hall_aware:
            sel = firstfree_mask_ordered(node_job, need, order_nodes)
        else:
            sel = firstfree_mask(node_job, need)
        fits = need <= free_count
        first_block = valid & ~fits & ~head_blocked
        sh_t, sh_extra = sched.shadow_for(end_sorted, cum_nodes, free_count,
                                          need)
        shadow_t = jnp.where(first_block, sh_t, shadow_t)
        shadow_extra = jnp.where(first_block, sh_extra, shadow_extra)
        easy_ok = ((t + table.limit[j] <= shadow_t) |
                   (need <= shadow_extra)) & ~head_capped
        if static:
            can_bf = {T.BF_NONE: ~blocked_any,
                      T.BF_FIRSTFIT: jnp.bool_(True),
                      T.BF_EASY: jnp.where(head_blocked | head_capped,
                                           easy_ok, True),
                      }[scen.backfill]
        else:
            can_bf = jnp.select(
                [scen.backfill == T.BF_NONE, scen.backfill == T.BF_FIRSTFIT],
                [~blocked_any, jnp.bool_(True)],
                jnp.where(head_blocked | head_capped, easy_ok, True))
        if has_grid:
            cap_ok = proj + est_add_pw[j] <= cap_active
            if dr is not None:
                runs_into = dr.in_notice & (t + table.limit[j] > dr.start_s)
                cap_ok &= ~runs_into | (proj + est_add_pw[j] <= dr.cap_w)
        else:
            cap_ok = jnp.bool_(True)
        th_ok = (need <= free_ok) if hall_aware else thermal_ok
        place = valid & fits & jnp.where(is_replay, True,
                                         can_bf & cap_ok & th_ok)
        end_j = t + table.wall[j]
        node_job, node_end = _place(node_job, node_end, sel, j, end_j, place)
        free_count = free_count - jnp.where(place, need, 0)
        if hall_aware:
            free_ok = free_ok - jnp.where(
                place, jnp.sum((sel & node_ok).astype(jnp.int32)), 0)
        if has_grid:
            proj = proj + jnp.where(place, est_add_pw[j], 0.0)
        jstate = jstate.at[j].set(jnp.where(place, T.RUNNING, jstate[j]))
        start = start.at[j].set(jnp.where(place, t, start[j]))
        end = end.at[j].set(jnp.where(place, end_j, end[j]))
        blocked_any |= valid & (~fits | ~cap_ok | ~th_ok)
        head_blocked |= valid & ~fits
        head_capped |= valid & fits & (~cap_ok | ~th_ok)
        return (node_job, node_end, jstate, start, end, free_count, free_ok,
                proj, blocked_any, head_blocked, head_capped,
                shadow_t, shadow_extra)

    carry = (st.node_job, st.node_end, st.jstate, st.start, st.end,
             st.free_count, jnp.int32(free_ok0), jnp.float32(proj_pw),
             jnp.bool_(False), jnp.bool_(False), jnp.bool_(False),
             jnp.float32(jnp.inf), jnp.int32(0))
    K = min(system.sched_budget, table.num_jobs)
    (node_job, node_end, jstate, start, end, free_count,
     *_) = jax.lax.fori_loop(0, K, body, carry)
    job_group_nodes = _record_placements(
        st.job_group_nodes, st.node_job, group_pos, order[:K], st.jstate,
        jstate, table.nodes)
    return dataclasses.replace(st, jstate=jstate, start=start, end=end,
                               node_job=node_job, node_end=node_end,
                               free_count=free_count,
                               job_group_nodes=job_group_nodes)


def oracle_external_step(system, table, st, place_ids):
    """``engine.external_step`` with neutral knobs and no signals, placing
    inside every pass."""
    st = eng._prepare_and_arrivals(system, table, st, False)
    hall_aware = system.cooling.n_halls > 1
    thermal = cooling.thermal_now(system.cooling, st.cooling, 0.0)
    thermal_ok = ~thermal.overheat
    if hall_aware:
        order_nodes, node_ok, free_ok0, group_pos = \
            sched.hall_placement_plan(system, st, thermal, is_replay=False)
    else:
        free_ok0 = st.free_count
        group_pos = jnp.arange(system.cooling.n_groups, dtype=jnp.int32)

    def body(i, carry):
        node_job, node_end, jstate, start, end, free_count, free_ok = carry
        j = place_ids[i]
        jj = jnp.maximum(j, 0)
        need = table.nodes[jj]
        th_ok = (need <= free_ok) if hall_aware else thermal_ok
        can = (j >= 0) & (jstate[jj] == T.QUEUED) & (need <= free_count) & \
            th_ok
        if hall_aware:
            sel = firstfree_mask_ordered(node_job, need, order_nodes)
        else:
            sel = firstfree_mask(node_job, need)
        end_j = st.t + table.wall[jj]
        node_job, node_end = _place(node_job, node_end, sel, jj, end_j, can)
        free_count = free_count - jnp.where(can, need, 0)
        if hall_aware:
            free_ok = free_ok - jnp.where(
                can, jnp.sum((sel & node_ok).astype(jnp.int32)), 0)
        jstate = jstate.at[jj].set(jnp.where(can, T.RUNNING, jstate[jj]))
        start = start.at[jj].set(jnp.where(can, st.t, start[jj]))
        end = end.at[jj].set(jnp.where(can, end_j, end[jj]))
        return node_job, node_end, jstate, start, end, free_count, free_ok

    carry = (st.node_job, st.node_end, st.jstate, st.start, st.end,
             st.free_count, jnp.int32(free_ok0))
    (node_job, node_end, jstate, start, end, free_count,
     _) = jax.lax.fori_loop(0, place_ids.shape[0], body, carry)
    job_group_nodes = _record_placements(
        st.job_group_nodes, st.node_job, group_pos, place_ids, st.jstate,
        jstate, table.nodes)
    st = dataclasses.replace(st, jstate=jstate, start=start, end=end,
                             node_job=node_job, node_end=node_end,
                             free_count=free_count,
                             job_group_nodes=job_group_nodes)
    return eng._tick(system, table, st, None, None, None, 0.0, thermal,
                     0.0)[0]


# ---------------------------------------------------------------------------
# The writers.
# ---------------------------------------------------------------------------
# name: (halls, scenarios, grid admission, events, static, external)
# ``scenarios`` is a list of (policy, backfill) run as one vmapped sweep
# (traced policy and backfill), or one pair run with compile-time ids
# when ``static``.
_TRACED = [("fcfs", "none"), ("sjf", "first-fit"), ("ljf", "easy"),
           ("replay", "none")]
CASES = {
    "sweep": (1, _TRACED, False, None, False, None),
    "halls4-sweep": (4, _TRACED, False, None, False, None),
    "halls4-hot-sweep": (4, _TRACED, False, None, False, None),
    "static-none": (1, [("fcfs", "none")], False, None, True, None),
    "static-first-fit": (1, [("fcfs", "first-fit")], False, None, True,
                         None),
    "static-easy": (1, [("fcfs", "easy")], False, None, True, None),
    "static-replay": (1, [("replay", "none")], False, None, True, None),
    "grid-cap": (1, [("fcfs", "easy"), ("sjf", "first-fit")], True, None,
                 False, None),
    "grid-dr": (1, [("fcfs", "easy"), ("fcfs", "none")], True,
                EventConfig(), False, None),
    "events-down": (1, [("fcfs", "easy"), ("ljf", "first-fit")], False,
                    EventConfig(requeue=True), False, None),
    "external": (1, None, False, None, False, "queued"),
    "external-repeats": (1, None, False, None, False, "repeats"),
    "external-halls4": (4, None, False, None, False, "repeats"),
    "external-halls4-hot": (4, None, False, None, False, "repeats"),
}


def _heat(system, st, k):
    """Push the CDU supply of one or two halls, changing with the step,
    past setpoint plus margin: those halls overheat, so the per-hall gate
    admits a job only into the rest, and the nodes a placement takes in
    them are read from ``ok_upto``."""
    cfg = system.cooling
    hot = {k % 4, (k + 1) % 4} if k % 2 else {k % 4}
    hog = np.asarray(cooling.halls(cfg).hog)
    floor = np.where(np.isin(hog, sorted(hot)),
                     cfg.t_supply_setpoint_c + cfg.t_supply_margin_c + 1.0,
                     -np.inf)
    return dataclasses.replace(st, cooling=dataclasses.replace(
        st.cooling, t_supply=jnp.maximum(st.cooling.t_supply,
                                         floor.astype(np.float32))))


def _scenario(system, policy, backfill, events, static):
    if static:
        return T.Scenario(policy=T.POLICY_NAMES[policy],
                          backfill=T.BACKFILL_NAMES[backfill])
    floor = system.n_nodes * system.power.idle_node_w
    dr = {} if events is None else dict(
        dr_announce_s=T0 + 15 * system.dt, dr_notice_s=30 * system.dt,
        dr_duration_s=30 * system.dt, dr_cap_w=1.3 * floor)
    return T.Scenario.make(policy, backfill, node_fail_rate=(
        2e-4 if events else 0.0), repair_s=600.0, failure_seed=3.0, **dr)


def _replay_table(system):
    """``_table`` with the jobs submitted after t0 recorded as having
    waited up to 5 minutes, so replay's gate on the recorded start keeps
    queued jobs back."""
    table = _table(system)
    wait = np.random.default_rng(7).uniform(0.0, 300.0, table.num_jobs)
    late = np.asarray(table.submit) > T0
    return dataclasses.replace(table, rec_start=jnp.asarray(np.where(
        late, np.asarray(table.submit) + wait, np.asarray(table.rec_start)),
        jnp.float32))


def _ids(table, st, kind):
    """External decisions: the first queued jobs by submit time; for
    "repeats" each id twice and then -1, the last repeat after its -1."""
    ids = np.asarray(_external_ids(table, st, k=6))
    if kind == "repeats":
        ids = np.stack([ids, ids, np.full_like(ids, -1)], 1).reshape(-1)
        ids[-2:] = ids[-2:][::-1]
    return jnp.asarray(ids, jnp.int32)


def _writer(case, system, table, signals, oracle=False):
    """(state, extra) -> state of the case's writer; ``extra`` is the
    stacked scenarios of a sweep, the external ids, or unused."""
    _, scens, grid_admission, events, static, external = CASES[case]
    if external:
        if oracle:
            return lambda s, ids: oracle_external_step(system, table, s, ids)
        ext = eng.external_step.__wrapped__        # a fresh trace each time
        return lambda s, ids: ext(system, table, s, ids)[0]
    step = functools.partial(eng.engine_step, system, table,
                             signals=signals if grid_admission else None,
                             events=events)
    if static:
        scen = _scenario(system, *scens[0], events, True)
        return lambda s, _: step(s, scen)[0]
    return jax.vmap(lambda s, sc: step(s, sc)[0])


@pytest.mark.parametrize("case", sorted(CASES))
def test_admission_matches_per_pass_placement_at_every_step(case,
                                                            monkeypatch):
    halls, scens, _, events, static, external = CASES[case]
    system = _system(halls)
    table = _replay_table(system)
    signals = jax.tree_util.tree_map(jnp.asarray,
                                     make_signals(system, N_STEPS + 1))
    st = eng.init_state(system, table, T0, T0 + N_STEPS * system.dt,
                        num_accounts=8, events=events)
    extra = jnp.int32(0)
    if external:
        extra = _ids(table, st, external)
    elif not static:
        st = jax.tree_util.tree_map(lambda x: jnp.stack([x] * len(scens)),
                                    st)
        extra = T.stack_scenarios([_scenario(system, p, b, events, False)
                                   for p, b in scens])

    before = dict(timing.PLACEMENT_STATS)
    got_fn = jax.jit(_writer(case, system, table, signals)).lower(
        st, extra).compile()
    took = "ranks_ordered" if halls > 1 else "ranks"
    assert timing.PLACEMENT_STATS[took] == before[took] + 1
    assert sum(timing.PLACEMENT_STATS.values()) == sum(before.values()) + 1

    monkeypatch.setattr(sched, "schedule_step", oracle_schedule_step)
    want_fn = jax.jit(_writer(case, system, table, signals, oracle=True)
                      ).lower(st, extra).compile()
    monkeypatch.undo()
    assert sum(timing.PLACEMENT_STATS.values()) == sum(before.values()) + 1

    placed = 0
    for k in range(1, N_STEPS + 1):
        if "hot" in case:
            st = _heat(system, st, k)
        want = want_fn(st, extra)
        got = got_fn(st, extra)
        for name in RESULT:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, name)),
                np.asarray(getattr(want, name)),
                err_msg=f"{case} step {k}: {name}")
        placed += int(np.sum(np.isfinite(np.asarray(got.start))
                             & ~np.isfinite(np.asarray(st.start))))
        st = got
        if external:
            extra = _ids(table, st, external)
    assert placed >= 5 * len(scens or [0]), \
        "too few placements to exercise the writer"


@pytest.mark.parametrize("ordered", [False, True], ids=["flat", "ordered"])
@pytest.mark.parametrize("n_nodes", [64, 256, 9600])
def test_ranks_match_successive_selections(n_nodes, ordered):
    """One rank of the free nodes gives each placement of a loop the nodes
    the per-pass first-free mask picked, and ``ok_upto`` how many of them
    are ``ok``: with down nodes (-2), jobs that did not fit, empty
    placements, and ``ok`` nodes scattered through the order (replay's
    order does not put them first)."""
    rng = np.random.default_rng(n_nodes + ordered)
    node_job = rng.choice([-2, -1, -1, 3], size=n_nodes).astype(np.int32)
    node_end = rng.uniform(0.0, 1e4, n_nodes).astype(np.float32)
    ok = rng.random(n_nodes) < 0.6
    order = rng.permutation(n_nodes) if ordered else np.arange(n_nodes)
    before = dict(timing.PLACEMENT_STATS)
    rank = rm.free_ranks(jnp.asarray(node_job),
                         jnp.asarray(order, jnp.int32) if ordered else None)
    took_kind = "ranks_ordered" if ordered else "ranks"
    assert timing.PLACEMENT_STATS[took_kind] == before[took_kind] + 1
    assert sum(timing.PLACEMENT_STATS.values()) == sum(before.values()) + 1
    ok_upto = np.asarray(rm.ok_upto(rank, jnp.asarray(ok)))

    K = 8
    cand = np.arange(10, 10 + K, dtype=np.int32)
    end_t = rng.uniform(1e4, 2e4, K).astype(np.float32)
    took = np.zeros(K, np.int32)
    want_job, want_end = node_job.copy(), node_end.copy()
    c = 0
    for k, ask in enumerate(rng.integers(0, n_nodes // 4, K)):
        if ask > (want_job == -1).sum():
            continue                      # did not fit: nothing placed
        sel = np.asarray(firstfree_mask_ordered(
            jnp.asarray(want_job), jnp.int32(ask), jnp.asarray(order)))
        assert ok_upto[c + ask] - ok_upto[c] == (sel & ok).sum(), k
        want_job[sel], want_end[sel] = cand[k], end_t[k]
        took[k] = ask
        c += ask
    got_job, got_end = rm.place_ranks(
        jnp.asarray(node_job), jnp.asarray(node_end), rank,
        jnp.asarray(cand), jnp.asarray(took), jnp.asarray(end_t))
    np.testing.assert_array_equal(np.asarray(got_job), want_job)
    np.testing.assert_array_equal(np.asarray(got_end), want_end)
    assert took.any() and not took.all()
