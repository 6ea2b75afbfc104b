"""Built-in scheduler: policy priority keys + bounded admission loop with
no-backfill / first-fit / EASY semantics (paper §3.2.4-§3.2.5).

Design notes
------------
* The policy and the backfill mode are **traced integers** (fields of
  ``Scenario``), so an entire sweep of scheduling configurations runs as one
  vmapped program — this is the TPU-native form of the paper's what-if studies.
* The admission loop is a ``lax.fori_loop`` over the first ``sched_budget``
  entries of the key-sorted queue: bounded work per cycle, like a production
  scheduler's main loop.
* EASY (Mu'alem & Feitelson): when the queue head cannot start, it receives a
  reservation at the *shadow time* (earliest time enough nodes free up, from
  the running jobs' end times); later jobs may backfill iff they fit now and
  either (a) finish before the shadow time (by their *requested* limit) or
  (b) use no more than the ``extra`` nodes spare at the shadow time.
* Shadow times use the running set at the top of the scheduling pass; jobs
  placed earlier in the same pass consume ``free_count`` but are not added to
  the release profile (they end after ``t + their wall``, which can only make
  the true shadow later — so our backfill test is conservative in case (a)
  and standard in case (b)).
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from repro.cooling import model as cmodel
from repro.core import resource_manager as rm
from repro.core import types as T
from repro.grid import signals as gsig
from repro.kernels.power_topo.ref import group_ids
from repro.obs import phases
from repro.systems.config import SystemConfig


# ---------------------------------------------------------------------------
# Priority keys (smaller key = scheduled earlier).
# ---------------------------------------------------------------------------
def policy_key(table: T.JobTable, accounts: T.AccountStats,
               scen: T.Scenario,
               grid: gsig.GridNow | None = None,
               thermal: cmodel.ThermalNow | None = None) -> jnp.ndarray:
    """f32[J] primary sort key for the selected policy (smaller = earlier).

    Args:
      table: static job table (times s, power W).
      accounts: per-account ledgers feeding the incentive policies.
      scen: traced scenario knobs (policy id, deferral weights).
      grid: grid-signal values at this step (g CO2/kWh, $/kWh, W); neutral
        when ``None``.
      thermal: cooling-pressure signals at this step (°C-derived, see
        ``repro.cooling.model.thermal_now``); neutral when ``None``.

    When ``scen.policy`` is a *Python int* (static-scenario fast path,
    docs/architecture.md) only the selected key is computed; traced
    policies compute the full stack and select (vmappable sweeps).
    """
    if grid is None:
        grid = gsig.now_neutral()
    if thermal is None:
        thermal = cmodel.thermal_neutral()
    acct = table.account

    def avg_pw():
        return accounts.power_sum[acct] / jnp.maximum(
            accounts.jobs_done[acct], 1.0)

    # grid-aware deferral (carbon_aware / price_aware): FCFS order plus a
    # penalty on *energy-heavy* jobs (node-seconds as the energy proxy)
    # while the signal sits above its rolling mean. Weight 0 == pure FCFS,
    # so a (weight x cap) sweep brackets the baseline.
    defer_cost = table.nodes.astype(jnp.float32) * table.limit

    def grid_key(now, ref, weight):
        excess = jnp.maximum(now - ref, 0.0) / jnp.maximum(ref, 1e-6)
        return table.submit + weight * excess * defer_cost

    # cooling-aware deferral (thermal_aware): FCFS order plus a penalty on
    # *heat-dense* jobs (estimated W x node·s, in kW·node·s so the scale
    # matches the grid policies) that ramps in as the hottest CDU return
    # temperature enters the soft band below its limit. Weight 0 == FCFS.
    defer_heat = defer_cost * table.power_prof[:, 0] * 1e-3

    def thermal_key():
        return table.submit + scen.thermal_weight * thermal.excess * \
            defer_heat

    # ML-guided key (paper §4.4.2): higher score = earlier. The score has a
    # static part (``table.score``, baked at attach time) plus a
    # *parameterized* part ``ml_basis @ scen.alpha`` — linear in the traced
    # alpha vector, so a vmapped sweep evaluates one alpha per scenario
    # against the shared basis (the ES population axis, repro.ml.train).
    # ``ml_basis is None`` is compile-time "legacy score only".
    def ml_key():
        s = table.score
        if table.ml_basis is not None:
            s = s + jnp.sum(table.ml_basis * scen.alpha, axis=-1)
        return -s

    builders = [
        lambda: table.rec_start,            # REPLAY: recorded order
        lambda: table.submit,               # FCFS
        lambda: table.limit,                # SJF
        lambda: -table.nodes.astype(jnp.float32),   # LJF
        lambda: -table.priority,            # PRIORITY (higher first)
        lambda: -avg_pw(),                  # ACCT_AVG_POWER (descending)
        avg_pw,                             # ACCT_LOW_AVG_POWER (ascending)
        lambda: accounts.edp[acct],         # ACCT_EDP (lower first)
        lambda: accounts.ed2p[acct],        # ACCT_ED2P
        lambda: -accounts.fugaku_pts[acct],  # ACCT_FUGAKU_PTS
        ml_key,                             # ML score (higher is better)
        lambda: grid_key(grid.carbon, grid.carbon_ref,
                         scen.carbon_weight),       # CARBON_AWARE
        lambda: grid_key(grid.price, grid.price_ref,
                         scen.price_weight),        # PRICE_AWARE
        thermal_key,                                # THERMAL_AWARE
    ]
    if isinstance(scen.policy, int):        # static fast path
        k = builders[scen.policy]()
        if T.POLICY_ACCT_AVG_POWER <= scen.policy <= T.POLICY_ACCT_FUGAKU_PTS:
            k = k * scen.acct_weight
        return k
    keys = jnp.stack([b() for b in builders])
    k = jnp.take(keys, scen.policy, axis=0)
    # account-derived keys mix with the scenario weight (lets a sweep soften
    # the incentive signal); neutral for the base policies.
    is_acct = (scen.policy >= T.POLICY_ACCT_AVG_POWER) & \
              (scen.policy <= T.POLICY_ACCT_FUGAKU_PTS)
    return jnp.where(is_acct, k * scen.acct_weight, k)


def queue_order(table: T.JobTable, st: T.SimState, accounts: T.AccountStats,
                scen: T.Scenario, grid: gsig.GridNow | None = None,
                thermal: cmodel.ThermalNow | None = None
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sorted queue: eligible jobs first by (key, submit). Returns
    (order i32[J], eligible bool[J])."""
    queued = st.jstate == T.QUEUED
    replay_gate = jnp.where(scen.policy == T.POLICY_REPLAY,
                            table.rec_start <= st.t, True)
    elig = queued & replay_gate & table.valid
    key = jnp.where(elig, policy_key(table, accounts, scen, grid, thermal),
                    jnp.inf)
    tie = jnp.where(elig, table.submit, jnp.inf)
    order = jnp.lexsort((tie, key))  # primary: key, secondary: submit
    return order.astype(jnp.int32), elig


# ---------------------------------------------------------------------------
# EASY shadow-time machinery.
# ---------------------------------------------------------------------------
def release_profile(table: T.JobTable, st: T.SimState):
    """Sorted *estimated* end times of running jobs and cumulative nodes they
    release. Faithful EASY uses the user-requested limit, not the (unknown)
    true runtime: est_end = start + limit.

    Returns (end_sorted f32[J], cum_nodes i32[J]).
    """
    running = st.jstate == T.RUNNING
    est_end = jnp.where(running, st.start + table.limit, jnp.inf)
    order = jnp.argsort(est_end)
    nodes_released = jnp.where(running, table.nodes, 0)[order]
    return est_end[order], jnp.cumsum(nodes_released)


def shadow_for(end_sorted: jnp.ndarray, cum_nodes: jnp.ndarray,
               free_now: jnp.ndarray, need: jnp.ndarray):
    """Earliest time ``need`` nodes are simultaneously free, and the surplus
    ("extra") nodes available at that time."""
    deficit = jnp.maximum(need - free_now, 0)
    k = jnp.searchsorted(cum_nodes, deficit, side="left")
    k = jnp.clip(k, 0, cum_nodes.shape[0] - 1)
    shadow_t = jnp.where(deficit == 0, jnp.float32(0.0), end_sorted[k])
    extra = free_now + cum_nodes[k] - need
    return shadow_t, jnp.maximum(extra, 0)


# ---------------------------------------------------------------------------
# Hall-aware placement (repro.systems.config.FacilityTopology).
# ---------------------------------------------------------------------------
def _hall_spans(system: SystemConfig):
    """Static (node_hall i32[N], sizes i32[H], first-node i32[H]) of the
    contiguous per-hall node spans (host-side numpy; trace-time
    constants)."""
    n_nodes, n_groups = system.n_nodes, system.cooling.n_groups
    gid = np.asarray(group_ids(n_nodes, n_groups))  # the single source of
    #                          the node->CDU rule (kernels/power_topo/ref)
    node_hall = np.asarray(system.cooling.hall_of_group(),
                           np.int32)[gid]
    sizes = np.bincount(node_hall, minlength=system.cooling.n_halls)
    first = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    return node_hall, sizes.astype(np.int32), first.astype(np.int32)


def hall_placement_plan(system: SystemConfig, st: T.SimState,
                        thermal: cmodel.ThermalNow, is_replay):
    """Node preference order + per-hall admission inputs for one pass.

    Nodes are ordered by their hall's cooling pressure (soft-band
    ``excess_hall``, with overheated halls pushed last), index-stable
    within a hall — so first-free placement drains into the coolest hall
    first and an overheating hall stops receiving work while any other
    hall has room. Replay keeps the identity order (the recorded
    placement is ground truth).

    Halls are contiguous node spans, so the permutation is built from an
    H-element sort plus an O(N) scatter — no per-step N·log N sort inside
    the scan (H is tens, N up to ~160k).

    Returns (order i32[N], node_ok bool[N], free_ok i32[]): the
    preference permutation, which nodes sit in a non-overheated hall, and
    how many of those are currently free (the per-job admission budget —
    a job may start iff it fits inside ``free_ok``).
    """
    node_hall_np, sizes_np, first_np = _hall_spans(system)
    node_hall = jnp.asarray(node_hall_np)
    sizes = jnp.asarray(sizes_np)
    first = jnp.asarray(first_np)
    H = system.cooling.n_halls
    node_ok = ~thermal.overheat_hall[node_hall]
    penalty_h = thermal.excess_hall + \
        1e3 * thermal.overheat_hall.astype(jnp.float32)
    penalty_h = penalty_h * jnp.where(is_replay, 0.0, 1.0)
    # stable H-sort of halls by pressure, then concatenate their spans:
    # out_start[h] = where hall h's span begins in the preference order
    hall_order = jnp.lexsort((jnp.arange(H), penalty_h))
    sz_sorted = sizes[hall_order]
    starts_sorted = jnp.cumsum(sz_sorted) - sz_sorted     # exclusive cumsum
    out_start = jnp.zeros((H,), jnp.int32).at[hall_order].set(
        starts_sorted.astype(jnp.int32))
    idx = jnp.arange(system.n_nodes, dtype=jnp.int32)
    pos = out_start[node_hall] + (idx - first[node_hall])
    order = jnp.zeros_like(idx).at[pos].set(idx)
    free_ok = jnp.sum(((st.node_job == -1) & node_ok).astype(jnp.int32))
    return order, node_ok, free_ok


# ---------------------------------------------------------------------------
# The scheduling pass.
# ---------------------------------------------------------------------------
def schedule_step(system: SystemConfig, table: T.JobTable, st: T.SimState,
                  scen: T.Scenario, grid: gsig.GridNow | None = None,
                  proj_pw: jnp.ndarray | None = None,
                  thermal: cmodel.ThermalNow | None = None,
                  dr=None) -> T.SimState:
    """One call of ``schedule`` (paper Algorithm step 3): reorder the queue by
    the selected policy and admit jobs under the selected backfill rule.

    Cap-aware admission: when a power-cap schedule is active
    (``grid.cap_w * scen.cap_scale`` finite), a job is only started if the
    projected IT power (``proj_pw``, the current raw draw, plus the added
    draw of jobs placed earlier in this pass) stays under the cap — the
    DVFS throttle (repro.grid.powercap) then only has to absorb profile
    ramps, not admission mistakes. A head job blocked *by the cap alone*
    halts admission under BF_NONE and BF_EASY (backfilled jobs would eat
    the headroom it is waiting for and starve it); first-fit stays greedy.
    ``grid is None`` (no signals) is compile-time: the cap machinery folds
    away entirely.

    Thermal admission throttling: when a hall's cooling loop has lost the
    supply setpoint by more than ``CoolingConfig.t_supply_margin_c``
    (``thermal.overheat_hall``, see repro.cooling.model.thermal_now),
    admission into *that hall* is deferred for this step — starting more
    work while its CDUs cannot hold setpoint only pushes the loop further
    from it. On a multi-hall topology, placement is hall-aware
    (``hall_placement_plan``): nodes are drained coolest-hall-first and a
    job is admitted only if it fits inside the non-overheated halls; a
    flat (1-hall) plant keeps the original all-or-nothing gate and
    identity placement order bit-for-bit. Replay is exempt (the recorded
    schedule is ground truth), and running jobs are untouched (heat
    relief comes from completions).

    Demand-response notice window: ``dr`` (a ``repro.events.DrNow``,
    grid path only) announces a coming cap step. During the notice
    window, a job whose *requested limit* runs into the event is only
    admitted if the projected power would still fit under the announced
    cap — the scheduler pre-positions for the cap instead of slamming
    into it. ``dr is None`` is compile-time "no DR machinery"."""
    has_grid = grid is not None
    is_replay = scen.policy == T.POLICY_REPLAY
    hall_aware = thermal is not None and system.cooling.n_halls > 1
    with jax.named_scope(phases.QUEUE_ORDER):
        if hall_aware:
            order_nodes, node_ok, free_ok0 = hall_placement_plan(
                system, st, thermal, is_replay)
        else:
            order_nodes = node_ok = None
            free_ok0 = st.free_count
        thermal_ok = jnp.bool_(True) if thermal is None else ~thermal.overheat
        if has_grid:
            cap_active = grid.cap_w * scen.cap_scale
            if dr is not None:
                # an in-force DR event caps admission below the schedule
                cap_active = jnp.minimum(cap_active, dr.cap_now_w)
            # estimated power a job adds on start: first profile sample above
            # the idle floor its nodes already draw
            est_add_pw = jnp.maximum(
                table.power_prof[:, 0] - system.power.idle_node_w, 0.0) * \
                table.nodes.astype(jnp.float32)
        if proj_pw is None:
            proj_pw = jnp.float32(0.0)
        order, _elig = queue_order(table, st, st.accounts, scen, grid, thermal)
        static = isinstance(scen.backfill, int)
        if static and scen.backfill != T.BF_EASY:
            # static fast path: no reservation machinery needed
            end_sorted = jnp.zeros((1,), jnp.float32)
            cum_nodes = jnp.zeros((1,), jnp.int32)
        else:
            end_sorted, cum_nodes = release_profile(table, st)
    n_nodes = system.n_nodes
    t = st.t

    def body(i, carry):
        (node_job, jstate, start, end, free_count, free_ok, proj,
         blocked_any, head_blocked, head_capped,
         shadow_t, shadow_extra) = carry
        j = order[i]
        valid = jstate[j] == T.QUEUED
        # replay eligibility re-gate (queue_order already filtered, but jobs
        # whose recorded start is still in the future must keep waiting)
        valid &= jnp.where(is_replay, table.rec_start[j] <= t, True)
        need = table.nodes[j]

        # --- does it fit right now? ---
        # Placement is deterministic first-free (lowest-index free nodes);
        # the dataset generators use the same rule, so replay reproduces the
        # recorded occupancy without storing per-node assignments. On a
        # multi-hall plant the scan order is the hall-preference
        # permutation instead (coolest hall first, index-stable within a
        # hall; identity under replay and when every hall is equally cool).
        if hall_aware:
            sel = rm.firstfree_mask_ordered(node_job, need, order_nodes)
        else:
            sel = rm.firstfree_mask(node_job, need)
        fits = need <= free_count

        # --- EASY reservation for the first blocked (head) job ---
        first_block = valid & ~fits & ~head_blocked
        sh_t, sh_extra = shadow_for(end_sorted, cum_nodes, free_count, need)
        shadow_t = jnp.where(first_block, sh_t, shadow_t)
        shadow_extra = jnp.where(first_block, sh_extra, shadow_extra)

        # --- admission rule ---
        # a cap-blocked head has no node-shadow to reserve (power, not
        # nodes, is scarce): EASY halts instead, so backfill cannot eat
        # the headroom the head is waiting for
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
                [scen.backfill == T.BF_NONE,
                 scen.backfill == T.BF_FIRSTFIT],
                [~blocked_any,
                 jnp.bool_(True)],
                jnp.where(head_blocked | head_capped, easy_ok, True),
            )
        # cap-aware admission: starting this job must not breach the cap
        if has_grid:
            cap_ok = proj + est_add_pw[j] <= cap_active
            if dr is not None:
                # notice-window pre-positioning: a job that would still be
                # running when the announced DR cap engages must also fit
                # under *that* cap
                runs_into = dr.in_notice & (t + table.limit[j] > dr.start_s)
                cap_ok &= ~runs_into | (proj + est_add_pw[j] <= dr.cap_w)
        else:
            cap_ok = jnp.bool_(True)
        # thermal admission: flat plant -> all-or-nothing gate; multi-hall
        # -> the job must fit inside the halls still holding setpoint
        # (preference ordering guarantees the selection stays there).
        # Like the cap, thermal is a non-node resource: a head blocked by
        # it feeds blocked_any/head_capped below so BF_NONE keeps FIFO
        # order and EASY halts instead of reserving a node-shadow.
        th_ok = (need <= free_ok) if hall_aware else thermal_ok
        # replay ignores backfill, the cap and the thermal gate: recorded
        # schedule is truth
        place = valid & fits & jnp.where(is_replay, True,
                                         can_bf & cap_ok & th_ok)

        # --- commit ---
        node_job = rm.place(node_job, sel, j, place)
        free_count = free_count - jnp.where(place, need, 0)
        if hall_aware:
            free_ok = free_ok - jnp.where(
                place, jnp.sum((sel & node_ok).astype(jnp.int32)), 0)
        # (on a flat plant free_ok is inert carry: the all-or-nothing gate
        # never reads it)
        if has_grid:
            proj = proj + jnp.where(place, est_add_pw[j], 0.0)
        jstate = jstate.at[j].set(jnp.where(place, T.RUNNING, jstate[j]))
        start = start.at[j].set(jnp.where(place, t, start[j]))
        end = end.at[j].set(jnp.where(place, t + table.wall[j], end[j]))

        blocked_any |= valid & (~fits | ~cap_ok | ~th_ok)
        head_blocked |= valid & ~fits
        head_capped |= valid & fits & (~cap_ok | ~th_ok)
        return (node_job, jstate, start, end, free_count, free_ok, proj,
                blocked_any, head_blocked, head_capped,
                shadow_t, shadow_extra)

    with jax.named_scope(phases.ADMISSION):
        carry = (st.node_job, st.jstate, st.start, st.end, st.free_count,
                 jnp.int32(free_ok0),
                 jnp.float32(proj_pw), jnp.bool_(False), jnp.bool_(False),
                 jnp.bool_(False), jnp.float32(jnp.inf), jnp.int32(0))
        K = min(system.sched_budget, table.num_jobs)
        (node_job, jstate, start, end, free_count,
         *_rest) = jax.lax.fori_loop(0, K, body, carry)

    return dataclasses.replace(st, jstate=jstate, start=start, end=end,
                               node_job=node_job, free_count=free_count)
