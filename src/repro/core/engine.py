"""The S-RAPS simulation engine (paper §3.2.3), as a single ``lax.scan``.

Main loop per step (paper's four well-defined steps):
  (1) prepare     -- clear completed jobs, free their nodes, fold accounting;
  (2) arrivals    -- move submitted jobs into the queue;
  (3) schedule    -- policy sort + bounded admission (repro.core.scheduler),
                     cap-aware when a power-cap schedule is active and
                     thermally throttled when cooling loses its setpoint;
  (4) tick        -- power model (or measured-telemetry replay when the
                     table carries a ``power_profile`` channel —
                     repro.traces), summed per CDU group from the per-job
                     powers and ``SimState.job_group_nodes`` -> DVFS cap
                     enforcement (repro.grid) ->
                     conversion losses -> transient cooling loop
                     (repro.cooling, weather-driven) -> telemetry row;
                     advance time.

The engine is pure: ``simulate`` compiles once per (system, job-table shape)
and a *batch of scenarios* (policy x backfill x incentive weights) runs under
``vmap`` — see ``simulate_sweep``. With more than one device the scenario
axis shards across them as one ``shard_map`` program
(``simulate_sweep_sharded``; the CLI and examples call it by default).

Per-step environment inputs follow one pattern: host-precomputed arrays
(``repro.grid.signals.GridSignals``, ``repro.cooling.weather
.WeatherSignals``) are gathered at ``SimState.step`` inside the scan, so
one signal/weather set is shared by broadcast across a vmapped sweep —
or stacked on the batch axis for weather-scenario sweeps.

``external_step`` supports the paper's §4.2 plugin mode: an event-based
external scheduler decides placements between compiled steps.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import os
import weakref
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cooling import model as cooling
from repro.cooling import weather as wsig
from repro.core import accounts as acct_mod
from repro.core import resource_manager as rm
from repro.core import scheduler as sched
from repro.core import types as T
from repro.events import process as events_mod
from repro.grid import powercap
from repro.grid import signals as gsig
from repro.obs import phases
from repro.obs import timing as obs_timing
from repro.power import losses as plosses
from repro.power import model as pmodel
from repro.systems.config import SystemConfig


# ---------------------------------------------------------------------------
# Initialization (paper §3.2.1 / §3.2.3 prepopulation + dismissal).
# ---------------------------------------------------------------------------
def init_state(system: SystemConfig, table: T.JobTable, t0: float,
               t1: float, accounts: T.AccountStats | None = None,
               num_accounts: int = 64,
               events: "events_mod.EventConfig | None" = None) -> T.SimState:
    """Initial engine state for the window ``[t0, t1]`` (seconds).

    Dismisses jobs entirely outside the window, prepopulates jobs already
    running at ``t0`` per the telemetry, queues jobs submitted but not yet
    started, and starts the cooling loop from its idle-plant condition.
    ``events`` (an ``EventConfig``) rides the carry as an ``EventState``
    subtree; ``None`` keeps the carry identical to the pre-events layout.
    """
    J = table.num_jobs
    rec_end = table.rec_start + table.wall
    jstate = jnp.full((J,), T.PENDING, jnp.int32)

    # dismiss jobs entirely outside the window (paper Fig. 3 discussion)
    dismissed = (~table.valid) | (rec_end <= t0) | (table.submit >= t1)
    jstate = jnp.where(dismissed, T.DISMISSED, jstate)

    # prepopulate jobs running at t0 per the telemetry
    running0 = (~dismissed) & (table.rec_start <= t0) & (rec_end > t0) & \
               (table.first_node >= 0)
    jstate = jnp.where(running0, T.RUNNING, jstate)

    # jobs already submitted but not yet started at t0 join the queue
    queued0 = (~dismissed) & (~running0) & (table.submit <= t0)
    jstate = jnp.where(queued0, T.QUEUED, jstate)

    start = jnp.where(running0, table.rec_start, jnp.inf)
    end = jnp.where(running0, rec_end, jnp.inf)
    node_job = rm.prepopulate(system.n_nodes, table.first_node, table.nodes,
                              running0)
    # the per-node end time carries each job's own ``end`` value (a
    # gather, once; the prepopulation fill is not exact in f32)
    node_end = jnp.where(node_job >= 0, end[jnp.maximum(node_job, 0)],
                         jnp.inf)
    job_group_nodes = rm.prepopulate_groups(
        system.n_nodes, system.cooling.n_groups, table.first_node,
        table.nodes, running0)
    free_count = jnp.sum((node_job == -1).astype(jnp.int32))
    if accounts is None:
        accounts = T.AccountStats.zeros(num_accounts)
    else:
        # the ledger is embedded in the scan carry, which the runners
        # donate — copy so the caller's warm-start buffers survive the run
        accounts = jax.tree_util.tree_map(jnp.copy, accounts)
    # prepopulated jobs ran unthrottled before the window: work-time
    # progress equals their wall-clock elapsed at t0
    progress = jnp.where(running0, jnp.maximum(t0 - table.rec_start, 0.0),
                         0.0).astype(jnp.float32)
    return T.SimState(
        t=jnp.float32(t0), step=jnp.int32(0), jstate=jstate, start=start,
        end=end, progress=progress,
        jenergy=jnp.zeros((J,), jnp.float32), node_job=node_job,
        node_end=node_end, free_count=free_count,
        job_group_nodes=job_group_nodes, accounts=accounts,
        cooling=cooling.init_state(system.cooling),
        energy_total=jnp.float32(0.0), energy_it=jnp.float32(0.0),
        energy_loss=jnp.float32(0.0), completed=jnp.float32(0.0),
        emissions_kg=jnp.float32(0.0), energy_cost=jnp.float32(0.0),
        energy_cooling=jnp.float32(0.0), heat_reuse_j=jnp.float32(0.0),
        events=(None if events is None
                else events_mod.init_event_state(system)))


# ---------------------------------------------------------------------------
# Engine phases.
# ---------------------------------------------------------------------------
def _prepare_and_arrivals(system: SystemConfig, table: T.JobTable,
                          st: T.SimState, has_grid: bool) -> T.SimState:
    """Phases (1)+(2): completions, node release, accounting, arrivals.

    ``has_grid`` (static) picks the release: DVFS moves ``end`` after
    placement on the grid path, which frees nodes by a gather of the
    per-job flag; every other path compares ``t`` with ``node_end``.
    ``obs.timing.RELEASE_STATS`` counts which one each trace took."""
    with jax.named_scope(phases.PREPARE):
        t = st.t
        done_now = (st.jstate == T.RUNNING) & (t >= st.end)
        if has_grid:
            obs_timing.RELEASE_STATS["job_gather"] += 1
            node_job = rm.release_done_gather(st.node_job, done_now)
        else:
            obs_timing.RELEASE_STATS["node_end"] += 1
            node_job = rm.release_done(st.node_job, st.node_end, t)
        freed = jnp.sum(jnp.where(done_now, table.nodes, 0))
        jstate = jnp.where(done_now, T.DONE, st.jstate)
        accounts = acct_mod.fold_completions(system, table, st.accounts,
                                             done_now, st.start, st.end,
                                             st.jenergy)
        jstate = jnp.where((jstate == T.PENDING) & (table.submit <= t),
                           T.QUEUED, jstate)
        return dataclasses.replace(
            st, jstate=jstate, node_job=node_job,
            free_count=st.free_count + freed, accounts=accounts,
            completed=st.completed + jnp.sum(done_now))


def _tick(system: SystemConfig, table: T.JobTable, st: T.SimState,
          grid: gsig.GridNow | None, cap_active: jnp.ndarray | None,
          wx: wsig.WeatherNow | None = None,
          setpoint_delta_c=0.0,
          thermal: cooling.ThermalNow | None = None,
          cells_offline=0.0, cells_failed=0.0,
          ev_now: "events_mod.EventsNow | None" = None
          ) -> Tuple[T.SimState, T.StepRecord]:
    """Phase (4): cap enforcement + physics + accounting + telemetry.

    When the projected IT draw exceeds ``cap_active`` the DVFS pass
    (repro.grid.powercap) throttles every running node's dynamic power by a
    common factor c and the affected jobs' remaining runtime dilates by 1/c
    for this step — capping trades completion latency for peak power.
    ``grid is None`` is compile-time "no grid layer": no accrual, no
    dilation, no cap. Either way the cooling plant takes per-CDU heat
    summed from the per-job powers and the per-group occupancy
    (``repro.power.model.group_power``); no per-node power is formed.

    ``wx`` carries the ambient conditions for this step (°C, scalar or
    per-hall f32[H]); ``None`` is compile-time "no weather trace" and the
    static ``CoolingConfig`` wet-bulb applies. ``setpoint_delta_c`` and
    ``cells_offline`` are the traced sweep knobs
    (``Scenario.setpoint_delta_c`` / ``Scenario.cells_offline``).
    ``cells_failed`` / ``ev_now`` arrive from the failure pass
    (repro.events) when the event layer is on — failed cells degrade the
    cooling plant and the telemetry row picks up the outage counters.
    """
    dt = system.dt
    t = st.t
    has_grid = grid is not None
    t_wb = None if wx is None else wx.t_wetbulb_c
    with jax.named_scope(phases.POWER):
        # profiles are indexed by work-time progress, so a throttled job's
        # trace plays at its dilated tempo instead of wall-clock time
        job_pw = pmodel.job_node_power_elapsed(table, st.jstate,
                                               st.progress, system.prof_dt)
        occ = pmodel.group_occupancy(st.job_group_nodes, st.jstate)
        running = st.jstate == T.RUNNING
    if has_grid:
        with jax.named_scope(phases.POWER):
            idle = system.power.idle_node_w
            floor_g, dyn_g = powercap.group_split(system, occ, job_pw)
            cap = powercap.enforce_cap_groups(system, floor_g, dyn_g,
                                              cap_active)
            p_it = cap.p_it
            # DVFS only slows jobs with dynamic (above-idle) draw; a job at
            # or below the idle floor keeps full speed (its power is
            # untouched by throttle_power, so its runtime must be too)
            c_job = jnp.where(running & (job_pw > idle), cap.c, 1.0)
            job_pw = powercap.throttle_power(job_pw, idle, cap.c)
            throttle = 1.0 - cap.c
        with jax.named_scope(phases.COOLING):
            group_heat = cap.group_heat
    else:
        cap_active = T.INF
        throttle = jnp.float32(0.0)
        with jax.named_scope(phases.POWER):
            group_heat = pmodel.group_power(system, occ, job_pw)
            p_it = jnp.sum(group_heat)
    with jax.named_scope(phases.COOLING):
        cool_state, cool = cooling.step(system.cooling, st.cooling,
                                        group_heat, dt, t_wb,
                                        setpoint_delta_c, cells_offline,
                                        cells_failed)
    with jax.named_scope(phases.POWER):
        n_racks = max(system.n_nodes // system.power.nodes_per_rack, 1)
        p_in, p_loss = plosses.conversion(system.power, p_it, float(n_racks))
    with jax.named_scope(phases.TELEMETRY):
        p_cool = cool.p_cooling
        t_tower_ret = cool.t_tower_return
        p_total = p_in + p_cool
        pue = cooling.pue(p_it, p_loss, p_cool)

        job_e_step = jnp.where(
            running, job_pw * table.nodes.astype(jnp.float32) * dt, 0.0)
        jenergy = st.jenergy + job_e_step

        if has_grid:
            accounts = acct_mod.accrue_grid(table, st.accounts, job_e_step,
                                            grid.carbon, grid.price)
            # runtime dilation: a throttled step advances a job's work-time by
            # only c*dt (each unit of work takes 1/c longer), so its projected
            # end recedes by the shortfall dt*(1 - c). The two views agree:
            # t >= end  <=>  progress >= wall.  A job throttled at c for its
            # whole life runs 1/c times longer in total.
            end = jnp.where(running & jnp.isfinite(st.end),
                            st.end + dt * (1.0 - c_job), st.end)
            progress = st.progress + jnp.where(running, c_job * dt, 0.0)
            emissions = p_total * dt * grid.carbon / 3.6e6 * 1e-3  # kg
            cost = p_total * dt * grid.price / 3.6e6               # $
        else:
            accounts = st.accounts
            end = st.end
            progress = st.progress + jnp.where(running, dt, 0.0)
            emissions = jnp.float32(0.0)
            cost = jnp.float32(0.0)

        busy = jnp.float32(system.n_nodes) - st.free_count.astype(jnp.float32)
        if ev_now is not None:
            # down free nodes are parked at -2 (outside the -1 free pool), so
            # they'd otherwise count as busy; utilization should count work
            busy = busy - ev_now.nodes_down
        H = system.cooling.n_halls
        rec = T.StepRecord(
            t=t, power_it=p_it, power_loss=p_loss, power_cooling=p_cool,
            power_total=p_total, pue=pue, t_tower_return=t_tower_ret,
            util=busy / system.n_nodes,
            n_queued=jnp.sum(st.jstate == T.QUEUED).astype(jnp.float32),
            n_running=jnp.sum(running).astype(jnp.float32),
            emissions_kg=emissions, energy_cost=cost, cap_w=cap_active,
            throttle_frac=throttle,
            power_fan=cool.p_fan, power_pump=cool.p_pump,
            q_reuse_w=cool.q_reuse_w, t_basin=cool.t_basin,
            t_supply_max=cool.t_supply_max,
            t_wetbulb=(jnp.float32(system.cooling.t_wetbulb_c) if wx is None
                       else jnp.mean(wx.t_wetbulb_c)),
            thermal_throttled=(jnp.float32(0.0) if thermal is None else
                               thermal.overheat.astype(jnp.float32)),
            # per-hall telemetry: the hall heat sums ARE the per-hall IT power
            # (the cooling plant is fed the (throttled) IT draw per group)
            power_it_hall=cool.q_hall_w, t_basin_hall=cool.t_basin_hall,
            t_supply_max_hall=cool.t_supply_max_hall,
            t_wetbulb_hall=cool.t_wetbulb_hall, cells_online=cool.cells_online,
            nodes_down=(jnp.float32(0.0) if ev_now is None
                        else ev_now.nodes_down),
            n_killed=(jnp.float32(0.0) if ev_now is None else ev_now.n_killed),
            overheat_hall=(jnp.zeros((H,), jnp.float32) if thermal is None
                           else thermal.overheat_hall.astype(jnp.float32)))

        new = dataclasses.replace(
            st, t=t + dt, step=st.step + 1, end=end, progress=progress,
            jenergy=jenergy, accounts=accounts, cooling=cool_state,
            energy_total=st.energy_total + p_total * dt,
            energy_it=st.energy_it + p_it * dt,
            energy_loss=st.energy_loss + p_loss * dt,
            emissions_kg=st.emissions_kg + emissions,
            energy_cost=st.energy_cost + cost,
            energy_cooling=st.energy_cooling + p_cool * dt,
            heat_reuse_j=st.heat_reuse_j + cool.q_reuse_w * dt)
        return new, rec


def engine_step(system: SystemConfig, table: T.JobTable, st: T.SimState,
                scen: T.Scenario, signals: gsig.GridSignals | None = None,
                weather: wsig.WeatherSignals | None = None,
                events: "events_mod.EventConfig | None" = None
                ) -> Tuple[T.SimState, T.StepRecord]:
    """One engine step: phases (1)-(4). ``signals`` enables the grid layer,
    ``weather`` drives the cooling tower's ambient wet-bulb, ``events``
    enables the stochastic failure + demand-response layer (repro.events);
    all three are compile-time ``None`` when absent (their machinery folds
    away and the graph is bit-identical to the pre-events engine)."""
    st = _prepare_and_arrivals(system, table, st, signals is not None)
    if events is not None:
        # phase (2b): draw failures/repairs, kill hit jobs, update the
        # availability map; DR cap steps are evaluated at the same point
        with jax.named_scope(phases.FAILURES):
            st, ev_now = events_mod.apply_failures(events, system, table,
                                                   st, scen)
            dr = events_mod.dr_now(scen, st.t)
            cells_failed = ev_now.cells_failed_hall
    else:
        ev_now = None
        dr = None
        cells_failed = 0.0
    with jax.named_scope(phases.QUEUE_ORDER):
        wx = None if weather is None else wsig.at_step(weather, st.step)
        # cooling-pressure signals for the thermal_aware policy + admission
        # gate
        thermal = cooling.thermal_now(system.cooling, st.cooling,
                                      scen.setpoint_delta_c)
    if signals is None:
        # no grid layer: skip the admission power pass and cap machinery
        # (demand-response needs the grid path — the CLI injects neutral
        # signals when DR knobs are set without a grid trace)
        st = sched.schedule_step(system, table, st, scen, thermal=thermal)
        return _tick(system, table, st, None, None, wx,
                     scen.setpoint_delta_c, thermal, scen.cells_offline,
                     cells_failed, ev_now)
    with jax.named_scope(phases.POWER):
        grid = gsig.at_step(signals, st.step)
        cap_active = grid.cap_w * scen.cap_scale
        if dr is not None:
            # an active demand-response event caps below the schedule
            cap_active = jnp.minimum(cap_active, dr.cap_now_w)
        # raw IT draw after completions: the cap-aware admission baseline
        job_pw = pmodel.job_node_power_elapsed(table, st.jstate, st.progress,
                                               system.prof_dt)
        occ = pmodel.group_occupancy(st.job_group_nodes, st.jstate)
        proj_pw = jnp.sum(pmodel.group_power(system, occ, job_pw))
    st = sched.schedule_step(system, table, st, scen, grid, proj_pw=proj_pw,
                             thermal=thermal, dr=dr)
    return _tick(system, table, st, grid, cap_active, wx,
                 scen.setpoint_delta_c, thermal, scen.cells_offline,
                 cells_failed, ev_now)


# ---------------------------------------------------------------------------
# Plugin mode for external event-based schedulers (paper §4.2).
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=(0,))
def external_step(system: SystemConfig, table: T.JobTable, st: T.SimState,
                  place_ids: jnp.ndarray,
                  signals: gsig.GridSignals | None = None,
                  weather: wsig.WeatherSignals | None = None,
                  scen: T.Scenario | None = None
                  ) -> Tuple[T.SimState, T.StepRecord]:
    """One engine step where placement decisions come from outside.

    ``place_ids``: i32[K] job ids the external scheduler wants started now
    (padded with -1). S-RAPS "interprets the information returned from the
    scheduler ... and triggers the resource manager" (paper §3.2.4).
    The cap schedule (when ``signals`` is given) and the thermal admission
    gate still apply — an external scheduler cannot opt out of facility
    power or thermal management.

    ``scen`` routes the facility what-if knobs the external scheduler has
    no say over — ``cap_scale`` (scales the cap schedule),
    ``setpoint_delta_c`` (shifts the supply setpoint the overheat gate
    measures against) and ``cells_offline`` (tower maintenance); ``None``
    keeps every knob neutral. Policy/backfill fields are ignored: the
    external peer IS the policy.
    """
    with jax.named_scope(phases.QUEUE_ORDER):
        grid = None if signals is None else gsig.at_step(signals, st.step)
        wx = None if weather is None else wsig.at_step(weather, st.step)
    setpoint_delta = 0.0 if scen is None else scen.setpoint_delta_c
    cells_offline = 0.0 if scen is None else scen.cells_offline
    cap_scale = 1.0 if scen is None else scen.cap_scale
    st = _prepare_and_arrivals(system, table, st, signals is not None)
    hall_aware = system.cooling.n_halls > 1
    with jax.named_scope(phases.QUEUE_ORDER):
        thermal = cooling.thermal_now(system.cooling, st.cooling,
                                      setpoint_delta)
        thermal_ok = ~thermal.overheat
        if hall_aware:
            order_nodes, node_ok, free_ok0, group_pos = \
                sched.hall_placement_plan(system, st, thermal,
                                          is_replay=False)
        else:
            order_nodes = None
            free_ok0 = st.free_count
            group_pos = jnp.arange(system.cooling.n_groups, dtype=jnp.int32)

    def body(i, carry):
        free_count, free_ok, placed = carry
        need = need_k[i]
        th_ok = (need <= free_ok) if hall_aware else thermal_ok
        # a repeated id starts at most once: not if placed earlier here
        fresh = ~jnp.any(placed & (place_ids == place_ids[i]))
        can = queued[i] & fresh & (need <= free_count) & th_ok
        if hall_aware:
            # this placement takes the free ranks (c, c + need]
            c = st.free_count - free_count
            free_ok = free_ok - jnp.where(
                can, ok_upto[c + need] - ok_upto[c], 0)
        # (inert carry on a flat plant — the global gate never reads it)
        free_count = free_count - jnp.where(can, need, 0)
        return free_count, free_ok, placed.at[i].set(can)

    with jax.named_scope(phases.ADMISSION):
        jj = jnp.maximum(place_ids, 0)
        queued = (place_ids >= 0) & (st.jstate[jj] == T.QUEUED)
        need_k = table.nodes[jj]
        rank = rm.free_ranks(st.node_job, order_nodes)
        if hall_aware:
            ok_upto = rm.ok_upto(rank, node_ok)
        K = place_ids.shape[0]
        carry = (st.free_count, jnp.int32(free_ok0), jnp.zeros((K,), bool))
        _, _, placed = jax.lax.fori_loop(0, K, body, carry)
        st = rm.commit_placements(st, rank, group_pos, place_ids, placed,
                                  need_k, st.t + table.wall[jj])
    return _tick(system, table, st, grid,
                 None if grid is None else grid.cap_w * cap_scale, wx,
                 setpoint_delta, thermal, cells_offline)


# ---------------------------------------------------------------------------
# Full simulation: one cached scan runner behind every entry.
# ---------------------------------------------------------------------------
# Buffer donation on the scan carry: the input carry and the output carry
# are the same SimState pytree, so XLA can write the scan in place instead
# of allocating a second full copy of the (node map + job lifecycle +
# ledgers) state per call. A donated input is consumed: every entry either
# builds its carry fresh (init_state / jnp.stack) or its callers treat the
# passed carry as moved-from (repro.serve reassigns; see docs/serving.md).
# Only a per-row carry is donated: tables/signals are broadcast inputs
# reused across calls, and a sweep's broadcast st0 cannot alias its
# batched output. REPRO_NO_DONATE=1 disables donation for debugging
# (e.g. to inspect a carry after a call that consumed it).
DONATE_CARRIES = not os.environ.get("REPRO_NO_DONATE")


def _donate(*argnums: int) -> tuple:
    return tuple(argnums) if DONATE_CARRIES else ()


# The compiled-runner cache, keyed on (system, n_steps, events, axes, mesh,
# static, sizes) — every argument of ``_runner``. It is LRU-bounded: a
# long-lived server (repro.serve) advancing many distinct segment lengths
# would otherwise grow it without limit — least-recently-used runners are
# evicted past ``SWEEP_CACHE_LIMIT`` (dropping a compiled runner is safe:
# the next same-shape call re-jits and re-enters the cache).
_SWEEP_CACHE: "collections.OrderedDict" = collections.OrderedDict()
# ``bench/tests`` clears the cache under this older name as well.
_STATIC_CACHE = _SWEEP_CACHE
SWEEP_CACHE_LIMIT = 32
# Monotonic hit/miss/eviction counters of the runner cache, always on. A
# steady-state training loop should show hits only after generation 0;
# ``ml.train`` snapshots the deltas per generation and the run manifest
# embeds the totals.
SWEEP_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def _cache_lookup(key):
    """LRU lookup in the runner cache; bumps the hit/miss counters."""
    fn = _SWEEP_CACHE.pop(key, None)
    if fn is not None:
        _SWEEP_CACHE[key] = fn              # now the most recently used
    SWEEP_CACHE_STATS["hits" if fn is not None else "misses"] += 1
    return fn


def _cache_store(key, fn):
    """Insert a runner, evicting least-recently-used entries past the
    bound (counted in ``SWEEP_CACHE_STATS["evictions"]``)."""
    _SWEEP_CACHE.pop(key, None)
    _SWEEP_CACHE[key] = fn
    while len(_SWEEP_CACHE) > SWEEP_CACHE_LIMIT:
        del _SWEEP_CACHE[next(iter(_SWEEP_CACHE))]
        SWEEP_CACHE_STATS["evictions"] += 1
    return fn


# Argument specs of each runner's first call (a cache hit records only
# which runner ran), so ``last_runner_hlo`` can lower it again.
_RUNNER_ARGS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_last_runner = None


def _spec(x) -> jax.ShapeDtypeStruct:
    """What a jitted call specializes on: shape, dtype, weak type, and
    the sharding of an argument committed to its devices."""
    aval = jax.typeof(x)
    sharding = x.sharding if getattr(x, "committed", False) else None
    return jax.ShapeDtypeStruct(aval.shape, aval.dtype, sharding=sharding,
                                weak_type=aval.weak_type)


def _launch(fn, *args):
    """Call runner ``fn`` inside an ``engine.launch`` span (until the
    call returns: the device runs on asynchronously)."""
    global _last_runner
    if fn not in _RUNNER_ARGS:
        _RUNNER_ARGS[fn] = jax.tree_util.tree_map(_spec, args)
    _last_runner = weakref.ref(fn)
    with obs_timing.span("engine.launch"):
        return fn(*args)


def last_runner_hlo() -> str | None:
    """Optimized HLO text of the runner the engine called last, lowered
    and compiled again from the argument specs of its first call (a hit
    in the in-memory or persistent compilation cache); None before any
    call. ``repro.obs.phases`` reads each instruction's phase from it."""
    fn = _last_runner() if _last_runner is not None else None
    if fn is None or fn not in _RUNNER_ARGS:
        return None
    return fn.lower(*_RUNNER_ARGS[fn]).compile().as_text()


def _scan(n_steps: int, carry, step):
    """``lax.scan`` of ``step`` (carry -> (carry, StepRecord)) over
    ``n_steps``: (final carry, history). The scan's own per-step work,
    its counter and the write of each history row, is telemetry;
    ``engine_step`` names the phases of everything else."""
    with jax.named_scope(phases.TELEMETRY):
        return jax.lax.scan(lambda st, _: step(st), carry, None,
                            length=int(n_steps))


def sweep_mesh(devices=None) -> jax.sharding.Mesh:
    """1-D ``("scenario",)`` mesh over ``devices`` (default: every local
    device): the what-if sweep axis of ``simulate_sweep_sharded``.
    Scenario rows are embarrassingly parallel (they share the job table
    and signal arrays by replication), so a flat mesh is always the right
    shape."""
    devs = jax.devices() if devices is None else list(devices)
    return jax.sharding.Mesh(np.array(devs), ("scenario",),
                             axis_types=(jax.sharding.AxisType.Auto,))


def scenario_spec() -> jax.sharding.PartitionSpec:
    """PartitionSpec sharding dim0 over the sweep mesh's scenario axis."""
    return jax.sharding.PartitionSpec("scenario")


def pad_leading_axis(tree, multiple: int):
    """Pad every leaf's leading axis up to a multiple of ``multiple`` by
    replicating the last row (scenario batches must divide the mesh; the
    padded rows are dropped by the caller). Returns (padded_tree, pad)."""
    sizes = {leaf.shape[0] for leaf in jax.tree_util.tree_leaves(tree)}
    if len(sizes) != 1:
        raise ValueError(f"inconsistent leading-axis sizes: {sorted(sizes)}")
    (size,) = sizes
    pad = (-size) % multiple

    def one(x):
        if pad == 0:
            return x
        rep = jnp.broadcast_to(x[-1:], (pad,) + tuple(x.shape[1:]))
        return jnp.concatenate([x, rep], axis=0)
    return jax.tree_util.tree_map(one, tree), pad


def _runner(system: SystemConfig, n_steps: int, events=None, *, axes=None,
            mesh=None, static=None, sizes=()):
    """The cached jitted scan of ``n_steps`` engine steps that every entry
    runs: (table, carry, scenario, signals, weather) -> (carry, history).

    ``jax.jit`` caches traces per *function identity*; building the runner
    here, once per key, means repeated same-shape calls — the ES training
    loop's per-generation rollouts (repro.ml.train), a server's segments
    (repro.serve) — compile once and then run at steady-state throughput.

    Args:
      axes: None for a one-row program. Otherwise the vmap's
        ``(carry_axis, weather_axis)``, each 0 (one per row) or None
        (shared by broadcast); the scenario is always batched on axis 0,
        the table and grid signals are always shared.
      mesh: a 1-D ``("scenario",)`` mesh (``sweep_mesh``) to shard the
        rows over, one ``shard_map`` around the vmap; the batch must
        divide its size. Exposed so the program can be lowered for a mesh
        of devices that are described rather than attached.
      static: compile-time ``(policy, backfill)`` ids closed over in place
        of the scenario operand (pass None for it), so only the selected
        priority key is computed and every policy select folds away.
      sizes: further key parts, for a caller whose operand sizes should
        each get a runner of their own (``simulate_static``'s table and
        ledger sizes: ``last_runner_hlo`` then lowers the one it ran).

    The carry is donated exactly when it is per-row: one-row programs and
    a batch of carries, never a sweep's broadcast ``st0``.
    """
    key = (system, int(n_steps), events, axes, mesh, static, sizes)
    fn = _cache_lookup(key)
    if fn is not None:
        return fn
    if static is not None:
        # keyword construction with raw Python ints (-> static in the
        # closure): every knob past policy/backfill takes its declared
        # neutral default, so growing Scenario can never silently shift
        # knobs
        fixed = T.Scenario(policy=static[0], backfill=static[1])

    # signals/weather=None are empty pytrees and events=None is static
    # (closed over): the no-grid / no-weather / no-failure fast paths in
    # engine_step are selected at trace time and their machinery vanishes
    # entirely
    def run(table_, carry_, scen_, signals_, weather_):
        scen1 = fixed if static is not None else scen_
        return _scan(n_steps, carry_, lambda st: engine_step(
            system, table_, st, scen1, signals_, weather_, events))

    per_row = axes is None or axes[0] == 0
    if axes is not None:
        carry_axis, w_axis = axes
        run = jax.vmap(run, in_axes=(None, carry_axis, 0, None, w_axis))
    if mesh is not None:
        rows, rep = scenario_spec(), jax.sharding.PartitionSpec()
        spec = lambda axis: rows if axis == 0 else rep
        # check_vma=False: every loop carry inside engine_step (the scan's
        # replicated initial state, the admission loop's fresh
        # constants) becomes per-device data after one step, which the
        # varying-axes type check rejects. The body has no collective
        # whose result depends on those types: scenario rows never
        # communicate.
        run = jax.shard_map(
            run, mesh=mesh,
            in_specs=(rep, spec(carry_axis), rows, rep, spec(w_axis)),
            out_specs=rows, check_vma=False)
    return _cache_store(key, jax.jit(
        run, donate_argnums=_donate(1) if per_row else ()))


def simulate(system: SystemConfig, table: T.JobTable, scen: T.Scenario,
             t0: float, t1: float,
             accounts: T.AccountStats | None = None,
             num_accounts: int = 64,
             signals: gsig.GridSignals | None = None,
             weather: wsig.WeatherSignals | None = None,
             carry: T.SimState | None = None,
             events: "events_mod.EventConfig | None" = None
             ) -> Tuple[T.SimState, T.StepRecord]:
    """Run the twin from ``t0`` to ``t1`` (seconds).

    Args:
      system: static machine description (compile-time constant).
      table: padded job table (times s, power W).
      scen: traced scenario knobs (policy, backfill, weights).
      t0, t1: simulation window (s).
      accounts: optional warm-start per-account ledgers.
      num_accounts: ledger size when ``accounts`` is None.
      signals: per-step grid signals (g CO2/kWh, $/kWh, cap W) — enables
        carbon/price accounting, the facility power-cap schedule and the
        grid-aware policies. ``None`` = neutral (zero carbon/price,
        uncapped).
      weather: per-step ambient conditions (°C) driving the cooling tower.
        ``None`` = the static ``CoolingConfig.t_wetbulb_c``.
      carry: start from this scan carry instead of ``init_state`` (the
        resume-from-checkpoint path, repro.serve). ``t0``/``t1`` still
        size the window: ``n_steps = (t1 - t0) / dt`` steps run *from
        the carry's own clock*. The carry is donated.
      events: static ``EventConfig`` enabling the stochastic failure +
        demand-response layer (repro.events); the per-scenario rates and
        seeds stay traced ``Scenario`` knobs. ``None`` = bit-identical
        pre-events engine. A passed ``carry`` must match (its ``events``
        subtree present iff an ``EventConfig`` is given).
    Returns:
      (final SimState, StepRecord history with one row per step).
    """
    with obs_timing.span("engine.dispatch", entry="simulate"):
        n_steps = int(round((t1 - t0) / system.dt))
        if carry is None:
            with obs_timing.span("engine.init_state"):
                carry = init_state(system, table, t0, t1, accounts,
                                   num_accounts, events)
        with obs_timing.span("engine.runner"):
            fn = _runner(system, n_steps, events)
        return _launch(fn, table, carry, scen, signals, weather)


def simulate_static(system: SystemConfig, table: T.JobTable, policy: str,
                    backfill: str, t0: float, t1: float,
                    accounts: T.AccountStats | None = None,
                    num_accounts: int = 64,
                    signals: gsig.GridSignals | None = None,
                    weather: wsig.WeatherSignals | None = None,
                    carry: T.SimState | None = None):
    """Single-scenario fast path: policy/backfill are *compile-time*
    constants, so only the selected priority key is computed, non-EASY runs
    skip the reservation machinery entirely, and all policy selects fold
    away (docs/architecture.md, "The engine is a single lax.scan").

    ``carry`` starts the scan from an arbitrary checkpointed state
    instead of ``init_state`` (see ``simulate``)."""
    with obs_timing.span("engine.dispatch", entry="simulate_static"):
        n_steps = int(round((t1 - t0) / system.dt))
        with obs_timing.span("engine.scenarios"):
            static = (T.POLICY_NAMES[policy], T.BACKFILL_NAMES[backfill])
        if carry is None:
            with obs_timing.span("engine.init_state"):
                carry = init_state(system, table, t0, t1, accounts,
                                   num_accounts)
        with obs_timing.span("engine.runner"):
            fn = _runner(system, n_steps, static=static, sizes=(
                table.num_jobs, table.prof_len, num_accounts,
                signals is None, weather is None))
        return _launch(fn, table, carry, None, signals, weather)


def _stack_weather(weather, scens):
    """(weather operand, vmap axis): a list of traces, one per scenario,
    stacks onto the batch axis (axis 0); a single trace or None is shared
    by broadcast (axis None)."""
    if isinstance(weather, (list, tuple)):
        if len(weather) != len(scens):
            raise ValueError(f"need one weather trace per scenario: "
                             f"{len(weather)} != {len(scens)}")
        return wsig.stack_weather(weather), 0
    return weather, None


def simulate_sweep(system: SystemConfig, table: T.JobTable,
                   scens: list[T.Scenario], t0: float, t1: float,
                   accounts: T.AccountStats | None = None,
                   num_accounts: int = 64,
                   signals: gsig.GridSignals | None = None,
                   weather=None,
                   events: "events_mod.EventConfig | None" = None,
                   ) -> Tuple[T.SimState, T.StepRecord]:
    """Vectorized what-if sweep: one compiled program, S scenarios.

    The job table, initial state and grid signals are shared (broadcast);
    only the Scenario leaves carry a batch axis — so a (policy x cap-level
    x carbon-weight) sweep reads ONE signal set and scales the cap via
    ``Scenario.cap_scale``.

    ``weather`` may be a single ``WeatherSignals`` (shared by broadcast,
    like signals) or a *list* with one trace per scenario — stacked onto
    the batch axis so a (policy x weather-scenario x setpoint) sweep runs
    as one vmapped program (see examples/cooling_whatif.py).

    ``events`` (static ``EventConfig``) turns on the failure layer for the
    whole sweep; each scenario row then carries its own failure universe
    through the traced ``failure_seed``/rate knobs — a (seed x rate x
    demand-response) risk grid is one compiled program.
    """
    with obs_timing.span("engine.dispatch", entry="simulate_sweep"):
        n_steps = int(round((t1 - t0) / system.dt))
        with obs_timing.span("engine.init_state"):
            st0 = init_state(system, table, t0, t1, accounts, num_accounts,
                             events)
        batched = T.stack_scenarios(scens)
        weather_b, w_axis = _stack_weather(weather, scens)
        with obs_timing.span("engine.runner"):
            fn = _runner(system, n_steps, events, axes=(None, w_axis))
        return _launch(fn, table, st0, batched, signals, weather_b)


def simulate_sweep_sharded(system: SystemConfig, table: T.JobTable,
                           scens: list[T.Scenario], t0: float, t1: float,
                           accounts: T.AccountStats | None = None,
                           num_accounts: int = 64,
                           signals: gsig.GridSignals | None = None,
                           weather=None,
                           events: "events_mod.EventConfig | None" = None,
                           ) -> Tuple[T.SimState, T.StepRecord]:
    """``simulate_sweep`` with the scenario axis sharded across devices.

    One ``jax.shard_map`` over a 1-D ``("scenario",)`` mesh
    (``sweep_mesh()`` over every local device; ``_runner(..., mesh=)``
    takes any other): each device scans its slice of the scenario batch
    with the job table, initial state and grid signals replicated —
    scenario rows never communicate, so the program contains no
    collectives and scales linearly across hosts. Per-scenario weather
    (a list, possibly hall-stacked — see ``cooling.weather.stack_halls``)
    is sharded with the scenarios. The batch is padded to the device
    count by replicating the last scenario; padded rows are sliced off
    the result. With a single device this degenerates to exactly
    ``simulate_sweep`` (one vmapped program, no sharding machinery).
    """
    mesh = sweep_mesh()
    n_dev = mesh.devices.size
    if n_dev <= 1:
        return simulate_sweep(system, table, scens, t0, t1, accounts,
                              num_accounts, signals, weather, events)
    with obs_timing.span("engine.dispatch", entry="simulate_sweep_sharded"):
        n_steps = int(round((t1 - t0) / system.dt))
        with obs_timing.span("engine.init_state"):
            st0 = init_state(system, table, t0, t1, accounts, num_accounts,
                             events)
        weather_b, w_axis = _stack_weather(weather, scens)
        S = len(scens)
        batched, _ = pad_leading_axis(T.stack_scenarios(scens), n_dev)
        if w_axis == 0:
            weather_b, _ = pad_leading_axis(weather_b, n_dev)
        with obs_timing.span("engine.runner"):
            fn = _runner(system, n_steps, events, axes=(None, w_axis),
                         mesh=mesh)
        final, hist = _launch(fn, table, st0, batched, signals, weather_b)
        trim = lambda x: x[:S]
        return (jax.tree_util.tree_map(trim, final),
                jax.tree_util.tree_map(trim, hist))


# ---------------------------------------------------------------------------
# Segment simulation (resume-from-checkpoint; repro.serve).
# ---------------------------------------------------------------------------
def simulate_segment(system: SystemConfig, table: T.JobTable,
                     carry: T.SimState, scen: T.Scenario, n_steps: int,
                     signals: gsig.GridSignals | None = None,
                     weather: wsig.WeatherSignals | None = None,
                     events: "events_mod.EventConfig | None" = None
                     ) -> Tuple[T.SimState, T.StepRecord]:
    """Advance the twin ``n_steps`` from an arbitrary scan carry.

    The carry IS the complete simulation state (``SimState`` holds the
    job lifecycle, node occupancy, account ledgers, the transient
    ``CoolingState`` and the step cursor), so chaining segments is
    bit-identical to one uninterrupted ``simulate`` scan: the per-step
    body is the same ``engine_step`` and per-step environment inputs
    (grid signals, weather) are gathered at the carry's *absolute*
    ``step`` cursor — pass the same full-horizon arrays to every
    segment. This is the persistent-server primitive: checkpoint the
    carry at interval boundaries, resume or fork later without
    re-simulating the prefix (``repro.serve``, docs/serving.md).

    Args:
      system: static machine description (compile-time constant).
      table: padded job table shared by every segment.
      carry: the scan carry to start from — ``init_state(...)`` for a
        fresh trajectory, or any previously returned carry.
      scen: traced scenario knobs for *this* segment (a fork changes
        them mid-trajectory).
      n_steps: number of engine steps to advance.
      signals / weather: full-horizon per-step inputs (indexed by the
        carry's absolute step, clamped LOCF past the end).
      events: static ``EventConfig``; must match the carry's lineage (an
        ``EventState`` subtree is present iff the layer is on). Serve
        sessions use this to fork failure-injected branches.
    Returns:
      (carry after ``n_steps``, StepRecord history of the segment).
    """
    with obs_timing.span("engine.dispatch", entry="simulate_segment"):
        with obs_timing.span("engine.runner"):
            fn = _runner(system, n_steps, events)
        return _launch(fn, table, carry, scen, signals, weather)


def simulate_segment_sweep(system: SystemConfig, table: T.JobTable,
                           carries, scens, n_steps: int,
                           signals: gsig.GridSignals | None = None,
                           weather: wsig.WeatherSignals | None = None,
                           events: "events_mod.EventConfig | None" = None
                           ) -> Tuple[T.SimState, T.StepRecord]:
    """Batched ``simulate_segment``: B divergent branches as one program.

    Unlike ``simulate_sweep`` (one shared ``init_state`` broadcast), the
    *carry* rides the batch axis too, so branches that have already
    diverged — different fork points, different histories — advance
    together: one compiled program per (system, segment length), B
    lock-stepped scans inside. This is what lets a serving session
    coalesce concurrent client what-ifs into a single dispatch
    (repro.serve.session). The stacked carries are a fresh buffer every
    call, so donating them is always safe.

    Args:
      carries: list of ``SimState`` carries (stacked on axis 0), one per
        branch. All must come from the same (system, table) lineage.
      scens: list of ``Scenario``, one per branch.
      n_steps: segment length shared by the batch.
    Returns:
      (stacked carries after ``n_steps``, stacked StepRecord histories).
    """
    if len(carries) != len(scens):
        raise ValueError(f"need one carry per scenario: "
                         f"{len(carries)} != {len(scens)}")
    with obs_timing.span("engine.dispatch", entry="simulate_segment_sweep"):
        with obs_timing.span("engine.init_state"):
            stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                             *carries)
        batched = T.stack_scenarios(list(scens))
        with obs_timing.span("engine.runner"):
            fn = _runner(system, n_steps, events, axes=(0, None))
        return _launch(fn, table, stacked, batched, signals, weather)
