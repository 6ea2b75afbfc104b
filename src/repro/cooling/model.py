"""Transient thermo-fluid cooling twin: CDUs, facility HX, towers, basins —
hierarchical: halls -> CDU groups -> nodes.

Stand-in for the Modelica transient model of Kumar et al. [25] / Greenwood
et al. [22] used by ExaDigiT, grown from the original first-order lumped
model into a small transient plant so Fig. 6-style "what does this schedule
do to the tower loop?" questions — and their weather and maintenance
what-ifs — have real dynamics behind them. The plant is a
``FacilityTopology`` (repro.systems.config): each *hall* owns a tower loop
(basin + fan cells) serving its contiguous span of CDU groups, with its
own ambient wet-bulb (per-hall weather traces) and its own maintenance
state (``cells_offline``). A flat plant is the one-hall special case and
reproduces the pre-hierarchy behavior exactly. Per engine step ``dt``
(units: W, kg/s, °C, s):

CDU loop, per group g (``kernels.power_topo.cdu_update_ref`` — fused with
the node->group segment reduction on the accelerated path; each group
relaxes against its *hall's* basin):
  valve      mdot[g]  -> demand q[g]/(cp·ΔT_design), slewed with tau_valve
  pickup     T_ret[g]  = T_sup[g] + q[g]/(mdot[g]·cp)
  supply     T_sup[g] -> max(setpoint, T_basin[hall(g)] + q[g]/UA),
             relaxed w/ tau_hx

Heat reuse (district-heating export), per hall: when the hall's
flow-weighted return temp is hot enough to be useful, up to ``reuse_frac``
of that hall's heat (capped at its share of ``reuse_max_w``) is diverted
before the tower and never loads it.

Tower + basin, per hall h:
  staging    s[h] -> (q_tower[h] + basin-error correction)/(cell_ua·ΔT),
              slewed with tau_fan, clipped to [0, cells online in h] —
              ``cells_offline`` (maintenance) shrinks the ceiling
  rejection  q_rej[h] = s[h]·cell_ua·(T_basin[h] − T_wb[h])  (evaporative:
              the hall's wet-bulb is the floor — per-hall weather enters
              the twin here)
  basin      M[h]·cp·dT_basin[h]/dt = q_tower[h] − q_rej[h]

Parasitic power: tower fans follow a staged cube law per hall (whole cells
at rated power + the modulating cell at speed³); CDU pumps follow a cube
law on flow fraction with a 20% base. PUE = (P_IT + P_loss + P_cool) /
P_IT, calibrated so nominal load lands near the paper's note of ~1.06 for
the real system.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core.types import CoolingState
from repro.kernels.power_topo import ops as topo_ops
from repro.kernels.power_topo.ref import (CduParams, cdu_update_ref,
                                          hall_matrix, hall_max_ref,
                                          segment_dot)
from repro.systems.config import CoolingConfig


class CoolingOut(NamedTuple):
    """Per-step cooling telemetry. Scalars are facility aggregates (max /
    flow-weighted mix / sum over halls — identical to the flat-plant
    values when H = 1); ``*_hall`` fields carry the per-hall view
    (f32[H])."""
    p_cooling: jnp.ndarray      # total cooling parasitics, fans + pumps (W)
    p_fan: jnp.ndarray          # tower fan power (W)
    p_pump: jnp.ndarray         # CDU pump power (W)
    t_tower_return: jnp.ndarray  # flow-weighted water temp at the towers (°C)
    t_basin: jnp.ndarray        # hottest basin temperature after the step (°C)
    t_supply_max: jnp.ndarray   # hottest CDU supply temperature (°C)
    t_return_max: jnp.ndarray   # hottest CDU return temperature (°C)
    q_reuse_w: jnp.ndarray      # heat exported for reuse this step (W)
    q_reject_w: jnp.ndarray     # heat rejected by the towers this step (W)
    # per-hall telemetry (H = FacilityTopology.n_halls)
    q_hall_w: jnp.ndarray          # f32[H] heat landing in each hall (W)
    t_basin_hall: jnp.ndarray      # f32[H] basin temperature per hall (°C)
    t_supply_max_hall: jnp.ndarray  # f32[H] hottest CDU supply per hall (°C)
    t_return_max_hall: jnp.ndarray  # f32[H] hottest CDU return per hall (°C)
    q_reject_hall_w: jnp.ndarray   # f32[H] tower rejection per hall (W)
    fan_w_hall: jnp.ndarray        # f32[H] fan power per hall (W)
    cells_online: jnp.ndarray      # f32[H] tower cells available per hall
    t_wetbulb_hall: jnp.ndarray    # f32[H] ambient wet-bulb per hall (°C)


class ThermalNow(NamedTuple):
    """Cooling-pressure signals for the scheduler. Scalars aggregate over
    halls (max / any) — the flat-plant semantics; the ``*_hall`` arrays
    let the hall-aware placement and admission gate target (only) the
    overheating hall."""
    excess: jnp.ndarray      # f32[] how far the hottest return temp sits
    #                          inside the soft band below its limit (0 = cool,
    #                          1 = at the limit; unclipped above)
    overheat: jnp.ndarray    # bool[] supply setpoint lost by more than the
    #                          margin in SOME hall -> admission throttling
    t_return_max: jnp.ndarray  # f32[] hottest CDU return temperature (°C)
    t_supply_max: jnp.ndarray  # f32[] hottest CDU supply temperature (°C)
    excess_hall: jnp.ndarray   # f32[H] per-hall soft-band excess
    overheat_hall: jnp.ndarray  # bool[H] per-hall setpoint-lost flag


def cdu_params(cfg: CoolingConfig, dt: float) -> CduParams:
    """Static kernel scalars for the per-CDU loop update."""
    return CduParams(
        cp_j_kg_k=cfg.cp_j_kg_k, ua_w_k=cfg.ua_w_k, dt=dt,
        tau_hx_s=cfg.tau_hx_s, tau_valve_s=cfg.tau_valve_s,
        delta_t_design_c=cfg.delta_t_design_c,
        mdot_min_kg_s=cfg.mdot_min_frac * cfg.mdot_kg_s,
        mdot_max_kg_s=cfg.mdot_kg_s)


class _Halls(NamedTuple):
    """Static per-hall constants, materialized once per trace from the
    ``FacilityTopology`` (all f32[H] / f32[G] / f32[G, H] jnp constants)."""
    hog: jnp.ndarray        # i32[G] hall of each CDU group
    hmat: jnp.ndarray       # f32[G, H] one-hot group->hall matrix
    cells: jnp.ndarray      # f32[H] installed tower cells
    mcp: jnp.ndarray        # f32[H] basin thermal mass x cp (J/K)
    passive_ua: jnp.ndarray  # f32[H] fans-off ambient coupling (W/K)
    reuse_max: jnp.ndarray  # f32[H] heat-export capacity share (W)


def halls(cfg: CoolingConfig) -> _Halls:
    """Resolve the static topology into per-hall jnp constants."""
    hog_t = cfg.hall_of_group()
    H = cfg.n_halls
    cells = jnp.asarray(cfg.cells_per_hall(), jnp.float32)
    cell_ua = cfg.cell_ua()
    return _Halls(
        hog=jnp.asarray(hog_t, jnp.int32),
        hmat=hall_matrix(hog_t, H),
        cells=cells,
        mcp=jnp.asarray(cfg.basin_mcp_per_hall(), jnp.float32),
        passive_ua=cfg.passive_ua_frac * cells * cell_ua,
        reuse_max=cfg.reuse_max_w * jnp.asarray(cfg.hall_weights(),
                                                jnp.float32))


def init_state(cfg: CoolingConfig) -> CoolingState:
    """Idle-plant initial condition: supply at setpoint, valves at the floor,
    every hall's basin at wet-bulb + approach, fans off."""
    g = jnp.full((cfg.n_groups,), cfg.t_supply_setpoint_c, jnp.float32)
    H = cfg.n_halls
    return CoolingState(
        t_supply=g,
        t_return=g + 5.0,
        mdot=jnp.full((cfg.n_groups,), cfg.mdot_min_frac * cfg.mdot_kg_s,
                      jnp.float32),
        t_basin=jnp.full((H,), cfg.t_wetbulb_c + cfg.tower_approach_c,
                         jnp.float32),
        fan_stages=jnp.zeros((H,), jnp.float32))


def _effective(cfg: CoolingConfig, t_wetbulb_c, setpoint_delta_c):
    """(per-hall ambient wet-bulb f32[H], effective supply setpoint f32[])
    for this step (°C).

    Single source of the two per-step knobs: the wet-bulb defaults to the
    static config when no weather trace drives the run and broadcasts a
    shared trace across halls (a per-hall trace arrives as f32[H], see
    ``repro.cooling.weather.stack_halls``); the setpoint is the config
    value shifted by the traced ``Scenario.setpoint_delta_c``.
    """
    t_wb = jnp.float32(cfg.t_wetbulb_c) if t_wetbulb_c is None \
        else jnp.asarray(t_wetbulb_c, jnp.float32)
    t_wb = jnp.broadcast_to(t_wb, (cfg.n_halls,))
    t_set = cfg.t_supply_setpoint_c + jnp.asarray(setpoint_delta_c,
                                                  jnp.float32)
    return t_wb, t_set


def _finish_step(cfg: CoolingConfig, state: CoolingState, dt: float,
                 t_wb, t_set, q, t_return, t_supply, mdot,
                 cells_offline=0.0, cells_failed=0.0, q_hall=None
                 ) -> tuple[CoolingState, CoolingOut]:
    """Tower-side half of the step, vectorized over halls: reuse split, fan
    staging, basin mass, parasitic power. ``q``/``t_return``/``t_supply``/
    ``mdot`` come from the CDU update (plain jnp or the fused kernel);
    ``t_wb`` is the per-hall wet-bulb f32[H]; ``t_set`` the effective
    (setpoint-swept) supply setpoint the basin targets follow;
    ``cells_offline`` the traced maintenance knob (scalar or f32[H]);
    ``cells_failed`` the stochastic-failure cell count from the event
    layer (scalar or f32[H]) — unlike planned maintenance, a *failed*
    cell also loses its passive windage coupling (seized fan, closed
    dampers), so it derates ``passive_ua`` proportionally;
    ``q_hall`` the per-hall heat sums when the caller already reduced
    them (the hierarchical fused kernel) — recomputed here otherwise."""
    hs = halls(cfg)
    if q_hall is None:
        q_hall = segment_dot(q, hs.hmat)

    # water temperature arriving at each hall's towers = the hall's
    # flow-weighted return temp; the facility scalar mixes all groups
    mdot_hall = segment_dot(mdot, hs.hmat)
    t_ret_mix_hall = segment_dot(mdot * t_return, hs.hmat) / \
        jnp.maximum(mdot_hall, 1e-6)
    t_ret_mix = jnp.sum(mdot * t_return) / jnp.maximum(jnp.sum(mdot), 1e-6)

    # heat reuse, per hall: divert exportable heat from the hot return
    # stream before the tower (only worth it when the water is hot enough
    # to sell). The export capacity split is each hall's *static*
    # CDU-count share (hall_weights) — district-heating tie-ins are
    # plumbed per hall, so capacity stranded in a load-shedding hall does
    # not migrate to the loaded one
    q_reuse_h = jnp.where(t_ret_mix_hall >= cfg.reuse_t_min_c,
                          jnp.minimum(cfg.reuse_frac * q_hall, hs.reuse_max),
                          0.0)
    q_tower_h = q_hall - q_reuse_h

    # fan staging, per hall: reject the tower-bound heat (minus what the
    # passive path already carries) at the current driving ΔT, plus a
    # proportional correction that steers the basin to its target. Offline
    # cells (maintenance) cap the staging ceiling — the basin mass and the
    # passive (windage) path are installed hardware and stay
    cell_ua = cfg.cell_ua()
    passive_ua = hs.passive_ua
    off = jnp.asarray(cells_offline, jnp.float32)
    if not (isinstance(cells_failed, (int, float)) and cells_failed == 0.0):
        # stochastic failures stack on top of maintenance and, unlike
        # maintenance, take the failed cells' windage path down with them
        cf = jnp.clip(jnp.asarray(cells_failed, jnp.float32), 0.0, hs.cells)
        off = off + cf
        passive_ua = hs.passive_ua * (1.0 - cf / hs.cells)
    cells_on = jnp.clip(hs.cells - off, 0.0, hs.cells)
    q_passive = passive_ua * (state.t_basin - t_wb)
    t_b_tgt = jnp.maximum(t_wb + cfg.tower_approach_c,
                          t_set - cfg.basin_margin_c)
    drive = jnp.maximum(state.t_basin - t_wb, 0.5)
    q_need = q_tower_h - q_passive + \
        hs.mcp * (state.t_basin - t_b_tgt) / cfg.tower_tau_s
    s_tgt = jnp.clip(q_need / (cell_ua * drive), 0.0, cells_on)
    fan = state.fan_stages + (s_tgt - state.fan_stages) * \
        jnp.clip(dt / cfg.tau_fan_s, 0.0, 1.0)
    # a cell pulled offline mid-run also drops out of the *current*
    # staging state, not just the target
    fan = jnp.minimum(fan, cells_on)

    # basin thermal mass, per hall: heat in from the HX minus tower
    # rejection. The fan path only ever rejects (evaporative, wet-bulb
    # floor); the passive path is bidirectional — a heat wave warms an
    # idle basin
    q_rej = jnp.maximum(fan * cell_ua * (state.t_basin - t_wb), 0.0) + \
        q_passive
    t_basin = state.t_basin + (q_tower_h - q_rej) * dt / hs.mcp

    # parasitics: staged cube-law fans per hall (whole cells at rated
    # power, the modulating cell at speed^3) + cube-law pumps with a 20%
    # base
    k = jnp.floor(fan)
    r = fan - k
    fan_w_h = cfg.fan_rated_w * (k + r ** 3)
    fan_w = jnp.sum(fan_w_h)
    frac = mdot / cfg.mdot_kg_s
    pump_w = jnp.sum(cfg.pump_w_per_group * (0.2 + 0.8 * frac ** 3))

    new = CoolingState(t_supply=t_supply, t_return=t_return, mdot=mdot,
                       t_basin=t_basin, fan_stages=fan)
    out = CoolingOut(
        p_cooling=fan_w + pump_w, p_fan=fan_w, p_pump=pump_w,
        t_tower_return=t_ret_mix, t_basin=jnp.max(t_basin),
        t_supply_max=jnp.max(t_supply), t_return_max=jnp.max(t_return),
        q_reuse_w=jnp.sum(q_reuse_h), q_reject_w=jnp.sum(q_rej),
        q_hall_w=q_hall, t_basin_hall=t_basin,
        t_supply_max_hall=hall_max_ref(t_supply, hs.hog, cfg.n_halls),
        t_return_max_hall=hall_max_ref(t_return, hs.hog, cfg.n_halls),
        q_reject_hall_w=q_rej, fan_w_hall=fan_w_h, cells_online=cells_on,
        t_wetbulb_hall=t_wb)
    return new, out


def step(cfg: CoolingConfig, state: CoolingState, group_heat_w: jnp.ndarray,
         dt: float, t_wetbulb_c=None, setpoint_delta_c=0.0,
         cells_offline=0.0, cells_failed=0.0
         ) -> tuple[CoolingState, CoolingOut]:
    """Advance the cooling plant by ``dt`` seconds from per-group heat.

    Args:
      group_heat_w: f32[G] heat load per CDU group (W) — IT power per group,
        already throttled when a power cap is active.
      t_wetbulb_c: ambient wet-bulb (°C, traced); scalar (shared) or
        f32[H] (per-hall weather); defaults to the static
        ``cfg.t_wetbulb_c`` when no weather trace drives the run.
      setpoint_delta_c: offset on the supply setpoint (°C, traced) — the
        ``Scenario.setpoint_delta_c`` sweep knob.
      cells_offline: tower cells out for maintenance (traced; scalar or
        f32[H]) — the ``Scenario.cells_offline`` what-if knob.
      cells_failed: tower cells down from stochastic failures (traced;
        scalar or f32[H]) — fed by ``repro.events``; also derates the
        passive windage path.
    Returns:
      (new_state, CoolingOut telemetry).
    """
    t_wb, t_set = _effective(cfg, t_wetbulb_c, setpoint_delta_c)
    hs = halls(cfg)
    t_basin_g = state.t_basin[hs.hog]   # each group sees its hall's basin
    q, t_return, t_supply, mdot = cdu_update_ref(
        group_heat_w, state.t_supply, state.mdot, t_basin_g,
        jnp.broadcast_to(t_set, t_basin_g.shape), cdu_params(cfg, dt))
    return _finish_step(cfg, state, dt, t_wb, t_set, q, t_return, t_supply,
                        mdot, cells_offline, cells_failed)


def step_from_node_power(cfg: CoolingConfig, state: CoolingState,
                         node_pw: jnp.ndarray, dt: float,
                         t_wetbulb_c=None, setpoint_delta_c=0.0,
                         cells_offline=0.0, cells_failed=0.0,
                         use_pallas: bool = False
                         ) -> tuple[CoolingState, CoolingOut, jnp.ndarray]:
    """Like ``step`` but fused: the node->CDU->hall segment reduction and
    the CDU loop update run as one pass
    (``kernels.power_topo.fused_cooling_hier``), and total IT power falls
    out of the hall sums for free.

    Returns:
      (new_state, CoolingOut, p_it) with ``p_it`` = f32[] total IT power (W).
    """
    t_wb, t_set = _effective(cfg, t_wetbulb_c, setpoint_delta_c)
    q, t_return, t_supply, mdot, q_hall = topo_ops.fused_cooling_hier(
        node_pw, state.t_supply, state.mdot, state.t_basin, t_set,
        cfg.hall_of_group(), cfg.n_groups, cdu_params(cfg, dt),
        use_pallas=use_pallas)
    new, out = _finish_step(cfg, state, dt, t_wb, t_set, q, t_return,
                            t_supply, mdot, cells_offline, cells_failed,
                            q_hall=q_hall)
    return new, out, jnp.sum(q_hall)


def thermal_now(cfg: CoolingConfig, state: CoolingState,
                setpoint_delta_c=0.0) -> ThermalNow:
    """Cooling-pressure signals for the scheduler, from the current state.

    ``excess`` ramps 0 -> 1 across the soft band
    [t_return_limit_c - thermal_margin_c, t_return_limit_c]; the
    thermal_aware policy multiplies it into its heat-dense-job penalty.
    ``overheat`` trips when a hall's hottest CDU supply exceeds the
    (effective) setpoint by ``t_supply_margin_c`` — that hall has lost
    setpoint control, so admission into it throttles until it recovers
    (the scalar aggregates keep the flat-plant semantics: max / any).
    """
    hs = halls(cfg)
    t_ret_h = hall_max_ref(state.t_return, hs.hog, cfg.n_halls)
    t_sup_h = hall_max_ref(state.t_supply, hs.hog, cfg.n_halls)
    soft = cfg.t_return_limit_c - cfg.thermal_margin_c
    excess_h = jnp.maximum(t_ret_h - soft, 0.0) / cfg.thermal_margin_c
    _, t_set = _effective(cfg, None, setpoint_delta_c)
    overheat_h = t_sup_h > t_set + cfg.t_supply_margin_c
    return ThermalNow(excess=jnp.max(excess_h),
                      overheat=jnp.any(overheat_h),
                      t_return_max=jnp.max(t_ret_h),
                      t_supply_max=jnp.max(t_sup_h),
                      excess_hall=excess_h, overheat_hall=overheat_h)


def thermal_neutral(n_halls: int = 1) -> ThermalNow:
    """Signals that make every cooling-aware term a no-op."""
    z = jnp.float32(0.0)
    return ThermalNow(excess=z, overheat=jnp.bool_(False), t_return_max=z,
                      t_supply_max=z,
                      excess_hall=jnp.zeros((n_halls,), jnp.float32),
                      overheat_hall=jnp.zeros((n_halls,), jnp.bool_))


def pue(p_it: jnp.ndarray, p_loss: jnp.ndarray,
        p_cooling: jnp.ndarray) -> jnp.ndarray:
    """Power usage effectiveness: facility input power over IT power (W/W)."""
    return (p_it + p_loss + p_cooling) / jnp.maximum(p_it, 1.0)
