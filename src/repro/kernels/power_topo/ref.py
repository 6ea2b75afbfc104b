"""Pure-jnp oracles for the power-topology kernels.

Node n belongs to CDU group ``n * G // N`` (contiguous spans, mirroring how
cabinets map to CDUs). Inputs may carry a leading scenario-batch axis.

Two oracles live here:

* ``group_power_ref`` — the plain segment reduction (node power -> per-CDU
  heat), used by the engine's capped path and by the DVFS enforcement pass.
* ``cdu_update_ref`` / ``fused_cooling_ref`` — the per-CDU piece of the
  transient cooling update (valve dynamics + heat pickup + supply-loop
  relaxation), optionally fused with the segment reduction. This is the
  single source of truth for the in-kernel math: ``repro.cooling.model``
  calls ``cdu_update_ref`` directly and the Pallas kernel
  (``power_topo.fused_cooling_pallas``) must match it to <= 1e-4.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp


class CduParams(NamedTuple):
    """Static scalars of the CDU loop update (units: SI, °C).

    Mirrors the relevant ``CoolingConfig`` fields; kept as a plain tuple so
    the kernel layer does not depend on repro.systems.
    """
    cp_j_kg_k: float      # water specific heat (J/(kg·K))
    ua_w_k: float         # facility HX conductance per group (W/K)
    dt: float             # engine step (s)
    tau_hx_s: float       # supply-loop relaxation time constant (s)
    tau_valve_s: float    # valve/flow slew time constant (s)
    delta_t_design_c: float  # design water ΔT across a CDU (°C)
    mdot_min_kg_s: float  # valve floor (kg/s)
    mdot_max_kg_s: float  # full-open flow (kg/s)


def group_ids(n_nodes: int, n_groups: int) -> np.ndarray:
    """i32[N] CDU group of each node, as *host* numpy: the assignment is
    static, so keeping it concrete lets jnp consumers fold it as a
    constant while host-side planners (the scheduler's hall spans) read
    it without tripping over tracers."""
    span = -(-n_nodes // n_groups)  # ceil: groups are equal spans, last ragged
    idx = np.arange(n_nodes, dtype=np.int32)
    return np.minimum(idx // span, n_groups - 1)


def group_sizes(n_nodes: int, n_groups: int) -> np.ndarray:
    """i32[G] nodes in each CDU group (host numpy, like ``group_ids``)."""
    return np.bincount(group_ids(n_nodes, n_groups),
                       minlength=n_groups).astype(np.int32)


def segment_dot(x: jnp.ndarray, one_hot: jnp.ndarray) -> jnp.ndarray:
    """``x @ one_hot`` at full f32 precision: the segment sum behind every
    one-hot reduction here. The TPU's default f32 matmul rounds its
    operands to bf16 (about three significant digits), which would round
    each node's power before it is summed."""
    return jnp.matmul(x, one_hot, precision=jax.lax.Precision.HIGHEST)


def group_power_ref(node_pw: jnp.ndarray, n_groups: int) -> jnp.ndarray:
    """f32[..., N] -> f32[..., G] segment sum over contiguous node spans."""
    n_nodes = node_pw.shape[-1]
    gid = group_ids(n_nodes, n_groups)
    one_hot = (gid[:, None] == jnp.arange(n_groups)[None, :]).astype(
        node_pw.dtype)
    return segment_dot(node_pw, one_hot)


def hall_matrix(hall_of_group, n_halls: int,
                dtype=jnp.float32) -> jnp.ndarray:
    """One-hot group->hall matrix f32[G, H] for the second reduction level
    of the node -> CDU -> hall hierarchy. ``x @ hall_matrix(...)`` is the
    per-hall segment sum of a per-group quantity (via ``segment_dot``)."""
    hog = jnp.asarray(hall_of_group, jnp.int32)
    return (hog[:, None] == jnp.arange(n_halls)[None, :]).astype(dtype)


def hall_power_ref(group_q: jnp.ndarray, hall_of_group,
                   n_halls: int) -> jnp.ndarray:
    """f32[..., G] -> f32[..., H] segment sum of per-group heat per hall."""
    return segment_dot(group_q,
                       hall_matrix(hall_of_group, n_halls, group_q.dtype))


def hall_max_ref(group_x: jnp.ndarray, hall_of_group,
                 n_halls: int) -> jnp.ndarray:
    """f32[..., G] -> f32[..., H] per-hall max of a per-group quantity
    (e.g. the hottest CDU return temperature in each hall)."""
    mask = hall_matrix(hall_of_group, n_halls, jnp.bool_)
    masked = jnp.where(mask, group_x[..., :, None], -jnp.inf)
    return jnp.max(masked, axis=-2)


def _per_group(x: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """Align a basin/setpoint operand with the per-group heat array ``q``:
    already per-group (same rank as q) -> as is; one rank lower (the flat
    plant's scalar-per-batch form) -> broadcast over the trailing G axis."""
    x = jnp.asarray(x, q.dtype)
    return x if x.ndim == q.ndim else x[..., None]


def cdu_update_ref(q: jnp.ndarray, t_supply: jnp.ndarray, mdot: jnp.ndarray,
                   t_basin: jnp.ndarray, t_set: jnp.ndarray,
                   p: CduParams):
    """Per-CDU loop update for one engine step (pure jnp, elementwise in G).

    Args:
      q: f32[..., G] heat load per CDU group (W).
      t_supply: f32[..., G] current supply water temperature (°C).
      mdot: f32[..., G] current water mass flow (kg/s).
      t_basin: basin temperature feeding each CDU (°C): f32[...] (one
        basin for the whole plant, broadcast over G) or f32[..., G]
        (hierarchical plant — each group sees its *hall's* basin, gathered
        by the caller, e.g. ``t_basin_hall[..., hall_of_group]``).
      t_set: effective supply setpoint (°C), f32[...] or f32[..., G].
      p: static scalars (CduParams).
    Returns:
      (q, t_return, t_supply_new, mdot_new), each f32[..., G]:
      the heat passthrough, return water temperature, relaxed supply
      temperature and slewed flow.
    """
    # valve: flow slews toward the demand that holds the design ΔT. The
    # slew factors are clipped at 1 (static Python min when dt and tau
    # are compile-time scalars — the engine path; traced min when a tau
    # is a calibration candidate, see repro.traces.calibrate) so a
    # coarse engine dt > tau snaps to the target instead of overshooting
    # the [min, max] flow bounds
    def _a(tau):
        if isinstance(tau, (int, float)):
            return min(p.dt / tau, 1.0)
        return jnp.minimum(p.dt / tau, 1.0)
    a_valve = _a(p.tau_valve_s)
    a_hx = _a(p.tau_hx_s)
    dem = jnp.clip(q / (p.cp_j_kg_k * p.delta_t_design_c),
                   p.mdot_min_kg_s, p.mdot_max_kg_s)
    mdot_new = mdot + (dem - mdot) * a_valve
    # heat pickup across the cold plates at the new flow
    t_return = t_supply + q / (mdot_new * p.cp_j_kg_k)
    # supply relaxes toward what the facility HX can deliver: never below
    # basin temperature + HX penalty, never below the setpoint
    tgt = jnp.maximum(_per_group(t_set, q), _per_group(t_basin, q)
                      + q / p.ua_w_k)
    t_supply_new = t_supply + (tgt - t_supply) * a_hx
    return q, t_return, t_supply_new, mdot_new


def fused_cooling_ref(node_pw: jnp.ndarray, t_supply: jnp.ndarray,
                      mdot: jnp.ndarray, t_basin: jnp.ndarray,
                      t_set: jnp.ndarray, n_groups: int, p: CduParams):
    """Segment-reduce heat per CDU group + CDU loop update, one logical pass.

    f32[..., N] node power -> (q, t_return, t_supply_new, mdot_new), each
    f32[..., G]. Oracle for ``power_topo.fused_cooling_pallas``.
    """
    q = group_power_ref(node_pw, n_groups)
    return cdu_update_ref(q, t_supply, mdot, t_basin, t_set, p)


def fused_cooling_hier_ref(node_pw: jnp.ndarray, t_supply: jnp.ndarray,
                           mdot: jnp.ndarray, t_basin_hall: jnp.ndarray,
                           t_set: jnp.ndarray, hall_of_group,
                           n_groups: int, p: CduParams):
    """Hierarchical fused update: node -> CDU -> hall segment reduction +
    per-CDU loop update against each group's *hall* basin, one logical pass.

    Args:
      node_pw: f32[..., N] per-node power (W).
      t_supply, mdot: f32[..., G] CDU loop state.
      t_basin_hall: f32[..., H] per-hall basin temperatures (°C).
      t_set: f32[...] effective supply setpoint (°C, shared across halls).
      hall_of_group: static i32[G]-like hall index of each CDU group.
      n_groups: number of CDU groups G.
      p: static CduParams scalars.
    Returns:
      (q, t_return, t_supply_new, mdot_new, q_hall): the per-group pieces
      f32[..., G] plus the per-hall heat sums f32[..., H]. Oracle for the
      hierarchical Pallas path (``ops.fused_cooling`` with per-group
      basin operands).
    """
    hog = jnp.asarray(hall_of_group, jnp.int32)
    n_halls = t_basin_hall.shape[-1]
    t_basin_g = t_basin_hall[..., hog]           # gather: group -> its hall
    q, t_ret, t_sup, md = fused_cooling_ref(node_pw, t_supply, mdot,
                                            t_basin_g, t_set, n_groups, p)
    return q, t_ret, t_sup, md, hall_power_ref(q, hog, n_halls)
