"""DVFS power-cap enforcement (per engine step).

When the projected IT power exceeds the active cap, every running node is
throttled by a common cap factor ``c`` in ``[c_min, 1]``. DVFS only buys
back *dynamic* power: each node keeps its idle floor and scales the draw
above it,

    p_throttled = min(p, idle) + c * max(p - idle, 0)

so the solvable cap range is ``[floor_total, raw_total]`` and

    c = clip((cap - floor_total) / dyn_total, c_min, 1).

The cap needs only each CDU group's ``floor`` and ``dyn`` sums, so the
throttled per-CDU heat loads come out of the enforcement pass for free.
The engine forms them from the per-job powers and the per-group occupancy
(``group_split``, no per-node array); ``enforce_cap`` forms them from a
per-node array by segment sum and is kept as the oracle.

The runtime cost of throttling is modelled as proportional slowdown: the
engine stretches every affected job's remaining runtime by ``1/c`` for the
throttled step (repro.core.engine._tick).
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.kernels.power_topo import ops as topo_ops
from repro.power import model as pmodel
from repro.systems.config import SystemConfig


class CapResult(NamedTuple):
    c: jnp.ndarray           # f32[]  cap factor in [c_min, 1]
    p_it: jnp.ndarray        # f32[]  throttled total IT power (W)
    group_heat: jnp.ndarray  # f32[G] throttled per-CDU-group heat (W)
    p_it_raw: jnp.ndarray    # f32[]  unthrottled IT power (W)


def throttle_power(pw: jnp.ndarray, idle_w: float,
                   c: jnp.ndarray) -> jnp.ndarray:
    """Scale the dynamic (above-idle) share of a power array by ``c``.

    Args:
      pw: f32[...] power draws (W).
      idle_w: per-node idle floor (W) — not DVFS-addressable.
      c: f32[] cap factor in [c_min, 1].
    Returns:
      f32[...] throttled powers (W): ``min(pw, idle) + c·max(pw−idle, 0)``.
    """
    floor = jnp.minimum(pw, idle_w)
    return floor + c * (pw - floor)


def group_split(system: SystemConfig, occ: jnp.ndarray,
                job_pw: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-CDU-group idle floor and dynamic draw, f32[G] each (W), from
    the per-job per-node powers ``job_pw`` f32[J] and the running jobs'
    per-group node counts ``occ`` f32[G, J]
    (``repro.power.model.group_occupancy``): nodes no running job holds
    sit at the floor, ``idle_node_w``."""
    idle = system.power.idle_node_w
    floor_g = pmodel.group_power(system, occ, jnp.minimum(job_pw, idle))
    dyn_g = jnp.sum(occ * jnp.maximum(job_pw - idle, 0.0), axis=-1)
    return floor_g, dyn_g


def enforce_cap_groups(system: SystemConfig, floor_g: jnp.ndarray,
                       dyn_g: jnp.ndarray, cap_w: jnp.ndarray) -> CapResult:
    """Compute the cap factor for this step and the throttled aggregates.

    Args:
      floor_g, dyn_g: f32[G] per-CDU-group idle floor and dynamic
        (above-idle) draw (W).
      cap_w: f32[] active facility IT power cap (W); ``inf`` = uncapped
        -> c = 1. A cap below the idle floor saturates at ``c_min``: the
        idle draw is not DVFS-addressable, matching real power-capping
        interfaces.
    Returns:
      ``CapResult``: cap factor c, throttled total IT power (W), throttled
      per-CDU-group heat (W) and the unthrottled total (W).
    """
    floor_tot = jnp.sum(floor_g)
    dyn_tot = jnp.sum(dyn_g)

    c_raw = (cap_w - floor_tot) / jnp.maximum(dyn_tot, 1.0)
    c = jnp.clip(c_raw, system.grid.c_min, 1.0)
    c = jnp.where(jnp.isfinite(cap_w), c, jnp.float32(1.0))

    group_heat = floor_g + c * dyn_g
    return CapResult(c=c, p_it=floor_tot + c * dyn_tot,
                     group_heat=group_heat, p_it_raw=floor_tot + dyn_tot)


def enforce_cap(system: SystemConfig, node_pw: jnp.ndarray,
                cap_w: jnp.ndarray) -> CapResult:
    """``enforce_cap_groups`` from per-node draws ``node_pw`` f32[N] (W),
    summed into CDU groups."""
    idle = system.power.idle_node_w
    floor = jnp.minimum(node_pw, idle)
    G = system.cooling.n_groups
    return enforce_cap_groups(system, topo_ops.group_power(floor, G),
                              topo_ops.group_power(node_pw - floor, G),
                              cap_w)
