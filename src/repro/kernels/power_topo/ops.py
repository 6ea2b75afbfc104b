"""Jit'd wrappers for the power-topology kernels.

``group_power`` (segment reduce) and ``fused_cooling`` (segment reduce +
CDU loop update in one pass) default to the XLA path (the oracle math).
``use_pallas=True`` takes the VMEM-tiled TPU kernels, compiled unless
``interpret=True`` asks for the Pallas interpreter (the only way to run
them off the TPU). The wrappers own padding so the kernels only see
aligned shapes.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.power_topo.power_topo import (LANE, fused_cooling_pallas,
                                                 group_power_pallas)
from repro.kernels.power_topo.ref import (CduParams, cdu_update_ref,
                                          fused_cooling_hier_ref,
                                          fused_cooling_ref, group_power_ref,
                                          hall_power_ref)

def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _group_layout(x: jnp.ndarray, n_groups: int) -> jnp.ndarray:
    """Lay f32[S, N] out for the per-group kernels: (S, G, span) with span
    padded to the lane width, flattened back to (S, G*span_pad).

    Zero padding is exact for a sum reduction, and the ceil-span grouping
    MUST match ``ref.group_ids`` (node n -> group ``min(n // span, G-1)``)
    — this helper is the single place that encodes it for the Pallas path.
    """
    S, N = x.shape
    span = -(-N // n_groups)          # ceil: matches ref.group_ids
    x = _pad_to(x, 1, span * n_groups)
    x = x.reshape(S, n_groups, span)
    x = _pad_to(x, 2, LANE)
    return x.reshape(S, -1)


def group_power(node_pw: jnp.ndarray, n_groups: int,
                use_pallas: bool = False, interpret: bool = False
                ) -> jnp.ndarray:
    """f32[N] or f32[S, N] -> f32[G] / f32[S, G]."""
    squeeze = node_pw.ndim == 1
    x = node_pw[None, :] if squeeze else node_pw
    if use_pallas:
        # each kernel program sees exactly one ref-group tile; the batch
        # axis pads to the sublane width
        S = x.shape[0]
        x = _pad_to(_group_layout(x, n_groups), 0, 8)
        out = group_power_pallas(x, n_groups, s_block=8, interpret=interpret)
        out = out[:S]
    else:
        out = group_power_ref(x, n_groups)
    return out[0] if squeeze else out


def fused_cooling(node_pw: jnp.ndarray, t_supply: jnp.ndarray,
                  mdot: jnp.ndarray, t_basin: jnp.ndarray,
                  t_set: jnp.ndarray, n_groups: int, params: CduParams,
                  use_pallas: bool = False, interpret: bool = False):
    """Fused per-step cooling update: per-CDU heat + loop state in one pass.

    Args:
      node_pw: f32[N] or f32[S, N] per-node power (W).
      t_supply, mdot: f32[G] / f32[S, G] CDU supply temps (°C), flows (kg/s).
      t_basin, t_set: basin temp and effective setpoint (°C) — f32[] /
        f32[S] (flat plant: one basin shared by every group) or f32[G] /
        f32[S, G] (hierarchical plant: each group sees its hall's basin,
        see ``fused_cooling_hier``).
      n_groups: number of CDU groups G.
      params: static CduParams scalars.
    Returns:
      (q, t_return, t_supply_new, mdot_new) with the input's batch shape:
      per-group heat (W), return temp (°C), relaxed supply (°C), flow (kg/s).
    """
    squeeze = node_pw.ndim == 1
    if not use_pallas:
        return fused_cooling_ref(node_pw, t_supply, mdot, t_basin, t_set,
                                 n_groups, params)
    x = node_pw[None, :] if squeeze else node_pw
    up = lambda a: a[None, ...] if squeeze else a
    ts, md = up(t_supply), up(mdot)
    # basin/setpoint go to the kernel as per-group columns: broadcast the
    # flat-plant scalar-per-batch form across G
    S0 = x.shape[0]
    col = lambda a: jnp.broadcast_to(
        up(a)[:, None] if up(a).ndim == 1 else up(a), (S0, n_groups))
    tb, tset = col(t_basin), col(t_set)
    S = x.shape[0]
    x = _group_layout(x, n_groups)
    # pad the batch axis to the sublane width; state pads replicate row 0 so
    # padded rows stay finite (they are sliced off below)
    pad_rows = (-S) % 8
    pad = lambda a: jnp.concatenate(
        [a, jnp.broadcast_to(a[:1], (pad_rows,) + a.shape[1:])]) \
        if pad_rows else a
    outs = fused_cooling_pallas(pad(x), pad(ts), pad(md), pad(tb), pad(tset),
                                params, n_groups, s_block=8,
                                interpret=interpret)
    outs = tuple(o[:S] for o in outs)
    return tuple(o[0] for o in outs) if squeeze else outs


def hall_power(group_q: jnp.ndarray, hall_of_group,
               n_halls: int) -> jnp.ndarray:
    """f32[..., G] -> f32[..., H]: the hall level of the node -> CDU ->
    hall segment-reduction hierarchy. G and H are both tiny (tens), so
    this level always runs as the XLA one-hot matmul — only the node ->
    CDU level is worth a kernel."""
    return hall_power_ref(group_q, hall_of_group, n_halls)


def fused_cooling_hier(node_pw: jnp.ndarray, t_supply: jnp.ndarray,
                       mdot: jnp.ndarray, t_basin_hall: jnp.ndarray,
                       t_set, hall_of_group, n_groups: int,
                       params: CduParams, use_pallas: bool = False,
                       interpret: bool = False):
    """Hierarchical fused cooling update: node -> CDU -> hall reduction +
    per-CDU loop update against each group's hall basin.

    Args:
      node_pw: f32[N] or f32[S, N] per-node power (W).
      t_supply, mdot: f32[G] / f32[S, G] CDU loop state.
      t_basin_hall: f32[H] / f32[S, H] per-hall basin temperatures (°C).
      t_set: f32[] / f32[S] effective supply setpoint (°C).
      hall_of_group: static i32[G]-like hall index per CDU group.
    Returns:
      (q, t_return, t_supply_new, mdot_new, q_hall): per-group pieces plus
      per-hall heat sums f32[H] / f32[S, H]. Matches
      ``ref.fused_cooling_hier_ref`` to <= 1e-4 on the Pallas path.
    """
    if not use_pallas:
        return fused_cooling_hier_ref(node_pw, t_supply, mdot, t_basin_hall,
                                      t_set, hall_of_group, n_groups, params)
    hog = jnp.asarray(hall_of_group, jnp.int32)
    t_basin_g = t_basin_hall[..., hog]          # gather: group -> its hall
    tset_g = jnp.broadcast_to(jnp.asarray(t_set, node_pw.dtype)[..., None],
                              t_basin_g.shape)
    q, t_ret, t_sup, md = fused_cooling(node_pw, t_supply, mdot, t_basin_g,
                                        tset_g, n_groups, params,
                                        use_pallas=True, interpret=interpret)
    return q, t_ret, t_sup, md, hall_power_ref(q, hog,
                                               t_basin_hall.shape[-1])
