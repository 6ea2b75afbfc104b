"""Pallas TPU kernels: batched node-power -> CDU-group segment reduction,
plus the fused per-step cooling update.

This is the twin's per-tick hot spot at scale: with S sharded scenarios and
N nodes (up to 158,976 for Fugaku) the reduction is (S x N) -> (S x G) every
step. Grouping is by contiguous span, so each grid program reduces one
S_block of scenarios over every group while the tile is held in VMEM.

Tiling: grid = (S/S_block,); the input block is the whole (S_block,
G*span) row tile, with each group's span padded to a multiple of 128 lanes
by the wrapper (ops.py), so every group starts on a lane boundary. The
output block is (S_block, G_pad) with G padded to a multiple of 128: the
TPU compiler requires a block's last two dims to be multiples of (8, 128)
(or the full array dims), and a lane-dense output block also keeps the
stores unmasked. Padded groups are sliced off before returning.

``fused_cooling_pallas`` extends the reduction kernel with the per-CDU
piece of the transient cooling update (valve slew + heat pickup +
supply-loop relaxation, see ``ref.cdu_update_ref``): the per-group heat
never round-trips to HBM between the reduce and the loop update — one
grid program produces the group heat AND the new CDU temperatures/flows
for its S_block of scenarios while the tile is resident in VMEM.

Hierarchical (multi-hall) plants reuse the same kernel: the basin and
setpoint operands are *per-group* columns (the wrapper gathers each
group's hall basin, ``t_basin_hall[..., hall_of_group]``) — a flat plant
is just the special case where every column is identical. The CDU -> hall
heat reduction (G -> H, both tiny) stays outside the kernel in XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.power_topo.ref import CduParams

LANE = 128  # TPU vector lane width: minor block dims are multiples of it


def _lane_pad(n: int) -> int:
    return -(-n // LANE) * LANE


def _group_sums(x_ref, n_groups: int, width: int):
    """(S_block, G*span) tile -> (S_block, width) per-group sums, group g
    in lane g; lanes >= n_groups stay zero. ``span`` is a multiple of 128,
    so every slice below is lane-aligned."""
    rows = x_ref.shape[0]
    span = x_ref.shape[1] // n_groups
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    q = jnp.zeros((rows, width), x_ref.dtype)
    for g in range(n_groups):
        col = jnp.sum(x_ref[:, g * span:(g + 1) * span], axis=1,
                      keepdims=True)
        q = jnp.where(lane == g, col, q)
    return q


def _kernel(n_groups, x_ref, o_ref):
    o_ref[...] = _group_sums(x_ref, n_groups, o_ref.shape[1])


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def group_power_pallas(node_pw: jnp.ndarray, n_groups: int,
                       s_block: int = 8, interpret: bool = False
                       ) -> jnp.ndarray:
    """f32[S, N] -> f32[S, G]; N must be divisible by G (wrapper pads)."""
    S, N = node_pw.shape
    assert N % n_groups == 0, "pad N to a multiple of n_groups first"
    assert S % s_block == 0, "pad S to a multiple of s_block first"
    width = _lane_pad(n_groups)
    out = pl.pallas_call(
        functools.partial(_kernel, n_groups),
        grid=(S // s_block,),
        in_specs=[pl.BlockSpec((s_block, N), lambda s: (s, 0))],
        out_specs=pl.BlockSpec((s_block, width), lambda s: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((S, width), node_pw.dtype),
        interpret=interpret,
    )(node_pw)
    return out[:, :n_groups]


def _fused_kernel(p: CduParams, n_groups: int, x_ref, ts_ref, md_ref,
                  tb_ref, tset_ref, q_ref, tr_ref, tso_ref, mdo_ref):
    """One S_block of scenarios: segment-reduce + CDU loop update.

    Refs: x (S_block, G*span); all others (S_block, G_pad) — including
    the basin/setpoint columns, which carry each group's *hall* values on
    the hierarchical path. The math must mirror ``ref.cdu_update_ref``
    exactly (the parity test holds it to 1e-4).
    """
    q = _group_sums(x_ref, n_groups, q_ref.shape[1])
    ts = ts_ref[...]
    # slew factors clipped at 1, matching the ref (coarse dt snaps)
    a_valve = min(p.dt / p.tau_valve_s, 1.0)
    a_hx = min(p.dt / p.tau_hx_s, 1.0)
    dem = jnp.clip(q / (p.cp_j_kg_k * p.delta_t_design_c),
                   p.mdot_min_kg_s, p.mdot_max_kg_s)
    md_new = md_ref[...] + (dem - md_ref[...]) * a_valve
    tgt = jnp.maximum(tset_ref[...], tb_ref[...] + q / p.ua_w_k)
    q_ref[...] = q
    tr_ref[...] = ts + q / (md_new * p.cp_j_kg_k)
    tso_ref[...] = ts + (tgt - ts) * a_hx
    mdo_ref[...] = md_new


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def fused_cooling_pallas(node_pw: jnp.ndarray, t_supply: jnp.ndarray,
                         mdot: jnp.ndarray, t_basin: jnp.ndarray,
                         t_set: jnp.ndarray, params: CduParams,
                         n_groups: int, s_block: int = 8,
                         interpret: bool = False):
    """Fused (segment-reduce + CDU update) over a scenario batch.

    Args:
      node_pw: f32[S, N] per-node power; N divisible by ``n_groups``
        (the wrapper in ops.py owns that padding).
      t_supply, mdot: f32[S, G] current CDU loop state.
      t_basin, t_set: f32[S, G] basin temperature / effective setpoint
        seen by each group (per-group columns; a flat plant broadcasts
        its single basin across G — the wrapper owns that).
      params: static CduParams scalars (baked into the kernel).
    Returns:
      (q, t_return, t_supply_new, mdot_new), each f32[S, G].
    """
    S, N = node_pw.shape
    assert N % n_groups == 0, "pad N to a multiple of n_groups first"
    assert S % s_block == 0, "pad S to a multiple of s_block first"
    assert t_basin.shape == (S, n_groups) and t_set.shape == (S, n_groups), \
        "basin/setpoint must be per-group columns (wrapper broadcasts)"
    width = _lane_pad(n_groups)
    # padded lanes replicate the last real group so they stay finite; they
    # are sliced off below
    lanes = lambda a: jnp.pad(a, ((0, 0), (0, width - n_groups)),
                              mode="edge")
    col = pl.BlockSpec((s_block, width), lambda s: (s, 0))
    gshape = jax.ShapeDtypeStruct((S, width), node_pw.dtype)
    outs = pl.pallas_call(
        functools.partial(_fused_kernel, params, n_groups),
        grid=(S // s_block,),
        in_specs=[pl.BlockSpec((s_block, N), lambda s: (s, 0)),
                  col, col, col, col],
        out_specs=(col, col, col, col),
        out_shape=(gshape, gshape, gshape, gshape),
        interpret=interpret,
    )(node_pw, lanes(t_supply), lanes(mdot), lanes(t_basin), lanes(t_set))
    return tuple(o[:, :n_groups] for o in outs)
