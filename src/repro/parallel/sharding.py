"""Logical-axis sharding rules -> concrete NamedShardings (DP/FSDP/TP/EP/SP).

Every parameter is annotated at init with logical axis names (see
``repro.models.common.param``). Rules map logical names to mesh axes; a
divisibility guard drops a rule per-leaf-dim when the dim does not divide the
mesh axis (e.g. phi3's 40 heads on a 16-way ``model`` axis, mixtral's 8
experts), falling back to replication for that dim — every (arch x mesh)
cell lowers without hand-tuning; the benchmarks record where the
fallback fired.

Parallelism mapping (production mesh (pod, data, model)):
  DP   : batch over ("pod", "data")
  FSDP : parameter "embed" (d_model) dims over "data"  (ZeRO-3-style; XLA
         inserts the all-gathers at use and reduce-scatters in the backward)
  TP   : "mlp" (d_ff), "heads", "vocab" over "model"
  EP   : "expert" over "model" when divisible
  SP   : "kv_seq" over "data" for long-context decode (sequence-sharded
         KV cache / streaming state)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import ArchConfig, Annotated

Rules = Dict[str, Any]


DEFAULT_RULES: Rules = {
    "batch": ("pod", "data"),
    "embed": "data",          # FSDP
    "mlp": "model",           # TP
    "heads": "model",
    "kv_heads": None,
    "head_dim": None,
    "vocab": "model",
    "expert": "model",        # EP
    "heads_x_dim": "model",   # fused H*hd projections (rwkv)
    "seq": None,
    "kv_seq": None,
    "unsharded": None,
}


def rules_for(cfg: ArchConfig, mesh: Mesh, kind: str = "train") -> Rules:
    rules = dict(DEFAULT_RULES)
    msize = dict(zip(mesh.axis_names, mesh.devices.shape))
    model = msize.get("model", 1)
    if cfg.n_experts and cfg.n_experts % model != 0:
        rules["expert"] = None            # mixtral: 8 experts on 16-way TP
    if cfg.h_pad % model != 0:
        rules["heads"] = None             # unpadded phi3 (40), internvl (14)
    if cfg.kv_pad % model == 0:
        rules["kv_heads"] = "model"       # MHA archs: shard kv heads too
    if kind == "long_decode":
        rules["kv_seq"] = "data"          # SP over the KV cache / state
    return rules


def _axis_size(mesh: Mesh, axis) -> int:
    msize = dict(zip(mesh.axis_names, mesh.devices.shape))
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([msize.get(a, 1) for a in axis]))
    return msize.get(axis, 1)


def _filter_axis(mesh: Mesh, axis):
    """Drop mesh axes that don't exist on this mesh (e.g. 'pod' single-pod)."""
    if axis is None:
        return None
    if isinstance(axis, tuple):
        kept = tuple(a for a in axis if a in mesh.axis_names)
        return kept if kept else None
    return axis if axis in mesh.axis_names else None


def spec_for(mesh: Mesh, rules: Rules, axes: Tuple[str, ...],
             shape: Tuple[int, ...]) -> P:
    """Map logical axes -> PartitionSpec with divisibility guard."""
    if len(axes) < len(shape):   # stacked layer/block leading dims
        axes = ("unsharded",) * (len(shape) - len(axes)) + tuple(axes)
    out = []
    used = set()
    for dim, name in zip(shape, axes):
        ax = _filter_axis(mesh, rules.get(name))
        if ax is None or _axis_size(mesh, ax) == 1 or \
                dim % _axis_size(mesh, ax) != 0 or str(ax) in used:
            out.append(None)
        else:
            out.append(ax)
            used.add(str(ax))
    return P(*out)


def shard_params(mesh: Mesh, rules: Rules, annotated_tree):
    """Annotated tree -> (value tree, NamedSharding tree)."""
    is_ann = lambda x: isinstance(x, Annotated)
    vals = jax.tree_util.tree_map(lambda a: a.value, annotated_tree,
                                  is_leaf=is_ann)
    shardings = jax.tree_util.tree_map(
        lambda a: NamedSharding(mesh, spec_for(mesh, rules, a.axes,
                                               a.value.shape)),
        annotated_tree, is_leaf=is_ann)
    return vals, shardings


def sharding_tree_from_axes(mesh: Mesh, rules: Rules, axes_tree, shape_tree):
    """axes tree (tuples) + ShapeDtypeStruct tree -> NamedSharding tree."""
    return jax.tree_util.tree_map(
        lambda axes, sds: NamedSharding(
            mesh, spec_for(mesh, rules, axes, sds.shape)),
        axes_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


def batch_spec(mesh: Mesh, rules: Rules, ndim: int) -> NamedSharding:
    """Shard dim0 (global batch) over DP axes, replicate the rest."""
    ax = _filter_axis(mesh, rules["batch"])
    return NamedSharding(mesh, P(ax, *([None] * (ndim - 1))))


def batch_shardings(mesh: Mesh, rules: Rules, batch_struct,
                    global_batch: int) -> Any:
    dp = _axis_size(mesh, _filter_axis(mesh, rules["batch"]))

    def one(sds):
        if sds.shape and sds.shape[0] == global_batch and \
                global_batch % dp == 0:
            return batch_spec(mesh, rules, len(sds.shape))
        return NamedSharding(mesh, P(*([None] * len(sds.shape))))
    return jax.tree_util.tree_map(one, batch_struct)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# -- activation hints --------------------------------------------------------
def hint(x, *spec):
    """Best-effort with_sharding_constraint (no-op outside a mesh ctx)."""
    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except Exception:
        return x


# Batch axes for activation hints inside model code. The step builders set
# this to the mesh's DP axes; without the hint XLA's SPMD partitioner is
# free to replicate the scan-carried activations, which measured as ~4x
# redundant per-device flops.
_BATCH_AXES: tuple = ("data",)


def set_batch_axes(axes) -> None:
    global _BATCH_AXES
    _BATCH_AXES = axes


def hint_batch(x):
    """Constrain dim0 (batch) to the DP axes, rest unspecified."""
    try:
        return jax.lax.with_sharding_constraint(
            x, P(_BATCH_AXES, *([None] * (x.ndim - 1))))
    except Exception:
        return x


def hint_axes(x, spec):
    """Constrain with a symbolic spec: 'batch' -> DP axes, 'model' -> TP
    axis, None -> unspecified. Pins layouts across scan bodies so the SPMD
    partitioner doesn't insert per-iteration reshard collective-permutes
    (saves a transpose on the hot path)."""
    resolved = tuple(_BATCH_AXES if a == "batch" else a for a in spec)
    try:
        return jax.lax.with_sharding_constraint(x, P(*resolved))
    except Exception:
        return x


# -- cache/state shardings ----------------------------------------------------
def state_shardings(mesh: Mesh, rules: Rules, state_struct, cfg: ArchConfig,
                    batch: int, kind: str):
    """Heuristic shardings for decode states: shard the batch dim when it
    divides DP; shard the longest (sequence) dim over 'data' for
    long-context; shard a dim equal to n_heads/n_kv_heads over 'model' when
    divisible."""
    dp_ax = _filter_axis(mesh, rules["batch"])
    dp = _axis_size(mesh, dp_ax)
    model = _axis_size(mesh, "model")
    kv_seq_ax = _filter_axis(mesh, rules.get("kv_seq"))

    def one(sds):
        spec = [None] * len(sds.shape)
        used_data = False
        for i, d in enumerate(sds.shape):
            if d == batch and batch % dp == 0 and dp > 1 and not used_data:
                spec[i] = dp_ax
                used_data = True
                break
        # model axis: heads-like dims
        for i, d in enumerate(sds.shape):
            if spec[i] is None and d in (cfg.n_heads, cfg.n_kv_heads,
                                         2 * cfg.d_model // 64) and \
                    d % model == 0 and model > 1:
                spec[i] = "model"
                break
        if kind == "long_decode" and kv_seq_ax is not None and not used_data:
            # largest dim = the sequence axis of the KV cache
            big = int(np.argmax(sds.shape)) if sds.shape else None
            if big is not None and sds.shape[big] >= 4096 and \
                    spec[big] is None and \
                    sds.shape[big] % _axis_size(mesh, kv_seq_ax) == 0:
                spec[big] = kv_seq_ax
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map(one, state_struct)
