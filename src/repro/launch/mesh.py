"""Production meshes.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — the "pod"
axis carries cross-pod data parallelism (DCN-ish), "data" carries in-pod
FSDP/DP, "model" carries TP/EP.

Defined as functions (never module-level constants) so importing this module
never touches jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Degenerate 1-device mesh for CPU tests (same axis names)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


# Hardware constants for the roofline (TPU v5e-class, per chip).
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # B/s
ICI_BW = 50e9                  # B/s per link
HBM_BYTES = 16 * 1024**3       # capacity used for "does it fit" checks
