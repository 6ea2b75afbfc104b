"""Utilization -> electrical power (paper §3.1: "simulated utilization is
converted to a power profile, with power rectification and conversion losses
applied [42]").

Per-node IT power comes either from the job's recorded per-node power trace
(trace datasets: Frontier, Marconi100) with last-observation-carried-forward
for missing samples, or from a scalar per-job average (summary datasets:
Fugaku, Lassen, Adastra). Idle nodes draw ``idle_node_w``.

The engine sums power per CDU group straight from the per-job values and
the per-group occupancy ``SimState.job_group_nodes`` (``group_power``):
J x G dense work, where spreading per-job power over the N nodes would
be a gather of N elements. ``node_power`` is that per-node map, kept as
the oracle the group sums are tested against.

Telemetry replay (repro.traces): when the table carries a measured
``power_profile`` channel, jobs with a measurement play it back verbatim —
the scan gathers the recorded sample at the job's work-time index instead
of evaluating the ``power_prof`` model — while profile-less jobs (negative
sentinel rows) keep the model bit-for-bit. ``power_profile is None`` is
the compile-time "replay off" fast path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import types as T
from repro.kernels.power_topo.ref import group_sizes
from repro.systems.config import SystemConfig


def job_node_power_elapsed(table: T.JobTable, jstate: jnp.ndarray,
                           elapsed: jnp.ndarray,
                           prof_dt: float) -> jnp.ndarray:
    """Per-node power of each job ``elapsed`` work-seconds into its run
    -> f32[J]. Under DVFS throttling the engine passes its work-time
    progress (which advances at c*dt per step) so a slowed job's profile
    plays at its dilated tempo rather than in wall-clock time.

    LOCF semantics (paper §3.2.2): the profile index is clamped into
    [0, P-1], so times before the first / after the last sample reuse the
    nearest recorded value.

    Replay mode: a measured ``table.power_profile`` sample (same clamped
    work-time indexing, at its own width Q) overrides the model wherever
    one exists — the -1 sentinel marks "no measurement", so the per-job
    switch is traced and profile-less jobs are untouched.
    """
    P = table.prof_len
    idx = jnp.clip((elapsed / prof_dt).astype(jnp.int32), 0, P - 1)
    p = jnp.take_along_axis(table.power_prof, idx[:, None], axis=1)[:, 0]
    if table.power_profile is not None:
        Q = table.power_profile.shape[1]
        qidx = jnp.clip((elapsed / prof_dt).astype(jnp.int32), 0, Q - 1)
        m = jnp.take_along_axis(table.power_profile, qidx[:, None],
                                axis=1)[:, 0]
        p = jnp.where(m >= 0.0, m, p)
    running = jstate == T.RUNNING
    return jnp.where(running, p, 0.0)


def job_node_power(table: T.JobTable, jstate: jnp.ndarray, start: jnp.ndarray,
                   t: jnp.ndarray, prof_dt: float) -> jnp.ndarray:
    """Per-node power drawn by each job at time ``t``  -> f32[J]."""
    return job_node_power_elapsed(table, jstate,
                                  jnp.maximum(t - start, 0.0), prof_dt)


def job_node_util(table: T.JobTable, jstate: jnp.ndarray, start: jnp.ndarray,
                  t: jnp.ndarray, prof_dt: float) -> jnp.ndarray:
    """Per-node utilization of each job at time ``t`` -> f32[J] in [0,1]."""
    P = table.prof_len
    elapsed = jnp.maximum(t - start, 0.0)
    idx = jnp.clip((elapsed / prof_dt).astype(jnp.int32), 0, P - 1)
    u = jnp.take_along_axis(table.util_prof, idx[:, None], axis=1)[:, 0]
    return jnp.where(jstate == T.RUNNING, u, 0.0)


def node_power(system: SystemConfig, table: T.JobTable, node_job: jnp.ndarray,
               job_pw: jnp.ndarray) -> jnp.ndarray:
    """Map per-job power onto the node axis -> f32[N].

    ``node_job[n]`` is the occupying job id (or -1). Free nodes draw idle
    power.
    """
    occupied = node_job >= 0
    safe = jnp.maximum(node_job, 0)
    p = jnp.take(job_pw, safe)
    return jnp.where(occupied, p, system.power.idle_node_w)


def group_occupancy(job_group_nodes: jnp.ndarray,
                    jstate: jnp.ndarray) -> jnp.ndarray:
    """f32[G, J] nodes each RUNNING job holds in each CDU group (other
    jobs' columns, stale since their release, read 0)."""
    running = jstate == T.RUNNING
    return jnp.where(running, job_group_nodes, 0).astype(jnp.float32)


def group_power(system: SystemConfig, occ: jnp.ndarray,
                job_pw: jnp.ndarray) -> jnp.ndarray:
    """IT power per CDU group -> f32[G]: the group's nodes no running job
    holds at ``idle_node_w`` plus each job's per-node power ``job_pw``
    (f32[J]) times its nodes there (``occ``, from ``group_occupancy``).
    Full f32: an elementwise product and a sum over jobs."""
    n_g = group_sizes(system.n_nodes, system.cooling.n_groups)
    idle_nodes = n_g - jnp.sum(occ, axis=-1)
    return system.power.idle_node_w * idle_nodes + jnp.sum(occ * job_pw,
                                                           axis=-1)
