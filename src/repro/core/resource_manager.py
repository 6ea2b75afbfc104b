"""Node allocation / release (paper §3.2.3: "the resource manager then
completes the job placement, allocating nodes").

Node state is a single int32 array ``node_job[N]`` (occupying job id, -1 when
free, -2 when down for repair — the failure layer ``repro.events`` parks
unavailable free nodes there so placement skips them).

Placement is first-free, and it is written once per admission loop, not
once per placed job. The loop decides on scalars alone (free counts, the
power projection, the EASY flags); a loop frees nothing, so the jobs it
places take the free nodes one after another: the k-th placement takes
the free ranks ``(C[k-1], C[k]]``, C the running sum of the placed
jobs' node counts. After the loop ``commit_placements`` writes the node
map, ``node_end``, the jobs' lifecycle and the per-group occupancy from
one rank of the free nodes (``free_ranks``):

* reschedule mode: ranks in node-index order (lowest free index first);
* hall-aware mode: ranks in a caller-supplied node *preference order* —
  the scheduler orders nodes by their hall's cooling pressure so
  placement drains into the coolest hall first
  (repro.systems.config.FacilityTopology); ``ok_upto`` counts the
  placements' nodes in halls still holding setpoint, for the loop's
  per-hall gate;
* replay mode: the same first-free rule; the dataset generators place by
  it too, so the recorded occupancy is reproduced (paper §3.2.3: "the
  exact node placement as specified in the telemetry is used in replay
  mode"; jobs running at t0 take their recorded spans, ``prepopulate``).

The node map has a per-group summary, ``job_group_nodes[G, J]``: the
number of nodes each job holds in each CDU group (groups are the
contiguous node spans of ``kernels/power_topo/ref.group_ids``). It is
written where a job gains nodes: ``prepopulate_groups`` at t0, then
``record_placements`` after each placement loop. Release never clears
it: only RUNNING jobs' columns are read, and a job leaves RUNNING exactly
when its nodes are freed. Per-CDU power and heat come from it as J x G
dense work (``repro.power.model.group_power``) instead of a gather of
per-job power onto every node.

Beside the node map rides ``node_end[N]``, the end time of the job on
each node, written with the very f32 value the job's ``end`` gets.
Release is then an elementwise compare (``release_done``), not a gather
of the per-job completion flag onto every node. On the grid path DVFS
stretches ``end`` after placement, so ``node_end`` is written there but
not read: that path releases through ``release_done_gather``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax.numpy as jnp

from repro.core import types as T
from repro.kernels.power_topo.ref import group_sizes, segment_dot
from repro.obs import timing


def release_done(node_job: jnp.ndarray, node_end: jnp.ndarray,
                 t: jnp.ndarray) -> jnp.ndarray:
    """Free every node whose occupying job has reached its end time: an
    elementwise compare against the per-node end time written at
    placement.
    Exact while every running job's ``end`` is the one written at
    placement (every path but the grid's)."""
    return jnp.where((node_job >= 0) & (t >= node_end), -1, node_job)


def release_done_gather(node_job: jnp.ndarray,
                        done_now: jnp.ndarray) -> jnp.ndarray:
    """Free every node whose occupying job just completed, by a gather of
    the per-job flag onto the nodes. The grid path's release: DVFS
    stretches a running job's ``end`` there, so ``node_end`` falls behind
    it."""
    occupied = node_job >= 0
    safe = jnp.maximum(node_job, 0)
    freed = occupied & jnp.take(done_now, safe)
    return jnp.where(freed, -1, node_job)


def group_counts(mask: jnp.ndarray, n_groups: int) -> jnp.ndarray:
    """bool[N] -> i32[G] nodes of the mask in each CDU group."""
    n = mask.shape[-1]
    span = -(-n // n_groups)               # ceil: matches ref.group_ids
    x = jnp.pad(mask.astype(jnp.int32), (0, span * n_groups - n))
    return jnp.sum(x.reshape(n_groups, span), axis=-1)


def prepopulate(n_nodes: int, first_node: jnp.ndarray, nodes: jnp.ndarray,
                running0: jnp.ndarray) -> jnp.ndarray:
    """Build the initial node_job map from jobs already running at sim start
    (paper §3.2.3 prepopulation). Spans are disjoint by construction.

    Uses a delta-encoding + cumsum fill: O(J + N), no per-job loop.
    """
    J = first_node.shape[0]
    jid = jnp.arange(J, dtype=jnp.int32)
    val = jnp.where(running0, jid + 1, 0)  # 0 == free sentinel
    start = jnp.where(running0, first_node, 0)
    stop = jnp.where(running0, first_node + nodes, 0)
    delta = jnp.zeros((n_nodes + 1,), jnp.int32)
    delta = delta.at[start].add(val)
    delta = delta.at[stop].add(-val)
    fill = jnp.cumsum(delta[:-1])
    return fill - 1  # -1 == free


def prepopulate_groups(n_nodes: int, n_groups: int, first_node: jnp.ndarray,
                       nodes: jnp.ndarray, running0: jnp.ndarray
                       ) -> jnp.ndarray:
    """``job_group_nodes`` i32[G, J] of the jobs ``prepopulate`` places:
    the overlap of each span ``[first_node, first_node + nodes)`` with
    each CDU group's span, in closed form (J x G elementwise, no
    scatter)."""
    sizes = group_sizes(n_nodes, n_groups)
    g_hi = np.cumsum(sizes, dtype=np.int32)[:, None]
    g_lo = g_hi - sizes[:, None]
    lo = jnp.maximum(first_node[None, :], g_lo)
    hi = jnp.minimum((first_node + nodes)[None, :], g_hi)
    return jnp.where(running0[None, :], jnp.maximum(hi - lo, 0), 0)


def placement_counts(group_free: jnp.ndarray, group_pos: jnp.ndarray,
                     need: jnp.ndarray) -> jnp.ndarray:
    """Nodes each placement of one loop took from each CDU group, i32[K, G].

    A placement loop frees nothing, so its first-free placements take the
    free nodes one after another in preference order: the k-th placed
    job (``need[k]`` nodes, 0 where none was placed) takes the free ranks
    ``[C[k-1], C[k])``, C the running sum of ``need``. Groups are
    contiguous in any preference order, so group g holds the free ranks
    ``[S[g], S[g] + group_free[g])``, S the free nodes of the groups
    reached before it (lower ``group_pos``, the preference position of a
    group's first node). The counts are the overlaps: K x G elementwise.

    Args:
      group_free: i32[G] free nodes in each group before the loop.
      group_pos: i32[G] preference position of each group's first node.
      need: i32[K] nodes of the job placed at each iteration, or 0.
    """
    before = group_pos[None, :] < group_pos[:, None]
    start = jnp.sum(jnp.where(before, group_free[None, :], 0), axis=1)
    hi = jnp.cumsum(need)
    lo = hi - need
    return jnp.maximum(
        jnp.minimum(hi[:, None], (start + group_free)[None, :])
        - jnp.maximum(lo[:, None], start[None, :]), 0)


def record_placements(job_group_nodes: jnp.ndarray, free: jnp.ndarray,
                      group_pos: jnp.ndarray, hit: jnp.ndarray,
                      took: jnp.ndarray) -> jnp.ndarray:
    """Write the columns of the jobs one first-free placement loop started.

    Args:
      job_group_nodes: i32[G, J] the carry's per-group occupancy.
      free: bool[N] the free nodes before the loop.
      group_pos: i32[G] preference position of each group's first node.
      hit: bool[K, J] placement k started job j.
      took: i32[K] nodes placement k took (0 where none was placed).
    Returns:
      i32[G, J] with the placed jobs' columns replaced. One [G, K] x
      [K, J] one-hot product at full f32 precision (exact: counts are far
      below 2^24), so no scatter whose index varies per batch row.
    """
    counts = placement_counts(group_counts(free, group_pos.shape[0]),
                              group_pos, took)
    cols = segment_dot(counts.T.astype(jnp.float32), hit.astype(jnp.float32))
    return jnp.where(jnp.any(hit, axis=0)[None, :],
                     cols.astype(jnp.int32), job_group_nodes)


def free_ranks(node_job: jnp.ndarray,
               order: jnp.ndarray | None = None) -> jnp.ndarray:
    """i32[N] rank of each free node among the free nodes, from 1, in node
    order or in the preference order ``order`` (i32[N], a permutation of
    node indices); 0 where the node is not free (a -2 down node is not).

    One cumsum; the ordered form adds one gather of the free mask into the
    order and one scatter back. ``obs.timing.PLACEMENT_STATS`` counts each
    trace by kind ("ranks" or "ranks_ordered"); nothing runs on the device
    for it.
    """
    free = node_job == -1
    if order is None:
        timing.PLACEMENT_STATS["ranks"] += 1
        return jnp.where(free, jnp.cumsum(free.astype(jnp.int32)), 0)
    timing.PLACEMENT_STATS["ranks_ordered"] += 1
    free_o = free[order]
    rank_o = jnp.where(free_o, jnp.cumsum(free_o.astype(jnp.int32)), 0)
    return jnp.zeros_like(rank_o).at[order].set(rank_o)


def ok_upto(rank: jnp.ndarray, ok: jnp.ndarray) -> jnp.ndarray:
    """i32[N + 1]: entry r counts the ``ok`` nodes among the free ranks
    1..r (``rank`` from ``free_ranks``). A placement over the ranks
    ``(c, c + need]`` takes ``ok_upto[c + need] - ok_upto[c]`` of them: a
    scalar lookup in the loop. No assumption that the ``ok`` nodes come
    first in the order."""
    on_rank = jnp.zeros((rank.shape[-1] + 1,), jnp.int32).at[rank].add(
        (ok & (rank > 0)).astype(jnp.int32))
    return jnp.cumsum(on_rank)


def place_ranks(node_job: jnp.ndarray, node_end: jnp.ndarray,
                rank: jnp.ndarray, cand: jnp.ndarray, took: jnp.ndarray,
                end_t: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(node_job, node_end) with placement k on the free ranks
    ``(C[k-1], C[k]]``, C the running sum of ``took`` (i32[K], 0 where
    nothing was placed): its nodes get job ``cand[k]`` and end ``end_t[k]``.
    One [K, N] mask, reduced over K (the major axis: elementwise over N)."""
    hi = jnp.cumsum(took)
    lo = hi - took
    on = (rank[None, :] > lo[:, None]) & (rank[None, :] <= hi[:, None])
    jid = jnp.max(jnp.where(on, cand[:, None], -1), axis=0)
    end = jnp.max(jnp.where(on, end_t[:, None], -jnp.inf), axis=0)
    taken = jid >= 0
    return jnp.where(taken, jid, node_job), jnp.where(taken, end, node_end)


def commit_placements(st: T.SimState, rank: jnp.ndarray,
                      group_pos: jnp.ndarray, cand: jnp.ndarray,
                      placed: jnp.ndarray, need: jnp.ndarray,
                      end_t: jnp.ndarray) -> T.SimState:
    """Write what one admission loop decided, once, after the loop.

    A loop frees nothing, so its first-free placements take the free
    nodes one after another in the loop's order: placement k takes the
    free ranks ``(C[k-1], C[k]]``, C the running sum of the placed jobs'
    ``need``. Node map, ``node_end``, lifecycle, start and end times, free
    count and per-group occupancy are written from those ranks
    (``place_ranks``, ``record_placements``), with [K, N] and [K, J]
    masks reduced over K: no scatter whose index varies per batch row.

    Args:
      st: the state before the loop.
      rank: i32[N] ``free_ranks(st.node_job, order)`` in the loop's order.
      group_pos: i32[G] preference position of each group's first node.
      cand: i32[K] the job each iteration tried (-1: none).
      placed: bool[K] iteration k started ``cand[k]`` (each job at most
        once).
      need: i32[K] nodes of ``cand[k]``.
      end_t: f32[K] ``t + wall`` of ``cand[k]``: the one value its
        ``end`` and its nodes' ``node_end`` both get.
    """
    took = jnp.where(placed, need, 0)
    node_job, node_end = place_ranks(st.node_job, st.node_end, rank, cand,
                                     took, end_t)
    J = st.jstate.shape[-1]
    hit = placed[:, None] & (cand[:, None] == jnp.arange(J)[None, :])
    started = jnp.any(hit, axis=0)
    end_j = jnp.max(jnp.where(hit, end_t[:, None], -jnp.inf), axis=0)
    return dataclasses.replace(
        st, node_job=node_job, node_end=node_end,
        jstate=jnp.where(started, T.RUNNING, st.jstate),
        start=jnp.where(started, st.t, st.start),
        end=jnp.where(started, end_j, st.end),
        free_count=st.free_count - jnp.sum(took),
        job_group_nodes=record_placements(
            st.job_group_nodes, st.node_job == -1, group_pos, hit, took))
