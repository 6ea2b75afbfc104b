"""Node allocation / release (paper §3.2.3: "the resource manager then
completes the job placement, allocating nodes").

Node state is a single int32 array ``node_job[N]`` (occupying job id, -1 when
free, -2 when down for repair — the failure layer ``repro.events`` parks
unavailable free nodes there so placement skips them). Placement is
vectorized:

* reschedule mode: first-free placement by prefix-sum rank over the free mask;
* hall-aware mode: the same prefix-sum rank, taken in a caller-supplied
  node *preference order* (``firstfree_mask_ordered``) — the scheduler
  orders nodes by their hall's cooling pressure so placement drains into
  the coolest hall first (repro.systems.config.FacilityTopology);
* replay mode: the exact recorded contiguous span ``[first_node,
  first_node+need)`` (paper §3.2.3: "the exact node placement as specified in
  the telemetry is used in replay mode").

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
each node, which ``place`` writes with the very f32 value the scheduler
writes into the job's ``end``. Release is then an elementwise compare
(``release_done``), not a gather of the per-job completion flag onto
every node. On the grid path DVFS stretches ``end`` after placement, so
``node_end`` is written there but not read: that path releases through
``release_done_gather``.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.core import types as T
from repro.kernels.power_topo.ref import group_sizes, segment_dot


def release_done(node_job: jnp.ndarray, node_end: jnp.ndarray,
                 t: jnp.ndarray) -> jnp.ndarray:
    """Free every node whose occupying job has reached its end time: an
    elementwise compare against the per-node end time ``place`` wrote.
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


def firstfree_mask(node_job: jnp.ndarray, need: jnp.ndarray) -> jnp.ndarray:
    """Boolean mask selecting the first ``need`` free nodes (a -2 down
    node is not free)."""
    free = node_job == -1
    rank = jnp.cumsum(free.astype(jnp.int32))
    return free & (rank <= need)


def group_counts(mask: jnp.ndarray, n_groups: int) -> jnp.ndarray:
    """bool[N] -> i32[G] nodes of the mask in each CDU group."""
    n = mask.shape[-1]
    span = -(-n // n_groups)               # ceil: matches ref.group_ids
    x = jnp.pad(mask.astype(jnp.int32), (0, span * n_groups - n))
    return jnp.sum(x.reshape(n_groups, span), axis=-1)


def firstfree_mask_ordered(node_job: jnp.ndarray, need: jnp.ndarray,
                           order: jnp.ndarray) -> jnp.ndarray:
    """Boolean mask selecting the first ``need`` free nodes *in preference
    order* (``order``: i32[N] permutation of node indices; identity order
    reproduces ``firstfree_mask`` exactly)."""
    free = node_job == -1
    free_o = free[order]
    rank = jnp.cumsum(free_o.astype(jnp.int32))
    sel_o = free_o & (rank <= need)
    return jnp.zeros_like(free).at[order].set(sel_o)


def contiguous_mask(n_nodes: int, first: jnp.ndarray,
                    need: jnp.ndarray) -> jnp.ndarray:
    idx = jnp.arange(n_nodes, dtype=jnp.int32)
    return (idx >= first) & (idx < first + need)


def place(node_job: jnp.ndarray, node_end: jnp.ndarray, sel: jnp.ndarray,
          jid: jnp.ndarray, end_t: jnp.ndarray, do_place: jnp.ndarray
          ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Assign job ``jid``, ending at ``end_t``, to nodes in ``sel`` when
    ``do_place``: (node_job, node_end)."""
    m = sel & do_place
    return jnp.where(m, jid, node_job), jnp.where(m, end_t, node_end)


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


def record_placements(job_group_nodes: jnp.ndarray, node_job: jnp.ndarray,
                      group_pos: jnp.ndarray, cand: jnp.ndarray,
                      jstate_before: jnp.ndarray, jstate_after: jnp.ndarray,
                      job_nodes: jnp.ndarray) -> jnp.ndarray:
    """Write the columns of the jobs one first-free placement loop started.

    Args:
      job_group_nodes: i32[G, J] the carry's per-group occupancy.
      node_job: i32[N] the node map before the loop.
      group_pos: i32[G] preference position of each group's first node.
      cand: i32[K] the job each iteration tried (-1: none). The job
        placed at iteration k is ``cand[k]`` if it was QUEUED before the
        loop and is RUNNING after it (at its first occurrence: a repeat
        finds it running), so the loop itself records nothing.
      jstate_before, jstate_after: i32[J] lifecycle around the loop.
      job_nodes: i32[J] nodes each job takes.
    Returns:
      i32[G, J] with the placed jobs' columns replaced. One [G, K] x
      [K, J] one-hot product at full f32 precision (exact: counts are far
      below 2^24), so no scatter whose index varies per batch row.
    """
    K = cand.shape[0]
    k = jnp.arange(K)
    repeat = jnp.any((cand[:, None] == cand[None, :])
                     & (k[:, None] > k[None, :]), axis=1)
    safe = jnp.maximum(cand, 0)
    started = (cand >= 0) & ~repeat & \
        (jstate_before[safe] == T.QUEUED) & (jstate_after[safe] == T.RUNNING)
    counts = placement_counts(
        group_counts(node_job == -1, group_pos.shape[0]), group_pos,
        jnp.where(started, job_nodes[safe], 0))
    J = job_group_nodes.shape[-1]
    hit = started[:, None] & (cand[:, None] == jnp.arange(J)[None, :])
    cols = segment_dot(counts.T.astype(jnp.float32), hit.astype(jnp.float32))
    return jnp.where(jnp.any(hit, axis=0)[None, :],
                     cols.astype(jnp.int32), job_group_nodes)
