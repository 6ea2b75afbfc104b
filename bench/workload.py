"""The benchmark's workload generator: a job backlog drawn from ``--seed``.

A copy of the twin's calibrated synthetic generator (diurnal Poisson
arrivals, log2-mix node counts, lognormal walltimes scaled to a target
offered load, per-node power profiles, and a recorded ground-truth
schedule from an event-driven fcfs/first-fit scheduler). It is kept here
so that the yardstick stays fixed while the program's own datasets
change. The knobs of each deployment's backlog live in its configuration
file under ``"workload"``.

``make_jobs`` returns the job table as host numpy arrays in the dtypes
the engine consumes (times and power float32, counts int32): both the
program under test and the plain reference read exactly these numbers.
"""
from __future__ import annotations

import heapq

import numpy as np


def event_schedule(submit, limit, wall, nodes, n_nodes: int,
                   dt: float) -> np.ndarray:
    """Recorded start times (grid-aligned) of an event-driven fcfs,
    first-fit scheduler; completions release nodes before placements at
    the same instant."""
    J = len(submit)
    submit_g = np.ceil(submit / dt) * dt
    start = np.full(J, np.inf)
    free = n_nodes
    queue: list[int] = []
    ev = [(float(submit_g[j]), 1, j) for j in range(J)]
    heapq.heapify(ev)
    while ev:
        t, kind, j = heapq.heappop(ev)
        if kind == 0:
            free += int(nodes[j])
        else:
            queue.append(j)
        if ev and ev[0][0] == t:
            continue
        queue.sort(key=lambda q: (submit_g[q], q))
        placed = []
        for q in queue:
            need = int(nodes[q])
            if need <= free:
                free -= need
                start[q] = t
                heapq.heappush(ev, (t + float(wall[q]), 0, q))
                placed.append(q)
        for q in placed:
            queue.remove(q)
    return start


def generate(n_nodes: int, dt: float, idle_w: float, peak_w: float,
             spec: dict, seed: int) -> dict:
    """Draw a backlog (float64 host arrays) for a machine of ``n_nodes``
    stepping at ``dt`` seconds; ``spec`` holds the workload knobs."""
    rng = np.random.default_rng(seed % 2 ** 64)
    J = int(spec["n_jobs"])
    duration = float(spec["duration_s"])
    mean_wall = float(spec["mean_wall_s"])

    base = rng.exponential(duration / J, J)
    submit = np.cumsum(base)
    submit *= duration / submit[-1]
    day_phase = 2 * np.pi * submit / 86400.0
    submit = submit + float(spec["diurnal"]) * mean_wall * np.sin(day_phase)
    submit = np.clip(np.sort(submit), 0.0, duration)

    max_nodes = max(int(n_nodes * float(spec["max_frac_nodes"])), 1)
    raw = 2 ** rng.uniform(0, np.log2(max(max_nodes, 2)), J)
    nodes = np.maximum(raw.astype(np.int64), 1)
    n_full = int(spec["full_system_jobs"])
    if n_full:
        idx = rng.choice(J // 2, n_full, replace=False) + J // 4
        nodes[idx] = n_nodes

    wall = rng.lognormal(np.log(mean_wall), 0.8, J)
    wall = np.maximum(np.round(wall / dt), 1.0) * dt
    limit = wall * rng.uniform(1.1, 3.0, J)
    limit = np.ceil(limit / dt) * dt

    offered = float((nodes * wall).sum())
    scale = float(spec["load"]) * n_nodes * duration / offered
    if scale < 1.0:
        nodes = np.maximum((nodes * scale).astype(np.int64), 1)

    n_acct = int(spec["n_accounts"])
    acct_prob = 1.0 / np.arange(1, n_acct + 1)
    acct_prob /= acct_prob.sum()
    account = rng.choice(n_acct, J, p=acct_prob)
    temperament = rng.beta(2, 2, n_acct)[account]

    priority = np.log2(nodes + 1) + rng.uniform(0, 1, J)

    P = int(spec["trace_len"])
    base_util = np.clip(0.35 + 0.55 * temperament + rng.normal(0, 0.1, J),
                        0.05, 1.0)
    if P == 1:
        util = base_util[:, None].astype(np.float32)
    else:
        walk = rng.normal(0, 0.05, (J, P)).cumsum(1)
        util = np.clip(base_util[:, None] + walk, 0.02, 1.0).astype(np.float32)
    power_prof = (idle_w + (peak_w - idle_w) * util).astype(np.float32)

    rec_start = event_schedule(submit, limit, wall, nodes, n_nodes, dt)
    rec_start[~np.isfinite(rec_start)] = duration * 2
    return dict(submit=submit, limit=limit, wall=wall, nodes=nodes,
                priority=priority, account=account, rec_start=rec_start,
                power_prof=power_prof, util_prof=util)


def prepop_first_node(rec_start, wall, nodes, t0: float,
                      n_nodes: int) -> np.ndarray:
    """Contiguous node spans, in job order, for jobs the record shows
    running at ``t0``; -1 for every other job (and for a running job that
    no longer fits)."""
    first = np.full(len(nodes), -1, np.int64)
    running0 = (rec_start <= t0) & (rec_start + wall > t0)
    cursor = 0
    for j in np.nonzero(running0)[0]:
        need = int(nodes[j])
        if cursor + need <= n_nodes:
            first[j] = cursor
            cursor += need
    return first


def make_jobs(config: dict, seed: int, t0: float = 0.0) -> dict:
    """The job table of ``config``'s backlog for ``seed``, packed in the
    engine's dtypes: float32 times/power/priority, int32 counts/ids."""
    sysc = config["system"]
    pw = sysc["power"]
    g = generate(int(sysc["n_nodes"]), float(sysc["dt"]),
                 float(pw["idle_node_w"]), float(pw["peak_node_w"]),
                 config["workload"], seed)
    first = prepop_first_node(g["rec_start"], g["wall"], g["nodes"], t0,
                              int(sysc["n_nodes"]))
    J = len(g["nodes"])
    f32 = lambda x: np.asarray(x, np.float32)
    i32 = lambda x: np.asarray(x, np.int32)
    return dict(
        submit=f32(g["submit"]), limit=f32(g["limit"]), wall=f32(g["wall"]),
        nodes=i32(g["nodes"]), priority=f32(g["priority"]),
        account=i32(g["account"]), rec_start=f32(g["rec_start"]),
        first_node=i32(first), score=np.zeros(J, np.float32),
        power_prof=f32(g["power_prof"]),
        util_prof=f32(g["util_prof"]),
        valid=np.ones(J, bool))
