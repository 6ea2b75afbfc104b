"""Plain reference of the twin's semantics: numpy, float64, step by step.

It implements what ``repro.core`` documents (scheduler, power model,
node -> CDU -> hall segment sums, transient cooling plant, conversion
losses and the ledgers) from the configuration file and the job table
alone, and imports nothing of the program.

One engine step at time ``t`` (seconds; ``t = k * dt``):

1. prepare: jobs whose end has come complete and free their nodes; their
   wait, turnaround, energy and Fugaku points fold into the per-account
   ledgers; jobs submitted by ``t`` join the queue.
2. schedule: eligible queued jobs sort by (policy key, submit, index);
   the first ``sched_budget`` of them are tried in order under the
   backfill rule (none: stop at the first blocked job; first-fit: skip
   it; EASY: the first blocked job reserves the shadow time from the
   running jobs' requested limits, later jobs start only if they end
   before it or fit in the spare nodes). Replay starts each job at its
   recorded start and ignores the rest. A started job takes the
   lowest-numbered free nodes. Admission halts while the cooling loop has
   lost its supply setpoint by more than the margin.
3. tick: each running job's nodes draw its profile sample at its elapsed
   time (free nodes draw idle power); node power sums into CDU groups and
   halls; the CDU valves, supply loop, tower fans and basin advance one
   step; conversion losses and cooling parasitics give facility power;
   energies accumulate.

``run`` is free-running by default. With ``forced_start`` (the start
times a program reported) it follows the program's decisions, as a
served model's reference follows the served tokens, and counts the steps
at which its own scheduler would have decided otherwise. ``rounding``
names a dtype that every computed quantity (profiles, powers, CDU and
tower state, energies, ledgers, policy keys) is rounded through: the
lower-precision control. The step clock and the job times (whole seconds
on the step grid) stay exact.
"""
from __future__ import annotations

import math

import numpy as np

PENDING, QUEUED, RUNNING, DONE, DISMISSED = 0, 1, 2, 3, 4
POLICIES = ("replay", "fcfs", "sjf", "ljf", "priority", "acct_avg_power",
            "acct_low_avg_power", "acct_edp", "acct_ed2p", "acct_fugaku_pts")
BACKFILLS = ("none", "first-fit", "easy")


def _rounder(rounding):
    if rounding is None:
        return lambda x: np.asarray(x, np.float64)
    dtype = np.dtype(rounding)
    return lambda x: np.asarray(x, np.float64).astype(dtype).astype(
        np.float64)


class Plant:
    """Static constants of one configuration's machine and cooling plant."""

    def __init__(self, system: dict):
        c = system["cooling"]
        self.n_nodes = int(system["n_nodes"])
        self.dt = float(system["dt"])
        self.prof_dt = float(system["prof_dt"])
        self.budget = int(system["sched_budget"])
        p = system["power"]
        self.idle_w = float(p["idle_node_w"])
        self.ref_node_w = float(p["ref_node_w"])
        self.rect_c = tuple(float(x) for x in p["rect_c"])
        self.sivoc_c = tuple(float(x) for x in p["sivoc_c"])
        n_racks = max(self.n_nodes // int(p["nodes_per_rack"]), 1)
        self.rated_w = n_racks * float(p["rated_rack_kw"]) * 1e3
        self.c = {k: v for k, v in c.items() if k != "topology"}
        topo = c.get("topology", {})
        self.n_halls = int(topo.get("n_halls", 1))
        if self.n_halls != 1 or topo.get("groups_per_hall") or \
                topo.get("cells_per_hall"):
            raise NotImplementedError("the reference schedules a one-hall "
                                      "plant only")
        G = int(c["n_groups"])
        self.n_groups = G
        span = -(-self.n_nodes // G)
        self.gid = np.minimum(np.arange(self.n_nodes) // span, G - 1)
        self.group_size = np.bincount(self.gid, minlength=G).astype(
            np.float64)
        cells = float(c["n_tower_cells"])
        self.cells = cells
        self.cell_ua = (float(c["cell_ua_w_k"]) if c.get("cell_ua_w_k")
                        else float(c["cell_rated_heat_w"]) / 6.0)
        self.mcp = (float(c["basin_mcp_j_k"]) if c.get("basin_mcp_j_k")
                    else float(c["tower_tau_s"]) * cells * self.cell_ua)
        self.passive_ua = float(c["passive_ua_frac"]) * cells * self.cell_ua
        self.mdot_max = float(c["mdot_kg_s"])
        self.mdot_min = float(c["mdot_min_frac"]) * self.mdot_max

    def eta(self, coeffs, load):
        c0, c1, c2 = coeffs
        return np.clip(c0 + c1 * load + c2 * load * load, 0.5, 0.999)


def _shadow(end_sorted, cum, free_now, need):
    """Earliest time ``need`` nodes are free together, and the spare
    nodes then, from the running jobs' estimated ends."""
    deficit = max(need - free_now, 0)
    k = int(np.searchsorted(cum, deficit, side="left"))
    k = min(max(k, 0), len(cum) - 1)
    shadow_t = 0.0 if deficit == 0 else float(end_sorted[k])
    return shadow_t, max(free_now + int(cum[k]) - need, 0)


def _admit(visit, jstate, nodes, limit, rec_start, free_count, t, replay,
           backfill, thermal_ok, profile):
    """The jobs one scheduling pass starts, in the order it starts them
    (counts only: where they land does not change the decision)."""
    free = free_count
    started = set()
    out = []
    blocked_any = head_blocked = head_capped = False
    shadow_t, shadow_extra = math.inf, 0
    for j in visit:
        valid = jstate[j] == QUEUED and j not in started
        if replay:
            valid = valid and rec_start[j] <= t
        need = int(nodes[j])
        fits = need <= free
        if valid and not fits and not head_blocked:
            shadow_t, shadow_extra = _shadow(*profile(), free, need)
        easy_ok = ((t + limit[j] <= shadow_t) or
                   (need <= shadow_extra)) and not head_capped
        if backfill == "none":
            can_bf = not blocked_any
        elif backfill == "first-fit":
            can_bf = True
        else:
            can_bf = easy_ok if (head_blocked or head_capped) else True
        if replay:
            place = valid and fits
        else:
            place = valid and fits and can_bf and thermal_ok
        if place:
            free -= need
            started.add(j)
            out.append(j)
        blocked_any |= valid and (not fits or not thermal_ok)
        head_blocked |= valid and not fits
        head_capped |= valid and fits and not thermal_ok
    return out


def run(system: dict, jobs: dict, policy: str, backfill: str,
        horizon_s: float, cells_offline: float = 0.0,
        num_accounts: int = 64, forced_start=None, rounding=None) -> dict:
    """Simulate ``[0, horizon_s)`` of ``jobs`` on ``system``.

    Returns the per-step IT, cooling and facility power (W), the final
    job states, starts and node map, the free-node count, completed jobs
    and total energy, and with ``forced_start`` the count of steps whose
    admissions the program decided otherwise (``wrong_steps``) and the
    forced starts that could not happen (``invalid_starts``)."""
    if policy not in POLICIES or backfill not in BACKFILLS:
        raise ValueError(f"unknown policy/backfill {policy}:{backfill}")
    r = _rounder(rounding)
    pl = Plant(system)
    c = pl.c
    dt = pl.dt
    N, G = pl.n_nodes, pl.n_groups
    n_steps = int(round(horizon_s / dt))
    f64 = lambda x: np.asarray(x, np.float64)
    submit = f64(jobs["submit"])
    limit = f64(jobs["limit"])
    wall = f64(jobs["wall"])
    nodes = np.asarray(jobs["nodes"], np.int64)
    prio = r(jobs["priority"])
    acct = np.asarray(jobs["account"], np.int64)
    rec_start = f64(jobs["rec_start"])
    first_node = np.asarray(jobs["first_node"], np.int64)
    prof = r(jobs["power_prof"])
    valid = np.asarray(jobs["valid"], bool)
    J, P = prof.shape
    A = max(num_accounts, int(acct.max()) + 1)
    replay = policy == "replay"
    K = min(pl.budget, J)

    # --- initial state: dismiss, prepopulate, queue -----------------------
    t0 = 0.0
    rec_end = rec_start + wall
    dismissed = ~valid | (rec_end <= t0) | (submit >= horizon_s)
    running0 = ~dismissed & (rec_start <= t0) & (rec_end > t0) & \
        (first_node >= 0)
    jstate = np.full(J, PENDING, np.int64)
    jstate[dismissed] = DISMISSED
    jstate[running0] = RUNNING
    jstate[~dismissed & ~running0 & (submit <= t0)] = QUEUED
    start = np.where(running0, rec_start, np.inf)
    end = np.where(running0, rec_end, np.inf)
    progress = np.where(running0, np.maximum(t0 - rec_start, 0.0), 0.0)
    jenergy = np.zeros(J)
    node_job = np.full(N, -1, np.int64)
    count = np.zeros((J, G))          # nodes of job j in CDU group g
    owned = {}
    for j in np.nonzero(running0)[0]:
        span = np.arange(first_node[j], first_node[j] + nodes[j])
        node_job[span] = j
        owned[j] = span
        count[j] = np.bincount(pl.gid[span], minlength=G)
    free_count = int((node_job == -1).sum())
    led = {k: np.zeros(A) for k in ("jobs_done", "power_sum", "edp",
                                    "ed2p", "fugaku_pts")}
    t_set = float(c["t_supply_setpoint_c"])
    t_wb = float(c["t_wetbulb_c"])
    t_supply = np.full(G, t_set)
    t_return = t_supply + 5.0
    mdot = np.full(G, pl.mdot_min)
    t_basin = t_wb + float(c["tower_approach_c"])
    fan = 0.0
    e_total = 0.0
    completed = 0

    forced = None
    if forced_start is not None:
        fs = np.asarray(forced_start, np.float64)
        forced = {}
        pick = np.isfinite(fs) & ~running0
        for j in np.nonzero(pick)[0]:
            k = int(round(fs[j] / dt))
            if fs[j] != k * dt:
                k = -1                    # not on the step grid
            forced.setdefault(k, []).append(int(j))
    wrong = invalid = 0

    hist = {k: np.zeros(n_steps)
            for k in ("power_it", "power_cooling", "power_total")}
    a_valve = min(dt / float(c["tau_valve_s"]), 1.0)
    a_hx = min(dt / float(c["tau_hx_s"]), 1.0)
    a_fan = min(max(dt / float(c["tau_fan_s"]), 0.0), 1.0)
    cp = float(c["cp_j_kg_k"])
    cells_on = min(max(pl.cells - float(cells_offline), 0.0), pl.cells)
    if forced is not None and -1 in forced:
        invalid += len(forced.pop(-1))

    for step in range(n_steps):
        t = step * dt
        # (1) completions, ledgers, arrivals
        done = (jstate == RUNNING) & (t >= end)
        if done.any():
            for j in np.nonzero(done)[0]:
                node_job[owned.pop(j)] = -1
            free_count += int(nodes[done].sum())
            count[done] = 0.0
            jstate[done] = DONE
            completed += int(done.sum())
            nf = nodes[done].astype(np.float64)
            w = np.maximum(end[done] - start[done], 1.0)
            turn = np.maximum(end[done] - submit[done], 0.0)
            nh = nf * w / 3600.0
            avg = jenergy[done] / np.maximum(nf * w, 1.0)
            pts = nh * np.clip((pl.ref_node_w - avg) / pl.ref_node_w,
                               0.0, 1.0)
            a = acct[done]
            for name, vals in (("jobs_done", np.ones_like(nf)),
                               ("power_sum", avg),
                               ("edp", jenergy[done] * turn),
                               ("ed2p", jenergy[done] * turn * turn),
                               ("fugaku_pts", pts)):
                np.add.at(led[name], a, vals)
                led[name] = r(led[name])
        jstate[(jstate == PENDING) & (submit <= t)] = QUEUED

        # (2) schedule
        thermal_ok = not (float(t_supply.max()) >
                          t_set + float(c["t_supply_margin_c"]))
        elig = (jstate == QUEUED) & valid
        if replay:
            elig &= rec_start <= t
        idx = np.nonzero(elig)[0]
        todo = forced.get(step, []) if forced is not None else None
        if idx.size:
            key = _policy_key(policy, idx, submit, limit, nodes, prio,
                              rec_start, acct, led, r)
            srt = np.lexsort((submit[idx], key))
            order = idx[srt]

            def profile():
                run_ = jstate == RUNNING
                est = np.where(run_, start + limit, np.inf)
                o = np.argsort(est, kind="stable")
                return est[o], np.cumsum(np.where(run_, nodes, 0)[o])

            mine = _admit(order[:K], jstate, nodes, limit, rec_start,
                          free_count, t, replay, backfill, thermal_ok,
                          profile)
        else:
            order, mine = idx, []
        if todo is None or set(todo) == set(mine):
            todo = mine
        else:
            wrong += 1
            rank = {int(j): i for i, j in enumerate(order)}
            todo = sorted(todo, key=lambda j: (rank.get(j, J + j)))
        for j in todo:
            need = int(nodes[j])
            if jstate[j] not in (PENDING, QUEUED) or need > free_count:
                invalid += 1
                continue
            span = np.flatnonzero(node_job == -1)[:need]
            node_job[span] = j
            owned[j] = span
            count[j] = np.bincount(pl.gid[span], minlength=G)
            free_count -= need
            jstate[j] = RUNNING
            start[j] = t
            end[j] = t + wall[j]

        # (3) power, cooling, ledgers
        running = jstate == RUNNING
        pidx = np.clip((progress / pl.prof_dt).astype(np.int64), 0, P - 1)
        job_pw = np.where(running, prof[np.arange(J), pidx], 0.0)
        occ = count.sum(axis=0)
        q = r(job_pw @ count + pl.idle_w * (pl.group_size - occ))
        dem = np.clip(q / (cp * float(c["delta_t_design_c"])), pl.mdot_min,
                      pl.mdot_max)
        mdot_new = r(mdot + (dem - mdot) * a_valve)
        t_return = r(t_supply + q / (mdot_new * cp))
        tgt = np.maximum(t_set, t_basin + q / float(c["ua_w_k"]))
        t_supply = r(t_supply + (tgt - t_supply) * a_hx)
        mdot = mdot_new
        q_hall = float(r(q.sum()))
        p_it = q_hall
        t_mix = float((mdot * t_return).sum() / max(mdot.sum(), 1e-6))
        q_reuse = (min(float(c["reuse_frac"]) * q_hall,
                       float(c["reuse_max_w"]))
                   if t_mix >= float(c["reuse_t_min_c"]) else 0.0)
        q_tower = q_hall - q_reuse
        q_passive = pl.passive_ua * (t_basin - t_wb)
        t_b_tgt = max(t_wb + float(c["tower_approach_c"]),
                      t_set - float(c["basin_margin_c"]))
        drive = max(t_basin - t_wb, 0.5)
        q_need = q_tower - q_passive + pl.mcp * (t_basin - t_b_tgt) / \
            float(c["tower_tau_s"])
        s_tgt = min(max(q_need / (pl.cell_ua * drive), 0.0), cells_on)
        fan = float(r(min(fan + (s_tgt - fan) * a_fan, cells_on)))
        q_rej = max(fan * pl.cell_ua * (t_basin - t_wb), 0.0) + q_passive
        t_basin = float(r(t_basin + (q_tower - q_rej) * dt / pl.mcp))
        k_fan = math.floor(fan)
        fan_w = float(c["fan_rated_w"]) * (k_fan + (fan - k_fan) ** 3)
        pump_w = float((float(c["pump_w_per_group"]) *
                        (0.2 + 0.8 * (mdot / pl.mdot_max) ** 3)).sum())
        p_cool = float(r(fan_w + pump_w))
        load = min(max(p_it / max(pl.rated_w, 1.0), 0.0), 1.5)
        p_in = float(r(p_it / (pl.eta(pl.rect_c, load) *
                               pl.eta(pl.sivoc_c, load))))
        p_total = float(r(p_in + p_cool))
        jenergy = r(jenergy + np.where(
            running, job_pw * nodes.astype(np.float64) * dt, 0.0))
        progress = progress + np.where(running, dt, 0.0)
        e_total = float(r(e_total + p_total * dt))
        hist["power_it"][step] = p_it
        hist["power_cooling"][step] = p_cool
        hist["power_total"][step] = p_total

    if forced is not None:
        invalid += sum(len(v) for k, v in forced.items() if k >= n_steps)
    return dict(hist=hist, jstate=jstate, start=start, node_job=node_job,
                free_count=free_count, completed=completed,
                energy_total=e_total, wrong_steps=wrong,
                invalid_starts=invalid)


def _policy_key(policy, idx, submit, limit, nodes, prio, rec_start, acct,
                led, r):
    """Sort key of the eligible jobs ``idx`` (smaller starts earlier)."""
    a = acct[idx]

    def avg_pw():
        return led["power_sum"][a] / np.maximum(led["jobs_done"][a], 1.0)
    key = {
        "replay": lambda: rec_start[idx],
        "fcfs": lambda: submit[idx],
        "sjf": lambda: limit[idx],
        "ljf": lambda: -nodes[idx].astype(np.float64),
        "priority": lambda: -prio[idx],
        "acct_avg_power": lambda: -avg_pw(),
        "acct_low_avg_power": avg_pw,
        "acct_edp": lambda: led["edp"][a],
        "acct_ed2p": lambda: led["ed2p"][a],
        "acct_fugaku_pts": lambda: -led["fugaku_pts"][a],
    }[policy]()
    return r(key)

