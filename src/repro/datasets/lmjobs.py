"""AI-workload dataset: the twin schedules LM training/serving jobs.

Each job is a (arch x shape) run from the assigned grid. Its per-node
power comes from an analytic utilisation table — every runnable cell at
the same utilisation (``_CELL_UTIL``), jittered per job — through the
standard utilization->power proxy ``idle + (peak - idle) * util``.
"""
from __future__ import annotations

import numpy as np

from repro.datasets.base import JobSet
from repro.datasets.synthetic import event_schedule
from repro.systems.config import SystemConfig

# compute-unit utilisation of every (arch x shape) cell
_CELL_UTIL = 0.6


def _cell_utilization() -> dict:
    """(arch, shape) -> utilisation for every runnable cell of the grid."""
    from repro.configs import ARCHS, SHAPES
    return {(a, s): _CELL_UTIL for a in ARCHS for s in SHAPES
            if s not in ARCHS[a].skip_shapes}


def generate_lm_workload(system: SystemConfig, n_jobs: int = 256,
                         duration_s: float = 86400.0, seed: int = 0,
                         n_accounts: int = 16) -> JobSet:
    """Jobs = LM runs drawn from the assigned (arch x shape) grid.

    Returns a ``JobSet`` with times in s and scalar per-node power
    profiles (W) derived from each cell's utilisation
    (idle + (peak - idle) * util); walltimes are grid-aligned to
    ``system.dt`` and a ground-truth schedule is recorded via
    ``event_schedule`` (replay semantics, paper §3.2.2)."""
    rng = np.random.default_rng(seed)
    cells = _cell_utilization()
    keys = list(cells.keys())
    pick = rng.integers(0, len(keys), n_jobs)

    # job sizing: training runs are wide + long, decode serving narrow + long,
    # prefill batch jobs short
    kind_of = {"train_4k": (0.10, 6.0), "prefill_32k": (0.02, 1.0),
               "decode_32k": (0.04, 8.0), "long_500k": (0.01, 4.0)}
    nodes = np.empty(n_jobs, np.int64)
    wall = np.empty(n_jobs)
    util = np.empty(n_jobs, np.float32)
    arch_ids = []
    for i, k in enumerate(pick):
        arch, shape = keys[k]
        frac, hours = kind_of.get(shape, (0.05, 2.0))
        nodes[i] = max(int(system.n_nodes * frac * rng.uniform(0.5, 2.0)), 1)
        wall[i] = max(rng.lognormal(np.log(hours * 3600.0), 0.5),
                      system.dt)
        util[i] = np.clip(cells[keys[k]] * rng.uniform(0.9, 1.05), 0.05, 1.0)
        arch_ids.append(f"{arch}:{shape}")
    wall = np.round(wall / system.dt) * system.dt
    nodes = np.minimum(nodes, system.n_nodes)

    submit = np.sort(rng.uniform(0, duration_s, n_jobs))
    limit = wall * rng.uniform(1.1, 2.0, n_jobs)
    idle, peak = system.power.idle_node_w, system.power.peak_node_w
    power = (idle + (peak - idle) * util)[:, None].astype(np.float32)
    rec_start = event_schedule(submit, limit, wall, nodes, system.n_nodes,
                               system.dt)
    rec_start = np.where(np.isfinite(rec_start), rec_start, duration_s * 2)
    js = JobSet(submit=submit, limit=limit, wall=wall, nodes=nodes,
                priority=np.log2(nodes + 1.0),
                account=rng.integers(0, n_accounts, n_jobs),
                rec_start=rec_start, power_prof=power,
                util_prof=util[:, None].astype(np.float32),
                name=f"lmjobs-{system.name}")
    js.arch_ids = arch_ids  # type: ignore[attr-defined]
    return js
