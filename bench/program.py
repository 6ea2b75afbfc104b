"""The system under test, driven the way the ``simulate`` CLI drives a
study: build the cell's scenarios, call the engine's entry, bring the
results to the host and summarise every row.

Everything the benchmark takes from the program passes through here: the
``SystemConfig`` and ``JobTable`` types, the two entries
(``engine.simulate_sweep_sharded`` and ``engine.simulate_static``) and
``stats.summarize``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core import stats
from repro.core import types as T
from repro.systems import config as sc


def _tuples(d: dict) -> dict:
    return {k: tuple(v) if isinstance(v, list) else v for k, v in d.items()}


def system_config(system: dict) -> sc.SystemConfig:
    """The program's ``SystemConfig`` from a configuration file's
    ``"system"`` object (every field given there)."""
    d = dict(system)
    cool = dict(d.pop("cooling"))
    topo = sc.FacilityTopology(**_tuples(cool.pop("topology")))
    power = sc.PowerConfig(**_tuples(d.pop("power")))
    grid = sc.GridConfig(**_tuples(d.pop("grid")))
    return sc.SystemConfig(**d, power=power, grid=grid,
                           cooling=sc.CoolingConfig(**cool, topology=topo))


def rows_of(traffic: dict) -> list[dict]:
    """One dict per scenario row: the policy:backfill pairs crossed with
    every ``cells_offline`` level, pairs varying fastest."""
    levels = traffic.get("cells_offline", [0.0])
    return [dict(policy=p.split(":")[0], backfill=p.split(":")[1],
                 cells_offline=float(c))
            for c in levels for p in traffic["pairs"]]


class Study:
    """One what-if study of a cell: S scenarios over the horizon."""

    def __init__(self, config: dict, traffic: dict, jobs: dict):
        self.system = system_config(config["system"])
        fields = {f.name for f in dataclasses.fields(T.JobTable)}
        self.table = T.JobTable(**{k: jnp.asarray(v) for k, v in jobs.items()
                                   if k in fields})
        self.rows = rows_of(traffic)
        self.horizon = float(traffic["horizon_s"])
        self.entry = traffic["entry"]
        if self.entry == "static" and len(self.rows) != 1:
            raise ValueError("the static entry runs one scenario")
        self.n_steps = int(round(self.horizon / self.system.dt))

    def dispatch(self):
        """Steps 1 and 2: build the scenarios and call the entry; returns
        (final, history) as device arrays with a leading row axis."""
        if self.entry == "static":
            r = self.rows[0]
            final, hist = engine.simulate_static(
                self.system, self.table, r["policy"], r["backfill"], 0.0,
                self.horizon)
            return jax.tree_util.tree_map(lambda x: x[None], (final, hist))
        scens = [T.Scenario.make(r["policy"], r["backfill"],
                                 cells_offline=r["cells_offline"])
                 for r in self.rows]
        return engine.simulate_sweep_sharded(self.system, self.table, scens,
                                             0.0, self.horizon)

    def collect(self, out):
        """Steps 3 and 4: results to the host, one summary per row."""
        final, hist = jax.device_get(out)
        summaries = []
        for i in range(len(self.rows)):
            row = lambda x, i=i: x[i]
            summaries.append(stats.summarize(
                self.system, self.table, jax.tree_util.tree_map(row, final),
                jax.tree_util.tree_map(row, hist)))
        return final, hist, summaries


def row_outputs(final, hist, i: int) -> dict:
    """What the check compares of row ``i`` (host numpy)."""
    return dict(
        start=np.asarray(final.start[i]), jstate=np.asarray(final.jstate[i]),
        node_job=np.asarray(final.node_job[i]),
        free_count=int(final.free_count[i]),
        completed=float(final.completed[i]),
        energy_total=float(final.energy_total[i]),
        power_it=np.asarray(hist.power_it[i]),
        power_cooling=np.asarray(hist.power_cooling[i]),
        power_total=np.asarray(hist.power_total[i]))
