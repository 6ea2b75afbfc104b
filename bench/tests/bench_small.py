"""Small copies of the benchmark's configurations, for tests on the CPU."""
from __future__ import annotations

import copy
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def load(kind: str, name: str) -> dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def scaled(config: dict, n_nodes: int, n_jobs: int,
           duration_s: float) -> dict:
    """``config`` cut to ``n_nodes`` the way ``SystemConfig.scaled`` cuts
    a machine (tower capacity, fans and heat export in proportion, at
    least two CDU groups), with a backlog of ``n_jobs`` over
    ``duration_s``."""
    c = copy.deepcopy(config)
    s = c["system"]
    cool = s["cooling"]
    ratio = n_nodes / s["n_nodes"]
    cells = max(int(round(cool["n_tower_cells"] * ratio)), 1)
    cap = cool["n_tower_cells"] * cool["cell_rated_heat_w"] * ratio
    cool.update(
        n_groups=max(int(round(cool["n_groups"] * ratio)), 2),
        n_tower_cells=cells, cell_rated_heat_w=cap / cells,
        fan_rated_w=cool["fan_rated_w"] * (cap / cells) /
        cool["cell_rated_heat_w"],
        reuse_max_w=cool["reuse_max_w"] * ratio)
    s["n_nodes"] = n_nodes
    s["name"] = f"{s['name']}-scaled{n_nodes}"
    c["workload"].update(n_jobs=n_jobs, duration_s=duration_s)
    return c


def traffic(name: str, horizon_s: float) -> dict:
    t = load("traffic", name)
    t["horizon_s"] = horizon_s
    return t
