"""The comparison that decides ``correct``.

Rows of the window's studies are compared with the plain reference
(``reference.run``), which follows each row's reported start times and
counts the steps at which its own scheduler would have decided
otherwise. The rows compared are drawn from the seed, one from each of
``check_strata`` contiguous blocks of the scenario axis, so that every
block (and on several chips every chip's slice) is covered. Every other
study of the window must equal the compared one exactly.

Each number compared is a worst case over the rows compared; the limits
live in ``limits/<cell>.json``.
"""
from __future__ import annotations

import numpy as np

import reference

NAMES = ("wrong_steps", "jstate_diff", "completed_diff", "free_diff",
         "power_it_rel", "cooling_rel", "facility_rel", "energy_rel",
         "repeat_diff")


def sample_rows(n_rows: int, strata: int, seed: int) -> list[int]:
    """One row from each of ``strata`` contiguous blocks, drawn from
    ``seed``."""
    rng = np.random.default_rng([seed % 2 ** 63, 0x5EED])
    blocks = np.array_split(np.arange(n_rows), min(strata, n_rows))
    return [int(rng.choice(b)) for b in blocks]


def _rel(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    with np.errstate(divide="ignore", invalid="ignore"):
        r = np.abs(got - want) / np.abs(want)
    return float(np.nan_to_num(r, nan=np.inf).max())


def compare_row(system: dict, jobs: dict, row: dict, horizon_s: float,
                got: dict) -> dict:
    """The numbers of one row: the program's outputs ``got``
    (``program.row_outputs``) against the reference that follows them."""
    ref = reference.run(system, jobs, row["policy"], row["backfill"],
                        horizon_s, row["cells_offline"],
                        forced_start=got["start"])
    h = ref["hist"]
    free_map = int((np.asarray(got["node_job"]) == -1).sum())
    return dict(
        wrong_steps=ref["wrong_steps"] + ref["invalid_starts"],
        jstate_diff=int((np.asarray(got["jstate"]) != ref["jstate"]).sum()),
        completed_diff=abs(got["completed"] - ref["completed"]),
        free_diff=abs(got["free_count"] - free_map) +
        abs(got["free_count"] - ref["free_count"]),
        power_it_rel=_rel(got["power_it"], h["power_it"]),
        cooling_rel=_rel(got["power_cooling"], h["power_cooling"]),
        facility_rel=_rel(got["power_total"], h["power_total"]),
        energy_rel=_rel(got["energy_total"], ref["energy_total"]))


def repeat_diff(studies) -> int:
    """How many studies differ in any output from the first one."""
    import jax
    first = jax.tree_util.tree_leaves(studies[0])
    n = 0
    for s in studies[1:]:
        leaves = jax.tree_util.tree_leaves(s)
        if len(leaves) != len(first) or not all(
                np.array_equal(a, b, equal_nan=True)
                for a, b in zip(first, leaves)):
            n += 1
    return n


def check(config: dict, traffic: dict, jobs: dict, studies, seed: int,
          limits: dict) -> tuple[dict, list[int], int]:
    """Compare the window's studies (host (final, history) pairs, all of
    the same scenarios) with the reference. Returns ({name: (value,
    limit)}, rows compared, rows that failed)."""
    import program
    rows = program.rows_of(traffic)
    final, hist = studies[-1]
    picked = sample_rows(len(rows), int(traffic["check_strata"]), seed)
    worst = {k: 0.0 for k in NAMES}
    failed_rows = 0
    for i in picked:
        got = program.row_outputs(final, hist, i)
        nums = compare_row(config["system"], jobs, rows[i],
                           float(traffic["horizon_s"]), got)
        if any(nums[k] > limits[k] for k in nums):
            failed_rows += 1
        for k, v in nums.items():
            worst[k] = max(worst[k], v)
    worst["repeat_diff"] = repeat_diff(studies)
    if worst["repeat_diff"] > limits["repeat_diff"]:
        failed_rows += int(worst["repeat_diff"]) * len(rows)
    return ({k: (worst[k], limits[k]) for k in NAMES}, picked,
            failed_rows)
