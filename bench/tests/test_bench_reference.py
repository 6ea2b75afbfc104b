"""The plain reference against the engine, on the CPU at 64 nodes.

Every policy:backfill pair that the benchmark's cells run is simulated by
the program (through the same entry the cell uses) and by the reference:
free-running, the reference must reproduce every start time exactly;
following the program's starts, every number ``check.py`` compares must
read as a sound run does.
"""
import numpy as np
import pytest

import bench_small as bs
import check
import program
import reference
import workload

CASES = [("frontier", "policy16", 300), ("frontier", "replay1", 300)]


@pytest.fixture(scope="module", params=CASES, ids=lambda c: f"{c[0]}.{c[1]}")
def case(request):
    name, mix, n_jobs = request.param
    config = bs.scaled(bs.load("configs", name), 64, n_jobs, 6 * 3600.0)
    traffic = bs.traffic(mix, 4 * 3600.0)
    jobs = workload.make_jobs(config, 20261016)
    study = program.Study(config, traffic, jobs)
    final, hist, summaries = study.collect(study.dispatch())
    return config, traffic, jobs, study, final, hist, summaries


def test_free_running_reference_reproduces_every_start(case):
    config, traffic, jobs, study, final, hist, _ = case
    for i, row in enumerate(study.rows):
        got = program.row_outputs(final, hist, i)
        ref = reference.run(config["system"], jobs, row["policy"],
                            row["backfill"], traffic["horizon_s"],
                            row["cells_offline"])
        fin = lambda a: np.where(np.isfinite(a), a, -1.0)
        assert np.array_equal(fin(ref["start"]), fin(got["start"])), row
        assert np.array_equal(ref["jstate"], got["jstate"]), row
        np.testing.assert_allclose(got["power_it"], ref["hist"]["power_it"],
                                   rtol=1e-5)


def test_every_compared_number_reads_sound(case):
    config, traffic, jobs, study, final, hist, summaries = case
    assert len(summaries) == len(study.rows)
    started = 0
    for i, row in enumerate(study.rows):
        got = program.row_outputs(final, hist, i)
        started += int(np.isfinite(got["start"]).sum())
        nums = check.compare_row(config["system"], jobs, row,
                                 traffic["horizon_s"], got)
        assert nums["wrong_steps"] == 0, row
        for k in ("jstate_diff", "completed_diff", "free_diff"):
            assert nums[k] == 0, (row, k, nums[k])
        for k in ("power_it_rel", "cooling_rel", "facility_rel",
                  "energy_rel"):
            assert nums[k] < 1e-5, (row, k, nums[k])
    assert started > 50 * len(study.rows)


def test_a_shifted_start_is_a_wrong_step(case):
    config, traffic, jobs, study, final, hist, _ = case
    got = program.row_outputs(final, hist, 0)
    start = got["start"].copy()
    j = int(np.flatnonzero(np.isfinite(start) & (start > 0))[0])
    start[j] += config["system"]["dt"]
    got["start"] = start
    row = study.rows[0]
    nums = check.compare_row(config["system"], jobs, row,
                             traffic["horizon_s"], got)
    assert nums["wrong_steps"] >= 1
