"""The benchmark's own files: BENCHMARK.json against its contract, every
cell's files found by name, the copied workload generator, and the
harness refusing to run without an accelerator."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

import bench_small as bs
import workload

ROOT, BENCH = bs.ROOT, bs.BENCH
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= SPEC["run_seconds"] <= 51
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= len(SPEC["workloads"]) <= 24


def test_names_units_and_text():
    entries = (SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] +
               SPEC["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for group in ("configs", "workloads"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in [m["name"] for m in SPEC["end_to_end"]]
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and TEXT.match(m["layer"])
    for e in SPEC["configs"]:
        assert TEXT.match(e["source"]) and TEXT.match(e["why"])
    for w in SPEC["workloads"]:
        assert TEXT.match(w["why"]) and w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])


def test_entries_have_only_their_keys():
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    cells = {w["name"] for w in SPEC["workloads"]}
    for group, want in keys.items():
        for e in SPEC[group]:
            assert set(e) - {"workloads"} == want, e["name"]
            assert set(e.get("workloads", [])) <= cells, e["name"]
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(len(cells) // 2, 1)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_finds_its_files(cell):
    import run
    c = run.load_cell(cell)
    assert c["config"]["name"] == c["cell"]["config"]
    assert set(c["limits"]) >= {"wrong_steps", "power_it_rel",
                                "repeat_diff"}
    assert c["traffic"]["entry"] in ("sweep", "static")
    for m in SPEC["per_layer"]:
        assert callable(run.metric_reader(m["name"]))


def test_every_config_is_used_and_lies_under_paths():
    used = {w["config"] for w in SPEC["workloads"]}
    files = set()
    for e in SPEC["configs"]:
        assert e["name"] in used
        assert e["file"].startswith("bench/") and e["file"] not in files
        files.add(e["file"])
        conf = json.loads((ROOT / e["file"]).read_text())
        assert conf["source"] == e["source"]
        assert conf["reduced"] == e["reduced"] == []


@pytest.mark.parametrize("name,nodes,jobs,full", [
    ("frontier", 9600, 1238, 3)])
def test_generator_is_deterministic_at_published_size(name, nodes, jobs,
                                                      full):
    conf = bs.load("configs", name)
    assert conf["system"]["n_nodes"] == nodes
    a = workload.make_jobs(conf, 2 ** 31 + 11)
    b = workload.make_jobs(conf, 2 ** 31 + 11)
    c = workload.make_jobs(conf, 7)
    for k in a:
        assert np.array_equal(a[k], b[k]), k
    assert not np.array_equal(a["submit"], c["submit"])
    assert len(a["nodes"]) == jobs
    assert a["nodes"].max() <= nodes
    # the full-system jobs are drawn at the machine's size, then shrink
    # with every job when the offered load is rescaled: they stay largest
    assert int((a["nodes"] == a["nodes"].max()).sum()) >= max(full, 1)
    assert a["power_prof"].shape[1] == conf["workload"]["trace_len"]
    assert np.all(a["submit"] <= a["rec_start"])


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "frontier.policy16",
         "--seed", "3", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_an_accelerator():
    p = _run_cli(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "accelerator" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces",
                                                  "__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
