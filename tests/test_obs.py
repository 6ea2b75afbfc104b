"""Flight recorder: manifests, NDJSON streams, span timers, bridge and
sweep-cache counters, and the perf-trajectory gate (tools/bench_compare)."""
import json
import pathlib
import socket
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.core import engine as eng
from repro.core import external as ext
from repro.core import transport
from repro.core import types as T
from repro.obs import (MetricsSink, RunRecorder, SpanTimer, build_manifest,
                       load_manifest, read_frames, schema, stream_history,
                       timing, use)
from repro.obs.timing import LatencyHistogram

ROOT = pathlib.Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# Manifest + schema
# ---------------------------------------------------------------------------
def test_manifest_valid_and_digest_deterministic(small_system, small_jobs):
    kw = dict(command="simulate", argv=["-t", "1h"],
              scenario={"policy": "fcfs"}, seed=7, jobs=small_jobs)
    m1 = build_manifest(small_system, **kw)
    m2 = build_manifest(small_system, **kw)
    assert m1["system"]["digest"] == m2["system"]["digest"]
    assert m1["jobs"]["digest"] == m2["jobs"]["digest"]
    assert m1["system"]["n_nodes"] == small_system.n_nodes
    assert m1["jobs"]["n_jobs"] == len(small_jobs)
    for k in ("python", "numpy", "jax", "backend"):
        assert k in m1["versions"]
    # distinct runs still mint distinct run ids
    assert m1["run_id"] != m2["run_id"]


def test_manifest_validation_names_missing_fields(small_system):
    m = build_manifest(small_system, command="simulate", argv=[],
                       scenario={})
    del m["seed"]
    m["argv"] = "not-a-list"
    with pytest.raises(schema.SchemaError) as e:
        schema.validate_manifest(m)
    assert "seed" in str(e.value) and "argv" in str(e.value)


def test_jsonable_strips_nonfinite():
    out = schema.jsonable({"cap_w": float("inf"),
                           "arr": np.array([1.0, np.nan]),
                           "n": np.int32(3)})
    assert out == {"cap_w": None, "arr": [1.0, None], "n": 3}
    json.dumps(out)  # strict-JSON safe


def test_frame_envelopes_validate():
    f = schema.metrics_frame("r", 0, 15.0, {"pue": 1.1}, label="fcfs:easy")
    assert schema.validate_frame(f) is f
    with pytest.raises(schema.SchemaError):
        schema.validate_frame({"v": 99, "kind": "metrics", "run_id": "r"})
    with pytest.raises(schema.SchemaError):
        schema.validate_frame(schema.event_frame("r", 0, 0.0, "x")
                              | {"kind": "nope"})


# ---------------------------------------------------------------------------
# Recorder: manifest + event log on disk
# ---------------------------------------------------------------------------
def test_recorder_writes_manifest_and_events(tmp_path, small_system):
    mpath, epath = tmp_path / "run.json", tmp_path / "events.ndjson"
    clock = iter(float(i) for i in range(100))
    with RunRecorder(manifest_path=mpath, events_path=epath,
                     clock=lambda: next(clock)) as rec:
        rec.begin(small_system, command="simulate", argv=["-t", "1h"],
                  scenario={"policy": "fcfs"}, seed=0)
        rec.event("run_start")
        rec.event("checkpoint", path="ck.json", generation=2)
        rec.finalize(spans={"spans": {}, "counters": {}}, wall_s=1.25)
    m = load_manifest(mpath)
    assert m["n_events"] == 2 and m["wall_s"] == 1.25
    frames = read_frames(epath)
    assert [f["event"] for f in frames] == ["run_start", "checkpoint"]
    assert frames[1]["generation"] == 2
    assert all(f["run_id"] == m["run_id"] for f in frames)
    assert [f["seq"] for f in frames] == [0, 1]


def test_recorder_survives_missing_finalize(tmp_path, small_system):
    """A crash before finalize still leaves the event log behind."""
    epath = tmp_path / "events.ndjson"
    rec = RunRecorder(events_path=epath)
    rec.begin(small_system, command="train", argv=[], scenario={})
    rec.event("run_start")
    rec.close()  # simulated crash: no finalize
    assert [f["event"] for f in read_frames(epath)] == ["run_start"]


# ---------------------------------------------------------------------------
# Metrics sink: file + socket targets
# ---------------------------------------------------------------------------
def _tiny_run(small_system, small_table, n_steps=8):
    t1 = n_steps * small_system.dt
    final, hist = eng.simulate(small_system, small_table,
                               T.Scenario.make("fcfs", "first-fit"),
                               0.0, t1)
    return final, hist, n_steps


def test_metrics_sink_file_one_frame_per_interval(tmp_path, small_system,
                                                  small_table):
    final, hist, n_steps = _tiny_run(small_system, small_table)
    path = tmp_path / "metrics.ndjson"
    with MetricsSink(str(path)) as sink:
        n = stream_history(sink, "run-1", small_system, small_table,
                           final, hist, label="fcfs:none")
    assert n == n_steps + 1 == sink.n_frames
    frames = read_frames(path)
    assert len(frames) == n_steps + 1
    metrics = [f for f in frames if f["kind"] == schema.KIND_METRICS]
    assert len(metrics) == n_steps
    assert [f["seq"] for f in metrics] == list(range(n_steps))
    for f in metrics:
        assert f["label"] == "fcfs:none"
        assert f["data"]["pue"] >= 1.0
        # per-hall vectors have the topology's width
        assert len(f["data"]["t_basin_hall"]) == \
            small_system.cooling.n_halls
    summary = frames[-1]
    assert summary["kind"] == schema.KIND_SUMMARY
    assert summary["data"]["jobs_completed"] >= 0.0


def test_metrics_sink_socket_roundtrip(tmp_path, small_system, small_table):
    final, hist, n_steps = _tiny_run(small_system, small_table, n_steps=4)
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    got = []

    def serve():
        conn, _ = srv.accept()
        with conn, conn.makefile("rb") as rf:
            while True:
                try:
                    got.append(transport.read_frame(rf))
                except ConnectionError:
                    break

    th = threading.Thread(target=serve, daemon=True)
    th.start()
    with MetricsSink(f"tcp:127.0.0.1:{port}") as sink:
        stream_history(sink, "run-s", small_system, small_table,
                       final, hist)
    th.join(timeout=10.0)
    srv.close()
    assert len(got) == n_steps + 1
    assert got[0]["kind"] == schema.KIND_METRICS
    assert got[-1]["kind"] == schema.KIND_SUMMARY


def test_metrics_sink_rejects_bad_tcp_target():
    with pytest.raises(ValueError):
        MetricsSink("tcp:no-port-here")


# ---------------------------------------------------------------------------
# Span timers: an observed run takes the same cached runner as any other
# ---------------------------------------------------------------------------
def test_timer_spans_and_parity(small_system, small_table):
    scen = T.Scenario.make("fcfs", "first-fit")
    t1 = 13 * small_system.dt     # a horizon no other test compiles
    timer = SpanTimer()
    with use(timer):
        final1, hist1 = eng.simulate(small_system, small_table, scen,
                                     0.0, t1)
    before = dict(eng.SWEEP_CACHE_STATS)
    final0, hist0 = eng.simulate(small_system, small_table, scen, 0.0, t1)
    # the unobserved call runs the runner the observed one built
    assert eng.SWEEP_CACHE_STATS["hits"] == before["hits"] + 1
    assert eng.SWEEP_CACHE_STATS["misses"] == before["misses"]
    np.testing.assert_array_equal(np.asarray(hist0.power_total),
                                  np.asarray(hist1.power_total))
    np.testing.assert_array_equal(np.asarray(final0.start),
                                  np.asarray(final1.start))
    spans = timer.summary()["spans"]
    # the build comes from JAX's own compile events
    for name in ("engine.lower", "engine.compile"):
        assert spans[name]["count"] >= 1
        assert spans[name]["total_s"] >= 0.0
    for name in ("engine.dispatch", "engine.init_state", "engine.runner",
                 "engine.launch"):
        assert spans[name]["count"] == 1
    assert timing.current() is None  # uninstalled on exit


def test_static_path_counters_and_parity(small_system, small_table):
    t1 = 6 * small_system.dt
    f0, h0 = eng.simulate_static(small_system, small_table, "fcfs",
                                 "first-fit", 0.0, t1)
    before = dict(eng.SWEEP_CACHE_STATS)
    timer = SpanTimer()
    with use(timer):
        f1, h1 = eng.simulate_static(small_system, small_table, "fcfs",
                                     "first-fit", 0.0, t1)
        f2, _ = eng.simulate_static(small_system, small_table, "fcfs",
                                    "first-fit", 0.0, t1)
    np.testing.assert_array_equal(np.asarray(f0.t), np.asarray(f1.t))
    np.testing.assert_array_equal(np.asarray(f0.start), np.asarray(f2.start))
    np.testing.assert_array_equal(np.asarray(h0.power_total),
                                  np.asarray(h1.power_total))
    # the first call above already populated the cache: both observed
    # calls hit, in the always-on runner-cache counters
    assert eng.SWEEP_CACHE_STATS["hits"] == before["hits"] + 2
    assert eng.SWEEP_CACHE_STATS["misses"] == before["misses"]
    summary = timer.summary()
    assert summary["counters"] == {}
    assert summary["spans"]["engine.launch"]["count"] == 2
    assert "engine.compile" not in summary["spans"]


def test_sweep_cache_stats_monotonic(small_system, small_table):
    before = dict(eng.SWEEP_CACHE_STATS)
    scens = [T.Scenario.make("fcfs"), T.Scenario.make("sjf")]
    t1 = 4 * small_system.dt
    eng.simulate_sweep(small_system, small_table, scens, 0.0, t1)
    eng.simulate_sweep(small_system, small_table, scens, 0.0, t1)
    after = eng.SWEEP_CACHE_STATS
    assert after["hits"] + after["misses"] >= \
        before["hits"] + before["misses"] + 2
    assert after["hits"] >= before["hits"] + 1  # second call reuses


def test_span_timer_deterministic_clock_and_listener():
    events = []
    clock = iter([0.0, 1.5, 2.0, 2.25]).__next__
    timer = SpanTimer(clock=clock,
                      listener=lambda what, f: events.append((what, f)))
    with timer.span("engine.compile", system="x"):
        pass
    with timer.span("engine.scan"):
        pass
    s = timer.summary()["spans"]
    assert s["engine.compile"]["total_s"] == 1.5
    assert s["engine.scan"]["total_s"] == 0.25
    assert [e[0] for e in events] == ["span_start", "span_end"] * 2
    assert events[1][1]["dur_s"] == 1.5


def test_latency_histogram_buckets():
    h = LatencyHistogram()
    for d in (5e-4, 0.02, 0.02, 250.0):
        h.record(d)
    s = h.summary()
    assert s["count"] == 4
    assert s["buckets"]["le_0.001s"] == 1
    assert s["buckets"]["le_0.1s"] == 2
    assert s["buckets"]["overflow"] == 1
    assert s["max_s"] == 250.0


# ---------------------------------------------------------------------------
# Bridge counters
# ---------------------------------------------------------------------------
def test_bridge_counters_surface_polls(small_system, small_jobs):
    events = []
    bridge = ext.SchedulerBridge(
        ext.FastSimLike(policy="fcfs", backfill="firstfit"),
        on_event=lambda ev, f: events.append(ev))
    t1 = 6 * small_system.dt
    ext.run_plugin_mode(small_system, small_jobs, bridge, 0.0, t1)
    s = bridge.stats()
    assert s["polls"] >= 1
    assert s["poll_failures"] == 0 and s["reconnects"] == 0
    assert s["poll_latency"]["count"] == s["polls"]
    assert events == []  # no reconnects -> no bridge events


# ---------------------------------------------------------------------------
# Perf-trajectory gate (tools/bench_compare.py)
# ---------------------------------------------------------------------------
def _gate(tmp_path, payload, history, append=False):
    bench = tmp_path / "BENCH.json"
    bench.write_text(json.dumps(payload))
    cmd = [sys.executable, str(ROOT / "tools" / "bench_compare.py"),
           str(bench), "--history", str(history)]
    if append:
        cmd.append("--append")
    return subprocess.run(cmd, capture_output=True, text=True)


def test_bench_compare_gate_paths(tmp_path):
    hist = tmp_path / "hist.ndjson"
    ok = {"engine/smoke": {"steps_per_s": 100.0, "wall_s": 1.0},
          "meta": {"backend": "cpu", "device": "cpu"}}
    # 1. no history: free pass, --append seeds the trajectory
    r = _gate(tmp_path, ok, hist, append=True)
    assert r.returncode == 0, r.stderr
    assert "no history" in r.stdout
    assert len(hist.read_text().splitlines()) == 1
    # 2. identical run gates green
    r = _gate(tmp_path, ok, hist)
    assert r.returncode == 0, r.stderr
    # 3. synthetic 2x regression gates red
    bad = {"engine/smoke": {"steps_per_s": 50.0}, "meta": ok["meta"]}
    r = _gate(tmp_path, bad, hist)
    assert r.returncode == 1
    assert "REGRESSION" in r.stderr
    # 4. small wobble (within 30%) stays green
    wobble = {"engine/smoke": {"steps_per_s": 85.0}, "meta": ok["meta"]}
    assert _gate(tmp_path, wobble, hist).returncode == 0
    # 5. different backend never gates against cpu history
    gpu = {"engine/smoke": {"steps_per_s": 1.0},
           "meta": {"backend": "gpu", "device": "H100"}}
    r = _gate(tmp_path, gpu, hist)
    assert r.returncode == 0
    assert "no history" in r.stdout
    # 6. a file with no *_per_s metrics is a usage error
    assert _gate(tmp_path, {"meta": ok["meta"]}, hist).returncode == 2


def test_bench_compare_gates_committed_baselines():
    """CI runs the gate against benchmarks/baselines/ — the committed
    history must parse and carry the engine/ml throughput metrics."""
    base = ROOT / "benchmarks" / "baselines"
    for name, metric in (("engine_history.ndjson", "steps_per_s"),
                         ("ml_history.ndjson", "generations_per_s")):
        lines = (base / name).read_text().splitlines()
        assert lines, f"{name} is empty"
        e = json.loads(lines[-1])
        assert e["backend"]
        assert any(k.endswith(metric) for k in e["metrics"])


# ---------------------------------------------------------------------------
# CLI: the acceptance path (tiny twin, manifest + metrics, run twice)
# ---------------------------------------------------------------------------
def test_simulate_cli_flight_recorder_deterministic(tmp_path):
    from repro.launch import simulate as cli
    outs = []
    for i in (1, 2):
        m = tmp_path / f"run{i}.json"
        mx = tmp_path / f"metrics{i}.ndjson"
        ev = tmp_path / f"events{i}.ndjson"
        cli.main(["--system", "marconi100", "--scale", "64", "--jobs",
                  "20", "-t", "10m", "--policy", "fcfs",
                  "--manifest", str(m), "--metrics", str(mx),
                  "--events", str(ev), "--quiet"])
        outs.append((load_manifest(m), read_frames(mx), read_frames(ev)))
    (m1, fr1, ev1), (m2, fr2, _) = outs
    # identical configuration -> identical system digest (acceptance)
    assert m1["system"]["digest"] == m2["system"]["digest"]
    assert m1["jobs"]["digest"] == m2["jobs"]["digest"]
    n_steps = int(round(600.0 / m1["system"]["dt"]))
    metrics1 = [f for f in fr1 if f["kind"] == schema.KIND_METRICS]
    assert len(metrics1) == n_steps  # >= 1 frame per interval
    assert len(fr1) == len(fr2)
    assert m1["counters"]["metrics_frames"] == len(fr1)
    assert "engine.scan" in m1["spans"]["spans"]
    assert any(f["event"] == "run_start" for f in ev1)
    assert any(f["event"] == "run_end" for f in ev1)
