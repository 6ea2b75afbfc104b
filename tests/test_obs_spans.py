"""Measuring the twin from inside: program spans (always recorded, on the
profiler's clock), JAX's compile events, and the engine step's phases as
the compiled module names them."""
import glob
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as eng
from repro.core import types as T
from repro.events import EventConfig
from repro.grid import signals as gsig
from repro.obs import phases, timing

# instructions that move no data and run no kernel of their own
FREE = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}


def _named(name):
    return [sp for sp in timing.RECENT.spans() if sp.name == name]


# ---------------------------------------------------------------------------
# The in-memory recorder
# ---------------------------------------------------------------------------
def test_spans_record_their_parent_without_a_timer():
    assert timing.current() is None
    with timing.span("test.outer"):
        with timing.span("test.inner", k=1) as sp:
            assert sp is None            # no timer: nothing to yield
    inner, = _named("test.inner")[-1:]
    outer, = _named("test.outer")[-1:]
    assert inner.parent == "test.outer" and inner.meta == {"k": 1}
    assert outer.t_start <= inner.t_start
    assert inner.t_start + inner.dur_s <= outer.t_start + outer.dur_s


def test_spans_of_a_worker_thread_are_kept():
    def work():
        with timing.span("test.worker"):
            with timing.span("test.worker_child"):
                pass
    th = threading.Thread(target=work)
    th.start()
    th.join(timeout=10.0)
    assert not th.is_alive()
    child, = _named("test.worker_child")[-1:]
    assert child.parent == "test.worker"
    # the worker's stack is its own: its outermost span has no parent
    assert _named("test.worker")[-1].parent is None


def test_recorder_is_bounded_and_safe_across_threads():
    rec = timing.SpanRecorder(limit=64)
    errors, stop = [], threading.Event()

    def write(i):
        for k in range(2000):
            rec.add(timing.Span(name=f"w{i}", t_start=float(k)))

    def read():
        try:
            while not stop.is_set():
                assert len(rec.spans()) <= 64
        except Exception as e:          # a copy racing an append
            errors.append(e)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        reader = threading.Thread(target=read)
        reader.start()
        writers = [threading.Thread(target=write, args=(i,))
                   for i in range(16)]
        for th in writers:
            th.start()
        for th in writers:
            th.join(timeout=60.0)
        stop.set()
        reader.join(timeout=60.0)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in writers + [reader])
    assert not errors
    spans = rec.spans()
    assert len(spans) == 64
    # the last writes of every writer that finished last are the ones kept
    assert all(sp.t_start >= 2000 - 64 for sp in spans)


def test_a_timer_gets_the_same_spans_and_the_listener_sees_them():
    events = []
    timer = timing.SpanTimer(listener=lambda what, f: events.append(what))
    with timing.use(timer):
        with timing.span("test.timed") as sp:
            assert sp is not None and sp.name == "test.timed"
    assert timer.summary()["spans"]["test.timed"]["count"] == 1
    assert events == ["span_start", "span_end"]
    assert _named("test.timed")


def test_compile_events_become_spans_and_counts():
    before = dict(timing.COMPILE_STATS)
    with timing.span("test.build"):
        jax.jit(lambda x: jnp.cos(x) * 3.0 + 1.0)(
            jnp.arange(7.0)).block_until_ready()
    after = timing.COMPILE_STATS
    assert after["compiles"] >= before["compiles"] + 1
    assert after["compile_s"] > before["compile_s"]
    assert after["lower_s"] > before["lower_s"]
    for name in ("engine.lower", "engine.compile"):
        assert _named(name)[-1].parent == "test.build"


# ---------------------------------------------------------------------------
# Spans in a profiler capture
# ---------------------------------------------------------------------------
def test_program_span_lands_in_a_profiler_trace_under_its_parent(tmp_path):
    from jax.profiler import ProfileData
    fn = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = jnp.ones((128, 128))
    fn(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with timing.span("engine.dispatch"):
            with timing.span("engine.launch"):
                y = fn(x)
            y.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    profile = ProfileData.from_file(path)
    found, ops = {}, []
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name in ("engine.dispatch", "engine.launch"):
                    found[ev.name] = (plane.name, line.name, iv)
                elif "sine" in ev.name or "dot" in ev.name:
                    ops.append(iv)
    (p0, l0, outer), (p1, l1, inner) = (found["engine.dispatch"],
                                        found["engine.launch"])
    assert (p0, l0) == (p1, l1)                 # one host line
    assert outer[0] <= inner[0] and inner[1] <= outer[1]
    # the program's operations ran inside the span, on the same clock
    assert ops and all(outer[0] <= s and e <= outer[1] for s, e in ops)


# ---------------------------------------------------------------------------
# Phases: the compiled sweep runner names each instruction's phase
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def sweep_hlo(small_system, small_table):
    """The optimized HLO of the sweep runner with every layer on (grid
    signals, failures), as the engine lowers it again after a call."""
    n = 5
    scens = [T.Scenario.make("fcfs", "easy", node_fail_rate=1e-4),
             T.Scenario.make("sjf", "first-fit")]
    out = eng.simulate_sweep(small_system, small_table, scens, 0.0,
                             n * small_system.dt,
                             signals=gsig.neutral(n), events=EventConfig())
    jax.block_until_ready(out)
    text = eng.last_runner_hlo()
    assert text is not None
    return text


def _scan_body(text):
    return [i for i in phases.instructions(text)
            if "/while/body/" in i.op_name and i.opcode not in FREE]


def test_every_phase_the_step_runs_is_named(sweep_hlo):
    found = {i.phase for i in _scan_body(sweep_hlo)}
    assert set(phases.PHASES) <= found


def test_other_holds_under_a_tenth_of_the_scan_body(sweep_hlo):
    body = _scan_body(sweep_hlo)
    other = [i for i in body if i.phase == phases.OTHER]
    assert len(other) < 0.1 * len(body), sorted(
        (i.opcode, i.op_name) for i in other)


def test_containers_are_not_counted_twice(sweep_hlo):
    ins = phases.instructions(sweep_hlo)
    loop = next(i for i in ins if i.opcode == "while"
                and i.phase == phases.ADMISSION)
    inside = next(i for i in ins if i.container == loop.name
                  and i.opcode not in FREE | phases.CONTAINERS)
    assert inside.phase == phases.ADMISSION
    op_s = {f"%{loop.name} = (s32[]) while((s32[]))": 5.0,
            f"%{inside.name} = f32[8] {inside.opcode}(f32[8])": 1.0,
            # an operation of another program: same name, other opcode
            f"%{inside.name} = f32[8] sort(f32[8])": 7.0}
    # the loop's 5 s hold its body's 1 s: 5 s in all, not 6
    assert phases.phase_seconds(op_s, sweep_hlo) == {phases.ADMISSION: 5.0}
    # the scan over steps holds everything: its own time counts nowhere
    scan = next(i for i in ins if i.opcode == "while" and i.depth == 0)
    assert phases.phase_seconds(
        {f"%{scan.name} = (s32[]) while((s32[]))": 9.0}, sweep_hlo) == {}


def test_runner_text_follows_the_last_call(small_system, small_table,
                                           sweep_hlo):
    eng.simulate_static(small_system, small_table, "replay", "none", 0.0,
                        3 * small_system.dt)
    text = eng.last_runner_hlo()
    assert text != sweep_hlo
    body = _scan_body(text)
    assert {phases.PREPARE, phases.ADMISSION, phases.COOLING} <= \
        {i.phase for i in body}
    # the static runner has no failure layer
    assert phases.FAILURES not in {i.phase for i in body}


def test_phase_of_takes_the_innermost_phase():
    assert phases.phase_of("jit(f)/engine.queue_order/engine.admission/x") \
        == phases.ADMISSION
    assert phases.phase_of("jit(f)/while/body/engine.nope/add") == \
        phases.OTHER
    np.testing.assert_equal(phases.opcode("(s32[], f32[8]{0}) while(%t)"),
                            "while")
