"""The per-phase readers (``metrics/prepare_us_per_step.py``,
``admission_us_per_step``, ``tick_us_per_step``, ``input_build_share``)
on a hand-made context: a runner's HLO text in which each instruction's
``op_name`` names its phase, per-operation device seconds keyed the way
``trace_reduce.op_label`` keys them, and spans in the program's
recorder. A program without phases or spans gives None, never an
error."""
import pytest

import bench_small  # noqa: F401  (puts bench/ and src/ on sys.path)
import run
import trace_reduce as tr
from repro.core import engine
from repro.obs import timing

HLO = """HloModule jit_fn, is_scheduled=true

%fused.1 (p.0: f32[16,1238]) -> pred[153600] {
  %p.0 = f32[16,1238]{1,0} parameter(0)
  ROOT %g.1 = pred[153600]{0} gather(%p.0), metadata={op_name="jit(fn)/while/body/closed_call/engine.prepare/gather"}
}

%adm.body (t.1: (s32[], s32[16,9600])) -> (s32[], s32[16,9600]) {
  %t.1 = (s32[], s32[16,9600]{1,0}) parameter(0)
  %gte.1 = s32[16,9600]{1,0} get-tuple-element(%t.1), index=1
  %reduce-window.44 = s32[16,75,128]{2,1,0} reduce-window(%gte.1), window={size=1x1x128}, to_apply=%sum
  ROOT %tuple.3 = (s32[], s32[16,9600]{1,0}) tuple(%gte.1, %gte.1)
}

%scan.body (t.0: (s32[], f32[16,1238])) -> (s32[], f32[16,1238]) {
  %t.0 = (s32[], f32[16,1238]{1,0}) parameter(0)
  %gte.0 = f32[16,1238]{1,0} get-tuple-element(%t.0), index=1
  %fusion.250 = pred[153600]{0:T(1024)} fusion(f32[16,1238]{1,0} %gte.0), kind=kLoop, calls=%fused.1
  %fusion.267 = f32[16,1238]{1,0} fusion(f32[16,1238]{1,0} %gte.0), kind=kLoop, calls=%f2, metadata={op_name="jit(fn)/while/body/closed_call/engine.queue_order/gather"}
  %while.89 = (s32[], s32[16,9600]{1,0}) while(%t.0), condition=%c, body=%adm.body, metadata={op_name="jit(fn)/while/body/closed_call/engine.admission/while"}
  %fusion.275 = f32[153600]{0} fusion(f32[16,1238]{1,0} %gte.0), kind=kLoop, calls=%f3, metadata={op_name="jit(fn)/while/body/closed_call/engine.power/gather"}
  %fusion.280 = f32[16]{0} fusion(f32[153600]{0} %fusion.275), kind=kLoop, calls=%f4, metadata={op_name="jit(fn)/while/body/closed_call/engine.cooling/dot_general"}
  %dynamic-update-slice.9 = f32[16,5760]{1,0} dynamic-update-slice(%gte.0), metadata={op_name="jit(fn)/engine.telemetry/while/body/dynamic_update_slice"}
  %copy.7 = f32[16,1238]{1,0} copy(%gte.0)
  ROOT %tuple.2 = (s32[], f32[16,1238]{1,0}) tuple(%gte.0, %gte.0)
}

ENTRY %main (a: f32[16,1238]) -> f32[16,1238] {
  %a = f32[16,1238]{1,0} parameter(0)
  %while.1 = (s32[], f32[16,1238]{1,0}) while(%a), condition=%c0, body=%scan.body, metadata={op_name="jit(fn)/engine.telemetry/while"}
  ROOT %gte.9 = f32[16,1238]{1,0} get-tuple-element(%while.1), index=1
}
"""

# trace event names as the device lists them: layouts, operand names,
# attributes (reduced by op_label as the harness does)
EVENTS = {
    "%fusion.250 = pred[153600]{0:T(1024)} fusion(f32[16,1238]{1,0} "
    "%gte.0), kind=kLoop, calls=%fused.1": 1.0,
    "%fusion.267 = f32[16,1238]{1,0} fusion(f32[16,1238]{1,0} %gte.0)": 0.1,
    "%while.89 = (s32[], s32[16,9600]{1,0}) while(%t.0), condition=%c": 0.9,
    "%reduce-window.44 = s32[16,75,128]{2,1,0} reduce-window(s32[16,9600]"
    "{1,0} %gte.1), window={size=1x1x128}": 0.6,
    "%fusion.275 = f32[153600]{0} fusion(f32[16,1238]{1,0} %gte.0)": 0.8,
    "%fusion.280 = f32[16]{0} fusion(f32[153600]{0} %fusion.275)": 0.2,
    "%dynamic-update-slice.9 = f32[16,5760]{1,0} dynamic-update-slice("
    "f32[16,1238]{1,0} %gte.0)": 0.05,
    "%copy.7 = f32[16,1238]{1,0} copy(f32[16,1238]{1,0} %gte.0)": 0.25,
    # an eager program's operation in the boundary
    "%broadcast.3 = f32[16] broadcast(f32[] %p)": 0.01,
}


def _ctx(steps=100, rows=16.0, busy=3.0):
    op_s = {}
    for name, s in EVENTS.items():
        key = tr.op_label(name)
        op_s[key] = op_s.get(key, 0.0) + s
    dev = dict(op_s=op_s, scan_steps=steps, long_module_busy_s=busy)
    return dict(trace=dict(devices=[dev]), rows_per_device=rows,
                n_steps=5760, cycle_s=30.0, ready_s=29.9, spans=[])


@pytest.fixture
def runner_text(monkeypatch):
    monkeypatch.setattr(engine, "last_runner_hlo", lambda: HLO)


@pytest.mark.parametrize("metric,seconds", [
    ("prepare_us_per_step", 1.0),
    # the loop's own event spans its body's: the body's time counts, and
    # what it leaves uncovered (0.9 - 0.6 s); the compiler's unnamed
    # reduce-window takes the loop's phase
    ("admission_us_per_step", 0.9),
    ("tick_us_per_step", 0.8 + 0.2 + 0.05)])
def test_phase_readers_on_a_hand_made_trace(runner_text, metric, seconds):
    value = run.metric_reader(metric)(_ctx())
    assert value == pytest.approx(seconds / (100 * 16) * 1e6)


def test_phase_readers_without_scan_steps_give_none(runner_text):
    for metric in ("prepare_us_per_step", "admission_us_per_step",
                   "tick_us_per_step"):
        assert run.metric_reader(metric)(_ctx(steps=None)) is None


def test_phase_readers_on_a_program_without_phases(monkeypatch):
    monkeypatch.delattr(engine, "last_runner_hlo")
    for metric in ("prepare_us_per_step", "admission_us_per_step",
                   "tick_us_per_step"):
        assert run.metric_reader(metric)(_ctx()) is None


def _span(name, t, dur, parent=None):
    return timing.Span(name=name, t_start=t, dur_s=dur, parent=parent)


def test_input_build_share_reads_the_last_study(monkeypatch):
    rec = timing.SpanRecorder()
    for sp in [
            # an earlier study: not counted
            _span("engine.scenarios", 0.0, 0.5),
            _span("engine.init_state", 1.0, 0.2, "engine.dispatch"),
            _span("engine.dispatch", 1.0, 0.4),
            _span("stats.summarize", 40.0, 0.1),
            # the last study: scenarios built before the call and inside
            # it, and the initial carry; compiles under them are not
            # counted twice
            _span("engine.scenarios", 50.0, 0.1),
            _span("engine.scenarios", 50.1, 0.1),
            _span("engine.compile", 50.3, 0.01, "engine.init_state"),
            _span("engine.init_state", 50.3, 0.05, "engine.dispatch"),
            _span("engine.scenarios", 50.35, 0.02, "engine.dispatch"),
            _span("engine.dispatch", 50.3, 0.2)]:
        rec.add(sp)
    monkeypatch.setattr(timing, "RECENT", rec)
    value = run.metric_reader("input_build_share")(_ctx())
    assert value == pytest.approx((0.1 + 0.1 + 0.05 + 0.02) / 30.0)


def test_input_build_share_without_spans_gives_none(monkeypatch):
    monkeypatch.setattr(timing, "RECENT", timing.SpanRecorder())
    assert run.metric_reader("input_build_share")(_ctx()) is None
    monkeypatch.delattr(timing, "RECENT")
    assert run.metric_reader("input_build_share")(_ctx()) is None
