"""The trace reducer and the per-layer readers on a hand-made trace.

The fixture (``fixtures/trace_two_devices.pbtxt``) is a synthetic XSpace
in the layout of a TPU trace, with intervals that can be counted by hand
in the [0, 10) us window. TPU 0 runs two scans, [0, 4) and [6, 10) us
within the window, of two iterations each (a 1 us fusion, then a 0.5 us
sort), a short program's 0.5 us op at [4.5, 5) us between them, and
three ops that start once in the scans, under the others or past the
window's end. TPU 1 is busy throughout and records no program.
"""
import pathlib

import pytest

import bench_small  # noqa: F401  (puts bench/ on sys.path)
import run
import trace_reduce as tr

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / \
    "trace_two_devices.pbtxt"


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    profile = ProfileData.from_text_proto(FIXTURE.read_text())
    spans = tr.host_spans(profile)
    (lo, hi), = [(s, e) for name, s, e in spans if name == "bench.window"]
    return tr.reduce(profile, lo, hi, long_module_ns=1000), spans


def test_busy_union_and_window(reduced):
    red, _ = reduced
    assert red["window_s"] == pytest.approx(10e-6)
    d0, d1 = red["devices"]
    assert (d0["name"], d1["name"]) == ("/device:TPU:0", "/device:TPU:1")
    # [0, 1.5) + [2, 3.5) + [4.5, 5) + [6, 7.5) + [8, 10) us
    assert d0["busy_s"] == pytest.approx(7e-6)
    assert d1["busy_s"] == pytest.approx(10e-6)
    assert d0["long_module_s"] == pytest.approx(8e-6)
    assert d0["long_module_busy_s"] == pytest.approx(6.5e-6)
    assert d1["long_module_s"] == 0.0


def test_scan_steps_and_boundary(reduced):
    red, _ = reduced
    d0, d1 = red["devices"]
    # the fusion and the sort start 4 times in the scans; three ops start
    # once, which would be the most common count if they were not left out
    assert d0["scan_steps"] == 4
    assert d0["opcode_s"]["copy-start"] == pytest.approx(0.1e-6)
    assert d0["boundary_s"] == pytest.approx(2e-6)          # [4, 6) us
    assert d0["boundary_busy_s"] == pytest.approx(0.5e-6)
    assert d1["scan_steps"] is None and d1["boundary_s"] is None


def test_opcodes_and_gaps(reduced):
    red, spans = reduced
    d0 = red["devices"][0]
    assert d0["opcode_s"]["sort"] == pytest.approx(2e-6)
    assert d0["opcode_s"]["fusion"] == pytest.approx(4e-6)
    assert d0["opcode_s"]["while"] == pytest.approx(0.5e-6)
    label = "%sort.73 = (f32[16,1238], s32[16,1238]) sort(f32[16,1238], " \
        "s32[16,1238])"
    assert d0["op_s"][label] == pytest.approx(2e-6)
    assert tr.op_label("%fusion.250 = pred[153600]{0:T(1024)} fusion("
                       "pred[16,1238]{1,0} %get-tuple-element.4869), "
                       "kind=kCustom, calls=%fused_computation.15") == \
        "%fusion.250 = pred[153600] fusion(pred[16,1238])"
    assert tr.opcode("%while.89 = (s32[]{:T(128)}) while((s32[]) %t)") \
        == "while"
    named = tr.name_gaps(d0["gaps"], spans)
    assert [n for n, _ in named] == ["bench.wait", "bench.collect",
                                     "bench.wait", "bench.dispatch"]
    assert [s for _, s in named] == pytest.approx([1e-6, 1e-6, 0.5e-6,
                                                   0.5e-6])


# device 0 alone: n_steps = 1000 iterations a study, 2 rows on the device
ON_DEVICE_0 = [
    ("device_us_per_step", 6.5e-6 / (4 * 2) * 1e6),
    # idle: boundary 2 - 0.5 us, scans (8 - 6.5) us per 4 iterations
    ("device_idle_share", (1.5 + 1.5 * 1000 / 4) / (2 + 8 * 1000 / 4)),
    ("sort_share", 2e-6 / 7e-6),
    ("host_summary_share", 1.0 / 4.0),
]


@pytest.mark.parametrize("name,want", ON_DEVICE_0)
def test_readers(reduced, name, want):
    red, spans = reduced
    trace = dict(red, devices=red["devices"][:1])
    ctx = dict(trace=trace, spans=spans, ready_s=3.0, cycle_s=4.0,
               rows_per_device=2, n_steps=1000)
    assert run.metric_reader(name)(ctx) == pytest.approx(want)


def test_device_readers_need_a_scan_on_every_device(reduced):
    red, spans = reduced
    ctx = dict(trace=red, spans=spans, ready_s=3.0, cycle_s=4.0,
               rows_per_device=2, n_steps=1000)
    assert run.metric_reader("device_us_per_step")(ctx) is None
    assert run.metric_reader("device_idle_share")(ctx) is None
    assert run.metric_reader("sort_share")(ctx) == pytest.approx(2 / 17)


def test_readers_without_a_device_return_nothing():
    ctx = dict(trace={"window_s": 1.0, "devices": []}, spans=[],
               ready_s=1.0, cycle_s=1.1, rows_per_device=1, n_steps=10)
    for name in ("device_idle_share", "device_us_per_step", "sort_share"):
        assert run.metric_reader(name)(ctx) is None
