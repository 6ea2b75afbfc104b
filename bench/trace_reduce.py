"""Reduce a JAX profiler trace (``.xplane.pb``) to device-time numbers.

A device is a plane named ``/device:TPU:<n>`` (any ``/device:`` plane
other than ``CUSTOM`` ones). Its operations are the events of the line
``XLA Ops``; the line ``XLA Modules`` holds whole program executions,
which tell a study's scan from the eager operations around it. Host
spans are the benchmark's own ``jax.profiler.TraceAnnotation`` events,
found by name on any host plane.

For a window ``[lo, hi]`` (ns, on the profiler's clock) the reduction
gives per device the busy time (the union of operation intervals clipped
to the window), the time per operation name and per HLO opcode, and the
idle gaps between busy intervals, each named by the host span it falls
in. Inside the scans it also counts the scan's iterations (engine steps)
and finds the boundary between two studies: the longest stretch between
two long program executions.
"""
from __future__ import annotations

import collections
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# a program execution at least this long (within the window) is a
# study's scan, as against the eager set-up operations around it
LONG_MODULE_NS = 10e6


def opcode(name: str) -> str:
    """HLO opcode of an ``XLA Ops`` event name such as
    ``%sort.73 = (f32[16,1238]{...}, ...) sort(...), ...``."""
    _, sep, rest = name.partition(" = ")
    if not sep:
        return name.split(".")[0].lstrip("%")
    i = 0
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    else:
        i = rest.find(" ")
        i = len(rest) if i < 0 else i
    tail = rest[i:].lstrip()
    return tail.split("(", 1)[0].strip()


def op_label(name: str, width: int = 160) -> str:
    """An ``XLA Ops`` event name without layouts, operand names and
    attributes: ``%fusion.250 = pred[153600] fusion(pred[16,1238])`` of
    ``%fusion.250 = pred[153600]{0:T(1024)} fusion(pred[16,1238]{1,0}
    %get-tuple-element.4869), kind=kCustom, calls=...``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:width]
    rest = re.sub(r"\{[^{}]*\}", "", rest)
    rest = re.sub(r" ?%[\w.\-]+", "", rest)
    rest = re.split(r", [a-z_]+=", rest)[0]
    return f"{head} = {rest}"[:width]


def latest_xplane(directory: str) -> str | None:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def _merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def host_spans(profile, prefix: str = "bench.") -> list:
    """(name, start_ns, end_ns) of every host event whose name starts
    with ``prefix``."""
    spans = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return sorted(spans, key=lambda s: s[1])


def device_planes(profile) -> list:
    return sorted((p for p in profile.planes
                   if p.name.startswith("/device:")
                   and not p.name.startswith("/device:CUSTOM")),
                  key=lambda p: p.name)


def reduce(profile, lo_ns: float, hi_ns: float,
           long_module_ns: float = LONG_MODULE_NS) -> dict:
    """Device numbers of the window ``[lo_ns, hi_ns]``.

    Returns ``{"window_s", "devices": [per device: {"name", "busy_s",
    "long_module_s", "long_module_busy_s", "scan_steps", "boundary_s",
    "boundary_busy_s", "op_s": {op_label: s}, "opcode_s": {opcode: s},
    "gaps": [(start_ns, end_ns)]}]}``. ``long_module_*`` are the time
    inside program executions of at least ``long_module_ns`` (a study's
    scan) and the busy time within them; ``scan_steps`` the iterations
    those executions ran in the window (``None`` without one);
    ``boundary_*`` the longest stretch between two of them and the busy
    time within it (``None`` with fewer than two)."""
    devices = []
    for plane in device_planes(profile):
        ivals = []
        op_s = collections.Counter()
        code_s = collections.Counter()
        long_mods, ops = [], []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                s = max(ev.start_ns, lo_ns)
                e = min(ev.start_ns + ev.duration_ns, hi_ns)
                if e <= s:
                    continue
                if line.name == MODULES_LINE:
                    if e - s >= long_module_ns:
                        long_mods.append((s, e))
                    continue
                ivals.append((s, e))
                ops.append((ev.start_ns, ev.name))
                op_s[op_label(ev.name)] += (e - s) * 1e-9
                code_s[opcode(ev.name)] += (e - s) * 1e-9
        busy = _merge(ivals)
        long_mods.sort()
        edges = [lo_ns] + [x for iv in busy for x in iv] + [hi_ns]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        boundary = max(((a[1], b[0]) for a, b in zip(long_mods,
                                                      long_mods[1:])),
                       key=lambda g: g[1] - g[0], default=None)
        devices.append(dict(
            name=plane.name, busy_s=_span_s(busy),
            long_module_s=_span_s(long_mods),
            long_module_busy_s=_overlap_s(busy, long_mods),
            scan_steps=_scan_steps(ops, long_mods),
            boundary_s=_span_s([boundary]) if boundary else None,
            boundary_busy_s=(_overlap_s(busy, [boundary]) if boundary
                             else None),
            op_s=dict(op_s), opcode_s=dict(code_s), gaps=gaps))
    return dict(window_s=(hi_ns - lo_ns) * 1e-9, devices=devices)


def _span_s(intervals) -> float:
    return sum(e - s for s, e in intervals) * 1e-9


def _overlap_s(a, b) -> float:
    """Seconds in which an interval of ``a`` overlaps one of ``b``."""
    return sum(max(min(e, be) - max(s, bs), 0)
               for bs, be in b for s, e in a) * 1e-9


def _scan_steps(ops, long_mods) -> int | None:
    """Iterations of the scans in ``long_mods``: the count of events that
    most op names started inside them share, once the ops that start at
    most once a program are left out (a program's set-up around its
    loop). An op of the scan's body runs once an iteration, and those of
    inner loops a multiple of that (in a 4 s window of the 16-row
    Frontier sweep on a TPU v5e: 249 names at 707 or 708 starts, 36 at
    32 times that and 3 at 352 times, 294 at one)."""
    if not long_mods:
        return None
    per_name = collections.Counter(
        name for t, name in ops if any(s <= t < e for s, e in long_mods))
    shared = collections.Counter(n for n in per_name.values()
                                 if n > len(long_mods))
    if not shared:
        return None
    return max(shared, key=lambda n: (shared[n], n))


def name_gaps(gaps, spans, top: int = 10) -> list:
    """The ``top`` longest idle gaps as [name, seconds], each named by
    the innermost host span that holds its midpoint (``idle`` when none
    does)."""
    named = []
    for s, e in gaps:
        mid = (s + e) / 2
        inner = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        name = min(inner, key=lambda sp: sp[2] - sp[1])[0] if inner \
            else "idle"
        named.append([name, (e - s) * 1e-9])
    return sorted(named, key=lambda x: -x[1])[:top]
