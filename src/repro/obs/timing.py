"""Program spans, compile counters and latency histograms (the flight
recorder's clock).

``span(name)`` is the one way the program times a phase. Every span

* opens a ``jax.profiler.TraceAnnotation`` of the same name, so it lands
  on the host lines of any profiler capture (``simulate --profile``, a
  benchmark trace) on the same clock as the device's operations;
* records into ``RECENT``, a process-wide, lock-protected, bounded
  in-memory ``SpanRecorder`` (spans from every thread, worker threads
  included), with the name of the span it opened inside;
* records into the thread's installed ``SpanTimer`` (``use(timer)``),
  when there is one, which aggregates spans into the run manifest
  (``repro.obs.recorder``).

Installing a timer changes what is recorded, never the code path: the
engine runs the same cached runner either way.

One ``jax.monitoring`` listener, registered when this module is first
imported, turns JAX's own compile events into ``engine.lower`` (trace
plus lowering to MLIR) and ``engine.compile`` (backend compile, or a
load from the persistent cache) spans, and counts them in
``COMPILE_STATS``. Those two spans carry no profiler annotation: JAX
reports them when they have ended.

``LatencyHistogram`` is the fixed-bucket (log-spaced) histogram behind
the external bridge's per-poll latency counters
(``core.external.SchedulerBridge``).

All durations in seconds.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax


@dataclass
class Span:
    """One completed (or in-flight) timed phase. ``parent`` is the name
    of the span that was open on the same thread when this one opened."""
    name: str
    t_start: float                 # clock() at entry (s)
    dur_s: float = 0.0             # filled at exit
    meta: dict = field(default_factory=dict)
    parent: Optional[str] = None

    def as_dict(self) -> dict:
        d = {"name": self.name, "t_start": self.t_start,
             "dur_s": self.dur_s}
        if self.meta:
            d["meta"] = dict(self.meta)
        if self.parent is not None:
            d["parent"] = self.parent
        return d


class SpanTimer:
    """Collects named wall-clock spans and event counters.

    ``clock`` is injectable for deterministic tests/doctests (any
    zero-arg callable returning seconds). ``listener`` (optional) is
    called with an event dict at every span start/end — the hook the run
    recorder uses to mirror phase boundaries into the NDJSON event log.
    """

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 listener: Optional[Callable[[str, dict], None]] = None):
        self.clock = clock
        self.listener = listener
        self.spans: List[Span] = []
        self.counts: Dict[str, int] = {}

    @contextlib.contextmanager
    def span(self, name: str, **meta):
        """Time a phase on this timer alone; the span is recorded even if
        the body raises. The program's spans go through the module-level
        ``span``, which calls this for the installed timer."""
        sp = Span(name=name, t_start=self.clock(), meta=meta,
                  parent=_open_name())
        if self.listener is not None:
            self.listener("span_start", {"span": name, **meta})
        try:
            yield sp
        finally:
            sp.dur_s = self.clock() - sp.t_start
            self.add(sp)

    def add(self, sp: Span) -> None:
        """Record a span that has ended (and tell the listener)."""
        self.spans.append(sp)
        if self.listener is not None:
            self.listener("span_end",
                          {"span": sp.name, "dur_s": sp.dur_s, **sp.meta})

    def count(self, name: str, n: int = 1) -> None:
        """Bump a named event counter (e.g. a cache hit)."""
        self.counts[name] = self.counts.get(name, 0) + n

    def summary(self) -> dict:
        """Aggregate spans by name: {name: {count, total_s, max_s}} plus
        the raw event counters — the shape the manifest embeds."""
        agg: Dict[str, dict] = {}
        for sp in self.spans:
            a = agg.setdefault(sp.name,
                               {"count": 0, "total_s": 0.0, "max_s": 0.0})
            a["count"] += 1
            a["total_s"] += sp.dur_s
            a["max_s"] = max(a["max_s"], sp.dur_s)
        return {"spans": agg, "counters": dict(self.counts)}


class SpanRecorder:
    """The last ``limit`` spans of every thread, oldest first."""

    def __init__(self, limit: int = 4096):
        self._lock = threading.Lock()
        self._spans: collections.deque = collections.deque(maxlen=limit)

    def add(self, sp: Span) -> None:
        with self._lock:
            self._spans.append(sp)

    def spans(self) -> List[Span]:
        """A copy of the recorded spans, in the order they ended."""
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()


RECENT = SpanRecorder()


# ---------------------------------------------------------------------------
# Active-timer registry and the program's spans.
# ---------------------------------------------------------------------------
_local = threading.local()


def current() -> Optional[SpanTimer]:
    """The timer installed by the innermost ``use()`` block, or None."""
    return getattr(_local, "timer", None)


@contextlib.contextmanager
def use(timer: SpanTimer):
    """Install ``timer`` as the active span timer for this thread."""
    prev = current()
    _local.timer = timer
    try:
        yield timer
    finally:
        _local.timer = prev


def _open_stack() -> list:
    stack = getattr(_local, "open", None)
    if stack is None:
        stack = _local.open = []
    return stack


def _open_name() -> Optional[str]:
    stack = _open_stack()
    return stack[-1] if stack else None


@contextlib.contextmanager
def span(name: str, **meta):
    """Time a phase: a profiler annotation, a span in ``RECENT`` and, when
    a timer is installed on this thread, a span on it (yielded; None
    otherwise). Recorded even if the body raises."""
    timer = current()
    sp = Span(name=name, t_start=time.perf_counter(), meta=meta,
              parent=_open_name())
    timed = (contextlib.nullcontext() if timer is None
             else timer.span(name, **meta))
    stack = _open_stack()
    try:
        with timed as tsp, jax.profiler.TraceAnnotation(name):
            stack.append(name)
            try:
                yield tsp
            finally:
                stack.pop()
    finally:
        sp.dur_s = time.perf_counter() - sp.t_start
        RECENT.add(sp)


# ---------------------------------------------------------------------------
# Compile events.
# ---------------------------------------------------------------------------
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# Process totals: executables built or loaded from the persistent cache,
# and the seconds spent building them.
COMPILE_STATS = {"compiles": 0, "compile_s": 0.0, "lower_s": 0.0}
_stats_lock = threading.Lock()


def _record_done(name: str, start_unix: float, end_unix: float) -> None:
    """Record a span JAX reports after it ended (times on ``time.time``)."""
    now = time.perf_counter()
    sp = Span(name=name, t_start=now - (time.time() - start_unix),
              dur_s=end_unix - start_unix, parent=_open_name())
    RECENT.add(sp)
    timer = current()
    if timer is not None:
        timer.add(sp)


def _on_compile_event(event: str, start: float, end: float, **_) -> None:
    """``engine.lower`` runs from the start of the outermost trace that
    precedes a lowering on this thread to the end of the lowering;
    ``engine.compile`` is the backend's compile."""
    if event == TRACE_EVENT:
        last = getattr(_local, "trace", None)
        # traces nest (a jitted function traces what it calls): keep the
        # outermost, and drop one that ended before this one began
        _local.trace = ((start, end) if last is None or start > last[1]
                        else (min(last[0], start), max(last[1], end)))
    elif event == LOWER_EVENT:
        last = getattr(_local, "trace", None)
        _local.trace = None
        if last is not None:
            start = min(start, last[0])
        with _stats_lock:
            COMPILE_STATS["lower_s"] += end - start
        _record_done("engine.lower", start, end)
    elif event == COMPILE_EVENT:
        with _stats_lock:
            COMPILE_STATS["compiles"] += 1
            COMPILE_STATS["compile_s"] += end - start
        _record_done("engine.compile", start, end)


jax.monitoring.register_event_time_span_listener(_on_compile_event)


# ---------------------------------------------------------------------------
# Latency histogram (bridge poll counters).
# ---------------------------------------------------------------------------
class LatencyHistogram:
    """Fixed log-spaced latency histogram: 100 µs .. 100 s + overflow.

    Monotonic counters only (record / merge); ``summary()`` is JSON-able
    so the external bridge can surface its per-poll latency distribution
    in the run manifest and in ``fig7_external`` rows.
    """

    EDGES = (1e-4, 1e-3, 1e-2, 1e-1, 1.0, 10.0, 100.0)  # upper edges (s)

    def __init__(self):
        self.counts = [0] * (len(self.EDGES) + 1)  # last = overflow
        self.n = 0
        self.total_s = 0.0
        self.min_s = float("inf")
        self.max_s = 0.0

    def record(self, dur_s: float) -> None:
        self.n += 1
        self.total_s += dur_s
        self.min_s = min(self.min_s, dur_s)
        self.max_s = max(self.max_s, dur_s)
        for i, edge in enumerate(self.EDGES):
            if dur_s <= edge:
                self.counts[i] += 1
                return
        self.counts[-1] += 1

    def summary(self) -> dict:
        buckets = {f"le_{e:g}s": c for e, c in zip(self.EDGES, self.counts)}
        buckets["overflow"] = self.counts[-1]
        return {"count": self.n, "total_s": self.total_s,
                "min_s": self.min_s if self.n else 0.0,
                "max_s": self.max_s, "buckets": buckets}
