"""Observability layer (flight recorder) for the digital twin.

* ``obs.schema``   — schema-versioned manifest + NDJSON frame formats;
* ``obs.recorder`` — per-run manifest writer + lifecycle event log;
* ``obs.sink``     — StepRecord telemetry → NDJSON metrics stream
  (file or socket, PR 5 transport framing);
* ``obs.timing``   — program spans, always recorded in memory and on the
  profiler's clock (and on an installed ``SpanTimer`` for the manifest),
  compile counters, the engine's trace-time release and placement
  counters, and the bridge's latency histogram;
* ``obs.phases``   — the engine step's phase names and the device time
  each one takes in a compiled runner;
* ``obs.reporter`` — logging-based CLI output (progress → stderr,
  results → stdout, ``--quiet`` / ``--json``).

The manifest, streams and reporter are opt-in; spans cost a few
microseconds each and nothing on the device. See
``docs/observability.md`` for the formats and workflows.
"""
from repro.obs import phases, schema, timing    # noqa: F401
from repro.obs.recorder import RunRecorder, build_manifest, load_manifest  # noqa: F401
from repro.obs.reporter import Reporter, add_output_flags, get_logger  # noqa: F401
from repro.obs.sink import MetricsSink, history_frames, read_frames, stream_history  # noqa: F401
from repro.obs.timing import LatencyHistogram, SpanTimer, current, span, use  # noqa: F401
