"""Device time per engine phase, for the per-layer readers of
``metrics/``: the trace's per-operation seconds (``ctx["trace"]``) laid
over the phases the program's compiled runner names
(``repro.obs.phases``, the engine's ``last_runner_hlo``).

A program that names no phases gives nothing: every reader then returns
None, and the result line leaves its metric out."""
from __future__ import annotations

import sys
import traceback

_LAST = []          # [(trace, seconds per phase)] of the last trace read


def phase_seconds(ctx) -> dict | None:
    """Seconds per phase summed over the traced devices, or None."""
    trace = ctx["trace"]
    if _LAST and _LAST[0][0] is trace:
        return _LAST[0][1]
    try:
        from repro.core import engine
        from repro.obs import phases
        text = engine.last_runner_hlo()
    except (ImportError, AttributeError):
        return None                     # a program without phases
    except Exception:                   # noqa: BLE001 - the run goes on
        traceback.print_exc(file=sys.stderr)
        return None
    devs = trace["devices"]
    if text is None or not devs:
        return None
    out = {}
    for d in devs:
        for name, s in phases.phase_seconds(d["op_s"], text).items():
            out[name] = out.get(name, 0.0) + s
    busy = sum(d["long_module_busy_s"] for d in devs)
    named = sum(s for name, s in out.items() if name != phases.OTHER)
    print(f"phases: seconds {sorted(out.items())}; scans busy {busy!r} s, "
          f"share in phases {named / busy if busy else None!r}",
          file=sys.stderr, flush=True)
    _LAST[:] = [(trace, out)]
    return out


def us_per_step(ctx, names) -> float | None:
    """Device time of the phases ``names`` per scenario-step (us), on the
    base of ``device_us_per_step``: the scan iterations counted in the
    trace times the rows each device carries."""
    devs = ctx["trace"]["devices"]
    if not devs or any(not d["scan_steps"] for d in devs):
        return None
    per = phase_seconds(ctx)
    if per is None:
        return None
    steps = sum(d["scan_steps"] for d in devs) * ctx["rows_per_device"]
    return sum(per.get(n, 0.0) for n in names) / steps * 1e6
