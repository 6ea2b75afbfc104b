"""Share of a study's wall time spent building its inputs on the host:
the scenarios (``engine.scenarios``) and the initial carry
(``engine.init_state``) of the last study the program's in-memory span
recorder holds, over the traced study's time from dispatch to its last
summary (``ctx["cycle_s"]``, host clock).

The last study is everything from the end of the engine call before the
last one (``engine.dispatch``) to the end of the last one: the scenarios
built before the call and those it builds."""

INPUTS = ("engine.scenarios", "engine.init_state")


def read(ctx):
    try:
        from repro.obs import timing
        spans = timing.RECENT.spans()
    except (ImportError, AttributeError):
        return None                     # a program that records no spans
    ends = [sp.t_start + sp.dur_s for sp in spans
            if sp.name == "engine.dispatch"]
    if not ends or ctx["cycle_s"] <= 0:
        return None
    lo = max(ends[:-1], default=float("-inf"))
    hi = ends[-1]
    s = sum(sp.dur_s for sp in spans
            if sp.name in INPUTS
            and lo <= sp.t_start and sp.t_start + sp.dur_s <= hi)
    return s / ctx["cycle_s"]
