"""Share of one study's wall time spent on its host side: bringing the
results to the host and summarising every row, over the time from
dispatch to the last summary (the traced study B, host clock)."""


def read(ctx):
    if ctx["cycle_s"] <= 0:
        return None
    return (ctx["cycle_s"] - ctx["ready_s"]) / ctx["cycle_s"]
