"""Share of one study's device time in which no operation ran, from the
trace alone, the mean over devices. A study is its scan of ``n_steps``
iterations and one boundary (from the end of one study's scan to the
start of the next one's: results, summaries, the next dispatch). The
traced window holds one boundary and parts of two scans, so

    idle / study = (boundary idle + scan idle per iteration x n_steps)
                   / (boundary + scan time per iteration x n_steps),

which does not depend on how much of the scans the window holds."""


def read(ctx):
    devs = ctx["trace"]["devices"]
    n = ctx["n_steps"]
    if not devs or any(not d["scan_steps"] or d["boundary_s"] is None
                       for d in devs):
        return None
    shares = []
    for d in devs:
        per = n / d["scan_steps"]
        idle = (d["boundary_s"] - d["boundary_busy_s"] +
                (d["long_module_s"] - d["long_module_busy_s"]) * per)
        shares.append(idle / (d["boundary_s"] + d["long_module_s"] * per))
    return sum(shares) / len(shares)
