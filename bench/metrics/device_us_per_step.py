"""Device busy time per scenario-step (us), from the trace alone: the busy
time inside the studies' scans in the traced window, over the scan
iterations run there times the scenario rows each device carries."""


def read(ctx):
    devs = ctx["trace"]["devices"]
    if not devs or any(not d["scan_steps"] for d in devs):
        return None
    busy = sum(d["long_module_busy_s"] for d in devs)
    steps = sum(d["scan_steps"] for d in devs) * ctx["rows_per_device"]
    return busy / steps * 1e6
