"""Share of device busy time spent in HLO ``sort`` operations (the
scheduler's queue-order lexsort and EASY's release-profile argsort)."""


def read(ctx):
    devs = ctx["trace"]["devices"]
    busy = sum(d["busy_s"] for d in devs)
    if busy <= 0:
        return None
    return sum(d["opcode_s"].get("sort", 0.0) for d in devs) / busy
