"""Device time of the end of the engine step per scenario-step (us):
job and node power, the node -> CDU -> hall sums and the cooling plant,
and the telemetry row, ledgers and energy sums (the phases
``engine.power``, ``engine.cooling`` and ``engine.telemetry``), on the
base of ``device_us_per_step``."""
import phase_time


def read(ctx):
    return phase_time.us_per_step(
        ctx, ["engine.power", "engine.cooling", "engine.telemetry"])
