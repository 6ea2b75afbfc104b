"""Device time of the engine step's first phase per scenario-step (us):
completions, node release, the account fold and arrivals (the phase
``engine.prepare`` names), on the base of ``device_us_per_step``."""
import phase_time


def read(ctx):
    return phase_time.us_per_step(ctx, ["engine.prepare"])
