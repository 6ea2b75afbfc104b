"""Device time of the admission loop and placement per scenario-step
(us): the phase ``engine.admission`` names, on the base of
``device_us_per_step``."""
import phase_time


def read(ctx):
    return phase_time.us_per_step(ctx, ["engine.admission"])
