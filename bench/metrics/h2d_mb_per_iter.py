"""Megabytes of host arrays the hot path turns into device arrays per
iteration, as the program counts them (`repro.obs`, ``h2d_bytes``):
every such byte since the process started, over the iterations the run
has made, set-up's and the traced window's.  A run's process makes these
transfers only inside iterations, and every iteration of a cell makes
the same ones, so this is the count of one iteration.  None where the
program keeps no such counter."""
from bench import harness


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    nbytes = obs.snapshot()["h2d_bytes"]
    return nbytes / 1e6 / (harness.SETUP_STEPS + ctx.iterations)
