"""Calls of the crypto engine's hot-path entries per iteration (on the
chip each is one RNS kernel launch and its glue), as the program counts
them (`repro.obs`, ``engine_calls``): every call since the process
started, over the iterations the run has made, set-up's and the traced
window's.  A run's process calls the engine only inside iterations, and
every iteration of a cell makes the same calls, so this is the count of
one iteration.  None where the program keeps no such counter."""
from bench import harness


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    calls = sum(obs.snapshot()["engine_calls"].values())
    return calls / (harness.SETUP_STEPS + ctx.iterations)
