"""Calls of the compiled share-ring programs per iteration (a Z_2^64 op,
the dealer's triple draw, a share split: each one launch on the chip),
as the program counts them (`repro.obs`, ``share_calls``): every call
since the process started, over the iterations the run has made,
set-up's and the traced window's.  A run's process makes these calls
only inside iterations, and every iteration of a cell makes the same
calls, so this is the count of one iteration.  None where the program
keeps no such counter."""
from bench import harness


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    calls = obs.snapshot().get("share_calls")
    if calls is None:
        return None
    return sum(calls.values()) / (harness.SETUP_STEPS + ctx.iterations)
