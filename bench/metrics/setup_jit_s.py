"""Seconds the process spent making compiled programs, as the program
counts them (`repro.obs`, ``jit_s``): tracing, lowering to MLIR, backend
compiles and persistent-cache loads, each counted once where they nest.
A run's window compiles nothing (the harness prints that count), so this
is set-up's share of `setup_s`.  None where the program keeps no such
counter."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    jit_s = obs.snapshot().get("jit_s")
    return None if jit_s is None else sum(jit_s.values())
