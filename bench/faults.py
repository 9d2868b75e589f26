"""Faults planted in the program, to show that the check catches them.

Used by `calibrate.py` on the chip (the upper readings of the limits) and
by the benchmark's tests on the CPU.  Each is a context manager that
patches one place of the timed path and restores it on exit:

* ``unchanged``:      a step that leaves the weights as they were;
* ``half_batch``:     half of each batch left out, the mean taken over
                      the rest;
* ``altered_answer``: one ciphertext of each HE matvec altered where the
                      kernel produces it (one bit of its lowest limb).

The exchange between chips has no fault here: every cell runs on one
chip.
"""
from __future__ import annotations

import contextlib

FAULTS = ("unchanged", "half_batch", "altered_answer")


@contextlib.contextmanager
def planted(fault: str):
    from repro.core import protocols
    from repro.runtime import party, scheduler
    if fault == "unchanged":
        owner, attr = party.Party, "_apply_update"
        patched = lambda self: None  # noqa: E731
    elif fault == "half_batch":
        owner, attr = scheduler.VFLScheduler, "_iteration"
        orig = owner._iteration

        def patched(self, idx):
            return orig(self, idx[:len(idx) // 2])
    elif fault == "altered_answer":
        owner, attr = protocols.PaillierBackend, "matvec"
        orig = owner.matvec

        def patched(self, *a, **kw):
            out = orig(self, *a, **kw)
            return out.at[0, 0].set(out[0, 0] ^ 1)
    else:
        raise ValueError(f"unknown fault {fault!r}; choose from {FAULTS}")
    saved = owner.__dict__[attr]
    setattr(owner, attr, patched)
    try:
        yield
    finally:
        setattr(owner, attr, saved)
