"""Readings the check's limits are set from, for one cell, in one process.

    python bench/calibrate.py --workload credit_lr_k2.ladder \\
        --sound 12 --control 3 --faults 3 --steps 6 --first-seed 5000

Runs on the chip.  For each seed it builds the cell as a run does (the
configuration's keys are built once and shared), drives the scheduler
through `--steps` iterations and prints the comparison numbers of
`reference.compare` as one JSON line:

* sound:   the program as the configuration states it (lower readings);
* control: the program at the configuration's `control` precision
           (halved fractional bits), the step a later change might take;
* faults:  each fault of `faults.FAULTS` planted in the program.

The benchmark's own runs never run this.
"""
import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sound", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--first-seed", type=int, default=5000)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import faults, harness
    from repro.launch import compile_cache
    compile_cache.enable()
    spec = harness.load_spec(ROOT, args.workload)
    harness.device_info(True, spec.workload["chips"])
    keys = harness.party_keys(spec.config, spec.traffic["noise"])
    runs = [("sound", None, None)] * args.sound \
        + [("control", spec.config["control"], None)] * args.control \
        + [(f, None, f) for f in faults.FAULTS for _ in range(args.faults)]
    seed = args.first_seed
    for kind, overrides, fault in runs:
        seed += 1
        t0 = time.perf_counter()
        cell = harness.build_cell(spec, harness.Seeds.of(seed), keys=keys,
                                  overrides=overrides)
        with faults.planted(fault) if fault else contextlib.nullcontext():
            states = harness.first_steps(cell, args.steps)
        numbers = harness.check(
            cell, {k: states[k] for k in (*harness.COMPARED, args.steps)})
        print(json.dumps({"workload": args.workload, "kind": kind,
                          "seed": seed, "steps": args.steps, **numbers,
                          "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
