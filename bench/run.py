"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload credit_lr_k2.ladder --seed 7 \\
        --seconds 30 --trace 0

Run from the root of a checkout: it reads ``BENCHMARK.json`` there and
the program under ``src``.  Exits non-zero and prints no result when JAX
finds no TPU, or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench/run.py: no program under src/repro in this checkout",
              file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    try:
        harness.run(ROOT, args.workload, args.seed, args.seconds,
                    bool(args.trace), t_start=T_START)
    except harness.RunError as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
