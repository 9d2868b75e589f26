"""Chip benchmark of EFMVFL training: one cell per run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

`harness` drives Algorithm 1 over the in-process party runtime; the rest of
this package is the yardstick a change to the program cannot move: data
generators (`data`), the float64 reference (`reference`), product counts
(`counts`), the trace reduction (`traces`) and the peaks (`peaks.json`).
Configurations, traffic mixes and per-layer metric readers are files of
their own under `configs/`, `traffic/` and `metrics/`, found by name.
"""
