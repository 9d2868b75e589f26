"""Put a cell's device idle time down to the program's own spans.

    python bench/attribute.py --workload dvisits_pr_k3.tables --seed 7

Runs one cell as `bench/run.py --trace 1` does (set-up, then the
traffic's traced iterations), and in the traced window also reads the
program's ``vfl.*`` spans (`bench.spans`) and the difference of two
`repro.obs.snapshot()`s.  Before the window it runs one iteration under
`jax.transfer_guard("log")`, so every host↔device transfer that the
program does not make explicitly is logged; those lines are counted by
message.  Prints one JSON object: the idle time per span layer and per
span name, the busy time per span name, the host's wait on the busy
device, the longest idle gaps labelled by the innermost span, the
counters per iteration and the transfer-guard summary.  Exits non-zero
with no result when JAX finds no TPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _delta(before: dict, after: dict) -> dict:
    out = {}
    for k, v in after.items():
        if isinstance(v, dict):
            out[k] = {str(kk): vv - before[k].get(kk, 0)
                      for kk, vv in v.items()
                      if vv != before[k].get(kk, 0)}
        else:
            out[k] = v - before[k]
    return out


def _guarded_step(cell, state, path: str):
    """One iteration under `jax.transfer_guard("log")`, with the
    process's stderr sent to `path` meanwhile (the guard logs there)."""
    import jax
    sys.stderr.flush()
    saved = os.dup(2)
    with open(path, "w") as f:
        os.dup2(f.fileno(), 2)
        try:
            with jax.transfer_guard("log"):
                state = cell.sched.step(state)
                jax.effects_barrier()
        finally:
            sys.stderr.flush()
            os.dup2(saved, 2)
            os.close(saved)
    counts = collections.Counter()
    with open(path) as f:
        for line in f:
            if "transfer" in line:
                msg = line.split("] ", 1)[-1].strip()
                counts[re.sub(r"0x[0-9a-f]+", "0x…", msg)] += 1
    return state, counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default="chiprun_out",
                    help="directory for the transfer-guard log")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import counts, harness, spans, traces
    try:
        spec = harness.load_spec(ROOT, args.workload)
        device = harness.device_info(True, spec.workload["chips"])
    except harness.RunError as e:
        print(f"bench/attribute.py: {e}", file=sys.stderr)
        return 3
    import jax
    from repro import obs
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    cell = harness.build_cell(spec, harness.Seeds.of(args.seed))
    states = harness.first_steps(cell, harness.SETUP_STEPS)
    state = states[max(states)]
    setup_s = time.perf_counter() - T_START
    os.makedirs(args.out, exist_ok=True)
    log = os.path.join(args.out, f"transfer_guard.{args.workload}.log")
    c0 = obs.snapshot()
    state, guard = _guarded_step(cell, state, log)
    c1 = obs.snapshot()

    n = spec.traffic["trace_iterations"]
    log_dir = tempfile.mkdtemp(prefix="attribute_trace_")
    try:
        state, wall = harness.traced(cell, state, n, log_dir)
        c2 = obs.snapshot()
        path = traces.find_xplane(log_dir)
        devices, notes, kind = traces.load(path, counts.KERNELS)
        program = spans.program_spans(path)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    win = [a for a in notes if a.name == traces.WINDOW][0]
    t0, t1 = win.start_ns, win.end_ns
    summary = traces.reduce(devices, notes, kind)
    att = spans.attribute(devices, program, t0, t1)
    by_name = spans.idle_by_span(devices, program, t0, t1)
    busy_by_name = spans.busy_by_span(devices, program, t0, t1)
    window = _delta(c1, c2)
    gc_spans = sorted((s.dur_ns * 1e-9 for s in program
                       if s.name == "vfl.runtime.gc"
                       and t0 <= s.start_ns < t1), reverse=True)
    result = {
        "workload": args.workload, "seed": args.seed, "device": device,
        "setup_s": setup_s, "iterations": n, "window_wall_s": wall,
        "window_s_per_iter": wall / n,
        "idle_share": 100.0 * summary.idle_share,
        "host_protocol_share": 100.0 * summary.host_share,
        "idle_protocol_share": 100.0 * att.share("protocol"),
        "idle_crypto_host_share": 100.0 * att.share("crypto_host"),
        "idle_gc_share": 100.0 * att.share("gc"),
        "idle_unattributed_share": 100.0 * att.share("unattributed"),
        "host_wait_share": 100.0 * att.share("host_wait"),
        "engine_calls_per_iter":
            sum(window["engine_calls"].values()) / n,
        "h2d_mb_per_iter": window["h2d_bytes"] / 1e6 / n,
        "engine_calls_per_iter_by_op":
            {k: v / n for k, v in window["engine_calls"].items()},
        "guarded_step_counters": _delta(c0, c1),
        "window_counters": window,
        "gc_spans_in_window_s": gc_spans[:10],
        "idle_s_by_span": dict(sorted(by_name.items(),
                                      key=lambda kv: -kv[1])),
        "busy_s_by_span": dict(sorted(busy_by_name.items(),
                                      key=lambda kv: -kv[1])),
        "busy_s": summary.busy_s, "window_s": summary.window_s,
        "gaps": spans.gaps(devices, notes, program, t0, t1),
        "gaps_harness_labels": summary.gaps,
        "transfer_guard": dict(guard.most_common()),
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
