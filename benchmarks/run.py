"""Benchmark harness — one entry per paper table/figure plus kernel and
roofline reports.  Prints ``name,us_per_call,derived`` CSV lines.

The kernels bench additionally writes ``BENCH_crypto.json`` at the repo
root (per-kernel µs, analytic Montgomery-product counts, backend, jax
metadata) — the machine-readable perf trajectory; commit it so speedups
and regressions accumulate in history.

  PYTHONPATH=src python -m benchmarks.run [--paper] [--only table1_lr]
      [--smoke]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH_CRYPTO_PATH = REPO_ROOT / "BENCH_crypto.json"
BENCH_WIRE_PATH = REPO_ROOT / "BENCH_wire.json"
BENCH_CHECKPOINT_PATH = REPO_ROOT / "BENCH_checkpoint.json"
BENCH_WAN_PATH = REPO_ROOT / "BENCH_wan.json"
BENCH_SERVE_PATH = REPO_ROOT / "BENCH_serve.json"


def _csv(name: str, us: float, derived: str = "") -> None:
    print(f"{name},{us:.1f},{derived}")


def bench_table1(paper_scale: bool) -> None:
    from benchmarks import table1_lr
    t0 = time.perf_counter()
    rows = table1_lr.run(paper_scale)
    total = (time.perf_counter() - t0) * 1e6
    for r in rows:
        ref = r.pop("paper_ref")
        _csv(f"table1.{r['framework']}", r["runtime_s"] * 1e6,
             f"auc={r['auc']};ks={r['ks']};comm_mb={r['comm_mb']};"
             f"paper_auc={ref[0]};paper_comm_mb={ref[2]}")
    _csv("table1.total", total)


def bench_table2(paper_scale: bool) -> None:
    from benchmarks import table2_pr
    rows = table2_pr.run(paper_scale)
    for r in rows:
        ref = r.pop("paper_ref")
        _csv(f"table2.{r['framework']}", r["runtime_s"] * 1e6,
             f"mae={r['mae']};rmse={r['rmse']};comm_mb={r['comm_mb']};"
             f"paper_mae={ref[0]};paper_comm_mb={ref[2]}")


def bench_fig1(_: bool) -> None:
    from benchmarks import fig1_losses
    curves = fig1_losses.run()
    for glm, c in curves.items():
        gap = max(abs(a - b) for a, b in zip(c["efmvfl"], c["centralized"]))
        _csv(f"fig1.{glm}", 0.0,
             f"iters={len(c['efmvfl'])};max_gap_vs_centralized={gap:.4f}")
        print(f"# fig1.{glm}.efmvfl="
              + ";".join(f"{v:.4f}" for v in c["efmvfl"]))
        print(f"# fig1.{glm}.tp="
              + ";".join(f"{v:.4f}" for v in c["tp"]))


def bench_fig2(paper: bool) -> None:
    """k-scaling rows; full measurement + BENCH_scaling.json come from
    `python -m benchmarks.fig2_scaling` (or --paper here)."""
    from benchmarks import fig2_scaling
    if paper:
        report = fig2_scaling.run()
        print(f"# wrote {fig2_scaling.write_report(report)}")
    else:
        report = fig2_scaling.run(ks=(2, 4, 8), glms=("logistic",),
                                  iters=3, batch=128, n_samples=1000,
                                  smoke=True)
    for r in report["rows"]:
        _csv(f"fig2.{r['glm']}.k{r['parties']}.{r['transport']}",
             r["per_iter_s"] * 1e6,
             f"comm_mb={r['comm_mb']};he={r['he_backend']}")
    for f in report["linear_fits"]:
        _csv(f"fig2.linear_fit.{f['glm']}", 0.0,
             f"slope_mb_per_party={f['slope_mb_per_party']};"
             f"max_residual_mb={f['max_residual_mb']}")


def check_committed_guards() -> None:
    """Re-validate the guard rows of the committed BENCH_crypto.json and
    BENCH_serve.json (structure + ratios), without re-measuring.  Exits
    non-zero on any violation so CI fails if a regressing measurement is
    committed."""
    from benchmarks import kernel_bench, serve_bench
    report = json.loads(BENCH_CRYPTO_PATH.read_text())
    rows = report["kernels"]
    guarded = [r["name"] for r in rows if r.get("guard_vs")]
    if not guarded:
        raise SystemExit(f"{BENCH_CRYPTO_PATH.name}: no guard rows found "
                         "— regenerate with python -m benchmarks.run "
                         "--only kernels")
    failures = kernel_bench.check_guards(rows)
    if failures:
        raise SystemExit(f"{BENCH_CRYPTO_PATH.name} guard violations:\n  "
                         + "\n  ".join(failures))
    print(f"# {BENCH_CRYPTO_PATH.name}: {len(guarded)} guard rows ok "
          f"({', '.join(guarded)})")
    serve_report = json.loads(BENCH_SERVE_PATH.read_text())
    srows = serve_report["rows"]
    sguarded = [r["name"] for r in srows
                if r.get("guard_vs") or "wire_ok" in r]
    if not sguarded:
        raise SystemExit(f"{BENCH_SERVE_PATH.name}: no guard rows found "
                         "— regenerate with python -m benchmarks.run "
                         "--only serve")
    failures = serve_bench.check_guards(srows)
    if failures:
        raise SystemExit(f"{BENCH_SERVE_PATH.name} guard violations:\n  "
                         + "\n  ".join(failures))
    print(f"# {BENCH_SERVE_PATH.name}: {len(sguarded)} guard rows ok "
          f"({', '.join(sguarded)})")


def bench_kernels(_: bool, smoke: bool = False) -> None:
    import jax

    from benchmarks import kernel_bench
    from repro.crypto import engine as engine_mod
    rows = kernel_bench.run(smoke=smoke)
    for r in rows:
        _csv(f"kernel.{r['name']}", r["us"], r["derived"])
    failures = kernel_bench.check_guards(rows)
    if failures:
        # SystemExit (not Exception) so main()'s report-and-continue
        # wrapper does NOT swallow it — the CI smoke run must go red
        raise SystemExit("kernel guard violations (engine-routed "
                         "interpret mode slower than the library):\n  "
                         + "\n  ".join(failures))
    if smoke:
        # drift check only — never clobber the committed full-measurement
        # perf trajectory with tiny smoke numbers
        print(f"# smoke mode: {BENCH_CRYPTO_PATH.name} not written")
        return
    report = {
        "schema": "bench_crypto/v1",
        "jax": jax.__version__,
        "jax_backend": jax.default_backend(),
        "default_crypto_engine": engine_mod.resolve_backend(),
        "kernels": [
            {k: v for k, v in r.items()} for r in rows
        ],
    }
    BENCH_CRYPTO_PATH.write_text(json.dumps(report, indent=1) + "\n")
    print(f"# wrote {BENCH_CRYPTO_PATH}")


def bench_wire(_: bool, smoke: bool = False) -> None:
    """Codec throughput (encode/decode of the training frame classes);
    full mode writes BENCH_wire.json."""
    import jax

    from benchmarks import wire_bench
    rows = wire_bench.run(smoke=smoke)
    for r in rows:
        _csv(r["name"], r["us"], r["derived"])
    if smoke:
        print(f"# smoke mode: {BENCH_WIRE_PATH.name} not written")
        return
    report = {
        "schema": "bench_wire/v1",
        "jax": jax.__version__,
        "jax_backend": jax.default_backend(),
        "rows": [{k: v for k, v in r.items() if k != "derived"}
                 for r in rows],
    }
    BENCH_WIRE_PATH.write_text(json.dumps(report, indent=1) + "\n")
    print(f"# wrote {BENCH_WIRE_PATH}")


def bench_checkpoint(_: bool, smoke: bool = False) -> None:
    """TrainState save/restore latency + bytes vs k and key size; full
    mode writes BENCH_checkpoint.json."""
    import jax

    from benchmarks import checkpoint_bench
    rows = checkpoint_bench.run(smoke=smoke)
    for r in rows:
        _csv(r["name"], r["us"], r["derived"])
    if smoke:
        print(f"# smoke mode: {BENCH_CHECKPOINT_PATH.name} not written")
        return
    report = {
        "schema": "bench_checkpoint/v1",
        "jax": jax.__version__,
        "rows": [{k: v for k, v in r.items() if k != "derived"}
                 for r in rows],
    }
    BENCH_CHECKPOINT_PATH.write_text(json.dumps(report, indent=1) + "\n")
    print(f"# wrote {BENCH_CHECKPOINT_PATH}")


def bench_wan(_: bool, smoke: bool = False) -> None:
    """Measured socket training under shaped WAN profiles vs the
    analytic rounds × RTT model; full mode writes BENCH_wan.json."""
    from benchmarks import wan_bench
    report = wan_bench.run(smoke=smoke)
    for r in report["rows"]:
        _csv(r["name"], r["us"], r["derived"])
    if smoke:
        print(f"# smoke mode: {BENCH_WAN_PATH.name} not written")
        return
    print(f"# wrote {wan_bench.write_report(report)}")


def bench_serve(_: bool, smoke: bool = False) -> None:
    """Secure scoring service: p50/p99 latency + throughput vs batch
    size x k x crypto backend; full mode writes BENCH_serve.json."""
    import jax

    from benchmarks import serve_bench
    rows = serve_bench.run(smoke=smoke)
    for r in rows:
        _csv(r["name"], r["us"], r["derived"])
    failures = serve_bench.check_guards(rows)
    if failures:
        # SystemExit so the CI smoke gate goes red (see bench_kernels)
        raise SystemExit("serve guard violations:\n  "
                         + "\n  ".join(failures))
    if smoke:
        print(f"# smoke mode: {BENCH_SERVE_PATH.name} not written")
        return
    report = {
        "schema": "bench_serve/v1",
        "jax": jax.__version__,
        "rows": [{k: v for k, v in r.items() if k not in ("us", "derived")}
                 for r in rows],
    }
    BENCH_SERVE_PATH.write_text(json.dumps(report, indent=1) + "\n")
    print(f"# wrote {BENCH_SERVE_PATH}")


def bench_roofline(_: bool) -> None:
    from benchmarks import roofline
    rows = roofline.run()
    if not rows:
        print("# roofline: no dry-run results found "
              "(run python -m repro.launch.dryrun --all first)")
        return
    for r in rows:
        if r["status"] != "ok":
            _csv(f"roofline.{r['arch']}.{r['cell']}.{r['mesh']}", 0.0,
                 f"FAIL:{r['error']}")
            continue
        _csv(f"roofline.{r['arch']}.{r['cell']}.{r['mesh']}",
             max(r["compute_ms"], r["memory_ms"], r["collective_ms"]) * 1e3,
             f"dom={r['dominant']};frac={r['roofline_frac']};"
             f"useful={r['useful_flops_ratio']};peak_gib={r['peak_gib']}")


BENCHES = {
    "table1_lr": bench_table1,
    "table2_pr": bench_table2,
    "fig1_losses": bench_fig1,
    "fig2_scaling": bench_fig2,
    "kernels": bench_kernels,
    "wire": bench_wire,
    "checkpoint": bench_checkpoint,
    "wan": bench_wan,
    "serve": bench_serve,
    "roofline": bench_roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper", action="store_true",
                    help="full paper-scale configurations (slow on CPU)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes (CI kernel-drift check; kernels only)")
    ap.add_argument("--only", default=None, choices=list(BENCHES))
    ap.add_argument("--guards", action="store_true",
                    help="validate the committed BENCH_crypto.json guard "
                         "rows and exit (no measurement)")
    args = ap.parse_args()
    if args.guards:
        check_committed_guards()
        return
    from repro.launch import compile_cache
    compile_cache.enable()
    print("name,us_per_call,derived")
    failed = []
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        try:
            if name in ("kernels", "wire", "checkpoint", "wan", "serve"):
                fn(args.paper, smoke=args.smoke)
            else:
                fn(args.paper)
        except Exception as e:   # noqa: BLE001 — report, run the rest
            _csv(f"{name}.ERROR", 0.0, f"{type(e).__name__}:{e}")
            import traceback
            traceback.print_exc(file=sys.stderr)
            failed.append(name)
    if failed:
        raise SystemExit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
