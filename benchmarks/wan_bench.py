"""WAN latency economics: measured socket training vs rounds × RTT.

The protocol's communication-round count (`TrainResult.rounds` — one
sequential transport latency step per round) predicts how wall-clock
scales with link latency: a shaped run should cost roughly

    base_s  +  rounds × (latency_s + jitter_s / 2)

on top of the fault-free compute.  This bench trains the same k=3 mock
run under `runtime.chaos.PROFILES` shaping (`wan20` = 20 ms one-way,
`wan100` = 100 ms — pure shaping, no faults) plus an unshaped baseline,
and reports the measured wall-clock next to that analytic model — the
deployment-economics view of docs/transports.md §WAN, and the guard
that round-count regressions show up as *seconds* at WAN latencies.

The conductor is this process; the party processes run on the host CPU
(`cluster.PARTY_PLATFORM`, recorded in the report), so the chip, if
any, has this process as its one owner.

  PYTHONPATH=src python -m benchmarks.wan_bench [--smoke]

writes BENCH_wan.json at the repo root (committed, like BENCH_crypto);
`benchmarks/run.py --only wan` prints the same rows as CSV (`--smoke`
for the CI-sized variant, which never overwrites the committed file).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import numpy as np

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH_WAN_PATH = REPO_ROOT / "BENCH_wan.json"

#: shaped-only profiles measured against the unshaped baseline
WAN_PROFILES = ("wan20", "wan100")


def _mock_run(chaos, iters: int, nb: int, k: int = 3):
    """One k-party mock-HE socket training run; returns TrainResult."""
    from repro.core.trainer import PartyData, VFLConfig
    from repro.launch.cluster import train_vfl_socket

    m = 4
    rng = np.random.default_rng(7)
    X = rng.normal(size=(nb, k * m)) * 0.3
    y = (rng.random(nb) < 0.5).astype(np.float64) * 2 - 1
    parties = [PartyData("C", X[:, :m])] + [
        PartyData(f"B{i}", X[:, i * m:(i + 1) * m]) for i in range(1, k)]
    cfg = VFLConfig(glm="logistic", lr=0.1, max_iter=iters, batch_size=nb,
                    he_backend="mock", key_bits=256, tol=0.0, seed=0)
    return train_vfl_socket(parties, y, cfg, chaos=chaos)


def run(smoke: bool = False) -> dict:
    from repro.launch.cluster import PARTY_PLATFORM
    from repro.runtime.chaos import PROFILES

    iters = 2 if smoke else 4
    nb = 64 if smoke else 128
    profiles = WAN_PROFILES[:1] if smoke else WAN_PROFILES

    # `runtime_s` is the conductor's training-loop wall clock (post-
    # handshake, pre-teardown) — process spawn + jax import would drown
    # the rounds × RTT signal if we timed the whole launch instead
    base = _mock_run(None, iters, nb)          # plain SocketTransport
    base_s = base.runtime_s
    rows = [{
        "name": "wan.base", "profile": "none", "latency_ms": 0.0,
        "iters": base.n_iter, "rounds": base.rounds,
        "analytic_comm_s": 0.0, "measured_s": round(base_s, 3),
        "wan_extra_s": 0.0, "us": base_s * 1e6, "derived": "",
    }]
    for name in profiles:
        p = PROFILES[name]
        res = _mock_run(name, iters, nb)
        wall = res.runtime_s
        # the protocol must be UNCHANGED by shaping — only slower
        assert res.losses == base.losses, f"{name}: shaping changed losses"
        assert dict(res.meter.by_tag) == dict(base.meter.by_tag), \
            f"{name}: shaping changed the analytic meter"
        assert res.rounds == base.rounds, f"{name}: round count changed"
        analytic = res.rounds * (p.latency_s + p.jitter_s / 2)
        extra = wall - base_s
        rows.append({
            "name": f"wan.{name}",
            "profile": name,
            "latency_ms": p.latency_s * 1e3,
            "iters": res.n_iter,
            "rounds": res.rounds,
            "analytic_comm_s": round(analytic, 3),
            "measured_s": round(wall, 3),
            "wan_extra_s": round(extra, 3),
            "us": wall * 1e6,
            "derived": (f"rounds={res.rounds};"
                        f"analytic_comm_s={analytic:.3f};"
                        f"wan_extra_s={extra:.3f}"),
        })
    return {"schema": "bench_wan/v1", "parties": 3, "iters": iters,
            "batch": nb, "he_backend": "mock",
            "party_platform": PARTY_PLATFORM, "rows": rows}


def write_report(report: dict) -> pathlib.Path:
    out = dict(report)
    out["rows"] = [{k: v for k, v in r.items() if k not in ("us", "derived")}
                   for r in report["rows"]]
    BENCH_WAN_PATH.write_text(json.dumps(out, indent=1) + "\n")
    return BENCH_WAN_PATH


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes, wan20 only, no file written")
    args = ap.parse_args()
    report = run(smoke=args.smoke)
    for r in report["rows"]:
        print(f"{r['name']}: rounds={r['rounds']} "
              f"latency={r['latency_ms']:.0f}ms "
              f"analytic_comm={r['analytic_comm_s']:.3f}s "
              f"measured={r['measured_s']:.3f}s "
              f"wan_extra={r['wan_extra_s']:.3f}s")
    if not args.smoke:
        print(f"# wrote {write_report(report)}")


if __name__ == "__main__":
    main()
