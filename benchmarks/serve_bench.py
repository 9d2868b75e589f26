"""Serving bench: p50/p99 request latency and throughput of the
continuous-batching secure scoring service vs micro-batch size x party
count x crypto backend.

Every row scores the SAME request stream through `VFLScoringEngine`
(admission -> per-version serving caches -> `infer.wx_share` shares ->
inverse link at C), varying only the batch-close size; the guard rows
assert that batching pays: throughput at the largest batch must be at
least that of singleton batches.  Full mode adds one socket row (real
party processes over TCP) and records the wire invariant measured ==
analytic bytes for the `infer.wx_share` tag.

A chip has one owner: the in-process rows run on this process's JAX
device, and the socket row's party processes run on the host CPU
(`cluster.PARTY_PLATFORM`, recorded in the row), so none of them reaches
for a chip this process holds.

  PYTHONPATH=src python -m benchmarks.run --only serve [--smoke]
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.trainer import PartyData, VFLConfig
from repro.data import synthetic, vertical
from repro.runtime import VFLScheduler
from repro.serve import VFLScoringEngine

#: (k, he_backend) grid; smoke keeps one mock row so CI proves the
#: path end-to-end without paying Paillier
GRID_FULL = [(2, "mock"), (3, "mock"), (4, "mock"), (3, "paillier")]
GRID_SMOKE = [(3, "mock")]
BATCHES_FULL = (1, 8, 32)
BATCHES_SMOKE = (1, 8)


def _setup(k: int, backend: str, n: int = 256):
    X, y = synthetic.credit_default(n=n, d=8, seed=17)
    parts = vertical.split_columns(X, k)
    names = ["C"] + [f"B{i}" for i in range(1, k)]
    parties = [PartyData(nm, p) for nm, p in zip(names, parts)]
    cfg = VFLConfig(glm="logistic", lr=0.1, max_iter=2, batch_size=128,
                    he_backend=backend,
                    key_bits=256 if backend == "paillier" else 1024,
                    tol=0.0, seed=7)
    return parties, y, cfg, names, parts


def _requests(names, parts, n_req):
    return [{nm: part[i % part.shape[0]]
             for nm, part in zip(names, parts)} for i in range(n_req)]


def _drive(eng, reqs, batch):
    """Submit in waves of `batch` and close each wave as one micro-batch
    — per-request latency is submit->scored against the engine's own
    clock, throughput is the wall clock over the whole stream."""
    t0 = time.perf_counter()
    for i in range(0, len(reqs), batch):
        for r in reqs[i:i + batch]:
            eng.submit(r)
        while eng.batcher.pending:
            eng.step(flush=True)
    wall = time.perf_counter() - t0
    lat = eng.latencies()
    return {"p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "throughput_rps": len(reqs) / wall}


def _row(name, stats, k, backend, batch, mode, n_req, guard_vs=None,
         **extra):
    r = {"name": name, "k": k, "backend": backend, "batch": batch,
         "mode": mode, "n_req": n_req,
         "p50_ms": round(stats["p50_ms"], 4),
         "p99_ms": round(stats["p99_ms"], 4),
         "throughput_rps": round(stats["throughput_rps"], 1),
         "guard_vs": guard_vs,
         "us": stats["p50_ms"] * 1e3,
         "derived": (f"p50_ms={stats['p50_ms']:.3f};"
                     f"p99_ms={stats['p99_ms']:.3f};"
                     f"rps={stats['throughput_rps']:.0f}")}
    r.update(extra)
    return r


def run(smoke: bool = False) -> list[dict]:
    grid = GRID_SMOKE if smoke else GRID_FULL
    batches = BATCHES_SMOKE if smoke else BATCHES_FULL
    n_req = 32 if smoke else 96
    rows = []
    for k, backend in grid:
        parties, y, cfg, names, parts = _setup(k, backend)
        sched = VFLScheduler(parties, y, cfg)
        sched.run()
        reqs = _requests(names, parts, n_req)
        base = f"serve.inproc.k{k}.{backend}"
        for b in batches:
            eng = VFLScoringEngine(sched.parties, max_batch=b)
            stats = _drive(eng, reqs, b)
            guard = f"{base}.b{batches[0]}" if b == batches[-1] else None
            rows.append(_row(f"{base}.b{b}", stats, k, backend, b,
                             "inproc", n_req, guard_vs=guard))
    if not smoke:
        rows.append(_socket_row(n_req=48, batch=16))
    return rows


def _socket_row(n_req: int, batch: int) -> dict:
    """One distributed row: real party processes over TCP, plus the wire
    invariant (measured frame bytes == analytic meter) for the serving
    tag — the same per-tag identity training asserts."""
    from repro.launch.cluster import PARTY_PLATFORM, SocketCluster
    k, backend = 3, "mock"
    parties, y, cfg, names, parts = _setup(k, backend)
    with SocketCluster(parties, y, cfg) as cl:
        cl.train()
        eng = VFLScoringEngine(cluster=cl, max_batch=batch)
        stats = _drive(eng, _requests(names, parts, n_req), batch)
        meters = cl.fetch_meters()
    analytic = meters["meter"].by_tag["infer.wx_share"]
    measured = meters["measured"].by_tag["infer.wx_share"]
    return _row(f"serve.socket.k{k}.{backend}.b{batch}", stats, k,
                backend, batch, "socket", n_req,
                party_platform=PARTY_PLATFORM,
                wx_bytes_analytic=int(analytic),
                wx_bytes_measured=int(measured),
                wire_ok=bool(analytic == measured
                             == n_req * (k - 1) * 8))


def check_guards(rows: list[dict]) -> list[str]:
    """Guard rows: the largest batch's throughput must not fall below
    singleton batching (batching must amortize, or the admission
    controller is broken); socket rows must hold the wire identity."""
    by_name = {r["name"]: r for r in rows}
    failures = []
    for r in rows:
        ref = r.get("guard_vs")
        if ref:
            other = by_name.get(ref)
            if other is None:
                failures.append(f"{r['name']}: guard target {ref} missing")
            elif r["throughput_rps"] < other["throughput_rps"]:
                failures.append(
                    f"{r['name']}: {r['throughput_rps']} rps < "
                    f"{other['throughput_rps']} rps ({ref}) — batching "
                    "no longer amortizes")
        if "wire_ok" in r and not r["wire_ok"]:
            failures.append(
                f"{r['name']}: measured infer.wx_share bytes "
                f"{r['wx_bytes_measured']} != analytic "
                f"{r['wx_bytes_analytic']}")
    return failures


if __name__ == "__main__":
    for row in run(smoke=True):
        print(row)
