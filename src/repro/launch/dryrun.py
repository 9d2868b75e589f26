"""Multi-pod dry-run: lower + compile every (architecture × input-shape ×
mesh) combination and extract memory / cost / collective statistics.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --cell train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only]
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun

The first two lines force 512 host platform devices BEFORE any jax import
(jax locks the device count at first init).  Do NOT replicate this in
conftest/pyproject: smoke tests must see 1 device.
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import numpy as np   # noqa: E402

from repro.configs import registry                     # noqa: E402
from repro.configs.base import (LONG_CONTEXT_ARCHS,    # noqa: E402
                                SHAPE_CELLS)
from repro.launch import mesh as mesh_lib              # noqa: E402
from repro.launch import steps as steps_lib            # noqa: E402

# v5e hardware model (roofline constants; see EXPERIMENTS.md §Roofline)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}

_COLL_RE = re.compile(
    r"=\s*\(?(\w+)\[([\d,]*)\][^)]*?\)?\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-device result bytes of every collective op in the SPMD
    module, by op kind ('-done' halves of async pairs are skipped so
    nothing is double-counted)."""
    out: dict[str, float] = {}
    count: dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        dtype, dims, kind, suffix = m.groups()
        if suffix == "-done" or dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes = n * _DTYPE_BYTES[dtype]
        out[kind] = out.get(kind, 0.0) + nbytes
        count[kind] = count.get(kind, 0) + 1
    out["total_bytes"] = float(sum(out.values()))
    out["op_counts"] = count
    return out


def roofline_terms(flops_per_dev: float, hbm_bytes_per_dev: float,
                   coll_bytes_per_dev: float) -> dict:
    return {
        "compute_s": flops_per_dev / PEAK_FLOPS,
        "memory_s": hbm_bytes_per_dev / HBM_BW,
        "collective_s": coll_bytes_per_dev / ICI_BW,
    }


def cell_plan(arch: str) -> list[str]:
    """Which shape cells run for this arch (documented skips)."""
    cells = ["train_4k", "prefill_32k", "decode_32k"]
    if arch in LONG_CONTEXT_ARCHS:
        cells.append("long_500k")
    return cells


# ---------------------------------------------------------------------------
# Cost source: XLA's cost_analysis() counts each while-loop (lax.scan) body
# ONCE, so scanned stacks/recurrences undercount by orders of magnitude.
# Roofline terms therefore come from the analytic model in costmodel.py,
# which is validated against cost_analysis on fully-UNROLLED small configs
# (tests/test_costmodel.py) — the regime where XLA's numbers are exact.
# The raw full-compile numbers + the HLO collective op census are kept in
# each record for structural cross-checks.
# ---------------------------------------------------------------------------
from repro.launch import costmodel  # noqa: E402


def _apply_overrides(cfg, overrides: str | None):
    """--set k=v,k=v — §Perf variant knobs (dataclasses.replace)."""
    if not overrides:
        return cfg
    import dataclasses
    kw = {}
    for pair in overrides.split(","):
        k, v = pair.split("=")
        cur = getattr(cfg, k)
        if isinstance(cur, bool):
            kw[k] = v.lower() in ("1", "true")
        elif isinstance(cur, int):
            kw[k] = int(v)
        elif isinstance(cur, float):
            kw[k] = float(v)
        else:
            kw[k] = v
    return dataclasses.replace(cfg, **kw)


def run_cell(arch: str, cell_name: str, mesh, *, smoke: bool = False,
             overrides: str | None = None) -> dict:
    cfg = (registry.get_smoke_config(arch) if smoke
           else registry.get_config(arch))
    cfg = _apply_overrides(cfg, overrides)
    cell = SHAPE_CELLS[cell_name]
    if smoke:   # shrink the cell so CI meshes can lower it quickly
        import dataclasses
        cell = dataclasses.replace(cell, seq_len=256,
                                   global_batch=mesh.devices.size * 2 //
                                   (2 if "pod" in mesh.axis_names else 1))
    t0 = time.time()
    bundle = steps_lib.build_step_bundle(cfg, cell, mesh)
    lowered = jax.jit(bundle.fn,
                      in_shardings=bundle.in_shardings,
                      out_shardings=bundle.out_shardings,
                      donate_argnums=bundle.donate).lower(*bundle.args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    colls = parse_collectives(compiled.as_text())
    n_dev = mesh.devices.size
    costs = costmodel.cell_costs(cfg, cell, mesh)
    flops = costs["flops_per_dev"]
    hbm_bytes = costs["hbm_bytes_per_dev"]
    coll_bytes = costs["coll_bytes_per_dev"]
    res = {
        "arch": arch, "cell": cell_name, "overrides": overrides,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "n_devices": int(n_dev),
        "ok": True,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "peak_bytes_per_dev": ma.peak_memory_in_bytes,
        "arg_bytes_per_dev": int(ma.argument_size_in_bytes),
        "temp_bytes_per_dev": int(ma.temp_size_in_bytes),
        "output_bytes_per_dev": int(ma.output_size_in_bytes),
        "flops_per_dev": flops,
        "hbm_bytes_per_dev": hbm_bytes,
        "coll_bytes_per_dev": coll_bytes,
        "costmodel": costs,
        "raw_fullcompile_hlo": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": colls["total_bytes"]},
        "collectives": colls,
        **roofline_terms(flops, hbm_bytes, coll_bytes),
    }
    # model-FLOPs utilisation denominators (6·N·D; MoE: active params)
    n_active = cfg.active_param_count()
    if cell.kind == "train":
        tokens = cell.seq_len * cell.global_batch
        model_flops = 6 * n_active * tokens
    elif cell.kind == "prefill":
        tokens = cell.seq_len * cell.global_batch
        model_flops = 2 * n_active * tokens
    else:
        tokens = cell.global_batch
        model_flops = 2 * n_active * tokens
    res["model_flops_total"] = float(model_flops)
    res["useful_flops_ratio"] = (
        float(model_flops) / (flops * n_dev) if flops else 0.0)
    return res


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--cell", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true",
                    help="also run the 2-pod (2,16,16) mesh")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs on a small mesh (CI)")
    ap.add_argument("--mesh-shape", default=None,
                    help="override mesh, e.g. 64x4 (perf-iteration "
                         "sharding variants; axes stay (data, model))")
    ap.add_argument("--set", dest="overrides", default=None,
                    help="config overrides, e.g. kv_cache_dtype=int8,"
                         "kv_head_replication=2")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    if args.mesh_shape:
        dims = tuple(int(x) for x in args.mesh_shape.split("x"))
        names = ("data", "model") if len(dims) == 2 else \
            ("pod", "data", "model")
        meshes = [mesh_lib._make_mesh(dims, names)]
    elif args.smoke:
        meshes = [mesh_lib.make_debug_mesh(),
                  mesh_lib.make_debug_mesh(multi_pod=True)]
    else:
        meshes = []
        if not args.multi_pod_only:
            meshes.append(mesh_lib.make_production_mesh())
        if args.multi_pod or args.multi_pod_only:
            meshes.append(mesh_lib.make_production_mesh(multi_pod=True))

    archs = [args.arch] if args.arch else registry.list_archs()
    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for mesh in meshes:
        mesh_tag = "x".join(str(s) for s in mesh.devices.shape)
        for arch in archs:
            cells = [args.cell] if args.cell else cell_plan(arch)
            for cell_name in cells:
                tag = f"{arch}__{cell_name}__{mesh_tag}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[skip cached] {tag}")
                    continue
                print(f"[dryrun] {tag} ...", flush=True)
                try:
                    res = run_cell(arch, cell_name, mesh, smoke=args.smoke,
                                   overrides=args.overrides)
                    print(f"  ok: peak={res['peak_bytes_per_dev']/2**30:.2f}"
                          f"GiB compute={res['compute_s']*1e3:.2f}ms "
                          f"mem={res['memory_s']*1e3:.2f}ms "
                          f"coll={res['collective_s']*1e3:.2f}ms "
                          f"(compile {res['compile_s']:.0f}s)", flush=True)
                except Exception as e:   # noqa: BLE001 — record, keep going
                    failures += 1
                    res = {"arch": arch, "cell": cell_name, "mesh": mesh_tag,
                           "ok": False, "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                    print(f"  FAIL: {res['error'][:200]}", flush=True)
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
    print(f"done; {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
