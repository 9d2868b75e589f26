"""A throwaway benchmark root for the CPU tests: a small Poisson k=3
configuration (256-bit keys, batch 64), the two traffic mixes, one
per-layer metric reader — all as new files, none of the benchmark's own
edited."""
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:            # the `bench` package, as run.py finds it
    sys.path.insert(0, ROOT)

TOY_METRIC = '''"""Share of the traced window outside the backend's calls."""


def read(ctx):
    return 100.0 * ctx.summary.host_share
'''


def toy_config() -> dict:
    with open(os.path.join(ROOT, "bench", "configs",
                           "dvisits_pr_k3.json")) as f:
        cfg = json.load(f)
    cfg.update(name="toy_pr_k3", batch_size=64, key_bits=256, rho_bits=80,
               data={**cfg["data"], "rows": 400})
    return cfg


def make_root(tmp: str) -> str:
    """BENCHMARK.json with cells toy_pr_k3.tables / toy_pr_k3.ladder and
    the metric toy_host_share, beside files of their own."""
    bench = os.path.join(tmp, "bench")
    for d in ("configs", "traffic", "metrics"):
        os.makedirs(os.path.join(bench, d))
    with open(os.path.join(bench, "configs", "toy_pr_k3.json"), "w") as f:
        json.dump(toy_config(), f)
    for t in ("tables", "ladder"):
        shutil.copy(os.path.join(ROOT, "bench", "traffic", t + ".json"),
                    os.path.join(bench, "traffic", "toy_" + t + ".json"))
    with open(os.path.join(bench, "metrics", "toy_host_share.py"),
              "w") as f:
        f.write(TOY_METRIC)
    spec = {
        "configs": [{"name": "toy_pr_k3", "source": "test",
                     "file": "bench/configs/toy_pr_k3.json",
                     "reduced": [], "why": "test"}],
        "workloads": [
            {"name": f"toy_pr_k3.{t}", "config": "toy_pr_k3",
             "traffic": f"toy_{t}", "chips": 1, "why": "test"}
            for t in ("tables", "ladder")],
        "end_to_end": [
            {"name": n, "unit": u, "better": "lower", "bound": 0.25,
             "source": "host_clock"}
            for n, u in (("iter_s", "s"), ("setup_s", "s"),
                         ("wire_mb_per_iter", "MB"),
                         ("rounds_per_iter", "rounds"))],
        "per_layer": [{"name": "toy_host_share", "unit": "%",
                       "better": "lower", "source": "device_trace",
                       "layer": "conductor and protocol legs",
                       "moves": "iter_s",
                       "workloads": ["toy_pr_k3.tables"]}],
    }
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return tmp
