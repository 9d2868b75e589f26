"""The per-layer metrics that read the program's counters
(`engine_calls_per_iter`, `h2d_mb_per_iter`): a toy traced run reports
them, each equal to one iteration's count, building a cell counts
nothing (the readers divide the process's totals by the iterations),
and a program without the counters makes them fall silent instead of
failing."""
import importlib.util
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import benchfixtures
import pytest

ROOT = benchfixtures.ROOT
COUNTERS = ("engine_calls_per_iter", "h2d_mb_per_iter")

# One process, as `bench/run.py` is: a traced toy run, then a fresh
# cell's set-up and one more iteration, each counted by snapshot
# difference.
SCRIPT = r"""
import io, json, os, shutil, sys
root, tmp = sys.argv[1], sys.argv[2]
sys.path[:0] = [root, os.path.join(root, "src"),
                os.path.join(root, "tests", "bench")]
import benchfixtures
from bench import harness
toy = benchfixtures.make_root(tmp)
with open(os.path.join(toy, "BENCHMARK.json")) as f:
    spec = json.load(f)
cells = [w["name"] for w in spec["workloads"]]
for name, unit in (("engine_calls_per_iter", "calls"),
                   ("h2d_mb_per_iter", "MB")):
    shutil.copy(os.path.join(root, "bench", "metrics", name + ".py"),
                os.path.join(toy, "bench", "metrics", name + ".py"))
    spec["per_layer"].append({"name": name, "unit": unit, "better": "lower",
                              "source": "program_counter",
                              "layer": "crypto engine", "moves": "iter_s",
                              "workloads": cells})
with open(os.path.join(toy, "BENCHMARK.json"), "w") as f:
    json.dump(spec, f)
seed = 2**31 + 5
res = harness.run(toy, "toy_pr_k3.tables", seed, 0.5, True,
                  require_chip=False, persistent_cache=False,
                  out=io.StringIO(), err=io.StringIO())
from repro import obs
def delta(a, b):
    return {"calls": sum(b["engine_calls"].values())
                     - sum(a["engine_calls"].values()),
            "h2d_bytes": b["h2d_bytes"] - a["h2d_bytes"]}
before_build = obs.snapshot()
cell = harness.build_cell(harness.load_spec(toy, "toy_pr_k3.tables"),
                          harness.Seeds.of(seed))
build = delta(before_build, obs.snapshot())
state = harness.first_steps(cell, 1)[1]
a = obs.snapshot()
cell.sched.step(state)
b = obs.snapshot()
print(json.dumps({"result": res, "build": build, **delta(a, b)}))
"""


@pytest.fixture(scope="module")
def toy_counts(tmp_path_factory):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "REPRO_CRYPTO_PIPELINE": "rns"}
    proc = subprocess.run([sys.executable, "-c", SCRIPT, ROOT,
                           str(tmp_path_factory.mktemp("toy"))], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


def test_toy_traced_run_reports_the_counters(toy_counts):
    out = toy_counts
    res = out["result"]
    assert res["correct"] is True
    assert set(COUNTERS) <= set(res["metrics"])
    assert res["metrics"]["engine_calls_per_iter"] == {
        "value": float(out["calls"]), "unit": "calls"}
    assert res["metrics"]["h2d_mb_per_iter"]["value"] == \
        out["h2d_bytes"] / 1e6
    assert out["calls"] > 0 and out["h2d_bytes"] > 0


def test_building_a_cell_counts_no_engine_call_or_transfer(toy_counts):
    # The readers book every count of the process to the iterations; work
    # moved into set-up (keys, tables, features put on the device once)
    # would be spread over them and must change the readers first.
    assert toy_counts["build"] == {"calls": 0, "h2d_bytes": 0}


def _reader(name):
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("counter_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_counters_fall_silent_without_the_program_counters(monkeypatch):
    import repro
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    ctx = SimpleNamespace(iterations=3)
    for name in COUNTERS:
        assert _reader(name)(ctx) is None


def test_counter_entries_in_benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = [w["name"] for w in spec["workloads"]]
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name in COUNTERS:
        m = entries[name]
        assert m["source"] == "program_counter" and m["moves"] == "iter_s"
        assert m["workloads"] == cells
