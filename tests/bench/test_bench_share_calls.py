"""The per-layer metric `share_calls_per_iter`: a toy traced run reports
it, equal to one iteration's count of compiled share-ring calls and
above zero; building a cell makes no such call (the reader divides the
process's total by the iterations); and the reader falls silent on a
program without the counter."""
import importlib.util
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import benchfixtures
import pytest

ROOT = benchfixtures.ROOT
NAME = "share_calls_per_iter"

# One process, as `bench/run.py` is: a traced toy run, then a fresh
# cell's set-up and one more iteration, each counted by snapshot
# difference.
SCRIPT = r"""
import io, json, os, shutil, sys
root, tmp, name = sys.argv[1], sys.argv[2], sys.argv[3]
sys.path[:0] = [root, os.path.join(root, "src"),
                os.path.join(root, "tests", "bench")]
import benchfixtures
from bench import harness
toy = benchfixtures.make_root(tmp)
with open(os.path.join(toy, "BENCHMARK.json")) as f:
    spec = json.load(f)
shutil.copy(os.path.join(root, "bench", "metrics", name + ".py"),
            os.path.join(toy, "bench", "metrics", name + ".py"))
spec["per_layer"].append({"name": name, "unit": "calls", "better": "lower",
                          "source": "program_counter",
                          "layer": "conductor and protocol legs",
                          "moves": "iter_s",
                          "workloads": [w["name"] for w in spec["workloads"]]})
with open(os.path.join(toy, "BENCHMARK.json"), "w") as f:
    json.dump(spec, f)
seed = 2**31 + 9
res = harness.run(toy, "toy_pr_k3.tables", seed, 0.5, True,
                  require_chip=False, persistent_cache=False,
                  out=io.StringIO(), err=io.StringIO())
from repro import obs
def calls():
    return sum(obs.snapshot()["share_calls"].values())
before_build = calls()
cell = harness.build_cell(harness.load_spec(toy, "toy_pr_k3.tables"),
                          harness.Seeds.of(seed))
build = calls() - before_build
state = harness.first_steps(cell, 1)[1]
a = calls()
cell.sched.step(state)
print(json.dumps({"result": res, "build": build, "step": calls() - a}))
"""


@pytest.fixture(scope="module")
def toy_share_calls(tmp_path_factory):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "REPRO_CRYPTO_PIPELINE": "rns"}
    proc = subprocess.run([sys.executable, "-c", SCRIPT, ROOT,
                           str(tmp_path_factory.mktemp("toy")), NAME],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


def test_toy_traced_run_reports_share_calls(toy_share_calls):
    out = toy_share_calls
    assert out["result"]["correct"] is True
    assert out["step"] > 0
    assert out["result"]["metrics"][NAME] == {"value": float(out["step"]),
                                              "unit": "calls"}


def test_building_a_cell_makes_no_share_call(toy_share_calls):
    assert toy_share_calls["build"] == 0


def _reader():
    path = os.path.join(ROOT, "bench", "metrics", NAME + ".py")
    spec = importlib.util.spec_from_file_location("counter_" + NAME, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_share_calls_fall_silent_without_the_counter(monkeypatch):
    """A program without `repro.obs`, or with an `obs` that keeps no
    ``share_calls`` (the parent of this metric), reads None."""
    from repro import obs
    ctx = SimpleNamespace(iterations=3)
    monkeypatch.setattr(obs, "snapshot", lambda: {"engine_calls": {}})
    assert _reader()(ctx) is None
    import repro
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert _reader()(ctx) is None


def test_share_calls_entry_in_benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    m = {m["name"]: m for m in spec["per_layer"]}[NAME]
    assert m["source"] == "program_counter" and m["moves"] == "iter_s"
    assert m["layer"] == "conductor and protocol legs"
    assert m["workloads"] == [w["name"] for w in spec["workloads"]]
