"""The key width and the set-up counters.

Every homomorphic result is exact and the masks cancel, so at one
``--seed`` the program trains the same model whatever its Paillier key
width: a toy cell at 256- and at 512-bit keys gives bit-identical losses
and weights.  The `credit_lr_k2_2048` cell runs 2048-bit keys and
320-bit noise exponents.  The readers of
`setup_jit_s` and `setup_keys_s` report set-up's compile and key-building
seconds in a toy traced run, the counters grow in set-up and not in an
iteration, and the readers fall silent where the program keeps no such
counter."""
import importlib.util
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import benchfixtures
import jax
import numpy as np
import pytest

from bench import harness
from repro.crypto import fixed_base

ROOT = benchfixtures.ROOT
SETUP_COUNTERS = ("setup_jit_s", "setup_keys_s")
STEPS = 4                  # the comparison reads 0, 1, 3 and the last


@pytest.fixture(scope="module")
def width_root(tmp_path_factory):
    """The toy root with a second configuration, the toy at 512-bit keys
    and keys of its own, and its tables cell."""
    root = benchfixtures.make_root(str(tmp_path_factory.mktemp("width")))
    cfg = benchfixtures.toy_config()
    cfg.update(name="toy_pr_k3_512", key_bits=512,
               key_seeds={p: s + 1000 for p, s in cfg["key_seeds"].items()})
    with open(os.path.join(root, "bench", "configs",
                           "toy_pr_k3_512.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({**spec["configs"][0], "name": "toy_pr_k3_512",
                            "file": "bench/configs/toy_pr_k3_512.json"})
    spec["workloads"].append({**spec["workloads"][0],
                              "name": "toy_pr_k3_512.tables",
                              "config": "toy_pr_k3_512"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def _trajectory(root, workload, seed):
    cell = harness.build_cell(harness.load_spec(root, workload),
                              harness.Seeds.of(seed))
    pub = next(iter(cell.sched.backend.keys.values())).pub
    states = harness.first_steps(cell, STEPS)
    return pub.n.bit_length(), harness.trajectory(
        {k: states[k] for k in (*harness.COMPARED, STEPS)})


def test_key_width_trains_the_same_model(width_root, monkeypatch):
    monkeypatch.setenv("REPRO_CRYPTO_PIPELINE", "rns")
    seed = 2**31 + 23
    bits_a, a = _trajectory(width_root, "toy_pr_k3.tables", seed)
    bits_b, b = _trajectory(width_root, "toy_pr_k3_512.tables", seed)
    assert (bits_a, bits_b) == (256, 512)
    assert len(a["losses"]) == STEPS
    assert np.array_equal(np.asarray(a["losses"]), np.asarray(b["losses"]))
    assert a["w"].keys() == b["w"].keys()
    for step in a["w"]:
        for party, w in a["w"][step].items():
            assert np.array_equal(w, b["w"][step][party]), (step, party)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="the cells' compiled kernels need a TPU")
def test_credit_cells_train_the_same_model_on_the_chip():
    """`credit_lr_k2.tables` and `credit_lr_k2_2048.tables` at one seed,
    as `bench/run.py` builds them: bit-identical losses and weights at
    the compared steps and the last."""
    seed = 2**31 + 4099
    bits_a, a = _trajectory(ROOT, "credit_lr_k2.tables", seed)
    bits_b, b = _trajectory(ROOT, "credit_lr_k2_2048.tables", seed)
    assert (bits_a, bits_b) == (1024, 2048)
    assert np.array_equal(np.asarray(a["losses"]), np.asarray(b["losses"]))
    for step in a["w"]:
        for party, w in a["w"][step].items():
            assert np.array_equal(w, b["w"][step][party]), (step, party)


def test_credit_2048_builds_2048_bit_keys():
    """The cell `bench/run.py` times, as `harness.build_cell` builds it:
    both parties' keys are 2048 bits and their noise tables walk 320-bit
    exponents in 80 levels of window 4 at 674 channels; nothing else of
    the configuration differs from `credit_lr_k2`."""
    spec = harness.load_spec(ROOT, "credit_lr_k2_2048.tables")
    cell = harness.build_cell(spec, harness.Seeds.of(2**31 + 7))
    backend = cell.sched.backend
    assert set(backend.keys) == set(backend.tables) == {"C", "B1"}
    for party, key in backend.keys.items():
        table = backend.tables[party]
        assert key.pub.n.bit_length() == 2048
        assert key.pub.mod_n2.L == 342
        assert (table.window, table.levels, table.exp_bits) == (4, 80, 320)
        assert table.table_rns.shape == (80, 16, 674)
        assert table.fingerprint == fixed_base.key_fingerprint(key.pub.n)
    twin = harness.load_spec(ROOT, "credit_lr_k2.tables").config
    assert {k: v for k, v in spec.config.items()
            if twin.get(k) != v}.keys() == {
        "name", "source", "deployment", "key_bits", "key_seeds",
        "table_seeds", "assumed"}


# One process, as `bench/run.py` is: set-up of a toy cell, one more
# iteration, each measured by snapshot difference; then a traced toy run
# that reports the two readers.
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
for name in ("setup_jit_s", "setup_keys_s"):
    shutil.copy(os.path.join(root, "bench", "metrics", name + ".py"),
                os.path.join(toy, "bench", "metrics", name + ".py"))
    spec["per_layer"].append({"name": name, "unit": "s", "better": "lower",
                              "source": "program_counter",
                              "layer": "crypto engine", "moves": "setup_s",
                              "workloads": cells})
with open(os.path.join(toy, "BENCHMARK.json"), "w") as f:
    json.dump(spec, f)
from repro import obs
def counters():
    s = obs.snapshot()
    return {"jit_s": sum(s["jit_s"].values()), "keys_s": s["keys_s"]}
seed = 2**31 + 5
start = counters()
cell = harness.build_cell(harness.load_spec(toy, "toy_pr_k3.tables"),
                          harness.Seeds.of(seed))
state = harness.first_steps(cell, harness.SETUP_STEPS)[harness.SETUP_STEPS]
setup = counters()
cell.sched.step(state)
step = counters()
res = harness.run(toy, "toy_pr_k3.tables", seed + 1, 0.5, True,
                  require_chip=False, persistent_cache=False,
                  out=io.StringIO(), err=io.StringIO())
print(json.dumps({"start": start, "setup": setup, "step": step,
                  "end": counters(), "result": res}))
"""


@pytest.fixture(scope="module")
def setup_counts(tmp_path_factory):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "REPRO_CRYPTO_PIPELINE": "rns"}
    proc = subprocess.run([sys.executable, "-c", SCRIPT, ROOT,
                           str(tmp_path_factory.mktemp("toy"))], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


def test_setup_counters_grow_in_setup_not_in_an_iteration(setup_counts):
    start, setup, step = (setup_counts[k] for k in ("start", "setup",
                                                    "step"))
    assert setup["keys_s"] > start["keys_s"]
    assert setup["jit_s"] > start["jit_s"]
    assert step == setup


def test_toy_traced_run_reports_the_setup_counters(setup_counts):
    res = setup_counts["result"]
    assert res["correct"] is True
    for name in SETUP_COUNTERS:
        assert res["metrics"][name]["unit"] == "s"
    end = setup_counts["end"]
    assert res["metrics"]["setup_keys_s"]["value"] == end["keys_s"]
    assert res["metrics"]["setup_jit_s"]["value"] == pytest.approx(
        end["jit_s"], rel=1e-12)
    assert end["keys_s"] > setup_counts["step"]["keys_s"]


def _reader(name):
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("setup_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_setup_counters_fall_silent_without_the_program_counters(
        monkeypatch):
    import repro
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    for name in SETUP_COUNTERS:
        assert _reader(name)(SimpleNamespace(iterations=3)) is None


def test_setup_counters_fall_silent_in_an_older_program(monkeypatch):
    # a `repro.obs` from before these counters: snapshot() lacks them
    from repro import obs
    monkeypatch.setattr(obs, "snapshot", lambda: {"engine_calls": {},
                                                  "h2d_bytes": 0})
    for name in SETUP_COUNTERS:
        assert _reader(name)(SimpleNamespace(iterations=3)) is None


def test_setup_counter_entries_in_benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = [w["name"] for w in spec["workloads"]]
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name in SETUP_COUNTERS:
        m = entries[name]
        assert m["source"] == "program_counter" and m["moves"] == "setup_s"
        assert m["layer"] == "crypto engine" and m["unit"] == "s"
        assert m["workloads"] == cells
