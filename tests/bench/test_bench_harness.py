"""The benchmark harness on the CPU, at a small size: cells found from
files alone, a full run that comes out correct, the control and every
planted fault that come out not correct, and runs that must print no
result."""
import io
import json
import os
import re
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

import benchfixtures
from bench import faults, harness

ROOT = benchfixtures.ROOT


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return benchfixtures.make_root(str(tmp_path_factory.mktemp("toy")))


@pytest.fixture(scope="module")
def run_toy(toy_root):
    """Runs a toy cell without the chip check or the persistent cache.
    The RNS pipeline at every width keeps the CPU library's per-call
    recompiles out of these small keys' runs."""
    mp = pytest.MonkeyPatch()
    mp.setenv("REPRO_CRYPTO_PIPELINE", "rns")

    def run(workload="toy_pr_k3.tables", seed=2**31 + 11, trace=False,
            **kw):
        out, err = io.StringIO(), io.StringIO()
        res = harness.run(toy_root, workload, seed, 0.5, trace,
                          require_chip=False, persistent_cache=False,
                          out=out, err=err, **kw)
        assert json.loads(out.getvalue().splitlines()[-1]) == res
        assert err.getvalue().splitlines()[-1].startswith("check ")
        return res

    yield run
    mp.undo()


def test_added_files_are_found(toy_root):
    spec = harness.load_spec(toy_root, "toy_pr_k3.tables")
    assert spec.config["name"] == "toy_pr_k3"
    assert spec.traffic["noise"] == "tables"
    assert [m["name"] for m in spec.per_layer] == ["toy_host_share"]
    assert harness.load_spec(toy_root, "toy_pr_k3.ladder").per_layer == []
    read = harness.load_reader(spec, "toy_host_share")
    assert read(SimpleNamespace(summary=SimpleNamespace(
        host_share=0.25))) == 25.0
    with pytest.raises(harness.RunError):
        harness.load_spec(toy_root, "toy_pr_k3.missing")


def test_seeds_take_large_and_negative_values():
    a, b = harness.Seeds.of(2**31 + 11), harness.Seeds.of(-3)
    assert a == harness.Seeds.of(2**31 + 11) and a != b
    assert all(0 <= v < 2**32 for v in (*vars(a).values(),
                                        *vars(b).values()))
    assert a.protocol < 2**31


def test_toy_cell_is_correct(run_toy):
    res = run_toy()
    assert res["correct"] is True
    assert set(res["metrics"]) == {"iter_s", "setup_s", "wire_mb_per_iter",
                                   "rounds_per_iter"}
    assert res["metrics"]["rounds_per_iter"]["value"] > 0
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "check"
    assert set(res["check"]) == set(benchfixtures.toy_config()["limits"])


def test_toy_traced_run_reads_its_metric(run_toy):
    res = run_toy(trace=True)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"toy_host_share"}
    assert 0.0 <= res["metrics"]["toy_host_share"]["value"] <= 100.0
    assert res["device"]["window_s"] > 0.0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_noise_forms_train_the_same_model(run_toy):
    a = run_toy("toy_pr_k3.tables", seed=77)
    b = run_toy("toy_pr_k3.ladder", seed=77)
    for k in ("grad_gap", "change_gap"):     # the window lengths differ
        assert a["check"][k] == b["check"][k]


def test_control_is_not_correct(run_toy):
    cfg = benchfixtures.toy_config()
    res = run_toy(overrides=cfg["control"])
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["check"].values())


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_planted_fault_is_not_correct(run_toy, fault):
    with faults.planted(fault):
        res = run_toy()
    assert res["correct"] is False


def _run_cli(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "credit_lr_k2.ladder",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())


def test_no_tpu_exits_nonzero_without_a_result():
    _no_result(_run_cli(ROOT))


def test_bare_checkout_exits_nonzero_without_a_result(tmp_path):
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in spec["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run_cli(str(tmp_path)))


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keeps_its_shape():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    cfgs = {c["name"]: c for c in spec["configs"]}
    cells = {w["name"]: w for w in spec["workloads"]}
    metrics = spec["end_to_end"] + spec["per_layer"]
    for n in [*cfgs, *cells, *(m["name"] for m in metrics)]:
        assert NAME.match(n), n
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert {w["config"] for w in cells.values()} == set(cfgs)
    for c in cfgs.values():
        assert c["file"].startswith("bench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert set(cfg["limits"]) == {"loss_gap", "grad_gap", "change_gap",
                                      "final_gap"}
    for w in cells.values():
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
