"""The benchmark's yardstick on the CPU: the trace reduction on synthetic
and on recorded traces, and the work counts against the analytic product
counts of benchmarks/kernel_bench.py."""
import glob
import math
import os

import pytest

import benchfixtures  # noqa: F401  (puts the checkout root on the path)
from bench import counts, traces
from bench.traces import Event

MS = 1_000_000  # ns


def _summary():
    window = Event(traces.WINDOW, 0, 100 * MS)
    notes = [window,
             Event("bench.matvec", 10 * MS, 30 * MS),       # 10–40
             Event("bench.decrypt_to_r64", 60 * MS, 10 * MS),  # 60–70
             Event("bench.encrypt_share", 75 * MS, 15 * MS)]   # 75–90
    ops = [Event("rns_he_matvec.1", 12 * MS, 20 * MS),      # 12–32
           Event("fusion.7", 30 * MS, 5 * MS),              # 30–35
           Event("rns_mont_exp", 61 * MS, 4 * MS),          # 61–65
           Event("fusion.7", 95 * MS, 10 * MS)]             # 95–105
    kind = {"rns_he_matvec.1": "rns_he_matvec", "fusion.7": "",
            "rns_mont_exp": "rns_mont_exp"}
    return traces.reduce([ops], notes, kind)


def test_reduce_busy_union_and_idle_share():
    s = _summary()
    assert s.window_s == pytest.approx(0.1)
    # 12–35 (overlap merged), 61–65, 95–100 (clipped to the window)
    assert s.busy_s == pytest.approx(0.032)
    assert s.idle_share == pytest.approx(0.68)


def test_reduce_kernel_glue_and_host_shares():
    s = _summary()
    assert s.kernel_s == pytest.approx({"rns_he_matvec": 0.020,
                                        "rns_mont_exp": 0.004})
    assert s.glue_share == pytest.approx(0.010 / 0.034)
    # idle outside every call: 0–10, 40–60, 70–75, 90–95; the device
    # idle inside a call (65–70, 10–12) and busy outside one (95–100)
    # are not the protocol's
    assert s.host_share == pytest.approx(0.40)


def test_reduce_labels_gaps_by_annotation():
    s = _summary()
    # idle 65–95 (middle 80, in encrypt_share), 35–61, 0–12 (no call)
    assert s.gaps == [("bench.encrypt_share", pytest.approx(0.030)),
                      ("protocol host work", pytest.approx(0.026)),
                      ("protocol host work", pytest.approx(0.012))]
    out = traces.breakdown(s)
    assert out["device_ops"][0] == ["rns_he_matvec.1", pytest.approx(0.02)]
    assert len(out["idle_gaps"]) <= 10


def _bench_reader(name):
    import importlib.util
    path = os.path.join(benchfixtures.ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_benchmark_readers_on_a_synthetic_trace():
    from types import SimpleNamespace
    work = {"rns_mont_exp": counts.Work()}
    work["rns_mont_exp"].add(1000, 2048, 0.0)
    peak = {"int8_ops_per_s": 1e9, "hbm_bytes_per_s": 1e9}
    ctx = SimpleNamespace(summary=_summary(), iterations=2, peak=peak,
                          work=work)
    assert _bench_reader("host_protocol_share")(ctx) == pytest.approx(40.0)
    assert _bench_reader("idle_share")(ctx) == pytest.approx(68.0)
    # 2 iterations x 1000 products x 512 ops over 32 ms busy at 1e9 ops/s
    assert _bench_reader("iter_mfu")(ctx) == pytest.approx(
        100.0 * 2 * 1000 * 512 / (0.032 * 1e9))
    ctx.summary.busy_s = 0.0
    assert _bench_reader("iter_mfu")(ctx) is None


def test_op_names_and_kernels_from_hlo_text():
    text = ("%rns_he_matvec.1 = u32[9,329]{1,0:T(8,128)S(1)} custom-call("
            "u32[512,329]{1,0:T(8,128)S(1)} %fusion.18), "
            'custom_call_target="tpu_custom_call"')
    name = traces.op_name(text)
    assert name == "rns_he_matvec.1 u32[9,329]"
    assert traces.kernel_of(name, counts.KERNELS) == "rns_he_matvec"
    # an operation that reads a kernel's output is no kernel
    user = traces.op_name("%fusion.2 = (f32[9]{0}, f32[9]{0}) fusion("
                          "u32[9,85]{1,0} %rns_montmul.1), kind=kLoop")
    assert user == "fusion.2 (tuple)"
    assert traces.kernel_of(user, counts.KERNELS) == ""
    assert traces.kernel_of("rns_mont_exp", counts.KERNELS) == "rns_mont_exp"


def test_reduce_needs_one_window():
    with pytest.raises(ValueError):
        traces.reduce([[]], [], {})


def test_load_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(traces.WINDOW):
            with jax.profiler.TraceAnnotation("bench.matvec"):
                f(x).block_until_ready()
    devices, notes, kind = traces.load(traces.find_xplane(str(tmp_path)),
                                       counts.KERNELS)
    assert sorted(n.name for n in notes) == ["bench.matvec", traces.WINDOW]
    s = traces.reduce(devices, notes, kind)     # no TPU plane on a CPU
    assert s.busy_s == 0.0 and s.glue_share is None
    assert 0.0 <= s.host_share < 1.0


# --- counts -----------------------------------------------------------------

def test_ops_per_product_is_the_digit_lower_bound():
    assert counts.ops_per_product(2048) == 512.0
    assert counts.ops_per_product(1024) == 256.0


@pytest.mark.parametrize("kind,args,want", [
    # kernel_bench: exp_mm = 2 * exp_bits_n * exp_batch
    ("ladder", (64, 16), 2 * 16 * 64),
    # kernel_bench: mv_mm = enc_batch*((1 << window) - 2)
    #               + levels*(enc_batch*mv_m + (window + 1)*mv_m)
    ("matvec", (64, 8, 22, 4), 64 * 14 + 6 * (64 * 8 + 5 * 8)),
    # kernel_bench: montmuls=(table.levels + 1) * fb_batch
    ("fixed_base", (64, 80), 81 * 64),
])
def test_products_match_kernel_bench(kind, args, want):
    fn = {"ladder": counts.ladder_products,
          "matvec": counts.matvec_products,
          "fixed_base": counts.fixed_base_products}[kind]
    assert fn(*args) == want


def _cfg(batch):
    return {"key_bits": 1024, "batch_size": batch, "exp_width": 22,
            "window": 4, "rho_bits": 320, "table_window": 4}


def test_credit_ladder_iteration_work():
    work = counts.iteration_work(_cfg(2048), {"C": 12, "B1": 12}, "ladder")
    enc, dec = 2 * 2048 + 24, 24               # 4 120 encryptions
    assert work["rns_mont_exp"].products == \
        enc * 2 * 1024 + 2 * dec * 2 * 512
    assert work["rns_he_matvec"].products == \
        2 * counts.matvec_products(2048, 12, 22, 4)
    assert work["rns_fixed_base"].products == 0
    ladder_share = enc * 2048 / sum(w.products for w in work.values())
    assert 0.94 < ladder_share < 0.97          # "≈ 95 % of the HE work"
    assert work["rns_mont_exp"].ops == pytest.approx(
        enc * 2048 * 512 + 2 * dec * 1024 * 256)


def test_dvisits_tables_iteration_work():
    feats = {"C": 10, "B1": 9, "B2": 9}
    work = counts.iteration_work(_cfg(512), feats, "tables")
    enc = 2 * 512 + 10 + 9 + 2 * 9
    assert work["rns_fixed_base"].products == enc * 81
    assert work["rns_he_matvec"].products == sum(
        counts.matvec_products(512, m, 22, 4) for m in (10, 9, 9, 9))
    assert work["rns_mont_exp"].products == 2 * 37 * 2 * 512


def test_roofline_takes_the_larger_bound():
    peak = {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    w = counts.Work(products=1, ops=2e12, bytes=1e9)
    assert counts.roofline_seconds(w, peak) == 2.0
    w.bytes = 3e9
    assert counts.roofline_seconds(w, peak) == 3.0
    assert not math.isnan(counts.roofline_seconds(counts.Work(), peak))


def test_peaks_cover_the_v5e():
    import json
    with open(os.path.join(benchfixtures.ROOT, "bench", "peaks.json")) as f:
        table = json.load(f)
    v5e = table["devices"]["TPU v5 lite"]
    assert v5e["int8_ops_per_s"] == 393e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in table["source"]
    assert glob.glob(os.path.join(benchfixtures.ROOT, "bench", "metrics",
                                  "*_roofline.py"))
