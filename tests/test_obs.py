"""The program's spans and counters (`repro.obs`): one toy Poisson k=3
training step under the profiler names every layer's work, nests it in
the step, carries only identifier and shape arguments, and counts the
same engine calls and host→device bytes as the step before it."""
import gc
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.core import protocols
from repro.core.trainer import PartyData, VFLConfig
from repro.crypto import engine as engine_mod
from repro.crypto import paillier
from repro.crypto.ring import R64
from repro.runtime import LocalTransport, VFLScheduler

PARTIES = ("C", "B1", "B2")

#: every span one sequential k=3 Poisson step opens
STEP_SPANS = {
    "vfl.conductor.step", "vfl.conductor.begin", "vfl.conductor.p1",
    "vfl.conductor.p2", "vfl.conductor.p3", "vfl.conductor.p4",
    "vfl.conductor.flags", "vfl.conductor.capture",
    "vfl.conductor.sync_jkey",
    "vfl.protocol.share_z", "vfl.protocol.share_y", "vfl.protocol.share_ez",
    "vfl.protocol.ez_chain", "vfl.protocol.grad_op", "vfl.protocol.loss",
    "vfl.protocol.enc_d", "vfl.protocol.masked_grad",
    "vfl.protocol.decrypt_unmask", "vfl.protocol.update",
    "vfl.protocol.sync_grad", "vfl.protocol.sync_loss",
    "vfl.protocol.sync_dealer",
    "vfl.transport.pump",
    "vfl.crypto.encrypt", "vfl.crypto.matvec", "vfl.crypto.add_mask",
    "vfl.crypto.decrypt", "vfl.crypto.noise_draw", "vfl.crypto.noise_exp",
    "vfl.crypto.to_limbs", "vfl.crypto.from_limbs",
}


def _host_spans(log_dir):
    """[(name, start, end, {arg: value}, thread)] of the vfl.* events."""
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("vfl."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                {k: v for k, v in e.stats}, i))
    return out


def _delta(a, b):
    return {"engine_calls": {k: v - a["engine_calls"].get(k, 0)
                             for k, v in b["engine_calls"].items()},
            "h2d_bytes": b["h2d_bytes"] - a["h2d_bytes"]}


@pytest.fixture(scope="module")
def toy_steps(tmp_path_factory):
    """Three steps of a 256-bit Paillier k=3 Poisson scheduler: the
    first compiles, the second and third are counted, the third runs
    under the profiler."""
    rng = np.random.default_rng(0)
    widths = (3, 2, 2)
    X = rng.normal(size=(160, sum(widths))) * 0.3
    y = rng.poisson(1.0, size=160).astype(np.float64)
    cols = np.cumsum((0,) + widths)
    parties = [PartyData(nm, X[:, cols[i]:cols[i + 1]])
               for i, nm in enumerate(PARTIES)]
    cfg = VFLConfig(glm="poisson", lr=0.1, max_iter=10, tol=0.0,
                    batch_size=32, key_bits=256, he_backend="paillier",
                    cp_selection="fixed", seed=3)
    keys = {nm: paillier.keygen(256, seed=40 + i)
            for i, nm in enumerate(PARTIES)}
    backend = protocols.PaillierBackend(
        keys, np.random.default_rng(1),
        engine=engine_mod.CryptoEngine(backend="jnp", pipeline="rns"))
    sched = VFLScheduler(parties, y, cfg, backend=backend,
                         transport=LocalTransport())
    state = sched.step(sched.init_state())
    c1 = obs.snapshot()
    state = sched.step(state)
    c2 = obs.snapshot()
    log_dir = str(tmp_path_factory.mktemp("obs_trace"))
    jax.profiler.start_trace(log_dir)
    try:
        state = sched.step(state)
    finally:
        jax.profiler.stop_trace()
    c3 = obs.snapshot()
    return {"spans": _host_spans(log_dir), "deltas": (_delta(c1, c2),
                                                      _delta(c2, c3)),
            "batch": cfg.batch_size}


def test_step_opens_every_span(toy_steps):
    names = {s[0] for s in toy_steps["spans"]}
    assert STEP_SPANS <= names, sorted(STEP_SPANS - names)
    assert {n.split(".")[1] for n in names} <= set(obs.LAYERS)


def test_spans_nest_in_the_step(toy_steps):
    spans = toy_steps["spans"]
    step, = [s for s in spans if s[0] == "vfl.conductor.step"]

    def inside(s, outer):
        return outer[1] <= s[1] and s[2] <= outer[2]

    legs = [s for s in spans if s[0].split(".")[1] in ("protocol",
                                                       "conductor")]
    for s in spans:
        if s[0] == "vfl.runtime.gc" or s is step:
            continue
        assert inside(s, step), s[0]
        if s[0].startswith("vfl.crypto."):
            assert any(inside(s, o) for o in legs), s[0]


def test_span_arguments_are_identifiers_and_shapes(toy_steps):
    for name, _, _, args, _ in toy_steps["spans"]:
        assert set(args) <= obs.SPAN_ARGS, (name, args)
        for k, v in args.items():
            if k in ("party", "key_owner"):
                assert v in PARTIES, (name, k, v)
            else:
                assert isinstance(v, int) and 0 <= v <= 10 * \
                    toy_steps["batch"], (name, k, v)


def test_span_refuses_other_arguments():
    with pytest.raises(ValueError):
        obs.span("vfl.protocol.update", weight=1.0)


def test_identical_steps_count_the_same(toy_steps):
    a, b = toy_steps["deltas"]
    assert a == b
    assert a["h2d_bytes"] > 0
    assert {"mont_mul", "mont_exp_bits", "he_matvec_windowed", "to_mont",
            "decrypt_crt"} <= set(a["engine_calls"])


def test_gc_collection_is_a_span(tmp_path):
    before = obs.snapshot()
    jax.profiler.start_trace(str(tmp_path))
    try:
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    after = obs.snapshot()
    gcs = [s for s in _host_spans(str(tmp_path)) if s[0] == "vfl.runtime.gc"]
    assert any(s[3].get("gen") == 2 for s in gcs)
    assert after["gc_collections"][2] > before["gc_collections"].get(2, 0)
    assert after["gc_pause_s"][2] > before["gc_pause_s"].get(2, 0.0)


def test_engine_entries_count_once_each():
    eng = engine_mod.CryptoEngine(backend="jnp", pipeline="cios")
    key = paillier.keygen(128, seed=5)
    mod = key.pub.mod_n2
    x = np.zeros((2, mod.L), np.uint32)
    x[:, 0] = (3, 5)
    x = jnp.asarray(x)
    before = obs.snapshot()["engine_calls"]
    eng.from_mont(eng.to_mont(x, mod), mod)
    after = obs.snapshot()["engine_calls"]
    moved = {k: v - before.get(k, 0) for k, v in after.items()
             if v != before.get(k, 0)}
    assert moved == {"to_mont": 1, "from_mont": 1}


def test_crt_decryption_counts_one_call_per_launch():
    """A CRT decryption is one compiled program: each call, the first
    (which traces it) included, counts one `decrypt_crt` and nothing
    else, neither the engine entries nor the key constants inside."""
    eng = engine_mod.CryptoEngine(backend="jnp", pipeline="cios")
    key = paillier.keygen(128, seed=21)
    c = paillier.encrypt(key.pub, paillier.encode_ints(key.pub, [7, 8]),
                         rng=np.random.default_rng(0))
    for _ in range(3):
        before = obs.snapshot()
        paillier.decrypt_crt(key, c, engine=eng)
        after = obs.snapshot()
        assert _delta(before, after) == {
            "engine_calls": {op: int(op == "decrypt_crt")
                             for op in after["engine_calls"]},
            "h2d_bytes": 0}


def test_transfers_count_host_bytes_only():
    before = obs.snapshot()["h2d_bytes"]
    dev = obs.to_device(np.arange(6, dtype=np.uint32))
    assert isinstance(dev, jax.Array) and dev.dtype == jnp.uint32
    again = obs.to_device(dev, jnp.uint32)
    assert again is dev
    assert obs.snapshot()["h2d_bytes"] - before == 24
    host = obs.to_host(R64(dev, dev + 1), "test", "protocol")
    assert isinstance(host, R64) and isinstance(host.lo, np.ndarray)
    np.testing.assert_array_equal(host.lo, np.arange(1, 7))


def _jit_events(events):
    """Feed JAX's stage events to the listeners: ("start", stage) opens
    one, (stage, seconds) closes it, ("cache", seconds) is a cache read."""
    names = {v: k for k, v in obs.JIT_STAGES.items()}
    for kind, arg in events:
        if kind == "start":
            obs._on_jit_start(names[arg], 0.0, fun_name="f")
        elif kind == "cache":
            obs._on_jit_duration(obs._CACHE_LOAD, arg)
        else:
            obs._on_jit_duration(names[kind], arg, fun_name="f")


def _jit_delta(before, after):
    return {k: round(v - before.get(k, 0.0), 9)
            for k, v in after.items() if v != before.get(k, 0.0)}


@pytest.mark.parametrize("events, booked", [
    # an eager op compiled while a function traces: all of it is tracing
    ([("start", "trace"), ("start", "compile"), ("compile", 0.5),
      ("trace", 2.0)], {"trace": 2.0}),
    # a persistent-cache hit inside a backend compile
    ([("start", "compile"), ("cache", 0.25), ("compile", 0.75)],
     {"compile": 0.5, "cache_load": 0.25}),
    ([("start", "trace"), ("trace", 1.0), ("start", "lower"),
      ("lower", 0.5), ("start", "compile"), ("compile", 3.0)],
     {"trace": 1.0, "lower": 0.5, "compile": 3.0}),
])
def test_jit_seconds_count_each_second_once(events, booked):
    before = obs.snapshot()["jit_s"]
    _jit_events(events)
    assert _jit_delta(before, obs.snapshot()["jit_s"]) == booked


def test_a_jit_counts_its_stages():
    before = obs.snapshot()["jit_s"]
    jax.jit(lambda x: jnp.cos(x) * 3)(jnp.arange(5.0)).block_until_ready()
    moved = _jit_delta(before, obs.snapshot()["jit_s"])
    assert {"trace", "lower", "compile"} <= set(moved)
    assert all(v > 0 for v in moved.values())


def test_key_and_table_builds_are_counted_once(tmp_path):
    before = obs.snapshot()["keys_s"]
    jax.profiler.start_trace(str(tmp_path))
    try:
        paillier.keygen(256, seed=9, table_path=str(tmp_path / "t.npz"))
    finally:
        jax.profiler.stop_trace()
    spent = obs.snapshot()["keys_s"] - before
    spans = [s for s in _host_spans(str(tmp_path))
             if s[0] in ("vfl.crypto.keygen", "vfl.crypto.table_build")]
    (keygen,), (table,) = ([s for s in spans if s[0] == n] for n in
                           ("vfl.crypto.keygen", "vfl.crypto.table_build"))
    assert keygen[1] <= table[1] and table[2] <= keygen[2]
    # the table is built inside keygen and counted once, with it
    assert spent <= (keygen[2] - keygen[1]) / 1e9 + 0.05
    assert spent >= (table[2] - table[1]) / 1e9
