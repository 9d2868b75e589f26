"""The device's idle time put down to the program's own spans
(`bench.spans`), on synthetic events and on a recorded CPU trace."""
import importlib.util
import os
from types import SimpleNamespace

import pytest

import benchfixtures
from bench import counts, spans, traces
from bench.spans import Span
from bench.traces import Event

MS = 1_000_000  # ns


def _trace():
    """A 100 ms window: device busy 10–30 and 60–80; the step spans it
    all, with a crypto call, a protocol leg, a sync, a GC pause and a
    second thread's protocol leg inside."""
    devices = [[Event("rns_mont_exp.1", 10 * MS, 20 * MS),
                Event("fusion.3", 60 * MS, 20 * MS)]]
    program = [
        Span("vfl.conductor.step", 0, 100 * MS, 0),
        Span("vfl.conductor.p3", 5 * MS, 90 * MS, 0),
        Span("vfl.crypto.encrypt", 5 * MS, 30 * MS, 0),      # 5–35
        Span("vfl.crypto.noise_draw", 31 * MS, 2 * MS, 0),   # 31–33
        Span("vfl.protocol.update", 40 * MS, 45 * MS, 0),    # 40–85
        Span("vfl.protocol.sync_grad", 50 * MS, 25 * MS, 0),  # 50–75
        Span("vfl.runtime.gc", 52 * MS, 4 * MS, 1),          # 52–56
        Span("vfl.protocol.grad_op", 86 * MS, 6 * MS, 1),    # 86–92
    ]
    return devices, program


def test_innermost_span_is_the_latest_started():
    devices, program = _trace()
    by = spans.idle_by_span(devices, program, 0, 100 * MS)
    assert by == pytest.approx({
        "vfl.conductor.step": 0.010,         # 0–5, 95–100
        "vfl.conductor.p3": 0.009,           # 35–40, 85–86, 92–95
        "vfl.crypto.encrypt": 0.008,         # 5–10 (it starts with p3,
                                             # later in the list), 30–35
                                             # less 31–33
        "vfl.crypto.noise_draw": 0.002,
        "vfl.protocol.update": 0.015,        # 40–50, 80–85
        "vfl.protocol.sync_grad": 0.006,     # 50–60 less 52–56
        "vfl.runtime.gc": 0.004,             # another thread, later start
        "vfl.protocol.grad_op": 0.006,
    })


def test_idle_categories_partition_the_idle_time():
    devices, program = _trace()
    att = spans.attribute(devices, program, 0, 100 * MS)
    assert att.idle_s == pytest.approx({
        "protocol": 0.009 + 0.015 + 0.006 + 0.006,
        "crypto_host": 0.010, "gc": 0.004, "unattributed": 0.010})
    s = traces.reduce(devices, [Event(traces.WINDOW, 0, 100 * MS)], {})
    shares = sum(att.share(c) for c in spans.CATEGORIES)
    assert abs(shares - s.idle_share) < 1e-9
    assert att.busy_s == pytest.approx(s.busy_s)
    # no program span at all: every idle instant is unattributed
    none = spans.attribute(devices, [], 0, 100 * MS)
    assert none.share("unattributed") == pytest.approx(s.idle_share)


def test_host_wait_counts_only_device_busy_time():
    devices, program = _trace()
    att = spans.attribute(devices, program, 0, 100 * MS)
    # sync_grad 50–75 overlaps the busy 60–80 for 15 ms; the 10 ms of
    # it the device is idle are the protocol's idle time, not a wait
    assert att.host_wait_s == pytest.approx(0.015)
    assert att.share("host_wait") == pytest.approx(0.15)


def test_gap_labels_prefer_the_program_span():
    devices, program = _trace()
    notes = [Event(traces.WINDOW, 0, 100 * MS),
             Event("bench.encrypt_share", 4 * MS, 32 * MS)]   # 4–36
    out = spans.gaps(devices, notes, program, 0, 100 * MS, n=3)
    # idle 30–60 (middle 45: update), 80–100 (90: grad_op), 0–10 (5:
    # the encrypt span starts after the annotation, with p3 but later
    # in the list)
    assert out == [("vfl.protocol.update", pytest.approx(0.030),
                    pytest.approx(0.030)),
                   ("vfl.protocol.grad_op", pytest.approx(0.020),
                    pytest.approx(0.080)),
                   ("vfl.crypto.encrypt", pytest.approx(0.010),
                    pytest.approx(0.0))]
    plain = spans.gaps(devices, notes, [], 0, 100 * MS, n=3)
    assert [g[0] for g in plain] == [traces.HOST_WORK, traces.HOST_WORK,
                                     "bench.encrypt_share"]


@pytest.mark.parametrize("name,category", [
    ("vfl.conductor.step", "unattributed"),
    ("vfl.conductor.capture", "protocol"),
    ("vfl.transport.pump", "protocol"),
    ("vfl.protocol.sync_loss", "protocol"),
    ("vfl.crypto.to_limbs", "crypto_host"),
    ("vfl.runtime.gc", "gc"),
    ("vfl.unknown", "unattributed"),
])
def test_span_layers(name, category):
    assert spans.category(name) == category


def _reader(name):
    path = os.path.join(benchfixtures.ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("spans_reader_" + name,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


EXISTING = ("host_protocol_share", "xla_glue_share", "rns_mont_exp_roofline",
            "rns_he_matvec_roofline", "rns_fixed_base_roofline",
            "idle_share", "iter_mfu")


def test_program_spans_leave_the_existing_readers_alone(tmp_path):
    """A trace that carries program spans: `traces.load` keeps exactly
    the harness's annotations, so the seven readers of the accepted
    benchmark read the same numbers as on the trace without them."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(traces.WINDOW):
            with jax.profiler.TraceAnnotation("vfl.conductor.step", it=4):
                with jax.profiler.TraceAnnotation("bench.matvec"):
                    with jax.profiler.TraceAnnotation(
                            "vfl.crypto.matvec", key_owner="C", rows=32):
                        f(x).block_until_ready()
    path = traces.find_xplane(str(tmp_path))
    _, notes, _ = traces.load(path, counts.KERNELS)
    program = spans.program_spans(path)
    assert sorted(n.name for n in notes) == ["bench.matvec", traces.WINDOW]
    assert sorted(s.name for s in program) == ["vfl.conductor.step",
                                               "vfl.crypto.matvec"]
    bench_only = [Event(e.name, e.start_ns, e.duration_ns)
                  for p in ProfileData.from_file(path).planes
                  if p.name == traces.HOST_PLANE
                  for line in p.lines for e in line.events
                  if e.name.startswith(traces.PREFIX)]
    assert sorted(notes, key=lambda e: e.name) == \
        sorted(bench_only, key=lambda e: e.name)
    win = [n for n in notes if n.name == traces.WINDOW][0]
    dev = [[Event("rns_he_matvec.1", win.start_ns, win.dur_ns / 3),
            Event("fusion.1", win.start_ns + win.dur_ns / 2,
                  win.dur_ns / 4)]]
    kind = {"rns_he_matvec.1": "rns_he_matvec", "fusion.1": ""}
    work = {k: counts.Work() for k in counts.KERNELS}
    for k in counts.KERNELS:
        work[k].add(100, 2048, 1e3)
    peak = {"int8_ops_per_s": 1e9, "hbm_bytes_per_s": 1e9}

    def values(ns):
        ctx = SimpleNamespace(summary=traces.reduce(dev, ns, kind),
                              iterations=1, peak=peak, work=work)
        return {m: _reader(m)(ctx) for m in EXISTING}

    assert values(notes) == values(bench_only)
    assert values(notes)["idle_share"] is not None
