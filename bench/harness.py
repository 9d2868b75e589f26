"""One run of one benchmark cell: set-up, a measured window, the check.

A cell is a configuration (``<bench>/configs/*.json``, named by
``BENCHMARK.json``) under a traffic mix (``<bench>/traffic/<traffic>.json``).
The run builds the parties' data from ``--seed``, the Paillier keys and
fixed-base tables from the configuration's own seeds, and one
`VFLScheduler` over `LocalTransport` with a `PaillierBackend` on the
default compiled engine.  Set-up drives that scheduler through its first
steps, which are compared with the reference; the window then runs whole
iterations back to back on the same object until ``--seconds`` have
passed.  With ``--trace 1`` the window runs under the profiler instead,
for the traffic's ``trace_iterations`` iterations, and the per-layer
metrics are read from that trace by the readers in ``<bench>/metrics/``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from bench import counts, data, reference, traces

BENCH_DIR = "bench"
COMPARED = (0, 1, 3)         # the steps the check reads besides the last
SETUP_STEPS = COMPARED[-1]   # set-up drives the scheduler through these


class RunError(RuntimeError):
    """A run that cannot report: no chip, or an unknown cell."""


# ---------------------------------------------------------------------------
# What the files name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Spec:
    root: str
    workload: dict
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic file's contents
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def bench_dir(self) -> str:
        return os.path.join(self.root, BENCH_DIR)


def _reports(metric: dict, name: str) -> bool:
    return "workloads" not in metric or name in metric["workloads"]


def load_spec(root: str, workload: str) -> Spec:
    """Find a cell's configuration, traffic and metrics by name."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise RunError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, BENCH_DIR, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _reports(m, workload) and m["moves"] in moved]
    return Spec(root, cell, config, traffic, e2e, layer)


def load_reader(spec: Spec, name: str):
    """The `read(ctx) -> float | None` of ``<bench>/metrics/<name>.py``."""
    path = os.path.join(spec.bench_dir, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass(frozen=True)
class Seeds:
    """Every stream a run draws, derived from ``--seed``."""
    protocol: int     # VFLConfig.seed: batch order, masks, shares, triples
    data: int
    split: int
    noise: int        # the backend's encryption-noise draws

    @classmethod
    def of(cls, seed: int) -> "Seeds":
        words = np.random.SeedSequence(seed % (1 << 64)).generate_state(4)
        return cls(int(words[0] >> 1), int(words[1]), int(words[2]),
                   int(words[3]))


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------

class AnnotatedBackend:
    """Delegates to a `PaillierBackend`, opening one profiler annotation
    per protocol call so a trace can tell backend time from protocol host
    time.  Adds no synchronisation."""

    def __init__(self, inner):
        self._inner = inner
        import jax
        self._annotate = jax.profiler.TraceAnnotation

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def encrypt_share(self, *a, **kw):
        with self._annotate(traces.PREFIX + "encrypt_share"):
            return self._inner.encrypt_share(*a, **kw)

    def matvec(self, *a, **kw):
        with self._annotate(traces.PREFIX + "matvec"):
            return self._inner.matvec(*a, **kw)

    def add_mask(self, *a, **kw):
        with self._annotate(traces.PREFIX + "add_mask"):
            return self._inner.add_mask(*a, **kw)

    def decrypt_to_r64(self, *a, **kw):
        with self._annotate(traces.PREFIX + "decrypt_to_r64"):
            return self._inner.decrypt_to_r64(*a, **kw)


def vfl_config(cfg: dict, protocol_seed: int):
    from repro.core.trainer import VFLConfig
    return VFLConfig(glm=cfg["glm"], lr=cfg["lr"], max_iter=1 << 30, tol=0.0,
                     batch_size=cfg["batch_size"], f=cfg["f"], fx=cfg["fx"],
                     exp_width=cfg["exp_width"], key_bits=cfg["key_bits"],
                     he_backend="paillier", cp_selection="fixed",
                     crypto_engine="auto", seed=protocol_seed)


def party_keys(cfg: dict, noise: str) -> dict:
    """The configuration's long-lived keys, with a fixed-base noise table
    each where the traffic asks for one: {party: (key, table | None)}."""
    from repro.crypto import fixed_base, paillier
    out = {}
    for name in cfg["data"]["parties"]:
        key = paillier.keygen(cfg["key_bits"], seed=cfg["key_seeds"][name])
        table = None
        if noise == "tables":
            table = fixed_base.build_noise_table(
                key.pub.n, key.pub.mod_n2, window=cfg["table_window"],
                rho_bits=cfg["rho_bits"],
                rng=np.random.default_rng(cfg["table_seeds"][name]))
        out[name] = (key, table)
    return out


@dataclasses.dataclass
class Cell:
    sched: object
    blocks: list[tuple[str, np.ndarray]]
    y: np.ndarray
    cfg: dict
    seeds: Seeds


def build_cell(spec: Spec, seeds: Seeds, keys: dict | None = None,
               overrides: dict | None = None) -> Cell:
    """Data from the seed, keys from the configuration, one scheduler.
    `overrides` replaces configuration entries (the control's precision);
    `keys` reuses `party_keys` of an earlier cell of this configuration."""
    from repro.core import protocols
    from repro.core.trainer import PartyData
    from repro.crypto import engine as engine_mod
    from repro.runtime import LocalTransport, VFLScheduler
    cfg = {**spec.config, **(overrides or {})}
    noise = spec.traffic["noise"]
    blocks, y = data.make_parties(cfg["data"], seeds)
    keys = keys if keys is not None else party_keys(cfg, noise)
    backend = protocols.PaillierBackend(
        {nm: k for nm, (k, _) in keys.items()},
        np.random.default_rng(seeds.noise), engine=engine_mod.make("auto"))
    for nm, (_, table) in keys.items():
        if table is not None:
            backend.attach_table(nm, table)
    vcfg = vfl_config(cfg, seeds.protocol)
    sched = VFLScheduler([PartyData(nm, x) for nm, x in blocks], y, vcfg,
                         backend=AnnotatedBackend(backend),
                         transport=LocalTransport())
    return Cell(sched, blocks, y, cfg, seeds)


def trajectory(states: dict) -> dict:
    """{"losses", "w": {step: {party: weights}}} of the program's states
    at the steps the comparison reads (the last one holds every loss)."""
    last = max(states)
    return {"losses": list(states[last].losses),
            "w": {k: {nm: np.asarray(v) for nm, v in s.weights.items()}
                  for k, s in states.items()}}


def check(cell: Cell, states: dict) -> dict[str, float]:
    """The comparison numbers of `reference.compare` for the program's
    states {step: TrainState} (the steps `COMPARED` and the last)."""
    n = max(states)
    ref = reference.train(cell.blocks, cell.y, glm=cell.cfg["glm"],
                          lr=cell.cfg["lr"], batch=cell.cfg["batch_size"],
                          seed=cell.seeds.protocol, steps=n)
    return reference.compare(trajectory(states), ref, cell.cfg["lr"])


def first_steps(cell: Cell, n: int):
    """Drive the scheduler from its initial state through `n` steps;
    returns {0: s0, 1: s1, …, n: sn}."""
    state = cell.sched.init_state()
    states = {0: state}
    for i in range(1, n + 1):
        state = cell.sched.step(state)
        states[i] = state
    return states


class CompileCounter:
    """Backend compiles JAX reports, as `chip_smoke.Phase` counts them."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.count, self.seconds = 0, 0.0

        def on_event(event, duration, **_):
            if event == dispatch.BACKEND_COMPILE_EVENT:
                self.count += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(on_event)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def device_info(require_chip: bool, chips: int) -> dict:
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise RunError(f"the cell needs {chips} TPU chip(s); JAX finds "
                       f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def load_peak(spec: Spec, kind: str) -> dict:
    with open(os.path.join(spec.bench_dir, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise RunError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def measure(cell: Cell, state, seconds: float):
    """Whole iterations back to back until `seconds` have passed."""
    tp = cell.sched.transport
    b0, r0 = tp.meter.total_bytes, tp.rounds
    n = 0
    t0 = time.perf_counter()
    while True:
        state = cell.sched.step(state)
        n += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    return state, n, elapsed, tp.meter.total_bytes - b0, tp.rounds - r0


def traced(cell: Cell, state, iterations: int, log_dir: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(traces.WINDOW):
            for _ in range(iterations):
                state = cell.sched.step(state)
        elapsed = time.perf_counter() - t0
    finally:
        jax.profiler.stop_trace()
    return state, elapsed


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        *, require_chip: bool = True, persistent_cache: bool = True,
        overrides: dict | None = None, t_start: float | None = None,
        out=None, err=None) -> dict:
    """One run of one cell.  Returns the result line's object (also
    printed as the last line of `out`); raises `RunError` before any
    result when the cell cannot run here.  Tests turn off the chip check
    and JAX's persistent compile cache, and may pass `overrides` of the
    configuration (the control's precision)."""
    out = out or sys.stdout
    err = err or sys.stderr
    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_spec(root, workload)
    device = device_info(require_chip, spec.workload["chips"])
    peak = load_peak(spec, device["kind"]) if trace and require_chip \
        else None
    if persistent_cache:
        import jax
        from repro.launch import compile_cache
        compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileCounter()

    seeds = Seeds.of(seed)
    cell = build_cell(spec, seeds, overrides=overrides)
    states = first_steps(cell, SETUP_STEPS)
    state = states[max(states)]
    setup_s = time.perf_counter() - t_start
    c0 = compiles.count

    result: dict = {}
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            state, wall = traced(cell, state,
                                 spec.traffic["trace_iterations"], log_dir)
            n = spec.traffic["trace_iterations"]
            devices, notes, kind = traces.load(traces.find_xplane(log_dir),
                                               counts.KERNELS)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        summary = traces.reduce(devices, notes, kind)
        feats = {nm: x.shape[1] for nm, x in cell.blocks}
        ctx = SimpleNamespace(
            summary=summary, iterations=n, peak=peak,
            work=counts.iteration_work(spec.config, feats,
                                       spec.traffic["noise"]),
            config=spec.config, traffic=spec.traffic)
        metrics = {}
        for m in spec.per_layer:
            value = load_reader(spec, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = traces.breakdown(summary)
        attempted = n
    else:
        state, n, wall, nbytes, rounds = measure(cell, state, seconds)
        values = {"iter_s": wall / n, "setup_s": setup_s,
                  "wire_mb_per_iter": nbytes / 1e6 / n,
                  "rounds_per_iter": rounds / n}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec.end_to_end}
        attempted = n
    in_window = compiles.count - c0
    print(f"# backend compiles inside the window: {in_window} "
          f"({n} iterations, {wall:.3f} s)", file=out, flush=True)
    device["memory_peak_bytes"] = memory_peak_bytes()

    states[max(states) + n] = state
    numbers = check(cell, {k: states[k] for k in (*COMPARED, max(states))})
    limits = spec.config["limits"]
    correct = all(numbers[k] <= limits[k] for k in limits)
    for k in limits:
        print(f"check {k} = {numbers[k]!r}  limit {limits[k]!r}",
              file=err, flush=True)
    result = {"correct": bool(correct), "attempted": attempted, "failed": 0,
              "metrics": metrics, "device": device, **result,
              "check": {k: {"value": _finite(numbers[k]), "limit": limits[k]}
                        for k in limits}}
    print(json.dumps(result), file=out, flush=True)
    return result


def _finite(x: float):
    return x if math.isfinite(x) else str(x)

