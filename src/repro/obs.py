"""Spans and counters for observing a run from inside the program.

Spans are `jax.profiler.TraceAnnotation`s, so they land on the
profiler's host plane on the same clock as the device's operations.
Each is named ``vfl.<layer>.<what>``, with ``<layer>`` one of `LAYERS`:

* ``conductor``  the scheduler's phases (`runtime/scheduler.py`);
* ``protocol``   the parties' protocol legs and the joint share math;
* ``transport``  message delivery (`runtime/transport.py`);
* ``crypto``     the Paillier backend's calls and their host steps;
* ``runtime``    the interpreter itself (garbage collection).

A span costs well under a microsecond when no profiler is running and
is recorded only while one is, so spans are always emitted.  Their
arguments are restricted to `SPAN_ARGS`: iteration numbers, party
names and shapes.  A span never carries a share, mask, key, feature,
weight, loss or any value derived from them.

Counters are plain numbers in one module-level table; `snapshot()`
returns a copy and a reader takes the difference of two snapshots:

* ``engine_calls[op]``  calls of a `CryptoEngine` hot-path entry (one
  kernel launch and its glue on the chip route), and of
  `paillier.decrypt_crt` (one compiled program);
* ``share_calls[op]``   calls of a compiled share-ring program
  (`crypto.ring.share_program`: a Z_2^64 op, the dealer's triple draw,
  a share split) made from the host; one traced into another program
  counts nothing;
* ``h2d_bytes``         bytes of host arrays the hot path turned into
  device arrays (`to_device`);
* ``gc_pause_s[gen]``, ``gc_collections[gen]``  time in and number of
  the interpreter's garbage collections, per generation;
* ``jit_s[stage]``      seconds JAX spent making compiled programs, by
  stage (``trace``, ``lower``, ``compile``: `JIT_STAGES`; and
  ``cache_load``), from its own duration events.  Stages nest (an eager op compiled
  while a function traces, a cache read inside a backend compile), so a
  second is booked once, to the outermost stage open on its thread,
  except a persistent-cache read, which is booked to ``cache_load`` and
  taken out of the stage around it;
* ``keys_s``            seconds in `paillier.keygen` and
  `fixed_base.build_noise_table` (spans ``vfl.crypto.keygen`` and
  ``vfl.crypto.table_build``), once where one runs inside the other.

Inside `uncounted()`, which wraps the body of a compiled program while
it is traced, the first three count nothing: that body runs once per
trace, not per launch, and the launch is counted by its caller.
"""
from __future__ import annotations

import contextlib
import gc
import threading
import time

import jax
import numpy as np

LAYERS = ("conductor", "protocol", "transport", "crypto", "runtime")
SPAN_ARGS = frozenset({"it", "party", "key_owner", "rows", "cols", "tag",
                       "gen"})

_lock = threading.Lock()
_counters: dict = {"engine_calls": {}, "share_calls": {}, "h2d_bytes": 0,
                   "gc_pause_s": {}, "gc_collections": {}, "jit_s": {},
                   "keys_s": 0.0}
_uncounted = threading.local()


def span(name: str, **args):
    """A profiler host span ``name`` (``vfl.<layer>.<what>``) with
    identifier and shape arguments only (`SPAN_ARGS`)."""
    if not SPAN_ARGS.issuperset(args):
        raise ValueError(f"span arguments {sorted(set(args) - SPAN_ARGS)} "
                         f"are not among {sorted(SPAN_ARGS)}")
    return jax.profiler.TraceAnnotation(name, **args)


def snapshot() -> dict:
    """A copy of every counter."""
    with _lock:
        return {k: dict(v) if isinstance(v, dict) else v
                for k, v in _counters.items()}


@contextlib.contextmanager
def uncounted():
    """Count no engine call, share call or transfer on this thread
    inside."""
    prev = _counting()
    _uncounted.on = True
    try:
        yield
    finally:
        _uncounted.on = not prev


def _counting() -> bool:
    return not getattr(_uncounted, "on", False)


def count_engine_call(op: str) -> None:
    if not _counting():
        return
    with _lock:
        calls = _counters["engine_calls"]
        calls[op] = calls.get(op, 0) + 1


def count_share_call(op: str, probe) -> None:
    """Count a share-ring program call; `probe`, one array argument of
    the call, is a tracer when the call is traced into another program,
    which launches it, and then nothing is counted."""
    if not _counting() or isinstance(probe, jax.core.Tracer):
        return
    with _lock:
        calls = _counters["share_calls"]
        calls[op] = calls.get(op, 0) + 1


def to_device(x, dtype=None) -> jax.Array:
    """`jnp.asarray(x, dtype)` for the hot path: a host array goes to
    the device by an explicit transfer whose bytes are counted; a device
    array only changes dtype where asked."""
    if isinstance(x, jax.Array):
        return x if dtype is None or x.dtype == dtype else x.astype(dtype)
    out = jax.device_put(np.asarray(x, dtype))
    if _counting():
        with _lock:
            _counters["h2d_bytes"] += out.nbytes
    return out


def to_host(x, site: str, layer: str):
    """Read device arrays (a pytree) back to NumPy inside the span
    ``vfl.<layer>.sync_<site>``: the host's wait for the device gets a
    name."""
    with jax.profiler.TraceAnnotation(f"vfl.{layer}.sync_{site}"):
        return jax.device_get(x)


# -- garbage collection --------------------------------------------------
# Collections never overlap (the interpreter runs one at a time, and
# "start" and "stop" come on the collecting thread), so one open slot is
# enough and the callback takes no lock: a collection that starts while
# another thread holds `_lock` must not wait for it.

_gc_open: list = []


def _on_gc(phase: str, info: dict) -> None:
    gen = info.get("generation", -1)
    if phase == "start":
        ann = jax.profiler.TraceAnnotation("vfl.runtime.gc", gen=gen)
        ann.__enter__()
        _gc_open.append((ann, time.perf_counter()))
        return
    if not _gc_open:
        return
    ann, t0 = _gc_open.pop()
    ann.__exit__(None, None, None)
    pause, n = _counters["gc_pause_s"], _counters["gc_collections"]
    pause[gen] = pause.get(gen, 0.0) + (time.perf_counter() - t0)
    n[gen] = n.get(gen, 0) + 1


gc.callbacks.append(_on_gc)


# -- compilation -----------------------------------------------------------
# JAX opens each stage with a scalar event (its start time) and closes it
# with a duration event, on the thread that compiles; a persistent-cache
# read reports only its duration, from inside a backend compile.

JIT_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_jit = threading.local()


def _jit_state():
    if not hasattr(_jit, "open"):
        _jit.open, _jit.held = [], 0.0
    return _jit


def _on_jit_start(event: str, _value, **_) -> None:
    if event in JIT_STAGES:
        _jit_state().open.append(event)


def _on_jit_duration(event: str, duration: float, **_) -> None:
    if event == _CACHE_LOAD:
        _book_jit("cache_load", duration)
        _jit_state().held += duration
        return
    if event not in JIT_STAGES:
        return
    st = _jit_state()
    if st.open:
        st.open.pop()
    if not st.open:
        _book_jit(JIT_STAGES[event], duration - st.held)
        st.held = 0.0


def _book_jit(stage: str, seconds: float) -> None:
    with _lock:
        jit_s = _counters["jit_s"]
        jit_s[stage] = jit_s.get(stage, 0.0) + seconds


jax.monitoring.register_scalar_listener(_on_jit_start)
jax.monitoring.register_event_duration_secs_listener(_on_jit_duration)


# -- keys and tables -------------------------------------------------------

_keys = threading.local()


@contextlib.contextmanager
def key_setup(what: str):
    """The span ``vfl.crypto.<what>`` around building a key or a table,
    its seconds added to ``keys_s`` unless it runs inside another
    (`paillier.keygen` may build the key's noise table)."""
    depth = getattr(_keys, "depth", 0)
    _keys.depth = depth + 1
    t0 = time.perf_counter()
    try:
        with span(f"vfl.crypto.{what}"):
            yield
    finally:
        _keys.depth = depth
        if depth == 0:
            with _lock:
                _counters["keys_s"] += time.perf_counter() - t0
