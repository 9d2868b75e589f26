"""Bring-up check on one TPU: the paper's 1024-bit Paillier path, end to end.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the mesh-sharded HE engine only

Runs in one process (a chip belongs to one process) from the root of a
checkout, and puts ``src`` on the path itself.  Phases, one line each with
wall time, compile time and verdict:

* device      — JAX sees a TPU, and the default crypto engine is the
                compiled ``pallas`` backend on the RNS route at n².
* kernels     — Montgomery product, ladder, windowed HE matvec (window 4)
                and fixed-base exponentiation at 1024-bit keys, bit for
                bit against the `crypto.bigint` oracle.
* train       — Algorithm 1 (`VFLScheduler.run`) with real Paillier at the
                `VFLConfig` defaults on credit data 30000×24 split over C
                and B1, 3 iterations: losses equal the mock-HE run's to
                1e-6 and track `train_centralized` to 5e-3.
* score       — `VFLScoringEngine` over the trained actors: 64 served
                predictions equal the one-shot scorer bit for bit.

With ``--chips 4`` it runs only the sharded path and what it is compared
with: `ShardedCryptoEngine` over the four chips against the single-device
engine, on the kernel phase and on 2 training iterations (losses and
weights bit-identical).

Any failed check raises, and the script exits non-zero.  It also exits
non-zero, printing no result, when JAX finds no TPU.  The last line of a
passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
KEY_BITS = 1024                  # the paper's deployment (VFLConfig default)
N_ELEMS = 256                    # elements per kernel parity check
MATVEC_ROWS, MATVEC_COLS, MATVEC_WIDTH = 512, 12, 22
N_SCORED = 64


class Phase:
    """Times one phase: wall clock around work that ends on the host
    (every check reads results back), and the backend compile time JAX
    reports while the phase runs."""

    compile_s = 0.0

    @classmethod
    def listen(cls):
        import jax
        from jax._src import dispatch

        def on_event(event, duration, **_):
            if event == dispatch.BACKEND_COMPILE_EVENT:
                cls.compile_s += duration

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), Phase.compile_s
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            wall = time.perf_counter() - self.t0
            comp = Phase.compile_s - self.c0
            print(f"[{self.name}] ok  wall_s={wall:.3f}  "
                  f"compile_s={comp:.3f}  {getattr(self, 'note', '')}",
                  flush=True)
        return False


def _limbs(ints, L):
    import jax.numpy as jnp
    from repro.crypto import bigint
    return jnp.asarray(bigint.ints_to_limbs(ints, L))


def _same(name, got, want):
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = int((got != want).any(axis=-1).sum()) \
            if got.shape == want.shape else "shape"
        raise AssertionError(f"{name}: differs from the reference "
                             f"({bad} rows; shapes {got.shape} vs "
                             f"{want.shape})")


def phase_device(want_count: int, cache_dir: str):
    import jax
    from repro.crypto import engine as engine_mod, paillier
    with Phase("device") as ph:
        devs = jax.devices()
        if devs[0].platform != "tpu":
            raise SystemExit("chip_smoke.py: JAX finds no TPU "
                             f"(platform {devs[0].platform!r})")
        if len(devs) < want_count:
            raise SystemExit(f"chip_smoke.py: {want_count} chips asked "
                             f"for, JAX sees {len(devs)}")
        eng = engine_mod.get_engine()
        assert eng.backend == "pallas", eng.backend
        key = paillier.keygen(KEY_BITS, seed=1)
        route = eng._route(key.pub.mod_n2)
        assert route == "rns", route
        ph.note = (f"kind={devs[0].device_kind}  count={len(devs)}  "
                   f"engine={eng.backend}  route@n2={route}  "
                   f"compile_cache={cache_dir}")
    return devs, key


def phase_kernels(key, engine, label="kernels"):
    """Every engine op of the Paillier hot path at n² of a 1024-bit key,
    bit for bit against the bigint library."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import protocols
    from repro.crypto import bigint, fixed_base
    pub = key.pub
    mod = pub.mod_n2
    rng = np.random.default_rng(5)
    with Phase(label) as ph:
        nbytes = mod.value.bit_length() // 8 + 8
        a = _limbs([int.from_bytes(rng.bytes(nbytes), "little") % mod.value
                    for _ in range(N_ELEMS)], mod.L)
        b = _limbs([int.from_bytes(rng.bytes(nbytes), "little") % mod.value
                    for _ in range(N_ELEMS)], mod.L)
        _same("mont_mul", engine.mont_mul(a, b, mod),
              bigint.mont_mul(a, b, mod))

        # encryption-noise ladder r^n: the n-bit exponent of a real key
        bits = jnp.asarray(bigint.int_to_bits(pub.n, pub.n.bit_length()))
        _same("ladder", engine.mont_exp_bits(a, bits, mod),
              bigint.mont_exp_bits(a, bits, mod))

        cts = jnp.concatenate([a, b])[:MATVEC_ROWS]
        exps = rng.integers(0, 1 << MATVEC_WIDTH,
                            size=(MATVEC_ROWS, MATVEC_COLS), dtype=np.uint32)
        digits = protocols.window_digits(exps, MATVEC_WIDTH, 4)
        _same("he_matvec_windowed",
              engine.he_matvec_windowed(cts, digits, mod, 4),
              protocols.he_matvec(pub, cts, jnp.asarray(exps), MATVEC_WIDTH,
                                  4, engine=_library_engine()))

        table = fixed_base.build_noise_table(pub.n, mod,
                                             rng=np.random.default_rng(6))
        exps_rho = [int.from_bytes(rng.bytes(40), "little")
                    for _ in range(N_ELEMS)]
        rho_digits = fixed_base.exp_digits(exps_rho, table.levels,
                                           table.window)
        h_mont = bigint.to_mont(_limbs([table.base], mod.L), mod)
        rho_bits = jnp.asarray(np.stack(
            [bigint.int_to_bits(e, 8 * 40) for e in exps_rho]))
        _same("fixed_base_exp", engine.fixed_base_exp(table, rho_digits, mod),
              bigint.mont_exp_bits(h_mont, rho_bits, mod))
        ph.note = (f"n2_bits={mod.value.bit_length()}  elems={N_ELEMS}  "
                   f"matvec={MATVEC_ROWS}x{MATVEC_COLS}@w4  "
                   f"fixed_base_levels={table.levels}  vs=bigint")


def _library_engine():
    from repro.crypto import engine as engine_mod
    return engine_mod.CryptoEngine(backend="jnp", pipeline="cios")


def _credit_parties():
    from repro.core.trainer import PartyData
    from repro.data import synthetic, vertical
    X, y = synthetic.credit_default(n=30000, d=24)
    parts = vertical.split_columns(X, 2)
    return X, y, [PartyData(name=nm, X=p) for nm, p in zip(["C", "B1"], parts)]


def phase_train():
    import numpy as np
    from repro.core import trainer
    from repro.runtime import VFLScheduler
    X, y, parties = _credit_parties()
    cfg = trainer.VFLConfig(max_iter=3, tol=0.0, he_backend="paillier")
    with Phase("train") as ph:
        sched = VFLScheduler(parties, y, cfg)
        res = sched.run()
        mock = trainer.train_vfl(
            parties, y, trainer.VFLConfig(**{**cfg.__dict__,
                                             "he_backend": "mock"}))
        _, cent = trainer.train_centralized(X, y, cfg)
        assert len(res.losses) == cfg.max_iter, res.losses
        np.testing.assert_allclose(res.losses, mock.losses, atol=1e-6)
        np.testing.assert_allclose(res.losses, cent, atol=5e-3)
        ph.note = (f"iters={res.n_iter}  key_bits={cfg.key_bits}  "
                   f"batch={cfg.batch_size}  "
                   f"losses={[round(v, 6) for v in res.losses]}  "
                   f"max|paillier-mock|="
                   f"{max(abs(a - b) for a, b in zip(res.losses, mock.losses)):.2e}  "
                   f"max|paillier-centralized|="
                   f"{max(abs(a - b) for a, b in zip(res.losses, cent)):.2e}")
    return parties, sched, res


def phase_score(parties, sched, res):
    import numpy as np
    from repro.serve import VFLScoringEngine
    with Phase("score") as ph:
        want = sched.model.predict(res.predict_wx(parties))[:N_SCORED]
        eng = VFLScoringEngine(sched.parties)
        for i in range(N_SCORED):
            eng.submit({p.name: p.X[i] for p in parties})
        done = sorted(eng.run(), key=lambda r: r.rid)
        got = np.array([r.prediction for r in done])
        _same("served predictions", got, want)
        ph.note = f"requests={N_SCORED}  vs=model.predict(res.predict_wx)"


def phase_sharded(key, devs, backend="pallas"):
    """ShardedCryptoEngine over four chips ≡ the single-device engine."""
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import protocols, trainer
    from repro.crypto import bigint, engine as engine_mod, paillier
    from repro.distributed.he_sharding import ShardedCryptoEngine
    mesh = Mesh(np.array(devs[:4]), ("data",))
    sharded = ShardedCryptoEngine(backend=backend, mesh=mesh)
    single = engine_mod.CryptoEngine(backend=backend)
    mod = key.pub.mod_n2
    one = bigint.mont_one(mod)[None].repeat(8, axis=0)
    placed = sharded.mont_mul(one, one, mod).sharding.device_set
    assert placed == set(devs[:4]), f"shards landed on {sorted(placed)}"
    phase_kernels(key, sharded, label="kernels-sharded4")

    X, y, parties = _credit_parties()
    cfg = trainer.VFLConfig(max_iter=2, tol=0.0, he_backend="paillier")

    def run_with(engine):
        rng = np.random.default_rng(cfg.seed + 90001)
        keys = {p.name: paillier.keygen(cfg.key_bits,
                                        seed=int(rng.integers(2**31)))
                for p in parties}
        backend = protocols.PaillierBackend(keys, rng, engine=engine)
        return trainer.train_vfl(parties, y, cfg, backend=backend)

    with Phase("train-single") as ph:
        ref = run_with(single)
        ph.note = f"iters={ref.n_iter}  losses={ref.losses}"
    with Phase("train-sharded4") as ph:
        res = run_with(sharded)
        assert res.losses == ref.losses, (res.losses, ref.losses)
        for name in ref.weights:
            _same(f"weights[{name}]", res.weights[name], ref.weights[name])
        ph.note = (f"mesh=data:{mesh.shape['data']}  losses and weights "
                   "bit-identical to single-device")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded HE engine phase")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(REPO, "src", "repro")):
        print("chip_smoke.py: run it from a checkout of the repository "
              "(src/repro not found)", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    Phase.listen()

    devs, key = phase_device(args.chips, cache_dir)
    if args.chips == 4:
        phase_sharded(key, devs)
    else:
        phase_kernels(key, _default_engine())
        parties, sched, res = phase_train()
        phase_score(parties, sched, res)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


def _default_engine():
    from repro.crypto import engine as engine_mod
    return engine_mod.get_engine()


if __name__ == "__main__":
    sys.exit(main())
