"""Mesh-sharded HE engine: `ShardedCryptoEngine` must be bit-exact
against the single-device engine on every hot-path op, on a real
multi-device CPU mesh (forced host devices — subprocess, so the device
count can't leak into other tests' jax state)."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.crypto import engine as engine_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}


def test_sharded_engine_requires_mesh():
    from repro.distributed.he_sharding import ShardedCryptoEngine
    with pytest.raises(ValueError):
        ShardedCryptoEngine(backend="jnp")


def test_unsharded_engine_mesh_knob_inert():
    """mesh=None (and shard_batch=False) keep the single-device routing;
    `sharded` only flips on a real multi-device axis."""
    eng = engine_mod.CryptoEngine(backend="jnp")
    assert not eng.sharded
    assert eng.single_device() is eng

    class OneDevMesh:
        shape = {"data": 1}

    assert not engine_mod.CryptoEngine(backend="jnp",
                                       mesh=OneDevMesh()).sharded

    class TwoDevMesh:
        shape = {"data": 2}

    assert engine_mod.CryptoEngine(backend="jnp", mesh=TwoDevMesh()).sharded
    assert not engine_mod.CryptoEngine(backend="jnp", mesh=TwoDevMesh(),
                                       shard_batch=False).sharded

    class WrongAxisMesh:
        shape = {"batch": 2}

    with pytest.raises(ValueError, match="no axis"):
        engine_mod.CryptoEngine(backend="jnp", mesh=WrongAxisMesh()).sharded
    with pytest.raises(ValueError, match="no axis"):
        from repro.distributed.he_sharding import ShardedCryptoEngine
        ShardedCryptoEngine(backend="jnp", mesh=WrongAxisMesh())


def test_sharded_engine_bit_exact_multidevice():
    """All sharded ops ≡ single-device engine on a 4-device CPU mesh:
    mont_mul, the constant-time ladder (incl. the shared-exponent
    decrypt pattern), the windowed HE matvec via `protocols.he_matvec`
    for both jnp and pallas-interpret backends (odd row counts exercise
    the pad path; `modmul_reduce` ⊕-combines the partials), and a full
    Paillier encrypt → matvec → CRT-decrypt roundtrip."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.crypto import bigint, paillier
from repro.crypto.bigint import Modulus
from repro.crypto import engine as engine_mod
from repro.distributed.he_sharding import (ShardedCryptoEngine,
                                           make_sharded_engine)
from repro.core import protocols

mesh = jax.make_mesh((4,), ("data",))
n = (1 << 61) - 1
mod = Modulus.make(n)
rng = np.random.default_rng(0)
vals = [int(v) % n for v in rng.integers(1, 1 << 60, size=7)]
A = jnp.asarray(bigint.ints_to_limbs(vals, mod.L))
eng1 = engine_mod.CryptoEngine(backend="jnp")
engS = ShardedCryptoEngine(backend="jnp", mesh=mesh)
got = engS.mont_mul(A, A, mod)
np.testing.assert_array_equal(np.asarray(got),
                              np.asarray(eng1.mont_mul(A, A, mod)))
# the shards land on all four devices, not all on the first
assert got.sharding.device_set == set(jax.devices()), got.sharding
# jax.make_mesh gives Explicit axes; an Auto-axis mesh works the same
meshA = jax.make_mesh((4,), ("data",),
                      axis_types=(jax.sharding.AxisType.Auto,))
np.testing.assert_array_equal(
    np.asarray(ShardedCryptoEngine(backend="jnp", mesh=meshA)
               .mont_mul(A, A, mod)), np.asarray(got))
Am =bigint.to_mont(A, mod)
bits = jnp.asarray(np.stack(
    [bigint.int_to_bits(int(e), 16)
     for e in rng.integers(0, 1 << 16, size=7)]))
np.testing.assert_array_equal(
    np.asarray(engS.mont_exp_bits(Am, bits, mod)),
    np.asarray(eng1.mont_exp_bits(Am, bits, mod)))
shared = jnp.asarray(bigint.int_to_bits(0xBEEF, 16))
np.testing.assert_array_equal(
    np.asarray(engS.mont_exp_bits(Am, shared, mod)),
    np.asarray(eng1.mont_exp_bits(Am, shared, mod)))

key = paillier.keygen(128, seed=1)
pub = key.pub
msgs = [int(v) for v in rng.integers(0, 1 << 16, size=6)]
cts = paillier.encrypt(pub, paillier.encode_ints(pub, msgs), rng=rng)
exps = jnp.asarray(rng.integers(0, 1 << 22, size=(6, 3), dtype=np.uint32))
want = protocols.he_matvec(pub, cts, exps, 22)
got_jnp = protocols.he_matvec(pub, cts, exps, 22, engine=engS)
np.testing.assert_array_equal(np.asarray(got_jnp), np.asarray(want))
engK = make_sharded_engine(mesh, "pallas-interpret", chunk_n=2, tile_m=2)
got_pal = protocols.he_matvec(pub, cts, exps, 22, engine=engK)
np.testing.assert_array_equal(np.asarray(got_pal), np.asarray(want))
w1 = protocols.he_matvec(pub, cts, exps[:, :2] & 0x3FF, 10, window=1)
g1 = protocols.he_matvec(pub, cts, exps[:, :2] & 0x3FF, 10, window=1,
                         engine=engS)
np.testing.assert_array_equal(np.asarray(g1), np.asarray(w1))

m = paillier.encode_ints(pub, msgs)
c_s = paillier.encrypt(pub, m, rng=np.random.default_rng(7), engine=engS)
c_1 = paillier.encrypt(pub, m, rng=np.random.default_rng(7))
np.testing.assert_array_equal(np.asarray(c_s), np.asarray(c_1))
np.testing.assert_array_equal(
    np.asarray(paillier.decrypt_crt(key, c_1, engine=engS)),
    np.asarray(paillier.decrypt_crt(key, c_1)))
assert paillier.decode_ints(
    np.asarray(paillier.decrypt_crt(key, got_jnp, engine=engS))) == \
    paillier.decode_ints(np.asarray(paillier.decrypt_crt(key, want)))
print("HE_SHARDING_OK")
"""
    r = subprocess.run([sys.executable, "-c", script], env=ENV,
                       capture_output=True, text=True, cwd=REPO)
    assert "HE_SHARDING_OK" in r.stdout, (r.stdout[-1500:], r.stderr[-3000:])


@pytest.mark.slow
def test_sharded_engine_end_to_end_training():
    """Algorithm 1 end-to-end with a mesh-sharded Paillier backend (2
    fake devices): bit-identical losses and weights vs the single-device
    engine."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, numpy as np
from repro.core import trainer, protocols
from repro.crypto import paillier
from repro.data import synthetic, vertical
from repro.distributed.he_sharding import ShardedCryptoEngine

X, y = synthetic.credit_default(n=40, d=4, seed=3)
parts = vertical.split_columns(X, 2)
parties = [trainer.PartyData(name=nm, X=p)
           for nm, p in zip(["C", "B1"], parts)]
cfg = trainer.VFLConfig(glm="logistic", lr=0.1, max_iter=1, batch_size=16,
                        he_backend="paillier", key_bits=192, tol=0.0,
                        seed=2)

def backend_with(engine):
    rng = np.random.default_rng(cfg.seed + 90001)
    keys = {p: paillier.keygen(cfg.key_bits,
                               seed=int(rng.integers(2**31)))
            for p in ["C", "B1"]}
    return protocols.PaillierBackend(keys, rng, engine=engine), rng

mesh = jax.make_mesh((2,), ("data",))
b1, _ = backend_with(None)
ref = trainer.train_vfl(parties, y, cfg, backend=b1)
b2, _ = backend_with(ShardedCryptoEngine(backend="jnp", mesh=mesh))
res = trainer.train_vfl(parties, y, cfg, backend=b2)
assert res.losses == ref.losses, (res.losses, ref.losses)
for name in ref.weights:
    np.testing.assert_array_equal(res.weights[name], ref.weights[name])
print("HE_SHARDING_E2E_OK")
"""
    r = subprocess.run([sys.executable, "-c", script], env=ENV,
                       capture_output=True, text=True, cwd=REPO)
    assert "HE_SHARDING_E2E_OK" in r.stdout, (r.stdout[-1500:],
                                              r.stderr[-3000:])
