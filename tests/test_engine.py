"""Crypto compute-engine parity: the fused Pallas kernels (interpret
mode — same IR as the TPU path) must be bit-exact vs the pure-jnp
`bigint` oracles on every hot-path op, across key sizes and both GLMs,
and the runtime's noise-pool prefetch must leave the trained model
bit-identical."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax.numpy as jnp

from repro.crypto import bigint, paillier
from repro.crypto import engine as engine_mod
from repro.crypto.bigint import Modulus
from repro.kernels import ops

RNG = np.random.default_rng(17)

MODS = [
    (1 << 61) - 1,                                   # 61-bit prime
    int("0x" + "b" * 64, 16) | 1,                    # 256-bit odd
    int("0x" + "7" * 128, 16) | 1,                   # 512-bit odd
]

INTERP = engine_mod.CryptoEngine(backend="pallas-interpret")


def rand_residues(n_mod, size):
    nbytes = (n_mod.bit_length() + 7) // 8
    return [int.from_bytes(RNG.bytes(nbytes), "little") % n_mod
            for _ in range(size)]


# ---------------------------------------------------------------------------
# Engine plumbing
# ---------------------------------------------------------------------------

def test_engine_resolution_and_context():
    assert engine_mod.resolve_backend("jnp") == "jnp"
    assert engine_mod.resolve_backend("pallas-interpret") == "pallas-interpret"
    with pytest.raises(ValueError):
        engine_mod.resolve_backend("cuda")
    base = engine_mod.get_engine()
    with engine_mod.use_engine("pallas-interpret") as eng:
        assert eng.uses_kernels and eng.interpret
        assert engine_mod.get_engine() is eng
    assert engine_mod.get_engine() == base


def test_engine_jnp_is_library():
    mod = Modulus.make(MODS[0])
    a = jnp.asarray(bigint.ints_to_limbs(rand_residues(MODS[0], 3), mod.L))
    eng = engine_mod.CryptoEngine(backend="jnp")
    np.testing.assert_array_equal(
        np.asarray(eng.mont_mul(a, a, mod)),
        np.asarray(bigint.mont_mul(a, a, mod)))


# ---------------------------------------------------------------------------
# Fused mont_exp ≡ bigint ladder
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", MODS)
@pytest.mark.parametrize("batch", [1, 5, 8])
def test_mont_exp_fused_vs_oracle(n, batch):
    mod = Modulus.make(n)
    base = rand_residues(n, batch)
    exps = rand_residues(1 << 24, batch)
    B = bigint.to_mont(jnp.asarray(bigint.ints_to_limbs(base, mod.L)), mod)
    bits = jnp.asarray(np.stack([bigint.int_to_bits(e, 24) for e in exps]))
    want = np.asarray(bigint.mont_exp_bits(B, bits, mod))
    got = np.asarray(ops.rns_mont_exp_fused(B, bits, mod, tile_b=4,
                                            interpret=True))
    np.testing.assert_array_equal(got, want)
    # python-int ground truth
    ints = [bigint.limbs_to_int(x)
            for x in np.asarray(bigint.from_mont(jnp.asarray(got), mod))]
    assert ints == [pow(x, e, n) for x, e in zip(base, exps)]


def test_mont_exp_fused_broadcast_bits():
    """Single shared exponent vector (the decrypt lam_bits pattern)."""
    n = MODS[1]
    mod = Modulus.make(n)
    B = bigint.to_mont(
        jnp.asarray(bigint.ints_to_limbs(rand_residues(n, 6), mod.L)), mod)
    bits = jnp.asarray(bigint.int_to_bits(0xDEADBEEF, 32))
    np.testing.assert_array_equal(
        np.asarray(ops.rns_mont_exp_fused(B, bits, mod, interpret=True)),
        np.asarray(bigint.mont_exp_bits(B, bits, mod)))


def test_engine_mont_exp_const_cached_bits():
    n = MODS[0]
    mod = Modulus.make(n)
    B = bigint.to_mont(
        jnp.asarray(bigint.ints_to_limbs(rand_residues(n, 2), mod.L)), mod)
    for e in (0, 1, 12345, 0xFFFF):
        np.testing.assert_array_equal(
            np.asarray(INTERP.mont_exp_const(B, e, mod)),
            np.asarray(bigint.mont_exp_const(B, e, mod)))


# ---------------------------------------------------------------------------
# Fused he_matvec ≡ library ladders (both paths, chunking, precompute)
# ---------------------------------------------------------------------------

def _matvec_case(key_bits, n_rows, m, width, seed):
    from repro.core import protocols
    key = paillier.keygen(key_bits, seed=seed)
    pub = key.pub
    rng = np.random.default_rng(seed + 1)
    msgs = [int(v) for v in rng.integers(0, 1 << 16, size=n_rows)]
    cts = paillier.encrypt(pub, paillier.encode_ints(pub, msgs), rng=rng)
    exps = rng.integers(0, 1 << width, size=(n_rows, m), dtype=np.uint32)
    return protocols, key, pub, cts, jnp.asarray(exps), msgs, exps


@pytest.mark.parametrize("key_bits", [128, 256])
def test_he_matvec_fused_vs_library(key_bits):
    protocols, key, pub, cts, exps, msgs, exps_np = _matvec_case(
        key_bits, n_rows=7, m=3, width=22, seed=key_bits)
    want = protocols.he_matvec(pub, cts, exps, 22)
    # fused engine path, with n-chunking and m-tiling exercised
    eng = engine_mod.CryptoEngine(backend="pallas-interpret",
                                  chunk_n=3, tile_m=2)
    got = protocols.he_matvec(pub, cts, exps, 22, engine=eng)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # decrypted integers match the plaintext matvec
    dec = paillier.decode_ints(np.asarray(paillier.decrypt(key, got)))
    assert dec == [sum(int(exps_np[i, j]) * msgs[i]
                       for i in range(len(msgs)))
                   for j in range(exps_np.shape[1])]


def test_he_matvec_fused_bitserial_window():
    protocols, key, pub, cts, exps, msgs, _ = _matvec_case(
        128, n_rows=5, m=2, width=10, seed=3)
    want = protocols.he_matvec(pub, cts, exps, 10, window=1)
    got = protocols.he_matvec(pub, cts, exps, 10, window=1, engine=INTERP)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_he_matvec_precomputed_digits_match():
    from repro.core import protocols
    protocols_, key, pub, cts, exps, msgs, exps_np = _matvec_case(
        128, n_rows=6, m=3, width=22, seed=9)
    digits = protocols.window_digits(exps_np, 22, protocols.DEFAULT_WINDOW)
    want = protocols.he_matvec(pub, cts, exps, 22)
    got = protocols.he_matvec(pub, cts, exps, 22,
                              digits=digits.astype(np.uint32))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # stale digits (wrong level count for the requested window) re-derive
    got2 = protocols.he_matvec(pub, cts, exps, 22, window=6,
                               digits=digits.astype(np.uint32))
    want2 = protocols.he_matvec(pub, cts, exps, 22, window=6)
    np.testing.assert_array_equal(np.asarray(got2), np.asarray(want2))


def test_encoded_features_digits_sliced():
    from repro.core import protocols
    X = RNG.normal(size=(20, 3))
    feats = protocols.EncodedFeatures.make(X, fx=10)
    assert feats.digits is not None
    levels = -(-feats.width // protocols.DEFAULT_WINDOW)
    assert feats.digits.shape == feats.exps.shape + (levels,)
    sl = feats.slice(np.array([3, 1, 7]))
    np.testing.assert_array_equal(
        sl.digits,
        protocols.window_digits(sl.exps, feats.width,
                                protocols.DEFAULT_WINDOW))


_PROP_KEY = None


def _prop_key():
    global _PROP_KEY
    if _PROP_KEY is None:
        _PROP_KEY = paillier.keygen(128, seed=41)
    return _PROP_KEY


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=2, max_value=24),
       st.integers(min_value=1, max_value=6),
       st.integers(min_value=0))
def test_hypothesis_windowed_equals_bitserial(width, window, seed):
    """Property (satellite): windowed ≡ bit-serial he_matvec for random
    widths/windows.  (Fused ≡ library is covered at fixed sizes above —
    keeping the sweep on the library path bounds kernel compile count.)"""
    from repro.core import protocols
    rng = np.random.default_rng(seed % (1 << 32))
    key = _prop_key()
    pub = key.pub
    n_rows, m = 4, 2
    msgs = [int(v) for v in rng.integers(0, 1 << 16, size=n_rows)]
    cts = paillier.encrypt(pub, paillier.encode_ints(pub, msgs), rng=rng)
    exps = jnp.asarray(rng.integers(0, 1 << width, size=(n_rows, m),
                                    dtype=np.uint32))
    bit_serial = protocols.he_matvec(pub, cts, exps, width, window=1)
    windowed = protocols.he_matvec(pub, cts, exps, width, window=window)
    np.testing.assert_array_equal(np.asarray(windowed),
                                  np.asarray(bit_serial))


# ---------------------------------------------------------------------------
# Whole-cryptosystem parity under the engine switch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("key_bits", [128, 256])
def test_paillier_roundtrip_engine_parity(key_bits):
    """encrypt / decrypt / decrypt_crt / smul / hom_sum: kernel engine ≡
    jnp engine bit-for-bit (same noise stream ⇒ same ciphertexts)."""
    key = paillier.keygen(key_bits, seed=key_bits + 1)
    pub = key.pub
    msgs = [int(v) for v in RNG.integers(0, 1 << 20, size=6)]
    m = paillier.encode_ints(pub, msgs)
    c_jnp = paillier.encrypt(pub, m, rng=np.random.default_rng(7))
    c_eng = paillier.encrypt(pub, m, rng=np.random.default_rng(7),
                             engine=INTERP)
    np.testing.assert_array_equal(np.asarray(c_eng), np.asarray(c_jnp))
    np.testing.assert_array_equal(
        np.asarray(paillier.decrypt(key, c_jnp, engine=INTERP)),
        np.asarray(paillier.decrypt(key, c_jnp)))
    np.testing.assert_array_equal(
        np.asarray(paillier.decrypt_crt(key, c_jnp, engine=INTERP)),
        np.asarray(paillier.decrypt_crt(key, c_jnp)))
    np.testing.assert_array_equal(
        np.asarray(paillier.smul_const(pub, c_jnp, 997, engine=INTERP)),
        np.asarray(paillier.smul_const(pub, c_jnp, 997)))
    np.testing.assert_array_equal(
        np.asarray(paillier.hom_sum(pub, c_jnp, engine=INTERP)),
        np.asarray(paillier.hom_sum(pub, c_jnp)))


@pytest.mark.slow
@pytest.mark.parametrize("glm", ["logistic", "poisson"])
def test_train_engine_parity_both_glms(glm):
    """End-to-end Algorithm 1 with real Paillier: the pallas-interpret
    engine trains the bit-identical model to the jnp engine."""
    from repro.core import trainer
    from repro.data import synthetic, vertical
    if glm == "poisson":
        X, y = synthetic.dvisits(n=60, seed=7)
    else:
        X, y = synthetic.credit_default(n=60, d=4, seed=3)
    parts = vertical.split_columns(X, 2)
    parties = [trainer.PartyData(name=nm, X=p)
               for nm, p in zip(["C", "B1"], parts)]
    cfg_jnp = trainer.VFLConfig(glm=glm, lr=0.1, max_iter=1, batch_size=16,
                                he_backend="paillier", key_bits=256,
                                tol=0.0, seed=2, crypto_engine="jnp")
    cfg_eng = trainer.VFLConfig(glm=glm, lr=0.1, max_iter=1, batch_size=16,
                                he_backend="paillier", key_bits=256,
                                tol=0.0, seed=2,
                                crypto_engine="pallas-interpret")
    ref = trainer.train_vfl(parties, y, cfg_jnp)
    res = trainer.train_vfl(parties, y, cfg_eng)
    assert res.losses == ref.losses
    for name in ref.weights:
        np.testing.assert_array_equal(res.weights[name], ref.weights[name])


# ---------------------------------------------------------------------------
# Noise-pool prefetch
# ---------------------------------------------------------------------------

def test_noise_pool_prefetch_and_fallback():
    from concurrent.futures import ThreadPoolExecutor

    from repro.core import protocols
    from repro.crypto import fixed_point, ring
    key = paillier.keygen(128, seed=5)
    backend = protocols.PaillierBackend({"C": key},
                                        np.random.default_rng(3))
    d = ring.from_numpy_u64(
        RNG.integers(0, 1 << 64, size=4, dtype=np.uint64))
    # no executor: prefetch is a no-op, encrypt falls back to sync
    backend.prefetch_noise("C", 4)
    assert not backend._noise["C"]
    c_sync = backend.encrypt_share("C", d)
    assert paillier.decode_ints(np.asarray(paillier.decrypt(key, c_sync))) \
        == [int(v) for v in np.asarray(ring.to_numpy_u64(d))]
    # with executor: pooled noise is consumed, decryption unchanged
    with ThreadPoolExecutor(max_workers=2) as ex:
        backend.attach_noise_executor(ex)
        backend.prefetch_noise("C", 4)
        assert len(backend._noise["C"]) == 1
        c_pool = backend.encrypt_share("C", d)
        assert not backend._noise["C"]          # consumed
        # count mismatch falls back without touching the pool
        backend.prefetch_noise("C", 2)
        c_other = backend.encrypt_share("C", d)
        assert len(backend._noise["C"]) == 1
    for c in (c_pool, c_other):
        assert paillier.decode_ints(np.asarray(paillier.decrypt(key, c))) \
            == [int(v) for v in np.asarray(ring.to_numpy_u64(d))]


def test_pipelined_paillier_prefetch_model_parity():
    """PipelinedTransport + Paillier: the noise pool reorders only the
    entropy stream for r and masks — masks cancel and noise never reaches
    a decrypted value, so the model is bit-identical to LocalTransport."""
    from repro.core import trainer
    from repro.data import synthetic, vertical
    from repro.runtime import LocalTransport, PipelinedTransport
    X, y = synthetic.credit_default(n=45, d=6, seed=5)
    parts = vertical.split_columns(X, 3)   # k=3: exercises the non-CP
    parties = [trainer.PartyData(name=nm, X=p)   # two-key masking legs
               for nm, p in zip(["C", "B1", "B2"], parts)]
    cfg = trainer.VFLConfig(glm="logistic", lr=0.2, max_iter=1,
                            batch_size=16, he_backend="paillier",
                            key_bits=192, tol=0.0, seed=1)
    local = trainer.train_vfl(parties, y, cfg, transport=LocalTransport())
    piped = trainer.train_vfl(parties, y, cfg,
                              transport=PipelinedTransport())
    assert piped.losses == local.losses
    for name in local.weights:
        np.testing.assert_array_equal(piped.weights[name],
                                      local.weights[name])
    assert dict(piped.meter.by_tag) == dict(local.meter.by_tag)


# ---------------------------------------------------------------------------
# Vectorized host helpers (satellites)
# ---------------------------------------------------------------------------

def test_int_to_bits_vectorized():
    for e, nbits in [(0, 1), (1, 1), (5, 3), (0xDEAD, 16),
                     ((1 << 200) - 3, 200)]:
        got = bigint.int_to_bits(e, nbits)
        want = np.array([(e >> (nbits - 1 - i)) & 1 for i in range(nbits)],
                        dtype=np.uint32)
        np.testing.assert_array_equal(got, want)
        assert got.dtype == np.uint32
    with pytest.raises(ValueError):
        bigint.int_to_bits(4, 2)


def test_cached_bits_identity_and_immutability():
    a = bigint.cached_bits(12345, 14)
    b = bigint.cached_bits(12345, 14)
    assert a is b
    with pytest.raises(ValueError):
        a[0] = 1
    np.testing.assert_array_equal(a, bigint.int_to_bits(12345, 14))


def test_decode_ints_vectorized():
    key = paillier.keygen(128, seed=11)
    vals = [0, 1, (1 << 60) + 12345, (1 << 100) - 1]
    limbs = bigint.ints_to_limbs(vals, key.pub.Ln)
    assert paillier.decode_ints(limbs) == vals
    assert paillier.decode_ints(limbs[0]) == [0]
    # nested batch keeps its structure
    nested = limbs.reshape(2, 2, -1)
    assert paillier.decode_ints(nested) == [vals[:2], vals[2:]]
