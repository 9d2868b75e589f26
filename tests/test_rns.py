"""RNS pipeline parity: pallas kernels ≡ pallas-interpret ≡ bigint oracle.

Sweeps key sizes × window widths × batch shapes for every RNS op —
montmul, the constant-time ladder, fixed-base exponentiation and the
windowed HE matvec.  Interpret-mode rows always run (they are the CI
guarantee that the compiled IR computes the right thing — interpret
executes the same traced kernel body); compiled rows run only on a TPU
host and skip elsewhere.  `crypto.bigint` is the bit-exactness oracle
throughout, itself oracle-tested against python ints in
test_crypto_bigint.py.
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax
import jax.numpy as jnp

from repro.crypto import bigint, rns
from repro.crypto import engine as engine_mod
from repro.crypto.bigint import Modulus
from repro.kernels import ops

RNG = np.random.default_rng(29)

ON_TPU = jax.default_backend() == "tpu"
compiled = pytest.mark.skipif(not ON_TPU,
                              reason="compiled pallas rows need a TPU")

# moduli spanning the auto-routing threshold (RNS_MIN_BITS = 512):
# below it, at it, and the paper's 1024-bit ciphertext size is covered
# by the slow-marked rows and benchmarks.
MODS = [
    (1 << 61) - 1,                                   # 61-bit prime
    (1 << 256) - 159,                                # 256-bit odd
    (1 << 512) - 569,                                # 512-bit odd (≥ thresh)
]
MODS_SLOW = [(1 << 1024) - 105]                      # paper-scale


def rand_residues(n_mod, size):
    nbytes = (n_mod.bit_length() + 7) // 8
    return [int.from_bytes(RNG.bytes(nbytes), "little") % n_mod
            for _ in range(size)]


def limbs(ints, L):
    return jnp.asarray(bigint.ints_to_limbs(ints, L))


# ---------------------------------------------------------------------------
# montmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", MODS)
@pytest.mark.parametrize("batch", [1, 5, 64])
def test_rns_montmul_vs_oracle(n, batch):
    mod = Modulus.make(n)
    a, b = rand_residues(n, batch), rand_residues(n, batch)
    A, B = limbs(a, mod.L), limbs(b, mod.L)
    want = np.asarray(bigint.mont_mul(A, B, mod))
    # jnp library pipeline
    ctx = rns.for_modulus(mod)
    np.testing.assert_array_equal(np.asarray(rns.mont_mul(ctx, A, B)), want)
    # kernel, interpret mode
    got = np.asarray(ops.rns_montmul(A, B, mod, tile_b=32, interpret=True))
    np.testing.assert_array_equal(got, want)


@compiled
@pytest.mark.parametrize("n", MODS)
def test_rns_montmul_compiled(n):
    mod = Modulus.make(n)
    a, b = rand_residues(n, 64), rand_residues(n, 64)
    A, B = limbs(a, mod.L), limbs(b, mod.L)
    want = np.asarray(bigint.mont_mul(A, B, mod))
    got = np.asarray(ops.rns_montmul(A, B, mod, interpret=False))
    np.testing.assert_array_equal(got, want)


def test_reciprocal_remainder_is_exact():
    """`mod_u28_recip` ≡ `%` on every channel modulus of the paper-scale
    context, at each multiple-of-m boundary up to 2^28 and at random."""
    ctx = rns.for_modulus(Modulus.make(MODS_SLOW[0]))
    m = np.asarray(ctx.all_mods, np.int64)[:, None]
    k = np.arange(0, (1 << 28) // 2**11 + 1, 97)[None, :]
    x = np.concatenate([k * m - 1, k * m, k * m + 1,
                        RNG.integers(0, 1 << 28, (m.shape[0], 512))], 1)
    x = np.clip(x, 0, (1 << 28) - 1).astype(np.uint32)
    mods = np.broadcast_to(ctx.all_mods[:, None], x.shape)
    np.testing.assert_array_equal(
        np.asarray(rns.mod_u28_recip(jnp.asarray(x), jnp.asarray(mods))),
        x % mods)


@pytest.mark.parametrize("n", [MODS[2], MODS_SLOW[0]])
def test_library_round_on_tpu_remainder(monkeypatch, n):
    """The library conversions and round take the reciprocal remainder
    when JAX's backend is a TPU; the result is the same bit for bit."""
    mod = Modulus.make(n)
    ctx = rns.for_modulus(mod)
    A, B = limbs(rand_residues(n, 9), mod.L), limbs(rand_residues(n, 9), mod.L)

    def round_trip():
        t = rns.rns_montmul(ctx, rns.to_rns(ctx, A), rns.to_rns_scaled(ctx, B))
        return np.asarray(rns.from_rns(ctx, t))

    want = round_trip()
    np.testing.assert_array_equal(want, np.asarray(bigint.mont_mul(A, B, mod)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    np.testing.assert_array_equal(round_trip(), want)


def test_rns_montmul_batch_shapes():
    n = MODS[0]
    mod = Modulus.make(n)
    A = limbs(rand_residues(n, 12), mod.L).reshape(3, 4, mod.L)
    got = ops.rns_montmul(A, A, mod, tile_b=8, interpret=True)
    assert got.shape == (3, 4, mod.L)
    want = bigint.mont_mul(A, A, mod)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=(1 << 256) - 160),
       st.integers(min_value=0, max_value=(1 << 256) - 160))
def test_hypothesis_rns_montmul(a, b):
    mod = Modulus.make((1 << 256) - 159)
    ctx = rns.for_modulus(mod)
    A, B = limbs([a], mod.L), limbs([b], mod.L)
    want = np.asarray(bigint.mont_mul(A, B, mod))
    np.testing.assert_array_equal(np.asarray(rns.mont_mul(ctx, A, B)), want)


# ---------------------------------------------------------------------------
# constant-time ladder
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", MODS)
@pytest.mark.parametrize("ebits", [1, 16, 61])
def test_rns_ladder_vs_oracle(n, ebits):
    mod = Modulus.make(n)
    batch = 4
    base = rand_residues(n, batch)
    e = int.from_bytes(RNG.bytes((ebits + 7) // 8), "little") % (1 << ebits)
    e |= 1 << (ebits - 1)
    bits = jnp.asarray(bigint.int_to_bits(e, ebits))
    B = limbs(base, mod.L)
    want = np.asarray(bigint.mont_exp_bits(B, bits, mod))
    ctx = rns.for_modulus(mod)
    np.testing.assert_array_equal(
        np.asarray(rns.mont_exp_bits(ctx, B, bits)), want)
    got = np.asarray(ops.rns_mont_exp_fused(B, bits, mod, tile_b=4,
                                            interpret=True))
    np.testing.assert_array_equal(got, want)


@compiled
def test_rns_ladder_compiled():
    n = MODS[1]
    mod = Modulus.make(n)
    B = limbs(rand_residues(n, 8), mod.L)
    bits = jnp.asarray(bigint.int_to_bits(0xC0FFEE, 24))
    want = np.asarray(bigint.mont_exp_bits(B, bits, mod))
    got = np.asarray(ops.rns_mont_exp_fused(B, bits, mod, interpret=False))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# fixed-base exponentiation (persistent-table form)
# ---------------------------------------------------------------------------

def _noise_table(mod, window, exp_bits=32):
    from repro.crypto import fixed_base
    n_fake = mod.value          # fingerprint input only; base is explicit
    return fixed_base.build_noise_table(n_fake, mod, window=window,
                                        rho_bits=exp_bits, x=0xDEADBEEF)


@pytest.mark.parametrize("n", MODS)
@pytest.mark.parametrize("window", [1, 2, 4])
def test_rns_fixed_base_vs_oracle(n, window):
    from repro.crypto import fixed_base
    mod = Modulus.make(n)
    table = _noise_table(mod, window)
    batch = 5
    exps = [int(RNG.integers(0, 1 << 31)) for _ in range(batch)]
    digits = fixed_base.exp_digits(exps, table.levels, window)
    ctx = rns.for_modulus(mod)
    R = 1 << (12 * mod.L)
    want = np.asarray(limbs(
        [(pow(table.base, e, n) * R) % n for e in exps], mod.L))
    got_jnp = np.asarray(rns.fixed_base_exp(
        ctx, jnp.asarray(table.table_rns), jnp.asarray(digits)))
    np.testing.assert_array_equal(got_jnp, want)
    got_k = np.asarray(ops.rns_fixed_base_fused(
        jnp.asarray(table.table_rns), jnp.asarray(digits), mod,
        window=window, tile_b=4, interpret=True))
    np.testing.assert_array_equal(got_k, want)


@compiled
def test_rns_fixed_base_compiled():
    from repro.crypto import fixed_base
    mod = Modulus.make(MODS[1])
    table = _noise_table(mod, 4)
    exps = [int(RNG.integers(0, 1 << 31)) for _ in range(8)]
    digits = fixed_base.exp_digits(exps, table.levels, 4)
    want = np.asarray(ops.rns_fixed_base_fused(
        jnp.asarray(table.table_rns), jnp.asarray(digits), mod,
        window=4, interpret=True))
    got = np.asarray(ops.rns_fixed_base_fused(
        jnp.asarray(table.table_rns), jnp.asarray(digits), mod,
        window=4, interpret=False))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# windowed HE matvec
# ---------------------------------------------------------------------------

# tier-1 runs one bit-serial and one windowed row (≈20 s of interpret
# time); the full key-size × window × shape cross-product is slow-marked
# — interpret-mode he_matvec costs ~5 s per digit level on CPU.
_MV_FAST = [(1, 2, (4, 3), MODS[0]), (4, 8, (3, 2), MODS[2])]
_MV_FULL = [(w, wd, sh, n) for (w, wd) in [(1, 8), (3, 9), (4, 22)]
            for sh in [(4, 3), (9, 2)] for n in MODS]


@pytest.mark.parametrize(
    "window,width,shape,n",
    _MV_FAST + [pytest.param(*p, marks=pytest.mark.slow)
                for p in _MV_FULL])
def test_rns_he_matvec_vs_oracle(window, width, shape, n):
    from repro.core.protocols import window_digits
    mod = Modulus.make(n)
    rows, cols = shape
    cts = limbs(rand_residues(n, rows), mod.L)
    exps = RNG.integers(0, 1 << width, size=shape).astype(np.uint32)
    digits = jnp.asarray(window_digits(exps, width, window))
    # oracle: per-column ladder over the bigint library
    want = []
    for j in range(cols):
        acc = bigint.mont_one(mod)[None, :]
        for i in range(rows):
            bits = jnp.asarray(bigint.int_to_bits(int(exps[i, j]), width))
            term = bigint.mont_exp_bits(cts[i:i + 1], bits, mod)
            acc = bigint.mont_mul(acc, term, mod)
        want.append(np.asarray(acc[0]))
    want = np.stack(want)
    ctx = rns.for_modulus(mod)
    got_jnp = np.asarray(rns.he_matvec(ctx, cts, digits, window))
    np.testing.assert_array_equal(got_jnp, want)
    got_k = np.asarray(ops.rns_he_matvec_fused(
        cts, digits, mod, window=window, tile_m=2, chunk_n=4,
        interpret=True))
    np.testing.assert_array_equal(got_k, want)


@compiled
def test_rns_he_matvec_compiled():
    from repro.core.protocols import window_digits
    mod = Modulus.make(MODS[1])
    cts = limbs(rand_residues(mod.value, 8), mod.L)
    exps = RNG.integers(0, 1 << 22, size=(8, 4)).astype(np.uint32)
    digits = jnp.asarray(window_digits(exps, 22, 4))
    want = np.asarray(ops.rns_he_matvec_fused(cts, digits, mod, window=4,
                                              interpret=True))
    got = np.asarray(ops.rns_he_matvec_fused(cts, digits, mod, window=4,
                                             interpret=False))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# engine routing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas-interpret"])
@pytest.mark.parametrize("pipeline", ["auto", "cios", "rns"])
@pytest.mark.parametrize("n", [MODS[0], MODS[2]])
def test_engine_pipeline_parity(backend, pipeline, n):
    """Every (backend, pipeline) cell is bit-exact vs the library — the
    pipeline field is purely a performance knob."""
    mod = Modulus.make(n)
    A = limbs(rand_residues(n, 6), mod.L)
    B = limbs(rand_residues(n, 6), mod.L)
    want = np.asarray(bigint.mont_mul(A, B, mod))
    eng = engine_mod.CryptoEngine(backend=backend, pipeline=pipeline,
                                  tile_b=8)
    np.testing.assert_array_equal(np.asarray(eng.mont_mul(A, B, mod)), want)
    bits = jnp.asarray(bigint.int_to_bits(0xBEEF, 16))
    want_e = np.asarray(bigint.mont_exp_bits(A, bits, mod))
    np.testing.assert_array_equal(
        np.asarray(eng.mont_exp_bits(A, bits, mod)), want_e)


def test_engine_auto_threshold_routing():
    """auto routes by RNS_MIN_BITS: small moduli to the CIOS library on
    every backend, large ones to the RNS kernels (or the RNS library on
    jnp) — no route of the compiled backend ends in another kernel."""
    small = Modulus.make(MODS[0])
    large = Modulus.make(MODS[2])
    jnp_eng = engine_mod.CryptoEngine(backend="jnp")
    interp = engine_mod.CryptoEngine(backend="pallas-interpret")
    tpu = engine_mod.CryptoEngine(backend="pallas")
    assert jnp_eng._route(small) == "lib"
    assert jnp_eng._route(large) == "rns-jnp"
    assert interp._route(small) == "lib"
    assert interp._route(large) == "rns"
    assert tpu._route(small) == "lib"
    assert tpu._route(large) == "rns"
    # explicit pipelines pin the arithmetic
    assert engine_mod.CryptoEngine(backend="pallas",
                                   pipeline="cios")._route(large) == "lib"
    assert engine_mod.CryptoEngine(backend="pallas",
                                   pipeline="rns")._route(small) == "rns"
    assert engine_mod.CryptoEngine(backend="jnp",
                                   pipeline="rns")._route(small) == "rns-jnp"


def test_engine_pipeline_env_and_single_device(monkeypatch):
    monkeypatch.setenv(engine_mod.PIPELINE_ENV_VAR, "rns")
    eng = engine_mod.CryptoEngine(backend="jnp")
    assert eng._route(Modulus.make(MODS[0])) == "rns-jnp"
    monkeypatch.delenv(engine_mod.PIPELINE_ENV_VAR)
    with pytest.raises(ValueError):
        engine_mod.resolve_pipeline("turbo")
    # single_device carries the pipeline through
    eng2 = engine_mod.CryptoEngine(backend="jnp", pipeline="rns",
                                   mesh=None)
    assert eng2.single_device().pipeline == "rns"
