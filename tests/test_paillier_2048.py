"""Paillier at the 2048-bit keys a ≥112-bit security rule asks for (NIST
SP 800-57 Pt 1 Rev 5, Table 2): the RNS channel system of n² (4096 bits)
stays within the exact-f32 bound, and fixed-base encryption, the
windowed HE matvec and CRT decryption equal Python-int Paillier (`pow`)
bit for bit.  The CPU rows run the jnp RNS engine on a few rows; the
chip row runs the compiled kernels at the benchmark cell's shapes (batch
2048, 12 columns) and checks sampled outputs, and skips off a TPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import protocols
from repro.crypto import engine as engine_mod
from repro.crypto import fixed_base, paillier, rns
from repro.crypto.bigint import LIMB_BITS

KEY_BITS = 2048
WINDOW = 4            # table and matvec window, as the cells run them
EXP_WIDTH = 22        # VFLConfig.exp_width
RHO_BITS = 320

ON_TPU = jax.default_backend() == "tpu"


@pytest.fixture(scope="module")
def key():
    return paillier.keygen(KEY_BITS, seed=120301)


@pytest.fixture(scope="module")
def table(key):
    return fixed_base.build_noise_table(
        key.pub.n, key.pub.mod_n2, window=WINDOW, rho_bits=RHO_BITS,
        rng=np.random.default_rng(120401))


# -- the Python-int oracle -------------------------------------------------

def _mont_r(pub) -> int:
    return 1 << (LIMB_BITS * pub.mod_n2.L)


def _ints(limbs) -> list[int]:
    return paillier.decode_ints(np.asarray(limbs))


def _rho(digits_row) -> int:
    return sum(int(d) << (WINDOW * lvl) for lvl, d in enumerate(digits_row))


def _enc_oracle(pub, table, m: int, digits_row) -> int:
    """(1 + m·n)·h^ρ mod n², Montgomery domain (·R)."""
    n2 = pub.n * pub.n
    c = (1 + m * pub.n) * pow(table.base, _rho(digits_row), n2) % n2
    return c * _mont_r(pub) % n2


def _matvec_oracle(pub, cts_mont: list[int], exps: np.ndarray) -> list[int]:
    """Π_i c_i^{e_ij} mod n² of Montgomery-domain ciphertexts, per column,
    Montgomery domain."""
    n2 = pub.n * pub.n
    R = _mont_r(pub)
    plain = [c * pow(R, -1, n2) % n2 for c in cts_mont]
    out = []
    for j in range(exps.shape[1]):
        acc = 1
        for c, e in zip(plain, exps[:, j]):
            acc = acc * pow(c, int(e), n2) % n2
        out.append(acc * R % n2)
    return out


def _dec_oracle(key, c_mont: int) -> int:
    pub = key.pub
    n2 = pub.n * pub.n
    c = c_mont * pow(_mont_r(pub), -1, n2) % n2
    u = pow(c, key.lam, n2)
    return (u - 1) // pub.n * pow(key.lam, -1, pub.n) % pub.n


def _encrypt(key, table, msgs, digits, eng):
    pub = key.pub
    noise = paillier.noise_from_table(pub, table, digits, engine=eng)
    return paillier.encrypt_with_noise(pub, paillier.encode_ints(pub, msgs),
                                       noise, engine=eng)


# -- CPU, jnp RNS engine ---------------------------------------------------

CPU_ENGINE = engine_mod.CryptoEngine(backend="jnp", pipeline="rns")


def test_channels_at_2048_bits(key):
    ctx = rns.for_modulus(key.pub.mod_n2)
    assert (ctx.kA, ctx.kB, ctx.CH) == (347, 326, 674)
    assert ctx.CH <= rns._MAX_DOT_K
    assert key.pub.n.bit_length() == KEY_BITS
    assert key.pub.mod_n2.L == 342


def test_fixed_base_encryption_equals_python_ints(key, table):
    rng = np.random.default_rng(3)
    msgs = [int(rng.integers(1 << 62)), key.pub.n - 1]
    digits = fixed_base.draw_exponent_digits(table, 2, rng)
    cts = _encrypt(key, table, msgs, digits, CPU_ENGINE)
    assert _ints(cts) == [_enc_oracle(key.pub, table, m, d)
                          for m, d in zip(msgs, digits)]


def test_windowed_matvec_equals_python_ints(key, table):
    rng = np.random.default_rng(4)
    msgs = [int(v) for v in rng.integers(1 << 40, size=4)]
    cts = _encrypt(key, table, msgs,
                   fixed_base.draw_exponent_digits(table, 4, rng),
                   CPU_ENGINE)
    exps = rng.integers(1 << EXP_WIDTH, size=(4, 2)).astype(np.uint32)
    out = protocols.he_matvec(key.pub, cts, jnp.asarray(exps), EXP_WIDTH,
                              WINDOW, engine=CPU_ENGINE)
    assert _ints(out) == _matvec_oracle(key.pub, _ints(cts), exps)


def test_crt_decryption_equals_python_ints(key, table):
    rng = np.random.default_rng(5)
    msgs = [int(rng.integers(1 << 62)), key.pub.n - 2]
    cts = _encrypt(key, table, msgs,
                   fixed_base.draw_exponent_digits(table, 2, rng),
                   CPU_ENGINE)
    dec = _ints(paillier.decrypt_crt(key, cts, engine=CPU_ENGINE))
    assert dec == msgs == [_dec_oracle(key, c) for c in _ints(cts)]


# -- the chip: compiled kernels at the cell's shapes -----------------------

@pytest.mark.skipif(not ON_TPU, reason="compiled pallas kernels need a TPU")
def test_cell_shapes_on_the_chip_equal_python_ints(key, table):
    """One batch-2048 iteration's three kernels as `credit_lr_k2_2048`
    runs them (fixed-base noise for 2048 rows, a 2048 × 12 matvec, CRT
    decryption of its 12 outputs), sampled rows against the oracle."""
    eng = engine_mod.make("pallas")
    rng = np.random.default_rng(6)
    batch, cols = 2048, 12
    msgs = [int(v) for v in rng.integers(1 << 62, size=batch)]
    digits = fixed_base.draw_exponent_digits(table, batch, rng)
    cts = _encrypt(key, table, msgs, digits, eng)
    ct_ints = _ints(cts)
    for i in rng.choice(batch, size=16, replace=False):
        assert ct_ints[i] == _enc_oracle(key.pub, table, msgs[i], digits[i])

    exps = rng.integers(1 << EXP_WIDTH, size=(batch, cols)).astype(np.uint32)
    out = protocols.he_matvec(key.pub, cts, jnp.asarray(exps), EXP_WIDTH,
                              WINDOW, engine=eng)
    sample = rng.choice(cols, size=3, replace=False)
    want = _matvec_oracle(key.pub, ct_ints, exps[:, sample])
    assert [_ints(out)[j] for j in sample] == want

    dec = _ints(paillier.decrypt_crt(key, out, engine=eng))
    n = key.pub.n
    sums = [sum(m * int(e) for m, e in zip(msgs, exps[:, j])) % n
            for j in range(cols)]
    assert dec == sums
