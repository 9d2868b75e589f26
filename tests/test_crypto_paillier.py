"""Paillier correctness: roundtrip, homomorphism, protocol encodings."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.crypto import bigint, fixed_point, paillier, ring
from repro.crypto import engine as engine_mod

KEY = paillier.keygen(128, seed=7)        # small key: fast CPU tests
PUB = KEY.pub
RNG = np.random.default_rng(3)


def rand_msgs(k, bits=100):
    return [int.from_bytes(RNG.bytes(bits // 8), "little") for _ in range(k)]


def test_keygen_sane():
    assert PUB.n.bit_length() == 128
    assert (KEY.lam * pow(KEY.lam, -1, PUB.n)) % PUB.n == 1


def test_enc_dec_roundtrip():
    msgs = rand_msgs(6) + [0, 1, PUB.n - 1]
    m = paillier.encode_ints(PUB, msgs)
    c = paillier.encrypt(PUB, m, rng=RNG)
    got = paillier.decode_ints(np.asarray(paillier.decrypt(KEY, c)))
    assert got == msgs


def test_homomorphic_add():
    a, b = rand_msgs(5), rand_msgs(5)
    ca = paillier.encrypt(PUB, paillier.encode_ints(PUB, a), rng=RNG)
    cb = paillier.encrypt(PUB, paillier.encode_ints(PUB, b), rng=RNG)
    cs = paillier.add_ct(PUB, ca, cb)
    got = paillier.decode_ints(np.asarray(paillier.decrypt(KEY, cs)))
    assert got == [(x + y) % PUB.n for x, y in zip(a, b)]


def test_scalar_mul_const():
    a = rand_msgs(4)
    k = 123457
    ca = paillier.encrypt(PUB, paillier.encode_ints(PUB, a), rng=RNG)
    ck = paillier.smul_const(PUB, ca, k)
    got = paillier.decode_ints(np.asarray(paillier.decrypt(KEY, ck)))
    assert got == [(x * k) % PUB.n for x in a]


def test_scalar_mul_traced_bits():
    a = rand_msgs(4)
    ks = [3, 9999, (1 << 22) - 1, 1]
    ca = paillier.encrypt(PUB, paillier.encode_ints(PUB, a), rng=RNG)
    bits = jnp.asarray(np.stack([bigint.int_to_bits(k, 22) for k in ks]))
    ck = paillier.smul_bits(PUB, ca, bits)
    got = paillier.decode_ints(np.asarray(paillier.decrypt(KEY, ck)))
    assert got == [(x * k) % PUB.n for x, k in zip(a, ks)]


def test_hom_sum_tree():
    a = rand_msgs(9)
    ca = paillier.encrypt(PUB, paillier.encode_ints(PUB, a), rng=RNG)
    cs = paillier.hom_sum(PUB, ca, axis=0)
    got = paillier.decode_ints(np.asarray(paillier.decrypt(KEY, cs[None])))
    assert got == [sum(a) % PUB.n]


def test_noise_precompute_matches_fresh():
    msgs = rand_msgs(3)
    m = paillier.encode_ints(PUB, msgs)
    r = paillier.raw_noise(PUB, 3, rng=np.random.default_rng(11))
    rn = paillier.noise_to_mont(PUB, r)
    c = paillier.encrypt_with_noise(PUB, m, rn)
    got = paillier.decode_ints(np.asarray(paillier.decrypt(KEY, c)))
    assert got == msgs


def test_ring64_residue_protocol_semantics():
    """The DESIGN §7 convention: decrypt(…) mod 2^64 == ring result, with
    multipliers lifted to non-negative residues mod 2^64."""
    key = paillier.keygen(256, seed=9)  # big enough for exact 128-bit values
    pub = key.pub
    vals = np.array([123456789, 2 ** 63 + 17], np.uint64)   # ring residues
    mult = -7  # signed multiplier, lifted
    m = np.stack([bigint.int_to_limbs(int(v), pub.Ln) for v in vals])
    c = paillier.encrypt(pub, m, rng=RNG)
    k = (mult) % (1 << 64)
    ck = paillier.smul_const(pub, c, k)
    dec = np.asarray(paillier.decrypt(key, ck))
    got = [x % (1 << 64) for x in paillier.decode_ints(dec)]
    want = [int((v * np.uint64(k)) & np.uint64(0xFFFFFFFFFFFFFFFF)) for v in vals]
    assert got == want


def test_r64_limb_bridge():
    vals = np.array([0, 1, 2 ** 40 + 3, 2 ** 64 - 1, 0xDEADBEEFCAFEBABE],
                    np.uint64)
    a = ring.from_numpy_u64(vals)
    limbs = fixed_point.r64_to_limbs(a, 10)
    ints = [bigint.limbs_to_int(x) for x in np.asarray(limbs)]
    assert ints == [int(v) for v in vals]
    back = fixed_point.limbs_to_r64(limbs)
    assert (ring.to_numpy_u64(back) == vals).all()


def test_u64_bits_msb():
    vals = np.array([0xDEADBEEFCAFEBABE, 1, 2 ** 63], np.uint64)
    a = ring.from_numpy_u64(vals)
    bits = np.asarray(fixed_point.u64_bits_msb(a))
    for i, v in enumerate(vals):
        want = bigint.int_to_bits(int(v), 64)
        assert (bits[i] == want).all()


CRT_KEY = paillier.keygen(128, seed=13)
CRT_ENGINES = {
    "jnp": engine_mod.CryptoEngine(backend="jnp"),
    # RNS pinned: at this key size `auto` would route to the library
    "pallas-interpret": engine_mod.CryptoEngine(backend="pallas-interpret",
                                                pipeline="rns"),
}


def _pow_decrypt(key, c_mont) -> int:
    """Textbook decryption with Python ints: L(c^λ mod n²) · μ mod n."""
    n, n2 = key.pub.n, key.pub.n * key.pub.n
    r_inv = pow(1 << (bigint.LIMB_BITS * key.pub.Ln2), -1, n2)
    c = bigint.limbs_to_int(np.asarray(c_mont)) * r_inv % n2
    return (pow(c, key.lam, n2) - 1) // n * pow(key.lam, -1, n) % n


@pytest.mark.parametrize("rows", [None, 9, 10, 12])
@pytest.mark.parametrize("engine", sorted(CRT_ENGINES))
def test_crt_decrypt_equals_plain(engine, rows):
    """CRT decryption (≈4× cheaper) is bit-identical to plain decryption
    and to Python-int decryption, as one compiled program per key,
    engine and shape: the first call of a shape traces it once, the
    next call of that shape does not trace again."""
    key, eng = CRT_KEY, CRT_ENGINES[engine]
    pub = key.pub
    rng = np.random.default_rng(5 + (rows or 1))
    msgs = [pub.n - 1]
    if rows:
        msgs = [0, 1] + msgs + [int.from_bytes(rng.bytes(16), "little")
                                % pub.n for _ in range(rows - 3)]
    m = paillier.encode_ints(pub, msgs)
    c = paillier.encrypt(pub, m if rows else m[0], rng=rng)
    programs = paillier._decrypt_crt_program._cache_size()
    crt = np.asarray(paillier.decrypt_crt(key, c, engine=eng))
    assert paillier._decrypt_crt_program._cache_size() == programs + 1
    again = np.asarray(paillier.decrypt_crt(key, c, engine=eng))
    assert paillier._decrypt_crt_program._cache_size() == programs + 1
    np.testing.assert_array_equal(again, crt)
    plain = np.asarray(paillier.decrypt(key, c))
    np.testing.assert_array_equal(crt, plain)
    assert crt.shape == c.shape[:-1] + (pub.Ln,)
    cts = c.reshape(-1, pub.Ln2)
    assert paillier.decode_ints(crt.reshape(-1, pub.Ln)) == msgs == [
        _pow_decrypt(key, ci) for ci in cts]
