"""Pallas kernels vs pure-jnp oracles (interpret mode), with shape sweeps
and hypothesis value sweeps.  The Montgomery kernels are the RNS channel
kernels (`ops.rns_*`); compiling them for the TPU is
tests/test_tpu_compile.py's job."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax.numpy as jnp

from repro.crypto import bigint, ring
from repro.crypto.bigint import Modulus
from repro.kernels import ops, ref

RNG = np.random.default_rng(41)

MODS = [
    (1 << 61) - 1,                                   # 61-bit prime
    int("0x" + "b" * 64, 16) | 1,                    # 256-bit odd
    int("0x" + "7" * 128, 16) | 1,                   # 512-bit odd
]


def rand_residues(n_mod, size):
    nbytes = (n_mod.bit_length() + 7) // 8
    return [int.from_bytes(RNG.bytes(nbytes), "little") % n_mod
            for _ in range(size)]


@pytest.mark.parametrize("n", MODS)
@pytest.mark.parametrize("batch", [1, 7, 128, 300])
def test_montmul_kernel_vs_ref(n, batch):
    mod = Modulus.make(n)
    a = rand_residues(n, batch)
    b = rand_residues(n, batch)
    A = jnp.asarray(bigint.ints_to_limbs(a, mod.L))
    B = jnp.asarray(bigint.ints_to_limbs(b, mod.L))
    got = np.asarray(ops.rns_montmul(A, B, mod, tile_b=128, interpret=True))
    want = np.asarray(ref.montmul_ref(A, B, mod))
    np.testing.assert_array_equal(got, want)
    # and against python ints
    R = 1 << (12 * mod.L)
    rinv = pow(R, -1, n)
    got_ints = [bigint.limbs_to_int(g) for g in got]
    assert got_ints == [(x * y * rinv) % n for x, y in zip(a, b)]


def test_montmul_kernel_batch_shapes():
    n = MODS[0]
    mod = Modulus.make(n)
    a = rand_residues(n, 12)
    A = jnp.asarray(bigint.ints_to_limbs(a, mod.L)).reshape(3, 4, mod.L)
    got = ops.rns_montmul(A, A, mod, tile_b=8, interpret=True)
    assert got.shape == (3, 4, mod.L)
    want = ref.montmul_ref(A, A, mod)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mont_exp_bits_kernel():
    n = MODS[0]
    mod = Modulus.make(n)
    base = rand_residues(n, 4)
    exps = rand_residues(1 << 24, 4)
    B = bigint.to_mont(jnp.asarray(bigint.ints_to_limbs(base, mod.L)), mod)
    bits = jnp.asarray(np.stack([bigint.int_to_bits(e, 24) for e in exps]))
    got = bigint.from_mont(
        ops.rns_mont_exp_fused(B, bits, mod, interpret=True), mod)
    ints = [bigint.limbs_to_int(x) for x in np.asarray(got)]
    assert ints == [pow(x, e, n) for x, e in zip(base, exps)]


@pytest.mark.parametrize("shape", [(4, 8, 4), (128, 64, 128), (100, 33, 50),
                                   (1, 1, 1), (130, 40000, 10)])
def test_ring_matmul_kernel_vs_ref(shape):
    M, K, N = shape
    a = ring.from_numpy_u64(RNG.integers(0, 1 << 64, (M, K), dtype=np.uint64))
    b = ring.from_numpy_u64(RNG.integers(0, 1 << 64, (K, N), dtype=np.uint64))
    got = ops.ring_matmul(a, b, tm=32, tn=32, interpret=True)
    want = ref.ring_matmul_ref(a, b)
    np.testing.assert_array_equal(np.asarray(got.hi), np.asarray(want.hi))
    np.testing.assert_array_equal(np.asarray(got.lo), np.asarray(want.lo))
    # spot-check a cell against python ints
    av = ring.to_numpy_u64(a).astype(object)
    bv = ring.to_numpy_u64(b).astype(object)
    want00 = sum(int(av[0, k]) * int(bv[k, 0]) for k in range(K)) % (1 << 64)
    assert int(ring.to_numpy_u64(got)[0, 0]) == want00


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=3, max_value=(1 << 128) - 1),
       st.integers(min_value=0), st.integers(min_value=0))
def test_hypothesis_montmul_kernel(n, a, b):
    n |= 1
    a %= n
    b %= n
    mod = Modulus.make(n)
    A = jnp.asarray(bigint.int_to_limbs(a, mod.L))[None]
    B = jnp.asarray(bigint.int_to_limbs(b, mod.L))[None]
    got = bigint.limbs_to_int(np.asarray(
        ops.rns_montmul(A, B, mod, tile_b=8, interpret=True))[0])
    R = 1 << (12 * mod.L)
    assert got == (a * b * pow(R, -1, n)) % n


@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=(1 << 64) - 1),
                min_size=4, max_size=4),
       st.lists(st.integers(min_value=0, max_value=(1 << 64) - 1),
                min_size=4, max_size=4))
def test_hypothesis_ring_matmul(avals, bvals):
    a = ring.from_numpy_u64(np.array(avals, np.uint64).reshape(2, 2))
    b = ring.from_numpy_u64(np.array(bvals, np.uint64).reshape(2, 2))
    got = ring.to_numpy_u64(ops.ring_matmul(a, b, tm=8, tn=8,
                                            interpret=True))
    for i in range(2):
        for j in range(2):
            want = sum(avals[2 * i + k] * bvals[2 * k + j]
                       for k in range(2)) % (1 << 64)
            assert int(got[i, j]) == want
