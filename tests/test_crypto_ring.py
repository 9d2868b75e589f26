"""Z_2^64 (hi,lo)-pair arithmetic vs numpy uint64 oracles."""
import jax
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax.numpy as jnp

from repro.crypto import fixed_point, ring
from repro.mpc import beaver, sharing

RNG = np.random.default_rng(5)
U64 = st.integers(min_value=0, max_value=(1 << 64) - 1)


def rand_u64(shape):
    return RNG.integers(0, 1 << 64, size=shape, dtype=np.uint64)


def test_roundtrip():
    x = rand_u64((4, 3))
    assert (ring.to_numpy_u64(ring.from_numpy_u64(x)) == x).all()


@settings(max_examples=60, deadline=None)
@given(U64, U64)
def test_add_sub_mul_hypothesis(a, b):
    A = ring.from_numpy_u64(np.array([a], np.uint64))
    B = ring.from_numpy_u64(np.array([b], np.uint64))
    m = (1 << 64) - 1
    assert int(ring.to_numpy_u64(ring.add(A, B))[0]) == (a + b) & m
    assert int(ring.to_numpy_u64(ring.sub(A, B))[0]) == (a - b) & m
    assert int(ring.to_numpy_u64(ring.mul(A, B))[0]) == (a * b) & m
    assert int(ring.to_numpy_u64(ring.neg(A))[0]) == (-a) & m


def test_mul_pub_and_shifts():
    x = rand_u64((16,))
    X = ring.from_numpy_u64(x)
    for k in [0, 1, 3, -5, 1 << 40]:
        got = ring.to_numpy_u64(ring.mul_pub_int(X, k))
        want = x * np.uint64(k % (1 << 64))
        assert (got == want).all()
    for s in [0, 1, 12, 31, 32, 33, 63]:
        assert (ring.to_numpy_u64(ring.shift_left(X, s)) == (x << np.uint64(s))).all()
        assert (ring.to_numpy_u64(ring.shift_right_logical(X, s))
                == (x >> np.uint64(s))).all()


def test_fixed_point_roundtrip():
    x = RNG.normal(size=(32,)) * 100
    enc = fixed_point.encode(x, 20)
    dec = fixed_point.decode(enc, 20)
    np.testing.assert_allclose(dec, x, atol=2 ** -20)


def test_sum_axis():
    x = rand_u64((7, 5))
    got = ring.to_numpy_u64(ring.sum_axis(ring.from_numpy_u64(x), 0))
    want = x.sum(axis=0)  # numpy uint64 wraps mod 2^64
    assert (got == want).all()


def test_matmul_public_by_ring():
    xs = RNG.integers(-1000, 1000, size=(4, 6)).astype(np.int32)
    a = rand_u64((6, 3))
    got = ring.to_numpy_u64(ring.matmul(jnp.asarray(xs), ring.from_numpy_u64(a)))
    want = (xs.astype(np.int64).astype(np.uint64)[:, :, None] * a[None]).sum(1)
    assert (got == want).all()


# --- every compiled op against Python integers mod 2^64, at the edges ---

M64 = (1 << 64) - 1
#: carry and borrow chains through both halves, 0 and 2^64 − 1
EDGES = [0, 1, 2, 0xFFFFFFFF, 1 << 32, (1 << 32) + 1, 0xFFFFFFFF00000000,
         0x00000000FFFFFFFF, 0x7FFFFFFFFFFFFFFF, 1 << 63, M64 - 1, M64,
         0x123456789ABCDEF0, 0xFEDCBA9876543210]
EDGE_A = np.array([a for a in EDGES for _ in EDGES], np.uint64)
EDGE_B = np.array([b for _ in EDGES for b in EDGES], np.uint64)
SHIFTS = (0, 1, 31, 32, 33, 63, 64)
PUB_INTS = (0, 1, -1, -5, -(1 << 63), (1 << 64) - 1, 1 << 64,
            (1 << 64) + 3, (1 << 70) + 12345, -(1 << 80) - 7)


def _ints(x):
    return [int(v) for v in np.asarray(x, np.uint64).ravel()]


def _elementwise(op, oracle):
    def case():
        got = op(ring.from_numpy_u64(EDGE_A), ring.from_numpy_u64(EDGE_B))
        want = [oracle(a, b) for a, b in zip(_ints(EDGE_A), _ints(EDGE_B))]
        return got, want
    return case


def _shifts(op, oracle):
    def case():
        X = ring.from_numpy_u64(EDGE_A)
        got = [op(X, s) for s in SHIFTS]
        want = [[oracle(a, s) & M64 for a in _ints(EDGE_A)] for s in SHIFTS]
        return got, want
    return case


def _mul_pub():
    X = ring.from_numpy_u64(EDGE_A)
    got = [ring.mul_pub_int(X, k) for k in PUB_INTS]
    want = [[(a * k) & M64 for a in _ints(EDGE_A)] for k in PUB_INTS]
    return got, want


def _sum_axis():
    got, want = [], []
    for n in (1, 3, 511, 2048):
        x = np.array([EDGES[i % len(EDGES)] for i in range(2 * n)],
                     np.uint64).reshape(n, 2)
        X = ring.from_numpy_u64(x)
        got += [ring.sum_axis(X, 0), ring.sum_axis(
            ring.from_numpy_u64(x.T.copy()), axis=-1)]
        col = [sum(_ints(x[:, j])) & M64 for j in range(2)]
        want += [col, col]
    return got, want


def _matmul():
    xs = np.array([[0, 1, -1, 2**31 - 1], [-2**31, 7, -3, 0]], np.int32)
    a = np.array(EDGES[:12], np.uint64).reshape(4, 3)
    got = ring.matmul(jnp.asarray(xs), ring.from_numpy_u64(a))
    want = [sum(int(xs[i, k]) * int(a[k, j]) for k in range(4)) & M64
            for i in range(2) for j in range(3)]
    return got, want


def _eq():
    got = ring.eq(ring.from_numpy_u64(EDGE_A), ring.from_numpy_u64(EDGE_B))
    return got, [a == b for a, b in zip(_ints(EDGE_A), _ints(EDGE_B))]


ORACLE_CASES = {
    "add": _elementwise(ring.add, lambda a, b: (a + b) & M64),
    "sub": _elementwise(ring.sub, lambda a, b: (a - b) & M64),
    "mul": _elementwise(ring.mul, lambda a, b: (a * b) & M64),
    "neg": _elementwise(lambda a, b: ring.neg(a), lambda a, b: -a & M64),
    "eq": _eq,
    "shift_left": _shifts(ring.shift_left, lambda a, s: a << s),
    "shift_right_logical": _shifts(ring.shift_right_logical,
                                   lambda a, s: a >> s),
    "mul_pub_int": _mul_pub,
    "sum_axis": _sum_axis,
    "matmul": _matmul,
}


def _flat(got):
    if isinstance(got, list):
        return [_flat(g) for g in got]
    if isinstance(got, ring.R64):
        return _ints(ring.to_numpy_u64(got))
    return [bool(v) for v in np.asarray(got).ravel()]


@pytest.mark.parametrize("op", sorted(ORACLE_CASES))
def test_compiled_op_matches_python_ints(op):
    got, want = ORACLE_CASES[op]()
    assert _flat(got) == want


def _tree_bits(tree):
    return [np.asarray(v) for v in jax.tree.leaves(tree)]


def test_compiled_draw_and_split_equal_their_bodies():
    """The dealer's triple draw and the share split give the bits, and
    leave the stream where, their bodies run eagerly would."""
    def draw():
        dealer = beaver.DealerTripleSource(seed=2**31 + 11)
        t = [dealer.elementwise(s) for s in ((5,), (2, 3), (5,))]
        split = sharing.share(ring.from_numpy_u64(EDGE_A),
                              jax.random.key(2**31 + 12))
        flat = [(x.a, x.b, x.c) for pair in t for x in pair]
        st = dealer.state()
        return _tree_bits((flat, split)) + [np.asarray(st["key"]),
                                            np.asarray(st["drawn"])]

    compiled = draw()
    with jax.disable_jit():
        eager = draw()
    assert len(compiled) == len(eager)
    for c, e in zip(compiled, eager):
        np.testing.assert_array_equal(c, e)
