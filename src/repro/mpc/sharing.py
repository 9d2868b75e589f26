"""Protocol 1 — additive secret sharing over Z_2^64.

`share(x, key)` splits a ring tensor into uniformly-random additive
shares; `reconstruct` adds them back.  The multi-party variant splits into
exactly two shares destined for the two computing parties (CPs), matching
EFMVFL §4.3 — non-CP parties never hold shares of anything.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.crypto import prng, ring
from repro.crypto.ring import R64


@ring.share_program("share")
def share(x: R64, key: jax.Array) -> tuple[R64, R64]:
    """x -> (⟨x⟩_0, ⟨x⟩_1), ⟨x⟩_0 uniform (Theorem 2's PRNG assumption).
    One compiled program per shape."""
    hi, lo = prng.u32_pair(key, x.lo.shape)
    s0 = R64(hi, lo)
    s1 = ring.sub(x, s0)
    return s0, s1


def share_zero(shape, key: jax.Array) -> tuple[R64, R64]:
    """Shares of zero (used for re-randomization)."""
    hi, lo = prng.u32_pair(key, shape)
    s0 = R64(hi, lo)
    return s0, ring.neg(s0)


def reconstruct(*shares: R64) -> R64:
    acc = shares[0]
    for s in shares[1:]:
        acc = ring.add(acc, s)
    return acc


# Share-level linear algebra (each party runs these locally on its share;
# addition/subtraction/public-scalar ops commute with reconstruction).

add = ring.add
sub = ring.sub
neg = ring.neg


def add_public(share_val: R64, pub: R64, party: int) -> R64:
    """x + c where c is public: only party 0 adds the constant."""
    return ring.add(share_val, pub) if party == 0 else share_val


def mul_public_int(share_val: R64, k: int) -> R64:
    return ring.mul_pub_int(share_val, k)


def mul_public_elem(share_val: R64, pub: R64) -> R64:
    """Elementwise multiply by a public ring tensor."""
    return ring.mul(share_val, pub)


def matmul_public(x_pub_int: jnp.ndarray, share_val: R64) -> R64:
    return ring.matmul(x_pub_int, share_val)
