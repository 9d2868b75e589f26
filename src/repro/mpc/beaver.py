"""Beaver-triple multiplication over Z_2^64 shares.

Two triple sources:

* `DealerTripleSource` — the classic preprocessing model (a semi-honest
  dealer, or an offline phase run before training).  Cheap; used by
  benchmarks to match the paper's accounting, which treats triples as
  preprocessing.
* `paillier_triple` — 2-party online generation using the same Paillier
  keys the framework already has, closing the "no third party anywhere"
  loop: c = (a0+a1)(b0+b1) with cross terms computed under P1→P0
  encryption.  (Gilboa-style; one ciphertext round-trip per triple
  batch.)

`mul` consumes one triple per elementwise product:
  z = c + d·b + e·a + d·e   with d = x−a, e = y−b revealed.
The opened d, e are uniformly masked, so nothing leaks (Theorem 3).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.crypto import paillier, prng, ring
from repro.crypto import bigint, fixed_point
from repro.crypto.ring import R64
from repro.mpc import sharing


@dataclasses.dataclass
class TripleShares:
    """One party's share of (a, b, c) with c = a*b (elementwise)."""
    a: R64
    b: R64
    c: R64


class DealerTripleSource:
    """Preprocessing-phase triples from a seeded dealer.

    `drawn` counts stream advances (one per `elementwise` draw or
    `skip` unit) so replicated dealers can be audited for alignment;
    `state()`/`set_state()` capture the exact stream position for
    resumable sessions (`runtime.session.TrainState`)."""

    def __init__(self, seed: int = 0):
        self._key = jax.random.key(seed)
        self.drawn = 0

    def _next_key(self):
        self.drawn += 1
        self._key, sub = jax.random.split(self._key)
        return sub

    def state(self) -> dict:
        return {"key": obs.to_host(jax.random.key_data(self._key),
                                   "dealer", "protocol"),
                "drawn": int(self.drawn)}

    def set_state(self, st: dict) -> None:
        self._key = jax.random.wrap_key_data(
            jnp.asarray(np.asarray(st["key"], np.uint32)))
        self.drawn = int(st["drawn"])

    def skip(self, n: int) -> None:
        """Advance the triple stream by `n` draws without materializing
        them.  The distributed runtime replicates one dealer per party
        from the shared seed; parties not selected as computing parties
        this iteration skip the draws the CP pair consumed so every
        replica stays stream-aligned (one key split per draw, shapes
        irrelevant)."""
        for _ in range(n):
            self._next_key()

    def elementwise(self, shape) -> tuple[TripleShares, TripleShares]:
        """Both halves of the next triple: one compiled program per
        shape, the stream advanced by one key split as in `skip`."""
        self.drawn += 1
        self._key, t0, t1 = _draw_triple(self._key, tuple(shape))
        return TripleShares(*t0), TripleShares(*t1)


@ring.share_program("dealer_triple", static_argnums=(1,))
def _draw_triple(key: jax.Array, shape: tuple[int, ...]):
    """(next stream key, (a0, b0, c0), (a1, b1, c1))."""
    key, sub = jax.random.split(key)
    ka, kb, ks1, ks2, ks3 = jax.random.split(sub, 5)
    a = R64(*prng.u32_pair(ka, shape))
    b = R64(*prng.u32_pair(kb, shape))
    c = ring.mul(a, b)
    a0, a1 = sharing.share(a, ks1)
    b0, b1 = sharing.share(b, ks2)
    c0, c1 = sharing.share(c, ks3)
    return key, (a0, b0, c0), (a1, b1, c1)


def open_masked(x0: R64, x1: R64) -> R64:
    """Both parties exchange and add their shares of a *masked* value.
    (Communication: 8 bytes per element per direction — metered by the
    caller's transport.)"""
    return ring.add(x0, x1)


def mul(x: tuple[R64, R64], y: tuple[R64, R64],
        t0: TripleShares, t1: TripleShares) -> tuple[R64, R64]:
    """Elementwise share multiplication (simulation evaluates both
    parties).  Returns shares of x*y."""
    d = open_masked(ring.sub(x[0], t0.a), ring.sub(x[1], t1.a))
    e = open_masked(ring.sub(y[0], t0.b), ring.sub(y[1], t1.b))
    de = ring.mul(d, e)

    def party(i, t, xs, ys):
        z = ring.add(t.c, ring.mul(d, t.b))
        z = ring.add(z, ring.mul(e, t.a))
        if i == 0:
            z = ring.add(z, de)
        return z

    return (party(0, t0, x[0], y[0]), party(1, t1, x[1], y[1]))


def square(x: tuple[R64, R64], t0: TripleShares, t1: TripleShares):
    return mul(x, x, t0, t1)


def dot(x: tuple[R64, R64], y: tuple[R64, R64],
        t0: TripleShares, t1: TripleShares) -> tuple[R64, R64]:
    """Shares of sum_i x_i * y_i (triple shapes match x)."""
    z0, z1 = mul(x, y, t0, t1)
    return ring.sum_axis(z0, 0), ring.sum_axis(z1, 0)


# ---------------------------------------------------------------------------
# Paillier-based triple generation (fully third-party-free preprocessing)
# ---------------------------------------------------------------------------

def paillier_triple(shape, key0: paillier.PrivateKey,
                    rng: np.random.Generator, jkey: jax.Array
                    ) -> tuple[TripleShares, TripleShares]:
    """P0 owns key0.  P0 samples (a0, b0), P1 samples (a1, b1, r).
    P1 computes [[a0]]⊗b1 ⊕ [[b0]]⊗a1 ⊕ [[r]] and returns it; then
      c0 = a0 b0 + Dec(·) mod 2^64,   c1 = a1 b1 − r mod 2^64.
    Residue-lift semantics make the mod-2^64 reduction exact (DESIGN §7);
    requires key_bits ≥ 64 + 64 + log2(#terms) + 40 — use ≥ 256-bit keys.
    """
    pub = key0.pub
    if pub.key_bits < 192:
        raise ValueError("paillier_triple needs >=192-bit keys for exactness")
    n_elems = int(np.prod(shape))
    k0, k1, k2, k3 = jax.random.split(jkey, 4)
    a0 = R64(*prng.u32_pair(k0, shape))
    b0 = R64(*prng.u32_pair(k1, shape))
    a1 = R64(*prng.u32_pair(k2, shape))
    b1 = R64(*prng.u32_pair(k3, shape))
    # P0 -> P1: [[a0]], [[b0]]
    ca0 = paillier.encrypt(pub, fixed_point.r64_to_limbs(a0, pub.Ln).reshape(-1, pub.Ln), rng=rng)
    cb0 = paillier.encrypt(pub, fixed_point.r64_to_limbs(b0, pub.Ln).reshape(-1, pub.Ln), rng=rng)
    # P1: cross terms + statistical mask r (uniform 64+40 bits)
    r_ints = prng.host_uniform_below(1 << 104, n_elems, rng=rng)
    r_limbs = bigint.ints_to_limbs(r_ints, pub.Ln)
    cr = paillier.encrypt(pub, r_limbs, rng=rng)
    b1_bits = fixed_point.u64_bits_msb(b1).reshape(n_elems, 64)
    a1_bits = fixed_point.u64_bits_msb(a1).reshape(n_elems, 64)
    cross = paillier.add_ct(pub, paillier.smul_bits(pub, ca0, b1_bits),
                            paillier.smul_bits(pub, cb0, a1_bits))
    cross = paillier.add_ct(pub, cross, cr)
    # P0 decrypts, reduces mod 2^64
    dec = paillier.decrypt(key0, cross)
    cross64 = fixed_point.limbs_to_r64(dec)
    cross64 = R64(cross64.hi.reshape(shape), cross64.lo.reshape(shape))
    c0 = ring.add(ring.mul(a0, b0), cross64)
    r64v = fixed_point.limbs_to_r64(jnp.asarray(r_limbs))
    r64v = R64(r64v.hi.reshape(shape), r64v.lo.reshape(shape))
    c1 = ring.sub(ring.mul(a1, b1), r64v)
    return TripleShares(a0, b0, c0), TripleShares(a1, b1, c1)
