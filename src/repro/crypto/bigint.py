"""Vectorized big-integer arithmetic on radix-2^12 uint32 limb arrays.

Design (TPU adaptation of gmp-style word-serial bignum):

* A k-bit integer is a little-endian vector of ``L = ceil(k/12)`` limbs,
  each stored in a uint32 lane but holding < 2^12.  Limb products are
  < 2^24 and a full convolution row accumulates < L * 2^24 < 2^32 for
  L <= 255 (covers 3060-bit moduli), so the entire schoolbook/Montgomery
  pipeline runs in *native int32 vector ops* — the representation chosen
  because TPUs have no 64x64 multiplier and no carry flag, but do have
  wide int32 vector ALUs and an int MXU.
* All functions broadcast over arbitrary leading batch dimensions; the
  limb axis is always the last axis.
* Montgomery residue arithmetic: ``R = 2^(12*L)``; `mont_mul(a, b)`
  returns ``a*b*R^-1 mod N``.  Ciphertexts are kept in the Montgomery
  domain end-to-end (see paillier.py).

The exactness trick that keeps everything branch-free: the stored uint32
vector always represents the exact value ``sum_j T[j] * 2^(12 j)`` — limbs
are allowed to exceed 2^12 transiently ("lazy carries"), and since no limb
lies *below* limb 0, ``T[0] mod 2^12`` is always exact, which is all the
Montgomery round needs.  A single `lax.scan` carry sweep restores the
canonical form where required.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

LIMB_BITS = 12
LIMB_RADIX = 1 << LIMB_BITS
MASK = LIMB_RADIX - 1
_U32 = jnp.uint32


# ---------------------------------------------------------------------------
# Host-side conversions (numpy / python int — used for keys & test oracles)
# ---------------------------------------------------------------------------

def nlimbs(nbits: int) -> int:
    return -(-nbits // LIMB_BITS)


def int_to_limbs(x: int, L: int) -> np.ndarray:
    """Python int -> (L,) uint32 limb vector (host-side)."""
    if x < 0:
        raise ValueError("int_to_limbs takes non-negative integers")
    if x >> (LIMB_BITS * L):
        raise ValueError(f"value needs more than {L} limbs")
    out = np.zeros(L, dtype=np.uint32)
    for i in range(L):
        out[i] = x & MASK
        x >>= LIMB_BITS
    return out


def ints_to_limbs(xs: Sequence[int], L: int) -> np.ndarray:
    return np.stack([int_to_limbs(int(x), L) for x in xs])


def limbs_to_int(limbs) -> int:
    """(… , L) limb array -> python int (host-side; batch -> list)."""
    arr = np.asarray(limbs)
    if arr.ndim == 1:
        val = 0
        for i in range(arr.shape[0] - 1, -1, -1):
            val = (val << LIMB_BITS) | int(arr[i])
        return val
    return [limbs_to_int(a) for a in arr]


# ---------------------------------------------------------------------------
# Carry / borrow sweeps (exact, one sequential pass along the limb axis)
# ---------------------------------------------------------------------------
#
# The sweeps and the schoolbook product are jitted: called eagerly (as
# Paillier encryption and CRT decryption call them), an unjitted
# `lax.scan` or `fori_loop` is traced and compiled again on every call —
# on a TPU a compile per call.  Jitted, they compile once per shape.

@jax.jit
def carry_sweep(t: jnp.ndarray) -> jnp.ndarray:
    """Exact normalization: limbs < 2^12 afterwards.  Input limbs may hold
    any uint32 value; the final carry out of the top limb is dropped
    (i.e. arithmetic is mod 2^(12 L))."""
    t = t.astype(_U32)
    xs = jnp.moveaxis(t, -1, 0)

    def step(c, x):
        s = x + c
        return s >> LIMB_BITS, s & MASK

    _, ys = jax.lax.scan(step, jnp.zeros(t.shape[:-1], _U32), xs)
    return jnp.moveaxis(ys, 0, -1)


@jax.jit
def _sub_with_borrow(a: jnp.ndarray, b: jnp.ndarray):
    """a - b limbwise for canonical inputs.  Returns (diff, borrow_out)
    where diff is canonical and borrow_out is 1 where a < b."""
    xs = jnp.moveaxis(jnp.stack([a, b], axis=0), -1, 0)  # (L, 2, ...)

    def step(borrow, ab):
        aj, bj = ab[0], ab[1]
        t = aj + _U32(LIMB_RADIX) - bj - borrow
        return _U32(1) - (t >> LIMB_BITS), t & MASK

    borrow, ys = jax.lax.scan(
        step, jnp.zeros(a.shape[:-1], _U32), xs)
    return jnp.moveaxis(ys, 0, -1), borrow


@jax.jit
def _add_limbs(a: jnp.ndarray, b: jnp.ndarray):
    """a + b, canonical inputs -> (canonical sum mod 2^(12L), carry_out)."""
    s = a + b
    xs = jnp.moveaxis(s, -1, 0)

    def step(c, x):
        t = x + c
        return t >> LIMB_BITS, t & MASK

    carry, ys = jax.lax.scan(step, jnp.zeros(a.shape[:-1], _U32), xs)
    return jnp.moveaxis(ys, 0, -1), carry


def big_lt(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a < b elementwise over the batch (canonical limbs)."""
    _, borrow = _sub_with_borrow(a, b)
    return borrow.astype(jnp.bool_)


def big_eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(a == b, axis=-1)


# ---------------------------------------------------------------------------
# Modulus descriptor
# ---------------------------------------------------------------------------

def _inv_mod(a: int, m: int) -> int:
    return pow(a, -1, m)


@dataclasses.dataclass(frozen=True)
class Modulus:
    """Static per-key modulus data.  The numpy arrays become constants in
    jitted computations (keys are long-lived)."""

    value: int              # N as python int (host only)
    L: int                  # limb count; R = 2^(12 L) > N
    limbs: np.ndarray       # (L,) uint32
    n0inv: int              # -N^{-1} mod 2^12
    r1: np.ndarray          # R mod N       == mont(1)
    r2: np.ndarray          # R^2 mod N     (to_mont multiplier)
    hensel: np.ndarray | None = None  # N^{-1} mod 2^(12 Lh) for exact div

    @staticmethod
    def make(n: int, hensel_limbs: int | None = None) -> "Modulus":
        if n % 2 == 0:
            raise ValueError("modulus must be odd")
        L = nlimbs(n.bit_length())
        R = 1 << (LIMB_BITS * L)
        hens = None
        if hensel_limbs is not None:
            hm = 1 << (LIMB_BITS * hensel_limbs)
            hens = int_to_limbs(_inv_mod(n, hm), hensel_limbs)
        return Modulus(
            value=n,
            L=L,
            limbs=int_to_limbs(n, L),
            n0inv=(-_inv_mod(n, LIMB_RADIX)) % LIMB_RADIX,
            r1=int_to_limbs(R % n, L),
            r2=int_to_limbs((R * R) % n, L),
            hensel=hens,
        )


# ---------------------------------------------------------------------------
# Core modular ops
# ---------------------------------------------------------------------------

def cond_sub_mod(t: jnp.ndarray, mod: Modulus) -> jnp.ndarray:
    """t - N if t >= N else t (canonical t < 2N, same limb count as N)."""
    n = jnp.asarray(mod.limbs, _U32)
    diff, borrow = _sub_with_borrow(t, jnp.broadcast_to(n, t.shape))
    keep = (borrow == 1)[..., None]
    return jnp.where(keep, t, diff)


def mod_add(a: jnp.ndarray, b: jnp.ndarray, mod: Modulus) -> jnp.ndarray:
    a, b = jnp.broadcast_arrays(a, b)
    s, carry = _add_limbs(a, b)
    # a, b < N < 2^(12L): sum < 2N may carry out one bit; fold the carry in
    # by treating it virtually: if carry==1 the sum >= R > N, must subtract.
    n = jnp.asarray(mod.limbs, _U32)
    diff, borrow = _sub_with_borrow(s, jnp.broadcast_to(n, s.shape))
    need_sub = (carry == 1) | (borrow == 0)
    return jnp.where(need_sub[..., None], diff, s)


def mod_sub(a: jnp.ndarray, b: jnp.ndarray, mod: Modulus) -> jnp.ndarray:
    a, b = jnp.broadcast_arrays(a, b)
    d, borrow = _sub_with_borrow(a, b)
    n = jnp.asarray(mod.limbs, _U32)
    dn, _ = _add_limbs(d, jnp.broadcast_to(n, d.shape))
    return jnp.where((borrow == 1)[..., None], dn, d)


def _one_shot_carry(t: jnp.ndarray) -> jnp.ndarray:
    """Move each limb's overflow one position up (value-preserving; does
    NOT fully normalize).  Keeps lazy limbs bounded during the Montgomery
    loop.  The top limb's overflow must be representable (guaranteed by
    the round bounds, see module docstring)."""
    low = t & MASK
    hi = t >> LIMB_BITS
    return low + jnp.concatenate(
        [jnp.zeros_like(hi[..., :1]), hi[..., :-1]], axis=-1)


def mont_mul(a: jnp.ndarray, b: jnp.ndarray, mod: Modulus) -> jnp.ndarray:
    """Montgomery product a*b*R^-1 mod N (CIOS, vectorized over batch).

    a, b canonical (< N).  Output canonical (< N).
    Per-round invariant: lazy limbs stay < 2^16 entering a round, grow to
    < 2^16 + 2^25 after the two MAC rows, and the one-shot carry plus the
    shift restore < 2^16 — all comfortably inside uint32.
    """
    a, b = jnp.broadcast_arrays(a.astype(_U32), b.astype(_U32))
    L = mod.L
    n = jnp.asarray(mod.limbs, _U32)
    n0inv = _U32(mod.n0inv)
    bshape = a.shape[:-1]

    t0 = jnp.zeros(bshape + (L + 1,), _U32)

    def round_fn(i, t):
        ai = jax.lax.dynamic_index_in_dim(a, i, axis=-1, keepdims=True)
        t = t.at[..., :L].add(ai * b)
        m = (t[..., 0] * n0inv) & MASK
        t = t.at[..., :L].add(m[..., None] * n)
        # limb 0 is now ≡ 0 mod 2^12; shift down one limb, carrying its top.
        carry0 = t[..., 0] >> LIMB_BITS
        t = jnp.concatenate(
            [t[..., 1:], jnp.zeros(bshape + (1,), _U32)], axis=-1)
        t = t.at[..., 0].add(carry0)
        return _one_shot_carry(t)

    t = jax.lax.fori_loop(0, L, round_fn, t0)
    t = carry_sweep(t)          # canonical, L+1 limbs, value < 2N
    t = cond_sub_mod(t, Modulus(  # compare against N padded to L+1 limbs
        value=mod.value, L=L + 1,
        limbs=np.concatenate([mod.limbs, np.zeros(1, np.uint32)]),
        n0inv=mod.n0inv, r1=mod.r1, r2=mod.r2))
    return t[..., :L]


def to_mont(a: jnp.ndarray, mod: Modulus) -> jnp.ndarray:
    return mont_mul(a, jnp.asarray(mod.r2, _U32), mod)


def from_mont(a: jnp.ndarray, mod: Modulus) -> jnp.ndarray:
    one = jnp.zeros(mod.L, _U32).at[0].set(1)
    return mont_mul(a, one, mod)


def mont_one(mod: Modulus) -> jnp.ndarray:
    return jnp.asarray(mod.r1, _U32)


# ---------------------------------------------------------------------------
# Exponentiation (constant-time square-and-multiply over a bit vector)
# ---------------------------------------------------------------------------

def int_to_bits(e: int, nbits: int) -> np.ndarray:
    """MSB-first bit vector of a host integer (vectorized: one to_bytes
    plus an unpackbits, no per-bit python loop)."""
    e = int(e)
    if e >> nbits:
        raise ValueError("exponent wider than nbits")
    if nbits == 0:
        return np.zeros(0, dtype=np.uint32)
    by = np.frombuffer(e.to_bytes((nbits + 7) // 8, "big"), np.uint8)
    return np.unpackbits(by)[-nbits:].astype(np.uint32)


@functools.lru_cache(maxsize=4096)
def cached_bits(e: int, nbits: int) -> np.ndarray:
    """Memoized MSB-first bit decomposition keyed on (exponent, width) —
    host-known exponents (n, λ, smul_const multipliers) repeat every
    iteration, so the decomposition is paid once per key/constant."""
    out = int_to_bits(e, nbits)
    out.setflags(write=False)
    return out


def limbs_to_bits(x: jnp.ndarray, nbits: int) -> jnp.ndarray:
    """Traced limb vector -> MSB-first bit vector of width nbits."""
    L = x.shape[-1]
    pos = np.arange(nbits - 1, -1, -1)
    limb_idx = pos // LIMB_BITS
    bit_idx = pos % LIMB_BITS
    if (limb_idx >= L).any():
        raise ValueError("nbits exceeds limb capacity")
    gathered = jnp.take(x, jnp.asarray(limb_idx), axis=-1)
    return (gathered >> jnp.asarray(bit_idx, _U32)) & _U32(1)


def mont_exp_bits(base_mont: jnp.ndarray, bits: jnp.ndarray,
                  mod: Modulus) -> jnp.ndarray:
    """base^e in the Montgomery domain.  `bits` is MSB-first, shape
    broadcastable to base's batch + (nbits,).  Constant-time (select, not
    branch) — appropriate for secret exponents (Paillier decryption)."""
    base_mont = jnp.asarray(base_mont, _U32)
    bshape = jnp.broadcast_shapes(base_mont.shape[:-1], bits.shape[:-1])
    base_mont = jnp.broadcast_to(base_mont, bshape + base_mont.shape[-1:])
    bits = jnp.broadcast_to(bits.astype(_U32), bshape + bits.shape[-1:])
    acc0 = jnp.broadcast_to(mont_one(mod), base_mont.shape)

    def step(acc, bit):
        acc = mont_mul(acc, acc, mod)
        mul = mont_mul(acc, base_mont, mod)
        return jnp.where(bit[..., None] == 1, mul, acc), None

    acc, _ = jax.lax.scan(step, acc0, jnp.moveaxis(bits, -1, 0))
    return acc


def mont_exp_const(base_mont: jnp.ndarray, e: int, mod: Modulus) -> jnp.ndarray:
    """base^e for a host-known exponent (key material: n, lambda)."""
    if e == 0:
        return jnp.broadcast_to(mont_one(mod), base_mont.shape)
    bits = jnp.asarray(cached_bits(e, e.bit_length()))
    return mont_exp_bits(base_mont, bits, mod)


# ---------------------------------------------------------------------------
# Plain (non-modular) products used by Paillier
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames="out_limbs")
def big_mul_full(a: jnp.ndarray, b: jnp.ndarray, out_limbs: int) -> jnp.ndarray:
    """Exact product of canonical inputs, truncated/padded to out_limbs.
    Accumulation bound: min(La, Lb) * 2^24 < 2^32 for <=255 limbs."""
    a = a.astype(_U32)
    b = b.astype(_U32)
    La = a.shape[-1]
    bshape = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    a = jnp.broadcast_to(a, bshape + (La,))
    b = jnp.broadcast_to(b, bshape + (b.shape[-1],))
    acc0 = jnp.zeros(bshape + (out_limbs,), _U32)
    bpad = jnp.pad(b, [(0, 0)] * (b.ndim - 1)
                   + [(0, max(0, out_limbs - b.shape[-1]))])[..., :out_limbs]

    def step(i, acc):
        ai = jax.lax.dynamic_index_in_dim(a, i, axis=-1, keepdims=True)
        shifted = jnp.roll(bpad, i, axis=-1)
        keep = jnp.arange(out_limbs) >= i
        shifted = jnp.where(keep, shifted, 0)
        return acc + ai * shifted

    acc = jax.lax.fori_loop(0, min(La, out_limbs), step, acc0)
    return carry_sweep(acc)


def mul_low(a: jnp.ndarray, b: jnp.ndarray, L: int) -> jnp.ndarray:
    """a*b mod 2^(12 L) — used for Hensel exact division."""
    return big_mul_full(a, b, L)


def add_small(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """a + k for small k >= 0 (canonical in, canonical out)."""
    t = a.at[..., 0].add(_U32(k))
    return carry_sweep(t)


def sub_small(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """a - k for small k, assuming a >= k."""
    kv = jnp.zeros(a.shape[-1], _U32).at[0].set(k)
    d, _ = _sub_with_borrow(a, jnp.broadcast_to(kv, a.shape))
    return d
