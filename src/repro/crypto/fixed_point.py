"""Fixed-point codecs bridging floats, the Z_2^64 share ring, and Z_n
Paillier plaintexts.

Conventions (DESIGN.md §7):
* Ring fixed point: value x ↦ round(x·2^f) mod 2^64 (two's complement).
  Default f = 20 fractional bits.
* Z_n plaintexts are non-negative; ring residues embed as their unsigned
  64-bit value, multipliers as residues mod 2^64.  Decrypted integers are
  reduced mod 2^64 to land back in the ring.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.crypto import bigint, ring
from repro.crypto.ring import R64

DEFAULT_FRAC_BITS = 20

_U32 = jnp.uint32
_R64_LIMBS = 6  # ceil(64 / 12)


def encode(x, f: int = DEFAULT_FRAC_BITS) -> R64:
    return ring.from_signed_f64(x, f)


def decode(a: R64, f: int = DEFAULT_FRAC_BITS) -> np.ndarray:
    return ring.to_signed_f64(a, f)


def encode_pub_int(x, f: int = DEFAULT_FRAC_BITS) -> np.ndarray:
    """Public floats -> signed int32 fixed-point multipliers (for
    public-by-share products where the multiplier fits 32 bits)."""
    v = np.rint(np.asarray(x, np.float64) * (1 << f))
    if np.any(np.abs(v) >= 2 ** 31):
        raise ValueError("public fixed-point multiplier exceeds int32")
    return v.astype(np.int32)


def r64_to_limbs(a: R64, L: int) -> jnp.ndarray:
    """Ring residue (unsigned 64-bit value) -> L-limb vector (L >= 6)."""
    with obs.span("vfl.crypto.to_limbs",
                  rows=a.lo.shape[0] if a.lo.ndim else 1):
        shifts_lo = [0, 12, 24]           # limbs 0..2 from lo (+ bridge)
        limbs = []
        lo, hi = a.lo, a.hi
        limbs.append(lo & _U32(0xFFF))                              # bits 0-11
        limbs.append((lo >> 12) & _U32(0xFFF))                      # 12-23
        limbs.append((lo >> 24) | ((hi & _U32(0xF)) << 8))          # 24-35
        limbs.append((hi >> 4) & _U32(0xFFF))                       # 36-47
        limbs.append((hi >> 16) & _U32(0xFFF))                      # 48-59
        limbs.append(hi >> 28)                                      # 60-63
        del shifts_lo
        out = jnp.stack(limbs, axis=-1) & _U32(0xFFF)
        pad = jnp.zeros(out.shape[:-1] + (L - _R64_LIMBS,), _U32)
        return jnp.concatenate([out, pad], axis=-1)


def limbs_to_r64(x: jnp.ndarray) -> R64:
    """Low 64 bits of a limb vector -> ring residue (i.e. reduce mod 2^64)."""
    with obs.span("vfl.crypto.from_limbs",
                  rows=x.shape[0] if x.ndim > 1 else 1):
        x = x.astype(_U32)
        l0, l1, l2, l3, l4, l5 = (x[..., i] for i in range(6))
        lo = l0 | (l1 << 12) | (l2 << 24)
        hi = (l2 >> 8) | (l3 << 4) | (l4 << 16) | (l5 << 28)
        return R64(hi, lo)


def u64_bits_msb(a: R64, nbits: int = 64) -> jnp.ndarray:
    """Ring residue -> MSB-first bit vector (for HE scalar multiply)."""
    bits_hi = [(a.hi >> (31 - i)) & _U32(1) for i in range(32)]
    bits_lo = [(a.lo >> (31 - i)) & _U32(1) for i in range(32)]
    full = jnp.stack(bits_hi + bits_lo, axis=-1)
    return full[..., 64 - nbits:]


def int_bits_msb(x: jnp.ndarray, nbits: int) -> jnp.ndarray:
    """Non-negative int32/uint32 array -> MSB-first bit vector."""
    x = x.astype(_U32)
    return jnp.stack([(x >> (nbits - 1 - i)) & _U32(1)
                      for i in range(nbits)], axis=-1)
