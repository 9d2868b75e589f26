"""Z_{2^64} arithmetic as (hi, lo) uint32 pairs.

The MPC share ring.  TPUs have no native 64-bit integer multiplier, so a
ring element is a pair of uint32 lanes and every op is built from 32-bit
(and, inside kernels, 8/16-bit MXU) primitives.  uint32 add/sub/mul in XLA
wrap modulo 2^32, which is exactly the semantics we need.

A `R64` is a NamedTuple pytree of two equal-shape uint32 arrays.

Each public op is one compiled program per shape (and static argument:
shift amount, axis, public integer), so a call from the host is one
launch, not one per uint32 primitive, and its constants are part of the
program rather than host scalars sent with each primitive.  Called while
another program traces, an op inlines into it.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

_U32 = jnp.uint32


class R64(NamedTuple):
    hi: jnp.ndarray
    lo: jnp.ndarray

    @property
    def shape(self):
        return self.lo.shape


def r64(hi, lo) -> R64:
    return R64(jnp.asarray(hi, _U32), jnp.asarray(lo, _U32))


def zeros(shape) -> R64:
    return R64(jnp.zeros(shape, _U32), jnp.zeros(shape, _U32))


def from_numpy_u64(x: np.ndarray) -> R64:
    x = np.asarray(x, np.uint64)
    return R64(obs.to_device((x >> np.uint64(32)).astype(np.uint32)),
               obs.to_device((x & np.uint64(0xFFFFFFFF)).astype(np.uint32)))


def to_numpy_u64(x: R64) -> np.ndarray:
    hi = np.asarray(x.hi, np.uint64)
    lo = np.asarray(x.lo, np.uint64)
    return (hi << np.uint64(32)) | lo


def share_program(op: str, static_argnums: tuple[int, ...] = ()):
    """Compile `body` as one program per shape and static argument; each
    call from the host counts one ``share_calls[op]`` (`repro.obs`), and
    a call traced into another program counts nothing."""
    def wrap(body):
        program = jax.jit(obs.uncounted()(body),
                          static_argnums=static_argnums)

        @functools.wraps(body)
        def call(first, *rest, **kw):
            obs.count_share_call(
                op, first[0] if isinstance(first, tuple) else first)
            return program(first, *rest, **kw)
        return call
    return wrap


@share_program("add")
def add(a: R64, b: R64) -> R64:
    lo = a.lo + b.lo
    carry = (lo < a.lo).astype(_U32)
    return R64(a.hi + b.hi + carry, lo)


@share_program("sub")
def sub(a: R64, b: R64) -> R64:
    lo = a.lo - b.lo
    borrow = (a.lo < b.lo).astype(_U32)
    return R64(a.hi - b.hi - borrow, lo)


@share_program("neg")
def neg(a: R64) -> R64:
    return sub(zeros(a.lo.shape), a)


def umul32(a: jnp.ndarray, b: jnp.ndarray):
    """Full 32x32 -> 64-bit product as (hi, lo), via 16-bit halves."""
    a0 = a & _U32(0xFFFF)
    a1 = a >> 16
    b0 = b & _U32(0xFFFF)
    b1 = b >> 16
    p00 = a0 * b0
    p01 = a0 * b1
    p10 = a1 * b0
    p11 = a1 * b1
    mid = (p00 >> 16) + (p01 & _U32(0xFFFF)) + (p10 & _U32(0xFFFF))
    lo = (p00 & _U32(0xFFFF)) | (mid << 16)
    hi = p11 + (p01 >> 16) + (p10 >> 16) + (mid >> 16)
    return hi, lo


@share_program("mul")
def mul(a: R64, b: R64) -> R64:
    """a*b mod 2^64."""
    hi, lo = umul32(a.lo, b.lo)
    hi = hi + a.lo * b.hi + a.hi * b.lo
    return R64(hi, lo)


def mul_pub_int(a: R64, k: int) -> R64:
    """Multiply by a public python integer (reduced mod 2^64)."""
    return _mul_pub_int(a, int(k) % (1 << 64))


@share_program("mul_pub_int", static_argnums=(1,))
def _mul_pub_int(a: R64, k: int) -> R64:
    kb = R64(jnp.full(a.lo.shape, (k >> 32) & 0xFFFFFFFF, _U32),
             jnp.full(a.lo.shape, k & 0xFFFFFFFF, _U32))
    return mul(a, kb)


@share_program("shift_left", static_argnums=(1,))
def shift_left(a: R64, s: int) -> R64:
    if s == 0:
        return a
    if s >= 64:
        return zeros(a.lo.shape)
    if s >= 32:
        return R64(a.lo << (s - 32) if s > 32 else a.lo, jnp.zeros_like(a.lo))
    return R64((a.hi << s) | (a.lo >> (32 - s)), a.lo << s)


@share_program("shift_right_logical", static_argnums=(1,))
def shift_right_logical(a: R64, s: int) -> R64:
    if s == 0:
        return a
    if s >= 64:
        return zeros(a.lo.shape)
    if s >= 32:
        return R64(jnp.zeros_like(a.hi),
                   a.hi >> (s - 32) if s > 32 else a.hi)
    return R64(a.hi >> s, (a.lo >> s) | (a.hi << (32 - s)))


def from_signed_f64(x, f: int) -> R64:
    """Encode floats as fixed-point ring elements: round(x * 2^f) mod 2^64.
    Uses float64-safe two-stage splitting so 64-bit precision survives."""
    x = np.asarray(x, np.float64) * float(1 << f)
    v = np.asarray(np.rint(x), np.int64).astype(np.uint64)
    return from_numpy_u64(v)


def to_signed_f64(a: R64, f: int) -> np.ndarray:
    """Decode: centered lift to [-2^63, 2^63) then scale by 2^-f."""
    v = to_numpy_u64(a).astype(np.int64)  # two's complement reinterpret
    return v.astype(np.float64) / float(1 << f)


@share_program("eq")
def eq(a: R64, b: R64) -> jnp.ndarray:
    return (a.hi == b.hi) & (a.lo == b.lo)


@share_program("sum_axis", static_argnums=(1,))
def sum_axis(a: R64, axis: int) -> R64:
    """Sum along an axis mod 2^64: widen lo into (carry-tracked) pieces.
    Implemented as pairwise tree-reduction using `add` semantics."""
    hi, lo = a.hi, a.lo
    n = hi.shape[axis]
    # move axis first, then fold sequentially in log steps
    hi = jnp.moveaxis(hi, axis, 0)
    lo = jnp.moveaxis(lo, axis, 0)
    cur = R64(hi, lo)
    length = n
    while length > 1:
        half = length // 2
        a1 = R64(cur.hi[:half], cur.lo[:half])
        a2 = R64(cur.hi[half:2 * half], cur.lo[half:2 * half])
        s = add(a1, a2)
        if length % 2:
            tail = R64(cur.hi[2 * half:], cur.lo[2 * half:])
            s = R64(jnp.concatenate([s.hi, tail.hi], 0),
                    jnp.concatenate([s.lo, tail.lo], 0))
        cur = s
        length = half + (length % 2)
    return R64(cur.hi[0], cur.lo[0])


@share_program("matmul")
def matmul(x_pub_int: jnp.ndarray, a: R64) -> R64:
    """Public signed-int32 matrix times ring matrix — used where one
    operand is public (e.g. X^T times a revealed-masked vector).  For
    share-by-share products use mpc.beaver instead.

    x: (..., m, n) int32 (signed, public); a: R64 of shape (..., n, k).
    Signed entries are lifted to their Z_2^64 residues (hi = sign
    extension), which is exact under mod-2^64 semantics.
    """
    xlo = x_pub_int.astype(_U32)
    xhi = jnp.where(x_pub_int < 0, _U32(0xFFFFFFFF), _U32(0))
    # elementwise product then sum: broadcast (..., m, n, 1) x (..., 1, n, k)
    xa = R64(xhi[..., :, :, None], xlo[..., :, :, None])
    av = R64(a.hi[..., None, :, :], a.lo[..., None, :, :])
    prod = mul(xa, av)
    return sum_axis(prod, -2)
