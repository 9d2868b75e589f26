"""Pallas TPU kernel: Z_{2^64} matrix multiplication via limb-decomposed
integer MXU contractions.

Secret-share linear algebra (SS-LR baselines, Beaver-based dot products,
X^T·⟨d⟩ in mod-2^64 semantics) is matmul over the ring Z_2^64.  TPUs have
no 64-bit integer units, but the MXU eats low-precision integer matmuls.
We split each 64-bit operand into eight 8-bit limbs and evaluate the 36
partial-product contractions whose weight 2^{8(i+j)} survives mod 2^64:

    C = Σ_{i+j ≤ 7}  (A_i @ B_j) · 2^{8(i+j)}   (mod 2^64)

Each A_i @ B_j is an integer matmul with operands < 2^8 and K ≤ 2^15, so
int32 accumulation is exact.  Recombination lifts each partial into a
(hi, lo) uint32 pair and shift-adds — pure VPU work.

  grid   : (M/TM, N/TN)
  blocks : A hi/lo (TM, K), B hi/lo (K, TN), out hi/lo (TM, TN) in VMEM
  VMEM   : (2·TM·K + 2·K·TN + 2·TM·TN) × 4 B — e.g. TM=TN=128, K=2048
           → 4.3 MB (ops.py splits larger K and carries between chunks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_U32 = jnp.uint32
MAX_K_EXACT = 1 << 15        # 255*255*K < 2^31 → K ≤ 32768

DEFAULT_TM = 128
DEFAULT_TN = 128


def _limbs8(hi: jnp.ndarray, lo: jnp.ndarray) -> list[jnp.ndarray]:
    """(…) uint32 pair -> eight (…) int32 planes of 8-bit limbs (LSB first).
    int32 planes (values 0..255) hit the MXU integer path on TPU; interpret
    mode evaluates them as plain integer dots."""
    out = []
    for w, src in ((0, lo), (1, hi)):
        for s in range(4):
            out.append(((src >> (8 * s)) & _U32(0xFF)).astype(jnp.int32))
    return out


def _shift_add_u64(acc_hi, acc_lo, p: jnp.ndarray, shift_bits: int):
    """acc (uint32 pair) += p · 2^shift_bits (p: int32 ≥ 0, < 2^31)."""
    p = p.astype(_U32)
    if shift_bits == 0:
        add_hi, add_lo = jnp.zeros_like(p), p
    elif shift_bits < 32:
        add_lo = p << shift_bits
        add_hi = p >> (32 - shift_bits)
    elif shift_bits == 32:
        add_hi, add_lo = p, jnp.zeros_like(p)
    else:
        add_hi, add_lo = p << (shift_bits - 32), jnp.zeros_like(p)
    new_lo = acc_lo + add_lo
    carry = (new_lo < acc_lo).astype(_U32)
    return acc_hi + add_hi + carry, new_lo


def _kernel(a_hi_ref, a_lo_ref, b_hi_ref, b_lo_ref, o_hi_ref, o_lo_ref):
    a_limbs = _limbs8(a_hi_ref[...], a_lo_ref[...])   # 8 × (TM, K)
    b_limbs = _limbs8(b_hi_ref[...], b_lo_ref[...])   # 8 × (K, TN)
    shape = (a_limbs[0].shape[0], b_limbs[0].shape[1])
    acc_hi = jnp.zeros(shape, _U32)
    acc_lo = jnp.zeros(shape, _U32)
    for i in range(8):
        for j in range(8 - i):                        # weight < 2^64 only
            p = jax.lax.dot_general(
                a_limbs[i], b_limbs[j],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)     # MXU int contraction
            acc_hi, acc_lo = _shift_add_u64(acc_hi, acc_lo, p, 8 * (i + j))
    o_hi_ref[...] = acc_hi
    o_lo_ref[...] = acc_lo


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def ring_matmul_tiled(a_hi, a_lo, b_hi, b_lo, *, interpret: bool,
                      tm: int = DEFAULT_TM, tn: int = DEFAULT_TN):
    """(M, K) × (K, N) over Z_2^64; M % tm == N % tn == 0, K ≤ 2^15
    (ops.py handles padding and K-chunking)."""
    M, K = a_hi.shape
    N = b_hi.shape[1]
    assert M % tm == 0 and N % tn == 0 and K <= MAX_K_EXACT
    grid = (M // tm, N // tn)
    out_shape = [jax.ShapeDtypeStruct((M, N), jnp.uint32)] * 2
    a_spec = pl.BlockSpec((tm, K), lambda i, j: (i, 0))
    b_spec = pl.BlockSpec((K, tn), lambda i, j: (0, j))
    o_spec = pl.BlockSpec((tm, tn), lambda i, j: (i, j))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[a_spec, a_spec, b_spec, b_spec],
        out_specs=[o_spec, o_spec],
        out_shape=out_shape,
        interpret=interpret,
    )(a_hi, a_lo, b_hi, b_lo)
