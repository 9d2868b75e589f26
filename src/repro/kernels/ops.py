"""jit'd public wrappers around the Pallas kernels: padding, K-chunking,
batch flattening, and drop-in integration points for the crypto layer.

`interpret` is a required keyword everywhere: True runs the kernel body
through the Pallas interpreter (CPU parity tests), False compiles it for
the TPU.  Callers pass `CryptoEngine.interpret`, so a kernel never runs
interpreted on the chip by default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.crypto import rns
from repro.crypto.bigint import Modulus
from repro.crypto.ring import R64
from repro.kernels import montexp as montexp_k
from repro.kernels import montmul as montmul_k
from repro.kernels import ring_matmul as ringmm_k

_U32 = jnp.uint32


def ring_matmul(a: R64, b: R64, *, tm: int = ringmm_k.DEFAULT_TM,
                tn: int = ringmm_k.DEFAULT_TN,
                interpret: bool) -> R64:
    """(M, K) @ (K, N) over Z_2^64 via the limb-MXU kernel.  Pads M/N to
    tiles and chunks K at the exactness bound."""
    M, K = a.lo.shape
    N = b.lo.shape[1]
    padM = (-M) % tm
    padN = (-N) % tn

    def padded(x, pr, pc):
        return jnp.pad(x, ((0, pr), (0, pc)))

    out_hi = jnp.zeros((M + padM, N + padN), _U32)
    out_lo = jnp.zeros_like(out_hi)
    for k0 in range(0, K, ringmm_k.MAX_K_EXACT):
        k1 = min(K, k0 + ringmm_k.MAX_K_EXACT)
        oh, ol = ringmm_k.ring_matmul_tiled(
            padded(a.hi[:, k0:k1], padM, 0), padded(a.lo[:, k0:k1], padM, 0),
            padded(b.hi[k0:k1, :], 0, padN), padded(b.lo[k0:k1, :], 0, padN),
            tm=tm, tn=tn, interpret=interpret)
        new_lo = out_lo + ol
        carry = (new_lo < out_lo).astype(_U32)
        out_hi = out_hi + oh + carry
        out_lo = new_lo
    return R64(out_hi[:M, :N], out_lo[:M, :N])


# ---------------------------------------------------------------------------
# RNS pipeline wrappers (kernels/montmul.py + montexp.py channel kernels)
# ---------------------------------------------------------------------------
#
# Same public contracts as the `bigint` library ops (canonical radix-2^12
# limbs in, canonical limbs out, bit-exact vs the bigint oracle), but the
# kernel-resident representation is the RNS channel state of
# crypto/rns.py.  Conversions run outside the pallas_call — one exact
# split-f32 matmul each way — so a ladder of 2·nbits rounds or a matvec
# of n·levels rounds pays for them once.

def _rns_parts(ctx):
    return (jnp.asarray(ctx.all_mods, _U32), jnp.asarray(ctx.t_b, _U32),
            jnp.asarray(ctx.t_a, _U32), jnp.asarray(ctx.vecs, _U32))


def _flatten_pad(x, width, tile):
    """(..., width) → ((flat+pad, width), flat).  Zero rows are harmless:
    every RNS op maps 0 → 0 and padded outputs are dropped."""
    bshape = x.shape[:-1]
    flat = int(np.prod(bshape)) if bshape else 1
    x2 = x.reshape(flat, width)
    pad = (-flat) % tile
    if pad:
        x2 = jnp.concatenate([x2, jnp.zeros((pad, width), x2.dtype)], 0)
    return x2, flat


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("tile_b", "interpret"))
def _rns_montmul_flat(ctx, a2, b2, *, tile_b, interpret):
    mods, t_b, t_a, vecs = _rns_parts(ctx)
    t = montmul_k.rns_montmul_tiled(
        rns.to_rns(ctx, a2), rns.to_rns_scaled(ctx, b2), mods, t_b, t_a,
        vecs, kA=ctx.kA, kB=ctx.kB, ainv_r=ctx.ainv_r, tile_b=tile_b,
        interpret=interpret)
    return rns.from_rns(ctx, t)


def rns_montmul(a: jnp.ndarray, b: jnp.ndarray, mod: Modulus, *,
                interpret: bool,
                tile_b: int = montmul_k.DEFAULT_TILE_B) -> jnp.ndarray:
    """Batched Montgomery product via the RNS channel kernel — drop-in
    peer of `bigint.mont_mul`."""
    a, b = jnp.broadcast_arrays(a.astype(_U32), b.astype(_U32))
    bshape = a.shape[:-1]
    ctx = rns.for_modulus(mod)
    flat = int(np.prod(bshape)) if bshape else 1
    tb = min(tile_b, max(flat, 1))
    a2, _ = _flatten_pad(a, mod.L, tb)
    b2, _ = _flatten_pad(b, mod.L, tb)
    out = _rns_montmul_flat(ctx, a2, b2, tile_b=tb, interpret=interpret)
    return out[:flat].reshape(bshape + (mod.L,))


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("tile_b", "interpret"))
def _rns_exp_flat(ctx, b2, e2, *, tile_b, interpret):
    mods, t_b, t_a, vecs = _rns_parts(ctx)
    t = montexp_k.rns_mont_exp_tiled(
        rns.to_rns_scaled(ctx, b2), e2, mods, t_b, t_a, vecs,
        rns.const_rns(ctx, "one"), rns.const_rns(ctx, "exit"),
        kA=ctx.kA, kB=ctx.kB, ainv_r=ctx.ainv_r, tile_b=tile_b,
        interpret=interpret)
    return rns.from_rns(ctx, t)


def rns_mont_exp_fused(base: jnp.ndarray, bits: jnp.ndarray,
                       mod: Modulus, *,
                       interpret: bool,
                       tile_b: int = montexp_k.DEFAULT_TILE_B) -> jnp.ndarray:
    """Fused constant-time ladder via the RNS kernel (peer of
    `bigint.mont_exp_bits`)."""
    base = jnp.asarray(base, _U32)
    bits = jnp.asarray(bits, _U32)
    bshape = jnp.broadcast_shapes(base.shape[:-1], bits.shape[:-1])
    nbits = bits.shape[-1]
    base = jnp.broadcast_to(base, bshape + (mod.L,))
    bits = jnp.broadcast_to(bits, bshape + (nbits,))
    ctx = rns.for_modulus(mod)
    flat = int(np.prod(bshape)) if bshape else 1
    tb = min(tile_b, max(flat, 1))
    b2, _ = _flatten_pad(base, mod.L, tb)
    e2, _ = _flatten_pad(bits, nbits, tb)
    out = _rns_exp_flat(ctx, b2, e2, tile_b=tb, interpret=interpret)
    return out[:flat].reshape(bshape + (mod.L,))


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("window", "tile_m", "chunk_n",
                                    "interpret"))
def _rns_matvec_flat(ctx, cts, dt, *, window, tile_m, chunk_n, interpret):
    mods, t_b, t_a, vecs = _rns_parts(ctx)
    one = rns.const_rns(ctx, "one")
    u = rns.to_rns_scaled(ctx, cts)
    n = u.shape[0]
    acc = None
    for n0 in range(0, n, chunk_n):
        n1 = min(n, n0 + chunk_n)
        part = montexp_k.rns_he_matvec_tiled(
            u[n0:n1], dt[:, :, n0:n1], mods, t_b, t_a, vecs, one,
            kA=ctx.kA, kB=ctx.kB, ainv_r=ctx.ainv_r, window=window,
            tile_m=tile_m, interpret=interpret)
        # chunk-⊕ in the ·B domain: one extra RNS round per chunk
        acc = part if acc is None else rns.rns_montmul(ctx, acc, part)
    out = rns.rns_montmul(ctx, acc, jnp.broadcast_to(
        rns.const_rns(ctx, "exit"), acc.shape))
    return rns.from_rns(ctx, out)


def rns_he_matvec_fused(cts: jnp.ndarray, digits: jnp.ndarray,
                        mod: Modulus, *, window: int, interpret: bool,
                        tile_m: int = montexp_k.DEFAULT_TILE_M,
                        chunk_n: int = montexp_k.DEFAULT_CHUNK_N
                        ) -> jnp.ndarray:
    """Fused windowed HE matvec via the RNS kernel (peer of
    `protocols._he_matvec_windowed`): cts (n, L)
    Montgomery ciphertexts, digits (n, m, levels) MSB-first window
    digits → (m, L) canonical ciphertexts of Σ_i exps[i,j]·m_i."""
    cts = jnp.asarray(cts, _U32)
    digits = jnp.asarray(digits, _U32)
    n, m, levels = digits.shape
    ctx = rns.for_modulus(mod)
    tm = min(tile_m, max(m, 1))
    pad_m = (-m) % tm
    dt = jnp.transpose(digits, (2, 1, 0))       # (levels, m, n)
    if pad_m:
        dt = jnp.concatenate(
            [dt, jnp.zeros((levels, pad_m, n), _U32)], axis=1)
    out = _rns_matvec_flat(ctx, cts, dt, window=window, tile_m=tm,
                           chunk_n=chunk_n, interpret=interpret)
    return out[:m]


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("window", "tile_b", "interpret"))
def _rns_fixb_flat(ctx, table, d2, *, window, tile_b, interpret):
    mods, t_b, t_a, vecs = _rns_parts(ctx)
    t = montexp_k.rns_fixed_base_tiled(
        table, d2, mods, t_b, t_a, vecs, rns.const_rns(ctx, "one"),
        rns.const_rns(ctx, "exit"), kA=ctx.kA, kB=ctx.kB,
        ainv_r=ctx.ainv_r, window=window, tile_b=tile_b,
        interpret=interpret)
    return rns.from_rns(ctx, t)


def rns_fixed_base_fused(table: jnp.ndarray, digits: jnp.ndarray,
                         mod: Modulus, *, window: int, interpret: bool,
                         tile_b: int = montexp_k.DEFAULT_TILE_B
                         ) -> jnp.ndarray:
    """Fixed-base windowed exponentiation via the RNS kernel from a
    prepared ·B-domain table (levels, 2^window, CH) — the kernel twin of
    `rns.fixed_base_exp`.  digits: (..., levels) LSB-first base-2^window
    digits; returns (..., L) canonical limbs of h^e·R."""
    digits = jnp.asarray(digits, _U32)
    table = jnp.asarray(table, _U32)
    bshape = digits.shape[:-1]
    levels = digits.shape[-1]
    ctx = rns.for_modulus(mod)
    flat = int(np.prod(bshape)) if bshape else 1
    tb = min(tile_b, max(flat, 1))
    d2, _ = _flatten_pad(digits, levels, tb)
    out = _rns_fixb_flat(ctx, table, d2, window=window, tile_b=tb,
                         interpret=interpret)
    return out[:flat].reshape(bshape + (mod.L,))
