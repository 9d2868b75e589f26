"""Fused Pallas TPU kernels for the Paillier hot path: the constant-time
Montgomery ladder, the windowed HE matvec and the fixed-base table walk,
each inside ONE `pallas_call`.

Every Montgomery product is ONE RNS round (`rns.montmul_channels` traced
inline): channel-pointwise VPU ops plus two exact f32 base-extension
matmuls that map onto the MXU.  All three kernels work on channel states
in the ·B domain and keep the working set resident in VMEM for the whole
ladder / matvec / table walk; the limbs ↔ channels conversions and the
final exact reconstruction (`rns.from_rns`) stay outside in ops.py,
amortized over the whole walk.

Layout rules the TPU kernel compiler imposes, which the interpreter does
not check:

* no dynamic slice along the lane (last) axis — a per-step exponent bit
  or digit is picked out of its row by a one-hot lane sum (`_pick`);
* dynamic indices only on a ref's leading axes — the matvec power table
  lives in a VMEM scratch ref (npow, nrows, CH) written one power at a
  time, and the fixed-base table is indexed per level through its ref;
* loops are `fori_loop`s so each kernel holds a few copies of the round,
  not one per unrolled step.

VMEM per program: the double-buffered blocks (the two (CH, CH) extension
matrices dominate), the matvec's power table npow × n_chunk × CH × 4 B,
and the temporaries of one RNS round; ops.py chunks n to bound the table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.crypto import rns as rns_lib

_U32 = jnp.uint32

DEFAULT_TILE_B = 128
DEFAULT_TILE_M = 128
DEFAULT_CHUNK_N = 512

# Scoped-VMEM limit of every kernel here.  v5e has 128 MiB of VMEM per
# core; the default scoped limit of 32 MiB is too small at 2048-bit keys,
# where the v5e compiler reported scoped stacks of 47.2 MB (ladder),
# 44.6 MB (fixed-base) and 77.0 MB (matvec at chunk 512, of which 25.2 MB
# is its power table).  The rest is left to Mosaic's own scratch.
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=100 * 2**20)


def _rns_mm(mods, tb, ta, vecs, kA, kB, ainv_r):
    return functools.partial(rns_lib.montmul_channels, mods=mods, t_b=tb,
                             t_a=ta, vecs=vecs, kA=kA, kB=kB,
                             ainv_r=ainv_r, rem=rns_lib.mod_u28_recip)


def _pick(rows, i):
    """Column `i` (traced) of a (R, C) uint32 block as (R, 1): a one-hot
    lane sum, since the kernel compiler has no dynamic lane slice."""
    lane = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    hit = jnp.where(lane == i, rows.astype(jnp.int32), 0)
    return jnp.sum(hit, axis=1, keepdims=True).astype(_U32)


def _select(d, one, powers, npow):
    """Row-wise table lookup: powers[d] per row of the (R, 1) digits d,
    as lane-wise wheres (no gather)."""
    sel = jnp.broadcast_to(one, (d.shape[0], one.shape[-1]))
    for j in range(1, npow):
        sel = jnp.where(d == j, powers[j:j + 1], sel)
    return sel


# ---------------------------------------------------------------------------
# Constant-time ladder
# ---------------------------------------------------------------------------

def _rns_exp_kernel(kA: int, kB: int, ainv_r: int, nbits: int,
                    u_ref, bits_ref, mods_ref, tb_ref, ta_ref, vecs_ref,
                    one_ref, exit_ref, o_ref):
    u = u_ref[...]                               # (TB, CH) scaled base
    bits = bits_ref[...]                         # (TB, nbits) MSB-first
    mm = _rns_mm(mods_ref[...], tb_ref[...], ta_ref[...], vecs_ref[...],
                 kA, kB, ainv_r)
    acc0 = jnp.broadcast_to(one_ref[...], u.shape)

    def step(i, acc):
        acc = mm(acc, acc)
        mul = mm(acc, u)
        return jnp.where(_pick(bits, i) == 1, mul, acc)

    acc = jax.lax.fori_loop(0, nbits, step, acc0)
    o_ref[...] = mm(acc, exit_ref[...])          # v^e·B ↦ v^e·R


@functools.partial(jax.jit, static_argnames=("kA", "kB", "ainv_r",
                                             "tile_b", "interpret"))
def rns_mont_exp_tiled(u: jnp.ndarray, bits: jnp.ndarray,
                       mods: jnp.ndarray, t_b: jnp.ndarray,
                       t_a: jnp.ndarray, vecs: jnp.ndarray,
                       one: jnp.ndarray, exitc: jnp.ndarray, *,
                       kA: int, kB: int, ainv_r: int, interpret: bool,
                       tile_b: int = DEFAULT_TILE_B) -> jnp.ndarray:
    """Fused constant-time ladder on channel states.  u: (batch, CH) the
    base via `rns.to_rns_scaled`; bits: (batch, nbits) MSB-first.
    Returns the channel state of base^e·R (< (kB+2)·N) — finish with
    `rns.from_rns` outside."""
    batch, nbits = bits.shape
    CH = u.shape[1]
    assert batch % tile_b == 0, "pad batch to a tile multiple in ops.py"
    grid = (batch // tile_b,)
    return pl.pallas_call(
        functools.partial(_rns_exp_kernel, kA, kB, ainv_r, nbits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, CH), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, nbits), lambda i: (i, 0)),
            pl.BlockSpec((1, CH), lambda i: (0, 0)),
            pl.BlockSpec(t_b.shape, lambda i: (0, 0)),
            pl.BlockSpec(t_a.shape, lambda i: (0, 0)),
            pl.BlockSpec((6, CH), lambda i: (0, 0)),
            pl.BlockSpec((1, CH), lambda i: (0, 0)),
            pl.BlockSpec((1, CH), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_b, CH), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, CH), jnp.uint32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="rns_mont_exp",
    )(u, bits, mods.reshape(1, CH), t_b, t_a, vecs,
      one.reshape(1, CH), exitc.reshape(1, CH))


# ---------------------------------------------------------------------------
# Windowed HE matvec
# ---------------------------------------------------------------------------

def _rns_matvec_kernel(kA: int, kB: int, ainv_r: int, window: int,
                       levels: int, nrows: int,
                       u_ref, dig_ref, mods_ref, tb_ref, ta_ref, vecs_ref,
                       one_ref, o_ref, table_ref):
    one = one_ref[...]                           # (1, CH)
    mm = _rns_mm(mods_ref[...], tb_ref[...], ta_ref[...], vecs_ref[...],
                 kA, kB, ainv_r)
    TM, CH = o_ref.shape
    npow = 1 << window

    # power table c_i^j·B for j < 2^window: (npow, nrows, CH) in VMEM
    u = u_ref[...]                               # (nrows, CH) scaled cts
    table_ref[0] = jnp.broadcast_to(one, (nrows, CH))
    table_ref[1] = u

    def build(j, carry):
        table_ref[j] = mm(table_ref[j - 1], u)
        return carry

    jax.lax.fori_loop(2, npow, build, 0)
    one_tm = jnp.broadcast_to(one, (TM, CH))

    def level(lvl, acc):
        acc = jax.lax.fori_loop(0, window, lambda _, a: mm(a, a), acc)
        dig_lvl = dig_ref[lvl]                   # (TM, nrows)

        def row(i, p):
            powers = table_ref[:, pl.ds(i, 1), :][:, 0, :]   # (npow, CH)
            return mm(p, _select(_pick(dig_lvl, i), one, powers, npow))

        return mm(acc, jax.lax.fori_loop(0, nrows, row, one_tm))

    o_ref[...] = jax.lax.fori_loop(0, levels, level, one_tm)   # ·B domain


@functools.partial(jax.jit, static_argnames=("kA", "kB", "ainv_r",
                                             "window", "tile_m",
                                             "interpret"))
def rns_he_matvec_tiled(u: jnp.ndarray, digits: jnp.ndarray,
                        mods: jnp.ndarray, t_b: jnp.ndarray,
                        t_a: jnp.ndarray, vecs: jnp.ndarray,
                        one: jnp.ndarray, *, kA: int, kB: int,
                        ainv_r: int, window: int, interpret: bool,
                        tile_m: int = DEFAULT_TILE_M) -> jnp.ndarray:
    """Fused windowed HE matvec on channel states.  u: (nrows, CH) the
    ciphertexts via `rns.to_rns_scaled`; digits: (levels, m, nrows)
    MSB-first window digits, output column on the sublane axis.  Returns
    (m, CH) ·B-domain channel states of the column products — chunk-⊕,
    exit, and `rns.from_rns` happen in ops.py."""
    levels, m, nrows = digits.shape
    CH = u.shape[1]
    npow = 1 << window
    assert m % tile_m == 0, "pad m to a tile multiple in ops.py"
    grid = (m // tile_m,)
    return pl.pallas_call(
        functools.partial(_rns_matvec_kernel, kA, kB, ainv_r, window,
                          levels, nrows),
        grid=grid,
        in_specs=[
            pl.BlockSpec((nrows, CH), lambda i: (0, 0)),
            pl.BlockSpec((levels, tile_m, nrows), lambda i: (0, i, 0)),
            pl.BlockSpec((1, CH), lambda i: (0, 0)),
            pl.BlockSpec(t_b.shape, lambda i: (0, 0)),
            pl.BlockSpec(t_a.shape, lambda i: (0, 0)),
            pl.BlockSpec((6, CH), lambda i: (0, 0)),
            pl.BlockSpec((1, CH), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_m, CH), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, CH), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((npow, nrows, CH), jnp.uint32)],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="rns_he_matvec",
    )(u, digits, mods.reshape(1, CH), t_b, t_a, vecs, one.reshape(1, CH))


# ---------------------------------------------------------------------------
# Fixed-base table walk
# ---------------------------------------------------------------------------

def _rns_fixb_kernel(kA: int, kB: int, ainv_r: int, window: int,
                     levels: int,
                     tab_ref, dig_ref, mods_ref, tb_ref, ta_ref, vecs_ref,
                     one_ref, exit_ref, o_ref):
    digs = dig_ref[...]                          # (TB, levels) LSB-first
    one = one_ref[...]                           # (1, CH)
    mm = _rns_mm(mods_ref[...], tb_ref[...], ta_ref[...], vecs_ref[...],
                 kA, kB, ainv_r)
    TB = digs.shape[0]
    CH = one.shape[-1]
    npow = 1 << window

    def step(lvl, acc):
        # digit 0 selects table[lvl][0] = one — mm(acc, one) is identity
        sel = _select(_pick(digs, lvl), one, tab_ref[lvl], npow)
        return mm(acc, sel)

    acc = jax.lax.fori_loop(0, levels, step,
                            jnp.broadcast_to(one, (TB, CH)))
    o_ref[...] = mm(acc, exit_ref[...])          # h^e·B ↦ h^e·R


@functools.partial(jax.jit, static_argnames=("kA", "kB", "ainv_r",
                                             "window", "tile_b",
                                             "interpret"))
def rns_fixed_base_tiled(table: jnp.ndarray, digits: jnp.ndarray,
                         mods: jnp.ndarray, t_b: jnp.ndarray,
                         t_a: jnp.ndarray, vecs: jnp.ndarray,
                         one: jnp.ndarray, exitc: jnp.ndarray, *,
                         kA: int, kB: int, ainv_r: int, window: int,
                         interpret: bool,
                         tile_b: int = DEFAULT_TILE_B) -> jnp.ndarray:
    """Fixed-base windowed exponentiation from a prepared ·B-domain
    table (levels, 2^window, CH); digits: (batch, levels) LSB-first
    base-2^window digits of the exponent.  Returns the channel state of
    h^e·R — finish with `rns.from_rns` outside.  The whole walk is one
    table-lookup ⊕ per level: ~levels RNS rounds instead of 2·nbits
    ladder rounds."""
    batch, levels = digits.shape
    CH = table.shape[-1]
    npow = 1 << window
    assert table.shape == (levels, npow, CH)
    assert batch % tile_b == 0, "pad batch to a tile multiple in ops.py"
    grid = (batch // tile_b,)
    return pl.pallas_call(
        functools.partial(_rns_fixb_kernel, kA, kB, ainv_r, window,
                          levels),
        grid=grid,
        in_specs=[
            pl.BlockSpec((levels, npow, CH), lambda i: (0, 0, 0)),
            pl.BlockSpec((tile_b, levels), lambda i: (i, 0)),
            pl.BlockSpec((1, CH), lambda i: (0, 0)),
            pl.BlockSpec(t_b.shape, lambda i: (0, 0)),
            pl.BlockSpec(t_a.shape, lambda i: (0, 0)),
            pl.BlockSpec((6, CH), lambda i: (0, 0)),
            pl.BlockSpec((1, CH), lambda i: (0, 0)),
            pl.BlockSpec((1, CH), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_b, CH), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, CH), jnp.uint32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="rns_fixed_base",
    )(table, digits, mods.reshape(1, CH), t_b, t_a, vecs,
      one.reshape(1, CH), exitc.reshape(1, CH))
