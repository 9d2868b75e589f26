"""Pallas TPU kernel: batched RNS Montgomery modular multiplication.

The compute hot spot of EFMVFL is Paillier arithmetic — Protocol 3's
plaintext-matrix × ciphertext-vector product is millions of Montgomery
products over 2048-bit residues.  This kernel evaluates a *batch* of
Montgomery products on residue-channel states (crypto/rns.py) in VMEM:

  grid     : (batch / TILE_B,)
  blocks   : x, y, out — (TILE_B, CH) uint32 channel states
             mods (1, CH), t_b / t_a (CH, CH), vecs (6, CH) — broadcast
             to every program
  compute  : ONE round of channel-pointwise math plus two exact f32
             matmuls (the base extensions) — the shape the MXU wants.

The body is `rns.montmul_channels` traced inline, so the kernel and the
jnp library path are the same arithmetic by construction.  Conversions
limbs ↔ channels stay outside the kernel (ops.py), amortized across
ladder / matvec steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.crypto import rns as rns_lib
from repro.kernels.montexp import COMPILER_PARAMS

DEFAULT_TILE_B = 128


def _rns_kernel(kA: int, kB: int, ainv_r: int,
                x_ref, y_ref, mods_ref, tb_ref, ta_ref, vecs_ref, o_ref):
    o_ref[...] = rns_lib.montmul_channels(
        x_ref[...], y_ref[...], mods_ref[...], tb_ref[...], ta_ref[...],
        vecs_ref[...], kA=kA, kB=kB, ainv_r=ainv_r,
        rem=rns_lib.mod_u28_recip)


@functools.partial(jax.jit, static_argnames=("kA", "kB", "ainv_r",
                                             "tile_b", "interpret"))
def rns_montmul_tiled(x: jnp.ndarray, y: jnp.ndarray, mods: jnp.ndarray,
                      t_b: jnp.ndarray, t_a: jnp.ndarray,
                      vecs: jnp.ndarray, *, kA: int, kB: int, ainv_r: int,
                      interpret: bool,
                      tile_b: int = DEFAULT_TILE_B) -> jnp.ndarray:
    """x, y: (batch, CH) channel states < (kB+2)·N (y usually entered via
    `rns.to_rns_scaled`).  Returns the channel state of x·y·B⁻¹, same
    bound.  batch must be a multiple of tile_b (ops.py pads)."""
    batch, CH = x.shape
    assert batch % tile_b == 0, "pad batch to a tile multiple in ops.py"
    grid = (batch // tile_b,)
    return pl.pallas_call(
        functools.partial(_rns_kernel, kA, kB, ainv_r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, CH), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, CH), lambda i: (i, 0)),
            pl.BlockSpec((1, CH), lambda i: (0, 0)),
            pl.BlockSpec(t_b.shape, lambda i: (0, 0)),
            pl.BlockSpec(t_a.shape, lambda i: (0, 0)),
            pl.BlockSpec((6, CH), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_b, CH), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, CH), jnp.uint32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="rns_montmul",
    )(x, y, mods.reshape(1, CH), t_b, t_a, vecs)
