"""Secure collectives on the production mesh.

`modmul_reduce` — the homomorphic ⊕-reduction over a mesh axis.  Paillier
addition is modular *multiplication* of ciphertext residues, which psum
cannot express; this is a log2(axis)-depth ppermute ladder (recursive
halving), each rank combining with its partner via `mont_mul`.  It is the
collective the EFMVFL gradient step (pod = party) lowers to in
launch/secure_dryrun.py — DESIGN.md §3's "homomorphic reduction as a tree
collective".

`secure_allreduce_shares` — additive-share psum: each party holds an
additive share of a gradient; summing shares IS a psum, so cross-silo
secure aggregation of LM gradients (core/vfl_lm.py) maps onto the native
collective with zero overhead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.crypto.bigint import Modulus, mont_mul


def modmul_reduce(x: jnp.ndarray, mod: Modulus, axis_name: str,
                  axis_size: int) -> jnp.ndarray:
    """x: (..., L) Montgomery residues, one shard per rank along
    `axis_name` (power-of-two size).  Returns the ⊕-product of all ranks'
    residues, replicated (all ranks end with the same value)."""
    assert axis_size & (axis_size - 1) == 0, "power-of-two axis"
    idx = jax.lax.axis_index(axis_name)
    step = 1
    while step < axis_size:
        # exchange with the partner at distance `step` (butterfly — every
        # rank stays active, so the result ends replicated, not rooted)
        perm = [(i, i ^ step) for i in range(axis_size)]
        other = jax.lax.ppermute(x, axis_name, perm)
        x = mont_mul(x, other, mod)
        step <<= 1
    del idx
    return x


def secure_allreduce_shares(share: jnp.ndarray, axis_name: str
                            ) -> jnp.ndarray:
    """Additive-share aggregation = native psum over the party axis."""
    return jax.lax.psum(share, axis_name)


def make_modmul_reduce_shardmap(mesh, mod: Modulus, axis_name: str):
    """shard_map wrapper: (n_shards, batch, L) global → (batch, L) product
    per shard group, replicated."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    axis_size = mesh.shape[axis_name]

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=P(axis_name, None, None),
        out_specs=P(axis_name, None, None),
        check_vma=False)
    def reduce_fn(x):
        out = modmul_reduce(x[0], mod, axis_name, axis_size)
        return out[None]

    return reduce_fn
