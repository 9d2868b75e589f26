"""Mesh-sharded HE engine: data-parallel `shard_map` over the ciphertext
batch axis of the Paillier hot path.

PR 2 made every Paillier hot loop dispatch through one
`crypto.engine.CryptoEngine`; every one of those ops is batched over
ciphertexts (encryption-noise modexps over the batch, the Protocol-3
matvec over ciphertext rows, CRT decryption over received ciphertexts),
and the batch elements are independent group elements of Z*_{n²}.  That
makes the whole hot path data-parallel: shard the batch axis over a
device mesh, run the single-device engine per shard, and combine — for
the matvec, with the homomorphic ⊕ (`secure_ops.modmul_reduce`, the
same ppermute ladder the pod-level lowering uses).

Bit-exactness (the invariant `tests/test_he_sharding.py` pins):

* `mont_mul` / `mont_exp_bits` are row-wise independent — sharding the
  batch is a pure layout change.
* the windowed matvec's per-shard partials are exact group elements;
  group products are associative and canonical Montgomery residues are
  unique, so the butterfly ⊕-combine equals the single-device
  sequential/chunked fold bit for bit (the `ops.rns_he_matvec_fused`
  chunking argument, lifted across devices).
* padded rows carry zero digits, which select mont(1) from the power
  table and fold to the group identity.

Entry points: `ShardedCryptoEngine` (a `CryptoEngine` whose `mesh` is
mandatory) or any `CryptoEngine` constructed with ``mesh=`` — the base
class routes its batched ops here whenever `engine.sharded` is true.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.crypto.bigint import Modulus
from repro.crypto.engine import CryptoEngine
from repro.distributed.secure_ops import modmul_reduce
from jax import shard_map

_U32 = jnp.uint32


@dataclasses.dataclass(frozen=True)
class ShardedCryptoEngine(CryptoEngine):
    """A `CryptoEngine` that REQUIRES a device mesh.

    Identical dispatch surface to `CryptoEngine` (every batched op —
    `mont_mul`, `mont_exp_bits`, `mont_exp_const`, `he_matvec_windowed`
    and the `to_mont`/`from_mont` conveniences — accepts and returns the
    same canonical uint32 limb arrays); the batch axis is sharded over
    ``mesh.shape[mesh_axis]`` devices.  Construct with e.g.::

        mesh = jax.make_mesh((n_dev,), ("data",))
        eng = ShardedCryptoEngine(backend="jnp", mesh=mesh)

    Meshes with `Explicit` axes (the `jax.make_mesh` default) and with
    `Auto` axes both work: the shard_map bodies run on an `Auto` view of
    the same devices.

    or equivalently ``CryptoEngine(..., mesh=mesh)``; `ShardedCryptoEngine`
    only adds the constructor-time check that a mesh is present.
    """

    def __post_init__(self):
        if self.mesh is None:
            raise ValueError("ShardedCryptoEngine requires mesh=; use "
                             "CryptoEngine for the single-device path")
        if self.mesh_axis not in self.mesh.shape:
            raise ValueError(f"mesh has no axis {self.mesh_axis!r}; "
                             f"axes are {tuple(self.mesh.shape)}")
        size = self.mesh.shape[self.mesh_axis]
        if size & (size - 1):
            raise ValueError(
                f"mesh axis {self.mesh_axis!r} has size {size}; the "
                "matvec ⊕-combine (modmul_reduce butterfly) needs a "
                "power-of-two axis")


def make_sharded_engine(mesh, backend: str | None = None,
                        mesh_axis: str = "data", **kw) -> ShardedCryptoEngine:
    """Resolve `backend` like `engine.make` (env var / auto) and wrap it
    in a `ShardedCryptoEngine` over `mesh`'s `mesh_axis`."""
    from repro.crypto import engine as engine_mod
    return ShardedCryptoEngine(backend=engine_mod.resolve_backend(backend),
                               mesh=mesh, mesh_axis=mesh_axis, **kw)


# ---------------------------------------------------------------------------
# Batch-axis plumbing
# ---------------------------------------------------------------------------

def _flatten_batch(arrs, trailing_dims):
    """Broadcast leading (batch) dims across `arrs` and flatten them to
    one row axis.  `trailing_dims[i]` = number of non-batch trailing dims
    of arrs[i].  Returns (flat_arrays, batch_shape, flat_count)."""
    bshape = jnp.broadcast_shapes(*[a.shape[:a.ndim - t]
                                    for a, t in zip(arrs, trailing_dims)])
    flat = int(np.prod(bshape)) if bshape else 1
    out = []
    for a, t in zip(arrs, trailing_dims):
        tail = a.shape[a.ndim - t:]
        a = jnp.broadcast_to(a, bshape + tail)
        out.append(a.reshape((flat,) + tail))
    return out, bshape, flat


def _pad_rows(x: jnp.ndarray, multiple: int) -> jnp.ndarray:
    pad = (-x.shape[0]) % multiple
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
    return x


def _auto_axes(mesh):
    """`mesh` with every axis `Auto`.  `jax.make_mesh` gives `Explicit`
    axes, under which slicing or indexing a batch-sharded result outside
    the shard_map body is a sharding type error; the bodies here state
    their layouts in in_specs/out_specs and need nothing more."""
    from jax.sharding import AxisType, Mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


# jitted shard_map bodies, cached on (op, engine, modulus[, window]) so
# the Paillier hot path traces once per op/shape instead of per call
# (engines and meshes are hashable; Modulus is keyed by its int value)
_BODY_CACHE: dict = {}


def _rowwise_fn(engine: CryptoEngine, op: str, mod: Modulus):
    """Build (or fetch) the jitted shard_map body for a row-independent
    two-array op: (B, L)×(B, t) row shards → (B, L)."""
    key = (op, engine, mod.value)
    fn = _BODY_CACHE.get(key)
    if fn is not None:
        return fn
    inner = engine.single_device()
    mesh, axis = _auto_axes(engine.mesh), engine.mesh_axis
    if op == "mont_mul":
        def body(a, b):
            return inner.mont_mul(a, b, mod)
    else:
        def body(a, b):
            return inner.mont_exp_bits(a, b, mod)
    fn = jax.jit(shard_map(body, mesh=mesh,
                           in_specs=(P(axis, None), P(axis, None)),
                           out_specs=P(axis, None), check_vma=False))
    _BODY_CACHE[key] = fn
    return fn


def _sharded_rowwise(engine: CryptoEngine, op: str, mod: Modulus, arrs):
    """Run a row-wise-independent batched op under shard_map: broadcast +
    flatten the batch dims, pad to the axis size, one shard per device."""
    size = engine.mesh.shape[engine.mesh_axis]
    flat_arrs, bshape, flat = _flatten_batch(arrs, (1, 1))
    padded = [_pad_rows(a, size) for a in flat_arrs]
    out = _rowwise_fn(engine, op, mod)(*padded)
    return out[:flat].reshape(bshape + (mod.L,))


# ---------------------------------------------------------------------------
# Sharded ops (called by CryptoEngine when `engine.sharded`)
# ---------------------------------------------------------------------------

def sharded_mont_mul(engine: CryptoEngine, a: jnp.ndarray, b: jnp.ndarray,
                     mod: Modulus) -> jnp.ndarray:
    """Batched Montgomery product, batch rows sharded over the mesh.
    Row-wise independent, so the result is trivially bit-exact vs the
    single-device engine."""
    a = jnp.asarray(a, _U32)
    b = jnp.asarray(b, _U32)
    return _sharded_rowwise(engine, "mont_mul", mod, (a, b))


def sharded_mont_exp_bits(engine: CryptoEngine, base: jnp.ndarray,
                          bits: jnp.ndarray, mod: Modulus) -> jnp.ndarray:
    """Batched constant-time ladder, batch rows sharded over the mesh.
    Padded rows run the ladder on zeros and are dropped on the way out."""
    base = jnp.asarray(base, _U32)
    bits = jnp.asarray(bits, _U32)
    return _sharded_rowwise(engine, "mont_exp", mod, (base, bits))


def _matvec_fn(engine: CryptoEngine, mod: Modulus, window: int):
    """Build (or fetch) the jitted shard_map body for the sharded
    windowed matvec."""
    key = ("matvec", engine, mod.value, window)
    fn = _BODY_CACHE.get(key)
    if fn is not None:
        return fn
    inner = engine.single_device()
    mesh, axis = _auto_axes(engine.mesh), engine.mesh_axis
    size = mesh.shape[axis]

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None, None)),
        out_specs=P(), check_vma=False)
    def body(cts_loc, dig_loc):
        # the butterfly leaves every device with the same product, so the
        # result is replicated: no gather of one shard's copy afterwards
        part = inner.he_matvec_windowed(cts_loc, dig_loc, mod, window)
        return modmul_reduce(part, mod, axis, size)

    fn = jax.jit(body)
    _BODY_CACHE[key] = fn
    return fn


def sharded_he_matvec(engine: CryptoEngine, cts: jnp.ndarray, digits,
                      mod: Modulus, window: int) -> jnp.ndarray:
    """Windowed HE matvec with the ciphertext-row axis sharded over the
    mesh: each device folds its row shard into an (m, L) partial, then
    the partials ⊕-combine across devices with the `modmul_reduce`
    butterfly (Paillier ⊕ is modular multiplication — psum can't express
    it).  cts: (n, L); digits: (n, m, levels) MSB-first window digits;
    returns (m, L), bit-exact vs the single-device engine."""
    size = engine.mesh.shape[engine.mesh_axis]
    cts = _pad_rows(jnp.asarray(cts, _U32), size)
    digits = _pad_rows(jnp.asarray(digits, _U32), size)
    return _matvec_fn(engine, mod, window)(cts, digits)
