"""Crypto compute engine: one dispatch point for every Paillier hot loop.

Every modular-arithmetic hot spot — encryption noise r^n, the Protocol-3
HE matvec, CRT decryption, scalar ⊗, homomorphic ⊕ — funnels through a
`CryptoEngine`, which routes each op to either the pure-jnp library
(`crypto.bigint`) or the fused Pallas kernels (`kernels.montexp` /
`kernels.montmul`).  Backends:

* ``jnp``              — `lax`-loop library code (CPU default; also the
                         bit-exactness oracle).
* ``pallas-interpret`` — fused kernels in interpret mode (CPU: same IR
                         as the TPU path, runs as jitted jax — used by
                         the parity suite and CI).
* ``pallas``           — fused kernels compiled for TPU (deployment).

All three produce bit-identical canonical limbs (tests/test_engine.py),
so the switch is purely a performance knob: select with the
``REPRO_CRYPTO_ENGINE`` env var, `VFLConfig.crypto_engine`, or
`set_engine`/`use_engine`.  ``auto`` resolves to ``pallas`` on TPU and
``jnp`` elsewhere.

Orthogonal to the backend, the ``pipeline`` field picks the *arithmetic*
each op runs (docs/engine.md §RNS):

* ``cios`` — the radix-2^12 CIOS limb loops of `crypto.bigint`, as plain
             jitted jnp on every backend (mask/shift only; wins at small
             moduli);
* ``rns``  — the residue-number-system channel pipeline (`crypto.rns`):
             montmul becomes one pointwise round + two exact f32
             matmuls, the MXU-shaped form; the kernel backends run it as
             the fused Pallas kernels, ``jnp`` as library code;
* ``auto`` — per-modulus routing at `RNS_MIN_BITS`: RNS at ≥ 512-bit
             moduli, the CIOS library below.

Fixed-base exponentiation (`fixed_base_exp`, fed by
`crypto.fixed_base.FixedBaseTable`) is always RNS — the table stores
·B-domain channel states and beats the ladder at every size.

Scale-out: give the engine a device ``mesh`` (or construct a
`distributed.he_sharding.ShardedCryptoEngine`) and every batched op runs
under `shard_map` with the ciphertext batch axis sharded over
``mesh.shape[mesh_axis]`` devices — still bit-exact against the
single-device path (tests/test_he_sharding.py); `shard_batch=False`
turns the routing off without dropping the mesh.  See
docs/architecture.md for where the sharded path sits in the stack.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Any

import jax
import jax.numpy as jnp

from repro import obs
from repro.crypto import bigint
from repro.crypto.bigint import Modulus

_U32 = jnp.uint32

BACKENDS = ("jnp", "pallas-interpret", "pallas")
ENV_VAR = "REPRO_CRYPTO_ENGINE"

PIPELINES = ("auto", "cios", "rns")
PIPELINE_ENV_VAR = "REPRO_CRYPTO_PIPELINE"
# Modulus width (bits of N, i.e. of n² for ciphertext ops) at and above
# which ``auto`` routes to the RNS pipeline.  Set from CPU timings
# (BENCH_crypto.json): at 1024-bit montmul RNS runs 0.7–0.8× the
# library; at 256-bit its ~14 integer-divides per round lose to CIOS's
# pure mask/shift arithmetic.  Not measured on the chip yet.
RNS_MIN_BITS = 512


def resolve_backend(name: str | None = None) -> str:
    """Resolve a backend name to one of `BACKENDS`.

    Args:
      name: backend name, ``"auto"``, ``""`` or None.  ``auto``/None/""
        consults the ``REPRO_CRYPTO_ENGINE`` env var, then the hardware
        default (``pallas`` on TPU, ``jnp`` elsewhere).
    Returns:
      One of ``"jnp" | "pallas-interpret" | "pallas"``.
    Raises:
      ValueError: for any other name.
    """
    if name in (None, "", "auto"):
        name = os.environ.get(ENV_VAR, "auto")
    if name in ("", "auto"):
        name = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if name not in BACKENDS:
        raise ValueError(f"unknown crypto engine {name!r}; "
                         f"choose from {BACKENDS + ('auto',)}")
    return name


def resolve_pipeline(name: str | None = None) -> str:
    """Resolve a pipeline name to one of `PIPELINES`.  ``auto``/None/""
    consults ``REPRO_CRYPTO_PIPELINE`` and stays ``auto`` (per-modulus
    routing) when unset."""
    if name in (None, "", "auto"):
        name = os.environ.get(PIPELINE_ENV_VAR, "auto") or "auto"
    if name not in PIPELINES:
        raise ValueError(f"unknown crypto pipeline {name!r}; "
                         f"choose from {PIPELINES}")
    return name


@dataclasses.dataclass(frozen=True)
class CryptoEngine:
    """Immutable dispatch descriptor (hashable, so it can ride through
    jit static args).

    Every op takes and returns *canonical* uint32 limb arrays (radix-2^12
    limbs, values < the modulus; Montgomery-domain where noted) — the
    representation `crypto.bigint` defines — so engines with different
    backends or meshes are interchangeable bit for bit.

    Fields:
      backend: ``"jnp"`` (library lax loops), ``"pallas-interpret"``
        (fused kernels, interpret mode) or ``"pallas"`` (fused kernels
        compiled for TPU).
      pipeline: ``"cios"`` | ``"rns"`` | ``"auto"`` — which arithmetic
        the big ops run.  ``auto`` (default) picks per modulus at
        `RNS_MIN_BITS`.  Explicit values pin the arithmetic — the
        parity suite uses that.
      tile_b: batch tile for the montmul / fused-ladder kernels.
      tile_m: output-column tile for the fused HE matvec kernel.
      chunk_n: ciphertext-row chunk bounding the matvec power table's
        VMEM footprint.
      mesh: optional `jax.sharding.Mesh`; when set (and `shard_batch`),
        batched ops run under `shard_map` with the ciphertext batch axis
        sharded over ``mesh.shape[mesh_axis]`` devices
        (`distributed.he_sharding`).
      mesh_axis: name of the mesh axis carrying the ciphertext batch.
      shard_batch: master switch for the sharded routing (lets callers
        thread a mesh through config without committing to sharding).
    """

    backend: str = "jnp"
    pipeline: str = "auto"      # arithmetic: auto | cios | rns
    tile_b: int = 128           # montmul / ladder batch tile
    tile_m: int = 128           # he_matvec output-column tile
    chunk_n: int = 512          # he_matvec ciphertext-row chunk (VMEM)
    mesh: Any = None            # device mesh for ciphertext-batch sharding
    mesh_axis: str = "data"     # mesh axis the batch shards over
    shard_batch: bool = True    # route batched ops through he_sharding

    @property
    def uses_kernels(self) -> bool:
        """True when ops go to the fused Pallas kernels (non-jnp)."""
        return self.backend != "jnp"

    @property
    def interpret(self) -> bool:
        """Pallas interpret mode (CPU); False only for backend="pallas"."""
        return self.backend != "pallas"

    @property
    def sharded(self) -> bool:
        """True when batched ops run mesh-sharded over `mesh_axis`.
        Raises a clear ValueError for a mesh without that axis, or with
        a non-power-of-two axis size (the matvec ⊕-combine is the
        `modmul_reduce` butterfly, which needs one) — instead of an
        opaque error mid-protocol."""
        if self.mesh is None or not self.shard_batch:
            return False
        if self.mesh_axis not in self.mesh.shape:
            raise ValueError(f"engine mesh has no axis {self.mesh_axis!r};"
                             f" axes are {tuple(self.mesh.shape)}")
        size = self.mesh.shape[self.mesh_axis]
        if size & (size - 1):
            raise ValueError(
                f"mesh axis {self.mesh_axis!r} has size {size}; the "
                "sharded ⊕-combine (modmul_reduce butterfly) needs a "
                "power of two")
        return size > 1

    def single_device(self) -> "CryptoEngine":
        """This engine with the mesh dropped — the per-shard inner
        engine `he_sharding` runs inside each shard_map body."""
        if self.mesh is None:
            return self
        return CryptoEngine(backend=self.backend, pipeline=self.pipeline,
                            tile_b=self.tile_b, tile_m=self.tile_m,
                            chunk_n=self.chunk_n)

    def _route(self, mod: Modulus) -> str:
        """Pick the arithmetic for one op on modulus `mod`:
        ``"lib"`` (bigint CIOS loops), ``"rns-jnp"`` (`crypto.rns`
        library) or ``"rns"`` (RNS Pallas kernels).  Every kernel the
        ``pallas`` backend can reach is an RNS kernel: those are the ones
        the TPU compiler accepts (tests/test_tpu_compile.py)."""
        pipe = resolve_pipeline(self.pipeline)
        if pipe == "auto":
            pipe = ("rns" if mod.value.bit_length() >= RNS_MIN_BITS
                    else "cios")
        if pipe == "cios":
            return "lib"
        return "rns" if self.uses_kernels else "rns-jnp"

    # -- fused hot-path ops -------------------------------------------------
    def mont_mul(self, a: jnp.ndarray, b: jnp.ndarray,
                 mod: Modulus) -> jnp.ndarray:
        """Batched Montgomery product a ⊙ b mod N.

        Args:
          a, b: (..., L) canonical Montgomery-domain limb arrays
            (broadcast against each other over the batch dims).
          mod: the modulus descriptor (L limbs).
        Returns:
          (..., L) canonical Montgomery-domain product.
        """
        obs.count_engine_call("mont_mul")
        return self._mont_mul(a, b, mod)

    def _mont_mul(self, a: jnp.ndarray, b: jnp.ndarray,
                  mod: Modulus) -> jnp.ndarray:
        if self.sharded:
            from repro.distributed import he_sharding
            return he_sharding.sharded_mont_mul(self, a, b, mod)
        route = self._route(mod)
        if route == "lib":
            return bigint.mont_mul(a, b, mod)
        if route == "rns-jnp":
            from repro.crypto import rns
            return rns.mont_mul(rns.for_modulus(mod), a, b)
        from repro.kernels import ops
        return ops.rns_montmul(a, b, mod, tile_b=self.tile_b,
                               interpret=self.interpret)

    def mont_exp_bits(self, base: jnp.ndarray, bits: jnp.ndarray,
                      mod: Modulus) -> jnp.ndarray:
        """Constant-time square-and-multiply ladder base^e mod N.

        Args:
          base: (..., L) Montgomery-domain bases.
          bits: (..., nbits) MSB-first exponent bits (uint32 0/1;
            broadcast against base's batch dims — a single shared bit
            vector is the decrypt-λ pattern).
          mod: the modulus descriptor.
        Returns:
          (..., L) Montgomery-domain base^e, canonical.  The kernel path
          runs the whole ladder in ONE pallas_call.
        """
        obs.count_engine_call("mont_exp_bits")
        if self.sharded:
            from repro.distributed import he_sharding
            return he_sharding.sharded_mont_exp_bits(self, base, bits, mod)
        route = self._route(mod)
        if route == "lib":
            return bigint.mont_exp_bits(base, bits, mod)
        if route == "rns-jnp":
            from repro.crypto import rns
            return rns.mont_exp_bits(rns.for_modulus(mod), base, bits)
        from repro.kernels import ops
        return ops.rns_mont_exp_fused(base, bits, mod, tile_b=self.tile_b,
                                      interpret=self.interpret)

    def mont_exp_const(self, base: jnp.ndarray, e: int,
                       mod: Modulus) -> jnp.ndarray:
        """Ladder with a host-constant exponent `e` ≥ 0 (bit decomposition
        memoized via `bigint.cached_bits`).  Same contract as
        `mont_exp_bits`; e == 0 short-circuits to mont(1)."""
        if e == 0:
            return jnp.broadcast_to(bigint.mont_one(mod), base.shape)
        bits = obs.to_device(bigint.cached_bits(int(e), int(e).bit_length()))
        return self.mont_exp_bits(base, bits, mod)

    def he_matvec_windowed(self, cts: jnp.ndarray, digits,
                           mod: Modulus, window: int) -> jnp.ndarray:
        """Fixed-window HE matvec: (m, L) ciphertexts of Σ_i exps[i,j]·m_i.

        Args:
          cts: (n, L) Montgomery-domain ciphertexts mod n².
          digits: (n, m, levels) MSB-first window digits of the uint32
            exponents (window=1 → plain MSB-first bits).
          mod: the ciphertext modulus (n²).
          window: window width in bits (≥ 1).
        Returns:
          (m, L) Montgomery-domain ciphertexts, canonical.

        Kernel backends run the fused kernel; a mesh-sharded engine
        shards the ciphertext-row axis and ⊕-combines partials across
        devices.  The ``lib`` route is the library ladder — the shard
        body of a sharded jnp engine; single-device callers
        (`protocols.he_matvec`) run their own jitted copy of it.
        """
        obs.count_engine_call("he_matvec_windowed")
        if self.sharded:
            from repro.distributed import he_sharding
            return he_sharding.sharded_he_matvec(self, cts, digits, mod,
                                                 window)
        digits = obs.to_device(digits, _U32)
        route = self._route(mod)
        if route == "lib":
            return _lib_he_matvec(cts, digits, mod, window)
        if route == "rns-jnp":
            from repro.crypto import rns
            return rns.he_matvec(rns.for_modulus(mod), cts, digits, window)
        from repro.kernels import ops
        return ops.rns_he_matvec_fused(cts, digits, mod, window=window,
                                       tile_m=self.tile_m,
                                       chunk_n=self.chunk_n,
                                       interpret=self.interpret)

    def fixed_base_exp(self, table, digits, mod: Modulus) -> jnp.ndarray:
        """Windowed fixed-base exponentiation from a persistent table.

        Args:
          table: a `crypto.fixed_base.FixedBaseTable` for base h mod N
            (its ``table_rns`` holds ·B-domain channel states; read from
            the device copy it keeps, ``device_rns``).
          digits: (..., levels) LSB-first base-2^window exponent digits
            (`fixed_base.exp_digits`).
          mod: the table's modulus (n² for noise tables).
        Returns:
          (..., L) canonical Montgomery-domain limbs of h^e·R — the
          `paillier.noise_to_mont` contract, at ~levels RNS rounds
          instead of a 2·|N|-round ladder (BENCH fixed_base rows).

        Always the RNS pipeline regardless of `pipeline` — the table
        format *is* RNS, and the digit walk beats the ladder at every
        committed size.  Not mesh-routed: noise prefetch is party-local
        (runtime noise pool), so a sharded engine evaluates on its own
        device.
        """
        obs.count_engine_call("fixed_base_exp")
        digits = obs.to_device(digits, _U32)
        table_rns = table.device_rns
        if self.uses_kernels:
            from repro.kernels import ops
            return ops.rns_fixed_base_fused(table_rns, digits, mod,
                                            window=table.window,
                                            tile_b=self.tile_b,
                                            interpret=self.interpret)
        from repro.crypto import rns
        return rns.fixed_base_exp(rns.for_modulus(mod), table_rns, digits)

    # -- derived conveniences (same dispatch, used by paillier.py) ----------
    def to_mont(self, a: jnp.ndarray, mod: Modulus) -> jnp.ndarray:
        """Lift canonical residues into the Montgomery domain (⊙ R² )."""
        obs.count_engine_call("to_mont")
        return self._mont_mul(a, obs.to_device(mod.r2, _U32), mod)

    def from_mont(self, a: jnp.ndarray, mod: Modulus) -> jnp.ndarray:
        """Drop Montgomery-domain residues back to plain canonical form."""
        obs.count_engine_call("from_mont")
        one = jnp.zeros(mod.L, _U32).at[0].set(1)
        return self._mont_mul(a, one, mod)


def _lib_he_matvec(cts, digits, mod: Modulus, window: int) -> jnp.ndarray:
    """Windowed matvec on the bigint library: power table, then per
    digit level `window` squarings and a log-depth tree-⊕ of the selected
    powers (the schedule of `protocols._he_matvec_windowed`)."""
    n, m, levels = digits.shape
    one = bigint.mont_one(mod)
    table = [jnp.broadcast_to(one, cts.shape), cts]
    for _ in range(2, 1 << window):
        table.append(bigint.mont_mul(table[-1], cts, mod))
    table = jnp.stack(table, axis=0)                  # (2^w, n, L)
    acc = jnp.broadcast_to(one, (m, mod.L))
    for lvl in range(levels):
        for _ in range(window):
            acc = bigint.mont_mul(acc, acc, mod)
        c = jnp.take_along_axis(
            table[:, :, None, :], digits[None, :, :, lvl, None], axis=0)[0]
        while c.shape[0] > 1:
            half = c.shape[0] // 2
            merged = bigint.mont_mul(c[:half], c[half:2 * half], mod)
            if c.shape[0] % 2:
                merged = jnp.concatenate([merged, c[2 * half:]], axis=0)
            c = merged
        acc = bigint.mont_mul(acc, c[0], mod)
    return acc


def make(name: str | None = None, **kw) -> CryptoEngine:
    """Build a `CryptoEngine` from a backend name (resolved like
    `resolve_backend`); extra kwargs (tile sizes, ``mesh=``, …) pass
    through to the dataclass."""
    return CryptoEngine(backend=resolve_backend(name), **kw)


_DEFAULT: CryptoEngine | None = None


def get_engine() -> CryptoEngine:
    """Process-default engine (env-resolved on first use)."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = make()
    return _DEFAULT


def set_engine(engine: CryptoEngine | str | None) -> CryptoEngine:
    """Install the process-default engine; accepts a backend name
    (resolved via `make`) or a ready `CryptoEngine`.  Returns it."""
    global _DEFAULT
    _DEFAULT = make(engine) if isinstance(engine, (str, type(None))) \
        else engine
    return _DEFAULT


@contextlib.contextmanager
def use_engine(engine: CryptoEngine | str):
    """Temporarily switch the process-default engine (tests/benchmarks).
    Yields the installed engine; restores the previous default on exit."""
    global _DEFAULT
    prev = _DEFAULT
    set_engine(engine)
    try:
        yield get_engine()
    finally:
        _DEFAULT = prev
