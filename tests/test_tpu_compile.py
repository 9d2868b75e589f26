"""Compile the RNS Pallas kernels for a described TPU v5e, without a chip.

Interpret mode (tests/test_rns.py) checks what the kernels compute; only
the TPU compiler checks how they are laid out: lane slices, casts it has
no lowering for, and scoped-VMEM sizes.  Every kernel the compiled
``pallas`` backend can reach is compiled here at the real widths of 1024-
and 2048-bit Paillier keys (n² of 2048 and 4096 bits), with the engine's
real tiles and matvec chunk.

This is the only test file that describes the chip.  The topology is
described inside a module-scoped fixture, never at import: only one
process may hold the TPU library, and every pytest-xdist worker imports
this file.
"""
import os

import pytest

import jax
from jax.sharding import SingleDeviceSharding

from repro.crypto import fixed_base, rns
from repro.crypto.bigint import Modulus
from repro.kernels import montexp, montmul

KEY_BITS = [1024, 2048]
WINDOW = 4                      # protocols.DEFAULT_WINDOW
MATVEC_LEVELS = 6               # 22-bit feature exponents at window 4
TILE = montmul.DEFAULT_TILE_B   # == montexp.DEFAULT_TILE_M
CHUNK_N = montexp.DEFAULT_CHUNK_N


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _lowered(kernel: str, key_bits: int, sharding):
    mod = Modulus.make((1 << (2 * key_bits)) - 159)     # n²-sized, odd
    ctx = rns.for_modulus(mod)
    CH = ctx.CH

    def S(*shape):
        return jax.ShapeDtypeStruct(shape, jax.numpy.uint32,
                                    sharding=sharding)

    consts = (S(CH), S(*ctx.t_b.shape), S(*ctx.t_a.shape), S(6, CH))
    kw = dict(kA=ctx.kA, kB=ctx.kB, ainv_r=ctx.ainv_r, interpret=False)
    batch = 2 * TILE                                     # two programs
    if kernel == "montmul":
        fn = lambda *a: montmul.rns_montmul_tiled(*a, tile_b=TILE, **kw)
        args = (S(batch, CH), S(batch, CH)) + consts
    elif kernel == "ladder":
        fn = lambda *a: montexp.rns_mont_exp_tiled(*a, tile_b=TILE, **kw)
        args = (S(batch, CH), S(batch, key_bits)) + consts + (S(CH), S(CH))
    elif kernel == "matvec":
        fn = lambda *a: montexp.rns_he_matvec_tiled(
            *a, window=WINDOW, tile_m=TILE, **kw)
        args = (S(CHUNK_N, CH), S(MATVEC_LEVELS, TILE, CHUNK_N)) + consts \
            + (S(CH),)
    else:
        levels = -(-fixed_base.DEFAULT_RHO_BITS // WINDOW)
        fn = lambda *a: montexp.rns_fixed_base_tiled(
            *a, window=WINDOW, tile_b=TILE, **kw)
        args = (S(levels, 1 << WINDOW, CH), S(batch, levels)) + consts \
            + (S(CH), S(CH))
    return jax.jit(fn).lower(*args)


@pytest.mark.parametrize("key_bits", KEY_BITS)
@pytest.mark.parametrize("kernel", ["montmul", "ladder", "matvec",
                                    "fixed_base"])
def test_rns_kernel_compiles_for_v5e(kernel, key_bits, one_chip,
                                     no_compile_cache):
    compiled = _lowered(kernel, key_bits, one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
