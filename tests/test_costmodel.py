"""Validates the analytic cost model against XLA's cost_analysis on
FULLY-UNROLLED small configs — the regime where XLA's numbers are exact
(no while loops).  This is the ground-truth anchor for §Roofline."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig, ShapeCell
from repro.launch import costmodel
from repro.models import transformer


class _Mesh1:
    axis_names = ("data", "model")

    class devices:
        size = 1
        shape = (1, 1)


MESH1 = _Mesh1()


def _val_cfg(**kw):
    base = dict(name="val", family="dense", n_layers=2, d_model=256,
                n_heads=4, n_kv_heads=2, d_ff=512, vocab_size=2048,
                head_dim=64, remat=False, debug_unroll=True, act="silu")
    base.update(kw)
    return ModelConfig(**base)


def _xla_flops(fn, *args) -> float:
    compiled = jax.jit(fn).lower(*args).compile()
    return float(compiled.cost_analysis()["flops"])


@pytest.mark.parametrize("S,B", [(128, 2), (256, 1)])
def test_forward_flops_dense(S, B):
    cfg = _val_cfg()
    cell = ShapeCell("t", S, B, "prefill")
    params = transformer.init_params(jax.random.key(0), cfg)
    tokens = jnp.zeros((B, S), jnp.int32)

    def fwd(p, t):
        logits, _, _ = transformer.forward(p, cfg, t, kv_block=None)
        return logits

    got = _xla_flops(fwd, params, tokens)
    want = costmodel.forward_flops_total(cfg, cell, costmodel.CostKnobs())
    assert abs(got - want) / want < 0.25, (got, want, got / want)


def test_train_flops_multiplier():
    """fwd+bwd ≈ 3× fwd (no remat): the analytic multiplier is right."""
    cfg = _val_cfg()
    B, S = 2, 128
    params = transformer.init_params(jax.random.key(0), cfg)
    batch = {"tokens": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.zeros((B, S), jnp.int32)}

    def fwd(p, b):
        return transformer.train_loss(p, cfg, b)

    f_fwd = _xla_flops(fwd, params, batch)
    f_train = _xla_flops(
        lambda p, b: jax.value_and_grad(fwd)(p, b), params, batch)
    ratio = f_train / f_fwd
    assert 2.4 < ratio < 3.6, ratio


def test_moe_flops():
    cfg = _val_cfg(family="moe", n_experts=8, experts_per_token=2,
                   moe_d_ff=256, capacity_factor=1.25)
    B, S = 2, 128
    cell = ShapeCell("t", S, B, "prefill")
    params = transformer.init_params(jax.random.key(0), cfg)
    tokens = jnp.zeros((B, S), jnp.int32)

    def fwd(p, t):
        logits, _, _ = transformer.forward(p, cfg, t, kv_block=None)
        return logits

    got = _xla_flops(fwd, params, tokens)
    want = costmodel.forward_flops_total(cfg, cell, costmodel.CostKnobs())
    # sorted dispatch adds gather/scatter overhead; model counts GEMMs
    assert abs(got - want) / want < 0.35, (got, want, got / want)


def test_decode_flops():
    cfg = _val_cfg()
    B, S_ctx = 4, 256
    cell = ShapeCell("t", S_ctx, B, "decode")
    params = transformer.init_params(jax.random.key(0), cfg)
    cache = transformer.init_cache(cfg, B, S_ctx)
    cache = cache._replace(length=jnp.asarray(S_ctx - 1, jnp.int32))
    token = jnp.zeros((B, 1), jnp.int32)

    def step(p, c, t):
        return transformer.decode_step(p, cfg, c, t)[0]

    got = _xla_flops(step, params, cache, token)
    want = costmodel.forward_flops_total(cfg, cell, costmodel.CostKnobs())
    assert abs(got - want) / want < 0.35, (got, want, got / want)


def test_cell_costs_sane_at_scale():
    """Full-size sanity: useful-flops ratio ≤ 1ish and memory > params."""
    from repro.configs import registry
    from repro.configs.base import SHAPE_CELLS
    for arch in ("qwen3-4b", "kimi-k2-1t-a32b", "rwkv6-1.6b"):
        cfg = registry.get_config(arch)
        cell = SHAPE_CELLS["train_4k"]

        class M:
            axis_names = ("data", "model")

            class devices:
                size = 256
                shape = (16, 16)

        costs = costmodel.cell_costs(cfg, cell, M)
        model_flops = 6 * cfg.active_param_count() * cell.seq_len \
            * cell.global_batch
        total = costs["flops_per_dev"] * 256
        assert total >= model_flops * 0.8, (arch, total / model_flops)
        assert total <= model_flops * 4.0, (arch, total / model_flops)
