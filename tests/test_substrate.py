"""Unit tests for the training substrate: optimizers (incl. int8-EF
gradient compression), data pipeline determinism, and the analytic
parameter counts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch
from repro.train.data import DataConfig, DataIterator, synth_batch
from repro.train.optimizer import (OptConfig, apply_updates,
                                   clip_by_global_norm, init_opt_state,
                                   lr_schedule, quantize_int8)
from repro.configs.base import ShapeConfig


# ------------------------------------------------------------- optimizer
def quad_loss(p):
    return jnp.sum((p["w"] - 3.0) ** 2) + jnp.sum((p["b"] + 1.0) ** 2)


@pytest.mark.parametrize("name", ["adamw", "adafactor"])
def test_optimizer_reduces_quadratic(name):
    cfg = OptConfig(name=name, lr=0.1, warmup_steps=1, total_steps=200,
                    weight_decay=0.0)
    params = {"w": jnp.zeros((4, 4)), "b": jnp.zeros((4,))}
    state = init_opt_state(params, cfg)
    loss0 = float(quad_loss(params))
    for step in range(60):
        grads = jax.grad(quad_loss)(params)
        params, state, _ = apply_updates(grads, state, params, cfg, step)
    assert float(quad_loss(params)) < 0.05 * loss0


def test_int8_ef_compression_converges():
    """Error feedback: quantization noise must not prevent convergence."""
    cfg = OptConfig(name="adamw", lr=0.1, warmup_steps=1, total_steps=200,
                    weight_decay=0.0, compress="int8_ef")
    params = {"w": jnp.zeros((4, 4)), "b": jnp.zeros((4,))}
    state = init_opt_state(params, cfg)
    assert "ef" in state
    for step in range(80):
        grads = jax.grad(quad_loss)(params)
        params, state, _ = apply_updates(grads, state, params, cfg, step)
    assert float(quad_loss(params)) < 0.5


def test_quantize_int8_bounds_and_scale():
    x = jnp.array([-4.0, 0.0, 2.0, 4.0])
    q, scale = quantize_int8(x)
    assert q.dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(q.astype(jnp.float32) * scale),
                               np.asarray(x), atol=float(scale))


def test_clip_by_global_norm():
    g = {"a": jnp.full((10,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) > 1.0
    total = jnp.sqrt(sum(jnp.sum(x ** 2) for x in jax.tree.leaves(clipped)))
    assert float(total) == pytest.approx(1.0, rel=1e-5)


def test_lr_schedule_warmup_and_decay():
    cfg = OptConfig(lr=1.0, warmup_steps=10, total_steps=100)
    assert float(lr_schedule(cfg, 0)) < float(lr_schedule(cfg, 9))
    assert float(lr_schedule(cfg, 99)) < float(lr_schedule(cfg, 20))


# ------------------------------------------------------------------ data
def test_data_deterministic_and_resumable():
    cfg = get_arch("qwen2.5-3b").reduced()
    shape = ShapeConfig("t", "train", 32, 4)
    it1 = DataIterator(cfg, shape)
    batches = [next(it1) for _ in range(3)]
    it2 = DataIterator.from_state(cfg, shape, {"step": 1, "seed": 0})
    b1 = next(it2)
    np.testing.assert_array_equal(batches[1]["tokens"], b1["tokens"])
    # different steps differ
    assert not np.array_equal(batches[0]["tokens"], batches[1]["tokens"])


def test_data_has_learnable_structure():
    cfg = get_arch("qwen2.5-3b").reduced()
    shape = ShapeConfig("t", "train", 64, 2)
    b = synth_batch(cfg, shape, 0)
    toks = np.concatenate([b["tokens"][:, :1], b["labels"]], axis=1)
    # n-gram period 8: most positions repeat 8 steps later
    same = (toks[:, :-8] == toks[:, 8:]).mean()
    assert same > 0.6


def test_stub_archs_get_embeds():
    cfg = get_arch("pixtral-12b").reduced()
    b = synth_batch(cfg, ShapeConfig("t", "train", 16, 2), 0)
    assert "embeds" in b and b["embeds"].shape == (2, 16, cfg.d_model)
    assert "tokens" not in b


# ---------------------------------------------------------------- params
def test_model_flops_moe_counts_active_only():
    moe = get_arch("moonlight-16b-a3b")
    assert moe.active_param_count() < 0.35 * moe.param_count()
    # the published 2.9 B active: embedding, head, MLA, the dense layer,
    # shared and 6 routed experts in each of 26 layers
    assert 2.8e9 < moe.active_param_count() < 3.0e9
    dense = get_arch("qwen3-8b")
    assert dense.active_param_count() == dense.param_count()
    # sanity: param counts in the right ballpark
    assert 6e9 < dense.param_count() < 10e9
    assert 300e9 < get_arch("arctic-480b").param_count() < 600e9


@pytest.mark.parametrize("held, low, high", [
    (0, 15.5e9, 16.5e9),     # the whole model: 64 routed experts a layer
    (8, 3.3e9, 3.4e9),       # one chip's share of 8-way expert parallelism
])
def test_moonlight_parameter_count(held, low, high):
    import dataclasses
    cfg = dataclasses.replace(get_arch("moonlight-16b-a3b"),
                              experts_held=held)
    assert low < cfg.param_count() < high
