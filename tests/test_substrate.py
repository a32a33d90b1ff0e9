"""Unit tests for the training substrate: optimizers (incl. int8-EF
gradient compression), data pipeline determinism, sharding rules, and
the loop-aware roofline analyzer."""

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


# ------------------------------------------------------------- sharding
def test_param_specs_cover_all_archs():
    import os
    from jax.sharding import PartitionSpec
    if jax.device_count() < 8:
        pytest.skip("needs >= 8 host devices (run via dryrun path)")


def test_roofline_loop_multiplication():
    from repro.roofline import analyze_hlo

    def scanned(w, x):
        def body(x, wi):
            return jnp.tanh(x @ wi), None
        x, _ = jax.lax.scan(body, x, w)
        return x

    w = jax.ShapeDtypeStruct((8, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((16, 64), jnp.float32)
    c8 = analyze_hlo(jax.jit(scanned).lower(w, x).compile().as_text())
    w2 = jax.ShapeDtypeStruct((4, 64, 64), jnp.float32)
    c4 = analyze_hlo(jax.jit(scanned).lower(w2, x).compile().as_text())
    assert c8.flops == pytest.approx(2 * c4.flops, rel=0.05)
    expected = 8 * 2 * 16 * 64 * 64
    assert c8.flops == pytest.approx(expected, rel=0.05)


def test_roofline_counts_collectives():
    from repro.roofline import RooflineCounts, roofline_terms
    c = RooflineCounts(flops=197e12, hbm_bytes=819e9, link_bytes=25e9)
    t = roofline_terms(c, peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(1.0)
    assert t["collective_s"] == pytest.approx(0.5)
    assert t["dominant"] in ("compute", "memory")


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


# ------------------------------------------------------------------- moe
def test_grouped_moe_matches_flat_dispatch():
    """The grouped dispatch (§Perf iteration 6, off by default) must be
    numerically equivalent to the sorted dispatch when capacity is ample,
    under sigmoid routing with a held share and shared experts."""
    import dataclasses
    from repro.models.moe import apply_moe, init_moe
    from repro.sharding import ctx

    cfg = get_arch("moonlight-16b-a3b").reduced()
    assert cfg.router_scoring == "sigmoid" and cfg.held < cfg.n_experts
    cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts))
    key = jax.random.PRNGKey(0)
    p = init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (32, cfg.d_model),
                          jnp.float32) * 0.1
    ctx.set_moe_groups(1)
    flat, aux1, counts1 = apply_moe(p, x, cfg)
    ctx.set_moe_groups(4)
    try:
        grouped, aux2, counts2 = apply_moe(p, x, cfg)
    finally:
        ctx.set_moe_groups(1)
    assert int(counts1["expert_tokens"].sum()) > 0
    np.testing.assert_array_equal(counts1["expert_tokens"],
                                  counts2["expert_tokens"])
    np.testing.assert_allclose(np.asarray(flat), np.asarray(grouped),
                               atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(float(aux1), float(aux2), rtol=1e-5)
