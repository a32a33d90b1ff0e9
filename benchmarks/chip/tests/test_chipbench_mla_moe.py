"""The latent-attention MoE decoder (Moonlight-16B-A3B's block) against
``reference_mla_moe`` at the program's reduced sizes on the CPU, with
float32 weights: both sides compute the same mathematics in the same
precision, so they agree to float32 rounding, where a wrong cache slot,
position, expert, routing weight or key split moves the logits by the
order of the logits themselves. Then the work the new readers count and
the batches they find in a trace."""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import reference_mla_moe as ref  # noqa: E402
import traced  # noqa: E402
import work_mla_moe  # noqa: E402
from devtrace import Event, Trace  # noqa: E402
from peaks import PEAKS  # noqa: E402
from reference import f32_dot  # noqa: E402
from repro.models import init_params, mla  # noqa: E402
from repro.models.moe import (DISPATCH_CHUNK, _swiglu, apply_moe,  # noqa: E402
                              init_moe, route)
from repro.serve.engine import Engine, ServeConfig  # noqa: E402

CONFIG = json.loads((BENCH / "configs" / "moonlight-16b-a3b-ep8.json")
                    .read_text())
DRIVER = harness.load_module(BENCH / "drivers" / "serve_batches_mla.py")
# float32 to rounding: the sums of a few dozen products of unit-scale
# float32 numbers, taken in other orders
TOL = 1e-5


def _small(held=None):
    """The program's reduced sizes with float32 weights, and the reference
    configuration at the same sizes."""
    arch = dataclasses.replace(harness.arch_config(CONFIG, rehearsal=True),
                               param_dtype="float32")
    if held is not None:
        arch = dataclasses.replace(arch, experts_held=held)
    cfg = DRIVER.reference_config(CONFIG, arch,
                                  harness.reference_config(CONFIG, arch))
    cfg["param_dtype"] = "float32"
    return arch, cfg


def _close(a, b):
    scale = float(np.abs(b).max())
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=TOL * scale, rtol=0)


def test_reduced_sizes_keep_every_mechanism():
    arch, cfg = _small()
    assert arch.is_mla and arch.first_k_dense == 1 and arch.n_shared_experts
    assert arch.router_scoring == "sigmoid"
    assert cfg["experts_held"] * 8 == cfg["n_experts"]
    assert arch.n_layers > arch.first_k_dense + 1   # the MoE layers scan


def test_weights_are_the_programs():
    arch, cfg = _small()
    prog = init_params(jax.random.PRNGKey(CONFIG["weights"]["key"]), arch)
    _, layer_keys, _ = ref._top_keys(cfg)
    for i in range(cfg["n_layers"]):
        dense = i < cfg["first_k_dense"]
        stack, j = ("dense_layers", i) if dense else ("layers", i - 1)
        ours = jax.tree.map(lambda a: a[j], prog[stack])
        theirs = ref.layer_weights(cfg, layer_keys[i], dense)
        assert jax.tree.structure(ours) == jax.tree.structure(theirs)
        for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(ours)[0],
                                jax.tree.leaves(theirs)):
            assert a.shape == b.shape and float(jnp.max(jnp.abs(a - b))) \
                == 0.0, (i, path)
    outer = ref.outer_weights(cfg)
    for k in outer:
        assert float(jnp.max(jnp.abs(prog[k] - outer[k]))) == 0.0, k


def test_prefill_and_latent_decode_match_the_full_forward():
    arch, cfg = _small()
    params = init_params(jax.random.PRNGKey(0), arch)
    prompts = np.random.default_rng(3).integers(
        0, arch.vocab_size, (3, 9)).astype(np.int32)
    ids, logits = Engine(arch, params, ServeConfig(max_new_tokens=7)) \
        .generate(jnp.asarray(prompts), return_logits=True)
    want = ref.logits_at(cfg, np.concatenate([prompts, ids[:, :-1]], 1),
                         prompts.shape[1] - 1)
    assert want.shape == logits.shape
    _close(logits, want)


def test_absorbed_decode_matches_expanded_attention():
    """Attention of one new token through the latent cache, W_kvb
    absorbed, against the same token's row of the expanded attention over
    the whole sequence."""
    arch, _ = _small()
    p = mla.init_mla(jax.random.PRNGKey(5), arch, jnp.float32)
    b, s = 2, 10
    x = jax.random.normal(jax.random.PRNGKey(6), (b, s + 1, arch.d_model))
    full, _ = mla.apply_mla_seq(p, x, arch,
                                mla.mla_rope(arch, jnp.arange(s + 1)))
    _, cache = mla.apply_mla_seq(p, x[:, :s], arch,
                                 mla.mla_rope(arch, jnp.arange(s)))
    cache = {"latent": jnp.pad(cache["latent"], ((0, 0), (0, 3), (0, 0)))}
    out, new = mla.apply_mla_decode(p, x[:, s:], arch, cache,
                                    jnp.full((b,), s, jnp.int32))
    _close(out[:, 0], full[:, s])
    # the new token's latent is written at its own position
    _close(new["latent"][:, :s + 1],
           mla.apply_mla_seq(p, x, arch, mla.mla_rope(
               arch, jnp.arange(s + 1)))[1]["latent"])


@pytest.mark.parametrize("length", [10, 12])
def test_reference_attention_pads_a_last_partial_chunk(length):
    """The reference's attention in query chunks of 4, the last one
    partial or whole, against the same attention in one chunk."""
    _, cfg = _small()
    _, layer_keys, _ = ref._top_keys(cfg)
    w = ref.layer_weights(cfg, layer_keys[0], dense=True)["attn"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, length, cfg["d_model"]))
    with jax.default_matmul_precision("highest"):
        _close(ref.attention(cfg, w, x, f32_dot, q_chunk=4),
               ref.attention(cfg, w, x, f32_dot, q_chunk=length))


def _moe_weights(p):
    """The program's expert-layer weights as the reference names them."""
    return {k: p[k] for k in ("router", "router_bias", "w_gate", "w_up",
                              "w_down", "shared")}


@pytest.mark.parametrize("tokens", [1, 7, 64, 2 * DISPATCH_CHUNK])
def test_no_token_is_dropped_at_any_batch(tokens):
    """Every token-expert pair of a held expert is computed, however many
    tokens a call holds (above DISPATCH_CHUNK, in chunks), and the result
    is the reference's dense product over every token."""
    arch, cfg = _small()
    p = init_moe(jax.random.PRNGKey(7), arch, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, arch.d_model))
    out, _, counts = apply_moe(p, x, arch)
    _, idx, _ = route(p, x, arch)
    held = np.asarray(idx) < arch.held
    assert int(counts["expert_tokens"].sum()) == int(held.sum())
    np.testing.assert_array_equal(
        counts["expert_tokens"],
        np.bincount(np.asarray(idx)[held], minlength=arch.held))
    with jax.default_matmul_precision("highest"):
        want = ref.moe(cfg, _moe_weights(p), x[None], f32_dot)[0][0]
    _close(out, want)


def test_the_eight_shares_sum_to_the_uncut_layer():
    """The 8 chips of the deployment, each with its own 2 of the 16
    experts: their routed parts, with the shared experts counted once, add
    up to the reference layer that holds every expert."""
    arch, _ = _small()
    held = arch.held
    whole, whole_cfg = _small(held=arch.n_experts)
    p = init_moe(jax.random.PRNGKey(8), whole, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(9), (48, arch.d_model))
    shared = _swiglu(p["shared"], x)
    total, pairs = jnp.zeros_like(x), 0
    for chip in range(arch.n_experts // held):
        mine = slice(chip * held, (chip + 1) * held)
        share = dict(p, **{k: p[k][mine] for k in ("w_gate", "w_up",
                                                    "w_down")})
        out, _, counts = apply_moe(share, x, arch, first=chip * held)
        total = total + out - shared
        pairs += int(counts["expert_tokens"].sum())
    assert pairs == x.shape[0] * arch.experts_per_token
    with jax.default_matmul_precision("highest"):
        want = ref.moe(whole_cfg, _moe_weights(p), x[None], f32_dot)[0][0]
    _close(total + shared, want)


def test_a_program_without_the_model_keys_is_refused(monkeypatch):
    arch, _ = _small()
    monkeypatch.setattr(DRIVER, "MODEL_KEYS",
                        DRIVER.MODEL_KEYS + ("q_lora_rank",))
    with pytest.raises(harness.BenchError, match="q_lora_rank"):
        DRIVER.reference_config(CONFIG, arch, {})


# ----------------------------------------------------------------- work
def test_work_at_moonlight_sizes():
    cfg = CONFIG
    # one layer's prefill attention of a 1024-token prompt: 524800 causal
    # pairs x 16 heads x 2 x (192 + 128)
    flops, nbytes = work_mla_moe.mla_prefill_attention_work(cfg, 1, 1024)
    assert flops == 524800 * 16 * 2 * 320
    assert nbytes == 2 * 1024 * 16 * (2 * 192 + 2 * 128)
    # an expert's SwiGLU: 3 x 2048 x 1408 weights
    assert work_mla_moe.expert_work(cfg, 1, 1) == (2 * 3 * 2048 * 1408,
                                                    2 * 3 * 2048 * 1408)
    # a batch of 64 x 1024 prompts and 128 new tokens, the held experts
    # taking 8/64 of the 6 pairs a position in each of 26 layers: 2.13
    # GFLOP a position; 149 TFLOP of prefill, 31 of decode (its attention
    # over the latent and the head on every new token)
    positions = 64 * (1024 + 127)
    pairs = positions * 6 * 26 // 8
    total = work_mla_moe.serve_batch_flops(cfg, 64, 1024, 128, pairs)
    assert 175e12 < total < 185e12


# ---------------------------------------------------------------- trace
DECODE = "jit(d)/vmemkernel_decode_attention/dot"
PREFILL = "jit(p)/vmemkernel_flash_attention/dot"
EXPERTS = "jit(d)/moe_experts/mul"
# the grouped product's kernel, as a TPU trace names it: no scope
KERNEL = "ragged-dot-none"
DISPATCH = "jit(d)/moe_dispatch/sort"


def _trace():
    """Three batches of one prefill and two decode steps each; the device
    trace stops during the third batch's prefill (its program's end is
    not recorded), and the first prefill starts before its host span, as
    the device's clock may run a millisecond off."""
    progs, ops = [], []
    for t0 in (0.0, 10.0, 20.0):
        runs = [(t0 - 0.001, t0 + 1.0, PREFILL),
                (t0 + 2.0, t0 + 2.5, DECODE), (t0 + 3.0, t0 + 3.5, DECODE)]
        for a, b, path in runs[: {0.0: 3, 10.0: 2, 20.0: 1}[t0]]:
            if t0 < 20.0:
                progs.append(Event("jit_x", a, b))
            ops.append(Event("op", a, a + 0.1, path, path))
            ops.append(Event(KERNEL, a + 0.1, a + 0.25, KERNEL, KERNEL))
            ops.append(Event("op", a + 0.25, a + 0.3, EXPERTS, EXPERTS))
            ops.append(Event("op", a + 0.3, a + 0.35, DISPATCH, DISPATCH))
    host = [Event("bench:window", 0.0, 30.0),
            Event("engine.generate", 0.0, 4.0),
            Event("engine.generate", 10.0, 14.0),
            Event("engine.generate", 20.0, 24.0)]
    return Trace([{"ops": ops, "programs": progs}], host)


def test_batches_the_trace_holds():
    tr = _trace()
    assert [i for i, _, _ in traced.held_batches(tr)] == [0, 1]
    assert traced.held_batches(tr, decode_steps=2) == [(0, -0.001, 3.5)]
    # the expert operations of the first batch's three programs: under
    # the scope, and with the grouped-product kernels
    assert traced.scope_time_in(tr, "moe_experts", [(-0.001, 3.5)]) \
        == pytest.approx(3 * 0.05)
    assert traced.scope_time_in(tr, "moe_experts", [(-0.001, 3.5)],
                                kernels=(traced.GROUPED_PRODUCT,)) \
        == pytest.approx(3 * 0.2)


def _read(metric, work):
    ctx = harness.Context(config=CONFIG, traffic={},
                          run=harness.Run({}, 0, 0, {}, 0, work),
                          window_s=20.0, compiles=0,
                          peaks=PEAKS["TPU v5 lite"], trace=_trace())
    return harness.load_module(BENCH / "metrics" / f"{metric}.py").read(ctx)


def test_readers_count_only_the_batches_held():
    work = {"batch": 64, "prompt_len": 1024, "new_tokens": 3,
            "expert_pairs": [[1000, 10], [5000, 50]],
            "expert_loads": [[208, 20], [208, 20]]}
    peaks = PEAKS["TPU v5 lite"]
    least = sum(max(2 * 3 * 2048 * 1408 * n / peaks.bf16_flops_per_s,
                    2 * 3 * 2048 * 1408 * m / peaks.hbm_bytes_per_s)
                for n, m in ((1000, 208), (10, 20)))
    assert _read("expert_ffn_roofline.serve", work) == pytest.approx(
        100 * least / 0.6)
    flops, nbytes = work_mla_moe.mla_prefill_attention_work(CONFIG, 64, 1024)
    assert _read("mla_prefill_attn_roofline.serve", work) == pytest.approx(
        100 * 27 * 2 * max(flops / peaks.bf16_flops_per_s,
                           nbytes / peaks.hbm_bytes_per_s) / 0.2)
    # three decode programs held, 0.05 s of dispatch in each
    assert _read("moe_dispatch_ms.serve", work) == pytest.approx(50.0)
    # without the engine's counts (a program without them): nothing
    assert _read("expert_ffn_roofline.serve", {"new_tokens": 3}) is None
