"""The float32 reference against the program at reduced size on the CPU,
and the configuration files against the program's own settings.

With float32 weights both sides compute the same mathematics in the same
precision, so they agree to float32 rounding: a wrong mask, position,
head grouping, key split or optimizer constant would not."""

import dataclasses
import json
import math
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import reference  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402
from repro.models import forward_train, init_params  # noqa: E402
from repro.serve.engine import Engine, ServeConfig  # noqa: E402
from repro.train.data import synth_batch  # noqa: E402
from repro.train.optimizer import OptConfig, lr_schedule  # noqa: E402
from repro.train.train_step import init_train_state, train_step  # noqa: E402

SERVE = json.loads((BENCH / "configs" / "h2o-danube-1.8b.json").read_text())
TRAIN = json.loads((BENCH / "configs" / "h2o-danube-1.8b-train.json")
                   .read_text())


def _small(config: dict):
    """The program's reduced sizes, with float32 weights."""
    arch = dataclasses.replace(harness.arch_config(config, rehearsal=True),
                               param_dtype="float32")
    cfg = harness.reference_config(config, arch)
    cfg["param_dtype"] = "float32"
    return arch, cfg


def test_weights_are_the_programs():
    arch, cfg = _small(SERVE)
    # eagerly, as start_engine and run_training draw them
    prog = init_params(jax.random.PRNGKey(SERVE["weights"]["key"]), arch)
    ref = reference.all_weights(cfg)
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(prog)[0],
            jax.tree_util.tree_flatten_with_path(ref)[0]):
        assert a.shape == b.shape, path
        assert float(jnp.max(jnp.abs(a - b))) == 0.0, path


def test_prefill_and_cached_decode_match_the_full_forward():
    arch, cfg = _small(SERVE)
    params = init_params(jax.random.PRNGKey(0), arch)
    prompts = np.random.default_rng(3).integers(
        0, arch.vocab_size, (2, 10)).astype(np.int32)
    new = 6           # prompt and new tokens fill the 16-token window
    ids, logits = Engine(arch, params, ServeConfig(max_new_tokens=new)) \
        .generate(jnp.asarray(prompts), return_logits=True)
    with jax.default_matmul_precision("highest"):
        ref = reference.logits_at(
            cfg, np.concatenate([prompts, ids[:, :-1]], 1), prompts.shape[1] - 1)
    assert ref.shape == logits.shape
    scale = np.abs(ref).max()
    np.testing.assert_allclose(logits, ref, atol=1e-5 * scale)


def test_served_gap_is_zero_for_the_references_own_tokens():
    _, cfg = _small(SERVE)
    prompts = np.random.default_rng(4).integers(
        0, cfg["vocab_size"], (2, 6)).astype(np.int32)
    tokens = prompts
    served = []
    for _ in range(4):     # greedy decode by the reference itself
        logits = reference.logits_at(cfg, tokens, tokens.shape[1] - 1)
        served.append(logits[:, -1].argmax(-1))
        tokens = np.concatenate([tokens, served[-1][:, None]], 1)
    served = np.stack(served, 1).astype(np.int32)
    assert reference.served_gaps(cfg, prompts, served).max() == 0.0
    altered = (served + 1) % cfg["vocab_size"]
    assert reference.served_gaps(cfg, prompts, altered).min() > 0.0


def test_training_loss_and_gradients_match():
    arch, cfg = _small(TRAIN)
    params = jax.jit(partial(init_params, cfg=arch))(jax.random.PRNGKey(0))
    batch = reference.synth_batch(cfg, cfg["data"], 2, 32, step=0)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: forward_train(p, arch, batch)))(params)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = reference._loss_and_grads(
            reference.Static(cfg), "f32", 1, reference.all_weights(cfg),
            jnp.asarray(batch["tokens"]), jnp.asarray(batch["labels"]))
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    prog, ref = reference.leaf_norms(grads), reference.leaf_norms(ref_grads)
    assert prog.keys() == ref.keys()
    for k in ref:
        assert prog[k] == pytest.approx(ref[k], rel=1e-4), k


def test_three_optimizer_steps_match():
    """The program's first three steps against the reference's, read as
    the benchmark reads them."""
    arch, cfg = _small(TRAIN)
    steps = 30
    opt = OptConfig(name="adamw", warmup_steps=min(20, max(2, steps // 10)),
                    total_steps=max(steps, 100))
    batches = [reference.synth_batch(cfg, cfg["data"], 2, 32, s)
               for s in range(3)]
    state = jax.jit(partial(init_train_state, cfg=arch, opt_cfg=opt))(
        jax.random.PRNGKey(0))
    start = state["params"]
    step = jax.jit(partial(train_step, cfg=arch, opt_cfg=opt))
    losses = []
    for s, b in enumerate(batches):
        state, metrics = step(state, b)
        losses.append(float(metrics["loss"]))
        if s == 0:
            scale = max(1.0, float(metrics["grad_norm"]))
            first = {k: v * scale / (1 - cfg["adamw"]["b1"]) for k, v in
                     reference.leaf_norms(state["opt"]["m"]).items()}
    change = reference.leaf_norms(jax.tree.map(jnp.subtract,
                                               state["params"], start))
    ref = reference.train_reference(cfg, batches, steps)
    gaps = harness.train_gaps({"losses": losses, "first_grad_norms": first,
                               "change_norms": change}, ref)
    assert max(gaps.values()) < 1e-3, gaps


def test_configuration_files_state_what_the_program_runs():
    arch = get_arch("h2o-danube-1.8b")
    for config in (SERVE, TRAIN):
        ours = harness.arch_config(config, rehearsal=False)
        for f in dataclasses.fields(arch):
            if f.name not in ("name", "source", "n_layers", "head_dim"):
                assert getattr(ours, f.name) == getattr(arch, f.name), f.name
        assert ours.hd == arch.hd
    assert harness.arch_config(SERVE, False).n_layers == arch.n_layers
    assert TRAIN["reduced"] == ["n_layers"]
    assert [k for k in SERVE if SERVE[k] != TRAIN.get(k)] == [
        "name", "n_layers", "reduced", "deployment", "assumed"]


def test_optimizer_and_data_are_the_programs():
    a = TRAIN["adamw"]
    opt = OptConfig()
    assert (a["lr"], a["b1"], a["b2"], a["eps"], a["weight_decay"],
            a["clip_norm"]) == (opt.lr, opt.b1, opt.b2, opt.eps,
                                opt.weight_decay, opt.clip_norm)
    for steps in (4, 33, 70, 250):
        # run_training's schedule for a run of ``steps`` steps
        prog = OptConfig(warmup_steps=min(20, max(2, steps // 10)),
                         total_steps=max(steps, 100))
        for s in (0, 1, 5, steps - 1):
            assert reference.lr_at(a, steps, s) == pytest.approx(
                float(lr_schedule(prog, jnp.asarray(s))), rel=1e-6)
    arch = get_arch("h2o-danube-1.8b")
    shape = ShapeConfig("t", "train", 64, 3)
    for step in (0, 7):
        prog = synth_batch(arch, shape, step)
        ref = reference.synth_batch(TRAIN, TRAIN["data"], 3, 64, step)
        for k in ("tokens", "labels"):
            np.testing.assert_array_equal(prog[k], ref[k])


def test_fp8_control_rounds_coarser_than_bf16():
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 64), jnp.float32)
    eye = jnp.eye(64)
    fp8 = reference.fp8_dot("ij,jk->ik", x, eye)
    bf16 = x.astype(jnp.bfloat16).astype(jnp.float32)
    err = lambda y: float(jnp.max(jnp.abs(y - x)) / jnp.max(jnp.abs(x)))
    assert err(fp8) > 4 * err(bf16)
    assert math.isfinite(err(fp8)) and err(fp8) < 0.1
