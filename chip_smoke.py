"""Smoke run of the data plane on one TPU chip, through the entry points a
user calls.

* train: ``run_training`` on the ``100m`` preset (f32) for 6 steps at
  batch 8 x seq 512, saving one checkpoint whose manifest is committed
  through the Raft coordinator; the checkpoint is restored and compared
  bit for bit with the trained state.
* serve: h2o-danube-1.8b at its published width (bf16, random weights
  from a seed) behind a leaseguard coordinator, 4 requests of 512-token
  prompts, 32 greedy new tokens. The first decode step's logits are
  checked against a jitted full forward over prompt + first token.

One process runs every phase. The timings it prints are those of one
smoke run, not benchmark results. On success the last line of stdout is
``{"ok": true, "device": {...}}``; a run that finds no TPU, or any failed
check, exits nonzero without it.

Usage:
  python chip_smoke.py                                  # on a TPU host
  JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal
      # the same phases at reduced sizes on any device; never prints the
      # ok line
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs import get_arch
from repro.configs.base import ArchConfig, ShapeConfig
from repro.coord.registry import REPORTS_KEY, ClusterRegistry
from repro.launch.runtime import describe_devices, enable_compile_cache
from repro.launch.serve import (fresh_init_manifest, random_prompts,
                                start_engine)
from repro.launch.train import PRESETS, run_training
from repro.models import prefill
from repro.serve.engine import ServeConfig
from repro.train.checkpoint import restore_checkpoint

# The decode step and the full forward round differently in bf16 (other
# fusions, cached K/V vs recomputed). Allowed: 5% of the largest |logit|,
# about twice what 24 layers of bf16 residual adds (2^-8 relative each,
# ~sqrt(48) of them) could accumulate; a wrong cache slot, position or
# mask moves logits by the order of the logits themselves.
DECODE_LOGIT_TOL = 0.05


@dataclass(frozen=True)
class Plan:
    serve_cfg: ArchConfig
    requests: int
    prompt_len: int
    max_new: int
    train_cfg: ArchConfig
    train_shape: ShapeConfig
    train_steps: int


CHIP = Plan(serve_cfg=get_arch("h2o-danube-1.8b"), requests=4,
            prompt_len=512, max_new=32, train_cfg=PRESETS["100m"],
            train_shape=ShapeConfig("smoke", "train", 512, 8), train_steps=6)
# prompt + new tokens stay inside the reduced config's 16-token window
REHEARSAL = Plan(serve_cfg=get_arch("h2o-danube-1.8b").reduced(),
                 requests=4, prompt_len=8, max_new=4,
                 train_cfg=PRESETS["tiny"],
                 train_shape=ShapeConfig("smoke", "train", 32, 4),
                 train_steps=6)


def tpu_refusal(devices) -> str | None:
    """Why this process may not count as a chip run; None on a TPU."""
    if devices[0].platform != "tpu":
        return (f"chip_smoke needs a TPU; JAX found "
                f"{len(devices)} {devices[0].platform} device(s)")
    return None


def _peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B"


def _tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def train_phase(plan: Plan) -> dict:
    """Train a few steps, commit one checkpoint, restore it."""
    registry = ClusterRegistry()
    steps = plan.train_steps
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt_dir:
        t0 = time.perf_counter()
        out = run_training(plan.train_cfg, plan.train_shape, steps, ckpt_dir,
                           ckpt_every=steps, registry=registry, log_every=1)
        state = jax.block_until_ready(out["state"])
        wall_s = time.perf_counter() - t0
        history = registry.checkpoint_history()
        restored = restore_checkpoint(state, history[-1])
        bit_equal = all(
            a.dtype == b.dtype and a.shape == b.shape
            and np.asarray(a).tobytes() == np.asarray(b).tobytes()
            for a, b in zip(jax.tree.leaves(state),
                            jax.tree.leaves(restored)))
    losses = out["losses"]
    step_s = [r["s"] for r in registry.coord.read_list(REPORTS_KEY)]
    return {
        "checks": {
            f"{steps} losses, all finite":
                len(losses) == steps and bool(np.isfinite(losses).all()),
            f"one checkpoint committed through Raft at step {steps}":
                [m["step"] for m in history] == [steps],
            "restored checkpoint equals the trained state bit for bit":
                bit_equal,
        },
        "info": {
            "config": (f"{plan.train_cfg.name} {plan.train_cfg.param_dtype}"
                       f", batch {plan.train_shape.global_batch} x seq "
                       f"{plan.train_shape.seq_len}"),
            "param bytes": _tree_bytes(state["params"]),
            "losses": [round(x, 4) for x in losses],
            "first step s (compile included)": round(step_s[0], 3),
            "median later step s": round(statistics.median(step_s[1:]), 3),
            "phase wall s (init, compile, steps, save)": round(wall_s, 3),
        },
    }


def serve_phase(plan: Plan) -> dict:
    """Serve one batch through the launcher's flow and check it."""
    cfg = plan.serve_cfg
    t0 = time.perf_counter()
    engine = start_engine(cfg, ServeConfig(max_new_tokens=plan.max_new))
    jax.block_until_ready(engine.params)
    setup_s = time.perf_counter() - t0
    prompts = random_prompts(cfg, plan.requests, plan.prompt_len)
    t0 = time.perf_counter()
    # host arrays come back, so the device has finished
    ids, logits = engine.generate(prompts, return_logits=True)
    wall_s = time.perf_counter() - t0

    full_forward = jax.jit(lambda p, t: prefill(p, cfg, {"tokens": t})[0])
    ref = np.asarray(full_forward(
        engine.params, jnp.concatenate([prompts, ids[:, :1]], axis=1)))
    err = float(np.max(np.abs(logits[:, 1] - ref)))
    scale = float(np.max(np.abs(ref)))
    coord = engine.registry.coord.stats()
    return {
        "checks": {
            "output shapes": (ids.shape == (plan.requests, plan.max_new)
                              and logits.shape == (plan.requests,
                                                   plan.max_new,
                                                   cfg.vocab_size)),
            "every logit finite": bool(np.isfinite(logits).all()),
            "every token id in [0, vocab)":
                bool(((ids >= 0) & (ids < cfg.vocab_size)).all()),
            (f"first decode step vs jitted full forward: max |diff| "
             f"{err:.5g} <= {DECODE_LOGIT_TOL} x max |logit| {scale:.5g}"):
                err <= DECODE_LOGIT_TOL * scale,
            "engine serves the committed manifest":
                engine.model_version == fresh_init_manifest(cfg),
            (f"{coord['consistency']} model-version reads took 0 "
             f"messages ({coord['reads']} reads, "
             f"{coord['read_messages']} messages)"):
                coord["consistency"] == "leaseguard" and coord["reads"] > 0
                and coord["read_messages"] == 0,
        },
        "info": {
            "config": (f"{cfg.name} {cfg.param_dtype}, {plan.requests} "
                       f"requests x {plan.prompt_len} prompt tokens, "
                       f"{plan.max_new} new"),
            "param bytes": _tree_bytes(engine.params),
            "set-up s (coordinator, init params)": round(setup_s, 3),
            "generate wall s (compile included)": round(wall_s, 3),
            "first-step greedy ids agree with full forward":
                f"{int((ref.argmax(-1) == ids[:, 1]).sum())}/{plan.requests}",
        },
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Smoke run of training and serving on one TPU chip.")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run the same phases at reduced sizes on any "
                         "device (e.g. JAX_PLATFORMS=cpu); never prints the "
                         "ok line")
    args = ap.parse_args(argv)

    devices = jax.devices()
    refusal = None if args.cpu_rehearsal else tpu_refusal(devices)
    if refusal:
        print(refusal, file=sys.stderr)
        return 1
    print(f"[smoke] devices: {describe_devices()}")
    print(f"[smoke] compile cache: {enable_compile_cache()}")
    print("[smoke] timings are one smoke run, not benchmark results",
          flush=True)

    plan = REHEARSAL if args.cpu_rehearsal else CHIP
    failed = []
    # train first: the process-wide peak after it is training's own
    for phase in (train_phase, serve_phase):
        result = phase(plan)
        name = phase.__name__.removesuffix("_phase")
        for key, value in result["info"].items():
            print(f"[smoke] {name}: {key}: {value}")
        print(f"[smoke] {name}: peak bytes in use so far: {_peak_bytes()}")
        for check, ok in result["checks"].items():
            print(f"[smoke] {name}: {'PASS' if ok else 'FAIL'}: {check}",
                  flush=True)
            if not ok:
                failed.append(f"{name}: {check}")
    if failed:
        print(f"[smoke] failed checks: {failed}", file=sys.stderr)
        return 1
    if args.cpu_rehearsal:
        print("[smoke] CPU rehearsal passed; this is not a chip run")
        return 0
    print(json.dumps({"ok": True,
                      "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
