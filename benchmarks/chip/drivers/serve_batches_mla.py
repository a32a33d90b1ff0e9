"""Offline batches of chat requests, in a closed loop, through the
program's serving entry point, for a latent-attention MoE configuration
(``serve_batches``' loop, checked against ``reference_mla_moe``).

As ``serve_batches``: set-up builds the engine and serves one warm-up
batch of the cell's own shapes; the window starts batch after batch and
starts none after ``--seconds``; prompts come from the seed; after the
window the engine is freed and a sample of the finished requests drawn
from the seed is checked against the float32 reference. Besides, the
engine's expert counts (``Engine.stats()``) are read after each batch,
for the per-layer readers.

Set-up first refuses a program whose ``ArchConfig`` lacks a model key
that the configuration file sets: ``harness.arch_config`` would drop it
and serve another model.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import reference_mla_moe
from harness import BenchError, Run, load_module, peak_bytes, percentile
from repro.configs.base import ArchConfig
from repro.launch.serve import start_engine
from repro.serve.engine import ServeConfig

serve_batches = load_module(Path(__file__).with_name("serve_batches.py"))

# The model keys beyond a dense decoder's that the reference reads, taken
# from the program's config at the sizes run (``harness.reference_config``
# copies only the dense ones).
MODEL_KEYS = ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
              "v_head_dim", "n_experts", "experts_per_token", "experts_held",
              "n_shared_experts", "router_scoring", "routed_scale",
              "first_k_dense", "dense_d_ff", "rope_theta", "norm_eps")


def reference_config(config: dict, arch, dense: dict) -> dict:
    """The configuration file ``config`` as the reference reads it, at the
    sizes of ``arch`` (``dense``: harness.reference_config's part);
    refuses a program that cannot run the file's model."""
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    missing = [k for k in MODEL_KEYS if k in config and k not in fields]
    if missing:
        raise BenchError(f"the program's ArchConfig has no {missing}: it "
                         f"cannot serve {config['name']}")
    cfg = dict(dense)
    cfg.update({k: getattr(arch, k) for k in MODEL_KEYS})
    cfg["experts_held"] = arch.held
    return cfg


def gap_checks(gaps: np.ndarray, limits: dict) -> dict:
    """The widest gap, as ``serve_batches`` checks it, and the mean gap
    over every position checked. Where bf16 and float32 select different
    experts for a token, at a near tie of the k-th and the next expert's
    scores, that position's logits move by a good part of their spread,
    so the program's widest gap reaches towards the control's; such ties
    are rare, and the mean gap keeps the two apart (PERF.md, section
    6)."""
    return {"served_logit_gap": (float(gaps.max()),
                                 limits["served_logit_gap"]),
            "served_logit_gap_mean": (float(gaps.mean()),
                                      limits["served_logit_gap_mean"])}


def run(spec) -> Run:
    ref_cfg = reference_config(spec.cell.config, spec.arch, spec.ref_cfg)
    p, arch = spec.traffic, spec.arch
    new = p["new_tokens"]
    engine = start_engine(arch, ServeConfig(max_new_tokens=new))
    engine.generate(jnp.asarray(serve_batches._prompts(
        np.random.default_rng([spec.seed, serve_batches.WARMUP_STREAM]), p,
        arch.vocab_size)))
    rng = np.random.default_rng([spec.seed, serve_batches.WINDOW_STREAM])
    setup_s = time.perf_counter() - spec.t_start

    batches = []            # (prompts, served ids, start, end, counts)
    before = engine.stats()
    w = spec.window
    w.start()
    while not batches or time.perf_counter() - w.t0 < spec.seconds:
        prompts = serve_batches._prompts(rng, p, arch.vocab_size)
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:generate"):
            ids = engine.generate(jnp.asarray(prompts))  # host array: done
        after = engine.stats()
        batches.append((prompts, ids, t, time.perf_counter(),
                        jax.tree.map(np.subtract, after, before)))
        before = after
    w.stop()
    memory = peak_bytes()

    served_manifest = engine.model_version
    committed = engine.registry.checkpoint_history()
    del engine
    gc.collect()

    ok = [(pr, ids) for pr, ids, *_ in batches
          if ids.shape == (p["batch"], new)
          and ((ids >= 0) & (ids < arch.vocab_size)).all()]
    attempted = p["batch"] * len(batches)
    done = p["batch"] * len(ok)
    latencies = [end - start for _, _, start, end, _ in batches
                 for _ in range(p["batch"])]
    tokens = sum(ids.size for _, ids, *_ in batches)
    span = batches[-1][3] - batches[0][2]

    checks = {"manifest_mismatch": (
        int(not committed or served_manifest != committed[-1]), 0)}
    if ok:
        prompts = np.concatenate([pr for pr, _ in ok])
        served = np.concatenate([ids for _, ids in ok])
        pick = np.random.default_rng(
            [spec.seed, serve_batches.CHECK_STREAM]).choice(
                done, size=min(p["check_requests"], done), replace=False)
        checks.update(gap_checks(reference_mla_moe.served_gaps(
            ref_cfg, prompts[pick], served[pick]), p["limits"]))
    counts = [c for *_, c in batches]
    phases = ("prefill", "decode")
    return Run(
        end_to_end={"setup_s": setup_s,
                    "serve_tokens_per_s": tokens / span,
                    "serve_request_p95_s": percentile(latencies, 95)},
        attempted=attempted, failed=attempted - done, checks=checks,
        memory_peak_bytes=memory,
        work={"batches": len(batches), "batch": p["batch"],
              "prompt_len": p["prompt_len"], "new_tokens": new,
              "decode_steps": len(batches) * (new - 1),
              "generate_s": span,
              # per batch, for prefill and for decode: the held experts'
              # token-expert pairs, and (layer call, held expert) pairs
              # that had tokens
              "expert_pairs": [[int(c[ph]["expert_tokens"].sum())
                                for ph in phases] for c in counts],
              "expert_loads": [[int(c[ph]["expert_loads"]) for ph in phases]
                               for c in counts]})
