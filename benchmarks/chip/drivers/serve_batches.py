"""Offline batches of chat requests, in a closed loop, through the
program's serving entry point (``launch/serve.start_engine`` and
``Engine.generate``).

Set-up builds the engine (the coordinator commits the model's manifest,
the engine reads it back with a leased read, the weights are drawn) and
serves one warm-up batch of the cell's own shapes. The window then starts
batch after batch, each as soon as the previous one has returned, and
starts none after ``--seconds``; the batch in flight at the close runs to
its end and counts. Every request of a batch completes when ``generate``
returns, so its latency is its batch's time.

Prompts are drawn from the seed, one stream for the warm-up and another
for the window, so every seed sends the same sizes. After the window the
engine is freed, and a sample of the finished requests drawn from the
seed is checked against the float32 reference.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import reference
from harness import Run, peak_bytes, percentile
from repro.launch.serve import start_engine
from repro.serve.engine import ServeConfig

WARMUP_STREAM, WINDOW_STREAM, CHECK_STREAM = 0, 1, 2


def _prompts(rng, p: dict, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, (p["batch"], p["prompt_len"]),
                        dtype=np.int32)


def run(spec) -> Run:
    p, arch = spec.traffic, spec.arch
    new = p["new_tokens"]
    engine = start_engine(arch, ServeConfig(max_new_tokens=new))
    engine.generate(jnp.asarray(_prompts(
        np.random.default_rng([spec.seed, WARMUP_STREAM]), p,
        arch.vocab_size)))
    rng = np.random.default_rng([spec.seed, WINDOW_STREAM])
    setup_s = time.perf_counter() - spec.t_start

    batches = []            # (prompts, served ids, start, end)
    w = spec.window
    w.start()
    while not batches or time.perf_counter() - w.t0 < spec.seconds:
        prompts = _prompts(rng, p, arch.vocab_size)
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:generate"):
            ids = engine.generate(jnp.asarray(prompts))  # host array: done
        batches.append((prompts, ids, t, time.perf_counter()))
    w.stop()
    memory = peak_bytes()

    served_manifest = engine.model_version
    committed = engine.registry.checkpoint_history()
    del engine
    gc.collect()

    ok = [(pr, ids) for pr, ids, _, _ in batches
          if ids.shape == (p["batch"], new)
          and ((ids >= 0) & (ids < arch.vocab_size)).all()]
    attempted = p["batch"] * len(batches)
    done = p["batch"] * len(ok)
    latencies = [end - start for _, ids, start, end in batches
                 for _ in range(p["batch"])]
    tokens = sum(ids.size for _, ids, _, _ in batches)
    span = batches[-1][3] - batches[0][2]

    checks = {"manifest_mismatch": (
        int(not committed or served_manifest != committed[-1]), 0)}
    if ok:
        prompts = np.concatenate([pr for pr, _ in ok])
        served = np.concatenate([ids for _, ids in ok])
        # every request serves the same number of tokens, so any sample
        # holds one of the longest
        pick = np.random.default_rng([spec.seed, CHECK_STREAM]).choice(
            done, size=min(p["check_requests"], done), replace=False)
        gaps = reference.served_gaps(spec.ref_cfg, prompts[pick],
                                     served[pick])
        checks["served_logit_gap"] = (float(gaps.max()),
                                      p["limits"]["served_logit_gap"])
    return Run(
        end_to_end={"setup_s": setup_s,
                    "serve_tokens_per_s": tokens / span,
                    "serve_request_p95_s": percentile(latencies, 95)},
        attempted=attempted, failed=attempted - done, checks=checks,
        memory_peak_bytes=memory,
        work={"batches": len(batches), "batch": p["batch"],
              "prompt_len": p["prompt_len"], "new_tokens": new,
              "decode_steps": len(batches) * (new - 1),
              "generate_s": span})
