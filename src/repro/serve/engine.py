"""Batched serving engine: prefill + decode over a request batch, with
the KV-cache pytree managed per step and serving metadata (model version
= latest committed checkpoint) read from the coordinator with leased
zero-roundtrip reads."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from ..configs.base import ArchConfig
from ..models import decode_step, expert_counts, grow_decode_cache, prefill


@dataclass
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0     # 0 = greedy
    seed: int = 0


class Engine:
    """Single-host batched engine (the multi-pod serve path is lowered by
    launch/dryrun.py with the production mesh; this class drives real
    arrays for the examples/tests)."""

    def __init__(self, cfg: ArchConfig, params, serve_cfg: ServeConfig =
                 ServeConfig(), registry=None,
                 consistency: Optional[str] = None) -> None:
        if registry is None and consistency is not None:
            # stand up a coordinator with the named policy from the
            # repro.consistency registry (e.g. "leaseguard", "readindex")
            from ..coord.registry import ClusterRegistry
            registry = ClusterRegistry(consistency=consistency)
        self.cfg = cfg
        self.params = params
        self.scfg = serve_cfg
        self.registry = registry
        self.model_version: Optional[dict] = None
        if registry is not None:
            # leased read: which checkpoint should we be serving?
            self.model_version = registry.latest_checkpoint()
        # The expert counts of every batch served, per phase (apply_moe's,
        # summed over layers; empty without experts), fetched with each
        # batch's ids.
        self._counts: dict = {}
        # One compiled program per phase and shape, built once. The decode
        # step also advances the positions (and, in the caches, the expert
        # counts), and its new caches take the buffers of the caches it is
        # given. Every program in flight holds one of the device queue's
        # slots, so a step issues two (decode and sample) and the host
        # runs that much further ahead.
        temperature = serve_cfg.temperature

        def serve_prefill(params, tokens):
            return prefill(params, cfg, {"tokens": tokens})

        def serve_decode(params, tok, caches, pos):
            return (*decode_step(params, cfg, tok, caches, pos), pos + 1)

        def serve_sample(logits, key):
            if temperature <= 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jax.random.categorical(key, logits / temperature,
                                          axis=-1).astype(jnp.int32)

        self._prefill = jax.jit(serve_prefill)
        self._decode = jax.jit(serve_decode, donate_argnums=2)
        self._pick = jax.jit(serve_sample)

    def generate(self, tokens: jax.Array,
                 max_new_tokens: Optional[int] = None,
                 return_logits: bool = False):
        """tokens: (B, S) prompt batch -> (B, new) generated ids. With
        ``return_logits``, also the (B, new, V) float32 logits each id
        was sampled from: index 0 from prefill, index i from the i-th
        decode step.

        Each phase runs under a profiler span (``engine.generate`` over
        ``engine.prefill``, ``engine.grow_cache``, ``engine.sample``, one
        ``engine.decode_step`` per further token and ``engine.fetch``),
        on the clock of the device trace when the profiler runs; the
        spans add no device work and no synchronisation.

        Prefill, each decode step and each sample run as compiled
        programs, so after the first batch of a shape nothing is traced
        or compiled again. Nothing is read from the device before the
        fetch: a step waits only while the device's queue is full."""
        cfg = self.cfg
        b, s = tokens.shape
        n_new = max_new_tokens or self.scfg.max_new_tokens
        with TraceAnnotation("engine.generate", batch=b, prompt_len=s,
                             new_tokens=n_new):
            with TraceAnnotation("engine.prefill"):
                logits, caches, pos = self._prefill(self.params, tokens)
                # a copy: the decode steps take over the caches' buffers
                prefilled = expert_counts(caches)
            # grow the sequence caches to hold the generated tokens
            if not cfg.attn_free:
                with TraceAnnotation("engine.grow_cache"):
                    caches = grow_decode_cache(caches, n_new)
            out, seen = [], []
            key = jax.random.PRNGKey(self.scfg.seed)
            tok = self._sample(logits, key)
            out.append(tok)
            if return_logits:
                seen.append(logits)
            for i in range(n_new - 1):
                with StepTraceAnnotation("engine.decode_step", step_num=i):
                    logits, caches, pos = self._decode(self.params, tok,
                                                       caches, pos)
                    if self.scfg.temperature > 0.0:   # greedy needs no key
                        key = jax.random.fold_in(key, i)
                    tok = self._sample(logits, key)
                out.append(tok)
                if return_logits:
                    seen.append(logits)
            with TraceAnnotation("engine.fetch"):
                # device_get starts every copy before it waits on one
                out, prefilled, counts, seen = jax.device_get(
                    (out, prefilled, expert_counts(caches), seen))
                ids = np.stack(out, axis=1)
                self._count(prefilled, counts)
                if return_logits:
                    return ids, np.stack(seen, axis=1)
            return ids

    def _count(self, prefilled: dict, total: dict) -> None:
        if not total:
            return
        batch = {"prefill": prefilled,
                 "decode": jax.tree.map(np.subtract, total, prefilled)}
        batch = jax.tree.map(lambda c: np.asarray(c, np.int64), batch)
        self._counts = jax.tree.map(np.add, self._counts, batch) \
            if self._counts else batch

    def stats(self) -> dict:
        """Counts over every batch served so far, for ``prefill`` and for
        ``decode``, as host arrays: ``expert_tokens``, the tokens routed to
        each held expert, summed over layers, and ``expert_loads``, the
        (layer call, held expert) pairs that had any token. Empty for a
        model without experts."""
        return jax.tree.map(np.copy, self._counts)

    def _sample(self, logits: jax.Array, key) -> jax.Array:
        with TraceAnnotation("engine.sample"):
            return self._pick(logits, key)
