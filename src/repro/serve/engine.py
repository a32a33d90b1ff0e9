"""Batched serving engine: prefill + decode over a request batch, with
the KV-cache pytree managed per step and serving metadata (model version
= latest committed checkpoint) read from the coordinator with leased
zero-roundtrip reads."""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.compilation_cache import compilation_cache
from jax.experimental.layout import Format, Layout
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
        # given, in the layout the compiler picks for them: the layer scan
        # carries them and writes one row a layer in place, and with the
        # layout fixed from outside XLA would relay the whole cache out on
        # entering and leaving that loop. Every program in flight holds one
        # of the device queue's slots, so a step issues two (decode and
        # sample) and the host runs that much further ahead.
        temperature = serve_cfg.temperature
        auto = Format(Layout.AUTO)

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
        self._decode = jax.jit(serve_decode, donate_argnums=2,
                               in_shardings=(None, None, auto, None),
                               out_shardings=(None, auto, None))
        self._pick = jax.jit(serve_sample)
        self._compiled: dict = {}

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
        b, s = tokens.shape
        n_new = max_new_tokens or self.scfg.max_new_tokens
        with TraceAnnotation("engine.generate", batch=b, prompt_len=s,
                             new_tokens=n_new):
            with TraceAnnotation("engine.prefill"):
                logits, caches, pos = self._prefill(self.params, tokens)
                # a copy: the decode steps take over the caches' buffers
                prefilled = expert_counts(caches)
            with TraceAnnotation("engine.grow_cache"):
                # room for the generated tokens, in the decode step's layout
                decode, grow = self._programs(pos, caches, pos, n_new)
                caches = grow(caches)
            out, seen = [], []
            key = jax.random.PRNGKey(self.scfg.seed)
            tok = self._sample(logits, key)
            out.append(tok)
            if return_logits:
                seen.append(logits)
            for i in range(n_new - 1):
                with StepTraceAnnotation("engine.decode_step", step_num=i):
                    logits, caches, pos = decode(self.params, tok, caches,
                                                 pos)
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

    def _programs(self, tok, caches, pos, n_new: int):
        """The decode step compiled for the arguments of a batch's first
        step, whose caches are a prefill's (arrays or
        ``jax.ShapeDtypeStruct``s) grown by ``n_new`` positions; and,
        compiled for those caches, the program, run once a batch, that
        grows them into the layout the step takes them in. Both built
        once per shape."""
        key = (n_new, tuple((a.shape, a.dtype)
                            for a in jax.tree.leaves((tok, caches, pos))))
        if key not in self._compiled:
            grow = partial(grow_decode_cache, n=n_new)
            grown = jax.tree.map(
                lambda g, c: jax.ShapeDtypeStruct(g.shape, g.dtype,
                                                  sharding=c.sharding),
                jax.eval_shape(grow, caches), caches)
            decode = _compile_anew(self._decode.lower(self.params, tok,
                                                      grown, pos))
            grow = _compile_anew(jax.jit(
                grow, out_shardings=decode.input_formats[0][2]
            ).lower(caches))
            self._compiled[key] = decode, grow
        return self._compiled[key]

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


def _compile_anew(lowered):
    """``lowered`` compiled, and never read back from JAX's persistent
    compilation cache: on a TPU v5e an executable read back from it
    returns its outputs in the default layout, not the one it was
    compiled for, so a program that hands arrays on in a layout of its
    own is compiled anew in each process."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
