"""The serving engine runs compiled programs: after the first batch of a
shape, a further batch traces, lowers and compiles nothing; the output
is that of the plain eager prefill and decode steps, for every kind of
cache; and the decode step takes over the buffers of the caches it is
given."""

import dataclasses
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch.train import PRESETS
from repro.models import decode_step, grow_decode_cache, init_params, prefill
from repro.serve.engine import Engine, ServeConfig

TINY = PRESETS["tiny"]
NEW_TOKENS = 5


def _prompts(cfg, batch=2, length=8):
    return jax.random.randint(jax.random.PRNGKey(1), (batch, length), 0,
                              cfg.vocab_size)


# Dense with a sliding window, attention-free, hybrid, and latent
# attention with a held share of the experts.
ARCHS = ["h2o-danube-1.8b", "rwkv6-3b", "hymba-1.5b", "moonlight-16b-a3b"]


@pytest.mark.parametrize("arch", ARCHS)
def test_second_batch_compiles_nothing(arch):
    """One batch warms the programs up, a second of the same shape is
    served from them."""
    cfg = get_arch(arch).reduced()
    engine = Engine(cfg, init_params(jax.random.PRNGKey(0), cfg),
                    ServeConfig(max_new_tokens=NEW_TOKENS))
    engine.generate(_prompts(cfg))
    events = Counter()

    def count(event, _secs, **_kw):
        events[event] += 1

    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        ids = engine.generate(_prompts(cfg))
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    assert ids.shape == (2, NEW_TOKENS)
    for event in ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration"):
        assert events[event] == 0, dict(events)


def _eager_greedy(params, cfg, prompts, n_new):
    """Greedy decoding by the plain eager prefill and decode steps."""
    logits, caches, pos = prefill(params, cfg, {"tokens": prompts})
    caches = grow_decode_cache(caches, n_new)
    ids, seen = [], [logits]
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    ids.append(tok)
    for i in range(n_new - 1):
        logits, caches = decode_step(params, cfg, tok, caches, pos + i)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ids.append(tok)
        seen.append(logits)
    return np.stack(ids, axis=1), np.stack(seen, axis=1)


def test_compiled_programs_serve_what_the_eager_steps_do():
    params = init_params(jax.random.PRNGKey(0), TINY)
    engine = Engine(TINY, params, ServeConfig(max_new_tokens=NEW_TOKENS))
    prompts = _prompts(TINY)
    ids, logits = engine.generate(prompts, return_logits=True)
    want_ids, want_logits = _eager_greedy(params, TINY, prompts, NEW_TOKENS)
    np.testing.assert_array_equal(ids, want_ids)
    assert logits.dtype == np.float32
    scale = np.abs(want_logits).max()
    np.testing.assert_allclose(logits, want_logits, rtol=1e-5,
                               atol=1e-5 * scale)
    # the caches given away to the decode steps are never read again:
    # a second call serves the same batch again
    again, logits_again = engine.generate(prompts, return_logits=True)
    np.testing.assert_array_equal(again, ids)
    np.testing.assert_array_equal(logits_again, logits)


@pytest.mark.parametrize("arch", ARCHS)
def test_engine_serves_what_a_plain_decode_loop_does(arch):
    """Greedy ids, float32 weights: in bf16 a random model's best logits
    can tie exactly, and then the order of summation picks the id."""
    cfg = dataclasses.replace(get_arch(arch).reduced(),
                              param_dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    engine = Engine(cfg, params, ServeConfig(max_new_tokens=NEW_TOKENS))
    ids = engine.generate(_prompts(cfg))
    want, _ = _eager_greedy(params, cfg, _prompts(cfg), NEW_TOKENS)
    np.testing.assert_array_equal(ids, want)


def test_decode_step_takes_over_the_cache_buffers():
    params = init_params(jax.random.PRNGKey(0), TINY)
    engine = Engine(TINY, params, ServeConfig(max_new_tokens=NEW_TOKENS))
    logits, caches, pos = engine._prefill(params, _prompts(TINY))
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    decode, grow = engine._programs(tok, caches, pos, NEW_TOKENS)
    grown = grow(caches)
    decode(params, tok, grown, pos)
    assert all(c.is_deleted() for c in jax.tree.leaves(grown))
    assert not any(p.is_deleted() for p in jax.tree.leaves(params))


def test_layout_programs_are_compiled_anew(tmp_path):
    """The decode step and the cache growth hand caches on in a layout of
    their own, which an executable read back from the persistent
    compilation cache loses on a TPU: a second engine compiles them anew
    while its prefill comes back from the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    params = init_params(jax.random.PRNGKey(0), TINY)
    hits = Counter()

    def count(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            hits["now"] += 1

    saved = {name: getattr(jax.config, name) for name in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    jax.monitoring.register_event_listener(count)
    try:
        for _ in range(2):
            engine = Engine(TINY, params,
                            ServeConfig(max_new_tokens=NEW_TOKENS))
            hits.clear()
            logits, caches, pos = engine._prefill(params, _prompts(TINY))
            prefill_hits = hits["now"]
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            engine._programs(tok, caches, pos, NEW_TOKENS)
        assert prefill_hits > 0           # the cache is on and read
        assert hits["now"] == prefill_hits, dict(hits)
    finally:
        jax.monitoring.unregister_event_listener(count)
        for name, value in saved.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
