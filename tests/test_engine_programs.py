"""The serving engine runs compiled programs: after the first batch of a
shape, a further batch traces, lowers and compiles nothing; the output
is that of the plain eager prefill and decode steps; and the decode step
takes over the buffers of the caches it is given."""

from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch.train import PRESETS
from repro.models import decode_step, init_params, prefill
from repro.serve.engine import Engine, ServeConfig

TINY = PRESETS["tiny"]
NEW_TOKENS = 5


def _prompts(cfg, batch=2, length=8):
    return jax.random.randint(jax.random.PRNGKey(1), (batch, length), 0,
                              cfg.vocab_size)


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "rwkv6-3b",
                                  "hymba-1.5b"])
def test_second_batch_compiles_nothing(arch):
    """Dense with a sliding window, attention-free and hybrid: one batch
    warms the programs up, a second of the same shape is served from
    them."""
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
    caches = jax.tree.map(
        lambda c: jnp.pad(c, [(0, 0), (0, 0), (0, n_new), (0, 0), (0, 0)]),
        caches)
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


def test_decode_step_takes_over_the_cache_buffers():
    params = init_params(jax.random.PRNGKey(0), TINY)
    engine = Engine(TINY, params, ServeConfig(max_new_tokens=NEW_TOKENS))
    logits, caches, pos = engine._prefill(params, _prompts(TINY))
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    engine._decode(params, tok, caches, pos)
    assert all(c.is_deleted() for c in jax.tree.leaves(caches))
    assert not any(p.is_deleted() for p in jax.tree.leaves(params))
