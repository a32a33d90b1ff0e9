"""Decoding through caches that the layer scan carries and updates in
place: every step after a prefill agrees with a full prefill of the same
tokens, for a sliding window whose ring buffer wraps, for rows at
positions of their own, and for latent attention with a held share of
the experts, whose per-layer counts equal those of the full prefill.
The weights are float32, so that what differs between the two paths is
where the caches put each row, not how bf16 rounds the two orders of
summation (nor which expert a near tie picks)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import decode_step, grow_decode_cache, init_params, prefill
from repro.models.transformer import SEQUENCE_CACHES

STEPS = 8

# name: (arch, each row's prompt length, decode cache length). Reduced
# danube attends over a window of 16, so a cache of 16 is a ring buffer
# that the steps past position 15 wrap.
CASES = {
    "danube": ("h2o-danube-1.8b", (6, 6), 14),
    "danube-ring": ("h2o-danube-1.8b", (12, 12), 16),
    "danube-rows-apart": ("h2o-danube-1.8b", (5, 9), 16),
    "moonlight": ("moonlight-16b-a3b", (6, 6), 14),
    "moonlight-rows-apart": ("moonlight-16b-a3b", (5, 9), 17),
}


def _routed(caches):
    """Each expert segment's per-layer counts."""
    return {name: c["routed"] for name, c in caches.items()
            if "routed" in c}


def _join(path, *rows):
    """One row's caches each: sequence caches side by side on the batch
    axis, the expert counts (which hold no batch axis) summed."""
    key = getattr(path[-1], "key", None)
    if key in SEQUENCE_CACHES:
        return jnp.concatenate(rows, axis=1)
    assert any(getattr(p, "key", None) == "routed" for p in path), path
    return sum(rows)


def _prefill_rows(params, cfg, seqs, lengths, cache_len):
    """Each row prefilled alone to its own length, its caches grown to
    ``cache_len`` positions, and the rows joined into one batch."""
    rows = []
    for seq, n in zip(seqs, lengths):
        _, caches, _ = prefill(params, cfg, {"tokens": seq[None, :n]})
        rows.append(grow_decode_cache(caches, cache_len - n))
    return jax.tree_util.tree_map_with_path(_join, *rows)


@pytest.mark.parametrize("case", sorted(CASES))
def test_decode_steps_match_a_full_prefill(case):
    arch, lengths, cache_len = CASES[case]
    cfg = dataclasses.replace(get_arch(arch).reduced(),
                              param_dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    seqs = jax.random.randint(jax.random.PRNGKey(1),
                              (len(lengths), max(lengths) + STEPS), 0,
                              cfg.vocab_size)
    caches = _prefill_rows(params, cfg, seqs, lengths, cache_len)
    full = jax.jit(lambda p, t: prefill(p, cfg, {"tokens": t})[:2])
    step = jax.jit(lambda p, t, c, pos: decode_step(p, cfg, t, c, pos))
    rows = jnp.arange(len(lengths))
    counted = _routed(caches)          # the prompts' own prefills
    for i in range(STEPS):
        pos = jnp.asarray(lengths, jnp.int32) + i
        logits, caches = step(params, seqs[rows, pos], caches, pos)
        prefixes = []
        for b, n in enumerate(lengths):
            want, want_caches = full(params, seqs[b:b + 1, :n + i + 1])
            assert jnp.allclose(logits[b], want[0], atol=2e-2, rtol=2e-2), \
                f"{case} step {i} row {b}: max diff " \
                f"{jnp.abs(logits[b] - want[0]).max()}"
            prefixes.append(_routed(want_caches))
        # each layer has counted every token so far, and one load for
        # each held expert that took a token of this step
        counted = {name: _after_step(
            counted[name], sum(p[name]["expert_tokens"] for p in prefixes))
            for name in counted}
        jax.tree.map(np.testing.assert_array_equal, _routed(caches),
                     counted)
    assert bool(counted) == cfg.is_moe


def _after_step(before: dict, tokens: jax.Array) -> dict:
    """A layer's counts after a decode step, from those before it and
    the tokens each held expert has taken in all."""
    step = tokens - before["expert_tokens"]
    return {"expert_tokens": tokens,
            "expert_loads": before["expert_loads"]
            + jnp.sum(step > 0, axis=-1)}
