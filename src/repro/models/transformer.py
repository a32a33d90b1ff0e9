"""Unified decoder stack covering all 10 architectures.

A scan-over-layers decoder parameterized by ArchConfig:
* dense / MoE SwiGLU MLPs (+ arctic's parallel dense residual, shared
  experts, a held share of the routed experts)
* GQA attention with RoPE, optional qk_norm / QKV bias / sliding window,
  or latent attention (MLA) with a latent decode cache
* RWKV6 blocks (attention-free)
* hymba hybrid blocks (parallel attention + mamba heads)
* VLM/audio variants take precomputed frontend embeddings (stub)

Like layers are stacked (leading axis = layer) and applied with
``lax.scan`` — compile time is O(1) in depth; remat is applied per layer
for training. A stack with leading dense layers before its expert layers
(``first_k_dense``) scans each run of like layers in turn: params and
caches hold one stacked tree per run, keyed as ``segments`` names them.
An expert layer's decode cache also counts, under ``routed``, the tokens
its held experts took since prefill (``expert_counts`` sums them).

Three entry points:
  forward_train   tokens/embeds -> chunked-CE loss (never materializes
                  the full (B,S,V) logits)
  prefill         tokens/embeds -> (last-token logits, decode caches)
  decode_step     one token + caches -> (logits, caches)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import mla, ssm
from .layers import (apply_rope, causal_attention_ref, decode_attention_ref,
                     dense_init, repeat_kv, rms_norm, rope_tables,
                     write_rows)
from .moe import apply_moe, init_moe

LOSS_CHUNK = 1024


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.param_dtype)


def segments(cfg: ArchConfig) -> list[tuple[str, ArchConfig]]:
    """Each run of like layers, in order: its key in params and caches,
    and the config its layers follow (``n_layers`` = the run's length)."""
    if not (cfg.is_moe and cfg.first_k_dense):
        return [("layers", cfg)]
    lead = dataclasses.replace(cfg, n_layers=cfg.first_k_dense, n_experts=0,
                               d_ff=cfg.dense_d_ff or cfg.d_ff)
    rest = dataclasses.replace(cfg, n_layers=cfg.n_layers - cfg.first_k_dense)
    return [("dense_layers", lead), ("layers", rest)]


# ================================================================= init
def init_attn(key: jax.Array, cfg: ArchConfig, dtype) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dtype),
        "wk": dense_init(ks[1], (d, hkv * hd), dtype),
        "wv": dense_init(ks[2], (d, hkv * hd), dtype),
        "wo": dense_init(ks[3], (h * hd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def init_layer(key: jax.Array, cfg: ArchConfig) -> dict:
    dtype = _dtype(cfg)
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 6)
    p: dict[str, Any] = {
        "ln1": jnp.ones((d,), jnp.float32),
        "ln2": jnp.ones((d,), jnp.float32),
    }
    if cfg.attn_free:
        p["tmix"] = ssm.init_rwkv_tmix(ks[0], cfg, dtype)
        p["cmix"] = ssm.init_rwkv_cmix(ks[1], cfg, dtype)
        return p
    p["attn"] = (mla.init_mla if cfg.is_mla else init_attn)(ks[0], cfg, dtype)
    if cfg.hybrid_ssm:
        p["mamba"] = ssm.init_mamba(ks[1], cfg, dtype)
    if cfg.is_moe:
        p["moe"] = init_moe(ks[2], cfg, dtype)
    else:
        p["mlp"] = {
            "w_gate": dense_init(ks[2], (d, f), dtype),
            "w_up": dense_init(ks[3], (d, f), dtype),
            "w_down": dense_init(ks[4], (f, d), dtype),
        }
    return p


def init_params(key: jax.Array, cfg: ArchConfig) -> dict:
    dtype = _dtype(cfg)
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    params = {
        "embed": dense_init(k_embed, (cfg.vocab_size, cfg.d_model), dtype),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    first = 0
    for name, seg in segments(cfg):
        keys = layer_keys[first:first + seg.n_layers]
        params[name] = jax.vmap(lambda k: init_layer(k, seg))(keys)
        first += seg.n_layers
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(
            k_head, (cfg.d_model, cfg.vocab_size), dtype)
    return params


# ============================================================ attention
def _qkv(p: dict, x: jax.Array, cfg: ArchConfig):
    b, s, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, cfg.hd)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.hd)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def apply_attn_seq(p: dict, x: jax.Array, cfg: ArchConfig,
                   rope: tuple) -> tuple[jax.Array, dict]:
    """Full-sequence attention; returns output and the (k, v) for caching.
    ``rope``: precomputed (cos, sin) tables (hoisted out of the layer
    scan — loop-invariant)."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, x, cfg)
    q = apply_rope(q, rope)
    k = apply_rope(k, rope)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    out = causal_attention_ref(q, repeat_kv(k, n_rep), repeat_kv(v, n_rep),
                               window=cfg.sliding_window)
    out = out.reshape(b, s, cfg.n_heads * cfg.hd) @ p["wo"]
    return out, {"k": k, "v": v}


def apply_attn_decode(p: dict, x: jax.Array, cfg: ArchConfig,
                      cache: dict, pos: jax.Array,
                      layer: jax.Array) -> tuple[jax.Array, dict]:
    """One-token decode against a (possibly ring-buffered SWA) KV cache.

    cache: every layer's, {"k": (L, B, C, Hkv, hd), "v": ...},
    C = min(S_max, window); this layer (index ``layer``) writes its new
    rows in place (``write_rows``) and attends over its own part.
    pos: (B,) absolute position of the new token.
    """
    b, s, _ = x.shape
    assert s == 1
    q, k, v = _qkv(p, x, cfg)
    rope = rope_tables(pos[:, None], cfg.hd, cfg.rope_theta)
    q = apply_rope(q, rope)
    k = apply_rope(k, rope)
    cache_size = cache["k"].shape[-3]
    slot = (pos % cache_size).astype(jnp.int32)
    k_stack, k_cache = write_rows(cache["k"], k[:, 0], slot, layer)
    v_stack, v_cache = write_rows(cache["v"], v[:, 0], slot, layer)
    cache_len = jnp.minimum(pos + 1, cache_size)
    # ring buffer holds exactly the window; mask by valid slot count only.
    # GQA is grouped inside, so the cache is never repeated per query head.
    out = decode_attention_ref(q, k_cache, v_cache, cache_len, window=None)
    out = out.reshape(b, 1, cfg.n_heads * cfg.hd) @ p["wo"]
    return out, {"k": k_stack, "v": v_stack}


# =============================================================== blocks
def _attn_cache(cfg: ArchConfig, cache: dict) -> dict:
    return cache if cfg.is_mla else {"kv": cache}


def apply_block_seq(lp: dict, x: jax.Array, cfg: ArchConfig,
                    rope: tuple):
    """One layer over a full sequence. Returns (x, aux_loss, cache)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.attn_free:
        h, tstate = ssm.apply_rwkv_tmix(lp["tmix"], rms_norm(x, lp["ln1"], cfg.norm_eps), cfg)
        x = x + h
        h, cstate = ssm.apply_rwkv_cmix(lp["cmix"], rms_norm(x, lp["ln2"], cfg.norm_eps), cfg)
        x = x + h
        cache = {"tmix": tstate, "cmix": cstate}
        return x, aux, cache
    normed = rms_norm(x, lp["ln1"], cfg.norm_eps)
    attn = mla.apply_mla_seq if cfg.is_mla else apply_attn_seq
    attn_out, kv = attn(lp["attn"], normed, cfg, rope)
    cache = _attn_cache(cfg, kv)
    if cfg.hybrid_ssm:
        ssm_out, mstate = ssm.apply_mamba(lp["mamba"], normed, cfg)
        x = x + 0.5 * (attn_out + ssm_out)
        cache["mamba"] = mstate
    else:
        x = x + attn_out
    normed2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.is_moe:
        b, s, d = normed2.shape
        out, aux, cache["routed"] = apply_moe(
            lp["moe"], normed2.reshape(b * s, d), cfg)
        x = x + out.reshape(b, s, d)
    else:
        m = lp["mlp"]
        g = normed2 @ m["w_gate"]
        u = normed2 @ m["w_up"]
        x = x + jax.nn.silu(g) * u @ m["w_down"]
    return x, aux, cache


def _at_layer(stack, layer: jax.Array):
    return jax.tree.map(lambda s: s[layer], stack)


def _set_layer(stack, layer: jax.Array, new):
    return jax.tree.map(lambda s, n: s.at[layer].set(n), stack, new)


def apply_block_decode(lp: dict, x: jax.Array, cfg: ArchConfig,
                       cache: dict, pos: jax.Array, layer: jax.Array):
    """One layer for one decode token. ``cache`` holds every layer's
    stacked state; this layer (index ``layer``) writes one new row into
    each sequence cache and its own slice of the other states. Returns
    (x, cache)."""
    if cfg.attn_free:
        normed = rms_norm(x, lp["ln1"], cfg.norm_eps)
        h, tstate = ssm.apply_rwkv_tmix(lp["tmix"], normed, cfg,
                                        state=_at_layer(cache["tmix"], layer))
        x = x + h
        normed = rms_norm(x, lp["ln2"], cfg.norm_eps)
        h, cstate = ssm.apply_rwkv_cmix(lp["cmix"], normed, cfg,
                                        state=_at_layer(cache["cmix"], layer))
        x = x + h
        return x, {"tmix": _set_layer(cache["tmix"], layer, tstate),
                   "cmix": _set_layer(cache["cmix"], layer, cstate)}
    normed = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.is_mla:
        attn_out, kv = mla.apply_mla_decode(lp["attn"], normed, cfg, cache,
                                            pos, layer)
    else:
        attn_out, kv = apply_attn_decode(lp["attn"], normed, cfg,
                                         cache["kv"], pos, layer)
    new_cache = {**cache, **_attn_cache(cfg, kv)}
    if cfg.hybrid_ssm:
        ssm_out, mstate = ssm.apply_mamba(lp["mamba"], normed, cfg,
                                          state=_at_layer(cache["mamba"],
                                                          layer))
        x = x + 0.5 * (attn_out + ssm_out)
        new_cache["mamba"] = _set_layer(cache["mamba"], layer, mstate)
    else:
        x = x + attn_out
    normed2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.is_moe:
        b, s, d = normed2.shape
        out, _, counts = apply_moe(lp["moe"], normed2.reshape(b * s, d), cfg)
        new_cache["routed"] = jax.tree.map(lambda c, n: c.at[layer].add(n),
                                           cache["routed"], counts)
        x = x + out.reshape(b, s, d)
    else:
        m = lp["mlp"]
        x = x + jax.nn.silu(normed2 @ m["w_gate"]) * (normed2 @ m["w_up"]) \
            @ m["w_down"]
    return x, new_cache


# ============================================================== forward
def _embed_inputs(params: dict, cfg: ArchConfig, batch: dict) -> jax.Array:
    if cfg.embedding_stub:
        # VLM/audio: precomputed patch/frame embeddings from the frontend
        return batch["embeds"].astype(_dtype(cfg))
    return params["embed"][batch["tokens"]]


def _stack_layers(params: dict, cfg: ArchConfig, x: jax.Array,
                  rope: tuple, with_cache: bool,
                  remat: bool):
    """Every layer over full sequences. Returns (x, aux_loss, caches per
    segment or None)."""
    aux = jnp.zeros((), jnp.float32)
    caches = {}
    for name, seg in segments(cfg):
        def body(carry, lp, seg=seg):
            x, aux = carry
            x, a, cache = apply_block_seq(lp, x, seg, rope)
            out = cache if with_cache else None
            return (x, aux + a), out

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (x, aux), out = jax.lax.scan(body, (x, aux), params[name])
        caches[name] = out
    return x, aux, caches if with_cache else None


def _rope_for(cfg: ArchConfig, s: int) -> tuple:
    if cfg.attn_free:
        return ()
    if cfg.is_mla:
        return mla.mla_rope(cfg, jnp.arange(s))
    # 1-D positions: broadcast over batch AND heads without materializing
    return rope_tables(jnp.arange(s), cfg.hd, cfg.rope_theta)


def hidden_states(params: dict, cfg: ArchConfig, batch: dict,
                  remat: Optional[bool] = None) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward to final hidden states (pre-head)."""
    x = _embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    use_remat = cfg.remat if remat is None else remat
    x, aux, _ = _stack_layers(params, cfg, x, _rope_for(cfg, s),
                              with_cache=False, remat=use_remat)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux


def lm_head_weight(params: dict, cfg: ArchConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


def forward_train(params: dict, cfg: ArchConfig, batch: dict) -> jax.Array:
    """Mean next-token cross-entropy, chunked over the sequence so the
    full (B, S, V) logits are never materialized."""
    h, aux = hidden_states(params, cfg, batch)
    labels = batch["labels"]
    w = lm_head_weight(params, cfg)
    b, s, d = h.shape
    n_chunks = max(1, s // min(LOSS_CHUNK, s))
    chunk = s // n_chunks
    h_c = h.reshape(b, n_chunks, chunk, d).transpose(1, 0, 2, 3)
    l_c = labels.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    def ce(carry, hc_lc):
        hc, lc = hc_lc
        logits = (hc @ w).astype(jnp.float32)             # (B, C, V)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(ce, jnp.zeros((), jnp.float32), (h_c, l_c))
    loss = total / (b * n_chunks * chunk)
    return loss + 0.01 * aux


def prefill(params: dict, cfg: ArchConfig, batch: dict):
    """Returns (last-token logits, caches, positions) for decoding."""
    x = _embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    x, _, caches = _stack_layers(params, cfg, x, _rope_for(cfg, s),
                                 with_cache=True, remat=False)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x[:, -1, :] @ lm_head_weight(params, cfg)).astype(jnp.float32)
    return logits, caches, jnp.full((b,), s, jnp.int32)


def expert_counts(caches: dict) -> dict:
    """The counts of apply_moe (``expert_tokens`` per held expert,
    ``expert_loads``) that the expert layers' caches hold, summed over the
    layers: since prefill, prefill's included. Empty without experts."""
    routed = [c["routed"] for c in caches.values() if "routed" in c]
    return jax.tree.map(lambda *a: sum(jnp.sum(x, axis=0) for x in a),
                        *routed) if routed else {}


# Sequence caches, (L, B, S, ...): the engine grows these to hold the
# tokens it generates. Recurrent states (rwkv, mamba) keep their size.
SEQUENCE_CACHES = frozenset({"k", "v", "latent"})


def grow_decode_cache(caches: dict, n: int) -> dict:
    """``caches`` with room for ``n`` more positions in each sequence
    cache. A layer at a time, so that a change of layout on the way (into
    the decode step's) needs room for one layer's cache, not the stack's."""
    def grow(path, c):
        if getattr(path[-1], "key", None) not in SEQUENCE_CACHES:
            return c
        pad = [(0, 0)] * (c.ndim - 1)
        pad[1] = (0, n)
        return jax.lax.map(lambda layer: jnp.pad(layer, pad), c)
    return jax.tree_util.tree_map_with_path(grow, caches)


def init_decode_cache(cfg: ArchConfig, batch_size: int, max_len: int) -> dict:
    """Blank decode caches (used to lower serve_step without a prefill),
    one stacked tree per segment."""
    return {name: _blank_cache(seg, batch_size, max_len)
            for name, seg in segments(cfg)}


def _blank_cache(cfg: ArchConfig, batch_size: int, max_len: int) -> dict:
    dtype = _dtype(cfg)
    L = cfg.n_layers

    def per_layer():
        if cfg.attn_free:
            h = cfg.d_model // cfg.rwkv_head_dim
            return {
                "tmix": {"shift": jnp.zeros((batch_size, cfg.d_model), dtype),
                         "wkv": jnp.zeros((batch_size, h, cfg.rwkv_head_dim,
                                           cfg.rwkv_head_dim), jnp.float32)},
                "cmix": jnp.zeros((batch_size, cfg.d_model), dtype),
            }
        size = max_len if cfg.sliding_window is None \
            else min(max_len, cfg.sliding_window)
        if cfg.is_mla:
            c = {"latent": jnp.zeros(
                (batch_size, size, mla.latent_width(cfg)), dtype)}
        else:
            c = {"kv": {
                "k": jnp.zeros((batch_size, size, cfg.n_kv_heads, cfg.hd),
                               dtype),
                "v": jnp.zeros((batch_size, size, cfg.n_kv_heads, cfg.hd),
                               dtype),
            }}
        if cfg.is_moe:
            c["routed"] = {"expert_tokens": jnp.zeros((cfg.held,), jnp.int32),
                           "expert_loads": jnp.zeros((), jnp.int32)}
        if cfg.hybrid_ssm:
            di = cfg.n_heads * cfg.hd
            c["mamba"] = {
                "conv": jnp.zeros((batch_size, ssm.CONV_K - 1, di), dtype),
                "h": jnp.zeros((batch_size, di, cfg.ssm_state), jnp.float32),
            }
        return c

    one = per_layer()
    return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (L,) + a.shape),
                        one)


def decode_step(params: dict, cfg: ArchConfig, tokens: jax.Array,
                caches: dict, pos: jax.Array):
    """One decoding step. tokens: (B,) int32 (or (B,D) embeds for stub
    archs); pos: (B,) absolute positions. Returns (logits, new_caches)."""
    if cfg.embedding_stub:
        x = tokens.astype(_dtype(cfg))[:, None, :]
    else:
        x = params["embed"][tokens][:, None, :]

    # Each run of layers carries its stacked caches through the scan, so
    # each layer writes its new rows in place and no step copies a whole
    # cache (the engine also keeps them in the layout the compiler picks).
    new_caches = {}
    for name, seg in segments(cfg):
        def body(carry, lp_layer, seg=seg):
            x, cache = carry
            lp, layer = lp_layer
            return apply_block_decode(lp, x, seg, cache, pos, layer), None

        (x, new_caches[name]), _ = jax.lax.scan(
            body, (x, caches[name]),
            (params[name], jnp.arange(seg.n_layers)))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x[:, 0, :] @ lm_head_weight(params, cfg)).astype(jnp.float32)
    return logits, new_caches
