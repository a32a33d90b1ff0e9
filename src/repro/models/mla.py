"""Multi-head latent attention (MLA, DeepSeek-V2/V3; q_lora_rank null).

Each token is projected to a query per head, ``q = x W_q`` (a part
without rotary positions of ``qk_nope_head_dim`` and a roped part of
``qk_rope_head_dim``), and to one latent shared by every head,
``[c, k_pe] = x W_kva``: ``c`` (``kv_lora_rank`` wide) is RMS-normed and
``k_pe`` roped. Keys and values are expanded from the latent,
``[k_nope, v] = c W_kvb`` per head, and each head's key is
``[k_nope, k_pe]``. Scores are scaled by ``1 / sqrt(nope + rope)``.

The decode cache holds only the latent, ``[c, k_pe]`` per position
(``kv_lora_rank + qk_rope_head_dim`` values, shared by all heads).
Prefill expands it into per-head K and V and runs the causal attention;
decode absorbs ``W_kvb`` into the query and the output instead, and
attends over the cached latent itself:

  score = (q_nope W_uk) . c + q_pe . k_pe,   out = (p . c) W_uv

Rotary positions rotate the two halves of the roped part against each
other, as the rest of the stack does (DeepSeek interleaves the pairs: for
random weights that only permutes columns of ``W_q`` and ``W_kva``).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .layers import apply_rope, causal_attention_ref, dense_init, rms_norm, \
    rope_tables, write_rows

# Bytes of prefill scores one query chunk may hold (f32), so that the
# scores of a large batch never exist at once.
SCORE_CHUNK_BYTES = 1 << 29


def init_mla(key: jax.Array, cfg: ArchConfig, dtype) -> dict:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, h * (nope + rope)), dtype),
        "wkv_a": dense_init(ks[1], (d, r + rope), dtype),
        "kv_norm": jnp.ones((r,), jnp.float32),
        "wkv_b": dense_init(ks[2], (r, h * (nope + dv)), dtype),
        "wo": dense_init(ks[3], (h * dv, d), dtype),
    }


def latent_width(cfg: ArchConfig) -> int:
    """Values cached per position: the latent and the shared roped key."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def mla_rope(cfg: ArchConfig, positions: jax.Array) -> tuple:
    return rope_tables(positions, cfg.qk_rope_head_dim, cfg.rope_theta)


def _project(p: dict, x: jax.Array, cfg: ArchConfig, rope: tuple):
    """Queries (nope and roped parts) and the latent ``[c, k_pe]`` of
    each position of x: (B, S, d)."""
    b, s, _ = x.shape
    h, nope = cfg.n_heads, cfg.qk_nope_head_dim
    q = (x @ p["wq"]).reshape(b, s, h, -1)
    q_nope, q_pe = q[..., :nope], apply_rope(q[..., nope:], rope)
    kv = x @ p["wkv_a"]
    c = rms_norm(kv[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_pe = apply_rope(kv[..., None, cfg.kv_lora_rank:], rope)[:, :, 0]
    return q_nope, q_pe, jnp.concatenate([c, k_pe], axis=-1)


def apply_mla_seq(p: dict, x: jax.Array, cfg: ArchConfig,
                  rope: tuple) -> tuple[jax.Array, dict]:
    """Causal attention over whole sequences, keys and values expanded
    per head from the latent; returns the output and the latent cache."""
    b, s, _ = x.shape
    h, nope, r = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q_nope, q_pe, latent = _project(p, x, cfg, rope)
    kv = (latent[..., :r] @ p["wkv_b"]).reshape(b, s, h, -1)
    k_pe = jnp.broadcast_to(latent[:, :, None, r:],
                            (b, s, h, cfg.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    chunk = max(8, min(512, SCORE_CHUNK_BYTES // (4 * b * h * s)))
    out = causal_attention_ref(q, k, kv[..., nope:],
                               window=cfg.sliding_window, chunk=chunk)
    return out.reshape(b, s, -1) @ p["wo"], {"latent": latent}


def apply_mla_decode(p: dict, x: jax.Array, cfg: ArchConfig, cache: dict,
                     pos: jax.Array, layer: Optional[jax.Array] = None
                     ) -> tuple[jax.Array, dict]:
    """One token per sequence against the latent cache (B, C, r + rope),
    or against every layer's stacked (L, B, C, r + rope) at ``layer``
    (``write_rows``), ``W_kvb`` absorbed into the query and the output.
    pos: (B,)."""
    b = x.shape[0]
    h, nope, r = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q_nope, q_pe, new = _project(p, x, cfg, mla_rope(cfg, pos[:, None]))
    size = cache["latent"].shape[-2]
    slot = (pos % size).astype(jnp.int32)
    stack, latent = write_rows(cache["latent"], new[:, 0], slot, layer)
    w_kvb = p["wkv_b"].reshape(r, h, -1)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_kvb[..., :nope],
                       preferred_element_type=jnp.float32).astype(x.dtype)
    o_lat = latent_decode_attention(
        q_lat, q_pe[:, 0], latent, jnp.minimum(pos + 1, size),
        1.0 / math.sqrt(nope + cfg.qk_rope_head_dim))
    out = jnp.einsum("bhr,rhv->bhv", o_lat, w_kvb[..., nope:],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    return out.reshape(b, 1, -1) @ p["wo"], {"latent": stack}


def latent_decode_attention(q_lat: jax.Array, q_pe: jax.Array,
                            latent: jax.Array, cache_len: jax.Array,
                            scale: float) -> jax.Array:
    """q_lat: (B, H, r) queries absorbed into the latent; q_pe: (B, H,
    rope); latent: (B, C, r + rope) with ``cache_len`` valid entries.
    Returns the attention-weighted latent, (B, H, r)."""
    r = q_lat.shape[-1]
    with jax.named_scope("vmemkernel_decode_attention"):
        c, k_pe = latent[..., :r], latent[..., r:]
        s = (jnp.einsum("bhr,bcr->bhc", q_lat, c,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhe,bce->bhc", q_pe, k_pe,
                          preferred_element_type=jnp.float32)) * scale
        valid = jnp.arange(latent.shape[1])[None, :] < cache_len[:, None]
        s = jnp.where(valid[:, None, :], s, -jnp.inf)
        probs = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhc,bcr->bhr", probs.astype(c.dtype), c,
                          preferred_element_type=jnp.float32
                          ).astype(q_lat.dtype)
