"""Core NN building blocks (pure JAX, functional, pytree params).

Conventions:
* params are nested dicts of jnp arrays; compute dtype bf16, accumulation
  and norms in fp32;
* attention projections are kept flat — (d_model, n_heads*head_dim) —
  and heads are reshaped after the matmul;
* the causal-attention reference is **chunked** over queries (bounded
  memory: never materializes the full S×S score matrix), which is also the
  oracle for the Pallas flash-attention kernel.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------- norms
def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def group_norm_heads(x: jax.Array, w: jax.Array, b: jax.Array,
                     eps: float = 64e-5) -> jax.Array:
    """Per-head LayerNorm (RWKV's group_norm over heads). x: (..., H, hd)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * w + b).astype(x.dtype)


# ---------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def rope_tables(positions: jax.Array, head_dim: int,
                theta: float) -> tuple[jax.Array, jax.Array]:
    """Precomputed (cos, sin), shaped (..., 1, hd/2). positions: (S,) or
    (B, S). Computed ONCE outside the layer scan (loop-invariant)."""
    freqs = rope_freqs(head_dim, theta)                 # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(angles)[..., None, :], jnp.sin(angles)[..., None, :]


def apply_rope(x: jax.Array, rope: tuple[jax.Array, jax.Array]) -> jax.Array:
    """x: (B, S, H, hd); rope = (cos, sin) from rope_tables (broadcasts
    right-aligned against (B, S, H, hd/2))."""
    cos, sin = rope
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------- attention
def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, Hkv, hd) -> (B, S, Hkv*n_rep, hd)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)) \
        .reshape(b, s, h * n_rep, d)


def causal_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         window: Optional[int] = None,
                         q_offset: int = 0,
                         chunk: int = 512) -> jax.Array:
    """Chunked causal attention. q, k: (B,Sq,H,hd), (B,Sk,H,hd); v:
    (B,Sk,H,hd_v), which may be narrower (latent attention).

    ``q_offset``: absolute position of q[0] relative to k[0] (decode:
    Sk-1). Memory is O(Sq_chunk * Sk), never O(Sq*Sk) at once. Each chunk
    is rematerialized in backward (flash-attention-style: probabilities
    are never stashed across chunks).
    """
    b, sq, h, hd = q.shape
    sk, hd_v = k.shape[1], v.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    kpos = jnp.arange(sk)

    @partial(jax.checkpoint, prevent_cse=False)
    def attend(q_chunk: jax.Array, qpos: jax.Array) -> jax.Array:
        # q_chunk: (B, C, H, hd); qpos: (C,)
        # the scope marks the region whose device time the chip
        # benchmark's prefill_attn_roofline.serve divides by; a kernel
        # that replaces this code keeps the name
        with jax.named_scope("vmemkernel_flash_attention"):
            # bf16 inputs, f32 accumulation (MXU-native); the cotangents
            # stay bf16
            s = jnp.einsum("bqhd,bkhd->bhqk", q_chunk, k,
                           preferred_element_type=jnp.float32) * scale
            mask = qpos[:, None] >= kpos[None, :]
            if window is not None:
                mask &= qpos[:, None] - kpos[None, :] < window
            s = jnp.where(mask[None, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32
                              ).astype(q.dtype)

    if sq <= chunk:
        return attend(q, q_offset + jnp.arange(sq))

    n_chunks = (sq + chunk - 1) // chunk
    pad = n_chunks * chunk - sq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qp = qp.reshape(b, n_chunks, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    pos = (q_offset + jnp.arange(n_chunks * chunk)).reshape(n_chunks, chunk)
    out = jax.lax.map(lambda args: attend(*args), (qp, pos))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, n_chunks * chunk, h, hd_v)
    return out[:, :sq]


def decode_attention_ref(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         cache_len: jax.Array,
                         window: Optional[int] = None) -> jax.Array:
    """Single-step GQA decode. q: (B,1,H,hd); caches: (B,Smax,Hkv,hd) —
    NOT repeated: query heads are grouped onto their shared KV head, so
    the cache is read once, never broadcast per query head.
    ``cache_len``: #valid entries incl. the new token."""
    b, _, h, hd = q.shape
    hkv = k_cache.shape[2]
    grp = h // hkv
    smax = k_cache.shape[1]
    scale = 1.0 / math.sqrt(hd)
    qg = q[:, 0].reshape(b, hkv, grp, hd)
    with jax.named_scope("vmemkernel_decode_attention"):
        s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                       preferred_element_type=jnp.float32) * scale
        kpos = jnp.arange(smax)
        mask = kpos[None, :] < cache_len[:, None]
        if window is not None:
            mask &= kpos[None, :] >= cache_len[:, None] - window
        s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype),
                         v_cache, preferred_element_type=jnp.float32)
        return out.reshape(b, 1, h, hd).astype(q.dtype)


def write_rows(cache: jax.Array, rows: jax.Array, slot: jax.Array,
               layer: Optional[jax.Array] = None):
    """A sequence cache with one new row per sequence, ``rows[b]`` at
    slot ``slot[b]``: in the stack of every layer's cache (L, B, C, ...)
    at ``layer``, or in one layer's (B, C, ...) when ``layer`` is None.
    Only the rows are written, so a stack carried through the layer scan
    is updated in place. Returns the cache and this layer's part of it."""
    at = (jnp.arange(rows.shape[0]), slot)
    if layer is None:
        cache = cache.at[at].set(rows)
        return cache, cache
    cache = cache.at[(layer, *at)].set(rows)
    return cache, cache[layer]


# ------------------------------------------------------------- MLP
def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    g = jnp.dot(x, w_gate)
    u = jnp.dot(x, w_up)
    return jnp.dot(jax.nn.silu(g) * u, w_down)


# ------------------------------------------------------------- init
def dense_init(key: jax.Array, shape: tuple, dtype, scale: float = 1.0):
    """N(0, scale^2 / fan_in); a stack of matrices (rank 3) is read as
    (stack, fan_in, fan_out)."""
    fan_in = shape[-2] if len(shape) > 1 else 1
    std = scale / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)
