"""Pallas TPU flash attention: blockwise causal attention with online
softmax, GQA (grouped KV indexing — KV never materialized per-q-head),
and optional sliding window.

TPU adaptation (not a CUDA port): the grid is (batch·q_heads, q_blocks,
k_blocks) iterated sequentially per core with VMEM-resident accumulators
(o_acc, running max m, denominator l) carried across the k_block
dimension — the Pallas/TPU analogue of a persistent-CTA flash kernel.
Block shapes are MXU-aligned (q/k blocks multiples of 128 where the
sequence allows; head_dim padded by the caller when < 128 is needed).
Scores and probabilities live only in VMEM: HBM traffic is Q+K+V+O.

Safety: k_blocks that are fully masked (causal/window) contribute nothing;
they are computed-and-masked rather than skipped, keeping the kernel
grid static (Pallas TPU requires a static grid).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, block_q: int, block_k: int, n_k_blocks: int,
               window, seq_q: int, seq_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                      # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                      # (bk, hd)
    v = v_ref[0].astype(jnp.float32)                      # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = (qpos >= kpos) & (qpos < seq_q) & (kpos < seq_k)
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                   # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                # (bq, bk)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_k_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        window=None, block_q: int = 128,
                        block_k: int = 128,
                        interpret: bool = False) -> jax.Array:
    """q: (BH, Sq, hd); k/v: (BHkv, Sk, hd) with BH % BHkv == 0 (GQA:
    the kernel indexes the shared KV head — no repeat in HBM)."""
    bh, sq, hd = q.shape
    bhkv, sk, _ = k.shape
    assert bh % bhkv == 0
    n_rep = bh // bhkv
    scale = 1.0 / math.sqrt(hd)

    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    n_q = pl.cdiv(sq, block_q)
    n_k = pl.cdiv(sk, block_k)

    kernel = functools.partial(
        _fa_kernel, scale=scale, block_q=block_q, block_k=block_k,
        n_k_blocks=n_k, window=window, seq_q=sq, seq_k=sk)

    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b // n_rep, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b // n_rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # denominator l
            pltpu.VMEM((block_q, hd), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
