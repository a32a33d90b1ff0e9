"""Pallas TPU flash-decode kernel: one new token against a deep KV cache.

GQA-native: the query block is the GROUP of query heads sharing one KV
head — (grp, hd) lives in registers while the kernel streams the cache in
(block_s, hd) VMEM tiles with online softmax. HBM traffic = K + V read
once + (grp, hd) out; the XLA reference materializes (grp, S) scores and
(after GSPMD) broadcasts repeated KV in f32 (§Perf iteration 5b).

Grid = (B·Hkv, S/block_s), cache-block dim minormost so the (grp, hd)
accumulator persists in VMEM scratch across cache blocks. Invalid slots
(beyond ``cache_len``, e.g. unwritten ring-buffer entries) are masked via
a per-row length that is scalar-prefetched into SMEM: Mosaic accepts a
rank-1 VMEM block only when it spans the array or a multiple of 128, so
the lengths cannot be tiled ``(1,)`` per row.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, block_s: int, n_s_blocks: int):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                     # (grp, hd)
    k = k_ref[0].astype(jnp.float32)                     # (bs, hd)
    v = v_ref[0].astype(jnp.float32)                     # (bs, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = kpos < len_ref[pl.program_id(0)]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                               # (grp, bs)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(si == n_s_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 cache_len: jax.Array, *, block_s: int = 256,
                 interpret: bool = False) -> jax.Array:
    """q: (BHkv, grp, hd) grouped queries; caches: (BHkv, S, hd);
    cache_len: (BHkv,) int32 valid-slot counts. Returns (BHkv, grp, hd)."""
    bhkv, grp, hd = q.shape
    s = k_cache.shape[1]
    block_s = min(block_s, s)
    n_s = pl.cdiv(s, block_s)
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(_decode_kernel, scale=scale, block_s=block_s,
                               n_s_blocks=n_s)
    # index maps take the prefetched lengths as a trailing argument
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bhkv, n_s),
        in_specs=[
            pl.BlockSpec((1, grp, hd), lambda b, j, lens: (b, 0, 0)),
            pl.BlockSpec((1, block_s, hd), lambda b, j, lens: (b, j, 0)),
            pl.BlockSpec((1, block_s, hd), lambda b, j, lens: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, grp, hd), lambda b, j, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((grp, 1), jnp.float32),
            pltpu.VMEM((grp, 1), jnp.float32),
            pltpu.VMEM((grp, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bhkv, grp, hd), q.dtype),
        interpret=interpret,
    )(cache_len.astype(jnp.int32), q, k_cache, v_cache)
