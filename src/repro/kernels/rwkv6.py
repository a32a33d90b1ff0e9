"""Pallas TPU kernel for the RWKV6 (Finch) WKV recurrence.

    y_t = r_t · (S_{t-1} + (u ⊙ k_t) v_tᵀ)
    S_t = diag(w_t) S_{t-1} + k_t v_tᵀ

TPU adaptation: the recurrence is inherently sequential in t, so the
kernel processes the sequence in CHUNKS with the (hd × hd) state matrix
resident in VMEM scratch across the chunk-grid dimension — per-token HBM
round-trips of the state (the naive lowering) are eliminated; HBM traffic
is r/k/v/w in + y out, once. Inside a chunk, a fori_loop runs the
per-token update entirely in VMEM/VREGs. Grid = (batch·heads, n_chunks),
chunk dim minormost so scratch persists across chunks of one head.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, state_scr, *,
                chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    u = u_ref[0, 0]                                        # (hd,)

    def step(t, state):
        r = r_ref[0, t, :]                                 # (hd,)
        k = k_ref[0, t, :]
        v = v_ref[0, t, :]
        w = w_ref[0, t, :]
        kv = k[:, None] * v[None, :]                       # (hd, hd)
        y = jnp.sum(r[:, None] * (state + u[:, None] * kv), axis=0)
        y_ref[0, t, :] = y.astype(y_ref.dtype)
        return w[:, None] * state + kv

    state = jax.lax.fori_loop(0, chunk, step, state_scr[...])
    state_scr[...] = state


def wkv6_chunked(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                 u: jax.Array, *, chunk: int = 64,
                 interpret: bool = False) -> jax.Array:
    """r,k,v,w: (BH, S, hd) fp32; u: (BH, hd). Returns y (BH, S, hd)."""
    bh, s, hd = r.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    n_chunks = s // chunk

    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    seq_spec = pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0))
    # u as (BH, 1, hd): a (1, hd) block over (BH, hd) breaks the TPU
    # tiling rule (second-to-last block dim divisible by 8 or full)
    u_spec = pl.BlockSpec((1, 1, hd), lambda b, c: (b, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(bh, n_chunks),
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  u_spec],
        out_specs=seq_spec,
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u[:, None, :])
