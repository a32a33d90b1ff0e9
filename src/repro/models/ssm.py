"""Attention-free sequence mixers.

* RWKV6 ("Finch"): token-shift + data-dependent per-channel decay, matrix
  WKV state (head_dim × head_dim per head) — O(1) state decode.
* Mamba-style selective SSM head for hymba's hybrid layers (parallel
  attention + SSM in the same layer), ssm_state=16.

Both expose a full-sequence path (lax.scan over time — the oracle for the
Pallas chunked kernel) and a single-step decode path over carried state.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .layers import dense_init, group_norm_heads

DECAY_LORA = 64
DT_RANK = 64
CONV_K = 4
TIME_CHUNK = 256


def chunked_time_scan(step_fn, state0, seq, chunk: int = TIME_CHUNK):
    """scan-over-time in rematerialized chunks: backward keeps only
    chunk-boundary states instead of one residual per token (32 states
    for a 4k+ sequence vs 4096). This mirrors the chunked Pallas kernels
    (kernels/rwkv6.py) and is what makes SSM training memory-feasible."""
    S = jax.tree.leaves(seq)[0].shape[0]
    if S <= chunk or S % chunk != 0:
        return jax.lax.scan(step_fn, state0, seq)
    n = S // chunk
    seq_c = jax.tree.map(lambda a: a.reshape(n, chunk, *a.shape[1:]), seq)

    @partial(jax.checkpoint, prevent_cse=False)
    def chunk_body(state, chunk_seq):
        return jax.lax.scan(step_fn, state, chunk_seq)

    final, ys = jax.lax.scan(chunk_body, state0, seq_c)
    ys = jax.tree.map(lambda a: a.reshape(S, *a.shape[2:]), ys)
    return final, ys


# ============================================================== RWKV6
def init_rwkv_tmix(key: jax.Array, cfg: ArchConfig, dtype) -> dict:
    d = cfg.d_model
    h = d // cfg.rwkv_head_dim
    hd = cfg.rwkv_head_dim
    ks = jax.random.split(key, 10)
    return {
        "mu": 0.5 * jnp.ones((5, d), jnp.float32),     # r,k,v,w,g shift mixes
        "w_r": dense_init(ks[0], (d, d), dtype),
        "w_k": dense_init(ks[1], (d, d), dtype),
        "w_v": dense_init(ks[2], (d, d), dtype),
        "w_g": dense_init(ks[3], (d, d), dtype),
        "w_o": dense_init(ks[4], (d, d), dtype),
        "w0": -6.0 * jnp.ones((d,), jnp.float32),      # decay bias
        "w_lora_a": dense_init(ks[5], (d, DECAY_LORA), jnp.float32),
        "w_lora_b": dense_init(ks[6], (DECAY_LORA, d), jnp.float32, 0.1),
        "bonus_u": dense_init(ks[7], (h, hd), jnp.float32),
        "ln_w": jnp.ones((hd,), jnp.float32),
        "ln_b": jnp.zeros((hd,), jnp.float32),
    }


def init_rwkv_cmix(key: jax.Array, cfg: ArchConfig, dtype) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "mu": 0.5 * jnp.ones((2, d), jnp.float32),     # k, r shift mixes
        "w_k": dense_init(ks[0], (d, f), dtype),
        "w_v": dense_init(ks[1], (f, d), dtype),
        "w_r": dense_init(ks[2], (d, d), dtype),
    }


def _shift(x: jax.Array, prev: jax.Array) -> jax.Array:
    """Token shift: y_t = x_{t-1}; y_0 = prev. x: (B,S,D), prev: (B,D)."""
    return jnp.concatenate([prev[:, None, :], x[:, :-1, :]], axis=1)


def rwkv_decay(p: dict, xw: jax.Array) -> jax.Array:
    """Data-dependent decay in (0,1): exp(-exp(w0 + lora(x)))."""
    w = p["w0"] + jnp.tanh(xw.astype(jnp.float32) @ p["w_lora_a"]) @ p["w_lora_b"]
    return jnp.exp(-jnp.exp(w))


def wkv_step(state, rkvw, u):
    """One WKV6 recurrence step.
    state: (B,H,hd,hd) [key-dim i, value-dim j]
    r,k,v,decay: (B,H,hd); u: (H,hd)
    """
    r, k, v, decay = rkvw
    kv = k[..., :, None] * v[..., None, :]               # (B,H,hd,hd)
    y = jnp.einsum("bhi,bhij->bhj", r, state + u[None, :, :, None] * kv)
    new_state = decay[..., :, None] * state + kv
    return new_state, y


def apply_rwkv_tmix(p: dict, x: jax.Array, cfg: ArchConfig,
                    state: dict | None = None) -> tuple[jax.Array, dict]:
    """x: (B,S,D). state: {"shift": (B,D), "wkv": (B,H,hd,hd)} or None."""
    b, s, d = x.shape
    hd = cfg.rwkv_head_dim
    h = d // hd
    prev = state["shift"] if state is not None else jnp.zeros((b, d), x.dtype)
    wkv0 = state["wkv"] if state is not None else \
        jnp.zeros((b, h, hd, hd), jnp.float32)

    xx = _shift(x, prev)
    mix = lambda i: x + (xx - x) * p["mu"][i].astype(x.dtype)
    proj = lambda i, w: mix(i) @ w
    r = proj(0, p["w_r"]).reshape(b, s, h, hd)
    k = proj(1, p["w_k"]).reshape(b, s, h, hd)
    v = proj(2, p["w_v"]).reshape(b, s, h, hd)
    g = proj(4, p["w_g"])
    decay = rwkv_decay(p, mix(3)).reshape(b, s, h, hd)   # fp32

    rkvw = (r.astype(jnp.float32).transpose(1, 0, 2, 3),
            k.astype(jnp.float32).transpose(1, 0, 2, 3),
            v.astype(jnp.float32).transpose(1, 0, 2, 3),
            decay.transpose(1, 0, 2, 3))
    # VMEM-resident on the TPU target (kernels/rwkv6.py chunked kernel)
    with jax.named_scope("vmemkernel_wkv6"):
        wkv_final, ys = chunked_time_scan(
            lambda st, rkvw_t: wkv_step(st, rkvw_t, p["bonus_u"]), wkv0, rkvw)
    y = ys.transpose(1, 0, 2, 3)                          # (B,S,H,hd)
    y = group_norm_heads(y, p["ln_w"], p["ln_b"]).reshape(b, s, d)
    out = (y * jax.nn.silu(g).astype(y.dtype)).astype(x.dtype) @ p["w_o"]
    new_state = {"shift": x[:, -1, :], "wkv": wkv_final}
    return out, new_state


def apply_rwkv_cmix(p: dict, x: jax.Array, cfg: ArchConfig,
                    state: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    b, s, d = x.shape
    prev = state if state is not None else jnp.zeros((b, d), x.dtype)
    xx = _shift(x, prev)
    mix = lambda i: x + (xx - x) * p["mu"][i].astype(x.dtype)
    k = jnp.square(jax.nn.relu(mix(0) @ p["w_k"]))
    v = k @ p["w_v"]
    r = jax.nn.sigmoid(mix(1) @ p["w_r"])
    return (r * v).astype(x.dtype), x[:, -1, :]


# ====================================================== Mamba (hymba)
def init_mamba(key: jax.Array, cfg: ArchConfig, dtype) -> dict:
    d = cfg.d_model
    di = cfg.n_heads * cfg.hd                # SSM heads mirror attn heads
    n = cfg.ssm_state
    ks = jax.random.split(key, 7)
    return {
        "in_proj": dense_init(ks[0], (d, 2 * di), dtype),
        "conv_w": dense_init(ks[1], (CONV_K, di), dtype),
        "conv_b": jnp.zeros((di,), dtype),
        "dt_a": dense_init(ks[2], (di, DT_RANK), dtype),
        "dt_b": dense_init(ks[3], (DT_RANK, di), dtype),
        "dt_bias": jnp.zeros((di,), jnp.float32),
        "w_bc": dense_init(ks[4], (di, 2 * n), dtype),
        "a_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, n + 1, dtype=jnp.float32), (di, n))),
        "d_skip": jnp.ones((di,), jnp.float32),
        "out_proj": dense_init(ks[5], (di, d), dtype),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 conv_state: jax.Array | None) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal conv1d. x: (B,S,di); w: (K,di)."""
    bsz, s, di = x.shape
    if conv_state is None:
        conv_state = jnp.zeros((bsz, CONV_K - 1, di), x.dtype)
    xp = jnp.concatenate([conv_state, x], axis=1)        # (B, S+K-1, di)
    out = sum(xp[:, i:i + s, :] * w[i] for i in range(CONV_K)) + b
    return out, xp[:, -(CONV_K - 1):, :]


def apply_mamba(p: dict, x: jax.Array, cfg: ArchConfig,
                state: dict | None = None) -> tuple[jax.Array, dict]:
    """Selective SSM. x: (B,S,D). state: {"conv": (B,K-1,di),
    "h": (B,di,n)}."""
    b, s, d = x.shape
    n = cfg.ssm_state
    xz = x @ p["in_proj"]
    x_in, z = jnp.split(xz, 2, axis=-1)                  # (B,S,di) each
    conv_state = state["conv"] if state is not None else None
    x_c, new_conv = _causal_conv(x_in, p["conv_w"], p["conv_b"], conv_state)
    x_c = jax.nn.silu(x_c)

    dt = jax.nn.softplus(
        (x_c @ p["dt_a"] @ p["dt_b"]).astype(jnp.float32) + p["dt_bias"])
    bc = x_c @ p["w_bc"]
    b_t, c_t = jnp.split(bc.astype(jnp.float32), 2, axis=-1)  # (B,S,n)
    a = -jnp.exp(p["a_log"])                              # (di,n)
    x_f = x_c.astype(jnp.float32)

    h0 = state["h"] if state is not None else jnp.zeros((b, x_in.shape[-1], n),
                                                        jnp.float32)

    def step(h, t):
        dt_t, b_tt, c_tt, x_t = t                        # (B,di),(B,n),(B,n),(B,di)
        da = jnp.exp(dt_t[..., None] * a[None])          # (B,di,n)
        h = da * h + (dt_t * x_t)[..., None] * b_tt[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, c_tt)
        return h, y

    seq = (dt.transpose(1, 0, 2), b_t.transpose(1, 0, 2),
           c_t.transpose(1, 0, 2), x_f.transpose(1, 0, 2))
    with jax.named_scope("vmemkernel_mamba_scan"):
        h_final, ys = chunked_time_scan(step, h0, seq)
    y = ys.transpose(1, 0, 2) + p["d_skip"] * x_f        # (B,S,di)
    out = (y.astype(x.dtype) * jax.nn.silu(z)) @ p["out_proj"]
    return out, {"conv": new_conv, "h": h_final}
