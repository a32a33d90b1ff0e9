"""Mixture-of-Experts layer.

The router scores every routed expert of the model (``n_experts``), picks
``experts_per_token`` per token and normalises their weights; with
``router_scoring="sigmoid"`` (DeepSeek-V3's ``noaux_tc``) the scores are
sigmoids and a per-expert bias is added to them to select, not to weight.
The weights are then scaled by ``routed_scale``.

The layer holds the weights of ``cfg.held`` routed experts, ``first`` to
``first + held - 1``, as one chip of an expert-parallel deployment does,
and computes their part of the result for the tokens routed to them; the
part of the experts held elsewhere is left to their chips. Shared experts
(one SwiGLU of ``n_shared_experts * d_ff``) and arctic's parallel dense
FFN see every token.

Dispatch: the (token, expert) pairs are sorted by held expert and run
through grouped products (``jax.lax.ragged_dot``); pairs of experts held
elsewhere sort last and are not computed. No token is dropped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .layers import dense_init

# Tokens dispatched at once: prefill runs its tokens through the sorted
# dispatch in chunks of this many, so that the sorted copies of a large
# batch (experts_per_token rows a token) never exist at once.
DISPATCH_CHUNK = 8192


def init_moe(key: jax.Array, cfg: ArchConfig, dtype) -> dict:
    d, f, e, held = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.held
    keys = jax.random.split(key, 7)
    p = {
        "router": dense_init(keys[0], (d, e), jnp.float32),  # fp32 routing
        "w_gate": dense_init(keys[1], (held, d, f), dtype),
        "w_up": dense_init(keys[2], (held, d, f), dtype),
        "w_down": dense_init(keys[3], (held, f, d), dtype),
    }
    if cfg.moe_dense_residual:
        p["dense"] = _init_swiglu(keys[4], d, f, dtype)
    if cfg.n_shared_experts:
        p["shared"] = _init_swiglu(keys[5], d, cfg.n_shared_experts * f, dtype)
    if cfg.router_scoring == "sigmoid":
        # a trained model's bias balances the load; drawn here so that
        # selection and weighting differ
        p["router_bias"] = 0.1 * jax.random.normal(keys[6], (e,), jnp.float32)
    return p


def _init_swiglu(key, d: int, f: int, dtype) -> dict:
    ks = jax.random.split(key, 3)
    return {"w_gate": dense_init(ks[0], (d, f), dtype),
            "w_up": dense_init(ks[1], (d, f), dtype),
            "w_down": dense_init(ks[2], (f, d), dtype)}


def _swiglu(p: dict, x: jax.Array) -> jax.Array:
    g = jnp.dot(x, p["w_gate"])
    u = jnp.dot(x, p["w_up"])
    return jnp.dot(jax.nn.silu(g) * u, p["w_down"])


def route(p: dict, x: jax.Array, cfg: ArchConfig):
    """(weights, expert ids), each (T, k), and the load-balancing loss."""
    k = cfg.experts_per_token
    with jax.named_scope("moe_route"):
        # float32 at full precision: near ties between the k-th and the
        # next expert's scores decide which experts a token reaches
        logits = jnp.dot(x.astype(jnp.float32), p["router"],
                         precision=jax.lax.Precision.HIGHEST)   # (T, E)
        if cfg.router_scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            _, idx = jax.lax.top_k(scores + p["router_bias"], k)
            gate = jnp.take_along_axis(scores, idx, axis=-1)
        else:
            scores = jax.nn.softmax(logits, axis=-1)
            gate, idx = jax.lax.top_k(scores, k)
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True) * cfg.routed_scale
        # Switch-style load balance over the normalised scores
        share = scores / jnp.sum(scores, axis=-1, keepdims=True)
        top1 = jnp.mean(jax.nn.one_hot(idx[:, 0], cfg.n_experts), axis=0)
        aux = cfg.n_experts * jnp.sum(jnp.mean(share, axis=0) * top1)
    return gate, idx, aux


def apply_moe(p: dict, x: jax.Array, cfg: ArchConfig, first: int = 0
              ) -> tuple[jax.Array, jax.Array, dict]:
    """x: (T, d) tokens (caller flattens batch×seq). Returns the held
    experts' part plus the shared and dense parts, the load-balancing
    loss, and counts: ``expert_tokens`` (held,), the tokens routed to each
    held expert, and ``expert_loads``, how many held experts had any."""
    t = x.shape[0]
    gate, idx, aux = route(p, x, cfg)
    if t > DISPATCH_CHUNK and t % DISPATCH_CHUNK == 0:
        n = t // DISPATCH_CHUNK
        out, sizes = jax.lax.map(
            lambda a: _dispatch_sorted(p, *a, cfg),
            (x.reshape(n, DISPATCH_CHUNK, -1),
             gate.reshape(n, DISPATCH_CHUNK, -1),
             (idx - first).reshape(n, DISPATCH_CHUNK, -1)))
        out, sizes = out.reshape(x.shape), jnp.sum(sizes, axis=0)
    else:
        out, sizes = _dispatch_sorted(p, x, gate, idx - first, cfg)
    if "shared" in p:
        out = out + _swiglu(p["shared"], x)
    if cfg.moe_dense_residual:
        out = out + _swiglu(p["dense"], x)
    counts = {"expert_tokens": sizes,
              "expert_loads": jnp.sum(sizes > 0).astype(jnp.int32)}
    return out, aux, counts


def _dispatch_sorted(p: dict, x: jax.Array, gate: jax.Array,
                     local: jax.Array, cfg: ArchConfig):
    """The held experts' part for tokens x (T, d), routed to held-local
    expert ids ``local`` (T, k) with weights ``gate``; and the tokens
    each held expert took."""
    t, d = x.shape
    k, held = cfg.experts_per_token, cfg.held
    with jax.named_scope("moe_dispatch"):
        flat = local.reshape(-1)                                # (T*k,)
        here = (flat >= 0) & (flat < held)
        group = jnp.where(here, flat, held)       # held elsewhere: last
        order = jnp.argsort(group, stable=True)
        sizes = jnp.sum(jax.nn.one_hot(group, held, dtype=jnp.int32),
                        axis=0)
        rows = x[order // k]                                    # (T*k, d)
        weight = gate.reshape(-1)[order][:, None]
    with jax.named_scope("moe_experts"):
        f32 = jnp.float32
        g = jax.lax.ragged_dot(rows, p["w_gate"], sizes,
                               preferred_element_type=f32)
        u = jax.lax.ragged_dot(rows, p["w_up"], sizes,
                               preferred_element_type=f32)
        h = (jax.nn.silu(g) * u * weight).astype(x.dtype)
        y = jax.lax.ragged_dot(h, p["w_down"], sizes,
                               preferred_element_type=f32)
    with jax.named_scope("moe_dispatch"):
        # rows past the held experts' groups are not computed
        y = jnp.where(here[order][:, None], y, 0.0)
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        out = jnp.sum(y[back].reshape(t, k, d), axis=1).astype(x.dtype)
    return out, sizes
