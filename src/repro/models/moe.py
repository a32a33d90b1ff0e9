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

Dispatch layouts (``repro.sharding.ctx.moe_groups()`` selects):
* sorted (1 group, the default): the (token, expert) pairs are sorted by
  held expert and run through grouped products (``jax.lax.ragged_dot``);
  pairs of experts held elsewhere sort last and are not computed. No
  token is dropped.
* group-local (n_groups = dp extent): GShard capacity buffers per group
  (§Perf iteration 6, measured worse under GSPMD; kept for a shard_map
  follow-up). Pairs beyond a group's capacity are dropped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..sharding.ctx import constrain
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
    g = constrain(jnp.dot(x, p["w_gate"]), "dp", "tp")
    u = constrain(jnp.dot(x, p["w_up"]), "dp", "tp")
    return constrain(jnp.dot(jax.nn.silu(g) * u, p["w_down"]), "dp", None)


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
    from ..sharding.ctx import moe_groups
    t = x.shape[0]
    gate, idx, aux = route(p, x, cfg)
    groups = moe_groups()
    if groups > 1 and t % groups == 0:
        out, sizes = _dispatch_grouped(p, x, gate, idx - first, cfg, groups)
    elif t > DISPATCH_CHUNK and t % DISPATCH_CHUNK == 0:
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


def _dispatch_grouped(p: dict, x: jax.Array, gate: jax.Array,
                      local: jax.Array, cfg: ArchConfig, groups: int):
    """Group-local dispatch (§Perf iteration 6): the token axis is split
    into ``groups`` contiguous slices aligned with the `data` sharding;
    each group has a private capacity slice of every held expert, so the
    dispatch scatter and combine gather touch only group-local rows."""
    t, d = x.shape
    k, held = cfg.experts_per_token, cfg.held
    tg = t // groups

    cap_g = max(1, int(cfg.capacity_factor * tg * k / cfg.n_experts))
    flat_e = local.reshape(groups, tg * k)                      # (G, Tg*k)
    here = (flat_e >= 0) & (flat_e < held)
    flat_e = jnp.where(here, flat_e, 0)
    onehot = jax.nn.one_hot(flat_e, held, dtype=jnp.int32) \
        * here[..., None]                                       # (G, Tg*k, E)
    sizes = jnp.sum(onehot, axis=(0, 1))
    pos = jnp.cumsum(onehot, axis=1) * onehot                   # rank+1
    pos = jnp.sum(pos, axis=-1) - 1                             # (G, Tg*k)
    valid = here & (pos < cap_g)
    pos_c = jnp.clip(pos, 0, cap_g - 1)

    x_rep = jnp.repeat(x.reshape(groups, tg, d), k, axis=1)     # (G, Tg*k, d)
    x_rep = constrain(x_rep * valid[..., None].astype(x.dtype),
                      "dp", None, None)
    # group-local scatter: each group writes only its own capacity slice
    buf = jnp.zeros((groups, held, cap_g, d), x.dtype)
    gidx = jnp.arange(groups)[:, None].repeat(tg * k, 1)        # (G, Tg*k)
    buf = buf.at[gidx, flat_e, pos_c].add(x_rep)
    # experts on tp, groups stay on dp END-TO-END (4-D einsums: merging
    # (G@dp, Cg) into one dim would force GSPMD to replicate); expert
    # weights are FSDP-sharded on their NON-contraction dim (rules.py) so
    # the matmuls gather weights over data instead of all-reducing
    # (E, G, Cg, f) partials
    buf = constrain(buf.transpose(1, 0, 2, 3), "tp", "dp", None, None)

    g_ = constrain(jnp.einsum("egcd,edf->egcf", buf, p["w_gate"]),
                   "tp", "dp", None, None)
    u_ = constrain(jnp.einsum("egcd,edf->egcf", buf, p["w_up"]),
                   "tp", "dp", None, None)
    h = jax.nn.silu(g_) * u_
    out_buf = constrain(jnp.einsum("egcf,efd->egcd", h, p["w_down"]),
                        "tp", "dp", None, None)
    out_buf = out_buf.transpose(1, 0, 2, 3)

    gathered = out_buf[gidx, flat_e, pos_c]                     # (G, Tg*k, d)
    gathered = gathered * (gate.reshape(groups, tg * k, 1)
                           .astype(x.dtype) * valid[..., None].astype(x.dtype))
    out = jnp.sum(gathered.reshape(groups, tg, k, d), axis=2)
    return constrain(out.reshape(t, d), "dp", None), sizes
