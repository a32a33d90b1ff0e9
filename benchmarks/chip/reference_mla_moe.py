"""Plain reference of the benchmark's latent-attention MoE decoder
(DeepSeek-V3 block, as Moonlight-16B-A3B configures it), in float32.

It imports nothing of the program under test. It builds each layer's
weights from that layer's key as it goes, by the initialisation the
configuration file states, rounded to the stored ``param_dtype`` as the
program stores them, so that the weights of all layers never exist at
once in float32. Everything after that is float32 with ``highest``
matmul precision: no cache, no batching of requests, no sorting of
tokens, every expert a plain dense product over every token.

The model, per layer: pre-norm RMSNorm; multi-head latent attention
(queries ``x W_q``; a latent ``[c, k_pe] = x W_kva``, ``c`` RMS-normed,
keys and values ``c W_kvb`` per head, each head's key ``[k_nope, k_pe]``,
scores over ``sqrt(nope + rope)``) under a causal mask; then a dense
SwiGLU in the first ``first_k_dense`` layers, and after them a mixture
of experts: sigmoid scores of the router, the top ``experts_per_token``
selected by score plus a per-expert bias, their scores normalised and
scaled by ``routed_scale``, and ``n_shared_experts`` shared experts as
one SwiGLU. The head is untied.

Departures from the published model, each shared with the program:

* only the routed experts the configuration holds here (``experts_held``,
  experts 0 to held-1) are computed; the tokens routed to the others get
  nothing from them, as on one chip of the stated expert-parallel
  deployment;
* rotary positions rotate the first half of the roped part against the
  second, where DeepSeek interleaves the pairs: for random weights that
  only permutes the columns of ``W_q`` and ``W_kva``;
* the selection bias is drawn from the key (a trained model's balances
  the expert load).

``served_gaps`` and the float8 control are those of ``reference.py``;
``bf16`` besides rounds every product's operands to bfloat16 (float32
accumulation), and ``logits_at`` can give each position's routing margin:
the witness in ``control_mla.py`` reads both.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import reference
from reference import F32, Static, rms_norm, rope


def bf16_dot(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """Operands rounded to bfloat16, their products summed in float32."""
    bf16 = lambda x: x.astype(jnp.bfloat16).astype(F32)
    return jnp.einsum(spec, bf16(a), bf16(b), preferred_element_type=F32)


DOTS = {**reference.DOTS, "bf16": bf16_dot}


# ------------------------------------------------------------- weights
def _normal(key, shape, cfg, dtype=None):
    """N(0, 1/fan_in) drawn in float32 (a stack (E, in, out) by its in),
    rounded to ``dtype`` (``param_dtype`` unless given)."""
    w = jax.random.normal(key, shape, F32) * (1.0 / math.sqrt(shape[-2]))
    return w.astype(dtype or cfg["param_dtype"]).astype(F32)


def _swiglu_weights(key, d, f, cfg):
    ks = jax.random.split(key, 3)
    return {"w_gate": _normal(ks[0], (d, f), cfg),
            "w_up": _normal(ks[1], (d, f), cfg),
            "w_down": _normal(ks[2], (f, d), cfg)}


def layer_weights(cfg: dict, layer_key: jax.Array, dense: bool) -> dict:
    """One layer's weights from its key: the key splits into six; the
    first splits into W_q, W_kva, W_kvb and W_o; the third is the dense
    layer's SwiGLU (keys 2-4 its gate, up and down) or the expert layer's,
    which splits into seven: the router, the held experts' gate, up and
    down stacks, (an unused dense residual), the shared experts and the
    selection bias."""
    d, h, r = cfg["d_model"], cfg["n_heads"], cfg["kv_lora_rank"]
    nope, rope_d = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    ks = jax.random.split(layer_key, 6)
    ka = jax.random.split(ks[0], 4)
    w = {"ln1": jnp.ones((d,), F32), "ln2": jnp.ones((d,), F32),
         "attn": {"wq": _normal(ka[0], (d, h * (nope + rope_d)), cfg),
                  "wkv_a": _normal(ka[1], (d, r + rope_d), cfg),
                  "kv_norm": jnp.ones((r,), F32),
                  "wkv_b": _normal(ka[2], (r, h * (nope + cfg["v_head_dim"])),
                                   cfg),
                  "wo": _normal(ka[3], (h * cfg["v_head_dim"], d), cfg)}}
    if dense:
        f = cfg["dense_d_ff"]
        w["mlp"] = {"w_gate": _normal(ks[2], (d, f), cfg),
                    "w_up": _normal(ks[3], (d, f), cfg),
                    "w_down": _normal(ks[4], (f, d), cfg)}
        return w
    f, e, held = cfg["d_ff"], cfg["n_experts"], cfg["experts_held"]
    km = jax.random.split(ks[2], 7)
    w["moe"] = {
        "router": _normal(km[0], (d, e), cfg, F32),
        "w_gate": _normal(km[1], (held, d, f), cfg),
        "w_up": _normal(km[2], (held, d, f), cfg),
        "w_down": _normal(km[3], (held, f, d), cfg),
        "shared": _swiglu_weights(km[5], d, cfg["n_shared_experts"] * f, cfg),
        "router_bias": 0.1 * jax.random.normal(km[6], (e,), F32)}
    return w


def _top_keys(cfg: dict):
    key = jax.random.PRNGKey(cfg["weights"]["key"])
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    return k_embed, jax.random.split(k_layers, cfg["n_layers"]), k_head


def outer_weights(cfg: dict) -> dict:
    k_embed, _, k_head = _top_keys(cfg)
    d, v = cfg["d_model"], cfg["vocab_size"]
    return {"embed": _normal(k_embed, (v, d), cfg),
            "final_norm": jnp.ones((d,), F32),
            "lm_head": _normal(k_head, (d, v), cfg)}


# --------------------------------------------------------------- model
def attention(cfg, w, x, dot, q_chunk=512):
    """Causal latent attention over whole sequences x (B, S, d), keys and
    values expanded per head; queries in chunks so that the scores of a
    long sequence never exist at once. Where the chunk does not divide
    the length, the queries are padded to whole chunks and the pad's
    rows dropped; causality keeps the keys as they are."""
    b, s, _ = x.shape
    h, r = cfg["n_heads"], cfg["kv_lora_rank"]
    nope, rope_d, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"])
    pos = jnp.arange(s)
    q = dot("bsd,de->bse", x, w["wq"]).reshape(b, s, h, nope + rope_d)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos,
                                              cfg["rope_theta"])], -1)
    kv = dot("bsd,de->bse", x, w["wkv_a"])
    c = rms_norm(kv[..., :r], w["kv_norm"], cfg["norm_eps"])
    k_pe = rope(kv[:, :, None, r:], pos, cfg["rope_theta"])
    kv = dot("bsr,re->bse", c, w["wkv_b"]).reshape(b, s, h, nope + dv)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (b, s, h, rope_d))], -1)
    v = kv[..., nope:]

    def chunk(qp):
        qc, qpos = qp
        sc = dot("bqhe,bshe->bhqs", qc, k) / math.sqrt(nope + rope_d)
        sc = jnp.where(qpos[:, None] >= pos[None, :], sc, -jnp.inf)
        return dot("bhqs,bshv->bqhv", jax.nn.softmax(sc, axis=-1), v)

    c_len = min(q_chunk, s)
    n = -(-s // c_len)
    qp = jnp.pad(q, ((0, 0), (0, n * c_len - s), (0, 0), (0, 0)))
    out = jax.lax.map(jax.checkpoint(chunk), (
        qp.reshape(b, n, c_len, h, -1).transpose(1, 0, 2, 3, 4),
        jnp.arange(n * c_len).reshape(n, c_len)))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, n * c_len, h * dv)[:, :s]
    return dot("bse,ed->bsd", out, w["wo"])


def swiglu(w, x, dot):
    g = dot("bsd,df->bsf", x, w["w_gate"])
    u = dot("bsd,df->bsf", x, w["w_up"])
    return dot("bsf,fd->bsd", jax.nn.silu(g) * u, w["w_down"])


def moe(cfg, w, x, dot):
    """The held routed experts' part plus the shared experts', x (B, S,
    d), and each token's routing margin, (B, S): by how much its k-th
    selected expert's biased score tops the next expert's. Each held
    expert runs over every token; a token's weight for it is nought
    unless the expert is among its selected ones."""
    k, held = cfg["experts_per_token"], cfg["experts_held"]
    scores = jax.nn.sigmoid(dot("bsd,de->bse", x, w["router"]))
    ranked, idx = jax.lax.top_k(scores + w["router_bias"], k + 1)
    idx = idx[..., :k]
    gate = jnp.take_along_axis(scores, idx, -1)
    gate = gate / jnp.sum(gate, -1, keepdims=True) * cfg["routed_scale"]
    # (B, S, held): the weight of each held expert for each token
    weight = jnp.sum(jax.nn.one_hot(idx, held, dtype=F32) * gate[..., None],
                     axis=-2)
    g = dot("bsd,edf->bsef", x, w["w_gate"])
    u = dot("bsd,edf->bsef", x, w["w_up"])
    y = dot("bsef,efd->bsed", jax.nn.silu(g) * u * weight[..., None],
            w["w_down"])
    return (jnp.sum(y, axis=2) + swiglu(w["shared"], x, dot),
            ranked[..., k - 1] - ranked[..., k])


def layer(cfg, w, x, dot, dense: bool):
    """The layer's output and its tokens' routing margins (a dense
    layer's are infinite)."""
    x = x + attention(cfg, w["attn"], rms_norm(x, w["ln1"], cfg["norm_eps"]),
                      dot)
    n = rms_norm(x, w["ln2"], cfg["norm_eps"])
    if dense:
        return x + swiglu(w["mlp"], n, dot), jnp.full(x.shape[:2], jnp.inf)
    y, margin = moe(cfg, w["moe"], n, dot)
    return x + y, margin


# ------------------------------------------------------------- serving
@partial(jax.jit, static_argnames=("cfg", "dot_name", "dense"))
def _serve_layer(cfg, dot_name, dense, layer_key, x):
    return layer(cfg, layer_weights(cfg, layer_key, dense), x,
                 DOTS[dot_name], dense)


@partial(jax.jit, static_argnames=("cfg",))
def _embed(cfg, tokens):
    return outer_weights(cfg)["embed"][tokens]


@partial(jax.jit, static_argnames=("cfg", "dot_name"))
def _head(cfg, dot_name, x):
    w = outer_weights(cfg)
    x = rms_norm(x, w["final_norm"], cfg["norm_eps"])
    return DOTS[dot_name]("bsd,dv->bsv", x, w["lm_head"])


def logits_at(cfg: dict, tokens: np.ndarray, first: int,
              dot_name: str = "f32", margins: bool = False):
    """Logits that predict positions ``first + 1 ..`` of ``tokens``
    (B, S): the full forward over every position, layer by layer with
    each layer's weights made on the device, the head applied to
    positions ``first .. S-1``. With ``margins``, also each of those
    positions' least routing margin over the expert layers."""
    static = Static(cfg)
    _, layer_keys, _ = _top_keys(cfg)
    with jax.default_matmul_precision("highest"):
        x = _embed(static, jnp.asarray(tokens))
        least = jnp.full(x.shape[:2], jnp.inf)
        for i in range(cfg["n_layers"]):
            x, margin = _serve_layer(static, dot_name,
                                     i < cfg["first_k_dense"],
                                     layer_keys[i], x)
            least = jnp.minimum(least, margin)
        logits = np.asarray(_head(static, dot_name, x[:, first:]))
        return (logits, np.asarray(least[:, first:])) if margins else logits


def served_gaps(cfg: dict, prompts: np.ndarray, served: np.ndarray,
                control: str | None = None) -> np.ndarray:
    """By how much each served token's logit lies below the reference's
    best at its position, (B, N). With ``control`` (a name in ``DOTS``),
    the gap of the token that the control's arithmetic puts first
    instead, read on the same prompts and served tokens."""
    tokens = np.concatenate([prompts, served[:, :-1]], axis=1)
    first = prompts.shape[1] - 1
    ref = logits_at(cfg, tokens, first)
    chosen = served
    if control is not None:
        chosen = logits_at(cfg, tokens, first, control).argmax(-1)
    picked = np.take_along_axis(ref, chosen[..., None], -1)[..., 0]
    return ref.max(-1) - picked
