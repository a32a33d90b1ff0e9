"""Plain reference of the benchmark's dense decoder, in float32.

It imports nothing of the program under test. It builds the weights
itself from the configuration's key, by the initialisation the
configuration file states, and rounds them to the stored ``param_dtype``
as the program stores them. Everything after that is float32 with
``highest`` matmul precision: no KV cache, no batching of requests, no
kernels. The model is the configuration's: pre-norm RMSNorm, rotary
positions on the two halves of each head, grouped-query causal attention
under a sliding window, SwiGLU, an untied output head.

``dot`` selects the arithmetic of every matrix product. ``f32_dot`` is
the reference; ``fp8_dot`` is the control, the next precision below the
configuration's bfloat16: both operands scaled per tensor into float8
e4m3 and multiplied with float32 accumulation.
"""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def f32_dot(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a, b, preferred_element_type=F32)


def _to_fp8(x: jax.Array) -> jax.Array:
    """x rounded to float8 e4m3 under a per-tensor scale. The gradient
    passes straight through, as in float8 training, where the backward
    pass multiplies the rounded operands in higher precision."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    rounded = (x / scale).astype(FP8).astype(F32) * scale
    return x + jax.lax.stop_gradient(rounded - x)


def fp8_dot(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, _to_fp8(a), _to_fp8(b), preferred_element_type=F32)


DOTS = {"f32": f32_dot, "fp8": fp8_dot}

# leaves the configuration stores in float32 whatever its param_dtype
FLOAT32_LEAVES = ("ln1", "ln2", "final_norm")


class Static(dict):
    """A configuration passed to ``jax.jit`` as a static argument."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]


# ------------------------------------------------------------- weights
def _normal(key, shape, cfg):
    """N(0, 1/fan_in) drawn in float32, rounded to ``param_dtype``."""
    w = jax.random.normal(key, shape, F32) * (1.0 / math.sqrt(shape[0]))
    return w.astype(cfg["param_dtype"]).astype(F32)


def layer_weights(cfg: dict, layer_key: jax.Array) -> dict:
    """One layer's weights from its key: the key splits into six, the
    first of which splits into the four attention projections; keys 2-4
    are the MLP's gate, up and down projections."""
    d, f, hd = cfg["d_model"], cfg["d_ff"], head_dim(cfg)
    h, hkv = cfg["n_heads"], cfg["n_kv_heads"]
    ks = jax.random.split(layer_key, 6)
    ka = jax.random.split(ks[0], 4)
    return {
        "ln1": jnp.ones((d,), F32), "ln2": jnp.ones((d,), F32),
        "attn": {"wq": _normal(ka[0], (d, h * hd), cfg),
                 "wk": _normal(ka[1], (d, hkv * hd), cfg),
                 "wv": _normal(ka[2], (d, hkv * hd), cfg),
                 "wo": _normal(ka[3], (h * hd, d), cfg)},
        "mlp": {"w_gate": _normal(ks[2], (d, f), cfg),
                "w_up": _normal(ks[3], (d, f), cfg),
                "w_down": _normal(ks[4], (f, d), cfg)},
    }


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


def all_weights(cfg: dict) -> dict:
    """Every weight at once, layers stacked on a leading axis (the layout
    the configuration states, in which the optimizer treats each stacked
    array as one tensor)."""
    _, layer_keys, _ = _top_keys(cfg)
    layers = [layer_weights(cfg, layer_keys[i]) for i in range(cfg["n_layers"])]
    w = outer_weights(cfg)
    w["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return w


# --------------------------------------------------------------- model
def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x: (B, S, H, hd); rotate the first half of each head against the
    second by position times 1 / theta^(2i/hd)."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos[:, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(cfg, q, k, v, qpos, kpos, dot):
    """q: (B, Sq, H, hd) at positions ``qpos``; k, v: (B, Sk, Hkv, hd) at
    ``kpos``. Query head j reads KV head j // (H / Hkv)."""
    b, sq, h, hd = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, sq, hkv, h // hkv, hd)
    s = dot("bqkgd,bskd->bkgqs", qg, k) / math.sqrt(hd)
    rel = qpos[:, None] - kpos[None, :]
    mask = rel >= 0
    if cfg.get("sliding_window") is not None:
        mask &= rel < cfg["sliding_window"]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = dot("bkgqs,bskd->bqkgd", p, v)
    return out.reshape(b, sq, h * hd)


def layer(cfg, lw, x, dot, q_chunk=512):
    """One decoder layer over whole sequences, queries in chunks so that
    the scores of a long sequence never exist at once."""
    b, s, _ = x.shape
    hd, h, hkv = head_dim(cfg), cfg["n_heads"], cfg["n_kv_heads"]
    pos = jnp.arange(s)
    a = lw["attn"]
    n = rms_norm(x, lw["ln1"], cfg["norm_eps"])
    q = rope(dot("bsd,de->bse", n, a["wq"]).reshape(b, s, h, hd), pos,
             cfg["rope_theta"])
    k = rope(dot("bsd,de->bse", n, a["wk"]).reshape(b, s, hkv, hd), pos,
             cfg["rope_theta"])
    v = dot("bsd,de->bse", n, a["wv"]).reshape(b, s, hkv, hd)
    c = min(q_chunk, s)
    if s % c:
        c = s
    qc = q.reshape(b, s // c, c, h, hd).transpose(1, 0, 2, 3, 4)
    pc = pos.reshape(s // c, c)
    att = jax.lax.map(
        jax.checkpoint(lambda qp: attention(cfg, qp[0], k, v, qp[1], pos, dot)),
        (qc, pc))
    att = att.transpose(1, 0, 2, 3).reshape(b, s, h * hd)
    x = x + dot("bse,ed->bsd", att, a["wo"])
    m = lw["mlp"]
    n = rms_norm(x, lw["ln2"], cfg["norm_eps"])
    g = dot("bsd,df->bsf", n, m["w_gate"])
    u = dot("bsd,df->bsf", n, m["w_up"])
    return x + dot("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_down"])


# ------------------------------------------------------------- serving
@partial(jax.jit, static_argnames=("cfg", "dot_name"))
def _serve_layer(cfg, dot_name, layer_key, x):
    return layer(cfg, layer_weights(cfg, layer_key), x, DOTS[dot_name])


@partial(jax.jit, static_argnames=("cfg",))
def _embed(cfg, tokens):
    return outer_weights(cfg)["embed"][tokens]


@partial(jax.jit, static_argnames=("cfg", "dot_name"))
def _head(cfg, dot_name, x):
    w = outer_weights(cfg)
    x = rms_norm(x, w["final_norm"], cfg["norm_eps"])
    return DOTS[dot_name]("bsd,dv->bsv", x, w["lm_head"])


def logits_at(cfg: dict, tokens: np.ndarray, first: int,
              dot_name: str = "f32") -> np.ndarray:
    """Logits that predict positions ``first + 1 ..`` of ``tokens``
    (B, S): the full forward over every position, layer by layer with
    each layer's weights made on the device, the head applied to
    positions ``first .. S-1``."""
    static = Static(cfg)
    _, layer_keys, _ = _top_keys(cfg)
    with jax.default_matmul_precision("highest"):
        x = _embed(static, jnp.asarray(tokens))
        for i in range(cfg["n_layers"]):
            x = _serve_layer(static, dot_name, layer_keys[i], x)
        return np.asarray(_head(static, dot_name, x[:, first:]))


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


# ------------------------------------------------------------ training
def synth_batch(cfg: dict, data: dict, batch: int, seq_len: int,
                step: int) -> dict:
    """The training rows the configuration's data stream defines: each
    row repeats a random n-gram, with a share of tokens replaced by
    uniform noise; the generator is keyed by (seed, step)."""
    rng = np.random.default_rng(
        np.uint64(data["seed"] * 1_000_003 + step * 7919))
    v = cfg["vocab_size"]
    base = rng.integers(0, v, size=(batch, data["ngram"]), dtype=np.int64)
    reps = int(np.ceil((seq_len + 1) / data["ngram"]))
    seq = np.tile(base, (1, reps))[:, : seq_len + 1]
    noise = rng.random((batch, seq_len + 1)) < data["noise"]
    seq = np.where(noise, rng.integers(0, v, size=(batch, seq_len + 1)), seq)
    return {"tokens": seq[:, :-1].astype(np.int32),
            "labels": seq[:, 1:].astype(np.int32)}


def _row_loss_sum(cfg, dot, params, tokens, labels, loss_chunk=1024):
    x = params["embed"][tokens]

    def body(x, lw):
        return jax.checkpoint(lambda x, lw: layer(cfg, lw, x, dot))(x, lw), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg["norm_eps"])
    b, s, d = x.shape
    c = min(loss_chunk, s)
    xc = x.reshape(b, s // c, c, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, s // c, c).transpose(1, 0, 2)

    def ce(tot, xl):
        logits = dot("bcd,dv->bcv", xl[0], params["lm_head"])
        gold = jnp.take_along_axis(logits, xl[1][..., None], -1)[..., 0]
        return tot + jnp.sum(jax.nn.logsumexp(logits, -1) - gold), None

    tot, _ = jax.lax.scan(jax.checkpoint(ce), jnp.zeros((), F32), (xc, lc))
    return tot


@partial(jax.jit, static_argnames=("cfg", "dot_name"),
         donate_argnames=("acc",))
def _accumulate(cfg, dot_name, acc, params, tokens, labels):
    """``acc`` plus the summed loss of the rows given and its gradient."""
    loss, grads = jax.value_and_grad(
        partial(_row_loss_sum, cfg, DOTS[dot_name]))(params, tokens, labels)
    return jax.tree.map(jnp.add, acc, (loss, grads))


@partial(jax.jit, donate_argnames=("tree",))
def _scaled(tree, factor):
    return jax.tree.map(lambda x: x * factor, tree)


def _loss_and_grads(cfg, dot_name, rows, params, tokens, labels):
    """Mean next-token cross-entropy over the whole batch and its
    gradient, summed one block of ``rows`` rows at a time, so that only
    one block's activations and gradient exist at once."""
    acc = (jnp.zeros((), F32), jax.tree.map(jnp.zeros_like, params))
    for i in range(0, len(tokens), rows):
        acc = _accumulate(cfg, dot_name, acc, params,
                          jnp.asarray(tokens[i:i + rows]),
                          jnp.asarray(labels[i:i + rows]))
    return _scaled(acc, 1.0 / tokens.size)


def lr_at(opt: dict, steps: int, step: int) -> float:
    """Linear warm-up, then cosine to a tenth, over the run's length."""
    warm_steps = min(opt["warmup_cap"], max(2, steps // 10))
    total = max(steps, opt["min_total_steps"])
    warm = min(1.0, (step + 1) / warm_steps)
    t = min(max((step - warm_steps) / max(1, total - warm_steps), 0.0), 1.0)
    return opt["lr"] * warm * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * t)))


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("params", "m", "v"))
def _adamw(cfg, params, m, v, grads, lr, step):
    """Clip by the global norm, then AdamW with bias correction; decay
    applies to every stored array of rank two or more; parameters are
    stored back in ``param_dtype``, the norms' weights in float32."""
    opt = cfg["adamw"]
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / (norm + 1e-12))
    b1, b2 = opt["b1"], opt["b2"]
    bc1, bc2 = 1 - b1 ** (step + 1.0), 1 - b2 ** (step + 1.0)

    def one(path, g, m, v, p):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        upd = (m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
        if p.ndim >= 2:
            upd = upd + opt["weight_decay"] * p
        p = p - lr * upd
        if path[-1].key not in FLOAT32_LEAVES:
            p = p.astype(cfg["param_dtype"]).astype(F32)
        return p, m, v

    out = jax.tree_util.tree_map_with_path(one, grads, m, v, params)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


def leaf_norms(tree) -> dict[str, float]:
    """L2 norm of each leaf, keyed by its path joined with '/'."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path): float(jnp.sqrt(jnp.sum(
                         jnp.square(jnp.asarray(x, F32)))))
            for path, x in flat}


def train_reference(cfg: dict, batches: list[dict], run_steps: int,
                    dot_name: str = "f32", rows: int = 1) -> dict:
    """Follow the configuration's training from its initial weights over
    ``batches`` (one per step): each step's loss, each leaf's norm of the
    first step's gradient before clipping, and each leaf's norm of the
    parameters' change over all the steps. ``run_steps`` is the length
    of the run being followed, which sets the learning-rate schedule."""
    static = Static(cfg)
    rows = min(rows, len(batches[0]["tokens"]))
    with jax.default_matmul_precision("highest"):
        params = all_weights(cfg)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, first_grads = [], None
        for step, batch in enumerate(batches):
            loss, grads = _loss_and_grads(static, dot_name, rows, params,
                                          batch["tokens"], batch["labels"])
            losses.append(float(loss))
            if first_grads is None:
                first_grads = leaf_norms(grads)
            params, m, v = _adamw(static, params, m, v, grads,
                                  lr_at(cfg["adamw"], run_steps, step),
                                  step)
            del grads
        del m, v
        # the initial weights are made again rather than kept, so the
        # reference holds one copy of the parameters at a time
        change = leaf_norms(jax.tree.map(jnp.subtract, params,
                                         all_weights(cfg)))
    return {"losses": losses, "first_grad_norms": first_grads,
            "change_norms": change}
