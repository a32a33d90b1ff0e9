"""The work each measured call needs, counted from shapes.

These count what the algorithm requires, not what a given implementation
does: a causal mask halves the attention pairs whether or not the code
skips the masked half, and a decode step reads only the valid cache
entries. So a faster kernel put in the same place is read against the
same work, and a share of a peak above 100% means that the measured time
left out part of the work.

A multiply-add counts as two operations. Sizes are in the units of the
configuration file (``d_model``, ``n_heads``, ...); byte counts assume
the configuration's ``param_dtype`` for activations, weights and the
KV cache.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one dense layer."""
    d, f, hd = cfg["d_model"], cfg["d_ff"], head_dim(cfg)
    attn = d * cfg["n_heads"] * hd * 2 + d * cfg["n_kv_heads"] * hd * 2
    return attn + 3 * d * f


def attended_pairs(seq_len: int, window: int | None) -> int:
    """(query, key) pairs of one causal sequence under a sliding window
    of ``window`` keys (the query's own position included)."""
    w = seq_len if window is None else min(window, seq_len)
    # the first w queries see 1..w keys, every later one sees w
    return w * (w + 1) // 2 + (seq_len - w) * w


def attention_flops(cfg: dict, pairs: int) -> int:
    """Scores and the weighted sum of values, over every head."""
    return 4 * cfg["n_heads"] * head_dim(cfg) * pairs


def forward_flops(cfg: dict, layer_tokens: int, pairs: int,
                  head_tokens: int) -> int:
    """Forward FLOPs for ``layer_tokens`` token positions through every
    layer, ``pairs`` attended pairs per layer in all, and the output head
    on ``head_tokens`` positions. The embedding is a gather: no FLOPs."""
    n_layers = cfg["n_layers"]
    return (2 * layer_matmul_params(cfg) * n_layers * layer_tokens
            + attention_flops(cfg, pairs) * n_layers
            + 2 * cfg["d_model"] * cfg["vocab_size"] * head_tokens)


def serve_batch_flops(cfg: dict, batch: int, prompt_len: int,
                      new_tokens: int) -> int:
    """One batch through prefill and greedy decode: prefill runs every
    prompt position through the layers and the head on the last one;
    each of the ``new_tokens - 1`` decode steps runs one position (the
    last generated token is never fed back)."""
    window = cfg.get("sliding_window")
    steps = new_tokens - 1
    pairs = attended_pairs(prompt_len, window)
    for j in range(steps):
        ctx = prompt_len + j + 1
        pairs += ctx if window is None else min(ctx, window)
    return forward_flops(cfg, batch * (prompt_len + steps), batch * pairs,
                         batch * new_tokens)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward (three forwards' worth) per trained token.
    Recomputation under remat is not counted."""
    pairs = attended_pairs(seq_len, cfg.get("sliding_window"))
    return 3 * forward_flops(cfg, seq_len, pairs, seq_len) / seq_len


def prefill_attention_work(cfg: dict, batch: int, seq_len: int
                           ) -> tuple[int, int]:
    """(FLOPs, bytes) of one layer's causal, windowed attention over a
    whole prompt: read q, K and V once (K and V at their own head count),
    write the output; only unmasked pairs count."""
    hd, b = head_dim(cfg), DTYPE_BYTES[cfg["param_dtype"]]
    h, hkv = cfg["n_heads"], cfg["n_kv_heads"]
    pairs = attended_pairs(seq_len, cfg.get("sliding_window"))
    flops = attention_flops(cfg, batch * pairs)
    nbytes = b * batch * seq_len * (2 * h * hd + 2 * hkv * hd)
    return flops, nbytes


def least_time_s(flops: float, nbytes: float, peaks) -> tuple[float, str]:
    """The roofline: the larger of compute time and memory time at the
    chip's peaks, and which of the two bounds it."""
    t_flops = flops / peaks.bf16_flops_per_s
    t_bytes = nbytes / peaks.hbm_bytes_per_s
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
