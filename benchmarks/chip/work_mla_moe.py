"""The work of the latent-attention MoE decoder (Moonlight-16B-A3B's
block), counted from shapes and from the engine's expert counts, as
``work.py`` counts the dense decoder's.

Sizes are the configuration file's keys as the program names them
(``d_model``, ``kv_lora_rank``, ...). A multiply-add counts as two
operations; bytes assume ``param_dtype``. Prefill expands the latent into
per-head keys and values; a decode step attends over the cached latent
with ``W_kvb`` absorbed into its query and output, so its attention reads
``kv_lora_rank + qk_rope_head_dim`` values a cached position. The held
experts' work is what the engine counted (token-expert pairs, and the
experts that had any token in a layer's call): their share of a
deployment's experts, not the whole model's.
"""

from __future__ import annotations

from work import DTYPE_BYTES, attended_pairs


def _qk(cfg: dict) -> int:
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def mla_projection_flops(cfg: dict) -> int:
    """One token's projections in one layer: W_q, W_kva, then W_kvb
    (prefill: expanding the token's own keys and values; decode: absorbed
    into the query and the output, per head: the same count), and W_o."""
    d, h, r = cfg["d_model"], cfg["n_heads"], cfg["kv_lora_rank"]
    nope, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    params = (d * h * _qk(cfg) + d * (r + cfg["qk_rope_head_dim"])
              + r * h * (nope + dv) + h * dv * d)
    return 2 * params


def mla_prefill_attention_work(cfg: dict, batch: int, seq_len: int
                               ) -> tuple[int, int]:
    """(FLOPs, bytes) of one layer's causal attention over whole prompts,
    keys and values expanded per head: scores over ``nope + rope`` and
    the weighted sum over ``v_head_dim`` for every causal pair; q, K, V
    read once and the output written."""
    h, qk, dv = cfg["n_heads"], _qk(cfg), cfg["v_head_dim"]
    pairs = batch * attended_pairs(seq_len, None)
    flops = 2 * h * (qk + dv) * pairs
    nbytes = DTYPE_BYTES[cfg["param_dtype"]] * batch * seq_len * h \
        * (2 * qk + 2 * dv)
    return flops, nbytes


def expert_work(cfg: dict, pairs: int, loads: int) -> tuple[int, int]:
    """(FLOPs, bytes) of the held experts' SwiGLU products: ``pairs``
    token-expert pairs, and the weights of ``loads`` (layer call, expert)
    pairs that had tokens, each read once."""
    per = 3 * cfg["d_model"] * cfg["d_ff"]
    return 2 * per * pairs, DTYPE_BYTES[cfg["param_dtype"]] * per * loads


def serve_batch_flops(cfg: dict, batch: int, prompt_len: int,
                      new_tokens: int, expert_pairs: int) -> int:
    """One batch through prefill and greedy decode, the held experts'
    ``expert_pairs`` (as counted) included: every layer's latent
    attention (prefill expanded, decode absorbed), the dense layers' and
    the shared experts' SwiGLU and the router on every position, and the
    head where a token is chosen."""
    d, h, r = cfg["d_model"], cfg["n_heads"], cfg["kv_lora_rank"]
    n_layers, n_dense = cfg["n_layers"], cfg["first_k_dense"]
    steps = new_tokens - 1
    positions = batch * (prompt_len + steps)
    # attention: expanded causal pairs in prefill; absorbed scores over
    # r + rope and values over r for every cached position in decode
    prefill_pairs = batch * attended_pairs(prompt_len, None)
    decode_pairs = batch * sum(prompt_len + j + 1 for j in range(steps))
    attn = (2 * h * (_qk(cfg) + cfg["v_head_dim"]) * prefill_pairs
            + 2 * h * (2 * r + cfg["qk_rope_head_dim"]) * decode_pairs)
    proj = mla_projection_flops(cfg) * positions
    ffn_dense = 2 * 3 * d * cfg["dense_d_ff"] * n_dense
    moe_token = 2 * d * cfg["n_experts"] \
        + 2 * 3 * d * cfg["n_shared_experts"] * cfg["d_ff"]
    per_position = ffn_dense + moe_token * (n_layers - n_dense)
    return (n_layers * (attn + proj) + per_position * positions
            + expert_work(cfg, expert_pairs, 0)[0]
            + 2 * d * cfg["vocab_size"] * batch * new_tokens)
