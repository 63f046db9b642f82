"""Operations and least bytes of the two launch kinds of A.X-K1's share
(``configs/axk1-ep16-l6.json``), from shapes.

A STEP launch (one new token for each of ``sessions`` sessions) is
bound by bytes. Least bytes: every matrix it touches read once in
bfloat16 (of the routed experts only those some token of the launch is
routed to: each token picks ``num_experts_per_tok`` of ``router_experts``
uniformly, so of the ``experts_here`` held, ``E (1 - (1 - k/R)^sessions)``
are touched in expectation, a layer), the embedding rows of the new
tokens, the cache rows of the sessions' histories once (``context``
positions each, every layer), the new cache rows and the logits
written. Activations that a perfect schedule keeps on chip count
nothing.

A PREFILL launch (``tokens`` new tokens of one session on ``context``
cached positions before them) is bound by operations: two per
multiply-add of every matrix product a token goes through (of the
routed experts the ``k * E / R`` a token reaches here in expectation),
and of attention in its expanded form: scores and values over the
causal half of the new tokens plus the whole context before them, and
the expansion of those positions' latents to keys and values. Padding
to a launch shape and the masked half of the scores are work the chip
does and the count leaves out: the share can only read low.
"""

from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    m = cfg["model"]
    d, h = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    attn = (d * m["q_lora_rank"] + m["q_lora_rank"] * h * qk + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + h * m["v_head_dim"] * d)
    kv_b = m["kv_lora_rank"] * h * (m["qk_nope_head_dim"] + m["v_head_dim"])
    expert = 3 * d * m["moe_intermediate_size"]
    return {
        "m": m, "attn": attn, "kv_b": kv_b, "expert": expert, "dense_mlp": 3 * d * m["intermediate_size"],
        "shared": expert * m["n_shared_experts"], "router": d * m["router_experts"],
        "n_dense": m["first_k_dense_replace"], "n_moe": m["num_hidden_layers"] - m["first_k_dense_replace"],
        "cache_row": 2 * (m["kv_lora_rank"] + m["qk_rope_head_dim"]),
    }


def count_step(cfg: dict, sessions: float, context: float) -> dict:
    """One step launch of ``sessions`` sessions (their mean) whose
    histories hold ``context`` positions each (their mean)."""
    s = _sizes(cfg)
    m = s["m"]
    touched = m["experts_here"] * (1.0 - (1.0 - m["num_experts_per_tok"] / m["router_experts"]) ** sessions)
    layers = m["num_hidden_layers"]
    weights = (layers * (s["attn"] + s["kv_b"]) + s["n_dense"] * s["dense_mlp"]
               + s["n_moe"] * (s["router"] + s["shared"] + touched * s["expert"]) + m["hidden_size"] * m["vocab_size"])
    cache = sessions * layers * s["cache_row"] * (context + 1)
    io = sessions * (2 * m["hidden_size"] + 4 * m["vocab_size"])
    per_token = (layers * (s["attn"] + s["kv_b"]) + s["n_dense"] * s["dense_mlp"]
                 + s["n_moe"] * (s["router"] + s["shared"] + s["expert"] * m["num_experts_per_tok"] * m["experts_here"]
                                 / m["router_experts"]) + m["hidden_size"] * m["vocab_size"])
    attention = layers * m["num_attention_heads"] * context * 2 * (2 * m["kv_lora_rank"] + m["qk_rope_head_dim"])
    return {"bytes": 2 * weights + cache + io, "flops": sessions * (2 * per_token + attention),
            "flops_dtype": "bf16", "experts_touched": touched}


def count_prefill(cfg: dict, tokens: float, context: float = 0.0) -> dict:
    """One prefill launch of ``tokens`` new tokens after ``context``
    cached positions."""
    s = _sizes(cfg)
    m = s["m"]
    layers, h = m["num_hidden_layers"], m["num_attention_heads"]
    routed = s["expert"] * m["num_experts_per_tok"] * m["experts_here"] / m["router_experts"]
    per_token = layers * s["attn"] + s["n_dense"] * s["dense_mlp"] + s["n_moe"] * (s["router"] + s["shared"] + routed)
    keys = context + tokens
    pairs = tokens * context + tokens * (tokens + 1) / 2  # (query, key) pairs under the causal mask
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    attention = layers * (2 * h * pairs * (qk + m["v_head_dim"]) + 2 * keys * s["kv_b"])
    flops = 2 * tokens * per_token + attention + 2 * m["hidden_size"] * m["vocab_size"]
    weights = layers * (s["attn"] + s["kv_b"]) + s["n_dense"] * s["dense_mlp"] + s["n_moe"] * (
        s["router"] + s["shared"] + m["experts_here"] * s["expert"]) + m["hidden_size"] * m["vocab_size"]
    return {"flops": flops, "bytes": 2 * weights + layers * s["cache_row"] * keys, "flops_dtype": "bf16"}


def count(cfg: dict, rows: int) -> dict:
    """``step_roofline``'s form: a step launch of ``rows`` sessions at
    half a slot's history."""
    return count_step(cfg, rows, cfg["model"]["slot_len"] / 2)
