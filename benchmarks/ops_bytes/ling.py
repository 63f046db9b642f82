"""Operations and least bytes of the two launch kinds of Ling-3.0-flash's
share (``configs/ling3flash-ep8-l13.json``), from shapes: a model whose
layers are of two kinds (``model.layer_types``), Kimi Delta Attention
(KDA) layers whose per-session state does not grow with the context and
latent-attention (MLA) layers whose cache does. ``ops_bytes/axk1.py``
says what a step and a prefill launch count; what is here is what the
two kinds of layer change.

  * a KDA layer's attention: the five projections a token goes through
    (``qkv``, ``f``, ``o`` and the two head-wise gates) and its CORE, the
    delta rule over a head's state (:func:`count_kda_chunk`). An MLA
    layer's: ``q``, ``kv_a``, the head-wise gate, ``o``, and attention
    over the causal pairs at that context, with the expansion of the
    positions read, as ``ops_bytes/axk1.py`` counts them;
  * a step's least bytes hold every session's STATE read AND written (a
    layer's state is read whole by the next token: 2.1 MB a session a
    layer at the published widths) beside the latent rows of its history
    in the MLA layers alone; of the routed experts only those some token
    of the launch is routed to, as ``ops_bytes/axk1.py`` has it (the
    program's step launch reads every held expert: its share reads low
    for that, and says so).

Padding to a launch shape, the masked half of the scores, the solve
inside a chunk and the products of a chunk that lie over the diagonal
are work the chip does and the count leaves out: a share can only read
low.
"""

from __future__ import annotations

CHUNK = 64  # positions the chunkwise form takes at a time: the pairs inside a chunk are counted at this length


def _sizes(cfg: dict) -> dict:
    m = cfg["model"]
    d, h, hd = m["hidden_size"], m["num_attention_heads"], m["head_dim"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    expert = 3 * d * m["moe_intermediate_size"]
    return {
        "m": m, "kda": m["layer_types"].count("kda"), "mla": m["layer_types"].count("mla"),
        "kda_attn": 5 * d * h * hd + 2 * d * h,  # qkv (three), f, o; b, g
        "mla_attn": d * h * qk + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) + d * h + h * m["v_head_dim"] * d,
        "kv_b": m["kv_lora_rank"] * h * (m["qk_nope_head_dim"] + m["v_head_dim"]),
        "expert": expert, "dense_mlp": 3 * d * m["intermediate_size"], "shared": expert * m["n_shared_experts"],
        "router": d * m["router_experts"], "n_dense": m["first_k_dense_replace"],
        "n_moe": m["num_hidden_layers"] - m["first_k_dense_replace"],
        "cache_row": 2 * (m["kv_lora_rank"] + m["qk_rope_head_dim"]),
        "state": 4 * h * hd * hd + 2 * 3 * 3 * h * hd,  # a session's state and convolution tail, one KDA layer
    }


def count_kda_chunk(cfg: dict, tokens: float) -> dict:
    """The core of ONE KDA layer over ``tokens`` new tokens of one
    session, chunkwise: a token a head, the state read for its key and
    its query and moved by its corrected value (``6 d^2``), and its pairs
    with the tokens before it in its chunk, for the key and the query
    (scores) and the corrected values (``3 d`` a pair, ``CHUNK / 2`` pairs
    a token). Least bytes: ``q``, ``k``, ``v`` in and ``o`` out in
    bfloat16, the log decay in float32, the state read and written."""
    m = cfg["model"]
    h, d = m["num_attention_heads"], m["head_dim"]
    return {"flops": 2 * tokens * h * (3 * d * d + 3 * d * CHUNK / 2),
            "bytes": tokens * h * d * (4 * 2 + 4) + 2 * 4 * h * d * d, "flops_dtype": "bf16"}


def _per_token(s: dict, routed: float) -> float:
    """Parameters a token's matrix products go through, the head apart."""
    return (s["kda"] * s["kda_attn"] + s["mla"] * s["mla_attn"] + s["n_dense"] * s["dense_mlp"]
            + s["n_moe"] * (s["router"] + s["shared"] + routed))


def count_prefill(cfg: dict, tokens: float, context: float = 0.0) -> dict:
    """One prefill launch of ``tokens`` new tokens after ``context``
    cached positions: the matrix products, the MLA layers' pairs at that
    context, the KDA layers' cores (which do not know the context)."""
    s = _sizes(cfg)
    m = s["m"]
    routed = s["expert"] * m["num_experts_per_tok"] * m["experts_here"] / m["router_experts"]
    keys = context + tokens
    pairs = tokens * context + tokens * (tokens + 1) / 2
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    attention = s["mla"] * (2 * m["num_attention_heads"] * pairs * (qk + m["v_head_dim"]) + 2 * keys * s["kv_b"])
    core = count_kda_chunk(cfg, tokens)
    weights = (s["kda"] * s["kda_attn"] + s["mla"] * (s["mla_attn"] + s["kv_b"]) + s["n_dense"] * s["dense_mlp"]
               + s["n_moe"] * (s["router"] + s["shared"] + m["experts_here"] * s["expert"]) + m["hidden_size"] * m["vocab_size"])
    return {"flops": 2 * tokens * _per_token(s, routed) + attention + s["kda"] * core["flops"] + 2 * m["hidden_size"] * m["vocab_size"],
            "bytes": 2 * weights + s["mla"] * s["cache_row"] * keys + s["kda"] * 2 * s["state"], "flops_dtype": "bf16"}


def count_step(cfg: dict, sessions: float, context: float) -> dict:
    """One step launch of ``sessions`` sessions whose histories hold
    ``context`` positions each: the weights touched once, every
    session's state read and written in the KDA layers, its history's
    rows in the MLA layers, the new rows and the logits."""
    s = _sizes(cfg)
    m = s["m"]
    h, hd = m["num_attention_heads"], m["head_dim"]
    touched = m["experts_here"] * (1.0 - (1.0 - m["num_experts_per_tok"] / m["router_experts"]) ** sessions)
    weights = (s["kda"] * s["kda_attn"] + s["mla"] * (s["mla_attn"] + s["kv_b"]) + s["n_dense"] * s["dense_mlp"]
               + s["n_moe"] * (s["router"] + s["shared"] + touched * s["expert"]) + m["hidden_size"] * m["vocab_size"])
    state = sessions * (s["kda"] * 2 * s["state"] + s["mla"] * s["cache_row"] * (context + 1))
    io = sessions * (2 * m["hidden_size"] + 4 * m["vocab_size"])
    routed = s["expert"] * m["num_experts_per_tok"] * m["experts_here"] / m["router_experts"]
    attention = s["mla"] * (h * context * 2 * (2 * m["kv_lora_rank"] + m["qk_rope_head_dim"]) + 2 * s["kv_b"])
    core = s["kda"] * 2 * h * 3 * hd * hd
    return {"bytes": 2 * weights + state + io,
            "flops": sessions * (2 * (_per_token(s, routed) + m["hidden_size"] * m["vocab_size"]) + attention + core),
            "flops_dtype": "bf16", "experts_touched": touched}


def count(cfg: dict, rows: int) -> dict:
    """``step_roofline``'s form: a step launch of ``rows`` sessions at half a slot's history."""
    return count_step(cfg, rows, cfg["model"]["slot_len"] / 2)
