"""Operations and least bytes of the two launch kinds of SDAR's share
(``configs/sdar30b-ep8-l48.json``), from shapes.

A BLOCK launch (one block of ``block_length`` positions for each of
``sessions`` sessions) is bound by bytes. Least bytes: every matrix it
touches read once in bfloat16 (of the held experts only those some
position of the launch is routed to: each position picks
``num_experts_per_tok`` of ``router_experts`` uniformly, so of the
``experts_here`` held ``E (1 - (1 - k/R)^positions)`` are touched in
expectation, a layer), the embedding rows of the block's tokens, the
cached keys and values of the sessions' histories once (``context``
positions each, every layer), the rows the committing sessions write and
the logits written. Activations that a perfect schedule keeps on chip
count nothing; the program reads every slot of the cache whole, in use
or not, and the count leaves that out: the share can only read low.

A PREFILL launch (``tokens`` new tokens of one session on ``context``
cached positions) is bound by operations: two per multiply-add of every
matrix product a token goes through (of the held experts the ``k * E /
R`` a token reaches here in expectation) and of attention's scores and
values over the pairs the block mask lets through (a position reads up
to the end of its own block). Padding to a launch shape and masked
pairs are work the chip does and the count leaves out.
"""

from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    m = cfg["model"]
    d, h, g, hd = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    return {
        "m": m, "attn": d * (h + 2 * g) * hd + h * hd * d, "router": d * m["router_experts"],
        "expert": 3 * d * m["moe_intermediate_size"], "head": d * m["vocab_size"],
        "cache_row": 2 * 2 * g * hd,  # bytes a position a layer: keys and values of every key/value head
    }


def _per_token(s: dict) -> float:
    """Multiply-adds of the layers' matrix products a token goes through."""
    m = s["m"]
    routed = s["expert"] * m["num_experts_per_tok"] * m["experts_here"] / m["router_experts"]
    return m["num_hidden_layers"] * (s["attn"] + s["router"] + routed)


def count_block(cfg: dict, sessions: float, context: float, commit_share: float = 1.0 / 3.0) -> dict:
    """One block launch of ``sessions`` sessions (their mean) whose
    histories hold ``context`` positions each (their mean), of which the
    share ``commit_share`` write their block."""
    s = _sizes(cfg)
    m = s["m"]
    layers, b = m["num_hidden_layers"], m["block_length"]
    positions = sessions * b
    touched = m["experts_here"] * (1.0 - (1.0 - m["num_experts_per_tok"] / m["router_experts"]) ** positions)
    weights = layers * (s["attn"] + s["router"] + touched * s["expert"]) + s["head"]
    cache = sessions * layers * s["cache_row"] * (context + commit_share * b)
    io = positions * (2 * m["hidden_size"] + 4 * m["vocab_size"])
    attention = layers * m["num_attention_heads"] * (context + b) * 2 * 2 * m["head_dim"]
    return {"bytes": 2 * weights + cache + io, "flops": positions * (2 * (_per_token(s) + s["head"]) + attention),
            "flops_dtype": "bf16", "experts_touched": touched}


def count_prefill(cfg: dict, tokens: float, context: float = 0.0) -> dict:
    """One prefill launch of ``tokens`` new tokens (whole blocks) after
    ``context`` cached positions; the logits of its last position."""
    s = _sizes(cfg)
    m = s["m"]
    layers, b = m["num_hidden_layers"], m["block_length"]
    pairs = tokens * context + tokens * (tokens + b) / 2  # a position reads up to the end of its own block
    attention = layers * 2 * m["num_attention_heads"] * pairs * 2 * m["head_dim"]
    weights = layers * (s["attn"] + s["router"] + m["experts_here"] * s["expert"]) + s["head"]
    return {"flops": 2 * tokens * _per_token(s) + attention + 2 * s["head"],
            "bytes": 2 * weights + layers * s["cache_row"] * (context + tokens), "flops_dtype": "bf16"}


def count(cfg: dict, rows: int) -> dict:
    """``step_roofline``'s form: a block launch of ``rows`` sessions at half a slot's history."""
    return count_block(cfg, rows, cfg["model"]["slot_len"] / 2)
