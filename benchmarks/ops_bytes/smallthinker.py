"""Operations and least bytes of the two launch kinds of SmallThinker's
pipeline stage (``configs/smallthinker21b-ep1-l12.json``), from shapes:
the WORK, whatever implements it.

A STEP launch (one token of each of ``sessions`` sessions) is bound by
bytes. Least bytes: every matrix it touches read once in bfloat16: the
attention and the router of every layer, of a layer's experts those some
token of the launch is routed to (each token picks ``k`` of ``E``
uniformly, so ``E (1 - (1 - k / E)^sessions)`` are touched in expectation:
35 of 64 at 8 sessions, 51 at 16), the head ONCE a launch (778 MB) and
the launch's embedding rows; the cached keys and values a token may see
(``context + 1`` positions a full layer, ``min(context + 1, window)`` a
window layer; at a MEAN context the minimum is taken of the mean, which
overstates the window layers' part where sessions lie on both sides of
the window: by 0.2 GB of some 7 at this cell's ladder), the rows written
and the logits. A program that reads every slot of the cache whole, in
use or not, reads more, and the count leaves that out: the share can only
read low.

An EXTEND launch (``tokens`` new tokens of one session on ``context``
cached positions) is bound by operations: two per multiply-add of every
matrix product a token goes through (``k`` of the experts) and of
attention's scores and values over the pairs the mask lets through (a
full layer: every earlier position and the token's own; a window layer:
the latest ``window`` of them). Padding to a launch shape and masked
pairs inside a key block are work the chip does and the count leaves out.
"""

from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    m = cfg["model"]
    d, h, g, hd = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    return {
        "m": m, "attn": d * (h + 2 * g) * hd + h * hd * d, "router": d * m["moe_num_primary_experts"],
        "expert": 3 * d * m["moe_ffn_hidden_size"], "head": d * m["vocab_size"],
        "cache_row": 2 * 2 * g * hd,  # bytes a position a layer: keys and values of every key/value head
        "full": m["layer_types"].count("full"), "window": m["layer_types"].count("window"),
    }


def _window_pairs(tokens: float, context: float, window: int) -> float:
    """Pairs a window layer's ``tokens`` new positions after ``context``
    read: position p reads ``min(p + 1, window)``."""
    first, last = context + 1, context + tokens  # keys the first and the last new position may see
    if last <= window:
        return tokens * (first + last) / 2
    inside = max(0.0, window - first + 1)  # new positions that still see every earlier one
    return inside * (first + window) / 2 + (tokens - inside) * window


def count_extend(cfg: dict, tokens: float, context: float = 0.0) -> dict:
    """One extend launch of ``tokens`` new tokens after ``context``
    cached positions; the logits of its last position."""
    s = _sizes(cfg)
    m = s["m"]
    layers, window = s["full"] + s["window"], m["sliding_window_size"]
    per_token = layers * (s["attn"] + s["router"] + m["moe_num_active_primary_experts"] * s["expert"])
    pairs = s["full"] * (tokens * context + tokens * (tokens + 1) / 2) + s["window"] * _window_pairs(tokens, context, window)
    attention = 2 * m["num_attention_heads"] * pairs * 2 * m["head_dim"]
    weights = layers * (s["attn"] + s["router"] + m["moe_num_primary_experts"] * s["expert"]) + s["head"]
    keys = s["full"] * (context + tokens) + s["window"] * min(context + tokens, window + tokens - 1)
    return {"flops": 2 * tokens * per_token + attention + 2 * s["head"],
            "bytes": 2 * weights + s["cache_row"] * keys + 2 * tokens * m["hidden_size"], "flops_dtype": "bf16"}


count_prefill = count_extend  # the name ``layer_metrics/lm_extend_roofline.py`` asks a family for


def count_step(cfg: dict, sessions: float, context: float) -> dict:
    """One step launch of ``sessions`` sessions (their mean) whose
    histories hold ``context`` positions each (their mean)."""
    s = _sizes(cfg)
    m = s["m"]
    layers, window, e = s["full"] + s["window"], m["sliding_window_size"], m["moe_num_primary_experts"]
    touched = e * (1.0 - (1.0 - m["moe_num_active_primary_experts"] / e) ** sessions)
    weights = layers * (s["attn"] + s["router"] + touched * s["expert"]) + s["head"]
    keys = s["full"] * (context + 1) + s["window"] * min(context + 1, window)
    io = sessions * (2 * m["hidden_size"] + 4 * m["vocab_size"])
    per_token = layers * (s["attn"] + s["router"] + m["moe_num_active_primary_experts"] * s["expert"])
    attention = 2 * m["num_attention_heads"] * keys * 2 * m["head_dim"]
    return {"bytes": 2 * weights + sessions * s["cache_row"] * (keys + layers) + io,
            "flops": sessions * (2 * (per_token + s["head"]) + attention), "flops_dtype": "bf16", "experts_touched": touched}
