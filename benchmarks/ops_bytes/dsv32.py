"""Operations and least bytes of the two launch kinds of
DeepSeek-V3.2-Exp's share (``configs/dsv32-ep32-l6.json``), from shapes.
``ops_bytes/axk1.py`` counts the block both share; what is here is what
the learned sparse attention adds and changes.

Per layer, for ``tokens`` new tokens after ``context`` cached positions,
``pairs`` the (query, key) pairs under the causal mask:

  * :func:`count_index`: the indexer's projections (``q_lora_rank x Hi
    Di``, ``D x Di``, ``D x Hi`` a token) and its scores, ``2 Hi Di`` a
    pair. Least bytes: its three matrices, and the WHOLE context's index
    keys read once (every key is scored for every query);
  * :func:`count_select`: choosing the ``index_topk`` best of a row is
    comparisons, not multiply-adds: 0 operations; least bytes the
    float32 scores written and read once;
  * :func:`count_sparse_attention`: attention over ``min(pairs, index_topk
    a query)`` pairs. In a prefill launch the expanded form (``qk + v`` a
    head a pair, and the expansion of the positions read: at most all of
    them); in a step launch the absorbed form (``2 kv_rank + rope`` a
    head a pair). Least bytes: the SELECTED rows of the latent cache, a
    session a layer;
  * :func:`count_selected_kernel`: the extend launch's Pallas kernel
    alone (the pairs' products, without the expansion), for
    ``lm_sparse_attention_roofline``.

A STEP launch is bound by bytes, a PREFILL launch by operations; padding
to a launch shape, the masked half of the scores and every position
attention reads under the mask without having selected it are work the
chip does and the count leaves out: a share can only read low.
"""

from __future__ import annotations

from benchmarks.ops_bytes import axk1


def _index(m: dict) -> dict:
    hi, di = m["index_n_heads"], m["index_head_dim"]
    return {"hi": hi, "di": di, "topk": m["index_topk"],
            "weights": m["q_lora_rank"] * hi * di + m["hidden_size"] * (di + hi), "key_row": 2 * di}


def _pairs(tokens: float, context: float) -> float:
    return tokens * context + tokens * (tokens + 1) / 2


def _selected_pairs(tokens: float, context: float, topk: int) -> float:
    """Sum over the new tokens of ``min(visible, topk)``; ``visible``
    runs from ``context + 1`` to ``context + tokens``."""
    under = max(0.0, min(tokens, topk - context))  # tokens that still see no more than topk
    return under * context + under * (under + 1) / 2 + (tokens - under) * topk


def count_index(cfg: dict, tokens: float, context: float) -> dict:
    ix = _index(cfg["model"])
    flops = 2 * tokens * ix["weights"] + 2 * ix["hi"] * ix["di"] * _pairs(tokens, context)
    return {"flops": flops, "bytes": 2 * ix["weights"] + ix["key_row"] * (context + tokens), "flops_dtype": "bf16"}


def count_select(cfg: dict, tokens: float, context: float) -> dict:
    return {"flops": 0.0, "bytes": 2 * 4 * _pairs(tokens, context), "flops_dtype": "bf16"}


def count_sparse_attention(cfg: dict, tokens: float, context: float, absorbed: bool) -> dict:
    m, s = cfg["model"], axk1._sizes(cfg)
    h, rank, rp = m["num_attention_heads"], m["kv_lora_rank"], m["qk_rope_head_dim"]
    pairs = _selected_pairs(tokens, context, m["index_topk"])
    read = min(context + tokens, tokens * m["index_topk"])  # positions some query selected, at most
    if absorbed:
        flops = 2 * h * pairs * (2 * rank + rp)
    else:
        flops = 2 * h * pairs * (m["qk_nope_head_dim"] + rp + m["v_head_dim"]) + 2 * read * s["kv_b"]
    return {"flops": flops, "bytes": s["cache_row"] * min(pairs, read if not absorbed else pairs), "flops_dtype": "bf16"}


def count_selected_kernel(cfg: dict, tokens: float, context: float) -> dict:
    """What the Pallas kernel ``lm_sparse_attention`` of an extend launch
    does in ONE layer: scores and values of the selected pairs a head
    (the expansion of the latents runs before it, in XLA). Least bytes:
    the expanded keys and values of the positions read, once, and the
    queries in and their output back."""
    m = cfg["model"]
    h, qk, vd = m["num_attention_heads"], m["qk_nope_head_dim"] + m["qk_rope_head_dim"], m["v_head_dim"]
    read = min(context + tokens, tokens * m["index_topk"])
    return {"flops": 2 * h * _selected_pairs(tokens, context, m["index_topk"]) * (qk + vd),
            "bytes": 2 * (read * (h * (m["qk_nope_head_dim"] + vd) + m["qk_rope_head_dim"]) + tokens * h * (qk + vd)),
            "flops_dtype": "bf16"}


def _layered(cfg: dict, tokens: float, context: float, absorbed: bool) -> dict:
    layers = cfg["model"]["num_hidden_layers"]
    parts = [count_index(cfg, tokens, context), count_select(cfg, tokens, context),
             count_sparse_attention(cfg, tokens, context, absorbed)]
    return {"flops": layers * sum(p["flops"] for p in parts), "bytes": layers * sum(p["bytes"] for p in parts)}


def count_step(cfg: dict, sessions: float, context: float) -> dict:
    """One step launch of ``sessions`` sessions whose histories hold
    ``context`` positions each: ``ops_bytes/axk1.count_step`` with no
    history (the weights touched, the new rows, the logits), then a
    session's index keys whole, its scores and its selected rows."""
    base = axk1.count_step(cfg, sessions, 0.0)
    ix = _index(cfg["model"])
    mechanism = _layered(cfg, 1.0, context, absorbed=True)
    weights = 2 * cfg["model"]["num_hidden_layers"] * ix["weights"]
    return {"bytes": base["bytes"] + weights + sessions * (mechanism["bytes"] - weights),
            "flops": base["flops"] + sessions * mechanism["flops"], "flops_dtype": "bf16",
            "experts_touched": base["experts_touched"]}


def count_prefill(cfg: dict, tokens: float, context: float = 0.0) -> dict:
    """One prefill launch of ``tokens`` new tokens after ``context``
    cached positions, sparse form: the matrix products a token goes
    through (``ops_bytes/axk1.count_prefill`` less its dense attention),
    index scores over the causal pairs, attention over the selected."""
    dense = axk1.count_prefill(cfg, tokens, context)
    s, m = axk1._sizes(cfg), cfg["model"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    dense_attention = m["num_hidden_layers"] * (
        2 * m["num_attention_heads"] * _pairs(tokens, context) * (qk + m["v_head_dim"]) + 2 * (context + tokens) * s["kv_b"])
    mechanism = _layered(cfg, tokens, context, absorbed=False)
    return {"flops": dense["flops"] - dense_attention + mechanism["flops"],
            "bytes": dense["bytes"] + m["num_hidden_layers"] * count_index(cfg, tokens, context)["bytes"],
            "flops_dtype": "bf16"}


def count(cfg: dict, rows: int) -> dict:
    """``step_roofline``'s form: a step launch of ``rows`` sessions at half a slot's history."""
    return count_step(cfg, rows, cfg["model"]["slot_len"] / 2)
