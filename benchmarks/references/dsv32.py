"""Plain reference for DeepSeek-V3.2-Exp (``model_type`` ``deepseek_v32``):
multi-head latent attention that reads only the positions a learned
indexer selects, leading dense layers, then layers of group-limited
sigmoid-routed experts beside a shared one.

Source: https://huggingface.co/deepseek-ai/DeepSeek-V3.2-Exp/blob/main/config.json
and the model's published inference code (``model.py``: ``Indexer``,
``MLA``, ``Gate``); YaRN and the softmax scale are DeepSeek-V3's.
float32 ``jax.numpy`` at ``jax.default_matmul_precision("highest")``, no
cache, no batching, no kernels: the full causal forward pass over ONE
stream's tokens, a layer at a time and within a layer ``SEGMENT``
queries at a time (each jitted call casts the weights it reads to
float32), the heads of a segment ``HEAD_GROUP`` at a time and its
queries in blocks of ``QUERY_BLOCK``, so that a stream of 34k positions
fits beside 7.65 GB of weights: ``[T, heads, .]`` in float32 would be
8 GB, a block of ``[T, S]`` index scores is 139 MB. It imports nothing
of the program.

Per layer, ``x`` the normalised stream, ``qr = RMSNorm(x W_qa)``::

    q^I[t,j] = (qr[t] W_qb^I)[j]            j = 1..index_n_heads, index_head_dim values, the first rope rotary
    k^I[s]   = LayerNorm(x[s] W_k^I)        index_head_dim values (scale and bias), the first rope rotary
    w[t,j]   = (x[t] W_w)[j] * index_n_heads^-1/2 * index_head_dim^-1/2
    I[t,s]   = sum_j w[t,j] ReLU(q^I[t,j] . k^I[s])
    S_t      = the min(t+1, index_topk) positions s <= t with the largest I[t,s]
    attention = MLA's softmax over s in S_t only, then W_o

and the router (``topk_method: noaux_tc``): ``s = sigmoid(x W_r)``,
``s' = s + b``; a group's score is the sum of its two largest ``s'``
(``n_group`` groups); the ``topk_group`` best groups are kept; the
``num_experts_per_tok`` largest ``s'`` inside them are chosen; gates
``routed_scaling_factor * s_i / sum s_j`` over the chosen, from ``s``.

Departures from the published model, each in the configuration's file:

  * the chip's share of a 32-chip deployment (``experts_here`` of
    ``router_experts`` routed experts from ``expert_offset`` on,
    ``vocab_size`` rows, ``num_hidden_layers`` layers of which
    ``first_k_dense_replace`` dense): what absent experts would add is
    left out and nothing stands in for it;
  * the indexer's products are bfloat16 with float32 accumulation in the
    program (published: FP8 with per-block scales), so the Hadamard
    rotation that only prepares the FP8 rounding (orthogonal: it leaves
    ``q . k`` as it is) is left out; here they are float32;
  * ``e_score_correction_bias`` is drawn from the seed (normal, std 0.05);
  * rotary pairs are adjacent channels ``(2i, 2i+1)`` in MLA and in the
    indexer alike;
  * the multi-token-prediction module is not part of the answer: the
    logits are the main model's;
  * positions that tie with the ``index_topk``-th largest score are all
    selected (float32 scores of seeded weights do not tie);
  * weights are drawn from the seed (``init_params``), scaled as
    ``references/axk1.py`` scales them.

Tree layout (what the program's loader reads): as ``references/axk1.py``,
with ``attn/index/{q_b [q_rank, Hi * Di], k [D, Di], k_scale [Di],
k_bias [Di], w [D, Hi]}`` and, in an expert layer, ``router_bias [E]``
float32.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HEAD_GROUP = 8  # heads a block of the attention scores holds: [8, QUERY_BLOCK, S] float32
QUERY_BLOCK = 128  # queries a block of the index scores holds: [128, 8, S] float32
SEGMENT = 4096  # queries of a segment share one key extent: the stream up to the segment's end


def sizes(cfg: dict) -> dict:
    return cfg["model"]


def _normal(key, shape, std: float, dtype=jnp.bfloat16):
    if len(shape) >= 3:
        return jax.lax.map(lambda k: _normal(k, shape[1:], std, dtype), jax.random.split(key, shape[0]))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _attention_params(key, m: dict) -> dict:
    d, h = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    hi, di = m["index_n_heads"], m["index_head_dim"]
    k = jax.random.split(key, 9)
    return {
        "q_a": _normal(k[0], (d, m["q_lora_rank"]), d**-0.5),
        "q_norm": jnp.ones((m["q_lora_rank"],), jnp.float32),
        "q_b": _normal(k[1], (m["q_lora_rank"], h * qk), m["q_lora_rank"] ** -0.5),
        "kv_a": _normal(k[2], (d, m["kv_lora_rank"] + m["qk_rope_head_dim"]), d**-0.5),
        "kv_norm": jnp.ones((m["kv_lora_rank"],), jnp.float32),
        "kv_b": _normal(k[3], (m["kv_lora_rank"], h * (m["qk_nope_head_dim"] + m["v_head_dim"])),
                        m["kv_lora_rank"] ** -0.5),
        "o": _normal(k[4], (h * m["v_head_dim"], d), 0.5 * (h * m["v_head_dim"]) ** -0.5),
        "index": {
            "q_b": _normal(k[5], (m["q_lora_rank"], hi * di), m["q_lora_rank"] ** -0.5),
            "k": _normal(k[6], (d, di), d**-0.5),
            "k_scale": jnp.ones((di,), jnp.float32),
            "k_bias": _normal(k[7], (di,), 0.1, jnp.float32),
            "w": _normal(k[8], (d, hi), d**-0.5),
        },
    }


def _mlp_params(key, d: int, f: int, lead=()) -> dict:
    k = jax.random.split(key, 3)
    return {"gate": _normal(k[0], (*lead, d, f), d**-0.5), "up": _normal(k[1], (*lead, d, f), d**-0.5),
            "down": _normal(k[2], (*lead, f, d), f**-0.5)}


def init_params(key, calibration, cfg: dict) -> dict:
    """Seeded weights in the served type and layout (module docstring).
    Traced in one jitted call; ``calibration`` is None."""
    del calibration
    m = sizes(cfg)
    d, v = m["hidden_size"], m["vocab_size"]
    keys = jax.random.split(key, m["num_hidden_layers"] + 2)
    layers = {}
    for i in range(m["num_hidden_layers"]):
        k = jax.random.split(keys[i], 6)
        layer = {"norm1": jnp.ones((d,), jnp.float32), "norm2": jnp.ones((d,), jnp.float32),
                 "attn": _attention_params(k[0], m)}
        if i < m["first_k_dense_replace"]:
            layer["mlp"] = _mlp_params(k[1], d, m["intermediate_size"])
        else:
            layer["router"] = _normal(k[2], (d, m["router_experts"]), 1.5 * d**-0.5)
            layer["router_bias"] = _normal(k[5], (m["router_experts"],), 0.05, jnp.float32)
            layer["shared"] = _mlp_params(k[3], d, m["moe_intermediate_size"] * m["n_shared_experts"])
            layer["experts"] = _mlp_params(k[4], d, m["moe_intermediate_size"], (m["experts_here"],))
        layers[str(i)] = layer
    return {"embed": _normal(keys[-2], (v, d), 1.0), "head": _normal(keys[-1], (d, v), 2.0 * d**-0.5),
            "final_norm": jnp.ones((d,), jnp.float32), "layers": layers}


# -- the equations -------------------------------------------------------------


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def layer_norm(x, scale, bias, eps: float = 1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def yarn_inv_freq(m: dict):
    """DeepSeek-V3's blended inverse frequencies (``references/axk1.py``)."""
    dim, base, rs = m["qk_rope_head_dim"], float(m["rope_theta"]), m["rope_scaling"]
    original = rs["original_max_position_embeddings"]

    def correction_dim(rotations: float) -> float:
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 0.001), 0.0, 1.0)
    return extra / rs["factor"] * ramp + extra * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(m: dict) -> float:
    """``192^-0.5 * m^2``, ``m = 0.1 ln 40 + 1``."""
    rs = m["rope_scaling"]
    return (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5 * yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2


def rope(x, positions, m: dict):
    """Rotate adjacent channel pairs of ``x [T, ..., rope]`` by their positions."""
    rs = m["rope_scaling"]
    scale = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    angle = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(m)[None, :]
    cos, sin = jnp.cos(angle) * scale, jnp.sin(angle) * scale
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def rope_head(x, positions, m: dict):
    """The first ``qk_rope_head_dim`` values of ``x [T, ..., n]`` rotated, the rest as they are."""
    rp = m["qk_rope_head_dim"]
    return jnp.concatenate([rope(x[..., :rp], positions, m), x[..., rp:]], axis=-1)


def _f32(p):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)


def swiglu(x, p: dict, r):
    p = _f32(p)
    return r(jax.nn.silu(r(x) @ p["gate"]) * (r(x) @ p["up"])) @ p["down"]


def selection(qi, w, ki, q_pos, topk: int):
    """Which keys each query reads. ``qi [Q, Hi, Di]``, ``w [Q, Hi]``,
    ``ki [S, Di]``, ``q_pos [Q]``: the index scores ``I [Q, S]`` (minus
    infinity after the query's own position) and ``keep [Q, S]``, the
    ``min(visible, topk)`` largest of each row."""
    hi = qi.shape[1]
    g = math.gcd(hi, HEAD_GROUP)

    def heads(block):
        q, ww = block  # [G, Q, Di], [G, Q]
        return jnp.sum(ww[:, :, None] * jax.nn.relu(jnp.einsum("gtd,sd->gts", q, ki)), axis=0)

    split = lambda a: jnp.moveaxis(a, 1, 0).reshape(hi // g, g, *a.shape[:1], *a.shape[2:])
    scores = jnp.sum(jax.lax.map(heads, (split(qi), split(w))), axis=0)
    causal = jnp.arange(ki.shape[0])[None, :] <= q_pos[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(scores, min(topk, ki.shape[0]))[0][:, -1:]  # minus infinity where fewer are visible
    return scores, causal & (scores >= kth)


def key_side(x, positions, p: dict, m: dict, r):
    """What attention keeps of every position: ``x [T, D]`` normalised
    -> the latent ``c [T, kv_rank]``, the ONE rotated key head ``kr [T,
    rope]`` that all heads share, the index key ``ki [T, Di]``."""
    rank, ix = m["kv_lora_rank"], _f32(p["index"])
    ckr = r(x) @ p["kv_a"].astype(jnp.float32)
    c = r(rms_norm(ckr[:, :rank], p["kv_norm"], m["rms_norm_eps"]))
    kr = r(rope(ckr[:, rank:], positions, m))
    ki = r(rope_head(layer_norm(r(x) @ ix["k"], ix["k_scale"], ix["k_bias"]), positions, m))
    return c, kr, ki


def attend(x, positions, keys, p: dict, m: dict, r, select: str = "topk"):
    """Latent attention of the queries ``x [n, D]`` (normalised, at
    ``positions [n]``) over ``keys`` (:func:`key_side` of every position
    up to the last query's) under the indexer's selection, then ``W_o``.
    The heads go ``HEAD_GROUP`` at a time (a group's queries, keys and
    values exist only while it is computed: at 128 heads and 34k
    positions all of them at once are 8 GB), the queries of a group
    ``QUERY_BLOCK`` at a time against ALL keys with a plain softmax.
    ``r`` rounds what a served matrix product reads (identity in the
    reference proper). ``select`` is the configuration's (``topk``) or
    one of the controls' ways of being wrong: ``recent`` (the
    ``index_topk`` latest positions), ``dense`` (every visible one)."""
    n, h = x.shape[0], m["num_attention_heads"]
    nope, rp, vd, eps = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"], m["rms_norm_eps"]
    hi, di, topk = m["index_n_heads"], m["index_head_dim"], m["index_topk"]
    c, kr, ki = keys
    s = c.shape[0]
    g, qb = math.gcd(h, HEAD_GROUP), min(QUERY_BLOCK, n)
    pad = -n % qb
    part = lambda a: jnp.concatenate([a, jnp.zeros((pad, *a.shape[1:]), a.dtype)]).reshape((n + pad) // qb, qb, *a.shape[1:])
    qr = r(rms_norm(r(x) @ p["q_a"].astype(jnp.float32), p["q_norm"], eps))
    causal = lambda pos: jnp.arange(s)[None, :] <= pos[:, None]
    if select == "topk":
        ix = _f32(p["index"])
        qi = r(rope_head((qr @ ix["q_b"]).reshape(n, hi, di), positions, m))
        w = (r(x) @ ix["w"]) * (hi**-0.5 * di**-0.5)
        keep = jax.lax.map(lambda a: selection(a[0], a[1], ki, a[2], topk)[1], (part(qi), part(w), part(positions)))
    elif select == "recent":
        keep = jax.lax.map(lambda pos: causal(pos) & (jnp.arange(s)[None, :] > pos[:, None] - topk), part(positions))
    else:
        keep = jax.lax.map(causal, part(positions))
    scale = softmax_scale(m)
    by_group = lambda a, width: jnp.moveaxis(a.reshape(a.shape[0], h // g, g * width), 1, 0)

    def group(acc, weights):
        q_b, kv_b, o = _f32(weights)  # [q_rank, g * (nope + rope)], [kv_rank, g * (nope + v)], [g * v, D]
        q = (qr @ q_b).reshape(n, g, nope + rp)
        q_nope, q_rope = r(q[..., :nope]), r(rope(q[..., nope:], positions, m))
        kv = (c @ kv_b).reshape(s, g, nope + vd)
        k_nope, v = r(kv[..., :nope]), r(kv[..., nope:])

        def block(args):
            qn, qrp, kept = args  # [qb, g, .], [qb, S]
            sc = (jnp.einsum("tgd,sgd->gts", qn, k_nope) + jnp.einsum("tgr,sr->gts", qrp, kr)) * scale
            wts = jax.nn.softmax(jnp.where(kept[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("gts,sgd->tgd", r(wts), v).reshape(qb, g * vd)

        out = jax.lax.map(block, (part(q_nope), part(q_rope), keep)).reshape(n + pad, g * vd)[:n]
        return acc + r(out) @ o, None

    o = p["o"].reshape(h // g, g * vd, -1)
    out, _ = jax.lax.scan(group, jnp.zeros((n, o.shape[-1]), jnp.float32),
                          (by_group(p["q_b"], nope + rp), by_group(p["kv_b"], nope + vd), o))
    return out


def route(x, router, bias, m: dict):
    """``noaux_tc``: sigmoid scores ``s`` over ALL experts; chosen by
    ``s' = s + bias`` under the group limit; gates from ``s``. The
    margin is how far the routing is from changing what this chip adds:
    the smaller of (a) how far the last kept group's score is from the
    first group's left out (a group that changes sides exchanges several
    chosen experts at once, so the gates' sum jumps) and (b), where the
    group of an expert held here is kept, how far that expert's ``s'``
    is from changing sides among the kept groups' experts."""
    s = jax.nn.sigmoid(x @ router)
    k, n_group, keep_groups = m["num_experts_per_tok"], m["n_group"], m["topk_group"]
    biased = s + bias
    t, e = s.shape
    per_group = biased.reshape(t, n_group, e // n_group)
    group_score = jnp.sum(jax.lax.top_k(per_group, 2)[0], axis=-1)  # [T, n_group]
    ranked = jax.lax.top_k(group_score, min(keep_groups + 1, n_group))[0]
    last_group = ranked[:, keep_groups - 1 : keep_groups]
    kept = jnp.repeat(group_score >= last_group, e // n_group, axis=-1)
    masked = jnp.where(kept, biased, -jnp.inf)
    top, idx = jax.lax.top_k(masked, k + 1)
    chosen = jnp.take_along_axis(s, idx[:, :k], axis=-1)
    gates = chosen / jnp.sum(chosen, axis=-1, keepdims=True) if m["norm_topk_prob"] else chosen
    group_margin = (ranked[:, keep_groups - 1] - ranked[:, keep_groups]) if n_group > keep_groups else jnp.full((t,), jnp.inf)
    last_in, first_out = top[:, k - 1 : k], top[:, k : k + 1]
    lo, hi = m["expert_offset"], m["expert_offset"] + m["experts_here"]
    here, here_kept = biased[:, lo:hi], kept[:, lo:hi]
    expert_margin = jnp.where(here >= last_in, here - first_out, last_in - here)
    expert_margin = jnp.min(jnp.where(here_kept, expert_margin, jnp.inf), axis=-1)
    return idx[:, :k], gates * m["routed_scaling_factor"], jnp.minimum(group_margin, expert_margin)


def experts_here(x, p: dict, idx, gates, m: dict, r):
    """What the experts held here add: every held expert over every
    token, weighted by the token's gate for it (0 where not chosen)."""
    y = jnp.zeros_like(x)
    for e in range(m["experts_here"]):
        g = jnp.sum(jnp.where(idx == e + m["expert_offset"], gates, 0.0), axis=-1)
        y = y + g[:, None] * swiglu(x, jax.tree_util.tree_map(lambda a: a[e], p), r)
    return y


def _rounding(round_acts: bool):
    return (lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)) if round_acts else (lambda a: a)


def segment_keys(hs, lo, layer: dict, m: dict, round_acts: bool):
    """:func:`key_side` of the segment ``hs [n, D]`` whose first position is ``lo``."""
    x = rms_norm(hs, layer["norm1"], m["rms_norm_eps"])
    return key_side(x, lo + jnp.arange(hs.shape[0]), layer["attn"], m, _rounding(round_acts))


def segment_attend(hs, lo, keys, layer: dict, m: dict, round_acts: bool, select: str):
    """The segment after attention over ``keys`` (every position up to the segment's end, at least)."""
    x = rms_norm(hs, layer["norm1"], m["rms_norm_eps"])
    return hs + attend(x, lo + jnp.arange(hs.shape[0]), keys, layer["attn"], m, _rounding(round_acts), select)


def segment_feed(hs, layer: dict, m: dict, round_acts: bool):
    """The segment after the layer's feed-forward part (``layer`` without ``attn``), and its router margins."""
    r = _rounding(round_acts)
    x = rms_norm(hs, layer["norm2"], m["rms_norm_eps"])
    if "mlp" in layer:
        return hs + swiglu(x, layer["mlp"], r), jnp.full((hs.shape[0],), jnp.inf)
    idx, gates, margin = route(x, layer["router"].astype(jnp.float32), layer["router_bias"], m)
    return hs + experts_here(x, layer["experts"], idx, gates, m, r) + swiglu(x, layer["shared"], r), margin


def layer_segments(segments: list, layer: dict, m: dict, round_acts: bool, select: str = "topk", programs=None):
    """One layer over one stream held as a list of segments ``[n, D]``
    (``SEGMENT`` positions each; the last may be shorter). Every
    position's key side first; then a segment at a time: attention over
    the stream up to the segment's end, then the segment's own
    feed-forward part, so that nothing of ``[T, heads, .]`` or ``[T,
    intermediate]`` exists at 34k positions. Each segment of the list
    is REPLACED by the layer's output as it comes (the stream exists
    once); returns the list and each segment's router margins (infinite
    for a dense layer). ``programs``: the three segment functions
    jitted (:func:`_programs`)."""
    keys_of, attend_to, feed = programs or (
        lambda hs, lo, layer: segment_keys(hs, lo, layer, m, round_acts),
        lambda hs, lo, keys, layer: segment_attend(hs, lo, keys, layer, m, round_acts, select),
        lambda hs, layer: segment_feed(hs, layer, m, round_acts),
    )
    attention = {k: layer[k] for k in ("norm1", "attn")}
    rest = {k: v for k, v in layer.items() if k not in attention}
    starts = [sum(seg.shape[0] for seg in segments[:i]) for i in range(len(segments))]
    keys = tuple(jnp.concatenate(part) for part in zip(*[keys_of(hs, lo, attention) for hs, lo in zip(segments, starts)]))
    margins = []
    for i, lo in enumerate(starts):
        hi = lo + segments[i].shape[0]
        segments[i], margin = feed(attend_to(segments[i], lo, tuple(k[:hi] for k in keys), attention), rest)
        margins.append(margin)
    return segments, margins


def layer_forward(h, layer: dict, m: dict, round_acts: bool, select: str = "topk"):
    """:func:`layer_segments` for a stream ``h [T, D]`` in one array:
    the stream and each position's router margin."""
    segments, margins = layer_segments([h[lo : lo + SEGMENT] for lo in range(0, h.shape[0], SEGMENT)], layer, m, round_acts, select)
    return jnp.concatenate(segments), jnp.concatenate(margins)


@functools.lru_cache(maxsize=None)
def _programs(model_json: str, round_acts: bool, select: str):
    m = json.loads(model_json)
    highest = lambda f: jax.jit(lambda *a: jax.default_matmul_precision("highest")(f)(*a))
    return (
        (
            highest(lambda hs, lo, layer: segment_keys(hs, lo, layer, m, round_acts)),
            highest(lambda hs, lo, keys, layer: segment_attend(hs, lo, keys, layer, m, round_acts, select)),
            highest(lambda hs, layer: segment_feed(hs, layer, m, round_acts)),
        ),
        highest(lambda h, norm, head: rms_norm(h, norm, m["rms_norm_eps"]) @ head.astype(jnp.float32)),
    )


def stream_logits(tree: dict, tokens, cfg: dict, first, round_acts: bool = False, select: str = "topk"):
    """The full causal forward pass over one stream's ``tokens [T]``:
    logits of the positions ``first`` (an index array, or an int: that
    position and every later one), and for each of them the smallest
    router margin over the layers. ``round_acts`` rounds every matrix
    product's activations to bfloat16 (the weights already are): how far
    that moves the logits is the seed's sensitivity. A stream longer
    than ``SEGMENT`` is padded to whole segments (a causal pass: what
    follows a position does not reach it), so that the compiled
    programs are a few and streams of different lengths share them."""
    m = sizes(cfg)
    programs, head_fn = _programs(json.dumps(m, sort_keys=True), bool(round_acts), select)
    tokens = np.asarray(tokens)
    t = tokens.shape[0]
    if t > SEGMENT:
        tokens = np.concatenate([tokens, np.zeros((-t % SEGMENT,), tokens.dtype)])
    segments = [tree["embed"][jnp.asarray(tokens[lo : lo + SEGMENT])].astype(jnp.float32) for lo in range(0, len(tokens), SEGMENT)]
    margin = None
    for i in range(m["num_hidden_layers"]):
        segments, margins = layer_segments(segments, tree["layers"][str(i)], m, bool(round_acts), select, programs)
        margins = jnp.concatenate(margins)
        margin = margins if margin is None else jnp.minimum(margin, margins)
    at = np.arange(first, t) if isinstance(first, int) else np.asarray(first)
    ascending = np.sort(at)
    rows = jnp.concatenate([segments[i][ascending[ascending // SEGMENT == i] % SEGMENT] for i in range(len(segments))])
    back = np.argsort(np.argsort(at, kind="stable"), kind="stable")  # where each of ``at`` lies among the sorted
    return head_fn(rows[back], tree["final_norm"], tree["head"]), margin[at]
