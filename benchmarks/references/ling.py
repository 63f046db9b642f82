"""Plain reference for Ling-3.0-flash (``model_type`` ``bailing_hybrid``):
layers of Kimi Delta Attention (KDA) with a latent-attention (MLA) layer
among every six, a leading dense SwiGLU layer, then group-limited
sigmoid-routed experts beside a shared one.

Source: https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/config.json;
KDA is Kimi Linear's (arXiv:2510.26692; flash-linear-attention's
``KimiDeltaAttention``), MLA and the router DeepSeek-V3's. float32
``jax.numpy`` at ``jax.default_matmul_precision("highest")``, no cache,
no batching, no kernels and no chunkwise form: the full causal forward
pass over ONE stream's tokens, a layer at a time. KDA is the recurrence
as written, one ``lax.scan`` step a position::

    S_t = (I - beta_t k_t k_t^T) diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,   o_t = S_t^T q_t

after a plain causal depthwise convolution of width 4 and SiLU on ``q``,
``k``, ``v``, an L2 norm of ``q`` and ``k`` over a head's values and
``q * d^-1/2``; ``g_t = kda_lower_bound * sigmoid(exp(A_log_h) * (x W_f +
dt_bias))`` a key channel, ``beta_t = sigmoid(x W_b)`` a head; the output
is ``concat_h(sigmoid(x W_g)_h * rms_d(o_h)) W_o``. MLA is
``references/dsv32.py``'s without an indexer, with ``q = x W_q`` (no
query compression), plain rotary embeddings on adjacent pairs and the
same head-wise sigmoid gate before ``W_o``. So that a stream of 62k
positions fits beside 10.81 GB of weights (and the reference peaks under
the served program), the stream is held as a list of ``SEGMENT``
positions: a KDA layer's scan runs on from segment to segment with its
state and the last three rows that entered the convolution as the carry
(one recurrence over the whole stream, evaluated in pieces), an MLA
layer goes ``HEAD_GROUP`` heads and ``QUERY_BLOCK`` queries at a time
against all keys. Each jitted call casts the weights it reads to
float32. It imports nothing of the program.

Departures from the published model, each in the configuration's file:
the chip's share of an 8-chip deployment (``experts_here`` of
``router_experts`` experts from ``expert_offset`` on, ``vocab_size``
rows, the layers ``layer_types`` lists: what absent experts would add is
left out and nothing stands in for it); the ``assumed`` list there (which
layers are MLA, the decay's form, the gate on both attention kinds, no
SwiGLU clamp, the multi-token-prediction module not part of the answer);
seeded weights (:func:`init_params`).

Two ways of being WRONG about the state, for ``check_state.py``'s
controls: ``reset_at`` zeroes every KDA layer's state and convolution
tail at those positions (a turn boundary that forgot), ``initial`` starts
the stream from another stream's final state instead of zero (a slot
reused without a reset).

Tree layout (what the program's loader reads): ``embed [V, D]``, ``head
[D, V]``, ``final_norm [D]``, ``layers/<i>/{norm1, norm2, attn, mlp |
router, router_bias, shared, experts}``; ``attn`` of a KDA layer ``{qkv
[D, 3 H d] (queries, keys, values), conv [4, 3 H d] (the last row
multiplies the token's own position), f [D, H d], A_log [H], dt_bias [H,
d], b [D, H], g [D, H], o_norm [d], o [H d, D]}``, of an MLA layer ``{q
[D, H (nope + rope)], kv_a [D, rank + rope], kv_norm [rank], kv_b [rank,
H (nope + v)], gate [D, H], o [H v, D]}``; matrices bfloat16, the rest
float32.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HEAD_GROUP = 2  # heads a block of the attention scores holds: [2, QUERY_BLOCK, S] float32
QUERY_BLOCK = 128
SEGMENT = 2048  # positions a jitted call takes
KEY_EXTENT = 8 * SEGMENT  # an MLA segment reads the stream's keys up to a multiple of this (the mask cuts at the query)
CONV = 4


def sizes(cfg: dict) -> dict:
    return cfg["model"]


def _normal(key, shape, std: float, dtype=jnp.bfloat16):
    if len(shape) >= 3 and math.prod(shape) > 1 << 24:  # a stack of experts: one at a time, so that its float32 draft is one expert's
        return jax.lax.map(lambda k: _normal(k, shape[1:], std, dtype), jax.random.split(key, shape[0]))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _mlp_params(key, d: int, f: int, lead=()) -> dict:
    k = jax.random.split(key, 3)
    return {"gate": _normal(k[0], (*lead, d, f), d**-0.5), "up": _normal(k[1], (*lead, d, f), d**-0.5),
            "down": _normal(k[2], (*lead, f, d), f**-0.5)}


def _kda_params(key, m: dict) -> dict:
    """THE SEEDED DECAY IS SLOW. With ``kda_lower_bound`` -5 a gate drawn
    around 0 would forget in three tokens, and a state that is never
    carried, or never reset, would then move no logit. ``g = lb *
    sigmoid(z)``, ``z = exp(A_log_h) (x W_f + dt_bias)``: ``dt_bias`` is
    drawn uniformly so that ``z`` lies in [-10.8, -3.84] before ``x W_f``
    (std 0.5) moves it: a channel's decay a token ``exp(g)`` then spreads
    log-uniformly from about 0.9 to about 0.9999 over the channels of a
    head, time constants of 10 to 10,000 tokens. ``W_b`` at 1.5 / sqrt(D):
    ``beta`` spreads over (0.1, 0.9)."""
    d, h, hd = m["hidden_size"], m["num_attention_heads"], m["head_dim"]
    k = jax.random.split(key, 9)
    a_log = jax.random.uniform(k[6], (h,), jnp.float32, -0.2, 0.2)
    z = jax.random.uniform(k[7], (h, hd), jnp.float32, -10.8, -3.84)
    return {
        "qkv": _normal(k[0], (d, 3 * h * hd), d**-0.5),
        "conv": _normal(k[1], (CONV, 3 * h * hd), 0.5),
        "f": _normal(k[2], (d, h * hd), 0.5 * d**-0.5),
        "A_log": a_log,
        "dt_bias": z / jnp.exp(a_log)[:, None],
        "b": _normal(k[3], (d, h), 1.5 * d**-0.5),
        "g": _normal(k[4], (d, h), d**-0.5),
        "o_norm": jnp.ones((hd,), jnp.float32),
        "o": _normal(k[5], (h * hd, d), 0.5 * (h * hd) ** -0.5),
    }


def _mla_params(key, m: dict) -> dict:
    d, h = m["hidden_size"], m["num_attention_heads"]
    nope, rp, vd, rank = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"], m["kv_lora_rank"]
    k = jax.random.split(key, 5)
    return {
        "q": _normal(k[0], (d, h * (nope + rp)), d**-0.5),
        "kv_a": _normal(k[1], (d, rank + rp), d**-0.5),
        "kv_norm": jnp.ones((rank,), jnp.float32),
        "kv_b": _normal(k[2], (rank, h * (nope + vd)), rank**-0.5),
        "gate": _normal(k[3], (d, h), d**-0.5),
        "o": _normal(k[4], (h * vd, d), 0.5 * (h * vd) ** -0.5),
    }


def init_params(key, calibration, cfg: dict) -> dict:
    """Seeded weights in the served type and layout (module docstring):
    normal, std 1 / sqrt(fan_in); the attention outputs at half that, the
    router at 1.5 / sqrt(hidden), the head at 2 / sqrt(hidden), embedding
    rows of unit size, the router's correction bias normal std 0.05; the
    KDA gates as :func:`_kda_params` says. Traced in one jitted call;
    ``calibration`` is None."""
    del calibration
    m = sizes(cfg)
    d, v = m["hidden_size"], m["vocab_size"]
    keys = jax.random.split(key, m["num_hidden_layers"] + 2)
    layers = {}
    for i, kind in enumerate(m["layer_types"]):
        k = jax.random.split(keys[i], 6)
        layer = {"norm1": jnp.ones((d,), jnp.float32), "norm2": jnp.ones((d,), jnp.float32),
                 "attn": (_kda_params if kind == "kda" else _mla_params)(k[0], m)}
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


def rope(x, positions, m: dict):
    """Rotate adjacent channel pairs of ``x [T, ..., rope]`` by their positions (``rope_scaling`` null)."""
    dim = m["qk_rope_head_dim"]
    inv_freq = 1.0 / float(m["rope_theta"]) ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def _f32(p):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)


def _rounding(round_acts: bool):
    return (lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)) if round_acts else (lambda a: a)


def swiglu(x, p: dict, r):
    p = _f32(p)
    return r(jax.nn.silu(r(x) @ p["gate"]) * (r(x) @ p["up"])) @ p["down"]


def kda(x, carry, since, reset, p: dict, m: dict, r):
    """A KDA layer over the segment ``x [n, D]`` (normalised). ``carry``:
    the state ``[H, d_k, d_v]`` and the three rows ``[3, 3 H d]`` that
    entered the convolution before the segment. ``since [n]``: how many
    of the positions before each one the convolution may read (the
    stream's start, or a boundary that forgot, lies that far back; 3 or
    more: all); ``reset [n]``: the state is zeroed BEFORE this position.
    Returns the layer's output ``[n, D]`` and the carry after it."""
    n = x.shape[0]
    h, d = m["num_attention_heads"], m["head_dim"]
    state, tail = carry
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    rows = jnp.concatenate([tail, r(x) @ p["qkv"]])  # [3 + n, 3 H d]
    # the causal convolution over the whole stream: tap w reads the row (3 - w) positions back
    taps = [jnp.where((since >= CONV - 1 - w)[:, None], rows[w : w + n], 0.0) * p["conv"][w] for w in range(CONV)]
    q, k, v = (a.reshape(n, h, d) for a in jnp.split(jax.nn.silu(sum(taps)), 3, axis=-1))
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    q, k, v = r(unit(q) * d**-0.5), r(unit(k)), r(v)
    g = m["kda_lower_bound"] * jax.nn.sigmoid(jnp.exp(p["A_log"])[:, None] * ((r(x) @ p["f"]).reshape(n, h, d) + p["dt_bias"]))
    beta = jax.nn.sigmoid(r(x) @ p["b"])  # [n, H]

    def one(s, xs):
        q, k, v, g, beta, reset = xs
        s = jnp.where(reset, 0.0, s) * jnp.exp(g)[:, :, None]
        s = s + k[:, :, None] * (beta[:, None] * (v - jnp.einsum("hkv,hk->hv", s, k)))[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q)

    state, o = jax.lax.scan(one, state, (q, k, v, g, beta, reset))
    gated = rms_norm(o, p["o_norm"], m["rms_norm_eps"]) * jax.nn.sigmoid(r(x) @ p["g"])[..., None]
    return r(gated.reshape(n, h * d)) @ p["o"], (state, rows[n:])


def mla_keys(x, positions, p: dict, m: dict, r):
    """What attention keeps of every position: the latent ``c [n, rank]`` and the ONE rotated key head ``kr [n, rope]``."""
    rank = m["kv_lora_rank"]
    ckr = r(x) @ p["kv_a"].astype(jnp.float32)
    return r(rms_norm(ckr[:, :rank], p["kv_norm"], m["rms_norm_eps"])), r(rope(ckr[:, rank:], positions, m))


def mla(x, positions, keys, p: dict, m: dict, r):
    """Latent attention of the queries ``x [n, D]`` (normalised, at
    ``positions``) over ``keys`` (every position up to the last
    query's), the head-wise gate, then ``W_o``: ``HEAD_GROUP`` heads at a
    time, a group's queries ``QUERY_BLOCK`` at a time against ALL keys
    with a plain softmax."""
    n, h = x.shape[0], m["num_attention_heads"]
    nope, rp, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    c, kr = keys
    s = c.shape[0]
    g, qb = math.gcd(h, HEAD_GROUP), min(QUERY_BLOCK, n)
    pad = -n % qb
    part = lambda a: jnp.concatenate([a, jnp.zeros((pad, *a.shape[1:]), a.dtype)]).reshape((n + pad) // qb, qb, *a.shape[1:])
    scale = (nope + rp) ** -0.5
    gate = jax.nn.sigmoid(r(x) @ p["gate"].astype(jnp.float32))  # [n, H]
    by_group = lambda a, width: jnp.moveaxis(a.reshape(a.shape[0], h // g, g * width), 1, 0)

    def group(acc, weights):
        q_w, kv_b, o, gates = weights  # [D, g (nope + rope)], [rank, g (nope + v)], [g v, D], [g, n]
        q = (r(x) @ q_w.astype(jnp.float32)).reshape(n, g, nope + rp)
        q_nope, q_rope = r(q[..., :nope]), r(rope(q[..., nope:], positions, m))
        kv = (c @ kv_b.astype(jnp.float32)).reshape(s, g, nope + vd)
        k_nope, v = r(kv[..., :nope]), r(kv[..., nope:])

        def block(args):
            qn, qrp, pos = args
            sc = (jnp.einsum("tgd,sgd->gts", qn, k_nope) + jnp.einsum("tgr,sr->gts", qrp, kr)) * scale
            wts = jax.nn.softmax(jnp.where(jnp.arange(s)[None, None, :] <= pos[None, :, None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("gts,sgd->tgd", r(wts), v)

        out = jax.lax.map(block, (part(q_nope), part(q_rope), part(positions))).reshape(n + pad, g, vd)[:n]
        return acc + r((out * gates.T[..., None]).reshape(n, g * vd)) @ o.astype(jnp.float32), None

    o = p["o"].reshape(h // g, g * vd, -1)
    out, _ = jax.lax.scan(group, jnp.zeros((n, o.shape[-1]), jnp.float32),
                          (by_group(p["q"], nope + rp), by_group(p["kv_b"], nope + vd), o, gate.T.reshape(h // g, g, n)))
    return out


def route(x, router, bias, m: dict):
    """``noaux_tc`` (``references/dsv32.py``): sigmoid scores ``s`` over
    ALL experts; chosen by ``s' = s + bias`` among the ``topk_group``
    best of ``n_group`` groups (a group's score: the sum of its two
    largest ``s'``); gates from ``s``, renormalised, times
    ``routed_scaling_factor``. The margin is how far the routing is from
    changing what this chip adds: the smaller of how far the last kept
    group is from the first left out and, where the group of an expert
    held here is kept, how far that expert's ``s'`` is from changing
    sides."""
    s = jax.nn.sigmoid(x @ router)
    k, n_group, keep_groups = m["num_experts_per_tok"], m["n_group"], m["topk_group"]
    biased = s + bias
    t, e = s.shape
    group_score = jnp.sum(jax.lax.top_k(biased.reshape(t, n_group, e // n_group), 2)[0], axis=-1)
    ranked = jax.lax.top_k(group_score, min(keep_groups + 1, n_group))[0]
    kept = jnp.repeat(group_score >= ranked[:, keep_groups - 1 : keep_groups], e // n_group, axis=-1)
    top, idx = jax.lax.top_k(jnp.where(kept, biased, -jnp.inf), k + 1)
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

    def one(y, xs):
        e, expert = xs
        g = jnp.sum(jnp.where(idx == e + m["expert_offset"], gates, 0.0), axis=-1)
        return y + g[:, None] * swiglu(x, expert, r), None

    return jax.lax.scan(one, jnp.zeros_like(x), (jnp.arange(m["experts_here"]), p))[0]


def feed(hs, layer: dict, m: dict, r):
    """The segment after the layer's feed-forward part, and its router margins (infinite for a dense layer)."""
    x = rms_norm(hs, layer["norm2"], m["rms_norm_eps"])
    if "mlp" in layer:
        return hs + swiglu(x, layer["mlp"], r), jnp.full((hs.shape[0],), jnp.inf)
    idx, gates, margin = route(x, layer["router"].astype(jnp.float32), layer["router_bias"], m)
    return hs + experts_here(x, layer["experts"], idx, gates, m, r) + swiglu(x, layer["shared"], r), margin


@functools.lru_cache(maxsize=None)
def _programs(model_json: str, round_acts: bool):
    m, r = json.loads(model_json), _rounding(round_acts)
    highest = lambda f: jax.jit(lambda *a: jax.default_matmul_precision("highest")(f)(*a))
    norm1 = lambda hs, layer: rms_norm(hs, layer["norm1"], m["rms_norm_eps"])

    def kda_segment(hs, carry, since, reset, layer):
        out, carry = kda(norm1(hs, layer), carry, since, reset, layer["attn"], m, r)
        return hs + out, carry

    return {
        "kda": highest(kda_segment),
        "keys": highest(lambda hs, lo, layer: mla_keys(norm1(hs, layer), lo + jnp.arange(hs.shape[0]), layer["attn"], m, r)),
        "mla": highest(lambda hs, lo, keys, layer: hs + mla(
            norm1(hs, layer), lo + jnp.arange(hs.shape[0]), keys, layer["attn"], m, r)),
        "feed": highest(lambda hs, layer: feed(hs, layer, m, r)),
        "head": highest(lambda h, norm, head: rms_norm(h, norm, m["rms_norm_eps"]) @ head.astype(jnp.float32)),
    }


def zero_carry(m: dict):
    h, d = m["num_attention_heads"], m["head_dim"]
    return jnp.zeros((h, d, d), jnp.float32), jnp.zeros((CONV - 1, 3 * h * d), jnp.float32)


def stream_logits(tree: dict, tokens, cfg: dict, first, round_acts: bool = False, reset_at=(), initial=None,
                  return_state: bool = False):
    """The full causal forward pass over one stream's ``tokens [T]``:
    logits of the positions ``first`` (an index array, or an int: that
    position and every later one), and for each of them the smallest
    router margin over the layers. ``round_acts`` rounds every matrix
    product's activations to bfloat16 (the weights already are): how far
    that moves the logits is the seed's sensitivity. ``reset_at``,
    ``initial``: the two wrong ways with the state (module docstring;
    ``initial``: a KDA layer's carry each, in order). ``return_state``:
    also every KDA layer's carry after the last token. A stream longer
    than ``SEGMENT`` is padded to whole segments (a causal pass: what
    follows a position does not reach it), so that the compiled programs
    are a few and streams of different lengths share them; not where the
    carry is asked for, which has to be the last token's."""
    m = sizes(cfg)
    run = _programs(json.dumps(m, sort_keys=True), bool(round_acts))
    tokens = np.asarray(tokens)
    t = tokens.shape[0]
    if t > SEGMENT and not return_state:
        tokens = np.concatenate([tokens, np.zeros((-t % SEGMENT,), tokens.dtype)])
    starts = list(range(0, len(tokens), SEGMENT))
    segments = [tree["embed"][jnp.asarray(tokens[lo : lo + SEGMENT])].astype(jnp.float32) for lo in starts]
    # where the state and the convolution's reach begin anew: the stream's start (unless it starts from another
    # stream's state) and every boundary that forgot
    position, marks = np.arange(len(tokens)), np.asarray(sorted(reset_at), np.int64)
    points = np.unique(np.concatenate([marks, np.zeros(int(initial is None), np.int64)]))
    behind = np.searchsorted(points, position, side="right")  # such points at or before each position
    since = np.full(len(tokens), CONV - 1) if not len(points) else np.where(
        behind > 0, np.minimum(position - points[np.maximum(behind - 1, 0)], CONV - 1), CONV - 1)
    since, reset = jnp.asarray(since, jnp.int32), jnp.asarray(np.isin(position, marks))
    margin, carries, kda_at = None, [], 0
    for i, kind in enumerate(m["layer_types"]):
        layer = tree["layers"][str(i)]
        attention = {k: layer[k] for k in ("norm1", "attn")}
        rest = {k: v for k, v in layer.items() if k not in attention}
        if kind == "kda":
            carry = zero_carry(m) if initial is None else initial[kda_at]
            kda_at += 1
            for j, lo in enumerate(starts):
                n = segments[j].shape[0]
                segments[j], carry = run["kda"](segments[j], carry, since[lo : lo + n], reset[lo : lo + n], attention)
            carries.append(carry)
        else:
            keys = tuple(jnp.concatenate(part) for part in zip(*[run["keys"](hs, lo, attention) for hs, lo in zip(segments, starts)]))
            for j, lo in enumerate(starts):
                # the keys up to the segment's end, to whole ``KEY_EXTENT``s: a few compiled shapes, not one a segment
                hi = min(-(-(lo + segments[j].shape[0]) // KEY_EXTENT) * KEY_EXTENT, len(tokens))
                segments[j] = run["mla"](segments[j], lo, tuple(k[:hi] for k in keys), attention)
        margins = []
        for j in range(len(segments)):
            segments[j], seg_margin = run["feed"](segments[j], rest)
            margins.append(seg_margin)
        margins = jnp.concatenate(margins)
        margin = margins if margin is None else jnp.minimum(margin, margins)
    at = np.arange(first, t) if isinstance(first, int) else np.asarray(first)
    ascending = np.sort(at)
    rows = jnp.concatenate([segments[i][ascending[ascending // SEGMENT == i] % SEGMENT] for i in range(len(segments))])
    back = np.argsort(np.argsort(at, kind="stable"), kind="stable")  # where each of ``at`` lies among the sorted
    out = run["head"](rows[back], tree["final_norm"], tree["head"]), margin[at]
    return (*out, carries) if return_state else out
