"""Plain reference for SmallThinker-21BA3B-Instruct (``model_name``
``smallthinker_21b_instruct``): grouped-query attention in layers of two
kinds, FULL layers without any positional encoding and WINDOW layers
with rotary positions, a router that reads the layer's INPUT, and
ReLU-gated experts with no shared one.

Source: https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json.
The equations are written from that config's keys (``configs/
smallthinker21b-ep1-l12.json`` carries them, and lists what was
assumed). For a layer with input stream ``x``, one row a position ``p``:

    g      = x W_r                          router logits over all experts, from the layer's INPUT
    S      = the k largest of g;  w_e = exp(g_e) / sum_{j in S} exp(g_j)
    n1     = RMSNorm(x; norm1)
    q,k,v  = n1 W_q [H x d], n1 W_k [G x d], n1 W_v [G x d]     query head j reads key/value head j // (H / G)
    window layer:  q,k <- RoPE_p(q), RoPE_p(k);  p reads keys s with  p - window < s <= p
    full layer:    no rotation;                   p reads keys s with  0 <= s <= p
    a      = x + concat_heads(softmax(q k^T / sqrt(d)) v) W_o
    n2     = RMSNorm(a; norm2)
    y      = a + sum_{e in S} w_e (relu(n2 W_gate,e) * (n2 W_up,e)) W_down,e

then the final RMS norm and the head. float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``, no cache, no ring, no
batching, no kernels: a full causal pass over a stream's tokens with the
window as a MASK, a layer at a time and ``SEGMENT`` queries at a time,
each layer's matrices cast to float32 from the bfloat16 tree inside its
own jitted call and the experts ONE at a time (so no second copy of a
layer's 755 MB of experts exists beside the 11.12 GB tree). It imports
nothing of the program.

:func:`stream_logits` takes ``wrong``, the ways NOT to compute this model
that the controls hold the check against (``check_window.py``, the CPU
tests): ``window_ignored`` (every layer reads every earlier position),
``full_rotated`` (rotary positions in the full layers too),
``window_unrotated`` (none in the window layers), ``silu`` (SiLU for the
experts' ReLU), ``router_after_attention`` (the router reads ``n2``).

Tree layout (what the program's loader reads): ``embed [V, D]``,
``head [D, V]``, ``final_norm [D]`` and ``layers/<i>`` with ``norm1``,
``norm2``, ``attn/{qkv, o}`` (``qkv [D, (H + 2 G) d]``: the query heads'
columns, then the key heads', then the value heads'), ``router [D, E]``
and ``experts/{gate, up, down}`` with a leading axis over the experts.
Matrices are ``[in, out]`` bfloat16, norm scales float32. Weights are
drawn from the seed (:func:`init_params`), scaled so that attention
scores (std about 1), router logits (1.5) and output logits (2) spread
as a trained model's do and every residual branch adds at most half the
stream's own size.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

SEGMENT = 512  # queries a jitted call takes
KEY_EXTENT = 4096  # a segment's keys are cut to whole extents: a few compiled shapes, not one a segment
WRONG = ("window_ignored", "full_rotated", "window_unrotated", "silu", "router_after_attention")


def sizes(cfg: dict) -> dict:
    return cfg["model"]


def _normal(key, shape, std: float):
    """bfloat16 normal weights; a stack of experts one expert at a time,
    so that the float32 draw of a whole layer's experts never exists."""
    if len(shape) >= 3:
        return jax.lax.map(lambda k: _normal(k, shape[1:], std), jax.random.split(key, shape[0]))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.bfloat16)


def init_params(key, calibration, cfg: dict) -> dict:
    """Seeded weights in the served type and layout (module docstring).
    Traced in one jitted call; ``calibration`` is None (no statistics
    are taken on inputs)."""
    del calibration
    m = sizes(cfg)
    d, v, hd, f = m["hidden_size"], m["vocab_size"], m["head_dim"], m["moe_ffn_hidden_size"]
    h, g, e = m["num_attention_heads"], m["num_key_value_heads"], m["moe_num_primary_experts"]
    keys = jax.random.split(key, m["num_hidden_layers"] + 2)
    layers = {}
    for i in range(m["num_hidden_layers"]):
        k = jax.random.split(keys[i], 6)
        layers[str(i)] = {
            "norm1": jnp.ones((d,), jnp.float32), "norm2": jnp.ones((d,), jnp.float32),
            "attn": {"qkv": _normal(k[0], (d, (h + 2 * g) * hd), d**-0.5), "o": _normal(k[1], (h * hd, d), 0.5 * (h * hd) ** -0.5)},
            "router": _normal(k[2], (d, e), 1.5 * d**-0.5),
            "experts": {"gate": _normal(k[3], (e, d, f), d**-0.5), "up": _normal(k[4], (e, d, f), d**-0.5),
                        "down": _normal(k[5], (e, f, d), f**-0.5)},
        }
    return {"embed": _normal(keys[-2], (v, d), 1.0), "head": _normal(keys[-1], (d, v), 2.0 * d**-0.5),
            "final_norm": jnp.ones((d,), jnp.float32), "layers": layers}


# -- the equations -------------------------------------------------------------


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta: float):
    """Rotate adjacent channel pairs of ``x [N, heads, d]`` by their
    positions, over all ``d`` values of a head: the plain rotary
    embedding, no scaling."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def _rounding(round_acts: bool):
    return (lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)) if round_acts else (lambda a: a)


def keys_values(hs, lo, p: dict, rotated: bool, m: dict, r):
    """The keys and values ``[N, G, d]`` of a segment ``hs [N, D]``
    (the layer's input) that stands at positions ``lo`` on."""
    n, h, g, d = hs.shape[0], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    kv = r(rms_norm(hs, p["norm1"], m["rms_norm_eps"])) @ p["attn"]["qkv"][:, h * d :].astype(jnp.float32)
    k, v = kv[:, : g * d].reshape(n, g, d), kv[:, g * d :].reshape(n, g, d)
    if rotated:
        k = rope(k, lo + jnp.arange(n), float(m["rope_theta"]))
    return r(k), r(v)


def attention(hs, lo, keys, values, p: dict, rotated: bool, window: int, m: dict, r):
    """``hs [N, D]`` (the layer's input at positions ``lo`` on) plus its
    attention over ``keys``, ``values`` ``[K, G, d]`` at positions 0 on:
    position ``i`` reads ``j <= i`` and, under a ``window``, ``i - j <
    window``: the window is a mask."""
    n, h, g, d = hs.shape[0], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q = (r(rms_norm(hs, p["norm1"], m["rms_norm_eps"])) @ p["attn"]["qkv"][:, : h * d].astype(jnp.float32)).reshape(n, h, d)
    i, j = (lo + jnp.arange(n))[:, None], jnp.arange(keys.shape[0])[None, :]
    if rotated:
        q = rope(q, i[:, 0], float(m["rope_theta"]))
    visible = (j <= i) & ((i - j < window) if window else True)

    def group(args):
        qq, kk, vv = args  # [h / g, N, d], [K, d], [K, d]: query head j reads key/value head j // (h / g)
        s = jnp.einsum("jtd,sd->jts", qq, kk) * d**-0.5
        w = jax.nn.softmax(jnp.where(visible[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("jts,sd->jtd", r(w), vv)

    out = jax.lax.map(group, (jnp.moveaxis(r(q), 0, 1).reshape(g, h // g, n, d), jnp.moveaxis(keys, 0, 1), jnp.moveaxis(values, 0, 1)))
    return hs + r(jnp.moveaxis(out.reshape(h, n, d), 0, 1).reshape(n, h * d)) @ p["attn"]["o"].astype(jnp.float32)


def route(x, router, m: dict):
    """The ``k`` largest logits of ``x W_r`` and a softmax over them
    (``moe_primary_router_apply_softmax``, ``norm_topk_prob``), and the
    router's MARGIN: how far, in softmax probability over all experts,
    the last chosen expert lies above the first left out."""
    k = m["moe_num_active_primary_experts"]
    logits = x @ router
    top, idx = jax.lax.top_k(logits, k + 1)
    every = jax.nn.softmax(logits, axis=-1)
    ranked = jnp.take_along_axis(every, idx, axis=-1)
    return idx[:, :k], jax.nn.softmax(top[:, :k], axis=-1), ranked[:, k - 1] - ranked[:, k]


def experts(x, p: dict, idx, gates, act, r):
    """The chosen experts' sum: every expert over every position, one
    expert at a time (cast to float32 as it comes), weighted by the
    position's gate for it (0 where it was not chosen)."""

    def one(y, xs):
        e, w = xs
        w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        return y + g[:, None] * (r(act(r(x) @ w["gate"]) * (r(x) @ w["up"])) @ w["down"]), None

    return jax.lax.scan(one, jnp.zeros_like(x), (jnp.arange(p["gate"].shape[0]), p))[0]


def feed(hs, a, p: dict, m: dict, r, wrong):
    """The layer's second half: the router reads the layer's input
    ``hs``, the experts the normalised ``a`` (the input plus its
    attention). Returns the layer's output and the router's margins."""
    n2 = rms_norm(a, p["norm2"], m["rms_norm_eps"])
    idx, gates, margin = route(n2 if wrong == "router_after_attention" else hs, p["router"].astype(jnp.float32), m)
    return a + experts(n2, p["experts"], idx, gates, jax.nn.silu if wrong == "silu" else jax.nn.relu, r), margin


@functools.lru_cache(maxsize=None)
def _programs(model_json: str, round_acts: bool, wrong):
    m, r = json.loads(model_json), _rounding(round_acts)
    highest = lambda f, **kw: jax.jit(lambda *a: jax.default_matmul_precision("highest")(f)(*a), **kw)
    return {
        "keys": highest(lambda hs, lo, p, rotated: keys_values(hs, lo, p, rotated, m, r), static_argnums=3),
        "attend": highest(lambda hs, lo, keys, values, p, rotated, window: attention(
            hs, lo, keys, values, p, rotated, window, m, r), static_argnums=(5, 6)),
        "feed": highest(lambda hs, a, p: feed(hs, a, p, m, r, wrong)),
        "head": highest(lambda h, norm, head: rms_norm(h, norm, m["rms_norm_eps"]) @ head.astype(jnp.float32)),
    }


def stream_logits(tree: dict, tokens, cfg: dict, first, round_acts: bool = False, wrong: str | None = None):
    """The full causal forward pass over one stream's ``tokens [T]``:
    logits of the positions ``first`` (an index array, or an int: that
    position and every later one), and for each of them the smallest
    router margin over the layers. ``round_acts`` rounds every matrix
    product's activations to bfloat16 (the weights already are): how far
    that moves the logits is the seed's sensitivity. ``wrong``: one of
    :data:`WRONG` (module docstring). A stream longer than ``SEGMENT`` is
    padded to whole segments (a causal pass: what follows a position does
    not reach it), so that streams of different lengths share the
    compiled programs."""
    assert wrong is None or wrong in WRONG, wrong
    m = sizes(cfg)
    run = _programs(json.dumps(m, sort_keys=True), bool(round_acts), wrong)
    tokens = np.asarray(tokens)
    t = tokens.shape[0]
    if t > SEGMENT:
        tokens = np.concatenate([tokens, np.zeros((-t % SEGMENT,), tokens.dtype)])
    starts = list(range(0, len(tokens), SEGMENT))
    segments = [tree["embed"][jnp.asarray(tokens[lo : lo + SEGMENT])].astype(jnp.float32) for lo in starts]
    margin = None
    for i, kind in enumerate(m["layer_types"]):
        layer = tree["layers"][str(i)]
        attn = {k: layer[k] for k in ("norm1", "attn")}
        rest = {k: layer[k] for k in ("norm2", "router", "experts")}
        windowed = kind == "window"
        rotated = (windowed and wrong != "window_unrotated") or (not windowed and wrong == "full_rotated")
        window = int(m["sliding_window_size"]) if windowed and wrong != "window_ignored" else 0
        keys, values = (jnp.concatenate(part) for part in zip(*[run["keys"](hs, lo, attn, rotated) for hs, lo in zip(segments, starts)]))
        # zero rows up to a whole extent: they lie after every query, out of its sight
        keys, values = (jnp.pad(a, ((0, -len(tokens) % KEY_EXTENT), (0, 0), (0, 0))) for a in (keys, values))
        margins = []
        for j, lo in enumerate(starts):
            hi = -(-(lo + segments[j].shape[0]) // KEY_EXTENT) * KEY_EXTENT  # the keys up to the segment's end, to whole extents
            a = run["attend"](segments[j], lo, keys[:hi], values[:hi], attn, rotated, window)
            segments[j], seg_margin = run["feed"](segments[j], a, rest)
            margins.append(seg_margin)
        margins = jnp.concatenate(margins)
        margin = margins if margin is None else jnp.minimum(margin, margins)
    at = np.arange(first, t) if isinstance(first, int) else np.asarray(first)
    ascending = np.sort(at)
    rows = jnp.concatenate([segments[i][ascending[ascending // SEGMENT == i] % SEGMENT] for i in range(len(segments))])
    back = np.argsort(np.argsort(at, kind="stable"), kind="stable")  # where each of ``at`` lies among the sorted
    return run["head"](rows[back], tree["final_norm"], tree["head"]), margin[at]
