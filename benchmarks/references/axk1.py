"""Plain reference for A.X-K1 (``model_type`` ``axk1``): multi-head
latent attention, one leading dense layer, then layers of routed
experts beside a shared one.

Source: https://huggingface.co/skt/A.X-K1/blob/main/config.json. The
block is DeepSeek-V3's; the equations below are written from that
config's keys and DeepSeek-V3's published inference code (YaRN's
blended inverse frequencies, the softmax scale). float32 ``jax.numpy``
at ``jax.default_matmul_precision("highest")``, no cache, no batching,
no kernels: the full causal forward pass over ONE stream's tokens, a
layer at a time, each layer's weights cast to float32 from the
bfloat16 tree inside the layer's own jitted call (so no second whole
copy of the weights exists). It imports nothing of the program.

Departures from the published config, each stated in the
configuration's file as well:

  * the chip's share of a 16-chip deployment: ``experts_here`` routed
    experts of ``router_experts`` from ``expert_offset`` on (the router
    keeps its published width and top-k; what the absent experts would
    add is left out and nothing stands in for it), ``vocab_size`` rows
    of embedding and head, ``num_hidden_layers`` layers;
  * ``topk_method: "none"`` is read as plain top-k over all experts'
    sigmoid scores: no group limit (``n_group``, ``topk_group`` are not
    applied) and no correction bias;
  * rotary pairs are adjacent channels ``(2i, 2i+1)`` as in DeepSeek-V3's
    own code (``view_as_complex``); with seeded weights the pairing is a
    permutation of the columns of ``q_b`` and ``kv_a``;
  * weights are drawn from the seed (``init_params``), scaled so that
    attention scores (std about 1.8), router logits (1.5) and output
    logits (2) spread as a trained model's do and every residual branch
    adds about half the stream's own size: depth is not chaotic.

Tree layout (what the program's loader reads): ``embed [V, D]``,
``head [D, V]``, ``final_norm [D]`` and ``layers/<i>`` with ``norm1``,
``norm2``, ``attn/{q_a, q_norm, q_b, kv_a, kv_norm, kv_b, o}`` and
either ``mlp/{gate, up, down}`` (the dense layer) or ``router [D, E]``,
``shared/{gate, up, down}``, ``experts/{gate, up, down}`` with a leading
axis over the experts held. Matrices are ``[in, out]`` bfloat16, norm
scales float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HEAD_GROUP = 8  # heads a block of the attention scores holds: [8, T, T] float32


def sizes(cfg: dict) -> dict:
    return cfg["model"]


def _normal(key, shape, std: float):
    """bfloat16 normal weights; a stack of experts one expert at a time,
    so that the float32 draw of a whole layer's experts never exists."""
    if len(shape) >= 3:
        return jax.lax.map(lambda k: _normal(k, shape[1:], std), jax.random.split(key, shape[0]))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.bfloat16)


def _attention_params(key, m: dict) -> dict:
    d, h = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    k = jax.random.split(key, 5)
    return {
        "q_a": _normal(k[0], (d, m["q_lora_rank"]), d**-0.5),
        "q_norm": jnp.ones((m["q_lora_rank"],), jnp.float32),
        "q_b": _normal(k[1], (m["q_lora_rank"], h * qk), m["q_lora_rank"] ** -0.5),
        "kv_a": _normal(k[2], (d, m["kv_lora_rank"] + m["qk_rope_head_dim"]), d**-0.5),
        "kv_norm": jnp.ones((m["kv_lora_rank"],), jnp.float32),
        "kv_b": _normal(k[3], (m["kv_lora_rank"], h * (m["qk_nope_head_dim"] + m["v_head_dim"])),
                        m["kv_lora_rank"] ** -0.5),
        "o": _normal(k[4], (h * m["v_head_dim"], d), 0.5 * (h * m["v_head_dim"]) ** -0.5),
    }


def _mlp_params(key, d: int, f: int, lead=()) -> dict:
    k = jax.random.split(key, 3)
    return {"gate": _normal(k[0], (*lead, d, f), d**-0.5), "up": _normal(k[1], (*lead, d, f), d**-0.5),
            "down": _normal(k[2], (*lead, f, d), f**-0.5)}


def init_params(key, calibration, cfg: dict) -> dict:
    """Seeded weights in the served type and layout (module docstring).
    Traced in one jitted call; ``calibration`` is None (no statistics
    are taken on inputs)."""
    del calibration
    m = sizes(cfg)
    d, v = m["hidden_size"], m["vocab_size"]
    keys = jax.random.split(key, m["num_hidden_layers"] + 2)
    layers = {}
    for i in range(m["num_hidden_layers"]):
        k = jax.random.split(keys[i], 5)
        layer = {"norm1": jnp.ones((d,), jnp.float32), "norm2": jnp.ones((d,), jnp.float32),
                 "attn": _attention_params(k[0], m)}
        if i < m["first_k_dense_replace"]:
            layer["mlp"] = _mlp_params(k[1], d, m["intermediate_size"])
        else:
            layer["router"] = _normal(k[2], (d, m["router_experts"]), 1.5 * d**-0.5)
            layer["shared"] = _mlp_params(k[3], d, m["moe_intermediate_size"] * m["n_shared_experts"])
            layer["experts"] = _mlp_params(k[4], d, m["moe_intermediate_size"], (m["experts_here"],))
        layers[str(i)] = layer
    return {"embed": _normal(keys[-2], (v, d), 1.0), "head": _normal(keys[-1], (d, v), 2.0 * d**-0.5),
            "final_norm": jnp.ones((d,), jnp.float32), "layers": layers}


# -- the equations -------------------------------------------------------------


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def yarn_inv_freq(m: dict):
    """DeepSeek-V3's blended inverse frequencies: the published ones
    where a channel turns more than ``beta_fast`` times over the
    original context, those divided by ``factor`` where it turns fewer
    than ``beta_slow`` times, a linear ramp between."""
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
    """``192^-0.5 * m^2``, ``m = 0.1 ln 32 + 1``."""
    rs = m["rope_scaling"]
    return (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5 * yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2


def rope(x, positions, m: dict):
    """Rotate adjacent channel pairs of ``x [T, ..., rope]`` by their
    positions; the cos/sin scale ``mscale / mscale_all_dim`` is 1."""
    rs = m["rope_scaling"]
    scale = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    angle = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(m)[None, :]
    cos, sin = jnp.cos(angle) * scale, jnp.sin(angle) * scale
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def swiglu(x, p: dict, r):
    return r(jax.nn.silu(r(x) @ p["gate"]) * (r(x) @ p["up"])) @ p["down"]


def attention(x, p: dict, m: dict, r):
    """Latent attention over the whole stream: ``x [T, D]`` normalised;
    ``r`` rounds what a served matrix product reads (identity in the
    reference proper)."""
    t, h = x.shape[0], m["num_attention_heads"]
    nope, rp, vd, eps = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"], m["rms_norm_eps"]
    positions = jnp.arange(t)
    q = (r(rms_norm(r(x) @ p["q_a"], p["q_norm"], eps)) @ p["q_b"]).reshape(t, h, nope + rp)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], positions, m)
    ckr = r(x) @ p["kv_a"]
    c = r(rms_norm(ckr[:, : m["kv_lora_rank"]], p["kv_norm"], eps))
    kr = r(rope(ckr[:, m["kv_lora_rank"]:], positions, m))  # ONE head, shared by all
    kv = (c @ p["kv_b"]).reshape(t, h, nope + vd)
    k_nope, v = r(kv[..., :nope]), r(kv[..., nope:])
    q_nope, q_rope = r(q_nope), r(q_rope)
    causal = positions[None, :] <= positions[:, None]
    scale = softmax_scale(m)

    def heads(block):
        qn, qr, kn, vv = block  # [G, T, .]
        s = (jnp.einsum("gtd,gsd->gts", qn, kn) + jnp.einsum("gtr,sr->gts", qr, kr)) * scale
        w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,gsd->gtd", r(w), vv)

    g = math.gcd(h, HEAD_GROUP)
    split = lambda a: jnp.moveaxis(a, 1, 0).reshape(h // g, g, t, a.shape[-1])
    out = jax.lax.map(heads, (split(q_nope), split(q_rope), split(k_nope), split(v)))
    out = jnp.moveaxis(out.reshape(h, t, vd), 0, 1).reshape(t, h * vd)
    return r(out) @ p["o"]


def route(x, router, m: dict):
    """Sigmoid scores over ALL experts, the ``num_experts_per_tok``
    largest, their gates ``routed_scaling_factor * s_i / sum s_j`` and
    the margin: how far the nearest expert HELD HERE is from changing
    sides (a chosen one from the first left out, one left out from the
    last chosen). A swap among the absent experts moves only the gates'
    sum, by less than the two scores differ."""
    s = jax.nn.sigmoid(x @ router)
    k = m["num_experts_per_tok"]
    top, idx = jax.lax.top_k(s, k + 1)
    gates = top[:, :k] / jnp.sum(top[:, :k], axis=-1, keepdims=True) if m["norm_topk_prob"] else top[:, :k]
    last_in, first_out = top[:, k - 1 : k], top[:, k : k + 1]
    here = s[:, m["expert_offset"] : m["expert_offset"] + m["experts_here"]]
    margin = jnp.min(jnp.where(here >= last_in, here - first_out, last_in - here), axis=-1)
    return idx[:, :k], gates * m["routed_scaling_factor"], margin


def experts_here(x, p: dict, idx, gates, m: dict, r):
    """What the experts held here add: every held expert over every
    token, weighted by the token's gate for it (0 where it was not
    chosen). Tokens routed elsewhere add nothing."""
    y = jnp.zeros_like(x)
    for e in range(m["experts_here"]):
        g = jnp.sum(jnp.where(idx == e + m["expert_offset"], gates, 0.0), axis=-1)
        y = y + g[:, None] * swiglu(x, jax.tree_util.tree_map(lambda a: a[e], p), r)
    return y


def _f32(p):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)


def layer_forward(h, layer: dict, m: dict, round_acts: bool):
    """One layer over one stream. Returns the stream and each position's
    router margin (infinite for the dense layer)."""
    r = (lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)) if round_acts else (lambda a: a)
    p = _f32(layer)
    eps = m["rms_norm_eps"]
    h = h + attention(rms_norm(h, p["norm1"], eps), p["attn"], m, r)
    x = rms_norm(h, p["norm2"], eps)
    if "mlp" in p:
        return h + swiglu(x, p["mlp"], r), jnp.full((h.shape[0],), jnp.inf)
    idx, gates, margin = route(x, p["router"], m)
    return h + experts_here(x, p["experts"], idx, gates, m, r) + swiglu(x, p["shared"], r), margin


@functools.lru_cache(maxsize=None)
def _programs(model_json: str, round_acts: bool):
    import json

    m = json.loads(model_json)
    highest = lambda f: jax.jit(lambda *a: jax.default_matmul_precision("highest")(f)(*a))
    return (
        highest(lambda h, layer: layer_forward(h, layer, m, round_acts)),
        highest(lambda h, norm, head: rms_norm(h, norm, m["rms_norm_eps"]) @ head.astype(jnp.float32)),
    )


def stream_logits(tree: dict, tokens, cfg: dict, first: int, round_acts: bool = False):
    """The full causal forward pass over one stream's ``tokens [T]``:
    logits ``[T - first, V]`` of positions ``first`` on, and for each of
    them the smallest router margin over the layers. ``round_acts``
    rounds every matrix product's activations to bfloat16 (the weights
    already are): how far that moves the logits is the seed's
    sensitivity."""
    import json

    m = sizes(cfg)
    layer_fn, head_fn = _programs(json.dumps(m, sort_keys=True), bool(round_acts))
    h = tree["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    margin = jnp.full((h.shape[0],), jnp.inf)
    for i in range(m["num_hidden_layers"]):
        h, mg = layer_fn(h, tree["layers"][str(i)])
        margin = jnp.minimum(margin, mg)
    return head_fn(h[first:], tree["final_norm"], tree["head"]), margin[first:]
