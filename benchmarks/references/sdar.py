"""Plain reference for SDAR (``model_type`` ``sdar_moe``): the Qwen3-MoE
layer, grouped-query attention with an RMS norm over each head of the
queries and keys, softmax-routed experts and no shared one, under the
mask of generation by DIFFUSION OVER BLOCKS: causal between blocks of
``block_length`` positions, bidirectional inside a block.

Source: https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json.
The equations are written from that config's keys and the Qwen3-MoE
block its ``model_type`` derives from. float32 ``jax.numpy`` at
``jax.default_matmul_precision("highest")``, no cache, no batching, no
kernels: ONE forward pass over a set of positions under a mask the
caller gives (:func:`forward`), a layer at a time, each layer's weights
cast to float32 from the bfloat16 tree inside the layer's own jitted
call (so no second whole copy of the weights exists) and the attention
scores a key/value head's eight query heads at a time (``[8, N, N]``
float32: 0.2 GB at 2,556 positions beside the 9.24 GB tree).
:func:`stream_logits` is that pass over one stream's tokens under the
block mask. It imports nothing of the program.

Why a mask and not a sequence: what a served session answers to a
DENOISING pass is the model over ``committed prefix + the block as the
request carried it``. Blocks before a position do not depend on it, so
every such pass of a stream is the same prefix with another last block:
:func:`forward` takes them all at once as extra positions (with the
rotary positions of the block they stand for) that read the prefix and
their own block and that nothing else reads. One pass a stream instead
of one a request, the same numbers.

Departures from the published model, each stated in the configuration's
file as well:

  * the chip's share of an 8-chip deployment: ``experts_here`` of the
    ``router_experts`` experts from ``expert_offset`` on (the router
    keeps its published width, softmax and top-8; what the absent
    experts would add is left out and nothing stands in for it) and
    ``vocab_size`` rows of embedding and head; depth is uncut;
  * the ``[MASK]`` id is moved into the vocabulary's slice (its last
    row, ``mask_token_id``): to the model it is one more embedding row;
  * ``block_length`` and the schedule (which positions a pass reveals)
    are not in ``config.json``: block length 4 is set by the
    configuration, the schedule is the traffic's;
  * the per-head q/k norms are Qwen3's (``config.json`` has no key for
    them); rotary pairs are adjacent channels ``(2i, 2i+1)`` as in the
    repo's other language models (with seeded weights the pairing is a
    permutation of the columns of ``qkv``);
  * weights are drawn from the seed (``init_params``), scaled so that
    attention scores (std about 1), router logits (1.5) and output logits
    (2) spread as a trained model's do and every residual branch adds at
    most half the stream's own size: 48 layers deep, depth is not chaotic.

Tree layout (what the program's loader reads): ``embed [V, D]``,
``head [D, V]``, ``final_norm [D]`` and ``layers/<i>`` with ``norm1``,
``norm2``, ``attn/{qkv, q_norm, k_norm, o}`` (``qkv [D, (H + 2 G) d]``:
the query heads' columns, then the key heads', then the value heads'),
``router [D, E]`` and ``experts/{gate, up, down}`` with a leading axis
over the experts held. Matrices are ``[in, out]`` bfloat16, norm scales
float32.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp


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
    d, v, hd, f = m["hidden_size"], m["vocab_size"], m["head_dim"], m["moe_intermediate_size"]
    h, g, held = m["num_attention_heads"], m["num_key_value_heads"], m["experts_here"]
    keys = jax.random.split(key, m["num_hidden_layers"] + 2)
    layers = {}
    for i in range(m["num_hidden_layers"]):
        k = jax.random.split(keys[i], 6)
        layers[str(i)] = {
            "norm1": jnp.ones((d,), jnp.float32), "norm2": jnp.ones((d,), jnp.float32),
            "attn": {"qkv": _normal(k[0], (d, (h + 2 * g) * hd), d**-0.5), "q_norm": jnp.ones((hd,), jnp.float32),
                     "k_norm": jnp.ones((hd,), jnp.float32), "o": _normal(k[1], (h * hd, d), 0.5 * (h * hd) ** -0.5)},
            "router": _normal(k[2], (d, m["router_experts"]), 1.5 * d**-0.5),
            "experts": {"gate": _normal(k[3], (held, d, f), d**-0.5), "up": _normal(k[4], (held, d, f), d**-0.5),
                        "down": _normal(k[5], (held, f, d), f**-0.5)},
        }
    return {"embed": _normal(keys[-2], (v, d), 1.0), "head": _normal(keys[-1], (d, v), 2.0 * d**-0.5),
            "final_norm": jnp.ones((d,), jnp.float32), "layers": layers}


# -- the equations -------------------------------------------------------------


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta: float):
    """Rotate adjacent channel pairs of ``x [N, heads, d]`` by their
    positions: the plain rotary embedding, no scaling."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def block_mask(positions, block: int):
    """``visible[i, j]``: position ``i`` reads position ``j``, where
    ``j``'s block is ``i``'s or an earlier one."""
    blk = positions // block
    return blk[None, :] <= blk[:, None]


def attention(x, p: dict, positions, visible, m: dict, r):
    """Grouped-query attention of ``x [N, D]`` normalised under
    ``visible [N, N]``; ``r`` rounds what a served matrix product reads
    (identity in the reference proper)."""
    n, h, g, d, eps = x.shape[0], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"], m["rms_norm_eps"]
    qkv = r(x) @ p["qkv"]
    q = qkv[:, : h * d].reshape(n, h, d)
    k = qkv[:, h * d : (h + g) * d].reshape(n, g, d)
    v = r(qkv[:, (h + g) * d :].reshape(n, g, d))
    q = r(rope(rms_norm(q, p["q_norm"], eps), positions, float(m["rope_theta"])))
    k = r(rope(rms_norm(k, p["k_norm"], eps), positions, float(m["rope_theta"])))

    def group(args):
        qq, kk, vv = args  # [h / g, N, d], [N, d], [N, d]: query head j reads key/value head j // (h / g)
        s = jnp.einsum("jtd,sd->jts", qq, kk) * d**-0.5
        w = jax.nn.softmax(jnp.where(visible[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("jts,sd->jtd", r(w), vv)

    out = jax.lax.map(group, (jnp.moveaxis(q, 0, 1).reshape(g, h // g, n, d), jnp.moveaxis(k, 0, 1), jnp.moveaxis(v, 0, 1)))
    return r(jnp.moveaxis(out.reshape(h, n, d), 0, 1).reshape(n, h * d)) @ p["o"]


def route(x, router, m: dict):
    """Softmax over ALL experts in float32, the ``num_experts_per_tok``
    largest, renormalised to sum 1 (``norm_topk_prob``)."""
    top, idx = jax.lax.top_k(jax.nn.softmax(x @ router, axis=-1), m["num_experts_per_tok"])
    return idx, top / jnp.sum(top, axis=-1, keepdims=True) if m["norm_topk_prob"] else top


def experts_here(x, p: dict, idx, gates, m: dict, r):
    """What the experts held here add: every held expert over every
    position, weighted by the position's gate for it (0 where it was not
    chosen). Positions routed elsewhere add nothing."""
    y = jnp.zeros_like(x)
    for e in range(m["experts_here"]):
        g = jnp.sum(jnp.where(idx == e + m["expert_offset"], gates, 0.0), axis=-1)
        y = y + g[:, None] * (r(jax.nn.silu(r(x) @ p["gate"][e]) * (r(x) @ p["up"][e])) @ p["down"][e])
    return y


def layer_forward(h, layer: dict, positions, visible, m: dict, round_acts: bool):
    r = (lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)) if round_acts else (lambda a: a)
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), layer)
    eps = m["rms_norm_eps"]
    h = h + attention(rms_norm(h, p["norm1"], eps), p["attn"], positions, visible, m, r)
    x = rms_norm(h, p["norm2"], eps)
    idx, gates = route(x, p["router"], m)
    return h + experts_here(x, p["experts"], idx, gates, m, r)


@functools.lru_cache(maxsize=None)
def _programs(model_json: str, round_acts: bool):
    m = json.loads(model_json)
    highest = lambda f: jax.jit(lambda *a: jax.default_matmul_precision("highest")(f)(*a))
    return (
        highest(lambda h, layer, positions, visible: layer_forward(h, layer, positions, visible, m, round_acts)),
        highest(lambda h, norm, head: rms_norm(h, norm, m["rms_norm_eps"]) @ head.astype(jnp.float32)),
    )


def forward(tree: dict, tokens, positions, visible, cfg: dict, rows=None, round_acts: bool = False):
    """One forward pass over ``tokens [N]`` standing at rotary
    ``positions [N]`` under ``visible [N, N]``: logits ``[len(rows), V]``
    of ``rows`` (all, where None). ``round_acts`` rounds every matrix
    product's activations to bfloat16 (the weights already are): how far
    that moves the logits is the seed's sensitivity."""
    m = sizes(cfg)
    layer_fn, head_fn = _programs(json.dumps(m, sort_keys=True), bool(round_acts))
    positions, visible = jnp.asarray(positions, jnp.int32), jnp.asarray(visible, bool)
    h = tree["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(m["num_hidden_layers"]):
        h = layer_fn(h, tree["layers"][str(i)], positions, visible)
    return head_fn(h if rows is None else h[jnp.asarray(rows)], tree["final_norm"], tree["head"])


def stream_logits(tree: dict, tokens, cfg: dict, round_acts: bool = False):
    """The full forward pass over one stream's ``tokens [T]`` under the
    block mask: logits ``[T, V]``, each the model's belief about the
    token AT that position."""
    positions = jnp.arange(len(tokens), dtype=jnp.int32)
    return forward(tree, tokens, positions, block_mask(positions, sizes(cfg)["block_length"]), cfg, None, round_acts)
