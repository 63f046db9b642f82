"""SDAR's block (``family: sdar_moe``; SDAR-30B-A3B-Chat,
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat, ``model_type``
``sdar_moe``): the Qwen3-MoE layer, grouped-query attention with an RMS
norm over each head of the queries and the keys and softmax-routed
experts with no shared one, generating by DIFFUSION OVER BLOCKS: the
attention mask is causal between blocks of ``block_length`` positions
and bidirectional inside one (ops/block_attention.py), and the logits at
a position are the model's belief about the token AT that position (a
masked one holds the ``[MASK]`` id). Served as one chip's share of a
wider deployment: ``experts_here`` of the ``router_experts`` experts of
each layer, ``vocab_size`` rows of embedding and head, every layer; every
width is the published one.

A sibling of models/axk1.py, not a switch inside it: the two share the
embedding, ``_rms``, the seeded initialisation's helpers, ``stack_layers``,
the ONE ``lax.scan`` over stacked layers, ops/experts.py (``route`` with
``softmax``, ``routed_experts``) and ops/rope.py (YaRN's tables at factor
1 are the plain rotary embedding); attention, the cache and the launch
kinds, which are most of either module, cannot be shared: latent rows
there, per-head keys and values here.

The cache is a dict of keys ``k`` and values ``v``, each ``[layers,
slots, slot_len, kv_heads * head_dim]`` bfloat16, donated together
(ops/block_attention.py says why a position is one row). Two operations,
one a launch kind:

  * :func:`extend`: append ``lengths[0]`` of ``tokens [1, n]``, whole
    blocks, to the session in slot ``slots[0]`` from ``positions[0]`` on
    and answer the logits of the last appended position;
  * :func:`block`: ONE block ``tokens [R, B]`` of each of R sessions at
    its slot's length. Every row attends to what its slot holds and to
    its own B positions and answers the logits of all of them; a row
    whose ``commit`` is set also writes its block's keys and values (the
    session's length then moves by B, runtime/sessions.py), any other
    row writes nothing: a denoising pass leaves the cache as it was.

The router, norms, softmax and logits are float32; everything a matrix
product reads is bfloat16.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from triton_client_tpu.models import axk1
from triton_client_tpu.ops import block_attention
from triton_client_tpu.ops import experts as experts_op
from triton_client_tpu.ops import rope

#: every layer is an expert layer: one stack, one scan; 48 layers deep a leaf's stack is
#: gigabytes, so each is one program (``one_program``: no copy of the parts beside it)
stack_layers = functools.partial(axk1.stack_layers, one_program=True)

_PUBLISHED = {
    "hidden_size", "moe_intermediate_size", "num_attention_heads", "num_key_value_heads",
    "head_dim", "router_experts", "experts_here", "expert_offset", "num_experts_per_tok",
    "norm_topk_prob", "num_hidden_layers", "vocab_size", "rms_norm_eps", "rope_theta",
    "block_length", "expert_chunk_rows",
}


@dataclasses.dataclass(frozen=True)
class SDARConfig:
    """The published sizes (defaults) and this chip's share."""

    hidden_size: int = 2048
    moe_intermediate_size: int = 768
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    router_experts: int = 128  # the router's width: every expert of the model
    experts_here: int = 16  # held on this chip ...
    expert_offset: int = 0  # ... from this one on
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    num_hidden_layers: int = 48
    vocab_size: int = 18992
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    block_length: int = 4  # positions denoised together: the mask's block, and a block launch's width
    expert_chunk_rows: int = experts_op.CHUNK_ROWS  # as models/axk1.py's
    first_k_dense_replace = 0  # no leading dense layer (what ``stack_layers`` asks)

    @classmethod
    def from_dict(cls, doc: dict) -> "SDARConfig":
        doc = dict(doc)
        doc.pop("precision", None)  # the serving policy's, not a size
        unknown = set(doc) - _PUBLISHED
        if unknown:
            raise KeyError(f"sdar_moe model config: unknown keys {sorted(unknown)}")
        cfg = cls(**doc)
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError("sdar_moe model config: num_attention_heads is a multiple of num_key_value_heads")
        return cfg

    @property
    def yarn(self) -> rope.YarnConfig:
        """No scaling: at factor 1 ops/rope.py's tables are the plain rotary embedding."""
        return rope.YarnConfig(dim=self.head_dim, theta=float(self.rope_theta), factor=1.0)


Config = SDARConfig  # what pipelines/lm.py asks of a model module


def init_params(key, cfg: SDARConfig) -> dict:
    """The program's own initialisation (an entry without a weights
    file serves it): the layout a ``weights.msgpack`` has, layer by
    layer under ``layers/<i>``. ``attn/qkv`` holds the query heads'
    columns, then the key heads', then the value heads'."""
    d, hd = cfg.hidden_size, cfg.head_dim
    h, g = cfg.num_attention_heads, cfg.num_key_value_heads
    keys = jax.random.split(key, cfg.num_hidden_layers + 2)
    layers = {}
    for i in range(cfg.num_hidden_layers):
        k = jax.random.split(keys[i], 4)
        layers[str(i)] = {
            "norm1": jnp.ones((d,), jnp.float32),
            "norm2": jnp.ones((d,), jnp.float32),
            "attn": {
                "qkv": axk1._normal(k[0], (d, (h + 2 * g) * hd), d**-0.5),
                "q_norm": jnp.ones((hd,), jnp.float32),
                "k_norm": jnp.ones((hd,), jnp.float32),
                "o": axk1._normal(k[1], (h * hd, d), 0.5 * (h * hd) ** -0.5),
            },
            "router": axk1._normal(k[2], (d, cfg.router_experts), 1.5 * d**-0.5),
            "experts": axk1._mlp(k[3], d, cfg.moe_intermediate_size, (cfg.experts_here,)),
        }
    return {
        "embed": axk1._normal(keys[-2], (cfg.vocab_size, d), 1.0),
        "head": axk1._normal(keys[-1], (d, cfg.vocab_size), 2.0 * d**-0.5),
        "final_norm": jnp.ones((d,), jnp.float32),
        "layers": layers,
    }


def abstract_params(cfg: SDARConfig):
    """The tree's shapes and types, nothing built."""
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


def empty_cache(cfg: SDARConfig, slots: int, slot_len: int):
    """The device state of ``slots`` sessions: the keys and the values
    of every key/value head, a row a position."""
    shape = (cfg.num_hidden_layers, slots, slot_len, cfg.num_key_value_heads * cfg.head_dim)
    return {"k": jnp.zeros(shape, jnp.bfloat16), "v": jnp.zeros(shape, jnp.bfloat16)}


# -- the forward pass -----------------------------------------------------------


def _qkv(cfg, p, x, cos, sin):
    """``x [R, n, D]`` bfloat16 normalised -> queries ``[R, n, H, d]``,
    keys and values ``[R, n, G, d]`` bfloat16; queries and keys
    normalised over each head's values, then rotated."""
    rows, n, _ = x.shape
    h, g, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    qkv = x @ p["qkv"]
    q = qkv[..., : h * d].reshape(rows, n, h, d)
    k = qkv[..., h * d : (h + g) * d].reshape(rows, n, g, d)
    v = qkv[..., (h + g) * d :].reshape(rows, n, g, d)
    cos, sin = cos[:, :, None], sin[:, :, None]
    q = rope.apply_rope(axk1._rms(q, p["q_norm"], cfg.rms_norm_eps), cos, sin)
    k = rope.apply_rope(axk1._rms(k, p["k_norm"], cfg.rms_norm_eps), cos, sin)
    return q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v


def _forward(cfg, weights, kv, tokens, pos, valid, attend):
    """The layers over ``tokens [R, n]`` at positions ``pos [R, n]``
    (``valid``: no pad token). ``attend(q, k, v, kv, layer) -> (out [R,
    n, H * d], kv)`` is the launch kind's attention, and what it writes.
    Returns the final stream ``[R, n, D]`` float32, ``expert_rows
    [layers, experts_here]`` int32 and the cache."""
    bf = jnp.bfloat16
    eps = cfg.rms_norm_eps
    rows, n = tokens.shape
    cos, sin = rope.rope_tables(pos, cfg.yarn)
    flat_valid = valid.reshape(-1)

    def body(carry, xs):
        hidden, kv = carry
        p, layer = xs
        q, k, v = _qkv(cfg, p["attn"], axk1._rms(hidden, p["norm1"], eps).astype(bf), cos, sin)
        a, kv = attend(q, k, v, kv, layer)
        hidden = hidden + (a @ p["attn"]["o"]).astype(jnp.float32)
        x32 = axk1._rms(hidden, p["norm2"], eps).reshape(rows * n, -1)
        idx, gates = experts_op.route(
            x32, p["router"], cfg.num_experts_per_tok, 1.0, cfg.norm_topk_prob, softmax=True,
        )
        y, seen = experts_op.routed_experts(
            x32.astype(bf), flat_valid, idx, gates, p["experts"], cfg.expert_offset, cfg.expert_chunk_rows,
        )
        return (hidden + y.reshape(hidden.shape), kv), seen

    hidden = weights["embed"][tokens].astype(jnp.float32)
    (hidden, kv), expert_rows = jax.lax.scan(
        body, (hidden, kv), (weights["moe"], jnp.arange(cfg.num_hidden_layers, dtype=jnp.int32)),
    )
    return hidden, expert_rows, kv


def _logits(cfg, weights, hidden):
    return jnp.dot(
        axk1._rms(hidden, weights["final_norm"], cfg.rms_norm_eps).astype(jnp.bfloat16),
        weights["head"], preferred_element_type=jnp.float32,
    )


def extend(cfg: SDARConfig, weights: dict, kv, tokens, slots, positions, lengths):
    """Append ``lengths[0]`` of ``tokens [1, n]`` (whole blocks) to the
    session in slot ``slots[0]`` from ``positions[0]`` on. Returns
    ``logits [1, V]`` float32 of the last appended position,
    ``expert_rows`` and the cache. A launch of pad tokens alone (length
    0: a compile) writes at position 0 of a slot that holds nothing a
    session has appended."""
    rows, n = tokens.shape
    assert rows == 1, "an extend launch holds one session"
    offsets = jnp.arange(n, dtype=jnp.int32)[None, :]
    pos = positions[:, None] + offsets
    scale = cfg.head_dim**-0.5

    def attend(q, k, v, kv, layer):
        kv, slot_rows = block_attention.write_span(kv, layer, slots[0], positions[0], k[0], v[0])
        with jax.named_scope("lm_attention"):
            out = block_attention.prefill_attention(q[0], slot_rows, pos[0], cfg.block_length, scale)
        return out[None], kv

    hidden, expert_rows, kv = _forward(cfg, weights, kv, tokens, pos, offsets < lengths[:, None], attend)
    last = jnp.clip(lengths - 1, 0, n - 1)
    final = jnp.take_along_axis(hidden, last[:, None, None], axis=1)[:, 0]
    return _logits(cfg, weights, final), expert_rows, kv


def block(cfg: SDARConfig, weights: dict, kv, tokens, slots, positions, lengths, commit):
    """One block ``tokens [R, B]`` of each of R sessions at ``positions
    [R]`` (its slot's length); ``lengths [R]`` is B for a real row and 0
    for a pad row, ``commit [R]`` non-zero for a row that writes its
    block. Returns ``logits [R * B, V]`` float32 of every position,
    ``expert_rows`` and the cache."""
    rows, b = tokens.shape
    offsets = jnp.arange(b, dtype=jnp.int32)[None, :]
    pos = positions[:, None] + offsets
    real = lengths > 0
    n_slots, slot_len = kv["k"].shape[1:3]
    where = jnp.where((real & (commit != 0))[:, None], pos, slot_len)  # past the slot: dropped
    attended = jnp.where(real, slots, n_slots)  # a pad row attends nowhere
    scale = cfg.head_dim**-0.5

    def attend(q, k, v, kv, layer):
        # written first, read after: the mask hides the block's own rows of the cache
        # either way, and every layer's update of the cache stays in place
        kv = block_attention.write_rows(kv, layer, slots, where, k, v)
        with jax.named_scope("lm_block_attention"):
            out = block_attention.block_attention(q, k, v, kv, layer, attended, positions, scale)
        return out, kv

    valid = jnp.broadcast_to(real[:, None], (rows, b))
    hidden, expert_rows, kv = _forward(cfg, weights, kv, tokens, pos, valid, attend)
    return _logits(cfg, weights, hidden.reshape(rows * b, -1)), expert_rows, kv
