"""Ling-3.0-flash's block (``family: bailing_hybrid``;
https://huggingface.co/inclusionAI/Ling-3.0-flash, ``model_type``
``bailing_hybrid``): layers of Kimi Delta Attention (KDA,
ops/delta_attention.py) with a latent-attention (MLA) layer among every
``layer_group_size`` of them, ``first_k_dense_replace`` leading dense
SwiGLU layers, then group-limited sigmoid-routed experts beside a shared
one. Served as one chip's share of a wider deployment: ``experts_here``
of the ``router_experts`` experts of each layer, ``vocab_size`` rows of
embedding and head, the layers the entry lists; every width is the
published one. Which layer is of which kind is the entry's
``layer_types`` (a list, one entry a layer held: ``kda`` or ``mla``),
never the family's name.

A sibling of models/axk1.py, not a switch inside it. The MLA layer IS
that module's block (``axk1._attention`` with ``q_lora_rank: null`` and
a head-wise gate, both of which it takes from the layer's parameters),
and the dense and expert halves of a layer, ``_rms``, the seeded
initialisation's helpers, ``stack_group``, ops/experts.py and
ops/rope.py are shared. What cannot be: the cache is three arrays of two
kinds (below), the layers are of two kinds in a fixed period, so the
ONE scan over identical layers becomes a scan over PERIODS (a period's
KDA layers under an inner scan, its MLA layer after them: each kernel
stays one op name in a device trace), and 35 of 42 layers hold a state
that a session's slot must carry, reset and lose (runtime/sessions.py).

The cache, a dict of three arrays donated together:

  * ``latent [mla layers, slots, slot_len, 640]`` bfloat16: models/axk1.py's
    rows, a position each: what grows with a session's length;
  * ``state [kda layers, slots, heads, d_v, d_k]`` float32: a session's
    recurrent state, VALUE-major (the decay scales lanes), 2.1 MB a layer
    whatever the session's length;
  * ``conv [kda layers, slots, 3 * (3 * heads * d)]`` bfloat16: the last
    three rows that entered the short convolution, side by side in ONE
    row of whole lane tiles (with the three rows on an axis of their own
    the chip's compiler lays a fresh array out with the slots minor to
    them, and channel/staged.py refuses a state that is not row-major).

A row admitted at position 0 starts from zero state and zero tail: the
launch reads zeros for it, nothing is cleared beforehand. Pad rows and
slots that are not in a launch keep theirs bit for bit. One operation,
:func:`extend`, in models/axk1.py's two launch shapes: many tokens of ONE
session (the chunkwise form from the slot's state; expanded latent
attention) or one token of each of several (the recurrent form; absorbed
latent attention).

KDA's decay sums, solve and state are float32, as are the router, norms,
softmax and logits; everything a matrix product reads is bfloat16.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from triton_client_tpu.models import axk1
from triton_client_tpu.ops import delta_attention, latent_attention
from triton_client_tpu.ops import experts as experts_op
from triton_client_tpu.ops import rope

_PUBLISHED = {
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads", "head_dim",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "short_conv_kernel_size", "kda_lower_bound", "layer_types",
    "router_experts", "experts_here", "expert_offset", "n_shared_experts", "num_experts_per_tok",
    "norm_topk_prob", "routed_scaling_factor", "n_group", "topk_group", "num_hidden_layers",
    "first_k_dense_replace", "vocab_size", "rms_norm_eps", "rope_theta", "expert_chunk_rows",
}


@dataclasses.dataclass(frozen=True)
class LingConfig:
    """The published sizes (defaults) and this chip's share."""

    hidden_size: int = 2560
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    num_attention_heads: int = 32
    head_dim: int = 128  # a KDA head's keys and values
    q_lora_rank: int | None = None  # no query compression in the MLA layers
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0  # a key channel's log decay a token lies in [this, 0)
    #: the kind of each layer held, in order: the dense layers, then whole periods that end in an MLA layer
    layer_types: tuple = ("kda",) + (("kda",) * 5 + ("mla",)) * 2
    router_experts: int = 512
    experts_here: int = 64
    expert_offset: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    n_group: int = 8
    topk_group: int = 4
    num_hidden_layers: int = 13
    first_k_dense_replace: int = 1
    vocab_size: int = 19648
    rms_norm_eps: float = 1e-6
    rope_theta: float = 6000000.0
    expert_chunk_rows: int = experts_op.CHUNK_ROWS  # as models/axk1.py's
    index_topk = 0  # attention reads every cached position (what ``axk1._attention`` asks)
    group_limited = True  # ``noaux_tc``: the router chooses under the group limit, by scores + bias

    @classmethod
    def from_dict(cls, doc: dict) -> "LingConfig":
        doc = dict(doc)
        doc.pop("precision", None)  # the serving policy's, not a size
        unknown = set(doc) - _PUBLISHED
        if unknown:
            raise KeyError(f"bailing_hybrid model config: unknown keys {sorted(unknown)}")
        if "layer_types" in doc:
            doc["layer_types"] = tuple(doc["layer_types"])
        cfg = cls(**doc)
        types, dense = cfg.layer_types, cfg.first_k_dense_replace
        if len(types) != cfg.num_hidden_layers or set(types) - {"kda", "mla"}:
            raise ValueError(
                f"bailing_hybrid model config: layer_types names each of the {cfg.num_hidden_layers} layers "
                f"held, kda or mla; got {list(types)}"
            )
        period = cfg.period
        if types[dense:] != (("kda",) * (period - 1) + ("mla",)) * cfg.periods or not cfg.periods:
            raise ValueError(
                "bailing_hybrid model config: the layers after the dense ones are whole periods of KDA "
                f"layers that end in one MLA layer; got {list(types[dense:])}"
            )
        if cfg.short_conv_kernel_size != delta_attention.CONV_WIDTH:
            raise ValueError(f"bailing_hybrid model config: short_conv_kernel_size is {delta_attention.CONV_WIDTH}")
        return cfg

    @property
    def period(self) -> int:
        """Layers a period holds: up to and with the first MLA layer after the dense ones."""
        rest = self.layer_types[self.first_k_dense_replace :]
        return rest.index("mla") + 1 if "mla" in rest else max(len(rest), 1)

    @property
    def periods(self) -> int:
        return (self.num_hidden_layers - self.first_k_dense_replace) // self.period

    @property
    def kda_layers(self) -> int:
        return self.layer_types.count("kda")

    @property
    def mla_layers(self) -> int:
        return self.layer_types.count("mla")

    @property
    def conv_width(self) -> int:
        """Values of a row that enters the short convolution: every head's query, key and value."""
        return 3 * self.num_attention_heads * self.head_dim

    @property
    def yarn(self) -> rope.YarnConfig:
        """``rope_scaling: null``: at factor 1 ops/rope.py's tables are the plain rotary embedding."""
        return rope.YarnConfig(dim=self.qk_rope_head_dim, theta=float(self.rope_theta), factor=1.0)

    cache_width = axk1.AXK1Config.cache_width
    cache_row = axk1.AXK1Config.cache_row
    softmax_scale = axk1.AXK1Config.softmax_scale

    def state_bytes(self, slots: int = 1) -> int:
        """What ``slots`` sessions' recurrent state and convolution tails take, whatever their lengths."""
        h, d = self.num_attention_heads, self.head_dim
        return self.kda_layers * slots * (h * d * d * 4 + (delta_attention.CONV_WIDTH - 1) * self.conv_width * 2)

    def step_key_blocks(self, slot_len: int) -> tuple:
        """As ``AXK1Config.step_key_blocks``: the MLA layers alone attend over a slot's rows."""
        return latent_attention.step_block(slot_len), self.mla_layers


Config = LingConfig  # what pipelines/lm.py asks of a model module


def _kda_params(key, cfg: LingConfig) -> dict:
    d, h, hd = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
    k = jax.random.split(key, 6)
    return {
        "qkv": axk1._normal(k[0], (d, cfg.conv_width), d**-0.5),
        "conv": axk1._normal(k[1], (delta_attention.CONV_WIDTH, cfg.conv_width), 0.5),
        "f": axk1._normal(k[2], (d, h * hd), d**-0.5),
        "A_log": jnp.zeros((h,), jnp.float32),
        "dt_bias": jnp.zeros((h, hd), jnp.float32),
        "b": axk1._normal(k[3], (d, h), d**-0.5),
        "g": axk1._normal(k[4], (d, h), d**-0.5),
        "o_norm": jnp.ones((hd,), jnp.float32),
        "o": axk1._normal(k[5], (h * hd, d), 0.5 * (h * hd) ** -0.5),
    }


def _mla_params(key, cfg: LingConfig) -> dict:
    d, h = cfg.hidden_size, cfg.num_attention_heads
    k = jax.random.split(key, 5)
    return {
        **axk1._query_params(k[0], k[0], cfg),
        "kv_a": axk1._normal(k[1], (d, cfg.cache_width), d**-0.5),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
        "kv_b": axk1._normal(
            k[2], (cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)), cfg.kv_lora_rank**-0.5
        ),
        "gate": axk1._normal(k[3], (d, h), d**-0.5),
        "o": axk1._normal(k[4], (h * cfg.v_head_dim, d), 0.5 * (h * cfg.v_head_dim) ** -0.5),
    }


def init_params(key, cfg: LingConfig) -> dict:
    """The program's own initialisation (an entry without a weights
    file serves it): the layout a ``weights.msgpack`` has, layer by
    layer under ``layers/<i>``; ``attn`` holds a KDA layer's parameters
    (``qkv``: the query heads' columns, then the key heads', then the
    value heads'; ``conv [4, 3 H d]``: its last row multiplies the
    token's own position) or an MLA layer's."""
    d = cfg.hidden_size
    keys = jax.random.split(key, cfg.num_hidden_layers + 2)
    layers = {}
    for i, kind in enumerate(cfg.layer_types):
        k = jax.random.split(keys[i], 5)
        layer = {
            "norm1": jnp.ones((d,), jnp.float32),
            "norm2": jnp.ones((d,), jnp.float32),
            "attn": (_kda_params if kind == "kda" else _mla_params)(k[0], cfg),
        }
        if i < cfg.first_k_dense_replace:
            layer["mlp"] = axk1._mlp(k[1], d, cfg.intermediate_size)
        else:
            layer["router"] = axk1._normal(k[2], (d, cfg.router_experts), 1.5 * d**-0.5)
            layer["router_bias"] = jnp.zeros((cfg.router_experts,), jnp.float32)
            layer["shared"] = axk1._mlp(k[3], d, cfg.moe_intermediate_size * cfg.n_shared_experts)
            layer["experts"] = axk1._mlp(k[4], d, cfg.moe_intermediate_size, (cfg.experts_here,))
        layers[str(i)] = layer
    return {
        "embed": axk1._normal(keys[-2], (cfg.vocab_size, d), 1.0),
        "head": axk1._normal(keys[-1], (d, cfg.vocab_size), 2.0 * d**-0.5),
        "final_norm": jnp.ones((d,), jnp.float32),
        "layers": layers,
    }


def abstract_params(cfg: LingConfig):
    """The tree's shapes and types, nothing built."""
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


def stack_layers(tree: dict, cfg: LingConfig) -> dict:
    """The served form of a loaded tree: the dense layers as they are,
    the periods' KDA layers stacked leaf by leaf on ONE leading axis
    (``periods * (period - 1)``: the launch program views it as
    ``[periods, period - 1, ...]``, which is no copy there) and their MLA
    layers on another. TAKES the per-layer leaves out of ``tree``; each
    stack is one program (``axk1.stack_layers`` says why)."""
    layers = tree["layers"]
    dense = [layers.pop(str(i)) for i in range(cfg.first_k_dense_replace)]
    rest = range(cfg.first_k_dense_replace, cfg.num_hidden_layers)
    of = lambda kind: [layers.pop(str(i)) for i in rest if cfg.layer_types[i] == kind]
    return {
        "embed": tree["embed"], "head": tree["head"], "final_norm": tree["final_norm"], "dense": dense,
        "kda": axk1.stack_group(of("kda"), one_program=True),
        "mla": axk1.stack_group(of("mla"), one_program=True),
    }


def empty_cache(cfg: LingConfig, slots: int, slot_len: int) -> dict:
    """The device state of ``slots`` sessions (module docstring)."""
    h, d = cfg.num_attention_heads, cfg.head_dim
    return {
        "latent": jnp.zeros((cfg.mla_layers, slots, slot_len, cfg.cache_row), jnp.bfloat16),
        "state": jnp.zeros((cfg.kda_layers, slots, h, d, d), jnp.float32),
        "conv": jnp.zeros((cfg.kda_layers, slots, (delta_attention.CONV_WIDTH - 1) * cfg.conv_width), jnp.bfloat16),
    }


# -- the forward pass -----------------------------------------------------------


def _kda(cfg, p, x, state, conv, layer, slots, positions, lengths):
    """``x [B, n, D]`` bfloat16 normalised; ``layer`` the KDA layer's
    place in ``state`` and ``conv``. Moves the rows' slots on by their
    tokens and returns the attention output ``[B, n, D]``, ``state`` and
    ``conv``. A row at position 0 reads a zero state and tail; a row of
    length 0 writes nothing."""
    b, n, _ = x.shape
    h, d, bf = cfg.num_attention_heads, cfg.head_dim, jnp.bfloat16
    n_slots = state.shape[1]
    fresh, real = positions == 0, lengths > 0
    valid = jnp.arange(n)[None, :] < lengths[:, None]  # [B, n]
    pre = x @ p["qkv"]
    f = jnp.dot(x, p["f"], preferred_element_type=jnp.float32).reshape(b, n, h, d)
    g = jnp.where(valid[..., None, None], delta_attention.log_decay(f, p["A_log"], p["dt_bias"], cfg.kda_lower_bound), 0.0)
    beta = jnp.where(valid[..., None], jax.nn.sigmoid((x @ p["b"]).astype(jnp.float32)), 0.0)
    tails = conv[layer, jnp.minimum(slots, n_slots - 1)].reshape(b, delta_attention.CONV_WIDTH - 1, -1)
    tails = jnp.where(fresh[:, None, None], 0, tails)
    with jax.named_scope("lm_kda_conv"):
        qkv, full = delta_attention.short_conv(tails, pre, p["conv"])
    # the last three rows that entered the convolution, pad tokens left out
    tails = jax.vmap(lambda rows, at: jax.lax.dynamic_slice_in_dim(rows, at, tails.shape[1]))(full, lengths)
    conv = conv.at[layer, jnp.where(real, slots, n_slots)].set(tails.reshape(b, -1).astype(conv.dtype), mode="drop")
    q, k, v = (qkv[..., i * h * d : (i + 1) * h * d].reshape(b, n, h, d) for i in range(3))
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    q, k = unit(q) * d**-0.5, unit(k)
    if n == 1:
        with jax.named_scope("lm_kda_step"):
            out, state = delta_attention.step(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], real, fresh, state, layer, slots)
        out = out[:, None]
    else:
        assert b == 1, "a launch of many tokens a row holds one session"
        at = (layer, slots[0], 0, 0, 0)
        old = jax.lax.dynamic_slice(state, at, (1, 1, *state.shape[2:]))[0, 0]
        out, new = delta_attention.extend(q[0], k[0], v[0], g[0], beta[0], jnp.where(fresh[0], 0.0, old))
        state = jax.lax.dynamic_update_slice(state, jnp.where(real[0], new, old)[None, None], at)
        out = out[None]
    gate = jax.nn.sigmoid((x @ p["g"]).astype(jnp.float32))[..., None]
    out = axk1._rms(out, p["o_norm"], cfg.rms_norm_eps) * gate
    return out.astype(bf).reshape(b, n, h * d) @ p["o"], state, conv


def _feed(cfg, p, hidden, valid, at=None):
    """A layer's second half: the dense SwiGLU, or the routed experts
    held here beside the shared one (with ``at``, ``p["experts"]`` are
    the stacks of the layer's group and ``at`` its place in them: a step
    launch then reads single experts from the stack, ops/experts.py).
    Returns the stream and the rows each held
    expert saw (None for a dense layer)."""
    x32 = axk1._rms(hidden, p["norm2"], cfg.rms_norm_eps)
    x = x32.astype(jnp.bfloat16)
    if "mlp" in p:
        return hidden + axk1._swiglu(x, p["mlp"]).astype(jnp.float32), None
    b, n, d = x.shape
    idx, gates = experts_op.route(
        x32.reshape(b * n, d), p["router"], cfg.num_experts_per_tok, cfg.routed_scaling_factor,
        cfg.norm_topk_prob, bias=p["router_bias"], n_group=cfg.n_group, topk_group=cfg.topk_group,
    )
    y, rows = experts_op.routed_experts(
        x.reshape(b * n, d), valid.reshape(-1), idx, gates, p["experts"], cfg.expert_offset, cfg.expert_chunk_rows, at,
    )
    return hidden + y.reshape(b, n, d) + axk1._swiglu(x, p["shared"]).astype(jnp.float32), rows


def extend(cfg: LingConfig, weights: dict, cache: dict, tokens, slots, positions, lengths):
    """Append ``lengths[b]`` of ``tokens [B, n]`` to the session in slot
    ``slots[b]`` from ``positions[b]`` on; ``cache`` in
    :func:`empty_cache`'s form. Returns ``logits [B, V]`` float32 of
    each row's last appended position, ``expert_rows [expert layers,
    experts_here]`` int32 and the cache."""
    b, n = tokens.shape
    offsets = jnp.arange(n, dtype=jnp.int32)[None, :]
    valid = offsets < lengths[:, None]
    pos = positions[:, None] + offsets
    cos, sin = rope.rope_tables(pos, cfg.yarn)
    bf, eps = jnp.bfloat16, cfg.rms_norm_eps

    def kda_layer(p, hidden, cache, i, at=None):
        x = axk1._rms(hidden, p["norm1"], eps).astype(bf)
        a, state, conv = _kda(cfg, p["attn"], x, cache["state"], cache["conv"], i, slots, positions, lengths)
        hidden, rows = _feed(cfg, p, hidden + a.astype(jnp.float32), valid, at)
        return hidden, {**cache, "state": state, "conv": conv}, rows

    def mla_layer(p, hidden, cache, i, at=None):
        x = axk1._rms(hidden, p["norm1"], eps).astype(bf)
        a, latent, _ = axk1._attention(cfg, p["attn"], x, cache["latent"], None, i, slots, pos, valid, cos, sin)
        hidden, rows = _feed(cfg, p, hidden + a.astype(jnp.float32), valid, at)
        return hidden, {**cache, "latent": latent}, rows

    hidden = weights["embed"][tokens].astype(jnp.float32)
    kda_at = mla_at = 0
    for p, kind in zip(weights["dense"], cfg.layer_types):
        if kind == "kda":
            hidden, cache, _ = kda_layer(p, hidden, cache, kda_at)
            kda_at += 1
        else:
            hidden, cache, _ = mla_layer(p, hidden, cache, mla_at)
            mla_at += 1
    periods, inner = cfg.periods, cfg.period - 1
    # a group's experts go to each of its layers WHOLE beside the layer's place in them: sliced by the scan
    # like the rest, a layer's experts would be written out before the loop over the chosen ones could read one
    apart = lambda group: ({n: leaf for n, leaf in group.items() if n != "experts"}, group["experts"])
    (kda, kda_experts), (mla, mla_experts) = apart(weights["kda"]), apart(weights["mla"])

    def period(carry, xs):
        p_mla, j = xs

        def one(carry, i):
            # a layer of the KDA stack by its place: the stack itself stays where it is (as ``xs`` of this
            # inner scan the outer one would first copy a period's layers out of it, gigabytes a launch)
            at = j * inner + i
            p = jax.tree_util.tree_map(lambda a: jax.lax.dynamic_index_in_dim(a, at, 0, False), kda)
            hidden, cache, rows = kda_layer({**p, "experts": kda_experts}, *carry, kda_at + at, at)
            return (hidden, cache), rows

        carry, kda_rows = jax.lax.scan(one, carry, jnp.arange(inner, dtype=jnp.int32))
        hidden, cache, mla_rows = mla_layer({**p_mla, "experts": mla_experts}, *carry, mla_at + j, j)
        return (hidden, cache), jnp.concatenate([kda_rows, mla_rows[None]])

    (hidden, cache), expert_rows = jax.lax.scan(
        period, (hidden, cache), (mla, jnp.arange(periods, dtype=jnp.int32)),
    )
    last = jnp.clip(lengths - 1, 0, n - 1)
    final = jnp.take_along_axis(hidden, last[:, None, None], axis=1)[:, 0]
    logits = jnp.dot(
        axk1._rms(final, weights["final_norm"], eps).astype(bf), weights["head"], preferred_element_type=jnp.float32,
    )
    return logits, expert_rows.reshape(-1, cfg.experts_here), cache
