"""SmallThinker's block (``family: smallthinker``;
SmallThinker-21BA3B-Instruct,
https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct,
``model_name`` ``smallthinker_21b_instruct``): grouped-query attention
in layers of TWO kinds in a fixed period, one FULL layer WITHOUT any
positional encoding (a position reads every earlier one) and then WINDOW
layers with rotary positions (a position reads the latest
``sliding_window_size`` positions, its own among them); a router that
reads the layer's INPUT, ahead of the attention and of its norm; and
ReLU-gated experts with no shared one. Served as ONE pipeline stage of a
deployment that gives each layer a whole chip: every expert of a layer
and the whole vocabulary are here, the layers the entry lists; every
width is the published one. Which layer is of which kind is the entry's
``layer_types`` (a list, one entry a layer held: ``full`` or
``window``), never the family's name.

A sibling of models/sdar.py, not a switch inside it: the two share the
row layout of the key/value cache and its extend
(ops/block_attention.py: ``write_span``, ``prefill_attention``, whose
block mask at block 1 is the causal one), the embedding, ``axk1._rms``,
the seeded initialisation's helpers, ``stack_group``, ops/experts.py
(``route`` with ``softmax``, ``routed_experts``) and ops/rope.py. What
cannot be: the layers are of two kinds, so the ONE scan over identical
layers becomes models/ling.py's scan over PERIODS (a period's full layer,
then its window layers under an inner scan: each kind stays one op name
in a device trace), and a session's slot keeps rows in TWO GEOMETRIES.

The cache, a dict of two key/value caches donated together, each a dict
of keys ``k`` and values ``v`` in ops/block_attention.py's layout (a
position ONE row of ``kv_heads * head_dim`` bfloat16):

  * ``full [full layers, slots, slot_len, 512]``: a row a position, as
    long as the session;
  * ``window [window layers, slots, window_ring, 512]``: a RING, position
    ``p`` at row ``p % window_ring``. ``window_ring`` is the window plus
    the longest extend launch, so that a launch writes its own keys first
    and still finds every key it may see (``Config.row_geometries``
    states both to runtime/sessions.py, which holds the extend to it).
    A session longer than the ring loses nothing it may still read.

Positions, never contents, decide what a query sees, so a slot is reused
without touching the device: whatever an earlier session or an earlier
lap of the ring left in a row is out of sight until the row is written
again. One operation, :func:`extend`, in models/axk1.py's two launch
shapes: many tokens of ONE session, or one token of each of several (a
step launch: every session's ring or rows read in place, and of the
experts only those some row chose, ops/experts.py).

The router, norms, softmax and logits are float32; everything a matrix
product reads is bfloat16.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from triton_client_tpu.models import axk1
from triton_client_tpu.ops import block_attention
from triton_client_tpu.ops import experts as experts_op
from triton_client_tpu.ops import rope

_PUBLISHED = {
    "hidden_size", "moe_ffn_hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "moe_num_primary_experts", "moe_num_active_primary_experts", "norm_topk_prob", "layer_types",
    "sliding_window_size", "window_ring", "num_hidden_layers", "vocab_size", "rms_norm_eps", "rope_theta",
    "expert_chunk_rows",
}


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    """The published sizes (defaults) and the layers held."""

    hidden_size: int = 2560
    moe_ffn_hidden_size: int = 768
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_num_primary_experts: int = 64  # all of them are held
    moe_num_active_primary_experts: int = 6
    norm_topk_prob: bool = True
    #: the kind of each layer held, in order: whole periods of one full layer and its window layers
    layer_types: tuple = ("full", "window", "window", "window") * 3
    sliding_window_size: int = 4096  # the keys a window layer's query reads, its own among them
    window_ring: int = 4096 + 2048  # rows a window layer keeps a session: the window and the longest extend launch
    num_hidden_layers: int = 12
    vocab_size: int = 151936
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1500000.0
    expert_chunk_rows: int = experts_op.CHUNK_ROWS  # as models/axk1.py's

    @classmethod
    def from_dict(cls, doc: dict) -> "SmallThinkerConfig":
        doc = dict(doc)
        doc.pop("precision", None)  # the serving policy's, not a size
        unknown = set(doc) - _PUBLISHED
        if unknown:
            raise KeyError(f"smallthinker model config: unknown keys {sorted(unknown)}")
        if "layer_types" in doc:
            doc["layer_types"] = tuple(doc["layer_types"])
        cfg = cls(**doc)
        types = cfg.layer_types
        if len(types) != cfg.num_hidden_layers or set(types) - {"full", "window"}:
            raise ValueError(
                f"smallthinker model config: layer_types names each of the {cfg.num_hidden_layers} layers held, "
                f"full or window; got {list(types)}"
            )
        if not cfg.periods or types != (("full",) + ("window",) * (cfg.period - 1)) * cfg.periods:
            raise ValueError(
                "smallthinker model config: the layers held are whole periods of one full layer and the window "
                f"layers that follow it; got {list(types)}"
            )
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError("smallthinker model config: num_attention_heads is a multiple of num_key_value_heads")
        if cfg.window_ring <= cfg.sliding_window_size:
            raise ValueError("smallthinker model config: window_ring is the window and the longest extend launch")
        return cfg

    @property
    def period(self) -> int:
        """Layers a period holds: a full layer and the window layers up to the next one."""
        rest = self.layer_types[1:]
        return rest.index("full") + 1 if "full" in rest else len(self.layer_types)

    @property
    def periods(self) -> int:
        return self.num_hidden_layers // self.period if self.layer_types[:1] == ("full",) else 0

    @property
    def yarn(self) -> rope.YarnConfig:
        """``rope_scaling: null``: at factor 1 ops/rope.py's tables are the plain rotary embedding."""
        return rope.YarnConfig(dim=self.head_dim, theta=float(self.rope_theta), factor=1.0)

    def row_geometries(self, slot_len: int) -> tuple:
        """What a session's slot keeps, a geometry of rows a kind of
        layer (runtime/sessions.py ``RowGeometry``'s fields): its name,
        its layers, the rows a slot has in each, the positions back a
        query reads (0: all) and a row's bytes, keys and values."""
        row = 2 * 2 * self.num_key_value_heads * self.head_dim
        return (
            ("full", self.layer_types.count("full"), slot_len, 0, row),
            ("window", self.layer_types.count("window"), self.window_ring, self.sliding_window_size, row),
        )


Config = SmallThinkerConfig  # what pipelines/lm.py asks of a model module


def init_params(key, cfg: SmallThinkerConfig) -> dict:
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
                "o": axk1._normal(k[1], (h * hd, d), 0.5 * (h * hd) ** -0.5),
            },
            "router": axk1._normal(k[2], (d, cfg.moe_num_primary_experts), 1.5 * d**-0.5),
            "experts": axk1._mlp(k[3], d, cfg.moe_ffn_hidden_size, (cfg.moe_num_primary_experts,)),
        }
    return {
        "embed": axk1._normal(keys[-2], (cfg.vocab_size, d), 1.0),
        "head": axk1._normal(keys[-1], (d, cfg.vocab_size), 2.0 * d**-0.5),
        "final_norm": jnp.ones((d,), jnp.float32),
        "layers": layers,
    }


def abstract_params(cfg: SmallThinkerConfig):
    """The tree's shapes and types, nothing built."""
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


def stack_layers(tree: dict, cfg: SmallThinkerConfig) -> dict:
    """The served form of a loaded tree: the periods' full layers
    stacked leaf by leaf on one leading axis and their window layers on
    another (``periods * (period - 1)``: models/ling.py's form). TAKES
    the per-layer leaves out of ``tree``; each stack is one program
    (``axk1.stack_layers`` says why)."""
    layers = tree["layers"]
    of = lambda kind: [layers.pop(str(i)) for i in range(cfg.num_hidden_layers) if cfg.layer_types[i] == kind]
    return {
        "embed": tree["embed"], "head": tree["head"], "final_norm": tree["final_norm"],
        "full": axk1.stack_group(of("full"), one_program=True),
        "window": axk1.stack_group(of("window"), one_program=True),
    }


def empty_cache(cfg: SmallThinkerConfig, slots: int, slot_len: int) -> dict:
    """The device state of ``slots`` sessions (module docstring)."""

    def rows(layers, length):
        shape = (layers, slots, length, cfg.num_key_value_heads * cfg.head_dim)
        return {"k": jnp.zeros(shape, jnp.bfloat16), "v": jnp.zeros(shape, jnp.bfloat16)}

    return {name: rows(layers, length) for name, layers, length, _, _ in cfg.row_geometries(slot_len)}


# -- the forward pass -----------------------------------------------------------


def _layer(cfg, p, hidden, kv, layer, at, slots, pos, lengths, table, window: int):
    """One layer over ``hidden [B, n, D]`` float32 at positions ``pos
    [B, n]``: ``kv`` the cache of the layer's kind and ``layer`` its
    place in it, ``p["experts"]`` the stacks of the kind's layers and
    ``at`` its place in them, ``table`` the rotary tables of a window
    layer (None: no positional encoding), ``window`` 0 for a full layer.
    Returns the stream, ``kv`` and the rows each expert saw."""
    b, n, d_model = hidden.shape
    h, g, d, bf = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, jnp.bfloat16
    valid = jnp.arange(n)[None, :] < lengths[:, None]
    # the router reads the layer's input, ahead of the attention and of its norm
    idx, gates = experts_op.route(
        hidden.reshape(b * n, d_model), p["router"], cfg.moe_num_active_primary_experts, 1.0,
        cfg.norm_topk_prob, softmax=True,
    )
    qkv = axk1._rms(hidden, p["norm1"], cfg.rms_norm_eps).astype(bf) @ p["attn"]["qkv"]
    q = qkv[..., : h * d].reshape(b, n, h, d)
    k = qkv[..., h * d : (h + g) * d].reshape(b, n, g, d)
    v = qkv[..., (h + g) * d :].reshape(b, n, g, d)
    if table is not None:
        cos, sin = (a[:, :, None] for a in table)
        rotate = lambda a: rope.apply_rope(a.astype(jnp.float32), cos, sin).astype(bf)
        q, k = rotate(q), rotate(k)
    scale = d**-0.5
    n_slots, rows = kv["k"].shape[1:3]
    if n == 1:
        # written first, read after, in place: a pad row's place is past the slot, and dropped
        real = lengths > 0
        where = jnp.where(real[:, None], pos % rows, rows)
        kv = block_attention.write_rows(kv, layer, slots, where, k, v)
        with jax.named_scope("lm_window_attention" if window else "lm_attention"):
            a = block_attention.step_attention(
                q[:, 0], kv, layer, jnp.where(real, slots, n_slots), pos[:, 0], scale, window
            )[:, None]
    else:
        assert b == 1, "a launch of many tokens a row holds one session"
        write = block_attention.write_ring if window else block_attention.write_span
        kv, slot_rows = write(kv, layer, slots[0], pos[0, 0], k[0], v[0])
        with jax.named_scope("lm_window_attention" if window else "lm_attention"):
            a = block_attention.prefill_attention(q[0], slot_rows, pos[0], 1, scale, window)[None]
    hidden = hidden + (a @ p["attn"]["o"]).astype(jnp.float32)
    x = axk1._rms(hidden, p["norm2"], cfg.rms_norm_eps).astype(bf).reshape(b * n, d_model)
    y, seen = experts_op.routed_experts(
        x, valid.reshape(-1), idx, gates, p["experts"], 0, cfg.expert_chunk_rows, at, activation=jax.nn.relu,
    )
    return hidden + y.reshape(hidden.shape), kv, seen


def extend(cfg: SmallThinkerConfig, weights: dict, cache: dict, tokens, slots, positions, lengths):
    """Append ``lengths[b]`` of ``tokens [B, n]`` to the session in slot
    ``slots[b]`` from ``positions[b]`` on; ``cache`` in
    :func:`empty_cache`'s form. Returns ``logits [B, V]`` float32 of
    each row's last appended position, ``expert_rows [layers, experts]``
    int32 in the layers' order and the cache. A launch of pad tokens
    alone (a compile) writes rows that no session has appended."""
    b, n = tokens.shape
    pos = positions[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    table = rope.rope_tables(pos, cfg.yarn)
    inner = cfg.period - 1
    # a kind's experts go to each of its layers WHOLE beside the layer's place in them (models/ling.py)
    apart = lambda group: ({name: leaf for name, leaf in group.items() if name != "experts"}, group["experts"])
    (full, full_experts), (window, window_experts) = apart(weights["full"]), apart(weights["window"])

    def period(carry, xs):
        hidden, cache = carry
        p_full, j = xs
        hidden, kv, full_rows = _layer(
            cfg, {**p_full, "experts": full_experts}, hidden, cache["full"], j, j, slots, pos, lengths, None, 0)
        cache = {**cache, "full": kv}

        def one(carry, i):
            # a layer of the window stack by its place: the stack itself stays where it is (models/ling.py)
            hidden, kv = carry
            at = j * inner + i
            p = jax.tree_util.tree_map(lambda a: jax.lax.dynamic_index_in_dim(a, at, 0, False), window)
            hidden, kv, rows = _layer(
                cfg, {**p, "experts": window_experts}, hidden, kv, at, at, slots, pos, lengths, table,
                cfg.sliding_window_size)
            return (hidden, kv), rows

        (hidden, kv), window_rows = jax.lax.scan(one, (hidden, cache["window"]), jnp.arange(inner, dtype=jnp.int32))
        return (hidden, {**cache, "window": kv}), jnp.concatenate([full_rows[None], window_rows])

    hidden = weights["embed"][tokens].astype(jnp.float32)
    (hidden, cache), expert_rows = jax.lax.scan(
        period, (hidden, cache), (full, jnp.arange(cfg.periods, dtype=jnp.int32)),
    )
    last = jnp.clip(lengths - 1, 0, n - 1)
    final = jnp.take_along_axis(hidden, last[:, None, None], axis=1)[:, 0]
    logits = jnp.dot(
        axk1._rms(final, weights["final_norm"], cfg.rms_norm_eps).astype(jnp.bfloat16), weights["head"],
        preferred_element_type=jnp.float32,
    )
    return logits, expert_rows.reshape(-1, cfg.moe_num_primary_experts), cache
