"""The DeepSeek-V3 block, latent attention and routed experts, served as
one chip's share of a wider deployment: ONE module for the two families
that carry it, ``family: axk1`` (A.X-K1, ``model_type`` ``axk1``, which
brought the module and gave it its name) and ``family: deepseek_v32``
(DeepSeek-V3.2-Exp, ``model_type`` ``deepseek_v32``). What differs is
read from the entry's ``model`` block, never from the family's name.

The published block (https://huggingface.co/skt/A.X-K1,
https://huggingface.co/deepseek-ai/DeepSeek-V3.2-Exp): RMSNorm,
multi-head latent attention (ops/latent_attention.py) with YaRN rotary
embeddings (ops/rope.py), ``first_k_dense_replace`` leading dense SwiGLU
layers, then layers of sigmoid-routed experts (ops/experts.py) beside a
shared one. This chip holds ``experts_here`` of the ``router_experts``
routed experts of each layer, ``vocab_size`` rows of embedding and head
and ``num_hidden_layers`` layers; every width is the published one.

What an entry may switch on:

  * ``index_topk`` > 0: the learned sparse attention (ops/sparse_index.py).
    An indexer of ``index_n_heads`` heads of ``index_head_dim`` values
    scores every cached position for every query, and attention reads
    only the ``index_topk`` best (all of them while the context is no
    longer). Every position's index key is cached beside its latent;
  * ``topk_method: noaux_tc``: the router chooses by ``scores +
    router_bias`` among the ``topk_group`` best of ``n_group`` groups of
    experts and gates with the unbiased scores; ``none`` is plain top-k.
  * ``expert_chunk_rows``: a tuning, the token-slots one grouped product
    of the held experts takes (ops/experts.py ``routed_experts``).

Functional: parameters are a pytree (bfloat16 matrices ``[in, out]``,
float32 norm scales), the cache is an array ``[layers, slots, slot_len,
kv_rank + rope]`` that a launch takes in and gives back; with an
indexer it is a dict of that array (``latent``) and the index keys
``[layers, slots, slot_len, index_head_dim]`` (``index``). One operation,
*extend*: a launch appends ``lengths[b]`` tokens to the session in slot
``slots[b]`` from position ``positions[b]`` on and answers the logits
of each row's last appended position. A launch of one row with many
tokens (a prompt, a further turn on whatever the slot holds) expands
the slot's latents per head; a launch of many rows with one token each
(decoded steps of different sessions) runs the absorbed form. The
router, norms, softmax, index scores and logits are float32; everything
a matrix product reads is bfloat16.

The layers after the dense ones run under ONE ``lax.scan`` (their
weights stacked on a leading axis by :func:`stack_layers`), so each
kernel is one op name in a device trace.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from triton_client_tpu.ops import experts as experts_op
from triton_client_tpu.ops import latent_attention, rope, sparse_index

#: the keys an entry's ``model`` block may hold beside ``rope_scaling`` and ``precision``
_PUBLISHED = {
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "router_experts", "experts_here",
    "expert_offset", "n_shared_experts", "num_experts_per_tok",
    "norm_topk_prob", "routed_scaling_factor", "num_hidden_layers",
    "first_k_dense_replace", "vocab_size", "rms_norm_eps", "rope_theta",
    "index_n_heads", "index_head_dim", "index_topk",
    "n_group", "topk_group", "topk_method", "expert_chunk_rows",
}


@dataclasses.dataclass(frozen=True)
class AXK1Config:
    """The published sizes (defaults) and this chip's share."""

    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_attention_heads: int = 64
    q_lora_rank: int | None = 1536  # None (``null``): no query compression, ``q = x W_q`` with no query norm
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    router_experts: int = 192  # the router's width: every expert of the model
    experts_here: int = 12  # held on this chip ...
    expert_offset: int = 0  # ... from this one on
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    num_hidden_layers: int = 6
    first_k_dense_replace: int = 1
    vocab_size: int = 20480
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 0  # positions a query attends to; 0: no indexer, every cached position
    n_group: int = 1  # groups the router's experts fall into ...
    topk_group: int = 1  # ... of which a token may choose among this many
    topk_method: str = "none"  # "noaux_tc": the group limit and a correction bias
    #: token-slots a grouped product of the held experts takes (ops/experts.py); an entry sets it to
    #: about twice the rows it expects here a launch, so that every layer takes ONE pass on every seed
    expert_chunk_rows: int = experts_op.CHUNK_ROWS
    yarn: rope.YarnConfig = rope.YarnConfig()

    @classmethod
    def from_dict(cls, doc: dict) -> "AXK1Config":
        doc = dict(doc)
        scaling = dict(doc.pop("rope_scaling", {}))
        doc.pop("precision", None)  # the serving policy's, not a size
        unknown = set(doc) - _PUBLISHED
        if unknown:
            raise KeyError(f"axk1 model config: unknown keys {sorted(unknown)}")
        cfg = cls(**doc)
        if cfg.topk_method not in ("none", "noaux_tc"):
            raise ValueError(f"axk1 model config: topk_method {cfg.topk_method!r} (known: none, noaux_tc)")
        if cfg.index_topk and not cfg.q_lora_rank:
            raise ValueError("axk1 model config: the indexer's queries are made from the compressed query: q_lora_rank")
        yarn = rope.YarnConfig(
            dim=cfg.qk_rope_head_dim,
            theta=float(cfg.rope_theta),
            factor=float(scaling.get("factor", 32.0)),
            original_max_position=int(
                scaling.get("original_max_position_embeddings", 4096)
            ),
            beta_fast=float(scaling.get("beta_fast", 32.0)),
            beta_slow=float(scaling.get("beta_slow", 1.0)),
            mscale=float(scaling.get("mscale", 1.0)),
            mscale_all_dim=float(scaling.get("mscale_all_dim", 1.0)),
        )
        return dataclasses.replace(cfg, yarn=yarn)

    @property
    def cache_width(self) -> int:
        """Values a cached position holds: the latent and the rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row(self) -> int:
        """A cache row as it is allocated: ``cache_width`` rounded up to
        whole 128-lane tiles (576 -> 640), the tail zero. The chip pads a
        576-wide row to 640 lanes anyway, or, left to choose, lays the
        array out with the POSITIONS minor; a row of whole tiles has one
        natural layout, row-major, which every launch program and a fresh
        array agree on without being told (channel/staged.py)."""
        return -(-self.cache_width // 128) * 128

    def step_key_blocks(self, slot_len: int) -> tuple:
        """What a step launch's row fetches of its slot: the positions
        it reads at a time (the device program's own rule) and the layers
        that attend so (runtime/sessions.py counts by it)."""
        return latent_attention.step_block(slot_len), self.num_hidden_layers

    @property
    def group_limited(self) -> bool:
        return self.topk_method == "noaux_tc"

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * (
            rope.attention_mscale(self.yarn) ** 2
        )


Config = AXK1Config  # what pipelines/lm.py asks of a model module


def _normal(key, shape, std):
    if len(shape) >= 3:
        return jax.lax.map(
            lambda k: _normal(k, shape[1:], std), jax.random.split(key, shape[0])
        )
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.bfloat16)


def _mlp(key, d, f, lead=()):
    k = jax.random.split(key, 3)
    return {
        "gate": _normal(k[0], (*lead, d, f), d**-0.5),
        "up": _normal(k[1], (*lead, d, f), d**-0.5),
        "down": _normal(k[2], (*lead, f, d), f**-0.5),
    }


def _query_params(key_a, key_b, cfg) -> dict:
    """The query side of latent attention: compressed through
    ``q_lora_rank`` values and normalised there, or, where the
    configuration states none, one matrix ``q``."""
    d, width = cfg.hidden_size, cfg.num_attention_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    if not cfg.q_lora_rank:
        return {"q": _normal(key_a, (d, width), d**-0.5)}
    return {
        "q_a": _normal(key_a, (d, cfg.q_lora_rank), d**-0.5),
        "q_norm": jnp.ones((cfg.q_lora_rank,), jnp.float32),
        "q_b": _normal(key_b, (cfg.q_lora_rank, width), cfg.q_lora_rank**-0.5),
    }


def init_params(key, cfg: AXK1Config) -> dict:
    """The program's own initialisation (an entry without a weights
    file serves it): the layout a ``weights.msgpack`` has, layer by
    layer under ``layers/<i>``."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    keys = jax.random.split(key, cfg.num_hidden_layers + 2)
    layers = {}
    for i in range(cfg.num_hidden_layers):
        k = jax.random.split(keys[i], 14)
        layer = {
            "norm1": jnp.ones((d,), jnp.float32),
            "norm2": jnp.ones((d,), jnp.float32),
            "attn": {
                **_query_params(k[0], k[1], cfg),
                "kv_a": _normal(k[2], (d, cfg.cache_width), d**-0.5),
                "kv_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
                "kv_b": _normal(
                    k[3],
                    (cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                    cfg.kv_lora_rank**-0.5,
                ),
                "o": _normal(k[4], (h * cfg.v_head_dim, d), 0.5 * (h * cfg.v_head_dim) ** -0.5),
            },
        }
        if cfg.index_topk:
            hi, di = cfg.index_n_heads, cfg.index_head_dim
            layer["attn"]["index"] = {
                "q_b": _normal(k[9], (cfg.q_lora_rank, hi * di), cfg.q_lora_rank**-0.5),
                "k": _normal(k[10], (d, di), d**-0.5),
                "k_scale": jnp.ones((di,), jnp.float32),
                "k_bias": jnp.zeros((di,), jnp.float32),
                "w": _normal(k[11], (d, hi), d**-0.5),
            }
        if i < cfg.first_k_dense_replace:
            layer["mlp"] = _mlp(k[5], d, cfg.intermediate_size)
        else:
            layer["router"] = _normal(k[6], (d, cfg.router_experts), 1.5 * d**-0.5)
            if cfg.group_limited:
                layer["router_bias"] = jnp.zeros((cfg.router_experts,), jnp.float32)
            layer["shared"] = _mlp(
                k[7], d, cfg.moe_intermediate_size * cfg.n_shared_experts
            )
            layer["experts"] = _mlp(
                k[8], d, cfg.moe_intermediate_size, (cfg.experts_here,)
            )
        layers[str(i)] = layer
    return {
        "embed": _normal(keys[-2], (cfg.vocab_size, d), 1.0),
        "head": _normal(keys[-1], (d, cfg.vocab_size), 2.0 * d**-0.5),
        "final_norm": jnp.ones((d,), jnp.float32),
        "layers": layers,
    }


def abstract_params(cfg: AXK1Config):
    """The tree's shapes and types, nothing built."""
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


def stack_layers(tree: dict, cfg, one_program: bool = False) -> dict:
    """The served form of a loaded tree: the dense layers as they are,
    the expert layers stacked leaf by leaf on a leading axis for the
    scan. TAKES the per-layer leaves out of ``tree`` (its ``layers`` is
    left empty) and drops each group of them as its stack is built, so
    at most one stacked leaf exists twice: beside 8.33 GB of weights a
    second copy of the expert layers (6.75 GB) does not fit a chip.
    ``one_program``: each stack is ONE jitted program and is waited for.
    An eager ``jnp.stack`` gives every part a leading axis first, a copy
    a part, and concatenates those: three times the leaf beside the
    tree, 7.25 GB for a 2.4 GB leaf of 48 layers (9.24 -> 16.49 GB of a
    chip's 16.9: my chip run, PR 39). The families that came first keep
    the eager form: their served peak is what ``benchmarks/run.py``
    holds the yardstick's under, and with this form the missionlog cell
    exits 4 (served 10.2 GB under the reference's 11.03: my chip run,
    PR 39; PERF.md section 7): one form once ``references/dsv32.py``
    is slimmer."""
    layers = tree["layers"]
    n_dense = cfg.first_k_dense_replace
    dense = [layers.pop(str(i)) for i in range(n_dense)]
    rest = [layers.pop(str(i)) for i in range(n_dense, cfg.num_hidden_layers)]
    return {
        "embed": tree["embed"], "head": tree["head"],
        "final_norm": tree["final_norm"], "dense": dense, "moe": stack_group(rest, one_program),
    }


_stack_in_one_program = jax.jit(lambda *parts: jnp.stack(parts))  # one for the module: an entry built again compiles no stack anew


def stack_group(rest: list, one_program: bool = False):
    """Layers of one kind stacked leaf by leaf on a leading axis
    (:func:`stack_layers` says why a leaf at a time); EMPTIES ``rest``.
    None for no layer."""
    if not rest:
        return None
    stack = _stack_in_one_program if one_program else (lambda *parts: jnp.stack(parts))
    flat = [jax.tree_util.tree_flatten(layer) for layer in rest]
    treedef = flat[0][1]
    columns = [list(leaves) for leaves, _ in flat]
    del flat, rest[:]  # the columns alone hold the per-layer leaves now
    out = []
    for j in range(len(columns[0])):
        out.append(stack(*[col[j] for col in columns]))
        if one_program:
            jax.block_until_ready(out[-1])  # a tracer (shapes alone) has nothing to wait for
        for col in columns:
            col[j] = None
    return jax.tree_util.tree_unflatten(treedef, out)


def empty_cache(cfg: AXK1Config, slots: int, slot_len: int):
    """The device state of ``slots`` sessions: the latent cache, and with
    an indexer the index keys beside it (a dict of both)."""
    latent = jnp.zeros(
        (cfg.num_hidden_layers, slots, slot_len, cfg.cache_row), jnp.bfloat16
    )
    if not cfg.index_topk:
        return latent
    index = jnp.zeros(
        (cfg.num_hidden_layers, slots, slot_len, cfg.index_head_dim), jnp.bfloat16
    )
    return {"latent": latent, "index": index}


# -- the forward pass -----------------------------------------------------------


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _swiglu(x, p):
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def _rope_head(x, cos, sin, rp):
    """The first ``rp`` values of each head of ``x [B, n, ..., dim]``
    rotated (float32), the rest as they are."""
    while cos.ndim < x.ndim:
        cos, sin = cos[:, :, None], sin[:, :, None]
    x = x.astype(jnp.float32)
    return jnp.concatenate([rope.apply_rope(x[..., :rp], cos, sin), x[..., rp:]], axis=-1)


def _selection(cfg, p, x, qr, ik, layer, slots, positions, where, cos, sin):
    """The indexer: writes the new tokens' index keys into ``ik[layer]``
    and scores every cached position for every new token. Returns the
    ``select`` the attention forms take (index scores and thresholds,
    one row a query) and ``ik``."""
    b, n, _ = x.shape
    hi, di, rp = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    bf = jnp.bfloat16
    q = _rope_head((qr @ p["q_b"]).reshape(b, n, hi, di), cos, sin, rp).astype(bf)
    k = (x @ p["k"]).astype(jnp.float32)
    mean = jnp.mean(k, axis=-1, keepdims=True)
    k = (k - mean) * jax.lax.rsqrt(jnp.mean((k - mean) ** 2, axis=-1, keepdims=True) + 1e-6)
    k = _rope_head(k * p["k_scale"] + p["k_bias"], cos, sin, rp).astype(bf)
    ik = ik.at[layer, slots[:, None], where].set(k, mode="drop")
    w = (x @ p["w"]).astype(jnp.float32) * (hi**-0.5 * di**-0.5)
    wanted = jnp.minimum(positions + 1, cfg.index_topk)
    if n == 1:
        with jax.named_scope("lm_index_scores"):
            scores = sparse_index.step_scores(q[:, 0], w[:, 0], ik, layer, slots, positions[:, 0])
        with jax.named_scope("lm_index_select"):
            tau = sparse_index.kth_largest(scores, wanted[:, 0])
    else:
        keys = jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(ik, layer, 0, keepdims=False), slots[0], 0, keepdims=False,
        )
        with jax.named_scope("lm_index_scores"):
            scores = sparse_index.extend_scores(q[0], w[0], keys, positions[0])
        with jax.named_scope("lm_index_select"):
            tau = sparse_index.kth_largest(scores, wanted[0], last=positions[0, -1])
    return (scores, tau), ik


def _attention(cfg, p, x, kv, ik, layer, slots, positions, valid, cos, sin):
    """``x [B, n, D]`` bfloat16 normalised. Writes the new tokens'
    ``(c, kr)`` into ``kv[layer]`` and, with an indexer, their index
    keys into ``ik[layer]`` (pad tokens are dropped), then attends.
    Returns the attention output ``[B, n, D]``, ``kv`` and ``ik``."""
    b, n, _ = x.shape
    h, nope, rp = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    rank, eps = cfg.kv_lora_rank, cfg.rms_norm_eps
    bf = jnp.bfloat16
    if "q" in p:  # no query compression (``q_lora_rank: null``)
        qr, q = None, (x @ p["q"]).reshape(b, n, h, nope + rp)
    else:
        qr = _rms(x @ p["q_a"], p["q_norm"], eps).astype(bf)
        q = (qr @ p["q_b"]).reshape(b, n, h, nope + rp)
    q_nope = q[..., :nope]
    q_rope = rope.apply_rope(
        q[..., nope:].astype(jnp.float32), cos[:, :, None], sin[:, :, None]
    ).astype(bf)
    ckr = x @ p["kv_a"]
    c = _rms(ckr[..., :rank], p["kv_norm"], eps)
    kr = rope.apply_rope(ckr[..., rank:].astype(jnp.float32), cos, sin)
    tail = jnp.zeros((b, n, kv.shape[-1] - cfg.cache_width), jnp.float32)
    new = jnp.concatenate([c, kr, tail], axis=-1).astype(bf)
    slot_len = kv.shape[2]
    # a pad token's position is past the slot: dropped, so a launch of
    # pad rows alone (a compile) writes nothing and disturbs no session
    where = jnp.where(valid, positions, slot_len)
    kv = kv.at[layer, slots[:, None], where].set(new, mode="drop")
    kv_b = p["kv_b"].reshape(rank, h, -1)
    select = None
    if cfg.index_topk:
        select, ik = _selection(cfg, p["index"], x, qr, ik, layer, slots, positions, where, cos, sin)
    if n == 1:
        with jax.named_scope("lm_sparse_attention" if select else "lm_attention"):
            out = latent_attention.absorbed_attention(
                q_nope[:, 0], q_rope[:, 0], kv, layer, slots, positions[:, 0],
                kv_b, cfg.softmax_scale, nope, select,
            )[:, None]
    else:
        # many tokens: ONE session a launch (pipelines/lm.py forms it so)
        assert b == 1, "a launch of many tokens a row holds one session"
        rows = jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(kv, layer, 0, keepdims=False),
            slots[0], 0, keepdims=False,
        )
        with jax.named_scope("lm_sparse_attention" if select else "lm_attention"):
            out = latent_attention.expanded_attention(
                q_nope[0], q_rope[0], rows, positions[0], kv_b,
                cfg.softmax_scale, nope, select,
            )[None]
    if "gate" in p:  # one sigmoid gate a head on the attention output (models/ling.py)
        out = out * jax.nn.sigmoid((x @ p["gate"]).astype(jnp.float32)).astype(bf)[..., None]
    return out.reshape(b, n, -1) @ p["o"], kv, ik


def _layer(cfg, p, hidden, cache, layer, slots, positions, valid, cos, sin):
    """One layer; returns the stream, the cache (:func:`empty_cache`'s
    form) and the rows each held expert saw (None for a dense layer)."""
    bf = jnp.bfloat16
    eps = cfg.rms_norm_eps
    kv, ik = (cache["latent"], cache["index"]) if cfg.index_topk else (cache, None)
    a, kv, ik = _attention(
        cfg, p["attn"], _rms(hidden, p["norm1"], eps).astype(bf), kv, ik, layer,
        slots, positions, valid, cos, sin,
    )
    kv = {"latent": kv, "index": ik} if cfg.index_topk else kv
    hidden = hidden + a.astype(jnp.float32)
    x32 = _rms(hidden, p["norm2"], eps)
    x = x32.astype(bf)
    if "mlp" in p:
        return hidden + _swiglu(x, p["mlp"]).astype(jnp.float32), kv, None
    b, n, d = x.shape
    idx, gates = experts_op.route(
        x32.reshape(b * n, d), p["router"], cfg.num_experts_per_tok,
        cfg.routed_scaling_factor, cfg.norm_topk_prob,
        bias=p["router_bias"] if cfg.group_limited else None, n_group=cfg.n_group, topk_group=cfg.topk_group,
    )
    y, rows = experts_op.routed_experts(
        x.reshape(b * n, d), valid.reshape(-1), idx, gates, p["experts"],
        cfg.expert_offset, cfg.expert_chunk_rows,
    )
    shared = _swiglu(x, p["shared"]).astype(jnp.float32)
    return hidden + y.reshape(b, n, d) + shared, kv, rows


def extend(cfg: AXK1Config, weights: dict, kv, tokens, slots, positions, lengths):
    """Append ``lengths[b]`` of ``tokens [B, n]`` to the session in slot
    ``slots[b]`` from ``positions[b]`` on; ``kv`` is the cache in
    :func:`empty_cache`'s form. Returns ``logits [B, V]`` float32 of
    each row's last appended position, ``expert_rows [expert layers,
    experts_here]`` int32 and the cache."""
    b, n = tokens.shape
    offsets = jnp.arange(n, dtype=jnp.int32)[None, :]
    valid = offsets < lengths[:, None]
    pos = positions[:, None] + offsets
    cos, sin = rope.rope_tables(pos, cfg.yarn)
    hidden = weights["embed"][tokens].astype(jnp.float32)
    layer = 0
    for p in weights["dense"]:
        hidden, kv, _ = _layer(cfg, p, hidden, kv, layer, slots, pos, valid, cos, sin)
        layer += 1
    n_moe = cfg.num_hidden_layers - cfg.first_k_dense_replace
    expert_rows = jnp.zeros((0, cfg.experts_here), jnp.int32)
    if n_moe:

        def body(carry, xs):
            hidden, kv = carry
            p, i = xs
            hidden, kv, rows = _layer(cfg, p, hidden, kv, i, slots, pos, valid, cos, sin)
            return (hidden, kv), rows

        (hidden, kv), expert_rows = jax.lax.scan(
            body, (hidden, kv),
            (weights["moe"], jnp.arange(layer, layer + n_moe, dtype=jnp.int32)),
        )
    last = jnp.clip(lengths - 1, 0, n - 1)
    final = jnp.take_along_axis(hidden, last[:, None, None], axis=1)[:, 0]
    logits = jnp.dot(
        _rms(final, weights["final_norm"], cfg.rms_norm_eps).astype(jnp.bfloat16),
        weights["head"], preferred_element_type=jnp.float32,
    )
    return logits, expert_rows, kv
