"""Token sessions over a language model whose cache lives on the device.

Builds the served form of a ``family: axk1`` or ``family: deepseek_v32``
repository entry (one model module for both, models/axk1.py: what an
entry's ``model`` block switches on decides, not the family's name) or
of a ``family: sdar_moe`` entry (models/sdar.py: grouped-query attention
under a block mask, generation by diffusion over blocks) or of a
``family: bailing_hybrid`` entry (models/ling.py: Kimi Delta Attention
layers with a latent-attention layer among every few; the contract of a
session with a RECURRENT STATE is further down) or of a ``family:
smallthinker`` entry (models/smallthinker.py: window layers with rotary
positions to one full layer without, a router that reads the layer's
input, ReLU-gated experts; the contract of a session whose layers keep
rows in TWO GEOMETRIES is further down):
the device program ``device_fn(inputs, params)``, the
``params`` it takes as launcher ARGUMENTS (``weights`` and, under
``cache``, the latent cache, with an indexer a dict of it and the index
keys, for ``sdar_moe`` the keys and values a head, for
``bailing_hybrid`` three arrays: latent rows, the recurrent state and
the convolution tails, for ``smallthinker`` the keys and values of the
full layers and of the window layers' rings, that the channel
donates into each launch and takes back from its
outputs: gigabytes of weights cannot be constants of an HLO module, and
the cache never crosses to the host),
and the session state the model declares
(``runtime.sessions.TokenSessions``).

The contract of the served model. One KServe request under a
``sequence_id`` carries ``tokens`` int32 ``[1, n]``; the server appends
them to that session's cache (empty on ``sequence_start``) and answers
``logits`` float32 ``[1, vocab]`` of the last appended position: one
operation, extend. A session may be fed in several many-token requests
(turns), each on whatever the slot already holds. Two launch shapes reach the device. An extend of ONE
session pads ``n`` to :func:`token_bucket`; the one-token requests of
up to ``max_batch_size`` DIFFERENT sessions merge into a step launch,
padded to :func:`step_bucket`. Sampling is the client's.

The contract of a BLOCK session (``family: sdar_moe``; B is the entry's
``block_length``). Three request forms under a ``sequence_id``:

  * extend: ``tokens`` int32 ``[1, n]``, ``n`` a multiple of B, appended
    to the session's cache under the block mask (empty on
    ``sequence_start``); answers ``logits`` float32 ``[1, vocab]`` of
    the last appended position. ONE session a launch, as above;
  * denoise: ``tokens`` int32 ``[1, B]`` (the block as the client holds
    it: revealed ids, the ``[MASK]`` id elsewhere) beside ``commit``
    int32 ``[1, 1]`` = 0. The block is run at the session's length
    against the cache and itself; answers ``logits`` float32 ``[B,
    vocab]``, a row a position of the block: the model's belief about
    the token AT that position. Cache and length stay as they were;
  * commit: the same with ``commit`` = 1: the same answer, and the
    block's keys and values are written: the length moves by B.

This family has no one-token step. The block requests of up to
``max_batch_size`` DIFFERENT sessions, denoise and commit rows mixed,
merge into one launch (``tokens [S, B]``, padded to
:func:`step_bucket`), as one-token steps do. Refused, with the reason:
an extend of ``n mod B != 0``; a block request of another width than B,
or of a session whose length is no multiple of B. Which positions a pass
reveals, and in how many passes, is the client's, as sampling is.

The contract of a session of a model WITH STATE (``family:
bailing_hybrid``; the requests are the first contract's, unchanged).
What a slot holds: rows of the latent cache in the MLA layers, which
grow with the session, and in every KDA layer one recurrent state
(``[heads, d_v, d_k]`` float32) and the last three rows that entered the
short convolution, which do not: a slot's bytes no longer follow its
length (``TokenSessions.stats``: ``session_state_bytes`` beside
``session_cache_tokens``). When it is zeroed: never by a launch of its
own; a row admitted at position 0 (``sequence_start``, a slot reused
after ``sequence_end`` or the TTL) reads a zero state and tail INSIDE
the launch it joins, from ``positions``. What a failed launch costs: one
refused before dispatch leaves state and length as they were; one that
failed after dispatch has overwritten the state of its rows, so its
sessions end and their next request is refused with that reason
(``lm_state_lost``; docs/OPERATIONS.md).

The contract of a session whose layers keep rows in TWO GEOMETRIES
(``family: smallthinker``; the requests are the first contract's,
unchanged). What a slot holds: in every full layer a row a position up
to ``slot_len``, in every window layer a ring of ``window_ring`` rows
(the window and the longest extend launch). A session longer than the
ring loses nothing it may still read: a window layer's query reads the
latest ``sliding_window_size`` positions, and those are in the ring. The
limit is ``slot_len`` positions (``SessionLimitError`` past it), whatever
the ring. A slot is reused without touching the device: positions, not
contents, decide what a query sees. ``TokenSessions.stats`` gives the
cache in bytes by geometry (``session_cache_bytes``,
``session_cache_bytes_in_use``) and ``lm_keys_read`` beside
``lm_keys_visible``.

Everything is read from the entry's ``config.yaml``: ``model`` (the
published sizes and this chip's share, ``precision``), ``pipeline``
(``slot_len``, ``max_tokens``, ``session_ttl_s``), ``max_batch_size``
(the cache's slot count).
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from triton_client_tpu.channel.base import InferRequest
from triton_client_tpu.config import ModelSpec, TensorSpec
from triton_client_tpu.models import axk1, ling, sdar, smallthinker
from triton_client_tpu.runtime import precision as precision_policy
from triton_client_tpu.runtime.repository import RegisteredModel
from triton_client_tpu.runtime.sessions import TokenSessions

#: the key of ``params`` (and of the device program's outputs) that is
#: the model's device state; the channel reads it from ``spec.extra``
STATE_KEY = "cache"

_STEP_FLOOR = 8  # the smallest step launch: sublane-aligned rows
_TOKEN_STRIDE = 1024  # extend launches over this many tokens pad to its multiples


def token_bucket(n: int) -> int:
    """The padded token count of an extend launch: the next power of
    two under 1024, a multiple of 1024 from there."""
    if n > _TOKEN_STRIDE:
        return -(-n // _TOKEN_STRIDE) * _TOKEN_STRIDE
    return max(2, 1 << (n - 1).bit_length())


def step_bucket(sessions: int, slots: int) -> int:
    """The padded row count of a step launch: 8, 16, 32, ... capped at
    the slot count."""
    rows = max(_STEP_FLOOR, 1 << (sessions - 1).bit_length())
    return min(rows, max(slots, sessions))


def launch_inputs(kind: str, size: int, block: int = 0) -> dict:
    """One launch's plain arrays at a launch shape, all rows pad (they
    write nothing): ``kind`` ``"extend"`` with ``size`` tokens of one
    session, ``"step"`` with ``size`` sessions, or ``"block"`` with
    ``size`` sessions' blocks of ``block`` tokens. What compiles a shape
    ahead of traffic sends this straight to the device channel."""
    rows, width = {"extend": (1, size), "step": (size, 1), "block": (size, block)}[kind]
    launch = {
        "tokens": np.zeros((rows, width), np.int32),
        "slots": np.zeros(rows, np.int32),
        "positions": np.zeros(rows, np.int32),
        "lengths": np.zeros(rows, np.int32),
    }
    if kind == "block":
        launch[TokenSessions.COMMIT] = np.zeros(rows, np.int32)
    return launch


def read_weights(path, template, put=None) -> dict:
    """``weights.msgpack`` (flax's format: nested maps, array leaves)
    onto the device LEAF BY LEAF: the file is walked along ``template``
    and each array goes to the device (through ``put``) as it is read,
    so no whole copy of the tree is ever on the host."""
    import flax.serialization
    import msgpack

    put = put or jax.device_put
    with open(path, "rb") as f:
        unpacker = msgpack.Unpacker(
            f, ext_hook=flax.serialization._msgpack_ext_unpack,
            raw=False, max_buffer_size=0,
        )

        def walk(node, where):
            if not isinstance(node, dict):
                leaf = unpacker.unpack()
                if tuple(leaf.shape) != tuple(node.shape):
                    raise ValueError(
                        f"{path}: {where} has shape {tuple(leaf.shape)}, "
                        f"the entry's config says {tuple(node.shape)}"
                    )
                return put(leaf)
            count = unpacker.read_map_header()
            out = {}
            for _ in range(count):
                key = unpacker.unpack()
                if key not in node:
                    raise KeyError(f"{path}: unexpected key {where}/{key}")
                out[key] = walk(node[key], f"{where}/{key}")
            missing = set(node) - set(out)
            if missing:
                raise KeyError(f"{path}: missing {where}/{sorted(missing)}")
            return out

        return walk(template, "")


@jax.jit
def _int8_rounded(w):
    """A matrix as per-output-channel int8 weights read it: rounded to
    the int8 grid of its column's range (the contraction axis is the
    last but one), held as the bfloat16 it dequantizes to."""
    return precision_policy.fake_quant_channelwise(w, contract_axis=-2)


#: family -> the model module that serves it: its ``Config``,
#: ``init_params``, ``abstract_params``, ``stack_layers``, ``empty_cache``
#: and ``extend`` (models/sdar.py also has ``block``; a ``Config`` may state
#: ``state_bytes``, models/ling.py, ``row_geometries``, models/smallthinker.py, or
#: ``step_key_blocks``, models/axk1.py and models/ling.py).
#: The ONE place a family is tied to a module; runtime/disk_repository.py reads its keys
MODULES = {
    "axk1": axk1, "deepseek_v32": axk1, "sdar_moe": sdar, "bailing_hybrid": ling, "smallthinker": smallthinker,
}


@functools.lru_cache(maxsize=None)
def make_device_fn(model, cfg):
    """``device_fn(inputs, params)`` of a configuration of the module
    ``model``: one launch, an extend or, where the inputs carry
    ``commit`` (a block model's), a block launch. The cache rides in
    ``params`` and comes back among the outputs under the same key. ONE
    function a configuration: an entry built again in the same process
    (a reload, a dozen seeds of one rehearsal) finds what jax traced and
    compiled for the first."""

    def device_fn(inputs, params):
        args = (
            cfg, params["weights"], params[STATE_KEY], inputs["tokens"],
            inputs["slots"], inputs["positions"], inputs["lengths"],
        )
        if TokenSessions.COMMIT in inputs:
            logits, expert_rows, kv = model.block(*args, inputs[TokenSessions.COMMIT])
        else:
            logits, expert_rows, kv = model.extend(*args)
        return {"logits": logits, TokenSessions.EXPERT_ROWS: expert_rows, STATE_KEY: kv}

    return device_fn


def build_registered(doc: dict, name: str, version: str, weights=None) -> RegisteredModel:
    """The entry as the repository registers it."""
    model_doc = dict(doc.get("model", {}))
    policy = precision_policy.PrecisionPolicy.parse(model_doc.pop("precision", "bf16"))
    family = doc.get("family", "axk1")
    model = MODULES[family]
    cfg = model.Config.from_dict(model_doc)
    block = getattr(cfg, "block_length", 0)  # the tokens a block request carries; 0: one-token steps
    index_topk = getattr(cfg, "index_topk", 0)
    pipe = dict(doc.get("pipeline", {}))
    slots = int(doc.get("max_batch_size", 8))
    slot_len = int(pipe.get("slot_len", 4352))
    max_tokens = int(pipe.get("max_tokens", min(4096, slot_len)))

    put = jax.device_put
    if policy.quantize_weights:
        put = lambda leaf: (
            _int8_rounded(jax.device_put(leaf)) if leaf.ndim >= 2 else jax.device_put(leaf)
        )
    if weights is not None:
        tree = read_weights(weights, model.abstract_params(cfg), put)
    else:
        tree = jax.tree_util.tree_map(
            put, jax.jit(lambda k: model.init_params(k, cfg))(jax.random.PRNGKey(0))
        )
    params = {
        "weights": model.stack_layers(tree, cfg),
        STATE_KEY: model.empty_cache(cfg, slots, slot_len),
    }
    del tree

    index_cache_bytes = (
        params[STATE_KEY]["index"].nbytes if index_topk else 0
    )
    sessions = TokenSessions(
        slots=slots, slot_len=slot_len, max_tokens=max_tokens,
        token_bucket=token_bucket,
        step_bucket=lambda n: step_bucket(n, slots),
        ttl_s=float(pipe.get("session_ttl_s", 60.0)),
        index_topk=index_topk, layers=cfg.num_hidden_layers,
        index_cache_bytes=index_cache_bytes, block=block,
        # a model whose layers hold a recurrent state says what one session's takes (models/ling.py)
        state_bytes=cfg.state_bytes() if hasattr(cfg, "state_bytes") else 0,
        # one whose layers keep rows in more than one geometry says which (models/smallthinker.py)
        geometries=cfg.row_geometries(slot_len) if hasattr(cfg, "row_geometries") else (),
        # one whose step launch reads a slot's latent rows by blocks says by which (ops/latent_attention.py)
        step_keys=cfg.step_key_blocks(slot_len) if hasattr(cfg, "step_key_blocks") else (),
    )
    device_fn = make_device_fn(model, cfg)
    program = jax.jit(device_fn)
    stepped = [True]  # the in-process session's last request was a step (or there was none)

    def infer_fn(inputs):
        """The in-process call (no channel, so no request parameters):
        ONE implicit session, a stream of turns and then steps.
        ``tokens [1, n]`` with n > 1 after a step (or first of all)
        starts it anew; after another many-token request it is a further
        turn of the same session; n = 1 extends it (a step). For a block
        model the steps are its block requests (those that carry
        ``commit``). Not for use
        beside a serving channel on the same model: both own the cache."""
        inputs = {k: np.asarray(v) for k, v in inputs.items()}
        many = inputs["tokens"].shape[1] > 1 and TokenSessions.COMMIT not in inputs
        request, ticket = sessions.open(InferRequest(
            name, inputs, sequence_id="__in_process__",
            sequence_start=many and stepped[0],
        ))
        stepped[0] = not many
        try:
            out = dict(program(dict(request.inputs), params))
        except Exception:
            sessions.abort(ticket)
            raise
        params[STATE_KEY] = out.pop(STATE_KEY)
        out = sessions.advance(ticket, out)
        sessions.close(ticket, out)
        return out

    spec = ModelSpec(
        name=name,
        version=version,
        platform="jax_lm",
        max_batch_size=slots,
        inputs=(TensorSpec("tokens", (-1, -1), "INT32"),),
        outputs=(TensorSpec("logits", (-1, cfg.vocab_size), "FP32"),),
        extra={
            "family": family,
            "device_state": STATE_KEY,
            # the steps of different sessions merge into one launch; a step
            # carries this many tokens (a block model's block, else one)
            "session_merge": True,
            "step_width": block or 1,
            "precision": policy.name,
            "slot_len": slot_len,
            "max_tokens": max_tokens,
        },
    )
    return RegisteredModel(
        spec=spec, infer_fn=infer_fn, device_fn=device_fn, params=params,
        sessions=sessions,
    )
