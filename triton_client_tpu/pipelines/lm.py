"""Token sessions over a language model whose cache lives on the device.

Builds the served form of a ``family: axk1`` or ``family: deepseek_v32``
repository entry (one model module for both, models/axk1.py: what an
entry's ``model`` block switches on decides, not the family's name):
the device program ``device_fn(inputs, params)``, the
``params`` it takes as launcher ARGUMENTS (``weights`` and, under
``cache``, the latent cache, with an indexer a dict of it and the index
keys, that the channel donates into each launch and takes back from its
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
from triton_client_tpu.models import axk1
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


def launch_inputs(kind: str, size: int) -> dict:
    """One launch's plain arrays at a launch shape, all rows pad (they
    write nothing): ``kind`` ``"extend"`` with ``size`` tokens of one
    session, or ``"step"`` with ``size`` sessions. What compiles a shape
    ahead of traffic sends this straight to the device channel."""
    rows, width = (1, size) if kind == "extend" else (size, 1)
    return {
        "tokens": np.zeros((rows, width), np.int32),
        "slots": np.zeros(rows, np.int32),
        "positions": np.zeros(rows, np.int32),
        "lengths": np.zeros(rows, np.int32),
    }


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


@functools.lru_cache(maxsize=None)
def make_device_fn(cfg: axk1.AXK1Config):
    """``device_fn(inputs, params)``: one extend launch. The cache rides
    in ``params`` and comes back among the outputs under the same key.
    ONE function a configuration: an entry built again in the same
    process (a reload, a dozen seeds of one rehearsal) finds what jax
    traced and compiled for the first."""

    def device_fn(inputs, params):
        logits, expert_rows, kv = axk1.extend(
            cfg, params["weights"], params[STATE_KEY], inputs["tokens"],
            inputs["slots"], inputs["positions"], inputs["lengths"],
        )
        return {"logits": logits, TokenSessions.EXPERT_ROWS: expert_rows, STATE_KEY: kv}

    return device_fn


def build_registered(doc: dict, name: str, version: str, weights=None) -> RegisteredModel:
    """The entry as the repository registers it."""
    model_doc = dict(doc.get("model", {}))
    policy = precision_policy.PrecisionPolicy.parse(model_doc.pop("precision", "bf16"))
    cfg = axk1.AXK1Config.from_dict(model_doc)
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
        tree = read_weights(weights, axk1.abstract_params(cfg), put)
    else:
        tree = jax.tree_util.tree_map(
            put, jax.jit(lambda k: axk1.init_params(k, cfg))(jax.random.PRNGKey(0))
        )
    params = {
        "weights": axk1.stack_layers(tree, cfg),
        STATE_KEY: axk1.empty_cache(cfg, slots, slot_len),
    }
    del tree

    index_cache_bytes = (
        params[STATE_KEY]["index"].nbytes if cfg.index_topk else 0
    )
    sessions = TokenSessions(
        slots=slots, slot_len=slot_len, max_tokens=max_tokens,
        token_bucket=token_bucket,
        step_bucket=lambda n: step_bucket(n, slots),
        ttl_s=float(pipe.get("session_ttl_s", 60.0)),
        index_topk=cfg.index_topk, layers=cfg.num_hidden_layers,
        index_cache_bytes=index_cache_bytes,
    )
    device_fn = make_device_fn(cfg)
    program = jax.jit(device_fn)
    stepped = [True]  # the in-process session's last request was a step (or there was none)

    def infer_fn(inputs):
        """The in-process call (no channel, so no request parameters):
        ONE implicit session, a stream of turns and then steps.
        ``tokens [1, n]`` with n > 1 after a step (or first of all)
        starts it anew; after another many-token request it is a further
        turn of the same session; n = 1 extends it (a step). Not for use
        beside a serving channel on the same model: both own the cache."""
        tokens = np.asarray(inputs["tokens"])
        many = tokens.shape[1] > 1
        request, ticket = sessions.open(InferRequest(
            name, {"tokens": tokens}, sequence_id="__in_process__",
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
            "family": doc.get("family", "axk1"),
            "device_state": STATE_KEY,
            # one-token requests of different sessions merge into one launch
            "session_merge": True,
            "precision": policy.name,
            "slot_len": slot_len,
            "max_tokens": max_tokens,
        },
    )
    return RegisteredModel(
        spec=spec, infer_fn=infer_fn, device_fn=device_fn, params=params,
        sessions=sessions,
    )
