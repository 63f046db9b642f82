"""SDAR served in block sessions, at tiny widths on the CPU (hidden 64,
4 query heads over 2 key/value heads of 16, 8 experts top-2 with 4 held,
2 layers, vocabulary 256, blocks of 4: the benchmark configuration's own
``rehearsal`` sizes), against the plain float32 reference
``benchmarks/references/sdar.py`` on seeded weights.

The tolerance. The program computes in bfloat16 what the reference
computes in float32: an answer (one position's logits, std 2) is off by
0.01-0.04 in its worst logit, and by 0.3-2 where an expert changed sides
in bfloat16 (at hidden 64 with 8 experts that happens to a few answers
in a hundred). ``_close`` holds the median answer under 0.06 and lets at
most a tenth of the answers pass 0.25; a wrong cache row, a wrong row of
a merged launch or a mask that leaks reads 3 and more on every answer.
"""

from __future__ import annotations

import dataclasses
import pathlib
import sys
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks import server_child as sc  # noqa: E402
from benchmarks.references import sdar as reference  # noqa: E402
from triton_client_tpu.channel.base import InferRequest, InferResponse  # noqa: E402
from triton_client_tpu.channel.tpu_channel import TPUChannel  # noqa: E402
from triton_client_tpu.models import sdar  # noqa: E402
from triton_client_tpu.ops import experts as experts_op  # noqa: E402
from triton_client_tpu.pipelines import lm  # noqa: E402
from triton_client_tpu.runtime.continuous import ContinuousBatchingChannel  # noqa: E402
from triton_client_tpu.runtime.sessions import SessionLimitError, TokenSessions  # noqa: E402

B, MASK, BLOCKS = 4, 255, 3
PROMPTS = (12, 13, 14, 15)  # remainders 0-3 over the block length


@pytest.fixture(scope="module")
def cfg():
    return sc.apply_rehearsal(sc.load_json(ROOT / "benchmarks/configs/sdar30b-ep8-l48.json"))


@pytest.fixture(scope="module")
def model_cfg(cfg):
    m = {k: v for k, v in cfg["model"].items() if k not in ("slot_len", "max_tokens")}
    return sdar.SDARConfig.from_dict(m)


@pytest.fixture(scope="module")
def tree(cfg):
    return jax.jit(lambda k: reference.init_params(k, None, cfg))(jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def repo(cfg, tree, tmp_path_factory):
    root = tmp_path_factory.mktemp("repo")
    return root, sc.write_repository(root, cfg, tree, True)


def _flag(value):
    return np.full((1, 1), value, np.int32)


def _stream(prompt: int, seed: int):
    """A stream as the traffic sends it: ``(requests, wanted rows)``.
    The extend of the prompt's whole blocks, then for each block a first
    pass (the unrevealed positions masked), a second (one more revealed)
    and the commit; ``wanted`` names, for each request, the tokens whose
    full forward pass answers it and the rows of that pass."""
    rng = np.random.default_rng(seed)
    fed = prompt // B * B
    ids = rng.integers(0, MASK, fed + BLOCKS * B).astype(np.int32)
    requests, wanted = [{"tokens": ids[None, :fed]}], [(ids[:fed], [fed - 1])]
    for k in range(BLOCKS):
        at = fed + k * B
        final = ids[at : at + B]
        first = final.copy()
        first[prompt - fed if k == 0 else 0 :] = MASK
        second = first.copy()
        second[B - 2] = final[B - 2]
        for block, commit in ((first, 0), (second, 0), (final, 1)):
            requests.append({"tokens": block[None], "commit": _flag(commit)})
            wanted.append((np.concatenate([ids[:at], block]), list(range(at, at + B))))
    return requests, wanted


def _expected(tree, cfg, wanted):
    return [np.asarray(reference.stream_logits(tree, tokens, cfg))[rows] for tokens, rows in wanted]


def _close(got, want):
    worst = np.abs(np.concatenate(got) - np.concatenate(want)).max(axis=-1)
    assert np.median(worst) < 0.06 and np.mean(worst > 0.25) <= 0.1, worst


@pytest.mark.parametrize("prompt", PROMPTS)
def test_prefill_passes_and_commits_match_the_full_forward_pass_in_process(cfg, tree, repo, prompt):
    """Through the entry's in-process ``infer_fn`` (one implicit
    session): every answer is the reference's forward pass over the
    committed prefix and the block as the request carried it."""
    from triton_client_tpu.runtime.disk_repository import build_model

    root, name = repo
    model = build_model(root / name, weights=root / name / "1" / "weights.msgpack")
    requests, wanted = _stream(prompt, seed=prompt)
    got = [np.asarray(model.infer_fn(r)["logits"]) for r in requests]
    assert got[0].shape == (1, 256) and all(g.shape == (B, 256) for g in got[1:])
    _close(got, _expected(tree, cfg, wanted))
    stats = model.sessions.stats()
    fed = prompt // B * B
    assert stats["session_cache_tokens"] == fed + BLOCKS * B
    assert (stats["lm_block_launches"], stats["lm_block_rows"], stats["lm_block_commit_rows"]) == (9, 9, 3)
    assert stats["lm_tokens_committed"] == BLOCKS * B and stats["lm_tokens_prefill"] == fed
    assert stats["lm_tokens_step"] == stats["lm_step_launches"] == 0
    # a position may attend up to the end of its own block, in both layers
    blocks = lambda start, n: sum(((p // B + 1) * B) for p in range(start, start + n))
    visible = blocks(0, fed) + sum(3 * blocks(fed + k * B, B) for k in range(BLOCKS))
    assert stats["lm_keys_visible"] == stats["lm_keys_selected"] == 2 * visible


@pytest.fixture(scope="module")
def channel(repo):
    from triton_client_tpu.runtime.disk_repository import scan_disk

    root, name = repo
    return TPUChannel(scan_disk(root), devices=jax.devices()[:1]), name


@pytest.fixture(scope="module")
def batched(channel):
    """The four streams (prompt remainders 0-3) sent side by side, each
    under its own ``sequence_id``, through the continuous batcher."""
    from triton_client_tpu.obs.trace import RequestTrace

    ch, name = channel
    batcher = ContinuousBatchingChannel(ch, max_batch=20, pipeline_depth=2)
    streams = [_stream(prompt, seed=100 + prompt) for prompt in PROMPTS]
    answers, traces = [[] for _ in streams], []
    before = ch.session_stats()["models"][name]

    def caller(k):
        requests = streams[k][0]
        for j, inputs in enumerate(requests):
            trace = RequestTrace(1000 + j, name) if k == 0 else None
            out = batcher.do_inference(InferRequest(
                name, inputs, sequence_id=f"blocks-{k}", sequence_start=j == 0,
                sequence_end=j == len(requests) - 1, trace=trace))
            answers[k].append(np.asarray(out.outputs["logits"]))
            if trace is not None:
                traces.append(trace)

    try:
        threads = [threading.Thread(target=caller, args=(k,)) for k in range(len(streams))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        stats = batcher.stats()
        batcher.close()
    return streams, answers, traces, before, ch.session_stats()["models"][name], stats


@pytest.mark.parametrize("k", range(len(PROMPTS)))
def test_a_stream_through_the_batcher_matches_the_full_forward_pass(cfg, tree, batched, k):
    streams, answers, _, _, _, _ = batched
    assert [a.shape for a in answers[k]] == [(1, 256)] + [(B, 256)] * (3 * BLOCKS)
    _close(answers[k], _expected(tree, cfg, streams[k][1]))


def test_block_requests_of_concurrent_sessions_share_launches(batched):
    """Denoising and committing rows of different sessions merge under
    the one ``__session_step__`` key: fewer block launches than block
    requests, every row counted, the span named ``lm_block``."""
    _, _, traces, before, after, stats = batched
    grown = lambda name: after[name] - before[name]
    assert grown("lm_block_rows") == 4 * 3 * BLOCKS and grown("lm_block_launches") < grown("lm_block_rows")
    assert grown("lm_block_commit_rows") == 4 * BLOCKS and grown("lm_tokens_committed") == 4 * BLOCKS * B
    assert grown("lm_step_launches") == 0 and after["session_cache_slots_in_use"] == 0
    spans = {sp.name: sp for tr in traces for sp in tr.spans}
    assert "lm_block" in spans and "lm_prefill" in spans and "lm_step" not in spans
    assert {"launch_id", "sessions", "commit_rows", "context"} <= set(spans["lm_block"].attrs)
    assert stats["merges"] > 0


def test_a_denoising_pass_leaves_cache_and_length_as_they_were(channel):
    ch, name = channel
    requests, _ = _stream(14, seed=3)
    ask = lambda inputs, **kw: ch.do_inference(InferRequest(name, inputs, sequence_id="quiet", **kw))
    ask(requests[0], sequence_start=True)
    ask(requests[3])  # the first block committed: the cache holds rows a pass could spoil
    cache = lambda: jax.tree_util.tree_map(np.asarray, ch.served_model(name).params[lm.STATE_KEY])
    held, tokens = cache(), ch.session_stats()["models"][name]["session_cache_tokens"]
    first = ask(requests[4]).outputs["logits"]
    again = ask(requests[4]).outputs["logits"]
    after = cache()
    assert all(np.array_equal(held[key], after[key]) for key in held)  # bit for bit
    assert ch.session_stats()["models"][name]["session_cache_tokens"] == tokens
    assert np.array_equal(first, again)  # and the same pass again answers the same
    ask(requests[6])  # the commit writes: the block's four rows, in every layer, keys and values
    wrote = cache()
    assert all((held[key] != wrote[key]).any(axis=-1).sum() == 2 * B for key in held)
    assert ch.session_stats()["models"][name]["session_cache_tokens"] == tokens + B
    ask(requests[7], sequence_end=True)


def _weights(tree, model_cfg):
    return sdar.stack_layers({**tree, "layers": dict(tree["layers"])}, model_cfg)


def test_rows_of_a_merged_launch_equal_the_rows_sent_alone(model_cfg, tree):
    """Three sessions at different lengths, a committing row between two
    denoising ones and pad rows after them: each row's logits, and what
    the launch wrote, are what the row gives in a launch of its own."""
    weights = _weights(tree, model_cfg)
    run = jax.jit(lambda kv, *a: sdar.block(model_cfg, weights, kv, *a))
    fill = jax.jit(lambda kv, *a: sdar.extend(model_cfg, weights, kv, *a))
    rng = np.random.default_rng(11)
    kv = sdar.empty_cache(model_cfg, 4, 32)
    lengths = (8, 16, 4)
    for slot, n in enumerate(lengths):
        ids = np.zeros((1, 16), np.int32)
        ids[0, :n] = rng.integers(0, MASK, n)
        _, _, kv = fill(kv, ids, np.asarray([slot], np.int32), np.zeros(1, np.int32), np.asarray([n], np.int32))
    blocks = rng.integers(0, MASK, (3, B)).astype(np.int32)
    commit = np.asarray([0, 1, 0], np.int32)

    def launch(rows, pad):
        pick = lambda a: np.concatenate([np.asarray(a)[rows], np.zeros((pad, *np.shape(a)[1:]), np.int32)])
        return run(kv, pick(blocks), pick(np.arange(3, dtype=np.int32)), pick(np.asarray(lengths, np.int32)),
                   pick(np.full(3, B, np.int32)), pick(commit))

    merged, _, kv_merged = launch([0, 1, 2], 5)
    merged = np.asarray(merged)
    for row in range(3):
        alone, _, kv_alone = launch([row], 7)
        assert np.abs(np.asarray(alone)[:B] - merged[row * B : (row + 1) * B]).max() < 2e-2
        if commit[row]:
            for key in kv:
                assert np.array_equal(np.asarray(kv_alone[key]), np.asarray(kv_merged[key]))
    assert merged.shape == (8 * B, 256)


def test_the_mask_is_causal_between_blocks_and_bidirectional_inside_one(cfg, tree, channel):
    """Changing a token of block k moves no logit of an earlier block
    and every position of block k: in the reference's forward pass, and
    in the served block pass (whose answer holds the whole block)."""
    rng = np.random.default_rng(2)
    ids = rng.integers(0, MASK, 16).astype(np.int32)
    changed = ids.copy()
    changed[9] = (changed[9] + 1) % MASK  # a position of block 2
    a, b = (np.asarray(reference.stream_logits(tree, t, cfg)) for t in (ids, changed))
    assert np.array_equal(a[:8], b[:8])
    assert (np.abs(a[8:] - b[8:]).max(axis=-1) > 1e-3).all()
    ch, name = channel
    ask = lambda inputs, **kw: ch.do_inference(InferRequest(name, inputs, sequence_id="mask", **kw)).outputs["logits"]
    ask({"tokens": ids[None, :8]}, sequence_start=True)
    one = ask({"tokens": ids[None, 8:12], "commit": _flag(0)})
    other = ask({"tokens": changed[None, 8:12], "commit": _flag(0)}, sequence_end=True)
    assert (np.abs(one - other).max(axis=-1) > 1e-3).all()
    _close([one], [a[8:12]])  # the later block of the reference's stream is invisible to it


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of all 16 / 2 = 8 shares give the reference's
    UNCUT expert layer (all 16 held): there is no shared expert to count
    once, and a token routed elsewhere adds nothing here."""
    m = {"experts_here": 16, "expert_offset": 0, "num_experts_per_tok": 4, "norm_topk_prob": True}
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    normal = lambda key, shape, std: (jax.random.normal(key, shape) * std).astype(jnp.bfloat16)
    full = {"gate": normal(k[0], (16, 64, 32), 0.125), "up": normal(k[1], (16, 64, 32), 0.125),
            "down": normal(k[2], (16, 32, 64), 0.18)}
    router = normal(k[3], (64, 16), 0.19)
    x = jax.random.normal(k[4], (24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        idx, gates = reference.route(x, router.astype(jnp.float32), m)
        uncut = reference.experts_here(x, jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), full), idx, gates, m,
                                       lambda a: a.astype(jnp.bfloat16).astype(jnp.float32))
    served_idx, served_gates = experts_op.route(x, router, 4, 1.0, True, softmax=True)
    assert np.array_equal(np.sort(np.asarray(idx)), np.sort(np.asarray(served_idx)))

    def share(offset):
        held = jax.tree_util.tree_map(lambda w: w[offset : offset + 2], full)
        y, rows = experts_op.routed_experts(x.astype(jnp.bfloat16), jnp.ones(24, bool), served_idx, served_gates, held, offset)
        return np.asarray(y), int(np.asarray(rows).sum())

    parts = [share(offset) for offset in range(0, 16, 2)]
    assert sum(rows for _, rows in parts) == 24 * 4  # every token-slot lands in exactly one share
    assert np.abs(sum(y for y, _ in parts) - np.asarray(uncut)).max() < 0.03
    assert np.abs(parts[0][0] - np.asarray(uncut)).max() > 0.1  # one share is not the layer


def _block_sessions(slots=8, slot_len=16):
    return TokenSessions(slots=slots, slot_len=slot_len, max_tokens=8, token_bucket=lm.token_bucket,
                         step_bucket=lambda n: lm.step_bucket(n, slots), block=B)


def _open(state, sid, inputs, **kw):
    return state.open(InferRequest("m", inputs, sequence_id=sid, **kw))


def test_block_launch_shapes_lengths_and_refusals():
    state = _block_sessions()
    ids = lambda n: np.arange(n, dtype=np.int32)[None]
    request, ticket = _open(state, "a", {"tokens": ids(8)}, sequence_start=True)
    assert ticket.kind == "lm_prefill" and request.inputs["tokens"].shape == (1, 8)
    assert set(request.inputs) == set(TokenSessions.LAUNCH_INPUTS)
    state.close(ticket, {"logits": np.zeros((1, 3))})
    # a denoising row takes its slot and position and moves nothing; a failed one has nothing to take back
    request, ticket = _open(state, "a", {"tokens": ids(B), "commit": _flag(0)})
    assert ticket.kind == "lm_block" and (ticket.sessions, ticket.answers, ticket.commit_rows) == (1, B, 0)
    assert request.inputs["tokens"].shape == (8, B) and request.inputs["commit"].tolist() == [0] * 8
    assert request.inputs["positions"][0] == 8 and request.inputs["lengths"].tolist() == [B] + [0] * 7
    assert state.launch_kind(request.inputs) == "lm_block"
    state.abort(ticket)
    assert state.stats()["session_cache_tokens"] == 8
    # a commit moves the length by a block, and gives it back if the launch fails
    request, ticket = _open(state, "a", {"tokens": ids(B), "commit": _flag(1)})
    assert request.inputs["commit"][0] == 1 and state.stats()["session_cache_tokens"] == 12
    state.abort(ticket)
    assert state.stats()["session_cache_tokens"] == 8
    _, ticket = _open(state, "a", {"tokens": ids(B), "commit": _flag(1)})
    host = {"logits": np.zeros((8 * B, 3))}
    state.close(ticket, host)
    assert host["logits"].shape == (B, 3)  # the pad rows' answers end here
    stats = state.stats()
    assert (stats["session_cache_tokens"], stats["lm_block_launches"], stats["lm_tokens_committed"]) == (12, 1, B)
    # refused, and why
    with pytest.raises(ValueError, match="whole blocks of 4"):
        _open(state, "b", {"tokens": ids(6)}, sequence_start=True)
    with pytest.raises(ValueError, match=r"tokens \[1, 4\]"):
        _open(state, "a", {"tokens": ids(8), "commit": _flag(0)})
    state._pool.slots["a"].length = 10  # a length no whole blocks make up cannot come about by requests
    with pytest.raises(SessionLimitError, match="block's boundary"):
        _open(state, "a", {"tokens": ids(B), "commit": _flag(0)})
    state._pool.slots["a"].length = 16
    with pytest.raises(SessionLimitError, match="outgrow"):
        _open(state, "a", {"tokens": ids(B), "commit": _flag(0)})
    assert state.stats()["session_cache_tokens"] == 16 and state._pool.slots["a"].refs == 0


class _Inner:
    """A ``session_merge`` model that answers a row a token; ``extra``
    is what its spec declares."""

    batch_multiple = 1

    def __init__(self, extra):
        self.extra, self.launches = extra, []

    def get_metadata(self, name, version=""):
        return types.SimpleNamespace(extra=self.extra)

    def do_inference_async(self, request):
        self.launches.append(request)
        tokens = np.asarray(request.inputs["tokens"])
        answer = np.repeat(tokens.reshape(-1, 1).astype(np.float32), 3, axis=1)  # a row a token: its id
        return types.SimpleNamespace(result=lambda: InferResponse(model_name=request.model_name, outputs={"y": answer}))


@pytest.mark.parametrize("width", (1, B))
def test_steps_of_either_width_merge_and_pass_the_step_wait(width):
    """A one-token step of a model that declares no width (the two
    families that had steps before blocks) and a block step of a model
    that declares ``step_width`` both come under the ``__session_step__``
    key, go through ``_step_wait_locked`` and get their own rows back; a
    request of another shape runs alone."""
    inner = _Inner({"session_merge": True, **({"step_width": width} if width > 1 else {})})
    batcher = ContinuousBatchingChannel(inner, max_batch=8, pipeline_depth=2)
    waits = []
    held = batcher._step_wait_locked
    batcher._step_wait_locked = lambda key, *rest: waits.append(key) or held(key, *rest)
    step = lambda sid, base: {"tokens": np.arange(base, base + width, dtype=np.int32)[None],
                              **({"commit": _flag(1)} if width > 1 else {})}
    try:
        assert batcher._session_step(InferRequest("m", step("a", 0), sequence_id="a"))
        assert not batcher._session_step(InferRequest("m", {"tokens": np.zeros((1, 8), np.int32)}, sequence_id="a"))
        # a block's tokens without the flag are an extend of one block: alone
        assert batcher._session_step(InferRequest("m", {"tokens": np.zeros((1, B), np.int32)}, sequence_id="a")) == (width == 1 and B == 1)
        answers = {}

        def caller(sid, base):
            answers[sid] = batcher.do_inference(InferRequest("m", step(sid, base), sequence_id=sid)).outputs["y"]

        threads = [threading.Thread(target=caller, args=(f"s{i}", 10 * i)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        batcher.close()
    assert waits and all(key[0] == "__session_step__" for key in waits)
    for i in range(4):
        assert answers[f"s{i}"].shape == (width, 3) and answers[f"s{i}"][:, 0].tolist() == list(range(10 * i, 10 * i + width))
    assert sum(len(r.sequence_rows or (1,)) for r in inner.launches) == 4
