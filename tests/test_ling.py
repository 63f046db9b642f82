"""Ling-3.0-flash served in token sessions, at tiny widths on the CPU (the
benchmark configuration's own ``rehearsal`` sizes: hidden 64, 4 heads of
16, 1 dense + one period of five Kimi Delta Attention layers and one
latent-attention layer, 16 experts in 2 groups of which 8 are held),
against the plain float32 reference ``benchmarks/references/ling.py`` on
seeded weights, whose decay is slow enough for a state that was not
carried, or not reset, to show.

The program differs from the reference by what bfloat16 activations
cost. An answer is held to ``REL`` of the logits' spread (the
reference's own rounded pass reads 0.03-0.05 of it at these widths), and
where an expert held here is within ``TIE_BAND`` of changing sides to
``FLIP_REL``: another session's state at position 0 moves the first
answer by 0.13, several times what bfloat16 costs it.
"""

from __future__ import annotations

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
from benchmarks.references import ling as reference  # noqa: E402
from triton_client_tpu.channel.base import InferRequest, InferResponse  # noqa: E402
from triton_client_tpu.channel.tpu_channel import TPUChannel  # noqa: E402
from triton_client_tpu.models import ling  # noqa: E402
from triton_client_tpu.ops import delta_attention  # noqa: E402
from triton_client_tpu.pipelines import lm  # noqa: E402
from triton_client_tpu.runtime.continuous import ContinuousBatchingChannel  # noqa: E402
from triton_client_tpu.runtime.sessions import SessionLimitError, TokenSessions  # noqa: E402

REL = 0.12
FLIP_REL = 0.6
TIE_BAND = 0.004
SLOTS, SLOT_LEN = 4, 512
SIZES = (96, 160, 1, 1, 1, 1)  # two turns, neither a multiple of the chunk of 64, then steps


@pytest.fixture(scope="module")
def cfg():
    return sc.apply_rehearsal(sc.load_json(ROOT / "benchmarks/configs/ling3flash-ep8-l13.json"))


@pytest.fixture(scope="module")
def model_cfg(cfg):
    m = {k: v for k, v in cfg["model"].items() if k not in ("slot_len", "max_tokens")}
    return ling.LingConfig.from_dict(m)


@pytest.fixture(scope="module")
def tree(cfg):
    return jax.jit(lambda k: reference.init_params(k, None, cfg))(jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(0, 256, sum(SIZES)).astype(np.int32)


@pytest.fixture(scope="module")
def want(cfg, tree, tokens):
    at = np.cumsum(SIZES) - 1
    exact, margin = (np.asarray(a) for a in reference.stream_logits(tree, tokens, cfg, at))
    return exact, margin, at


def _served(tree, model_cfg):
    return ling.stack_layers({**tree, "layers": dict(tree["layers"])}, model_cfg)


def _extend(model_cfg, weights):
    fn = jax.jit(lambda kv, t, s, p, l: ling.extend(model_cfg, weights, kv, t, s, p, l))

    def run(kv, rows, pad_slot=0):
        """``rows``: [(slot, start, tokens)], one row of many tokens or rows of one token each."""
        n = max(len(t) for _, _, t in rows)
        width = lm.token_bucket(n) if n > 1 else 1
        b = len(rows) if n > 1 else lm.step_bucket(len(rows), SLOTS * 2)
        t = np.zeros((b, width), np.int32)
        slots, pos, lengths = np.full(b, pad_slot, np.int32), np.zeros(b, np.int32), np.zeros(b, np.int32)
        for i, (slot, start, toks) in enumerate(rows):
            t[i, : len(toks)], slots[i], pos[i], lengths[i] = toks, slot, start, len(toks)
        logits, expert_rows, kv = fn(kv, t, slots, pos, lengths)
        return np.asarray(logits)[: len(rows)], np.asarray(expert_rows), kv

    return run


@pytest.fixture(scope="module")
def run(model_cfg, tree):
    """The model's launches on the seeded weights, compiled once a launch shape for the whole file."""
    return _extend(model_cfg, _served(tree, model_cfg))


def _stream(run, kv, slot, tokens, sizes):
    """The stream sent as requests of ``sizes`` tokens: the answers, in order, and the cache."""
    got, pos = [], 0
    for n in sizes:
        logits, _, kv = run(kv, [(slot, pos, tokens[pos : pos + n])])
        pos += n
        got.append(logits[0])
    return np.stack(got), kv


def _rel(got, exact):
    return np.sqrt(np.mean((np.asarray(got) - exact) ** 2, axis=-1)) / exact.std()


def _held(got, want):
    exact, margin, _ = want
    rel = _rel(got, exact)
    assert (rel[margin >= TIE_BAND] < REL).all() and (rel < FLIP_REL).all(), rel


# -- the three forms of the recurrence -------------------------------------------


def _kda_inputs(cfg, tree, length, zero):
    m = cfg["model"]
    p = tree["layers"]["1"]["attn"]
    h, d = m["num_attention_heads"], m["head_dim"]
    keys = jax.random.split(jax.random.PRNGKey(length), 3)
    x = jax.random.normal(keys[0], (length, m["hidden_size"]), jnp.float32)
    state = jnp.zeros((h, d, d)) if zero else 0.3 * jax.random.normal(keys[1], (h, d, d), jnp.float32)
    tail = jnp.zeros((3, 3 * h * d)) if zero else jax.random.normal(keys[2], (3, 3 * h * d)).astype(jnp.bfloat16).astype(jnp.float32)
    return p, x, state, tail


@pytest.fixture(scope="module")
def kda_layer(model_cfg, tree):
    """One KDA layer of the program on slot 1 of a cache of two slots, jitted once a launch shape."""
    p, slots = tree["layers"]["1"]["attn"], jnp.ones(1, jnp.int32)
    return jax.jit(lambda x, state, conv, position, length: ling._kda(
        model_cfg, p, x, state, conv, 0, slots, jnp.full(1, position), jnp.full(1, length)))


@pytest.mark.parametrize("zero", [True, False], ids=["from_zero", "from_a_state"])
@pytest.mark.parametrize("length", [1, 15, 16, 17, 63, 64, 65, 160])
def test_the_chunkwise_form_the_step_form_and_the_recurrence_agree(cfg, model_cfg, tree, kda_layer, length, zero):
    """One KDA layer over ``length`` positions from a zero and from a
    non-zero state and convolution tail: the reference's scan a
    position, the program's chunkwise form in one launch (padded to its
    bucket and to whole chunks) and its step form a token at a time
    leave the same outputs, the same state and the same tail."""
    m = cfg["model"]
    p, x, state, tail = _kda_inputs(cfg, tree, length, zero)
    with jax.default_matmul_precision("highest"):
        since = jnp.minimum(jnp.arange(length), 3) if zero else jnp.full(length, 3)
        exact, (exact_state, exact_tail) = reference.kda(
            x, (state, tail), since, jnp.zeros(length, bool), p, m, lambda a: a)
    start = 0 if zero else 7  # a position over 0: the slot's state is read, not zero
    cache = ling.empty_cache(model_cfg, 2, 8)
    held = lambda: (cache["state"].at[0, 1].set(jnp.swapaxes(state, -1, -2)), cache["conv"].at[0, 1].set(tail.astype(jnp.bfloat16).reshape(-1)))
    xb = x.astype(jnp.bfloat16)

    width = max(lm.token_bucket(length), 2)
    padded = jnp.zeros((1, width, x.shape[1]), jnp.bfloat16).at[0, :length].set(xb)
    out, s1, c1 = kda_layer(padded, *held(), start, length)
    chunkwise = (np.asarray(out[0, :length], np.float32), s1[0, 1], c1[0, 1])

    s2, c2 = held()
    steps = []
    for t in range(length):
        out, s2, c2 = kda_layer(xb[None, t : t + 1], s2, c2, start + t, 1)
        steps.append(np.asarray(out[0, 0], np.float32))
    stepped = (np.stack(steps), s2[0, 1], c2[0, 1])

    rms = lambda a: float(np.sqrt(np.mean(np.square(np.asarray(a, np.float32)))))
    for got, got_state, got_tail in (chunkwise, stepped):
        assert rms(got - np.asarray(exact)) < 0.02 * rms(exact) and np.abs(got - np.asarray(exact)).max() < 0.12 * np.abs(exact).max()
        assert rms(np.swapaxes(got_state, -1, -2) - exact_state) < 0.02 * max(rms(exact_state), 0.01)
        assert rms(np.asarray(got_tail, np.float32).reshape(3, -1) - exact_tail) < 0.01 * rms(exact_tail)
    # the other slot and the other layers are as they were, bit for bit
    assert jnp.array_equal(s1[:, 0], cache["state"][:, 0]) and jnp.array_equal(s2[1:], cache["state"][1:])


@pytest.mark.parametrize("length", [64, 160])
def test_the_kernel_interpreted_is_the_plain_chunk(length):
    keys = jax.random.split(jax.random.PRNGKey(length), 6)
    h, d = 2, 16
    q, k, v = (jax.random.normal(keys[i], (length, h, d)) for i in range(3))
    g = -5 * jax.nn.sigmoid(3 * jax.random.normal(keys[3], (length, h, d)))  # the whole range of decays
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (length, h)))
    s0 = jax.random.normal(keys[5], (h, d, d))
    plain = delta_attention.extend(q, k, v, g, beta, s0, kernel=False)
    kernel = delta_attention.extend(q, k, v, g, beta, s0, kernel=True, interpret=True)
    for a, b in zip(plain, kernel):
        np.testing.assert_allclose(a, b, atol=1e-5)
    assert np.isfinite(np.asarray(plain[0])).all()


# -- the model through its launches -------------------------------------------------


def test_turns_then_steps_through_the_three_caches_match_the_full_pass(model_cfg, run, tokens, want):
    cache = ling.empty_cache(model_cfg, SLOTS, SLOT_LEN)
    assert {k: v.shape for k, v in cache.items()} == {
        "latent": (1, SLOTS, SLOT_LEN, 128), "state": (6, SLOTS, 4, 16, 16), "conv": (6, SLOTS, 3 * 192)}
    got, _ = _stream(run, cache, 2, tokens, SIZES)
    _held(got, want)


@pytest.mark.parametrize("how", ["zeroed", "taken_out"])
def test_a_new_session_in_a_used_slot_starts_from_zero(model_cfg, tree, run, tokens, want, how, monkeypatch):
    """A session ended and another in the SAME slot: the launch reads a
    zero state for the row at position 0, so the answers are those of a
    fresh cache, bit for bit. With the zeroing taken out (the KDA layer
    told that no row starts) they are not: the seeded decay is slow
    enough to tell."""
    if how == "taken_out":
        kda = ling._kda
        monkeypatch.setattr(ling, "_kda", lambda cfg, p, x, state, conv, layer, slots, positions, lengths:
                            kda(cfg, p, x, state, conv, layer, slots, positions + 1, lengths))
        run = _extend(model_cfg, _served(tree, model_cfg))  # traced anew, without the zeroing
    other = np.random.default_rng(9).integers(0, 256, sum(SIZES)).astype(np.int32)
    _, used = _stream(run, ling.empty_cache(model_cfg, SLOTS, SLOT_LEN), 2, other, SIZES)
    got, _ = _stream(run, used, 2, tokens, SIZES)
    fresh, _ = _stream(run, ling.empty_cache(model_cfg, SLOTS, SLOT_LEN), 2, tokens, SIZES)
    if how == "zeroed":
        np.testing.assert_array_equal(got, fresh)
        _held(got, want)
    else:  # the first turn's answer carries what the former session left: several times what bfloat16 costs it
        assert _rel(got[:1], fresh[:1])[0] > 0.08 > 2 * _rel(fresh[:1], want[0][:1])[0]


def test_a_merged_step_launch_equals_its_rows_alone_and_touches_no_other_slot(model_cfg, run):
    rng = np.random.default_rng(11)
    streams = [rng.integers(0, 256, n + 2).astype(np.int32) for n in (40, 96, 130, 70)]

    def turns():
        kv = ling.empty_cache(model_cfg, SLOTS, SLOT_LEN)
        for slot, s in enumerate(streams):
            _, _, kv = run(kv, [(slot, 0, s[:-2])])
        return kv

    merged_kv, alone_kv = turns(), turns()
    for step in range(2):
        before = merged_kv
        # slots 1-3 step; slot 0 holds a session too and is where the launch's five pad rows point
        rows = [(slot, len(s) - 2 + step, s[len(s) - 2 + step :][:1]) for slot, s in enumerate(streams)][1:]
        merged, _, merged_kv = run(merged_kv, rows, pad_slot=0)
        for name in ("state", "conv", "latent"):
            assert jnp.array_equal(merged_kv[name][:, 0], before[name][:, 0]), name
            assert not jnp.array_equal(merged_kv[name][:, 1], before[name][:, 1]), name
        for i, row in enumerate(rows):
            alone, _, alone_kv = run(alone_kv, [row], pad_slot=0)
            np.testing.assert_allclose(merged[i], alone[0], atol=2e-2)
    np.testing.assert_allclose(merged_kv["state"][:, 1:], alone_kv["state"][:, 1:], atol=1e-3)


def test_the_shares_add_up_to_the_uncut_layer(cfg, model_cfg, tree):
    """The routed parts of both shares (each a whole group of 8), with
    the shared expert counted once, give the reference's UNCUT layer."""
    import dataclasses

    m = cfg["model"]
    full = reference._mlp_params(jax.random.PRNGKey(3), m["hidden_size"], m["moe_intermediate_size"], (m["router_experts"],))
    layer = {k: v for k, v in tree["layers"]["1"].items() if k != "attn"}
    h = jax.random.normal(jax.random.PRNGKey(4), (24, m["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.feed(h, {**layer, "experts": full}, {**m, "experts_here": m["router_experts"], "expert_offset": 0},
                                  lambda a: a.astype(jnp.bfloat16).astype(jnp.float32))

    def share(offset):
        held = jax.tree_util.tree_map(lambda w: w[offset : offset + 8] if offset < 16 else w[:8], full)
        c = dataclasses.replace(model_cfg, expert_offset=offset)
        out, rows = ling._feed(c, {**layer, "experts": held}, h[None], jnp.ones((1, 24), bool))
        return np.asarray(out[0]), np.asarray(rows)

    (base, none), (first, rows0), (second, rows1) = share(16), share(0), share(8)
    assert none.sum() == 0 and rows0.sum() + rows1.sum() == 24 * m["num_experts_per_tok"]
    assert np.abs(base + (first - base) + (second - base) - np.asarray(uncut)).max() < 0.08
    assert np.abs(base - np.asarray(uncut)).max() > 0.3


@pytest.mark.parametrize("bad", [
    {"layer_types": ["kda"] * 7}, {"layer_types": ["kda", "mla", "kda", "kda", "mla", "kda", "kda"]},
    {"layer_types": ["kda"] * 6 + ["gqa"]}, {"short_conv_kernel_size": 3}, {"sliding_window": 4},
])
def test_an_entry_whose_layers_are_no_whole_periods_is_refused(cfg, bad):
    m = {k: v for k, v in cfg["model"].items() if k not in ("slot_len", "max_tokens")}
    with pytest.raises((ValueError, KeyError)):
        ling.LingConfig.from_dict({**m, **bad})


# -- the slot's state in TokenSessions -----------------------------------------------


def _sessions(**kw):
    return TokenSessions(2, 64, 32, lm.token_bucket, lambda n: lm.step_bucket(n, 2), time_fn=lambda: 0.0, **kw)


def _open(state, sid, n, start=False, end=False):
    return state.open(InferRequest("m", {"tokens": np.zeros((1, n), np.int32)}, sequence_id=sid, sequence_start=start, sequence_end=end))


def _send(state, sid, n, **kw):
    launch, ticket = _open(state, sid, n, **kw)
    state.close(ticket, {"logits": np.zeros((launch.inputs["tokens"].shape[0], 4), np.float32)})
    return launch.inputs


def test_the_counters_and_the_gauge_of_a_slots_state():
    state = _sessions(state_bytes=1000)
    _send(state, "a", 12, start=True)
    _send(state, "a", 8)
    _send(state, "a", 1)
    _send(state, "b", 1, start=True)
    stats = state.stats()
    assert (stats["lm_state_resets"], stats["lm_state_carries"], stats["lm_state_lost"]) == (2, 1, 0)
    assert stats["session_state_bytes"] == 2000 and stats["session_cache_tokens"] == 22
    _send(state, "a", 1, end=True)
    assert state.stats()["session_state_bytes"] == 1000
    assert _send(state, "c", 4, start=True)["positions"].tolist() == [0]  # the freed slot, zeroed by the launch it joins
    assert state.stats()["lm_state_resets"] == 3
    plain = _sessions()
    _send(plain, "a", 12, start=True)
    _send(plain, "a", 8)
    assert {k: plain.stats()[k] for k in ("lm_state_resets", "lm_state_carries", "session_state_bytes")} == {
        "lm_state_resets": 0, "lm_state_carries": 0, "session_state_bytes": 0}


@pytest.mark.parametrize("state_bytes", [1000, 0], ids=["with_a_state", "rows_alone"])
def test_a_launch_that_failed_after_dispatch_ends_its_sessions_with_the_reason(state_bytes):
    """Refused before dispatch (``abort``): state and length as they
    were, for any model. Failed after dispatch (``close`` without
    outputs): a model that holds a state has lost it, its sessions end
    and say why; a model whose slot is rows and a length takes the
    length back and goes on, as before."""
    state = _sessions(state_bytes=state_bytes)
    _send(state, "a", 12, start=True)
    _, ticket = _open(state, "a", 8)
    state.abort(ticket)
    assert state.stats()["session_cache_tokens"] == 12 and state.stats()["lm_state_lost"] == 0
    assert _send(state, "a", 8)["positions"].tolist() == [12]
    _, ticket = _open(state, "a", 4)
    state.close(ticket, None)
    if state_bytes:
        assert state.stats()["lm_state_lost"] == 1 and state.stats()["session_cache_slots_in_use"] == 0
        with pytest.raises(SessionLimitError, match="recurrent state was lost in a launch that failed after dispatch"):
            _open(state, "a", 1)
        assert _send(state, "a", 4, start=True)["positions"].tolist() == [0]  # sequence_start: a new session, no stale reason
        with pytest.raises(SessionLimitError, match="holds no cache slot"):
            _open(state, "never", 1)
    else:
        assert state.stats()["lm_state_lost"] == 0
        assert _send(state, "a", 4)["positions"].tolist() == [20]


# -- through the served entry ---------------------------------------------------------


@pytest.fixture(scope="module")
def channel(cfg, tree, tmp_path_factory):
    root = tmp_path_factory.mktemp("repo")
    name = sc.write_repository(root, cfg, tree, True)
    from triton_client_tpu.runtime.disk_repository import scan_disk

    return TPUChannel(scan_disk(root), devices=jax.devices()[:1]), name


def test_the_in_process_call_carries_the_state_over_turns_and_steps(channel, tokens, want):
    ch, name = channel
    model = ch.served_model(name)
    assert model.spec.extra["family"] == "bailing_hybrid" and set(model.params[lm.STATE_KEY]) == {"latent", "state", "conv"}
    pos, got = 0, []
    for n in SIZES:
        got.append(np.asarray(model.infer_fn({"tokens": tokens[None, pos : pos + n]})["logits"])[0])
        pos += n
    _held(np.stack(got), want)
    stats = model.sessions.stats()
    assert stats["lm_state_resets"] == 1 and stats["lm_state_carries"] == 1
    assert stats["session_state_bytes"] == 6 * (4 * 16 * 16 * 4 + 3 * 192 * 2)


def test_turns_and_merged_steps_through_the_batcher_match_the_full_pass(channel, cfg, tree, tokens, want):
    """Three sessions side by side through ``ContinuousBatchingChannel``
    over the staged channel: their turns one launch each, their steps
    merged; every answer against the reference's full pass, the three
    cache arrays donated together, and a slot freed by ``sequence_end``
    taken by a new session that answers as on a fresh server."""
    ch, name = channel
    batcher = ContinuousBatchingChannel(ch, max_batch=8, pipeline_depth=2)
    rng = np.random.default_rng(3)
    streams = {"s0": tokens, "s1": rng.integers(0, 256, sum(SIZES)).astype(np.int32),
               "s2": rng.integers(0, 256, sum(SIZES)).astype(np.int32)}
    answers = {sid: [] for sid in streams}
    turnstile = threading.Barrier(len(streams))

    def caller(sid):
        pos = 0
        for i, n in enumerate(SIZES):
            if n == 1:
                turnstile.wait(timeout=120)  # the steps of the three arrive together
            answers[sid].append(batcher.do_inference(InferRequest(
                name, {"tokens": streams[sid][None, pos : pos + n]}, sequence_id=sid,
                sequence_start=i == 0, sequence_end=i == len(SIZES) - 1)).outputs["logits"][0])
            pos += n

    try:
        before, in_use = ch.served_model(name).params[lm.STATE_KEY], ch.session_stats()["models"][name]["session_cache_slots_in_use"]
        threads = [threading.Thread(target=caller, args=(sid,)) for sid in streams]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert all(a.is_deleted() for a in before.values())  # donated together, none copied
        _held(np.stack(answers["s0"]), want)
        at = want[2]
        for sid in ("s1", "s2"):
            exact, margin = (np.asarray(a) for a in reference.stream_logits(tree, streams[sid], cfg, at))
            _held(np.stack(answers[sid]), (exact, margin, at))
        stats = ch.session_stats()["models"][name]
        assert stats["session_cache_slots_in_use"] == in_use and stats["lm_step_sessions"] > stats["lm_step_launches"]
        # the freed slots go to new sessions
        again = batcher.do_inference(InferRequest(name, {"tokens": tokens[None, :96]}, sequence_id="t", sequence_start=True, sequence_end=True))
        assert _rel(again.outputs["logits"], want[0][:1])[0] < (REL if want[1][0] >= TIE_BAND else FLIP_REL)
    finally:
        batcher.close()


class _Inner:
    """A ``session_merge`` model that answers a row a token; ``extra`` is what its spec declares."""

    batch_multiple = 1

    def __init__(self, extra):
        self.extra, self.launches = extra, []

    def get_metadata(self, name, version=""):
        return types.SimpleNamespace(extra=self.extra)

    def do_inference_async(self, request):
        self.launches.append(request)
        answer = np.repeat(np.asarray(request.inputs["tokens"]).reshape(-1, 1).astype(np.float32), 3, axis=1)
        return types.SimpleNamespace(result=lambda: InferResponse(model_name=request.model_name, outputs={"y": answer}))


@pytest.mark.parametrize("family", ["axk1", "bailing_hybrid"])
def test_a_one_token_step_still_merges_and_passes_the_step_wait(family):
    """The one-token steps of an entry whose slots hold no state, and of
    one whose slots do, come under the same ``__session_step__`` key and
    go through ``_step_wait_locked``: the batcher knows nothing of the
    state (runtime/continuous.py is as it was)."""
    inner = _Inner({"session_merge": True, "step_width": 1, "family": family})
    batcher = ContinuousBatchingChannel(inner, max_batch=8, pipeline_depth=2)
    waits = []
    held = batcher._step_wait_locked
    batcher._step_wait_locked = lambda key, *rest: waits.append(key) or held(key, *rest)
    answers = {}

    def caller(sid, token):
        answers[sid] = batcher.do_inference(InferRequest("m", {"tokens": np.full((1, 1), token, np.int32)}, sequence_id=sid)).outputs["y"]

    try:
        assert batcher._session_step(InferRequest("m", {"tokens": np.zeros((1, 1), np.int32)}, sequence_id="a"))
        assert not batcher._session_step(InferRequest("m", {"tokens": np.zeros((1, 8), np.int32)}, sequence_id="a"))
        threads = [threading.Thread(target=caller, args=(f"s{i}", 10 * i)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        batcher.close()
    assert waits and all(key[0] == "__session_step__" for key in waits)
    assert [answers[f"s{i}"][0, 0] for i in range(4)] == [0.0, 10.0, 20.0, 30.0]
    assert sum(len(r.sequence_rows or (1,)) for r in inner.launches) == 4
