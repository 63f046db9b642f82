"""SmallThinker served in token sessions, at tiny widths on the CPU (the
benchmark configuration's own ``rehearsal`` sizes: hidden 64, 4 query
heads over 2 key/value heads of 16, two periods of one full layer
without positions and three window layers of 32 with rotary positions, 8
ReLU-gated experts top-2 behind a router that reads the layer's input, a
ring of 96 rows), against the plain float32 reference
``benchmarks/references/smallthinker.py`` on seeded weights: LOGITS, not
tokens, of every turn's last position and every step.

The program differs from the reference by what bfloat16 activations
cost. An answer is held to ``REL`` of the logits' spread (sound answers
read 0.005-0.02 of it at these widths), and where the router is within
``TIE_BAND`` of changing an expert to ``FLIP_REL``. Each of the
reference's five wrong ways (no window, rotated full layers, unrotated
window layers, SiLU, a router behind the attention) moves answers by
0.15-0.9 of the spread.
"""

from __future__ import annotations

import pathlib
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks import server_child as sc  # noqa: E402
from benchmarks.references import smallthinker as reference  # noqa: E402
from triton_client_tpu.channel.base import InferRequest  # noqa: E402
from triton_client_tpu.channel.tpu_channel import TPUChannel  # noqa: E402
from triton_client_tpu.models import smallthinker  # noqa: E402
from triton_client_tpu.ops import block_attention, rope  # noqa: E402
from triton_client_tpu.pipelines import lm  # noqa: E402
from triton_client_tpu.runtime.continuous import ContinuousBatchingChannel  # noqa: E402
from triton_client_tpu.runtime.sessions import SessionLimitError, TokenSessions  # noqa: E402

REL = 0.06
FLIP_REL = 0.5
TIE_BAND = 0.004
SLOTS, SLOT_LEN, WINDOW, RING = 4, 512, 32, 96
#: turns that cross the window and are no multiple of it, then steps: inside the ring, and round it three times
STREAMS = {"ring_not_wrapped": (24, 52, 1, 1, 1, 1), "ring_wrapped_thrice": (24, 52, 52, 52, 52, 52, 1, 1, 1, 1)}


@pytest.fixture(scope="module")
def cfg():
    return sc.apply_rehearsal(sc.load_json(ROOT / "benchmarks/configs/smallthinker21b-ep1-l12.json"))


@pytest.fixture(scope="module")
def model_cfg(cfg):
    m = {k: v for k, v in cfg["model"].items() if k not in ("slot_len", "max_tokens")}
    return smallthinker.Config.from_dict(m)


@pytest.fixture(scope="module")
def tree(cfg):
    return jax.jit(lambda k: reference.init_params(k, None, cfg))(jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(0, 256, sum(STREAMS["ring_wrapped_thrice"])).astype(np.int32)


def _want(cfg, tree, tokens, sizes, **wrong):
    at = np.cumsum(sizes) - 1
    exact, margin = (np.asarray(a) for a in reference.stream_logits(tree, tokens[: sum(sizes)], cfg, at, **wrong))
    return exact, margin, at


@pytest.fixture(scope="module")
def want(cfg, tree, tokens):
    return {name: _want(cfg, tree, tokens, sizes) for name, sizes in STREAMS.items()}


def _extend(model_cfg, weights):
    fn = jax.jit(lambda kv, t, s, p, l: smallthinker.extend(model_cfg, weights, kv, t, s, p, l))

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
    return _extend(model_cfg, smallthinker.stack_layers({**tree, "layers": dict(tree["layers"])}, model_cfg))


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


def _holds(got, want) -> bool:
    exact, margin, _ = want
    rel = _rel(got, exact)
    return bool((rel[margin >= TIE_BAND] < REL).all() and (rel < FLIP_REL).all())


@pytest.fixture(scope="module")
def served(model_cfg, run, tokens):
    """Each stream through the model's launches in a slot of a fresh cache: its answers."""
    return {name: _stream(run, smallthinker.empty_cache(model_cfg, SLOTS, SLOT_LEN), 2, tokens, sizes)[0]
            for name, sizes in STREAMS.items()}


# -- the model through its launches ---------------------------------------------------


def test_a_slot_keeps_rows_in_two_geometries(model_cfg):
    cache = smallthinker.empty_cache(model_cfg, SLOTS, SLOT_LEN)
    assert jax.tree_util.tree_map(lambda a: a.shape, cache) == {
        "full": {"k": (2, SLOTS, SLOT_LEN, 32), "v": (2, SLOTS, SLOT_LEN, 32)},
        "window": {"k": (6, SLOTS, RING, 32), "v": (6, SLOTS, RING, 32)}}
    assert model_cfg.row_geometries(SLOT_LEN) == (("full", 2, SLOT_LEN, 0, 128), ("window", 6, RING, WINDOW, 128))


@pytest.mark.parametrize("stream", sorted(STREAMS))
def test_turns_then_steps_through_ring_and_rows_match_the_full_pass(served, want, stream):
    assert _holds(served[stream], want[stream]), _rel(served[stream], want[stream][0])


@pytest.mark.parametrize("wrong", reference.WRONG)
@pytest.mark.parametrize("stream", sorted(STREAMS))
def test_the_reference_computed_wrong_is_not_what_is_served(cfg, tree, tokens, served, stream, wrong):
    """The five negative controls: without the window (which the short
    stream's FIRST answer, inside the window, cannot tell), with rotary
    positions in the full layers, without them in the window layers,
    with SiLU for ReLU, with the router behind the attention."""
    bad = _want(cfg, tree, tokens, STREAMS[stream], wrong=wrong)
    assert not _holds(served[stream], bad)
    if wrong == "window_ignored":  # inside the window a window layer and a full one read the same keys
        assert _rel(served[stream][:1], bad[0][:1])[0] < FLIP_REL


def test_a_new_session_in_a_used_slot_sees_no_stale_row(model_cfg, run, tokens, served):
    """A session ended and another in the SAME slot, whose ring and rows
    hold the former's keys at every row: positions, not contents, decide
    the mask, so the answers are those of a fresh cache, bit for bit."""
    sizes = STREAMS["ring_wrapped_thrice"]
    other = np.random.default_rng(9).integers(0, 256, sum(sizes)).astype(np.int32)
    _, used = _stream(run, smallthinker.empty_cache(model_cfg, SLOTS, SLOT_LEN), 2, other, sizes)
    got, _ = _stream(run, used, 2, tokens, sizes)
    np.testing.assert_array_equal(got, served["ring_wrapped_thrice"])


def test_a_merged_step_launch_of_a_short_and_a_long_session_equals_its_rows_alone(model_cfg, run):
    """Sessions shorter than the window (20), past it (70) and round the
    ring (130, 230) step in ONE launch: each row as alone, the slot the
    pad rows point at untouched."""
    rng = np.random.default_rng(11)
    streams = [rng.integers(0, 256, n + 2).astype(np.int32) for n in (40, 20, 130, 70)]

    def turns():
        kv = smallthinker.empty_cache(model_cfg, SLOTS, SLOT_LEN)
        for slot, s in enumerate(streams):
            for lo in range(0, len(s) - 2, 52):
                _, _, kv = run(kv, [(slot, lo, s[lo : min(lo + 52, len(s) - 2)])])
        return kv

    merged_kv, alone_kv = turns(), turns()
    for step in range(2):
        before = merged_kv
        rows = [(slot, len(s) - 2 + step, s[len(s) - 2 + step :][:1]) for slot, s in enumerate(streams)][1:]
        merged, _, merged_kv = run(merged_kv, rows, pad_slot=0)
        for kind in ("full", "window"):
            for name in ("k", "v"):
                assert jnp.array_equal(merged_kv[kind][name][:, 0], before[kind][name][:, 0]), (kind, name)
                assert not jnp.array_equal(merged_kv[kind][name][:, 1], before[kind][name][:, 1]), (kind, name)
        for i, row in enumerate(rows):
            alone, _, alone_kv = run(alone_kv, [row], pad_slot=0)
            np.testing.assert_allclose(merged[i], alone[0], atol=2e-2)


def test_rotary_positions_shifted_by_a_constant_change_no_answer(model_cfg, tree, tokens, served, monkeypatch):
    """The full layers carry no positional encoding and the window
    layers' is relative: with every ROTARY position shifted by a constant
    (the masks keep the true ones) the answers stay, to what bfloat16
    rounds. (That the window layers do rotate, and the full ones do not,
    are two of the controls above.)"""
    tables = rope.rope_tables
    monkeypatch.setattr(rope, "rope_tables", lambda pos, yarn: tables(pos + 1000, yarn))
    shifted = _extend(model_cfg, smallthinker.stack_layers({**tree, "layers": dict(tree["layers"])}, model_cfg))
    sizes = STREAMS["ring_not_wrapped"]
    got, _ = _stream(shifted, smallthinker.empty_cache(model_cfg, SLOTS, SLOT_LEN), 2, tokens, sizes)
    assert (_rel(got, served["ring_not_wrapped"]) < REL).all()


@pytest.mark.parametrize("start", [0, 60, 90, 200])
def test_a_span_written_to_the_ring_wraps_at_its_end(start):
    """``write_ring`` against the plain statement ``row = position % rows``, pad rows and all."""
    rows, n = 96, 16
    kv = {"k": jnp.zeros((2, 3, rows, 8), jnp.bfloat16), "v": jnp.zeros((2, 3, rows, 8), jnp.bfloat16)}
    k = jnp.arange(1, n * 8 + 1, dtype=jnp.float32).reshape(n, 2, 4)
    out, (key_rows, _) = jax.jit(block_attention.write_ring)(kv, 1, 2, start, k, -k)
    want = np.zeros((rows, 8), np.float32)
    want[(start + np.arange(n)) % rows] = np.asarray(k.reshape(n, 8).astype(jnp.bfloat16), np.float32)
    np.testing.assert_array_equal(np.asarray(key_rows, np.float32), want)
    np.testing.assert_array_equal(np.asarray(out["k"][1, 2], np.float32), want)
    np.testing.assert_array_equal(np.asarray(out["v"][1, 2], np.float32), -want)
    assert not np.asarray(out["k"][0]).any() and not np.asarray(out["k"][1, :2]).any()


@pytest.mark.parametrize("bad", [
    {"layer_types": ["window"] * 8}, {"layer_types": ["full", "window", "window", "full", "window", "window", "window", "window"]},
    {"layer_types": ["full", "window", "window", "gqa"] * 2}, {"window_ring": 32}, {"rope_scaling": 4},
])
def test_an_entry_whose_layers_are_no_whole_periods_is_refused(cfg, bad):
    m = {k: v for k, v in cfg["model"].items() if k not in ("slot_len", "max_tokens")}
    with pytest.raises((ValueError, KeyError)):
        smallthinker.Config.from_dict({**m, **bad})


# -- the two geometries in TokenSessions ----------------------------------------------

GEOMETRIES = (("full", 2, 64, 0, 128), ("window", 6, 24, 8, 128))


def _sessions(**kw):
    return TokenSessions(2, 64, 16, lm.token_bucket, lambda n: lm.step_bucket(n, 2), time_fn=lambda: 0.0, **kw)


def _send(state, sid, n, start=False, end=False):
    launch, ticket = state.open(InferRequest(
        "m", {"tokens": np.zeros((1, n), np.int32)}, sequence_id=sid, sequence_start=start, sequence_end=end))
    state.close(ticket, {"logits": np.zeros((launch.inputs["tokens"].shape[0], 4), np.float32)})
    return launch.inputs


def test_keys_read_and_bytes_in_use_follow_the_geometries():
    state = _sessions(geometries=GEOMETRIES, layers=8)
    _send(state, "a", 12, start=True)
    _send(state, "a", 16)
    _send(state, "a", 1)
    _send(state, "b", 5, start=True)
    stats = state.stats()
    visible = sum(range(1, 30)) + sum(range(1, 6))  # a token at position p may see p + 1, a layer
    windowed = sum(min(p, 8) for p in range(1, 30)) + sum(range(1, 6))
    assert stats["lm_keys_visible"] == 8 * visible and stats["lm_keys_read"] == 2 * visible + 6 * windowed
    assert stats["session_cache_bytes"] == 2 * (2 * 64 + 6 * 24) * 128
    assert stats["session_cache_bytes_in_use"] == (2 * 29 + 6 * 24 + 2 * 5 + 6 * 5) * 128  # a ring holds at most its rows
    assert stats["session_cache_bytes_by_geometry"]["window"] == {"allocated": 2 * 6 * 24 * 128, "in_use": 6 * (24 + 5) * 128}
    _send(state, "a", 1, end=True)
    assert state.stats()["session_cache_bytes_in_use"] == (2 * 5 + 6 * 5) * 128
    plain = _sessions()
    _send(plain, "a", 12, start=True)
    assert {k: plain.stats()[k] for k in ("lm_keys_read", "session_cache_bytes", "session_cache_bytes_in_use")} == {
        "lm_keys_read": 0, "session_cache_bytes": 0, "session_cache_bytes_in_use": 0}


def test_a_session_is_admitted_by_positions_whatever_the_ring():
    state = _sessions(geometries=GEOMETRIES)
    for i in range(4):
        _send(state, "a", 16, start=i == 0)  # 64 positions: the ring of 24 went round, the slot is full
    with pytest.raises(SessionLimitError, match="outgrow its cache slot of 64 positions"):
        _send(state, "a", 1)


def test_a_ring_that_cannot_hold_its_window_and_the_longest_launch_is_refused():
    with pytest.raises(ValueError, match="ring of 20 rows"):
        _sessions(geometries=(("window", 6, 20, 8, 128),))
    _sessions(geometries=(("window", 6, 23, 8, 128),))  # 8 + 16 - 1


# -- through the served entry -----------------------------------------------------------


@pytest.fixture(scope="module")
def channel(cfg, tree, tmp_path_factory):
    root = tmp_path_factory.mktemp("repo")
    name = sc.write_repository(root, cfg, tree, True)
    from triton_client_tpu.runtime.disk_repository import scan_disk

    return TPUChannel(scan_disk(root), devices=jax.devices()[:1]), name


def test_the_in_process_call_is_the_family_served_through_build_registered(channel, tokens, want):
    ch, name = channel
    model = ch.served_model(name)
    assert model.spec.extra["family"] == "smallthinker" and set(model.params[lm.STATE_KEY]) == {"full", "window"}
    sizes, pos, got = STREAMS["ring_not_wrapped"], 0, []
    for n in sizes:
        got.append(np.asarray(model.infer_fn({"tokens": tokens[None, pos : pos + n]})["logits"])[0])
        pos += n
    assert _holds(np.stack(got), want["ring_not_wrapped"])
    stats = model.sessions.stats()
    assert stats["session_cache_bytes"] == 16 * (2 * SLOT_LEN + 6 * RING) * 128
    assert 0 < stats["lm_keys_read"] < stats["lm_keys_visible"]


def test_turns_and_merged_steps_through_the_batcher_match_the_full_pass(channel, cfg, tree, tokens, want):
    """Three sessions side by side through ``ContinuousBatchingChannel``
    over the staged channel, one that stays inside the ring and two that
    go round it: their turns one launch each, their steps merged; every
    answer against the reference's full pass, the four cache arrays
    donated together, and a slot freed by ``sequence_end`` taken by a new
    session that answers as on a fresh server."""
    ch, name = channel
    batcher = ContinuousBatchingChannel(ch, max_batch=8, pipeline_depth=2)
    long_, short = STREAMS["ring_wrapped_thrice"], STREAMS["ring_not_wrapped"]
    rng = np.random.default_rng(3)
    streams = {"s0": (tokens, long_), "s1": (tokens, short), "s2": (rng.integers(0, 256, sum(long_)).astype(np.int32), long_)}
    answers = {sid: [] for sid in streams}
    turnstile = threading.Barrier(len(streams))

    def caller(sid):
        ids, sizes = streams[sid]
        pos = 0
        for i, n in enumerate(sizes):
            if n == 1:
                turnstile.wait(timeout=120)  # the steps of the three arrive together
            answers[sid].append(batcher.do_inference(InferRequest(
                name, {"tokens": ids[None, pos : pos + n]}, sequence_id=sid,
                sequence_start=i == 0, sequence_end=i == len(sizes) - 1)).outputs["logits"][0])
            pos += n

    try:
        before = jax.tree_util.tree_leaves(ch.served_model(name).params[lm.STATE_KEY])
        in_use = ch.session_stats()["models"][name]["session_cache_slots_in_use"]
        threads = [threading.Thread(target=caller, args=(sid,)) for sid in streams]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert len(before) == 4 and all(a.is_deleted() for a in before)  # donated together, none copied
        assert _holds(np.stack(answers["s0"]), want["ring_wrapped_thrice"])
        assert _holds(np.stack(answers["s1"]), want["ring_not_wrapped"])
        assert _holds(np.stack(answers["s2"]), _want(cfg, tree, streams["s2"][0], long_))
        stats = ch.session_stats()["models"][name]
        assert stats["session_cache_slots_in_use"] == in_use and stats["lm_step_sessions"] > stats["lm_step_launches"]
        # the freed slots go to new sessions
        again = batcher.do_inference(InferRequest(name, {"tokens": tokens[None, :24]}, sequence_id="t", sequence_start=True, sequence_end=True))
        exact, margin, _ = want["ring_not_wrapped"]
        assert _rel(again.outputs["logits"], exact[:1])[0] < (REL if margin[0] >= TIE_BAND else FLIP_REL)
    finally:
        batcher.close()
