"""DeepSeek-V3.2-Exp served in token sessions, at tiny widths on the CPU
(the benchmark configuration's own ``rehearsal`` sizes: hidden 64, 4
heads, an indexer of 4 heads of 16 values that keeps 16 positions of
contexts up to 96, 16 experts in 4 groups of which 2 are kept, top-4
with 4 held, 1 dense + 2 expert layers), against the plain float32
reference ``benchmarks/references/dsv32.py`` on seeded weights.

Two tolerances, as in ``benchmarks/checks/logits_turns.py``. While the
context is at most ``index_topk`` nothing is selected and the program
differs from the reference by what bfloat16 activations cost: ``RATIO``
times the reference's own ``sensitivity``, as in ``test_axk1.py``. Past
it, a query's selected positions differ here and there between float32
and bfloat16 index scores (one exchanged key of 16 moves an answer by a
tenth of the logits' spread), so answers are held to ``LONG_REL`` of
that spread in the mean, which a wrong selection (the latest positions,
or every position) passes several times over.
"""

from __future__ import annotations

import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks import server_child as sc  # noqa: E402
from benchmarks.references import dsv32 as reference  # noqa: E402
from triton_client_tpu.channel.base import InferRequest  # noqa: E402
from triton_client_tpu.channel.tpu_channel import TPUChannel  # noqa: E402
from triton_client_tpu.models import axk1  # noqa: E402
from triton_client_tpu.ops import experts as experts_op  # noqa: E402
from triton_client_tpu.ops import latent_attention, sparse_index  # noqa: E402
from triton_client_tpu.pipelines import lm  # noqa: E402
from triton_client_tpu.runtime.sessions import TokenSessions  # noqa: E402

RATIO = 2.5
LONG_REL = 0.2
SLOTS, SLOT_LEN = 4, 96
TIE_BAND = 0.004


@pytest.fixture(scope="module")
def cfg():
    return sc.apply_rehearsal(sc.load_json(ROOT / "benchmarks/configs/dsv32-ep32-l6.json"))


@pytest.fixture(scope="module")
def model_cfg(cfg):
    m = {k: v for k, v in cfg["model"].items() if k not in ("slot_len", "max_tokens")}
    return axk1.AXK1Config.from_dict(m)


@pytest.fixture(scope="module")
def tree(cfg):
    return jax.jit(lambda k: reference.init_params(k, None, cfg))(jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(0, 256, 80).astype(np.int32)


@pytest.fixture(scope="module")
def want(cfg, tree, tokens):
    exact, margin = (np.asarray(a) for a in reference.stream_logits(tree, tokens, cfg, 0))
    rounded = np.asarray(reference.stream_logits(tree, tokens[:16], cfg, 0, round_acts=True)[0])
    return exact, float(np.sqrt(np.mean((rounded - exact[:16]) ** 2))), margin


def _served(tree, model_cfg):
    return axk1.stack_layers({**tree, "layers": dict(tree["layers"])}, model_cfg)


def _extend(model_cfg, weights):
    fn = jax.jit(lambda kv, t, s, p, l: axk1.extend(model_cfg, weights, kv, t, s, p, l))

    def run(kv, rows):
        """``rows``: [(slot, start, tokens)], all of one width or all one token."""
        n = max(len(t) for _, _, t in rows)
        width = lm.token_bucket(n) if n > 1 else 1
        b = len(rows) if n > 1 else lm.step_bucket(len(rows), SLOTS * 2)
        t = np.zeros((b, width), np.int32)
        slots, pos, lengths = (np.zeros(b, np.int32) for _ in range(3))
        for i, (slot, start, toks) in enumerate(rows):
            t[i, : len(toks)], slots[i], pos[i], lengths[i] = toks, slot, start, len(toks)
        logits, expert_rows, kv = fn(kv, t, slots, pos, lengths)
        return np.asarray(logits)[: len(rows)], np.asarray(expert_rows), kv

    return run


def _turns_then_steps(run, kv, slot, tokens, sizes):
    """The stream sent as requests of ``sizes`` tokens: the answers and the positions they answer."""
    got, at, pos = [], [], 0
    for n in sizes:
        logits, _, kv = run(kv, [(slot, pos, tokens[pos : pos + n])])
        pos += n
        got.append(logits[0]), at.append(pos - 1)
    return np.stack(got), np.asarray(at), kv


def _rel(got, exact):
    return float(np.sqrt(np.mean((got - exact) ** 2)) / exact.std())


SIZES = (12, 16, 16, 1, 1, 1, 1, 16, 1, 1)  # a first turn under index_topk, two turns, steps, a further turn, steps


def test_turns_then_steps_through_both_caches_match_the_full_forward_pass(model_cfg, tree, tokens, want):
    exact, sensitivity, margin = want
    run = _extend(model_cfg, _served(tree, model_cfg))
    got, at, kv = _turns_then_steps(run, axk1.empty_cache(model_cfg, SLOTS, SLOT_LEN), 2, tokens, SIZES)
    assert set(kv) == {"latent", "index"} and kv["index"].shape == (3, SLOTS, SLOT_LEN, 16)
    assert margin[at[0]] >= TIE_BAND  # the first turn: 12 positions, nothing selected
    assert np.sqrt(np.mean((got[0] - exact[at[0]]) ** 2)) / sensitivity < RATIO
    long_ = at >= 16
    assert _rel(got[long_], exact[at[long_]]) < LONG_REL
    # an exchanged key also moves what later layers cache for that position, so the noise carries on down the
    # stream; an answer that saw none is as close as the short one
    each = np.sqrt(np.mean((got[long_] - exact[at[long_]]) ** 2, axis=1))
    assert each.min() < RATIO * sensitivity


@pytest.mark.parametrize("wrong", ["recent", "dense"])
def test_a_wrong_selection_fails_the_same_comparison(cfg, model_cfg, tree, tokens, want, wrong, monkeypatch):
    exact = want[0]
    if wrong == "dense":
        served_cfg = dataclasses.replace(model_cfg, index_topk=SLOT_LEN)
    else:
        served_cfg = model_cfg
        by_position = lambda s: jnp.where(jnp.isfinite(s), jnp.arange(s.shape[-1], dtype=s.dtype), s)
        step, extend = sparse_index.step_scores, sparse_index.extend_scores
        monkeypatch.setattr(sparse_index, "step_scores", lambda *a, **k: by_position(step(*a, **k)))
        monkeypatch.setattr(sparse_index, "extend_scores", lambda *a, **k: by_position(extend(*a, **k)))
    run = _extend(served_cfg, _served(tree, model_cfg))
    got, at, _ = _turns_then_steps(run, axk1.empty_cache(model_cfg, SLOTS, SLOT_LEN), 0, tokens, SIZES)
    long_ = at >= 32
    assert _rel(got[long_], exact[at[long_]]) > 2 * LONG_REL
    # ... and the reference's own wrong selection is what the wrongly served program computes
    same, _ = reference.stream_logits(tree, tokens, cfg, at, select=wrong)
    assert _rel(got[long_], np.asarray(same)[long_]) < LONG_REL


def test_the_reference_in_whole_segments_is_the_reference_in_one(cfg, tree, tokens, want, monkeypatch):
    """A stream longer than a segment is padded to whole segments and
    goes a segment of queries at a time: the same logits and margins."""
    monkeypatch.setattr(reference, "SEGMENT", 32)
    at = np.asarray([11, 31, 32, 63, 64, 79])
    logits, margin = reference.stream_logits(tree, tokens, cfg, at)
    np.testing.assert_allclose(np.asarray(logits), want[0][at], atol=2e-4)
    np.testing.assert_allclose(np.asarray(margin), want[2][at], atol=1e-5)


def test_a_context_crossing_index_topk_mid_stream(model_cfg, tree, tokens, want):
    """Steps from position 10 to 24: up to 15 every position is read,
    from 16 on the 16 best; the answers on both sides hold."""
    exact, sensitivity, margin = want
    run = _extend(model_cfg, _served(tree, model_cfg))
    got, at, _ = _turns_then_steps(run, axk1.empty_cache(model_cfg, SLOTS, SLOT_LEN), 1, tokens, (10, *[1] * 15))
    clear = margin[at] >= TIE_BAND
    before = (at < 16) & clear
    assert before.sum() >= 4
    assert np.sqrt(np.mean((got[before] - exact[at[before]]) ** 2)) / sensitivity < RATIO
    assert _rel(got[at >= 16], exact[at[at >= 16]]) < LONG_REL


def test_steps_merged_with_another_sessions_equal_the_steps_sent_alone(cfg, model_cfg, tree):
    rng = np.random.default_rng(11)
    streams = [rng.integers(0, 256, n + 3).astype(np.int32) for n in (9, 30, 44)]
    run = _extend(model_cfg, _served(tree, model_cfg))

    def turns():
        kv = axk1.empty_cache(model_cfg, SLOTS, SLOT_LEN)
        for slot, s in enumerate(streams):
            for lo in range(0, len(s) - 3, 16):
                _, _, kv = run(kv, [(slot, lo, s[lo : min(lo + 16, len(s) - 3)])])
        return kv

    kv_merged, kv_alone = turns(), turns()
    for step in range(3):
        rows = [(slot, len(s) - 3 + step, s[len(s) - 3 + step :][:1]) for slot, s in enumerate(streams)]
        merged, _, kv_merged = run(kv_merged, rows)
        for i, row in enumerate(rows):
            alone, _, kv_alone = run(kv_alone, [row])
            np.testing.assert_allclose(merged[i], alone[0], atol=2e-2)
    exact, _ = reference.stream_logits(tree, streams[0], cfg, len(streams[0]) - 1)
    assert np.abs(merged[0] - np.asarray(exact)[0]).max() < 0.15  # 12 positions: no selection, no flip


def test_the_shares_add_up_to_the_uncut_layer(cfg, model_cfg, tree):
    """The routed parts of all 16 / 4 = 4 shares (each a whole group),
    with attention, the indexer and the shared expert counted once, give
    the reference's UNCUT layer."""
    m = cfg["model"]
    full = reference._mlp_params(jax.random.PRNGKey(3), m["hidden_size"], m["moe_intermediate_size"], (m["router_experts"],))
    layer = {**tree["layers"]["1"], "experts": full}
    h = jax.random.normal(jax.random.PRNGKey(4), (12, m["hidden_size"]), jnp.float32)  # 12 positions: every one is read
    uncut, _ = reference.layer_forward(h, layer, {**m, "experts_here": m["router_experts"], "expert_offset": 0}, True)

    def share(offset):
        held = jax.tree_util.tree_map(lambda w: w[offset : offset + 4] if offset < 16 else w[:4], full)
        c = dataclasses.replace(model_cfg, expert_offset=offset)
        kv = jax.tree_util.tree_map(lambda a: a[:1], axk1.empty_cache(c, 1, 32))
        pos = jnp.arange(12)[None]
        cos, sin = axk1.rope.rope_tables(pos, c.yarn)
        out, _, _ = axk1._layer(c, {**tree["layers"]["1"], "experts": held}, h[None], kv, 0,
                                jnp.zeros(1, jnp.int32), pos, jnp.ones((1, 12), bool), cos, sin)
        return np.asarray(out[0])

    base = share(16)
    total = base + sum(share(o) - base for o in (0, 4, 8, 12))
    assert np.abs(total - np.asarray(uncut)).max() < 0.08
    assert np.abs(base - np.asarray(uncut)).max() > 0.3


def test_the_group_limited_router_is_the_references_and_plain_top_k_is_unchanged(cfg):
    m = {**cfg["model"], "router_experts": 32, "n_group": 8, "topk_group": 3, "num_experts_per_tok": 4}
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(200, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 32)) * 1.5 / 8, jnp.float32)
    bias = jnp.asarray(rng.normal(size=32) * 0.05, jnp.float32)
    want_idx, want_gates, margin = reference.route(x, router, bias, m)
    idx, gates = experts_op.route(x, router, 4, 2.5, True, bias=bias, n_group=8, topk_group=3)
    clear = np.asarray(margin) > 1e-6
    np.testing.assert_array_equal(np.sort(np.asarray(idx)[clear]), np.sort(np.asarray(want_idx)[clear]))
    np.testing.assert_allclose(np.sort(np.asarray(gates)[clear]), np.sort(np.asarray(want_gates)[clear]), rtol=1e-5)
    # the limit and the bias change who is chosen: not plain top-k of the scores
    plain_idx, plain_gates = experts_op.route(x, router, 4, 2.5, True)
    assert (np.sort(np.asarray(plain_idx)) != np.sort(np.asarray(idx))).any(axis=1).mean() > 0.2
    # every chosen expert lies in one of the 3 kept groups
    assert all(len({int(e) // 4 for e in row}) <= 3 for row in np.asarray(idx))
    # topk_method none: exactly the top-k of the sigmoid scores
    s = np.asarray(jax.nn.sigmoid(x @ router))
    np.testing.assert_array_equal(np.sort(np.asarray(plain_idx)), np.sort(np.argsort(-s, axis=1)[:, :4]))
    np.testing.assert_allclose(np.asarray(plain_gates).sum(axis=1), 2.5, rtol=1e-5)


def test_an_entry_without_the_new_keys_is_the_block_as_it_was():
    c = axk1.AXK1Config.from_dict({"num_attention_heads": 4})
    assert c.index_topk == 0 and not c.group_limited
    assert not isinstance(axk1.empty_cache(dataclasses.replace(c, num_hidden_layers=1), 1, 8), dict)
    layer = axk1.init_params(jax.random.PRNGKey(0), dataclasses.replace(
        c, hidden_size=16, intermediate_size=16, moe_intermediate_size=8, q_lora_rank=8, kv_lora_rank=8,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, router_experts=4, experts_here=2, num_hidden_layers=2,
        vocab_size=8))["layers"]["1"]
    assert "index" not in layer["attn"] and "router_bias" not in layer
    with pytest.raises(ValueError, match="topk_method"):
        axk1.AXK1Config.from_dict({"topk_method": "greedy"})


# -- the ops -----------------------------------------------------------------------


def test_kth_largest_is_the_sorted_rows_kth_value_exactly():
    rng = np.random.default_rng(0)
    scores = rng.normal(size=(24, 2048)).astype(np.float32)
    scores[:, 1500:] = -np.inf
    scores[3, :40] = 0.0  # ties, and negative zero beside them
    scores[3, 40] = -0.0
    k = rng.integers(1, 1500, 24).astype(np.int32)
    k[3] = 20
    want = np.sort(scores, axis=1)[np.arange(24), 2048 - k]
    got = jax.jit(sparse_index.kth_largest)(jnp.asarray(scores), jnp.asarray(k))
    np.testing.assert_array_equal(np.asarray(got), want)
    cut = jax.jit(lambda s, kk: sparse_index.kth_largest(s, kk, last=jnp.int32(1499)))(jnp.asarray(scores), jnp.asarray(k))
    np.testing.assert_array_equal(np.asarray(cut), want)
    assert sparse_index.kth_kernel_fits(24, 2048) and not sparse_index.kth_kernel_fits(32, 96)
    kernel = sparse_index._kth_largest_pallas(jnp.asarray(scores), jnp.asarray(k), interpret=True)  # whole rows in VMEM
    np.testing.assert_array_equal(np.asarray(kernel), want)


def _index_inputs(t, s_len, heads, dim, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(t, heads, dim)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(t, heads)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(s_len, dim)), jnp.bfloat16)
    return q, w, keys


def _scores_by_hand(q, w, keys, positions):
    s = np.einsum("tjd,sd->tjs", np.asarray(q, np.float32), np.asarray(keys, np.float32))
    scores = (np.asarray(w)[:, :, None] * np.maximum(s, 0.0)).sum(axis=1)
    return np.where(np.arange(keys.shape[0])[None, :] <= np.asarray(positions)[:, None], scores, -np.inf)


@pytest.mark.parametrize("start", [0, 300, 1500])
def test_the_index_score_kernel_and_the_plain_form_agree(start):
    """The Pallas kernel (interpreted here) on whole tiles: 512 queries
    from ``start`` on against a slot of 2,048 keys."""
    q, w, keys = _index_inputs(512, 2048, 4, 128)
    positions = jnp.arange(start, start + 512, dtype=jnp.int32)
    assert sparse_index.kernel_fits(512, 2048, 4, 128) and not sparse_index.kernel_fits(32, 96, 4, 16)
    want = _scores_by_hand(q, w, keys, positions)
    plain = np.asarray(sparse_index.extend_scores(q, w, keys, positions, kernel=False))
    kernel = np.asarray(sparse_index._extend_scores_pallas(q, w, keys, positions[:1], interpret=True))
    for got in (plain, kernel):
        assert np.array_equal(np.isfinite(got), np.isfinite(want))
        np.testing.assert_allclose(np.where(np.isfinite(want), got, 0), np.where(np.isfinite(want), want, 0), atol=2e-3)


def test_step_scores_read_each_sessions_own_slot():
    q, w, keys = _index_inputs(3, 4 * 64, 4, 16, seed=1)
    cache = keys.reshape(1, 4, 64, 16)
    slots, positions = jnp.asarray([2, 0, 3]), jnp.asarray([10, 63, 0])
    got = np.asarray(sparse_index.step_scores(q, w, cache, 0, slots, positions))
    for i in range(3):
        want = _scores_by_hand(q[i : i + 1], w[i : i + 1], cache[0, int(slots[i])], positions[i : i + 1])[0]
        assert np.array_equal(np.isfinite(got[i]), np.isfinite(want))
        np.testing.assert_allclose(got[i][np.isfinite(want)], want[np.isfinite(want)], atol=2e-3)


@pytest.mark.parametrize("selected", [False, True])
def test_a_slot_expanded_in_segments_gives_what_it_gives_whole(selected, monkeypatch):
    rng = np.random.default_rng(4)
    t, s_len, h, rank, nope, rp = 64, 512, 2, 16, 8, 4
    q_nope = jnp.asarray(rng.normal(size=(t, h, nope)), jnp.bfloat16)
    q_rope = jnp.asarray(rng.normal(size=(t, h, rp)), jnp.bfloat16)
    rows = jnp.asarray(rng.normal(size=(s_len, 128)), jnp.bfloat16)
    kv_b = jnp.asarray(rng.normal(size=(rank, h, nope + 8)) * 0.25, jnp.bfloat16)
    positions = jnp.arange(300, 300 + t, dtype=jnp.int32)
    select = None
    if selected:
        scores = jnp.asarray(_scores_by_hand(*_index_inputs(t, s_len, 2, 16, seed=5), positions))
        select = (scores, sparse_index.kth_largest(scores, jnp.full((t,), 32, jnp.int32)))
    run = lambda: np.asarray(latent_attention.expanded_attention(
        q_nope, q_rope, rows, positions, kv_b, 0.3, nope, select), np.float32)
    whole = run()
    monkeypatch.setattr(latent_attention, "SEGMENT_ROWS", 256)
    assert latent_attention._segment_rows(s_len, 256) == 256
    np.testing.assert_allclose(run(), whole, atol=2e-2)
    if selected:  # 32 keys a query are not all of them
        monkeypatch.setattr(latent_attention, "SEGMENT_ROWS", 8192)
        dense = np.asarray(latent_attention.expanded_attention(q_nope, q_rope, rows, positions, kv_b, 0.3, nope), np.float32)
        assert np.abs(dense - whole).max() > 0.1


def test_the_served_slot_is_cut_into_seven_segments():
    assert latent_attention._segment_rows(34048, 256) == 4864 and latent_attention._segment_rows(4352, 256) == 4352


# -- sessions: the counters, the index cache with the slot ------------------------------


def _sessions(**kw):
    return TokenSessions(2, 64, 32, lm.token_bucket, lambda n: lm.step_bucket(n, 2), time_fn=lambda: 0.0, **kw)


def _send(state, sid, n, start=False, end=False):
    request = InferRequest("m", {"tokens": np.zeros((1, n), np.int32)}, sequence_id=sid, sequence_start=start, sequence_end=end)
    launch, ticket = state.open(request)
    state.close(ticket, {"logits": np.zeros((launch.inputs["tokens"].shape[0], 4), np.float32)})
    return launch.inputs, ticket


def test_the_counters_of_context_and_of_keys_seen_and_read():
    state = _sessions(index_topk=16, layers=3, index_cache_bytes=4096)
    _send(state, "a", 12, start=True)
    first = state.stats()
    assert first["lm_context_prefill"] == 0 and first["lm_keys_visible"] == first["lm_keys_selected"] == 3 * 78
    turn, ticket = _send(state, "a", 8)  # positions 12..19: visible 13..20, read min(., 16)
    assert turn["positions"].tolist() == [12] and ticket.span == ("lm_prefill", {"tokens": 8, "sessions": 1, "context": 12})
    second = state.stats()
    assert second["lm_context_prefill"] == 12
    assert second["lm_keys_visible"] - first["lm_keys_visible"] == 3 * sum(range(13, 21))
    assert second["lm_keys_selected"] - first["lm_keys_selected"] == 3 * (13 + 14 + 15 + 16 * 5)
    _, step = _send(state, "a", 1)
    assert step.span[1]["context"] == 20 and state.stats()["lm_keys_selected"] - second["lm_keys_selected"] == 3 * 16
    assert state.stats()["session_index_cache_bytes"] == 4096
    plain = _sessions()
    _send(plain, "b", 20, start=True)
    assert plain.stats()["lm_keys_selected"] == plain.stats()["lm_keys_visible"] == 210


@pytest.fixture(scope="module")
def channel(cfg, tree, tmp_path_factory):
    root = tmp_path_factory.mktemp("repo")
    name = sc.write_repository(root, cfg, tree, True)
    from triton_client_tpu.runtime.disk_repository import scan_disk

    return TPUChannel(scan_disk(root), devices=jax.devices()[:1]), name


def test_turns_through_the_channel_and_the_index_cache_reclaimed_with_the_slot(channel, cfg, tree, tokens, want):
    ch, name = channel
    exact = want[0]
    model = ch.served_model(name)
    assert model.spec.extra["family"] == "deepseek_v32" and model.spec.extra["device_state"] == lm.STATE_KEY
    ask = lambda toks, sid="s", **kw: ch.do_inference(InferRequest(name, {"tokens": np.asarray(toks)[None]}, sequence_id=sid, **kw))
    ask(tokens[:12], sequence_start=True)
    ask(tokens[12:28])
    before = model.params[lm.STATE_KEY]
    got = ask(tokens[28:44]).outputs["logits"][0]  # a further turn on 28 cached positions
    assert before["latent"].is_deleted() and before["index"].is_deleted()  # both donated, neither copied
    assert _rel(got[None], exact[43:44]) < LONG_REL
    with jax.transfer_guard_device_to_host("disallow"):  # the index keys never cross to the host
        step = ask(tokens[44:45], sequence_end=True).outputs["logits"][0]
    assert _rel(step[None], exact[44:45]) < 2 * LONG_REL
    stats = ch.session_stats()["models"][name]
    assert stats["session_cache_slots_in_use"] == 0 and stats["lm_context_prefill"] == 12 + 28
    assert stats["session_index_cache_bytes"] == 3 * 8 * 96 * 16 * 2
    # the freed slot goes to the next session, whose first turn reads nothing of the last one's index keys
    other = np.random.default_rng(9).integers(0, 256, 30).astype(np.int32)
    ask(other[:14], sid="t", sequence_start=True)
    again = ask(other[14:30], sid="t", sequence_end=True).outputs["logits"][0]
    exact_other, _ = reference.stream_logits(tree, other, cfg, 29)
    assert _rel(again[None], np.asarray(exact_other)) < LONG_REL


def test_the_in_process_call_tells_a_further_turn_from_a_new_session(channel, tokens, want):
    ch, name = channel
    infer = ch.served_model(name).infer_fn
    exact = want[0]
    call = lambda toks: np.asarray(infer({"tokens": np.asarray(toks)[None]})["logits"])[0]
    call(tokens[:12])
    turn = call(tokens[12:28])  # after a turn: a further turn
    assert _rel(turn[None], exact[27:28]) < LONG_REL
    call(tokens[28:29])  # a step
    fresh = call(tokens[:12])  # after a step: a new session
    assert _rel(fresh[None], exact[11:12]) < 0.05


@pytest.mark.parametrize("start", [0, 700])
def test_the_selected_attention_kernel_and_the_plain_blocks_agree(start, monkeypatch):
    """The Pallas kernel (interpreted here) on whole tiles, a slot of
    1,024 positions in two segments: 256 queries from ``start`` on, each
    reading the 64 positions its index scores choose."""
    rng = np.random.default_rng(6)
    t, s_len, h, rank, nope, rp, vd = 256, 1024, 16, 32, 128, 64, 128
    q_nope = jnp.asarray(rng.normal(size=(t, h, nope)) * 0.3, jnp.bfloat16)
    q_rope = jnp.asarray(rng.normal(size=(t, h, rp)) * 0.3, jnp.bfloat16)
    rows = jnp.asarray(rng.normal(size=(s_len, 128)), jnp.bfloat16)
    kv_b = jnp.asarray(rng.normal(size=(rank, h, nope + vd)) * rank**-0.5, jnp.bfloat16)
    positions = jnp.arange(start, start + t, dtype=jnp.int32)
    scores = jnp.asarray(_scores_by_hand(*_index_inputs(t, s_len, 2, 16, seed=8), positions))
    select = (scores, sparse_index.kth_largest(scores, jnp.minimum(positions + 1, 64)))
    monkeypatch.setattr(latent_attention, "SEGMENT_ROWS", 512)
    assert latent_attention.selected_kernel_fits(t, s_len, nope, vd) and not latent_attention.selected_kernel_fits(32, 96, 16, 16)
    plain = latent_attention.expanded_attention(q_nope, q_rope, rows, positions, kv_b, 0.1, nope, select, kernel=False)
    kernel = latent_attention._selected_attention(q_nope, q_rope, rows, positions, kv_b, 0.1, nope, select, interpret=True)
    assert np.isfinite(np.asarray(kernel, np.float32)).all()
    np.testing.assert_allclose(np.asarray(kernel, np.float32), np.asarray(plain, np.float32), atol=2e-2)


@pytest.mark.parametrize("chunk_rows", [256, 512, 4096])
def test_the_experts_chunk_is_a_tuning_and_not_a_part_of_the_answer(chunk_rows):
    """The held experts' sum over a launch is the same whatever
    ``expert_chunk_rows`` (rows routed here: more than the smallest
    chunk holds, fewer than the largest), and the served entry states
    one that takes a 4,096-token launch's rows in ONE pass."""
    rng = np.random.default_rng(chunk_rows)
    t, d, f, held, k = 512, 32, 16, 4, 8
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.bfloat16)
    experts = {n: jnp.asarray(rng.normal(size=s) * 0.2, jnp.bfloat16)
               for n, s in (("gate", (held, d, f)), ("up", (held, d, f)), ("down", (held, f, d)))}
    idx = jnp.asarray(np.stack([rng.permutation(16)[:k] for _ in range(t)]).astype(np.int32))
    gates = jnp.asarray(rng.uniform(0.1, 1.0, size=(t, k)), jnp.float32)
    run = lambda rows: jax.jit(lambda: experts_op.routed_experts(x, jnp.ones(t, bool), idx, gates, experts, 0, rows))()
    want, want_rows = run(experts_op.CHUNK_ROWS)
    got, got_rows = run(chunk_rows)
    assert 256 < int(want_rows.sum()) < 4096 and np.array_equal(np.asarray(got_rows), np.asarray(want_rows))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2)
    served = sc.load_json(ROOT / "benchmarks/configs/dsv32-ep32-l6.json")["model"]
    expected = 4096 * served["num_experts_per_tok"] * served["experts_here"] / served["router_experts"]
    assert served["expert_chunk_rows"] == 2 * expected
    assert axk1.AXK1Config().expert_chunk_rows == experts_op.CHUNK_ROWS  # an entry that does not name it: as it was
