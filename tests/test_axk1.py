"""A.X-K1 served in token sessions, at tiny widths on the CPU (hidden
64, 4 heads, ranks 32/16, 16 experts top-4 with 4 held, 1 dense + 2
expert layers, vocabulary 256: the benchmark configuration's own
``rehearsal`` sizes), against the plain float32 reference
``benchmarks/references/axk1.py`` on seeded weights.

The tolerance. The program computes in bfloat16 what the reference
computes in float32, so its logits differ by what bfloat16 activations
cost: the reference says how much for these weights (``round_acts``:
every matrix product's activations rounded to bfloat16, the RMS logit
shift is the ``sensitivity``, about 0.009 on logits of std 2). A sound
path reads 1.2-1.7 times that (it also rounds each product's output);
the limit is ``RATIO`` = 2.5. The same weights rounded to per-channel
int8 read 5-7 times: the control, which must fail.
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
from benchmarks.references import axk1 as reference  # noqa: E402
from triton_client_tpu.channel.base import InferRequest  # noqa: E402
from triton_client_tpu.channel.tpu_channel import TPUChannel  # noqa: E402
from triton_client_tpu.models import axk1  # noqa: E402
from triton_client_tpu.obs.collector import CompileEvents  # noqa: E402
from triton_client_tpu.ops import experts as experts_op  # noqa: E402
from triton_client_tpu.pipelines import lm  # noqa: E402
from triton_client_tpu.runtime import precision  # noqa: E402
from triton_client_tpu.runtime.repository import ModelRepository  # noqa: E402
from triton_client_tpu.runtime.sessions import SessionLimitError, TokenSessions  # noqa: E402

RATIO = 2.5
SLOTS, SLOT_LEN = 4, 48


@pytest.fixture(scope="module")
def cfg():
    return sc.apply_rehearsal(sc.load_json(ROOT / "benchmarks/configs/axk1-ep16-l6.json"))


@pytest.fixture(scope="module")
def model_cfg(cfg):
    m = {k: v for k, v in cfg["model"].items() if k not in ("slot_len", "max_tokens")}
    return axk1.AXK1Config.from_dict(m)


@pytest.fixture(scope="module")
def tree(cfg):
    return jax.jit(lambda k: reference.init_params(k, None, cfg))(jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(0, 256, 43).astype(np.int32)


@pytest.fixture(scope="module")
def want(cfg, tree, tokens):
    """The reference's full forward pass over the stream, and how far
    bfloat16 activations move it."""
    exact, margin = reference.stream_logits(tree, tokens, cfg, 0)
    rounded, _ = reference.stream_logits(tree, tokens, cfg, 0, round_acts=True)
    return np.asarray(exact), float(np.sqrt(np.mean((np.asarray(rounded) - np.asarray(exact)) ** 2))), np.asarray(margin)


def _served(tree, model_cfg):
    """``stack_layers`` takes the per-layer leaves out of the tree it is
    given: hand it a copy of the tree's own dicts."""
    return axk1.stack_layers({**tree, "layers": dict(tree["layers"])}, model_cfg)


def _extend(model_cfg, weights):
    fn = jax.jit(lambda kv, t, s, p, l: axk1.extend(model_cfg, weights, kv, t, s, p, l))

    def run(kv, rows):
        """``rows``: [(slot, start, tokens)], all of one width or all one
        token; padded as pipelines/lm.py pads a launch."""
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


def _ratio(got, exact, sensitivity):
    return float(np.sqrt(np.mean((got - exact) ** 2))) / sensitivity


def _stream(run, kv, slot, tokens, prompt):
    """A prompt, 8 steps one by one, then a further turn of the rest:
    the answers in order and the positions they answer."""
    got, at = [], []
    logits, _, kv = run(kv, [(slot, 0, tokens[:prompt])])
    got.append(logits[0]), at.append(prompt - 1)
    for i in range(prompt, prompt + 8):
        logits, _, kv = run(kv, [(slot, i, tokens[i : i + 1])])
        got.append(logits[0]), at.append(i)
    logits, _, kv = run(kv, [(slot, prompt + 8, tokens[prompt + 8 :])])
    got.append(logits[0]), at.append(len(tokens) - 1)
    return np.stack(got), at, kv


TIE_BAND = 0.004  # a position whose router margin is under it may route otherwise in bfloat16: left out


def test_prompt_steps_and_a_further_turn_match_the_full_forward_pass(model_cfg, tree, tokens, want):
    exact, sensitivity, margin = want
    run = _extend(model_cfg, _served(tree, model_cfg))
    got, at, _ = _stream(run, axk1.empty_cache(model_cfg, SLOTS, SLOT_LEN), 2, tokens, 20)
    clear = margin[at] >= TIE_BAND
    assert clear[0] and clear[-1] and clear[1:9].sum() >= 6
    assert _ratio(got[:1], exact[at[:1]], sensitivity) < RATIO  # (a) the prompt
    assert _ratio(got[1:9][clear[1:9]], exact[at[1:9]][clear[1:9]], sensitivity) < RATIO  # (b) 8 steps through the cache
    assert _ratio(got[9:], exact[at[9:]], sensitivity) < RATIO  # (c) a further turn of 15 on 28 cached
    assert np.abs(got - exact[at])[clear].max() < 0.15  # logits of std 2: no position is off by their own size


def test_int8_weights_fail_the_same_comparison(model_cfg, tree, tokens, want):
    exact, sensitivity, _ = want
    rounded = jax.tree_util.tree_map(
        lambda w: precision.fake_quant_channelwise(w) if w.ndim >= 2 else w, tree)
    run = _extend(model_cfg, _served(rounded, model_cfg))
    got, at, _ = _stream(run, axk1.empty_cache(model_cfg, SLOTS, SLOT_LEN), 0, tokens, 20)
    clear = want[2][at] >= TIE_BAND
    assert _ratio(got[clear], exact[at][clear], sensitivity) > RATIO


def test_steps_of_three_sessions_merged_equal_the_steps_sent_alone(cfg, model_cfg, tree):
    """(d): three sessions of different lengths; their next steps in ONE
    launch give what each gives alone, and what the reference gives."""
    rng = np.random.default_rng(11)
    streams = [rng.integers(0, 256, n + 3).astype(np.int32) for n in (9, 17, 30)]
    run = _extend(model_cfg, _served(tree, model_cfg))

    def prompts():
        kv = axk1.empty_cache(model_cfg, SLOTS, SLOT_LEN)
        for slot, s in enumerate(streams):
            _, _, kv = run(kv, [(slot, 0, s[:-3])])
        return kv

    kv_merged, kv_alone = prompts(), prompts()
    for step in range(3):
        rows = [(slot, len(s) - 3 + step, s[len(s) - 3 + step :][:1]) for slot, s in enumerate(streams)]
        merged, _, kv_merged = run(kv_merged, rows)
        for i, row in enumerate(rows):
            alone, _, kv_alone = run(kv_alone, [row])
            np.testing.assert_allclose(merged[i], alone[0], atol=2e-2)
            exact, _ = reference.stream_logits(tree, streams[i][: row[1] + 1], cfg, row[1])
            assert np.abs(merged[i] - np.asarray(exact)[0]).max() < 0.15


def _moe_layer(tree):
    return jax.tree_util.tree_map(lambda x: x, tree["layers"]["1"])


def test_the_shares_add_up_to_the_uncut_layer(cfg, model_cfg, tree):
    """The routed parts of all 16 / 4 = 4 shares, with attention and the
    shared expert counted once, give the reference's UNCUT layer (all 16
    experts held)."""
    m = cfg["model"]
    key = jax.random.PRNGKey(3)
    full = reference._mlp_params(key, m["hidden_size"], m["moe_intermediate_size"], (m["router_experts"],))
    layer = {**_moe_layer(tree), "experts": full}
    h = jax.random.normal(jax.random.PRNGKey(4), (24, m["hidden_size"]), jnp.float32)
    uncut, _ = reference.layer_forward(h, layer, {**m, "experts_here": m["router_experts"], "expert_offset": 0}, True)

    def share(offset):
        held = jax.tree_util.tree_map(lambda w: w[offset : offset + 4] if offset < 16 else w[:4], full)
        c = dataclasses.replace(model_cfg, expert_offset=offset)
        kv = axk1.empty_cache(c, 1, 32)[:1]
        pos = jnp.arange(24)[None]
        cos, sin = axk1.rope.rope_tables(pos, c.yarn)
        out, _, _ = axk1._layer(c, {**_moe_layer(tree), "experts": held}, h[None], kv, 0,
                                jnp.zeros(1, jnp.int32), pos, jnp.ones((1, 24), bool), cos, sin)
        return np.asarray(out[0])

    base = share(16)  # no expert of the router's range is held: attention and the shared expert alone
    total = base + sum(share(o) - base for o in (0, 4, 8, 12))
    assert np.abs(total - np.asarray(uncut)).max() < 0.08
    assert np.abs(base - np.asarray(uncut)).max() > 0.3  # the routed part is not small


def test_a_skewed_router_drops_no_token():
    """One expert takes half the token-slots: every one of them is
    computed (more rows than a chunk holds, so the loop over chunks
    runs), against the dense sum over experts."""
    rng = np.random.default_rng(0)
    t, d, f, held, k = 1280, 32, 16, 4, 4
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.bfloat16)
    experts = {n: jnp.asarray(rng.normal(size=s) * 0.2, jnp.bfloat16)
               for n, s in (("gate", (held, d, f)), ("up", (held, d, f)), ("down", (held, f, d)))}
    idx = np.stack([rng.permutation(16)[:k] for _ in range(t)]).astype(np.int32)
    idx[: t // 2, 0] = 2  # half the tokens' first choice is expert 2 (a choice is distinct per token)
    for row in idx[: t // 2]:
        row[1:] = [e for e in rng.permutation(16) if e != 2][: k - 1]
    gates = jnp.asarray(rng.uniform(0.1, 1.0, size=(t, k)), jnp.float32)
    got, rows = jax.jit(lambda: experts_op.routed_experts(x, jnp.ones(t, bool), jnp.asarray(idx), gates, experts, 0))()
    assert int(rows[2]) >= t // 2 and int(rows.sum()) == int((idx < held).sum()) > experts_op.CHUNK_ROWS
    want = np.zeros((t, d), np.float32)
    x32 = np.asarray(x, np.float32)
    for e in range(held):
        w = {n: np.asarray(a[e], np.float32) for n, a in experts.items()}
        y = (jax.nn.silu(x32 @ w["gate"]) * (x32 @ w["up"])) @ w["down"]
        want += np.asarray(y) * np.where(idx == e, np.asarray(gates), 0.0).sum(-1)[:, None]
    np.testing.assert_allclose(np.asarray(got), want, atol=0.05)
    assert np.abs(want).max() > 0.5


# -- sessions: slots, the reclaim ladder, RESOURCE_EXHAUSTED ---------------------


def _sessions(slots=2, slot_len=16, ttl_s=10.0, clock=None):
    return TokenSessions(slots, slot_len, 8, lm.token_bucket, lambda n: lm.step_bucket(n, slots),
                         ttl_s=ttl_s, time_fn=clock or (lambda: 0.0))


def _send(state, sid, n, start=False, end=False):
    request = InferRequest("m", {"tokens": np.zeros((1, n), np.int32)}, sequence_id=sid,
                           sequence_start=start, sequence_end=end)
    launch, ticket = state.open(request)
    host = {"logits": np.zeros((launch.inputs["tokens"].shape[0], 4), np.float32)}
    state.close(ticket, host)
    assert host["logits"].shape[0] == 1  # the launch's pad rows are cut on the host
    return launch.inputs


def test_slots_positions_and_launch_shapes():
    state = _sessions()
    first = _send(state, "a", 5, start=True)
    assert first["tokens"].shape == (1, 8) and first["lengths"].tolist() == [5] and first["positions"].tolist() == [0]
    step = _send(state, "a", 1)
    assert step["tokens"].shape == (2, 1) and step["positions"][0] == 5 and step["lengths"].tolist() == [1, 0]
    assert state.stats()["session_cache_tokens"] == 6 and state.stats()["session_cache_slots_in_use"] == 1
    other = _send(state, "b", 3, start=True)
    assert other["slots"][0] != first["slots"][0]
    _send(state, "a", 1, end=True)
    assert state.stats()["session_cache_slots_in_use"] == 1  # ended: freed at once
    assert _send(state, "c", 2, start=True)["slots"][0] == first["slots"][0]


def test_reclaim_ladder_ended_then_ttl_then_lru():
    now = [0.0]
    state = _sessions(slots=2, ttl_s=10.0, clock=lambda: now[0])
    _send(state, "old", 2, start=True)
    now[0] = 1.0
    _send(state, "young", 2, start=True)
    now[0] = 5.0
    _send(state, "third", 2, start=True)  # nothing ended or expired: the least recently used goes
    assert state.stats()["reclaimed_total"] == 1 and "old" not in state._pool.slots
    now[0] = 20.0
    _send(state, "fourth", 2, start=True)  # both idle past the TTL: one expires
    assert state.stats()["expired_total"] == 1
    with pytest.raises(SessionLimitError, match="holds no cache slot"):
        _send(state, "old", 1)  # reclaimed: its history is gone, it has to start again


def test_a_full_pool_and_an_outgrown_slot_are_resource_exhausted():
    state = _sessions(slots=2, slot_len=16)
    tickets = [state.open(InferRequest("m", {"tokens": np.zeros((1, 4), np.int32)}, sequence_id=s,
                                       sequence_start=True))[1] for s in ("a", "b")]
    with pytest.raises(SessionLimitError, match="all in flight"):
        state.open(InferRequest("m", {"tokens": np.zeros((1, 4), np.int32)}, sequence_id="c", sequence_start=True))
    for t in tickets:
        state.close(t, {"logits": np.zeros((8, 4), np.float32)})
    _send(state, "a", 8)
    with pytest.raises(SessionLimitError, match="outgrow"):
        _send(state, "a", 8)  # 4 + 8 + 8 > 16
    assert state.stats()["session_cache_tokens"] == 12 + 4  # the refused request left the lengths as they were
    from triton_client_tpu.runtime.admission import AdmissionRejectedError

    assert issubclass(SessionLimitError, AdmissionRejectedError)  # RESOURCE_EXHAUSTED on the wire


def test_a_failed_launch_takes_its_tokens_back():
    state = _sessions()
    _send(state, "a", 4, start=True)
    _, ticket = state.open(InferRequest("m", {"tokens": np.zeros((1, 1), np.int32)}, sequence_id="a"))
    state.abort(ticket)
    assert state.stats()["session_cache_tokens"] == 4 and state.stats()["lm_tokens_step"] == 0


# -- through the channel: weights are arguments, the cache stays on the device ----


@pytest.fixture(scope="module")
def channel(cfg, tree, tmp_path_factory):
    root = tmp_path_factory.mktemp("repo")
    name = sc.write_repository(root, cfg, tree, True)
    from triton_client_tpu.runtime.disk_repository import scan_disk

    repo = scan_disk(root)
    return TPUChannel(repo, devices=jax.devices()[:1]), name


def test_a_stream_through_the_channel_keeps_the_cache_on_the_device(channel, tokens, want):
    ch, name = channel
    exact, sensitivity, _ = want
    ask = lambda toks, **kw: ch.do_inference(InferRequest(name, {"tokens": np.asarray(toks)[None]}, sequence_id="s", **kw))
    first = ask(tokens[:20], sequence_start=True)  # compiles the two launch kinds ...
    ask(tokens[20:21])
    got = []
    cache_before = ch.served_model(name).params[lm.STATE_KEY]
    # ... so that the steps below trace nothing. Every implicit device-to-host transfer raises; the
    # channel's readback of the logits is the one explicit transfer (np.asarray in _host_outputs)
    with jax.transfer_guard_device_to_host("disallow"):
        for i in range(21, 29):
            got.append(ask(tokens[i : i + 1]).outputs["logits"][0])
    assert cache_before.is_deleted()  # donated into the next launch, not copied
    assert set(first.outputs) == {"logits"} and first.outputs["logits"].shape == (1, 256)
    clear = want[2][21:29] >= TIE_BAND
    assert _ratio(np.stack(got)[clear], exact[21:29][clear], sensitivity) < RATIO
    ask(tokens[29:30], sequence_end=True)
    stats = ch.session_stats()["models"][name]
    assert stats["session_cache_slots_in_use"] == 0 and stats["lm_tokens_step"] == 10
    # token ids and rows cross as they came: the transfer view is for frame batches
    assert ch.stats()["staged_bytes"] > 0 and ch.stats()["staged_dense_bytes"] == 0
    assert np.asarray(stats["expert_rows"]).shape == (2, 4) and np.asarray(stats["expert_rows"]).sum() > 0


def test_the_served_module_holds_no_weights(channel):
    """Weights are launcher arguments: the step module's text has no
    constant over 1 MB (at the served size 8.33 GB could not be one)."""
    ch, name = channel
    model = ch.served_model(name)
    launcher, _, _ = ch._launcher(model)
    inputs = {k: jnp.asarray(v) for k, v in lm.launch_inputs("step", 8).items()}
    text = launcher.lower({}, inputs).as_text()
    assert "jit_mdl_" in text and "lm_step" in text
    assert max((len(line) for line in text.splitlines() if "constant" in line), default=0) < 1 << 20
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(model.params["weights"]))
    assert len(text) < n_params  # the text is smaller than the weights' element count, let alone their bytes


def test_steps_of_concurrent_sessions_merge_under_the_batcher(channel, cfg, tree):
    """Six callers, each a stream under its own sequence_id, through
    the continuous batcher at depth 2: the one-token steps that are ready
    when a launch slot frees share a launch, every answer still matches
    the reference, and ``merge_wait`` is traced."""
    import threading

    from triton_client_tpu.obs.trace import RequestTrace
    from triton_client_tpu.runtime.continuous import ContinuousBatchingChannel

    ch, name = channel
    batcher = ContinuousBatchingChannel(ch, max_batch=40, pipeline_depth=2)
    rng = np.random.default_rng(21)
    streams = [rng.integers(0, 256, n + 6).astype(np.int32) for n in (10, 14, 19, 10, 14, 19)]
    before = ch.session_stats()["models"][name]
    answers, traces = [[] for _ in streams], []
    # the launch shapes this traffic can reach, compiled ahead of it as a warm-up compiles them
    slots = ch.served_model(name).spec.max_batch_size
    shapes = {("step", lm.step_bucket(n, slots)) for n in range(1, 7)} | {("extend", lm.token_bucket(n)) for n in (10, 14, 19)}
    for kind, size in sorted(shapes):  # traced: the h2d marker is a program a launch shape too
        ch.do_inference(InferRequest(name, lm.launch_inputs(kind, size), trace=RequestTrace(999, name)))
    compiles = CompileEvents.install()
    compiled_before = compiles.snapshot()["compiles"]

    def caller(k):
        s = streams[k]
        sent = [s[:-6]] + [s[i : i + 1] for i in range(len(s) - 6, len(s))]
        for j, toks in enumerate(sent):
            trace = RequestTrace(1000 + j, name) if k == 0 and j > 0 else None
            out = batcher.do_inference(InferRequest(
                name, {"tokens": toks[None]}, sequence_id=f"merge-{k}", sequence_start=j == 0,
                sequence_end=j == len(sent) - 1, trace=trace))
            answers[k].append(out.outputs["logits"][0])
            if trace is not None:
                traces.append(trace)

    try:
        threads = [threading.Thread(target=caller, args=(k,)) for k in range(len(streams))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        batcher.close()
    after = ch.session_stats()["models"][name]
    launches = after["lm_step_launches"] - before["lm_step_launches"]
    sessions = after["lm_step_sessions"] - before["lm_step_sessions"]
    assert sessions == 36 and launches < 36  # some launch held more than one session's step
    # however many sessions a launch carried, it compiled nothing: one program a launch SHAPE
    assert compiles.snapshot()["compiles"] == compiled_before
    assert after["session_cache_slots_in_use"] == 0
    for k, s in enumerate(streams):
        exact, margin = (np.asarray(a) for a in reference.stream_logits(tree, s, cfg, len(s) - 7))
        clear = margin >= TIE_BAND
        assert len(answers[k]) == 7 and np.abs(np.stack(answers[k]) - exact)[clear].max() < 0.15
    spans = {sp.name for tr in traces for sp in tr.spans}
    assert "merge_wait" in spans and "lm_step" in spans
