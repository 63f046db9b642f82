"""Check kind ``logits_state`` on made-up answers (no model): what each
of its numbers catches (a state zeroed at a turn boundary, another
stream's state at position 0, a wrong row of a merged launch, a lower
precision); the cell's mix; ``ops_bytes/ling.py``'s counts; the two
readers the cell brings, on hand-made snapshots and spans."""

from __future__ import annotations

import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import server_child as sc  # noqa: E402
from benchmarks.checks import logits_state as check  # noqa: E402
from benchmarks.inputs import token_turns  # noqa: E402
from benchmarks.layer_metrics import extend_cost_growth, lm_extend_roofline, lm_kda_chunk_roofline, lm_step_roofline  # noqa: E402
from benchmarks.ops_bytes import ling as counts  # noqa: E402

FULL = sc.load_json(ROOT / "benchmarks/configs/ling3flash-ep8-l13.json")
CFG = sc.apply_rehearsal(FULL)
CFG["check"]["context_split"] = 64  # these made-up streams are 68 to 188 positions long
MIX = sc.load_json(ROOT / "benchmarks/traffic/shiftlog-rounds.json")
V = CFG["model"]["vocab_size"]
PARAMS = {**CFG["rehearsal"]["traffic_params"], "first_turn": 24, "turn": 40}
CELL = "ling3flash-ep8-l13-shiftlog-rounds"


class FakeReference:
    """Logits that depend on the stream's tokens and on how much of the
    stream the state remembers: a state zeroed at a boundary, or started
    from another stream's, moves every answer after it; rounding moves
    an answer by 0.02 of the spread; every fifth position is a near-tie."""

    @staticmethod
    def stream_logits(tree, tokens, cfg, first, round_acts=False, reset_at=(), initial=None):
        first = np.asarray(first)
        rng = np.random.default_rng(int(np.sum(tokens[:8])))
        logits = rng.normal(size=(len(tokens), V)).astype(np.float32) * 2
        if round_acts:
            logits = logits + np.random.default_rng(1).normal(size=logits.shape).astype(np.float32) * 0.04
        forgot = np.zeros(len(tokens), bool)
        for mark in reset_at:
            forgot[mark:] = True
        if initial is not None:
            forgot[: len(tokens) // 2] = True  # another stream's state fades with the decay
        logits = logits + forgot[:, None] * np.random.default_rng(2).normal(size=logits.shape).astype(np.float32)
        margin = np.where(np.arange(len(tokens)) % 5 == 0, 0.0005, 0.05).astype(np.float32)
        return logits[first], margin[first]


@pytest.fixture()
def state_sample_and_file(tmp_path):
    sample = token_turns.make(np.random.default_rng(3), 4, PARAMS, CFG)
    stats = check.expected(FakeReference, CFG, None, sample, tmp_path / "ref.npz")
    return sample, tmp_path / "ref.npz", stats


def answers(sample, noise=0.0, swap=None, **wrong):
    out = []
    for i, stream in enumerate(sample):
        tokens, at = check.answered(stream)
        how = {k: (v(at) if callable(v) else v) for k, v in wrong.items()}
        logits = FakeReference.stream_logits(None, tokens, CFG, at, **how)[0].copy()
        logits += np.random.default_rng(i).normal(size=logits.shape).astype(np.float32) * noise
        if swap == i:
            logits[[-1, -2]] = logits[[-2, -1]]  # two steps' answers exchanged: a wrong row of a merged launch
        out.append([types.SimpleNamespace(outputs={"logits": row[None]}) for row in logits])
    return out


def test_a_round_of_the_cells_mix_is_the_same_work_on_every_seed():
    for seed in (1, 2**31 + 5):
        streams = token_turns.make(np.random.default_rng([seed, 1]), MIX["sample_requests"], MIX["inputs"]["params"], FULL)
        sizes = [[r["items"] for r in s] for s in streams]
        assert sorted(len(s) for s in sizes) == [68, 72, 76, 80] and sum(map(len, sizes)) == 296
        assert sum(map(sum, sizes)) == 151_808 and sum(n for s in sizes for n in s if n > 1) == 151_552
        assert all(s[0] == 1024 and set(s[1:-64]) == {4096} and s[-64:] == [1] * 64 for s in sizes)
        assert sorted(sum(s[:-64]) for s in sizes) == [13_312, 29_696, 46_080, 62_464]
        assert max(sum(s) for s in sizes) == 62_528 <= MIX["cache"]["slot_len"] == FULL["model"]["slot_len"] == 245 * 256
        assert max(int(r["tokens"].max()) for s in streams for r in s) < FULL["model"]["vocab_size"] == 19_648
    assert MIX["clients"] % MIX["sample_requests"] == 0 and FULL["max_batch_size"] == MIX["cache"]["slots"]
    assert MIX["trace_at_s"] + MIX["trace_s"] <= 40 and MIX["trace_s"] >= MIX["round_s"]  # one whole round is counted


def test_the_configuration_keeps_every_published_key_but_the_four_it_cuts():
    import json

    rows = [json.loads(line) for line in open("/opt/skills/guides/model-configs/architectures.jsonl")] \
        if pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl").exists() else []
    published = next((r["config"] for r in rows if r["name"] == "Ling-3.0-flash"), None)
    if published is None:
        pytest.skip("the catalog is not on this machine")
    differing = sorted(k for k, v in published.items() if FULL.get(k) != v)
    assert differing == sorted(FULL["reduced"]) == ["first_k_dense_replace", "num_experts", "num_hidden_layers", "vocab_size"]
    assert FULL["published"] == {k: published[k] for k in FULL["reduced"]}
    m = FULL["model"]
    assert m["layer_types"] == ["kda"] + (["kda"] * 5 + ["mla"]) * 2 and len(m["layer_types"]) == m["num_hidden_layers"] == 13
    assert (m["hidden_size"], m["num_attention_heads"], m["head_dim"], m["moe_intermediate_size"], m["router_experts"]) == (
        published["hidden_size"], published["num_attention_heads"], published["head_dim"], published["moe_intermediate_size"],
        published["num_experts"])


def test_expected_holds_every_requests_answer_its_margin_and_its_context(state_sample_and_file):
    sample, path, stats = state_sample_and_file
    assert stats["answers"] == sum(len(s) for s in sample) == 4 + (1 + 2 + 3 + 4) + 16
    ref = np.load(path)
    assert sorted(int(ref[f"context_{i}"][-1]) for i in range(4)) == [68, 108, 148, 188]
    assert all(len(ref[f"moved_{i}"]) == 16 for i in range(4))
    assert 0.03 < check._sensitivity(ref) < 0.05  # the median answer's, not an RMS over all


def test_a_sound_answer_passes(state_sample_and_file):
    sample, path, _ = state_sample_and_file
    ok, lines, numbers = check.served(answers(sample, noise=0.04), path, CFG)
    assert ok and numbers["missing"] == 0 and 0.5 < numbers["short_logit_err_ratio"] < 2, lines
    assert "first_logit_err_ratio" in numbers  # logged, held to no limit
    assert {l["number"] for l in lines} == {"short_logit_err_ratio", "long_logit_err_ratio", "moved_share",
                                           "near_tie_share", "worst_answer_rel"}


@pytest.mark.parametrize("wrong, fails", [
    ({"reset_at": lambda at: at[:-1][np.diff(at, prepend=-1)[:-1] > 1] + 1}, "long_logit_err_ratio"),  # the state zeroed after every turn
    ({"initial": "another stream's"}, "short_logit_err_ratio"),  # another stream's state, where it lasts half a stream
    ({"swap": 2}, "worst_answer_rel"),  # a wrong row of a merged launch
    ({"noise": 0.4}, "short_logit_err_ratio"),  # a lower precision: every answer moves by ten times the rounding
])
def test_each_way_of_being_wrong_fails_a_limit(state_sample_and_file, wrong, fails):
    sample, path, _ = state_sample_and_file
    ok, lines, _ = check.served(answers(sample, **{"noise": 0.04, **wrong}), path, CFG)
    failed = {l["number"] for l in lines if l["value"] > l["limit"]}
    assert not ok and fails in failed, lines


def test_a_few_answers_in_which_an_expert_changed_sides_do_not_move_the_ratio(state_sample_and_file):
    sample, path, _ = state_sample_and_file
    got = answers(sample, noise=0.04)
    for stream in got[:2]:  # one answer in fifteen off by a fifth of the spread
        stream[3].outputs["logits"] = stream[3].outputs["logits"] + np.random.default_rng(5).normal(size=(1, V)).astype(np.float32) * 0.4
    ok, lines, numbers = check.served(got, path, CFG)
    assert ok and 0.05 < numbers["moved_share"] < 0.1 and numbers["long_logit_err_ratio"] < 2, lines


def test_an_answer_that_never_came_is_not_correct(state_sample_and_file):
    sample, path, _ = state_sample_and_file
    got = answers(sample, noise=0.04)
    got[1] = got[1][:-1]
    ok, _, numbers = check.served(got, path, CFG)
    assert not ok and numbers["empty_items"] == 1


def test_a_program_without_the_delta_rule_fails_at_the_first_launch_shape(monkeypatch):
    import builtins

    real = builtins.__import__

    def without(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "triton_client_tpu.ops" and "delta_attention" in (fromlist or ()):
            raise ImportError("cannot import name 'delta_attention'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", without)
    monkeypatch.delitem(sys.modules, "triton_client_tpu.ops.delta_attention", raising=False)
    with pytest.raises(ImportError):
        check.launch_request({}, {"extend": 128})


# -- counts and readers ------------------------------------------------------------------


def test_the_counts_hold_the_state_read_and_written_and_grow_with_the_context_in_two_layers_only():
    m = FULL["model"]
    s = counts._sizes(FULL)
    assert (s["kda"], s["mla"]) == (11, 2) and s["state"] == 4 * 32 * 128 * 128 + 2 * 9 * 4096
    short, long_ = counts.count_prefill(FULL, 4096, 8192), counts.count_prefill(FULL, 4096, 49152)
    pairs = 4096 * (49152 - 8192)
    assert long_["flops"] - short["flops"] == pytest.approx(2 * (2 * 32 * pairs * 320 + 2 * (49152 - 8192) * s["kv_b"]))
    assert long_["bytes"] - short["bytes"] == 2 * s["cache_row"] * (49152 - 8192)  # two layers' rows; eleven layers' state does not grow
    one, four = counts.count_step(FULL, 1, 30000), counts.count_step(FULL, 4, 30000)
    per_session = 11 * 2 * s["state"] + 2 * s["cache_row"] * 30001 + 2 * m["hidden_size"] + 4 * m["vocab_size"]
    touched = lambda n: 64 * (1 - (1 - 8 / 512) ** n)
    assert four["bytes"] - one["bytes"] == pytest.approx(3 * per_session + 2 * 12 * (touched(4) - touched(1)) * s["expert"])
    core = counts.count_kda_chunk(FULL, 4096)
    assert core["bytes"] / 819e9 > core["flops"] / 197e12  # the kernel's least time is set by its bytes
    # the parameters a token goes through: ISSUE 44's arithmetic
    assert s["kda_attn"] == pytest.approx(52.59e6, rel=2e-3) and s["mla_attn"] + s["kv_b"] == pytest.approx(31.97e6, rel=2e-3)


def snapshot(model="ling3_ep8", **stats):
    return {"sessions": {"models": {model: stats}}}


def _ctx(**over):
    launches = {"jit_mdl_ling3_ep8_1_lm_prefill": {"count": 40, "device_s": 9.6}, "jit_mdl_ling3_ep8_1_lm_step": {"count": 80, "device_s": 1.6}}
    before = snapshot(lm_tokens_prefill=0, lm_prefill_launches=0, lm_context_prefill=0, lm_step_sessions=0, lm_step_launches=0,
                      lm_state_resets=0, lm_state_carries=0)
    after = snapshot(lm_tokens_prefill=40 * 3788.8, lm_prefill_launches=40, lm_context_prefill=40 * 28000, lm_step_sessions=256,
                     lm_step_launches=80, lm_state_resets=4, lm_state_carries=36, session_state_bytes=4 * 23_150_592,
                     session_cache_tokens=120_000, session_cache_slots_in_use=4, session_cache_slots=8, session_cache_slot_len=62720)
    return {"cfg": FULL, "traffic": MIX, "model": "ling3_ep8", "device": {"kind": "TPU v5 lite"}, "snapshot_before": before,
            "snapshot_after": after, "snapshots_inside": [after],
            "profile": {"launches": launches, "breakdown": {"device_ops": [["lm_kda_chunk.1", 3.0], ["lm_kda_chunk", 0.3], ["fusion.9", 1.0]]}},
            **over}


def test_the_kernels_share_is_of_its_own_ops_and_a_program_without_it_yields_nothing(capsys):
    value = lm_kda_chunk_roofline.read(_ctx())
    core = counts.count_kda_chunk(FULL, 3788.8)
    # the largest instance (the scan's: the ten KDA layers after the dense one) alone: the dense layer's own instance
    # falls out of the ten largest ops at the served sizes, and eleven layers over one instance would read a tenth high
    assert value == pytest.approx(100 * 10 * 40 * (core["bytes"] / 819e9) / 3.0, rel=1e-3) and 0 < value < 100
    assert '"share_of_extend_launches": 0.34' in capsys.readouterr().out
    without = _ctx()
    without["profile"] = {**without["profile"], "breakdown": {"device_ops": [["lm_kda_chunk.1", 3.0], ["fusion.9", 1.0]]}}
    assert lm_kda_chunk_roofline.read(without) == pytest.approx(value)
    without["profile"] = {**without["profile"], "breakdown": {"device_ops": [["fusion.9", 1.0]]}}
    assert lm_kda_chunk_roofline.read(without) is None
    assert lm_kda_chunk_roofline.read(_ctx(cfg={**FULL, "ops_bytes": "axk1"})) is None


def test_the_readers_the_cell_reuses_take_the_new_counts():
    assert 0 < lm_extend_roofline.read(_ctx()) < 100 and 0 < lm_step_roofline.read(_ctx()) < 100


def test_extend_cost_growth_is_the_long_turns_busy_time_over_the_short_ones_and_needs_the_state_counters(capsys):
    """Launches back to back on one device, 4,096-token turns and steps:
    a launch's span begins at its dispatch, behind the launch ahead; the
    reader counts from where the launch ahead was ready."""
    events, t = [], 0.0

    def launch(k, busy_us, context=None, tokens=4096):
        nonlocal t
        dispatched = max(0.0, t - 150_000)  # dispatched while the launch ahead still runs
        span = lambda name, ts, dur, **args: {"ph": "X", "name": name, "ts": ts, "dur": dur, "tid": k, "args": {"launch_id": k, **args}}
        events.extend([span("h2d", dispatched, 1000, bytes=1), span("launch", dispatched, 500),
                       span("device_execute", dispatched + 500, t + busy_us - dispatched - 500)])
        if context is not None:
            events.append(span("lm_prefill", dispatched + 500, t + busy_us - dispatched - 500, tokens=tokens, context=context, sessions=1))
        t += busy_us

    for k, (busy_us, context) in enumerate([(200_000, 1024), (210_000, 5120), (26_000, None), (220_000, 9216), (250_000, 20000),
                                            (300_000, 33792), (26_000, None), (340_000, 50176)], start=1):
        launch(k, busy_us, context)
    launch(9, 60_000, 0, tokens=1024)
    assert extend_cost_growth.read(_ctx(traces={"traceEvents": events})) == pytest.approx(320_000 / 215_000)  # the first launch's wait is not known
    assert '"lm_state_carries": 36' in capsys.readouterr().out
    short_only = [e for e in events if e["args"]["launch_id"] <= 4]
    assert extend_cost_growth.read(_ctx(traces={"traceEvents": short_only})) is None  # no long turn in the span
    parent = _ctx(traces={"traceEvents": events})
    parent["snapshot_after"] = snapshot(lm_tokens_prefill=1, lm_prefill_launches=1)  # a program without the counters
    assert extend_cost_growth.read(parent) is None
