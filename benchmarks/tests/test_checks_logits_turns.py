"""Check kind ``logits_turns`` on made-up answers (no model): its two
classes of answers and what each number catches; the ``token_turns``
generator; ``ops_bytes/dsv32.py``'s counts; the two readers the sparse
cell brings, on hand-made snapshots."""

from __future__ import annotations

import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import server_child as sc  # noqa: E402
from benchmarks.checks import logits_turns as check  # noqa: E402
from benchmarks.inputs import token_turns  # noqa: E402
from benchmarks.layer_metrics import lm_extend_roofline, lm_sparse_attention_roofline, sparse_keys_share  # noqa: E402
from benchmarks.ops_bytes import axk1 as axk1_counts, dsv32 as counts  # noqa: E402

FULL = sc.load_json(ROOT / "benchmarks/configs/dsv32-ep32-l6.json")
CFG = sc.apply_rehearsal(FULL)
MIX = sc.load_json(ROOT / "benchmarks/traffic/missionlog-rounds.json")
V, TOPK = CFG["model"]["vocab_size"], CFG["model"]["index_topk"]
PARAMS = CFG["rehearsal"]["traffic_params"]


class FakeReference:
    """Logits that depend on the stream's tokens alone; rounding moves
    them by 0.01 RMS; position 11 of every stream is a near-tie."""

    @staticmethod
    def stream_logits(tree, tokens, cfg, first, round_acts=False):
        rng = np.random.default_rng(int(np.sum(tokens[:8])))
        logits = rng.normal(size=(96, V)).astype(np.float32)[: len(tokens)] * 2
        if round_acts:
            logits = logits + np.random.default_rng(1).normal(size=logits.shape).astype(np.float32) * 0.01
        margin = np.where(np.arange(len(tokens)) == 11, 0.0005, 0.05).astype(np.float32)
        return logits[np.asarray(first)], margin[np.asarray(first)]


@pytest.fixture()
def turns_sample_and_file(tmp_path):
    sample = token_turns.make(np.random.default_rng(3), 4, {**PARAMS, "first_turn": 10}, CFG)
    stats = check.expected(FakeReference, CFG, None, sample, tmp_path / "ref.npz")
    return sample, tmp_path / "ref.npz", stats


def answers(sample, short_noise=0.0, long_noise=0.0, swap=None):
    out = []
    for i, stream in enumerate(sample):
        tokens, at = check._answered(stream)
        logits = FakeReference.stream_logits(None, tokens, CFG, at)[0].copy()
        noise = np.where(at[:, None] < TOPK, short_noise, long_noise)
        logits += np.random.default_rng(i).normal(size=logits.shape).astype(np.float32) * noise
        if swap == i:
            logits[[-1, -2]] = logits[[-2, -1]]  # two steps' answers exchanged: a wrong row
        out.append([types.SimpleNamespace(outputs={"logits": row[None]}) for row in logits])
    return out


def test_a_round_of_the_cells_mix_is_the_same_work_on_every_seed():
    for seed in (1, 2**31 + 5):
        streams = token_turns.make(np.random.default_rng([seed, 1]), MIX["sample_requests"], MIX["inputs"]["params"], FULL)
        sizes = [[r["items"] for r in s] for s in streams]
        assert sorted(len(s) for s in sizes) == [67, 69, 71, 73] and sum(map(len, sizes)) == 280
        assert sum(map(sum, sizes)) == 86_272 and sum(n for s in sizes for n in s if n > 1) == 86_016
        assert all(s[0] == 1024 and set(s[1:-64]) == {4096} and s[-64:] == [1] * 64 for s in sizes)
        assert max(sum(s) for s in sizes) == 33_856 <= MIX["cache"]["slot_len"] == FULL["model"]["slot_len"]
        assert all(r["tokens"].shape == (1, r["items"]) and r["tokens"].dtype == np.int32 for s in streams for r in s)
        assert max(int(r["tokens"].max()) for s in streams for r in s) < FULL["model"]["vocab_size"]
    assert MIX["clients"] % MIX["sample_requests"] == 0 and FULL["max_batch_size"] == MIX["cache"]["slots"]


def test_expected_holds_every_requests_answer_and_its_context(turns_sample_and_file):
    sample, path, stats = turns_sample_and_file
    assert stats["answers"] == sum(len(s) for s in sample) == 30 and stats["short_answers"] == 4
    ref = np.load(path)
    for i, stream in enumerate(sample):
        assert ref[f"context_{i}"].tolist() == np.cumsum([r["items"] for r in stream]).tolist()
        assert ref[f"moved_{i}"].shape == (1,)  # the sensitivity is taken on the first turn alone
    assert stats["sensitivity"] == pytest.approx(0.01, rel=0.2)


def test_sound_answers_with_flip_noise_at_long_context_are_correct(turns_sample_and_file):
    sample, path, _ = turns_sample_and_file
    ok, lines, numbers = check.served(answers(sample, 0.012, 0.3), path, CFG)
    assert ok, lines
    assert [l["number"] for l in lines] == ["short_logit_err_ratio", "short_worst_logit_err", "near_tie_share",
                                            "long_logit_err_rel", "long_worst_answer_rel"]
    assert numbers["short_logit_err_ratio"] == pytest.approx(1.2, rel=0.1) and numbers["answers"] == 30
    assert numbers["long_logit_err_rel"] == pytest.approx(0.15, rel=0.1) and numbers["empty_items"] == 0


def test_a_lower_precision_fails_by_the_short_answers_alone(turns_sample_and_file):
    sample, path, _ = turns_sample_and_file
    ok, lines, _ = check.served(answers(sample, 0.1, 0.3), path, CFG)
    failed = [l["number"] for l in lines if l["value"] > l["limit"]]
    assert not ok and failed == ["short_logit_err_ratio"]


def test_a_wrong_selection_and_a_wrong_row_fail_by_the_long_answers(turns_sample_and_file):
    sample, path, _ = turns_sample_and_file
    ok, lines, _ = check.served(answers(sample, 0.012, 1.5), path, CFG)
    assert not ok and "long_logit_err_rel" in [l["number"] for l in lines if l["value"] > l["limit"]]
    ok, lines, _ = check.served(answers(sample, 0.012, 0.1, swap=2), path, CFG)
    # two of 26 long answers exchanged: the worst answer reads 1.4; the mean over so few may pass its limit too
    assert not ok and "long_worst_answer_rel" in [l["number"] for l in lines if l["value"] > l["limit"]]


def test_short_answers_that_are_all_near_ties_hold_nothing_against_a_run(turns_sample_and_file):
    sample, path, _ = turns_sample_and_file
    ok, lines, numbers = check.served(answers(sample, 0.5, 0.1), path, {**CFG, "check": {**CFG["check"], "tie_band": 0.06}})
    assert numbers["short_kept"] == 0 and numbers["near_tie_share"] == 1.0
    assert [l["value"] for l in lines[:2]] == [0.0, 0.0]
    assert not ok and [l["number"] for l in lines if l["value"] > l["limit"]] == ["near_tie_share"]


def test_a_missing_answer_is_not_correct(turns_sample_and_file):
    sample, path, _ = turns_sample_and_file
    got = answers(sample, 0.012, 0.1)
    got[1] = got[1][:-1]
    ok, _, numbers = check.served(got, path, CFG)
    assert not ok and numbers["missing"] == numbers["empty_items"] == 1


def test_the_counts_of_the_sparse_form():
    ix = counts.count_index(FULL, 1.0, 16383.0)
    assert ix["flops"] == pytest.approx(2 * 13.96e6 + 2 * 64 * 128 * 16384, rel=1e-3)  # 268 MFLOP of scores a token a layer
    assert counts._selected_pairs(4096, 0, 2048) == 2048 * 2049 / 2 + 2048 * 2048
    assert counts._selected_pairs(4096, 16384, 2048) == 4096 * 2048 and counts._selected_pairs(1, 10, 2048) == 11
    long_ = counts.count_prefill(FULL, 4096, 16384)
    dense = axk1_counts.count_prefill(FULL, 4096, 16384)
    assert 0.5 * dense["flops"] < long_["flops"] < dense["flops"]  # the selection saves more than the indexer costs
    step, dense_step = counts.count_step(FULL, 4, 30000), axk1_counts.count_step(FULL, 4, 30000)
    # least bytes: the whole context's index keys (256 B) and 2,048 selected rows, not the whole context's latents (1,152 B)
    assert step["bytes"] < dense_step["bytes"] and step["bytes"] > axk1_counts.count_step(FULL, 4, 0)["bytes"]
    per_session_layer = (step["bytes"] - counts.count_step(FULL, 3, 30000)["bytes"]) / 6
    assert per_session_layer > 256 * 30000 + 1152 * 2048


def snapshot(**counters):
    return {"sessions": {"models": {"m": counters}}}


def test_the_two_readers_the_cell_brings_and_a_parent_without_the_counters():
    before = snapshot(lm_keys_visible=100, lm_keys_selected=100, lm_context_prefill=0, lm_tokens_prefill=0, lm_prefill_launches=0)
    after = snapshot(lm_keys_visible=1100, lm_keys_selected=300, lm_context_prefill=16384 * 2, lm_tokens_prefill=8192,
                     lm_prefill_launches=2)
    ctx = {"model": "m", "snapshot_before": before, "snapshot_after": after, "cfg": FULL, "device": {"kind": "TPU v5 lite"},
           "profile": {"launches": {"jit_mdl_m_1_lm_prefill": {"count": 4, "device_s": 4 * 0.9}}}}
    assert sparse_keys_share.read(ctx) == pytest.approx(20.0)
    want = 100 * counts.count_prefill(FULL, 4096, 16384)["flops"] / 197e12 / 0.9
    assert lm_extend_roofline.read(ctx) == pytest.approx(want) and 5 < want < 100
    parent = {**ctx, "snapshot_before": snapshot(lm_tokens_prefill=0, lm_prefill_launches=0),
              "snapshot_after": snapshot(lm_tokens_prefill=8192, lm_prefill_launches=2)}
    assert sparse_keys_share.read(parent) is None and lm_extend_roofline.read(parent) is None
    assert sparse_keys_share.read({"model": "m"}) is None and lm_extend_roofline.read({"model": "m", "cfg": FULL}) is None


def test_the_kernels_roofline_reads_its_instances_among_the_largest_ops():
    before = snapshot(lm_context_prefill=0, lm_tokens_prefill=0, lm_prefill_launches=0)
    after = snapshot(lm_context_prefill=16384 * 2, lm_tokens_prefill=8192, lm_prefill_launches=2)
    ops = [["while.111", 9.0], ["lm_sparse_attention.7", 2.0], ["lm_sparse_attention.6", 0.4], ["fusion.3", 0.1]]
    ctx = {"model": "m", "snapshot_before": before, "snapshot_after": after, "cfg": FULL, "device": {"kind": "TPU v5 lite"},
           "profile": {"launches": {"jit_mdl_m_1_lm_prefill": {"count": 4, "device_s": 3.6}}, "breakdown": {"device_ops": ops}}}
    layer = counts.count_selected_kernel(FULL, 4096, 16384)
    assert layer["flops"] == 2 * 128 * 4096 * 2048 * 320 and layer["flops"] / 197e12 > layer["bytes"] / 819e9
    want = 100 * 6 * 4 * layer["flops"] / 197e12 / 2.4
    assert lm_sparse_attention_roofline.read(ctx) == pytest.approx(want) and 1 < want < 100
    # a program whose trace has no op of that name (the parent, or plain XLA) yields nothing, and so does one without the counter
    nameless = {**ctx, "profile": {**ctx["profile"], "breakdown": {"device_ops": ops[:1]}}}
    assert lm_sparse_attention_roofline.read(nameless) is None
    assert lm_sparse_attention_roofline.read({**ctx, "snapshot_after": snapshot(lm_tokens_prefill=8192, lm_prefill_launches=2)}) is None
    assert lm_sparse_attention_roofline.read({"model": "m", "cfg": FULL}) is None
