"""Check kind ``logits_blocks`` on a made-up model (one masked mean a
position, no weights): that ONE pass over a stream's ``layout`` gives
what a pass a request gives, what each number catches, and that a
denoising pass that wrote the cache and a commit that did not are both
seen; the ``token_blocks`` generator; ``ops_bytes/sdar.py``'s counts; the
readers the block cell brings, on hand-made snapshots."""

from __future__ import annotations

import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import loadgen, server_child as sc  # noqa: E402
from benchmarks.checks import logits_blocks as check  # noqa: E402
from benchmarks.inputs import token_blocks  # noqa: E402
from benchmarks.layer_metrics import (block_hold_ms, block_rows_per_token, block_sessions_mean, lm_block_ms,  # noqa: E402
                                      lm_block_roofline)
from benchmarks.ops_bytes import sdar as counts  # noqa: E402

FULL = sc.load_json(ROOT / "benchmarks/configs/sdar30b-ep8-l48.json")
CFG = sc.apply_rehearsal(FULL)
MIX = sc.load_json(ROOT / "benchmarks/traffic/reply-blocks.json")
V, B = CFG["model"]["vocab_size"], CFG["model"]["block_length"]
PARAMS = CFG["rehearsal"]["traffic_params"]
_TABLE = np.random.default_rng(0).normal(size=(V, 24)).astype(np.float32)
_PLACE = np.random.default_rng(1).normal(size=(96, 24)).astype(np.float32)
_HEAD = np.random.default_rng(2).normal(size=(24, V)).astype(np.float32)


class FakeReference:
    """A position's logits are a function of the tokens it may read and
    where they stand: the mean of their embeddings under the mask.
    Rounding moves them by 0.01 RMS."""

    @staticmethod
    def forward(tree, tokens, positions, visible, cfg, rows=None, round_acts=False):
        x = _TABLE[np.asarray(tokens)] * _PLACE[np.asarray(positions)]
        visible = np.asarray(visible, np.float32)
        h = np.tanh(3.0 * (visible @ x) / visible.sum(axis=1, keepdims=True) + x)
        logits = (h @ _HEAD * 0.6).astype(np.float32)
        if round_acts:
            logits = logits + np.random.default_rng(1).normal(size=logits.shape).astype(np.float32) * 0.01
        return logits if rows is None else logits[np.asarray(rows)]

    @classmethod
    def stream_logits(cls, tree, tokens, cfg):
        positions = np.arange(len(tokens))
        blk = positions // B
        return cls.forward(tree, tokens, positions, blk[None, :] <= blk[:, None], cfg)


def served(sample, noise=0.0, writes_on_pass=None, skips_commit=None, swap=None):
    """The streams' answers as a server gives them, a forward pass a
    REQUEST over what its cache holds and the block it carries.
    ``writes_on_pass`` / ``skips_commit``: the index of a block request
    of every stream whose denoising pass is written (and moves the
    session on), whose commit is not."""
    out = []
    for i, stream in enumerate(sample):
        cached, answers = np.zeros(0, np.int32), []
        for k, request in enumerate(loadgen.split_items(r)[0] for r in stream):
            ids = np.asarray(request["tokens"]).reshape(-1)
            tokens = np.concatenate([cached, ids])
            logits = FakeReference.stream_logits(None, tokens, CFG)
            if "commit" not in request:
                cached, logits = tokens, logits[-1:]
            else:
                commit = bool(np.asarray(request["commit"]).reshape(-1)[0])
                if (commit and k != skips_commit) or k == writes_on_pass:
                    cached = tokens
                logits = logits[-B:]
            logits = logits + np.random.default_rng(1000 * i + k).normal(size=logits.shape).astype(np.float32) * noise
            answers.append(logits)
        if swap == i:
            answers[-1], answers[-4] = answers[-4], answers[-1]  # two commits' answers exchanged: a wrong row
        out.append([types.SimpleNamespace(outputs={"logits": a}) for a in answers])
    return out


@pytest.fixture()
def blocks_sample_and_file(tmp_path):
    sample = token_blocks.make(np.random.default_rng(3), 4, PARAMS, CFG)
    stats = check.expected(FakeReference, CFG, None, sample, tmp_path / "ref.npz")
    return sample, tmp_path / "ref.npz", stats


def test_a_round_of_the_cells_mix_is_the_same_work_on_every_seed():
    mask = FULL["model"]["vocab_size"] - 1
    for seed in (1, 2**31 + 5):
        streams = token_blocks.make(np.random.default_rng([seed, 1]), MIX["sample_requests"], MIX["inputs"]["params"], FULL)
        assert [len(s) for s in streams] == [193] * 16 and sum(len(s) for s in streams) == 3_088
        assert sum(r["items"] for s in streams for r in s) == 23_488
        assert sorted(s[0]["items"] for s in streams) == sorted([508, 1020, 1532, 1788] * 4)
        for s in streams:
            fed = s[0]["items"]
            assert s[0]["tokens"].shape == (1, fed) and "commit" not in s[0] and fed % 4 == 0
            assert fed + 64 * 4 <= MIX["cache"]["slot_len"] == FULL["model"]["slot_len"]
            known = {508: 2, 1020: 1, 1532: 3, 1788: 0}[fed]  # the prompt's last tokens ride in the first block
            for k in range(64):
                first, second, last = s[1 + 3 * k : 4 + 3 * k]
                assert [int(r["commit"][0, 0]) for r in (first, second, last)] == [0, 0, 1]
                assert [r["items"] for r in (first, second, last)] == [0, 0, 4]
                assert all(r["tokens"].shape == (1, 4) and r["tokens"].dtype == np.int32 for r in (first, second, last))
                hidden = 4 - (known if k == 0 else 0)
                masked = lambda r: int((r["tokens"] == mask).sum())
                assert (masked(first), masked(second), masked(last)) == (hidden, hidden - -(-hidden // 2), 0)
                shown = second["tokens"] != mask
                assert np.array_equal(second["tokens"][shown], last["tokens"][shown])
            assert max(int(r["tokens"].max()) for r in s[::3]) < mask  # no drawn id is the [MASK] row
    assert MIX["clients"] % MIX["sample_requests"] == 0 and FULL["max_batch_size"] == MIX["cache"]["slots"]


def test_one_pass_over_the_layout_is_a_pass_a_request(blocks_sample_and_file):
    """``expected`` runs the reference ONCE a stream, every denoising
    pass as extra positions; a sound server that runs it once a request
    gives the same numbers to the last bit of float32 arithmetic."""
    sample, path, stats = blocks_sample_and_file
    assert stats["answers"] == sum(1 + 3 * 4 * B for _ in sample) == 196
    ok, lines, numbers = check.served(served(sample), path, CFG)
    assert ok and numbers["worst_logit_err"] < 1e-4 and numbers["missing"] == 0
    tokens, positions, visible, rows = check.layout(sample[0], B)
    fed, passes = sample[0][0]["items"], 2 * 4
    assert len(tokens) == fed + 4 * B + passes * B and len(rows) == len(sample[0])
    assert visible[:fed + 4 * B, fed + 4 * B:].sum() == 0  # nothing of the committed stream reads a pass
    first_pass = rows[1]
    assert positions[first_pass].tolist() == list(range(fed, fed + B))
    assert visible[first_pass[0]].sum() == fed + B and visible[first_pass[0], first_pass].all()


def test_sound_noise_passes_and_a_lower_precision_fails(blocks_sample_and_file):
    sample, path, _ = blocks_sample_and_file
    ok, lines, numbers = check.served(served(sample, noise=0.012), path, CFG)
    assert ok and 0.9 < numbers["logit_err_ratio"] < 1.5, numbers
    ok, lines, numbers = check.served(served(sample, noise=0.07), path, CFG)
    failed = {l["number"] for l in lines if l["value"] > l["limit"]}
    assert not ok and "logit_err_ratio" in failed and "worst_answer_rel" not in failed, lines


@pytest.mark.parametrize("fault", ({"writes_on_pass": 4}, {"writes_on_pass": 5}, {"skips_commit": 6}, {"skips_commit": 3}))
def test_a_pass_that_wrote_and_a_commit_that_did_not_are_both_caught(blocks_sample_and_file, fault):
    """Request 4 is the second block's first pass, 5 its second, 3 and 6
    are commits: after the fault every later answer of the stream (the
    commit's SUCCESSOR first of all) is read from a cache that holds
    another block than the committed one."""
    sample, path, _ = blocks_sample_and_file
    ok, lines, numbers = check.served(served(sample, noise=0.012, **fault), path, CFG)
    failed = {l["number"] for l in lines if l["value"] > l["limit"]}
    assert not ok and "worst_answer_rel" in failed and numbers["worst_answer_rel"] > 0.9, lines


def test_a_wrong_row_a_missing_answer_and_a_malformed_one(blocks_sample_and_file):
    sample, path, _ = blocks_sample_and_file
    ok, lines, numbers = check.served(served(sample, noise=0.012, swap=2), path, CFG)
    assert not ok and numbers["worst_answer_rel"] > 0.9 and numbers["logit_err_ratio"] < 1.5
    short = served(sample, noise=0.012)
    short[1] = short[1][:-1]
    ok, _, numbers = check.served(short, path, CFG)
    assert not ok and numbers["missing"] == numbers["empty_items"] == B
    answer = lambda a: types.SimpleNamespace(outputs={"logits": a})
    assert check.well_formed(answer(np.zeros((B, V), np.float32)), CFG) is None
    assert check.well_formed(answer(np.zeros((1, V), np.float32)), CFG) is None
    assert "shape" in check.well_formed(answer(np.zeros((2, V), np.float32)), CFG)
    assert "finite" in check.well_formed(answer(np.full((B, V), np.nan, np.float32)), CFG)
    assert "no output" in check.well_formed(types.SimpleNamespace(outputs={}), CFG)


def test_counts_of_a_block_launch_and_a_prompt():
    m = FULL["model"]
    layer = 2048 * 5120 + 4096 * 2048 + 2048 * 128 + 16 * 3 * 2048 * 768  # ISSUE 39's arithmetic: 94.6 M a layer
    assert abs(48 * layer + 2 * 2048 * 18992 - 4.62e9) < 0.01e9
    one = counts.count_block(FULL, 16, 1350.0)
    assert 15.5 < one["experts_touched"] <= 16 and one["flops_dtype"] == "bf16"
    held = 2048 * 5120 + 4096 * 2048 + 2048 * 128 + one["experts_touched"] * 3 * 2048 * 768
    weights = 2 * (48 * held + 2048 * 18992)
    cache = 16 * 48 * 2048 * (1350 + 4 / 3)
    assert abs(one["bytes"] - (weights + cache + 64 * (4096 + 4 * 18992))) / one["bytes"] < 0.01
    assert one["bytes"] / 819e9 > one["flops"] / 197e12  # the bytes bound it
    none, all_ = counts.count_block(FULL, 16, 1350.0, 0.0), counts.count_block(FULL, 16, 1350.0, 1.0)
    assert all_["bytes"] - none["bytes"] == 16 * 48 * 2048 * 4  # the rows sixteen commits write
    prompt = counts.count_prefill(FULL, 1788)
    attention = 48 * 2 * 32 * (1788 * 1792 / 2) * 2 * 128  # the pairs the block mask lets through
    assert 2.25e9 < (prompt["flops"] - attention) / 1788 < 2.35e9  # ISSUE 39: 2.3 GFLOP a token through the matrices
    assert 0.6e9 < attention / 1788 < 0.8e9
    assert prompt["flops"] / 197e12 > prompt["bytes"] / 819e9  # operations bound it
    assert counts.count_prefill(FULL, 1788, 1024)["flops"] > prompt["flops"]
    assert counts.count(FULL, 16)["bytes"] == counts.count_block(FULL, 16, m["slot_len"] / 2)["bytes"]


def _ctx(before, after, launches=None, batching=None):
    snap = lambda stats, b: {"sessions": {"models": {"m": stats}}, "batching": b or {}}
    zeros = {k: 0 for k in batching or {}}
    return {"model": "m", "cfg": FULL, "device": {"kind": "TPU v5 lite"}, "snapshot_before": snap(before, zeros),
            "snapshot_after": snap(after, batching), "snapshots_inside": [], "profile": {"launches": launches or {}}}


def test_the_block_readers_on_hand_made_snapshots(capsys):
    zero = {"lm_block_rows": 0, "lm_block_launches": 0, "lm_block_commit_rows": 0, "lm_tokens_committed": 0,
            "session_cache_tokens": 0, "session_cache_slots_in_use": 0}
    after = {"lm_block_rows": 3072, "lm_block_launches": 200, "lm_block_commit_rows": 1024, "lm_tokens_committed": 4096,
             "session_cache_tokens": 16 * 1350, "session_cache_slots_in_use": 16}
    launches = {"jit_mdl_m_1_lm_block": {"count": 100, "device_s": 3.0}, "jit_mdl_m_1_lm_prefill": {"count": 16, "device_s": 1.0}}
    hold = {"step_holds": 150, "step_hold_s": 0.4, "step_hold_joined": 2000, "step_hold_expired": 1, "step_launch_ms": 50.0}
    ctx = _ctx(zero, after, launches, hold)
    assert block_sessions_mean.read(ctx) == 3072 / 200
    assert block_rows_per_token.read(ctx) == 0.75
    assert lm_block_ms.read(ctx) == 30.0
    assert block_hold_ms.read(ctx) == 2.0 and '"block_hold"' in capsys.readouterr().out
    share = lm_block_roofline.read(ctx)
    least = counts.count_block(FULL, 3072 / 200, 1350.0, 1 / 3)["bytes"] / 819e9
    assert abs(share - 100 * least / 0.030) < 1e-6 and 40 < share < 50
    # a program without the launch kind and its counters (the parent) reports nothing, and raises nothing
    parent = _ctx({}, {"lm_step_launches": 5}, {"jit_mdl_m_1_lm_step": {"count": 5, "device_s": 0.1}}, {})
    for reader in (block_sessions_mean, block_rows_per_token, lm_block_ms, block_hold_ms, lm_block_roofline):
        assert reader.read(parent) is None


def test_entry_and_launch_requests_at_the_mixs_shapes():
    doc = sc.entry_doc(CFG, True, "int8")
    assert doc["model"]["precision"] == "int8" and doc["model"]["hidden_size"] == 64 and doc["family"] == "sdar_moe"
    assert doc["pipeline"]["slot_len"] == 64 and "slot_len" not in doc["model"] and doc["max_batch_size"] == 20
    first = {"tokens": np.zeros((1, 508), np.int32)}  # the sample's first request is an extend: its width is no block's
    launches = [check.launch_request(first, b) for b in MIX["launch_batch_sizes"]]
    assert [l["tokens"].shape for l in launches] == [(1, 512), (1, 1024), (1, 2048), (8, 4), (16, 4)]
    assert all(not l["lengths"].any() for l in launches)  # every row pad: such a launch writes nothing
    assert [set(l) - {"tokens", "slots", "positions", "lengths"} for l in launches] == [set()] * 3 + [{"commit"}] * 2
    tiny = [check.launch_request(first, b)["tokens"].shape for b in CFG["rehearsal"]["traffic"]["launch_batch_sizes"]]
    assert tiny == [(1, 16), (1, 32), (8, 4)]


def test_the_configuration_keeps_every_published_width_and_all_its_depth():
    """The file's top level carries the catalog row's keys; its ``model``
    block (what runs) agrees with them, apart from the two in
    ``reduced``, and the traffic file's buckets are the program's."""
    cfg, model = FULL, FULL["model"]
    for key in ("hidden_size", "moe_intermediate_size", "num_attention_heads", "num_key_value_heads", "head_dim",
                "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps", "rope_theta", "num_hidden_layers", "vocab_size"):
        assert model[key] == cfg[key], key
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]) == (2048, 32, 4, 128)
    assert (cfg["moe_intermediate_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]) == (768, 6144, 48)
    assert cfg["rope_scaling"] is None and cfg["sliding_window"] is None and cfg["mlp_only_layers"] == []
    assert sorted(cfg["reduced"]) == ["num_experts", "vocab_size"] and cfg["published"] == {"num_experts": 128, "vocab_size": 151936}
    assert model["experts_here"] == cfg["num_experts"] == 16 and model["router_experts"] == 128
    assert cfg["deployment"]["chips_per_layer"] * model["experts_here"] == model["router_experts"]
    assert cfg["deployment"]["chips_per_layer"] * cfg["vocab_size"] == cfg["published"]["vocab_size"]
    from triton_client_tpu.pipelines import lm

    shapes = MIX["launch_batch_sizes"]
    assert {b["block"] for b in shapes if "block" in b} == {lm.step_bucket(n, 20) for n in range(1, MIX["clients"] + 1)}
    assert {b["width"] for b in shapes if "block" in b} == {model["block_length"]}
    fed = [p // 4 * 4 for p in MIX["inputs"]["params"]["prompts"]]
    assert sorted({b["extend"] for b in shapes if "extend" in b}) == sorted({lm.token_bucket(n) for n in fed})
    assert MIX["clients"] == MIX["sample_requests"] == 16 and MIX["inputs"]["params"]["blocks"] == 64
    assert max(fed) + 64 * 4 <= model["slot_len"] and max(fed) <= model["max_tokens"]
