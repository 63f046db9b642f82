"""Check kind ``logits`` on made-up answers (no model): what each of its
four numbers catches, and the five functions' contract."""

from __future__ import annotations

import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import server_child as sc  # noqa: E402
from benchmarks.checks import logits as check  # noqa: E402
from benchmarks.inputs import token_streams  # noqa: E402

CFG = sc.apply_rehearsal(sc.load_json(ROOT / "benchmarks/configs/axk1-ep16-l6.json"))
V = CFG["model"]["vocab_size"]


class FakeReference:
    """Logits that depend on the stream's tokens alone; rounding moves
    them by 0.01 RMS; every fifth position is a near-tie."""

    @staticmethod
    def stream_logits(tree, tokens, cfg, first, round_acts=False):
        rng = np.random.default_rng(int(np.sum(tokens)))
        logits = rng.normal(size=(len(tokens), V)).astype(np.float32) * 2
        if round_acts:
            logits = logits + np.random.default_rng(1).normal(size=logits.shape).astype(np.float32) * 0.01
        margin = np.where(np.arange(len(tokens)) % 5 == 0, 0.0005, 0.05).astype(np.float32)
        return logits[first:], margin[first:]


@pytest.fixture()
def sample_and_file(tmp_path):
    sample = token_streams.make(np.random.default_rng(3), 4, {"prompt_ladder": [12, 20], "steps": 8}, CFG)
    stats = check.expected(FakeReference, CFG, None, sample, tmp_path / "ref.npz")
    return sample, tmp_path / "ref.npz", stats


def answers(sample, noise=0.0, shift_stream=None):
    out = []
    for i, stream in enumerate(sample):
        tokens, first = check._tokens(stream)
        logits, _ = FakeReference.stream_logits(None, tokens, CFG, first)
        if noise:
            logits = logits + np.random.default_rng(i).normal(size=logits.shape).astype(np.float32) * noise
        if shift_stream == i:
            logits = np.roll(logits, 1, axis=0)  # every answer one position late
        out.append([types.SimpleNamespace(outputs={"logits": row[None]}) for row in logits])
    return out


def test_streams_are_drawn_whole_from_the_seed():
    a = token_streams.make(np.random.default_rng([5, 1]), 8, {"prompt_ladder": [12, 20, 27, 32], "steps": 8}, CFG)
    b = token_streams.make(np.random.default_rng([5, 1]), 8, {"prompt_ladder": [12, 20, 27, 32], "steps": 8}, CFG)
    assert sorted(s[0]["items"] for s in a) == [12, 12, 20, 20, 27, 27, 32, 32]
    assert all(len(s) == 9 and s[0]["tokens"].shape == (1, s[0]["items"]) for s in a)
    assert all(r["tokens"].shape == (1, 1) and r["items"] == 1 and r["tokens"].dtype == np.int32 for s in a for r in s[1:])
    assert all(np.array_equal(x["tokens"], y["tokens"]) for s, t in zip(a, b) for x, y in zip(s, t))
    assert max(int(r["tokens"].max()) for s in a for r in s) < V


def test_expected_states_the_seeds_sensitivity(sample_and_file):
    _, _, stats = sample_and_file
    assert stats["streams"] == 4 and stats["answers"] == 4 * 9
    assert 0.009 < stats["sensitivity"] < 0.011 and 0.1 < stats["near_tie_share"] < 0.35


@pytest.mark.parametrize("noise,ok", [(0.0, True), (0.012, True), (0.06, False)])
def test_ratio_follows_the_error_over_the_sensitivity(sample_and_file, noise, ok):
    sample, path, _ = sample_and_file
    correct, lines, numbers = check.served(answers(sample, noise), path, CFG)
    assert correct is ok
    assert [l["number"] for l in lines] == ["logit_err_ratio", "beyond_tol_share", "near_tie_share", "beyond_wide_tol_share"]
    assert abs(numbers["logit_err_ratio"] - noise / 0.01) < 0.6
    assert numbers["beyond_tol_share"] == 0.0 and numbers["missing"] == 0


def test_a_stream_answered_one_position_late_is_beyond_tolerance(sample_and_file):
    sample, path, _ = sample_and_file
    correct, _, numbers = check.served(answers(sample, shift_stream=2), path, CFG)
    assert not correct and numbers["beyond_tol_share"] > 0.2 and numbers["beyond_wide_tol_share"] > 0.2
    assert numbers["logit_err_ratio"] > 100


def test_a_missing_answer_is_not_correct(sample_and_file):
    sample, path, _ = sample_and_file
    got = answers(sample)
    got[1] = got[1][:-2]
    correct, _, numbers = check.served(got, path, CFG)
    assert not correct and numbers["missing"] == 2


def test_near_ties_are_left_out_and_counted(sample_and_file):
    sample, path, _ = sample_and_file
    got = answers(sample)
    for i, stream in enumerate(sample):
        _, first = check._tokens(stream)
        for k, response in enumerate(got[i]):
            if (first + k) % 5 == 0:  # the near-tie positions alone are off, as an expert that changed sides is
                response.outputs["logits"] = response.outputs["logits"] + 0.8
    correct, _, numbers = check.served(got, path, CFG)
    assert correct and numbers["logit_err_ratio"] == 0.0 and numbers["err_by_margin"]["0.0005-0.001"][1] > 0.79
    limit = {**CFG, "check": {**CFG["check"], "max_near_tie_share": 0.05}}
    assert not check.served(got, path, limit)[0]  # their share has its own limit


def test_near_ties_are_still_held_to_the_wide_tolerance(sample_and_file):
    """A fault confined to near-tie positions (a wrong cache row moves a
    position by the logits' own size) is not correct."""
    sample, path, _ = sample_and_file
    got = answers(sample)
    for i, stream in enumerate(sample):
        _, first = check._tokens(stream)
        for k, response in enumerate(got[i]):
            if (first + k) % 5 == 0:
                response.outputs["logits"] = np.roll(response.outputs["logits"], 1, axis=1)  # another row's logits
    correct, lines, numbers = check.served(got, path, CFG)
    assert not correct and numbers["logit_err_ratio"] == 0.0 and numbers["beyond_tol_share"] == 0.0
    assert [l["number"] for l in lines if l["value"] > l["limit"]] == ["beyond_wide_tol_share"]


def test_well_formed_and_entry_and_launch_request():
    good = types.SimpleNamespace(outputs={"logits": np.zeros((1, V), np.float32)})
    assert check.well_formed(good, CFG) is None
    assert "shape" in check.well_formed(types.SimpleNamespace(outputs={"logits": np.zeros((2, V))}), CFG)
    assert "finite" in check.well_formed(types.SimpleNamespace(outputs={"logits": np.full((1, V), np.nan)}), CFG)
    doc = sc.entry_doc(CFG, True, "int8")
    assert doc["model"]["precision"] == "int8" and doc["model"]["hidden_size"] == 64
    assert doc["pipeline"]["slot_len"] == 48 and "slot_len" not in doc["model"] and doc["max_batch_size"] == 40
    step, extend = check.launch_request({}, {"step": 8}), check.launch_request({}, {"extend": 32})
    assert step["tokens"].shape == (8, 1) and extend["tokens"].shape == (1, 32)
    assert not step["lengths"].any() and set(step) == {"tokens", "slots", "positions", "lengths"}


def test_the_configuration_keeps_every_published_width():
    """The file's top level carries the catalog row's keys; its ``model``
    block (what runs) agrees with them, apart from the three in
    ``reduced``, and the traffic file's buckets are the program's."""
    cfg = sc.load_json(ROOT / "benchmarks/configs/axk1-ep16-l6.json")
    model = cfg["model"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "n_shared_experts",
                "num_experts_per_tok", "routed_scaling_factor", "rope_scaling", "rms_norm_eps", "first_k_dense_replace",
                "num_hidden_layers", "vocab_size"):
        assert model[key] == cfg[key], key
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["q_lora_rank"], cfg["kv_lora_rank"]) == (7168, 64, 1536, 512)
    assert (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]) == (128, 64, 128)
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"], model["router_experts"]) == (18432, 2048, 192)
    assert model["experts_here"] == cfg["n_routed_experts"] == 12 and cfg["published"]["n_routed_experts"] == 192
    assert sorted(cfg["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["deployment"]["chips_per_layer"] * model["experts_here"] == model["router_experts"]
    from triton_client_tpu.pipelines import lm

    mix = sc.load_json(ROOT / "benchmarks/traffic/fleet-rounds.json")
    shapes = mix["launch_batch_sizes"]
    assert {b["step"] for b in shapes if "step" in b} == {lm.step_bucket(n, 40) for n in range(1, 41)}
    ladder = mix["inputs"]["params"]["prompt_ladder"]  # what the mix sends is what it warms up; the program compiles a smaller extend on demand
    assert [b["extend"] for b in shapes if "extend" in b] == [lm.token_bucket(n) for n in ladder] == ladder
    assert mix["clients"] == 32 and mix["sample_requests"] == 16 and mix["inputs"]["params"]["steps"] == 256
