"""``lm_extend_attention_roofline`` on a hand-made ``ctx``: both of a
launch's instances among the ten ops, one of them (its layers alone are
counted), none or a program without the kernel (nothing), and a share
that reads 100 where the kernel took the count's own least time."""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import peaks  # noqa: E402
from benchmarks.layer_metrics import lm_extend_attention_roofline as reader  # noqa: E402
from benchmarks.ops_bytes import smallthinker as counts  # noqa: E402

CFG = json.loads((ROOT / "benchmarks/configs/smallthinker21b-ep1-l12.json").read_text())
MODEL, KIND = "smallthinker_ep1", "TPU v5 lite"
LAUNCHES, TOKENS, CONTEXT = 47, 1775.0, 6800.0  # traced launches; the window's mean launch


def ctx(ops, launches=LAUNCHES):
    before = {"lm_tokens_prefill": 1000, "lm_prefill_launches": 10, "lm_context_prefill": 500}
    after = {"lm_tokens_prefill": 1000 + 270 * TOKENS, "lm_prefill_launches": 280, "lm_context_prefill": 500 + 270 * CONTEXT}
    snapshot = lambda counters: {"sessions": {"models": {MODEL: counters}}}
    return {
        "model": MODEL, "cfg": CFG, "device": {"kind": KIND},
        "snapshot_before": snapshot(before), "snapshot_after": snapshot(after),
        "profile": {
            "launches": {f"jit_mdl_{MODEL}_1_lm_prefill": {"count": launches, "device_s": 5.6},
                         f"jit_mdl_{MODEL}_1_lm_step": {"count": 40, "device_s": 0.7}},
            "breakdown": {"device_ops": ops},
        },
    }


def least(kind: str) -> float:
    """A layer of ``kind`` at the mean launch, at the peak: ``count_extend``'s attention term."""
    m = CFG["model"]
    pairs = (TOKENS * CONTEXT + TOKENS * (TOKENS + 1) / 2 if kind == "full"
             else counts._window_pairs(TOKENS, CONTEXT, m["sliding_window_size"]))
    return 2 * m["num_attention_heads"] * pairs * 2 * m["head_dim"] / peaks.peaks(KIND)["flops_per_s"]["bf16"]


OTHERS = [["while.50", 5.5], ["while.51", 4.1], ["ragged-dot-none.5", 0.55]]


def test_both_instances_seen_count_every_layer(capsys):
    ops = OTHERS + [["lm_extend_attention_window.26", 0.40], ["lm_extend_attention.25", 0.22]]
    want = 100 * LAUNCHES * (3 * least("full") + 9 * least("window")) / 0.62
    assert reader.read(ctx(ops)) == pytest.approx(want) and 0 < want < 100
    log = json.loads(capsys.readouterr().out)["lm_extend_attention"]
    assert log["device_s"] == {"window": [0.40], "full": [0.22]} and log["launches"] == LAUNCHES
    assert log["share_of_extend_launches"] == pytest.approx(0.62 / 5.6)
    assert log["tokens_a_launch"] == TOKENS and log["context_a_launch"] == CONTEXT


@pytest.mark.parametrize("name, kind, layers", [
    ("lm_extend_attention_window.26", "window", 9), ("lm_extend_attention.25", "full", 3)])
def test_one_instance_seen_counts_its_layers_alone(name, kind, layers, capsys):
    got = reader.read(ctx(OTHERS + [[name, 0.40]]))
    assert got == pytest.approx(100 * LAUNCHES * layers * least(kind) / 0.40)
    both = reader.read(ctx(OTHERS + [[name, 0.40], ["lm_extend_attention_window.9" if kind == "full" else "lm_extend_attention.9", 0.3]]))
    assert got != pytest.approx(both)


def test_the_counts_agree_with_count_extend():
    """The reader's pairs are ``count_extend``'s attention term, layer kind by layer kind."""
    m = CFG["model"]
    whole = counts.count_extend(CFG, TOKENS, CONTEXT)["flops"]
    s = counts._sizes(CFG)
    products = 2 * TOKENS * 12 * (s["attn"] + s["router"] + m["moe_num_active_primary_experts"] * s["expert"]) + 2 * s["head"]
    peak = peaks.peaks(KIND)["flops_per_s"]["bf16"]
    assert (3 * least("full") + 9 * least("window")) * peak == pytest.approx(whole - products)


@pytest.mark.parametrize("make", [
    lambda: ctx(OTHERS),  # the parent: no kernel among the ops
    lambda: ctx([]),
    lambda: {**ctx(OTHERS + [["lm_extend_attention.25", 0.2]]), "profile": None},  # an untraced run
    lambda: ctx(OTHERS + [["lm_extend_attention.25", 0.2]], launches=0),  # no extend launch in the span
    lambda: {**ctx(OTHERS + [["lm_extend_attention.25", 0.2]]), "snapshot_before": {}, "snapshot_after": {}},
], ids=["parent", "no-ops", "untraced", "no-launch", "no-counters"])
def test_yields_nothing_where_there_is_nothing_to_read(make, capsys):
    assert reader.read(make()) is None
    assert capsys.readouterr().out == ""


def test_the_share_reads_a_hundred_at_the_counts_own_least_time():
    took = {"window": LAUNCHES * 9 * least("window"), "full": LAUNCHES * 3 * least("full")}
    ops = OTHERS + [["lm_extend_attention_window.26", took["window"]], ["lm_extend_attention.25", took["full"]]]
    assert reader.read(ctx(ops)) == pytest.approx(100.0)
    assert reader.read(ctx(OTHERS + [["lm_extend_attention.25", took["full"]]])) == pytest.approx(100.0)
    slower = OTHERS + [["lm_extend_attention_window.26", 2 * took["window"]], ["lm_extend_attention.25", 2 * took["full"]]]
    assert reader.read(ctx(slower)) == pytest.approx(50.0)
