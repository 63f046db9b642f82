"""Check kind ``logits_turns``: a language model served in token sessions
that are fed in several many-token TURNS and then stepped, and whose
attention reads a learned SELECTION of the cache once the context is
longer than ``model.index_topk``.

The sample is the traffic's own streams (``inputs/token_turns.py``).
EVERY request's answer is compared, each turn's last position and each
step, as the served path gave them through its caches and merged
launches, against the reference's full causal forward pass over the same
tokens (``references/<family>.stream_logits``: no cache, no batching).
The answers fall into two classes, held apart because top-k selection is
discontinuous, and not rarely so: between float32 and bfloat16 index
scores some of a query's selected keys differ at every long context, and
with seeded weights, whose index scores know nothing of the attention
weights, each exchanged key moves the layer's output. A margin band that
leaves near ties out (as ``tie_band`` does for experts) would leave every
long answer out.

  * SHORT: answers whose context is at most ``index_topk`` (each stream's
    first turn). Nothing is selected there, so ``checks/logits.py``'s
    tight numbers hold: ``short_logit_err_ratio``, the RMS logit
    difference over the seed's own ``sensitivity`` (how far the
    reference's logits move at THOSE positions when every matrix
    product's activations are rounded to bfloat16, taken on the first
    turn alone: no selection, so no flip, is in it), and
    ``short_worst_logit_err``, the largest logit difference. Answers
    whose router margin is under ``check.tie_band`` are left out of both
    (``near_tie_share`` counts them, over all answers). A model served
    one precision lower fails here.
  * LONG: every other answer. ``long_logit_err_rel``: the RMS logit
    difference over the reference's logits' own spread; it holds the
    flip noise, and a wrong selection (the latest positions, or none)
    moves every long answer by most of that spread.
    ``long_worst_answer_rel``: the same for the single worst answer: a
    stale or misplaced index key, a wrong cache row or a wrong row of a
    merged launch is the difference of two unrelated rows of logits,
    about 1.4 of the spread.

The harness's five functions; ``entry``, ``well_formed`` and
``perturbed`` are ``checks/logits.py``'s, and ``launch_request`` is but
for asking first whether the program has the selection at all.
"""

from __future__ import annotations

import numpy as np

from benchmarks import loadgen
from benchmarks.checks import logits
from benchmarks.checks.logits import entry, perturbed, well_formed  # noqa: F401


def launch_request(request: dict, b) -> dict:
    """``checks/logits.py``'s. A program without the index scores (the
    parent of the PR that brought them) fails HERE, at once, in the
    first seconds of set-up: before gigabytes of weights are drawn and
    the reference has run over 86k tokens for a server that cannot load
    the entry."""
    from triton_client_tpu.ops import sparse_index  # noqa: F401

    return logits.launch_request(request, b)


def _answered(stream: list) -> tuple[np.ndarray, np.ndarray]:
    """A stream's tokens in order and the position each request answers (its last token's)."""
    parts = [np.asarray(loadgen.split_items(r)[0]["tokens"]).reshape(-1) for r in stream]
    return np.concatenate(parts), np.cumsum([len(p) for p in parts]) - 1


def expected(reference, cfg: dict, tree, sample: list, out_path) -> dict:
    check, topk = cfg["check"], int(cfg["model"]["index_topk"])
    saved = {}
    for i, stream in enumerate(sample):
        tokens, at = _answered(stream)
        logits, margin = (np.asarray(a) for a in reference.stream_logits(tree, tokens, cfg, at))
        saved[f"logits_{i}"], saved[f"margin_{i}"], saved[f"context_{i}"] = logits, margin, at + 1
        short = at[at < topk]
        if i < int(check["sensitivity_streams"]) and len(short):
            # the stream up to its last short answer: nothing is selected there, in either pass
            head = tokens[: short[-1] + 1]
            rounded = np.asarray(reference.stream_logits(tree, head, cfg, short, round_acts=True)[0])
            saved[f"moved_{i}"] = np.mean((rounded - logits[: len(short)]) ** 2, axis=-1)
    np.savez(out_path, streams=np.asarray(len(sample)), **saved)
    logits = np.concatenate([saved[f"logits_{i}"] for i in range(len(sample))])
    margins = np.concatenate([saved[f"margin_{i}"] for i in range(len(sample))])
    contexts = np.concatenate([saved[f"context_{i}"] for i in range(len(sample))])
    return {"streams": len(sample), "answers": int(logits.shape[0]), "short_answers": int((contexts <= topk).sum()),
            "sensitivity": _sensitivity(saved, check["tie_band"]), "logit_std": float(logits.std()),
            "near_tie_share": float(np.mean(margins < check["tie_band"])), "longest_context": int(contexts.max())}


def _sensitivity(saved, band: float) -> float:
    """RMS, over the short answers clear of ``band``, of how far rounding
    the activations moved the reference; over all of them where none is clear."""
    moved = [(saved[k], saved["margin_" + k[6:]][: len(saved[k])]) for k in saved if k.startswith("moved_")]
    clear = [m[g >= band] for m, g in moved]
    values = np.concatenate(clear) if sum(len(c) for c in clear) else np.concatenate([m for m, _ in moved])
    return float(np.sqrt(np.mean(values)))


def differences(responses: list, ref, cfg: dict):
    """Served minus reference for every answer that came ``[N, V]``; the
    reference's logits, router margin and context at each; how many never came."""
    name = cfg["outputs"]["logits"]
    diffs, wants, margins, contexts, missing = [], [], [], [], 0
    for i in range(int(ref["streams"])):
        want = ref[f"logits_{i}"]
        stream = responses[i] if i < len(responses) else []
        got = np.concatenate([np.asarray(r.outputs[name], np.float32).reshape(1, -1) for r in stream]) \
            if stream else np.zeros((0, want.shape[1]), np.float32)
        missing += len(want) - len(got)
        k = min(len(want), len(got))
        diffs.append(got[:k] - want[:k])
        wants.append(want[:k])
        margins.append(ref[f"margin_{i}"][:k])
        contexts.append(ref[f"context_{i}"][:k])
    return np.concatenate(diffs), np.concatenate(wants), np.concatenate(margins), np.concatenate(contexts), missing


def served(responses: list, expected_file, cfg: dict) -> tuple[bool, list[dict], dict]:
    check, ref, topk = cfg["check"], np.load(expected_file), int(cfg["model"]["index_topk"])
    diff, want, margins, contexts, missing = differences(responses, ref, cfg)
    rms = lambda a: float(np.sqrt(np.mean(a**2))) if a.size else float("inf")
    short, tie = contexts <= topk, margins < check["tie_band"]
    kept = diff[short & ~tie]
    sensitivity = _sensitivity(ref, check["tie_band"])
    spread = float(want.std()) if want.size else 1.0
    per_answer = np.sqrt(np.mean(diff**2, axis=1)) / spread if diff.size else np.zeros(0)
    long_ = per_answer[~short]
    numbers = {
        # no short answer clear of the band (one seed in a thousand at a band that leaves a sixth out of
        # four): the class is empty and holds nothing against the run; answers that never came fail below
        "short_logit_err_ratio": rms(kept) / sensitivity if kept.size else 0.0,
        "short_worst_logit_err": float(np.abs(kept).max()) if kept.size else 0.0,
        "near_tie_share": float(tie.mean()) if tie.size else 1.0,
        "long_logit_err_rel": rms(diff[~short]) / spread,
        "long_worst_answer_rel": float(long_.max()) if long_.size else float("inf"),
    }
    lines = [{"number": k, "value": v, "limit": check[f"max_{k}"]} for k, v in numbers.items()]
    ok = missing == 0 and all(np.isfinite(l["value"]) and l["value"] <= l["limit"] for l in lines)
    edges = [0, topk, 4 * topk, 8 * topk, 12 * topk, 1 << 30]
    numbers["err_by_context"] = {  # for the log: [answers, RMS error over the spread, worst answer] as the context grows
        f"{lo}-{hi}": [int(sel.sum()), *((rms(diff[sel]) / spread, float(per_answer[sel].max())) if sel.any() else ())]
        for lo, hi in zip(edges, edges[1:]) for sel in [(contexts > lo) & (contexts <= hi)]
    }
    numbers.update(empty_items=missing, full_items=int((~np.isfinite(diff)).any(axis=1).sum()),
                   sensitivity=sensitivity, logit_std=spread, answers=len(diff), short_answers=int(short.sum()),
                   short_kept=int((short & ~tie).sum()), missing=missing)
    return bool(ok), lines, numbers
