"""Check kind ``logits_state``: a language model served in token sessions
that are fed in several many-token TURNS and then stepped, most of whose
layers hold a RECURRENT STATE (a session's slot carries it from request
to request) beside a few layers of cached rows.

The sample is the traffic's own streams (``inputs/token_turns.py``).
EVERY request's answer is compared, each turn's last position and each
step, as the served path gave them through its three caches and merged
step launches, against the reference's full pass over the same tokens
(``references/<family>.stream_logits``: the recurrence a position at a
time from a zero state, no cache, no batching). An ANSWER is one
position's row of logits, its error the RMS of its logit differences
over the reference's logits' own spread. Twelve expert layers deep, with
64 experts held, most answers pass a place where an expert held here is
near changing sides, and some do change in bfloat16, on both sides of
any ratio (``checks/logits_blocks.py`` met the same at 48 layers): an
RMS over all answers follows the few that flipped. So, as there, the
ratio is taken of MEDIANS, and the flipped answers are counted under a
limit of their own; the margin band of ``checks/logits.py`` is kept as
the structural number it is. In two classes by the answer's context (at
most, and over, ``check.context_split``) so that a drift that grows with
the state's age is seen as such:

  * ``short_logit_err_ratio``, ``long_logit_err_ratio``: the class's
    MEDIAN answer's error over the median answer's ``sensitivity`` (how
    far the reference's answers move when every matrix product's
    activations are rounded to bfloat16; taken at sixteen positions of
    each stream's first turn). A model served one precision lower, a
    state that a turn did not carry and a state that was not reset move
    every answer, and fail here. (The same ratio over each stream's
    FIRST answer alone is logged, ``first_logit_err_ratio``, and held to
    no limit: what a former session left in a slot shows there and only
    there, the model's own forgetting has worn it away by the second
    turn, and four answers of which one in three has an expert on the
    wrong side cannot carry a limit: PERF.md section 7);
  * ``moved_share``: the share of ALL answers whose error is over
    ``check.moved_rel`` of the spread: those in which an expert changed
    sides, and whatever else moved one answer and not the median;
  * ``near_tie_share``: answers whose router margin over the experts
    held here is under ``check.tie_band``: structural (a router whose
    margins collapsed reads 1);
  * ``worst_answer_rel``: the single worst answer: the wide tolerance. A
    wrong cache row, a wrong row of a merged launch or another session's
    state is the difference of two unrelated rows of logits, about 1.4
    of the spread.

The harness's five functions; ``entry``, ``well_formed`` and
``perturbed`` are ``checks/logits.py``'s.
"""

from __future__ import annotations

import numpy as np

from benchmarks import loadgen
from benchmarks.checks import logits
from benchmarks.checks.logits import entry, perturbed, well_formed  # noqa: F401

SENSITIVITY_POSITIONS = 16  # of each stream's first turn, evenly spaced, its last among them


def launch_request(request: dict, b) -> dict:
    """``checks/logits.py``'s. A program without the delta-rule
    attention (the parent of the PR that brought it) fails HERE, at
    once, in the first seconds of set-up: before gigabytes of weights
    are drawn and the reference has run over 150k tokens for a server
    that cannot load the entry."""
    from triton_client_tpu.ops import delta_attention  # noqa: F401

    return logits.launch_request(request, b)


def answered(stream: list) -> tuple[np.ndarray, np.ndarray]:
    """A stream's tokens in order and the position each request answers (its last token's)."""
    parts = [np.asarray(loadgen.split_items(r)[0]["tokens"]).reshape(-1) for r in stream]
    return np.concatenate(parts), np.cumsum([len(p) for p in parts]) - 1


def expected(reference, cfg: dict, tree, sample: list, out_path) -> dict:
    check = cfg["check"]
    saved = {}
    for i, stream in enumerate(sample):
        tokens, at = answered(stream)
        logits, margin = (np.asarray(a) for a in reference.stream_logits(tree, tokens, cfg, at))
        saved[f"logits_{i}"], saved[f"margin_{i}"], saved[f"context_{i}"] = logits, margin, at + 1
        if i < int(check["sensitivity_streams"]):
            head = tokens[: at[0] + 1]  # the first turn: a thousand tokens, not the stream
            some = np.unique(np.linspace(0, at[0], SENSITIVITY_POSITIONS + 1).astype(int)[1:])
            sound = np.asarray(reference.stream_logits(tree, head, cfg, some)[0])
            rounded = np.asarray(reference.stream_logits(tree, head, cfg, some, round_acts=True)[0])
            saved[f"moved_{i}"] = np.mean((rounded - sound) ** 2, axis=-1)
    np.savez(out_path, streams=np.asarray(len(sample)), **saved)
    logits = np.concatenate([saved[f"logits_{i}"] for i in range(len(sample))])
    margins = np.concatenate([saved[f"margin_{i}"] for i in range(len(sample))])
    contexts = np.concatenate([saved[f"context_{i}"] for i in range(len(sample))])
    return {"streams": len(sample), "answers": int(logits.shape[0]), "sensitivity": _sensitivity(saved),
            "logit_std": float(logits.std()), "near_tie_share": float(np.mean(margins < check["tie_band"])),
            "longest_context": int(contexts.max())}


def _sensitivity(saved) -> float:
    """The median, over the sensitivity's positions, of how far (RMS)
    rounding the activations moved the reference's answer."""
    return float(np.median(np.sqrt(np.concatenate([saved[k] for k in saved if k.startswith("moved_")]))))


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
    check, ref = cfg["check"], np.load(expected_file)
    diff, want, margins, contexts, missing = differences(responses, ref, cfg)
    split = int(check["context_split"])
    short, tie = contexts <= split, margins < check["tie_band"]
    first = np.concatenate([[True], np.diff(contexts) < 0]) if contexts.size else np.zeros(0, bool)  # a stream's first answer
    spread = float(want.std()) if want.size else 1.0
    sensitivity = _sensitivity(ref) / spread
    per_answer = np.sqrt(np.mean(diff**2, axis=1)) / spread if diff.size else np.zeros(0)
    median = lambda a: float(np.median(a)) if a.size else 0.0  # an empty class holds nothing against the run
    numbers = {
        "short_logit_err_ratio": median(per_answer[short]) / sensitivity,
        "long_logit_err_ratio": median(per_answer[~short]) / sensitivity,
        "moved_share": float(np.mean(per_answer > check["moved_rel"])) if per_answer.size else 1.0,
        "near_tie_share": float(tie.mean()) if tie.size else 1.0,
        "worst_answer_rel": float(per_answer.max()) if per_answer.size else float("inf"),
    }
    lines = [{"number": k, "value": v, "limit": check[f"max_{k}"]} for k, v in numbers.items()]
    ok = missing == 0 and all(np.isfinite(l["value"]) and l["value"] <= l["limit"] for l in lines)
    table = lambda sel: [int(sel.sum()), *((median(per_answer[sel]) / sensitivity, float(np.percentile(per_answer[sel], 90)),
                                            float(per_answer[sel].max())) if sel.any() else ())]
    edges = [0, split // 4, split, 2 * split, 3 * split, 1 << 30]
    numbers["err_by_context"] = {  # for the log: [answers, their median error over the sensitivity, 90th percentile and worst, of the spread]
        f"{lo}-{hi}": table((contexts > lo) & (contexts <= hi)) for lo, hi in zip(edges, edges[1:])}
    edges = [0.0, 2.5e-4, 5e-4, 1e-3, 2e-3, 4e-3, 8e-3, np.inf]
    numbers["err_by_margin"] = {f"{lo:g}-{hi:g}": table((margins >= lo) & (margins < hi)) for lo, hi in zip(edges, edges[1:])}
    numbers.update(first_logit_err_ratio=median(per_answer[first]) / sensitivity, empty_items=missing, full_items=int((~np.isfinite(diff)).any(axis=1).sum()),
                   sensitivity=sensitivity, logit_std=spread, answers=len(diff), short_answers=int(short.sum()), missing=missing)
    return bool(ok), lines, numbers
