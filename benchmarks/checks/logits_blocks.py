"""Check kind ``logits_blocks``: a language model served in token sessions
that generates by DIFFUSION OVER BLOCKS (``inputs/token_blocks.py``): a
stream is one extend of the prompt's whole blocks, then for every block
of the reply two denoising passes, which are answered and write nothing,
and a commit, which writes the block.

The sample is the traffic's own streams. EVERY request's answer is
compared, as the served path gave it through its cache and through
launches merged with other sessions': the extend's one row (the last
appended position) and each block request's ``[B, vocab]``, a row a
position, against the reference's forward pass over ``committed prefix +
the block as that request carried it``, last B positions
(``references/<family>.forward``: no cache, no batching). A stream's
passes all share its prefix, so the reference takes them in ONE pass:
the committed stream under the block mask, and every denoising pass's
block as extra positions that read the prefix before them and their own
block and that nothing else reads (:func:`layout`). Logits, not tokens.
A commit's SUCCESSOR (the next block's first pass) is among the answers,
so a denoising pass that moved the session on and a commit that wrote
nothing are both seen: the successor then reads a cache that holds
another block than the committed one.

Three numbers, each beside its limit in the configuration's ``check``
block; an ANSWER here is one position's row of logits, and its error the
RMS of its logit differences over the reference's logits' own spread:

  * ``logit_err_ratio``: the MEDIAN answer's error over the seed's own
    ``sensitivity``: the median answer's same RMS of how far the
    reference's logits move when every matrix product's activations are
    rounded to bfloat16 (the weights already are: the served type), on
    the first ``check.sensitivity_streams`` streams. A routed model is
    discontinuous where an expert is about to change sides, and 48
    layers deep a position passes such a place in some layer often: no
    margin band can leave those answers out (as ``checks/logits.py``
    does) and keep any. The median answer is one in which nothing
    changed sides, or nothing that matters, on both sides of the ratio;
    an RMS over all answers is set by the few in which something did
    (at hidden 64 it read 1.0-8.8 on twelve sound seeds where the median
    reads 0.8-1.2). A model served one precision lower fails here;
  * ``moved_share``: the share of answers whose error is over
    ``check.moved_rel``, some ten times the median's: those in which an
    expert changed sides. It has a limit of its own: a router whose
    margins collapsed, or a precision that flips everywhere, reads high;
  * ``worst_answer_rel``: the single worst answer: a wrong cache row, a
    wrong row of a merged launch, a block that was written when it
    should not have been, or was not when it should, is the difference
    of two unrelated rows of logits, about 1.4 of the spread.

The harness's five functions; ``entry`` and ``perturbed`` are
``checks/logits.py``'s.
"""

from __future__ import annotations

import numpy as np

from benchmarks import loadgen
from benchmarks.checks.logits import entry, perturbed  # noqa: F401


def launch_request(request: dict, b) -> dict:
    """One launch's plain arrays at the launch shape ``b`` of the mix's
    ``launch_batch_sizes``: ``{"extend": tokens}`` (one session) or
    ``{"block": sessions, "width": B}`` (one block of B tokens each). A
    program without block sessions (the parent of the PR that brought
    them) fails HERE, at once, in the first seconds of set-up: before
    gigabytes of weights are drawn for a server that cannot load the
    entry."""
    from triton_client_tpu.models import sdar  # noqa: F401
    from triton_client_tpu.pipelines import lm

    del request
    (kind,) = set(b) - {"width"}
    return lm.launch_inputs(kind, int(b[kind]), block=int(b.get("width", 0)))


def layout(stream: list, block: int):
    """A stream as ONE set of positions for the reference's forward pass:
    ``(tokens [N], positions [N], visible [N, N], rows)``. The first T
    are the committed stream (the extend's tokens, then each commit's)
    under the block mask; after them every denoising pass's block, at the
    rotary positions of the block it stands for, reading the committed
    blocks before it and itself. ``rows[k]``: the positions whose logits
    request ``k`` answers, in the answer's order."""
    tokens, starts, passes, rows = [], [], [], []
    for request in (loadgen.split_items(r)[0] for r in stream):
        ids = np.asarray(request["tokens"]).reshape(-1)
        at = len(tokens)
        if "commit" not in request:  # the extend answers its last position
            tokens.extend(ids)
            rows.append(np.asarray([len(tokens) - 1]))
        elif int(np.asarray(request["commit"]).reshape(-1)[0]):
            tokens.extend(ids)
            rows.append(np.arange(at, at + len(ids)))
        else:
            rows.append(("pass", len(passes)))
            starts.append(at)
            passes.append(ids)
    t = len(tokens)
    rows = [t + block * r[1] + np.arange(block) if isinstance(r, tuple) else r for r in rows]
    positions = np.concatenate([np.arange(t), *[s + np.arange(block) for s in starts]]).astype(np.int32)
    owner = np.concatenate([np.full(t, -1), np.repeat(np.arange(len(passes)), block)])
    blk = positions // block
    committed = owner[None, :] < 0
    visible = np.where(owner[:, None] < 0, committed & (blk[None, :] <= blk[:, None]),
                       (committed & (blk[None, :] < blk[:, None])) | (owner[None, :] == owner[:, None]))
    return np.concatenate([tokens, *passes]).astype(np.int32), positions, visible, rows


def expected(reference, cfg: dict, tree, sample: list, out_path) -> dict:
    check, block = cfg["check"], int(cfg["model"]["block_length"])
    saved = {}
    for i, stream in enumerate(sample):
        tokens, positions, visible, rows = layout(stream, block)
        rows = np.concatenate(rows)
        logits = np.asarray(reference.forward(tree, tokens, positions, visible, cfg, rows))
        saved[f"logits_{i}"] = logits
        if i < int(check["sensitivity_streams"]):
            rounded = np.asarray(reference.forward(tree, tokens, positions, visible, cfg, rows, round_acts=True))
            saved[f"moved_{i}"] = np.mean((rounded - logits) ** 2, axis=-1)
    np.savez(out_path, streams=np.asarray(len(sample)), **saved)
    logits = np.concatenate([saved[f"logits_{i}"] for i in range(len(sample))])
    return {"streams": len(sample), "answers": int(logits.shape[0]), "sensitivity": _sensitivity(saved),
            "logit_std": float(logits.std())}


def _sensitivity(saved) -> float:
    """How far rounding the activations moved the reference's MEDIAN
    answer (RMS over its logits), over the streams that have it
    (``moved_<i>``: an answer's mean square)."""
    return float(np.sqrt(np.median(np.concatenate([saved[k] for k in saved if k.startswith("moved_")]))))


def differences(responses: list, ref, cfg: dict):
    """Served minus reference for every answer that came ``[N, V]``, the
    reference's logits at each, and how many never came."""
    name = cfg["outputs"]["logits"]
    diffs, wants, missing = [], [], 0
    for i in range(int(ref["streams"])):
        want = ref[f"logits_{i}"]
        stream = responses[i] if i < len(responses) else []
        got = np.concatenate([np.asarray(r.outputs[name], np.float32).reshape(-1, want.shape[1]) for r in stream]) \
            if stream else np.zeros((0, want.shape[1]), np.float32)
        missing += max(0, len(want) - len(got))
        k = min(len(want), len(got))
        diffs.append(got[:k] - want[:k])
        wants.append(want[:k])
    return np.concatenate(diffs), np.concatenate(wants), missing


def served(responses: list, expected_file, cfg: dict) -> tuple[bool, list[dict], dict]:
    check, ref = cfg["check"], np.load(expected_file)
    diff, want, missing = differences(responses, ref, cfg)
    sensitivity = _sensitivity(ref)
    spread = float(want.std()) if want.size else 1.0
    per_answer = np.sqrt(np.mean(diff**2, axis=1)) / spread if diff.size else np.zeros(0)
    numbers = {
        "logit_err_ratio": float(np.median(per_answer)) * spread / sensitivity if per_answer.size else float("inf"),
        "moved_share": float(np.mean(per_answer > check["moved_rel"])) if per_answer.size else 1.0,
        "worst_answer_rel": float(per_answer.max()) if per_answer.size else float("inf"),
    }
    lines = [{"number": k, "value": v, "limit": check[f"max_{k}"]} for k, v in numbers.items()]
    ok = missing == 0 and all(np.isfinite(l["value"]) and l["value"] <= l["limit"] for l in lines)
    numbers.update(empty_items=missing, full_items=int((~np.isfinite(diff)).any(axis=1).sum()),
                   sensitivity=sensitivity, logit_std=spread, answers=len(diff), missing=missing,
                   worst_logit_err=float(np.abs(diff).max()) if diff.size else float("inf"),
                   logit_err_rel=float(np.sqrt(np.mean(diff**2))) / spread if diff.size else float("inf"),
                   answer_rel_p50_p90_p99=[float(np.percentile(per_answer, q)) for q in (50, 90, 99)] if per_answer.size else [])
    return bool(ok), lines, numbers


def well_formed(response, cfg: dict) -> str | None:
    logits = response.outputs.get(cfg["outputs"]["logits"])
    if logits is None:
        return f"no output {cfg['outputs']['logits']!r} among {sorted(response.outputs)}"
    logits = np.asarray(logits)
    vocab, block = cfg["model"]["vocab_size"], cfg["model"]["block_length"]
    if logits.shape not in ((1, vocab), (block, vocab)):
        return f"logits of shape {logits.shape}, neither (1, {vocab}) nor ({block}, {vocab})"
    return None if np.isfinite(logits).all() else "logits not finite"
