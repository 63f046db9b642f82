"""Check kind ``logits``: language models served in token sessions,
whose answer to a request is one row of logits (the last appended
position's) and whose state lives in a cache on the device.

The sample is the traffic's own streams (``inputs/token_streams.py``):
for every stream ALL answers are compared, the prompt's last position
and each decoded step, as the served path gave them through its cache
and through launches merged with other sessions', against the
reference's full causal forward pass over the same tokens
(``references/<family>.stream_logits``: no cache, no batching). Four
numbers, each beside its limit in the configuration's ``check`` block:

  * ``logit_err_ratio``: the RMS logit difference over the compared
    positions, over the seed's own ``sensitivity``: the same RMS of how
    far the reference's logits move when every matrix product's
    activations are rounded to bfloat16 (the weights already are
    bfloat16: the served type), taken on the first
    ``check.sensitivity_streams`` streams. Dividing makes one limit fit
    all seeds;
  * ``beyond_tol_share``: the share of compared positions whose largest
    logit difference is over ``check.logit_atol``;
  * ``near_tie_share``: a routed model is discontinuous where an expert
    held here is about to change sides (chosen or left out). The
    reference returns each position's smallest such margin over the
    layers; positions under ``check.tie_band`` are left out of the two
    numbers above and counted here, with a limit of their own (as
    ``gate_band`` leaves boxes at the threshold out);
  * ``beyond_wide_tol_share``: the share of ALL positions, the near
    ties too, whose largest logit difference is over
    ``check.logit_atol_all``: an expert that changed sides moves a
    position by a fraction of the logits' spread, a wrong cache row or
    a wrong row of a merged launch by the spread itself.

The five functions are the harness's contract (``checks/boxes.py`` has
it in full); ``perturbed`` is ``check_seeds.py``'s control, and the
numbers carry ``empty_items`` (answers that never came) and
``full_items`` (answers that are not finite) under the names the
rehearsal's tests read. Weights of gigabytes: ``expected`` holds no second copy of
the tree (the reference casts a layer at a time) and moves each
stream's logits to the host as they come.
"""

from __future__ import annotations

import numpy as np

from benchmarks import loadgen


def _tokens(stream: list) -> tuple[np.ndarray, int]:
    """A stream's tokens in order and the index of the first position
    that is answered (the prompt's last)."""
    parts = [np.asarray(loadgen.split_items(r)[0]["tokens"]).reshape(-1) for r in stream]
    return np.concatenate(parts), len(parts[0]) - 1


def _sensitivity(saved, band: float) -> float:
    """RMS over the positions clear of ``band`` of how far rounding the
    activations moved the reference (``moved_<i>``: a position's mean
    square), over the streams that have it."""
    keep = [saved[k][saved["margin_" + k[6:]] >= band] for k in saved if k.startswith("moved_")]
    return float(np.sqrt(np.mean(np.concatenate(keep))))


def expected(reference, cfg: dict, tree, sample: list, out_path) -> dict:
    check = cfg["check"]
    saved = {}
    for i, stream in enumerate(sample):
        tokens, first = _tokens(stream)
        logits, margin = (np.asarray(a) for a in reference.stream_logits(tree, tokens, cfg, first))
        saved[f"logits_{i}"], saved[f"margin_{i}"] = logits, margin
        if i < int(check["sensitivity_streams"]):
            rounded = np.asarray(reference.stream_logits(tree, tokens, cfg, first, round_acts=True)[0])
            saved[f"moved_{i}"] = np.mean((rounded - logits) ** 2, axis=-1)
    np.savez(out_path, streams=np.asarray(len(sample)), **saved)
    logits = np.concatenate([saved[f"logits_{i}"] for i in range(len(sample))])
    margins = np.concatenate([saved[f"margin_{i}"] for i in range(len(sample))])
    return {"streams": len(sample), "answers": int(logits.shape[0]), "sensitivity": _sensitivity(saved, check["tie_band"]),
            "logit_std": float(logits.std()), "near_tie_share": float(np.mean(margins < check["tie_band"])),
            "margin_p10": float(np.percentile(margins, 10))}


def differences(responses: list, ref, cfg: dict) -> tuple[np.ndarray, np.ndarray, int]:
    """Served minus reference for every answer that came ``[N, V]``, the
    reference's router margin at each ``[N]``, and how many never came."""
    name = cfg["outputs"]["logits"]
    diffs, margins, missing = [], [], 0
    for i in range(int(ref["streams"])):
        want, margin = ref[f"logits_{i}"], ref[f"margin_{i}"]
        stream = responses[i] if i < len(responses) else []
        got = np.concatenate([np.asarray(r.outputs[name], np.float32).reshape(1, -1) for r in stream]) \
            if stream else np.zeros((0, want.shape[1]), np.float32)
        missing += len(want) - len(got)
        k = min(len(want), len(got))
        diffs.append(got[:k] - want[:k])
        margins.append(margin[:k])
    return np.concatenate(diffs), np.concatenate(margins), missing


def served(responses: list, expected_file, cfg: dict) -> tuple[bool, list[dict], dict]:
    check, ref = cfg["check"], np.load(expected_file)
    diff, margins, missing = differences(responses, ref, cfg)
    worst = np.abs(diff).max(axis=1) if diff.size else np.zeros(0)
    tie = margins < check["tie_band"]
    kept = diff[~tie]
    rms = float(np.sqrt(np.mean(kept**2))) if kept.size else float("inf")
    sensitivity = _sensitivity(ref, check["tie_band"])
    numbers = {
        "logit_err_ratio": rms / sensitivity,
        "beyond_tol_share": float(np.mean(worst[~tie] > check["logit_atol"])) if kept.size else 1.0,
        "near_tie_share": float(tie.mean()) if tie.size else 1.0,
        "beyond_wide_tol_share": float(np.mean(worst > check["logit_atol_all"])) if worst.size else 1.0,
    }
    lines = [{"number": k, "value": v, "limit": check[f"max_{k}"]} for k, v in numbers.items()]
    ok = missing == 0 and all(np.isfinite(l["value"]) and l["value"] <= l["limit"] for l in lines)
    edges = [0.0, 1e-4, 2.5e-4, 5e-4, 1e-3, 2e-3, 4e-3, np.inf]
    numbers["err_by_margin"] = {  # for the log: [positions, RMS error, worst logit] as the router's margin grows
        f"{lo:g}-{hi:g}": [int(sel.sum()), *((float(np.sqrt(np.mean(diff[sel] ** 2))), float(worst[sel].max())) if sel.any() else ())]
        for lo, hi in zip(edges, edges[1:]) for sel in [(margins >= lo) & (margins < hi)]
    }
    numbers.update(empty_items=missing, full_items=int((~np.isfinite(diff)).any(axis=1).sum()), logit_err_rms=rms,
                   sensitivity=sensitivity, answers=len(diff), missing=missing)
    return bool(ok), lines, numbers


def well_formed(response, cfg: dict) -> str | None:
    logits = response.outputs.get(cfg["outputs"]["logits"])
    if logits is None:
        return f"no output {cfg['outputs']['logits']!r} among {sorted(response.outputs)}"
    logits = np.asarray(logits)
    if logits.shape != (1, cfg["model"]["vocab_size"]):
        return f"logits of shape {logits.shape}, not (1, {cfg['model']['vocab_size']})"
    return None if np.isfinite(logits).all() else "logits not finite"


def entry(doc: dict, cfg: dict, rehearse: bool) -> dict:
    """The committed entry at the configuration's sizes (a rehearsal's
    tiny ones): ``model`` is the configuration's own block, whose cache
    geometry (``slot_len``, ``max_tokens``) goes to ``pipeline``."""
    del rehearse  # apply_rehearsal has already shrunk cfg["model"]
    model = dict(cfg["model"])
    pipeline = {**dict(doc.get("pipeline", {})), "slot_len": model.pop("slot_len"), "max_tokens": model.pop("max_tokens")}
    return {**doc, "model": {**model, "precision": doc["model"].get("precision", "bf16")}, "pipeline": pipeline}


def launch_request(request: dict, b) -> dict:
    """One launch's plain arrays at the launch shape ``b`` of the mix's
    ``launch_batch_sizes``: ``{"extend": tokens}`` (one session) or
    ``{"step": sessions}`` (one token each). The program forms them
    (every row pad: such a launch writes nothing and disturbs no
    session); a program that has no such family fails here, at once."""
    from triton_client_tpu.pipelines import lm

    del request
    ((kind, size),) = b.items()
    return lm.launch_inputs(kind, int(size))


def perturbed(tree, amount: float):
    """``amount`` added to the final norm's scale: every logit grows by
    that share (``check_seeds.py``'s broken-head control)."""
    return {**tree, "final_norm": tree["final_norm"] + amount}
