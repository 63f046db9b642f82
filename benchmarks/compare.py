"""The comparison that decides ``correct``. NumPy only.

Served rows ``[box..., score, label]`` are held against the plain
reference's rows for the same request, order-insensitively: a pair
matches when the labels are equal, every box column differs by at most
``box_atol + box_rtol * (longest side)`` and the scores by at most
``score_tol``. Two numbers come out, each compared with a limit the
configuration states (``check`` block, set from measurement):

  * ``unmatched_share``: boxes without a partner over boxes compared.
    With ``mode == "boxes"`` (the reference ran its own NMS) both
    sides' boxes need partners. With ``mode == "candidates"`` (the
    reference stops before NMS) every served box needs a partner among
    the candidates, each item's best candidate has to be served, and a
    response that fills ``max_det`` counts every box as unmatched: the
    cap, not the gate and NMS, then decided it.
  * ``score_err_ratio``: the root mean square score difference over
    the matched pairs (``score_err_rms``), over the seed's own
    ``sensitivity``: how far the reference's scores move when its
    parameters are rounded to bfloat16. The precision of the served
    arithmetic, in units of one bfloat16 rounding of the weights.

A box without a partner whose score lies within ``gate_band`` of the
entry's score threshold is left out of both counts: any two sound
evaluations disagree on which side of a threshold such a box falls.
With spread scores that is a few per cent of the boxes, not all of
them. No single box and no single response decides either number.
"""

from __future__ import annotations

import numpy as np


def live_rows(detections, valid) -> list[np.ndarray]:
    """A response's ``detections``/``valid`` -> one row array per item
    (2D responses carry a batch axis, 3D ones do not)."""
    d, v = np.asarray(detections, np.float32), np.asarray(valid).astype(bool)
    if d.ndim == 2:
        d, v = d[None], v[None]
    return [rows[mask] for rows, mask in zip(d, v)]


def _sides(rows: np.ndarray, box_cols: int) -> np.ndarray:
    if box_cols == 4:  # x1 y1 x2 y2
        return np.maximum(rows[:, 2] - rows[:, 0], rows[:, 3] - rows[:, 1])
    return rows[:, 3:6].max(axis=1)  # x y z dx dy dz heading


def match(rows, others, box_cols: int, tol: dict, gate: float):
    """Greedy one-to-one partners of ``rows`` among ``others``:
    (unmatched away from the gate, unmatched at the gate, the matched
    pairs' (served score, reference score))."""
    free = np.ones(len(others), bool)
    unmatched, at_gate, diffs = 0, 0, []
    band = tol.get("gate_band", 0.0)
    if len(others):
        reach = tol["box_atol"] + tol["box_rtol"] * _sides(others, box_cols)
    for row in rows:
        if not len(others):
            if abs(row[-2] - gate) <= band:
                at_gate += 1
            else:
                unmatched += 1
            continue
        dist = np.abs(others[:, :box_cols] - row[:box_cols]).max(axis=1)
        off = np.abs(others[:, -2] - row[-2])
        fits = free & (others[:, -1] == row[-1]) & (dist <= reach) & (off <= tol["score_tol"])
        if fits.any():
            j = int(np.argmin(np.where(fits, dist / reach, np.inf)))
            free[j] = False
            diffs.append((float(row[-2]), float(others[j, -2])))
        elif abs(row[-2] - gate) <= band:
            at_gate += 1
        else:
            unmatched += 1
    return unmatched, at_gate, diffs


def compare(served: list[np.ndarray], reference: list[np.ndarray], mode: str,
            box_cols: int, max_det: int, gate: float, tol: dict, sensitivity: float = 1.0) -> dict:
    """Fold the per-item comparisons of one sample. ``gate`` is the
    entry's score threshold, ``sensitivity`` the seed's own scale for
    score errors (``server_child.run_reference``)."""
    compared = unmatched = at_gate = empty = full = 0
    diffs: list[float] = []
    for got, want in zip(served, reference):
        empty += len(got) == 0
        full += len(got) >= max_det
        miss, edge, d = match(got, want, box_cols, tol, gate)
        diffs += d
        if mode == "boxes":
            back, back_edge, _ = match(want, got, box_cols, tol, gate)
            compared += len(got) + len(want) - edge - back_edge
            unmatched += miss + back
            at_gate += edge + back_edge
        else:
            compared += len(got) - edge + (1 if len(want) else 0)
            at_gate += edge
            if len(got) >= max_det:
                miss = len(got) - edge
            unmatched += miss
            if len(want):
                best = want[np.argmax(want[:, -2])][None]
                unmatched += match(best, got, box_cols, tol, -1.0)[0]
    return {
        "items": len(served),
        "boxes_served": int(sum(len(g) for g in served)),
        "boxes_compared": int(compared),
        "unmatched": int(unmatched),
        "at_gate_left_out": int(at_gate),
        "unmatched_share": unmatched / compared if compared else 1.0,
        "score_err_rms": score_err_rms(diffs),
        "score_err_ratio": score_err_rms(diffs) / sensitivity,
        "pairs": diffs,
        "empty_items": int(empty),
        "full_items": int(full),
    }


def score_err_rms(pairs, space: str = "probability") -> float:
    """Root mean square difference of the matched pairs' scores, as
    probabilities or as logits (``log(s / (1 - s))``: a head's own
    output, where a rounding error has one size whatever the score)."""
    if not len(pairs):
        return float("inf")
    a = np.clip(np.asarray(pairs, float), 1e-4, 1.0 - 1e-4)
    if space == "logit":
        a = np.log(a / (1.0 - a))
    return float(np.sqrt(np.mean(np.square(a[:, 0] - a[:, 1]))))


def verdict(numbers: dict, check: dict) -> tuple[bool, list[dict]]:
    """Each number beside its limit, and whether all hold."""
    lines = [
        {"number": name, "value": numbers[name], "limit": check[f"max_{name}"]}
        for name in ("unmatched_share", "score_err_ratio")
    ]
    return all(l["value"] <= l["limit"] for l in lines), lines


def malformed(response_outputs: dict, names: dict, max_det: int, row_width: int) -> str | None:
    """Why a window response is not well-formed, or None. ``names`` is
    the configuration's ``outputs`` block: which output holds the rows
    and which their validity mask."""
    try:
        d = np.asarray(response_outputs[names["rows"]])
        v = np.asarray(response_outputs[names["valid"]])
    except KeyError as e:
        return f"missing output {e}"
    if d.shape[-2:] != (max_det, row_width) or v.shape != d.shape[:-1]:
        return f"shapes {d.shape} {v.shape}"
    if not np.isfinite(d).all():
        return "non-finite rows"
    return None
