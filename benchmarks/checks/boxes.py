"""Check kind ``boxes``: detectors, whose answers are rows
``[box..., score, label]`` under a validity mask.

A configuration names its check kind (``check.kind``) and the harness
(``run.py``, ``server_child.py``, ``check_seeds.py``) calls the module
of that name here for everything a model family decides:

  * ``expected(reference, cfg, tree, sample, out_path) -> stats``: the
    plain reference over the seeded sample, written to ``out_path``;
  * ``served(responses, expected_file, cfg) -> (ok, lines, numbers)``:
    the sample's served responses held against that file, each number
    beside its limit;
  * ``well_formed(response, cfg) -> str | None``: why a response of the
    measured window is not well-formed, or None;
  * ``entry(doc, cfg, rehearse) -> doc``: the committed entry's
    ``config.yaml`` as the benchmark serves it;
  * ``launch_request(request, b) -> inputs``: one request's inputs at a
    launch shape of the traffic file's ``launch_batch_sizes``;
  * ``perturbed(tree, amount) -> tree`` (``check_seeds.py`` only): the
    served weights with one head pushed beyond tolerance.

The arithmetic is ``benchmarks/compare.py``'s (order-insensitive box
matching, ``unmatched_share`` and ``score_err_ratio``); the limits are
the configuration's ``check.max_<number>``. A detector answers each
request by itself, so a sample that comes in streams (a ``sessions``
mix) is compared request by request, in the streams' order.
"""

from __future__ import annotations

import importlib
import pathlib

import numpy as np

from benchmarks import compare, loadgen

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _gate(cfg: dict) -> float:
    pipe = cfg["pipeline"]
    return pipe.get("conf_thresh", pipe.get("score_thresh"))


def expected(reference, cfg: dict, tree, sample: list, out_path) -> dict:
    """The plain float32 reference over the sample's first
    ``check.sample_items`` items in one call (a request of the replay
    cells holds more frames than that: the limits were read on this
    many, and the reference's memory stays far under the served
    path's); its rows go to ``out_path`` for the parent's comparison.

    The same program is run once more with every parameter rounded to
    bfloat16 and back (no new compile: the weights are an argument).
    How far that moves the scores is this seed's ``sensitivity``: some
    seeds' weights pass a rounding error on at twice the size others
    do, and the comparison divides by it so that one limit fits all.
    The rounded copy is a second tree on the device: a detector's
    weights are megabytes (a family whose weights are gigabytes brings
    a check kind that goes layer by layer)."""
    import jax
    import jax.numpy as jnp

    batch = loadgen.first_items(loadgen.stacked(sample, cfg), int(cfg["check"]["sample_items"]))
    forward = jax.jit(lambda t, x: reference.forward(t, x, cfg))
    rounded = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16).astype(x.dtype), tree)
    items, moved = (
        reference.detections(jax.tree_util.tree_map(np.asarray, forward(t, batch)), cfg)
        for t in (tree, rounded)
    )
    shift = compare.compare(
        [it["rows"] for it in moved], [it["rows"] for it in items], "boxes", reference.BOX_COLS,
        10**9, _gate(cfg), cfg["check"],
    )
    np.savez(
        out_path,
        **{f"rows_{i}": it["rows"] for i, it in enumerate(items)},
        gated=np.asarray([it["gated"] for it in items]),
        sensitivity=np.asarray(shift["score_err_rms"]),
    )
    return {
        "items": len(items),
        "boxes": [len(it["rows"]) for it in items],
        "gated_max": int(max(it["gated"] for it in items)),
        "sensitivity": shift["score_err_rms"],
    }


def served(responses: list, expected_file, cfg: dict) -> tuple[bool, list[dict], dict]:
    """The served sample against the reference's rows: whether every
    number holds, each number beside its limit, and all the numbers."""
    reference = importlib.import_module(f"benchmarks.references.{cfg['reference']}")
    ref = np.load(expected_file)
    rows, valid = cfg["outputs"]["rows"], cfg["outputs"]["valid"]
    got = []
    for response in loadgen.flat(responses):
        got += compare.live_rows(response.outputs[rows], response.outputs[valid])
    want = [ref[f"rows_{i}"] for i in range(len(ref["gated"]))]  # the sample's first check.sample_items items
    numbers = compare.compare(
        got[: len(want)], want, reference.COMPARE, reference.BOX_COLS, cfg["pipeline"]["max_det"], _gate(cfg),
        cfg["check"], float(ref["sensitivity"]),
    )
    ok, lines = compare.verdict(numbers, cfg["check"])
    numbers.pop("pairs")
    return ok, lines, numbers


def well_formed(response, cfg: dict) -> str | None:
    pipe = cfg["pipeline"]
    return compare.malformed(response.outputs, cfg["outputs"], pipe["max_det"], pipe["row_width"])


def entry(doc: dict, cfg: dict, rehearse: bool) -> dict:
    """The entry's ``pipeline`` and ``dataset`` as served from a
    temporary repository (paths made absolute). A rehearsal shrinks the
    input (``input_hw``, or ``voxel`` and ``point_bucket`` with the
    dataset yaml inlined so that it can shrink) and asks for the fused
    kernels, interpreted off a TPU."""
    from triton_client_tpu.dataset_config import load_yaml

    pipeline = dict(doc.get("pipeline", {}))
    if "class_names_file" in pipeline:
        pipeline["class_names_file"] = str(ROOT / pipeline["class_names_file"])
    if rehearse:
        if "dataset" in doc:
            dataset = load_yaml(str(ROOT / doc.pop("dataset")))
            dataset.pop("model")
            pipeline = {**dict(dataset.pop("pipeline", {})), **pipeline}
            dataset["voxel"] = {**dataset["voxel"], **cfg["rehearsal"]["model"]["voxel"]}
            doc["model"] = dataset
            pipeline["point_buckets"] = [cfg["rehearsal"]["model"]["point_bucket"]]
        else:
            doc["model"] = {**doc["model"], "input_hw": cfg["rehearsal"]["model"]["input_hw"]}
        pipeline["fused"] = "on"
    elif "dataset" in doc:
        doc["dataset"] = str(ROOT / doc["dataset"])
    if pipeline:
        doc["pipeline"] = pipeline
    return doc


def launch_request(request: dict, b) -> dict:
    """One request's rows repeated to ``b`` (0: the request as it is;
    3D: one scan, no batch axis)."""
    return {k: np.resize(v, (b, *v.shape[1:])) for k, v in request.items()} if b else request


def perturbed(tree, amount: float):
    """``amount`` added to the last Detect/class head's bias."""
    import jax

    tree = jax.tree_util.tree_map(lambda x: x, tree)
    head = sorted(k for k in tree["params"] if "detect" in k or k == "cls_head")[-1]
    tree["params"][head] = {**tree["params"][head], "bias": tree["params"][head]["bias"] + amount}
    return tree
