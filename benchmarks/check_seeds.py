"""A configuration's output check over many seeds in ONE process:
seeded weights -> ``weights.msgpack`` -> the program's own loading path
(``build_model``) -> the served pipeline on the sample, against the
plain reference. No timing: nothing here is a device metric.

  * ``--full`` on the chip: the cell's own sizes. This is how the
    limits of a configuration's ``check`` block were read: the largest
    numbers a dozen sound seeds give and the smallest the control
    (``--precision <the configuration's control>``) gives.
  * without it: the configuration's ``rehearsal`` sizes, Pallas kernels
    interpreted, for ``tests/test_rehearsal.py`` on a CPU.

``run.py`` makes the same comparison through the real server; this form
is what a dozen seeds can afford where every seed's weights compile anew.
"""

from __future__ import annotations

import functools
import importlib
import json
import pathlib
import sys
import tempfile
import types

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks import loadgen, server_child as sc  # noqa: E402


@functools.lru_cache(maxsize=None)
def _parts(config: str, traffic: str, full: bool):
    """``traffic`` names a mix under ``traffic/``, or is the path of a
    mix's file (the tests keep mixes that are in no cell)."""
    cfg = sc.load_json(ROOT / f"benchmarks/configs/{config}.json")
    if not full:
        cfg = sc.apply_rehearsal(cfg)
    mix = sc.load_json(ROOT / (traffic if traffic.endswith(".json") else f"benchmarks/traffic/{traffic}.json"))
    if not full:
        mix = sc.rehearsal_traffic(mix, cfg)
    reference = importlib.import_module(f"benchmarks.references.{cfg['reference']}")
    generator = importlib.import_module(f"benchmarks.inputs.{mix['inputs']['generator']}")
    return cfg, mix, reference, generator


def numbers(config: str, traffic: str, seed: int, precision: str | None = None,
            perturb: float = 0.0, full: bool = False) -> dict:
    """The output check's numbers for one seed, by the configuration's
    check module. ``precision`` serves the entry at a lower precision
    (the control); ``perturb`` hands the check module's ``perturbed``
    that amount for the SERVED weights."""
    import jax
    from triton_client_tpu.runtime.disk_repository import build_model

    cfg, mix, reference, generator = _parts(config, traffic, full)
    check = sc.check_module(cfg)
    if precision is None and "--precision" in cfg["serve_argv"]:  # as the cell serves it
        precision = cfg["serve_argv"][cfg["serve_argv"].index("--precision") + 1]
    params = sc.input_params(mix, cfg, not full)
    tree = sc.make_weights(reference, cfg, seed, sc.calibration_input(generator, mix, params, cfg, seed))
    served_tree = check.perturbed(tree, perturb) if perturb else tree
    with tempfile.TemporaryDirectory() as tmp:
        work = pathlib.Path(tmp)
        name = sc.write_repository(work / "repo", cfg, served_tree, not full, precision)
        model = build_model(work / "repo" / name, weights=work / "repo" / name / "1" / "weights.msgpack")
        sample = generator.make(sc.seeded(seed, 1), sc.sample_size(cfg, mix, not full), params, cfg)
        stats = check.expected(reference, cfg, tree, sample, work / "reference.npz")

        def answer(request: dict):
            out = model.infer_fn({k: jax.numpy.asarray(v) for k, v in loadgen.split_items(request)[0].items()})
            return types.SimpleNamespace(outputs={k: np.asarray(v) for k, v in out.items()})

        responses = [[answer(r) for r in s] if isinstance(s, list) else answer(s) for s in sample]  # streams or requests
        ok, _, result = check.served(responses, work / "reference.npz", cfg)
    result["correct"] = ok
    result["reference"] = stats
    return result


if __name__ == "__main__":  # check_seeds.py <config> <traffic> <seed>... [--precision p] [--full]
    argv = sys.argv[1:]
    precision = None
    full = "--full" in argv
    if full:
        argv.remove("--full")
        import jax

        if jax.devices()[0].platform != "tpu":
            sys.exit("check_seeds --full: needs a TPU")
    if "--precision" in argv:
        i = argv.index("--precision")
        precision = argv[i + 1]
        del argv[i : i + 2]
    for seed in argv[2:]:
        print(json.dumps({"seed": int(seed), "precision": precision,
                          **numbers(argv[0], argv[1], int(seed), precision, full=full)}), flush=True)
    if full:
        print(json.dumps({"memory": {k: v for k, v in (jax.devices()[0].memory_stats() or {}).items()
                                     if k in ("peak_bytes_in_use", "bytes_limit")}}), flush=True)
