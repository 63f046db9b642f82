#!/usr/bin/env python3
"""Two controls of the output check of a cell whose layers are of two
kinds, WINDOW layers with rotary positions and FULL layers without any,
about that mechanism and not about precision, taken from the reference
itself with no knob in the program:

    python3 benchmarks/check_window.py <cell> <seed> --wrong window_ignored|full_rotated|both [--rehearse]

``window_ignored``: the same streams with every layer reading every
earlier position (a served path that keeps whole slots and never masks:
answers at a context of at most the window are the sound ones, so the
SHORT class must pass and the LONG one fail). ``full_rotated``: rotary
positions applied in the full layers too (a served path that does not
know its layers' kinds: both classes must fail). One process, no server,
no timing: seeded weights, the configuration's plain reference over the
seeded sample as the check module's ``expected`` takes it, then the
reference once more, wrong in that one way, and ITS answers held against
the first by the check module's ``served`` as a server's would be.
Prints each number beside its limit and one JSON line a control; exits 0
whatever the verdict (the caller reads it).
"""

from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import sys
import tempfile
import types

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from benchmarks import server_child as sc  # noqa: E402

CONTROLS = ("window_ignored", "full_rotated")


def wrong_answers(reference, cfg: dict, tree, sample: list, check, wrong: str) -> list[list]:
    """Every stream's answers as a server that is wrong about the layers'
    kinds in this one way would give them: one response a request, in order."""
    name = cfg["outputs"]["logits"]
    out = []
    for tokens, at in map(check.answered, sample):
        logits = np.asarray(reference.stream_logits(tree, tokens, cfg, at, wrong=wrong)[0])
        out.append([types.SimpleNamespace(outputs={name: row[None]}) for row in logits])
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("cell")
    p.add_argument("seed", type=int)
    p.add_argument("--wrong", choices=(*CONTROLS, "both"), default="both")
    p.add_argument("--rehearse", action="store_true", help="tiny sizes on a CPU")
    args = p.parse_args(argv)

    from triton_client_tpu.utils.compilation_cache import enable_persistent_cache

    enable_persistent_cache()
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        sys.exit("check_window: needs a TPU (or --rehearse)")
    bench = sc.load_json(ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.cell)
    cfg = sc.load_json(ROOT / next(c["file"] for c in bench["configs"] if c["name"] == cell["config"]))
    traffic = sc.load_json(ROOT / f"{bench['paths'][0]}/traffic/{cell['traffic']}.json")
    if args.rehearse:
        cfg = sc.apply_rehearsal(cfg)
        traffic = sc.rehearsal_traffic(traffic, cfg)
    reference = importlib.import_module(f"benchmarks.references.{cfg['reference']}")
    generator = importlib.import_module(f"benchmarks.inputs.{traffic['inputs']['generator']}")
    check = sc.check_module(cfg)
    params = sc.input_params(traffic, cfg, args.rehearse)
    sample = generator.make(sc.seeded(args.seed, 1), sc.sample_size(cfg, traffic, args.rehearse), params, cfg)
    with tempfile.TemporaryDirectory() as tmp:
        expected = pathlib.Path(tmp) / "reference.npz"
        tree = sc.make_weights(reference, cfg, args.seed, None)
        stats = check.expected(reference, cfg, tree, sample, expected)
        for wrong in (CONTROLS if args.wrong == "both" else (args.wrong,)):
            ok, lines, numbers = check.served(wrong_answers(reference, cfg, tree, sample, check, wrong), expected, cfg)
            for line in lines:
                print(json.dumps({"wrong": wrong, "compared": line["number"], "value": line["value"], "limit": line["limit"]}), flush=True)
            print(json.dumps({"seed": args.seed, "wrong": wrong, "correct": ok, "numbers": numbers, "reference": stats,
                              "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
