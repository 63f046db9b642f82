#!/usr/bin/env python3
"""The output check's CONTROL for a cell whose answers come in streams
through the server: the cell's own entry served one precision lower.

    python3 benchmarks/check_control.py <cell> <seed> [--sound] [--rehearse]

One process, one seed, no timing (nothing here is a device metric):
seeded weights, the plain reference over the seeded sample, the entry
written with ``model.precision`` set to the configuration's
``control.serve_precision`` (``--sound``: as the cell serves it), the
real server (``serve``'s own parser and ``build_server``) in this
process, every launch shape compiled, the sample sent once through
``GRPCChannel`` as ``loadgen.<loop>_sample`` sends it, and the check
module's ``served`` on the answers. Prints each number beside its limit
and one JSON line with ``correct``; exits 0 whatever the verdict (the
caller reads it). ``check_seeds.py`` does the same for a configuration
whose every request answers by itself; a model whose state lives in the
server's cache has to go through the server.
"""

from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks import loadgen, server_child as sc  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("cell")
    p.add_argument("seed", type=int)
    p.add_argument("--sound", action="store_true", help="the stated precision, not the control's")
    p.add_argument("--rehearse", action="store_true", help="tiny sizes on a CPU")
    p.add_argument("--dump", help="write each answer's margin, RMS and worst difference here (.npz), for choosing limits")
    args = p.parse_args(argv)

    from triton_client_tpu.utils.compilation_cache import enable_persistent_cache

    enable_persistent_cache()
    import jax
    from triton_client_tpu.channel.grpc_channel import GRPCChannel

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        sys.exit("check_control: needs a TPU (or --rehearse)")
    bench = sc.load_json(ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.cell)
    cfg = sc.load_json(ROOT / next(c["file"] for c in bench["configs"] if c["name"] == cell["config"]))
    traffic = sc.load_json(ROOT / f"{bench['paths'][0]}/traffic/{cell['traffic']}.json")
    if args.rehearse:
        cfg = sc.apply_rehearsal(cfg)
        traffic = sc.rehearsal_traffic(traffic, cfg)
    precision = None if args.sound else cfg["control"]["serve_precision"]
    reference = importlib.import_module(f"benchmarks.references.{cfg['reference']}")
    generator = importlib.import_module(f"benchmarks.inputs.{traffic['inputs']['generator']}")
    check = sc.check_module(cfg)
    params = sc.input_params(traffic, cfg, args.rehearse)
    sample = generator.make(sc.seeded(args.seed, 1), sc.sample_size(cfg, traffic, args.rehearse), params, cfg)
    launches = [check.launch_request(loadgen.first_request(sample), b) for b in traffic["launch_batch_sizes"]]
    with tempfile.TemporaryDirectory() as tmp:
        work = pathlib.Path(tmp)
        tree = sc.make_weights(reference, cfg, args.seed, sc.calibration_input(generator, traffic, params, cfg, args.seed))
        stats = check.expected(reference, cfg, tree, sample, work / "reference.npz")
        name = sc.write_repository(work / "repo", cfg, tree, args.rehearse, precision)
        del tree
        server, serve_args = sc.start_server(work / "repo", [*cfg["serve_argv"], "--trace-capacity", "0"])
        try:
            sc.compile_launch_shapes(server, name, launches)
            make_channel = lambda: GRPCChannel(f"127.0.0.1:{server.port}", timeout_s=300.0, retries=0)
            requests = getattr(loadgen, f"{traffic['loop']}_requests")(name, sample)
            channel = make_channel()
            try:
                responses = getattr(loadgen, f"{traffic['loop']}_sample")(make_channel, channel, requests, traffic)
            finally:
                channel.close()
            ok, lines, numbers = check.served(responses, work / "reference.npz", cfg)
            if args.dump:
                import numpy as np

                ref = np.load(work / "reference.npz")
                diff, margin, _ = check.differences(responses, ref, cfg)
                np.savez(args.dump, margin=margin, mean_square=np.mean(diff**2, axis=1), worst=np.abs(diff).max(axis=1),
                         **{k: ref[k] for k in ref if k.startswith(("moved_", "margin_"))})
        finally:
            server.drain(timeout_s=serve_args.drain_timeout)
    for line in lines:
        print(json.dumps({"compared": line["number"], "value": line["value"], "limit": line["limit"]}), flush=True)
    print(json.dumps({"seed": args.seed, "precision": precision or "as served", "correct": ok, "numbers": numbers,
                      "reference": stats, "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
