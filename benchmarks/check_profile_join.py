#!/usr/bin/env python3
"""The builder's chip check of the program's own ``GET /profile``
(PR 26): during a cell's traffic, one capture of ``--profile-seconds``
through the live server's telemetry port. Prints, as JSON lines:

  * ``launch_timeline`` as the server returned it (the join of its
    spans with the device trace: ``obs/launch_timeline.py``), and how
    much of the device line's time between launches its named states
    cover, against the same capture read with ``trace_reduce``;
  * what ``op_summary`` holds (``obs/opstats.py`` on a chip trace);
  * the served rate before and during the capture: a capture must not
    slow what it measures.

    python3 benchmarks/check_profile_join.py --workload <cell> --seed <n>

Not a cell and not a metric: ``run.py`` hands its readers the reduced
profile only, so the device-clock join cannot be read there yet.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import threading
import time
import urllib.request

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def rate(done_at_s: list[float], items: int, t0: float, t1: float):
    """Items per second from the completions inside ``[t0, t1)``: first
    to last of them, so that where the edges fall between two answers
    of hundreds of frames does not move it."""
    inside = sorted(t for t in done_at_s if t0 <= t < t1)
    return items * (len(inside) - 1) / (inside[-1] - inside[0]) if len(inside) > 2 else None


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--profile-seconds", type=float, default=10.0)
    p.add_argument("--before-seconds", type=float, default=15.0, help="steady traffic before the capture")
    p.add_argument("--keep", default="", help="a directory for the server's /traces and /profile answers as they came")
    p.add_argument("--rehearse", action="store_true", help="CPU, tiny sizes: does the script run")
    args = p.parse_args(argv)

    import importlib
    import os
    import shutil
    import tempfile

    from benchmarks import loadgen, run, trace_reduce  # run keeps the environment as given for the child

    os.environ["JAX_PLATFORMS"] = "cpu"  # this process only, as in run.py
    from benchmarks.server_child import apply_rehearsal, input_params, load_json, rehearsal_traffic, seeded

    bench = load_json(ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    config_file = next(c["file"] for c in bench["configs"] if c["name"] == cell["config"])
    traffic_file = f"{bench['paths'][0]}/traffic/{cell['traffic']}.json"
    cfg, traffic = load_json(ROOT / config_file), load_json(ROOT / traffic_file)
    if args.rehearse:
        cfg = apply_rehearsal(cfg)
        traffic = rehearsal_traffic(traffic, cfg)
    work = pathlib.Path(tempfile.mkdtemp(prefix="bench_join_"))
    child = run.Child(["--config", config_file, "--traffic", traffic_file, "--seed", str(args.seed), "--work", str(work),
                       "--chips", str(cell["chips"]), "--trace", "1", *(["--rehearse"] if args.rehearse else [])])
    try:
        from triton_client_tpu.channel.grpc_channel import GRPCChannel

        generator = importlib.import_module(f"benchmarks.inputs.{traffic['inputs']['generator']}")
        inputs = generator.make(seeded(args.seed, 1), 1, input_params(traffic, cfg, args.rehearse), cfg)
        ready = child.read("ready")
        requests = getattr(loadgen, f"{traffic['loop']}_requests")(ready["model"], inputs)
        make_channel = lambda: GRPCChannel(f"127.0.0.1:{ready['port']}", timeout_s=120.0, retries=0)
        loop = getattr(loadgen, f"{traffic['loop']}_loop")
        channel = make_channel()
        rng = seeded(args.seed, 2)
        settled_at = ready["compiled_at_unix"] + (run.SETTLE_AFTER_COMPILE_S if ready["compiled_anew"] else 0.0)
        for round_ in range(120):  # warm-up, as run.py's
            loop(make_channel, channel, requests, traffic, float(traffic.get("warmup_s", 1.0)), rng, None)
            if round_ >= 1 and time.time() >= settled_at:
                break

        lead = float(traffic.get("trace_after_s", 0.0)) + args.before_seconds  # past the callers' start
        captured: dict = {}

        def capture() -> None:
            time.sleep(lead)
            captured["t0"] = time.perf_counter()
            try:
                url = f"http://127.0.0.1:{ready['metrics_port']}/profile?seconds={args.profile_seconds}"
                with urllib.request.urlopen(url, timeout=900.0) as r:  # the capture, then the server's own parse
                    captured["doc"] = json.load(r)
            except Exception as e:  # reported below; the traffic goes on
                captured["error"] = repr(e)
            captured["t1"] = time.perf_counter()

        thread = threading.Thread(target=capture)
        thread.start()
        win = loop(make_channel, channel, requests, traffic, lead + args.profile_seconds + 8.0, rng, None)
        thread.join()
        channel.close()
        if "doc" not in captured:
            run.log(profile_error=captured.get("error"))
            return 1
        doc = captured["doc"]
        if args.keep:
            keep = pathlib.Path(args.keep)
            keep.mkdir(parents=True, exist_ok=True)
            (keep / "profile.json").write_text(json.dumps(doc))
            (keep / "traces.json").write_text(json.dumps(run.http_json(ready["metrics_port"], "/traces?n=4096")))
        items = int(traffic["items_per_request"])
        t0 = captured["t0"] - win.t_start
        run.log(rate_before=rate(win.done_at_s, items, t0 - args.before_seconds, t0),
                rate_during=rate(win.done_at_s, items, t0, t0 + args.profile_seconds),
                capture_call_s=captured["t1"] - captured["t0"], window_failed=win.failed)
        from triton_client_tpu.runtime import shared_memory

        writes = [(t1 - t0, n) for t0, t1, n in shared_memory.write_log() if t1 >= win.t_start]
        run.log(client_writes=len(writes), client_write_bytes=sorted({n for _, n in writes}),
                client_write_ms=[round(1e3 * d, 1) for d, _ in writes[:: max(1, len(writes) // 12)]])
        run.log(launch_timeline=doc.get("launch_timeline"), launch_timeline_error=doc.get("launch_timeline_error"))
        summary = doc.get("op_summary")
        run.log(op_summary_error=doc.get("op_summary_error"),
                op_summary=summary and {**{k: v for k, v in summary.items() if k != "ops"}, "ops": summary["ops"][:5]})
        # the same capture through the benchmark's own reduction: the device line's time between launches
        files = sorted(pathlib.Path(doc["log_dir"]).rglob("*.xplane.pb"))
        planes = trace_reduce.read_xplane(files[-1])
        device = next((name for name in sorted(planes) if trace_reduce.DEVICE_PLANE.match(name)), None)  # none off a TPU
        modules = sorted((s, s + d) for n, s, d in planes.get(device, {}).get(trace_reduce.MODULES_LINE, [])
                         if n.startswith("jit_mdl_"))
        between = sum(max(0, b[0] - a[1]) for a, b in zip(modules, modules[1:])) / 1e9
        timeline = doc.get("launch_timeline") or {}
        states = timeline.get("idle_by_state_s") or {}
        named = sum(v for k, v in states.items() if k != "other")
        run.log(device_modules=len(modules), device_between_launches_s=between,
                device_module_s=sum(e - s for s, e in modules) / 1e9,
                idle_named_s=named, idle_named_share=named / between if between else None)
        child.send(cmd="finish")
        child.read("done")
        return 0
    finally:
        child.stop()
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
