#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print one JSON result line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Two processes. This one generates load through ``GRPCChannel`` and
compares responses with the reference's rows; it never initialises a
jax backend (importing ``triton_client_tpu.channel`` imports the jax
module, so it pins itself to the CPU platform to make sure). The child
(``server_child.py``) holds the chip: seeded weights, the real
``serve`` entry, the plain reference, the profiler.

Nothing here names a configuration, a traffic mix, a model family, a
kind of loop or a per-layer metric: the cell names its configuration
and traffic files, those name the reference, the check module
(``checks/<check.kind>.py``: what is compared and how), the input
generator, the loop kind (``loadgen.<loop>_requests/_loop/_sample``)
and the operation count, and each per-layer metric of BENCHMARK.json is
read by the module of its own name under ``layer_metrics/``.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import urllib.request  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
CHILD_ENV = dict(os.environ)  # as given: the child picks its own platform
# 15-27 s after a launcher was compiled anew (never after a cache hit)
# the runtime hands the compiler's memory back to the system, 2.4 GB in
# 1.5 s, and every thread of the serving process stands still meanwhile
# (PERF.md section 6). That is set-up: the warm-up outlasts it.
SETTLE_AFTER_COMPILE_S = 35.0

import numpy as np  # noqa: E402


def log(**row) -> None:
    print(json.dumps(row, default=str), flush=True)


def http_json(port: int, path: str):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=60.0) as r:
        return json.load(r)


def sample_snapshots(port: int, every_s: float, stop: threading.Event, into: list) -> None:
    """``/snapshot`` every ``every_s`` seconds until ``stop``: what the
    server's gauges read INSIDE a traced window (at its two ends a
    window of whole rounds is between rounds, and a gauge reads what
    the warm-up left behind)."""
    while not stop.wait(every_s):
        into.append(http_json(port, "/snapshot"))


def server_counts(snapshot: dict) -> dict:
    """The few server counters a builder reads beside a window."""
    b = snapshot["batching"]
    return {"compiles": snapshot["compile"]["compiles"], "merges": b["merges"],
            "ragged_batches": b.get("ragged_batches"), "occupancy": b.get("merge_occupancy"),
            "live_buckets": b.get("live_bucket_table")}


class Child:
    """The chip-holding process and its line protocol."""

    def __init__(self, argv: list[str]) -> None:
        env = {**CHILD_ENV, "PYTHONUNBUFFERED": "1"}
        self.proc = subprocess.Popen(
            [sys.executable, str(ROOT / "benchmarks" / "server_child.py"), *argv],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, cwd=str(ROOT), env=env,
        )

    def read(self, key: str) -> dict:
        while True:
            line = self.proc.stdout.readline()
            if not line:
                raise RuntimeError(f"server process ended (exit {self.proc.wait()}) before {key!r}")
            try:
                msg = json.loads(line)
            except ValueError:
                continue
            if key in msg:
                return msg

    def send(self, **cmd) -> None:
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


def output_check(check, cfg: dict, traffic: dict, ready: dict, make_channel, requests) -> dict:
    """The sample through the served path with the cell's own
    concurrency (``loadgen.<loop>_sample``), held against what the
    reference gave by the configuration's check module."""
    from benchmarks import loadgen

    channel = make_channel()
    try:
        responses = getattr(loadgen, f"{traffic['loop']}_sample")(make_channel, channel, requests, traffic)
    finally:
        channel.close()
    ok, lines, numbers = check.served(responses, ready["reference"], cfg)
    return {"ok": ok, "lines": lines, "numbers": numbers}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rates", default="", help="builder's sweep: comma-separated offered rates, "
                   "one window each in one server; prints no result line")
    p.add_argument("--record-trace", default="", help="builder's tool: with --trace 1, keep the "
                   "first events of the profiler trace at this path (the tests' fixture)")
    p.add_argument("--rehearse", action="store_true", help="CPU rehearsal at tiny sizes: "
                   "set-up and output check only; prints no metric")
    p.add_argument("--traffic-file", default="", help="with --rehearse: the cell's configuration under "
                   "this traffic file instead of the cell's own (the tests' mixes, which are in no cell)")
    args = p.parse_args(argv)

    os.environ["JAX_PLATFORMS"] = "cpu"  # this process only; the child gets its own
    from benchmarks import loadgen
    from benchmarks.server_child import (TRACE_CAPACITY, apply_rehearsal, check_module, input_params, load_json,
                                         rehearsal_traffic, sample_size, seeded)

    bench = load_json(ROOT / "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        print(f"no workload {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2
    config_file = next(c["file"] for c in bench["configs"] if c["name"] == cell["config"])
    traffic_file = f"{bench['paths'][0]}/traffic/{cell['traffic']}.json"
    if args.traffic_file:
        if not args.rehearse:
            print("--traffic-file is for a rehearsal: a measured run takes the cell's own mix", file=sys.stderr)
            return 2
        traffic_file = args.traffic_file
    cfg, traffic = load_json(ROOT / config_file), load_json(ROOT / traffic_file)
    if args.rehearse:
        cfg = apply_rehearsal(cfg)
        traffic = rehearsal_traffic(traffic, cfg)
    trace_s, trace_at_s = float(traffic.get("trace_s", 3.0)), traffic.get("trace_at_s")
    if not args.rehearse:  # what a measured run cannot do with the mix as its file stands, said before set-up is paid
        refused = getattr(loadgen, f"{traffic['loop']}_refused", None)
        why = refused(traffic, sample_size(cfg, traffic, False)) if refused else None
        if why is None and args.trace and trace_at_s is not None and args.seconds < trace_at_s + trace_s:
            why = (f"its trace lies {trace_at_s:g} s into the window and lasts {trace_s:g} s: "
                   f"--trace 1 needs --seconds {trace_at_s + trace_s:g} or more")
        if why:
            print(f"{traffic_file}: {why}", file=sys.stderr)
            return 2

    work = pathlib.Path(tempfile.mkdtemp(prefix="bench_"))
    child = Child([
        "--config", config_file, "--traffic", traffic_file, "--seed", str(args.seed),
        "--work", str(work), "--chips", str(cell["chips"]), "--trace", str(args.trace),
        *(["--rehearse"] if args.rehearse else []),
        *(["--record-trace", args.record_trace] if args.record_trace else []),
    ])
    try:
        from triton_client_tpu.channel.grpc_channel import GRPCChannel

        check = check_module(cfg)
        generator = importlib.import_module(f"benchmarks.inputs.{traffic['inputs']['generator']}")
        params = input_params(traffic, cfg, args.rehearse)
        # the same draw the child makes for the reference's sample
        inputs = generator.make(seeded(args.seed, 1), sample_size(cfg, traffic, args.rehearse), params, cfg)

        ready = child.read("ready")
        device = ready["device"]
        requests = getattr(loadgen, f"{traffic['loop']}_requests")(ready["model"], inputs)  # what the kind sends
        make_channel = lambda: GRPCChannel(f"127.0.0.1:{ready['port']}", timeout_s=120.0, retries=0)
        log(ready=ready)

        checked = output_check(check, cfg, traffic, ready, make_channel, requests)
        for line in checked["lines"]:
            log(compared=line["number"], value=line["value"], limit=line["limit"])
        log(output_check=checked["numbers"], reference=ready["reference_stats"])
        if args.rehearse:
            child.send(cmd="finish")
            child.read("done")
            print(json.dumps({"rehearsal": True, "correct": checked["ok"],
                              "numbers": checked["numbers"], "device": device}), flush=True)
            return 0

        well_formed = lambda resp: check.well_formed(resp, cfg)
        loop = getattr(loadgen, f"{traffic['loop']}_loop")  # the traffic file names its kind
        channel = make_channel()
        try:
            # warm-up: the cell's own traffic until the compile count stops
            # moving and, where a launcher was compiled anew, until the
            # runtime has handed the compiler's memory back. It draws from a
            # stream of its own: a warm-up one round longer (a compile, a
            # settling runtime) does not change what the window sends
            rng, window_rng = seeded(args.seed, 2), seeded(args.seed, 3)
            compiles = http_json(ready["metrics_port"], "/snapshot")["compile"]["compiles"]
            settled_at = ready["compiled_at_unix"] + (SETTLE_AFTER_COMPILE_S if ready["compiled_anew"] else 0.0)
            for round_ in range(120):
                loop(make_channel, channel, requests, traffic, float(traffic.get("warmup_s", 1.0)),
                     rng, None, rate=(float(args.rates.split(",")[0]) if args.rates else None))
                now = http_json(ready["metrics_port"], "/snapshot")["compile"]["compiles"]
                if now == compiles and round_ >= 1 and time.time() >= settled_at:
                    break
                compiles = now

            if args.rates:  # the sweep: a window a rate, no result line
                for rate in (float(r) for r in args.rates.split(",")):
                    win = loop(make_channel, channel, requests, traffic, args.seconds, window_rng, well_formed,
                               rate=rate)
                    lat = sorted(win.latencies_ms)
                    log(sweep_rate=rate, attempted=win.attempted, done=len(lat), failed=win.failed,
                        throughput=win.items_done / win.span_s(), p99=float(np.percentile(lat, 99)) if lat else None,
                        p50=float(np.median(lat)) if lat else None,
                        p95=float(np.percentile(lat, 95)) if lat else None,
                        last_tenth_p50=float(np.median(win.latencies_ms[-max(1, len(lat) // 10):])) if lat else None,
                        late_p95=float(np.percentile(win.late_ms, 95)) if win.late_ms else None,
                        server=server_counts(http_json(ready["metrics_port"], "/snapshot")),
                        timeline=win.timeline())
                child.send(cmd="finish")
                log(done=child.read("done"))
                return 0

            gc.collect()  # not inside the window
            snap0 = http_json(ready["metrics_port"], "/snapshot")
            inside, sampled = [], threading.Event()
            sampler = threading.Thread(target=sample_snapshots, args=(ready["metrics_port"], 2.0, sampled, inside))
            if args.trace:
                from benchmarks import trace_reduce

                if trace_at_s is not None:
                    # the mix pins the traced span to the WINDOW: what the reduction counts begins
                    # ``trace_at_s`` after the window's start and lasts ``trace_s``, whatever --seconds is
                    # (a mix whose window has phases says which one is traced; the check above keeps it inside).
                    # The command is the last thing sent before the window (the loop's channels and barrier
                    # are some tenths of a second); the child says when its profiler started
                    sampler.start()
                    child.send(cmd="profile", seconds=trace_reduce.HEAD_LEFT_OUT_S + trace_s,
                               after_s=max(0.0, trace_at_s - trace_reduce.HEAD_LEFT_OUT_S))
                else:
                    # starting the profiler holds the launches up for some 0.6 s: it starts in one
                    # more round of warm-up, and the reduction leaves the trace's head out;
                    # long enough for some ten launches (the traffic file's ``trace_s``, 3 s where it names
                    # none), and where the mix says so (``trace_after_s``) past the seconds in which a closed
                    # loop's callers all start at once: the held-up launches then fall into the head left out
                    child.send(cmd="profile", seconds=trace_reduce.HEAD_LEFT_OUT_S
                               + min(trace_s, max(0.5, args.seconds / 3)),
                               after_s=min(float(traffic.get("trace_after_s", 0.0)), args.seconds / 4))
                    loop(make_channel, channel, requests, traffic, 1.0, rng, None)
                    sampler.start()
            setup_s = time.perf_counter() - T0
            try:
                win = loop(make_channel, channel, requests, traffic, args.seconds, window_rng, well_formed)
            finally:
                sampled.set()
            snap1 = http_json(ready["metrics_port"], "/snapshot")
            traces = http_json(ready["metrics_port"], f"/traces?n={TRACE_CAPACITY}") if args.trace else None
            if args.trace:
                sampler.join()
        finally:
            channel.close()
        profiled = child.read("profiled") if args.trace else None
        child.send(cmd="finish")
        done = child.read("done")

        values = {**win.end_to_end(), "setup_s": setup_s}
        in_cell = lambda m: "workloads" not in m or cell["name"] in m["workloads"]
        metrics = {}
        if args.trace:
            ctx = {
                "cfg": cfg, "traffic": traffic, "cell": cell, "device": device, "window": win,
                "seconds": win.span_s(), "snapshot_before": snap0, "snapshot_after": snap1, "snapshots_inside": inside,
                # a trace pinned to the window: spans are read before the moment the child's profiler
                # started, on the clock both processes share (layer_metrics/_spans.py)
                "spans_until": None if trace_at_s is None else profiled["started_perf_counter_s"],
                "traces": traces, "profile": done["profile"], "model": ready["model"],
            }
            for m in filter(in_cell, bench["per_layer"]):
                reader = importlib.import_module(f"benchmarks.layer_metrics.{m['name'].split('.')[0]}")
                value = reader.read(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            for m in filter(in_cell, bench["end_to_end"]):
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

        log(server_before=server_counts(snap0), server_after=server_counts(snap1),
            timeline=win.timeline())
        if args.trace and done["profile"]:
            # the trace's idle share beside the one the whole window's launch count gives: they
            # differ where the profiler itself slowed the host, or the load was not steady
            rows = [v for k, v in done["profile"]["launches"].items() if f"mdl_{ready['model']}_" in k]
            per_launch = sum(r["device_s"] for r in rows) / max(1, sum(r["count"] for r in rows))
            launches = server_counts(snap1)["merges"] - server_counts(snap0)["merges"]
            log(idle_share_traced=1.0 - done["profile"]["busy_s"] / done["profile"]["window_s"],
                idle_share_from_window_launches=1.0 - launches * per_launch / win.span_s(),
                traced_launches=done["profile"]["launches"],
                profiler_started_at_s=profiled["started_perf_counter_s"] - win.t_start)
        log(window={"attempted": win.attempted, "failed": win.failed, "malformed": win.malformed,
                    "completed": len(win.latencies_ms), "items_done": win.items_done, "errors": win.errors,
                    "late_p95_ms": float(np.percentile(win.late_ms, 95)) if win.late_ms else None,
                    "span_s": win.span_s()},
            values=values, marks=ready["marks"])
        correct = bool(checked["ok"] and win.malformed == 0 and len(win.latencies_ms) > 0)
        # the peak on the chip: the allocator's peak over live buffers plus the served program's
        # temporaries, which that statistic leaves out (server_child.program_temp_bytes)
        device_out = {**device, "memory_peak_bytes": done["memory_peak_bytes"],
                      "memory_buffers_peak_bytes": done["memory_buffers_peak_bytes"],
                      "memory_program_temp_bytes": done["memory_program_temp_bytes"],
                      "memory_peak_before_server_bytes": done["memory_peak_before_server_bytes"],
                      "memory_peak_phase": done["memory_peak_phase"]}
        if done["memory_peak_phase"] == "yardstick":
            # an error in set-up, not a larger cell: no result line
            print(f"memory_peak_bytes was set before the server started ({done['memory_peak_before_server_bytes']} "
                  "bytes of the yardstick's own buffers: the reference, a second copy of the weights); the served "
                  f"program never passed it ({done['memory_buffers_peak_bytes']})", file=sys.stderr)
            return 4
        result = {"correct": correct, "attempted": win.attempted, "failed": win.failed,
                  "metrics": metrics, "device": device_out}
        if args.trace and done["profile"]:
            device_out["busy_s"] = done["profile"]["busy_s"]
            device_out["window_s"] = done["profile"]["window_s"]
            result["breakdown"] = done["profile"]["breakdown"]
        print(json.dumps(result), flush=True)
        for line in checked["lines"]:  # each number compared beside its limit, at the end of standard error too
            print(json.dumps({"compared": line["number"], "value": line["value"], "limit": line["limit"]}),
                  file=sys.stderr, flush=True)
        return 0
    finally:
        child.stop()
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
