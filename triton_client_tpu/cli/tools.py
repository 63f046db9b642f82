"""Dataset utility subcommands (the reference's tools/ scripts).

  pc-extract  — PointCloud2 topic of a bag -> numbered .npy point clouds
                (tools/pc_extractor.py:17-45; output feeds the 3D
                NpyPointCloudSource demo path).
  bag-stitch  — copy the first N messages (optionally per-topic) of a
                bag into a new bag: truncated fixture bags for tests
                (tools/bag_stitch.py:1-8).
  bag-info    — topics/types/counts of a bag (rosbag info equivalent,
                handy since TPU hosts have no ROS tooling).
  trace-dump  — pull the request-trace ring buffer off a serving
                process's telemetry port as Chrome-trace JSON
                (open in Perfetto / chrome://tracing). ``--ops`` turns
                it into a per-op device-time report instead: summarize
                an offline jax.profiler capture (``--ops PATH``) or
                take a live capture through ``/profile`` (bare
                ``--ops``) and rank XLA ops by device time with their
                owning model (obs/opstats.py).
  roofline    — per-model roofline report: measured flops/bytes from
                XLA's cost model (spec.extra, recorded at first
                launch), arithmetic intensity vs the machine knee,
                compute-/bandwidth-bound class, attainable-fps ceiling
                next to the measured rate. Reads a live /snapshot URL.
  trace-join  — merge several Chrome-trace exports (client / router /
                replica trace-dump outputs) onto ONE timeline: each
                source becomes its own pid row, shifted by an explicit
                per-source clock offset or one estimated from a probe
                round-trip against the source's live telemetry port
                (the same NTP-midpoint split obs.trace.graft_span_summary
                applies per response).
  lint        — tpulint: AST hazard analysis of the serving stack
                (recompilation/donation/host-sync/lock/telemetry rules;
                docs/LINTING.md). The CI gate runs this before pytest.
  route       — probe a replica set: liveness/readiness/labels per
                endpoint, the operator view of FrontDoorRouter's
                rotation decision (runtime/router.py).
"""

from __future__ import annotations

import argparse
import os

import numpy as np


def pc_extract(argv=None) -> None:
    p = argparse.ArgumentParser(description="bag -> .npy point clouds")
    p.add_argument("bag_file")
    p.add_argument("--pc-topic", default=None, help="default: first PointCloud2 topic")
    p.add_argument("-o", "--output", default="./extracted_clouds")
    p.add_argument("--limit", type=int, default=0)
    p.add_argument(
        "--intensity-scale",
        type=float,
        default=1.0,
        help="divide intensity by this (pc_extractor.py normalizes /255)",
    )
    args = p.parse_args(argv)

    from triton_client_tpu.io.bag_io import BagPointCloudSource

    os.makedirs(args.output, exist_ok=True)
    src = BagPointCloudSource(args.bag_file, topic=args.pc_topic, limit=args.limit)
    n = 0
    for i, frame in enumerate(src):
        pts = frame.data.copy()
        if args.intensity_scale != 1.0:
            pts[:, 3] /= args.intensity_scale
        np.save(os.path.join(args.output, f"{i:06d}.npy"), pts)
        n += 1
    print(f"extracted {n} point clouds from {src.topic} -> {args.output}")


def bag_stitch(argv=None) -> None:
    p = argparse.ArgumentParser(description="truncate/copy a bag")
    p.add_argument("in_bag")
    p.add_argument("out_bag")
    p.add_argument("-n", "--count", type=int, default=100, help="max messages")
    p.add_argument("--topics", nargs="*", default=None)
    args = p.parse_args(argv)

    from triton_client_tpu.io import rosbag as rb

    n = 0
    with rb.BagReader(args.in_bag) as r, rb.BagWriter(args.out_bag) as w:
        for topic, bm, t in r.read_messages(topics=args.topics, raw=True):
            if n >= args.count:
                break
            w.write(topic, bm, t=t)
            n += 1
    print(f"wrote {n} messages -> {args.out_bag}")


def bag_info(argv=None) -> None:
    p = argparse.ArgumentParser(description="bag topic/type/count summary")
    p.add_argument("bag_file")
    args = p.parse_args(argv)

    from triton_client_tpu.io import rosbag as rb

    counts: dict[str, int] = {}
    t0, t1 = None, None
    with rb.BagReader(args.bag_file) as r:
        for topic, _, t in r.read_messages(raw=True):
            counts[topic] = counts.get(topic, 0) + 1
            t0 = t if t0 is None else min(t0, t)
            t1 = t if t1 is None else max(t1, t)
        types = {c.topic: c.datatype for c in r.connections.values()}
    if t0 is not None:
        print(f"duration: {t1 - t0:.3f}s  messages: {sum(counts.values())}")
    for topic in sorted(counts):
        print(f"  {topic}  {types.get(topic, '?')}  {counts[topic]} msgs")


def trace_dump(argv=None) -> None:
    """Fetch recent request traces from a live server's telemetry port
    and write Chrome-trace JSON — the CLI face of the /traces handler
    (runtime server -> obs.TelemetryServer)."""
    p = argparse.ArgumentParser(
        description="dump recent request traces as Chrome-trace JSON"
    )
    p.add_argument(
        "--url", default="http://127.0.0.1:8002",
        help="telemetry endpoint of the serving process "
        "(serve --metrics-port)",
    )
    p.add_argument(
        "-n", "--count", type=int, default=0,
        help="most recent N traces (0 = everything buffered)",
    )
    p.add_argument(
        "-o", "--output", default="-",
        help="output file ('-' = stdout); load in Perfetto or "
        "chrome://tracing",
    )
    p.add_argument("--timeout", type=float, default=10.0)
    p.add_argument(
        "--ops", nargs="?", const="", default=None, metavar="TRACE",
        help="per-op device-time report instead of a raw trace dump: "
        "with a PATH, summarize that jax.profiler capture (a profile "
        "dir or .trace.json[.gz] file) offline; bare --ops takes a "
        "live capture through <url>/profile first",
    )
    p.add_argument(
        "--seconds", type=float, default=1.0,
        help="live capture window for bare --ops (the /profile knob)",
    )
    p.add_argument(
        "--top-k", type=int, default=20,
        help="op rows to keep in the --ops report",
    )
    args = p.parse_args(argv)

    import json
    import sys
    import urllib.request

    if args.ops is not None:
        from triton_client_tpu.obs import opstats

        if args.ops:
            summary = opstats.summarize_profile_dir(
                args.ops, top_k=args.top_k
            )
        else:
            url = (
                args.url.rstrip("/")
                + f"/profile?seconds={args.seconds}&top_k={args.top_k}"
            )
            with urllib.request.urlopen(url, timeout=args.timeout + args.seconds) as resp:
                doc = json.load(resp)
            if "op_summary" not in doc:
                raise SystemExit(
                    f"{url} returned no op summary "
                    f"({doc.get('op_summary_error', 'unknown failure')})"
                )
            summary = doc["op_summary"]
        total_us = summary.get("total_op_time_us", 0.0) or 0.0
        print(
            f"{summary.get('op_count', 0)} distinct ops, "
            f"{total_us / 1e3:.3f} ms total device op time"
        )
        for model, us in sorted(
            (summary.get("models") or {}).items(), key=lambda kv: -kv[1]
        ):
            print(f"  {model}: {us / 1e3:.3f} ms")
        unattr = summary.get("unattributed_us", 0.0)
        if unattr:
            print(f"  (unattributed: {unattr / 1e3:.3f} ms)")
        hdr = f"{'model':<16} {'kind':<14} {'occ':>5} {'ms':>10} {'share':>7}  op"
        print(hdr)
        print("-" * len(hdr))
        for row in summary.get("ops") or []:
            print(
                f"{(row.get('model') or '-'):<16} "
                f"{row.get('kind', '?'):<14} "
                f"{row.get('occurrences', 0):>5} "
                f"{row.get('time_us', 0.0) / 1e3:>10.3f} "
                f"{row.get('share', 0.0):>6.1%}  "
                f"{row.get('op', '?')}"
            )
        if args.output != "-":
            with open(args.output, "w") as f:
                json.dump(summary, f, indent=2)
            print(f"wrote op summary -> {args.output}", file=sys.stderr)
        return

    url = args.url.rstrip("/") + "/traces"
    if args.count:
        url += f"?n={args.count}"
    with urllib.request.urlopen(url, timeout=args.timeout) as resp:
        doc = json.load(resp)
    events = doc.get("traceEvents")
    if events is None:
        raise SystemExit(f"{url} returned no traceEvents (not a trace dump?)")
    body = json.dumps(doc)
    if args.output == "-":
        print(body)
    else:
        with open(args.output, "w") as f:
            f.write(body)
        n_req = sum(
            1 for e in events if e.get("ph") == "X" and e.get("name") == "request"
        )
        print(
            f"wrote {n_req} request traces ({len(events)} events) -> "
            f"{args.output}", file=sys.stderr,
        )


def trace_join(argv=None) -> None:
    """Merge per-process Chrome-trace exports onto one fleet timeline.

    Each process's chrome_trace export rebases its own earliest trace
    to t=0 on its own perf_counter clock, so client, router and replica
    dumps of the SAME request land at unrelated timestamps. This joins
    them: every input file becomes a distinct pid (Perfetto renders one
    process track per source), with its events shifted by a per-source
    clock offset — explicit (``--offset``), or estimated as half the
    best-of-N probe round-trip against the source's live telemetry
    port (``--probe``), the single-round-trip midpoint estimate NTP
    uses and graft_span_summary applies per response."""
    p = argparse.ArgumentParser(
        description="join client/router/replica Chrome-trace dumps "
        "onto one timeline"
    )
    p.add_argument(
        "inputs", nargs="+", metavar="[NAME=]FILE",
        help="Chrome-trace JSON files (trace-dump output); NAME labels "
        "the source's process track (default: file basename)",
    )
    p.add_argument(
        "--offset", action="append", default=[], metavar="NAME=US",
        help="shift NAME's events by this many microseconds "
        "(repeatable; positive = later on the joined timeline)",
    )
    p.add_argument(
        "--probe", action="append", default=[], metavar="NAME=URL",
        help="estimate NAME's offset as half the best-of-3 HTTP probe "
        "round-trip against its telemetry URL (repeatable)",
    )
    p.add_argument(
        "-o", "--output", default="-",
        help="output file ('-' = stdout); load in Perfetto",
    )
    p.add_argument("--timeout", type=float, default=5.0)
    args = p.parse_args(argv)

    import json
    import sys
    import time as _time
    import urllib.request

    def parse_kv(items, what):
        out = {}
        for item in items:
            name, sep, value = item.partition("=")
            if not sep:
                raise SystemExit(f"--{what} wants NAME=VALUE, got {item!r}")
            out[name] = value
        return out

    offsets = {
        name: float(us) for name, us in parse_kv(args.offset, "offset").items()
    }
    for name, url in parse_kv(args.probe, "probe").items():
        best = None
        for _ in range(3):
            t0 = _time.perf_counter()
            try:
                with urllib.request.urlopen(url, timeout=args.timeout):
                    pass
            except Exception as e:
                raise SystemExit(f"probe against {url} failed: {e}")
            rtt = _time.perf_counter() - t0
            best = rtt if best is None else min(best, rtt)
        offsets[name] = offsets.get(name, 0.0) + best / 2.0 * 1e6
        print(
            f"probe {name}: rtt {best * 1e3:.3f} ms -> offset "
            f"{best / 2.0 * 1e3:.3f} ms", file=sys.stderr,
        )

    events: list[dict] = []
    for i, item in enumerate(args.inputs):
        name, sep, path = item.partition("=")
        if not sep:
            name, path = "", item
        if not name:
            name = os.path.splitext(os.path.basename(path))[0]
        with open(path) as f:
            doc = json.load(f)
        src = doc.get("traceEvents")
        if src is None:
            raise SystemExit(f"{path}: no traceEvents (not a trace dump?)")
        pid = i + 1
        shift = offsets.get(name, 0.0)
        events.append(
            {
                "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                "args": {"name": name},
            }
        )
        n = 0
        for ev in src:
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                continue  # replaced by the source-labelled one above
            ev = dict(ev)
            ev["pid"] = pid
            if "ts" in ev:
                ev["ts"] = round(ev["ts"] + shift, 3)
            events.append(ev)
            n += 1
        print(
            f"{name}: {n} events, offset {shift / 1e3:+.3f} ms",
            file=sys.stderr,
        )

    events.sort(key=lambda e: (e.get("ts", -1.0), e.get("pid", 0)))
    body = json.dumps({"traceEvents": events, "displayTimeUnit": "ms"})
    if args.output == "-":
        print(body)
    else:
        with open(args.output, "w") as f:
            f.write(body)
        print(
            f"wrote {len(events)} joined events -> {args.output}",
            file=sys.stderr,
        )


def roofline(argv=None) -> None:
    """Per-model roofline report: measured flops/bytes (XLA cost model,
    recorded into spec.extra at first launch), arithmetic intensity vs
    the machine knee, the binding ceiling, and the attainable-fps
    ceiling next to the measured rate. Reads a live server's
    /snapshot."""
    p = argparse.ArgumentParser(
        description="per-model roofline classification "
        "(compute- vs bandwidth-bound, attainable-fps ceiling)"
    )
    p.add_argument(
        "source", nargs="?", default="http://127.0.0.1:8002",
        help="telemetry URL of a serving process (reads /snapshot)",
    )
    p.add_argument("--timeout", type=float, default=10.0)
    p.add_argument(
        "--json", action="store_true", help="machine-readable JSON output"
    )
    args = p.parse_args(argv)

    import json
    import urllib.request

    url = args.source.rstrip("/") + "/snapshot"
    with urllib.request.urlopen(url, timeout=args.timeout) as resp:
        snap = json.load(resp)
    device = snap.get("device")
    rows = []
    for m in snap.get("models") or []:
        roof = m.get("roofline")
        if not roof:
            continue
        rows.append(
            {
                "model": f"{m['model']}:{m['version']}",
                "precision": roof.get("precision", "f32"),
                "flops": roof.get("flops", 0.0),
                "bytes": roof.get("bytes", 0.0),
                "intensity": roof.get("intensity", 0.0),
                "bound": roof.get("bound", "unknown"),
                "attainable_fps": roof.get("attainable_fps", 0.0),
                "measured_fps": roof.get("measured_fps"),
                "attained_fraction": roof.get("attained_fraction"),
            }
        )
    if args.json:
        print(json.dumps({"device": device, "rows": rows}, indent=2))
        return
    if not rows:
        raise SystemExit(
            "no roofline rows: models record measured flops/bytes at "
            "their first launch (serve a request, then retry)"
        )
    if device:
        from triton_client_tpu.obs.roofline import DEVICE_PEAKS

        print(
            f"device: {device.get('platform')} ({device.get('kind')}) "
            f"x{device.get('count')}"
        )
        if device.get("kind") not in DEVICE_PEAKS:
            print(
                f"no peaks known for device_kind {device.get('kind')!r}: "
                "bound and ceiling are not computed (flop/B is the "
                "measured intensity only)"
            )
    hdr = (
        f"{'model':<40} {'prec':<6} {'GF/call':>9} {'MB/call':>9} "
        f"{'flop/B':>8} {'bound':<10} {'ceiling fps':>12} {'attained':>9}"
    )
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        attained = (
            f"{r['attained_fraction']:.1%}"
            if r.get("attained_fraction") is not None else "-"
        )
        print(
            f"{r['model']:<40} {r['precision']:<6} "
            f"{r['flops'] / 1e9:>9.2f} {r['bytes'] / 1e6:>9.2f} "
            f"{r['intensity']:>8.1f} {r['bound']:<10} "
            f"{r['attainable_fps']:>12.1f} {attained:>9}"
        )


def lint(argv=None) -> None:
    """tpulint CLI: run the TPL rule families over the package (or the
    given paths), apply the baseline, print text or JSON, and exit
    non-zero on NEW findings. The serving analogue of `ruff check` for
    hazards ruff cannot know about (donation, retraces, hot-path
    syncs)."""
    p = argparse.ArgumentParser(
        prog="tpulint",
        description="AST hazard analysis for the JAX serving stack "
        "(TPL1xx recompilation, TPL2xx donation, TPL3xx host-sync, "
        "TPL4xx locks, TPL5xx telemetry, TPL6xx concurrency, TPL7xx "
        "zero-copy, TPL8xx Pallas kernels; see docs/LINTING.md)",
    )
    p.add_argument(
        "paths", nargs="*",
        help="files/dirs to analyze (default: the triton_client_tpu "
        "package this CLI runs from)",
    )
    p.add_argument(
        "--json", action="store_true", help="machine-readable JSON output"
    )
    p.add_argument(
        "--baseline", default=None,
        help="baseline file of accepted findings (tpulint.baseline.json); "
        "only findings NOT in it fail the run",
    )
    p.add_argument(
        "--write-baseline", default=None, metavar="FILE",
        help="write every current finding to FILE as a baseline and "
        "exit 0; entries surviving from the previous baseline keep "
        "their justifications, stale entries are pruned, new entries "
        "start as TODO and must be edited",
    )
    p.add_argument(
        "--prune-stale", action="store_true",
        help="with --baseline: rewrite the baseline file with stale "
        "entries (fingerprints nothing matches anymore) removed, "
        "keeping every surviving entry and justification untouched",
    )
    p.add_argument(
        "--sarif", default=None, metavar="FILE",
        help="also write findings as SARIF 2.1.0 to FILE ('-' for "
        "stdout) for code-scanning UIs; fingerprints match the "
        "baseline's",
    )
    p.add_argument(
        "--jobs", type=int, default=1, metavar="N",
        help="load/parse files on N threads (CI passes this; default "
        "serial)",
    )
    p.add_argument(
        "--changed", action="store_true",
        help="treat the given paths as CHANGED FILES: analyze the "
        "whole package (interprocedural rules need it) but report "
        "only findings located in those files — the pre-commit fast "
        "path",
    )
    p.add_argument(
        "--rules", default=None,
        help="comma-separated code selection (full codes or family "
        "prefixes: 'TPL3,TPL401')",
    )
    p.add_argument(
        "--list-rules", action="store_true",
        help="print the rule catalogue and exit",
    )
    p.add_argument(
        "--no-stale-check", action="store_true",
        help="do not warn about baseline entries nothing matched",
    )
    p.add_argument(
        "--stats", action="store_true",
        help="print a per-rule findings/elapsed-ms table (stderr in "
        "text mode, summary.stats in --json) — keeps the ci.sh gate's "
        "cost visible as rule families grow",
    )
    args = p.parse_args(argv)

    import json as _json
    import sys

    from triton_client_tpu import analysis

    if args.list_rules:
        for code, cls in analysis.registry().items():
            print(f"{code}  {cls.name}")
            doc = " ".join((cls.doc or "").split())
            if doc:
                print(f"       {doc}")
        return

    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if args.changed:
        # fast path: the WHOLE package is analyzed (reachability, lock
        # and thread models are interprocedural — a changed callee can
        # create a finding in an unchanged caller's scope only via its
        # own file, but a changed file's findings need global context),
        # then the report is restricted to the files that changed
        if not args.paths:
            print("tpulint: --changed given but no files; nothing to do",
                  file=sys.stderr)
            return
        paths = [pkg_dir]
    else:
        paths = args.paths or [pkg_dir]
    codes = args.rules.split(",") if args.rules else None
    package = analysis.load_package(paths, jobs=max(1, args.jobs))
    rule_stats: dict = {}
    findings = analysis.run_rules(
        package, codes=codes, stats=rule_stats if args.stats else None
    )
    if args.changed:
        changed = {
            os.path.relpath(os.path.abspath(p)) for p in args.paths
        }
        # the TPL805 fused-route contract spans kernel modules, the
        # routing pipelines, ops/fused.py AND the parity test file —
        # its findings anchor in ops/fused.py, so a plain path filter
        # would hide them exactly when a contract participant changed.
        # Keep them whenever any changed file is a participant.
        contract_changed = any(
            os.path.basename(c).startswith("pallas_")
            or c.replace(os.sep, "/").endswith("ops/fused.py")
            or c.replace(os.sep, "/").endswith("tests/test_fused_parity.py")
            for c in changed
        )
        findings = [
            f for f in findings
            if f.path in changed
            or (contract_changed and f.code == "TPL805")
        ]

    if args.write_baseline:
        prior = None
        for prior_path in (args.write_baseline, args.baseline):
            if prior_path and os.path.exists(prior_path):
                prior = analysis.Baseline.load(prior_path)
                break
        bl = analysis.Baseline.from_findings(findings, prior=prior)
        bl.save(args.write_baseline)
        kept = sum(
            1 for e in bl.entries.values()
            if e.get("justification") not in ("", analysis.baseline.UNJUSTIFIED)
        ) if prior else 0
        todo = len(bl.entries) - kept
        print(
            f"wrote {len(bl.entries)} entr(ies) -> {args.write_baseline} "
            f"({kept} justification(s) preserved, {todo} TODO); edit the "
            "TODOs before committing",
            file=sys.stderr,
        )
        return

    suppressed: list = []
    problems: list[str] = list(package.errors)
    if args.baseline:
        bl = analysis.Baseline.load(args.baseline)
        if args.prune_stale and not args.changed:
            dropped = bl.prune(findings)
            bl.save(args.baseline)
            print(
                f"tpulint: pruned {len(dropped)} stale entr(ies) from "
                f"{args.baseline}",
                file=sys.stderr,
            )
        findings, suppressed = bl.split(findings)
        for fp in bl.unjustified():
            e = bl.entries[fp]
            problems.append(
                f"baseline entry {fp} ({e.get('code')} {e.get('path')}) "
                "has no justification"
            )
        # --changed reports a SUBSET of findings, so "nothing matches
        # this entry" would be meaningless noise there
        if not args.no_stale_check and not args.changed:
            for fp in bl.stale(findings + suppressed):
                e = bl.entries[fp]
                print(
                    f"tpulint: warning: stale baseline entry {fp} "
                    f"({e.get('code')} {e.get('path')}: nothing matches it)",
                    file=sys.stderr,
                )
    if args.sarif:
        body = analysis.render_sarif(findings, errors=problems)
        if args.sarif == "-":
            print(body)
        else:
            with open(args.sarif, "w", encoding="utf-8") as fh:
                fh.write(body + "\n")
            print(f"tpulint: SARIF -> {args.sarif}", file=sys.stderr)

    if args.stats and not args.json:
        # pre-baseline counts: the rule's raw cost, not its residual
        hdr = f"{'rule':<8} {'findings':>8} {'elapsed_ms':>11}"
        print(hdr, file=sys.stderr)
        print("-" * len(hdr), file=sys.stderr)
        for code in sorted(rule_stats):
            row = rule_stats[code]
            print(
                f"{code:<8} {row['findings']:>8} {row['elapsed_ms']:>11.1f}",
                file=sys.stderr,
            )
        total_ms = sum(r["elapsed_ms"] for r in rule_stats.values())
        print(
            f"{'total':<8} {sum(r['findings'] for r in rule_stats.values()):>8} "
            f"{total_ms:>11.1f}",
            file=sys.stderr,
        )

    if args.json:
        doc = _json.loads(
            analysis.render_json(
                findings, suppressed=len(suppressed), errors=problems
            )
        )
        if args.stats:
            doc["summary"]["stats"] = rule_stats
        print(_json.dumps(doc, indent=2, sort_keys=True))
    else:
        analysis.render_text(findings)
        for msg in problems:
            print(f"tpulint: error: {msg}", file=sys.stderr)
        tail = f", {len(suppressed)} baselined" if args.baseline else ""
        print(
            f"tpulint: {len(findings)} new finding(s){tail}",
            file=sys.stderr,
        )
    if findings or problems:
        raise SystemExit(1)


def repo_index(argv=None) -> None:
    """List a model repository: local directory (parsed, not built) or a
    live server's RepositoryIndex over gRPC."""
    p = argparse.ArgumentParser(
        description="list model-repository contents (local dir or grpc:<addr>)"
    )
    p.add_argument("target", help="repository root dir or grpc:<host:port>")
    args = p.parse_args(argv)

    if args.target.startswith("grpc:"):
        from triton_client_tpu.channel.grpc_channel import GRPCChannel

        channel = GRPCChannel(args.target[len("grpc:"):])
        try:
            for name, version, state in channel.repository_index():
                print(f"{name}:{version}  {state}")
        finally:
            channel.close()
        return

    import pathlib

    from triton_client_tpu.dataset_config import load_yaml
    from triton_client_tpu.runtime.disk_repository import (
        find_weights,
        version_dirs,
    )

    root = pathlib.Path(args.target)
    if not root.is_dir():
        raise SystemExit(f"{args.target!r} is not a directory or grpc: address")
    for model_dir in sorted(d for d in root.iterdir() if d.is_dir()):
        cfg = model_dir / "config.yaml"
        if not cfg.exists():
            continue
        doc = load_yaml(str(cfg))
        versions = version_dirs(model_dir)
        if not versions:
            print(f"{model_dir.name}:1  family={doc.get('family')}  (fresh-init)")
        for vdir in versions:
            try:
                artifact = find_weights(vdir).name
            except FileNotFoundError:
                artifact = "MISSING WEIGHTS"
            print(
                f"{model_dir.name}:{vdir.name}  family={doc.get('family')}  "
                f"{artifact}"
            )


def route(argv=None) -> None:
    """Probe a replica set the way the FrontDoorRouter sees it: one
    health pass over every endpoint (ServerLive / ServerReady /
    optional ModelReady), replica labels from ServerMetadata, and —
    with ``--watch`` — a live rotation view, so an operator can answer
    "which replicas would take traffic right now?" without standing up
    a router."""
    p = argparse.ArgumentParser(
        description="probe a replica set (health / readiness / labels)"
    )
    p.add_argument(
        "endpoints", nargs="+", help="replica endpoints (host:port ...)"
    )
    p.add_argument(
        "-m", "--model", action="append", default=[],
        help="also require ModelReady for this model (repeatable)",
    )
    p.add_argument(
        "--timeout", type=float, default=2.0,
        help="per-probe RPC deadline in seconds",
    )
    p.add_argument(
        "--watch", type=float, default=0.0,
        help="re-probe every N seconds until interrupted (0 = once)",
    )
    args = p.parse_args(argv)

    import time as _time

    from triton_client_tpu.channel.grpc_channel import GRPCChannel
    from triton_client_tpu.channel.kserve import pb

    channels = [
        GRPCChannel(ep, timeout_s=args.timeout, retries=0)
        for ep in args.endpoints
    ]

    def label_of(chan) -> str:
        try:
            meta = chan._call(
                chan._stub.ServerMetadata, pb.ServerMetadataRequest(),
                retryable=(), timeout_s=args.timeout,
            )
        except Exception:
            return "-"
        for ext in meta.extensions:
            if ext.startswith("replica_of:"):
                return ext.split(":", 1)[1]
        return "-"

    def pass_once() -> int:
        in_rotation = 0
        for ep, chan in zip(args.endpoints, channels):
            live = chan.server_live(timeout_s=args.timeout)
            ready = live and chan.server_ready(timeout_s=args.timeout)
            models_ok = ready and all(
                chan.model_ready(m, timeout_s=args.timeout)
                for m in args.model
            )
            ok = ready and models_ok
            in_rotation += 1 if ok else 0
            state = (
                "IN-ROTATION" if ok
                else "NOT-READY" if live
                else "DEAD"
            )
            detail = "" if models_ok or not ready else " (model not ready)"
            transport = getattr(chan, "transport", "grpc")
            print(
                f"{ep:<28} {state:<12} transport={transport:<8} "
                f"replica_of={label_of(chan)}{detail}",
                flush=True,
            )
        print(
            f"-- {in_rotation}/{len(args.endpoints)} in rotation",
            flush=True,
        )
        return in_rotation

    try:
        ok = pass_once()
        while args.watch > 0:
            _time.sleep(args.watch)
            print()
            ok = pass_once()
    except KeyboardInterrupt:
        pass
    finally:
        for chan in channels:
            try:
                chan.close()
            except Exception:
                pass
    # scripting-friendly: exit nonzero when NOTHING would take traffic
    if ok == 0:
        raise SystemExit(1)
