"""Bench regression gate: a fresh result row vs a baseline file.

Compares the ``results`` rows of a freshly produced bench JSON (any of
the perf/ scripts' output, the shape bench.py writes) against a
baseline file of the same shape, matched by ``metric`` name, and exits
nonzero when either

  * throughput (``value``, frames/scans per sec per chip) regressed by
    more than the threshold (default 10%), or
  * ``mfu`` dropped by more than the threshold, or
  * ``host_gap_ratio`` (serving rows: served fps / device ceiling)
    dropped by more than the threshold, or
  * ``roofline_attained_ratio`` (measured fps / roofline attainable
    fps from XLA-measured flops+bytes) dropped by more than the
    threshold, or
  * a fused-kernel row's ``speedup`` (reference ms / fused ms from
    perf/profile_fused.py, whose per-stage rows load under synthetic
    ``fused_<stage>`` metric names) dropped by more than the threshold

— so a perf regression fails CI the same way a test failure does.
Two comparisons are reported but never gated: rows measured under the
Pallas INTERPRETER (``interpret: true`` — correctness-true,
performance-false) and rows whose ``fused_stages`` route changed
between fresh and baseline (a different code path, not a regression).
The repository commits no baseline: both files come from runs on the
same chip (parent and change in one call of the chip tool), so
``--baseline`` is required.

Improvements never fail; metrics present on only one side are reported
but not gated (a new bench row has no baseline yet, a retired one no
fresh measurement).

Usage:
    python perf/bench_diff.py FRESH.json --baseline PARENT.json
                              [--threshold 0.10]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_rows(path: str) -> dict[str, dict]:
    """``metric name -> row`` from a bench JSON (tolerates both the
    wrapped ``{"results": [...]}`` shape and a bare row list)."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and "results" in doc:
        rows = doc["results"]
    elif isinstance(doc, dict) and "stages" in doc:
        # perf/profile_fused.py --json output: per-stage fused rows
        rows = doc["stages"]
    else:
        rows = doc
    if not isinstance(rows, list):
        raise SystemExit(f"{path}: expected a results list")
    out = {}
    for row in rows:
        if not isinstance(row, dict):
            continue
        if "metric" in row:
            out[row["metric"]] = row
        elif "stage" in row:
            # profile_fused rows carry no metric name; synthesize one
            # so fused before/after numbers diff round-over-round
            out[f"fused_{row['stage']}"] = row
    return out


def diff_rows(
    fresh: dict[str, dict],
    baseline: dict[str, dict],
    threshold: float = 0.10,
) -> tuple[list[str], list[str]]:
    """Compare fresh rows against baseline rows.

    Returns ``(report_lines, failures)`` — ``failures`` nonempty means
    the gate should exit nonzero."""
    lines: list[str] = []
    failures: list[str] = []
    for metric in sorted(set(fresh) | set(baseline)):
        f_row, b_row = fresh.get(metric), baseline.get(metric)
        if f_row is None:
            lines.append(f"  {metric}: baseline only (no fresh row)")
            continue
        if b_row is None:
            lines.append(f"  {metric}: NEW (no baseline)")
            continue
        if f_row.get("interpret") or b_row.get("interpret"):
            lines.append(
                f"  {metric}: interpret-mode timing (not gated; "
                "performance numbers need a real chip)"
            )
            continue
        f_route = f_row.get("fused_stages")
        b_route = b_row.get("fused_stages")
        if f_route is not None and b_route is not None \
                and list(f_route) != list(b_route):
            lines.append(
                f"  {metric}: fused route changed "
                f"{b_route} -> {f_route} (not gated; different code "
                "path — reset the baseline row to re-arm the gate)"
            )
            continue
        for key, label in (
            ("value", "throughput"),
            ("mfu", "mfu"),
            # the serving rows' host-gap headline (served fps /
            # device ceiling): a transport-stack regression can hide
            # inside a faster device (value improves while the host
            # share of the ceiling collapses) — gate the ratio itself
            ("host_gap_ratio", "host_gap_ratio"),
            # fraction of the roofline ceiling actually attained
            # (measured fps / attainable fps from flops+bytes): a drop
            # means the kernel moved away from its own hardware bound
            # even if absolute throughput held up
            ("roofline_attained_ratio", "roofline_attained_ratio"),
            # fused rows (profile_fused): reference ms / fused ms —
            # the per-stage device-time reduction the fusion claims
            ("speedup", "fused_speedup"),
            # quality-plane row: p99(sampling off) / p99(sampling on).
            # 1.0 means the sidecar is free; a DROP means the shadow
            # sampler started taxing the primary path (the >10%
            # threshold is the sidecar-tax gate from ISSUE 17)
            ("quality_overhead_headroom", "quality_overhead_headroom"),
            # temporal-reuse row: streams-per-chip(reuse on) /
            # streams-per-chip(reuse off) off the per-stream
            # device-seconds ledger — a drop means coast/partial
            # scheduling stopped saving detector work (ISSUE 19)
            ("temporal_speedup", "temporal_speedup"),
        ):
            f_v, b_v = f_row.get(key), b_row.get(key)
            if f_v is None or b_v is None or not b_v:
                continue
            rel = (float(f_v) - float(b_v)) / float(b_v)
            tag = f"{label} {b_v:g} -> {f_v:g} ({rel:+.1%})"
            if rel < -threshold:
                failures.append(f"{metric}: {tag} exceeds -{threshold:.0%}")
                lines.append(f"  {metric}: REGRESSED {tag}")
            else:
                lines.append(f"  {metric}: ok {tag}")
    return lines, failures


def main(argv=None) -> None:
    p = argparse.ArgumentParser(
        description="fail on >threshold throughput/MFU regression vs "
        "the committed bench baseline"
    )
    p.add_argument("fresh", help="freshly produced bench results JSON")
    p.add_argument(
        "--baseline", required=True,
        help="baseline results JSON from the same chip (the parent "
        "commit's run)",
    )
    p.add_argument(
        "--threshold", type=float, default=0.10,
        help="relative regression that fails the gate (default 0.10)",
    )
    args = p.parse_args(argv)

    lines, failures = diff_rows(
        load_rows(args.fresh), load_rows(args.baseline), args.threshold
    )
    print(f"bench diff vs {args.baseline} (threshold {args.threshold:.0%}):")
    for line in lines:
        print(line)
    if failures:
        for f in failures:
            print(f"bench_diff: FAIL {f}", file=sys.stderr)
        raise SystemExit(1)
    print("bench_diff: no regressions")


if __name__ == "__main__":
    main()
