"""``serve`` entry point: disk model repository -> KServe v2 gRPC server.

The reference's serving process is ``tritonserver
--model-repository=/opt/model_repo`` inside the server containers
(docker/server/Dockerfile:131-135, README.md:66). This is that process
for the TPU runtime: scan the repository layout, jit every model onto
the mesh, serve the KServe v2 protocol so the reference's ROS tooling
(and our GRPCChannel) connects unchanged.
"""

from __future__ import annotations

import argparse
import logging

log = logging.getLogger(__name__)


def make_parser() -> argparse.ArgumentParser:
    """The ``serve`` argv contract — split from :func:`main` so an
    embedder (tests, ``chip_smoke.py``) parses exactly the flags the
    CLI takes and hands the namespace to :func:`build_server`."""
    p = argparse.ArgumentParser(description="TPU inference server")
    p.add_argument(
        "-r", "--model-repository", required=True,
        help="model repository root (examples/ layout)",
    )
    p.add_argument("-a", "--address", default="0.0.0.0:8001")
    p.add_argument(
        "--uds", default="auto",
        help="unix-domain-socket listener alongside TCP: 'auto' "
        "(default) picks a per-process socket under $TMPDIR, "
        "'unix:/path.sock' or '/path.sock' pins it, 'off' disables. "
        "Same-host clients dialing the unix: target skip the loopback "
        "TCP stack and auto-negotiate shared-memory tensor transport "
        "(docs/OPERATIONS.md 'Host transport')",
    )
    p.add_argument("--max-workers", type=int, default=8)
    p.add_argument(
        "--mesh", default="",
        help="device mesh, e.g. 'data=4' — serve data-parallel over the "
        "mesh (ShardedTPUChannel): params replicated once, request "
        "batches padded and sharded over the data axis; empty = "
        "single-executable TPUChannel",
    )
    p.add_argument(
        "--precision", default="", choices=["", "f32", "bf16", "int8w", "int8"],
        help="serving precision policy applied to EVERY repository entry "
        "(runtime/precision.py), overriding per-model config.yaml "
        "model.precision: bf16 = params+compute+wire in bfloat16, "
        "int8w = int8 weights, int8 = int8 weights+activations with "
        "calibrated scales; empty = per-model config (default f32)",
    )
    p.add_argument(
        "--batching", action="store_true",
        help="micro-batch concurrent requests before dispatch (Triton's "
        "dynamic batcher role): admits while device work is in flight — "
        "EDF-ordered ready queue, packed ragged execution for models "
        "registered with a ragged_fn, live occupancy-driven pad buckets",
    )
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument(
        "--pipeline-depth", type=int, default=2,
        help="formed batches executing concurrently: batch N+1's "
        "host->device transfer overlaps batch N's compute (Triton's "
        "per-instance CUDA-stream role); 1 = strictly serial",
    )
    p.add_argument(
        "--max-merge", type=int, default=None,
        help="frame cap for one device batch formed at dispatch time "
        "(default: --max-batch). Higher values fuse more queued "
        "requests into one device call, amortizing per-dispatch cost "
        "(Triton preferred_batch_size role)",
    )
    p.add_argument(
        "--metrics-port", default=8002,
        type=lambda v: v if v == "auto" else int(v),
        help="telemetry endpoint: Prometheus metrics on /metrics (Triton "
        ":8002 parity), Chrome-trace JSON on /traces, raw collector "
        "state on /snapshot (0 disables, 'auto' binds a free port and "
        "prints it)",
    )
    p.add_argument(
        "--op-sample-interval", type=float, default=0.0,
        help="continuous op-level sampling: take a short jax.profiler "
        "window every this many seconds and export top-K per-op device "
        "time at tpu_serving_op_device_seconds{model,op,kind} "
        "(obs/sampler.py; capture share of wall time is structurally "
        "capped at 1%%). 0 disables. Requires --metrics-port",
    )
    p.add_argument(
        "--op-sample-window", type=float, default=0.2,
        help="length of one sampler capture window in seconds (clamped "
        "so window/interval never exceeds the 1%% duty-cycle budget)",
    )
    p.add_argument(
        "--history-interval", type=float, default=10.0,
        help="metric-history ring spacing in seconds: per-model×tenant "
        "launch/device-time rates, utilization and MFU snapshots "
        "served at /history (0 disables)",
    )
    p.add_argument(
        "--history-capacity", type=int, default=360,
        help="metric-history ring depth (default 360 x 10s = 1h)",
    )
    p.add_argument(
        "--history-path", default="",
        help="persist the metric-history ring to this JSON file on "
        "drain and restore from it on startup (empty disables)",
    )
    p.add_argument(
        "--canary", action="append", default=[],
        help="arm a quality-gated canary: [primary:]variant=fraction "
        "(e.g. det_int8=0.05 routes 5%% of det traffic — inferred from "
        "the variant name — to det_int8). Promoted to full traffic "
        "after --quality-promote-after consecutive clean shadow-scored "
        "windows, auto-rolled-back to the f32 primary on the first "
        "budget violation. Repeatable; implies the quality plane",
    )
    p.add_argument(
        "--quality-sample", type=float, default=0.0,
        help="continuous quality plane sampling rate in [0,1]: this "
        "fraction of live traffic (deterministic trace-id hash) is "
        "mirrored to the f32 reference and scored online "
        "(tpu_quality_* metric families, /snapshot['quality']). "
        "0 disables unless --canary arms it (then 0.25 is used)",
    )
    p.add_argument(
        "--quality-window", type=int, default=32,
        help="scored frames per quality window: gate verdicts, canary "
        "promotion counting, and the tpu_quality_* gauges all advance "
        "once per window",
    )
    p.add_argument(
        "--quality-promote-after", type=int, default=3,
        help="consecutive clean windows before a canary variant is "
        "promoted to full traffic",
    )
    p.add_argument(
        "--quality-pin-fused-off", action="store_true",
        help="on quality rollback, also export TPU_FUSED_KERNELS=0 so "
        "freshly compiled models take the reference (unfused) path",
    )
    p.add_argument(
        "--trace-capacity", type=int, default=256,
        help="recent request traces kept for /traces export "
        "(`trace-dump`); 0 disables request-scoped spans, and with them "
        "the one-element marker program a launch that times `h2d`",
    )
    p.add_argument(
        "--slo-ms", type=float, default=0.0,
        help="per-request latency SLO: requests are deadline-stamped at "
        "admission and scored met/missed per model+priority "
        "(tpu_serving_slo_requests_total); violating traces export at "
        "/traces?slo_violations=1. 0 disables scoring (latency "
        "histograms still export). Requires --metrics-port.",
    )
    p.add_argument(
        "--slo-tail-capacity", type=int, default=64,
        help="bounded ring of SLO-violating / p99+ exemplar traces",
    )
    p.add_argument(
        "--admission", type=int, default=0,
        help="per-model admitted-but-unfinished request cap: beyond it "
        "(or when the estimated queue wait already exceeds a request's "
        "deadline budget) new requests are rejected with "
        "RESOURCE_EXHAUSTED before parse. Enabling admission also arms "
        "deadline shedding in the batcher and staged channels (see "
        "--shed-expired). 0 = no admission control",
    )
    p.add_argument(
        "--admission-concurrency", type=int, default=4,
        help="assumed per-model service concurrency for the "
        "estimated-wait admission math (batcher width x pipeline "
        "depth, roughly)",
    )
    p.add_argument(
        "--shed-expired", action="store_true",
        help="fail requests whose deadline already expired at "
        "batcher-merge and pre-launch with DEADLINE_EXCEEDED instead "
        "of executing them (deadline_expired_launches stays 0 while "
        "tpu_serving_shed_total grows); implied by --admission > 0",
    )
    p.add_argument(
        "--breaker-threshold", type=int, default=5,
        help="consecutive launch/readback failures that open a "
        "model's circuit breaker (fail-fast UNAVAILABLE, launch cache "
        "invalidated; a timed probe half-opens it). 0 disables",
    )
    p.add_argument(
        "--breaker-reset-s", type=float, default=10.0,
        help="seconds an open circuit waits before admitting one "
        "half-open probe request",
    )
    p.add_argument(
        "--drain-timeout", type=float, default=10.0,
        help="graceful-shutdown budget (SIGTERM): health flips "
        "not-ready, new requests get UNAVAILABLE, in-flight work "
        "completes up to this many seconds before teardown",
    )
    p.add_argument(
        "--fault-plan", default="",
        help="JSON fault-injection plan file (runtime/faults.py) "
        "installed process-wide — CHAOS TESTING ONLY: injects "
        "launch/readback/codec failures and latency on a seeded, "
        "deterministic schedule",
    )
    p.add_argument(
        "--hbm-budget", type=float, default=0.0,
        help="HBM paging budget in MB for model params "
        "(runtime/lifecycle.py): models start COLD, page in on first "
        "request, and evict LRU-within-priority under pressure — "
        "register more models than fit at once. 0 = every model stays "
        "resident (legacy behavior)",
    )
    p.add_argument(
        "--tenants", default="",
        help="tenants.yaml path mapping models to tenants with HBM "
        "quotas, fair-share weights, and in-flight caps (see "
        "docs/OPERATIONS.md 'Multi-tenant serving')",
    )
    p.add_argument(
        "--max-sessions", type=int, default=64,
        help="streaming-session slot pool size (runtime/sessions.py): "
        "requests carrying a sequence_id parameter get device-resident "
        "per-stream tracker state in one of this many slots; ended and "
        "TTL-expired slots are reclaimed, a full unreclaimable pool "
        "sheds with RESOURCE_EXHAUSTED. 0 disables sessions (sequence "
        "params pass through untracked)",
    )
    p.add_argument(
        "--session-ttl-s", type=float, default=60.0,
        help="idle seconds before a streaming session's slot is "
        "reclaimable (streams that vanish without sequence_end)",
    )
    p.add_argument(
        "--session-id-namespace", type=int, default=0,
        help="track-id namespace (0-15) stamped into this replica's "
        "track ids — give each replica of a fleet a distinct value so "
        "ids stay globally unique across session re-homing",
    )
    p.add_argument(
        "--temporal-reuse", default="off",
        choices=("auto", "on", "off"),
        help="temporal compute reuse for streaming sessions "
        "(runtime/temporal.py): full detection every K frames with "
        "tracker-coast between, ROI-tile partial recompute on "
        "tile-capable models. 'auto' adapts K per stream from the "
        "Kalman innovation; 'on' runs a fixed K=--temporal-k-max; "
        "'off' (default) disables the plane. Per-model "
        "spec.extra['temporal_reuse'] overrides. Quality-gated: the "
        "plane auto-disables per stream on ID churn, and the quality "
        "plane's window violations disable it per model",
    )
    p.add_argument(
        "--temporal-k-max", type=int, default=8,
        help="keyframe-interval ceiling: at most K-1 consecutive "
        "coast/partial frames between full detections",
    )
    p.add_argument(
        "--temporal-tile", type=int, default=8,
        help="ROI recompute tile edge (pixels) for tile-capable models",
    )
    p.add_argument(
        "--temporal-forced-k", type=int, default=0,
        help="pin K to this value, no adaptation (cadence tests and "
        "over-aggressive-K drives; 0 = adaptive)",
    )
    p.add_argument(
        "--replica-of", default="",
        help="replica-set label: this server is one replica of the named "
        "fleet. Advertised via ServerMetadata extensions (the `route` "
        "tool reads it back) and keys the replica_down fault point so a "
        "chaos plan can kill one labeled replica",
    )
    p.add_argument(
        "--warmup", action="store_true",
        help="compile every registered model before accepting requests",
    )
    p.add_argument("-v", "--verbose", action="store_true")
    return p


def main(argv=None) -> None:
    args = make_parser().parse_args(argv)
    logging.basicConfig(level=logging.DEBUG if args.verbose else logging.INFO)
    server = build_server(args)
    server.start()
    # flush=True: supervisors/drives parse this line through a pipe,
    # where block buffering would hold it until exit.
    print(f"KServe v2 gRPC server listening on port {server.port}", flush=True)
    if getattr(server, "uds_address", None):
        print(f"unix socket: {server.uds_address}", flush=True)
    if server.metrics_enabled:
        print(
            f"telemetry on :{server.metrics_port} "
            "(/metrics /traces /snapshot /profile /history)", flush=True,
        )

    drain_on_sigterm(server, args.drain_timeout)
    try:
        server.wait()
    except KeyboardInterrupt:
        server.stop()


def drain_on_sigterm(server, drain_timeout: float) -> None:
    """Install ``serve``'s SIGTERM behaviour on the calling (main)
    thread: drain instead of dropping in-flight work on the floor."""
    import signal

    def _sigterm(signum, frame):
        # orchestrator shutdown. The handler interrupts wait() on the
        # main thread; drain() flips not-ready, waits out the building,
        # and stops the transport — wait() then returns and main exits.
        print(
            f"SIGTERM: draining (timeout {drain_timeout:.1f}s)",
            flush=True,
        )
        drained = server.drain(timeout_s=drain_timeout)
        print(
            "drain complete" if drained
            else "drain timeout: stragglers cancelled",
            flush=True,
        )

    signal.signal(signal.SIGTERM, _sigterm)


def build_server(args):
    """Repository scan + channel stack + InferenceServer (not started)
    from parsed ``main`` args — split out so tests and embedders can
    stand the server up on a loopback port without blocking in wait()."""
    from triton_client_tpu.channel.sharded_channel import ShardedTPUChannel
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.cli.common import parse_mesh
    from triton_client_tpu.obs.roofline import device_info
    from triton_client_tpu.runtime.disk_repository import scan_disk
    from triton_client_tpu.runtime.server import InferenceServer
    from triton_client_tpu.utils.compilation_cache import (
        enable_persistent_cache,
    )

    cache_dir = enable_persistent_cache()  # before the first compile
    device = device_info()
    print(
        f"device: {device['platform']} ({device['kind']}) x{device['count']}; "
        f"compile cache: {cache_dir or 'off'}",
        flush=True,
    )
    repo = scan_disk(
        args.model_repository,
        precision=getattr(args, "precision", "") or None,
    )
    for name, version in repo.list_models():
        model = repo.get(name, version)
        policy = model.spec.extra.get("precision", "f32")
        print(
            f"loaded {name}:{version} ({model.spec.platform}, "
            f"precision={policy})"
        )
        if args.warmup and model.warmup is not None:
            model.warmup()

    if getattr(args, "fault_plan", ""):
        # CHAOS TESTING ONLY: a seeded, deterministic fault timeline
        # installed process-wide before the channel stack is built
        from triton_client_tpu.runtime.faults import (
            FaultPlan,
            install_fault_plan,
        )

        with open(args.fault_plan) as fh:
            plan = FaultPlan.from_json(fh.read())
        install_fault_plan(plan)
        print(
            f"FAULT PLAN ACTIVE (seed {plan.seed}, "
            f"{len(plan.rules)} rule(s)) — chaos testing only",
            flush=True,
        )

    # admission implies deadline shedding: an overload plane that
    # rejects at the door but still executes expired work would shed
    # the wrong requests
    shed = bool(getattr(args, "shed_expired", False)) or (
        getattr(args, "admission", 0) > 0
    )
    chan_kw = dict(
        shed_expired=shed,
        breaker_threshold=getattr(args, "breaker_threshold", 5),
        breaker_reset_s=getattr(args, "breaker_reset_s", 10.0),
    )
    if args.mesh:
        # explicit --mesh: serve the whole mesh data-parallel — params
        # replicated, request batches sharded over the data axis
        channel = ShardedTPUChannel(
            repo, mesh_config=parse_mesh(args.mesh), **chan_kw
        )
        print(
            f"mesh serving: {channel.stats()['mesh_devices']} devices, "
            f"data axis {channel.batch_multiple} "
            f"(batches shard over 'data'; params replicated)", flush=True,
        )
    else:
        # no --mesh: ONE device. TPUChannel's default all-devices mesh
        # would shard any input whose leading dim divides the device
        # count — a 3D model's points axis included — which nobody
        # asked for; spreading over chips is what --mesh says.
        import jax

        first = jax.devices()[:1]
        channel = TPUChannel(repo, devices=first, **chan_kw)
        print(
            f"single-device serving on {first[0]} "
            f"({device['count']} visible; --mesh data=N shards batches "
            "over N)", flush=True,
        )
    base_channel = channel

    # multi-tenant model lifecycle: HBM-budgeted paging + tenant policy
    tenants = None
    tenants_path = getattr(args, "tenants", "") or ""
    if tenants_path:
        from triton_client_tpu.runtime.lifecycle import load_tenants

        tenants = load_tenants(tenants_path)
    lifecycle = None
    budget_mb = float(getattr(args, "hbm_budget", 0.0) or 0.0)
    if budget_mb > 0 or tenants is not None:
        from triton_client_tpu.runtime.lifecycle import ModelLifecycleManager

        lifecycle = ModelLifecycleManager(
            repo,
            budget_bytes=int(budget_mb * (1 << 20)),
            tenants=tenants,
        )
        base_channel.attach_lifecycle(lifecycle)
        print(
            f"model lifecycle: hbm_budget="
            f"{f'{budget_mb:g}MB' if budget_mb > 0 else 'unlimited'} "
            f"tenants={len(tenants.tenants()) if tenants else 0} "
            "(models page in on demand, evict LRU-within-priority)",
            flush=True,
        )
    # streaming sessions: device-resident per-stream tracker state keyed
    # by the KServe sequence_id parameter (runtime/sessions.py)
    max_sessions = int(getattr(args, "max_sessions", 64) or 0)
    sessions = None
    if max_sessions > 0 and hasattr(base_channel, "attach_sessions"):
        from triton_client_tpu.runtime.sessions import SessionManager

        sessions = SessionManager(
            max_sessions=max_sessions,
            ttl_s=float(getattr(args, "session_ttl_s", 60.0)),
            id_namespace=int(getattr(args, "session_id_namespace", 0)),
        )
        base_channel.attach_sessions(sessions)
        print(
            f"streaming sessions: max_sessions={max_sessions} "
            f"ttl={float(getattr(args, 'session_ttl_s', 60.0)):g}s "
            f"id_namespace={int(getattr(args, 'session_id_namespace', 0))} "
            "(device-resident tracking keyed by sequence_id)",
            flush=True,
        )
    if args.batching:
        from triton_client_tpu.runtime.continuous import (
            ContinuousBatchingChannel,
        )

        # getattr: embedders build the args Namespace by hand
        # (tests/test_serve_cli.py) and may predate these knobs
        channel = ContinuousBatchingChannel(
            channel,
            max_batch=args.max_batch,
            pipeline_depth=args.pipeline_depth,
            max_merge=getattr(args, "max_merge", None),
            shed_expired=shed,
        )
        if tenants is not None:
            # deficit-round-robin fair share folded into the EDF ready
            # ordering, weighted by each tenant's share
            channel.attach_tenants(tenants)
        print(
            f"micro-batching[continuous]: max_batch={args.max_batch} "
            f"windowless pipeline_depth={args.pipeline_depth} "
            # default merge cap scales with the inner channel's data
            # axis: max_batch frames per device
            f"max_merge={getattr(args, 'max_merge', None) or args.max_batch * getattr(channel.inner, 'batch_multiple', 1)}",
            flush=True,
        )
    # continuous quality plane: shadow-scored online accuracy + canary
    # routing. Armed by --quality-sample > 0 or any --canary spec; the
    # mirror dispatches through the server's own channel stack (wired
    # inside InferenceServer), so shadow work queues behind live work.
    quality = None
    canary_specs = list(getattr(args, "canary", []) or [])
    sample_rate = float(getattr(args, "quality_sample", 0.0) or 0.0)
    if canary_specs and sample_rate <= 0.0:
        # a canary without samples would never score a window — arm a
        # rate high enough that promotion happens in human time
        sample_rate = 0.25
    if sample_rate > 0.0:
        from triton_client_tpu.eval.quality_plane import (
            QualityPlane,
            infer_primary,
            parse_canary_spec,
            precision_of_name,
        )

        def _precision_of(variant):
            # the repo's own precision tag wins over name sniffing
            try:
                return repo.get(variant, "").spec.extra.get(
                    "precision"
                ) or precision_of_name(variant)
            except Exception:
                return precision_of_name(variant)

        quality = QualityPlane(
            sample_rate=sample_rate,
            window_frames=getattr(args, "quality_window", 32),
            promote_after=getattr(args, "quality_promote_after", 3),
            precision_of=_precision_of,
            pin_fused_off=bool(
                getattr(args, "quality_pin_fused_off", False)
            ),
        )
        names = [name for name, _ in repo.list_models()]
        for spec in canary_specs:
            primary, variant, fraction = parse_canary_spec(spec)
            if primary is None:
                primary = infer_primary(variant, names)
            if primary is None:
                raise SystemExit(
                    f"--canary {spec}: cannot infer the primary model "
                    f"from {variant!r}; use the primary:variant=fraction "
                    "form"
                )
            quality.set_canary(primary, variant, fraction)
            print(
                f"canary armed: {primary} -> {variant} at "
                f"{fraction * 100:g}% of traffic "
                f"(promote after {getattr(args, 'quality_promote_after', 3)}"
                " clean windows, auto-rollback on budget violation)",
                flush=True,
            )
        print(
            f"quality plane: sample_rate={sample_rate:g} "
            f"window_frames={getattr(args, 'quality_window', 32)} "
            "(shadow-scored online mAP/velocity/ID-switch vs the f32 "
            "reference; tpu_quality_* families)",
            flush=True,
        )
    # temporal compute reuse: per-stream keyframe scheduling + ROI
    # partial recompute, riding the session plane (ISSUE 19). The plane
    # dispatches tile sub-requests at the TOP of the channel stack so
    # the continuous batcher can pack them across streams.
    temporal = None
    t_mode = getattr(args, "temporal_reuse", "off") or "off"
    if t_mode != "off" and sessions is not None:
        from triton_client_tpu.runtime.temporal import (
            TemporalReuseConfig,
            TemporalReusePlane,
        )

        def _extra_of(name):
            try:
                return repo.get(name, "").spec.extra
            except Exception:
                return None

        t_cfg = TemporalReuseConfig(
            mode=t_mode,
            k_max=max(1, int(getattr(args, "temporal_k_max", 8))),
            tile=max(1, int(getattr(args, "temporal_tile", 8))),
            forced_k=max(0, int(getattr(args, "temporal_forced_k", 0))),
        )
        temporal = TemporalReusePlane(
            sessions, config=t_cfg, channel=channel,
            spec_extra_fn=_extra_of,
        )
        print(
            f"temporal reuse: mode={t_cfg.mode} "
            f"k=[{t_cfg.k_min},{t_cfg.k_max}] tile={t_cfg.tile} "
            + (f"forced_k={t_cfg.forced_k} " if t_cfg.forced_k else "")
            + "(keyframe scheduling + ROI partial recompute; coast "
            "frames skip the detector, charged per-stream in the "
            "device-time ledger)",
            flush=True,
        )
    elif t_mode != "off":
        print(
            "temporal reuse requested but sessions are disabled "
            "(--max-sessions 0); ignoring --temporal-reuse",
            flush=True,
        )
    uds = getattr(args, "uds", "auto") or "off"
    return InferenceServer(
        repo,
        channel,
        address=args.address,
        uds_address=None if uds == "off" else uds,
        max_workers=args.max_workers,
        metrics_port=args.metrics_port,
        trace_capacity=getattr(args, "trace_capacity", 256),
        slo_ms=getattr(args, "slo_ms", 0.0),
        slo_tail_capacity=getattr(args, "slo_tail_capacity", 64),
        admission_max_queue=getattr(args, "admission", 0),
        admission_concurrency=getattr(args, "admission_concurrency", 4),
        lifecycle=lifecycle,
        tenants=tenants,
        replica_of=getattr(args, "replica_of", "") or None,
        op_sample_interval_s=getattr(args, "op_sample_interval", 0.0),
        op_sample_window_s=getattr(args, "op_sample_window", 0.2),
        history_interval_s=getattr(args, "history_interval", 10.0),
        history_capacity=getattr(args, "history_capacity", 360),
        history_path=getattr(args, "history_path", "") or None,
        quality=quality,
        temporal=temporal,
    )


if __name__ == "__main__":
    main()
