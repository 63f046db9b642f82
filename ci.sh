#!/usr/bin/env bash
# CI gate: hazard lint -> conventional lint -> types -> tier-1 tests.
#
# Order matters: tpulint and ruff are seconds, pytest is minutes — a
# new serving hazard (use-after-donation, hot-path host sync, unguarded
# shared state...) fails the build before any test runs. ruff/mypy are
# REQUIRED stages pinned by the `lint` extra — install with
# `pip install -e '.[lint]'`. A gate that silently skips its linters
# drifts until someone installs them and inherits the backlog, so a
# missing linter now FAILS the build instead of skipping. tpulint is
# stdlib-only and needs no install.
#
# Usage: ./ci.sh [--fast]     (--fast skips the tier-1 pytest stage)
set -euo pipefail
cd "$(dirname "$0")"

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

echo "== tpulint (serving-hazard analysis, gate) =="
# file-parallel parse (--jobs), and the findings double as a SARIF
# artifact (tpulint.sarif) for code-scanning dashboards — same
# fingerprints as the baseline, so alert dedup and suppression agree
python -m triton_client_tpu lint triton_client_tpu/ \
    --baseline tpulint.baseline.json \
    --jobs "$(nproc 2>/dev/null || echo 4)" \
    --sarif tpulint.sarif

echo "== ruff (conventional lint, required stage) =="
if command -v ruff >/dev/null 2>&1; then
    ruff check triton_client_tpu/
elif python -c "import ruff" >/dev/null 2>&1; then
    python -m ruff check triton_client_tpu/
else
    echo "FAIL: ruff is not installed (pinned by the 'lint' extra)." >&2
    echo "  pip install -e '.[lint]'   # config: pyproject [tool.ruff]" >&2
    exit 1
fi

echo "== mypy (loose types on analysis/obs/channel, required stage) =="
if command -v mypy >/dev/null 2>&1; then
    mypy
else
    echo "FAIL: mypy is not installed (pinned by the 'lint' extra)." >&2
    echo "  pip install -e '.[lint]'   # config: pyproject [tool.mypy]" >&2
    exit 1
fi

if [[ "${1:-}" == "--fast" ]]; then
    echo "== tier-1 pytest: SKIPPED (--fast) =="
    exit 0
fi

echo "== multi-device serving shard (8 virtual host devices) =="
# the mesh-sharded channel's parity/stacking contract on the virtual
# CPU mesh conftest.py provisions — runs first and alone so a sharding
# regression is named by its shard, not buried in the tier-1 wall
python -m pytest tests/test_sharded_channel.py -q \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly

echo "== precision-policy shard (accuracy budgets + wire dtypes) =="
# the serving-precision contract (runtime/precision.py): bf16/int8
# parity floors, quantized-tree sharding, wire narrowing, gauges —
# named by its shard for the same reason as the mesh shard above
python -m pytest tests/test_precision.py -q \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly

echo "== SLO observability shard (histograms, deadlines, open-loop) =="
# the tail-latency contract (obs/histogram.py, obs/slo.py, open-loop
# loadgen): quantile accuracy, CO-safe percentiles, deadline scoring,
# violator export — named by its shard so an SLO-ring regression is
# visible before the tier-1 wall. Includes the slow-marked open-loop
# window (a ~2 s live-server drive) tier-1 deselects.
python -m pytest tests/test_slo.py -q -m '' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly

echo "== continuous-batching shard (EDF, ragged packing, pad tax) =="
# the windowless-scheduler contract (runtime/continuous.py,
# parallel/ragged_kernels.py): EDF ordering, packed-ragged parity vs
# solo on both channel shapes, dense bitwise parity vs the window
# batcher — plus the slow-marked seeded open-loop drives that hold the
# served pad fraction under the 5% acceptance bar (tier-1 deselects
# them, this shard runs them)
python -m pytest tests/test_continuous_batching.py -q -m '' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly

echo "== lifecycle shard (HBM paging, tenants, fair share) =="
# the multi-tenant contract (runtime/lifecycle.py): warm/cold paging
# with bitwise promotion parity, LRU×priority×pin eviction, tenant
# quotas/caps, DRR fair share in the EDF key — plus the slow-marked
# 2x-overload fairness drive (a low-share flood cannot push the
# high-share tenant's accepted p99 past SLO) tier-1 deselects
python -m pytest tests/test_lifecycle.py -q -m '' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly

echo "== chaos shard (fault injection + overload control, seed 7) =="
# the robustness contract (runtime/admission.py, runtime/faults.py,
# breaker + drain): every FaultPlan point driven end-to-end under a
# FIXED seed so injected-failure schedules are identical across runs.
# Includes the slow-marked 2x-overload acceptance drive (sheds grow,
# deadline-expired launches stay 0) tier-1 deselects.
TPU_FAULT_SEED=7 python -m pytest tests/test_faults.py -q -m '' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly

echo "== router chaos shard (replicated front door, seed 7) =="
# the replication contract (runtime/router.py): health probing,
# outlier ejection, p2c, hedges, retry budgets, the replica_down
# fault point, deadline-capped channel retries, and the dispatcher
# stall watchdog — plus the slow-marked kill-one/drain-one open-loop
# acceptance drive (zero lost responses, goodput recovers to >=90%
# of steady state) tier-1 deselects.
TPU_FAULT_SEED=7 python -m pytest tests/test_router.py -q -m '' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly

echo "== transport shard (shm pool, UDS, stream groups, parity) =="
# the host-transport contract (channel/transport.py, the shm region
# pool, UDS listener, multi-frame stream groups, wire encodings):
# bitwise wire/shm/stream parity on 2D and 3D shapes, the 8-thread
# no-alias gate over the region pool, shm_detach restart recovery,
# and transport metrics — named by its shard so a zero-copy-path
# regression is visible before the tier-1 wall
python -m pytest tests/test_transport.py tests/test_shared_memory.py \
    -q -m '' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly

echo "== kernel-attribution shard (op stats, roofline, history) =="
# the device-attribution contract (obs/opstats.py, obs/roofline.py,
# obs/sampler.py, obs/history.py): trace-parse fixtures, roofline
# classification + measured-cost capture, sampler duty-cycle/guard
# contention, history ring + drain-persist — includes the slow-marked
# live /profile capture tier-1 deselects
python -m pytest tests/test_opstats.py tests/test_roofline.py \
    tests/test_history.py -q -m '' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly

echo "== streaming-session shard (sessions, tracking, affinity) =="
# the streaming-session contract (runtime/sessions.py, ops/tracking.py,
# the router's rendezvous affinity): slot pool reclaim ladder + the
# refcount bracket, device/NumPy association parity (bitwise) and the
# transfer-guard residency proof, sequence-param round trips, and the
# slow-marked drives tier-1 deselects — the multi-stream replay and the
# kill-one-replica affinity chaos drive (>=90% goodput, no id aliases)
python -m pytest tests/test_sessions.py tests/test_tracking.py \
    -q -m '' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly

echo "== fused-kernels shard (Pallas parity matrix + profiler smoke) =="
# the fused hot-path contract (ops/pallas_voxel, ops/pallas_decode,
# ops/fused routing): {yolov5n, centerpoint, second_iou} x {fused,
# reference} x batch {1,3,8} bitwise, incl. downstream track
# associations — interpret-mode Pallas on CPU, the same kernels a TPU
# runs compiled. The profile_fused smoke then proves the before/after
# harness and the opstats per-stage split end-to-end on tiny shapes
# (timings under interpret are correctness-true, performance-false).
# test_fused_decode_groups: the grouped 2D kernel (eight frames a grid
# step) against nms_padded over batches 1/3/8/11/16, and its step count.
python -m pytest tests/test_fused_parity.py tests/test_fused_decode_groups.py -q \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly
python perf/profile_fused.py --stages decode_nms_2d \
    --repeats 2 --cands 128

echo "== quality-plane shard (shadow scoring, canary gate, rollback) =="
# the continuous-quality contract (eval/shadow.py, eval/quality_plane.py
# and the server/router/collector wiring): deterministic trace-id
# sampling and canary slices, 2D/3D shadow-window scoring against the
# f32 reference, gate budgets off runtime/precision.py, the canary
# promote/rollback state machine (incl. the seeded quality_corrupt
# ejection), folded legacy eval Summaries, and the tpu_quality_*
# collector families + history-ring quality rows. The slow-marked live
# E2E canary drive is tier-1-deselected but runs here with -m ''.
python -m pytest tests/test_quality_plane.py -q -m '' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly

echo "== temporal-reuse shard (keyframe scheduling, coast, ROI tiles) =="
# the temporal compute-reuse contract (runtime/temporal.py,
# ops/tracking.py coast, drivers/multicam.py suppression): coast-step
# device/NumPy parity, tile extract/pack/merge round trips at
# full-frame coordinates, forced-K cadence, innovation-driven K
# adaptation, the seeded temporal_overskip fault caught by the
# ID-churn auto-disable, quality-plane gating, and cross-camera
# suppression — plus the slow-marked >=3x streams-per-chip acceptance
# drive on the per-stream device-seconds ledger tier-1 deselects.
python -m pytest tests/test_temporal_reuse.py tests/test_multicam.py \
    -q -m '' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly

echo "== chip_smoke rehearsal (serve path end to end, tiny, CPU) =="
# the chip smoke's own phases — kernel checks, threshold calibration,
# serve over loopback gRPC, fused-vs-XLA comparison, /snapshot asserts,
# SIGTERM drain — at tiny sizes with interpreted kernels, then the
# --chips 4 mesh path on four virtual devices. Control flow only: the
# real thing is `python chip_smoke.py` through the chip tool.
JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    python chip_smoke.py --rehearse --chips 4

echo "== tier-1 pytest =="
exec python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly
