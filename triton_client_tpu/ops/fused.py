"""Fused-kernel routing: the ONE place that decides whether a pipeline
stage runs its hand-written Pallas fusion or the XLA reference path.

Mirrors the trace-time routing idiom of ``ops/nms._nms_mode``: shapes
and backends are static under jit, so the decision is baked into the
executable and can never flip mid-serve. Three layers of control, most
specific wins:

  * ``TPU_FUSED_KERNELS`` env — ``0``/``off`` disables every fusion;
    ``1``/``on``/``auto`` enables routing; a comma list
    (``voxelize_scatter,decode_nms``) enables ONLY the named stages.
    Read per trace, like ``TRITON_CLIENT_TPU_NMS``.
  * per-pipeline config knob (``Detect2DConfig.fused`` /
    ``Detect3DConfig.fused``: ``auto``/``on``/``off``) — the spec-extra
    opt-out: the resolved stage list is published as
    ``spec.extra["fused_stages"]`` so remote clients and bench rows can
    see exactly which fusions a served model runs.
  * backend — ``auto`` routes fused only on a real TPU backend, where
    the kernels are compiled by Mosaic and never interpreted; ``on``
    forces the fusion everywhere, which off-TPU means the SAME kernels
    under the Pallas interpreter (how the tier-1 parity matrix pins
    kernel numerics on CPU). Nothing falls back: a kernel Mosaic
    refuses fails the model's first compile, it is not swapped for
    the interpreter or the XLA reference.

Stage names are the shared vocabulary between pipelines, bench rows,
``obs/opstats`` per-stage attribution and ``perf/profile_fused``:

  * ``voxelize_scatter`` — ops/pallas_voxel.fused_mean_volume
  * ``decode_nms``       — ops/pallas_decode (2D decode+NMS+pack /
                           3D residual decode + suppress+pack)
"""

from __future__ import annotations

import os

FUSED_STAGES = ("voxelize_scatter", "decode_nms")

#: Reserved device-output key of a 2D detector whose ``decode_nms`` stage
#: is fused: the greedy steps each group of eight frames ran in the
#: kernel (ops/pallas_decode), a small int32 array that rides with the
#: rows. The staged channel sums it into ``stats()`` at readback and
#: drops it there. Never a wire tensor name.
NMS_STEPS_KEY = "__nms_steps__"

_OFF = ("0", "off", "false", "none", "")
_ON = ("1", "on", "true", "all", "auto")


def _env_stages() -> tuple[str, ...] | None:
    """Stage allowlist from ``TPU_FUSED_KERNELS``; ``None`` = everything
    off. Unknown stage names in a comma list are ignored (an operator
    typo should degrade to the reference path, not crash a server)."""
    raw = os.environ.get("TPU_FUSED_KERNELS", "auto").strip().lower()
    if raw in _OFF:
        return None
    if raw in _ON:
        return FUSED_STAGES
    names = tuple(s.strip() for s in raw.split(",") if s.strip())
    return tuple(s for s in names if s in FUSED_STAGES) or None


def fused_interpret() -> bool:
    """Whether a Pallas kernel built now runs under the interpreter:
    never on a TPU backend, always off it (where only ``fused="on"``,
    a forced NMS mode or a test reaches a kernel at all). The one
    backend probe every kernel call site shares."""
    import jax

    return jax.default_backend() != "tpu"


def fused_stage_enabled(stage: str, mode: str = "auto") -> bool:
    """Resolve one stage against the env knob, the pipeline ``mode``
    knob and the backend. ``mode='on'`` forces the fusion even off-TPU
    (interpret mode — tests); ``'off'`` is the spec-level opt-out;
    ``'auto'`` fuses only where it wins (TPU + env not disabled)."""
    if stage not in FUSED_STAGES:
        raise ValueError(f"unknown fused stage {stage!r} (of {FUSED_STAGES})")
    if mode == "off":
        return False
    allowed = _env_stages()
    if allowed is None or stage not in allowed:
        return False
    if mode == "on":
        return True
    if mode != "auto":
        raise ValueError(f"fused mode must be auto|on|off, got {mode!r}")
    return not fused_interpret()


def resolve_fused_stages(mode: str, candidates: tuple[str, ...]) -> tuple[str, ...]:
    """The pipeline-facing form: which of this pipeline's candidate
    stages actually route fused. Published as
    ``spec.extra['fused_stages']`` and keyed into bench rows."""
    return tuple(s for s in candidates if fused_stage_enabled(s, mode))
