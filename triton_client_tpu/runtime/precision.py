"""Per-model serving precision policies: f32 | bf16 | int8w | int8.

The small perception models this stack serves use a few percent of
the MXU — they are HBM-bandwidth-bound — so the
largest single-chip lever left (after dispatch overlap and data-parallel
sharding) is moving fewer bytes per call. TPUs run bf16 and int8 on the
MXU natively; production TPU serving stacks treat precision as a
*serving config*, not a model property. This module is that config:

  * ``f32``   — the legacy path, byte-for-byte unchanged.
  * ``bf16``  — params cast to bfloat16 (half the HBM reads per call),
    pipeline compute in bf16, float wire inputs staged as bf16 (half
    the H2D bytes; ml_dtypes provides the host-side numpy dtype).
  * ``int8w`` — weight-only quantization: conv/dense kernels stored as
    int8 with per-output-channel symmetric scales (max|w|/127), wire
    and compute stay f32. A quarter of the param HBM traffic;
    dequantization happens inside the jitted forward where it fuses.
  * ``int8``  — ``int8w`` plus activation quantization on the wire:
    float inputs are quantized host-side with per-tensor scales from a
    calibration pass over synthetic/eval frames and dequantized inside
    the launched program (``ingest``), quartering the H2D bytes.

The policy is applied ONCE at model-registration time:

  * :meth:`PrecisionPolicy.cast_params` tree-maps the variables tree
    (bf16 cast / int8 per-channel quantize into :class:`QuantizedParam`
    pytree nodes) BEFORE ``replicate_params`` runs, so the mesh-sharded
    channel ships the small tree to every device;
  * pipelines thread :meth:`cast_in` (ingress cast to the compute
    dtype) and :meth:`boundary` (the keep-list: box decode, NMS
    scores and voxelize coords stay f32 — see ``KEEP_F32_2D`` /
    ``KEEP_F32_3D``, recorded in each pipeline spec's
    ``extra["precision_keep_f32"]``);
  * the staged channels consult :meth:`wire_cast` when staging host
    arrays and wrap ``device_fn`` with :meth:`ingest` in their cached
    launchers, so the jit stages inputs in the wire dtype, runs the
    body in the policy dtype, and emits f32 outputs.

Accuracy contract (tests/test_precision.py): bf16 holds detection
outputs within tolerance of f32 and int8 holds synthetic-set mAP within
the policy's declared ``map_budget`` vs the f32 reference.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

# Host-side bfloat16 (ships with jax): staging a float32 frame as bf16
# halves the host->device copy without touching the round-4 "never
# widen on the host" rule — this is a DOWN-cast.
BF16 = np.dtype(ml_dtypes.bfloat16)

# The four policy names, in increasing compression order.
POLICIES = ("f32", "bf16", "int8w", "int8")

# Explicit keep-lists: precision-sensitive boundary ops that stay f32
# regardless of policy. Recorded in each pipeline spec's
# ``extra["precision_keep_f32"]`` so remote clients (and the docs) see
# the contract; enforced by the pipelines' ``boundary()`` casts.
KEEP_F32_2D = ("box_decode", "nms_scores", "box_rescale")
KEEP_F32_3D = ("voxelize_coords", "box_decode", "nms_scores")

# int8 symmetric range: +-127 keeps the scale invertible without the
# asymmetric -128 corner.
_QMAX = 127.0

# Declared accuracy budgets: max allowed synthetic-set mAP drop vs the
# f32 reference (tests/test_precision.py asserts 1 - budget as the
# floor; docs/OPERATIONS.md publishes the table). MAP_BUDGETS is the
# public spelling: the continuous quality plane's QualityGate (ISSUE
# 17, eval/quality_plane.py) gates live canary windows against these
# SAME numbers, so the offline parity suite and the runtime rollback
# trigger can never disagree about what "within budget" means.
_MAP_BUDGETS = {"f32": 0.0, "bf16": 0.05, "int8w": 0.10, "int8": 0.15}
MAP_BUDGETS = _MAP_BUDGETS


@jax.tree_util.register_pytree_node_class
class QuantizedParam:
    """One int8-quantized parameter leaf: ``q`` (int8) plus the
    per-output-channel f32 ``scale`` that dequantizes it.

    Registered as a jax pytree node so a quantized variables tree flows
    through ``tree_map``, ``device_put`` and ``replicate_params``
    unchanged — the mesh-sharded channel replicates the SMALL tree and
    the dequant multiply happens inside the trace (:func:`realize`),
    reading a quarter of the f32 bytes from HBM.
    """

    __slots__ = ("q", "scale")

    def __init__(self, q, scale) -> None:
        self.q = q
        self.scale = scale

    def dequant(self):
        return self.q.astype(jnp.float32) * self.scale

    @property
    def nbytes(self) -> int:
        return int(np.asarray(self.q).nbytes + np.asarray(self.scale).nbytes)

    @property
    def shape(self):
        return self.q.shape

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"QuantizedParam(shape={tuple(self.q.shape)})"


def quantize_channelwise(arr, axis: int = -1) -> QuantizedParam:
    """Symmetric per-channel int8 quantization: scale = max|x|/127 along
    every axis EXCEPT ``axis`` (the output-channel axis for conv/dense
    kernels, where per-channel ranges differ by orders of magnitude)."""
    x = np.asarray(arr, dtype=np.float32)
    reduce_axes = tuple(i for i in range(x.ndim) if i != (axis % x.ndim))
    amax = np.max(np.abs(x), axis=reduce_axes, keepdims=True)
    scale = np.where(amax > 0, amax / _QMAX, 1.0).astype(np.float32)
    q = np.clip(np.rint(x / scale), -_QMAX, _QMAX).astype(np.int8)
    return QuantizedParam(jnp.asarray(q), jnp.asarray(scale))


def fake_quant_channelwise(w, contract_axis: int = -2):
    """``w`` as symmetric per-output-channel int8 weights read it,
    computed on the device and held in ``w``'s own type: each value
    rounded to the 255-level grid of its channel's range (the maximum
    over ``contract_axis`` alone, so the stacked matrices of several
    layers or experts each keep their own scales). The numerics of
    int8 weights without a second storage format in the model: what a
    model served from a multi-gigabyte tree uses for ``int8`` (a leaf at
    a time, no float32 copy on the host)."""
    x = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=contract_axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / _QMAX, 1.0)
    return (jnp.clip(jnp.round(x / scale), -_QMAX, _QMAX) * scale).astype(w.dtype)


def _is_quant(x) -> bool:
    return isinstance(x, QuantizedParam)


def realize(tree):
    """Dequantize every :class:`QuantizedParam` leaf back to f32.

    Called INSIDE the jitted forward (pipelines' closure), so XLA reads
    the int8 bytes from HBM and fuses the scale multiply — the whole
    point of weight quantization on a bandwidth-bound model."""
    return jax.tree_util.tree_map(
        lambda x: x.dequant() if _is_quant(x) else x, tree, is_leaf=_is_quant
    )


def tree_bytes(tree) -> int:
    """Total parameter bytes of a (possibly quantized) variables tree —
    the number the collector's ``param_bytes`` gauge reports, so a
    quantized registration visibly shrinks HBM occupancy."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree, is_leaf=_is_quant):
        if _is_quant(leaf):
            total += leaf.nbytes
        else:
            total += int(np.asarray(leaf).nbytes)
    return total


def _is_float(arr) -> bool:
    return jnp.issubdtype(jnp.asarray(arr).dtype, jnp.floating)


def resolve_policy(precision, dtype):
    """Builder-shared policy resolution: parse the policy and pick the
    model compute dtype — the bf16 policy switches a default-f32 model
    to bf16 layers, while an explicit caller ``dtype`` wins (the legacy
    ``dtype=bf16`` bench path keeps its policy-less f32 wire). Returns
    ``(policy, model_dtype)``."""
    policy = PrecisionPolicy.parse(precision)
    if policy.name == "bf16" and dtype == jnp.float32:
        dtype = jnp.bfloat16
    return policy, dtype


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """One model's serving precision config (see module docstring).

    ``act_scales`` (int8 only): per-input-tensor symmetric scales from
    :meth:`calibrated`, stored as a sorted tuple of (name, scale) so the
    policy stays hashable. ``keep_f32_inputs``: wire inputs exempt from
    narrowing (the 3D pipelines keep ``points`` f32 — voxelize cell
    coords are precision-sensitive)."""

    name: str = "f32"
    act_scales: tuple[tuple[str, float], ...] = ()
    keep_f32_inputs: tuple[str, ...] = ()

    def __post_init__(self) -> None:
        if self.name not in POLICIES:
            raise ValueError(
                f"unknown precision policy {self.name!r} "
                f"(expected one of {'|'.join(POLICIES)})"
            )

    # -- parsing ------------------------------------------------------------

    @classmethod
    def parse(cls, value) -> "PrecisionPolicy":
        """str | PrecisionPolicy | None -> PrecisionPolicy (None = f32).
        Single source for the CLI ``--precision`` flag and repository
        ``config.yaml model.precision`` entries."""
        if value is None or value == "":
            return cls()
        if isinstance(value, cls):
            return value
        return cls(name=str(value))

    # -- derived properties --------------------------------------------------

    @property
    def compute_dtype(self):
        """Pipeline/model compute dtype: bf16 only for the bf16 policy —
        int8 policies dequantize to f32 compute."""
        return jnp.bfloat16 if self.name == "bf16" else jnp.float32

    @property
    def quantize_weights(self) -> bool:
        return self.name in ("int8w", "int8")

    @property
    def quantize_acts(self) -> bool:
        return self.name == "int8"

    @property
    def wire_ingest_needed(self) -> bool:
        """True when launched programs must dequantize wire inputs."""
        return self.name == "int8" and bool(self.act_scales)

    @property
    def map_budget(self) -> float:
        """Declared max synthetic-set mAP drop vs the f32 reference."""
        return _MAP_BUDGETS[self.name]

    def scale_for(self, name: str) -> float | None:
        for k, s in self.act_scales:
            if k == name:
                return s
        return None

    # -- registration-time param transform ------------------------------------

    def cast_params(self, tree):
        """Tree-map the variables tree into policy storage, ONCE at
        registration (before ``replicate_params`` for sharded serving):

          * ``bf16``: every float leaf -> bfloat16 (half the HBM);
          * ``int8w``/``int8``: float leaves with ndim >= 2 (conv/dense
            kernels) -> :class:`QuantizedParam`; 1-D leaves (biases,
            norm scales/stats) stay f32 — quantizing those costs
            accuracy for no measurable bandwidth;
          * ``f32``: identity.
        """
        if self.name == "f32":
            return tree
        if self.name == "bf16":
            return jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16) if _is_float(x) else x, tree
            )

        def quant(x):
            if _is_float(x) and getattr(x, "ndim", 0) >= 2:
                return quantize_channelwise(x)
            return x

        return jax.tree_util.tree_map(quant, tree)

    # -- pipeline hooks --------------------------------------------------------

    def cast_in(self, x):
        """Pipeline ingress cast (replaces the unconditional
        ``astype(float32)``): widen/narrow the staged wire input to the
        compute dtype inside the trace, where the cast fuses for free
        (the round-4 registration contract)."""
        return x.astype(self.compute_dtype)

    def boundary(self, tree):
        """The keep-list cast: model outputs re-enter f32 BEFORE the
        precision-sensitive boundary ops (box decode / NMS scoring /
        rescale — ``KEEP_F32_2D``/``KEEP_F32_3D``), so ranking ties and
        pixel coordinates never resolve in reduced precision."""
        if self.name == "f32":
            return tree
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32) if _is_float(x) else x, tree
        )

    # -- wire (channel) hooks ---------------------------------------------------

    def wire_cast(self, name: str, arr: np.ndarray) -> np.ndarray:
        """Host-side staging cast for one wire input. Extends the
        round-4 dtype policy (never widen on the host): bf16 DOWN-casts
        f32 floats to bfloat16 (half the H2D bytes), int8 quantizes
        calibrated float inputs to int8 (quarter), and everything
        else — integer frames, keep-list inputs, uncalibrated
        tensors — uploads as-is."""
        if self.name in ("f32", "int8w") or name in self.keep_f32_inputs:
            return arr
        if not np.issubdtype(arr.dtype, np.floating):
            return arr
        if self.name == "bf16":
            if arr.dtype.itemsize > BF16.itemsize:
                return arr.astype(BF16)
            return arr
        # int8: only inputs the calibration pass covered
        scale = self.scale_for(name)
        if scale is None or scale <= 0:
            return arr
        return np.clip(np.rint(arr / scale), -_QMAX, _QMAX).astype(np.int8)

    def ingest(self, inputs: dict) -> dict:
        """Device-side inverse of :meth:`wire_cast` for int8 wire
        inputs, applied INSIDE the launched jit (channel/staged.py):
        int8 tensors dequantize by their calibration scale; everything
        else passes through. Branches below are on static python/dtype
        facts, never tracer values."""
        if not self.wire_ingest_needed:
            return inputs
        out = {}
        for k in inputs:
            v = inputs[k]
            scale = self.scale_for(k)
            if scale is not None and v.dtype == jnp.int8:
                out[k] = v.astype(jnp.float32) * jnp.float32(scale)
            else:
                out[k] = v
        return out

    # -- calibration -------------------------------------------------------------

    def calibrated(self, samples: dict) -> "PrecisionPolicy":
        """Derive per-tensor activation scales from sample inputs
        (synthetic or eval frames), at registration time: scale =
        max|x|/127 over the whole calibration batch. No-op for
        non-quantizing policies; keep-list inputs are skipped."""
        if not self.quantize_acts:
            return self
        scales = dict(self.act_scales)
        for name, arr in samples.items():
            if name in self.keep_f32_inputs:
                continue
            a = np.asarray(arr)
            if not np.issubdtype(a.dtype, np.floating):
                # integer wire inputs (uint8 frames) already travel in
                # <= 1 byte; nothing to quantize
                continue
            amax = float(np.max(np.abs(a))) if a.size else 0.0
            scales[name] = (amax / _QMAX) if amax > 0 else 1.0
        return dataclasses.replace(
            self, act_scales=tuple(sorted(scales.items()))
        )

    # -- accounting ---------------------------------------------------------------

    def spec_extra(self, variables, keep_ops=KEEP_F32_2D) -> dict:
        """The spec ``extra`` entries every precision-aware builder
        records: policy name, keep-list, and post-cast param bytes (the
        collector's ``param_bytes`` gauge source)."""
        return {
            "precision": self.name,
            "precision_keep_f32": list(keep_ops),
            "param_bytes": tree_bytes(variables),
        }
