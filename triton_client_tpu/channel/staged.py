"""Staged dispatch protocol: the stage/launch/resolve engine.

PR 1 split the in-process serving hot path into three overlapping
phases (stage the H2D copy, launch the jitted compute, resolve the
readback lazily) inside ``TPUChannel``. The mesh-sharded channel
(``channel/sharded_channel.py``) needs the SAME engine — staging slots,
trace spans, donation-aware launch cache, deferred error surfacing —
over a different placement policy (pad + shard the batch over the
``data`` axis instead of device_put per array). This module is that
engine factored out once, so the protocol cannot drift between the
single-device and mesh paths:

  * **stage**   — validate, acquire a staging slot, then hand the
    request to :meth:`StagedChannel._place_inputs` (the subclass
    placement policy). Slot admission is per CHANNEL — i.e. per mesh,
    not per device: at ``pipeline_depth`` (default 2) batch N+1's
    host->device copy runs while batch N executes across the whole
    mesh; ``pipeline_depth=1`` is the strictly serial legacy path.
  * **launch**  — enqueue the jitted compute through the launcher the
    subclass builds in :meth:`StagedChannel._make_launcher` (cached per
    model identity; donation split handled here). Outputs stay
    device-resident.
  * **resolve** — lazy. ``launch`` returns an ``InferFuture``; the
    device->host copy happens in :meth:`StagedChannel._host_outputs`
    only when the driver resolves it, and resolution retires the
    staging slot.

``do_inference`` is stage→launch→result; ``do_inference_async`` defers
the readback (and any dispatch-time error) to ``result()``.
"""

from __future__ import annotations

import collections
import logging
import threading
import time

import jax
import numpy as np

from triton_client_tpu.channel.base import (
    BaseChannel,
    InferFuture,
    InferRequest,
    InferResponse,
)
from triton_client_tpu.config import ModelSpec
from triton_client_tpu.obs.roofline import name_launcher
from triton_client_tpu.ops.fused import NMS_STEPS_KEY
from triton_client_tpu.parallel.mesh import MeshConfig, make_mesh
from triton_client_tpu.runtime import faults
from triton_client_tpu.runtime.admission import (
    CircuitBreaker,
    CircuitOpenError,
    DeadlineExpiredError,
)
from triton_client_tpu.parallel.ragged_kernels import (
    RaggedLayout,
    ShardedRaggedLayout,
)
from triton_client_tpu.runtime.repository import ModelRepository

log = logging.getLogger(__name__)

#: Reserved device-input key carrying the packed batch's row->segment
#: table (parallel/ragged_kernels.py). Never a wire tensor name.
SEGMENT_IDS_KEY = "__segment_ids__"


def _batch_rows(device_inputs: dict) -> int:
    """Frames in one dense launch: the largest leading dim among the
    staged arrays (pure shape metadata — no host sync)."""
    rows = 1
    for v in device_inputs.values():
        if getattr(v, "ndim", 0) >= 1:
            rows = max(rows, int(v.shape[0]))
    return rows


def cast_wire_input(model, name: str, arr: np.ndarray) -> np.ndarray:
    """The round-4 host-side dtype policy, shared by every placement
    policy so single-device and sharded channels cannot drift: a stray
    WIDER dtype (float64/int64) casts down to the wire contract so it
    can't trigger one retrace per dtype, but a NARROWER input uploads
    as-is — casting uint8 camera frames up to FP32 on the host is 4x
    the host->device bytes, and every in-tree pipeline widens on device
    where the cast fuses for free (the registration contract in
    runtime/repository.py).

    Round 10 extends the same never-widen rule to precision policies
    (runtime/precision.py): a model registered at bf16/int8 narrows its
    float wire inputs FURTHER here (f32 frames stage as bf16 words or
    calibrated int8 codes — half/quarter the H2D bytes), with keep-list
    inputs and integer frames untouched."""
    try:
        want = model.spec.input_by_name(name).np_dtype()
        if arr.dtype != want and np.dtype(want).itemsize <= arr.dtype.itemsize:
            arr = arr.astype(want)
    except (KeyError, ValueError, TypeError):
        pass  # undeclared/BF16 inputs pass through as-is
    policy = getattr(model, "precision", None)
    if policy is not None:
        arr = policy.wire_cast(name, arr)
    return arr


_LANES = 128  # elements of a tile's minor dimension, whatever the type


def transfer_view(arr: np.ndarray) -> np.ndarray:
    """The form in which a host array crosses to the device: a FREE view
    of the same bytes whose two minor dimensions are whole tiles, or the
    array itself.

    A TPU array is tiled over its two minor dimensions, 128 lanes by 8
    sublanes of 32 bits; one- and two-byte elements are PACKED four or
    two rows to a sublane word (32 rows of ``uint8``, 16 of
    ``bfloat16``). A frame batch ``[B, H, W, 3]`` cannot be kept dense
    that way, so the device holds it PLANAR (``major_to_minor=(0, 3, 1,
    2)``), and for packed elements one thread of the transfer
    de-interleaves and packs every launch's bytes on the host: ``uint8``
    4.0-4.5 GB/s where the same bytes as ``[B, H*W*3/128, 128]`` move at
    5.5-5.7, ``bfloat16`` 4.5 against 5.3 (``perf/profile_h2d.py``;
    PERF.md, PR 34). Four-byte elements have no packing and cross at
    9.0-9.8 GB/s in either form, so they go as they are. The rule reads
    only the array: one- or two-byte elements, at least two trailing
    dimensions to merge (the batch axis is never merged, so a
    batch-sharded put still splits on rows), the trailing two not whole
    tiles already, a row's elements a whole number of tiles,
    C-contiguous and aligned (never a copy). Anything else goes as it
    is: ``float32`` frames, points ``[N, 4]``, a prompt ``[1, 4096]``,
    a step row ``[7]``."""
    item = arr.dtype.itemsize
    if arr.ndim < 3 or arr.size == 0 or item not in (1, 2):
        return arr
    if not (arr.flags.c_contiguous and arr.flags.aligned):
        return arr
    sublanes = 8 * (4 // item)
    if arr.shape[-1] % _LANES == 0 and arr.shape[-2] % sublanes == 0:
        return arr
    per_row = arr.size // arr.shape[0]
    if per_row % (sublanes * _LANES):
        return arr
    return arr.reshape(arr.shape[0], per_row // _LANES, _LANES)


@jax.tree_util.register_pytree_node_class
class DenseStaged:
    """A staged array in its transfer form (:func:`transfer_view`) with
    what undoes it: the wire array's trailing shape, static in every
    program that takes it. ``shape`` and ``ndim`` are the WIRE array's;
    the one leaf is the device array, so donation donates the dense
    buffer and a batch sharding splits its rows."""

    __slots__ = ("data", "trailing")

    def __init__(self, data, trailing) -> None:
        self.data, self.trailing = data, tuple(trailing)

    shape = property(lambda self: (self.data.shape[0], *self.trailing))
    ndim = property(lambda self: 1 + len(self.trailing))
    dtype = property(lambda self: self.data.dtype)
    nbytes = property(lambda self: self.data.nbytes)

    def tree_flatten(self):
        return (self.data,), self.trailing

    @classmethod
    def tree_unflatten(cls, trailing, children):
        return cls(children[0], trailing)

    def wire(self):
        """The array the caller sent, inside a traced program."""
        return self.data.reshape(self.data.shape[0], *self.trailing)


def put_staged(arr: np.ndarray, sharding=None):
    """``jax.device_put`` of a launch's host array in its transfer form."""
    view = transfer_view(arr)
    placed = jax.device_put(view, sharding)
    return placed if view is arr else DenseStaged(placed, arr.shape[1:])


class StagedRequest:
    """A request whose inputs live on the mesh, awaiting launch.

    Produced by ``StagedChannel.stage``; consumed exactly once by
    ``StagedChannel.launch`` (the staging slot it occupies frees when
    the launched batch finishes executing, or immediately on launch
    failure). ``meta`` carries subclass placement state (the sharded
    channel records the real row count so resolve can slice the pad
    rows back off)."""

    __slots__ = (
        "model", "device_inputs", "request", "t_stage", "meta",
        "lifecycle_key", "trace_state", "session",
    )

    def __init__(self, model, device_inputs, request, t_stage, meta=None) -> None:
        self.model = model
        self.device_inputs = device_inputs
        self.request = request
        self.t_stage = t_stage
        self.meta = meta
        # (name, version) in-flight reference on the lifecycle manager
        # (None when no manager is attached); dropped exactly once when
        # the request resolves or fails, so eviction can never reclaim
        # a model whose batch is still staged/executing
        self.lifecycle_key = None
        # traced requests only, (ids, h2d start, arrival marker, h2d
        # attrs): ``ids`` is the attrs dict of every span of this launch
        # but h2d, into which launch() writes ``launch_id`` once the
        # ordinal is known; the open ``h2d`` span is closed by resolve()
        self.trace_state = None
        # (state, ticket) of the session state this launch is bracketed
        # by (runtime/sessions.py: the model's own, else the server's
        # tracker for a request under a sequence_id), or None
        self.session = None


def _row_major(x):
    """A device array as the launch programs take their state: row-major.

    A fresh array has the layout the compiler likes for its shape, which
    on a TPU need not be row-major (bfloat16 ``[6, 40, 4352, 576]`` comes
    with the 4,352 minor, to save padding 576 to 640 lanes). Such an
    array is moved once, at load; but the move cannot be relied on: JAX's
    persistent compile cache forgets a pinned output layout of a program
    it loads (honoured in a first process, not in a second: my chip
    runs, PR 29), so a state that still is not row-major is refused, with
    the cure: give it a shape whose natural layout is row-major (rows of
    whole 128-lane tiles, as models/axk1.py does)."""
    from jax.experimental.layout import Format, Layout

    if not isinstance(x, jax.Array):
        return x  # shapes and types alone (a lowering ahead of any array)
    want = tuple(range(x.ndim))
    if tuple(x.format.layout.major_to_minor) != want:
        x = jax.device_put(x, Format(Layout(major_to_minor=want), x.sharding))
        if tuple(x.format.layout.major_to_minor) != want:
            raise ValueError(
                f"device state {x.shape} {x.dtype} has layout {x.format.layout} "
                "on this device and could not be moved to row-major: pad its "
                "last dimension to whole 128-lane tiles"
            )
    return x


class ParamLauncher:
    """The launcher of a model that registers ``params``: its weights
    are ARGUMENTS of the jitted device program, not constants of its
    module (gigabytes cannot be, and a module without them stays in the
    compile cache whatever the weights). Where the model names a device
    state (``spec.extra["device_state"]``: a key of ``params`` and of
    the program's outputs, such as a language model's cache), that
    subtree is DONATED into every launch and the one the launch returns
    is kept in its place: it never leaves the device and no launch
    copies it. Launches of one model are dispatched one at a time (the
    state threads through them in order); each launch shape is compiled
    ahead of the lock, so the shapes of a warm-up compile side by side.
    """

    def __init__(self, model, body) -> None:
        self._model = model
        self._state_key = key = model.spec.extra.get("device_state")

        def run(donated, kept, weights, state):
            params = weights if key is None else {**weights, key: state}
            out = dict(body({**donated, **kept}, params))
            return out, (out.pop(key) if key is not None else None)

        self._run = run
        if key is not None:
            model.params[key] = jax.tree_util.tree_map(
                _row_major, model.params[key]
            )
        self._programs: dict = {}  # launch kind -> its jitted program
        self._lock = threading.Lock()
        self._compiled: dict = {}

    def _program(self, inputs: dict):
        """The jitted program for these inputs. A model whose session
        state names its launches' kinds (``launch_kind(inputs)``: a
        language model's step and prefill) gets one named module a kind,
        ``jit_mdl_<name>_<version>_<kind>``, so that a device trace tells
        them apart; any other model has the one ``jit_mdl_<name>_<version>``.
        The state keeps ONE layout, row-major, on its way in and out: the
        compiler is otherwise free to give each launch shape's result a
        layout of its own, and every launch then begins and ends with a
        copy of the whole state."""
        kind_of = getattr(self._model.sessions, "launch_kind", None)
        kind = kind_of(inputs) if kind_of is not None else ""
        program = self._programs.get(kind)
        if program is None:
            fn = lambda *args: self._run(*args)
            name_launcher(fn, self._model)
            if kind:
                fn.__name__ = fn.__qualname__ = f"{fn.__name__}_{kind}"
            key, pinned = self._state_key, {}
            if key is not None:
                pinned = {
                    "in_shardings": (None, None, None, self._state_format()),
                    "out_shardings": (None, self._state_format()),
                }
            program = self._programs[kind] = jax.jit(
                fn, donate_argnums=(0,) if key is None else (0, 3), **pinned
            )
        return program

    def _state_format(self):
        """Row-major for every leaf of the state, on the device it is on."""
        from jax.experimental.layout import Format, Layout

        return jax.tree_util.tree_map(
            lambda x: Format(Layout(major_to_minor=tuple(range(x.ndim))), x.sharding),
            self._model.params[self._state_key],
        )

    def _params(self):
        params, key = self._model.params, self._state_key
        if key is None:
            return params, None
        return {k: v for k, v in params.items() if k != key}, params[key]

    def lower(self, donated, kept):
        """The launch's program for these inputs' shapes, the weights
        and the state as shapes and types (nothing read or donated)."""
        abstract = lambda tree: jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree
        )
        weights, state = self._params()
        return self._program({**donated, **kept}).lower(
            donated, kept, abstract(weights), abstract(state)
        )

    def __call__(self, donated, kept):
        shape_key = tuple(
            sorted((k, v.shape, str(v.dtype)) for k, v in {**donated, **kept}.items())
        )
        compiled = self._compiled.get(shape_key)
        if compiled is None:
            compiled = self._compiled[shape_key] = self.lower(donated, kept).compile()
        with self._lock:
            weights, state = self._params()
            out, state = compiled(donated, kept, weights, state)
            if self._state_key is not None:
                self._model.params[self._state_key] = state
        return out


@jax.jit
def _arrival_marker(device_inputs):
    """One element of every staged array, as a device program of its
    own: it runs once the arrays are on the device and, the device
    taking its programs in order, after the launch before it — and it
    survives launch(), which donates the arrays themselves."""
    return jax.tree_util.tree_map(
        lambda x: x[(slice(0, 1),) * x.ndim], device_inputs
    )


class _Inflight:
    """One launched, not-yet-retired batch (a staging slot occupant)."""

    __slots__ = ("outputs", "retired")

    def __init__(self, outputs) -> None:
        self.outputs = outputs
        self.retired = False

    def wait_device(self) -> None:
        # Execution-complete, NOT readback: arrays stay on device.
        jax.block_until_ready(self.outputs)


class _LaunchFuture(InferFuture):
    """A launch's lazy future that can also say when the outputs are
    ready ON THE DEVICE: ``wait_device()`` returns then, their readback
    still to come in ``result()``. The batcher closes a group of session
    steps behind a running launch on that event
    (runtime/continuous.py)."""

    __slots__ = ("wait_device",)

    def __init__(self, resolve, wait_device) -> None:
        super().__init__(resolve)
        self.wait_device = wait_device


class StagedChannel(BaseChannel):
    """Shared stage/launch/resolve machinery over a device mesh.

    Subclasses implement the placement policy:

      * :meth:`_place_inputs` — request host arrays -> device arrays on
        the mesh (plus opaque ``meta`` threaded to the readback);
      * :meth:`_make_launcher` — the cached jit wrapper over a model's
        ``device_fn`` (donation split, shardings);
      * :meth:`_host_outputs`  — device outputs -> host numpy at the
        wire dtypes (the designed readback sync point).
    """

    def __init__(
        self,
        repository: ModelRepository,
        mesh_config: MeshConfig | None = None,
        devices=None,
        validate: bool = True,
        pipeline_depth: int = 2,
        donate: bool = True,
        shed_expired: bool = False,
        breaker_threshold: int = 5,
        breaker_reset_s: float = 10.0,
    ) -> None:
        """``pipeline_depth``: launched-but-unretired batches allowed
        before ``stage`` blocks on the oldest batch's execution; 1 is
        the strictly serial legacy path. ``donate``: honor spec
        ``donatable`` marks (buffer reuse needs a ``device_fn``; on
        backends without donation support jax falls back to a copy).

        ``shed_expired``: enforce the deadline plane at launch — a
        request whose deadline already passed is FAILED with
        ``DeadlineExpiredError`` instead of executed (PR 6 only counted
        such launches; with shedding on, ``deadline_expired_launches``
        stays 0 while ``shed`` grows). Off by default so an SLO-less
        deployment keeps PR 6's count-only behavior.

        ``breaker_threshold``/``breaker_reset_s``: the per-model
        circuit breaker around launch+readback — ``threshold``
        consecutive failures open the circuit (fail-fast
        ``CircuitOpenError``, launch cache invalidated so recovery
        rebuilds the jitted launcher), a timed probe after ``reset_s``
        half-opens it, one success closes it. ``breaker_threshold=0``
        disables the breaker."""
        self._repository = repository
        self._mesh_config = mesh_config
        self._devices = devices
        self._mesh = None
        self._validate = validate
        self._pipeline_depth = max(1, int(pipeline_depth))
        self._donate = bool(donate)
        # staging slots: launched batches not yet retired (execution
        # still pending or readback not requested yet). Slots are per
        # channel — one admission window over the whole mesh.
        self._slot_cv = threading.Condition()
        self._inflight: collections.deque[_Inflight] = collections.deque()
        self._slots_active = 0
        self._slot_occupancy: collections.Counter = collections.Counter()
        self._stats = {
            "staged": 0,
            "launched": 0,
            "donated_launches": 0,
            "stage_slot_waits": 0,
            # launches whose request deadline (obs.slo deadline plane)
            # had already passed at enqueue time: sustained growth means
            # the queue ahead of the device eats the whole SLO budget —
            # the capacity-search saturation signal, visible live
            "deadline_expired_launches": 0,
            # launch/readback failures observed by the circuit breaker
            "launch_failures": 0,
            # the fused 2D decode+NMS kernel (ops/pallas_decode): greedy
            # steps its groups of eight frames ran, summed at readback,
            # and the frames of the launches that reported them. Steps
            # over frames: max_det = the early stop never engaged,
            # max_det / 8 = sublane packing alone, below = both
            "nms_steps": 0,
            "nms_frames": 0,
            # bytes placed on the device by stage(), and those of them
            # that crossed in a changed view (transfer_view)
            "staged_bytes": 0,
            "staged_dense_bytes": 0,
        }
        self._shed_expired = bool(shed_expired)
        self._breaker = (
            CircuitBreaker(
                threshold=breaker_threshold, reset_s=breaker_reset_s
            )
            if breaker_threshold > 0
            else None
        )
        # per "model|priority|stage" shed counts, merged into the
        # collector's tpu_serving_shed_total family at scrape time
        self._shed: collections.Counter = collections.Counter()
        # (name, version) -> (model identity, launcher, donate_names,
        # output wire dtypes); rebuilt when the repository reloads the
        # model (identity mismatch)
        self._launch_cache: dict = {}
        self._launcher_build = threading.Lock()
        # models whose measured flops/bytes (obs/roofline.py) were
        # already recorded into spec.extra — one attempt per model
        # identity, success or not, so a cost-model failure cannot
        # re-trace the launcher on every launch
        self._cost_measured: set = set()
        # optional ModelLifecycleManager (runtime/lifecycle.py): when
        # attached, stage() blocks until the model is WARM and holds an
        # in-flight reference through resolve
        self._lifecycle = None
        # optional DeviceTimeLedger (obs/device_time.py): when attached,
        # every launch's device-execute window accrues into per-
        # model×tenant device-seconds + live MFU
        self._device_time = None
        # optional SessionManager (runtime/sessions.py): when attached,
        # launches carrying a sequence_id run the device-resident
        # tracking step on their outputs before the response forms
        self._sessions = None
        # unregister must drop the cached launcher too — the cached
        # closure pins replicated params in HBM and would otherwise
        # leak until a same-named model happens to fail the identity
        # check; same invalidation path the circuit breaker uses
        subscribe = getattr(repository, "add_unregister_listener", None)
        if subscribe is not None:
            subscribe(self._on_unregister)
        self.register_channel()

    # -- BaseChannel protocol -------------------------------------------------

    def register_channel(self) -> None:
        self._mesh = make_mesh(self._mesh_config, self._devices)

    def fetch_channel(self):
        return self._mesh

    def get_metadata(self, model_name: str, model_version: str = "") -> ModelSpec:
        return self._repository.metadata(model_name, model_version)

    def models_generation(self) -> int:
        """Moves when the repository's models change: a wrapping
        channel that keeps facts of a model's spec asks them again."""
        return self._repository.generation

    def do_inference(self, request: InferRequest) -> InferResponse:
        return self.launch(self.stage(request)).result()

    def do_inference_async(self, request: InferRequest) -> InferFuture:
        """The in-process --async path: JAX dispatch is asynchronous, so
        launch returns as soon as the computation is enqueued on the
        device; materializing numpy (the only blocking step) is deferred
        to result(). The driver can therefore preprocess frame N+1 while
        the chip runs frame N — no threads needed.

        Per the BaseChannel contract, dispatch-time errors (validation,
        unknown model, staging) are deferred to result() rather than
        raised here, so async callers have one error-surfacing point."""
        try:
            staged = self.stage(request)
        except Exception as e:
            return InferFuture.failed(e)
        return self.launch(staged)

    # -- subclass placement hooks ---------------------------------------------

    def _place_inputs(self, model, request: InferRequest):
        """Place the request's host arrays onto the mesh.

        Returns ``(device_inputs, meta)``. Runs INSIDE the staging slot
        (a raised error releases the slot); must not block on device
        execution."""
        raise NotImplementedError

    def _make_launcher(self, model):
        """Build ``(launcher | None, donate_names, out_dtypes)`` for a
        model. ``launcher(donated, kept)`` runs the jitted device_fn
        with ``donated`` in a ``donate_argnums`` position; None falls
        back to the host-boundary ``infer_fn``. Called once per model
        identity (cached by :meth:`_launcher`)."""
        raise NotImplementedError

    def _place_ragged(self, model, request: InferRequest):
        """Place a PACKED ragged request (``request.ragged`` is a
        :class:`RaggedLayout`): packed inputs and the segment-id table
        upload with default placement (the ragged body's segment math
        is global — XLA partitions it), per-segment inputs ride along
        unchanged. Subclasses with explicit shardings override."""
        layout = request.ragged
        device_inputs = {
            name: jax.device_put(cast_wire_input(model, name, np.asarray(arr)))
            for name, arr in request.inputs.items()
        }
        device_inputs[SEGMENT_IDS_KEY] = jax.device_put(layout.segment_ids)
        return device_inputs, layout

    def _make_ragged_launcher(self, model, num_segments: int):
        """Build ``(launcher, out_dtypes)`` for a model's segment-aware
        body at a STATIC bucketed segment capacity. No donation: packed
        shapes recur less often than dense buckets and a donated packed
        buffer would alias the replicated-row pad region."""
        from triton_client_tpu.config import config_dtypes

        ragged_fn = model.ragged_fn

        # named distinctly from the dense `launcher`: this jit does NOT
        # donate, and tpulint's donor index pools jit-bound names
        # module-wide
        def ragged_launcher(device_inputs):
            inputs = dict(device_inputs)
            ids = inputs.pop(SEGMENT_IDS_KEY)
            return ragged_fn(inputs, ids, num_segments)

        # stamped with the model's launcher name (runtime only — the
        # local binding above keeps lint's donor index unambiguous) so
        # profiler op events attribute by HLO module (obs/opstats.py)
        from triton_client_tpu.obs.roofline import name_launcher

        ragged_launcher = jax.jit(name_launcher(ragged_launcher, model))

        out_dtype = {
            t.name: config_dtypes().get(t.dtype) for t in model.spec.outputs
        }
        return ragged_launcher, out_dtype

    def _ragged_launcher(self, model, num_segments: int):
        """The ragged analogue of :meth:`_launcher`: cached per
        ``(model identity, segment bucket)`` — the segment capacity is
        static in the traced program, so the executable set stays
        log-bounded in segments (and jit's own shape cache bounds it in
        packed rows)."""
        key = (model.spec.name, model.spec.version, "ragged", num_segments)
        with self._slot_cv:
            cached = self._launch_cache.get(key)
            if cached is not None and cached[0] is model:
                return cached[1], cached[2]
        launcher, out_dtype = self._make_ragged_launcher(model, num_segments)
        with self._slot_cv:
            self._launch_cache[key] = (model, launcher, out_dtype)
        return launcher, out_dtype

    def _device_body(self, model):
        """The traced body both launcher implementations jit: the
        model's ``device_fn``, wrapped with the registered precision
        policy's wire ingest when the policy quantized activations —
        int8 wire inputs then dequantize INSIDE the launched program
        (runtime/precision.py), so the cached launcher stages in the
        wire dtype and runs the body at the policy dtype."""
        device_fn = model.device_fn
        if device_fn is None:
            return None
        policy = getattr(model, "precision", None)
        if policy is not None and getattr(policy, "wire_ingest_needed", False):
            wire_fn = device_fn
            device_fn = lambda inputs, *rest: wire_fn(policy.ingest(inputs), *rest)

        def body(inputs, *rest):
            # first, undo the transfer form of what was staged dense; where
            # nothing was, this traces to the program it always was
            return device_fn(
                {
                    k: v.wire() if isinstance(v, DenseStaged) else v
                    for k, v in inputs.items()
                },
                *rest,
            )

        return body

    def _host_outputs(self, outputs, out_dtype, meta) -> dict:
        """Device outputs -> host numpy dict at the wire dtypes. The
        designed deferred-readback sync point (tpulint TPL301 baseline);
        subclasses slice off pad rows here before the copy."""
        if isinstance(meta, RaggedLayout):
            # drop the dead segment slots (lazy slice — the host copy
            # below pays only for real segments)
            outputs = {
                k: v[: meta.n_segments]
                if getattr(v, "ndim", 0) >= 1
                and v.shape[0] == meta.seg_bucket
                else v
                for k, v in outputs.items()
            }
        host = {}
        for k, v in outputs.items():
            # wire-contract dtypes at the host boundary: device traces
            # run with x64 disabled, so e.g. a scored head's INT64
            # classes come back int32 from device_fn — the cast keeps
            # launch paths identical
            dt = out_dtype.get(k) if out_dtype else None
            host[k] = np.asarray(v, dtype=dt) if dt else np.asarray(v)
        return host

    # -- pipeline knobs -------------------------------------------------------

    @property
    def pipeline_depth(self) -> int:
        return self._pipeline_depth

    @pipeline_depth.setter
    def pipeline_depth(self, depth: int) -> None:
        with self._slot_cv:
            self._pipeline_depth = max(1, int(depth))
            self._slot_cv.notify_all()

    @property
    def batch_multiple(self) -> int:
        """Preferred divisor for device batch sizes. 1 for per-device
        channels; the data-axis width for mesh-sharded channels (the
        batcher sizes merge groups and pad buckets off this)."""
        return 1

    def stats(self) -> dict:
        """Staging-slot counters (the channel-level analogue of
        the batcher's stats): ``slot_occupancy`` maps concurrent
        in-flight batches at launch -> launches observed at that depth."""
        with self._slot_cv:
            out = dict(self._stats)
            out["slot_occupancy"] = dict(sorted(self._slot_occupancy.items()))
            out["inflight"] = len(self._inflight)
            out["slots_active"] = self._slots_active
            out["pipeline_depth"] = self._pipeline_depth
            out["shed"] = dict(self._shed)
        if self._breaker is not None:
            out["breaker"] = self._breaker.states()
        if self._mesh is not None:
            out["mesh_devices"] = int(self._mesh.devices.size)
            out["data_axis_size"] = int(self._mesh.shape["data"])
        return out

    # -- stage ----------------------------------------------------------------

    def stage(self, request: InferRequest) -> StagedRequest:
        """Validate the request and place its arrays onto the mesh.

        Blocks while ``pipeline_depth`` launched batches are still
        executing, so the H2D copy of the next batch overlaps (at most)
        depth in-flight computations — double-buffered at the default
        depth of 2. Must be paired with ``launch``."""
        tr = request.trace
        t_s0 = time.perf_counter() if tr is not None else 0.0
        model = self._repository.get(request.model_name, request.model_version)
        ragged = request.ragged is not None
        if self._validate and not ragged:
            # ragged requests carry PACKED shapes (rows concatenated
            # across members, padded to the layout bucket) that the
            # per-tensor wire spec cannot describe; the continuous
            # batcher validated each member at admission
            for tensor_spec in model.spec.inputs:
                if tensor_spec.name not in request.inputs:
                    raise ValueError(
                        f"model '{model.spec.name}' requires input "
                        f"'{tensor_spec.name}'; request has "
                        f"{sorted(request.inputs)}"
                    )
                tensor_spec.validate(np.asarray(request.inputs[tensor_spec.name]))
        # the session state this launch belongs to: the model's own
        # (a token model: its rows get their cache slots and positions
        # here, and the request becomes the launch's plain arrays), else
        # the server's tracker for a request under a sequence_id
        state = model.sessions
        if state is None and request.sequence_id:
            state = self._sessions
        session = None
        if state is not None:
            try:
                request, ticket = state.open(request)
            except Exception:
                self._count_shed(model.spec.name, request.priority, "session")
                raise
            session = (state, ticket)
        lifecycle_key = None
        if self._lifecycle is not None:
            # block until the model is WARM (a cold model promotes on
            # demand here — first request pays the page-in, peers queue
            # behind it with a deadline-aware bound) and take the
            # in-flight reference that shields it from eviction
            t_p0 = time.perf_counter()
            try:
                lifecycle_key = self._lifecycle.acquire(
                    model.spec.name,
                    model.spec.version,
                    deadline_s=request.deadline_s,
                )
            except Exception:
                self._count_shed(model.spec.name, request.priority, "lifecycle")
                if session is not None:
                    session[0].abort(session[1])
                raise
            if tr is not None:
                tr.add("lifecycle", t_p0, time.perf_counter())
        if tr is not None:
            ids = {}  # launch() fills in launch_id: the ordinal is its to give
            t_w0 = time.perf_counter()
            self._acquire_slot()
            t_h0 = time.perf_counter()
            tr.add("slot_wait", t_w0, t_h0, ids)
        else:
            self._acquire_slot()
        try:
            if ragged:
                device_inputs, meta = self._place_ragged(model, request)
            else:
                device_inputs, meta = self._place_inputs(model, request)
        except Exception:
            self._release_slot()
            if lifecycle_key is not None:
                self._lifecycle.release(*lifecycle_key)
            if session is not None:
                session[0].abort(session[1])
            raise
        placed_bytes = sum(v.nbytes for v in device_inputs.values())
        dense_bytes = sum(
            v.nbytes for v in device_inputs.values() if isinstance(v, DenseStaged)
        )
        with self._slot_cv:
            self._stats["staged"] += 1
            self._stats["staged_bytes"] += placed_bytes
            self._stats["staged_dense_bytes"] += dense_bytes
        t_staged = time.perf_counter()
        staged = StagedRequest(model, device_inputs, request, t_staged, meta)
        staged.lifecycle_key = lifecycle_key
        staged.session = session
        if tr is not None:
            # the stage phase: validate + slot admission + the ENQUEUE of
            # the H2D copy (device_put returns before the bytes moved)
            tr.add("stage", t_s0, t_staged, ids)
            # h2d is closed by resolve(), on the thread that waits for
            # the outputs anyway, when the arrival marker is ready: at
            # the later of "frames on the device" and "previous launch
            # done". Waiting HERE for the arrays themselves held the
            # executor 0.14 s a launch off its next request and cost a
            # traced server 4-10% of its rate (PERF.md, PR 26). Traced
            # only: the untraced path dispatches nothing more and no
            # path waits for the device in stage().
            h2d = {
                "bytes": sum(
                    np.asarray(a).nbytes for a in request.inputs.values()
                ),
                "rows": _batch_rows(device_inputs),
            }
            staged.trace_state = (
                ids, t_h0, _arrival_marker(device_inputs), h2d
            )
        return staged

    def _acquire_slot(self) -> None:
        waited = False
        while True:
            rec = None
            with self._slot_cv:
                if self._slots_active < self._pipeline_depth:
                    self._slots_active += 1
                    if waited:
                        self._stats["stage_slot_waits"] += 1
                    return
                waited = True
                if self._inflight:
                    rec = self._inflight.popleft()
                else:
                    # every slot is held by a peer between stage and
                    # launch; timed wait covers a missed notify
                    self._slot_cv.wait(timeout=0.05)
                    continue
            # block on EXECUTION completion outside the lock (readback
            # stays lazy; a concurrent resolve() of the same record is
            # fine — _retire is idempotent)
            rec.wait_device()
            self._retire(rec)

    def _release_slot(self) -> None:
        with self._slot_cv:
            self._slots_active -= 1
            self._slot_cv.notify_all()

    def _retire(self, rec: _Inflight) -> None:
        with self._slot_cv:
            if rec.retired:
                return
            rec.retired = True
            try:
                self._inflight.remove(rec)
            except ValueError:
                pass  # already popped by a staging thread
            self._slots_active -= 1
            self._slot_cv.notify_all()

    # -- launch ---------------------------------------------------------------

    def launch(self, staged: StagedRequest) -> InferFuture:
        """Enqueue the jitted compute for a staged request; returns a
        lazy InferFuture holding device arrays. The device->host copy
        happens at result(); the staging slot frees when the batch
        finishes executing (whichever of a later ``stage`` or this
        future's resolution observes it first)."""
        model, request = staged.model, staged.request
        name = model.spec.name
        tr = request.trace
        t0 = time.perf_counter()
        deadline = request.deadline_s
        if self._shed_expired and deadline is not None and t0 > deadline:
            # shedding enforced: a request whose deadline already
            # passed NEVER executes — fail its future in microseconds
            # instead of burning a device slot on work nobody can use
            self._release_slot()
            self._release_lifecycle(staged)
            self._abort_session(staged)
            self._count_shed(name, request.priority, "launch")
            return InferFuture.failed(
                DeadlineExpiredError(
                    f"model '{name}': deadline expired "
                    f"{(t0 - deadline) * 1e3:.1f}ms before launch"
                )
            )
        if self._breaker is not None and not self._breaker.allow(name, t0):
            self._release_slot()
            self._release_lifecycle(staged)
            self._abort_session(staged)
            self._count_shed(name, request.priority, "breaker")
            return InferFuture.failed(
                CircuitOpenError(
                    f"model '{name}': circuit breaker open "
                    "(recent consecutive launch failures)"
                )
            )
        try:
            faults.probe("slow_launch", name)
            faults.probe("launch", name)
            if request.ragged is not None:
                # packed-ragged launch: one jitted segment-aware body at
                # a static segment bucket; no donation split (see
                # _make_ragged_launcher), hence the distinct name — the
                # dense branch's `launcher` is a donating callable
                ragged_launcher, out_dtype = self._ragged_launcher(
                    model, request.ragged.launch_segments
                )
                donate_names = frozenset()
                self._ensure_launch_cost(
                    model, ragged_launcher, (staged.device_inputs,),
                    batch_rows=request.ragged.n_segments,
                )
                with jax.profiler.TraceAnnotation(
                    f"launch:{name}:{model.spec.version}"
                ):
                    outputs = ragged_launcher(staged.device_inputs)
            else:
                launcher, donate_names, out_dtype = self._launcher(model)
                if launcher is not None:
                    donated = {
                        k: v
                        for k, v in staged.device_inputs.items()
                        if k in donate_names
                    }
                    kept = {
                        k: v
                        for k, v in staged.device_inputs.items()
                        if k not in donate_names
                    }
                    self._ensure_launch_cost(
                        model, launcher, (donated, kept),
                        batch_rows=_batch_rows(staged.device_inputs),
                    )
                    # named region around the dispatch: a profiler
                    # capture (/profile, the continuous sampler) then
                    # maps device ops back to this model even when the
                    # HLO module name is unavailable (obs/opstats.py)
                    with jax.profiler.TraceAnnotation(
                        f"launch:{name}:{model.spec.version}"
                    ):
                        outputs = launcher(donated, kept)
                else:
                    with jax.profiler.TraceAnnotation(
                        f"launch:{name}:{model.spec.version}"
                    ):
                        outputs = model.infer_fn(staged.device_inputs)
        except Exception as e:
            # fan the error to THIS request's future only; the slot
            # frees, the channel and its caches stay serviceable for
            # every other request (the breaker decides if the model
            # itself needs a timeout)
            self._release_slot()
            self._release_lifecycle(staged)
            self._abort_session(staged)
            self._record_launch_failure(name)
            return InferFuture.failed(e)
        session = staged.session
        session_id = request.sequence_id if session is not None else ""
        if session is not None:
            # the session state's step on the launched outputs: the
            # tracker appends the stream's device-resident tracking step
            # (async jit dispatch over arrays already in HBM; the track
            # tensors join the outputs, the state pytree stays on device
            # inside the session slot, and the slot ref advance() takes
            # is dropped by close() in resolve's finally); a token model
            # runs nothing more (close() cuts the logits' pad rows on the host)
            try:
                outputs = session[0].advance(session[1], outputs)
            except Exception as e:
                self._release_slot()
                self._release_lifecycle(staged)
                self._abort_session(staged)
                self._count_shed(name, request.priority, "session")
                return InferFuture.failed(e)
        rec = _Inflight(outputs)
        t_launched = time.perf_counter()
        with self._slot_cv:
            self._inflight.append(rec)
            self._stats["launched"] += 1
            launch_id = self._stats["launched"]
            if donate_names:
                self._stats["donated_launches"] += 1
            if deadline is not None and t_launched > deadline:
                self._stats["deadline_expired_launches"] += 1
            self._slot_occupancy[len(self._inflight)] += 1
        h2d = None
        if tr is not None:
            # the channel's launch ordinal on every span of this launch
            # (the same dicts on every member of a merged launch), so
            # spans group by launch and the k-th launch can be laid
            # beside the k-th jit_mdl_* module of a device trace
            ids, *h2d = staged.trace_state
            ids["launch_id"] = h2d[2]["launch_id"] = launch_id
            tr.add("launch", t0, t_launched, ids)

        # a fused 2D tail's step count rides with the rows; it is summed
        # into stats() in resolve and never reaches the response
        nms_steps = outputs.get(NMS_STEPS_KEY)
        if nms_steps is not None:
            outputs = {k: v for k, v in outputs.items() if k != NMS_STEPS_KEY}
        launch_rows = _batch_rows(staged.device_inputs)
        ledger = self._device_time
        # a token launch names its span: lm_prefill or lm_step
        launch_span = getattr(session[1], "span", None) if session else None

        ready = []

        def wait_device() -> float:
            """Until the outputs are ready ON THE DEVICE, their readback
            still to come; when that was, as the first call saw it. The
            batcher calls it ahead of ``result()`` for a launch of
            session steps (runtime/continuous.py), on the same thread."""
            nonlocal h2d
            if not ready:
                if h2d is not None:
                    (t_h0, marker, h2d_attrs), h2d = h2d, None
                    jax.block_until_ready(marker)
                    tr.add("h2d", t_h0, time.perf_counter(), h2d_attrs)
                jax.block_until_ready(outputs)
                ready.append(time.perf_counter())
            return ready[0]

        def resolve() -> InferResponse:
            host = None
            try:
                if tr is not None or ledger is not None:
                    # device window: enqueue -> execution complete.
                    # block_until_ready is what np.asarray would wait on
                    # anyway; forcing it here splits execute from the
                    # device->host copy in the request timeline. The
                    # ledger accrues the SAME window the trace spans, so
                    # its totals reconcile with the device_execute
                    # histogram by construction. A host wait, not device
                    # time: it holds what is left of the transfer, the
                    # queueing behind the previous launch and the
                    # compute; the end of h2d, inside it, says where the
                    # compute can have begun.
                    t_ready = wait_device()
                    if tr is not None:
                        tr.add("device_execute", t_launched, t_ready, ids)
                        if launch_span is not None:
                            tr.add(
                                launch_span[0], t_launched, t_ready,
                                {**ids, **launch_span[1]},
                            )
                    if ledger is not None:
                        # session frames accrue under a per-stream
                        # tenant, so the ledger's tenant axis answers
                        # "device seconds per live stream" directly
                        ledger.record(
                            name, t_ready - t_launched, model.spec.extra,
                            tenant=f"stream:{session_id}"
                            if session_id
                            else None,
                        )
                faults.probe("readback", name)
                host = self._host_outputs(outputs, out_dtype, staged.meta)
                if nms_steps is not None:
                    steps_run = int(np.asarray(nms_steps).sum())
                    with self._slot_cv:
                        self._stats["nms_steps"] += steps_run
                        self._stats["nms_frames"] += launch_rows
                if tr is not None:
                    tr.add("readback", t_ready, time.perf_counter(), ids)
            except Exception:
                # readback failure belongs to THIS batch's futures only
                # (the batcher fans it to the members); the breaker
                # aggregates consecutive failures into a model timeout
                self._record_launch_failure(name)
                raise
            finally:
                self._retire(rec)
                self._release_lifecycle(staged)
                if session is not None:
                    session[0].close(session[1], host)
            if self._breaker is not None:
                self._breaker.record_success(name)
            return InferResponse(
                model_name=request.model_name,
                model_version=model.spec.version,
                outputs=host,
                request_id=request.request_id,
                latency_s=time.perf_counter() - t0,
            )

        return _LaunchFuture(resolve, wait_device)

    def _launcher(self, model):
        """(jitted device_fn launcher | None, donate names, out dtypes),
        cached per model identity. Host-only models (no device_fn) keep
        the legacy infer_fn call, which may block on its own internal
        readback."""
        if model.device_fn is None:
            return None, (), None
        key = (model.spec.name, model.spec.version)
        with self._slot_cv:
            cached = self._launch_cache.get(key)
            if cached is not None and cached[0] is model:
                return cached[1], cached[2], cached[3]
        # one launcher a model: a model that registers params hands its
        # launcher the device state to thread through its launches, and
        # two launchers built side by side (the first requests of a
        # warm-up arrive together) would each donate the other's state
        with self._launcher_build:
            with self._slot_cv:
                cached = self._launch_cache.get(key)
                if cached is not None and cached[0] is model:
                    return cached[1], cached[2], cached[3]
            launcher, donate_names, out_dtype = self._make_launcher(model)
            with self._slot_cv:
                self._launch_cache[key] = (model, launcher, donate_names, out_dtype)
        return launcher, donate_names, out_dtype

    def _ensure_launch_cost(
        self, model, launcher, args, batch_rows: int = 1
    ) -> None:
        """Record XLA's measured flops/bytes for one launcher call into
        ``model.spec.extra`` (obs/roofline.py) — once per model
        identity, on the first launch, where the example args finally
        exist. Tracing-only (no backend compile) and immediately before
        the first call's full compile, so the marginal cost is
        milliseconds on a path about to pay seconds. Never fails the
        launch: the roofline is observability, not serving."""
        key = (model.spec.name, model.spec.version)
        with self._slot_cv:
            if key in self._cost_measured:
                return
            self._cost_measured.add(key)
        try:
            from triton_client_tpu.obs.roofline import record_launch_cost

            record_launch_cost(model, launcher, *args, batch_rows=batch_rows)
        except Exception:  # cost model unavailable on this backend
            log.debug(
                "measured-cost capture failed for %s:%s",
                *key, exc_info=True,
            )

    # -- model lifecycle (runtime/lifecycle.py) -------------------------------

    def attach_lifecycle(self, manager) -> None:
        """Attach a ModelLifecycleManager: stage() then blocks until the
        model is WARM (promoting it on demand) and brackets each request
        with acquire/release so eviction never reclaims a model with
        in-flight work. The manager's page-in hook builds this channel's
        cached launcher; its page-out hook drops it (freeing the
        replicated params the launcher closure pins in HBM)."""
        self._lifecycle = manager
        manager.set_hooks(warmer=self._warm_model, evictor=self._evict_model)

    @property
    def lifecycle(self):
        return self._lifecycle

    # -- device-time attribution (obs/device_time.py) -------------------------

    def attach_device_time(self, ledger) -> None:
        """Attach a DeviceTimeLedger: every subsequent launch records
        its device-execute window (t_launched -> block_until_ready)
        into the ledger from the resolve path."""
        self._device_time = ledger

    @property
    def device_time(self):
        return self._device_time

    # -- streaming sessions (runtime/sessions.py) -----------------------------

    def attach_sessions(self, manager) -> None:
        """Attach a SessionManager: launches whose request carries a
        ``sequence_id`` advance that stream's device-resident tracker
        on the launch outputs (state never leaves HBM between frames)
        and hold the session slot's refcount until resolve."""
        self._sessions = manager

    @property
    def sessions(self):
        return self._sessions

    def _abort_session(self, staged: StagedRequest) -> None:
        """A staged launch that never reached the device: its session
        state takes back what ``open`` gave."""
        if staged.session is not None:
            staged.session[0].abort(staged.session[1])

    def session_stats(self) -> dict | None:
        """The tracker sessions' counters (where a manager is attached)
        and, under ``models``, those of every registered model that
        declares a session state of its own."""
        out = self._sessions.stats() if self._sessions is not None else None
        models = {}
        for name, version in self._repository.list_models():
            state = self._repository.get(name, version).sessions
            if state is not None:
                models[name] = state.stats()
        if models:
            out = {**(out or {}), "models": models}
        return out

    def served_model(self, name: str, version: str = ""):
        """The model as this channel serves it (its registered entry:
        ``spec``, ``device_fn``, ``params`` as they stand on the
        device): the handle a memory statement or a warm-up lowers."""
        return self._repository.get(name, version)

    def _warm_model(self, name: str, version: str) -> None:
        """Lifecycle page-in hook: build + cache the jitted launcher (the
        sharded subclass replicates the param tree here — the actual HBM
        page-in) so the promoting request pays compile+placement once and
        everything queued behind it launches hot."""
        model = self._repository.get(name, version)
        if model.device_fn is not None:
            self._launcher(model)

    def _evict_model(self, name: str, version: str) -> None:
        """Lifecycle page-out hook: drop the cached launcher so XLA frees
        the replicated params its closure holds."""
        self._invalidate_model(name, version)

    def _on_unregister(self, name: str, version: str) -> None:
        # repository listener (registered in __init__): an unregistered
        # model must not keep serving from — or pinning HBM through —
        # a stale cached launcher
        self._invalidate_model(name, version)

    def _invalidate_model(self, name: str, version: str) -> None:
        """Drop every cached launcher for one (name, version): the dense
        entry plus all ragged segment buckets."""
        with self._slot_cv:
            for key in [
                k
                for k in self._launch_cache
                if k[0] == name and k[1] == version
            ]:
                del self._launch_cache[key]

    def _release_lifecycle(self, staged: StagedRequest) -> None:
        """Drop the in-flight lifecycle reference exactly once (every
        launch failure path and resolve's finally funnel here)."""
        key, staged.lifecycle_key = staged.lifecycle_key, None
        if key is not None and self._lifecycle is not None:
            self._lifecycle.release(*key)

    # -- failure isolation ----------------------------------------------------

    def _count_shed(self, model: str, priority: int, stage: str) -> None:
        with self._slot_cv:
            self._shed[f"{model}|{int(priority)}|{stage}"] += 1

    def _record_launch_failure(self, model: str) -> None:
        """One launch/readback failure for ``model``: feed the breaker;
        when this failure OPENS the circuit, drop the cached launcher so
        recovery (the half-open probe) rebuilds the jit wrapper from the
        repository's current model instead of reusing state that may
        have been poisoned by the failure."""
        with self._slot_cv:
            self._stats["launch_failures"] += 1
        if self._breaker is None:
            return
        if self._breaker.record_failure(model):
            self._invalidate_launcher(model)

    def _invalidate_launcher(self, model: str) -> None:
        with self._slot_cv:
            for key in [k for k in self._launch_cache if k[0] == model]:
                del self._launch_cache[key]

    @property
    def breaker(self):
        """The per-model circuit breaker (None when disabled) — the
        collector reads states() off it via stats()["breaker"]."""
        return self._breaker
