"""Continuous batching: coalesce concurrent requests into one TPU call.

Triton's dynamic batcher is a core piece of the serving runtime the
reference leans on (config.pbtxt max_batch_size; SURVEY.md §2.9 row 1).
Here the same role runs in-tree as ONE scheduler with no admission
window and no admission thread:

  * **admission** — ``do_inference`` stages the request straight into
    the ready set, kept ordered earliest-deadline-first (ties: higher
    priority, then arrival). The DISPATCHER forms the device batch at
    the moment an execution slot frees, merging every compatible
    request queued by then — the continuous-admission discipline of
    FlexNPU's dynamic co-location (PAPERS.md). Slot-time formation is
    self-clocking: while ``pipeline_depth`` groups execute, arrivals
    pool, and the next group takes them all, up to ``max_merge`` rows.
  * **the kind of a group** is read off what the scheduler can observe
    (:meth:`ContinuousBatchingChannel._group_kind`):

    - *pass-through*: ONE member whose rows already are a launch size
      (pad 0, or a request wider than ``max_merge``). The inner channel
      is handed the request's OWN arrays: for an shm request the
      zero-copy view of the caller's region, read in place until the
      answer leaves. ``stats()["passthrough_groups"]``.
    - *dense merge*: same model, version and non-batch input shapes.
      Rows concatenate into a new buffer (span ``batch_merge``,
      ``stats()["merged_bytes"]``) padded with replicated rows to a
      size from the LIVE occupancy histogram (:class:`LiveBuckets`), so
      steady traffic converges to near-zero padding while the inner
      channel still sees a bounded set of shapes. Bitwise identical per
      request (pad rows are sliced back off — the
      ``runtime/padding.py`` contract).
    - *ragged*: models that register a segment-aware body
      (``RegisteredModel.ragged_fn`` + ``spec.extra["ragged_inputs"]``)
      execute as PACKED batches: member rows concatenate back to back
      and a row->segment table rides along
      (``parallel/ragged_kernels.py``), so every request runs at its
      true size — zero pad rows beyond lane alignment.
    - *session steps*: the steps of DIFFERENT sessions (one new token
      each, or one block each of ``spec.extra["step_width"]`` tokens) of a
      model that declares ``spec.extra["session_merge"]`` share one
      launch, each row its own stream. The one kind whose group does
      NOT close the moment a slot frees: it stays open, absorbing
      arrivals, while a launch is ahead of it on the device. Behind a
      step launch of its own model whose sessions are not worth
      waiting for it closes when the device is about to take it
      (:meth:`ContinuousBatchingChannel._early_wait_locked`); else
      when the launch ahead has been answered, and at a free device it
      then waits for the sessions that are about to come back where
      that pays (:meth:`ContinuousBatchingChannel._step_wait_locked`).
    - *solo*: a session frame (its state advances per stream and per
      frame), a lone ragged request, a lone step: the original request
      goes down as it came.

``ContinuousBatchingChannel`` is itself a BaseChannel, so it stacks
under the gRPC façade or above TPUChannel unchanged, including in front
of the mesh-sharded channel — ragged batches are then packed
SHARD-major (``ShardedRaggedLayout``) so each device gets whole
segments and the sharded body needs no collectives.
"""

from __future__ import annotations

import bisect
import collections
import concurrent.futures
import itertools
import logging
import math
import threading
import time

import numpy as np

from triton_client_tpu.channel.base import (
    BaseChannel,
    InferRequest,
    InferResponse,
)
from triton_client_tpu.obs.trace import LaunchRecord
from triton_client_tpu.parallel.ragged_kernels import (
    RaggedLayout,
    pack_rows,
    shard_layout,
    shard_pack_rows,
    shard_stack_segments,
)
from triton_client_tpu.runtime import faults
from triton_client_tpu.runtime.admission import (
    AdmissionRejectedError,
    DeadlineExpiredError,
    QueueFullError,
)
from triton_client_tpu.runtime.padding import bucket_for, pad_batch, pad_rows

log = logging.getLogger(__name__)


def _merge_key(request: InferRequest):
    if request.sequence_id:
        # streaming-session frames NEVER merge: the device-resident
        # tracking step (runtime/sessions.py) consumes the launch's
        # outputs per stream and per frame — batching two streams (or
        # two frames of one) into a single launch would interleave
        # their state advances. A unique key makes every session frame
        # a group of one, dispatched through the solo path.
        return ("__session__", id(request))
    return (
        request.model_name,
        request.model_version,
        tuple(
            (name, np.asarray(a).shape[1:], np.asarray(a).dtype.str)
            for name, a in sorted(request.inputs.items())
        ),
    )


class _Answer:
    """One request's answer, handed from the thread that resolves its
    launch to the caller that waits in :meth:`do_inference`: the part
    of ``concurrent.futures.Future`` this batcher uses (set once, one
    waiter) on one bare lock. A Future builds a condition for every
    request and its waiter's lock besides, and wakes through both."""

    __slots__ = ("_ready", "_value", "_error", "_done")

    def __init__(self) -> None:
        self._ready = threading.Lock()
        self._ready.acquire()
        self._value = self._error = None
        self._done = False

    def set_result(self, value) -> None:
        self._value, self._done = value, True
        self._ready.release()

    def set_exception(self, error: BaseException) -> None:
        self._error, self._done = error, True
        self._ready.release()

    def done(self) -> bool:
        return self._done

    def exception(self) -> BaseException | None:
        return self._error

    def result(self):
        with self._ready:  # released again: a second reader passes too
            pass
        if self._error is not None:
            raise self._error
        return self._value


def _is_step(request: InferRequest, step_shapes: list | None) -> bool:
    """Whether a request's inputs are ONE step of a model whose steps
    merge (``step_shapes``: ``_model_facts``; None for any other model)."""
    if step_shapes is None:
        return False
    try:
        return sorted(a.shape for a in request.inputs.values()) == step_shapes
    except Exception:
        return False


def _rows(request: InferRequest) -> int:
    """Batch rows of a request: the leading dimension of its first input."""
    return next(
        iter(int(np.asarray(a).shape[0]) for a in request.inputs.values())
    )


class LiveBuckets:
    """Pad-bucket table learned from the live merge-size distribution.

    The static power-of-two table pads a steady stream of 6-frame
    merges to 8 forever — a 25% tax the workload never stops paying.
    This table watches the totals the dispatcher actually forms and
    promotes the frequent ones (>= ``min_share`` of observations, top
    ``max_sizes``) to first-class buckets, so recurring sizes pad to
    themselves. Rare sizes still fall back to the static table, keeping
    the compiled-shape set bounded: at most ``max_sizes`` learned
    entries + log2 static ones. Every entry is rounded up to
    ``multiple`` so a sharded inner channel can always split it.

    Callers synchronize externally (the batcher's ``_ready_cv``)."""

    def __init__(
        self,
        multiple: int = 1,
        max_sizes: int = 6,
        min_share: float = 0.10,
        warmup: int = 32,
    ) -> None:
        self._multiple = max(1, int(multiple))
        self._max_sizes = int(max_sizes)
        self._min_share = float(min_share)
        self._warmup = int(warmup)
        self._seen: collections.Counter = collections.Counter()
        self._n = 0
        self._table: tuple[int, ...] = ()

    def observe(self, total: int) -> None:
        m = self._multiple
        self._seen[((max(1, total) + m - 1) // m) * m] += 1
        self._n += 1
        # re-derive on a stride: the table is a snapshot, not a cache
        # that must be exact per observation
        if self._n >= self._warmup and self._n % 16 == 0:
            floor = self._min_share * self._n
            self._table = tuple(
                sorted(
                    s
                    for s, c in self._seen.most_common(self._max_sizes)
                    if c >= floor
                )
            )

    def target(self, total: int) -> int:
        """Smallest learned bucket >= total; static table fallback."""
        for size in self._table:
            if size >= total:
                return size
        return bucket_for(total, self._multiple)

    @property
    def table(self) -> tuple[int, ...]:
        return self._table


def _running_mean(mean: float | None, sample: float) -> float:
    """A mean that follows the last eight or so samples (the first
    sample, while there is none)."""
    return sample if mean is None else mean + (sample - mean) / 8.0


class _StepPace:
    """What the batcher has observed of ONE model's session steps: the
    times that decide whether a step group waits at a free device
    (:meth:`ContinuousBatchingChannel._step_wait_locked`) and when it
    closes behind a step launch that is still on the device
    (:meth:`ContinuousBatchingChannel._early_wait_locked`). Guarded by
    the batcher's ``_ready_cv``."""

    __slots__ = (
        "launch_s", "return_s", "return_dev_s", "answered", "device_s", "lead_s",
        "cohort",
    )

    def __init__(self, launch_s: float) -> None:
        # T: a step group's dispatch (or, where it was enqueued behind
        # another launch, that launch's end on the device) -> its
        # answers handed back
        self.launch_s = launch_s
        # a step launch's start on the device -> its outputs ready
        # there, and a step group's close -> its launch enqueued
        # (dispatcher, executor, stage, launch); None until observed
        self.device_s: float | None = None
        self.lead_s: float | None = None
        # sessions a step launch answers: the returns above followed
        # launches of about that many
        self.cohort: float | None = None
        # h: a step's answer -> the same session's next step staged (a
        # session not back after two launches counts as two launches),
        # and how far a return lies from that mean
        self.return_s: float | None = None
        self.return_dev_s = 0.0
        # open sessions whose last request was a step: when it was
        # answered
        self.answered: dict[str, float] = {}

    def returned(self, after_s: float) -> None:
        """One more return observed: the mean and the mean deviation,
        kept as a round-trip timer keeps them."""
        if self.return_s is None:
            self.return_s, self.return_dev_s = after_s, after_s / 2.0
            return
        self.return_dev_s += (abs(after_s - self.return_s) - self.return_dev_s) / 4.0
        self.return_s = _running_mean(self.return_s, after_s)

    def observe_launch(
        self, sessions: int, lead_s: float, device_s: float | None
    ) -> None:
        """One more step launch: the sessions it answered, its group's
        close to its launch enqueued, and its time on the device where
        that was observed."""
        self.cohort = _running_mean(self.cohort, sessions)
        self.lead_s = _running_mean(self.lead_s, lead_s)
        if device_s is not None:
            self.device_s = _running_mean(self.device_s, device_s)

    def back_within_s(self) -> float:
        """The time within which nearly every session is back: the mean
        return and two mean deviations. A wait for the LAST of the
        missing sessions pays only where this is under one launch (the
        mean alone says when half of them are back)."""
        return self.return_s + 2.0 * self.return_dev_s

    def forget_stale(self, now: float) -> None:
        """Sessions that are not back after two launches: counted as a
        return of that length, then forgotten (one that went away with
        no ``sequence_end`` must not be remembered for ever)."""
        limit = 2.0 * self.launch_s
        for sid in [s for s, t in self.answered.items() if now - t >= limit]:
            del self.answered[sid]
            self.returned(limit)


class _Formed:
    """One formed group from its close to its resolution, as
    ``free_slot`` on its way through ``_run_group``: called, it frees
    the group's execution slot (once: at its launch, or when the group
    resolves without one). Beside that it keeps what a step group
    BEHIND this one reads of it
    (:meth:`ContinuousBatchingChannel._early_wait_locked`). Guarded by
    the batcher's ``_ready_cv``."""

    __slots__ = (
        "key", "members", "closed_t", "launched_t", "ready_t", "device_s",
        "behind", "clear_t", "early", "_chan",
    )

    def __init__(
        self, chan, key, members: int, closed_t: float, behind, early: bool
    ) -> None:
        self._chan = chan
        self.key = key
        self.members = members
        self.closed_t = closed_t
        # enqueued on the inner channel (``do_inference_async`` back),
        # and its outputs ready ON THE DEVICE, before their readback
        self.launched_t: float | None = None
        self.ready_t: float | None = None
        # ready - start, where the inner channel said when (else None)
        self.device_s: float | None = None
        # the group it was closed behind while that one is still on the
        # device, then None and ``clear_t`` says when that one left it
        self.behind: _Formed | None = behind
        self.clear_t: float | None = None
        # a step group closed while a launch was ahead of it
        self.early = early

    @property
    def steps(self) -> bool:
        return self.key[0] == "__session_step__"

    def start_t(self) -> float | None:
        """When this launch began on the device, once that is known: the
        later of its enqueueing and the end of the launch ahead of it."""
        if self.launched_t is None or self.behind is not None:
            return None
        return max(self.launched_t, self.clear_t or 0.0)

    def __call__(self) -> None:
        self._chan._launched(self)

    def off_device(self) -> None:
        self._chan._off_device(self)


class ContinuousBatchingChannel(BaseChannel):
    """Windowless EDF scheduler: dense, ragged and session-step merges
    (see module docstring)."""

    def __init__(
        self,
        inner: BaseChannel,
        max_batch: int = 8,
        capacity: int = 256,
        pipeline_depth: int = 2,
        max_merge: int | None = None,
        shed_expired: bool = False,
        live_buckets: bool = True,
    ) -> None:
        """``capacity``: staged requests the ready set holds before
        ``do_inference`` refuses with ``QueueFullError``.

        ``pipeline_depth``: formed groups executing concurrently
        against the inner channel. At the default 2, group N+1's
        host->device transfer overlaps group N's execution (the role
        Triton's per-instance CUDA streams play); jax queues the
        dispatches and the device serializes execution. Depth 1
        restores strictly serial execution.

        ``max_merge``: row cap for one device batch (default:
        ``max_batch`` per device of a sharded inner channel). On a
        dispatch-bound path the per-call fixed cost amortizes over
        max_merge rows.

        ``shed_expired`` (overload control): at dispatch time, members
        whose deadline already passed are FAILED with
        ``DeadlineExpiredError`` and never reach the device — the
        merged batch would otherwise inherit the expired member's
        deadline and be shed whole by the inner channel. Off by
        default (count-only behavior).

        ``live_buckets``: dense pad targets follow the live occupancy
        histogram (:class:`LiveBuckets`); off, the static power-of-two
        table.

        Slot lifetime (overlapped dispatch): an execution slot frees
        at *launch*, not at readback. Each group dispatches through
        ``inner.do_inference_async`` and releases its permit as soon as
        the call returns (inputs staged on device, compute enqueued);
        the split/respond work then runs outside the permit, so batch
        formation self-clocks off device occupancy instead of host copy
        time. When the inner channel exposes a ``pipeline_depth``
        staging knob (TPUChannel), it is aligned to this batcher's
        depth so the channel's staging slots provide the device-side
        backpressure.

        When a group closes: a pass-through, dense, ragged or solo
        group the moment a permit frees, which is up to
        ``pipeline_depth`` launches before it runs; that look-ahead is
        what hides a large group's host->device transfer. A group of
        SESSION STEPS ships four bytes a session, so it stays open
        instead, absorbing arrivals, and closes by what the batcher
        observes of the model's steps (:class:`_StepPace`), no option.
        Where nearly every session is back within a launch's time: when
        every group formed before it has been answered, and then, at
        the free device, after a wait for the sessions that are about
        to come back (:meth:`_step_wait_locked`). Where the sessions
        take longer than that, the ones in the launch ahead will not
        make the next launch anyway: behind a step launch of the same
        model the group closes one measured close-to-launch time
        before that launch is due off the device, at the latest the
        moment its outputs are ready there, so that the device takes
        the next launch with no gap and the launch ahead is read back
        and answered meanwhile (:meth:`_early_wait_locked`). Behind
        anything else (a prompt, another model's group, a group that
        itself still waits for the device) it stays open until that
        has been answered."""
        self._inner = inner
        self._capacity = max(1, int(capacity))
        self._ids = itertools.count(1)
        # a mesh-sharded inner channel declares its data-axis width as
        # the preferred batch divisor: merged groups then grow to
        # max_batch frames PER DEVICE (max_batch x data_axis total) and
        # pad buckets stay divisible by the axis, so batcher padding and
        # shard padding agree on the same table (runtime/padding.py)
        self._batch_multiple = max(1, int(getattr(inner, "batch_multiple", 1)))
        self._max_merge = int(
            max_merge
            if max_merge is not None
            else max_batch * self._batch_multiple
        )
        self._live_buckets = (
            LiveBuckets(multiple=self._batch_multiple) if live_buckets else None
        )
        self._pipeline_depth = max(1, int(pipeline_depth))
        self._inflight = threading.Semaphore(max(1, pipeline_depth))
        self._exec = concurrent.futures.ThreadPoolExecutor(
            max_workers=max(1, pipeline_depth),
            thread_name_prefix="batch-exec",
        )
        # the ready set: staged (key, rows, request, future, t_staged)
        # items, an EDF-SORTED list (_edf_key), waiting for a slot
        self._ready: list = []
        self._ready_cv = threading.Condition()
        self._dispatch_stop = False
        # dispatcher heartbeat (stall watchdog): stamped every time the
        # dispatch loop makes observable progress — top of each slot AND
        # inside the idle cv-wait, so "idle" stays fresh and only a
        # genuinely wedged dispatcher (batcher_stall exhausting the
        # permit semaphore, a hung device call) goes stale. The
        # watchdog thread logs loudly past stall_threshold_s and the
        # age/stalled pair rides stats() into the collector.
        self.stall_threshold_s = 5.0
        self._hb_ts = time.perf_counter()
        self._stall_logged = False
        self._merge_stats = {
            "merges": 0, "merged_frames": 0, "padded_frames": 0,
            "launch_frees": 0,
            # dense groups handed to the inner channel without a copy
            # (one member, no pad rows), and the bytes of the buffers
            # the batcher did build for all the others
            "passthrough_groups": 0, "merged_bytes": 0,
            # step groups that waited at a FREE device for sessions
            # about to come back, the seconds so waited, the sessions
            # that came in meanwhile, and the waits that ran out
            "step_holds": 0, "step_hold_s": 0.0,
            "step_hold_joined": 0, "step_hold_expired": 0,
            # step groups closed while a launch was ahead of them, those
            # of them closed by the event (the launch ahead's outputs
            # ready on the device) and not by the prediction, and the
            # steps of their key staged between such a close and the end
            # of the launch ahead: what the early close cost
            "step_early_closes": 0, "step_early_by_event": 0,
            "step_early_missed": 0,
        }
        # groups formed and not yet resolved (answers handed back or
        # failed), oldest first: none means no launch is ahead of the
        # next group on the device (_launches_ahead); when that list
        # last emptied, and when it last changed
        self._formed: list[_Formed] = []
        self._device_free_t = time.perf_counter()
        self._ahead_changed_t = self._device_free_t
        # (model, version) -> _StepPace; the wait in progress at a free
        # device: (began, the steps' key, its members in the ready set
        # then)
        self._step_pace: dict = {}
        self._last_pace: _StepPace | None = None
        self._hold: tuple | None = None
        self._ragged_stats = {
            "ragged_batches": 0,
            "ragged_segments": 0,
            "ragged_rows": 0,
            "ragged_pad_rows": 0,
        }
        # padding-tax attribution: pad frames per MODEL, so the
        # Prometheus counter can carry a model label and an operator
        # can see WHICH model's buckets waste device rows
        self._padded_by_model: collections.Counter = collections.Counter()
        self._shed_expired = bool(shed_expired)
        # per "model|priority|stage" shed counts ("queue" = ready set
        # full, "merge" = deadline expired at dispatch), merged into
        # the collector's tpu_serving_shed_total family
        self._shed: collections.Counter = collections.Counter()
        self._merge_occupancy: collections.Counter = collections.Counter()
        # per-slot occupancy: concurrently-active execution slots
        # observed at each group launch (1..pipeline_depth)
        self._active_slots = 0
        self._slot_occupancy: collections.Counter = collections.Counter()
        # per-batch wall decomposition sums (stats() exposes means):
        # queue_wait (first item staged -> executor slot), exec_wait
        # (submit -> run), stage (host merge build), device (inner
        # channel call), respond (split + future resolution)
        self._decomp = collections.defaultdict(float)
        # (model, version) -> what the scheduler reads off the model's
        # spec (_model_facts); filled lazily from inner.get_metadata so
        # registration order doesn't matter. Filled from RPC threads AND
        # the dispatcher/executor threads, so writes go through
        # _model_facts_lock (the metadata RPC itself runs outside the
        # lock; racing fillers converge via setdefault). The facts of a
        # repository that has changed since (a model registered,
        # reloaded or unregistered) are dropped whole, where the inner
        # channel can say so (StagedChannel.models_generation)
        self._model_facts_cache: dict = {}
        self._model_facts_lock = threading.Lock()
        self._models_generation = getattr(
            inner, "models_generation", lambda: None
        )
        self._facts_generation = self._models_generation()
        # optional multi-tenant fair share (runtime/lifecycle.py
        # TenantTable): deficit-round-robin virtual time folded into the
        # EDF key — set via attach_tenants(); None keeps pure EDF
        self._tenant_table = None
        self._fair_quantum_s = 0.005
        self._vtime: dict[str, float] = {}
        self._tenant_frames: collections.Counter = collections.Counter()
        # plumb the depth through to the inner channel's staging slots
        # (TPUChannel double-buffers H2D against execution at depth 2):
        # the channel then backpressures on device occupancy while this
        # batcher's permits backpressure on formed groups
        if hasattr(inner, "pipeline_depth"):
            try:
                inner.pipeline_depth = max(1, int(pipeline_depth))
            except (AttributeError, TypeError):
                pass  # read-only attribute on a custom channel
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, daemon=True, name="batch-dispatch"
        )
        self._dispatcher.start()
        self._watchdog_stop = threading.Event()
        self._watchdog = threading.Thread(
            target=self._watchdog_loop, daemon=True, name="batch-watchdog"
        )
        self._watchdog.start()

    # -- BaseChannel ----------------------------------------------------------

    @property
    def inner(self) -> BaseChannel:
        """The wrapped channel (obs.RuntimeCollector walks the stack)."""
        return self._inner

    def register_channel(self) -> None:
        self._inner.register_channel()

    def fetch_channel(self):
        return self._inner.fetch_channel()

    def get_metadata(self, model_name: str, model_version: str = ""):
        return self._inner.get_metadata(model_name, model_version)

    # -- admission: straight into the EDF ready set ---------------------------

    def attach_tenants(self, table, quantum_s: float = 0.005) -> None:
        """Fold deficit-round-robin fair share over a TenantTable
        (runtime/lifecycle.py) into the ready ordering. Each tenant
        accrues virtual time ``frames / share`` as its work dispatches;
        a tenant ahead of the pack (``lag`` = its vtime minus the
        minimum) has its requests' effective deadlines pushed back by
        ``lag * quantum_s``, so a low-share tenant flooding the queue
        cannot starve a high-share tenant's SLO — the backlogged
        tenant's own requests sort later, they are not dropped.

        Ordering is approximate by design: ``insort`` re-evaluates the
        key against items placed under older vtimes, so the ready set
        drifts slightly as lags move. DRR only needs the drift to be
        bounded (it is — charges are applied at group formation under
        ``_ready_cv`` and lags renormalize), not a total order."""
        with self._ready_cv:
            self._tenant_table = table
            self._fair_quantum_s = float(quantum_s)

    def _edf_key(self, item):
        """Sort key over staged items: earliest deadline first,
        deadline-less requests last; higher priority breaks ties and
        ``insort`` keeps arrival order inside a class. With a tenant
        table attached, a tenant's DRR lag pushes its effective
        deadline back (deadline-less items order by lag directly)."""
        request = item[2]
        deadline = (
            request.deadline_s if request.deadline_s is not None else math.inf
        )
        table = self._tenant_table
        if table is None:
            return (deadline, -request.priority, 0.0)
        lag = 0.0
        if self._vtime:
            floor = min(self._vtime.values())
            lag = max(
                0.0,
                self._vtime.get(table.tenant_of(request.model_name), floor)
                - floor,
            )
        return (deadline + lag * self._fair_quantum_s, -request.priority, lag)

    def _charge_tenants_locked(self, group) -> None:
        """DRR accounting at group formation (caller holds
        ``_ready_cv``): each dispatched frame charges its tenant
        ``1 / share`` virtual time, so equal traffic advances a
        share-4 tenant's clock 4x slower than a share-1 tenant's."""
        table = self._tenant_table
        floor = min(self._vtime.values()) if self._vtime else 0.0
        for item in group:
            request, frames = item[2], item[1]
            tenant = table.tenant_of(request.model_name)
            self._vtime[tenant] = self._vtime.get(tenant, floor) + (
                frames / table.share(tenant)
            )
            self._tenant_frames[tenant] += frames
        # renormalize so vtimes (and the lags derived from them) stay
        # bounded over long uptimes
        floor = min(self._vtime.values())
        if floor > 1e6:
            for tenant in self._vtime:
                self._vtime[tenant] -= floor

    def do_inference(self, request: InferRequest) -> InferResponse:
        future = _Answer()
        if request.trace is not None:
            # closed at dispatch time (_open_group/_run_solo): ready-set
            # wait + slot backpressure, end to end
            request.trace.begin("batch_queue")
        ragged_names, step_shapes, step_key = self._model_facts(
            request.model_name, request.model_version
        )
        session_step = False
        if request.sequence_id:
            # session frames bypass BOTH merge paths (ragged packing
            # included): _merge_key solos them, so the tracking step
            # sees exactly one stream's frame per launch in order —
            # unless the model declares mergeable sessions
            ragged_names = None
            session_step = _is_step(request, step_shapes)
        if session_step:
            # one new token of a session whose state is a slot of the
            # model's own device cache: the steps of DIFFERENT sessions
            # merge into one launch under the model's key
            key = step_key
            size = 1
        elif ragged_names:
            # one segment per request: same-model ragged requests merge
            # regardless of their (wildly varying) row counts — that
            # variance is exactly what the packed layout absorbs
            key = ("__ragged__", request.model_name, request.model_version)
            size = 1
        else:
            try:
                key = _merge_key(request)
                size = _rows(request)
            except Exception:
                key, size = ("__solo__", next(self._ids)), 1
        with self._ready_cv:
            if len(self._ready) >= self._capacity:
                # fail-fast, never block the submitting RPC thread: the
                # server surfaces this as RESOURCE_EXHAUSTED, which the
                # client retry ladder treats as non-retryable for
                # ModelInfer — shedding must not amplify offered load
                self._shed[
                    f"{request.model_name}|{request.priority}|queue"
                ] += 1
                raise QueueFullError(
                    f"model '{request.model_name}': inference queue full"
                )
            now = time.perf_counter()
            if request.sequence_id:
                self._observe_session_request_locked(request, session_step, now)
            if session_step and any(
                f.early and f.behind is not None and f.key == key
                for f in self._formed
            ):
                # its group closed early and the launch ahead of that
                # still runs: closed when that launch ends, the group
                # would have taken this step
                self._merge_stats["step_early_missed"] += 1
            item = (key, size, request, future, now)
            if not self._ready or self._edf_key(self._ready[-1]) <= self._edf_key(item):
                # the common arrival (no deadline, or the latest one)
                # goes where insort would put it, without the bisection
                self._ready.append(item)
            else:
                bisect.insort(self._ready, item, key=self._edf_key)
            self._ready_cv.notify()
        return future.result()

    def _observe_session_request_locked(
        self, request: InferRequest, is_step: bool, now: float
    ) -> None:
        """A session's request reaches the ready set: if its last one
        was a step, how long it took to come back (``_StepPace.return_s``;
        capped at two launches, where :meth:`_StepPace.forget_stale`
        would have counted it). Either way the session is no longer one
        to wait for: it is here, or its next answer is no step's."""
        pace = self._step_pace.get((request.model_name, request.model_version))
        if pace is None:
            return
        answered = pace.answered.pop(request.sequence_id, None)
        if is_step and answered is not None:
            pace.returned(min(now - answered, 2.0 * pace.launch_s))

    # -- dispatch (forms the device batch when a slot frees) ------------------

    def _dispatch_loop(self) -> None:
        while True:
            try:
                if self._dispatch_once():
                    return
            except Exception:
                # The dispatcher is the only thread that forms batches:
                # an escaped error here would stall every later
                # do_inference forever on future.result(). Log and keep
                # serving; the failed slot's futures were already
                # failed by _dispatch_once.
                log.exception("dispatcher slot failed; dispatcher continues")

    def _beat(self) -> None:
        """Stamp the dispatcher heartbeat. Single writer (the dispatch
        thread); the watchdog and stats() only read, and a monotonic
        float store is atomic in CPython — deliberately lock-free so
        the heartbeat itself can never contend with dispatch."""
        self._hb_ts = time.perf_counter()

    def dispatcher_progress_age_s(self) -> float:
        """Seconds since the dispatch loop last made progress (slot
        start or idle wait). Small under load and at rest; grows only
        when the dispatcher is wedged."""
        return max(0.0, time.perf_counter() - self._hb_ts)

    def _watchdog_loop(self) -> None:
        """Stall watchdog: the batcher_stall fault (and any real hang —
        a device call that never returns, a deadlocked executor) can
        freeze the single dispatcher with NO signal: requests just
        queue forever. Log loudly once per stall episode, and again on
        recovery, so the operator sees the window edges."""
        poll = max(0.25, self.stall_threshold_s / 4.0)
        while not self._watchdog_stop.wait(poll):
            age = self.dispatcher_progress_age_s()
            if age >= self.stall_threshold_s:
                if not self._stall_logged:
                    self._stall_logged = True
                    log.error(
                        "dispatcher STALLED: no progress for %.1fs "
                        "(threshold %.1fs) — ready_depth=%d, "
                        "active_slots=%d; requests are queuing",
                        age, self.stall_threshold_s,
                        len(self._ready), self._active_slots,
                    )
            elif self._stall_logged:
                self._stall_logged = False
                log.warning("dispatcher recovered after stall")
            poll = max(0.25, self.stall_threshold_s / 4.0)

    def _dispatch_once(self) -> bool:
        """One dispatcher slot: acquire a permit, form the EDF head's
        group, submit. A group of any kind but session steps closes
        here the moment the permit is held and something is ready; a
        group of session steps closes when :meth:`_head_wait_locked`
        says so (behind a step launch that is about to leave the
        device, :meth:`_early_wait_locked`; or nothing ahead of it and
        nobody worth waiting for, :meth:`_step_wait_locked`), and keeps
        absorbing arrivals in the ready set until then.
        Returns True when the loop should exit (close() requested and
        the ready set is drained: close() waits for nobody). Any
        unexpected error fails the formed group's futures, releases the
        permit, and re-raises for the loop to log — the thread itself
        survives."""
        self._beat()
        self._inflight.acquire()
        self._beat()
        group = None
        try:
            with self._ready_cv:
                while not self._dispatch_stop:
                    wait_s = self._head_wait_locked()
                    if wait_s is None:
                        break
                    self._ready_cv.wait(timeout=min(wait_s, 0.1))
                    # idle is progress, and so is an open step group
                    # while the launches ahead of it keep resolving:
                    # only a dispatcher that cannot reach this loop
                    # (wedged on the permit semaphore or a hung group),
                    # or one whose steps wait behind a launch that has
                    # not moved for a whole threshold, lets the
                    # heartbeat go stale
                    if not (
                        self._ready
                        and self._launches_ahead
                        and time.perf_counter() - self._ahead_changed_t
                        >= self.stall_threshold_s
                    ):
                        self._beat()
                if self._ready:
                    group = self._form_group_locked()
                    self._merge_stats["merges"] += 1
                    frames = sum(it[1] for it in group)
                    self._merge_stats["merged_frames"] += frames
                    self._merge_occupancy[frames] += 1
                    self._active_slots += 1
                    slot = self._formed_locked(group[0][0], len(group))
                elif self._dispatch_stop:
                    self._inflight.release()
                    return True
            if group is None:
                self._inflight.release()
                return False

            def run(g=group, free_slot=slot, t_submit=time.perf_counter()):
                t_run = time.perf_counter()
                with self._ready_cv:
                    self._decomp["n"] += 1
                    self._decomp["exec_wait_s"] += t_run - t_submit
                    self._decomp["queue_wait_s"] += t_run - min(
                        it[4] for it in g
                    )
                    # PER-MEMBER queue delay, not just the merged
                    # batch's (one shared number): each member's own staging
                    # timestamp to this dispatch
                    self._decomp["members"] += len(g)
                    self._decomp["member_wait_s"] += sum(
                        t_run - it[4] for it in g
                    )
                # the slot frees the moment the group LAUNCHES (inputs
                # staged, compute enqueued on the inner channel) — the
                # dispatcher can then form the next batch against
                # device occupancy while this group's readback/split
                # still runs. Exactly-once (_launched): the finally
                # covers groups whose launch never happened (errors
                # before dispatch).
                try:
                    # (t_staged, request, future): the staging timestamp
                    # rides along so each member gets its own merge_wait
                    # span (staged -> this group's dispatch)
                    self._run_group(
                        [(it[4], it[2], it[3]) for it in g], free_slot
                    )
                except Exception as e:
                    # No exception may escape: an unresolved future
                    # hangs its caller forever.
                    for it in g:
                        if not it[3].done():
                            it[3].set_exception(e)
                finally:
                    free_slot()
                    self._group_resolved(g, t_run, free_slot)

            try:
                self._exec.submit(run)
            except RuntimeError as e:  # executor shut down mid-close
                with self._ready_cv:
                    self._active_slots -= 1
                    self._unform_locked(slot, time.perf_counter())
                self._inflight.release()
                for it in group:
                    if not it[3].done():
                        it[3].set_exception(e)
            return False
        except Exception as e:
            self._inflight.release()
            if group:
                for it in group:
                    if not it[3].done():
                        it[3].set_exception(e)
            raise

    def _form_group_locked(self):
        """Pop the EDF head, then walk the (still-sorted) ready set
        absorbing same-key items under the frame cap — later-deadline
        compatible work rides along with the most urgent request's
        launch. Incompatible items stay in place, keeping their EDF
        positions for the next slot (caller holds ``_ready_cv``)."""
        first = self._ready.pop(0)
        group = [first]
        frames = first[1]
        i = 0
        while i < len(self._ready) and frames < self._max_merge:
            item = self._ready[i]
            if (
                item[0] == first[0]
                and frames + item[1] <= self._max_merge
                # at most one request of a session in a launch: a later
                # one keeps its place and waits its turn
                and (
                    not item[2].sequence_id
                    or all(item[2].sequence_id != g[2].sequence_id for g in group)
                )
            ):
                group.append(self._ready.pop(i))
                frames += item[1]
            else:
                i += 1
        if self._tenant_table is not None:
            self._charge_tenants_locked(group)
        return group

    # -- when a group of session steps closes ----------------------------------

    @property
    def _launches_ahead(self) -> int:
        return len(self._formed)

    def _head_wait_locked(self) -> float | None:
        """Seconds until the dispatcher should look again, or None: form
        the EDF head's group now. Only a head that is a session step
        ever waits with something ready: behind a launch
        (:meth:`_early_wait_locked`; ``step_early_closes`` and its two
        companions count the groups that closed there), or at a FREE
        device (:meth:`_step_wait_locked`), which is what
        ``step_holds`` and its three companions count."""
        if not self._ready:
            return 0.1
        key = self._ready[0][0]
        now = time.perf_counter()
        wait_s, missing = None, 0
        if key[0] == "__session_step__":
            if self._launches_ahead:
                # a launch is ahead: the group stays open, absorbing
                # arrivals, until that launch is about to leave the
                # device or, where that is not the batcher's to
                # foresee, until it has been answered (each event of
                # the launch ahead notifies)
                return self._early_wait_locked(key, now)
            wait_s, missing = self._step_wait_locked(key, now)
        hold = self._hold
        if hold is not None and (wait_s is None or hold[1] != key):
            # the wait is over: the last one came in, the time ran out
            # (somebody is still missing), or other work became ready
            began, step_key, members_then = hold
            self._hold = None
            self._merge_stats["step_holds"] += 1
            self._merge_stats["step_hold_s"] += now - began
            self._merge_stats["step_hold_joined"] += (
                self._members_locked(step_key) - members_then
            )
            if missing and step_key == key:
                self._merge_stats["step_hold_expired"] += 1
        if wait_s is not None and self._hold is None:
            self._hold = (now, key, self._members_locked(key))
        return wait_s

    def _members_locked(self, key) -> int:
        return sum(1 for it in self._ready if it[0] == key)

    def _step_wait_locked(self, key, now: float) -> tuple[float | None, int]:
        """The device is free and the EDF head is a session step of the
        model of ``key``: ``(seconds to wait, sessions still missing)``,
        seconds None where the group closes now.

        The group waits only where a wait buys whole launches. A session
        is EXPECTED while it is open, its last request was a step, and
        less than ``T`` (the model's mean step launch) has passed since
        that answer. Where nothing else is ready for the device and the
        sessions come back within one launch's time (their mean return
        ``h`` and two mean deviations are under ``T``:
        :meth:`_StepPace.back_within_s`), the group waits for the
        expected sessions that are missing from it: until the last is in
        or stops being expected, or ``T`` after the device went free,
        whichever is first. So the wait comes out of the device's idle
        time alone, costs at most the one launch a joining session
        saves, and lets nobody overtake: the head stays the head. All
        three times are the batcher's own observations
        (:class:`_StepPace`). A model whose sessions take about as long
        to come back as a launch lasts (many callers on one interpreter:
        returns of 14-38 ms around a launch of 24) is never waited for:
        there the last of a launch's sessions is not back in time, every
        wait would run out, and a wait that runs out costs the sessions
        that were ready a launch's time for nothing."""
        pace = self._step_pace.get(key[1:])
        if pace is None:
            return None, 0
        pace.forget_stale(now)
        if pace.return_s is None or pace.back_within_s() >= pace.launch_s:
            return None, 0
        here = set()
        for it in self._ready:
            if it[0] != key:
                return None, 0  # something else is ready for the device
            here.add(it[2].sequence_id)
        # expected when this wait began (now, if none has)
        hold = self._hold
        began = hold[0] if hold is not None and hold[1] == key else now
        missing = [
            t
            for sid, t in pace.answered.items()
            if sid not in here and began - t < pace.launch_s
        ]
        if not missing or len(here) >= self._max_merge:
            return None, 0
        wait_s = min(self._device_free_t, max(missing)) + pace.launch_s - now
        return (wait_s if wait_s > 0 else None), len(missing)

    def _early_wait_locked(self, key, now: float) -> float | None:
        """A launch is ahead and the EDF head is a session step of the
        model of ``key``: seconds until the dispatcher should look
        again, or None where the group closes now, BEHIND the launch
        that runs, so that its stage and launch are done when the
        device is ready to take it.

        That is only where everything ahead is step launches of the
        same model, at most one of them still on the device and that
        one enqueued there (no second group closes behind one that
        itself still waits for the device), and where the sessions IN
        the launch ahead are not worth waiting for: they would not be
        back within a launch's time (:meth:`_StepPace.back_within_s`;
        where they would, the group stays open until the launch ahead is
        answered and then waits at the free device,
        :meth:`_step_wait_locked`). A launch that waited for them would
        answer the members that are ready AND the sessions ahead at
        once, and a return grows with the sessions answered together
        (their callers share an interpreter lock on each side), so the
        returns observed count in the proportion of that many sessions
        to those the observed returns followed (the model's mean
        sessions a step launch, or the sessions ahead if they are
        more): two cohorts that take turns stay two (each would be back
        in time alone, both together would not), while a straggler
        behind a launch that holds everybody else is no reason to split
        them. The group then closes ``lead``
        before the launch ahead is DUE off the device: ``due`` is that
        launch's start on the device plus the model's mean device time
        of a step launch, ``lead`` the mean time from a step group's
        close to its launch enqueued, both the batcher's own
        observations (:class:`_StepPace`). The prediction's latest
        bound is an event: the group closes at the latest when the
        launch ahead's outputs are ready on the device, before their
        readback and before any of its answers is handed back. A step
        staged between an early close and that event misses one launch
        (``step_early_missed``), which is why the lead is no longer
        than the time a close takes to reach the device. Anywhere else
        (a prompt or another model's group ahead, an inner channel that
        cannot say when a launch left the device) the group stays open
        until the launch ahead has been answered."""
        stay = 0.1  # every event of the launch ahead notifies
        pace = self._step_pace.get(key[1:])
        if pace is not None:
            pace.forget_stale(now)
        if (
            pace is None
            or pace.device_s is None
            or pace.return_s is None
            or any(f.key != key for f in self._formed)
        ):
            return stay
        ahead = sum(f.members for f in self._formed)
        together = (self._members_locked(key) + ahead) / max(ahead, pace.cohort)
        if pace.back_within_s() * max(1.0, together) < pace.launch_s:
            return stay
        flying = [f for f in self._formed if f.ready_t is None]
        if not flying:
            return None  # the event: everything ahead has left the device
        if len(flying) > 1:
            return stay
        start = flying[0].start_t()
        if start is None:
            return stay
        wait_s = start + pace.device_s - pace.lead_s - now
        return wait_s if wait_s > 0 else None

    def _formed_locked(self, key, members: int) -> _Formed:
        """A group of ``members`` requests under ``key`` has just closed:
        its record, behind the groups formed before it."""
        now = time.perf_counter()
        last = self._formed[-1] if self._formed else None
        early = bool(self._formed) and key[0] == "__session_step__"
        slot = _Formed(
            self, key, members, now,
            behind=last if last is not None and last.ready_t is None else None,
            early=early,
        )
        if early:
            self._merge_stats["step_early_closes"] += 1
            if slot.behind is None:
                self._merge_stats["step_early_by_event"] += 1
        self._formed.append(slot)
        self._ahead_changed_t = now
        return slot

    def _launched(self, slot: _Formed) -> None:
        """``slot``'s group is enqueued on the inner channel (or resolves
        without a launch): its execution slot frees, once."""
        with self._ready_cv:
            if slot.launched_t is not None:
                return
            slot.launched_t = time.perf_counter()
            self._slot_occupancy[self._active_slots] += 1
            self._active_slots -= 1
            self._merge_stats["launch_frees"] += 1
            if self._ready:
                # a step group behind it can now tell when it is due
                self._ready_cv.notify_all()
        self._inflight.release()

    def _off_device(self, slot: _Formed) -> None:
        """The inner channel says ``slot``'s outputs are ready on the
        device (their readback is still to come)."""
        with self._ready_cv:
            now = time.perf_counter()
            start = slot.start_t()
            if slot.ready_t is None and start is not None:
                slot.device_s = now - start
            self._off_device_locked(slot, now)

    def _off_device_locked(self, slot: _Formed, now: float) -> None:
        if slot.ready_t is not None:
            return
        slot.ready_t = now
        for f in self._formed:
            if f.behind is slot:
                f.behind, f.clear_t = None, now
        if self._ready:
            # with nothing ready the dispatcher has nothing to decide,
            # and a wake-up here would only contend with the handback
            self._ready_cv.notify_all()

    def _unform_locked(self, slot: _Formed, now: float) -> None:
        """``slot``'s group is resolved: off the device at the latest
        now, and no longer ahead of anything."""
        self._off_device_locked(slot, now)
        self._formed.remove(slot)
        self._ahead_changed_t = now
        if not self._formed:
            self._device_free_t = now

    def _group_resolved(self, group, t_run: float, slot: _Formed) -> None:
        """A formed group's answers are handed back (or it failed): one
        launch fewer is ahead of the next group, and a group of session
        steps leaves behind how long it took (its close to its launch
        enqueued; its time on the device, where the inner channel said
        when that ended; its dispatch, or the end on the device of the
        launch it was enqueued behind, to its answers handed back) and
        which sessions may come back for more."""
        key = group[0][0]
        with self._ready_cv:
            now = time.perf_counter()
            self._unform_locked(slot, now)
            answered = key[0] == "__session_step__" and [
                it[2]
                for it in group
                if it[3].done() and it[3].exception() is None
            ]
            if answered:
                # a launch enqueued behind another did not hold the
                # device while it queued there: T leaves that time out
                took = now - max(t_run, slot.clear_t or 0.0)
                pace = self._step_pace.get(key[1:])
                if pace is None:
                    pace = self._step_pace[key[1:]] = _StepPace(took)
                else:
                    pace.launch_s = _running_mean(pace.launch_s, took)
                pace.observe_launch(
                    len(answered), slot.launched_t - slot.closed_t, slot.device_s
                )
                for request in answered:
                    if not request.sequence_end:
                        pace.answered[request.sequence_id] = now
                self._last_pace = pace
            self._ready_cv.notify_all()

    def _pad_target(self, total: int) -> int:
        """Padded device-batch size for a merged total: the live
        occupancy table (buckets track the sizes traffic actually
        produces), else the static power-of-two one; both kept divisible
        by a sharded inner channel's data axis."""
        if self._live_buckets is None:
            return bucket_for(total, self._batch_multiple)
        with self._ready_cv:
            self._live_buckets.observe(total)
            return self._live_buckets.target(total)

    # -- what the scheduler can observe of a model ----------------------------

    def _session_step(self, request: InferRequest) -> bool:
        """Whether this session request may share a launch with other
        sessions' requests: the model declares it
        (``spec.extra["session_merge"]``: its session state is a slot of
        its own device cache, runtime/sessions.py TokenSessions) and the
        request is ONE step of the model: as many tokens as the model
        says a step carries (``spec.extra["step_width"]``: one new
        token, or a block model's block, which has its one-element
        ``commit`` flag beside it); a request of any other shape (a
        prompt, a further turn) runs alone."""
        return _is_step(
            request,
            self._model_facts(request.model_name, request.model_version)[1],
        )

    def _ragged_names(self, model_name: str, model_version: str):
        """Packed-input names for a model with a segment-aware body
        (``spec.extra["ragged_inputs"]``), else None."""
        return self._model_facts(model_name, model_version)[0]

    def _model_facts(self, model_name: str, model_version: str) -> tuple:
        """``(ragged names, step shapes, step key)``: what this
        scheduler reads off a registered model's spec, resolved once a
        model and looked up afterwards (it sits on every request's
        path): the packed-input names of a segment-aware body or None;
        the sorted input shapes of ONE mergeable session step or None
        (:meth:`_session_step`); the key such steps merge under.
        Negative answers are kept too; a model the inner channel does
        not know is asked for again.

        Called from RPC threads (``do_inference``) and from the
        dispatcher/executor threads (``_group_kind``), so the cache fill
        is double-checked: the lock-free fast path covers the steady
        state, the metadata RPC runs unlocked (it can block), and the
        insert goes through ``setdefault`` under ``_model_facts_lock``
        so racing fillers agree on one winner. A filler that raced a
        change of the repository inserts into the cache it started
        from, which that change has dropped."""
        generation = self._models_generation()
        if generation != self._facts_generation:
            with self._model_facts_lock:
                if generation != self._facts_generation:
                    self._model_facts_cache = {}
                    self._facts_generation = generation
        cache = self._model_facts_cache
        key = (model_name, model_version)
        try:
            return cache[key]
        except KeyError:
            pass
        facts = (None, None, None)
        try:
            spec = self._inner.get_metadata(model_name, model_version)
            extra = getattr(spec, "extra", None) or {}
            declared = extra.get("ragged_inputs")
            shapes = step_key = None
            if extra.get("session_merge"):
                width = extra.get("step_width", 1)
                shapes = [(1, 1)] if width == 1 else [(1, 1), (1, width)]
                step_key = ("__session_step__", model_name, model_version)
            facts = (frozenset(declared) if declared else None, shapes, step_key)
        except Exception:
            return facts  # not registered now: asked again next time
        with self._model_facts_lock:
            return cache.setdefault(key, facts)

    # -- group execution (runs on the executor threads) -----------------------

    def _shed_expired_members(self, group) -> list:
        """Fail members whose deadline already passed (the batcher-merge
        shed point) and return the still-live remainder. A merged batch
        inherits its tightest member's deadline, so ONE expired member
        left in place would get the whole group shed at launch."""
        now = time.perf_counter()
        live = []
        for item in group:
            t_staged, request, future = item
            deadline = request.deadline_s
            if deadline is None or now <= deadline:
                live.append(item)
                continue
            if request.trace is not None:
                request.trace.end("batch_queue")
            with self._ready_cv:
                self._shed[
                    f"{request.model_name}|{request.priority}|merge"
                ] += 1
            future.set_exception(
                DeadlineExpiredError(
                    f"model '{request.model_name}': deadline expired "
                    f"{(now - deadline) * 1e3:.1f}ms before dispatch"
                )
            )
        return live

    def _group_kind(self, group) -> tuple:
        """``(kind, sizes, pad)`` of a formed group, from what the
        scheduler can observe: a session frame, a lone step or a lone
        ragged request is ``solo`` (the original request goes down, at
        its true size and with its sequence fields); several sessions'
        steps are ``session_steps``; members of a model with a
        ``ragged_fn`` are ``ragged``; the rest is dense — ONE member
        whose rows already are a launch size is ``passthrough``, any
        other group a ``dense`` merge of ``sizes`` rows plus ``pad``."""
        first = group[0][1]
        lone = len(group) == 1
        if first.sequence_id:
            return ("solo" if lone else "session_steps"), None, 0
        if self._ragged_names(first.model_name, first.model_version):
            return ("solo" if lone else "ragged"), None, 0
        try:
            sizes = [_rows(r) for (_t, r, _f) in group]
        except Exception:
            return "solo", None, 0  # no batch axis to merge along
        total = sum(sizes)
        # pad only when the ROUNDED size still fits max_merge: a
        # non-power-of-two max_merge (e.g. 6) must not round a total of
        # 6 up to 8 — past the cap and past any size the inner channel
        # precompiled. Oversized single requests (> max_merge) pass
        # through unpadded for the same reason.
        rounded = self._pad_target(total)
        pad = rounded - total if rounded <= self._max_merge else 0
        return ("passthrough" if lone and pad == 0 else "dense"), sizes, pad

    def _run_group(self, group, free_slot=None) -> None:
        """Execute one formed group. ``free_slot`` (when given) is
        called exactly once, as soon as the group's device work is
        launched — inputs staged, compute enqueued — so the dispatcher
        slot frees before the readback/split work."""
        faults.probe("batcher_stall", group[0][1].model_name)
        if self._shed_expired:
            group = self._shed_expired_members(group)
            if not group:
                return  # every member expired; caller's finally frees
        kind, sizes, pad = self._group_kind(group)
        if kind == "solo":
            for t_staged, request, future in group:
                self._run_solo(request, future, free_slot, t_staged=t_staged)
        elif kind == "session_steps":
            self._run_session_steps(group, free_slot)
        elif kind == "ragged":
            self._run_ragged_group(group, free_slot)
        elif kind == "passthrough":
            self._run_passthrough(group, sizes, free_slot)
        else:
            self._run_dense_merge(group, sizes, pad, free_slot)

    def _open_group(self, group):
        """A merged group leaves the ready set: each member's own
        ``merge_wait`` (its staging timestamp -> this dispatch; the
        merge_wait SLO stage) and the end of its ``batch_queue``.
        Returns the members' requests and futures and, where any member
        is traced, the launch's ONE record (else None): what the batcher
        and the channel below it do for the whole launch is written
        there once."""
        requests = [g[1] for g in group]
        futures = [g[2] for g in group]
        t_dispatch = time.perf_counter()
        if log.isEnabledFor(logging.DEBUG):
            # correlated dispatch line: each member's trace/request tag,
            # so a fleet trace_id greps straight to ITS device batch
            from triton_client_tpu.obs.logs import log_tag

            log.debug(
                "dispatching merged batch of %d for model %s:%s",
                len(requests), requests[0].model_name,
                "".join(
                    log_tag(r.trace, r.request_id) for r in requests
                ) or " [untraced]",
            )
        for (t_staged, r, _f) in group:
            if r.trace is not None and t_staged is not None:
                r.trace.add("merge_wait", t_staged, t_dispatch)
        traced = [r.trace for r in requests if r.trace is not None]
        for tr in traced:
            tr.end("batch_queue")
        return requests, futures, LaunchRecord(traced) if traced else None

    def _still_live(self, group, free_slot) -> bool:
        """Second deadline pass AFTER the pack: the host merge build
        takes real time under load, so a member that was live at group
        formation can be expired by now — launching would hand the
        inner channel a batch whose inherited min-deadline is already
        past (shed whole at launch, failing every live member). Shed
        the stragglers and run the survivors as a group of their own
        (rare path; t_staged=None so merge_wait is not double-recorded).
        False when the group was taken over that way."""
        if not self._shed_expired:
            return True
        live = self._shed_expired_members(group)
        if len(live) == len(group):
            return True
        if live:
            self._run_group(
                [(None, r, f) for (_t, r, f) in live], free_slot
            )
        return False

    def _launch(
        self, requests, record, merged, free_slot, t_stage0, t_disp, **fields
    ):
        """One launch for the whole group, async + deferred readback:
        by the time ``do_inference_async`` returns, the inner channel
        has device_put the batch and enqueued the compute — the slot
        can free NOW; ``result()`` pays the device wait + host copy
        outside the permit."""
        deadlines = [r.deadline_s for r in requests if r.deadline_s is not None]
        try:
            fut = self._inner.do_inference_async(
                InferRequest(
                    model_name=requests[0].model_name,
                    model_version=requests[0].model_version,
                    inputs=merged,
                    # channel-side spans (stage/launch/device/readback)
                    # are written once, on the launch's record, which
                    # every traced member's trace points at
                    trace=record,
                    # the merged batch inherits its TIGHTEST member's
                    # deadline and HIGHEST priority: the batch is late
                    # the moment any member is
                    deadline_s=min(deadlines) if deadlines else None,
                    priority=max(r.priority for r in requests),
                    **fields,
                )
            )
            if free_slot is not None:
                free_slot()
            self._await_device(fut, free_slot)
            return fut.result()
        finally:
            t_dev_end = time.perf_counter()
            with self._ready_cv:
                self._decomp["stage_s"] += t_disp - t_stage0
                self._decomp["device_s"] += t_dev_end - t_disp

    @staticmethod
    def _await_device(fut, slot) -> None:
        """A launch of session steps whose future can say when its
        outputs are ready ON THE DEVICE (``wait_device``: before their
        readback, which ``result()`` pays): wait for that on this
        thread, which would wait in ``result()`` anyway, and tell the
        group's record, so that a step group behind this launch can
        close before this one's answers are copied, split and handed
        back."""
        wait = getattr(fut, "wait_device", None)
        if wait is None or not getattr(slot, "steps", False):
            return
        try:
            wait()
        except Exception:
            return  # result() raises it; the resolution says the rest
        slot.off_device()

    def _count_merged(self, merged: dict, record, t_stage0, t_disp) -> None:
        """``merged_bytes`` and the ``batch_merge`` span: a device batch
        this batcher built by copying its members' rows."""
        with self._ready_cv:
            self._merge_stats["merged_bytes"] += sum(
                a.nbytes for a in merged.values()
            )
        if record is not None:
            record.add("batch_merge", t_stage0, t_disp)

    def _retry_solo(self, requests, futures) -> None:
        """A merged failure must not take down unrelated requests: fall
        back to per-request execution."""
        for request, future in zip(requests, futures):
            self._run_solo(request, future)

    def _respond(self, requests, futures, resp, per_output) -> None:
        """Member i takes ``per_output[name][i]`` of every output."""
        t_resp0 = time.perf_counter()
        for i, (request, future) in enumerate(zip(requests, futures)):
            if request.trace is not None:
                # before set_result: the waiting thread may finish the
                # trace the moment the future resolves
                request.trace.add("batch_respond", t_resp0, time.perf_counter())
            future.set_result(
                InferResponse(
                    model_name=resp.model_name,
                    model_version=resp.model_version,
                    outputs={k: v[i] for k, v in per_output.items()},
                    request_id=request.request_id,
                    latency_s=resp.latency_s,
                )
            )

    def _respond_rows(self, requests, futures, resp, sizes, pad=0) -> None:
        """Split a row-batched answer back over the members, pad rows
        dropped; an output with no batch axis goes to every member."""
        total = sum(sizes)
        splits = np.cumsum(sizes)[:-1]
        per_output = {}
        for name, arr in resp.outputs.items():
            arr = np.asarray(arr)
            if arr.ndim >= 1 and arr.shape[0] in (total + pad, total):
                per_output[name] = np.split(arr[:total], splits)
            else:
                per_output[name] = [arr] * len(requests)
        self._respond(requests, futures, resp, per_output)

    def _run_passthrough(self, group, sizes, free_slot=None) -> None:
        """ONE member whose rows already are the launch: there is
        nothing to merge, so its own arrays go down as they came (for
        an shm request the zero-copy view of the caller's region; it is
        answered only after the device has read them). Concatenating
        one part into a fresh buffer cost 0.95 s for 604 MB and kept
        the device waiting two thirds of the time (PERF.md, PR 27). No
        ``batch_merge`` span where nothing was copied: one of zero
        length would still read as a state in obs/launch_timeline.py."""
        requests, futures, record = self._open_group(group)
        try:
            t_stage0 = time.perf_counter()
            merged = {
                name: np.asarray(a) for name, a in requests[0].inputs.items()
            }
            t_disp = time.perf_counter()
            with self._ready_cv:
                self._merge_stats["passthrough_groups"] += 1
            if not self._still_live(group, free_slot):
                return
            resp = self._launch(
                requests, record, merged, free_slot, t_stage0, t_disp
            )
        except Exception:
            self._retry_solo(requests, futures)
            return
        self._respond_rows(requests, futures, resp, sizes)

    def _run_dense_merge(self, group, sizes, pad, free_slot=None) -> None:
        """Same-shaped requests as ONE buffer per input, ``pad`` rows
        appended to reach the bucket."""
        requests, futures, record = self._open_group(group)
        try:
            t_stage0 = time.perf_counter()
            merged = {}
            for name in requests[0].inputs:
                parts = [np.asarray(r.inputs[name]) for r in requests]
                if pad:
                    # replicate a real row: zeros can steer a model
                    # down numerically different paths, a copy cannot
                    parts = pad_rows(parts, pad)
                merged[name] = np.concatenate(parts)
            t_disp = time.perf_counter()
            self._count_merged(merged, record, t_stage0, t_disp)
            if not self._still_live(group, free_slot):
                return
            resp = self._launch(
                requests, record, merged, free_slot, t_stage0, t_disp
            )
            if pad:
                # counted only for a padded call that actually ran,
                # under the same lock stats() reads through (executor
                # threads race here at pipeline_depth >= 2)
                with self._ready_cv:
                    self._merge_stats["padded_frames"] += pad
                    self._padded_by_model[requests[0].model_name] += pad
        except Exception:
            self._retry_solo(requests, futures)
            return
        self._respond_rows(requests, futures, resp, sizes, pad)

    def _run_session_steps(self, group, free_slot=None) -> None:
        """The steps of several sessions of a model that declares
        mergeable sessions: one launch, each row its own stream, no pad
        rows here (the model's session state forms the launch shape)
        and no retry of a failed launch (the cache may have moved on).
        The answer holds a row a token of each step (one, or a block
        model's block), in the members' order."""
        requests, futures, record = self._open_group(group)
        try:
            sizes = [
                max(np.shape(a)[1] for a in r.inputs.values()) for r in requests
            ]
            t_stage0 = time.perf_counter()
            merged = {
                name: np.concatenate(
                    [np.asarray(r.inputs[name]) for r in requests]
                )
                for name in requests[0].inputs
            }
            t_disp = time.perf_counter()
            self._count_merged(merged, record, t_stage0, t_disp)
            if not self._still_live(group, free_slot):
                return
            resp = self._launch(
                requests, record, merged, free_slot, t_stage0, t_disp,
                sequence_rows=tuple(
                    (r.sequence_id, r.sequence_start, r.sequence_end)
                    for r in requests
                ),
            )
        except AdmissionRejectedError:
            # a row refused at admission took the whole launch back
            # before it reached the device: those retry one by one
            self._retry_solo(requests, futures)
            return
        except Exception as e:
            for future in futures:
                future.set_exception(e)
            return
        self._respond_rows(requests, futures, resp, sizes)

    def _run_ragged_group(self, group, free_slot=None) -> None:
        """Execute one ragged group as a PACKED batch: member rows
        concatenate, the segment table rides in ``request.ragged``, and
        the inner channel's segment-aware launcher runs every member at
        true size."""
        requests, futures, record = self._open_group(group)
        try:
            ragged_names = self._ragged_names(
                requests[0].model_name, requests[0].model_version
            )
            first_ragged = next(
                n for n in requests[0].inputs if n in ragged_names
            )
            sizes = tuple(
                int(np.asarray(r.inputs[first_ragged]).shape[0])
                for r in requests
            )
            layout = RaggedLayout(sizes)
            w = self._batch_multiple
            lay = shard_layout(layout, w) if w > 1 else layout
            t_stage0 = time.perf_counter()
            merged = {}
            for name in requests[0].inputs:
                parts = [np.asarray(r.inputs[name]) for r in requests]
                if name in ragged_names:
                    merged[name] = (
                        shard_pack_rows(parts, lay)
                        if w > 1
                        else pack_rows(parts, layout)
                    )
                elif w > 1:
                    # per-segment inputs ride shard-major next to their
                    # segments
                    merged[name] = shard_stack_segments(parts, lay)
                else:
                    # per-segment inputs stack to the segment bucket
                    # (dead slots replicate the last real entry)
                    merged[name] = pad_batch(
                        np.stack(parts), layout.seg_bucket
                    )
            t_disp = time.perf_counter()
            self._count_merged(merged, record, t_stage0, t_disp)
            if not self._still_live(group, free_slot):
                return
            resp = self._launch(
                requests, record, merged, free_slot, t_stage0, t_disp,
                ragged=lay,
            )
            with self._ready_cv:
                self._ragged_stats["ragged_batches"] += 1
                self._ragged_stats["ragged_segments"] += len(requests)
                self._ragged_stats["ragged_rows"] += layout.total
                self._ragged_stats["ragged_pad_rows"] += (
                    lay.n_shards * lay.rows_pad - layout.total
                    if w > 1
                    else layout.pad_rows
                )
        except Exception:
            self._retry_solo(requests, futures)
            return
        n = len(requests)
        per_output = {}
        for name, arr in resp.outputs.items():
            arr = np.asarray(arr)
            if arr.ndim >= 1 and arr.shape[0] == n:
                # the channel already sliced dead segment slots off;
                # member i's output is row i WITHOUT the segment dim —
                # matching the model's solo (unbatched) output, which
                # is what the parity contract compares against
                per_output[name] = [arr[i] for i in range(n)]
            else:  # non-segmented output — replicate
                per_output[name] = [arr] * n
        self._respond(requests, futures, resp, per_output)

    def _run_solo(
        self, request: InferRequest, future, free_slot=None, t_staged=None
    ) -> None:
        if request.trace is not None:
            if t_staged is not None:
                # solo dispatches report merge_wait too (a group of
                # one), so queue-delay attribution covers every path;
                # None on the merged-failure retry path, whose wait was
                # already recorded by the group dispatch
                request.trace.add("merge_wait", t_staged, time.perf_counter())
            request.trace.end("batch_queue")  # no-op on the retry path
        try:
            fut = self._inner.do_inference_async(request)
            if free_slot is not None:
                free_slot()  # launched: slot frees before the readback
            self._await_device(fut, free_slot)
            future.set_result(fut.result())
        except Exception as e:
            future.set_exception(e)

    # -- stats / lifecycle ----------------------------------------------------

    def stats(self) -> dict:
        out: dict = {}
        with self._ready_cv:
            out.update(self._merge_stats)
            out["merge_occupancy"] = dict(
                sorted(self._merge_occupancy.items())
            )
            out["padded_by_model"] = dict(sorted(self._padded_by_model.items()))
            # concurrently-active execution slots observed at each
            # group launch: {slots_active: launches} — 2s and above mean
            # batch N+1 formed/staged while batch N still executed
            out["slot_occupancy"] = dict(sorted(self._slot_occupancy.items()))
            out["active_slots"] = self._active_slots
            out["ready_depth"] = len(self._ready)
            out["shed"] = dict(self._shed)
            out["max_merge"] = self._max_merge
            out["batch_multiple"] = self._batch_multiple
            out["pipeline_depth"] = self._pipeline_depth
            age = self.dispatcher_progress_age_s()
            out["dispatcher_last_progress_age_s"] = age
            out["dispatcher_stalled"] = (
                1 if age >= self.stall_threshold_s else 0
            )
            n = self._decomp.get("n", 0.0)
            if n:
                out["decomp_ms"] = {
                    k[:-2]: round(self._decomp[k] / n * 1e3, 2)
                    for k in (
                        "queue_wait_s", "exec_wait_s", "stage_s", "device_s"
                    )
                }
                out["decomp_batches"] = int(n)
            members = self._decomp.get("members", 0.0)
            if members:
                # mean PER-MEMBER ready-queue wait (merge_wait), vs
                # decomp_ms.queue_wait which is per merged batch from
                # its earliest member
                out["member_queue_delay_ms"] = round(
                    self._decomp["member_wait_s"] / members * 1e3, 2
                )
                out["merge_members"] = int(members)
            pace = self._last_pace
            if pace is not None:
                # the times behind step_holds, of the model whose step
                # group resolved last
                out["step_launch_ms"] = round(pace.launch_s * 1e3, 3)
                if pace.device_s is not None:
                    # the times behind step_early_closes
                    out["step_device_ms"] = round(pace.device_s * 1e3, 3)
                    out["step_lead_ms"] = round(pace.lead_s * 1e3, 3)
                if pace.return_s is not None:
                    out["step_return_ms"] = round(pace.return_s * 1e3, 3)
                    out["step_return_dev_ms"] = round(pace.return_dev_s * 1e3, 3)
            out["scheduler"] = "continuous"
            out.update(self._ragged_stats)
            if self._live_buckets is not None:
                out["live_bucket_table"] = list(self._live_buckets.table)
            if self._tenant_table is not None:
                out["tenant_served_frames"] = dict(self._tenant_frames)
                out["tenant_vtime"] = dict(self._vtime)
        # share of device rows that were padding — the headline
        # padding-tax number: dense pad rows are bucket slack, ragged
        # pad rows lane-alignment slack, both rows the device computed
        # for nobody
        padded = out["padded_frames"] + out["ragged_pad_rows"]
        shipped = padded + out["merged_frames"] + out["ragged_rows"]
        out["pad_fraction"] = padded / shipped if shipped else 0.0
        return out

    def close(self) -> None:
        # the watchdog first: a slow drain below is not a stall
        self._watchdog_stop.set()
        # the dispatcher keeps forming batches until the ready set is
        # empty, THEN exits — no admitted future is stranded
        with self._ready_cv:
            self._dispatch_stop = True
            self._ready_cv.notify_all()
        # The executor must not shut down while the dispatcher can
        # still submit (futures would get 'cannot schedule new
        # futures' instead of executing), and a first compile can run
        # minutes — so loop-join with a progress warning instead of
        # abandoning the thread after a fixed timeout.
        waited = 0.0
        while self._dispatcher.is_alive():
            self._dispatcher.join(timeout=30.0)
            if self._dispatcher.is_alive():
                waited += 30.0
                log.warning(
                    "batcher close(): dispatcher still draining after "
                    "%.0fs (device call in flight?)", waited,
                )
        # after the dispatcher stops, drain in-flight groups so every
        # admitted future resolves before close() returns
        self._exec.shutdown(wait=True)
