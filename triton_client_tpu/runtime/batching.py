"""Micro-batching channel: coalesce concurrent requests into one TPU call.

Triton's dynamic batcher is a core piece of the serving runtime the
reference leans on (config.pbtxt max_batch_size; SURVEY.md §2.9 row 1).
Here the same policy runs in-tree: admission + batch-window timing live
in the native C++ runtime (triton_client_tpu/native), and the formed
batch is executed as ONE inference over the wrapped channel with the
per-request arrays concatenated on the batch axis — bigger batches keep
the MXU busy and amortize dispatch overhead.

Batch formation is two-stage (round 4, VERDICT r3 #2). The admission
window (native C++ or the Python fallback) only signals arrival; the
DISPATCHER forms the device batch at the moment an execution slot
frees, merging every compatible request queued by then. A fixed
window had to guess how long a client burst takes to arrive — it
guessed wrong under load (r3 serving rows: occupancy 4/8 with 16
closed-loop clients and a 3 ms window, device idle ~60% of it) —
whereas slot-time formation is self-clocking: while ``pipeline_depth``
batches execute, arrivals pool, and the next batch takes them all.
Optional ``pad_to_buckets`` pads each merge to the next power of two
so the inner channel sees a handful of precompiled shapes instead of
every batch size (the role Triton's preferred_batch_size plays), and
``max_merge`` lets the device batch grow past the admission size —
the measured b8->b64 dispatch-amortization win, applied to serving.

A group of ONE member is never copied unless it needs pad rows. Without
``pad_to_buckets`` it runs solo. With it (the continuous scheduler
always sets it) the group still takes the dense path, so its size is
seen by the pad table, but when the pad comes out 0 (the rows already
are a launch size, or the request is wider than ``max_merge``) the
inner channel is handed the request's OWN arrays: for an shm request
the zero-copy view of the caller's region, read in place until the
answer leaves. Only a lone request that needs pad rows, and every group
of two or more, is built into a new buffer (``np.concatenate``, span
``batch_merge``). ``stats()`` counts both: ``passthrough_groups`` and
``merged_bytes``.

BatchingChannel is itself a BaseChannel, so it stacks under the gRPC
façade or above TPUChannel unchanged. Requests are only merged when
model, version and non-batch input shapes match; mismatches run solo.
A pure-Python batcher (same semantics, queue.Queue + thread) backstops
environments without the native toolchain.
"""

from __future__ import annotations

import collections
import concurrent.futures
import itertools
import logging
import queue
import threading
import time

import numpy as np

from triton_client_tpu.channel.base import BaseChannel, InferRequest, InferResponse
from triton_client_tpu.obs.trace import MultiTrace
from triton_client_tpu.runtime import faults
from triton_client_tpu.runtime.admission import (
    AdmissionRejectedError,
    DeadlineExpiredError,
    QueueFullError,
)
from triton_client_tpu.runtime.padding import bucket, bucket_for, pad_rows

log = logging.getLogger(__name__)

# compat alias: the bucket table now lives in runtime/padding.py (one
# copy shared with the mesh-sharded channel so the tables can't drift)
_bucket = bucket


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


class BatchingChannel(BaseChannel):
    def __init__(
        self,
        inner: BaseChannel,
        max_batch: int = 8,
        timeout_us: int = 2000,
        capacity: int = 256,
        use_native: bool = True,
        pipeline_depth: int = 2,
        max_merge: int | None = None,
        pad_to_buckets: bool = False,
        merge_hold_us: int = 0,
        arena_slots: int = 0,
        shed_expired: bool = False,
    ) -> None:
        """``pipeline_depth``: formed batches executing concurrently
        against the inner channel. At the default 2, batch N+1's
        host->device transfer overlaps batch N's execution (the role
        Triton's per-instance CUDA streams play); jax queues the
        dispatches and the device serializes execution. Depth 1
        restores strictly serial execution.

        ``max_merge``: frame cap for one device batch (default: same
        as ``max_batch``). Setting it higher lets the dispatcher fuse
        several admission windows into one device call — on a
        dispatch-bound path the per-call fixed cost then amortizes
        over max_merge frames instead of max_batch.

        ``pad_to_buckets``: pad each merged batch to the next power of
        two with replicated rows (outputs for the pad rows are
        discarded). Keeps the set of batch shapes the inner channel —
        and therefore XLA — ever sees to log2(max_merge)+1 sizes. A
        lone request that needs no pad rows is passed on uncopied
        (module docstring).

        ``merge_hold_us``: when a slot frees onto a SHALLOW queue (the
        formed group is under max_merge and nothing else is staged),
        hold the dispatch up to this long for the rest of the client
        burst to arrive. Closed-loop clients respond to a finished
        batch nearly simultaneously, but their next requests arrive
        staggered by the transport — eager dispatch ships the first
        arrival as a b1 fragment that burns a full fixed-cost device
        call (measured: fragments held serving to ~49% of the device
        ceiling; a hold of ~4% of the batch time converts them into
        full merges). 0 keeps strictly eager dispatch.

        ``arena_slots`` > 0 stages each merged device batch through the
        native 64-byte-aligned slot pool (native/ Arena, round 5:
        VERDICT r4 Weak #3) instead of a fresh ``np.concatenate``
        allocation per batch. Slots are sized from the first merged
        batch per input name; oversized batches and exhausted pools
        fall back to the allocating path. Requires the native library;
        silently off when it cannot build.

        ``shed_expired`` (round 12 — overload control): at dispatch
        time, members whose deadline already passed are FAILED with
        ``DeadlineExpiredError`` and never reach the device — the
        merged batch would otherwise inherit the expired member's
        deadline and be shed whole by the inner channel. Staged windows
        are also ordered highest-priority-first, so under a backlog the
        low-priority class queues longest and sheds first. Off by
        default (PR 6's count-only behavior).

        Slot lifetime (round 6 — overlapped dispatch): an execution
        slot frees at *launch*, not at readback. Each group dispatches
        through ``inner.do_inference_async`` and releases its permit as
        soon as the call returns (inputs staged on device, compute
        enqueued); the split/respond work then runs outside the permit,
        so batch formation self-clocks off device occupancy instead of
        host copy time. When the inner channel exposes a
        ``pipeline_depth`` staging knob (TPUChannel), it is aligned to
        this batcher's depth so the channel's staging slots provide the
        device-side backpressure."""
        self._inner = inner
        self._pending: dict[int, tuple[InferRequest, concurrent.futures.Future]] = {}
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._impl = None
        self._py = None
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
        self._pad_to_buckets = bool(pad_to_buckets)
        self._merge_hold_s = max(0, int(merge_hold_us)) / 1e6
        self._pipeline_depth = max(1, int(pipeline_depth))
        self._inflight = threading.Semaphore(max(1, pipeline_depth))
        self._exec = concurrent.futures.ThreadPoolExecutor(
            max_workers=max(1, pipeline_depth),
            thread_name_prefix="batch-exec",
        )
        # dispatch-time merge state: requests the admission stage has
        # released, waiting for an execution slot
        self._ready: collections.deque = collections.deque()
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
        }
        # padding-tax attribution (ISSUE 8 satellite): pad frames per
        # MODEL, so the Prometheus counter can carry a model label and
        # an operator can see WHICH model's buckets waste device rows
        self._padded_by_model: collections.Counter = collections.Counter()
        self._shed_expired = bool(shed_expired)
        # per "model|priority|stage" shed counts ("queue" = admission
        # queue full, "merge" = deadline expired at dispatch), merged
        # into the collector's tpu_serving_shed_total family
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
        # arena staging: created lazily once the first merged batch
        # reveals its slot size (max_merge rows of the widest input)
        self._arena_slots = max(0, int(arena_slots))
        self._arena = None
        # plumb the depth through to the inner channel's staging slots
        # (TPUChannel double-buffers H2D against execution at depth 2):
        # the channel then backpressures on device occupancy while this
        # batcher's permits backpressure on formed groups
        if hasattr(inner, "pipeline_depth"):
            try:
                inner.pipeline_depth = max(1, int(pipeline_depth))
            except (AttributeError, TypeError):
                pass  # read-only attribute on a custom channel
        self._start_admission(use_native, max_batch, timeout_us, capacity)
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, daemon=True, name="batch-dispatch"
        )
        self._dispatcher.start()
        self._watchdog_stop = threading.Event()
        self._watchdog = threading.Thread(
            target=self._watchdog_loop, daemon=True, name="batch-watchdog"
        )
        self._watchdog.start()

    def _start_admission(
        self, use_native: bool, max_batch: int, timeout_us: int, capacity: int
    ) -> None:
        """Bring up the admission window (native C++ server or the
        Python fallback). The continuous scheduler
        (runtime/continuous.py) overrides this to run WITHOUT a window
        — requests stage straight into the ready set."""
        if use_native:
            try:
                from triton_client_tpu.native import NativeBatchServer

                self._impl = NativeBatchServer(
                    self._on_batch,
                    max_batch=max_batch,
                    timeout_us=timeout_us,
                    capacity=capacity,
                )
                self._impl.start()
            except Exception as e:  # NativeUnavailable or load errors
                self._impl = None
                log.warning("native batcher unavailable (%s); python fallback", e)
        if self._impl is None:
            self._py = _PyBatcher(self._on_batch, max_batch, timeout_us, capacity)
            self._py.start()

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

    def do_inference(self, request: InferRequest) -> InferResponse:
        future: concurrent.futures.Future = concurrent.futures.Future()
        rid = next(self._ids)
        if request.trace is not None:
            # closed at dispatch time (_run_group/_run_solo): admission
            # window + ready-queue wait + slot backpressure, end to end
            request.trace.begin("batch_queue")
        with self._lock:
            self._pending[rid] = (request, future)
        try:
            admitted = (
                self._impl.enqueue(rid)
                if self._impl is not None
                else self._py.enqueue(rid)
            )
        except Exception:
            with self._lock:
                self._pending.pop(rid, None)
            raise
        if not admitted:
            with self._lock:
                self._pending.pop(rid, None)
            # fail-fast, never block the submitting RPC thread: the
            # server surfaces this as RESOURCE_EXHAUSTED, which the
            # client retry ladder treats as non-retryable for
            # ModelInfer — shedding must not amplify offered load
            with self._ready_cv:
                self._shed[
                    f"{request.model_name}|{request.priority}|queue"
                ] += 1
            raise QueueFullError(
                f"model '{request.model_name}': inference queue full"
            )
        return future.result()

    # -- admission release (runs on the batcher thread) -----------------------

    def _on_batch(self, ids) -> None:
        """The admission stage released a window of requests: stage
        them for the dispatcher. Merging happens THERE, at slot time —
        fragments from separate windows re-coalesce."""
        with self._lock:
            work = [(rid, *self._pending.pop(rid)) for rid in ids if rid in self._pending]
        staged = []
        t_now = time.perf_counter()
        for rid, request, future in work:
            try:
                key = _merge_key(request)
                size = next(
                    iter(int(np.asarray(a).shape[0]) for a in request.inputs.values())
                )
            except Exception:
                key, size = ("__solo__", rid), 1
            staged.append((key, size, request, future, t_now))
        if not staged:
            return
        if self._shed_expired and len(staged) > 1:
            # priority-aware ordering: within the released window the
            # high-priority class stages (and therefore dispatches)
            # first; under a backlog the low-priority tail queues
            # longest and its deadlines expire — shed — first. Stable
            # sort keeps arrival order within a class.
            staged.sort(key=lambda it: -it[2].priority)
        with self._ready_cv:
            self._ready.extend(staged)
            self._ready_cv.notify()

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
        """One dispatcher slot: acquire a permit, form a group, submit.
        Returns True when the loop should exit (close() requested and
        the staging deque is drained). Any unexpected error fails the
        formed group's futures, releases the permit, and re-raises for
        the loop to log — the thread itself survives."""
        self._beat()
        self._inflight.acquire()
        self._beat()
        group = None
        try:
            with self._ready_cv:
                while not self._ready and not self._dispatch_stop:
                    self._ready_cv.wait(timeout=0.1)
                    # idle is progress: only a dispatcher that cannot
                    # reach this loop (wedged on the permit semaphore or
                    # a hung group) lets the heartbeat go stale
                    self._beat()
                if self._ready:
                    group = self._form_group_locked()
                    if (
                        self._merge_hold_s > 0
                        and not self._dispatch_stop
                        and not self._ready  # nothing skipped/left over
                        and sum(it[1] for it in group) < self._max_merge
                    ):
                        # hold for the rest of the client burst: wait
                        # out the FULL hold window (arrival notifies
                        # and spurious wakeups return early from one
                        # wait, so re-wait the remaining deadline),
                        # absorbing same-key arrivals until the group
                        # fills or the hold expires
                        deadline = time.perf_counter() + self._merge_hold_s
                        while not self._dispatch_stop:
                            while self._ready:
                                frames = sum(it[1] for it in group)
                                item = self._ready[0]
                                if (
                                    item[0] != group[0][0]
                                    or frames + item[1] > self._max_merge
                                ):
                                    break
                                group.append(self._ready.popleft())
                            left = deadline - time.perf_counter()
                            if (
                                left <= 0
                                or sum(it[1] for it in group)
                                >= self._max_merge
                                # head is unabsorbable (other key or
                                # over-cap): ship now, it needs a slot
                                or self._ready
                            ):
                                break
                            self._ready_cv.wait(timeout=left)
                    self._merge_stats["merges"] += 1
                    frames = sum(it[1] for it in group)
                    self._merge_stats["merged_frames"] += frames
                    self._merge_occupancy[frames] += 1
                elif self._dispatch_stop:
                    self._inflight.release()
                    return True
            if group is None:
                self._inflight.release()
                return False

            with self._ready_cv:
                self._active_slots += 1

            def run(g=group, t_submit=time.perf_counter()):
                t_run = time.perf_counter()
                with self._ready_cv:
                    self._decomp["n"] += 1
                    self._decomp["exec_wait_s"] += t_run - t_submit
                    self._decomp["queue_wait_s"] += t_run - min(
                        it[4] for it in g
                    )
                    # PER-MEMBER queue delay, not just the merged
                    # batch's (which MultiTrace would fan out as one
                    # shared number): each member's own staging
                    # timestamp to this dispatch
                    self._decomp["members"] += len(g)
                    self._decomp["member_wait_s"] += sum(
                        t_run - it[4] for it in g
                    )
                # the slot frees the moment the group LAUNCHES (inputs
                # staged, compute enqueued on the inner channel) — the
                # dispatcher can then form the next batch against
                # device occupancy while this group's readback/split
                # still runs. Exactly-once: the finally covers groups
                # whose launch never happened (errors before dispatch).
                released = [False]

                def free_slot():
                    if released[0]:
                        return
                    released[0] = True
                    with self._ready_cv:
                        self._slot_occupancy[self._active_slots] += 1
                        self._active_slots -= 1
                        self._merge_stats["launch_frees"] += 1
                    self._inflight.release()

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

            try:
                self._exec.submit(run)
            except RuntimeError as e:  # executor shut down mid-close
                with self._ready_cv:
                    self._active_slots -= 1
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
        """Pop the head item plus every queued same-key item that fits
        under max_merge frames (caller holds _ready_cv). Items of other
        keys keep their relative order for the next slot. Stats are
        recorded by the caller once the group is FINAL (the merge-hold
        path may still grow it)."""
        first = self._ready.popleft()
        group = [first]
        frames = first[1]
        skipped = []
        while self._ready and frames < self._max_merge:
            item = self._ready.popleft()
            if item[0] == first[0] and frames + item[1] <= self._max_merge:
                group.append(item)
                frames += item[1]
            else:
                skipped.append(item)
        self._ready.extendleft(reversed(skipped))
        return group

    def _pad_target(self, total: int) -> int:
        """Padded device-batch size for a merged total: the static
        power-of-two table, kept divisible by a sharded inner channel's
        data axis. The continuous scheduler overrides this with a
        live-occupancy-driven table (runtime/continuous.py) so buckets
        track the sizes traffic actually produces."""
        return bucket_for(total, self._batch_multiple)

    # -- batch execution (runs on the executor threads) -----------------------

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
        if len(group) == 1 and (
            not self._pad_to_buckets or group[0][1].sequence_id
        ):
            # session frames take the solo path even under bucket
            # padding: pad rows would read as extra cameras to the
            # session layer, and the solo path is the one that carries
            # the original request (sequence fields intact) downstream
            t_staged, request, future = group[0]
            self._run_solo(request, future, free_slot, t_staged=t_staged)
            return
        requests = [g[1] for g in group]
        futures = [g[2] for g in group]
        traces = [r.trace for r in requests]
        # the steps of several sessions of a model that declares
        # mergeable sessions (the continuous scheduler keys them so):
        # one launch, each row its own stream, no pad rows here (the
        # model's session state forms the launch shape) and no retry of
        # a failed launch (the cache may have moved on)
        sequence_rows = (
            tuple((r.sequence_id, r.sequence_start, r.sequence_end) for r in requests)
            if requests[0].sequence_id
            else None
        )
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
                # per-member ready-queue residence: own staging
                # timestamp -> this group's dispatch (the merge_wait
                # SLO stage; batch_queue still covers the whole
                # admission+queue+slot window around it)
                r.trace.add("merge_wait", t_staged, t_dispatch)
        for tr in traces:
            if tr is not None:
                tr.end("batch_queue")
        try:
            sizes = [
                next(iter(np.asarray(a).shape[0] for a in r.inputs.values()))
                for r in requests
            ]
            total = sum(sizes)
            # pad only when the ROUNDED size still fits max_merge: a
            # non-power-of-two max_merge (e.g. 6) must not round a
            # total of 6 up to 8 — past the cap and past any size the
            # inner channel precompiled. Oversized single requests
            # (> max_merge) pass through unpadded for the same reason.
            # bucket_for keeps the padded size divisible by a sharded
            # inner channel's data axis (== _bucket at multiple 1); the
            # continuous scheduler overrides _pad_target with a
            # live-occupancy table
            rounded = self._pad_target(total)
            pad = (
                rounded - total
                if self._pad_to_buckets
                and rounded <= self._max_merge
                and sequence_rows is None
                else 0
            )
            # ONE member whose rows already are the launch: there is
            # nothing to merge, so its own arrays go down as they came
            # (for an shm request the zero-copy view of the caller's
            # region; it is answered only after the device has read
            # them). Concatenating one part into a fresh buffer cost
            # 0.95 s for 604 MB and kept the device waiting two thirds
            # of the time (PERF.md, PR 27).
            passthrough = len(requests) == 1 and pad == 0
            t_stage0 = time.perf_counter()
            merged = {}
            arena_held = []
            for name in requests[0].inputs:
                parts = [np.asarray(r.inputs[name]) for r in requests]
                if passthrough:
                    merged[name] = parts[0]
                    continue
                if pad:
                    # replicate a real row: zeros can steer a model
                    # down numerically different paths, a copy cannot
                    parts = pad_rows(parts, pad)
                merged[name] = self._merge_parts(name, parts, arena_held)
            t_disp = time.perf_counter()
            if passthrough:
                # and no span where nothing was copied: one of zero
                # length would still read as a state in
                # obs/launch_timeline.py
                with self._ready_cv:
                    self._merge_stats["passthrough_groups"] += 1
            else:
                self._count_merged(merged)
                for tr in traces:
                    if tr is not None:
                        tr.add("batch_merge", t_stage0, t_disp)
            if self._shed_expired:
                # second deadline pass AFTER the pack (ISSUE 8
                # satellite): the host merge build above takes real
                # time under load, so a member that was live at group
                # formation can be expired by now — launching would
                # hand the inner channel a batch whose inherited
                # min-deadline is already past (shed whole at launch,
                # failing every live member). Shed the stragglers and
                # rebuild from the survivors (rare path; t_staged=None
                # so merge_wait is not double-recorded).
                live = self._shed_expired_members(group)
                if len(live) != len(group):
                    if arena_held and self._arena is not None:
                        for arr in arena_held:
                            self._arena.release(arr)
                    if live:
                        self._run_group(
                            [(None, r, f) for (_t, r, f) in live], free_slot
                        )
                    return
            try:
                # async launch + deferred readback: by the time the
                # call returns, the inner channel has device_put the
                # merged batch and enqueued the compute — the slot can
                # free NOW; result() below pays the device wait +
                # host copy outside the permit
                deadlines = [
                    r.deadline_s for r in requests if r.deadline_s is not None
                ]
                fut = self._inner.do_inference_async(
                    InferRequest(
                        model_name=requests[0].model_name,
                        model_version=requests[0].model_version,
                        inputs=merged,
                        # channel-side spans (stage/launch/device/
                        # readback) fan out to every member's trace
                        trace=(
                            MultiTrace(traces)
                            if any(t is not None for t in traces)
                            else None
                        ),
                        # the merged batch inherits its TIGHTEST
                        # member's deadline and HIGHEST priority: the
                        # batch is late the moment any member is
                        deadline_s=min(deadlines) if deadlines else None,
                        priority=max(r.priority for r in requests),
                        sequence_rows=sequence_rows,
                    )
                )
                if free_slot is not None:
                    free_slot()
                resp = fut.result()
            finally:
                t_dev_end = time.perf_counter()
                if arena_held and self._arena is not None:
                    # device_put copied out of the slot synchronously;
                    # safe to recycle once the call returns
                    for arr in arena_held:
                        self._arena.release(arr)
                with self._ready_cv:
                    self._decomp["stage_s"] += t_disp - t_stage0
                    self._decomp["device_s"] += t_dev_end - t_disp
            if pad:
                # counted only for a padded call that actually ran,
                # under the same lock stats() reads through (executor
                # threads race here at pipeline_depth >= 2)
                with self._ready_cv:
                    self._merge_stats["padded_frames"] += pad
                    self._padded_by_model[requests[0].model_name] += pad
        except Exception as e:
            if sequence_rows is not None and not isinstance(
                e, AdmissionRejectedError
            ):
                # (a row refused at admission took the whole launch back
                # before it reached the device: those retry one by one)
                for future in futures:
                    future.set_exception(e)
                return
            # A merged failure must not take down unrelated requests:
            # fall back to per-request execution.
            for request, future in zip(requests, futures):
                self._run_solo(request, future)
            return
        t_resp0 = time.perf_counter()
        total_padded = total + pad
        splits = np.cumsum(sizes)[:-1]
        per_output = {}
        for name, arr in resp.outputs.items():
            arr = np.asarray(arr)
            if arr.ndim >= 1 and arr.shape[0] == total_padded:
                per_output[name] = np.split(arr[:total], splits)
            elif arr.ndim >= 1 and arr.shape[0] == total:
                per_output[name] = np.split(arr, splits)
            else:  # non-batched output — replicate
                per_output[name] = [arr] * len(requests)
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

    def _count_merged(self, merged: dict) -> None:
        """``merged_bytes``: the bytes of a device batch this batcher
        built by copying its members' rows."""
        with self._ready_cv:
            self._merge_stats["merged_bytes"] += sum(
                a.nbytes for a in merged.values()
            )

    def _merge_parts(self, name: str, parts: list, arena_held: list) -> np.ndarray:
        """Concatenate request tensors into the device-batch buffer —
        through a recycled aligned arena slot when enabled (round 5:
        the serving path consumes native/ Arena), else a fresh
        allocation. An oversized batch (a solo request wider than the
        slot, or an input with wider rows than the one the slot was
        sized from) falls back PER BATCH; only a failure to build/load
        the native pool disables staging for the channel."""
        if self._arena_slots:
            arena = self._arena
            if arena is None:
                with self._lock:  # double-checked: depth>=2 races here
                    arena = self._arena
                    if arena is None and self._arena_slots:
                        try:
                            from triton_client_tpu.native import Arena

                            rows = max(
                                self._max_merge, sum(len(p) for p in parts)
                            )
                            arena = Arena(
                                int(rows * parts[0][:1].nbytes),
                                self._arena_slots,
                            )
                            self._arena = arena
                        except Exception as e:
                            log.warning("arena staging unavailable (%s)", e)
                            self._arena_slots = 0
            if arena is not None:
                total = sum(len(p) for p in parts)
                try:
                    out = arena.acquire(
                        (total, *parts[0].shape[1:]), parts[0].dtype
                    )
                except ValueError:  # batch wider than the slot
                    out = None
                if out is not None:
                    o = 0
                    for p in parts:
                        out[o : o + len(p)] = p
                        o += len(p)
                    arena_held.append(out)
                    return out
        return np.concatenate(parts)

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
            future.set_result(fut.result())
        except Exception as e:
            future.set_exception(e)

    # -- stats / lifecycle ----------------------------------------------------

    def stats(self) -> dict:
        if self._impl is not None:
            out = self._impl.stats()
        elif self._py is not None:
            out = self._py.stats()
        else:  # windowless scheduler (runtime/continuous.py)
            out = {}
        with self._ready_cv:
            out.update(self._merge_stats)
            out["merge_occupancy"] = dict(
                sorted(self._merge_occupancy.items())
            )
            out["padded_by_model"] = dict(sorted(self._padded_by_model.items()))
            shipped = out["merged_frames"] + out["padded_frames"]
            # share of device rows that were padding — the headline
            # padding-tax number (ISSUE 8)
            out["pad_fraction"] = (
                out["padded_frames"] / shipped if shipped else 0.0
            )
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
            if self._arena is not None:
                out["arena_free_slots"] = self._arena.free_slots()
        return out

    def close(self) -> None:
        # the watchdog first: a slow drain below is not a stall
        self._watchdog_stop.set()
        # admission first: its close() drains every admitted id into
        # _on_batch, so by the time it returns all work is staged
        if self._impl is not None:
            self._impl.close()
        if self._py is not None:
            self._py.close()
        # the dispatcher keeps forming batches until the staging deque
        # is empty, THEN exits — no admitted future is stranded
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
        # _arena is published under _lock (_merge_parts' double-checked
        # init); tear it down under the same lock — tpulint TPL401
        # caught the bare mutation racing a straggler executor thread
        with self._lock:
            arena, self._arena = self._arena, None
        if arena is not None:
            arena.close()


class _PyBatcher:
    """queue.Queue + thread fallback with the same close semantics."""

    def __init__(self, on_batch, max_batch, timeout_us, capacity) -> None:
        self._on_batch = on_batch
        self._max_batch = max_batch
        self._timeout_s = timeout_us / 1e6
        self._q: queue.Queue = queue.Queue(maxsize=capacity)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._n_batches = 0
        self._n_requests = 0

    def start(self) -> None:
        self._thread.start()

    def enqueue(self, rid: int) -> bool:
        if self._stop.is_set():
            # Match the native path: enqueue after close raises rather
            # than accepting work no thread will ever drain.
            raise RuntimeError("server not running")
        try:
            self._q.put_nowait(rid)
            return True
        except queue.Full:
            return False

    def _run(self) -> None:
        while not self._stop.is_set() or not self._q.empty():
            try:
                first = self._q.get(timeout=0.05)
            except queue.Empty:
                continue
            ids = [first]
            deadline = time.perf_counter() + self._timeout_s
            while len(ids) < self._max_batch:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                try:
                    ids.append(self._q.get(timeout=remaining))
                except queue.Empty:
                    break
            self._n_batches += 1
            self._n_requests += len(ids)
            self._on_batch(ids)

    def stats(self) -> dict:
        return {
            "batches": self._n_batches,
            "batched_requests": self._n_requests,
            "mean_batch": self._n_requests / self._n_batches
            if self._n_batches
            else 0.0,
            "queue_depth": self._q.qsize(),
        }

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5.0)
