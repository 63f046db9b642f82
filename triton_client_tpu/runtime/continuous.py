"""Continuous batching: windowless EDF admission + packed ragged batches.

ISSUE 8's tentpole. The window batcher (``runtime/batching.py``) pays
two taxes:

  * **the window barrier** — requests pool behind an admission window
    even when an execution slot is free, so under open-loop traffic the
    device idles while arrivals wait for a timer;
  * **the padding tax** — every merge group rounds up to a static
    power-of-two bucket (up to a third of device work in one served
    run), and variable-size 3D inputs pad to the widest member
    besides.

This scheduler removes both, keeping the proven dispatch machinery
(permits, executor, launch-time slot free, shed/trace planes) of
``BatchingChannel`` and replacing its two policy surfaces:

  * **admission** — no window, no admission thread. ``do_inference``
    stages the request straight into the ready set, kept ordered
    earliest-deadline-first (ties: higher priority, then arrival), so
    the dispatcher — which keeps forming batches while device work is
    in flight, exactly the continuous-admission discipline of FlexNPU's
    dynamic co-location (PAPERS.md) — always launches the work closest
    to its deadline and merges compatible later arrivals into it.
  * **batch shape** — models that register a segment-aware body
    (``RegisteredModel.ragged_fn`` + ``spec.extra["ragged_inputs"]``)
    execute as PACKED ragged batches: member rows concatenate back to
    back and a row->segment table rides along
    (``parallel/ragged_kernels.py``), so every request runs at its true
    size — zero pad rows beyond lane alignment. Fixed-shape 2D models
    keep the dense padded path, but pad targets come from a LIVE
    occupancy histogram (:class:`LiveBuckets`) instead of the static
    power-of-two table, so steady traffic converges to near-zero
    padding there too. The dense path stays bitwise identical per
    request (pad rows replicate a real row and are sliced back off —
    the `runtime/padding.py` contract — and data-parallel splits never
    change a row's compute).

Stacking is unchanged: ``ContinuousBatchingChannel(inner)`` drops in
anywhere ``BatchingChannel(inner)`` did, including in front of the
mesh-sharded channel — ragged batches are then packed SHARD-major
(``ShardedRaggedLayout``) so each device gets whole segments and the
sharded body needs no collectives.

Migration note: the window-timeout knob (``timeout_us`` /
``--batch-timeout-us``) has no meaning here — there is no window. The
constructor accepts and ignores it so existing call sites and configs
keep working; ``merge_hold_us`` is likewise forced to 0 (the scheduler
self-clocks on slot frees, and EDF ordering makes a hold actively
harmful: it would delay the tightest-deadline work).
"""

from __future__ import annotations

import bisect
import collections
import concurrent.futures
import math
import threading
import time

import numpy as np

from triton_client_tpu.channel.base import (
    BaseChannel,
    InferRequest,
    InferResponse,
)
from triton_client_tpu.obs.trace import MultiTrace
from triton_client_tpu.parallel.ragged_kernels import (
    RaggedLayout,
    pack_rows,
    shard_layout,
    shard_pack_rows,
    shard_stack_segments,
)
from triton_client_tpu.runtime.admission import QueueFullError
from triton_client_tpu.runtime.batching import BatchingChannel, _merge_key
from triton_client_tpu.runtime.padding import bucket_for, pad_batch


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


class ContinuousBatchingChannel(BatchingChannel):
    """Windowless EDF scheduler with packed-ragged execution (see
    module docstring). Accepts the :class:`BatchingChannel` signature
    so call sites migrate by swapping the class; ``timeout_us`` and
    ``merge_hold_us`` are accepted for compatibility and ignored."""

    def __init__(
        self,
        inner: BaseChannel,
        max_batch: int = 8,
        timeout_us: int = 0,  # ignored: no admission window exists
        capacity: int = 256,
        use_native: bool = False,  # ignored: no admission thread exists
        pipeline_depth: int = 2,
        max_merge: int | None = None,
        pad_to_buckets: bool = True,
        merge_hold_us: int = 0,  # ignored: EDF head must not be held
        arena_slots: int = 0,
        shed_expired: bool = False,
        live_buckets: bool = True,
    ) -> None:
        self._capacity = max(1, int(capacity))
        # (model, version) -> frozenset of packed-input names, or None
        # when the model has no segment-aware body; filled lazily from
        # inner.get_metadata so registration order doesn't matter.
        # Filled from RPC threads AND the dispatcher/executor threads,
        # so writes go through _ragged_cache_lock (the metadata RPC
        # itself runs outside the lock; racing fillers converge via
        # setdefault)
        self._ragged_inputs_cache: dict = {}
        self._ragged_cache_lock = threading.Lock()
        self._ragged_stats = {
            "ragged_batches": 0,
            "ragged_segments": 0,
            "ragged_rows": 0,
            "ragged_pad_rows": 0,
        }
        # optional multi-tenant fair share (runtime/lifecycle.py
        # TenantTable): deficit-round-robin virtual time folded into the
        # EDF key — set via attach_tenants(); None keeps pure EDF
        self._tenant_table = None
        self._fair_quantum_s = 0.005
        self._vtime: dict[str, float] = {}
        self._tenant_frames: collections.Counter = collections.Counter()
        super().__init__(
            inner,
            max_batch=max_batch,
            timeout_us=0,
            capacity=capacity,
            use_native=False,
            pipeline_depth=pipeline_depth,
            max_merge=max_merge,
            pad_to_buckets=pad_to_buckets,
            merge_hold_us=0,
            arena_slots=arena_slots,
            shed_expired=shed_expired,
        )
        self._live_buckets = (
            LiveBuckets(multiple=self._batch_multiple) if live_buckets else None
        )
        with self._ready_cv:
            # the ready set is an EDF-SORTED list, not the base FIFO
            # deque (same item tuples; _form_group_locked is overridden
            # to match). Swapped under the cv so the already-running
            # dispatcher never sees a half-state.
            self._ready = []

    # -- admission: straight into the EDF ready set ---------------------------

    def _start_admission(self, use_native, max_batch, timeout_us, capacity):
        """No admission window: requests stage in ``do_inference``."""
        # _impl/_py stay None; close() and stats() branch on that

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

    def do_inference(self, request: InferRequest):
        future: concurrent.futures.Future = concurrent.futures.Future()
        if request.trace is not None:
            request.trace.begin("batch_queue")
        ragged_names = self._ragged_names(
            request.model_name, request.model_version
        )
        session_step = False
        if request.sequence_id:
            # session frames bypass BOTH merge paths (ragged packing
            # included): _merge_key solos them, so the tracking step
            # sees exactly one stream's frame per launch in order —
            # unless the model declares mergeable sessions
            ragged_names = None
            session_step = self._session_step(request)
        if session_step:
            # one new token of a session whose state is a slot of the
            # model's own device cache: the steps of DIFFERENT sessions
            # merge into one launch under the model's key
            key = ("__session_step__", request.model_name, request.model_version)
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
                size = next(
                    iter(
                        int(np.asarray(a).shape[0])
                        for a in request.inputs.values()
                    )
                )
            except Exception:
                key, size = ("__solo__", next(self._ids)), 1
        with self._ready_cv:
            if len(self._ready) >= self._capacity:
                self._shed[
                    f"{request.model_name}|{request.priority}|queue"
                ] += 1
                raise QueueFullError(
                    f"model '{request.model_name}': inference queue full"
                )
            bisect.insort(
                self._ready,
                (key, size, request, future, time.perf_counter()),
                key=self._edf_key,
            )
            self._ready_cv.notify()
        return future.result()

    # -- group formation: EDF head + compatible followers ---------------------

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

    # -- dense pad targets from the live histogram ----------------------------

    def _pad_target(self, total: int) -> int:
        if self._live_buckets is None:
            return super()._pad_target(total)
        with self._ready_cv:
            self._live_buckets.observe(total)
            return self._live_buckets.target(total)

    # -- mergeable sessions ---------------------------------------------------

    def _session_step(self, request: InferRequest) -> bool:
        """Whether this session request may share a launch with other
        sessions' requests: the model declares it
        (``spec.extra["session_merge"]``: its session state is a slot of
        its own device cache, runtime/sessions.py TokenSessions) and the
        request carries ONE new token; a request of many runs alone."""
        try:
            spec = self._inner.get_metadata(
                request.model_name, request.model_version
            )
            if not (getattr(spec, "extra", None) or {}).get("session_merge"):
                return False
            (tokens,) = request.inputs.values()
            return tuple(getattr(tokens, "shape", ())) == (1, 1)
        except Exception:
            return False

    # -- ragged capability ----------------------------------------------------

    def _ragged_names(self, model_name: str, model_version: str):
        """Packed-input names for a model with a segment-aware body
        (``spec.extra["ragged_inputs"]``), else None. Cached, including
        negative answers — this sits on the per-request path.

        Called from RPC threads (``do_inference``) and from the
        dispatcher/executor threads (``_run_group``), so the cache fill
        is double-checked: the lock-free fast path covers the steady
        state, the metadata RPC runs unlocked (it can block), and the
        insert goes through ``setdefault`` under ``_ragged_cache_lock``
        so racing fillers agree on one winner."""
        key = (model_name, model_version)
        try:
            return self._ragged_inputs_cache[key]
        except KeyError:
            pass
        names = None
        try:
            spec = self._inner.get_metadata(model_name, model_version)
            declared = (getattr(spec, "extra", None) or {}).get(
                "ragged_inputs"
            )
            if declared:
                names = frozenset(declared)
        except Exception:
            names = None
        with self._ragged_cache_lock:
            return self._ragged_inputs_cache.setdefault(key, names)

    # -- ragged execution -----------------------------------------------------

    def _run_group(self, group, free_slot=None) -> None:
        if self._ragged_names(
            group[0][1].model_name, group[0][1].model_version
        ):
            if len(group) == 1:
                # a lone ragged request runs solo at its TRUE size —
                # never through the dense merged path, whose bucket
                # padding is exactly the tax the ragged plane removes
                if self._shed_expired:
                    group = self._shed_expired_members(group)
                    if not group:
                        return
                t_staged, request, future = group[0]
                self._run_solo(request, future, free_slot, t_staged=t_staged)
            else:
                self._run_ragged_group(group, free_slot)
            return
        # dense groups keep the (bitwise-identical) base path
        super()._run_group(group, free_slot)

    def _run_ragged_group(self, group, free_slot=None) -> None:
        """Execute one ragged group as a PACKED batch: member rows
        concatenate, the segment table rides in ``request.ragged``, and
        the inner channel's segment-aware launcher runs every member at
        true size. Mirrors the base ``_run_group`` contract: futures
        always resolve, failures fall back to per-request execution,
        ``free_slot`` fires at launch."""
        if self._shed_expired:
            group = self._shed_expired_members(group)
            if not group:
                return
        requests = [g[1] for g in group]
        futures = [g[2] for g in group]
        traces = [r.trace for r in requests]
        t_dispatch = time.perf_counter()
        for (t_staged, r, _f) in group:
            if r.trace is not None and t_staged is not None:
                r.trace.add("merge_wait", t_staged, t_dispatch)
        for tr in traces:
            if tr is not None:
                tr.end("batch_queue")
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
            self._count_merged(merged)
            for tr in traces:
                if tr is not None:
                    tr.add("batch_merge", t_stage0, t_disp)
            if self._shed_expired:
                # same post-pack recheck as the dense path: a slow pack
                # must not launch already-expired members
                live = self._shed_expired_members(group)
                if len(live) != len(group):
                    if live:
                        self._run_ragged_group(
                            [(None, r, f) for (_t, r, f) in live], free_slot
                        )
                    return
            deadlines = [
                r.deadline_s for r in requests if r.deadline_s is not None
            ]
            try:
                fut = self._inner.do_inference_async(
                    InferRequest(
                        model_name=requests[0].model_name,
                        model_version=requests[0].model_version,
                        inputs=merged,
                        trace=(
                            MultiTrace(traces)
                            if any(t is not None for t in traces)
                            else None
                        ),
                        deadline_s=min(deadlines) if deadlines else None,
                        priority=max(r.priority for r in requests),
                        ragged=lay,
                    )
                )
                if free_slot is not None:
                    free_slot()
                resp = fut.result()
            finally:
                t_dev_end = time.perf_counter()
                with self._ready_cv:
                    self._decomp["stage_s"] += t_disp - t_stage0
                    self._decomp["device_s"] += t_dev_end - t_disp
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
            # a packed failure must not take down unrelated requests:
            # per-request fallback, same as the dense merged path
            for request, future in zip(requests, futures):
                self._run_solo(request, future)
            return
        t_resp0 = time.perf_counter()
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
        for i, (request, future) in enumerate(zip(requests, futures)):
            if request.trace is not None:
                request.trace.add(
                    "batch_respond", t_resp0, time.perf_counter()
                )
            future.set_result(
                InferResponse(
                    model_name=resp.model_name,
                    model_version=resp.model_version,
                    outputs={k: v[i] for k, v in per_output.items()},
                    request_id=request.request_id,
                    latency_s=resp.latency_s,
                )
            )

    # -- stats ----------------------------------------------------------------

    def stats(self) -> dict:
        out = super().stats()
        out["scheduler"] = "continuous"
        with self._ready_cv:
            out.update(self._ragged_stats)
            if self._live_buckets is not None:
                out["live_bucket_table"] = list(self._live_buckets.table)
            if self._tenant_table is not None:
                out["tenant_served_frames"] = dict(self._tenant_frames)
                out["tenant_vtime"] = dict(self._vtime)
        shipped = (
            out["merged_frames"]
            + out["padded_frames"]
            + out["ragged_rows"]
            + out["ragged_pad_rows"]
        )
        if shipped:
            # fold ragged rows into the headline pad fraction: ragged
            # pad rows are lane-alignment slack, dense pad rows are
            # bucket slack — both are rows the device computed for
            # nobody
            out["pad_fraction"] = (
                out["padded_frames"] + out["ragged_pad_rows"]
            ) / shipped
        return out
