"""GRPCChannel: KServe v2 client channel (remote-inference path).

The drop-in analogue of the reference's GRPCChannel
(communicator/channel/grpc_channel.py:8-84) so a driver can point at a
remote server — this framework's InferenceServer on a TPU host, or a
stock Triton — through the same BaseChannel seam the in-process
TPUChannel implements. Departures from the reference:

  * the message-size cap starts at a 64 MiB floor and grows on demand:
    get_metadata() sizes the served contract and re-dials with a larger
    cap when the model needs one — not ``batch_size * 8568044``
    hardcoded (grpc_channel.py:26-29, README.md:118 "make dynamic");
  * requests are built per call from typed arrays (zero-copy codec) —
    no shared mutable ModelInferRequest (grpc_channel.py:63-71), so the
    channel is thread-safe and drivers can pipeline;
  * transient RPC failures retry with exponential backoff instead of
    crashing the callback (the reference has no retry story, SURVEY.md
    §5 "failure detection: none").
"""

from __future__ import annotations

import collections
import itertools
import json
import logging
import os
import random
import threading
import time
import weakref

import grpc
import numpy as np

from triton_client_tpu.channel import transport as transports
from triton_client_tpu.channel.base import (
    BaseChannel,
    InferFuture,
    InferRequest,
    InferResponse,
)
from triton_client_tpu.channel.kserve import codec, pb, service
from triton_client_tpu.config import FRAMING_BYTES, ModelSpec, TensorSpec
from triton_client_tpu.obs.trace import SUMMARY_PARAM_KEY, TraceContext
from triton_client_tpu.runtime.shared_memory import ShmRegionPool

log = logging.getLogger(__name__)


def _wire_params(request: InferRequest) -> dict | None:
    """Request-level kserve parameters for one outbound ModelInfer:
    the W3C-style trace context (when the request's trace carries one)
    and the scheduling priority. None on the common untraced path so
    the codec skips the parameters map entirely."""
    params = None
    tr = request.trace
    ctx = getattr(tr, "context", None) if tr is not None else None
    if ctx is not None:
        params = {TraceContext.PARAM_KEY: ctx.encode()}
    if request.priority:
        if params is None:
            params = {}
        params["priority"] = int(request.priority)
    if request.sequence_id:
        if params is None:
            params = {}
        params[codec.SEQUENCE_ID_PARAM] = str(request.sequence_id)
        if request.sequence_start:
            params[codec.SEQUENCE_START_PARAM] = True
        if request.sequence_end:
            params[codec.SEQUENCE_END_PARAM] = True
    return params


def _response_params(resp) -> dict | None:
    """Response-level parameters decoded off the wire — today just the
    server's compact span summary, which the router (or any tracing
    client) grafts onto its own timeline."""
    raw = codec.get_string_param(resp, SUMMARY_PARAM_KEY)
    if raw is None:
        return None
    return {SUMMARY_PARAM_KEY: raw}

_RETRYABLE = (
    grpc.StatusCode.UNAVAILABLE,
    grpc.StatusCode.DEADLINE_EXCEEDED,
    grpc.StatusCode.RESOURCE_EXHAUSTED,
)
# ModelInfer may have executed server-side when the deadline fires, so
# only connection-level failures are safe to re-issue automatically.
# RESOURCE_EXHAUSTED is additionally a DELIBERATE server decision (the
# admission controller shed the request); re-issuing it would feed the
# exact overload the server is shedding — clients must back off or
# drop, so it is surfaced immediately and counted (stats()).
_INFER_RETRYABLE = (grpc.StatusCode.UNAVAILABLE,)

# retry backoff ceiling: with jitter, retries from a client fleet decor-
# relate instead of arriving in synchronized waves at each 2^n step
_BACKOFF_CAP_S = 5.0


class DeadlineExceededRpcError(grpc.RpcError):
    """Client-local deadline failure, raised WITHOUT touching the wire.

    The retry ladder synthesizes this when the request's remaining
    deadline budget is gone — either already expired, or so short the
    next backoff sleep would expire it. It subclasses grpc.RpcError and
    answers code()/details() so every caller's status-code dispatch
    (the router, _record_infer_error, tests) handles it exactly like a
    server-sent DEADLINE_EXCEEDED."""

    def __init__(self, details: str) -> None:
        super().__init__(details)
        self._details = details

    def code(self) -> grpc.StatusCode:
        return grpc.StatusCode.DEADLINE_EXCEEDED

    def details(self) -> str:
        return self._details

# shared-memory region-name tag: process-wide monotonic so no two
# channel instances (live or dead) ever share a name prefix
_SHM_CHANNEL_SEQ = itertools.count()

# A server that answers the shm extension with one of these codes does
# not serve it at all (stock gRPC UNIMPLEMENTED, the server's same-host
# PERMISSION_DENIED gate for tunneled "loopback" dials, fake test
# servicers' UNKNOWN): an auto-negotiated channel falls back to the
# wire permanently instead of failing every request. INVALID_ARGUMENT
# is deliberately absent — that is the restart-recovery signal.
_SHM_UNSUPPORTED = (
    grpc.StatusCode.UNIMPLEMENTED,
    grpc.StatusCode.PERMISSION_DENIED,
    grpc.StatusCode.UNKNOWN,
)

# per-output alignment inside a slot's output arena (cache-line)
_SHM_OUT_ALIGN = 64


class GRPCChannel(BaseChannel):
    def __init__(
        self,
        endpoint: str,
        max_message_bytes: int = 64 << 20,
        timeout_s: float = 30.0,
        retries: int = 3,
        backoff_s: float = 0.1,
        use_shared_memory: bool | None = None,
        pipeline_depth: int = 4,
    ) -> None:
        """``use_shared_memory``: same-host transport — inputs are
        written into client-owned POSIX shm segments and requests carry
        only region coordinates (Triton system-shared-memory
        extension), skipping the protobuf serialize/copy/deserialize of
        the tensor payload in both processes. ``None`` (the default)
        auto-detects: loopback and ``unix:`` endpoints with a usable
        /dev/shm ride shm, everything else rides the wire
        (channel/transport.py's eligibility matrix); a same-host-
        looking endpoint whose server rejects the extension (a
        tunnel, a stock server without it) degrades to the wire once
        and permanently. ``True``/``False`` force the decision.

        Regions live in a pool of ``pipeline_depth`` slots, each
        generation-tagged per input and sized to the largest array
        seen: ``do_inference``, ``do_inference_async`` and
        ``infer_stream`` all ride shm concurrently — the
        ``pipeline_depth+1``-th in-flight request blocks until a slot
        frees (natural backpressure mirroring the server's staging
        pipeline). Responses ride shm too once the channel has seen a
        model's output sizes (requested-output windows in a per-slot
        arena the server writes readback bytes into directly)."""
        self._endpoint = endpoint
        self._max_message_bytes = max_message_bytes
        self._timeout_s = timeout_s
        self._retries = retries
        self._backoff_s = backoff_s
        self._channel: grpc.Channel | None = None
        self._stub: service.GRPCInferenceServiceStub | None = None
        self._retired: list[grpc.Channel] = []
        self._shm_auto = use_shared_memory is None
        self._use_shm = (
            transports.shm_eligible(endpoint)
            if use_shared_memory is None
            else bool(use_shared_memory)
        )
        self._pipeline_depth = max(1, int(pipeline_depth))
        # region names were keyed on id(self), which CPython reuses
        # after GC: a dead channel whose close() failed to unregister
        # server-side left a stale registry entry that a NEW channel
        # reusing the id would collide with forever. A process-wide
        # monotonic tag can never recur within the process.
        self._shm_tag = next(_SHM_CHANNEL_SEQ)
        self._pool: ShmRegionPool | None = None
        self._pool_lock = threading.Lock()
        # learned response contract: model -> output name -> max bytes
        # seen. The first request for a model gets its response over
        # the wire; every later one carries requested-output windows
        # sized from this map, so responses bypass the wire too.
        self._learned_out: dict[str, dict[str, int]] = {}
        # client-side overload ledger: sheds the server sent back
        # (RESOURCE_EXHAUSTED on ModelInfer — never retried) vs
        # transient retries the ladder absorbed
        self._infer_rejections = 0
        self._retries_total = 0
        self.register_channel()

    @property
    def transport(self) -> str:
        """Negotiated transport label: ``grpc`` / ``uds`` / ``shm`` /
        ``uds+shm`` (channel/transport.py). Reported by stats(), the
        route CLI, and bench rows."""
        return transports.negotiated(self._endpoint, self._use_shm)

    # -- BaseChannel protocol -------------------------------------------------

    def register_channel(self) -> None:
        self._channel = grpc.insecure_channel(
            self._endpoint,
            options=[
                ("grpc.max_send_message_length", self._max_message_bytes),
                ("grpc.max_receive_message_length", self._max_message_bytes),
            ],
        )
        self._stub = service.GRPCInferenceServiceStub(self._channel)

    def fetch_channel(self) -> grpc.Channel:
        return self._channel

    def get_metadata(self, model_name: str, model_version: str = "") -> ModelSpec:
        meta = self._call(
            self._stub.ModelMetadata,
            pb.ModelMetadataRequest(name=model_name, version=model_version),
        )
        config = self._call(
            self._stub.ModelConfig,
            pb.ModelConfigRequest(name=model_name, version=model_version),
        ).config
        import json

        spec = ModelSpec(
            name=meta.name,
            version=model_version or (meta.versions[-1] if meta.versions else "1"),
            platform=meta.platform,
            inputs=tuple(
                TensorSpec(t.name, tuple(t.shape), t.datatype) for t in meta.inputs
            ),
            outputs=tuple(
                TensorSpec(t.name, tuple(t.shape), t.datatype) for t in meta.outputs
            ),
            max_batch_size=config.max_batch_size,
            extra={k: json.loads(v) for k, v in config.parameters.items()},
        )
        needed = 2 * spec.wire_bytes() + FRAMING_BYTES
        if needed > self._max_message_bytes:
            # Re-dial with the larger cap. The old channel is retired,
            # not closed: closing would cancel RPCs other threads have
            # in flight on it; it is drained and closed in close().
            self._max_message_bytes = needed
            if self._channel is not None:
                self._retired.append(self._channel)
            self.register_channel()
        return spec

    def do_inference(self, request: InferRequest) -> InferResponse:
        # fail-fast BEFORE any transport work: the shm path's region
        # registration is itself a wire RPC, and an already-expired
        # deadline must surface as DEADLINE_EXCEEDED, not whatever
        # that RPC happens to return
        if (
            request.deadline_s is not None
            and request.deadline_s - time.perf_counter() <= 0
        ):
            raise DeadlineExceededRpcError(
                "deadline expired before ModelInfer was issued"
            )
        if self._use_shm:
            try:
                return self._do_inference_shm(request)
            except grpc.RpcError as e:
                if not self._maybe_disable_shm(e):
                    raise
                # degraded to the wire (server lacks the extension)
        wire = codec.build_infer_request(
            model_name=request.model_name,
            inputs=request.inputs,
            model_version=request.model_version,
            request_id=request.request_id,
            parameters=_wire_params(request),
            input_parameters=request.input_params,
        )
        t0 = time.perf_counter()
        try:
            resp = self._call(
                self._stub.ModelInfer,
                wire,
                retryable=_INFER_RETRYABLE,
                deadline_s=request.deadline_s,
            )
        except grpc.RpcError as e:
            self._record_infer_error(e)
            raise
        return InferResponse(
            model_name=resp.model_name,
            model_version=resp.model_version,
            outputs=codec.parse_infer_response(resp),
            request_id=resp.id,
            latency_s=time.perf_counter() - t0,
            parameters=_response_params(resp),
        )

    # -- shared-memory transport ----------------------------------------------

    def _shm_pool(self) -> ShmRegionPool:
        pool = self._pool
        if pool is not None:
            return pool
        with self._pool_lock:
            if self._pool is None:
                # the pool's RPC callbacks must not hold a strong ref
                # back to the channel: channel->pool->bound-method->
                # channel is a cycle, and a CLI that simply drops its
                # channel then relies on refcount-immediate __del__ to
                # unregister + unlink /dev/shm segments — gc-deferred
                # teardown leaves regions registered on the server
                def _weak(method):
                    ref = weakref.WeakMethod(method)

                    def call(*a):
                        fn = ref()
                        if fn is not None:
                            fn(*a)

                    return call

                self._pool = ShmRegionPool(
                    tag=f"tct_{os.getpid()}_{self._shm_tag}",
                    depth=self._pipeline_depth,
                    register_fn=_weak(self._shm_register),
                    unregister_fn=_weak(self._shm_unregister_quiet),
                )
            return self._pool

    def _shm_register(self, name: str, key: str, byte_size: int) -> None:
        # no retry: register is not idempotent (duplicate names are
        # rejected), and it is a fast metadata RPC — a transient
        # failure surfaces to the caller, who may simply call again
        self._call(
            self._stub.SystemSharedMemoryRegister,
            pb.SystemSharedMemoryRegisterRequest(
                name=name, key=key, offset=0, byte_size=byte_size
            ),
            retryable=(),
        )

    def _shm_unregister_quiet(self, name: str) -> None:
        """Best-effort unregister (growth path, recovery's duplicate-
        name guard, teardown): failure must never mask the operation
        that needed it."""
        try:
            self._stub.SystemSharedMemoryUnregister(
                pb.SystemSharedMemoryUnregisterRequest(name=name),
                timeout=min(self._timeout_s, 2.0),
            )
        except grpc.RpcError as e:
            log.debug("unregister of shm region %s failed (%s)", name, e)

    def _maybe_disable_shm(self, e: grpc.RpcError) -> bool:
        """Auto-negotiation escape hatch: a server that answers the shm
        extension with UNIMPLEMENTED / PERMISSION_DENIED / UNKNOWN does
        not serve it (stock server, tunneled dial that only LOOKS
        loopback, fake test servicer) — flip this channel to the wire
        permanently and tell the caller to re-issue there. Forced
        ``use_shared_memory=True`` never degrades."""
        code = e.code() if hasattr(e, "code") else None
        if self._shm_auto and code in _SHM_UNSUPPORTED:
            log.info(
                "endpoint %s does not serve the shared-memory extension "
                "(%s); negotiated transport falls back to the wire",
                self._endpoint, code,
            )
            self._use_shm = False
            return True
        return False

    def _stage_shm(
        self,
        request: InferRequest,
        extra_params: dict | None = None,
        acquire_timeout_s: float | None = None,
    ):
        """Acquire a pool slot, write the request's inputs into its
        regions, and build the coordinate-carrying wire message.
        Returns ``(wire, slot)`` with the slot owned by the caller (it
        must be released when the response is parsed or the request
        abandoned). Known output sizes additionally attach requested-
        output windows in the slot's arena so the response bypasses
        the wire too. ``acquire_timeout_s`` overrides how long to wait
        for a free slot (the async path passes 0: a caller that
        pipelines PAST the pool depth overflows onto the wire rather
        than deadlocking its own issuing thread, since slots only free
        when that thread resolves futures)."""
        pool = self._shm_pool()
        slot = pool.acquire(
            timeout_s=self._timeout_s
            if acquire_timeout_s is None
            else acquire_timeout_s
        )
        try:
            shm_inputs = {}
            arrays = {}
            for name, value in request.inputs.items():
                arr = np.asarray(value)
                region = slot.region_for(f"i_{name}", arr.nbytes)
                region.write(arr)
                shm_inputs[name] = (region.key.lstrip("/"), 0, arr.nbytes)
                arrays[name] = arr
            params = _wire_params(request)
            if extra_params:
                params = {**(params or {}), **extra_params}
            wire = codec.build_infer_request_shm(
                model_name=request.model_name,
                inputs=arrays,
                shm_inputs=shm_inputs,
                model_version=request.model_version,
                request_id=request.request_id,
                parameters=params,
                input_parameters=request.input_params,
            )
            self._request_shm_outputs(wire, slot, request.model_name)
            return wire, slot
        except BaseException:
            pool.release(slot)
            raise

    def _request_shm_outputs(self, wire, slot, model_name: str) -> None:
        """Attach requested-output windows (learned sizes, cache-line
        aligned) in the slot's output arena. No-op until a first
        response has taught the channel this model's output sizes."""
        # a copy: another caller's response may teach this channel a
        # larger size between the arena's sizing and the windows below
        # (a model whose answers differ in rows: a window past the arena)
        sizes = dict(self._learned_out.get(model_name) or ())
        if not sizes:
            return
        offsets = {}
        total = 0
        for name in sorted(sizes):
            offsets[name] = total
            total += -(-sizes[name] // _SHM_OUT_ALIGN) * _SHM_OUT_ALIGN
        arena = slot.region_for("o", total)
        rname = arena.key.lstrip("/")
        for name, off in offsets.items():
            codec.add_requested_output(wire, name, rname, off, sizes[name])

    def _parse_shm_response(
        self, resp, slot, model_name: str, t0: float
    ) -> InferResponse:
        regions = {}
        arena = slot.regions.get("o")
        if arena is not None:
            regions[arena.key.lstrip("/")] = arena
        outputs = codec.parse_infer_response(resp, regions=regions)
        if arena is not None:
            # arena views die with the slot (the next request on this
            # slot overwrites them): materialize arena-backed outputs
            # into owned arrays — the single designed host copy on the
            # response path, replacing protobuf serialize + framing +
            # parse. Wire-backed views keep their protobuf buffer.
            arena_outs = {
                t.name for t in resp.outputs
                if codec.shm_params(t) is not None
            }
            for name in arena_outs:
                outputs[name] = np.copy(outputs[name])
        sizes = self._learned_out.setdefault(model_name, {})
        for name, arr in outputs.items():
            if sizes.get(name, 0) < arr.nbytes:
                sizes[name] = arr.nbytes
        return InferResponse(
            model_name=resp.model_name,
            model_version=resp.model_version,
            outputs=outputs,
            request_id=resp.id,
            latency_s=time.perf_counter() - t0,
            parameters=_response_params(resp),
        )

    def _recover_shm(self, e: grpc.RpcError, wire, request: InferRequest):
        """A restarted server has an empty registry: its
        INVALID_ARGUMENT 'not registered' is recoverable by
        re-registering the pool's segments and re-issuing once — the
        wire path recovers from restarts via the UNAVAILABLE ladder,
        the shm path must not be worse."""
        if not (
            e.code() == grpc.StatusCode.INVALID_ARGUMENT
            and "not registered" in (e.details() or "")
        ):
            self._record_infer_error(e)
            raise e
        pool = self._shm_pool()
        log.warning(
            "server lost shared-memory registrations (%s); "
            "re-registering %d region(s)",
            e.details(), len(pool.regions()),
        )
        pool.reregister_all()
        return self._call(
            self._stub.ModelInfer,
            wire,
            retryable=_INFER_RETRYABLE,
            deadline_s=request.deadline_s,
        )

    def _do_inference_shm(self, request: InferRequest) -> InferResponse:
        wire, slot = self._stage_shm(request)
        pool = self._pool
        try:
            t0 = time.perf_counter()
            try:
                # UNAVAILABLE-only retry, same contract as the wire path
                resp = self._call(
                    self._stub.ModelInfer,
                    wire,
                    retryable=_INFER_RETRYABLE,
                    deadline_s=request.deadline_s,
                )
            except grpc.RpcError as e:
                resp = self._recover_shm(e, wire, request)
            return self._parse_shm_response(
                resp, slot, request.model_name, t0
            )
        finally:
            pool.release(slot)

    def do_inference_async(self, request: InferRequest) -> InferFuture:
        """Non-blocking ModelInfer via a gRPC call future (the --async
        path): the RPC is on the wire when this returns; result() parses
        the response. A connection-level failure (UNAVAILABLE — the only
        code safe to re-issue, see _call) falls back to the sync retry
        ladder at resolution time; all other errors surface at result().

        On a shm-negotiated channel the async path rides shm too: each
        in-flight request owns a pool slot (released at resolution), so
        up to ``pipeline_depth`` async calls overlap without ever
        aliasing a live region — the pre-round-13 wire fallback and its
        one-time warning are gone.

        The returned future is cancellable and subscribable (see
        InferFuture): cancel() abandons the wire call, and
        add_done_callback fires on the gRPC completion thread — the
        router's hedging relies on both to take the first winner and
        release the loser's replica slot. The resolution-time retry
        fallback honors request.deadline_s, so a failover retry never
        sleeps past the caller's budget."""
        # same pre-transport fail-fast as do_inference: async contract
        # says errors surface at result(), so wrap it in a future
        if (
            request.deadline_s is not None
            and request.deadline_s - time.perf_counter() <= 0
        ):
            return InferFuture.failed(
                DeadlineExceededRpcError(
                    "deadline expired before async ModelInfer was issued"
                )
            )
        if self._use_shm:
            try:
                return self._do_inference_async_shm(request)
            except TimeoutError:
                # pool exhausted: the overflow request rides the wire
                # (see _stage_shm — blocking here could deadlock a
                # single-threaded pipelining driver)
                pass
            except grpc.RpcError as e:
                if not self._maybe_disable_shm(e):
                    # async contract: errors surface at result()
                    return InferFuture.failed(e)
        try:
            wire = codec.build_infer_request(
                model_name=request.model_name,
                inputs=request.inputs,
                model_version=request.model_version,
                request_id=request.request_id,
                parameters=_wire_params(request),
                input_parameters=request.input_params,
            )
            t0 = time.perf_counter()
            call = self._issue_async(wire, request.deadline_s)
        except Exception as e:  # async contract: errors surface at result()
            return InferFuture.failed(e)

        def resolve() -> InferResponse:
            try:
                resp = call.result()
            except grpc.RpcError as e:
                resp = self._async_retry(e, wire, request)
            return InferResponse(
                model_name=resp.model_name,
                model_version=resp.model_version,
                outputs=codec.parse_infer_response(resp),
                request_id=resp.id,
                latency_s=time.perf_counter() - t0,
                parameters=_response_params(resp),
            )

        return InferFuture(
            resolve,
            cancel=call.cancel,
            subscribe=lambda fn: call.add_done_callback(lambda _c: fn()),
        )

    def _issue_async(self, wire, deadline_s: float | None):
        t0 = time.perf_counter()
        timeout = self._timeout_s
        if deadline_s is not None:
            remaining = deadline_s - t0
            if remaining <= 0:
                raise DeadlineExceededRpcError(
                    "deadline expired before async ModelInfer was issued"
                )
            timeout = min(timeout, remaining)
        return self._stub.ModelInfer.future(wire, timeout=timeout)

    def _async_retry(self, e: grpc.RpcError, wire, request: InferRequest):
        """Resolution-time fallback shared by the wire and shm async
        paths. Only connection-level failures (UNAVAILABLE) are
        re-issued automatically — the code least likely to mean the
        request executed server-side (no such gRPC code guarantees
        it). DEADLINE_EXCEEDED/RESOURCE_EXHAUSTED requests frequently
        HAVE executed, so re-running those is unsafe for
        non-idempotent models and doubles load exactly when the server
        is saturated. CANCELLED means our own cancel() won the race —
        never re-issue it."""
        self._record_infer_error(e)
        code = e.code() if hasattr(e, "code") else None
        if code not in _INFER_RETRYABLE:
            raise e
        log.warning(
            "async ModelInfer failed (%s); re-issuing on the "
            "sync retry path", code,
        )
        return self._call(
            self._stub.ModelInfer,
            wire,
            retryable=_INFER_RETRYABLE,
            deadline_s=request.deadline_s,
        )

    def _do_inference_async_shm(self, request: InferRequest) -> InferFuture:
        wire, slot = self._stage_shm(request, acquire_timeout_s=0.0)
        pool = self._pool
        try:
            t0 = time.perf_counter()
            call = self._issue_async(wire, request.deadline_s)
        except BaseException:
            pool.release(slot)
            raise

        def resolve() -> InferResponse:
            try:
                try:
                    resp = call.result()
                except grpc.RpcError as e:
                    if (
                        e.code() == grpc.StatusCode.INVALID_ARGUMENT
                        and "not registered" in (e.details() or "")
                    ):
                        resp = self._recover_shm(e, wire, request)
                    else:
                        resp = self._async_retry(e, wire, request)
                return self._parse_shm_response(
                    resp, slot, request.model_name, t0
                )
            finally:
                pool.release(slot)

        def cancel() -> bool:
            ok = call.cancel()
            if ok:
                # the server may still write this request's outputs
                # into the arena arbitrarily late: retire it (next use
                # re-creates a fresh generation) so the slot's next
                # owner can never be corrupted by a ghost write
                slot.retire("o")
                pool.release(slot)
            return ok

        return InferFuture(
            resolve,
            cancel=cancel,
            subscribe=lambda fn: call.add_done_callback(lambda _c: fn()),
        )

    # -- extras ---------------------------------------------------------------

    def server_live(self, timeout_s: float | None = None) -> bool:
        try:
            return self._call(
                self._stub.ServerLive, pb.ServerLiveRequest(),
                timeout_s=timeout_s,
            ).live
        except grpc.RpcError:
            return False

    def server_ready(self, timeout_s: float | None = None) -> bool:
        """Readiness (vs liveness): a DRAINING server stays live but
        flips not-ready first, so orchestrators pull it from rotation
        before its in-flight work finishes. ``timeout_s`` overrides the
        channel deadline for this probe — the router's health loop
        probes every replica each interval and must not hang an
        interval's budget on one dead endpoint."""
        try:
            return self._call(
                self._stub.ServerReady, pb.ServerReadyRequest(),
                timeout_s=timeout_s,
            ).ready
        except grpc.RpcError:
            return False

    def model_ready(
        self,
        model_name: str,
        model_version: str = "",
        timeout_s: float | None = None,
    ) -> bool:
        """Per-model readiness (KServe ModelReady): the router probes
        this for its configured model set so a replica that is live but
        has not yet loaded/warmed the model stays out of rotation."""
        try:
            return self._call(
                self._stub.ModelReady,
                pb.ModelReadyRequest(name=model_name, version=model_version),
                retryable=(),
                timeout_s=timeout_s,
            ).ready
        except grpc.RpcError:
            return False

    def repository_index(self) -> list[tuple[str, str, str]]:
        """[(name, version, state)] from the server's RepositoryIndex
        (the 'what is actually being served' query the reference could
        only get from Triton's logs)."""
        resp = self._call(
            self._stub.RepositoryIndex, pb.RepositoryIndexRequest()
        )
        return [(m.name, m.version, m.state) for m in resp.models]

    def _stream_groups(self, requests, group_size: int):
        """Batch consecutive compatible requests into frame groups of
        up to ``group_size`` for the multi-frame stream protocol. A
        request joins a group only when it matches the group head on
        model/version/priority and every input's shape+dtype, carries
        no trace or per-input params, and all inputs have a leading
        axis to pack along; anything else flushes the group and streams
        as a singleton. Grouping buffers up to group_size requests, so
        it suits open-loop producers (a camera, a replayed log) — a
        closed-loop caller that waits on responses must keep
        ``group_size=1``."""

        def groupable(r: InferRequest) -> bool:
            if r.trace is not None or r.input_params:
                return False
            if r.sequence_id:
                # a packed group travels under the HEAD's parameters —
                # session frames must each carry their own sequence
                # params (and two streams must never share a message)
                return False
            return all(np.asarray(v).ndim >= 1 for v in r.inputs.values())

        def compatible(a: InferRequest, b: InferRequest) -> bool:
            if (
                a.model_name != b.model_name
                or a.model_version != b.model_version
                or a.priority != b.priority
                or set(a.inputs) != set(b.inputs)
            ):
                return False
            return all(
                np.asarray(v).shape == np.asarray(b.inputs[k]).shape
                and np.asarray(v).dtype == np.asarray(b.inputs[k]).dtype
                for k, v in a.inputs.items()
            )

        group: list[InferRequest] = []
        for r in requests:
            if group_size > 1 and groupable(r):
                if group and not compatible(group[0], r):
                    yield group
                    group = []
                group.append(r)
                if len(group) >= group_size:
                    yield group
                    group = []
            else:
                if group:
                    yield group
                    group = []
                yield [r]
        if group:
            yield group

    def _stage_stream_group(self, members: list[InferRequest]):
        """One wire message for a group of G compatible requests:
        members' inputs are packed back-to-back along the leading axis
        — into a pooled shm region per input on a shm channel (no
        intermediate concatenation; the region write IS the pack), or
        into joined raw content on the wire. Returns ``(wire, slot)``;
        slot is None on the wire path. Responses always ride the wire:
        a stream multiplexes many in-flight requests per slot, so
        there is no per-request output arena to target."""
        first = members[0]
        g = len(members)
        slot = (
            self._shm_pool().acquire(timeout_s=self._timeout_s)
            if self._use_shm
            else None
        )
        try:
            req = pb.ModelInferRequest(
                model_name=first.model_name,
                model_version=first.model_version,
                id=first.request_id,
            )
            params = dict(_wire_params(first) or {})
            if g > 1:
                params[codec.STREAM_GROUP_PARAM] = g
                ids = [m.request_id for m in members]
                if any(ids):
                    params[codec.STREAM_GROUP_IDS_PARAM] = json.dumps(ids)
            codec.set_request_params(req, params)
            for name in sorted(first.inputs):
                arrs = [np.asarray(m.inputs[name]) for m in members]
                a0 = arrs[0]
                shape = (
                    (g * a0.shape[0],) + tuple(a0.shape[1:])
                    if g > 1
                    else a0.shape
                )
                t = req.inputs.add(
                    name=name, datatype=codec.datatype_of(a0), shape=shape
                )
                if g == 1 and first.input_params:
                    codec.set_request_params(
                        t, first.input_params.get(name)
                    )
                if slot is not None:
                    region = slot.region_for(f"i_{name}", g * a0.nbytes)
                    for i, a in enumerate(arrs):
                        region.write(a, offset=i * a0.nbytes)
                    codec.set_shm_params(
                        t, region.key.lstrip("/"), 0, g * a0.nbytes
                    )
                else:
                    req.raw_input_contents.append(
                        b"".join(codec.serialize_tensor(a) for a in arrs)
                    )
            return req, slot
        except BaseException:
            if slot is not None:
                self._pool.release(slot)
            raise

    def infer_stream(
        self,
        requests,
        stream_timeout_s: float | None = 3600.0,
        group_size: int = 1,
    ):
        """Bidirectional streaming inference (the reference's unused
        --streaming flag, main.py:66-70, made real). ``requests`` is an
        iterable of InferRequest; yields InferResponse in request order.

        On a shm-negotiated channel every stream entry stages its
        inputs through the region pool (one slot per in-flight group,
        released when the group's last response lands), so the stream
        path skips the tensor serialize/copy/deserialize exactly like
        unary shm — the pre-round-13 wire fallback is gone.

        ``group_size > 1`` enables the multi-frame protocol: up to that
        many consecutive compatible requests pack into ONE stream
        message (frames concatenated on the leading axis) that the
        server fans back into individual batcher requests, so a long
        tunnel RTT is paid once per group instead of once per frame.
        The server streams one response per member as each resolves; a
        whole-group failure is prefixed ``stream group failed:`` so it
        consumes all member responses at once.

        ``stream_timeout_s`` bounds the WHOLE stream (gRPC deadlines are
        per-call): a stalled server or a silent network partition
        surfaces as DEADLINE_EXCEEDED instead of hanging the client
        forever — the unary path gets the same protection from
        ``timeout_s`` per request. Pass None for an unbounded session
        (long-lived live streams)."""
        # appended by wire_iter on gRPC's request-consumer thread,
        # consumed in order here: the server answers each message only
        # after receiving it, so an entry is always enqueued before its
        # first response arrives (deque ops are atomic under the GIL)
        entries: collections.deque = collections.deque()

        def wire_iter():
            for members in self._stream_groups(requests, group_size):
                wire, slot = self._stage_stream_group(members)
                entries.append(
                    {"members": members, "slot": slot,
                     "remaining": len(members)}
                )
                yield wire

        call = self._stub.ModelStreamInfer(
            wire_iter(), timeout=stream_timeout_s
        )
        try:
            for resp in call:
                entry = entries[0]
                if resp.error_message:
                    msg = resp.error_message
                    whole_entry = (
                        len(entry["members"]) == 1
                        or msg.startswith("stream group failed: ")
                    )
                    if whole_entry:
                        entries.popleft()
                        if entry["slot"] is not None:
                            self._pool.release(entry["slot"])
                        if (
                            entry["slot"] is not None
                            and "not registered" in msg
                        ):
                            # server lost its registry mid-stream (see
                            # _recover_shm): re-register the pool and
                            # re-issue this entry's members unary so
                            # the stream keeps its one-response-per-
                            # request contract
                            log.warning(
                                "stream entry hit an empty server shm "
                                "registry (%s); re-registering and "
                                "re-issuing %d member(s)",
                                msg, len(entry["members"]),
                            )
                            self._shm_pool().reregister_all()
                            for m in entry["members"]:
                                yield self.do_inference(m)
                            continue
                    raise RuntimeError(msg)
                entry["remaining"] -= 1
                if entry["remaining"] <= 0:
                    entries.popleft()
                    if entry["slot"] is not None:
                        self._pool.release(entry["slot"])
                inner = resp.infer_response
                yield InferResponse(
                    model_name=inner.model_name,
                    model_version=inner.model_version,
                    outputs=codec.parse_infer_response(inner),
                    request_id=inner.id,
                    parameters=_response_params(inner),
                )
        finally:
            call.cancel()
            while entries:
                entry = entries.popleft()
                if entry["slot"] is not None:
                    self._pool.release(entry["slot"])

    def close(self) -> None:
        # client owns the shm segments: the pool unregisters server-
        # side (best effort — the server may already be gone) and
        # unlinks every slot's regions
        pool = self._pool
        if pool is not None:
            pool.close()
        if self._channel is not None:
            self._channel.close()
        for ch in self._retired:
            ch.close()
        self._retired.clear()

    def __del__(self):
        # best-effort: a dropped channel (the CLIs let main()'s locals
        # go out of scope) must still unregister + unlink its shm
        # segments — /dev/shm files outlive the process otherwise
        try:
            self.close()
        except Exception:
            pass

    # -- internals ------------------------------------------------------------

    def _record_infer_error(self, e) -> None:
        """Count server sheds distinctly: a RESOURCE_EXHAUSTED on
        ModelInfer is the admission controller rejecting on purpose —
        load the client should drop or defer, not a fault to retry."""
        try:
            if e.code() == grpc.StatusCode.RESOURCE_EXHAUSTED:
                self._infer_rejections += 1
        except (AttributeError, ValueError):
            pass

    def stats(self) -> dict:
        """Client-side counters: ``infer_rejections`` (ModelInfer
        requests the server shed with RESOURCE_EXHAUSTED — never
        retried), ``retries`` (transient failures the backoff ladder
        re-issued), the negotiated ``transport`` label, and the shm
        ``pool``'s occupancy/alias counters once it exists."""
        out = {
            "infer_rejections": self._infer_rejections,
            "retries": self._retries_total,
            "transport": self.transport,
        }
        if self._pool is not None:
            out["shm_pool"] = self._pool.stats()
        return out

    def _call(
        self,
        method,
        request,
        retryable=_RETRYABLE,
        deadline_s: float | None = None,
        timeout_s: float | None = None,
    ):
        """Retry ladder with capped exponential backoff and full
        jitter. ``retryable`` is the set of status codes safe to
        re-issue for THIS method: idempotent queries (metadata,
        liveness, index) retry on the full set, while ModelInfer must
        pass only connection-level codes (UNAVAILABLE) — a
        DEADLINE_EXCEEDED/RESOURCE_EXHAUSTED request may have executed
        server-side, and re-running it is unsafe for non-idempotent
        models and doubles load exactly when the server is saturated.
        The jitter (uniform over (delay/2, delay]) decorrelates a fleet
        of clients retrying against one recovering server, so the
        retries do not arrive as synchronized 2^n waves.

        ``deadline_s`` is the request's ABSOLUTE perf_counter deadline
        (InferRequest.deadline_s). It caps every attempt's wire timeout
        to the remaining budget AND caps the cumulative backoff sleep:
        if the budget is spent, or the next sleep would spend it, the
        ladder fails fast with a client-local DeadlineExceededRpcError
        instead of sleeping past a deadline nobody is waiting on.
        ``timeout_s`` overrides the channel's per-attempt timeout for
        THIS call (the router's health probes want a short one without
        re-dialing a second channel)."""
        delay = self._backoff_s
        per_attempt = self._timeout_s if timeout_s is None else timeout_s
        for attempt in range(self._retries + 1):
            timeout = per_attempt
            if deadline_s is not None:
                remaining = deadline_s - time.perf_counter()
                if remaining <= 0:
                    raise DeadlineExceededRpcError(
                        "deadline expired before attempt %d of rpc %s"
                        % (attempt + 1, getattr(method, "_method", method))
                    )
                timeout = min(per_attempt, remaining)
            try:
                return method(request, timeout=timeout)
            except grpc.RpcError as e:
                code = e.code() if hasattr(e, "code") else None
                if attempt >= self._retries or code not in retryable:
                    raise
                sleep_s = delay * random.uniform(0.5, 1.0)
                if (
                    deadline_s is not None
                    and time.perf_counter() + sleep_s >= deadline_s
                ):
                    # the backoff sleep would outlive the caller's
                    # deadline: every further attempt is wasted work
                    # delivered to nobody — fail fast instead
                    raise DeadlineExceededRpcError(
                        "remaining deadline %.3fs < backoff %.3fs after "
                        "%s (attempt %d/%d)"
                        % (
                            deadline_s - time.perf_counter(),
                            sleep_s,
                            code,
                            attempt + 1,
                            self._retries,
                        )
                    ) from e
                log.warning(
                    "rpc %s failed (%s); retry %d/%d in %.2fs",
                    getattr(method, "_method", method),
                    code,
                    attempt + 1,
                    self._retries,
                    sleep_s,
                )
                self._retries_total += 1
                time.sleep(sleep_s)
                delay = min(delay * 2, _BACKOFF_CAP_S)
