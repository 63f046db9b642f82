"""KServe v2 gRPC serving façade over the model repository.

This is the in-tree replacement for the Triton Inference Server binary
the reference deploys in docker (SURVEY.md §2.9 row 1): a gRPC server
speaking the same KServe v2 protocol (so the reference's ROS tooling
and any tritonclient-based caller work unchanged), dispatching to
jit-compiled JAX functions through a BaseChannel (normally TPUChannel
on a device mesh) instead of CUDA backends.

Differences from the reference's serving story, by design:
  * message size limits are computed from the registered model specs
    (the reference hardcodes batch_size * 8568044 bytes with a "make
    dynamic" TODO, grpc_channel.py:26-29 / README.md:118);
  * ModelStreamInfer is implemented, not a dangling flag
    (main.py:59-70 exposes --streaming but the refactored client never
    exercises it);
  * errors surface as rich gRPC status codes rather than a returned
    exception object (yolov5_postprocess.py:124-125).
"""

from __future__ import annotations

import concurrent.futures
import itertools
import json
import logging
import os
import threading
import time

import grpc

from triton_client_tpu import __version__
from triton_client_tpu.channel.base import BaseChannel, InferRequest
from triton_client_tpu.channel.kserve import codec, pb, service
from triton_client_tpu.config import FRAMING_BYTES
from triton_client_tpu.runtime import faults
from triton_client_tpu.runtime.admission import (
    AdmissionController,
    AdmissionRejectedError,
    CircuitOpenError,
    DeadlineExpiredError,
    OverloadError,
    ReplicaDownError,
    ServerDrainingError,
)
from triton_client_tpu.obs.logs import log_tag
from triton_client_tpu.obs.trace import (
    SUMMARY_PARAM_KEY,
    TraceContext,
    encode_span_summary,
)
from triton_client_tpu.runtime import wire_encoding
from triton_client_tpu.runtime.repository import ModelRepository

log = logging.getLogger(__name__)

# Floor for the gRPC message cap; specs with dynamic (-1) dims fall back
# to this. 64 MiB covers the reference's largest contract (batch 8
# images, grpc_channel.py:26-29) with headroom.
_MIN_MSG_BYTES = 64 << 20


def message_limit(repository: ModelRepository) -> int:
    """Dynamic per-repository message cap (README.md:118's TODO).

    Computed from the specs registered *now*; InferenceServer reads it
    once at construction (gRPC server options are bind-time fixed), so
    register large models before constructing the server or pass an
    explicit ``max_message_bytes``.
    """
    best = _MIN_MSG_BYTES
    for name in repository.names():
        for version in repository.versions(name):
            spec = repository.metadata(name, version)
            best = max(best, 2 * spec.wire_bytes() + FRAMING_BYTES)
    return best


def _grpc_code(exc: BaseException) -> str:
    """gRPC status-code label for the per-model error counter, matching
    the codes ModelInfer aborts with. The overload family is mapped
    deliberately: RESOURCE_EXHAUSTED is non-retryable for ModelInfer
    clients (shedding must not amplify load), DEADLINE_EXCEEDED tells
    the caller its budget — not the server — killed the request, and
    UNAVAILABLE (breaker open / draining) is the connection-class code
    retry ladders and load balancers key on to go elsewhere."""
    if isinstance(exc, AdmissionRejectedError):  # incl. QueueFullError
        return "RESOURCE_EXHAUSTED"
    if isinstance(exc, DeadlineExpiredError):
        return "DEADLINE_EXCEEDED"
    if isinstance(
        exc, (CircuitOpenError, ServerDrainingError, ReplicaDownError)
    ):
        return "UNAVAILABLE"
    if isinstance(exc, KeyError):
        return "NOT_FOUND"
    if isinstance(exc, ValueError):
        return "INVALID_ARGUMENT"
    return "INTERNAL"


_GRPC_STATUS = {
    "RESOURCE_EXHAUSTED": grpc.StatusCode.RESOURCE_EXHAUSTED,
    "DEADLINE_EXCEEDED": grpc.StatusCode.DEADLINE_EXCEEDED,
    "UNAVAILABLE": grpc.StatusCode.UNAVAILABLE,
}


class _Memo:
    """What a peer string or a request's tensor descriptors fix,
    resolved once and looked up afterwards. Reads take no lock (a dict
    lookup is atomic), writers hold their owner's (a miss is rare); a
    full memo gives up its oldest entry. Every value is a pure function
    of its key, so an entry is never stale: one that is gone only costs
    the code that derives it again."""

    def __init__(self, capacity: int) -> None:
        self._entries: dict = {}
        self._capacity = capacity
        self.get = self._entries.get

    def put(self, key, value):
        if len(self._entries) >= self._capacity:
            del self._entries[next(iter(self._entries))]
        self._entries[key] = value


class _RemoteShm(Exception):
    """A shared-memory request from a peer that is not on this host."""


# transport label by (peer on a unix socket, input bytes in shm)
_TRANSPORT = {
    (False, False): "grpc", (False, True): "shm",
    (True, False): "uds", (True, True): "uds+shm",
}
_LOCAL_PEERS = ("ipv4:127.", "ipv6:[::1]", "ipv6:[::ffff:127.", "unix:")


class _Servicer(service.GRPCInferenceServiceServicer):
    def __init__(
        self,
        repository: ModelRepository,
        channel: BaseChannel,
        profiler=None,
        shm_registry=None,
        stream_pipeline_depth: int = 2,
        tracer=None,
        collector=None,
        slo=None,
        admission: AdmissionController | None = None,
        draining: threading.Event | None = None,
        lifecycle=None,
        replica_of: str | None = None,
        quality=None,
        temporal=None,
    ) -> None:
        self._repo = repository
        self._channel = channel
        self._lifecycle = lifecycle
        # replica label (--replica-of): names the replica set this
        # server belongs to. It keys the replica_down fault point so a
        # chaos plan can kill ONE labeled replica in a fleet, and rides
        # ServerMetadata.extensions so the route tool can display it.
        self._replica_of = replica_of
        self._profiler = profiler
        self._shm = shm_registry
        self._stream_depth = max(1, int(stream_pipeline_depth))
        self._tracer = tracer
        self._collector = collector
        self._slo = slo
        self._admission = admission
        self._draining = draining
        # continuous quality plane (ISSUE 17): canary routing before
        # dispatch, trace-hash shadow sampling after the readback —
        # both one attribute read on the un-wired hot path. The counter
        # backs an anonymous per-request key for id-less untraced
        # requests (sampling stays live, just not replay-deterministic)
        self._quality = quality
        self._quality_seq = itertools.count()
        # temporal-reuse plane (ISSUE 19): consulted before dispatch on
        # session frames — a coast/partial decision bypasses the full
        # detector launch entirely; the keyframe innovation feeds back
        # through finish(). One attribute read on the un-wired path.
        self._temporal = temporal
        # in-flight request count independent of the (optional)
        # collector — drain() polls it to know when the building is empty
        self._active = 0
        self._active_lock = threading.Lock()
        # the handler threads' own counters, a cell a thread so that a
        # request takes no lock for them: [thread CPU seconds inside
        # ModelInfer, requests, front-memo hits, misses, {transport
        # label: [requests, wire bytes, shm bytes]}]
        self._front_tls = threading.local()
        self._front_cells: list[list] = []
        # peer string -> (same host, unix socket); a request's tensor
        # descriptors -> its codec.RequestPlan (a client's shm slots
        # times its request shapes: a session's 193 requests are two)
        self._peers = _Memo(256)
        self._plans = _Memo(2048)
        self._memo_lock = threading.Lock()

    def active_requests(self) -> int:
        with self._active_lock:
            return self._active

    def _front_cell(self) -> list:
        try:
            return self._front_tls.cell
        except AttributeError:
            cell = self._front_tls.cell = [0.0, 0, 0, 0, {}]
            with self._active_lock:
                self._front_cells.append(cell)
            return cell

    def front_stats(self) -> dict:
        """What a request costs this process's interpreter, for
        ``/snapshot`` -> ``front_end``: ``handler_cpu_s`` is the handler
        threads' CPU time (``time.thread_time``: a thread that waits for
        its answer accrues none) inside ``ModelInfer`` over
        ``handler_requests`` of them. ``transport`` is the mix the
        collector reports (``/snapshot`` -> ``transport``): requests by
        negotiated label, input payload bytes by the wire and by shm."""
        with self._active_lock:
            cells = list(self._front_cells)
        transport: dict = {}
        for cell in cells:
            for label, counts in list(cell[4].items()):
                total = transport.setdefault(label, [0, 0, 0])
                for i, count in enumerate(counts):
                    total[i] += count
        return {
            "handler_cpu_s": sum(c[0] for c in cells),
            "handler_requests": sum(c[1] for c in cells),
            "front_memo_hits": sum(c[2] for c in cells),
            "front_memo_misses": sum(c[3] for c in cells),
            "transport": transport,
        }

    def _draining_now(self) -> bool:
        return self._draining is not None and self._draining.is_set()

    # -- health ---------------------------------------------------------------

    def ServerLive(self, request, context):
        return pb.ServerLiveResponse(live=True)

    def _replica_down_now(self) -> bool:
        return faults.probe_flag("replica_down", self._replica_of)

    def ServerReady(self, request, context):
        # a draining server flips not-ready FIRST so orchestrators pull
        # it from rotation before in-flight work finishes; an injected
        # replica_down fault answers not-ready the same way a dead
        # process would simply not answer
        return pb.ServerReadyResponse(
            ready=not self._draining_now() and not self._replica_down_now()
        )

    def ModelReady(self, request, context):
        if self._draining_now() or self._replica_down_now():
            return pb.ModelReadyResponse(ready=False)
        try:
            self._repo.get(request.name, request.version)
            ready = True
        except KeyError:
            ready = False
        return pb.ModelReadyResponse(ready=ready)

    # -- metadata -------------------------------------------------------------

    def ServerMetadata(self, request, context):
        extensions = [
            "model_repository",
            "binary_tensor_data",
            "system_shared_memory",
        ]
        if self._replica_of:
            # replica-set label as a metadata extension: the route tool
            # reads it back to confirm which fleet an endpoint claims
            extensions.append(f"replica_of:{self._replica_of}")
        return pb.ServerMetadataResponse(
            name="triton_client_tpu",
            version=__version__,
            extensions=extensions,
        )

    def _spec_or_abort(self, name, version, context):
        try:
            return self._repo.metadata(name, version)
        except KeyError as e:
            context.abort(grpc.StatusCode.NOT_FOUND, str(e))

    def ModelMetadata(self, request, context):
        spec = self._spec_or_abort(request.name, request.version, context)
        resp = pb.ModelMetadataResponse(
            name=spec.name,
            versions=list(self._repo.versions(spec.name)),
            platform=spec.platform,
        )
        for t in spec.inputs:
            resp.inputs.add(name=t.name, datatype=t.dtype, shape=t.shape)
        for t in spec.outputs:
            resp.outputs.add(name=t.name, datatype=t.dtype, shape=t.shape)
        return resp

    def ModelConfig(self, request, context):
        spec = self._spec_or_abort(request.name, request.version, context)
        config = pb.ModelConfig(
            name=spec.name,
            platform=spec.platform,
            max_batch_size=spec.max_batch_size,
        )
        for t in spec.inputs:
            config.input.add(
                name=t.name,
                data_type=codec.config_datatype(t.dtype),
                dims=t.shape,
            )
        for t in spec.outputs:
            config.output.add(
                name=t.name,
                data_type=codec.config_datatype(t.dtype),
                dims=t.shape,
            )
        # ModelSpec.extra rides the config parameters map (JSON values)
        # so remote clients self-configure host-side prep — the role the
        # reference's client-side parse_model plays over ModelConfig
        # (base_client.py:32-104).
        import json

        for key, value in spec.extra.items():
            config.parameters[key] = json.dumps(value)
        return pb.ModelConfigResponse(config=config)

    def RepositoryIndex(self, request, context):
        resp = pb.RepositoryIndexResponse()
        for name in self._repo.names():
            for version in self._repo.versions(name):
                resp.models.add(name=name, version=version, state="READY")
        return resp

    # -- shared memory (Triton system-shared-memory extension) ----------------

    @staticmethod
    def _is_local_peer(context) -> bool:
        # ipv6:[::ffff:127.*] is the v4-mapped loopback a dual-stack
        # bind reports for a 127.0.0.1 dial
        return context.peer().startswith(_LOCAL_PEERS)

    @classmethod
    def _require_local(cls, context) -> None:
        """Shared memory is a SAME-HOST transport: registration maps a
        /dev/shm file into the server and infer requests can read/write
        it, so a remote peer must never reach it (a remote client could
        otherwise attach any flat-named segment on the server host and
        exfiltrate or corrupt it through model IO). Loopback and unix
        sockets only."""
        if not cls._is_local_peer(context):
            cls._refuse_remote(context, context.peer())

    @staticmethod
    def _refuse_remote(context, peer: str) -> None:
        context.abort(
            grpc.StatusCode.PERMISSION_DENIED,
            f"shared-memory extension is restricted to same-host "
            f"clients (peer {peer})",
        )

    def SystemSharedMemoryRegister(self, request, context):
        self._require_local(context)
        try:
            self._shm.register(
                request.name, request.key, request.offset, request.byte_size
            )
        except (ValueError, OSError) as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        return pb.SystemSharedMemoryRegisterResponse()

    def SystemSharedMemoryUnregister(self, request, context):
        self._require_local(context)
        if request.name:
            self._shm.unregister(request.name)
        else:
            self._shm.unregister_all()
        return pb.SystemSharedMemoryUnregisterResponse()

    def SystemSharedMemoryStatus(self, request, context):
        self._require_local(context)
        resp = pb.SystemSharedMemoryStatusResponse()
        try:
            regions = self._shm.status(request.name)
        except KeyError as e:
            context.abort(grpc.StatusCode.NOT_FOUND, str(e))
        for name, reg in regions.items():
            resp.regions[name].name = name
            resp.regions[name].key = reg.key
            resp.regions[name].offset = reg.offset
            resp.regions[name].byte_size = reg.byte_size
        return resp

    # -- inference ------------------------------------------------------------

    def _issue(
        self, request, inputs_override=None, id_override=None, plan=None,
        t_front=None,
    ):
        """Parse + dispatch one request; returns a finisher callable.

        ``inputs_override``/``id_override``: set by _issue_group when
        this "request" is one member of a packed multi-frame stream
        message — the member's input views (already split off the
        group parse) and its per-member id replace the wire message's;
        parse, content decoding, and response shm placement are then
        skipped (the group was parsed once, encoded groups are not
        packed client-side, and a shared output region cannot serve G
        members). ``plan``: the request's ``codec.RequestPlan`` where
        :meth:`_front` holds one: parse and response then follow it
        instead of walking the tensors again, and the request carries
        no content encoding (a plan of one that does is not kept).
        ``t_front``: when ``ModelInfer`` was entered, taken there only
        with a tracer attached: the trace then has a ``front`` span
        from that moment to its own start (what :meth:`_front` did).

        The dispatch goes through ``do_inference_async`` so the device
        (or inner batcher) starts while THIS thread still prepares the
        response scaffolding; the finisher resolves the future (the
        only blocking step — deferred readback) and encodes the
        response. Stream pipelining keeps several finishers pending.

        Telemetry: a request-scoped trace (when tracing is on) rides
        the InferRequest through the batcher and channel, collecting
        parse/queue/stage/launch/device/readback/encode spans; the
        per-model latency histogram sample is recorded in a finally so
        FAILING requests are measured and counted too (they previously
        vanished from the metrics entirely).

        SLO plane: when a tracker with a budget is wired, the request's
        absolute deadline is stamped HERE — at admission, before parse —
        and rides the InferRequest through the batcher (a merge takes
        the min of its members') to the staged launchers; _account
        scores met/missed on every exit path."""
        t0 = time.perf_counter()
        request_id = id_override if id_override is not None else request.id
        trace = None
        if self._tracer is not None:
            # adopt the inbound distributed context (router- or client-
            # originated traceparent in the request parameters) so this
            # replica's spans join the fleet-wide trace; absent or
            # malformed context degrades to a purely local trace
            context = TraceContext.decode(
                codec.get_string_param(request, TraceContext.PARAM_KEY) or ""
            )
            trace = self._tracer.start(
                model=request.model_name, request_id=request_id,
                context=context,
            )
            if trace is not None and t_front is not None:
                trace.add("front", t_front, trace.t_start)
        # quality plane: the sampling/canary key is the trace id when
        # tracing is on (stable fleet-wide: the router's traceparent is
        # adopted above, so router and replica decide identically) and
        # the request id otherwise; routing may rewrite which registered
        # model actually serves this request (canary slice)
        tctx = getattr(trace, "context", None)
        tid = tctx.trace_id if tctx is not None else (request_id or "")
        served_name = request.model_name
        if self._quality is not None:
            if not tid:
                tid = f"anon-{next(self._quality_seq)}"
            served_name = self._quality.route(request.model_name, tid)
        # streaming-session identity (runtime/sessions.py), decoded
        # independent of the SLO plane and absent on stateless requests,
        # and the priority: one access of the request's parameters
        sequence_id, sequence_start, sequence_end, priority = (
            codec.sequence_params(request)
        )
        if trace is not None and sequence_id:
            trace.session = sequence_id
        deadline_s = None
        if self._slo is not None:
            deadline_s = self._slo.deadline_for(request.model_name, t0)
        else:
            priority = 0
        # the in-flight count is this one (the collector's gauge reads
        # it at scrape time: attach_front_end)
        with self._active_lock:
            self._active += 1
        admitted = False
        lifecycle_key = None
        try:
            # overload plane, cheapest checks first, BEFORE parse: a
            # shed request must cost microseconds, not a deserialize.
            # Raising from inside this try routes through _account, so
            # sheds are traced, error-counted, and SLO-scored as missed.
            if self._draining_now():
                raise ServerDrainingError(
                    "server is draining; retry against another replica"
                )
            if self._replica_down_now():
                # simulated process death: UNAVAILABLE with NO drain
                # marker, so routers run their ejection/budget path
                raise ReplicaDownError("replica is down (injected)")
            if self._admission is not None:
                try:
                    self._admission.admit(
                        request.model_name,
                        deadline_s=deadline_s,
                        priority=priority,
                        now=t0,
                    )
                except AdmissionRejectedError:
                    if self._collector is not None:
                        self._collector.record_shed(
                            request.model_name, priority, "admission"
                        )
                    raise
                admitted = True
            if self._lifecycle is not None:
                # promotion wait happens HERE, on the RPC thread: a
                # request for a cold model blocks (deadline-aware) while
                # the model pages in, so the batcher's single dispatcher
                # never head-of-line blocks on a warming model. The
                # reference is dropped in _account; the channel holds its
                # own acquire across the device window.
                try:
                    lifecycle_key = self._lifecycle.acquire(
                        served_name,
                        request.model_version,
                        deadline_s=deadline_s,
                    )
                except OverloadError:
                    if self._collector is not None:
                        self._collector.record_shed(
                            request.model_name, priority, "lifecycle"
                        )
                    raise
            if inputs_override is not None:
                inputs = inputs_override
            else:
                # chaos point: drop every attached segment right before
                # parse, so the parse fails exactly like a freshly
                # restarted server ('not registered' -> INVALID_ARGUMENT)
                # and clients must exercise their re-registration path
                if self._shm is not None and faults.probe_flag(
                    "shm_detach", request.model_name
                ):
                    self._shm.unregister_all()
                if trace is not None:
                    with trace.span("parse"):
                        inputs = codec.parse_infer_request(
                            request, shm=self._shm, plan=plan
                        )
                else:
                    inputs = codec.parse_infer_request(
                        request, shm=self._shm, plan=plan
                    )
                encodings = (
                    wire_encoding.encodings_of(request) if plan is None else None
                )
                if encodings:
                    # compressed wire payloads (JPEG frames, quantized
                    # pointclouds) decode on the host pool / device here;
                    # in a pipelined stream this runs on the reader
                    # thread while the previous request owns the device
                    if trace is not None:
                        with trace.span("decode"):
                            inputs = wire_encoding.decode_inputs(
                                inputs, encodings
                            )
                    else:
                        inputs = wire_encoding.decode_inputs(
                            inputs, encodings
                        )
            if trace is not None:
                # closed in finish() once the future resolves: the whole
                # channel-stack residence (queue/stage/device/readback
                # land inside it, plus the cross-thread hand-off gaps
                # none of those sub-spans can see)
                trace.begin("channel")
            ireq = InferRequest(
                model_name=served_name,
                model_version=request.model_version,
                inputs=inputs,
                request_id=request_id,
                trace=trace,
                deadline_s=deadline_s,
                priority=priority,
                sequence_id=sequence_id or "",
                sequence_start=sequence_start,
                sequence_end=sequence_end,
            )
            future = None
            if self._temporal is not None and sequence_id:
                # temporal reuse: the plane may serve this frame from
                # the stream's device-resident tracker alone (coast) or
                # from a changed-tiles sub-launch (partial); None means
                # keyframe — run the full detector below
                future = self._temporal.dispatch(ireq)
            if future is None:
                future = self._channel.do_inference_async(ireq)
            # overlapped with device execution: shm placement parsing
            # needs only the request, not the result
            templates = None
            if inputs_override is not None:
                shm_outputs = {}
            elif plan is not None:
                shm_outputs, templates = plan.shm_outputs, plan.responses
            else:
                shm_outputs = {
                    t.name: params
                    for t in request.outputs
                    if (params := codec.shm_params(t)) is not None
                }
        except BaseException as e:
            # parse/dispatch failed before a finisher existed: close out
            # the request's accounting here (finish() will never run)
            self._account(
                request.model_name, t0, trace, error=e,
                deadline_s=deadline_s, priority=priority,
                admitted=admitted, lifecycle_key=lifecycle_key,
            )
            raise

        def finish():
            error = None
            try:
                try:
                    result = future.result()
                finally:
                    if trace is not None:
                        trace.end("channel")
                if self._quality is not None:
                    # post-readback: outputs are host numpy here, so the
                    # sampled copy handed to the mirror queue costs no
                    # device sync on the serving path
                    try:
                        self._quality.observe(
                            request.model_name, served_name, tid,
                            inputs, result.outputs,
                        )
                    except Exception:
                        log.debug("quality observe failed", exc_info=True)
                if self._temporal is not None and sequence_id:
                    # keyframe feedback: stamps reuse_mode on the
                    # response, adapts K from the ridden-along
                    # innovation, runs the per-stream ID-churn gate
                    try:
                        self._temporal.observe(
                            request.model_name, sequence_id,
                            inputs, result.outputs,
                        )
                    except Exception:
                        log.debug("temporal observe failed", exc_info=True)
                if trace is not None:
                    t_e0 = time.perf_counter()
                    resp = codec.build_infer_response(
                        model_name=result.model_name,
                        model_version=result.model_version,
                        outputs=result.outputs,
                        request_id=result.request_id,
                        shm_outputs=shm_outputs,
                        shm=self._shm,
                        fallback_to_wire=True,
                        templates=templates,
                    )
                    trace.add("encode", t_e0, time.perf_counter())
                    if trace.context is not None:
                        # the request carried a traceparent: somebody
                        # upstream (the router originates one) grafts
                        # this replica's spans onto the end-to-end
                        # trace, so the compact span summary rides the
                        # response (AFTER the encode span lands, so the
                        # grafted timeline includes it). A bare client's
                        # traced request gets none: a sort and a
                        # json.dumps a request that nobody reads
                        codec.set_request_params(
                            resp,
                            {SUMMARY_PARAM_KEY: encode_span_summary(trace)},
                        )
                    return resp
                return codec.build_infer_response(
                    model_name=result.model_name,
                    model_version=result.model_version,
                    outputs=result.outputs,
                    request_id=result.request_id,
                    shm_outputs=shm_outputs,
                    shm=self._shm,
                    fallback_to_wire=True,
                    templates=templates,
                )
            except BaseException as e:
                error = e
                raise
            finally:
                self._account(
                    request.model_name, t0, trace, error=error,
                    deadline_s=deadline_s, priority=priority,
                    admitted=admitted, lifecycle_key=lifecycle_key,
                )

        return finish

    def _account(
        self, model_name, t0, trace, error=None, deadline_s=None, priority=0,
        admitted=False, lifecycle_key=None,
    ) -> None:
        """Per-request bookkeeping, success or failure: latency sample
        (the Triton :8002 serving-metrics role, README.md:88-95), error
        counter with a gRPC status-code label, in-flight gauge, trace
        finish, SLO attainment score. Reached from a ``finally`` on
        every request path (tpulint TPL503 pins that), so the
        deadline-missed and error paths are scored too."""
        now = time.perf_counter()
        if error is not None:
            # correlated failure line: the trace tag greps across the
            # router's and client's logs for the same request
            log.debug(
                "request for model %s failed with %s: %s%s",
                model_name, _grpc_code(error), error, log_tag(trace),
            )
        elif log.isEnabledFor(logging.DEBUG):
            log.debug(
                "request for model %s served in %.1f ms%s",
                model_name, (now - t0) * 1e3, log_tag(trace),
            )
        if self._tracer is not None:
            # close the trace FIRST: everything below is bookkeeping
            # that would otherwise show up as an uncovered tail on the
            # request wall. Finishing also feeds the per-(model, stage)
            # latency histograms, so the SLO tracker's p99 tail
            # criterion below sees this request's e2e sample.
            self._tracer.finish(
                trace, status="ok" if error is None else _grpc_code(error)
            )
        if self._slo is not None:
            self._slo.observe_request(
                model_name,
                wall_s=now - t0,
                deadline_s=deadline_s,
                priority=priority,
                status="ok" if error is None else _grpc_code(error),
                trace=trace,
                now=now,
            )
        if self._profiler is not None:
            self._profiler.record(
                f"infer_{model_name}", time.perf_counter() - t0
            )
        if self._collector is not None and error is not None:
            self._collector.record_error(model_name, _grpc_code(error))
        if self._admission is not None and admitted:
            # successful requests feed the EWMA the estimated-wait
            # check divides by; failures only release their slot
            self._admission.finished(
                model_name,
                service_s=(now - t0) if error is None else None,
            )
        if self._lifecycle is not None and lifecycle_key is not None:
            self._lifecycle.release(*lifecycle_key)
        with self._active_lock:
            self._active -= 1

    def _uses_shm(self, request) -> bool:
        return any(
            "shared_memory_region" in t.parameters
            for t in list(request.inputs) + list(request.outputs)
        )

    def _front(self, request, context, cell=None):
        """What the connection and the request's tensor descriptors fix,
        before anything is issued: ONE ``context.peer()`` (it lets go of
        the interpreter lock and takes it again), its locality and
        transport label looked up by the peer string; the request's
        ``codec.RequestPlan`` looked up by its descriptors' bytes, or
        made in one walk and kept. A shared-memory request from a peer
        that is not on this host raises :class:`_RemoteShm`, on EVERY
        request and from the peer THAT request came from (the memo
        holds what a peer string means, never who may pass); the
        transport mix is counted in the thread's cell. Returns the plan, or None for a
        request that has none to keep (malformed descriptors, a content
        encoding, whose parameters differ request by request): the
        caller then walks the tensors as it always did."""
        peer = context.peer()
        facts = self._peers.get(peer)
        if facts is None:
            facts = peer.startswith(_LOCAL_PEERS), peer.startswith("unix:")
            with self._memo_lock:
                self._peers.put(peer, facts)
        key = codec.request_fingerprint(request)
        plan = self._plans.get(key)
        if cell is None:
            cell = self._front_cell()
        if plan is not None:
            cell[2] += 1
        else:
            cell[3] += 1
            try:
                if not wire_encoding.encodings_of(request):
                    plan = codec.plan_infer_request(request)
                    with self._memo_lock:
                        self._plans.put(key, plan)
            except ValueError:
                pass  # said where it always was: by the parse, accounted
        if plan is not None:
            uses_shm, shm_bytes = plan.uses_shm, plan.shm_bytes
        else:
            uses_shm, shm_bytes = self._uses_shm(request), 0
            for t in request.inputs:
                p = t.parameters
                if "shared_memory_region" in p and "shared_memory_byte_size" in p:
                    shm_bytes += int(p["shared_memory_byte_size"].int64_param)
        if uses_shm and not facts[0]:
            raise _RemoteShm(peer)
        # which transport carried this request's tensors and how many
        # payload bytes each moved (input side only: it dominates for
        # perception serving, and response bytes are not knowable until
        # resolution)
        label = _TRANSPORT[facts[1], shm_bytes > 0]
        mix = cell[4].get(label)
        if mix is None:
            mix = cell[4][label] = [0, 0, 0]
        raws = request.raw_input_contents
        mix[0] += 1
        if raws:
            mix[1] += sum(map(len, raws))
        mix[2] += shm_bytes
        return plan

    @staticmethod
    def _stream_group_size(request) -> int:
        return max(1, codec.get_int_param(request, codec.STREAM_GROUP_PARAM, 1))

    def _issue_group(self, request, plan=None):
        """Fan one multi-frame stream message into per-member batcher
        requests; returns one finisher per member, in member order.

        The packed message concatenates G equal-shape frames along the
        leading axis (client: GRPCChannel._stage_stream_group); each
        member is issued through the full admission/lifecycle/batcher
        path as its own request with its own id, so the continuous
        batcher schedules members individually and responses stream
        back as each resolves. Member inputs are zero-copy views into
        the group parse — no unpack copy. A member whose ISSUE fails
        (shed, cold model) becomes a finisher that raises its error,
        so the other members still serve and the client sees a
        per-member error_message."""
        g = self._stream_group_size(request)
        if g == 1:
            return [self._issue(request, plan=plan)]
        if self._shm is not None and faults.probe_flag(
            "shm_detach", request.model_name
        ):
            self._shm.unregister_all()
        inputs = codec.parse_infer_request(request, shm=self._shm, plan=plan)
        members: list[dict] = [{} for _ in range(g)]
        for name, arr in inputs.items():
            if arr.ndim < 1 or arr.shape[0] % g:
                raise ValueError(
                    f"stream group of {g} needs every input's leading "
                    f"axis divisible by {g}; input {name!r} has shape "
                    f"{tuple(arr.shape)}"
                )
            b = arr.shape[0] // g
            for i in range(g):
                members[i][name] = arr[i * b : (i + 1) * b]
        raw_ids = codec.get_string_param(
            request, codec.STREAM_GROUP_IDS_PARAM
        )
        try:
            ids = json.loads(raw_ids) if raw_ids else []
        except ValueError:
            ids = []
        if len(ids) != g:
            ids = [f"{request.id}#{i}" if request.id else "" for i in range(g)]
        def deferred_error(err):
            def fin():
                raise err
            return fin

        finishers = []
        for i in range(g):
            try:
                fin = self._issue(
                    request, inputs_override=members[i], id_override=ids[i]
                )
            except Exception as e:
                # already accounted by _issue's except path; defer the
                # error to this member's response slot
                fin = deferred_error(e)
            finishers.append(fin)
        return finishers

    @staticmethod
    def _group_error(request, e: BaseException) -> str:
        """error_message for a failure that consumed a WHOLE stream
        entry before any member was issued (group parse/validation):
        the prefix tells the client to retire all G member slots at
        once instead of waiting for per-member responses."""
        g = _Servicer._stream_group_size(request)
        if g > 1:
            return f"stream group failed: {e}"
        return str(e)

    def ModelInfer(self, request, context):
        c0 = time.thread_time()
        # a traced request's ``front`` span begins here
        t_front = time.perf_counter() if self._tracer is not None else None
        cell = self._front_cell()
        try:
            return self._issue(
                request, plan=self._front(request, context, cell),
                t_front=t_front,
            )()
        except _RemoteShm as e:
            self._refuse_remote(context, str(e))
        except OverloadError as e:
            context.abort(_GRPC_STATUS[_grpc_code(e)], str(e))
        except KeyError as e:
            context.abort(grpc.StatusCode.NOT_FOUND, str(e))
        except ValueError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        except Exception as e:
            # launch/readback faults (incl. injected ones) abort as
            # INTERNAL — matching the _grpc_code error-counter label —
            # instead of grpc's opaque UNKNOWN, so clients can key
            # retry-elsewhere policy on a stable code
            context.abort(grpc.StatusCode.INTERNAL, str(e))
        finally:
            # what the request cost this process's interpreter: a thread
            # that waits for its answer accrues no CPU time
            cell[0] += time.thread_time() - c0
            cell[1] += 1

    def ModelStreamInfer(self, request_iterator, context):
        """Pipelined stream serving: up to ``stream_pipeline_depth``
        requests stay in flight per stream — request N+1 parses and
        launches (on a reader thread) while request N's compute runs;
        responses come back in request order, each sent the moment it
        resolves. Responses are NEVER withheld pending further
        requests, so a lock-step client (send, wait, send) sees
        strictly serial semantics regardless of depth — the pipelining
        only engages when the client itself keeps requests in flight.
        Depth 1 skips the reader thread entirely."""
        if self._stream_depth <= 1:
            for request in request_iterator:
                try:
                    plan = self._front(request, context)
                except _RemoteShm as e:
                    self._refuse_remote(context, str(e))
                    return
                if (
                    self._collector is not None
                    and (g := self._stream_group_size(request)) > 1
                ):
                    self._collector.record_stream_group(g)
                try:
                    finishers = self._issue_group(request, plan)
                except (KeyError, ValueError, OverloadError) as e:
                    yield pb.ModelStreamInferResponse(
                        error_message=self._group_error(request, e)
                    )
                    continue
                for fin in finishers:
                    try:
                        yield pb.ModelStreamInferResponse(
                            infer_response=fin()
                        )
                    except (KeyError, ValueError, OverloadError) as e:
                        yield pb.ModelStreamInferResponse(
                            error_message=str(e)
                        )
            return

        import queue
        import threading

        # bounded handoff: the reader blocks once `depth` issued
        # requests are awaiting resolution — the device-side
        # backpressure for a client that floods the stream
        q: queue.Queue = queue.Queue(maxsize=self._stream_depth)

        def issue_loop() -> None:
            try:
                for request in request_iterator:
                    try:
                        plan = self._front(request, context)
                    except _RemoteShm as e:
                        # the abort must run on the handler thread
                        q.put(("non_local", str(e)))
                        return
                    if (
                        self._collector is not None
                        and (g := self._stream_group_size(request)) > 1
                    ):
                        self._collector.record_stream_group(g)
                    try:
                        finishers = self._issue_group(request, plan)
                    except (KeyError, ValueError, OverloadError) as e:
                        q.put(("error", self._group_error(request, e)))
                        continue
                    # members are already issued (the batcher owns
                    # them); the bounded puts pace the READER so the
                    # next group is not parsed until this one's
                    # finishers are draining
                    for finish in finishers:
                        q.put(("finish", finish))
            except Exception as e:  # surface reader crashes to the RPC
                q.put(("crash", e))
            finally:
                q.put(("done", None))

        reader = threading.Thread(
            target=issue_loop, name="stream-issue", daemon=True
        )
        reader.start()
        try:
            while True:
                kind, payload = q.get()
                if kind == "done":
                    return
                if kind == "finish":
                    try:
                        yield pb.ModelStreamInferResponse(
                            infer_response=payload()
                        )
                    except (KeyError, ValueError, OverloadError) as e:
                        yield pb.ModelStreamInferResponse(
                            error_message=str(e)
                        )
                elif kind == "error":
                    yield pb.ModelStreamInferResponse(error_message=payload)
                elif kind == "non_local":
                    self._refuse_remote(context, payload)
                else:  # crash
                    raise payload
        finally:
            reader.join(timeout=5.0)


class InferenceServer:
    """Owns the grpc.Server; serve(), then shutdown()."""

    def __init__(
        self,
        repository: ModelRepository,
        channel: BaseChannel,
        address: str = "0.0.0.0:8001",
        uds_address: str | None = None,
        max_workers: int = 8,
        max_message_bytes: int | None = None,
        profiler=None,
        metrics_port: int | str = 0,
        stream_pipeline_depth: int = 2,
        trace_capacity: int = 256,
        slo_ms: float = 0.0,
        slo_per_model: dict | None = None,
        slo_tail_capacity: int = 64,
        admission_max_queue: int = 0,
        admission_concurrency: int = 4,
        lifecycle=None,
        tenants=None,
        replica_of: str | None = None,
        op_sample_interval_s: float = 0.0,
        op_sample_window_s: float = 0.2,
        history_interval_s: float = 10.0,
        history_capacity: int = 360,
        history_path: str | None = None,
        quality=None,
        temporal=None,
    ) -> None:
        """``metrics_port``: serve the telemetry endpoint — Prometheus
        exposition on ``/metrics`` (Triton's :8002 role), Chrome-trace
        JSON on ``/traces``, raw collector state on ``/snapshot``.
        0 disables; ``"auto"`` binds an ephemeral port (read it back
        from ``.metrics_port`` — tests and multi-server processes).
        ``profiler``: a StageProfiler to record into (created
        automatically when metrics_port is set).
        ``stream_pipeline_depth``: in-flight requests per
        ModelStreamInfer stream (request N+1 launches while N computes;
        1 = strictly serial, the pre-round-6 behavior).
        ``trace_capacity``: bounded ring of recent request traces kept
        for export (0 disables request tracing; spans then cost one
        attribute read per pipeline phase).
        ``slo_ms``: default per-request latency budget — requests are
        deadline-stamped at admission and scored met/missed on every
        exit path (0 = no SLO; histograms and the tail sampler's p99
        criterion still run). ``slo_per_model`` overrides budgets per
        model name; ``slo_tail_capacity`` bounds the ring of
        SLO-violating / p99+ exemplar traces exported at
        ``/traces?slo_violations=1``. The SLO ring requires
        ``metrics_port`` (it lives on the telemetry plane).
        ``admission_max_queue``: per-model admitted-but-unfinished cap
        for the admission controller (0 = no admission control, the
        pre-round-7 behavior); requests beyond it — or whose estimated
        queue wait exceeds their deadline budget — are rejected with
        RESOURCE_EXHAUSTED before parse. ``admission_concurrency``:
        assumed per-model service concurrency for the estimated-wait
        math (batcher width x pipeline depth, roughly).
        ``lifecycle``: a ModelLifecycleManager (runtime/lifecycle.py,
        already attached to the serving channel) — requests for COLD
        models then block on the RPC thread with a deadline-aware bound
        while the model pages in, instead of erroring.
        ``tenants``: a TenantTable mapping models to tenants; feeds the
        admission controller's per-tenant in-flight caps (fair-share
        ready ordering is attached on the batcher via
        ``attach_tenants``).
        ``replica_of``: replica-set label (``serve --replica-of``) —
        keys the ``replica_down`` fault point and is advertised via
        ServerMetadata.extensions for the route tool.
        ``uds_address``: additionally listen on a unix socket
        (``unix:/path`` / bare path / ``"auto"`` for a generated
        per-process path) alongside TCP — same-host clients then skip
        the loopback TCP stack entirely and their ``unix:`` peer
        passes the shared-memory locality gate by construction. Read
        the bound target back from ``.uds_address``; the socket file
        is unlinked on stop().
        ``op_sample_interval_s``: > 0 starts the continuous op sampler
        (obs/sampler.py): a short jax.profiler window every interval,
        parsed into top-K per-op device time on the collector
        (structurally capped at a 1% capture duty cycle;
        ``op_sample_window_s`` bounds one window). Shares the
        /profile capture guard — on-demand captures always win.
        ``history_interval_s``/``history_capacity``: the metric-history
        ring (obs/history.py) of per-model×tenant rate/util/MFU
        snapshots served at ``/history``; ``history_path`` persists the
        ring there on drain (and restores from it on startup).
        ``quality``: an eval.quality_plane.QualityPlane — the servicer
        then consults its canary router before dispatch and hands every
        response to its trace-hash sampler; shadow mirroring runs
        against this server's own channel stack unless the plane was
        built with an explicit (router) channel. Exports as the
        ``tpu_quality_*`` families, ``/snapshot["quality"]``, and the
        history ring's ``quality`` rows when telemetry is on.
        ``temporal``: a runtime.temporal.TemporalReusePlane — session
        frames then consult the per-stream keyframe scheduler before
        dispatch (coast/partial frames skip the detector), the device-
        time ledger is attached so skipped work is charged honestly,
        and the quality plane's window violations disable reuse per
        model. Exports under ``/snapshot["temporal"]`` and the
        ``tpu_serving_frames_total{mode=...}`` families."""
        self.lifecycle = lifecycle
        self.tenants = tenants
        self.replica_of = replica_of
        self.admission = (
            AdmissionController(
                max_queue=admission_max_queue,
                concurrency=admission_concurrency,
                tenants=tenants,
            )
            if admission_max_queue > 0
            else None
        )
        self._draining = threading.Event()
        if metrics_port and profiler is None:
            from triton_client_tpu.obs.profiling import StageProfiler

            profiler = StageProfiler()
        self.profiler = profiler
        self.tracer = None
        self.collector = None
        self.histograms = None
        self.slo = None
        self.device_time = None
        self.sampler = None
        self.history = None
        self._history_path = history_path
        self.quality = quality
        self.temporal = temporal
        if temporal is not None and quality is not None and hasattr(
            quality, "attach_temporal"
        ):
            # quality-gated reuse: a rolling-window violation on a
            # model turns its temporal shortcuts off, canary-style
            quality.attach_temporal(temporal)
        if quality is not None and getattr(
            quality.mirror, "_channel", None
        ) is None:
            # shadow dispatch defaults to this server's own stack: the
            # mirror re-issues sampled inputs at the back of the same
            # batcher/channel queue every live request rides
            quality.attach_channel(channel)
        self.metrics_enabled = False
        self._telemetry = None
        if metrics_port:
            # Degrade, don't die: telemetry is optional observability —
            # a missing prometheus_client or an occupied port must not
            # take down the inference service (the reference's optional
            # import pattern, communicator/__init__.py:5-8).
            registry = None
            try:
                import prometheus_client

                from triton_client_tpu.obs.profiling import (
                    PrometheusStageExporter,
                )

                # per-server registry: several InferenceServers in one
                # process each export their own complete metric set
                registry = prometheus_client.CollectorRegistry()
                PrometheusStageExporter(
                    0, registry=registry
                ).attach(profiler)
            except ImportError:
                log.warning(
                    "prometheus_client not installed; /metrics on port %s "
                    "disabled (traces still export)", metrics_port,
                )
            from triton_client_tpu.obs.collector import RuntimeCollector
            from triton_client_tpu.obs.histogram import HistogramFamily
            from triton_client_tpu.obs.slo import SLOTracker
            from triton_client_tpu.obs.trace import Tracer

            # the SLO ring: per-(model, stage) latency histograms fed
            # from finished traces, and the deadline/attainment tracker
            # whose tail sampler keeps slow-request exemplars. Built
            # whenever telemetry is on — with no slo_ms the histograms
            # and tail p99 criterion still run, only met/missed scoring
            # waits for a budget.
            self.histograms = HistogramFamily()
            self.slo = SLOTracker(
                slo_ms=slo_ms,
                per_model=slo_per_model,
                tail_capacity=slo_tail_capacity,
                histograms=self.histograms,
            )
            if trace_capacity > 0:
                self.tracer = Tracer(
                    capacity=trace_capacity, profiler=profiler,
                    histograms=self.histograms,
                )
            from triton_client_tpu.obs.device_time import DeviceTimeLedger

            # device-time ledger on the innermost staged channel (walk
            # one `inner` level for a batcher-wrapped stack): every
            # launch's device-execute window then accrues into per-
            # model×tenant device-seconds + live MFU, exported below
            target = channel
            if not hasattr(target, "attach_device_time"):
                target = getattr(channel, "inner", None)
            if target is not None and hasattr(target, "attach_device_time"):
                devices = 1
                try:
                    devices = int(target.fetch_channel().devices.size)
                except Exception:
                    pass
                tenant_table = tenants
                if tenant_table is None and lifecycle is not None:
                    tenant_table = getattr(lifecycle, "tenants", None)
                self.device_time = DeviceTimeLedger(
                    tenants=tenant_table, devices=devices
                )
                target.attach_device_time(self.device_time)
                if temporal is not None:
                    # coast/partial frames charge their (small) device
                    # windows to stream:<id> like full frames do — the
                    # per-stream device-seconds scoreboard stays honest
                    temporal.attach_ledger(self.device_time)
            # metric history: a fixed-interval ring of ledger deltas
            # (per-model×tenant rates, utilization, MFU) served at
            # /history and persisted across the drain/restart boundary
            if self.device_time is not None and history_interval_s > 0:
                from triton_client_tpu.obs.history import MetricHistory

                self.history = MetricHistory(
                    ledger=self.device_time,
                    interval_s=history_interval_s,
                    capacity=history_capacity,
                )
                if history_path and os.path.exists(history_path):
                    try:
                        self.history.restore(MetricHistory.load(history_path))
                    except (OSError, ValueError):
                        log.warning(
                            "could not restore metric history from %s",
                            history_path, exc_info=True,
                        )
                self.history.start()
            self.collector = RuntimeCollector(
                channel=channel, tracer=self.tracer, registry=registry,
                repository=repository, histograms=self.histograms,
                slo=self.slo, admission=self.admission,
                lifecycle=lifecycle, device_time=self.device_time,
            )
            if self.history is not None:
                self.collector.attach_history(self.history)
            if quality is not None:
                self.collector.attach_quality(quality)
                if self.history is not None:
                    self.history.attach_quality(quality)
            if temporal is not None:
                self.collector.attach_temporal(temporal)
            try:
                from triton_client_tpu.obs.http import TelemetryServer

                self._telemetry = TelemetryServer(
                    port=0 if metrics_port == "auto" else int(metrics_port),
                    registry=registry,
                    tracer=self.tracer,
                    collector=self.collector,
                    slo=self.slo,
                    history=self.history,
                )
                self.metrics_enabled = registry is not None
                if op_sample_interval_s > 0:
                    from triton_client_tpu.obs.sampler import (
                        ContinuousSampler,
                    )

                    # shares the /profile capture guard: a background
                    # window never collides with an on-demand capture
                    # (jax.profiler is a process-global singleton)
                    self.sampler = ContinuousSampler(
                        sink=self.collector,
                        interval_s=op_sample_interval_s,
                        window_s=op_sample_window_s,
                        lock=self._telemetry.profile_lock,
                        hlo_modules=self.collector.hlo_modules,
                    )
                    self.collector.attach_sampler(self.sampler)
                    self.sampler.start()
            except OSError as e:
                log.warning(
                    "could not bind metrics port %s (%s); telemetry "
                    "endpoint disabled", metrics_port, e,
                )
        limit = max_message_bytes or message_limit(repository)
        self._server = grpc.server(
            concurrent.futures.ThreadPoolExecutor(max_workers=max_workers),
            options=[
                ("grpc.max_send_message_length", limit),
                ("grpc.max_receive_message_length", limit),
            ],
        )
        from triton_client_tpu.runtime.shared_memory import (
            SystemSharedMemoryRegistry,
        )

        self.shm_registry = SystemSharedMemoryRegistry()
        self._servicer = _Servicer(
            repository,
            channel,
            profiler=profiler,
            shm_registry=self.shm_registry,
            stream_pipeline_depth=stream_pipeline_depth,
            tracer=self.tracer,
            collector=self.collector,
            slo=self.slo,
            admission=self.admission,
            draining=self._draining,
            lifecycle=lifecycle,
            replica_of=replica_of,
            quality=quality,
            temporal=temporal,
        )
        if self.collector is not None:
            self.collector.attach_front_end(
                self._servicer.front_stats, self._servicer.active_requests
            )
        service.add_servicer_to_server(self._servicer, self._server)
        self._port = self._server.add_insecure_port(address)
        if self._port == 0:
            raise RuntimeError(f"could not bind {address}")
        self._address = address
        self.uds_address: str | None = None
        self._uds_path: str | None = None
        if uds_address:
            from triton_client_tpu.channel import transport as transports

            path = uds_address
            if path == "auto":
                import tempfile

                path = os.path.join(
                    tempfile.gettempdir(),
                    f"tct_serve_{os.getpid()}_{self._port}.sock",
                )
            elif transports.is_uds(path):
                path = transports.uds_path(path)
            try:
                # a stale socket from a crashed run blocks the bind;
                # a LIVE server's socket would too — last binder wins,
                # same as SO_REUSEADDR semantics on the TCP side
                os.unlink(path)
            except FileNotFoundError:
                pass
            if self._server.add_insecure_port(f"unix:{path}") == 0:
                raise RuntimeError(f"could not bind unix:{path}")
            self.uds_address = f"unix:{path}"
            self._uds_path = path
        # the channel stack is part of the server's public surface:
        # embedders read stats()/batch_multiple off it, and start()
        # logs the mesh-serving shape it implies
        self.channel = channel

    def _channel_multiple(self) -> int:
        """Data-axis width of the serving channel stack (walk one
        ``inner`` level for a batcher-wrapped mesh channel)."""
        c = self.channel
        m = getattr(c, "batch_multiple", 1)
        inner = getattr(c, "inner", None)
        if inner is not None:
            m = max(m, getattr(inner, "batch_multiple", 1))
        return int(m)

    @property
    def port(self) -> int:
        return self._port

    @property
    def metrics_port(self) -> int:
        """Bound telemetry port (0 when telemetry is disabled)."""
        return self._telemetry.port if self._telemetry is not None else 0

    def start(self) -> None:
        self._server.start()
        multiple = self._channel_multiple()
        listening = self._address
        if self.uds_address:
            listening = f"{listening} + {self.uds_address}"
        if multiple > 1:
            log.info(
                "KServe v2 server listening on %s (mesh serving: batches "
                "shard over a data axis of %d)", listening, multiple,
            )
        else:
            log.info("KServe v2 server listening on %s", listening)

    def wait(self) -> None:
        self._server.wait_for_termination()

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def drain(self, timeout_s: float = 10.0, poll_s: float = 0.02) -> bool:
        """Graceful shutdown (the SIGTERM path): flip health not-ready
        and refuse NEW requests with UNAVAILABLE, let in-flight work
        complete up to ``timeout_s``, then tear down in order — gRPC
        transport, telemetry endpoint, collector, shared-memory
        mappings, and finally the channel stack (batcher dispatcher /
        executors / arena, via its ``close()``). Returns True when the
        building emptied inside the timeout, False when stragglers were
        force-cancelled. Idempotent with :meth:`stop`."""
        self._draining.set()
        if self.collector is not None:
            self.collector.set_draining(True)
        deadline = time.monotonic() + max(0.0, float(timeout_s))
        drained = False
        while time.monotonic() < deadline:
            if self._servicer.active_requests() <= 0:
                drained = True
                break
            time.sleep(poll_s)
        # let the shadow mirror finish scoring what it already holds —
        # the final history tick below should carry the last window
        if self.quality is not None:
            self.quality.drain(
                max(0.0, deadline - time.monotonic()) or 1.0
            )
        # final history tick + persist: the restart this ring is most
        # needed across is the one about to happen
        if self.history is not None:
            self.history.tick()
            if self._history_path:
                try:
                    self.history.persist(self._history_path)
                except OSError:
                    log.warning(
                        "could not persist metric history to %s",
                        self._history_path, exc_info=True,
                    )
        # stop(grace) rejects anything new at the transport and waits
        # out stragglers up to the remaining budget before cancelling
        self.stop(grace=max(0.0, deadline - time.monotonic()) + 0.1)
        close = getattr(self.channel, "close", None)
        if close is not None:
            close()
        return drained

    def stop(self, grace: float = 1.0) -> None:
        self._server.stop(grace).wait()
        if self.quality is not None:
            self.quality.close()
        if self.sampler is not None:
            self.sampler.close()
            self.sampler = None
        if self.history is not None:
            self.history.close()
        if self._telemetry is not None:
            self._telemetry.close()
            self._telemetry = None
        if self.collector is not None:
            self.collector.close()
        # detach (never unlink — the segments are client-owned)
        self.shm_registry.unregister_all()
        if self._uds_path is not None:
            # the SOCKET file is server-owned (unlike the shm segments)
            try:
                os.unlink(self._uds_path)
            except FileNotFoundError:
                pass
            self._uds_path = None
