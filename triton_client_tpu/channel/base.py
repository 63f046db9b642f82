"""Channel protocol: register / metadata / infer.

Mirrors the seam of the reference's BaseChannel
(communicator/channel/base_channel.py:12-34) with two deliberate
departures:

  * requests/responses are typed dicts of numpy arrays, not a mutable
    protobuf ModelInferRequest the driver re-fills per frame
    (grpc_channel.py:63-78) — no serialization on the in-process path;
  * do_inference takes the request explicitly instead of reading
    channel-held mutable state, so channels are thread-safe and the
    driver can pipeline frame N+1's preprocess against frame N's infer.
"""

from __future__ import annotations

import abc
import dataclasses
from typing import Mapping

import numpy as np


@dataclasses.dataclass
class InferRequest:
    model_name: str
    inputs: Mapping[str, np.ndarray]
    model_version: str = ""
    request_id: str = ""
    # request-scoped telemetry (obs.trace.RequestTrace; a merged
    # launch's obs.trace.LaunchRecord).
    # None on the un-traced hot path: channels guard on the attribute,
    # so disabled tracing costs one attribute read per phase.
    trace: object | None = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # SLO deadline plane (obs.slo.SLOTracker): the absolute
    # perf_counter deadline stamped at admission, carried through the
    # batcher (a merged group takes the min of its members') to the
    # staged launchers, which count launches past it. None = no SLO.
    deadline_s: float | None = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # scheduling/reporting class: attainment counters split on it, and
    # the continuous-batching scheduler (ROADMAP item 1) will order on
    # it. Higher = more important.
    priority: int = dataclasses.field(default=0, repr=False, compare=False)
    # packed-ragged marker (parallel.ragged_kernels.RaggedLayout): set
    # by the continuous batcher when this request's inputs are a packed
    # concatenation of several member requests' rows. None on every
    # dense request — channels guard on the attribute, so the dense
    # path pays one attribute read.
    ragged: object | None = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # per-input-tensor wire parameters (input name -> params dict),
    # e.g. runtime/wire_encoding's ``content_encoding`` for inputs that
    # travel compressed (JPEG bytes, quantized pointclouds) and decode
    # server-side. Only remote channels read it; None on the hot path.
    input_params: Mapping[str, dict] | None = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # streaming-session identity (runtime/sessions.py): frames of one
    # stream carry the same sequence_id; start/end bracket the stream's
    # life. Empty = stateless request — every existing path. Stateful
    # requests are solo-batched, affinity-routed, and never hedged.
    sequence_id: str = dataclasses.field(default="", repr=False, compare=False)
    sequence_start: bool = dataclasses.field(
        default=False, repr=False, compare=False
    )
    sequence_end: bool = dataclasses.field(
        default=False, repr=False, compare=False
    )
    # a launch the batcher merged from the requests of SEVERAL sessions
    # of a model that declares mergeable sessions (runtime/sessions.py
    # TokenSessions): row by row, (sequence_id, start, end). None on
    # every other request, whose own three fields above say it all.
    sequence_rows: tuple | None = dataclasses.field(
        default=None, repr=False, compare=False
    )


@dataclasses.dataclass
class InferResponse:
    model_name: str
    outputs: dict[str, np.ndarray]
    model_version: str = ""
    request_id: str = ""
    # device-side compute seconds, for the observability stack
    latency_s: float = 0.0
    # response-level kserve parameters decoded off the wire (e.g. the
    # server's compact span summary under obs.trace.SUMMARY_PARAM_KEY).
    # None on in-process channels and un-traced responses.
    parameters: dict | None = dataclasses.field(
        default=None, repr=False, compare=False
    )


class InferFuture:
    """Handle for an in-flight inference round-trip.

    ``result()`` blocks until the response is ready and returns it (or
    raises the deferred error). The reference defines an ``--async``
    flag it never exercises (main.py:59-70); this future is the real
    thing: channels issue the work on do_inference_async and the driver
    keeps several requests in flight, overlapping host preprocess with
    device/remote compute. Resolution is single-consumer: the driver
    retires each future exactly once, in issue order.

    Transports whose underlying handle can signal completion or be
    abandoned (gRPC call futures) wire the optional ``cancel`` /
    ``subscribe`` hooks; the front-door router (runtime/router.py)
    uses them to take the first hedged winner and cancel the loser.
    Lazy futures (the base-channel fallback, deferred TPU readback)
    leave them unset: ``cancel()`` is then a no-op returning False, and
    ``add_done_callback`` fires immediately — meaning only "result()
    may be called", which for a lazy future is always true.
    """

    __slots__ = ("_resolve", "_done", "_value", "_error", "_cancel",
                 "_subscribe")

    def __init__(self, resolve, cancel=None, subscribe=None) -> None:
        self._resolve = resolve
        self._done = False
        self._value = None
        self._error: BaseException | None = None
        self._cancel = cancel
        self._subscribe = subscribe

    @classmethod
    def completed(cls, value) -> "InferFuture":
        fut = cls(lambda: value)
        fut._done, fut._value = True, value
        return fut

    @classmethod
    def failed(cls, error: BaseException) -> "InferFuture":
        fut = cls(None)
        fut._done, fut._error = True, error
        return fut

    def result(self):
        if not self._done:
            try:
                self._value = self._resolve()
            except BaseException as e:
                self._error = e
            finally:
                self._done = True
                self._resolve = None  # free the closure (it may pin buffers)
        if self._error is not None:
            raise self._error
        return self._value

    def map(self, fn) -> "InferFuture":
        """A future whose result is ``fn(self.result())`` (lazy)."""
        return InferFuture(lambda: fn(self.result()))

    def cancel(self) -> bool:
        """Best-effort abandon of the in-flight work. Returns True only
        when the transport accepted the cancellation (the gRPC call had
        not completed); a lazy or already-retired future returns False.
        After a successful cancel, result() raises the transport's
        CANCELLED error — the caller must not expect a value."""
        if self._done or self._cancel is None:
            return False
        try:
            return bool(self._cancel())
        except Exception:
            return False

    def add_done_callback(self, fn) -> None:
        """Run ``fn()`` (no arguments) once result() will no longer
        block. Transport-backed futures invoke it from the transport's
        completion thread — keep it tiny and non-blocking (the router
        posts to a queue). Lazy futures invoke it immediately on the
        calling thread: their result() is always callable, it just does
        the work inline. fn must not raise; a raise is swallowed after
        logging nothing (completion threads must never die)."""
        sub = self._subscribe
        if sub is not None and not self._done:
            try:
                sub(fn)
                return
            except Exception:
                pass
        try:
            fn()
        except Exception:
            pass


class BaseChannel(abc.ABC):
    """Transport abstraction between drivers (L4) and models."""

    @abc.abstractmethod
    def register_channel(self) -> None:
        """Establish the transport (claim devices / dial the endpoint)."""

    @abc.abstractmethod
    def fetch_channel(self):
        """Return the underlying transport handle."""

    @abc.abstractmethod
    def get_metadata(self, model_name: str, model_version: str = ""):
        """Return the ModelSpec for a served model."""

    @abc.abstractmethod
    def do_inference(self, request: InferRequest) -> InferResponse:
        """Run one inference round-trip."""

    def do_inference_async(self, request: InferRequest) -> InferFuture:
        """Issue an inference without blocking for the response.

        Transports that can genuinely overlap (gRPC futures, JAX async
        dispatch) override this; the base implementation degrades to the
        blocking call wrapped in a completed future, so every channel
        supports the async driver path with unchanged semantics."""
        try:
            return InferFuture.completed(self.do_inference(request))
        except Exception as e:  # KeyboardInterrupt/SystemExit stay immediate
            return InferFuture.failed(e)
