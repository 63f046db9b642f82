"""Request-scoped spans through the overlapped serving pipeline.

A ``RequestTrace`` is a flat, thread-safe list of named
``(t0, t1)`` intervals on the ``time.perf_counter`` clock — one trace
per served request, carried on ``InferRequest.trace`` through the
server, the batcher and the channel. Call sites guard on the attribute
(``tr = request.trace; if tr is not None: ...``), so the un-traced hot
path costs one attribute read per phase and allocates nothing.

Spans deliberately do NOT form a tree: the overlapped pipeline runs a
request's phases on several threads (gRPC handler, batch dispatcher,
executor), and what tail-latency attribution needs is the wall-clock
interval of each phase, not a call stack. Nesting falls out of
interval containment in the Chrome trace view (``stage`` contains
``slot_wait``; the request row contains everything).

``Tracer`` owns the bounded ring buffer of recently finished traces
and the Chrome-trace JSON export (``chrome_trace``) that Perfetto /
``chrome://tracing`` load directly; finished spans also feed the
per-stage Prometheus histogram family through the attached
StageProfiler (stage label ``span_<name>``).
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import threading
import time
import uuid
from typing import Iterator

from triton_client_tpu.obs.histogram import SLO_STAGES


class TraceContext:
    """W3C-traceparent-style distributed context.

    ``trace_id`` (32 hex chars) names the end-to-end request across
    processes; ``parent_span_id`` (16 hex chars) names the hop that
    issued this RPC (the router attempt, or the originating client);
    ``sampled`` rides the flags byte. The wire form is the traceparent
    string ``00-<trace_id>-<parent_span_id>-<flags>`` carried in the
    kserve request ``parameters`` map — the same map the server already
    reads ``priority`` from, so propagation adds no new proto surface.

    Encode/decode are pure host-side string work (they sit on the
    serving hot path and are rooted in tpulint's HOT_PATH_ROOTS — no
    host syncs may creep in here).
    """

    __slots__ = ("trace_id", "parent_span_id", "sampled")

    #: kserve parameters key the context travels under
    PARAM_KEY = "traceparent"
    _VERSION = "00"

    def __init__(
        self, trace_id: str, parent_span_id: str, sampled: bool = True
    ) -> None:
        self.trace_id = trace_id
        self.parent_span_id = parent_span_id
        self.sampled = bool(sampled)

    @classmethod
    def new(cls, sampled: bool = True) -> "TraceContext":
        """Originate a fresh context (the router's front-door role)."""
        return cls(uuid.uuid4().hex, uuid.uuid4().hex[:16], sampled)

    def child(self) -> "TraceContext":
        """Same trace, fresh parent span id — one per hedge/retry
        attempt, so sibling attempts are distinguishable server-side."""
        return TraceContext(self.trace_id, uuid.uuid4().hex[:16], self.sampled)

    def encode(self) -> str:
        flags = "01" if self.sampled else "00"
        return f"{self._VERSION}-{self.trace_id}-{self.parent_span_id}-{flags}"

    @classmethod
    def decode(cls, value: str) -> "TraceContext | None":
        """Tolerant parse: anything malformed returns None (a foreign
        or corrupt header must never fail the request it rides on)."""
        if not value or not isinstance(value, str):
            return None
        parts = value.split("-")
        if len(parts) != 4 or not parts[1] or not parts[2]:
            return None
        return cls(parts[1], parts[2], sampled=parts[3] != "00")

    def __repr__(self) -> str:
        return f"TraceContext({self.encode()!r})"


class Span:
    """One named wall-clock interval on the perf_counter clock.

    ``attrs`` (optional dict) carries structured tags — the router
    stamps attempt number / endpoint / cancelled on its per-attempt
    spans and the Chrome export surfaces them as event ``args``."""

    __slots__ = ("name", "t0", "t1", "attrs")

    def __init__(
        self, name: str, t0: float, t1: float, attrs: dict | None = None
    ) -> None:
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.attrs = attrs

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0

    def __repr__(self) -> str:  # test/debug ergonomics
        return f"Span({self.name!r}, {self.duration_s * 1e3:.3f} ms)"


class RequestTrace:
    """Spans for one request. Append-only, safe from any thread.

    ``begin(name)`` / ``end(name)`` open and close a span across
    threads (the batcher opens ``batch_queue`` on the gRPC handler
    thread and closes it on the executor); ``end`` without a matching
    ``begin`` is a no-op, and a span left open when the trace finishes
    is dropped — observability must never fail the observed path.

    No lock: a write is one ``list.append`` or one ``dict`` store or
    ``pop``, each atomic under the interpreter lock, and a reader takes
    a copy. What a merged launch did for ALL its members is not written
    here: the trace points at the launch's :class:`LaunchRecord`
    (``launches``), and ``spans`` reads both.
    """

    __slots__ = (
        "trace_id",
        "model",
        "request_id",
        "session",
        "t_start",
        "t_end",
        "status",
        "own",
        "launches",
        "context",
        "_open",
    )

    def __init__(
        self,
        trace_id: int,
        model: str = "",
        request_id: str = "",
        context: TraceContext | None = None,
    ) -> None:
        self.trace_id = trace_id
        self.model = model
        self.request_id = request_id
        self.t_start = time.perf_counter()
        self.t_end: float | None = None
        self.status = "ok"
        # the sequence id the batcher merges on ("" for a stateless
        # request): what tells a session's consecutive requests to be
        # one caller's (obs/launch_timeline.py, the cycle's phases)
        self.session = ""
        self.own: list[Span] = []
        self.launches: list[LaunchRecord] = []
        # distributed context (TraceContext): None on purely local
        # traces; set when the server adopts an inbound traceparent or
        # the router originates one. The local int trace_id still keys
        # the ring buffer — the context's hex trace_id keys the FLEET.
        self.context = context
        self._open: dict[str, float] = {}

    @property
    def spans(self) -> list[Span]:
        """The request's own spans and, after them, those of every
        launch it rode on (each launch's in the order written): a new
        list every read, so a reader never holds one a writer grows."""
        return self.own + [s for rec in self.launches for s in rec.spans]

    # -- recording ------------------------------------------------------------

    def add(
        self, name: str, t0: float, t1: float, attrs: dict | None = None
    ) -> None:
        self.own.append(Span(name, t0, t1, attrs))

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(name, t0, time.perf_counter())

    def begin(self, name: str) -> None:
        self._open[name] = time.perf_counter()

    def end(self, name: str) -> None:
        t1 = time.perf_counter()
        t0 = self._open.pop(name, None)
        if t0 is not None:
            self.own.append(Span(name, t0, t1))

    # -- reading --------------------------------------------------------------

    def wall_s(self) -> float:
        end = self.t_end if self.t_end is not None else time.perf_counter()
        return end - self.t_start

    def span_coverage(self) -> float:
        """Fraction of [t_start, t_end] covered by the union of spans —
        the acceptance gauge for 'no invisible time in the pipeline'.
        A span is counted for its part inside the wall (``front`` ends
        where the wall begins and covers none of it)."""
        wall = self.wall_s()
        if wall <= 0:
            return 1.0
        lo, hi = self.t_start, self.t_start + wall
        ivals = sorted(
            (max(s.t0, lo), min(s.t1, hi)) for s in self.spans if s.t1 > lo and s.t0 < hi
        )
        covered, cur0, cur1 = 0.0, None, None
        for t0, t1 in ivals:
            if cur1 is None or t0 > cur1:
                if cur1 is not None:
                    covered += cur1 - cur0
                cur0, cur1 = t0, t1
            else:
                cur1 = max(cur1, t1)
        if cur1 is not None:
            covered += cur1 - cur0
        return min(1.0, covered / wall)

    def summary(self) -> dict:
        spans = [
            {
                "name": s.name,
                "t0_s": s.t0 - self.t_start,
                "dur_ms": s.duration_s * 1e3,
                **({"attrs": s.attrs} if s.attrs else {}),
            }
            for s in sorted(self.spans, key=lambda s: s.t0)
        ]
        out = {
            "trace_id": self.trace_id,
            "model": self.model,
            "request_id": self.request_id,
            "status": self.status,
            "wall_ms": self.wall_s() * 1e3,
            "spans": spans,
        }
        if self.context is not None:
            out["context"] = self.context.encode()
        return out


class LaunchRecord:
    """What ONE launch of a merged group did, written once.

    The batcher concatenates N requests into one inner-channel call;
    the merged InferRequest carries this record as its ``trace``, so
    the channel-side spans (``slot_wait``/``stage``/``h2d``/``launch``/
    ``device_execute``/the named device window/``readback``) and the
    batcher's ``batch_merge`` are ONE append each on the executor
    thread, however many members the launch has. Every member's trace
    points at the record (``RequestTrace.launches``), and a reader of
    ``RequestTrace.spans`` (the ``/traces`` export, the span
    histograms, the launch timeline) still sees the launch's spans on
    each member: the expansion happens where it is read."""

    __slots__ = ("spans",)

    def __init__(self, members=()) -> None:
        self.spans: list[Span] = []
        for m in members:
            if m is not None:
                m.launches.append(self)

    def add(
        self, name: str, t0: float, t1: float, attrs: dict | None = None
    ) -> None:
        self.spans.append(Span(name, t0, t1, attrs))


class Tracer:
    """Trace factory + bounded ring buffer of finished request traces.

    ``enabled=False`` makes ``start`` return None, which propagates the
    zero-cost path through every call site. ``profiler`` (a
    StageProfiler) receives each finished span as a ``span_<name>``
    stage sample, which the Prometheus stage-histogram family exports —
    per-stage span histograms under the existing ``stage`` label.

    ``histograms`` (an obs.histogram.HistogramFamily) additionally
    receives per-model SLO-stage samples at finish: each span named in
    ``SLO_STAGES`` lands as (model, stage), and the whole request wall
    lands as (model, "e2e") — the single feed point for the
    ``tpu_serving_latency_seconds`` family, riding the spans the
    pipeline already records instead of new instrumentation.
    """

    def __init__(
        self,
        enabled: bool = True,
        capacity: int = 256,
        profiler=None,
        histograms=None,
    ) -> None:
        self.enabled = bool(enabled) and capacity > 0
        self.capacity = int(capacity)
        self._profiler = profiler
        self._histograms = histograms
        self._ring: collections.deque[RequestTrace] = collections.deque(
            maxlen=max(1, self.capacity)
        )
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._finished = 0
        # span name -> its stage label, resolved once a name
        self._stage_names: dict[str, str] = {}
        # one (perf_counter, time_ns) pair, taken together: what lets a
        # reader lay this process's spans beside another clock's events
        self._clock_anchor = (time.perf_counter(), time.time_ns())

    def start(
        self,
        model: str = "",
        request_id: str = "",
        context: TraceContext | None = None,
    ) -> RequestTrace | None:
        """``context``: inbound distributed context to adopt (the
        server's _issue passes the decoded traceparent; the router
        passes the context it originated)."""
        if not self.enabled:
            return None
        return RequestTrace(
            next(self._ids), model=model, request_id=request_id,
            context=context,
        )

    def finish(self, trace: RequestTrace | None, status: str = "ok") -> None:
        if trace is None:
            return
        trace.t_end = time.perf_counter()
        trace.status = status
        with self._lock:
            self._ring.append(trace)
            self._finished += 1
        if self._profiler is None and self._histograms is None:
            return
        spans = trace.spans
        if self._profiler is not None:
            # one hold of the profiler's lock a request, the stage
            # names resolved once a name: not a lock and a label a span
            names = self._stage_names
            samples = []
            for s in spans:
                stage = names.get(s.name)
                if stage is None:
                    stage = names.setdefault(s.name, f"span_{s.name}")
                samples.append((stage, s.t1 - s.t0))
            self._profiler.record_many(samples)
        if self._histograms is not None:
            model = trace.model or ""
            for s in spans:
                stage = SLO_STAGES.get(s.name)
                if stage is not None:
                    self._histograms.observe(model, stage, s.t1 - s.t0)
            self._histograms.observe(model, "e2e", trace.t_end - trace.t_start)

    def recent(self, n: int = 0) -> list[RequestTrace]:
        """Most recent ``n`` finished traces (0 = everything buffered),
        oldest first."""
        with self._lock:
            traces = list(self._ring)
        return traces[-n:] if n else traces

    def stats(self) -> dict:
        with self._lock:
            return {
                "finished": self._finished,
                "buffered": len(self._ring),
                "capacity": self.capacity,
            }

    def chrome_trace(self, n: int = 0) -> dict:
        return chrome_trace(self.recent(n), clock_anchor=self._clock_anchor)


def chrome_trace(traces, clock_anchor=None) -> dict:
    """Chrome-trace ('Trace Event Format') JSON for a list of traces.

    Loadable in Perfetto / chrome://tracing: complete ('X') events with
    microsecond timestamps, one tid (row) per request, the whole
    request as a parent event so the per-phase spans nest visually
    inside it. Timestamps rebase onto the earliest trace start so the
    viewer opens at t=0; with ``clock_anchor`` (the Tracer's
    ``(perf_counter, time_ns)`` pair) a top-level ``clock`` object says
    which ``perf_counter`` value that zero is, so ``ts`` can be put
    back on the process's clock (and, through the pair, on wall time)."""
    traces = [t for t in traces if t is not None]
    base = min((t.t_start for t in traces), default=None)
    clock = {}
    if clock_anchor is not None:
        clock["clock"] = {
            "base_perf_counter_s": base,
            "anchor_perf_counter_s": clock_anchor[0],
            "anchor_time_ns": clock_anchor[1],
        }
    if not traces:
        return {"traceEvents": [], "displayTimeUnit": "ms", **clock}

    def us(t: float) -> float:
        return round((t - base) * 1e6, 3)

    events: list[dict] = [
        {
            "ph": "M",
            "name": "process_name",
            "pid": 1,
            "tid": 0,
            "args": {"name": "tpu_serving"},
        }
    ]
    for tr in traces:
        tid = tr.trace_id
        label = f"req {tr.trace_id} {tr.model}".strip()
        events.append(
            {
                "ph": "M",
                "name": "thread_name",
                "pid": 1,
                "tid": tid,
                "args": {"name": label},
            }
        )
        t_end = tr.t_end if tr.t_end is not None else time.perf_counter()
        req_args = {
            "model": tr.model,
            "request_id": tr.request_id,
            "status": tr.status,
        }
        ctx = getattr(tr, "context", None)
        if ctx is not None:
            req_args["traceparent"] = ctx.encode()
        if tr.session:
            req_args["session"] = tr.session
        events.append(
            {
                "ph": "X",
                "name": "request",
                "cat": "request",
                "pid": 1,
                "tid": tid,
                "ts": us(tr.t_start),
                "dur": max(0.0, (t_end - tr.t_start) * 1e6),
                "args": req_args,
            }
        )
        for s in sorted(tr.spans, key=lambda s: s.t0):
            ev = {
                "ph": "X",
                "name": s.name,
                "cat": "span",
                "pid": 1,
                "tid": tid,
                "ts": us(s.t0),
                "dur": max(0.0, s.duration_s * 1e6),
            }
            if s.attrs:
                ev["args"] = dict(s.attrs)
            events.append(ev)
    # the metadata events (no ``ts``) first: a ``front`` span begins
    # before its trace and so, on the first trace, before ``base``
    events.sort(key=lambda e: (e.get("ts", float("-inf")), e["tid"]))
    return {"traceEvents": events, "displayTimeUnit": "ms", **clock}


def dump_chrome_trace(traces, path: str) -> None:
    with open(path, "w") as f:
        json.dump(chrome_trace(traces), f)


# -- cross-process span summaries ---------------------------------------------

#: kserve response parameters key the server's span summary rides under
SUMMARY_PARAM_KEY = "trace_summary"


def encode_span_summary(trace: RequestTrace) -> str:
    """Compact server-side summary for the response ``parameters`` map.

    Times are microseconds RELATIVE to the trace's own t_start (each
    process has its own perf_counter epoch — absolute values would be
    meaningless on the far side): ``{"w": wall_us, "st": status,
    "s": [[name, t0_rel_us, dur_us], ...]}`` (``front`` lies before
    the start, so its offset is negative and the far side lays it into
    what it counts as the wire). Kept deliberately terse:
    this string rides every response whose REQUEST carried a
    ``traceparent`` (somebody upstream wants to graft it)."""
    t_start = trace.t_start
    spans = [
        [s.name, round((s.t0 - t_start) * 1e6), round(s.duration_s * 1e6)]
        for s in sorted(trace.spans, key=lambda s: s.t0)
    ]
    doc = {
        "w": round(trace.wall_s() * 1e6),
        "st": trace.status,
        "s": spans,
    }
    if trace.context is not None:
        doc["ctx"] = trace.context.encode()
    return json.dumps(doc, separators=(",", ":"))


def decode_span_summary(value: str) -> dict | None:
    """Tolerant inverse of encode_span_summary (None on garbage)."""
    if not value:
        return None
    try:
        doc = json.loads(value)
    except (ValueError, TypeError):
        return None
    if not isinstance(doc, dict) or "s" not in doc or "w" not in doc:
        return None
    return doc


def graft_span_summary(
    trace: RequestTrace,
    summary: dict,
    t_sent: float,
    t_recv: float,
    prefix: str = "srv.",
    attrs: dict | None = None,
) -> None:
    """Place a far-side span summary onto the LOCAL clock.

    The caller observed the RPC as [t_sent, t_recv] on its own
    perf_counter clock; the summary says the server spent ``w``
    microseconds of wall inside that window. The residue is wire +
    router transit — split symmetrically (the same midpoint estimate
    NTP uses for a single round trip), which also yields the clock
    offset the trace-join CLI applies. Server spans land prefixed
    (default ``srv.``) so local and remote phases stay distinguishable
    in one timeline; the wire residue lands as ``wire_send`` /
    ``wire_recv`` spans so the RTT of ROADMAP item 1 is a NAMED span."""
    rtt = max(0.0, t_recv - t_sent)
    server_wall = max(0.0, summary.get("w", 0) / 1e6)
    residue = max(0.0, rtt - server_wall)
    t_server_start = t_sent + residue / 2.0
    if residue > 0:
        trace.add("wire_send", t_sent, t_server_start, attrs)
        trace.add(
            "wire_recv", t_server_start + server_wall, t_recv, attrs
        )
    for row in summary.get("s", ()):
        try:
            name, t0_us, dur_us = row[0], float(row[1]), float(row[2])
        except (IndexError, TypeError, ValueError):
            continue
        t0 = t_server_start + t0_us / 1e6
        trace.add(f"{prefix}{name}", t0, t0 + dur_us / 1e6, attrs)
