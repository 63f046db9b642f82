"""Telemetry HTTP endpoint: /metrics + /traces + /snapshot on one port.

Replaces ``prometheus_client.start_http_server`` on the serving metrics
port so the same port the Prometheus scraper already targets (the
reference's :8002 story, data/prometheus.yml) also serves the request
traces and the raw collector snapshot:

  GET /metrics   Prometheus exposition of the server's registry
  GET /traces    Chrome-trace JSON of the tracer ring buffer
                 (?n=K limits to the K most recent; load in Perfetto)
                 (?slo_violations=1 serves the SLO tail-sampler ring
                 instead: only exemplars that missed their deadline or
                 landed at/above the live per-model p99)
  GET /snapshot  RuntimeCollector.snapshot() as JSON (debug/automation)
  GET /profile   on-demand jax.profiler capture (?seconds=N, default 1,
                 capped at 60; ?top_k=K bounds the op rows): blocks for
                 the window, writes the device timeline into a
                 server-local directory (on a TPU the device's lines
                 only: obs.profiling.device_trace, so the capture does
                 not slow the host path it looks at), and returns its
                 path PLUS the parsed per-op summary (obs.opstats: op,
                 kind, model, occurrences, device time) and, with a
                 tracer attached, ``launch_timeline``: the gaps between
                 launches split by what the host was doing in them
                 (obs.launch_timeline) as JSON. One capture at a
                 time — a concurrent request gets 409 (jax.profiler is
                 a process-global singleton; overlapping captures
                 abort). A trace that fails to parse still returns the
                 capture path (op_summary_error names the failure) and
                 NEVER wedges the capture guard.
  GET /history   the MetricHistory ring (?n=K most recent snapshots):
                 per-model×tenant launch/device-time rates, utilization
                 and MFU at a fixed interval (obs/history.py).

Paths degrade independently: without prometheus_client /metrics is 503
but traces still export; without a tracer /traces is 404 (and without
an SLO tracker, ?slo_violations=1 is 404); without jax /profile is 503;
without a history ring /history is 404.
"""

from __future__ import annotations

import json
import logging
import tempfile
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

log = logging.getLogger(__name__)

#: hard ceiling for one /profile capture window
_PROFILE_MAX_S = 60.0


class TelemetryServer:
    """Bound on construction (port 0 picks an ephemeral port — tests and
    multi-server processes); serves on a daemon thread until close()."""

    def __init__(
        self,
        port: int = 8002,
        registry=None,
        tracer=None,
        collector=None,
        host: str = "0.0.0.0",
        slo=None,
        history=None,
    ) -> None:
        self._registry = registry
        self._tracer = tracer
        self._collector = collector
        self._slo = slo
        self._history = history
        # /profile concurrency guard: jax.profiler keeps ONE process-
        # global capture; a second start_trace raises mid-capture and
        # would kill the first requester's window too
        self._profile_lock = threading.Lock()
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # no per-scrape stderr spam
                log.debug("telemetry http: " + fmt, *args)

            def do_GET(self):
                try:
                    outer._handle(self)
                except BrokenPipeError:
                    pass  # scraper went away mid-response
                except Exception:
                    log.exception("telemetry handler failed for %s", self.path)
                    try:
                        self.send_error(500)
                    except Exception:
                        pass

        self._httpd = ThreadingHTTPServer((host, int(port)), Handler)
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name="telemetry-http",
            daemon=True,
        )
        self._thread.start()

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    def _handle(self, req) -> None:
        parsed = urlparse(req.path)
        path = parsed.path.rstrip("/") or "/"
        if path == "/metrics":
            if self._registry is None:
                self._send(req, 503, b"prometheus_client unavailable\n")
                return
            import prometheus_client

            body = prometheus_client.generate_latest(self._registry)
            self._send(req, 200, body, prometheus_client.CONTENT_TYPE_LATEST)
        elif path in ("/traces", "/trace"):
            q = parse_qs(parsed.query)
            try:
                n = int(q.get("n", ["0"])[0])
            except ValueError:
                n = 0
            if q.get("slo_violations", ["0"])[0] not in ("0", ""):
                if self._slo is None:
                    self._send(req, 404, b"slo tracking disabled\n")
                    return
                from triton_client_tpu.obs.trace import chrome_trace

                payload = chrome_trace(self._slo.violations(n))
            elif self._tracer is None:
                self._send(req, 404, b"tracing disabled\n")
                return
            else:
                payload = self._tracer.chrome_trace(n)
            body = json.dumps(payload).encode()
            self._send(req, 200, body, "application/json")
        elif path == "/snapshot":
            if self._collector is None:
                self._send(req, 404, b"collector disabled\n")
                return
            body = json.dumps(self._collector.snapshot(), default=str).encode()
            self._send(req, 200, body, "application/json")
        elif path == "/profile":
            self._profile(req, parsed)
        elif path == "/history":
            if self._history is None:
                self._send(req, 404, b"metric history disabled\n")
                return
            q = parse_qs(parsed.query)
            try:
                n = int(q.get("n", ["0"])[0])
            except ValueError:
                n = 0
            body = json.dumps(
                {
                    "stats": self._history.stats(),
                    "snapshots": self._history.snapshots(n),
                }
            ).encode()
            self._send(req, 200, body, "application/json")
        elif path == "/":
            self._send(
                req, 200,
                b"tpu_serving telemetry: /metrics /traces /snapshot "
                b"/profile /history\n",
            )
        else:
            self._send(req, 404, b"not found\n")

    @property
    def profile_lock(self) -> threading.Lock:
        """The process-global capture guard. The ContinuousSampler
        shares this lock so background windows and on-demand /profile
        captures can never overlap (jax.profiler is a singleton)."""
        return self._profile_lock

    def _profile(self, req, parsed) -> None:
        """Blocking jax.profiler capture window; refuses overlap. The
        response carries the capture path AND the parsed per-op summary
        (obs.opstats). The guard covers ONLY the profiler singleton:
        it is released in a finally before the (pure-file) parse, so a
        malformed trace degrades to an ``op_summary_error`` field and
        can never wedge future captures."""
        q = parse_qs(parsed.query)
        try:
            seconds = float(q.get("seconds", ["1"])[0])
        except ValueError:
            self._send(req, 400, b"seconds must be a number\n")
            return
        try:
            top_k = int(q.get("top_k", ["20"])[0])
        except ValueError:
            top_k = 20
        seconds = min(max(seconds, 0.05), _PROFILE_MAX_S)
        try:
            import jax
        except ImportError:
            self._send(req, 503, b"jax unavailable; /profile disabled\n")
            return
        if not self._profile_lock.acquire(blocking=False):
            self._send(
                req, 409, b"a profile capture is already in progress\n"
            )
            return
        try:
            from triton_client_tpu.obs.profiling import device_trace

            log_dir = tempfile.mkdtemp(prefix="tpu_serving_profile_")
            t_capture = time.perf_counter()
            with device_trace(log_dir):
                time.sleep(seconds)
            t_captured = time.perf_counter()
        except Exception as e:
            log.exception("profile capture failed")
            self._send(req, 500, f"profile capture failed: {e}\n".encode())
            return
        finally:
            self._profile_lock.release()
        doc = {"log_dir": log_dir, "seconds": seconds}
        try:
            from triton_client_tpu.obs import opstats

            modules = None
            if self._collector is not None:
                hlo_modules = getattr(self._collector, "hlo_modules", None)
                if callable(hlo_modules):
                    modules = hlo_modules()
            doc["op_summary"] = opstats.summarize_profile_dir(
                log_dir, hlo_modules=modules, top_k=top_k
            )
        except Exception as e:
            log.exception("profile trace parse failed")
            doc["op_summary_error"] = str(e)
        if self._tracer is not None:
            try:
                from triton_client_tpu.obs import launch_timeline

                # the requests that lived inside the capture; the
                # profiler session's zero is near its start
                doc["launch_timeline"] = launch_timeline.timeline(
                    [
                        t for t in self._tracer.recent()
                        if t.t_end is not None
                        and t.t_end >= t_capture
                        and t.t_start <= t_captured
                    ],
                    launch_timeline.module_events(log_dir),
                    near_s=t_capture,
                )
            except Exception as e:
                log.exception("launch timeline failed")
                doc["launch_timeline_error"] = str(e)
        self._send(req, 200, json.dumps(doc).encode(), "application/json")

    @staticmethod
    def _send(req, code: int, body: bytes, ctype: str = "text/plain") -> None:
        req.send_response(code)
        req.send_header("Content-Type", ctype)
        req.send_header("Content-Length", str(len(body)))
        req.end_headers()
        req.wfile.write(body)

    def close(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5.0)
