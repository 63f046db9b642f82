"""SLO attainment accounting + tail-exemplar sampling.

The tentpole's deadline plane: the server stamps each request's
deadline at admission (``_Servicer._issue``), the batcher and staged
launchers carry it, and THIS module scores the outcome once per
request — on every exit path, success or failure — in the same
``finally``-rooted accounting hook that already feeds the error
counter (tpulint TPL503 enforces that placement).

Three jobs:

  * **attainment counters** — met/missed per (model, priority), read
    through ``RuntimeCollector.snapshot()["slo"]`` and exported as the
    ``tpu_serving_slo_requests_total`` counter family. A request with
    no deadline and no configured budget is not scored (an SLO-less
    server must not report 100% attainment as if it had one).
  * **tail sampler** — a bounded ring of full ``RequestTrace``
    exemplars, retained ONLY for requests that missed their SLO or
    landed at/above the p99 of their model's e2e histogram as last
    estimated (anew each time the histogram has grown by 3%, at least
    100 samples: not on every request). The
    main tracer ring keeps the last N requests regardless; this ring
    answers "show me the slow ones" after millions of fast requests
    have cycled the main ring. Exported at ``/traces?slo_violations=1``.
  * **per-model budgets** — ``slo_ms`` is the default; ``per_model``
    overrides individual models (capacity search probes one model's
    budget without touching its neighbors').
"""

from __future__ import annotations

import collections
import threading
import time

# Don't trust a p99 estimated from a handful of samples: below this
# many e2e observations the tail sampler retains only hard SLO misses.
_MIN_P99_SAMPLES = 100


class SLOTracker:
    """Scores one finished request per ``observe_request`` call."""

    def __init__(
        self,
        slo_ms: float = 0.0,
        per_model: dict[str, float] | None = None,
        tail_capacity: int = 64,
        histograms=None,
    ) -> None:
        """``slo_ms``: default latency budget (0 = no SLO configured —
        requests are scored only when they carry an explicit deadline).
        ``per_model``: model name -> budget ms overrides.
        ``histograms``: the serving ``HistogramFamily``; when present,
        its (model, e2e) p99 (``_p99_s``) also qualifies traces for the tail
        ring, so the sampler keeps exemplars even on a server whose SLO
        is generous enough to never miss."""
        self._slo_s = max(0.0, float(slo_ms)) / 1e3
        self._per_model_s = {
            str(m): max(0.0, float(v)) / 1e3
            for m, v in (per_model or {}).items()
        }
        self._hist = histograms
        self._lock = threading.Lock()
        # (model, priority) -> [met, missed]
        self._counts: dict[tuple[str, int], list[int]] = {}
        self._tail: collections.deque = collections.deque(
            maxlen=max(1, int(tail_capacity))
        )
        self._tail_retained = 0
        self._deadline_missed = 0
        # model -> (the e2e count at which its p99 is estimated anew,
        # the estimate): a snapshot and a walk of the buckets a traced
        # request cost 17 us on the handler thread (PERF.md, PR 42)
        self._p99: dict[str, tuple[int, float]] = {}

    # -- configuration --------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self._slo_s > 0 or bool(self._per_model_s)

    def slo_s(self, model: str) -> float:
        """The latency budget for ``model`` in seconds (0 = none)."""
        return self._per_model_s.get(str(model), self._slo_s)

    def set_budget(self, slo_ms: float, model: str | None = None) -> None:
        """Re-arm the default (or one model's) budget on a live
        tracker — how a calibration pass (perf/profile_slo.py auto-SLO:
        3x the lightly-loaded p50) turns scoring on after the server is
        already taking traffic. Already-scored requests keep their
        original verdicts; only future admissions see the new budget."""
        v = max(0.0, float(slo_ms)) / 1e3
        if model is None:
            self._slo_s = v
        else:
            self._per_model_s[str(model)] = v

    def deadline_for(self, model: str, t0: float) -> float | None:
        """Absolute perf_counter deadline for a request admitted at
        ``t0`` — what ``_Servicer._issue`` stamps onto the
        InferRequest; None when the model has no budget."""
        budget = self.slo_s(model)
        return t0 + budget if budget > 0 else None

    # -- scoring --------------------------------------------------------------

    def observe_request(
        self,
        model: str,
        wall_s: float,
        deadline_s: float | None = None,
        priority: int = 0,
        status: str = "ok",
        trace=None,
        now: float | None = None,
    ) -> None:
        """Score one finished request. ``deadline_s`` is the absolute
        perf_counter deadline stamped at admission (authoritative when
        present — it survives clock-relative drift through the batcher);
        otherwise the model's budget is compared against ``wall_s``.
        Failed requests (``status != "ok"``) count as missed: a served
        error inside budget is not an attained SLO."""
        budget = self.slo_s(model)
        if deadline_s is None and budget <= 0:
            # no SLO anywhere for this request: still feed the tail
            # sampler's p99 criterion, but never the attainment counters
            self._maybe_retain(model, wall_s, missed=False, trace=trace)
            return
        if now is None:
            now = time.perf_counter()
        if deadline_s is not None:
            missed = now > deadline_s
        else:
            missed = wall_s > budget
        if status != "ok":
            missed = True
        key = (str(model), int(priority))
        with self._lock:
            cell = self._counts.get(key)
            if cell is None:
                cell = self._counts[key] = [0, 0]
            cell[1 if missed else 0] += 1
            if missed:
                self._deadline_missed += 1
        self._maybe_retain(model, wall_s, missed=missed, trace=trace)

    def _maybe_retain(self, model, wall_s, missed, trace) -> None:
        if trace is None:
            return
        keep = missed
        if not keep and self._hist is not None:
            try:
                keep = wall_s >= self._p99_s(str(model))
            except Exception:
                keep = False  # observability must never fail the path
        if keep:
            with self._lock:
                self._tail.append(trace)
                self._tail_retained += 1

    def _p99_s(self, model: str) -> float:
        """The p99 of ``model``'s e2e histogram (infinite under
        ``_MIN_P99_SAMPLES``), estimated anew once the histogram has
        grown by 3% (at least ``_MIN_P99_SAMPLES``) since the last
        estimate, not on every request: between two estimates the
        threshold is up to that many samples old (the first, taken at
        100 samples, stands until 200). Handler threads write
        ``_p99`` without the lock: one dict store of a tuple, and two
        that race store estimates a few samples apart."""
        hist = self._hist.child(model, "e2e")
        n = hist.count
        if n < _MIN_P99_SAMPLES:
            return float("inf")
        known = self._p99.get(model)
        if known is None or n >= known[0]:
            known = self._p99[model] = (
                n + max(_MIN_P99_SAMPLES, n >> 5), hist.quantile(0.99)
            )
        return known[1]

    # -- reading --------------------------------------------------------------

    def violations(self, n: int = 0) -> list:
        """Most recent ``n`` retained exemplar traces (0 = all
        buffered), oldest first — the ``/traces?slo_violations=1``
        payload."""
        with self._lock:
            traces = list(self._tail)
        return traces[-n:] if n else traces

    def stats(self) -> dict:
        """Numeric-leaved dict for ``RuntimeCollector.snapshot()`` —
        attainment counts keyed ``"model|priority"``, like the error
        counter's ``"model|code"`` keys, so ``delta()`` windows it."""
        with self._lock:
            by_key = {
                f"{m}|{p}": {"met": c[0], "missed": c[1]}
                for (m, p), c in sorted(self._counts.items())
            }
            met = sum(c[0] for c in self._counts.values())
            missed = sum(c[1] for c in self._counts.values())
            return {
                "slo_ms": self._slo_s * 1e3,
                "met": met,
                "missed": missed,
                "requests": by_key,
                "tail_buffered": len(self._tail),
                "tail_retained": self._tail_retained,
            }

    def attainment(self) -> float:
        """Fraction of scored requests that met their SLO (1.0 when
        nothing has been scored yet)."""
        with self._lock:
            met = sum(c[0] for c in self._counts.values())
            total = met + sum(c[1] for c in self._counts.values())
        return met / total if total else 1.0
