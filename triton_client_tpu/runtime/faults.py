"""Deterministic fault injection for the serving stack.

The robustness ring (admission shedding, member-only failure fan-out,
circuit breakers, drain) is only trustworthy if every behavior is
provable in tier-1 tests WITHOUT real hardware faults — a TPU that
conveniently throws on the third launch does not exist. This module is
the lever: a seeded :class:`FaultPlan` is installed process-wide (test
fixture or ``serve --fault-plan plan.json``), and the serving hot paths
probe named injection points:

  ==============  ========================================== =========
  point           probed from                                effect
  ==============  ========================================== =========
  launch          StagedChannel.launch, before the jit call  raise
  readback        InferFuture resolve, before host copy      raise
  slow_launch     StagedChannel.launch, before the jit call  sleep
  codec_decode    codec.parse_infer_request                  raise
  batcher_stall   batcher _run_group, slot time              sleep
  replica_down    _Servicer ServerReady/ModelReady/_issue    flag
  shm_detach      _Servicer before shm request parse         flag
  quality_corrupt eval ShadowMirror worker, before scoring   flag
  temporal_overskip TemporalReusePlane.dispatch, per stream  flag
  ==============  ========================================== =========

The ``replica_down`` point is flag-class (:func:`probe_flag`): the
server consults it with its ``--replica-of`` label as the model key and
simulates process death while the transport stays up — ServerReady
answers not-ready and inference answers UNAVAILABLE (no drain marker) —
so the router chaos shard can kill a replica deterministically.

``shm_detach`` is flag-class too, keyed by model name: the servicer
drops its whole shared-memory registry before parsing the faulted
request, simulating a server restart under a client that still holds
mapped segments — the client must re-register its pool and re-issue
(unary) or fall back per-member (stream), never serve stale bytes.

``quality_corrupt`` (ISSUE 17) is flag-class, keyed by the *variant*
model name: the shadow mirror's scoring worker consults it and, when
armed, perturbs the variant's served detections deterministically
(``eval.shadow.corrupt_detections``, RNG seeded from the trace id)
before they are scored against the f32 reference — an unmistakably
out-of-budget quality regression with zero real model damage, so the
canary auto-rollback path is drivable in CI and the acceptance drive
("corrupting variant ejected before it serves 1% of traffic") replays
identically under a fixed plan.

``temporal_overskip`` (ISSUE 19) is flag-class, keyed by the STREAM id
(sequence_id), not a model name: while armed, the temporal reuse plane
pins that stream's keyframe interval wide open (K = k_max) and ignores
the innovation feedback that would normally collapse it — a
deterministically over-aggressive scheduler. The acceptance drive uses
it to prove the safety net: the per-stream ID-churn window must detect
the resulting track instability and auto-disable reuse for that stream
(``tpu_serving_temporal_disabled_total{reason="churn"}``) before the
quality budgets are violated.

Determinism: rules fire by COUNT windows (requests ``after`` .. ``after
+ count`` at that point/model), and probabilistic rules draw from a
``random.Random(seed)`` owned by the plan — the same plan over the same
request sequence replays the identical fault timeline, which is what
makes the chaos CI shard (ci.sh) reproducible and the bitwise
surviving-request parity test possible.

The probe is a module-level function guarded by a single global: with
no plan installed it is one ``is None`` check, so the hot paths pay
nothing in production.
"""

from __future__ import annotations

import json
import random
import threading
import time
from dataclasses import dataclass, field


class InjectedFault(RuntimeError):
    """The error raised at a faulted injection point. A distinct type
    so tests can assert the failure they see is the one they planned,
    not an incidental bug."""


@dataclass
class FaultRule:
    """One injection rule: fire at ``point`` (optionally only for
    ``model``) on probe numbers ``after`` <= n < ``after + count``,
    each firing gated by ``prob``. ``latency_s`` sleeps instead of
    raising for the sleep-class points (slow_launch/batcher_stall)."""

    point: str
    model: str | None = None
    after: int = 0
    count: int = 1
    prob: float = 1.0
    latency_s: float = 0.0
    message: str = "injected fault"
    # runtime state: probes observed / fires executed (not config)
    seen: int = field(default=0, compare=False)
    fired: int = field(default=0, compare=False)


class FaultPlan:
    """A seeded set of :class:`FaultRule`\\ s with thread-safe probes."""

    def __init__(self, rules=(), seed: int = 0) -> None:
        self.rules = [
            r if isinstance(r, FaultRule) else FaultRule(**dict(r))
            for r in rules
        ]
        self.seed = int(seed)
        self._rng = random.Random(self.seed)
        self._lock = threading.Lock()
        self.fired: list[tuple[str, str | None]] = []

    @classmethod
    def from_json(cls, text: str) -> "FaultPlan":
        """Build from the CLI/file form::

            {"seed": 7, "rules": [{"point": "launch", "model": "m",
                                   "after": 2, "count": 3}]}
        """
        doc = json.loads(text)
        return cls(rules=doc.get("rules", ()), seed=doc.get("seed", 0))

    def check(self, point: str, model: str | None = None) -> float:
        """Consult the plan at ``point`` for ``model``. Returns a sleep
        duration (0.0 = no sleep) or raises :class:`InjectedFault`.
        Counting and RNG draws happen under the plan lock so concurrent
        probes see one deterministic global order per (point, model)."""
        sleep_s = 0.0
        raise_msg = None
        with self._lock:
            for rule in self.rules:
                if rule.point != point:
                    continue
                if rule.model is not None and rule.model != model:
                    continue
                n = rule.seen
                rule.seen += 1
                if not (rule.after <= n < rule.after + rule.count):
                    continue
                if rule.prob < 1.0 and self._rng.random() >= rule.prob:
                    continue
                rule.fired += 1
                self.fired.append((point, model))
                if rule.latency_s > 0:
                    sleep_s = max(sleep_s, rule.latency_s)
                else:
                    raise_msg = rule.message
        if raise_msg is not None:
            raise InjectedFault(f"{point}: {raise_msg}")
        return sleep_s

    def stats(self) -> dict:
        with self._lock:
            return {
                "seed": self.seed,
                "fired": len(self.fired),
                "rules": [
                    {
                        "point": r.point,
                        "model": r.model,
                        "seen": r.seen,
                        "fired": r.fired,
                    }
                    for r in self.rules
                ],
            }


# -- process-wide installation hook ------------------------------------------

_ACTIVE: FaultPlan | None = None


def install_fault_plan(plan: FaultPlan | None) -> FaultPlan | None:
    """Install ``plan`` process-wide (None uninstalls); returns the
    previous plan so test fixtures can restore it."""
    global _ACTIVE
    prev, _ACTIVE = _ACTIVE, plan
    return prev


def active_plan() -> FaultPlan | None:
    return _ACTIVE


def probe(point: str, model: str | None = None) -> None:
    """The hot-path hook: no-op (one global read) without a plan;
    otherwise consult it — sleeping faults sleep HERE, raising faults
    raise :class:`InjectedFault` out of the calling injection point."""
    plan = _ACTIVE
    if plan is None:
        return
    sleep_s = plan.check(point, model)
    if sleep_s > 0:
        time.sleep(sleep_s)


def probe_flag(point: str, model: str | None = None) -> bool:
    """Flag-class probe: True iff a rule fired, never raises or
    sleeps. For injection points where the CALLER owns the failure
    shape (``replica_down``: the servicer must answer a protocol-
    correct not-ready / UNAVAILABLE, not leak an InjectedFault
    traceback). Same counting/seeding discipline as :func:`probe`, so
    flag rules replay identically too."""
    plan = _ACTIVE
    if plan is None:
        return False
    try:
        plan.check(point, model)
    except InjectedFault:
        return True
    return False
