"""Model repository: versioned registry of jit-compiled model functions.

Triton's model repository is a directory tree of config.pbtxt + backend
artifacts loaded by a C++ backend manager (reference examples/ layout,
SURVEY.md section 2 #20-21). Here a model is a ModelSpec plus a python
callable over jax arrays; versions are kept in a sorted dict and "the
latest version" is the default serve target, matching Triton's
version_policy default.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Mapping

from triton_client_tpu.config import ModelSpec

# An infer function maps {input_name: jax.Array} -> {output_name: jax.Array}.
InferFn = Callable[[Mapping[str, object]], dict[str, object]]


def _version_key(v: str):
    """Single source of the 'latest version' ordering used by get() and
    versions(): numeric-style compare ('10' > '9') with lexical tiebreak."""
    return (len(v), v)


@dataclasses.dataclass
class RegisteredModel:
    # CONTRACT (round 4 dtype policy): infer_fn may receive inputs
    # NARROWER than the declared wire dtype (e.g. uint8 frames against
    # an FP32 spec) — TPUChannel deliberately skips host-side widening
    # so the 4x-inflated host->device copy never happens
    # (channel/tpu_channel.py). Every pipeline registered here must
    # therefore widen/normalize INSIDE its jitted program, where the
    # cast fuses for free, and must not trust the declared dtype of a
    # leading input. Out-of-tree pipelines that cannot widen internally
    # should declare the narrow dtype in their spec instead.
    spec: ModelSpec
    infer_fn: InferFn
    # Optional warmup callable (compile-ahead on register)
    warmup: Callable[[], None] | None = None
    # Optional jit-traceable form of the model: {name: jax.Array} ->
    # {name: jax.Array} with the SAME tensor names as the wire spec but
    # device arrays end to end. Ensembles compose members through this
    # under ONE jit so intermediates stay in HBM (runtime/ensemble.py);
    # None means the model is host-only (wire path still works).
    device_fn: InferFn | None = None
    # Optional explicit param pytree for the replicate-params /
    # shard-batch serving shape (channel/sharded_channel.py): when set,
    # device_fn must accept ``(inputs, params)`` and the sharded channel
    # uploads the tree ONCE per mesh (replicated on every device) at
    # launcher build instead of letting the closure re-trace captured
    # host constants per executable. None keeps the closure-captured
    # convention every in-tree pipeline uses today.
    params: object | None = None
    # Optional serving PrecisionPolicy (runtime/precision.py), applied
    # at registration: the builder already cast/quantized the param
    # tree; the serving channels consult this for the WIRE half of the
    # policy (host-side narrowing in staged.cast_wire_input, int8
    # dequant inside the cached launcher). None serves the legacy f32
    # wire unchanged.
    precision: object | None = None
    # Optional segment-aware form of the model for packed-ragged
    # batches (runtime/continuous.py): ``ragged_fn(inputs, segment_ids,
    # num_segments) -> outputs`` where each input named in
    # ``spec.extra["ragged_inputs"]`` is a packed (R, ...) row
    # concatenation, ``segment_ids`` is the (R,) int32 row->request
    # table (pad rows carry an out-of-range id), ``num_segments`` is a
    # STATIC python int, and every output has leading dim
    # ``num_segments`` (request-major). None means the model only runs
    # dense.
    ragged_fn: object | None = None
    # Optional session state the model declares (runtime/sessions.py):
    # an object with ``open(request) -> (request, ticket)``,
    # ``advance(ticket, outputs) -> outputs`` and ``close(ticket,
    # host_outputs)``, which the staged channel brackets every launch of
    # this model with. A token model registers a ``TokenSessions`` (a
    # slot of its device-resident cache and a length per stream; its
    # ``params[spec.extra["device_state"]]`` is that cache, donated
    # into each launch and taken back from its outputs). None means the
    # server's tracker sessions apply to requests under a sequence_id.
    sessions: object | None = None


class ModelRepository:
    """Thread-safe name -> version -> model registry."""

    def __init__(self) -> None:
        self._models: dict[str, dict[str, RegisteredModel]] = {}
        self._lock = threading.Lock()
        # unregister listeners: fn(name, version), called once per
        # removed version OUTSIDE the registry lock. Serving channels
        # subscribe so a dropped model also drops its cached launcher
        # (and the replicated params that closure pins in HBM) — the
        # same invalidation path the circuit breaker uses.
        self._unregister_listeners: list[Callable[[str, str], None]] = []
        # moves whenever a model is registered (anew or over one that
        # stood) or unregistered: what a channel derived from a model's
        # spec and kept holds until this has moved
        self.generation = 0
        # access accounting for lifecycle LRU: per-name hit count and
        # last-touch monotonic sequence, maintained by get().
        self._access_count: dict[str, int] = {}
        self._access_seq: dict[str, int] = {}
        self._seq = 0

    def add_unregister_listener(self, fn: Callable[[str, str], None]) -> None:
        with self._lock:
            self._unregister_listeners.append(fn)

    def register(
        self,
        spec: ModelSpec,
        infer_fn: InferFn,
        warmup: Callable[[], None] | None = None,
        device_fn: InferFn | None = None,
        params: object | None = None,
        precision: object | None = None,
        ragged_fn: object | None = None,
        sessions: object | None = None,
    ) -> None:
        with self._lock:
            self._models.setdefault(spec.name, {})[spec.version] = RegisteredModel(
                spec, infer_fn, warmup, device_fn, params, precision, ragged_fn,
                sessions,
            )
            self.generation += 1

    def unregister(self, name: str, version: str = "") -> None:
        removed: list[tuple[str, str]] = []
        with self._lock:
            if version:
                if self._models.get(name, {}).pop(version, None) is not None:
                    removed.append((name, version))
                if not self._models.get(name):
                    self._models.pop(name, None)
            else:
                for v in self._models.pop(name, {}):
                    removed.append((name, v))
            self.generation += len(removed)
            listeners = list(self._unregister_listeners)
        # notify outside the lock: listeners take channel locks of
        # their own and must be free to call back into the repository
        for n, v in removed:
            for fn in listeners:
                fn(n, v)

    def get(self, name: str, version: str = "") -> RegisteredModel:
        with self._lock:
            versions = self._models.get(name)
            if not versions:
                raise KeyError(f"model '{name}' is not registered")
            self._seq += 1
            self._access_count[name] = self._access_count.get(name, 0) + 1
            self._access_seq[name] = self._seq
            if version:
                if version not in versions:
                    raise KeyError(f"model '{name}' has no version '{version}'")
                return versions[version]
            latest = max(versions, key=_version_key)
            return versions[latest]

    def metadata(self, name: str, version: str = "") -> ModelSpec:
        return self.get(name, version).spec

    def list_models(self) -> list[tuple[str, str]]:
        with self._lock:
            return [(n, v) for n, vs in self._models.items() for v in vs]

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self._models)

    def versions(self, name: str) -> list[str]:
        with self._lock:
            return sorted(self._models.get(name, {}), key=_version_key)

    def access_stats(self) -> dict[str, dict[str, int]]:
        """Per-name get() hit count and last-touch sequence (monotonic,
        repository-wide) — the lifecycle manager's LRU raw material."""
        with self._lock:
            return {
                name: {
                    "count": self._access_count.get(name, 0),
                    "last_seq": self._access_seq.get(name, 0),
                }
                for name in self._models
            }
