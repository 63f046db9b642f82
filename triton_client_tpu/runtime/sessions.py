"""Streaming-session state: device-resident per-stream tracking slots.

ROADMAP item 5's session layer. A :class:`SessionManager` owns a
bounded pool of per-stream slots, each holding the on-device tracker
state pytree from ops/tracking.py between frames — the KV-cache
pattern from PAPERS.md's ragged-paged-attention exemplar transplanted
to track state: per-sequence state lives in HBM for the stream's
lifetime and the per-frame step is appended to the detector's launch,
so on the steady-state path NOTHING crosses the host boundary (the
parity/residency gate in tests/test_sessions.py runs a whole stream
under ``jax.transfer_guard_device_to_host("disallow")``).

Wiring (the ``sequence_id`` thread): kserve clients set
``sequence_id`` / ``sequence_start`` / ``sequence_end`` request
parameters (channel/kserve/codec.py), ``_Servicer._issue`` decodes
them onto the InferRequest, the batchers solo-dispatch session frames
(state depends on frame order — merging two streams' frames into one
launch would interleave their steps), and StagedChannel.launch calls
:meth:`SessionManager.advance` on the launch outputs before the
response futures form. ``advance`` bumps the slot's refcount;
``release`` (called from the launch's resolve, success or failure)
drops it — exactly the lifecycle manager's acquire/release bracket, so
TTL/LRU reclaim can never free a slot with an in-flight launch.

Slot reclaim mirrors runtime/lifecycle.py's eviction ladder: ended
slots first, then TTL-expired, then LRU — always refs==0 only; a full
pool with every slot in flight rejects the new stream with
:class:`SessionLimitError` (RESOURCE_EXHAUSTED on the wire, same
non-retryable overload contract as admission).

Two kinds of state share that pool and ladder (:class:`SlotPool`), each
declared by the model it belongs to: the tracker pytree above (the
default: :class:`SessionManager`, for any detector), and a slot of a
token model's latent cache with a length (:class:`TokenSessions`, which
a language model registers as ``RegisteredModel.sessions``). The staged
channel drives either through the same three calls, ``open`` before the
launch's inputs are placed, ``advance`` on the launched outputs,
``close`` when the launch resolves.

Track-id namespace: ids are int32 ``namespace(4b) | epoch(11b) |
local(16b)`` — ``namespace`` distinguishes replicas (serve
``--session-id-namespace``), ``epoch`` increments on every session
(re)start, so a stream re-homed to a new replica after failover — or
restarted on the same one — mints ids PROVABLY disjoint from its
previous life's. 16 local bits bound one session life at 65k track
births; 11 epoch bits wrap at 2048 session lives per process
(documented in OPERATIONS.md).
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time

import numpy as np

from triton_client_tpu.ops import tracking
from triton_client_tpu.runtime.admission import AdmissionRejectedError

log = logging.getLogger(__name__)


class SessionLimitError(AdmissionRejectedError):
    """Session pool full and nothing reclaimable — maps to
    RESOURCE_EXHAUSTED (non-retryable overload) like every admission
    reject."""


#: output tensors ``advance`` consumes from the detector launch
DET_KEY = "detections"
VALID_KEY = "valid"

_NAMESPACE_BITS = 4
_EPOCH_BITS = 11
_LOCAL_BITS = 16


def id_base_for(namespace: int, epoch: int) -> int:
    """int32-positive id floor for one session life — see module doc."""
    ns = int(namespace) & ((1 << _NAMESPACE_BITS) - 1)
    ep = int(epoch) & ((1 << _EPOCH_BITS) - 1)
    return (ns << (_EPOCH_BITS + _LOCAL_BITS)) | (ep << _LOCAL_BITS)


@dataclasses.dataclass
class _Slot:
    stream_id: str
    epoch: int = 0
    id_base: int = 0
    # what the stream's model declares: the tracker's device pytree
    # (lazily built on frame 1), or a token model's cache slot index
    state: object | None = None
    length: int = 0  # tokens held (token sessions)
    group: int = 0  # 0 single-frame; >0 synchronized-camera group size
    refs: int = 0
    frames: int = 0
    ended: bool = False
    created: float = 0.0
    last_used: float = 0.0
    # serializes the per-frame step: frames of one stream must advance
    # in order even if a client pipelines requests
    step_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False
    )


class SlotPool:
    """A bounded pool of per-stream slots and its reclaim ladder: ended
    slots first, then TTL-expired, then LRU, always ``refs == 0`` only.
    Whatever a slot holds is its owner's; ``on_free`` is told of every
    slot that leaves the pool (caller holds ``lock``)."""

    def __init__(self, max_slots: int, ttl_s: float, time_fn, on_free) -> None:
        self.max = max(1, int(max_slots))
        self.ttl_s = float(ttl_s)
        self.time = time_fn
        self.on_free = on_free
        self.lock = threading.Lock()
        self.slots: dict[str, _Slot] = {}
        self.expired = 0
        self.reclaimed = 0
        self.rejected = 0

    def make_room_locked(self, now: float) -> None:
        """Free one refs==0 slot: ended > TTL-expired > LRU. Raises
        SessionLimitError when every slot has in-flight work."""
        if len(self.slots) < self.max:
            return
        idle = [s for s in self.slots.values() if s.refs == 0]
        victim = None
        for s in idle:
            if s.ended:
                victim = s
                break
        if victim is None and self.ttl_s > 0:
            for s in idle:
                if now - s.last_used > self.ttl_s:
                    victim = s
                    self.expired += 1
                    break
        if victim is None and idle:
            victim = min(idle, key=lambda s: s.last_used)
            self.reclaimed += 1
        if victim is None:
            self.rejected += 1
            raise SessionLimitError(
                f"session pool full ({self.max} slots, all in flight)"
            )
        self.free_locked(victim)

    def free_locked(self, slot: _Slot) -> None:
        del self.slots[slot.stream_id]
        self.on_free(slot)


class SessionManager:
    """Bounded pool of device-resident streaming-session slots.

    ``tracker``: the ops/tracking.py config every session runs.
    ``id_namespace``: replica-distinguishing 4-bit id prefix.
    ``time_fn``: injectable clock (tests drive TTL deterministically).
    """

    def __init__(
        self,
        max_sessions: int = 64,
        ttl_s: float = 60.0,
        tracker: tracking.TrackerConfig | None = None,
        id_namespace: int = 0,
        time_fn=time.monotonic,
    ) -> None:
        self.tracker = tracker or tracking.TrackerConfig()
        self._namespace = int(id_namespace)
        self._time = time_fn
        # the pool and its ladder are every session kind's; a freed
        # tracker slot queues its device counters for the next fold
        self._pool = SlotPool(
            max_sessions, ttl_s, time_fn, self._fold_async_locked
        )
        self._max = self._pool.max
        self._lock = self._pool.lock
        self._slots = self._pool.slots
        # dead sessions' state pytrees awaiting a counter fold (device
        # reads deferred to scrape time — see _drain_folds)
        self._dead_states: list = []
        self._epochs = 0
        # host-side counters; device birth/death totals fold in when a
        # session ends or restarts (one read per session LIFE, never on
        # the steady-state frame path)
        self._created = 0
        self._restarted = 0
        self._ended = 0
        self._frames = 0
        self._coasted = 0
        self._births_total = 0
        self._deaths_total = 0

    # -- pool bookkeeping (locked) --------------------------------------------

    def _next_epoch_locked(self) -> int:
        self._epochs += 1
        return self._epochs

    def _fold_async_locked(self, slot: _Slot) -> None:
        """Queue a dead slot's device counters for the next stats()
        fold (caller holds the pool lock) — the device READ happens
        later, outside the lock and off the frame path."""
        if slot.state is not None:
            self._dead_states.append(slot.state)

    def _drain_folds(self) -> None:
        """Fold queued dead sessions' device birth/death counters into
        the host totals. Device reads, so: never called from advance /
        release (the hot bracket) — only from stats() scrapes and
        end-of-stream folds."""
        while True:
            with self._lock:
                if not self._dead_states:
                    return
                state = self._dead_states.pop()
            births = int(np.asarray(state["births"]))
            deaths = int(np.asarray(state["deaths"]))
            with self._lock:
                self._births_total += births
                self._deaths_total += deaths

    # -- the frame bracket ----------------------------------------------------

    def open(self, request):
        """The channel's first call of a launch: a tracker changes
        nothing of the request; its ticket is the request."""
        return request, request

    def abort(self, request) -> None:
        """A launch that never reached :meth:`advance` took no ref."""

    def close(self, request, host_outputs=None) -> None:
        """The channel's last call of a launch that advanced (success
        or failure)."""
        self.release(request.sequence_id)

    def advance(self, request, outputs):
        """Run one tracking step on a detector launch's device outputs.

        Called from StagedChannel.launch with the raw (device) output
        dict; returns the dict extended with the track tensors. Bumps
        the slot refcount — the caller MUST pair with :meth:`release`
        (the launch's resolve does, on success and failure alike).
        Pure device work: the step is an async jit dispatch on arrays
        already in HBM; no host transfer happens here.
        """
        sid = request.sequence_id
        now = self._time()
        with self._lock:
            slot = self._slots.get(sid)
            fresh = None
            if slot is None:
                self._pool.make_room_locked(now)
                slot = _Slot(
                    stream_id=sid,
                    epoch=self._next_epoch_locked(),
                    id_base=0,
                    created=now,
                    last_used=now,
                )
                slot.id_base = id_base_for(self._namespace, slot.epoch)
                self._slots[sid] = slot
                self._created += 1
            elif request.sequence_start or slot.ended:
                # clean in-place restart: fresh epoch, disjoint ids —
                # the failover contract (router re-homes with
                # sequence_start=True on the new owner)
                fresh = slot.state
                slot.epoch = self._next_epoch_locked()
                slot.id_base = id_base_for(self._namespace, slot.epoch)
                slot.state = None
                slot.group = 0
                slot.frames = 0
                slot.ended = False
                slot.created = now
                self._restarted += 1
            slot.refs += 1
            slot.last_used = now
            if fresh is not None:
                self._dead_states.append(fresh)
        try:
            out = self._step(slot, outputs)
        except Exception:
            with self._lock:
                slot.refs -= 1
            raise
        if request.sequence_end:
            with self._lock:
                slot.ended = True
                self._ended += 1
        return out

    def _cfg_for(self, det_dim: int) -> tracking.TrackerConfig:
        """The stream tracker config adapted to this model's detection
        row width. The default config carries CenterPoint's
        ``velocity_cols=(7, 9)``; a 2D detector's 6-column rows hold no
        measured velocity, so the window must narrow to ``None`` rather
        than slice past the row (a width-0 ``z_vel`` crashes the
        update)."""
        cfg = self.tracker
        if cfg.velocity_cols is not None and det_dim < cfg.velocity_cols[1]:
            cfg = dataclasses.replace(cfg, velocity_cols=None)
        return cfg

    def _step(self, slot: _Slot, outputs):
        det = outputs.get(DET_KEY)
        valid = outputs.get(VALID_KEY)
        if det is None or valid is None:
            return outputs  # model has no tracking-compatible head
        ndim = getattr(det, "ndim", 2)
        cfg = self._cfg_for(int(det.shape[-1]))
        with slot.step_lock:
            if ndim == 3:
                # leading dim = synchronized camera group (B==1 is a
                # group of one): vmapped step, stacked state
                group = int(det.shape[0])
                if slot.state is None:
                    base = tracking.init_state(
                        cfg, int(det.shape[-1]), slot.id_base
                    )
                    # disjoint per-camera id ranges: split the session's
                    # 16-bit local id space evenly across the group
                    span = (1 << _LOCAL_BITS) // group
                    stacked = {
                        k: np.stack([v] * group) for k, v in base.items()
                    }
                    stacked["next_id"] = np.asarray(
                        [slot.id_base + 1 + c * span for c in range(group)],
                        np.int32,
                    )
                    slot.state = stacked
                    slot.group = group
                elif slot.group != group:
                    raise ValueError(
                        f"stream '{slot.stream_id}': camera-group size "
                        f"changed mid-stream ({slot.group} -> {group})"
                    )
                step = tracking.make_group_step(cfg)
            else:
                if slot.state is None:
                    slot.state = tracking.init_state(
                        cfg, int(det.shape[-1]), slot.id_base
                    )
                    slot.group = 0
                step = tracking.make_step(cfg)
            new_state, track_out = step(slot.state, det, valid)
            slot.state = new_state
            slot.frames += 1
        with self._lock:
            self._frames += 1
        out = dict(outputs)
        out.update(track_out)
        return out

    def coast(self, request):
        """Advance one frame by Kalman predict alone — the detector is
        skipped entirely (runtime/temporal.py's keyframe scheduler
        decided this frame is temporally redundant). Returns the coast
        outputs dict (track table only), or ``None`` when the stream
        has no device state yet — a coast before the first keyframe is
        meaningless and the caller must fall back to full detection.

        Same refcount contract as :meth:`advance`: bumps the slot ref,
        caller MUST pair with :meth:`release`. Pure device work — one
        jit dispatch over the resident state pytree, nothing crosses
        the host boundary."""
        sid = request.sequence_id
        now = self._time()
        with self._lock:
            slot = self._slots.get(sid)
            if slot is None or slot.state is None or slot.ended \
                    or request.sequence_start:
                return None
            slot.refs += 1
            slot.last_used = now
        try:
            with slot.step_lock:
                if slot.state is None:  # reset raced us
                    with self._lock:
                        slot.refs -= 1
                    return None
                # same det-width-narrowed config as _step, so the coast
                # jit shares the keyframe step's cache entry per stream
                cfg = self._cfg_for(int(slot.state["box"].shape[-1]))
                coast = (
                    tracking.make_group_coast(cfg)
                    if slot.group
                    else tracking.make_coast_step(cfg)
                )
                new_state, track_out = coast(slot.state)
                slot.state = new_state
                slot.frames += 1
        except Exception:
            with self._lock:
                slot.refs -= 1
            raise
        with self._lock:
            self._frames += 1
            self._coasted += 1
        if request.sequence_end:
            with self._lock:
                slot.ended = True
                self._ended += 1
        return dict(track_out)

    def release(self, stream_id: str) -> None:
        """Drop the in-flight ref taken by :meth:`advance`. Ended slots
        free (and queue their counters for the next stats fold) once
        the last ref drops."""
        with self._lock:
            slot = self._slots.get(stream_id)
            if slot is None:
                return
            slot.refs = max(0, slot.refs - 1)
            if slot.ended and slot.refs == 0:
                self._pool.free_locked(slot)

    def end(self, stream_id: str) -> None:
        """Explicitly end a session (server drain, client abort)."""
        with self._lock:
            slot = self._slots.get(stream_id)
            if slot is None:
                return
            slot.ended = True
            if slot.refs == 0:
                self._pool.free_locked(slot)
        self._drain_folds()

    def reset(self) -> None:
        """Drop every session (drain/shutdown). In-flight launches keep
        their state pytrees alive via closure; new frames restart."""
        with self._lock:
            slots = list(self._slots.values())
            self._slots.clear()
            for s in slots:
                self._fold_async_locked(s)

    # -- observability --------------------------------------------------------

    def stats(self) -> dict:
        """Pool counters for the collector. Folds queued dead-session
        device counters first (scrape-time device reads only — the
        frame path stays transfer-free)."""
        self._drain_folds()
        with self._lock:
            active = len(self._slots)
            inflight = sum(s.refs for s in self._slots.values())
            return {
                "active_sessions": active,
                "max_sessions": self._max,
                "slot_occupancy": active / self._max,
                "inflight_frames": inflight,
                "created_total": self._created,
                "restarted_total": self._restarted,
                "ended_total": self._ended,
                "expired_total": self._pool.expired,
                "reclaimed_total": self._pool.reclaimed,
                "rejected_total": self._pool.rejected,
                "frames_total": self._frames,
                "coast_frames_total": self._coasted,
                "track_births_total": self._births_total,
                "track_deaths_total": self._deaths_total,
            }


# -- token sessions: a slot of a model's device-resident cache ----------------


@dataclasses.dataclass
class TokenLaunch:
    """One launch of a token model, as :meth:`TokenSessions.open`
    admitted it: the sessions it extends row by row, and what its spans
    and counters say of it. ``rows`` is empty for a launch that came as
    plain arrays (a compile of a launch shape)."""

    #: "lm_prefill": one session, many tokens; "lm_step": one token a session;
    #: "lm_block": one block a session, written by the rows that commit
    kind: str
    rows: list  # [(stream_id, tokens its length moved by, restarted, ends)]
    tokens: int = 0
    sessions: int = 0
    context: int = 0  # positions cached before the launch, summed over its sessions
    keys_visible: int = 0  # over its tokens: the positions each may attend to ...
    keys_selected: int = 0  # ... and those it reads (the model's ``index_topk`` at most)
    keys_read: int = 0  # ... and, of a model with row geometries, those its windows leave it
    step_keys_fetched: int = 0  # a step launch of a model that reads latent rows by blocks: positions fetched ...
    step_keys_whole: int = 0  # ... and the positions of its rows' slots whole
    answers: int = 0  # rows of the answer that are some session's: one a session, a block launch's one a token
    commit_rows: int = 0  # of a block launch's sessions, those that wrote their block

    @property
    def span(self):
        """The launch's span over its device window, and its attributes."""
        attrs = {"tokens": self.tokens, "sessions": self.sessions, "context": self.context}
        if self.kind == "lm_block":
            attrs["commit_rows"] = self.commit_rows
        return self.kind, attrs


@dataclasses.dataclass(frozen=True)
class RowGeometry:
    """One geometry of the rows a session's slot keeps: the ``layers``
    that share it, the ``rows`` a slot has in each of them, the positions
    back a query of theirs reads, its own among them (``window``; 0: all,
    and a row a position) and a row's bytes. With a window the rows are a
    ring (models/smallthinker.py): a session holds ``min(length, rows)``
    of them, and a token at position ``p`` reads ``min(p + 1, window)``."""

    name: str
    layers: int
    rows: int
    window: int
    row_bytes: int

    def held(self, length: int) -> int:
        """Bytes a session of ``length`` positions holds in this geometry."""
        return self.layers * min(int(length), self.rows) * self.row_bytes


class TokenSessions:
    """The session state a token model declares: each stream holds one
    slot of the model's device-resident cache and a length.

    The model's pipeline (pipelines/lm.py) registers one of these as
    ``RegisteredModel.sessions``. A request under a ``sequence_id``
    carries ``tokens [1, n]``; the batcher may merge the one-token
    requests of DIFFERENT sessions into one launch (``tokens [B, 1]``
    with ``InferRequest.sequence_rows`` naming each row's stream). A
    model that generates by blocks (``block`` > 0) has a second
    operation on a slot, and no one-token step: a request that carries
    ``commit [1, 1]`` beside ``tokens [1, block]`` runs that block at
    the slot's length and answers every position of it; with ``commit``
    0 (a denoising pass) the slot's length, like the cache, stays as it
    was, with ``commit`` 1 it moves by ``block``. The batcher merges the
    block requests of different sessions as it merges steps.
    :meth:`open` gives every row its slot and position, refuses a stream
    that would outgrow its slot or finds no room
    (:class:`SessionLimitError`), and forms the launch's plain arrays
    (``tokens``, ``slots``, ``positions``, ``lengths``) padded to one of
    the model's launch shapes; pad rows have length 0 and write nothing.
    The cache itself never passes through here: it stays on the device,
    donated from launch to launch by the channel.

    A model whose layers hold a RECURRENT STATE beside rows of a cache
    (``state_bytes`` > 0: models/ling.py) changes what a slot is. Rows
    beyond a session's length are masked, so a slot described by a length
    alone is reused without touching the device; a state is read whole by
    the next token. So: a row admitted at position 0 is counted in
    :meth:`open` (``lm_state_resets``; a further many-token turn counts
    in ``lm_state_carries``) and the launch itself reads a zero state for
    it, from ``positions`` (no extra launch, no host copy: slot reuse
    after ``sequence_end`` and after the TTL goes the same way); a launch
    refused BEFORE dispatch (:meth:`abort`) leaves state and length as
    they were, as for any model; a launch that FAILED after dispatch
    (:meth:`close` without outputs) has already overwritten the state of
    its rows, which no length can take back: its sessions END, and their
    next request is refused with that reason (``lm_state_lost``).

    A model whose layers keep rows in MORE THAN ONE GEOMETRY
    (``geometries``: models/smallthinker.py's full and window layers)
    is admitted by positions all the same (``slot_len``: what its
    longest geometry holds); a ring must hold its window and the longest
    extend launch, which the constructor checks. What changes is the
    account: ``lm_keys_read`` beside ``lm_keys_visible`` (what a window
    leaves of a token's keys), and the cache in BYTES by geometry
    (``session_cache_bytes``, ``session_cache_bytes_in_use``), since the
    share of ``slot_len`` that sessions hold says nothing of a ring.

    One request of a stream at a time: a later request of a stream whose
    earlier one is still in flight waits its turn in :meth:`open`.
    """

    INPUT = "tokens"
    COMMIT = "commit"  # beside ``tokens``: a block request, and whether it writes its block
    LAUNCH_INPUTS = ("tokens", "slots", "positions", "lengths")
    EXPERT_ROWS = "expert_rows"

    def __init__(
        self,
        slots: int,
        slot_len: int,
        max_tokens: int,
        token_bucket,
        step_bucket,
        ttl_s: float = 60.0,
        time_fn=time.monotonic,
        turn_timeout_s: float = 30.0,
        index_topk: int = 0,
        layers: int = 1,
        index_cache_bytes: int = 0,
        block: int = 0,
        state_bytes: int = 0,
        geometries: tuple = (),
        step_keys: tuple = (),
    ) -> None:
        """``index_topk``: the positions a token of the model attends to
        at most (0: all), ``layers`` its layers, ``index_cache_bytes``
        what its index keys take beside the latent cache (a gauge): what
        the counters ``lm_keys_visible`` / ``lm_keys_selected`` need.
        ``block``: the tokens a block request of the model carries (0:
        the model has one-token steps and no block operation).
        ``state_bytes``: what ONE session's recurrent state takes on the
        device whatever its length (0: the model holds none, and a slot
        is its length). ``geometries``: :class:`RowGeometry`'s fields for
        each geometry of rows a slot keeps (empty: one row a position in
        every layer, which ``layers`` counts). ``step_keys``: the
        positions a row of the model's step launch fetches of its slot
        at a time and the layers that attend so (empty: the model's step
        launch does not read by blocks, and the two counters stay 0)."""
        self._geometries = tuple(RowGeometry(*g) for g in geometries)
        launch = token_bucket(int(max_tokens))
        for g in self._geometries:
            if g.window and g.rows < g.window + launch - 1:
                raise ValueError(
                    f"the {g.name} layers keep a ring of {g.rows} rows: under a window of {g.window} and extend "
                    f"launches of up to {launch} tokens it takes {g.window + launch - 1}"
                )
        self._state_bytes = int(state_bytes)
        self._step_key_block, self._step_key_layers = map(int, step_keys or (0, 0))
        self._lost: dict = {}  # stream -> why it holds no slot any more, while the stream may still ask
        self.slot_len = int(slot_len)
        self.max_tokens = int(max_tokens)
        self._block = int(block)
        self._index_topk = int(index_topk)
        self._layers = int(layers)
        self._index_cache_bytes = int(index_cache_bytes)
        self._token_bucket = token_bucket
        self._step_bucket = step_bucket
        self._free = list(range(int(slots) - 1, -1, -1))
        self._pool = SlotPool(slots, ttl_s, time_fn, self._freed_locked)
        self._turn = threading.Condition(self._pool.lock)
        self._turn_timeout_s = float(turn_timeout_s)
        self._oneshots = 0
        self._counters = {
            "lm_tokens_prefill": 0, "lm_tokens_step": 0,
            "lm_prefill_launches": 0, "lm_step_launches": 0,
            "lm_step_sessions": 0, "lm_context_prefill": 0,
            "lm_keys_visible": 0, "lm_keys_selected": 0, "lm_keys_read": 0,
            "lm_block_launches": 0, "lm_block_rows": 0,
            "lm_block_commit_rows": 0, "lm_tokens_committed": 0,
            "lm_state_resets": 0, "lm_state_carries": 0, "lm_state_lost": 0,
            "lm_step_experts_chosen": 0, "lm_step_experts_held": 0,
            "lm_step_keys_fetched": 0, "lm_step_keys_whole": 0,
            "created_total": 0, "ended_total": 0,
            "outgrown_total": 0, "unknown_total": 0,
        }
        self._expert_rows = None  # [expert layers, experts held], summed over launches

    def _freed_locked(self, slot: _Slot) -> None:
        self._free.append(slot.state)
        self._turn.notify_all()

    # -- the launch bracket ---------------------------------------------------

    def launch_kind(self, inputs: dict) -> str:
        """``lm_step`` for a launch of one token a row, ``lm_block`` for
        one of a block a row (it carries ``commit``), ``lm_prefill`` for
        one of many tokens: the launch's span, and the suffix of its
        module's name in a device trace."""
        if inputs[self.INPUT].shape[1] == 1:
            return "lm_step"
        return "lm_block" if self.COMMIT in inputs else "lm_prefill"

    def open(self, request):
        """Admit one launch. Returns the request as the device program
        takes it and the launch's :class:`TokenLaunch` ticket; pair with
        :meth:`close` (success or failure)."""
        inputs = request.inputs
        if "slots" in inputs:
            # plain arrays as the device program takes them (a launch
            # shape compiled ahead of traffic): no stream, nothing kept
            return request, TokenLaunch(self.launch_kind(inputs), [], 0)
        tokens = np.asarray(inputs[self.INPUT]).astype(np.int32, copy=False)
        if tokens.ndim != 2:
            raise ValueError(f"tokens must be [1, n]; got shape {tokens.shape}")
        b, n = tokens.shape
        named = request.sequence_rows or (
            (request.sequence_id, request.sequence_start, request.sequence_end),
        )
        if self._block:
            return self._open_blocks(request, tokens, named)
        if len(named) != b or (b > 1 and n != 1) or not 1 <= n <= self.max_tokens:
            raise ValueError(
                f"a request carries tokens [1, n], 1 <= n <= {self.max_tokens}; "
                f"got {tokens.shape} for {len(named)} session(s)"
            )
        ticket = TokenLaunch(self.launch_kind({self.INPUT: tokens}), [], b * n, b, answers=b)
        slots = np.zeros(b, np.int32)
        positions = np.zeros(b, np.int32)
        try:
            for i, (stream_id, start, end) in enumerate(named):
                slots[i], positions[i] = self._admit(ticket, stream_id, start, end, n)
        except Exception:
            self.close(ticket, None, failed=True)
            raise
        if n == 1:
            rows = self._step_bucket(b)
            pad = rows - b
            if self._step_key_block:
                # every row of the launch's shape reads the blocks up to its position: a pad row's one
                blocks = int((positions // self._step_key_block + 1).sum()) + pad
                ticket.step_keys_fetched = self._step_key_layers * blocks * self._step_key_block
                ticket.step_keys_whole = self._step_key_layers * rows * self.slot_len
            launch = {
                "tokens": np.concatenate([tokens, np.zeros((pad, 1), np.int32)]),
                "slots": np.concatenate([slots, np.zeros(pad, np.int32)]),
                "positions": np.concatenate([positions, np.zeros(pad, np.int32)]),
                "lengths": np.concatenate([np.ones(b, np.int32), np.zeros(pad, np.int32)]),
            }
        else:
            launch = self._extend_arrays(tokens, slots, positions)
        return dataclasses.replace(request, inputs=launch), ticket

    def _extend_arrays(self, tokens, slots, positions) -> dict:
        """An extend launch's plain arrays: one session's ``tokens [1,
        n]`` padded to the launch shape."""
        n = tokens.shape[1]
        width = self._token_bucket(n)
        return {
            "tokens": np.concatenate([tokens, np.zeros((1, width - n), np.int32)], axis=1),
            "slots": slots, "positions": positions,
            "lengths": np.full(1, n, np.int32),
        }

    def _open_blocks(self, request, tokens, named):
        """:meth:`open` for a model that generates by blocks: an extend
        of whole blocks (one session), or the block requests of
        ``len(named)`` sessions (``tokens [S, block]`` beside ``commit``:
        a row that commits moves its session's length by a block, a
        denoising row moves nothing, so a launch that fails has nothing
        of it to take back)."""
        (b, n), block = tokens.shape, self._block
        writes = request.inputs.get(self.COMMIT)
        if writes is None:
            if len(named) != 1 or b != 1 or n % block or not block <= n <= self.max_tokens:
                raise ValueError(
                    f"an extend of this model appends whole blocks of {block} tokens (attention is "
                    f"bidirectional inside a block): tokens [1, n], n a multiple of {block} up to "
                    f"{self.max_tokens}; got {tokens.shape} for {len(named)} session(s). A block "
                    f"request carries tokens [1, {block}] beside commit [1, 1]"
                )
            ticket = TokenLaunch("lm_prefill", [], n, 1, answers=1)
        else:
            writes = np.asarray(writes).reshape(-1) != 0
            if len(named) != b or len(writes) != b or n != block:
                raise ValueError(
                    f"a block request carries tokens [1, {block}] and commit [1, 1]; "
                    f"got {tokens.shape} and {len(writes)} flag(s) for {len(named)} session(s)"
                )
            ticket = TokenLaunch(
                "lm_block", [], b * n, b, answers=b * n, commit_rows=int(writes.sum())
            )
        slots = np.zeros(b, np.int32)
        positions = np.zeros(b, np.int32)
        try:
            for i, (stream_id, start, end) in enumerate(named):
                slots[i], positions[i] = self._admit(
                    ticket, stream_id, start, end, n, write=writes is None or bool(writes[i]),
                )
        except Exception:
            self.close(ticket, None, failed=True)
            raise
        if writes is None:
            launch = self._extend_arrays(tokens, slots, positions)
        else:
            pad = self._step_bucket(b) - b
            padded = lambda a: np.concatenate([a, np.zeros((pad, *a.shape[1:]), np.int32)])
            launch = {
                "tokens": padded(tokens), "slots": padded(slots), "positions": padded(positions),
                "lengths": padded(np.full(b, n, np.int32)),
                self.COMMIT: padded(writes.astype(np.int32)),
            }
        return dataclasses.replace(request, inputs=launch), ticket

    def _admit(self, ticket: TokenLaunch, stream_id: str, start: bool, end: bool, n: int,
               write: bool = True):
        """One row's slot index and start position; the stream's length
        moves on at once (rolled back if the launch fails). A row of a
        model that generates by blocks stands at a block's boundary and
        its ``n`` tokens all see each other; ``write`` False: a denoising
        pass of such a model, the length stays."""
        now = self._pool.time()
        with self._turn:
            if not stream_id:
                # no sequence_id: a session of this one request
                self._oneshots += 1
                stream_id, start, end = f"__oneshot__{self._oneshots}", True, True
            deadline = time.monotonic() + self._turn_timeout_s
            while True:
                slot = self._pool.slots.get(stream_id)
                if slot is None or slot.refs == 0:
                    break
                left = deadline - time.monotonic()
                if left <= 0:
                    raise SessionLimitError(
                        f"stream '{stream_id}': an earlier request is still in flight"
                    )
                self._turn.wait(timeout=left)
            restarted = False
            if slot is None:
                if not start:
                    self._counters["unknown_total"] += 1
                    raise SessionLimitError(
                        self._lost.get(stream_id) or
                        f"stream '{stream_id}' holds no cache slot here (never "
                        "started, ended, or reclaimed): send sequence_start"
                    )
                if self._lost:
                    self._lost.pop(stream_id, None)
                self._pool.make_room_locked(now)
                slot = _Slot(stream_id=stream_id, state=self._free.pop(), created=now)
                self._pool.slots[stream_id] = slot
                self._counters["created_total"] += 1
                restarted = True
            elif start or slot.ended:
                slot.length, slot.ended, restarted = 0, False, True
            if slot.length + n > self.slot_len:
                self._counters["outgrown_total"] += 1
                if restarted:
                    self._pool.free_locked(slot)
                raise SessionLimitError(
                    f"stream '{stream_id}': {slot.length} + {n} tokens outgrow "
                    f"its cache slot of {self.slot_len} positions"
                )
            position = slot.length
            if self._block and position % self._block:
                raise SessionLimitError(
                    f"stream '{stream_id}': its {position} cached positions are no whole number "
                    f"of blocks of {self._block}: a block is appended or denoised at a block's boundary"
                )
            moved = n if write else 0
            slot.length += moved
            slot.refs += 1
            slot.last_used = now
            ticket.rows.append((stream_id, moved, restarted, end))
            ticket.context += position
            if self._state_bytes:
                if position == 0:  # the launch reads a zero state for this row, whatever the slot holds
                    self._counters["lm_state_resets"] += 1
                elif ticket.kind == "lm_prefill":  # a further turn: the chunkwise form starts from the slot's state
                    self._counters["lm_state_carries"] += 1
            if self._block:
                # a position may attend to every position up to the end of its own block, in every layer
                ends = (np.arange(position, position + n, dtype=np.int64) // self._block + 1) * self._block
                visible = self._layers * int(ends.sum())
                ticket.keys_visible += visible
                ticket.keys_selected += visible
                return slot.state, position
            # a token at position p may attend to p + 1 positions, in every layer
            visible = np.arange(position + 1, position + n + 1, dtype=np.int64)
            ticket.keys_visible += self._layers * int(visible.sum())
            ticket.keys_selected += self._layers * int(
                np.minimum(visible, self._index_topk).sum() if self._index_topk else visible.sum()
            )
            for g in self._geometries:
                ticket.keys_read += g.layers * int((np.minimum(visible, g.window) if g.window else visible).sum())
            return slot.state, position

    def advance(self, ticket: TokenLaunch, outputs):
        """The launch itself extended the cache: nothing more runs on the
        device. (The pad rows of the logits are cut on the host, in
        :meth:`close`: a slice on the device is one more program for
        every number of sessions a launch can carry, each compiled the
        first time a launch of that many comes by.)"""
        return outputs

    def abort(self, ticket: TokenLaunch) -> None:
        """The launch was refused before it reached the device: lengths
        (and a model's recurrent state) are as they were."""
        self.close(ticket, None, failed=True)

    def close(self, ticket: TokenLaunch, host_outputs=None, failed: bool = False) -> None:
        """The launch resolved: drop its rows' references, free the
        slots of streams that ended, count. A failed launch takes its
        rows' lengths back (a restarted stream is freed). Where the model
        holds a recurrent state and the launch failed AFTER dispatch (no
        outputs, and not :meth:`abort`), the state of its rows is
        overwritten and no length takes that back: its sessions end."""
        lost = self._state_bytes and host_outputs is None and not failed
        failed = failed or host_outputs is None
        with self._turn:
            for stream_id, n, restarted, end in ticket.rows:
                slot = self._pool.slots.get(stream_id)
                if slot is None:
                    continue
                slot.refs = max(0, slot.refs - 1)
                if failed:
                    slot.length -= n
                    end = end or restarted
                    if lost and not end:
                        end = True
                        self._counters["lm_state_lost"] += 1
                        while len(self._lost) >= 4 * self._pool.max:  # streams that never asked again
                            self._lost.pop(next(iter(self._lost)))
                        self._lost[stream_id] = (
                            f"stream '{stream_id}': its recurrent state was lost in a launch that failed after "
                            f"dispatch (the state of its {slot.length} cached positions is overwritten and "
                            "cannot be taken back): the session has ended, send sequence_start"
                        )
                if end:
                    slot.ended = True
                    if not failed:
                        self._counters["ended_total"] += 1
                if slot.ended and slot.refs == 0:
                    self._pool.free_locked(slot)
            ticket.rows = []
            if not failed and ticket.tokens:
                self._counters["lm_keys_visible"] += ticket.keys_visible
                self._counters["lm_keys_selected"] += ticket.keys_selected
                self._counters["lm_keys_read"] += ticket.keys_read
                if ticket.kind == "lm_step":
                    self._counters["lm_step_keys_fetched"] += ticket.step_keys_fetched
                    self._counters["lm_step_keys_whole"] += ticket.step_keys_whole
                    self._counters["lm_tokens_step"] += ticket.tokens
                    self._counters["lm_step_launches"] += 1
                    self._counters["lm_step_sessions"] += ticket.tokens
                elif ticket.kind == "lm_block":
                    self._counters["lm_block_launches"] += 1
                    self._counters["lm_block_rows"] += ticket.sessions
                    self._counters["lm_block_commit_rows"] += ticket.commit_rows
                    self._counters["lm_tokens_committed"] += ticket.commit_rows * self._block
                else:
                    self._counters["lm_tokens_prefill"] += ticket.tokens
                    self._counters["lm_prefill_launches"] += 1
                    self._counters["lm_context_prefill"] += ticket.context
            self._turn.notify_all()
        if host_outputs is not None:
            rows = host_outputs.pop(self.EXPERT_ROWS, None)
            if ticket.answers:  # the real rows; the launch's pad rows end here
                for k, v in host_outputs.items():
                    host_outputs[k] = v[: ticket.answers]
            if rows is not None and ticket.tokens:
                with self._turn:
                    total = np.asarray(rows, np.int64)
                    self._expert_rows = (
                        total if self._expert_rows is None else self._expert_rows + total
                    )
                    if ticket.kind == "lm_step":
                        # the held experts some row of a step launch chose: all that a program which
                        # hands ops/experts.py the layers' stacks reads of them (models/ling.py)
                        self._counters["lm_step_experts_chosen"] += int(np.count_nonzero(total))
                        self._counters["lm_step_experts_held"] += total.size

    def end(self, stream_id: str) -> None:
        """Explicitly end a session (server drain, client abort)."""
        with self._turn:
            slot = self._pool.slots.get(stream_id)
            if slot is not None:
                slot.ended = True
                if slot.refs == 0:
                    self._pool.free_locked(slot)

    def stats(self) -> dict:
        with self._turn:
            slots = list(self._pool.slots.values())
            by_geometry = {
                g.name: {"allocated": self._pool.max * g.held(g.rows), "in_use": sum(g.held(s.length) for s in slots)}
                for g in self._geometries
            }
            return {
                **self._counters,
                # rows by geometry, in bytes: what is allocated and what live sessions hold of it
                "session_cache_bytes": sum(g["allocated"] for g in by_geometry.values()),
                "session_cache_bytes_in_use": sum(g["in_use"] for g in by_geometry.values()),
                "session_cache_bytes_by_geometry": by_geometry,
                "session_cache_slots": self._pool.max,
                "session_cache_slot_len": self.slot_len,
                "session_cache_slots_in_use": len(slots),
                "session_cache_tokens": sum(s.length for s in slots),
                "session_state_bytes": self._state_bytes * len(slots),
                "session_index_cache_bytes": self._index_cache_bytes,
                "expired_total": self._pool.expired,
                "reclaimed_total": self._pool.reclaimed,
                "rejected_total": self._pool.rejected,
                "expert_rows": (
                    self._expert_rows.tolist() if self._expert_rows is not None else []
                ),
            }
