"""TPL3xx — host synchronization on the serving hot path.

JAX dispatch is asynchronous: the whole overlapped-serving design
(stage → launch → lazy readback, PR 1) only works because nothing on
the request path forces the host to wait for the device. One stray
``np.asarray``/``.item()``/``float()`` on a device value serializes the
pipeline back to pre-overlap behavior — and profiling shows it as
"device time" because the wait happens inside the span. The rule walks
the package call graph from the serving roots and flags every
host-sync call in a reachable function:

  TPL301  blocking readback (``np.asarray``/``np.array``/
          ``jax.device_get``/``.item()``/``.tolist()``/``float()``/
          ``int()`` over a non-literal) in a hot-path function
  TPL302  explicit device fence (``block_until_ready``) in a hot-path
          function

Some syncs are the *point* (the readback in ``resolve()``, the trace's
execute/readback split): those stay, with a one-line justification in
``tpulint.baseline.json`` — the rule's job is making every sync an
explicit, reviewed decision rather than an accident.

Roots (suffix-matched against dotted qualnames) default to
:data:`HOT_PATH_ROOTS`; ``perf/_harness.py`` reuses this rule with a
single callable as the root set to vet timed regions.
"""

from __future__ import annotations

import ast
from typing import Iterable, Iterator

from triton_client_tpu.analysis.engine import (
    Finding,
    Package,
    Rule,
    call_name,
    register,
)

#: The serving hot path: the shared StagedChannel engine (stage/launch
#: and the nested ``resolve`` readback closure) plus each subclass's
#: placement/launcher/readback hooks — the call graph resolves
#: ``self._place_inputs()`` to the base-class stub only, so overrides
#: must be roots themselves — the batcher's dispatch/merge/execute
#: machinery, and the gRPC servicer's issue path.
HOT_PATH_ROOTS = (
    "StagedChannel.stage",
    "StagedChannel.launch",
    "StagedChannel.do_inference",
    "StagedChannel.do_inference_async",
    # stage/launch live on StagedChannel since the round-7 factoring,
    # but a subclass-qualified definition (out-of-tree channels, doc
    # examples, test fixtures) is just as hot — keep the historical
    # names rooted too (suffix patterns that match nothing are inert)
    "TPUChannel.stage",
    "TPUChannel.launch",
    "TPUChannel.do_inference",
    "TPUChannel.do_inference_async",
    "TPUChannel._place_inputs",
    "TPUChannel._make_launcher",
    "ShardedTPUChannel._place_inputs",
    "ShardedTPUChannel._make_launcher",
    "ShardedTPUChannel._host_outputs",
    "_Servicer._issue",
    # round-12 overload control: the admission gate and breaker check
    # run per request inside _issue/launch, but live on foreign objects
    # the call graph cannot follow through `self._admission.admit(...)`
    # — root them explicitly so a host sync there is still a finding
    "AdmissionController.admit",
    "AdmissionController.finished",
    "CircuitBreaker.allow",
    "CircuitBreaker.record_success",
    # the batcher: admission runs per request, dispatch and every kind
    # of group per formed batch, and the segment-pack placement/launcher
    # hooks are the ragged equivalents of _place_inputs/_make_launcher
    # — all hot
    "ContinuousBatchingChannel.do_inference",
    "ContinuousBatchingChannel._dispatch_once",
    "ContinuousBatchingChannel._form_group_locked",
    "ContinuousBatchingChannel._run_group",
    "ContinuousBatchingChannel._run_passthrough",
    "ContinuousBatchingChannel._run_dense_merge",
    "ContinuousBatchingChannel._run_session_steps",
    "ContinuousBatchingChannel._run_ragged_group",
    "ContinuousBatchingChannel._run_solo",
    "ContinuousBatchingChannel._pad_target",
    "StagedChannel._place_ragged",
    "StagedChannel._ragged_launcher",
    "StagedChannel._make_ragged_launcher",
    "ShardedTPUChannel._place_ragged",
    "ShardedTPUChannel._make_ragged_launcher",
    # ISSUE 9 multi-tenant lifecycle: acquire/release run per request
    # (RPC thread and stage), note_cost inside the launcher build, and
    # the DRR key/charge run under _ready_cv on every insort/group —
    # a host sync in any of them stalls every tenant at once
    "ModelLifecycleManager.acquire",
    "ModelLifecycleManager.release",
    "ModelLifecycleManager.note_cost",
    "ContinuousBatchingChannel._edf_key",
    "ContinuousBatchingChannel._charge_tenants_locked",
    # ISSUE 10 replicated front door: the router's pick/record/accounting
    # run per request (and per retry/hedge) on the caller's thread; a
    # host sync in any of them stalls every request through the fleet
    "FrontDoorRouter.do_inference",
    "FrontDoorRouter._launch",
    "ReplicaSet.pick",
    "ReplicaSet.release",
    "ReplicaSet.record_success",
    "ReplicaSet.record_failure",
    "RetryBudget.deposit",
    "RetryBudget.try_spend",
    # ISSUE 11 fleet tracing + device-time attribution: context
    # encode/decode run per traced request on the RPC thread, the
    # ledger accumulate runs inside the launch-resolve closure right
    # after the deliberate device fence, and the router's routing core
    # (attempt spans, summary grafting) runs on the caller's thread —
    # a host sync in any of them taxes EVERY traced request
    "TraceContext.encode",
    "TraceContext.decode",
    "DeviceTimeLedger.record",
    "FrontDoorRouter._route",
    "FrontDoorRouter._attempt_span",
    # ISSUE 14 kernel attribution: the sampler's capture tick and the
    # collector's sink run on the telemetry cadence but inside the
    # process serving traffic (and the tick holds the /profile guard);
    # the history tick runs on a timer diffing ledger snapshots under
    # the collector lock — a host sync in any of them turns background
    # observability into a serving stall. The launch-cost capture runs
    # once per model on the first-launch path itself.
    "ContinuousSampler.sample_once",
    "MetricHistory.tick",
    "RuntimeCollector.record_op_sample",
    "StagedChannel._ensure_launch_cost",
    # ISSUE 15 streaming sessions: advance/_step run per session frame
    # between stage and launch (the tracker's jit dispatch must stay
    # async — a host read there serializes every stream), release runs
    # inside the resolve closure, end on the RPC thread, and the
    # router's rendezvous pick on every stateful request. The
    # association core is rooted directly so a host sync inside the
    # device variant of greedy_assign can never hide behind the jit
    # boundary.
    "SessionManager.advance",
    "SessionManager._step",
    "SessionManager.release",
    "SessionManager.end",
    "ReplicaSet.pick_affinity",
    "tracking.greedy_assign",
    # ISSUE 16 fused Pallas kernels: the fused launch seams run inside
    # jit traces on the request path (pipelines route into them at
    # trace time), but rooting them directly means a host sync added to
    # a kernel wrapper — a debug `np.asarray` on a ref, a stray
    # `.item()` on a shape probe — is a finding even before any
    # pipeline test exercises the fused route
    "pallas_decode.fused_decode_nms_2d",
    "pallas_decode.fused_residual_decode",
    "pallas_decode.fused_suppress_pack_3d",
    "pallas_voxel.fused_mean_volume",
    "pallas_voxel.sorted_segment_mean_pallas",
    # ISSUE 17 continuous quality plane: the sampler/mirror seams run
    # per request on the RPC thread (server) or caller thread (router)
    # — route before dispatch, observe after the readback, enqueue is
    # the queue hand-off. They live on foreign objects the call graph
    # cannot follow through `self._quality.route(...)`, so each is
    # rooted directly; all numpy scoring must stay on the mirror's
    # worker thread, never in these.
    "QualityPlane.route",
    "QualityPlane.observe",
    "CanaryController.route",
    "ShadowMirror.enqueue",
    "shadow.sample_decision",
    "shadow.slice_decision",
    "FrontDoorRouter._observe_quality",
    # ISSUE 19 temporal compute reuse: dispatch runs per session frame
    # in _Servicer._issue BEFORE the channel (a host sync there taxes
    # every streaming request, keyframe or not); observe runs per frame
    # post-readback on the reply thread; the coast path's session step
    # must stay one async jit dispatch — a host read inside
    # SessionManager.coast or the plane's tile-selection path would
    # serialize every stream the way a sync in advance/_step would.
    "TemporalReusePlane.dispatch",
    "TemporalReusePlane.observe",
    "TemporalReusePlane._try_partial",
    "SessionManager.coast",
    "MultiCameraDriver._suppress",
)

# module-level call targets that force a host sync
_SYNC_CALLS = {
    "np.asarray": "blocking device->host readback",
    "np.array": "blocking device->host readback",
    "numpy.asarray": "blocking device->host readback",
    "numpy.array": "blocking device->host readback",
    "jax.device_get": "blocking device->host readback",
    "jax.block_until_ready": "device fence",
}
# zero-ambiguity method syncs on array-likes
_SYNC_METHODS = {
    "item": "scalar readback",
    "tolist": "full-array readback",
    "block_until_ready": "device fence",
}
# float() is the classic accidental fence (`float(loss)` in a hot
# loop); int()/bool() are overwhelmingly host-side shape/flag math in
# this codebase, so only float() is flagged.
_SCALAR_CASTS = {"float"}


def _sync_calls_in(fn: ast.AST) -> Iterator[tuple[ast.Call, str, str]]:
    """(call, code, description) for host-sync calls lexically inside
    ``fn`` but NOT inside a nested def (nested defs are their own call
    graph nodes and get scanned under their own qualname)."""

    def walk(node: ast.AST, top: bool) -> Iterator[tuple[ast.Call, str, str]]:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                continue
            if isinstance(child, ast.Call):
                name = call_name(child)
                if name in _SYNC_CALLS:
                    code = (
                        "TPL302"
                        if "block_until_ready" in name
                        else "TPL301"
                    )
                    yield child, code, f"`{name}` ({_SYNC_CALLS[name]})"
                elif (
                    isinstance(child.func, ast.Attribute)
                    and child.func.attr in _SYNC_METHODS
                ):
                    code = (
                        "TPL302"
                        if child.func.attr == "block_until_ready"
                        else "TPL301"
                    )
                    yield (
                        child,
                        code,
                        f"`.{child.func.attr}()` "
                        f"({_SYNC_METHODS[child.func.attr]})",
                    )
                elif (
                    name in _SCALAR_CASTS
                    and child.args
                    and not isinstance(child.args[0], ast.Constant)
                    and not (
                        isinstance(child.args[0], ast.Call)
                        and call_name(child.args[0])
                        in ("len", "round", "perf_counter", "time.perf_counter")
                    )
                ):
                    yield (
                        child,
                        "TPL301",
                        f"`{name}()` over a non-literal (scalar readback "
                        "if the value is on device)",
                    )
            yield from walk(child, top)

    yield from walk(fn, True)


@register
class HostSyncRule(Rule):
    code = "TPL301"
    name = "hot-path-host-sync"
    doc = (
        "A blocking device->host readback (`np.asarray`, `.item()`, "
        "`float()`, ...) sits in a function reachable from the serving "
        "hot path; it serializes the overlapped pipeline. Move it to "
        "the deferred-readback side or baseline it with a justification."
    )

    roots: tuple[str, ...] = HOT_PATH_ROOTS

    def check(self, package: Package) -> Iterator[Finding]:
        yield from check_reachable(package, self.roots)


def check_reachable(
    package: Package, roots: Iterable[str]
) -> Iterator[Finding]:
    """Shared worker: flag sync calls in every function reachable from
    ``roots``. Used by the registry rule and by perf/_harness.py's
    timed-region assertion."""
    graph = package.callgraph
    hot = graph.reachable(roots)
    rule = HostSyncRule()
    for qn in sorted(hot):
        info = graph.functions.get(qn)
        if info is None:
            continue
        for call, code, desc in _sync_calls_in(info.node):
            yield rule.finding(
                info.module,
                call,
                f"{desc} on the hot path (reachable from serving roots)",
                context=_short_context(qn),
                code=code,
            )


def _short_context(qualname: str) -> str:
    """Drop the module-path prefix: keep Class.method / func.nested."""
    parts = qualname.split(".")
    # heuristics: module path components are lowercase_with_underscores
    # file names; keep from the first CamelCase part or the last two
    for i, p in enumerate(parts):
        if p[:1].isupper():
            return ".".join(parts[i:])
    return ".".join(parts[-2:]) if len(parts) > 1 else qualname
