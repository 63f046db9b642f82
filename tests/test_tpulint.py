"""tpulint (triton_client_tpu.analysis): fixture-proven rule behavior.

Per rule family: at least one true-positive fixture, one true-negative
fixture, and a pragma-suppressed case; plus engine-level tests (JSON
schema, baseline round-trip/matching, call-graph reachability) and the
whole-package gate — the same invocation ci.sh runs — asserting the
tree lints clean against the committed baseline. Everything here is
pure-stdlib AST work: CPU-only, tier-1 safe, no jax import required.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from triton_client_tpu import analysis
from triton_client_tpu.analysis import Baseline, lint_source
from triton_client_tpu.analysis.engine import load_source
from triton_client_tpu.analysis.rules.hostsync import check_reachable

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "triton_client_tpu")
BASELINE = os.path.join(REPO, "tpulint.baseline.json")


def codes(findings):
    return sorted({f.code for f in findings})


# -- TPL1xx recompilation ---------------------------------------------------


class TestRecompileRules:
    def test_traced_branch_positive(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    if x > 0:\n"
            "        return x\n"
            "    return -x\n"
        )
        found = lint_source(src, codes=["TPL101"])
        assert len(found) == 1 and found[0].code == "TPL101"
        assert "`x`" in found[0].message

    def test_device_fn_counts_as_jitted(self):
        src = (
            "def device_fn(inputs):\n"
            "    for row in inputs:\n"
            "        pass\n"
        )
        assert codes(lint_source(src, codes=["TPL1"])) == ["TPL101"]

    def test_shape_branch_negative(self):
        # .shape/.ndim/len() are static at trace time — must NOT flag
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    if x.shape[0] > 2 and x.ndim == 2 and len(x) > 1:\n"
            "        return x\n"
            "    return x + 1\n"
        )
        assert lint_source(src, codes=["TPL1"]) == []

    def test_static_arg_is_not_traced(self):
        src = (
            "import jax\n"
            "@jax.jit(static_argnums=(1,))\n"
            "def f(x, n):\n"
            "    if n > 0:\n"
            "        return x\n"
            "    return -x\n"
        )
        assert lint_source(src, codes=["TPL101"]) == []

    def test_static_policy_param_is_exempt(self):
        # round 10: a precision policy (runtime/precision.py) threaded
        # through a jitted body is static python config, not a tracer —
        # dtype-dispatching on `policy.name` compiles one executable
        # per policy by design and must NOT flag
        src = (
            "def device_fn(inputs, policy):\n"
            "    if policy.name == 'bf16':\n"
            "        return {k: v * 2 for k, v in inputs.items()}\n"
            "    for key in policy.act_scales:\n"
            "        pass\n"
            "    return inputs\n"
        )
        assert lint_source(src, codes=["TPL1"]) == []

    def test_static_policy_suffix_convention(self):
        # *_policy / *_precision / precision all ride the convention;
        # an f-string over the policy name is fine too (TPL103)
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x, wire_policy, precision):\n"
            "    label = f'{wire_policy}/{precision}'\n"
            "    if precision == 'int8':\n"
            "        return x\n"
            "    return x + 1\n"
        )
        assert lint_source(src, codes=["TPL1"]) == []

    def test_ordinary_param_still_flags_beside_policy(self):
        # the exemption is name-scoped: a traced param in the same
        # signature still flags
        src = (
            "def device_fn(inputs, policy):\n"
            "    if inputs > 0:\n"
            "        return inputs\n"
            "    return -inputs\n"
        )
        found = lint_source(src, codes=["TPL101"])
        assert len(found) == 1 and "`inputs`" in found[0].message

    def test_policy_substring_is_not_exempt(self):
        # only the exact name or `_`-suffixed convention is static:
        # `policyx` is an ordinary traced param
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(policyx):\n"
            "    if policyx > 0:\n"
            "        return policyx\n"
            "    return -policyx\n"
        )
        assert codes(lint_source(src, codes=["TPL101"])) == ["TPL101"]

    def test_static_argnums_list_positive(self):
        src = "import jax\ng = jax.jit(lambda x, n: x, static_argnums=[1])\n"
        found = lint_source(src, codes=["TPL102"])
        assert len(found) == 1 and "tuple" in found[0].message

    def test_static_argnums_tuple_negative(self):
        src = "import jax\ng = jax.jit(lambda x, n: x, static_argnums=(1,))\n"
        assert lint_source(src, codes=["TPL102"]) == []

    def test_fstring_leak_positive(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    name = f'value={x}'\n"
            "    return x\n"
        )
        assert codes(lint_source(src, codes=["TPL103"])) == ["TPL103"]

    def test_fstring_of_shape_negative(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    name = f'shape={x.shape}'\n"
            "    return x\n"
        )
        assert lint_source(src, codes=["TPL103"]) == []

    def test_pragma_suppresses(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    if x > 0:  # tpulint: disable=TPL101\n"
            "        return x\n"
            "    return -x\n"
        )
        assert lint_source(src, codes=["TPL101"]) == []


# -- TPL2xx donation --------------------------------------------------------


DONATION_POSITIVE = (
    "import jax\n"
    "launcher = jax.jit(lambda a, b: a, donate_argnums=(0,))\n"
    "def go(x, y):\n"
    "    out = launcher(x, y)\n"
    "    return out, x.shape\n"  # x read after donation
)

DONATION_NEGATIVE = (
    "import jax\n"
    "launcher = jax.jit(lambda a, b: a, donate_argnums=(0,))\n"
    "def go(x, y):\n"
    "    out = launcher(x, y)\n"
    "    return out, y.shape\n"  # only the kept arg is re-read
)


class TestDonationRules:
    def test_read_after_donation_positive(self):
        found = lint_source(DONATION_POSITIVE, codes=["TPL201"])
        assert len(found) == 1
        assert "`x`" in found[0].message and found[0].context == "go"

    def test_kept_arg_read_negative(self):
        assert lint_source(DONATION_NEGATIVE, codes=["TPL201"]) == []

    def test_reassignment_clears_taint(self):
        src = (
            "import jax\n"
            "launcher = jax.jit(lambda a: a, donate_argnums=(0,))\n"
            "def go(x):\n"
            "    x = launcher(x)\n"
            "    return x + 1\n"
        )
        assert lint_source(src, codes=["TPL201"]) == []

    def test_donor_through_factory_unpack(self):
        # the TPUChannel shape: a same-module factory returns the
        # donating callable as the head of a tuple
        src = (
            "import jax\n"
            "def make():\n"
            "    launcher = jax.jit(lambda a: a, donate_argnums=(0,))\n"
            "    return launcher, 'meta'\n"
            "def go(x):\n"
            "    launcher, meta = make()\n"
            "    out = launcher(x)\n"
            "    return out + x\n"
        )
        found = lint_source(src, codes=["TPL201"])
        assert len(found) == 1 and "`x`" in found[0].message

    def test_donate_persistent_attribute_positive(self):
        src = (
            "import jax\n"
            "launcher = jax.jit(lambda a: a, donate_argnums=(0,))\n"
            "class C:\n"
            "    def go(self):\n"
            "        return launcher(self._buf)\n"
        )
        found = lint_source(src, codes=["TPL202"])
        assert len(found) == 1 and "self._buf" in found[0].message

    def test_donate_local_negative(self):
        src = (
            "import jax\n"
            "launcher = jax.jit(lambda a: a, donate_argnums=(0,))\n"
            "def go(x):\n"
            "    return launcher(x)\n"
        )
        assert lint_source(src, codes=["TPL202"]) == []

    def test_pragma_suppresses(self):
        src = DONATION_POSITIVE.replace(
            "    return out, x.shape\n",
            "    return out, x.shape  # tpulint: disable=TPL2\n",
        )
        assert lint_source(src, codes=["TPL2"]) == []


# -- TPL3xx host sync -------------------------------------------------------


HOT_SYNC = (
    "import numpy as np\n"
    "import jax\n"
    "class TPUChannel:\n"
    "    def stage(self, request):\n"
    "        return self._prep(request)\n"
    "    def _prep(self, request):\n"
    "        return np.asarray(request)\n"  # sync reachable from stage
    "def cold(x):\n"
    "    return np.asarray(x)\n"  # NOT reachable -> not flagged
)


class TestHostSyncRules:
    def test_reachable_sync_flagged_cold_not(self):
        found = lint_source(HOT_SYNC, codes=["TPL3"])
        assert len(found) == 1
        assert found[0].context == "TPUChannel._prep"

    def test_nested_closure_is_hot(self):
        src = (
            "class TPUChannel:\n"
            "    def launch(self, staged):\n"
            "        def resolve():\n"
            "            return staged.item()\n"
            "        return resolve\n"
        )
        found = lint_source(src, codes=["TPL3"])
        assert len(found) == 1 and ".item()" in found[0].message

    def test_block_until_ready_is_tpl302(self):
        src = (
            "import jax\n"
            "class TPUChannel:\n"
            "    def stage(self, x):\n"
            "        jax.block_until_ready(x)\n"
            "        return x\n"
        )
        assert codes(lint_source(src, codes=["TPL3"])) == ["TPL302"]

    def test_float_literal_negative(self):
        src = (
            "class TPUChannel:\n"
            "    def stage(self, x):\n"
            "        return float('1.5') + float(1)\n"
        )
        assert lint_source(src, codes=["TPL3"]) == []

    def test_pragma_suppresses(self):
        src = HOT_SYNC.replace(
            "        return np.asarray(request)\n",
            "        return np.asarray(request)  # tpulint: disable=TPL301\n",
        )
        assert lint_source(src, codes=["TPL3"]) == []

    def test_check_reachable_custom_roots(self):
        # the perf/_harness entry point: arbitrary roots, same rule
        src = "import numpy as np\ndef timed_region(x):\n    return np.asarray(x)\n"
        pkg = load_source(src, path="snippet.py")
        found = list(check_reachable(pkg, ["timed_region"]))
        assert len(found) == 1 and found[0].code == "TPL301"
        assert list(check_reachable(pkg, ["other_root"])) == []

    def test_continuous_dispatch_roots_are_hot(self):
        # ISSUE 8: the windowless scheduler's ragged dispatch is a root
        # — a sync in a helper it calls is a finding even though no
        # window/admission thread ever reaches it
        src = (
            "import numpy as np\n"
            "class ContinuousBatchingChannel:\n"
            "    def _run_ragged_group(self, group):\n"
            "        return _pack(group)\n"
            "def _pack(group):\n"
            "    return np.asarray(group)\n"
        )
        found = lint_source(src, codes=["TPL3"])
        assert len(found) == 1 and found[0].context.endswith("_pack")

    def test_segment_pack_placement_roots_are_hot(self):
        # the ragged placement/launcher hooks are the packed-batch
        # equivalents of _place_inputs/_make_launcher: a device fence
        # inside one is a finding
        src = (
            "import jax\n"
            "class StagedChannel:\n"
            "    def _place_ragged(self, model, request):\n"
            "        jax.block_until_ready(request)\n"
            "        return request\n"
            "class ShardedTPUChannel:\n"
            "    def _make_ragged_launcher(self, model, n):\n"
            "        jax.block_until_ready(model)\n"
            "        return model\n"
        )
        assert codes(lint_source(src, codes=["TPL3"])) == ["TPL302"]
        assert len(lint_source(src, codes=["TPL3"])) == 2

    def test_real_ragged_pack_path_reachable_from_roots(self):
        # the actual package: the segment-pack helpers the ragged
        # dispatch calls must sit in the reachable-from-hot-roots set
        from triton_client_tpu.analysis.rules.hostsync import HOT_PATH_ROOTS

        package = analysis.load_package([PKG], root=REPO)
        hot = package.callgraph.reachable(list(HOT_PATH_ROOTS))
        names = {q.rsplit(".", 1)[-1] for q in hot}
        assert "_run_ragged_group" in names
        assert "pack_rows" in names
        assert "shard_pack_rows" in names


class TestStreamingSessionLint:
    """ISSUE 15: the session frame bracket (advance/_step/release) and
    the association core are hot roots — a host sync anywhere in them
    would serialize every live stream at once."""

    def test_session_advance_root_is_hot(self):
        src = (
            "import numpy as np\n"
            "class SessionManager:\n"
            "    def advance(self, request, outputs):\n"
            "        return _snap(outputs)\n"
            "def _snap(outputs):\n"
            "    return np.asarray(outputs['detections'])\n"
        )
        found = lint_source(src, codes=["TPL3"])
        assert len(found) == 1 and found[0].context.endswith("_snap")

    def test_session_release_root_is_hot(self):
        # release runs inside the resolve closure: a scalar readback
        # there stalls the deferred-readback pipeline
        src = (
            "class SessionManager:\n"
            "    def release(self, stream_id):\n"
            "        return self._refs[stream_id].item()\n"
        )
        assert codes(lint_source(src, codes=["TPL3"])) == ["TPL301"]

    def test_affinity_pick_root_is_hot(self):
        src = (
            "import jax\n"
            "class ReplicaSet:\n"
            "    def pick_affinity(self, stream_id, exclude=()):\n"
            "        jax.block_until_ready(stream_id)\n"
            "        return None\n"
        )
        assert codes(lint_source(src, codes=["TPL3"])) == ["TPL302"]

    def test_association_core_is_hot(self):
        # tracking.greedy_assign is rooted DIRECTLY: a readback inside
        # the device association can't hide behind the jit boundary
        src = (
            "import numpy as np\n"
            "def greedy_assign(xp, cost, trips):\n"
            "    return float(cost[0, 0])\n"
        )
        pkg = load_source(src, path="triton_client_tpu/ops/tracking.py")
        found = list(check_reachable(pkg, ["tracking.greedy_assign"]))
        assert len(found) == 1 and found[0].code == "TPL301"

    def test_scrape_time_fold_negative(self):
        # stats()/_drain_folds is the DESIGNED device-read seam and is
        # not a hot root: a readback there is clean
        src = (
            "import numpy as np\n"
            "class SessionManager:\n"
            "    def stats(self):\n"
            "        return int(np.asarray(self._births))\n"
        )
        assert lint_source(src, codes=["TPL3"]) == []

    def test_real_session_path_reachable_from_roots(self):
        # the actual package: the whole frame bracket sits in the
        # reachable-from-hot-roots set
        from triton_client_tpu.analysis.rules.hostsync import (
            HOT_PATH_ROOTS,
        )

        package = analysis.load_package([PKG], root=REPO)
        hot = package.callgraph.reachable(list(HOT_PATH_ROOTS))
        names = {q.rsplit(".", 1)[-1] for q in hot}
        assert "advance" in names
        assert "greedy_assign" in names
        assert "pick_affinity" in names

    def test_session_pool_race_positive(self):
        # the frame bracket spans threads (advance on the request
        # thread, release on the readback executor — both DECLARED
        # roots): an unguarded slot-table mutation on either side is a
        # race
        src = (
            "import threading\n"
            "class SessionManager:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._slots = {}\n"
            "    def advance(self, request, outputs):\n"
            "        self._slots[request] = outputs\n"
            "    def release(self, stream_id):\n"
            "        with self._lock:\n"
            "            self._slots[stream_id] = None\n"
        )
        found = lint_source(src, codes=["TPL602"])
        assert len(found) == 1
        assert found[0].context == "SessionManager.advance"

    def test_session_pool_guarded_negative(self):
        src = (
            "import threading\n"
            "class SessionManager:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._slots = {}\n"
            "    def advance(self, request, outputs):\n"
            "        with self._lock:\n"
            "            self._slots[request] = outputs\n"
            "    def release(self, stream_id):\n"
            "        with self._lock:\n"
            "            self._slots[stream_id] = None\n"
        )
        assert lint_source(src, codes=["TPL602"]) == []


# -- TPL4xx lock discipline -------------------------------------------------


LOCK_POSITIVE = (
    "import threading\n"
    "class Slots:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self._active = 0\n"
    "    def acquire(self):\n"
    "        with self._lock:\n"
    "            self._active += 1\n"
    "    def release(self):\n"
    "        self._active -= 1\n"  # bare: races acquire()
)


class TestLockRules:
    def test_mixed_guard_positive(self):
        found = lint_source(LOCK_POSITIVE, codes=["TPL4"])
        assert len(found) == 1
        assert found[0].context == "Slots.release"
        assert "_active" in found[0].message

    def test_consistent_guard_negative(self):
        src = LOCK_POSITIVE.replace(
            "    def release(self):\n        self._active -= 1\n",
            "    def release(self):\n"
            "        with self._lock:\n"
            "            self._active -= 1\n",
        )
        assert lint_source(src, codes=["TPL4"]) == []

    def test_init_exempt(self):
        # the bare `self._active = 0` in __init__ must not count as an
        # unguarded site (object not shared during construction)
        src = LOCK_POSITIVE.replace(
            "    def release(self):\n        self._active -= 1\n", ""
        )
        assert lint_source(src, codes=["TPL4"]) == []

    def test_locked_suffix_convention_exempt(self):
        src = LOCK_POSITIVE.replace("def release(self):", "def release_locked(self):")
        assert lint_source(src, codes=["TPL4"]) == []

    def test_container_mutation_counts(self):
        src = (
            "import threading, collections\n"
            "class Q:\n"
            "    def __init__(self):\n"
            "        self._cv = threading.Condition()\n"
            "        self._ready = collections.deque()\n"
            "    def put(self, x):\n"
            "        with self._cv:\n"
            "            self._ready.append(x)\n"
            "    def steal(self, x):\n"
            "        self._ready.append(x)\n"
        )
        found = lint_source(src, codes=["TPL4"])
        assert len(found) == 1 and found[0].context == "Q.steal"

    def test_pragma_suppresses(self):
        src = LOCK_POSITIVE.replace(
            "        self._active -= 1\n",
            "        self._active -= 1  # tpulint: disable=TPL401\n",
        )
        assert lint_source(src, codes=["TPL4"]) == []


# -- TPL5xx telemetry -------------------------------------------------------


class TestTelemetryRules:
    def test_begin_without_end_positive(self):
        src = (
            "def issue(trace):\n"
            "    trace.begin('channel')\n"
            "    return 1\n"
        )
        found = lint_source(src, codes=["TPL501"])
        assert len(found) == 1 and "`channel`" in found[0].message

    def test_begin_with_end_negative(self):
        src = (
            "def issue(trace):\n"
            "    trace.begin('channel')\n"
            "def finish(trace):\n"
            "    trace.end('channel')\n"
        )
        assert lint_source(src, codes=["TPL501"]) == []

    def test_gauge_inc_no_finally_positive(self):
        src = (
            "def serve(g):\n"
            "    g.inc()\n"
            "    work()\n"
            "    g.dec()\n"  # not in a finally: leaks on exception
        )
        found = lint_source(src, codes=["TPL502"])
        assert len(found) == 1 and "finally" in found[0].message

    def test_gauge_dec_in_finally_negative(self):
        src = (
            "def serve(g):\n"
            "    g.inc()\n"
            "    try:\n"
            "        work()\n"
            "    finally:\n"
            "        g.dec()\n"
        )
        assert lint_source(src, codes=["TPL502"]) == []

    def test_gauge_dec_via_helper_called_in_finally(self):
        # the server.py shape: _account() holds the dec and is invoked
        # from a finally
        src = (
            "def serve(self):\n"
            "    self.request_started()\n"
            "    try:\n"
            "        work()\n"
            "    finally:\n"
            "        self._account()\n"
            "def _account(self):\n"
            "    self.request_finished()\n"
        )
        assert lint_source(src, codes=["TPL502"]) == []

    def test_slo_observe_outside_finally_positive(self):
        # the classic miss: score only on the happy path — exceptions
        # return unscored and the missed counter undercounts
        src = (
            "def issue(self, model, t0):\n"
            "    result = dispatch()\n"
            "    self._slo.observe_request(model, wall_s=now() - t0)\n"
            "    return result\n"
        )
        found = lint_source(src, codes=["TPL503"])
        assert len(found) == 1 and "finally" in found[0].message

    def test_slo_observe_in_finally_negative(self):
        src = (
            "def issue(self, model, t0):\n"
            "    try:\n"
            "        return dispatch()\n"
            "    finally:\n"
            "        self._slo.observe_request(model, wall_s=now() - t0)\n"
        )
        assert lint_source(src, codes=["TPL503"]) == []

    def test_slo_observe_via_helper_called_in_finally(self):
        # the server.py shape: _account() holds the observe and is
        # invoked from the finisher's finally
        src = (
            "def finish(self):\n"
            "    try:\n"
            "        return result()\n"
            "    finally:\n"
            "        self._account()\n"
            "def _account(self):\n"
            "    self._slo.observe_request('m', wall_s=1.0)\n"
        )
        assert lint_source(src, codes=["TPL503"]) == []

    def test_slo_observe_definer_module_skipped(self):
        # obs/slo.py defines observe_request; its own body is exempt
        src = (
            "class SLOTracker:\n"
            "    def observe_request(self, model, wall_s):\n"
            "        self.met += 1\n"
            "def helper(t):\n"
            "    t.observe_request('m', wall_s=1.0)\n"
        )
        assert lint_source(src, codes=["TPL503"]) == []

    def test_pragma_suppresses(self):
        src = (
            "def issue(trace):\n"
            "    trace.begin('x')  # tpulint: disable=TPL501\n"
        )
        assert lint_source(src, codes=["TPL501"]) == []


# -- engine / CLI / baseline ------------------------------------------------


class TestEngine:
    def test_file_pragma_disables_family(self):
        src = (
            "# tpulint: disable-file=TPL1\n"
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    if x > 0:\n"
            "        return x\n"
            "    return -x\n"
        )
        assert lint_source(src, codes=["TPL1"]) == []

    def test_registry_has_all_families(self):
        reg = analysis.registry()
        fams = {c[:4] for c in reg}
        assert {"TPL1", "TPL2", "TPL3", "TPL4", "TPL5"} <= fams
        for cls in reg.values():
            assert cls.doc, f"{cls.code} has no doc"

    def test_findings_sorted_and_fingerprint_stable(self):
        found = lint_source(DONATION_POSITIVE + LOCK_POSITIVE)
        assert found == sorted(
            found, key=lambda f: (f.path, f.line, f.col, f.code)
        )
        f = found[0]
        again = lint_source(DONATION_POSITIVE + LOCK_POSITIVE)[0]
        assert f.fingerprint() == again.fingerprint()

    def test_render_json_schema(self):
        found = lint_source(DONATION_POSITIVE)
        doc = json.loads(analysis.render_json(found, suppressed=3))
        assert doc["version"] == 1 and doc["tool"] == "tpulint"
        assert doc["summary"]["total"] == len(found)
        assert doc["summary"]["suppressed_by_baseline"] == 3
        for item in doc["findings"]:
            assert {
                "code", "name", "path", "line", "col", "message",
                "context", "fingerprint",
            } <= set(item)
        assert doc["summary"]["by_code"]
        assert isinstance(doc["errors"], list)


class TestBaseline:
    def test_round_trip_and_split(self, tmp_path):
        found = lint_source(DONATION_POSITIVE, path="fix.py")
        bl = Baseline.from_findings(found, justification="accepted: test")
        path = str(tmp_path / "bl.json")
        bl.save(path)
        loaded = Baseline.load(path)
        new, suppressed = loaded.split(found)
        assert new == [] and len(suppressed) == len(found)
        assert loaded.unjustified() == []

    def test_unjustified_detected(self):
        found = lint_source(DONATION_POSITIVE, path="fix.py")
        bl = Baseline.from_findings(found)  # default TODO justification
        assert bl.unjustified() == sorted(f.fingerprint() for f in found)

    def test_line_churn_keeps_match(self):
        # identical hazard shifted down two lines: same fingerprint
        a = lint_source(DONATION_POSITIVE, path="fix.py")
        b = lint_source("# pad\n# pad\n" + DONATION_POSITIVE, path="fix.py")
        assert [f.fingerprint() for f in a] == [f.fingerprint() for f in b]
        assert a[0].line != b[0].line

    def test_new_finding_not_suppressed(self, tmp_path):
        bl = Baseline.from_findings(
            lint_source(DONATION_POSITIVE, path="fix.py"), "ok"
        )
        other = lint_source(LOCK_POSITIVE, path="other.py")
        new, suppressed = bl.split(other)
        assert suppressed == [] and len(new) == len(other)


class TestCallGraph:
    def test_reachability_walks_methods_and_imports(self):
        pkg = load_source(
            "class TPUChannel:\n"
            "    def stage(self, r):\n"
            "        return helper(r)\n"
            "def helper(r):\n"
            "    return deeper(r)\n"
            "def deeper(r):\n"
            "    return r\n"
            "def unrelated(r):\n"
            "    return r\n",
            path="mod.py",
        )
        hot = pkg.callgraph.reachable(["TPUChannel.stage"])
        names = {q.rsplit(".", 1)[-1] for q in hot}
        assert {"stage", "helper", "deeper"} <= names
        assert "unrelated" not in names


# -- robustness paths (round 12: admission / breaker / shed) ----------------


class TestRobustnessPathCoverage:
    # the overload-control code (runtime/admission.py helpers called
    # from _Servicer._issue, breaker checks inside StagedChannel.launch,
    # shed scans inside the batcher's _run_group) must stay inside the
    # lint's hot-path and lock-discipline umbrellas — these fixtures
    # pin the rule behavior the real modules rely on.

    def test_issue_root_reaches_admission_helpers(self):
        # a host sync buried in an admission gate called from the
        # servicer issue path is hot: _Servicer._issue is a root and
        # the call graph walks into the helper
        src = (
            "import numpy as np\n"
            "class _Servicer:\n"
            "    def _issue(self, req):\n"
            "        self._admission.admit(req)\n"
            "        return _estimate_wait(req)\n"
            "def _estimate_wait(req):\n"
            "    return np.asarray(req.deadline)\n"
        )
        found = lint_source(src, codes=["TPL3"])
        assert len(found) == 1 and found[0].code == "TPL301"
        assert found[0].context.endswith("_estimate_wait")

    def test_launch_root_reaches_breaker_shed_scan(self):
        # per-member deadline scans at launch time must not sync the
        # host per element — .item() in a shed helper under
        # StagedChannel.launch is flagged
        src = (
            "class StagedChannel:\n"
            "    def launch(self, staged):\n"
            "        self._shed_expired_members(staged)\n"
            "    def _shed_expired_members(self, staged):\n"
            "        return [m.deadline.item() for m in staged]\n"
        )
        found = lint_source(src, codes=["TPL3"])
        assert len(found) == 1 and ".item()" in found[0].message

    def test_breaker_shaped_state_needs_lock(self):
        # CircuitBreaker's shape: failure counters + state enums
        # mutated from both the launch path and the probe path — a
        # bare mutation outside the lock is the classic torn
        # open/half-open transition
        src = (
            "import threading\n"
            "class CircuitBreaker:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._failures = 0\n"
            "    def record_failure(self):\n"
            "        with self._lock:\n"
            "            self._failures += 1\n"
            "    def record_success(self):\n"
            "        self._failures = 0\n"
        )
        found = lint_source(src, codes=["TPL4"])
        assert len(found) == 1
        assert found[0].context == "CircuitBreaker.record_success"

    def test_breaker_consistent_lock_negative(self):
        src = (
            "import threading\n"
            "class CircuitBreaker:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._failures = 0\n"
            "    def record_failure(self):\n"
            "        with self._lock:\n"
            "            self._failures += 1\n"
            "    def record_success(self):\n"
            "        with self._lock:\n"
            "            self._failures = 0\n"
        )
        assert lint_source(src, codes=["TPL4"]) == []

    def test_real_robustness_modules_reachable_from_roots(self):
        # the actual serving tree: admission + shed + breaker code must
        # sit inside the reachable-from-hot-roots set, so a future
        # host-sync regression there is a lint finding, not a tail spike
        from triton_client_tpu.analysis.rules.hostsync import HOT_PATH_ROOTS

        package = analysis.load_package([PKG], root=REPO)
        hot = package.callgraph.reachable(list(HOT_PATH_ROOTS))
        names = {q.rsplit(".", 1)[-1] for q in hot}
        assert "_shed_expired_members" in names
        assert "_record_launch_failure" in names
        assert "admit" in names


# -- whole-package gate (the same check ci.sh runs) -------------------------


class TestPackageGate:
    def test_package_lints_clean_against_baseline(self):
        package = analysis.load_package([PKG], root=REPO)
        assert not package.errors, package.errors
        findings = analysis.run_rules(package)
        bl = Baseline.load(BASELINE)
        new, suppressed = bl.split(findings)
        assert new == [], "un-baselined findings:\n" + "\n".join(
            f.render() for f in new
        )
        assert bl.unjustified() == []
        assert suppressed, "baseline should be exercised (stale otherwise)"

    def test_cli_json_and_exit_codes(self, tmp_path):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        ok = subprocess.run(
            [
                sys.executable, "-m", "triton_client_tpu", "lint",
                "triton_client_tpu", "--baseline", "tpulint.baseline.json",
                "--json",
            ],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
        )
        assert ok.returncode == 0, ok.stdout + ok.stderr
        doc = json.loads(ok.stdout)
        assert doc["summary"]["total"] == 0
        assert doc["summary"]["suppressed_by_baseline"] > 0
        # a known-bad snippet must fail with findings in the JSON
        bad = tmp_path / "bad.py"
        bad.write_text(LOCK_POSITIVE)
        fail = subprocess.run(
            [
                sys.executable, "-m", "triton_client_tpu", "lint",
                str(bad), "--json",
            ],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
        )
        assert fail.returncode == 1
        doc = json.loads(fail.stdout)
        assert doc["summary"]["total"] == 1
        assert doc["findings"][0]["code"] == "TPL401"


# -- TPL6xx whole-program concurrency (round 13) -----------------------------


RACE_POSITIVE = (
    "import threading\n"
    "class C:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self._cache = {}\n"
    "        threading.Thread(target=self._loop).start()\n"
    "    def _loop(self):\n"
    "        self._cache['k'] = 1\n"
    "    def do_inference(self, req):\n"
    "        self._cache['k'] = 2\n"
)

OPPOSITE_ORDER = (
    "import threading\n"
    "class C:\n"
    "    def __init__(self):\n"
    "        self._a = threading.Lock()\n"
    "        self._b = threading.Lock()\n"
    "    def one(self):\n"
    "        with self._a:\n"
    "            self._grab_b()\n"
    "    def _grab_b(self):\n"
    "        with self._b:\n"
    "            pass\n"
    "    def two(self):\n"
    "        with self._b:\n"
    "            with self._a:\n"
    "                pass\n"
)


class TestLockOrderRules:
    def test_interprocedural_cycle_positive(self):
        # one() holds _a when _grab_b() takes _b; two() nests the other
        # way — the cycle is only visible through the call edge
        found = lint_source(OPPOSITE_ORDER, codes=["TPL601"])
        assert found and all(f.code == "TPL601" for f in found)
        assert any("lock-order cycle" in f.message for f in found)

    def test_consistent_order_negative(self):
        src = OPPOSITE_ORDER.replace(
            "        with self._b:\n"
            "            with self._a:\n",
            "        with self._a:\n"
            "            with self._b:\n",
        )
        assert lint_source(src, codes=["TPL601"]) == []

    def test_self_deadlock_positive(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def outer(self):\n"
            "        with self._lock:\n"
            "            self._inner()\n"
            "    def _inner(self):\n"
            "        with self._lock:\n"
            "            pass\n"
        )
        found = lint_source(src, codes=["TPL601"])
        assert len(found) == 1 and "self-deadlock" in found[0].message
        assert found[0].context == "C._inner"

    def test_rlock_reacquire_negative(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "    def outer(self):\n"
            "        with self._lock:\n"
            "            self._inner()\n"
            "    def _inner(self):\n"
            "        with self._lock:\n"
            "            pass\n"
        )
        assert lint_source(src, codes=["TPL601"]) == []

    def test_pragma_suppresses(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def outer(self):\n"
            "        with self._lock:\n"
            "            self._inner()\n"
            "    def _inner(self):\n"
            "        with self._lock:  # tpulint: disable=TPL601\n"
            "            pass\n"
        )
        assert lint_source(src, codes=["TPL601"]) == []


class TestThreadEscapeRules:
    def test_two_root_race_positive(self):
        # `_cache` is written from a spawned thread AND the caller-side
        # do_inference entry point, with no lock on either side
        found = lint_source(RACE_POSITIVE, codes=["TPL602"])
        assert len(found) == 2
        assert {f.context for f in found} == {"C._loop", "C.do_inference"}
        assert all("thread roots" in f.message for f in found)

    def test_guarded_everywhere_negative(self):
        src = RACE_POSITIVE.replace(
            "    def _loop(self):\n"
            "        self._cache['k'] = 1\n",
            "    def _loop(self):\n"
            "        with self._lock:\n"
            "            self._cache['k'] = 1\n",
        ).replace(
            "    def do_inference(self, req):\n"
            "        self._cache['k'] = 2\n",
            "    def do_inference(self, req):\n"
            "        with self._lock:\n"
            "            self._cache['k'] = 2\n",
        )
        assert lint_source(src, codes=["TPL602"]) == []

    def test_single_root_negative(self):
        # only the spawned thread mutates; do_inference just reads
        src = RACE_POSITIVE.replace(
            "    def do_inference(self, req):\n"
            "        self._cache['k'] = 2\n",
            "    def do_inference(self, req):\n"
            "        return self._cache\n",
        )
        assert lint_source(src, codes=["TPL602"]) == []

    def test_class_without_locks_negative(self):
        # a class that never promised mutual exclusion is out of scope
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._cache = {}\n"
            "        threading.Thread(target=self._loop).start()\n"
            "    def _loop(self):\n"
            "        self._cache['k'] = 1\n"
            "    def do_inference(self, req):\n"
            "        self._cache['k'] = 2\n"
        )
        assert lint_source(src, codes=["TPL602"]) == []

    def test_locked_helper_convention_negative(self):
        # the mutation lives in a `*_locked` helper; every caller holds
        # the lock, so the entry-held fixpoint must clear it
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._cache = {}\n"
            "        threading.Thread(target=self._loop).start()\n"
            "    def _put_locked(self):\n"
            "        self._cache['k'] = 1\n"
            "    def _loop(self):\n"
            "        with self._lock:\n"
            "            self._put_locked()\n"
            "    def do_inference(self, req):\n"
            "        with self._lock:\n"
            "            self._put_locked()\n"
        )
        assert lint_source(src, codes=["TPL602"]) == []

    def test_pragma_line_suppresses_one_site(self):
        src = RACE_POSITIVE.replace(
            "        self._cache['k'] = 1\n",
            "        self._cache['k'] = 1  # tpulint: disable=TPL602\n",
        )
        found = lint_source(src, codes=["TPL602"])
        assert [f.context for f in found] == ["C.do_inference"]


class TestCheckThenActRules:
    CTA = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._spec = None\n"
        "    def fill(self, v):\n"
        "        with self._lock:\n"
        "            self._spec = v\n"
        "    def get(self, v):\n"
        "        if self._spec is None:\n"
        "            with self._lock:\n"
        "                self._spec = v\n"
        "        return self._spec\n"
    )

    def test_check_then_act_positive(self):
        found = lint_source(self.CTA, codes=["TPL603"])
        assert len(found) == 1
        assert found[0].context == "C.get"
        assert "check-then-act" in found[0].message

    def test_double_checked_negative(self):
        # re-checking under the lock is the sanctioned pattern
        src = self.CTA.replace(
            "            with self._lock:\n"
            "                self._spec = v\n",
            "            with self._lock:\n"
            "                if self._spec is None:\n"
            "                    self._spec = v\n",
        )
        assert lint_source(src, codes=["TPL603"]) == []

    def test_checked_under_lock_negative(self):
        src = self.CTA.replace(
            "    def get(self, v):\n"
            "        if self._spec is None:\n"
            "            with self._lock:\n"
            "                self._spec = v\n"
            "        return self._spec\n",
            "    def get(self, v):\n"
            "        with self._lock:\n"
            "            if self._spec is None:\n"
            "                self._spec = v\n"
            "        return self._spec\n",
        )
        assert lint_source(src, codes=["TPL603"]) == []

    def test_pragma_suppresses(self):
        src = self.CTA.replace(
            "            with self._lock:\n"
            "                self._spec = v\n",
            "            with self._lock:  # tpulint: disable=TPL603\n"
            "                self._spec = v\n",
        )
        assert lint_source(src, codes=["TPL603"]) == []


class TestThreadModel:
    def _model(self, src):
        return load_source(src, path="mod.py").threads

    def test_thread_root_discovery(self):
        src = (
            "import signal\n"
            "import threading\n"
            "def _handler(signum, frame):\n"
            "    pass\n"
            "def install():\n"
            "    signal.signal(15, _handler)\n"
            "class C:\n"
            "    def __init__(self, pool, fut):\n"
            "        threading.Thread(target=self._loop).start()\n"
            "        threading.Timer(0.1, self._tick)\n"
            "        pool.submit(self._work)\n"
            "        fut.add_done_callback(self._done)\n"
            "    def _loop(self):\n"
            "        pass\n"
            "    def _tick(self):\n"
            "        pass\n"
            "    def _work(self):\n"
            "        pass\n"
            "    def _done(self, fut):\n"
            "        pass\n"
        )
        model = self._model(src)
        kinds = {r.kind for r in model.roots}
        assert {
            "thread", "timer", "executor", "callback", "signal", "declared",
        } <= kinds
        pats = {r.pattern for r in model.roots}
        assert any(p.endswith("C._loop") for p in pats)
        assert any(p.endswith("C._tick") for p in pats)
        assert any(p.endswith("C._work") for p in pats)
        assert any(p.endswith("C._done") for p in pats)
        assert any(p.endswith("_handler") for p in pats)

    def test_declared_roots_always_present(self):
        model = self._model("def f():\n    pass\n")
        declared = {
            r.pattern for r in model.roots if r.kind == "declared"
        }
        assert {"_Servicer.*", "do_inference", "do_inference_async"} <= declared
        groups = {r.group for r in model.roots if r.kind == "declared"}
        # "executor" joined in PR 15: SessionManager.release is declared
        # on the readback-executor side of the frame bracket
        assert groups == {"rpc", "caller", "executor"}

    def test_held_lock_propagates_into_locked_helper(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def push(self):\n"
            "        with self._lock:\n"
            "            self._push_locked()\n"
            "    def _push_locked(self):\n"
            "        self._count = 1\n"
        )
        model = self._model(src)
        assert any(
            q.endswith("C._push_locked") and h == frozenset({"C._lock"})
            for q, h in model.entry_held.items()
        )
        (site,) = model.mutations[("C", "_count")]
        assert model.held_at(site) == frozenset({"C._lock"})

    def test_family_lock_unification_across_subclass(self):
        src = (
            "import threading\n"
            "class Base:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "class Sub(Base):\n"
            "    def bump(self):\n"
            "        with self._lock:\n"
            "            self._n = 1\n"
        )
        model = self._model(src)
        assert model.lock_id("Sub", "_lock") == "Base._lock"
        assert ("Base", "_n") in model.mutations

    def test_lock_order_edges_and_reentrancy(self):
        model = self._model(OPPOSITE_ORDER)
        edges = set(model.lock_order)
        assert ("C._a", "C._b") in edges and ("C._b", "C._a") in edges
        assert model.lock_cycles()
        assert not model.reentrant("C._a")
        rl = self._model(
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
        )
        assert rl.reentrant("C._lock")


# -- TPL7xx host-path zero-copy audit (round 13) -----------------------------


HOT_STAGE = (
    "import numpy as np\n"
    "class StagedChannel:\n"
    "    def stage(self, arr):\n"
)


class TestZeroCopyRules:
    def test_ascontiguousarray_positive(self):
        src = HOT_STAGE + "        return np.ascontiguousarray(arr)\n"
        found = lint_source(src, codes=["TPL7"])
        assert codes(found) == ["TPL701"]
        assert "hot path" in found[0].message

    def test_tobytes_positive(self):
        src = HOT_STAGE + "        return arr.tobytes()\n"
        assert codes(lint_source(src, codes=["TPL7"])) == ["TPL701"]

    def test_array_local_copy_positive(self):
        src = HOT_STAGE + (
            "        a = np.asarray(arr)\n"
            "        return a.copy()\n"
        )
        found = lint_source(src, codes=["TPL7"])
        assert len(found) == 1 and found[0].code == "TPL701"

    def test_dict_copy_negative(self):
        # .copy() on a plain dict is not an array copy — local
        # dataflow must keep the receiver out of the array set
        src = HOT_STAGE + (
            "        params = {}\n"
            "        q = params.copy()\n"
            "        return q\n"
        )
        assert lint_source(src, codes=["TPL7"]) == []

    def test_astype_unguarded_positive(self):
        src = HOT_STAGE + "        return arr.astype(np.float32)\n"
        assert codes(lint_source(src, codes=["TPL7"])) == ["TPL702"]

    def test_astype_dtype_guard_negative(self):
        src = HOT_STAGE + (
            "        if arr.dtype != np.float32:\n"
            "            arr = arr.astype(np.float32)\n"
            "        return arr\n"
        )
        assert lint_source(src, codes=["TPL7"]) == []

    def test_astype_copy_false_negative(self):
        src = HOT_STAGE + (
            "        return arr.astype(np.float32, copy=False)\n"
        )
        assert lint_source(src, codes=["TPL7"]) == []

    def test_frombuffer_materialized_positive(self):
        src = (
            "import numpy as np\n"
            "class StagedChannel:\n"
            "    def stage(self, raw):\n"
            "        return np.array(np.frombuffer(raw, dtype=np.uint8))\n"
        )
        found = lint_source(src, codes=["TPL7"])
        # the sharp TPL703 diagnosis subsumes the generic TPL701
        assert len(found) == 1 and found[0].code == "TPL703"

    def test_frombuffer_view_kept_negative(self):
        src = (
            "import numpy as np\n"
            "class StagedChannel:\n"
            "    def stage(self, raw):\n"
            "        return np.frombuffer(raw, dtype=np.uint8).reshape(2, 2)\n"
        )
        assert lint_source(src, codes=["TPL7"]) == []

    def test_per_element_loop_positive_no_double_report(self):
        src = (
            "import numpy as np\n"
            "class StagedChannel:\n"
            "    def stage(self, arrs):\n"
            "        out = []\n"
            "        for a in arrs:\n"
            "            out.append(a.tobytes())\n"
            "        return out\n"
        )
        found = lint_source(src, codes=["TPL7"])
        # the loop finding swallows the per-call .tobytes() finding
        assert len(found) == 1 and found[0].code == "TPL704"

    def test_cold_path_negative(self):
        src = (
            "import numpy as np\n"
            "def helper(arr):\n"
            "    return np.ascontiguousarray(arr)\n"
        )
        assert lint_source(src, codes=["TPL7"]) == []

    def test_pragma_suppresses(self):
        src = HOT_STAGE + (
            "        return arr.tobytes()  # tpulint: disable=TPL701\n"
        )
        assert lint_source(src, codes=["TPL7"]) == []


# -- SARIF + baseline maintenance + CLI flags (round 13) ---------------------


class TestSarifOutput:
    def test_render_sarif_schema(self):
        found = lint_source(LOCK_POSITIVE, path="fix.py")
        doc = json.loads(analysis.render_sarif(found, errors=["boom"]))
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
        assert {
            "TPL601", "TPL602", "TPL603",
            "TPL701", "TPL702", "TPL703", "TPL704",
        } <= rule_ids
        results = run["results"]
        assert results[0]["ruleId"] == "TPL401"
        assert (
            results[0]["partialFingerprints"]["tpulint/v1"]
            == found[0].fingerprint()
        )
        loc = results[0]["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == "fix.py"
        assert loc["region"]["startLine"] == found[0].line
        # analysis errors ride along as TPL000
        assert results[-1]["ruleId"] == "TPL000"
        assert "boom" in results[-1]["message"]["text"]


class TestBaselineMaintenance:
    def test_from_findings_preserves_prior_justifications(self):
        a = lint_source(DONATION_POSITIVE, path="fix.py")
        b = lint_source(LOCK_POSITIVE, path="other.py")
        prior = Baseline.from_findings(a, justification="reviewed: ok")
        prior.entries["deadbeefdeadbeef"] = {
            "code": "TPL999", "justification": "old",
        }
        merged = Baseline.from_findings(a + b, prior=prior)
        assert (
            merged.entries[a[0].fingerprint()]["justification"]
            == "reviewed: ok"
        )
        assert (
            merged.entries[b[0].fingerprint()]["justification"]
            == analysis.baseline.UNJUSTIFIED
        )
        assert "deadbeefdeadbeef" not in merged.entries

    def test_prune_drops_only_stale(self):
        a = lint_source(DONATION_POSITIVE, path="fix.py")
        bl = Baseline.from_findings(a, justification="ok")
        bl.entries["feedfacefeedface"] = {
            "code": "TPL101", "justification": "gone",
        }
        dropped = bl.prune(a)
        assert dropped == ["feedfacefeedface"]
        assert a[0].fingerprint() in bl.entries
        assert bl.entries[a[0].fingerprint()]["justification"] == "ok"


class TestLintCliFlags:
    def _run(self, args):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.run(
            [sys.executable, "-m", "triton_client_tpu", "lint", *args],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
        )

    def test_sarif_written_on_failure(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(LOCK_POSITIVE)
        out = tmp_path / "out.sarif"
        r = self._run([str(bad), "--sarif", str(out)])
        assert r.returncode == 1
        doc = json.loads(out.read_text())
        assert [x["ruleId"] for x in doc["runs"][0]["results"]] == ["TPL401"]

    def test_changed_scopes_report_to_given_files(self):
        r = self._run([
            "--changed", "triton_client_tpu/runtime/continuous.py",
            "--baseline", "tpulint.baseline.json", "--json",
        ])
        assert r.returncode == 0, r.stdout + r.stderr
        doc = json.loads(r.stdout)
        assert doc["summary"]["total"] == 0

    def test_changed_without_files_is_noop(self):
        r = self._run(["--changed"])
        assert r.returncode == 0
        assert "nothing to do" in r.stderr

    def test_write_baseline_preserves_and_prunes(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(LOCK_POSITIVE)
        bl = tmp_path / "bl.json"
        r1 = self._run([str(bad), "--write-baseline", str(bl)])
        assert r1.returncode == 0, r1.stdout + r1.stderr
        doc = json.loads(bl.read_text())
        (fp,) = doc["entries"]
        doc["entries"][fp]["justification"] = "reviewed: fixture"
        doc["entries"]["feedfacefeedface"] = {
            "code": "TPL999", "justification": "stale",
        }
        bl.write_text(json.dumps(doc))
        r2 = self._run([str(bad), "--write-baseline", str(bl)])
        assert "1 justification(s) preserved" in r2.stderr
        doc2 = json.loads(bl.read_text())
        assert doc2["entries"][fp]["justification"] == "reviewed: fixture"
        assert "feedfacefeedface" not in doc2["entries"]

    def test_prune_stale_rewrites_baseline(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(LOCK_POSITIVE)
        bl = tmp_path / "bl.json"
        self._run([str(bad), "--write-baseline", str(bl)])
        doc = json.loads(bl.read_text())
        (fp,) = doc["entries"]
        doc["entries"][fp]["justification"] = "reviewed: fixture"
        doc["entries"]["feedfacefeedface"] = {
            "code": "TPL999", "justification": "stale",
        }
        bl.write_text(json.dumps(doc))
        r = self._run([str(bad), "--baseline", str(bl), "--prune-stale"])
        assert r.returncode == 0, r.stdout + r.stderr
        assert "pruned 1 stale" in r.stderr
        doc2 = json.loads(bl.read_text())
        assert list(doc2["entries"]) == [fp]
        assert doc2["entries"][fp]["justification"] == "reviewed: fixture"

    def test_jobs_parallel_load_matches_serial(self):
        serial = analysis.load_package([PKG], root=REPO)
        par = analysis.load_package([PKG], root=REPO, jobs=4)
        assert [m.relpath for m in par.modules] == [
            m.relpath for m in serial.modules
        ]
        assert [f.fingerprint() for f in analysis.run_rules(par)] == [
            f.fingerprint() for f in analysis.run_rules(serial)
        ]


# -- lint --stats + the whole-package time budget (round 18) ------------------


class TestLintStats:
    """``lint --stats`` per-rule cost table, and the whole-package lint
    time budget the table exists to police: the ci.sh gate runs every
    family over the full tree on every push, so per-rule cost must stay
    visible and bounded as families grow."""

    def _run(self, args):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.run(
            [sys.executable, "-m", "triton_client_tpu", "lint", *args],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
        )

    def test_stats_table_lists_every_family(self, tmp_path):
        clean = tmp_path / "clean.py"
        clean.write_text("X = 1\n")
        r = self._run([str(clean), "--stats"])
        assert r.returncode == 0, r.stdout + r.stderr
        for code in ("TPL101", "TPL401", "TPL601", "TPL701", "TPL801",
                     "TPL805"):
            assert code in r.stderr, r.stderr
        assert "elapsed_ms" in r.stderr
        assert any(
            ln.startswith("total") for ln in r.stderr.splitlines()
        ), r.stderr

    def test_stats_rides_json_summary(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(LOCK_POSITIVE)
        r = self._run([str(bad), "--stats", "--json"])
        assert r.returncode == 1
        doc = json.loads(r.stdout)
        stats = doc["summary"]["stats"]
        assert stats["TPL401"]["findings"] == 1
        assert {"TPL801", "TPL802", "TPL803", "TPL804", "TPL805"} <= set(stats)
        assert all(row["elapsed_ms"] >= 0 for row in stats.values())

    def test_whole_package_lint_fits_time_budget(self):
        """Hard ceiling on full-tree rule evaluation (load excluded —
        parse cost is the gate's --jobs concern). Measured ~12 s for
        eight families on this tree; 60 s is the do-not-cross line
        before the gate stops being a pre-push tool."""
        stats: dict = {}
        package = analysis.load_package([PKG], root=REPO, jobs=4)
        analysis.run_rules(package, stats=stats)
        assert {"TPL801", "TPL802", "TPL803", "TPL804", "TPL805"} <= set(
            stats
        )
        total_ms = sum(r["elapsed_ms"] for r in stats.values())
        assert total_ms < 60_000, f"lint blew its budget: {total_ms:.0f} ms"
