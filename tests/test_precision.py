"""Serving precision policies (runtime/precision.py, round 10).

The contract under test, end to end:

  * policy algebra — parse/compute dtype/budget table, the
    registration-time ``cast_params`` transform (bf16 cast, int8
    per-channel quantization into :class:`QuantizedParam` pytree
    leaves), wire narrowing and the device-side ``ingest`` inverse;
  * accuracy parity — the f32 pipeline's detections on a synthetic set
    become ground truth; bf16/int8w/int8 must hold mAP within each
    policy's declared ``map_budget`` RELATIVE to the f32 self-score
    (f32 scored against its own detections lands slightly under 1.0 —
    AP interpolation over tied confidences — so budgets floor against
    that attainable ceiling);
  * selection — ``config.yaml model.precision`` per entry and the
    repository-wide ``serve --precision`` override both pick the same
    policy machinery;
  * wire — TPUChannel stages bf16/int8 wire dtypes and still answers
    in f32;
  * sharded — a quantized params tree (registered pytree nodes)
    replicates onto the mesh and serves;
  * gauges — the collector's per-model ``param_bytes`` /
    ``precision_info`` families, so a quantized registration visibly
    shrinks reported HBM occupancy.
"""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from triton_client_tpu.runtime.precision import (
    BF16,
    KEEP_F32_2D,
    POLICIES,
    PrecisionPolicy,
    QuantizedParam,
    quantize_channelwise,
    realize,
    resolve_policy,
    tree_bytes,
)

HW = (64, 64)
CONF = 0.05  # random weights barely clear 0.3; parity needs live boxes


# -- policy algebra -----------------------------------------------------------


class TestPolicy:
    def test_parse_none_and_empty_are_f32(self):
        assert PrecisionPolicy.parse(None).name == "f32"
        assert PrecisionPolicy.parse("").name == "f32"
        p = PrecisionPolicy.parse("bf16")
        assert p.name == "bf16"
        assert PrecisionPolicy.parse(p) is p  # idempotent

    def test_parse_rejects_unknown(self):
        with pytest.raises(ValueError, match="unknown precision"):
            PrecisionPolicy.parse("fp8")

    def test_compute_dtype_and_flags(self):
        assert PrecisionPolicy.parse("f32").compute_dtype == jnp.float32
        assert PrecisionPolicy.parse("bf16").compute_dtype == jnp.bfloat16
        # int8 policies dequantize to f32 compute
        assert PrecisionPolicy.parse("int8w").compute_dtype == jnp.float32
        assert PrecisionPolicy.parse("int8").compute_dtype == jnp.float32
        assert PrecisionPolicy.parse("int8w").quantize_weights
        assert not PrecisionPolicy.parse("int8w").quantize_acts
        assert PrecisionPolicy.parse("int8").quantize_acts

    def test_budgets_monotone_in_compression(self):
        budgets = [PrecisionPolicy.parse(p).map_budget for p in POLICIES]
        assert budgets[0] == 0.0
        assert budgets == sorted(budgets)

    def test_resolve_policy_bf16_switches_model_dtype(self):
        policy, dtype = resolve_policy("bf16", jnp.float32)
        assert policy.name == "bf16" and dtype == jnp.bfloat16
        # explicit caller dtype wins (the legacy dtype=bf16 bench path)
        _, dtype = resolve_policy("f32", jnp.bfloat16)
        assert dtype == jnp.bfloat16


class TestCastParams:
    def _tree(self):
        rng = np.random.default_rng(3)
        return {
            "kernel": jnp.asarray(
                rng.normal(0, 0.5, (3, 3, 8, 16)).astype(np.float32)
            ),
            "bias": jnp.asarray(rng.normal(0, 1, (16,)).astype(np.float32)),
            "step": jnp.asarray(np.int32(7)),
        }

    def test_f32_is_identity(self):
        tree = self._tree()
        assert PrecisionPolicy.parse("f32").cast_params(tree) is tree

    def test_bf16_casts_float_leaves_only(self):
        out = PrecisionPolicy.parse("bf16").cast_params(self._tree())
        assert out["kernel"].dtype == jnp.bfloat16
        assert out["bias"].dtype == jnp.bfloat16
        assert out["step"].dtype == jnp.int32  # non-float untouched

    def test_int8_quantizes_kernels_keeps_biases(self):
        for name in ("int8w", "int8"):
            out = PrecisionPolicy.parse(name).cast_params(self._tree())
            assert isinstance(out["kernel"], QuantizedParam)
            assert out["kernel"].q.dtype == jnp.int8
            # 1-D leaves (biases, norm stats) stay f32
            assert out["bias"].dtype == jnp.float32

    def test_quantize_roundtrip_error_bounded_by_half_scale(self):
        rng = np.random.default_rng(5)
        w = rng.normal(0, 2, (6, 32)).astype(np.float32)
        qp = quantize_channelwise(w)
        # per-output-channel scales: one per column of the (6, 32)
        assert qp.scale.shape == (1, 32)
        err = np.abs(np.asarray(qp.dequant()) - w)
        # symmetric rounding: off by at most half a quantization step
        assert np.all(err <= np.asarray(qp.scale) * 0.5 + 1e-7)

    def test_realize_restores_f32_tree(self):
        tree = self._tree()
        out = realize(PrecisionPolicy.parse("int8w").cast_params(tree))
        assert out["kernel"].dtype == jnp.float32
        assert out["kernel"].shape == tree["kernel"].shape
        np.testing.assert_array_equal(out["bias"], tree["bias"])

    def test_tree_bytes_shrink_ratios(self):
        tree = self._tree()
        f32 = tree_bytes(tree)
        bf16 = tree_bytes(PrecisionPolicy.parse("bf16").cast_params(tree))
        int8 = tree_bytes(PrecisionPolicy.parse("int8w").cast_params(tree))
        kernel = int(np.asarray(tree["kernel"]).nbytes)
        # float leaves exactly halve; the int32 scalar stays
        assert bf16 == f32 - (kernel + 64) // 2
        # kernel quarters (plus the tiny per-channel scale vector)
        assert int8 < f32 * 0.3
        assert int8 >= f32 - kernel + kernel // 4

    def test_spec_extra_records_the_gauge_sources(self):
        tree = self._tree()
        policy = PrecisionPolicy.parse("bf16")
        extra = policy.spec_extra(policy.cast_params(tree))
        assert extra["precision"] == "bf16"
        assert extra["precision_keep_f32"] == list(KEEP_F32_2D)
        assert extra["param_bytes"] == tree_bytes(
            policy.cast_params(tree)
        )


class TestWireCast:
    def test_f32_and_int8w_pass_through(self):
        x = np.ones((2, 4), np.float32)
        for name in ("f32", "int8w"):
            assert PrecisionPolicy.parse(name).wire_cast("images", x) is x

    def test_bf16_downcasts_floats_never_widens(self):
        p = PrecisionPolicy.parse("bf16")
        x = np.ones((2, 4), np.float32)
        assert p.wire_cast("images", x).dtype == BF16
        # uint8 frames already travel in one byte — untouched
        u = np.ones((2, 4), np.uint8)
        assert p.wire_cast("images", u) is u
        # an already-bf16 array must not round-trip through anything
        b = x.astype(BF16)
        assert p.wire_cast("images", b) is b

    def test_keep_list_inputs_exempt(self):
        p = dataclasses.replace(
            PrecisionPolicy.parse("bf16"), keep_f32_inputs=("points",)
        )
        x = np.ones((2, 4), np.float32)
        assert p.wire_cast("points", x) is x

    def test_calibration_then_int8_wire_roundtrip(self):
        rng = np.random.default_rng(0)
        frames = rng.normal(0, 40, (4, 8, 8, 3)).astype(np.float32)
        p = PrecisionPolicy.parse("int8").calibrated({"images": frames})
        scale = p.scale_for("images")
        assert scale == pytest.approx(np.abs(frames).max() / 127.0)
        wire = p.wire_cast("images", frames)
        assert wire.dtype == np.int8
        # uncalibrated tensors upload as-is
        other = np.ones((2, 2), np.float32)
        assert p.wire_cast("mystery", other) is other
        # device-side inverse: dequantized back within one step
        out = p.ingest({"images": jnp.asarray(wire)})
        err = np.abs(np.asarray(out["images"]) - frames)
        assert out["images"].dtype == jnp.float32
        assert float(err.max()) <= scale * 0.5 + 1e-6

    def test_calibration_skips_integer_and_keep_list_inputs(self):
        p = dataclasses.replace(
            PrecisionPolicy.parse("int8"), keep_f32_inputs=("points",)
        )
        p = p.calibrated(
            {
                "frames": np.ones((2, 4), np.uint8),
                "points": np.ones((2, 4), np.float32),
            }
        )
        assert p.scale_for("frames") is None
        assert p.scale_for("points") is None
        assert not p.wire_ingest_needed  # nothing calibrated

    def test_ingest_without_scales_is_identity(self):
        inputs = {"x": jnp.ones((2, 2))}
        assert PrecisionPolicy.parse("f32").ingest(inputs) is inputs


# -- accuracy parity (the budget gate) ---------------------------------------


def _build_yolo(precision):
    from triton_client_tpu.pipelines.detect2d import (
        Detect2DConfig,
        build_yolov5_pipeline,
    )

    cfg = Detect2DConfig(
        model_name="yolov5_prec", input_hw=HW, num_classes=2,
        conf_thresh=CONF,
    )
    return build_yolov5_pipeline(
        jax.random.PRNGKey(0), variant="n", num_classes=2, input_hw=HW,
        config=cfg, precision=precision,
    )


@pytest.fixture(scope="module")
def eval_frames():
    return (
        np.random.default_rng(0)
        .integers(0, 255, (4, *HW, 3))
        .astype(np.float32)
    )


@pytest.fixture(scope="module")
def f32_reference(eval_frames):
    """f32 detections as synthetic ground truth + the attainable
    self-score ceiling the budgets floor against."""
    from triton_client_tpu.eval.detection_map import DetectionEvaluator

    pipe, spec, _ = _build_yolo("f32")
    dets, valid = pipe.infer(eval_frames)
    gts = [
        d[v.astype(bool)][:, [0, 1, 2, 3, 5]] for d, v in zip(dets, valid)
    ]
    assert int(np.asarray(valid).sum()) > 0, "parity needs live boxes"
    ev = DetectionEvaluator()
    for d, v, gt in zip(dets, valid, gts):
        ev.add_frame(d, v, gt)
    return spec, gts, float(ev.summary()["map"])


def _parity_map(pipe, eval_frames, gts):
    from triton_client_tpu.eval.detection_map import DetectionEvaluator

    ev = DetectionEvaluator()
    dets, valid = pipe.infer(eval_frames)
    for d, v, gt in zip(dets, valid, gts):
        ev.add_frame(d, v, gt)
    return float(ev.summary()["map"]), dets


class TestDetectionParity:
    @pytest.mark.parametrize("name", ["bf16", "int8w", "int8"])
    def test_policy_holds_declared_map_budget(
        self, name, eval_frames, f32_reference
    ):
        ref_spec, gts, ref_map = f32_reference
        policy = PrecisionPolicy.parse(name)
        if policy.quantize_acts:
            # the production registration order: calibrate first
            policy = policy.calibrated({"images": eval_frames})
            assert policy.wire_ingest_needed
        pipe, spec, _ = _build_yolo(policy)
        mean_ap, dets = _parity_map(pipe, eval_frames, gts)
        assert mean_ap >= ref_map - policy.map_budget, (
            f"{name}: mAP {mean_ap:.4f} under floor "
            f"{ref_map - policy.map_budget:.4f}"
        )
        # boundary ops ran in f32: wire outputs are f32 whatever the
        # compute dtype
        assert np.asarray(dets).dtype == np.float32
        # spec records the policy + the shrunken footprint
        assert spec.extra["precision"] == name
        assert spec.extra["precision_keep_f32"] == list(KEEP_F32_2D)
        f32_bytes = ref_spec.extra["param_bytes"]
        if name == "bf16":
            assert spec.extra["param_bytes"] == f32_bytes // 2
        else:
            assert spec.extra["param_bytes"] < f32_bytes * 0.3


# -- wire: TPUChannel serves each policy end to end ---------------------------


class TestWireChannel:
    def _serve(self, precision, eval_frames):
        from triton_client_tpu.channel import InferRequest, TPUChannel
        from triton_client_tpu.runtime.repository import ModelRepository

        policy = PrecisionPolicy.parse(precision)
        if policy.quantize_acts:
            policy = policy.calibrated({"images": eval_frames})
        pipe, spec, _ = _build_yolo(policy)
        repo = ModelRepository()
        repo.register(
            spec, pipe.infer_fn(), device_fn=pipe.device_fn(),
            precision=pipe.precision,
        )
        chan = TPUChannel(repo)
        staged = chan.stage(
            InferRequest(spec.name, {"images": eval_frames[:2]})
        )
        resp = chan.launch(staged).result()
        return staged, resp

    def test_bf16_stages_half_width_wire(self, eval_frames):
        staged, resp = self._serve("bf16", eval_frames)
        assert staged.device_inputs["images"].dtype == jnp.bfloat16
        assert resp.outputs["detections"].dtype == np.float32
        assert resp.outputs["detections"].shape[0] == 2

    def test_int8_stages_quarter_width_wire_and_answers(self, eval_frames):
        staged, resp = self._serve("int8", eval_frames)
        assert staged.device_inputs["images"].dtype == jnp.int8
        assert resp.outputs["detections"].dtype == np.float32
        assert resp.outputs["detections"].shape[0] == 2


# -- selection: config.yaml model.precision + serve --precision ---------------


def _entry_doc(precision=None):
    model = {"variant": "n", "input_hw": list(HW), "num_classes": 2}
    if precision:
        model["precision"] = precision
    return {
        "family": "yolov5",
        "model": model,
        "pipeline": {"conf_thresh": CONF},
        "max_batch_size": 4,
    }


def _write_entry(root, name, doc):
    import pathlib

    import yaml

    d = pathlib.Path(root) / name
    d.mkdir(parents=True)
    (d / "config.yaml").write_text(yaml.safe_dump(doc))


class TestSelection:
    def test_config_yaml_model_precision_selects_policy(self, tmp_path):
        from triton_client_tpu.runtime import disk_repository as dr

        _write_entry(tmp_path, "tiny_f32", _entry_doc())
        _write_entry(tmp_path, "tiny_bf16", _entry_doc("bf16"))
        repo = dr.scan_disk(tmp_path)
        f32 = repo.get("tiny_f32")
        bf16 = repo.get("tiny_bf16")
        assert f32.spec.extra.get("precision", "f32") == "f32"
        assert bf16.spec.extra["precision"] == "bf16"
        assert bf16.precision.name == "bf16"
        # the HBM-occupancy half the gauge reports
        assert (
            bf16.spec.extra["param_bytes"]
            == f32.spec.extra["param_bytes"] // 2
        )

    def test_scan_disk_precision_overrides_every_entry(self, tmp_path):
        from triton_client_tpu.runtime import disk_repository as dr

        _write_entry(tmp_path, "tiny_f32", _entry_doc())
        _write_entry(tmp_path, "tiny_bf16", _entry_doc("bf16"))
        repo = dr.scan_disk(tmp_path, precision="int8w")
        for name in ("tiny_f32", "tiny_bf16"):
            model = repo.get(name)
            assert model.spec.extra["precision"] == "int8w", name
            assert isinstance(model.precision, PrecisionPolicy)

    def test_config_yaml_rejects_unknown_policy(self, tmp_path):
        from triton_client_tpu.runtime import disk_repository as dr

        _write_entry(tmp_path, "tiny_bad", _entry_doc("fp8"))
        with pytest.raises(ValueError, match="unknown precision"):
            dr.scan_disk(tmp_path)

    def test_serve_cli_precision_flag_reaches_the_wire(self, tmp_path):
        """serve --precision bf16 over a tiny repo: the loaded entry
        carries the policy and answers over real gRPC."""
        import argparse

        from triton_client_tpu.channel.base import InferRequest
        from triton_client_tpu.channel.grpc_channel import GRPCChannel
        from triton_client_tpu.cli import serve

        _write_entry(tmp_path, "tiny", _entry_doc())
        args = argparse.Namespace(
            model_repository=str(tmp_path), address="127.0.0.1:0",
            max_workers=2, mesh="", batching=False, max_batch=4,
            pipeline_depth=2, metrics_port=0,
            warmup=False, verbose=False, precision="bf16",
        )
        server = serve.build_server(args)
        server.start()
        try:
            chan = GRPCChannel(f"127.0.0.1:{server.port}", timeout_s=60.0)
            spec = chan.get_metadata("tiny")
            assert spec.extra["precision"] == "bf16"
            frame = np.zeros((1, *HW, 3), np.float32)
            resp = chan.do_inference(
                InferRequest(model_name="tiny", inputs={"images": frame})
            )
            assert resp.outputs["detections"].dtype == np.float32
            chan.close()
        finally:
            server.stop()


# -- sharded: the quantized tree replicates -----------------------------------


class TestShardedQuantized:
    def _toy_repo(self, policy_name):
        """Explicit-params toy (matmul head): device_fn(inputs, params)
        with QuantizedParam leaves in the registered tree — the shape
        replicate_params ships to every device."""
        from triton_client_tpu.config import ModelSpec, TensorSpec
        from triton_client_tpu.runtime.repository import ModelRepository

        rng = np.random.default_rng(11)
        w = rng.normal(0, 1, (4, 8)).astype(np.float32)
        policy = PrecisionPolicy.parse(policy_name)
        tree = policy.cast_params({"w": jnp.asarray(w)})
        expected_w = np.asarray(realize(tree)["w"], np.float32)

        spec = ModelSpec(
            name="toy_q",
            version="1",
            platform="jax",
            inputs=(TensorSpec("x", (-1, 4), "FP32"),),
            outputs=(TensorSpec("y", (-1, 8), "FP32"),),
            max_batch_size=8,
            extra=policy.spec_extra(tree),
        )
        repo = ModelRepository()
        repo.register(
            spec,
            lambda inputs: {
                "y": np.asarray(inputs["x"], np.float32) @ expected_w
            },
            device_fn=lambda inputs, params: {
                "y": inputs["x"].astype(jnp.float32)
                @ realize(params)["w"].astype(jnp.float32)
            },
            params=tree,
            precision=policy,
        )
        return repo, spec, expected_w

    def test_quantized_tree_replicates_and_matches_host(self):
        from triton_client_tpu.channel import (
            InferRequest,
            ShardedTPUChannel,
        )
        from triton_client_tpu.parallel.mesh import MeshConfig

        repo, spec, expected_w = self._toy_repo("int8w")
        assert spec.extra["param_bytes"] == tree_bytes(
            repo.get("toy_q").params
        )
        chan = ShardedTPUChannel(repo, MeshConfig(data=-1, model=1))
        x = np.random.default_rng(1).normal(0, 1, (8, 4)).astype(
            np.float32
        )
        resp = chan.do_inference(InferRequest("toy_q", {"x": x}))
        np.testing.assert_allclose(
            resp.outputs["y"], x @ expected_w, rtol=1e-5, atol=1e-5
        )
        # uneven batch: pad rows replicate + slice back off
        resp3 = chan.do_inference(InferRequest("toy_q", {"x": x[:3]}))
        assert resp3.outputs["y"].shape == (3, 8)
        np.testing.assert_allclose(
            resp3.outputs["y"], resp.outputs["y"][:3], rtol=1e-6
        )

    def test_bf16_tree_halves_the_gauge(self):
        repo_f32, spec_f32, _ = self._toy_repo("f32")
        repo_bf16, spec_bf16, _ = self._toy_repo("bf16")
        assert (
            spec_bf16.extra["param_bytes"]
            == spec_f32.extra["param_bytes"] // 2
        )


# -- gauges: the collector's per-model families -------------------------------


class TestCollectorGauges:
    def test_param_bytes_gauge_shrinks_with_quantization(self):
        pytest.importorskip("prometheus_client")
        from triton_client_tpu.config import ModelSpec, TensorSpec
        from triton_client_tpu.obs.collector import RuntimeCollector
        from triton_client_tpu.runtime.repository import ModelRepository

        repo = ModelRepository()
        rng = np.random.default_rng(2)
        w = jnp.asarray(rng.normal(0, 1, (32, 32)).astype(np.float32))
        for name, policy_name in (("m_f32", "f32"), ("m_int8", "int8w")):
            policy = PrecisionPolicy.parse(policy_name)
            tree = policy.cast_params({"w": w})
            repo.register(
                ModelSpec(
                    name=name,
                    version="1",
                    inputs=(TensorSpec("x", (-1, 32), "FP32"),),
                    outputs=(TensorSpec("y", (-1, 32), "FP32"),),
                    extra=policy.spec_extra(tree),
                ),
                lambda inputs: inputs,
                precision=policy,
            )
        collector = RuntimeCollector(repository=repo)
        try:
            fams = {f.name: f for f in collector.collect()}
            info = {
                s.labels["model"]: s.labels["precision"]
                for s in fams["tpu_serving_model_precision_info"].samples
            }
            assert info == {"m_f32": "f32", "m_int8": "int8w"}
            size = {
                s.labels["model"]: s.value
                for s in fams["tpu_serving_model_param_bytes"].samples
            }
            # the regression the gauge exists for: quantized
            # registration visibly shrinks reported HBM occupancy
            assert size["m_f32"] == 32 * 32 * 4
            assert size["m_int8"] < size["m_f32"] * 0.3
            assert size["m_int8"] == repo.get("m_int8").spec.extra[
                "param_bytes"
            ]
        finally:
            collector.close()

    def test_families_export_empty_without_repository(self):
        pytest.importorskip("prometheus_client")
        from triton_client_tpu.obs.collector import RuntimeCollector

        collector = RuntimeCollector()
        try:
            fams = {f.name: f for f in collector.collect()}
            assert fams["tpu_serving_model_precision_info"].samples == []
            assert fams["tpu_serving_model_param_bytes"].samples == []
        finally:
            collector.close()


# -- ensemble: per-step precision ---------------------------------------------


class TestEnsembleStepPrecision:
    def _repo(self):
        from triton_client_tpu.config import ModelSpec, TensorSpec
        from triton_client_tpu.runtime.repository import ModelRepository

        repo = ModelRepository()
        for name, out in (("scale", "scaled"), ("shift", "shifted")):
            repo.register(
                ModelSpec(
                    name=name,
                    version="1",
                    inputs=(TensorSpec("x", (-1, 4), "FP32"),),
                    outputs=(TensorSpec(out, (-1, 4), "FP32"),),
                ),
                (
                    (lambda inputs: {"scaled": np.asarray(inputs["x"]) * 2})
                    if name == "scale"
                    else (lambda inputs: {"shifted": np.asarray(inputs["x"]) + 1})
                ),
            )
        return repo

    def test_parse_steps_accepts_and_validates_precision(self):
        from triton_client_tpu.runtime.ensemble import parse_steps

        steps = parse_steps(
            [
                {
                    "model": "a",
                    "input_map": {"x": "raw"},
                    "output_map": {"y": "mid"},
                    "precision": "bf16",
                },
                {"model": "b", "input_map": {"x": "mid"}, "output_map": {"y": "out"}},
            ]
        )
        assert steps[0].precision == "bf16"
        assert steps[1].precision == ""  # inherit the member's policy
        with pytest.raises(ValueError, match="precision"):
            parse_steps(
                [
                    {
                        "model": "a",
                        "input_map": {},
                        "output_map": {},
                        "precision": "fp8",
                    }
                ]
            )

    def test_build_records_effective_step_precision(self):
        from triton_client_tpu.runtime.ensemble import (
            EnsembleStep,
            build_ensemble,
        )

        rm = build_ensemble(
            self._repo(),
            "chain",
            [
                EnsembleStep(
                    "scale", {"x": "raw"}, {"scaled": "mid"},
                    precision="bf16",
                ),
                # no override: inherits the member's registered policy
                EnsembleStep("shift", {"x": "mid"}, {"shifted": "final"}),
            ],
            outputs=["final"],
        )
        assert rm.spec.extra["step_precision"] == ["bf16", "f32"]
        x = np.arange(8, dtype=np.float32).reshape(2, 4)
        np.testing.assert_allclose(
            rm.infer_fn({"raw": x})["final"], x * 2 + 1
        )
