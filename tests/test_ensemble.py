"""Ensemble models: DAG-of-models serving.

The reference lists Triton ensemble mode as an unchecked TODO
(README.md:119); here it is implemented (runtime/ensemble.py) with
Triton's declaration semantics (ordered steps, input_map/output_map)
and TPU-first execution (members chain on device arrays). These tests
cover step parsing, contract derivation/validation, execution routing,
the channel seam, and disk-repository loading.
"""

import numpy as np
import pytest

from triton_client_tpu.channel.base import InferRequest
from triton_client_tpu.channel.tpu_channel import TPUChannel
from triton_client_tpu.config import ModelSpec, TensorSpec
from triton_client_tpu.runtime.ensemble import (
    EnsembleStep,
    build_ensemble,
    build_ensemble_doc,
    parse_steps,
)
from triton_client_tpu.runtime.repository import ModelRepository


def _register(repo, name, in_specs, out_specs, fn, version="1"):
    repo.register(
        ModelSpec(
            name=name,
            version=version,
            platform="jax",
            inputs=tuple(TensorSpec(n, s, d) for n, s, d in in_specs),
            outputs=tuple(TensorSpec(n, s, d) for n, s, d in out_specs),
        ),
        fn,
    )


@pytest.fixture
def repo():
    r = ModelRepository()
    _register(
        r, "scale",
        [("x", (-1, 4), "FP32")],
        [("scaled", (-1, 4), "FP32")],
        lambda inputs: {"scaled": np.asarray(inputs["x"]) * 2.0},
    )
    _register(
        r, "shift",
        [("x", (-1, 4), "FP32")],
        [("shifted", (-1, 4), "FP32")],
        lambda inputs: {"shifted": np.asarray(inputs["x"]) + 1.0},
    )
    return r


class TestParseSteps:
    def test_parses(self):
        steps = parse_steps(
            [
                {"model": "a", "input_map": {"x": "raw"}, "output_map": {"y": "mid"}},
                {"model": "b", "version": 2, "input_map": {"x": "mid"}, "output_map": {"y": "out"}},
            ]
        )
        assert steps[0] == EnsembleStep("a", {"x": "raw"}, {"y": "mid"})
        assert steps[1].version == "2"

    def test_rejects_unknown_keys(self):
        with pytest.raises(KeyError, match="unknown keys"):
            parse_steps([{"model": "a", "input_map": {}, "output_map": {}, "gpu": 1}])

    def test_rejects_missing_keys(self):
        with pytest.raises(KeyError, match="missing 'output_map'"):
            parse_steps([{"model": "a", "input_map": {}}])

    def test_rejects_empty(self):
        with pytest.raises(ValueError, match="at least one step"):
            parse_steps([])


class TestBuildEnsemble:
    def test_chain_executes_in_order(self, repo):
        # (x * 2) + 1 over two members with tensor renaming at each hop
        rm = build_ensemble(
            repo,
            "chain",
            [
                EnsembleStep("scale", {"x": "raw"}, {"scaled": "mid"}),
                EnsembleStep("shift", {"x": "mid"}, {"shifted": "final"}),
            ],
            outputs=["final"],
        )
        x = np.arange(8, dtype=np.float32).reshape(2, 4)
        out = rm.infer_fn({"raw": x})
        np.testing.assert_allclose(out["final"], x * 2.0 + 1.0)
        assert set(out) == {"final"}

    def test_derived_contract(self, repo):
        rm = build_ensemble(
            repo,
            "chain",
            [
                EnsembleStep("scale", {"x": "raw"}, {"scaled": "mid"}),
                EnsembleStep("shift", {"x": "mid"}, {"shifted": "final"}),
            ],
            outputs=["final", "mid"],
        )
        assert [t.name for t in rm.spec.inputs] == ["raw"]
        assert rm.spec.inputs[0].dtype == "FP32"
        assert [t.name for t in rm.spec.outputs] == ["final", "mid"]
        assert rm.spec.platform == "ensemble"
        assert rm.spec.extra["steps"] == ["scale", "shift"]

    def test_fanout_shares_input(self, repo):
        # both members consume the same ensemble input
        rm = build_ensemble(
            repo,
            "fan",
            [
                EnsembleStep("scale", {"x": "raw"}, {"scaled": "a"}),
                EnsembleStep("shift", {"x": "raw"}, {"shifted": "b"}),
            ],
            outputs=["a", "b"],
        )
        x = np.ones((1, 4), np.float32)
        out = rm.infer_fn({"raw": x})
        np.testing.assert_allclose(out["a"], 2.0)
        np.testing.assert_allclose(out["b"], 2.0)
        assert [t.name for t in rm.spec.inputs] == ["raw"]

    def test_unknown_member_model(self, repo):
        with pytest.raises(KeyError, match="not registered"):
            build_ensemble(
                repo, "e",
                [EnsembleStep("nope", {"x": "raw"}, {"y": "out"})],
                outputs=["out"],
            )

    def test_unknown_step_input(self, repo):
        with pytest.raises(KeyError, match="no inputs"):
            build_ensemble(
                repo, "e",
                [EnsembleStep("scale", {"wrong": "raw"}, {"scaled": "out"})],
                outputs=["out"],
            )

    def test_unbound_step_input(self, repo):
        with pytest.raises(KeyError, match="not bound"):
            build_ensemble(
                repo, "e",
                [EnsembleStep("scale", {}, {"scaled": "out"})],
                outputs=["out"],
            )

    def test_unknown_step_output(self, repo):
        with pytest.raises(KeyError, match="no outputs"):
            build_ensemble(
                repo, "e",
                [EnsembleStep("scale", {"x": "raw"}, {"wrong": "out"})],
                outputs=["out"],
            )

    def test_input_echo_output_rejected(self, repo):
        # an output naming an ensemble INPUT (typo: echoing raw back)
        # must fail at build, not silently pass input through
        with pytest.raises(ValueError, match="never produced"):
            build_ensemble(
                repo, "e",
                [EnsembleStep("scale", {"x": "raw"}, {"scaled": "mid"})],
                outputs=["mid", "raw"],
            )

    def test_undeclared_output(self, repo):
        with pytest.raises(ValueError, match="never produced"):
            build_ensemble(
                repo, "e",
                [EnsembleStep("scale", {"x": "raw"}, {"scaled": "mid"})],
                outputs=["final"],
            )

    def test_dtype_mismatch_fails_at_build(self, repo):
        _register(
            repo, "counter",
            [("x", (-1, 4), "FP32")],
            [("count", (-1,), "INT32")],
            lambda inputs: {"count": np.zeros(1, np.int32)},
        )
        with pytest.raises(ValueError, match="INT32.*consumes it as FP32"):
            build_ensemble(
                repo, "e",
                [
                    EnsembleStep("counter", {"x": "raw"}, {"count": "mid"}),
                    EnsembleStep("shift", {"x": "mid"}, {"shifted": "out"}),
                ],
                outputs=["out"],
            )

    def test_shape_mismatch_fails_at_build(self, repo):
        _register(
            repo, "wide",
            [("x", (-1, 4), "FP32")],
            [("y", (-1, 8), "FP32")],
            lambda inputs: {"y": np.zeros((1, 8), np.float32)},
        )
        with pytest.raises(ValueError, match="shape"):
            build_ensemble(
                repo, "e",
                [
                    EnsembleStep("wide", {"x": "raw"}, {"y": "mid"}),
                    EnsembleStep("shift", {"x": "mid"}, {"shifted": "out"}),
                ],
                outputs=["out"],
            )

    def test_no_outputs(self, repo):
        with pytest.raises(ValueError, match="at least one output"):
            build_ensemble(
                repo, "e",
                [EnsembleStep("scale", {"x": "raw"}, {"scaled": "mid"})],
                outputs=[],
            )


class TestChannelSeam:
    def test_serves_through_tpu_channel(self, repo):
        rm = build_ensemble(
            repo,
            "chain",
            [
                EnsembleStep("scale", {"x": "raw"}, {"scaled": "mid"}),
                EnsembleStep("shift", {"x": "mid"}, {"shifted": "final"}),
            ],
            outputs=["final"],
        )
        repo.register(rm.spec, rm.infer_fn)
        channel = TPUChannel(repo)
        x = np.ones((2, 4), np.float32)
        resp = channel.do_inference(
            InferRequest(model_name="chain", inputs={"raw": x})
        )
        np.testing.assert_allclose(resp.outputs["final"], 3.0)

    def test_ensemble_of_ensemble(self, repo):
        inner = build_ensemble(
            repo, "inner",
            [EnsembleStep("scale", {"x": "raw"}, {"scaled": "out"})],
            outputs=["out"],
        )
        repo.register(inner.spec, inner.infer_fn)
        outer = build_ensemble(
            repo, "outer",
            [
                EnsembleStep("inner", {"raw": "raw"}, {"out": "mid"}),
                EnsembleStep("shift", {"x": "mid"}, {"shifted": "final"}),
            ],
            outputs=["final"],
        )
        x = np.ones((1, 4), np.float32)
        np.testing.assert_allclose(outer.infer_fn({"raw": x})["final"], 3.0)


class TestDiskRepository:
    def test_scan_disk_loads_ensemble(self, tmp_path):
        import yaml

        from triton_client_tpu.runtime.disk_repository import scan_disk

        # a real (tiny) member model entry + an ensemble over it;
        # directory order puts the ensemble FIRST to prove deferred
        # registration ("aaa_..." sorts before "det")
        det = tmp_path / "det"
        det.mkdir()
        (det / "config.yaml").write_text(
            yaml.safe_dump(
                {
                    "family": "yolov5",
                    "model": {
                        "variant": "n",
                        "num_classes": 2,
                        "input_hw": [64, 64],
                    },
                }
            )
        )
        ens = tmp_path / "aaa_pipeline"
        ens.mkdir()
        (ens / "config.yaml").write_text(
            yaml.safe_dump(
                {
                    "family": "ensemble",
                    "steps": [
                        {
                            "model": "det",
                            "input_map": {"images": "camera"},
                            "output_map": {
                                "detections": "boxes",
                                "valid": "valid",
                            },
                        }
                    ],
                    "outputs": ["boxes", "valid"],
                }
            )
        )
        repo = scan_disk(tmp_path)
        names = dict(repo.list_models())
        assert "det" in names and "aaa_pipeline" in names
        rm = repo.get("aaa_pipeline")
        assert rm.spec.platform == "ensemble"
        frame = np.zeros((1, 64, 64, 3), np.float32)
        out = rm.infer_fn({"camera": frame})
        assert set(out) == {"boxes", "valid"}
        assert np.asarray(out["boxes"]).shape[0] == 1

    def test_scan_disk_nested_ensembles_any_order(self, tmp_path):
        # "a_outer" sorts before "z_inner" — registration must follow
        # dependency order, not directory order
        import yaml

        from triton_client_tpu.runtime.disk_repository import scan_disk

        det = tmp_path / "det"
        det.mkdir()
        (det / "config.yaml").write_text(
            yaml.safe_dump(
                {
                    "family": "yolov5",
                    "model": {"variant": "n", "num_classes": 2, "input_hw": [64, 64]},
                }
            )
        )
        inner = {
            "family": "ensemble",
            "steps": [
                {
                    "model": "det",
                    "input_map": {"images": "camera"},
                    "output_map": {"detections": "boxes", "valid": "valid"},
                }
            ],
            "outputs": ["boxes", "valid"],
        }
        outer = {
            "family": "ensemble",
            "steps": [
                {
                    "model": "z_inner",
                    "input_map": {"camera": "camera"},
                    "output_map": {"boxes": "boxes", "valid": "valid"},
                }
            ],
            "outputs": ["boxes"],
        }
        for dirname, doc in [("a_outer", outer), ("z_inner", inner)]:
            d = tmp_path / dirname
            d.mkdir()
            (d / "config.yaml").write_text(yaml.safe_dump(doc))
        repo = scan_disk(tmp_path)
        assert repo.get("a_outer").spec.platform == "ensemble"

    def test_scan_disk_ensemble_cycle_raises(self, tmp_path):
        import yaml

        from triton_client_tpu.runtime.disk_repository import scan_disk

        for a, b in [("ens_a", "ens_b"), ("ens_b", "ens_a")]:
            d = tmp_path / a
            d.mkdir()
            (d / "config.yaml").write_text(
                yaml.safe_dump(
                    {
                        "family": "ensemble",
                        "steps": [
                            {
                                "model": b,
                                "input_map": {"x": "raw"},
                                "output_map": {"y": "out"},
                            }
                        ],
                        "outputs": ["out"],
                    }
                )
            )
        with pytest.raises(ValueError, match="cycle"):
            scan_disk(tmp_path)

    def test_scan_disk_bad_ensemble_raises(self, tmp_path):
        import yaml

        from triton_client_tpu.runtime.disk_repository import scan_disk

        ens = tmp_path / "broken"
        ens.mkdir()
        (ens / "config.yaml").write_text(
            yaml.safe_dump(
                {
                    "family": "ensemble",
                    "steps": [
                        {
                            "model": "missing_member",
                            "input_map": {"x": "raw"},
                            "output_map": {"y": "out"},
                        }
                    ],
                    "outputs": ["out"],
                }
            )
        )
        with pytest.raises(KeyError, match="not registered"):
            scan_disk(tmp_path)


class TestDocParsing:
    def test_build_from_doc(self, repo):
        rm = build_ensemble_doc(
            repo,
            "chain",
            {
                "family": "ensemble",
                "steps": [
                    {"model": "scale", "input_map": {"x": "raw"}, "output_map": {"scaled": "out"}},
                ],
                "outputs": ["out"],
                "max_batch_size": 4,
            },
        )
        assert rm.spec.max_batch_size == 4

    def test_doc_unknown_keys(self, repo):
        with pytest.raises(KeyError, match="unknown config keys"):
            build_ensemble_doc(
                repo, "e", {"family": "ensemble", "steps": [], "outputs": [], "gpu": 1}
            )

    def test_doc_missing_sections(self, repo):
        with pytest.raises(KeyError, match="needs 'steps'"):
            build_ensemble_doc(repo, "e", {"family": "ensemble"})


class TestDeviceFusion:
    """Round-4 device-fused DAGs (VERDICT r3 #4): members exposing a
    jit-traceable device_fn compose under ONE jit — intermediates stay
    in HBM — and the fused path is numerically identical to the host
    path on the same DAG."""

    @staticmethod
    def _register_device(repo, name, in_specs, out_specs, host_fn, dev_fn):
        repo.register(
            ModelSpec(
                name=name,
                version="1",
                platform="jax",
                inputs=tuple(TensorSpec(n, s, d) for n, s, d in in_specs),
                outputs=tuple(TensorSpec(n, s, d) for n, s, d in out_specs),
            ),
            host_fn,
            device_fn=dev_fn,
        )

    @pytest.fixture
    def dev_repo(self):
        import jax.numpy as jnp

        r = ModelRepository()
        self._register_device(
            r, "scale",
            [("x", (-1, 4), "FP32")], [("scaled", (-1, 4), "FP32")],
            lambda i: {"scaled": np.asarray(i["x"]) * 2.0},
            lambda i: {"scaled": i["x"] * jnp.float32(2.0)},
        )
        self._register_device(
            r, "shift",
            [("x", (-1, 4), "FP32")], [("shifted", (-1, 4), "FP32")],
            lambda i: {"shifted": np.asarray(i["x"]) + 1.0},
            lambda i: {"shifted": i["x"] + jnp.float32(1.0)},
        )
        return r

    def _chain(self, repo, fuse):
        return build_ensemble(
            repo, "chain",
            [
                EnsembleStep("scale", {"x": "raw"}, {"scaled": "mid"}),
                EnsembleStep("shift", {"x": "mid"}, {"shifted": "out"}),
            ],
            outputs=["out"],
            fuse=fuse,
        )

    def test_fused_matches_host_path(self, dev_repo):
        x = np.arange(8, dtype=np.float32).reshape(2, 4)
        fused = self._chain(dev_repo, "auto")
        host = self._chain(dev_repo, "never")
        assert fused.spec.extra["fused"] is True
        assert host.spec.extra["fused"] is False
        np.testing.assert_allclose(
            fused.infer_fn({"raw": x})["out"],
            host.infer_fn({"raw": x})["out"],
        )
        np.testing.assert_allclose(
            host.infer_fn({"raw": x})["out"], x * 2.0 + 1.0
        )

    def test_always_rejects_host_only_member(self, repo):
        with pytest.raises(ValueError, match="no device_fn"):
            build_ensemble(
                repo, "chain",
                [EnsembleStep("scale", {"x": "raw"}, {"scaled": "out"})],
                outputs=["out"],
                fuse="always",
            )

    def test_auto_falls_back_to_host_members(self, repo):
        rm = build_ensemble(
            repo, "chain",
            [EnsembleStep("scale", {"x": "raw"}, {"scaled": "out"})],
            outputs=["out"],
            fuse="auto",
        )
        assert rm.spec.extra["fused"] is False
        out = rm.infer_fn({"raw": np.ones((1, 4), np.float32)})
        np.testing.assert_allclose(out["out"], 2.0)

    def test_doc_fuse_bool_coerces(self, dev_repo):
        rm = build_ensemble_doc(
            dev_repo, "chain",
            {
                "family": "ensemble",
                "fuse": True,
                "steps": [
                    {"model": "scale", "input_map": {"x": "raw"},
                     "output_map": {"scaled": "out"}},
                ],
                "outputs": ["out"],
            },
        )
        assert rm.spec.extra["fused"] is True

    def test_invalid_fuse_value(self, dev_repo):
        with pytest.raises(ValueError, match="auto/always/never"):
            build_ensemble(
                dev_repo, "chain",
                [EnsembleStep("scale", {"x": "raw"}, {"scaled": "out"})],
                outputs=["out"],
                fuse="maybe",
            )

    def test_examples_fused_entry_serves(self, tmp_path):
        """The shipped preprocess->detector entry loads from disk with
        fuse: always (every member has a device form) and detects."""
        import shutil

        from triton_client_tpu.runtime import disk_repository as dr

        # the entry and the members its config.yaml names, as shipped:
        # scan_disk builds every entry eagerly, and the other twelve
        # of examples/ (axk1_ep16's weights among them) were most of this
        # test's 376 s (24 s without them) and nothing it checks
        for entry in ("ensemble_fused_pipeline", "camera_preprocess", "yolov5_crop"):
            shutil.copytree(f"examples/{entry}", tmp_path / entry)
        repo = dr.scan_disk(str(tmp_path))
        rm = repo.get("ensemble_fused_pipeline")
        assert rm.spec.extra["fused"] is True
        frame = np.zeros((1, 96, 128, 3), np.uint8)
        out = rm.infer_fn({"camera_raw": frame})
        assert out["boxes"].shape[-1] == 6
        assert np.isfinite(np.asarray(out["boxes"], np.float32)).all()

    def test_nested_fusion_composes_device_fns(self, dev_repo):
        """A fused ensemble exposes its own device form, so a PARENT
        ensemble can fuse over it — the nesting boundary stays in HBM
        (scan_disk's fixpoint supports nested ensembles; fusion must
        not stop at one level)."""
        child = self._chain(dev_repo, "always")
        assert child.device_fn is not None
        dev_repo.register(
            child.spec, child.infer_fn, warmup=child.warmup,
            device_fn=child.device_fn,
        )
        parent = build_ensemble(
            dev_repo, "parent",
            [
                EnsembleStep("chain", {"raw": "x0"}, {"out": "mid"}),
                EnsembleStep("scale", {"x": "mid"}, {"scaled": "final"}),
            ],
            outputs=["final"],
            fuse="always",
        )
        assert parent.spec.extra["fused"] is True
        x = np.ones((2, 4), np.float32)
        np.testing.assert_allclose(
            parent.infer_fn({"x0": x})["final"], (x * 2 + 1) * 2
        )

    def test_fused_output_cast_to_spec_dtype(self):
        """Device traces run with x64 disabled, so an INT64 wire
        contract comes back int32 from the DAG; the fused boundary
        casts outputs to the declared spec dtype so fused == host on
        dtype too (the scored-head classes case)."""
        import jax.numpy as jnp

        r = ModelRepository()
        self._register_device(
            r, "ids",
            [("x", (-1, 4), "FP32")], [("classes", (-1,), "INT64")],
            lambda i: {"classes": np.zeros(len(i["x"]), np.int64)},
            lambda i: {"classes": jnp.zeros(i["x"].shape[0], jnp.int32)},
        )
        rm = build_ensemble(
            r, "e",
            [EnsembleStep("ids", {"x": "raw"}, {"classes": "out"})],
            outputs=["out"], fuse="always",
        )
        out = rm.infer_fn({"raw": np.zeros((3, 4), np.float32)})
        assert out["out"].dtype == np.int64

    def test_fused_warmup_compiles_the_dag(self, dev_repo):
        """warmup() on a fused ensemble must exercise the FUSED path
        (member warmups compile standalone programs the fused path
        never runs)."""
        rm = self._chain(dev_repo, "always")
        rm.warmup()  # no member warmups registered -> must not raise

    def test_3d_pipeline_exposes_device_fn(self):
        """3D members are fusable too: the detect3d pipeline's device
        form matches its wire adapter on the same padded cloud."""
        import jax

        from triton_client_tpu.models.pointpillars import PointPillarsConfig
        from triton_client_tpu.ops.voxelize import VoxelConfig, pad_points
        from triton_client_tpu.pipelines.detect3d import (
            Detect3DConfig,
            build_pointpillars_pipeline,
        )

        # tiny grid, same shape as test_pointpillars.TINY: equivalence
        # holds at any size and the full KITTI graph costs ~26 s of CI
        # compile for no extra coverage
        model_cfg = PointPillarsConfig(
            voxel=VoxelConfig(
                point_cloud_range=(0.0, -6.4, -3.0, 12.8, 6.4, 1.0),
                voxel_size=(0.2, 0.2, 4.0),
                max_voxels=512,
                max_points_per_voxel=8,
            ),
            backbone_layers=(1, 1, 1),
        )
        pipe_cfg = Detect3DConfig(
            point_buckets=(512,), max_det=16, pre_max=64
        )
        pipeline, _, _ = build_pointpillars_pipeline(
            jax.random.PRNGKey(0), model_cfg=model_cfg, config=pipe_cfg
        )
        rng = np.random.default_rng(0)
        pts = np.stack(
            [
                rng.uniform(0, 12.8, 512), rng.uniform(-6.4, 6.4, 512),
                rng.uniform(-2, 0.5, 512), rng.uniform(0, 1, 512),
            ],
            axis=1,
        ).astype(np.float32)
        # SMALLEST bucket: equivalence holds at any size, and the
        # full 131k-point graph costs ~26 s of CI compile for nothing
        padded, m = pad_points(pts, min(pipe_cfg.point_buckets))
        inputs = {"points": padded, "num_points": m}
        wire = pipeline.infer_fn()(inputs)
        dev = jax.jit(pipeline.device_fn())(inputs)
        np.testing.assert_allclose(
            np.asarray(wire["detections"], np.float32),
            np.asarray(dev["detections"], np.float32), rtol=1e-5, atol=1e-5,
        )
        np.testing.assert_array_equal(
            np.asarray(wire["valid"]), np.asarray(dev["valid"])
        )
