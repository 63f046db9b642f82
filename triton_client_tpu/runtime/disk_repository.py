"""On-disk model repository: Triton's directory layout, JAX semantics.

The reference serves from a model-repository directory tree —
``<repo>/<model>/config.pbtxt`` + numbered version dirs with backend
artifacts (examples/pointpillar_kitti/config.pbtxt, examples/YOLOv5/
config.pbtxt; loaded by tritonserver --model-repository, README.md:66).
This module is that layout for the TPU runtime::

    <root>/<model_name>/
        config.yaml      # family + model/pipeline config (config.pbtxt)
        1/weights.msgpack # flax-native weights (or .pt/.pth/.onnx
        2/weights.pt      # imported via runtime.importers)

``scan_disk`` builds every model's fused jit pipeline and registers it
(name, version) into a ModelRepository, so the gRPC serving facade and
TPUChannel can dispatch to any version. Unlike Triton there is no
backend zoo: every family maps to an in-tree flax pipeline builder.
"""

from __future__ import annotations

import dataclasses
import logging
import pathlib
from typing import Any, Callable, Mapping

import jax

from triton_client_tpu.dataset_config import (
    _apply_overrides,
    _SEQ_KEYS,
    load_yaml,
    model_config_from_dict,
)
from triton_client_tpu.runtime.repository import ModelRepository, RegisteredModel

log = logging.getLogger(__name__)

_WEIGHT_NAMES = ("weights.msgpack", "weights.pt", "weights.pth", "weights.onnx", "model.pt", "model.pth", "model.onnx")


def _families_2d() -> tuple[str, ...]:
    from triton_client_tpu.pipelines.detect2d import BUILDERS_2D

    return tuple(BUILDERS_2D)


def _families_lm() -> tuple[str, ...]:
    """Token models served in sessions over a device-resident cache
    (pipelines/lm.py): weights are launcher arguments, read from
    ``weights.msgpack`` onto the device leaf by leaf."""
    from triton_client_tpu.pipelines.lm import MODULES

    return tuple(MODULES)


def _families_3d() -> tuple[str, ...]:
    from triton_client_tpu.pipelines.detect3d import BUILDERS_3D

    return tuple(BUILDERS_3D)

# family -> importer fn(state_dict, template_variables) for torch/onnx
# artifacts; families without one accept only flax-native msgpack.
def _torch_importers() -> dict[str, Callable]:
    from triton_client_tpu.runtime import importers

    return {
        "yolov5": importers.load_yolov5,
        "yolov4": importers.load_yolov4,
        "retinanet": importers.load_retinanet,
        "fcos": importers.load_fcos,
        "pointpillars": importers.load_pointpillars,
        "second_iou": importers.load_second,
        "centerpoint": importers.load_centerpoint,
    }


def save_flax_weights(path: str | pathlib.Path, variables: Mapping) -> None:
    """Write a variables tree as flax-native msgpack bytes."""
    import flax.serialization

    pathlib.Path(path).write_bytes(flax.serialization.to_bytes(variables))


def load_weights(path: str | pathlib.Path, family: str, template: Mapping) -> Mapping:
    """Load a version dir's weight artifact onto a template tree."""
    path = pathlib.Path(path)
    ext = path.suffix
    if ext == ".msgpack":
        import flax.serialization

        return flax.serialization.from_bytes(template, path.read_bytes())
    importer = _torch_importers().get(family)
    if importer is None:
        raise ValueError(
            f"family {family!r} has no torch/onnx importer; provide "
            f"weights.msgpack (got {path.name})"
        )
    if ext in (".pt", ".pth"):
        return importer(str(path), template)
    if ext == ".onnx":
        from triton_client_tpu.runtime.onnx_reader import (
            onnx_to_state_dict,
            read_onnx_initializers,
        )

        state = onnx_to_state_dict(read_onnx_initializers(str(path)))
        return importer(state, template)
    raise ValueError(f"unrecognized weight artifact {path.name}")


def _resolve(path_str: str, model_dir: pathlib.Path) -> str:
    """Resolve a config-referenced file: relative to the model dir
    first, then the repository root, then cwd. Raises with the bases
    tried so a wrong serving cwd is diagnosable immediately."""
    p = pathlib.Path(path_str)
    if p.is_absolute():
        return str(p)
    bases = (model_dir, model_dir.parent, pathlib.Path.cwd())
    for base in bases:
        if (base / p).exists():
            return str(base / p)
    raise FileNotFoundError(
        f"{model_dir / 'config.yaml'} references {path_str!r}, not found "
        f"relative to any of {[str(b) for b in bases]}"
    )


def _build_2d(family: str, doc: Mapping[str, Any], model_dir: pathlib.Path):
    from triton_client_tpu.pipelines import detect2d

    builders = detect2d.BUILDERS_2D
    model_kwargs = dict(doc.get("model", {}))
    if "input_hw" in model_kwargs:
        model_kwargs["input_hw"] = tuple(model_kwargs["input_hw"])
    if "dtype" in model_kwargs:
        from triton_client_tpu.config import parse_compute_dtype

        model_kwargs["dtype"] = parse_compute_dtype(model_kwargs["dtype"])
    if "precision" in model_kwargs:
        # validate at scan time so a typo'd policy fails at startup,
        # not at first inference (fail-loudly policy)
        from triton_client_tpu.runtime.precision import PrecisionPolicy

        model_kwargs["precision"] = PrecisionPolicy.parse(
            model_kwargs["precision"]
        )

    if family == "preprocess":
        # paramless host-prep pipeline: nothing to cast/quantize, so a
        # repository-wide --precision override passes it by
        model_kwargs.pop("precision", None)

    pipe_d = dict(doc.get("pipeline", {}))
    names_file = pipe_d.pop("class_names_file", None)
    names = (
        detect2d.load_class_names(_resolve(names_file, model_dir))
        if names_file
        else None
    )
    if names:
        model_kwargs.setdefault("num_classes", len(names))

    def build(variables=None, config=None):
        return builders[family](
            rng=jax.random.PRNGKey(0), variables=variables, config=config,
            **model_kwargs,
        )

    def make_cfg(default_cfg):
        # Overlay config.yaml's pipeline section onto the FAMILY's
        # default config (detectron pipelines differ from YOLO in head
        # style and thresholds) — unknown keys fail loudly.
        cfg = _apply_overrides(default_cfg, pipe_d, _SEQ_KEYS)
        if names:
            cfg = dataclasses.replace(
                cfg, class_names=names, num_classes=model_kwargs["num_classes"]
            )
        if "input_hw" in model_kwargs:
            cfg = dataclasses.replace(cfg, input_hw=model_kwargs["input_hw"])
        return cfg

    return build, make_cfg


def _build_3d(family: str, doc: Mapping[str, Any], model_dir: pathlib.Path):
    from triton_client_tpu.dataset_config import detect3d_from_yaml
    from triton_client_tpu.pipelines import detect3d

    builders = detect3d.BUILDERS_3D
    model_doc = dict(doc.get("model", {}))
    from triton_client_tpu.config import parse_compute_dtype
    from triton_client_tpu.runtime.precision import PrecisionPolicy

    dtype = parse_compute_dtype(model_doc.pop("dtype", "fp32"))
    precision = PrecisionPolicy.parse(model_doc.pop("precision", None))
    if "dataset" in doc:
        got_family, model_cfg, pipe_cfg = detect3d_from_yaml(
            _resolve(doc["dataset"], model_dir)
        )
        if got_family != family:
            raise ValueError(
                f"config.yaml family {family!r} != dataset yaml model {got_family!r}"
            )
    else:
        model_cfg = model_config_from_dict(family, model_doc)
        pipe_cfg = _apply_overrides(
            detect3d.default_detect3d_config(family),
            dict(doc.get("pipeline", {})),
            _SEQ_KEYS,
        )

    def build(variables=None, config=pipe_cfg):
        return builders[family](
            rng=jax.random.PRNGKey(0), model_cfg=model_cfg, config=config,
            variables=variables, dtype=dtype, precision=precision,
        )

    return build, lambda _default: pipe_cfg


_TOP_KEYS = {"family", "model", "pipeline", "dataset", "max_batch_size", "warmup"}


class _Entry:
    """One model dir's parsed config + lazily-shared init template, so
    N version dirs cost ONE random init (the template tree), not N."""

    def __init__(
        self,
        model_dir: str | pathlib.Path,
        doc: Mapping[str, Any] | None = None,
        precision: str | None = None,
    ) -> None:
        self.model_dir = pathlib.Path(model_dir)
        if doc is None:
            doc = load_yaml(str(self.model_dir / "config.yaml"))
        doc = dict(doc)
        if precision:
            # serve --precision: a repository-wide override of each
            # entry's config.yaml model.precision (both select the same
            # policy machinery, runtime/precision.py)
            doc["model"] = {
                **dict(doc.get("model", {})), "precision": precision,
            }
        unknown = set(doc) - _TOP_KEYS
        if unknown:
            raise KeyError(
                f"{self.model_dir / 'config.yaml'}: unknown keys "
                f"{sorted(unknown)}; known: {sorted(_TOP_KEYS)}"
            )
        self.doc = doc
        self.family = doc.get("family")
        if self.family in _families_lm():
            # no pipeline object and no template tree: the builder
            # (pipelines/lm.py) reads the whole entry itself
            self._build = self.cfg = self._template = None
            return
        if self.family in _families_2d():
            self._build, make_cfg = _build_2d(self.family, doc, self.model_dir)
        elif self.family in _families_3d():
            self._build, make_cfg = _build_3d(self.family, doc, self.model_dir)
        else:
            raise ValueError(
                f"{self.model_dir}: unknown family {self.family!r} "
                f"(known: {_families_2d() + _families_3d() + _families_lm()})"
            )
        # Probe with empty variables (builders skip init when variables
        # is given; forward closures are lazy) to get the family-default
        # pipeline config without paying for a random init.
        probe, _, _ = self._build(variables={})
        self.cfg = make_cfg(probe.config)
        self._template = None

    def template(self) -> Mapping:
        if self._template is None:
            _, _, self._template = self._build(config=self.cfg)
        return self._template

    def registered(
        self, version: str, weights: str | pathlib.Path | None = None
    ) -> RegisteredModel:
        if self._build is None:
            from triton_client_tpu.pipelines import lm

            return lm.build_registered(
                self.doc, self.model_dir.name, version, weights
            )
        if weights is not None:
            variables = load_weights(weights, self.family, self.template())
        else:
            variables = self.template()
        pipeline, spec, _ = self._build(variables=variables, config=self.cfg)
        spec = dataclasses.replace(
            spec,
            name=self.model_dir.name,
            version=version,
            max_batch_size=int(self.doc.get("max_batch_size", spec.max_batch_size)),
        )

        def warmup(p=pipeline, c=self.cfg):
            # Compile the shape real traffic uses: batch 1 at the
            # model's native resolution (2D re-traces per distinct
            # camera resolution anyway; this covers the native one) or
            # the smallest point bucket (3D).
            import numpy as np

            if hasattr(c, "input_hw"):
                p.infer(np.zeros((1, *c.input_hw, 3), np.float32))
            else:
                p.infer(np.zeros((16, 4), np.float32))

        return RegisteredModel(
            spec=spec,
            infer_fn=pipeline.infer_fn(),
            warmup=warmup,
            # pipelines that expose a jit-traceable form make their
            # models fusable as ensemble members (intermediates stay
            # in HBM); host-only pipelines still serve the wire path
            device_fn=(
                pipeline.device_fn()
                if hasattr(pipeline, "device_fn")
                else None
            ),
            # the serving channels read the policy off the registered
            # model for the wire half (host narrowing + int8 ingest)
            precision=getattr(pipeline, "precision", None),
        )


def build_model(
    model_dir: str | pathlib.Path,
    version: str = "1",
    weights: str | pathlib.Path | None = None,
) -> RegisteredModel:
    """Build one model dir's pipeline (optionally a specific version's
    weights) into a RegisteredModel, without registering it."""
    return _Entry(model_dir).registered(version, weights)


def load_pipeline(
    model_dir: str | pathlib.Path,
    version: str = "",
    config_overrides: Mapping[str, Any] | None = None,
    kind: str = "",
):
    """One model dir -> (pipeline, spec) with its TRAINED weights, for
    direct in-process use — the detect CLIs' --repo path (the reference
    always runs served artifacts, never random init; this is the
    client-side equivalent of Triton loading a version dir). Empty
    ``version`` picks the latest; ``config_overrides`` overlays the
    entry's pipeline config (e.g. eval-time conf/iou thresholds);
    ``kind`` ('2d'/'3d') rejects a wrong-dimensionality entry up front
    instead of crashing deep in the pipeline."""
    entry = _Entry(model_dir)
    if kind:
        families = _families_2d() if kind == "2d" else _families_3d()
        if entry.family not in families:
            other = "3d" if kind == "2d" else "2d"
            raise ValueError(
                f"{entry.model_dir}: family {entry.family!r} is a {other} "
                f"model; use the detect{other} CLI for this entry"
            )
    cfg = entry.cfg
    if config_overrides:
        cfg = dataclasses.replace(cfg, **config_overrides)
    if version:
        vdir = entry.model_dir / version
        if not vdir.is_dir():
            raise FileNotFoundError(
                f"{entry.model_dir}: no version dir {version!r}"
            )
    else:
        vdirs = version_dirs(entry.model_dir)
        if not vdirs:
            raise FileNotFoundError(
                f"{entry.model_dir}: no version dirs with weights "
                "(a --repo entry must carry trained artifacts)"
            )
        vdir = vdirs[-1]
    variables = load_weights(find_weights(vdir), entry.family, entry.template())
    pipeline, spec, _ = entry._build(variables=variables, config=cfg)
    spec = dataclasses.replace(
        spec, name=entry.model_dir.name, version=vdir.name
    )
    return pipeline, spec


def conversion_template(
    family: str | None = None,
    model_kwargs: Mapping[str, Any] | None = None,
    doc: Mapping[str, Any] | None = None,
) -> Mapping:
    """Random-init variables tree for a family — the shape/structure
    template load_weights converts upstream checkpoints onto. Public
    entry for deploy tooling (no model dir needed): pass either an
    already-built config ``doc`` or ``family`` (+ ``model_kwargs``)."""
    if doc is None:
        doc = {"family": family}
        if model_kwargs:
            doc["model"] = dict(model_kwargs)
    return _Entry(pathlib.Path.cwd(), doc=doc).template()


def version_dirs(model_dir: pathlib.Path) -> list[pathlib.Path]:
    return sorted(
        (d for d in model_dir.iterdir() if d.is_dir() and d.name.isdigit()),
        key=lambda d: int(d.name),
    )


def find_weights(version_dir: pathlib.Path) -> pathlib.Path:
    """A version dir MUST carry a recognized artifact — registering
    random-init weights for a typo'd filename would serve garbage
    silently (fail-loudly policy; Triton likewise errors on a version
    dir its backend can't load)."""
    for name in _WEIGHT_NAMES:
        if (version_dir / name).exists():
            return version_dir / name
    present = sorted(p.name for p in version_dir.iterdir())
    raise FileNotFoundError(
        f"{version_dir}: no weight artifact (found {present}; "
        f"recognized names: {list(_WEIGHT_NAMES)})"
    )


def scan_disk(
    root: str | pathlib.Path,
    repository: ModelRepository | None = None,
    precision: str | None = None,
) -> ModelRepository:
    """Load every ``<root>/<model>/config.yaml`` entry into a repository.

    Version dirs (numeric names) each register separately; a model with
    no version dirs registers as version 1 with fresh-init weights
    (useful for spec-only entries and tests). A ``warmup: true`` entry
    compiles at scan time; every model also carries a warmup callable
    for serve --warmup. Broken entries raise — a serving process should
    fail loudly at startup, not skip models (the reference's Triton does
    the same for malformed config.pbtxt). ``precision`` overrides every
    entry's ``model.precision`` policy (the serve --precision flag).
    """
    root = pathlib.Path(root)
    repo = repository or ModelRepository()
    ensembles: list[tuple[pathlib.Path, dict]] = []
    for model_dir in sorted(p for p in root.iterdir() if p.is_dir()):
        if not (model_dir / "config.yaml").exists():
            log.info("skipping %s (no config.yaml)", model_dir)
            continue
        doc = dict(load_yaml(str(model_dir / "config.yaml")))
        if doc.get("family") == "ensemble":
            # composed over member models — register after them all
            # (steps inherit their members' precision, or override per
            # stage via a step-level ``precision`` key)
            ensembles.append((model_dir, doc))
            continue
        entry = _Entry(model_dir, doc=doc, precision=precision)
        versions = version_dirs(model_dir)
        pairs = (
            [(v.name, find_weights(v)) for v in versions]
            if versions
            else [("1", None)]
        )
        for version, weights in pairs:
            rm = entry.registered(version, weights)
            repo.register(
                rm.spec, rm.infer_fn, warmup=rm.warmup,
                device_fn=rm.device_fn, params=rm.params,
                precision=rm.precision, sessions=rm.sessions,
            )
            if entry.doc.get("warmup") and rm.warmup is not None:
                rm.warmup()
    if ensembles:
        from triton_client_tpu.runtime.ensemble import build_ensemble_doc

        # Dependency-order fixpoint: an ensemble whose step references a
        # not-yet-registered sibling ensemble waits for the next round
        # (nested ensembles must not depend on directory sort order).
        pending = {d.name: (d, doc) for d, doc in ensembles}
        while pending:
            ready = [
                name
                for name, (_, doc) in pending.items()
                if not any(
                    s.get("model") in pending for s in doc.get("steps", [])
                )
            ]
            if not ready:
                raise ValueError(
                    f"ensemble dependency cycle among {sorted(pending)}"
                )
            for name in ready:
                model_dir, doc = pending.pop(name)
                rm = build_ensemble_doc(repo, name, doc)
                # device_fn travels along so a fused ensemble can be a
                # member of a PARENT fused ensemble (nested fusion)
                repo.register(
                    rm.spec, rm.infer_fn, warmup=rm.warmup,
                    device_fn=rm.device_fn,
                )
                if doc.get("warmup"):
                    rm.warmup()
    return repo


def export_model(
    root: str | pathlib.Path,
    name: str,
    config_doc: Mapping[str, Any],
    variables: Mapping | None = None,
    version: str = "1",
) -> pathlib.Path:
    """Materialize a repository entry on disk (deploy.sh:56-65 parity:
    convert + place artifacts + write the config contract)."""
    import yaml

    model_dir = pathlib.Path(root) / name
    model_dir.mkdir(parents=True, exist_ok=True)
    with open(model_dir / "config.yaml", "w") as f:
        yaml.safe_dump(dict(config_doc), f, sort_keys=False)
    if variables is not None:
        vdir = model_dir / version
        vdir.mkdir(exist_ok=True)
        save_flax_weights(vdir / "weights.msgpack", variables)
    return model_dir
