"""The weights' hand-off and the memory statement (``server_child``):
the abstract-weights lowering states the same temporaries as lowering
with real arrays, and the leaf-by-leaf ``weights.msgpack`` is the file
``flax.serialization.to_bytes`` writes."""

from __future__ import annotations

import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import server_child  # noqa: E402


def toy_model(with_params: bool):
    """A two-leaf model: ``relu(x @ w + b)`` summed over rows."""
    import jax
    import jax.numpy as jnp
    from triton_client_tpu.runtime.repository import RegisteredModel

    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(256, 512)), jnp.float32), "b": jnp.zeros((512,), jnp.float32)}

    def apply(inputs, p):
        hidden = jax.nn.relu(inputs["x"] @ p["w"] + p["b"])
        return {"y": (hidden @ p["w"].T).sum(axis=0)}

    if with_params:
        return RegisteredModel(spec=None, infer_fn=None, device_fn=apply, params=params), params
    return RegisteredModel(spec=None, infer_fn=None, device_fn=lambda inputs: apply(inputs, params)), params


@pytest.mark.parametrize("rows", [8, 64])
def test_abstract_weights_state_the_same_temporaries_as_real_ones(rows):
    import jax
    import jax.numpy as jnp

    model, params = toy_model(with_params=True)
    shapes = {"x": jax.ShapeDtypeStruct((rows, 256), jnp.float32)}
    real = jax.jit(model.device_fn).lower(shapes, params).compile().memory_analysis().temp_size_in_bytes
    assert server_child.launch_temp_bytes(model, shapes) == int(real) > 0


def test_a_model_without_params_is_lowered_with_its_constants():
    import jax
    import jax.numpy as jnp

    model, _ = toy_model(with_params=False)
    shapes = {"x": jax.ShapeDtypeStruct((8, 256), jnp.float32)}
    real = jax.jit(model.device_fn).lower(shapes).compile().memory_analysis().temp_size_in_bytes
    assert server_child.launch_temp_bytes(model, shapes) == int(real)


def test_no_real_tree_is_made_for_a_model_with_params(monkeypatch):
    """Lowered from the served model alone: nothing is built, and the
    arguments of the lowering are shapes."""
    import jax
    import jax.numpy as jnp
    from triton_client_tpu.runtime import disk_repository

    model, _ = toy_model(with_params=True)
    monkeypatch.setattr(disk_repository, "build_model", lambda *a, **k: pytest.fail("built a second entry"))
    seen = []
    inner = model.device_fn
    model.device_fn = lambda inputs, p: (seen.append(jax.tree_util.tree_leaves((inputs, p))), inner(inputs, p))[1]
    launch = {"x": np.zeros((8, 256), np.float32)}
    cfg = {"name": "toy", "serve_argv": []}
    temp = server_child.program_temp_bytes(pathlib.Path("/nonexistent"), cfg, [launch], model, "")
    assert temp == server_child.launch_temp_bytes(model, {"x": jax.ShapeDtypeStruct((8, 256), jnp.float32)})
    assert seen and all(isinstance(leaf, jax.core.Tracer) for leaves in seen for leaf in leaves)


def test_the_statement_is_kept_beside_the_cache(tmp_path):
    model, _ = toy_model(with_params=True)
    launch = {"x": np.zeros((8, 256), np.float32)}
    cfg = {"name": "toy", "serve_argv": []}
    first = server_child.program_temp_bytes(tmp_path / "shapes", cfg, [launch], model, str(tmp_path / "kept"))
    (kept,) = (tmp_path / "kept").glob("program_temp_*.json")
    assert server_child.load_json(kept) == {"temp_size_in_bytes": first, "config": "toy", "weights": "abstract"}
    model.device_fn = None  # the second call reads the file and lowers nothing
    assert server_child.program_temp_bytes(tmp_path / "shapes", cfg, [launch], model, str(tmp_path / "kept")) == first


def test_weights_written_leaf_by_leaf_are_flax_s_file(tmp_path, monkeypatch):
    import flax.serialization
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    tree = {
        "params": {"conv": {"kernel": jnp.asarray(rng.normal(size=(3, 3, 4, 8)), jnp.bfloat16),
                            "bias": jnp.zeros((8,), jnp.float32)},
                   "head": {"kernel": jnp.asarray(rng.normal(size=(40, 7)), jnp.float32)}},
        "batch_stats": {"bn": {"mean": jnp.arange(8, dtype=jnp.float32), "var": jnp.ones((8,), jnp.float32)}},
    }
    whole = flax.serialization.to_bytes(jax.tree_util.tree_map(np.asarray, tree))  # what was written before
    server_child.write_msgpack(tmp_path / "weights.msgpack", tree)
    assert (tmp_path / "weights.msgpack").read_bytes() == whole
    # and with a leaf over flax's chunk size (1 GiB there; 64 bytes here)
    monkeypatch.setattr(flax.serialization, "MAX_CHUNK_SIZE", 64)
    chunked = flax.serialization.to_bytes(jax.tree_util.tree_map(np.asarray, tree))
    assert chunked != whole
    server_child.write_msgpack(tmp_path / "chunked.msgpack", tree)
    assert (tmp_path / "chunked.msgpack").read_bytes() == chunked
    restored = flax.serialization.msgpack_restore(chunked)
    assert (restored["params"]["head"]["kernel"] == np.asarray(tree["params"]["head"]["kernel"])).all()


def test_sample_size_is_the_mix_s_own_or_follows_the_items():
    cfg = {"check": {"sample_items": 64}, "rehearsal": {"sample_requests": 8}}
    assert server_child.sample_size(cfg, {"items_per_request": 768}, False) == 1
    assert server_child.sample_size(cfg, {"items_per_request": 1}, False) == 64
    assert server_child.sample_size(cfg, {"items_per_request": 1}, True) == 8
    assert server_child.sample_size(cfg, {"items_per_request": 8, "sample_requests": 2}, True) == 2
