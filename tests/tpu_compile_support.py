"""What the three files of TPU compile tests share: the described chip and
the steering of the one probe the detectors' pipelines ask.

``tests/test_tpu_compile_detectors.py``, ``..._latent.py`` and
``..._gqa.py`` import these fixtures by name. Rules they keep
(on-chip-measurement guide, section 2): the topology is described inside a
module-scoped fixture, never at import and never in a
``parametrize``/``skipif`` argument (every xdist worker imports every
test file, and the worker that is given a file loads libtpu from inside
the fixture); the compiles run in the test's own process; the persistent
compilation cache is off around them (an entry compiled for an unattached
chip cannot be read back).
"""

import pytest

jax = pytest.importorskip("jax")
from jax.sharding import SingleDeviceSharding  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def tpu_route(monkeypatch):
    """``fused: auto`` asks ``jax.default_backend()``, which says cpu
    here; steer the one probe every kernel call site shares so the
    pipelines build the route they take on the chip — compiled
    kernels, not interpreted ones. The program gets no option."""
    from triton_client_tpu.ops import fused

    monkeypatch.setattr(fused, "fused_interpret", lambda: False)


def compile_text(fn, one_chip, *shapes, **static):
    """Compile ``fn`` for the described chip; returns the executable's
    text. ``shapes``: (shape, dtype) pairs placed on that chip."""
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    return jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static
    ).compile().as_text()
