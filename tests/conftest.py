"""Test env: force an 8-device virtual CPU mesh.

Mirrors SURVEY.md section 4's recommendation: multi-device sharding
logic is exercised on host CPU with xla_force_host_platform_device_count
so tests don't need TPU hardware.

The one rig: tests and rehearsals run on the CPU — the platform is
forced here (env var for subprocesses, jax.config for this process,
whatever the shell exported) and XLA_FLAGS, read lazily at backend
init, asks for 8 virtual devices. Nothing here concerns libtpu: the
only tests that load it describe a chip from inside their own fixture
(tests/test_tpu_compile_*.py, tests/tpu_compile_support.py). Chip runs are not tests; they go through
the chip tool as ``python chip_smoke.py``.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # for any subprocesses

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def pytest_collection_modifyitems(config, items):
    """A module that states ``SHARD = (k, n)`` beside the names it
    ``ADOPTED`` (the shards of the benchmark's rehearsals,
    ``test_benchmark_seam.py``) keeps every n-th adopted case, counted
    from k in the order of the cases' ids; its own tests stay."""
    adopted = {}
    for item in items:
        module = getattr(item, "module", None)
        if hasattr(module, "SHARD") and getattr(item, "originalname", None) in module.ADOPTED:
            adopted.setdefault(module, []).append(item.name)
    dropped = set()
    for module, names in adopted.items():
        k, n = module.SHARD
        dropped.update((module, name) for rank, name in enumerate(sorted(names)) if rank % n != k)
    if dropped:
        kept, gone = [], []
        for item in items:
            (gone if (getattr(item, "module", None), item.name) in dropped else kept).append(item)
        items[:] = kept
        config.hook.pytest_deselected(items=gone)
