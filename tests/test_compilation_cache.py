"""utils/compilation_cache: the persistent compile cache is placed
from outside the program.

``JAX_COMPILATION_CACHE_DIR`` set -> jax reads the variable itself and
the helper sets NO path in code; unset on a non-CPU selection -> the
fixed ``<checkout>/.jax_cache`` (never a temp name, pid or time, or no
later process would hit it); CPU selected -> no cache.
"""

import pathlib

import pytest

pytest.importorskip("jax")

from triton_client_tpu.utils import compilation_cache  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture()
def config_updates(monkeypatch):
    """Record every ``jax.config.update`` the helper makes instead of
    applying it (a cache switched on mid-suite would leak into every
    later compile of this worker)."""
    import types

    calls = {}
    monkeypatch.setattr(
        compilation_cache, "jax",
        types.SimpleNamespace(
            config=types.SimpleNamespace(
                update=calls.__setitem__,
                jax_platforms=None,  # the platform comes from the env
            )
        ),
    )
    return calls


def _select(monkeypatch, platforms: str) -> None:
    monkeypatch.setenv("JAX_PLATFORMS", platforms)


def test_env_dir_sets_no_path_in_code(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    _select(monkeypatch, "tpu")
    assert compilation_cache.enable_persistent_cache() == "/x"
    assert "jax_compilation_cache_dir" not in config_updates
    # it may still lower the thresholds so small compiles are kept
    assert config_updates["jax_persistent_cache_min_entry_size_bytes"] == 0
    assert not pathlib.Path("/x").exists()  # nor does it create it


def test_env_dir_wins_on_cpu_too(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    _select(monkeypatch, "cpu")
    assert compilation_cache.enable_persistent_cache() == "/x"
    assert "jax_compilation_cache_dir" not in config_updates


@pytest.mark.parametrize("platforms", ["tpu", ""])
def test_unset_non_cpu_uses_fixed_checkout_dir(
    monkeypatch, config_updates, platforms
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    _select(monkeypatch, platforms)
    made = []
    monkeypatch.setattr(
        pathlib.Path, "mkdir", lambda self, **kw: made.append(self)
    )
    first = compilation_cache.enable_persistent_cache()
    second = compilation_cache.enable_persistent_cache()
    assert first == second == str(REPO / ".jax_cache")
    assert config_updates["jax_compilation_cache_dir"] == first
    assert made == [REPO / ".jax_cache"] * 2


def test_cpu_selected_means_no_cache(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    _select(monkeypatch, "cpu")
    assert compilation_cache.enable_persistent_cache() == ""
    assert config_updates == {}


def test_no_entry_point_sets_a_cache_path_itself():
    """grep-level: the helper is the only code that may name the
    config key, so an entry point cannot place the cache on its own."""
    offenders = [
        str(path.relative_to(REPO))
        for root in ("triton_client_tpu", "perf")
        for path in (REPO / root).rglob("*.py")
        if "jax_compilation_cache_dir" in path.read_text()
    ] + [
        name
        for name in ("chip_smoke.py", "__graft_entry__.py")
        if "jax_compilation_cache_dir" in (REPO / name).read_text()
    ]
    assert offenders == ["triton_client_tpu/utils/compilation_cache.py"]


@pytest.mark.parametrize(
    "module",
    ["cli/serve.py", "cli/detect2d.py", "cli/detect3d.py"],
)
def test_cli_entry_points_go_through_the_helper(module):
    text = (REPO / "triton_client_tpu" / module).read_text()
    assert "enable_persistent_cache()" in text
