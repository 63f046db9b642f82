"""Ask the TPU's compiler before the chip is asked: the token families
with grouped-query attention over key/value rows (SDAR, SmallThinker).

``lm_extend_attention`` at every served shape and whole launch programs
as ``ParamLauncher`` launches them, compiled by the installed libtpu for
a DESCRIBED v5e (no device attached): what Mosaic or XLA:TPU would refuse
on the chip, it refuses here, at no chip time, and the executable's text
shows what a launch copies and writes out.

A compile that passes is not a chip run: nothing executes here, so
these tests say nothing about results or times (``chip_smoke.py`` does).
The described chip and the rules that keep it to one worker's fixture are
in ``tests/tpu_compile_support.py``. A kernel PR adds its compile case to
the file of its family: detectors' kernels and launchers in
``test_tpu_compile_detectors.py``, the latent-attention families (A.X-K1,
DeepSeek-V3.2, Ling) in ``test_tpu_compile_latent.py``, the grouped-query
families (SDAR, SmallThinker) in ``test_tpu_compile_gqa.py``.
"""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tpu_compile_support import compile_text as _compile, one_chip, topo  # noqa: E402,F401


def _as_on_the_chip(monkeypatch):
    """The extend launches' attention as on the chip: the Pallas kernel
    ``lm_extend_attention``, not its interpreted form (the probe it asks
    is ``latent_attention.on_chip``, and the backend here says cpu)."""
    from triton_client_tpu.ops import latent_attention

    monkeypatch.setattr(latent_attention, "on_chip", lambda: True)


def _sdar_launch(one_chip, model: dict, slots: int, slot_len: int, launch: dict, monkeypatch=None):
    """One launch shape of ``family: sdar_moe`` compiled as
    ``ParamLauncher`` launches it: weights and cache as arguments, the
    cache donated and row-major on both sides; with ``monkeypatch`` the
    prompt's attention as on the chip (the served head size: the tiny
    preset's is no lane tile). Returns the executable's text and the
    configuration."""
    from jax.experimental.layout import Format, Layout
    from triton_client_tpu.models import sdar
    from triton_client_tpu.pipelines import lm

    if monkeypatch is not None:
        _as_on_the_chip(monkeypatch)

    cfg = sdar.SDARConfig.from_dict(model)
    placed = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    weights = placed(jax.eval_shape(lambda: sdar.stack_layers(sdar.init_params(jax.random.PRNGKey(0), cfg), cfg)))
    cache = placed(jax.eval_shape(lambda: sdar.empty_cache(cfg, slots, slot_len)))
    row_major = jax.tree_util.tree_map(
        lambda x: Format(Layout(major_to_minor=tuple(range(x.ndim))), one_chip), cache)
    ((kind, size),) = launch.items()
    inputs = placed({k: jnp.asarray(v) for k, v in lm.launch_inputs(kind, size, cfg.block_length).items()})
    device_fn = lm.make_device_fn.__wrapped__(sdar, cfg)  # traced here, with the probe as steered: not the memoized one

    def run(inputs, weights, cache):
        out = dict(device_fn(inputs, {"weights": weights, lm.STATE_KEY: cache}))
        return out, out.pop(lm.STATE_KEY)

    return jax.jit(
        run, donate_argnums=(2,), in_shardings=(None, None, row_major), out_shardings=(None, row_major),
    ).lower(inputs, weights, cache).compile().as_text(), cfg


def _sdar_config() -> dict:
    import json
    import pathlib

    return json.loads(
        (pathlib.Path(__file__).resolve().parents[1] / "benchmarks/configs/sdar30b-ep8-l48.json").read_text())


@pytest.mark.parametrize("launch", ({"extend": 16}, {"extend": 32}, {"block": 8}))
def test_sdar_launch_kinds_lower_at_the_tiny_preset(one_chip, launch):
    """The launch shapes of the benchmark configuration's rehearsal: an
    extend of whole blocks at two buckets and a block launch of
    denoising and committing rows."""
    doc = _sdar_config()
    model = {**doc["model"], **doc["rehearsal"]["model"]}
    slot_len = model.pop("slot_len")
    model.pop("max_tokens")
    text, cfg = _sdar_launch(one_chip, model, 8, slot_len, launch)
    ((kind, size),) = launch.items()
    rows = size * cfg.block_length if kind == "block" else 1
    assert f"f32[{rows},{cfg.vocab_size}]" in text


def _score_blocks_outside_the_kernel(text: str) -> list[str]:
    """Ops of a program that yield a float32 array of a score block's
    shape (a key/value head's group of query heads x a tile of queries x
    a block of keys, as the XLA loop wrote them out every key block until
    PR 50): none, where the scores stay in the kernel's fast memory."""
    import re

    scores = re.compile(r"= f32\[4,(3584|4096),(512|1024)\]")
    return [line.strip()[:160] for line in text.splitlines() if scores.search(line)]


@pytest.mark.parametrize("launch", ({"extend": 512}, {"extend": 1024}, {"extend": 2048}, {"block": 16}))
def test_sdar_launches_update_the_served_cache_in_place(one_chip, launch, monkeypatch):
    """At the served widths (two layers of the 48) the cache keeps its
    row-major layout through the layer scan: no launch begins or ends
    with a copy of it. With the key/value heads on an axis of their own
    the compiler laid the cache out with the heads minor and copied all
    of it, 4 GB at the served depth, at both ends of every launch. A
    prompt launch's text names the attention kernel and writes no block
    of scores out; a block launch has no such kernel."""
    model = {**_sdar_config()["model"], "num_hidden_layers": 2}
    slot_len = model.pop("slot_len")
    model.pop("max_tokens")
    text, cfg = _sdar_launch(one_chip, model, 20, slot_len, launch, monkeypatch)
    whole = f"bf16[2,20,{slot_len},{cfg.num_key_value_heads * cfg.head_dim}]"
    assert whole in text
    assert not [line for line in text.splitlines() if f"= {whole}" in line and " copy(" in line]
    assert ("lm_extend_attention" in text) == ("extend" in launch)
    assert not _score_blocks_outside_the_kernel(text)


@pytest.mark.parametrize("tokens, heads, rows, block, window", [
    (2048, 28, 16384, 1, 0), (1024, 28, 16384, 1, 0), (2048, 28, 6144, 1, 4096), (1024, 28, 6144, 1, 4096),
    (512, 32, 2048, 4, 0), (1024, 32, 2048, 4, 0), (2048, 32, 2048, 4, 0),
])
def test_lm_extend_attention_lowers_at_the_served_shapes(one_chip, monkeypatch, tokens, heads, rows, block, window):
    """``prefill_attention`` alone at every shape the two cells that run
    it serve: SmallThinker's launches of 2,048 and 1,024 tokens on a full
    layer's slot and on a window layer's ring (7 query heads a key/value
    head), SDAR's three prompt launches on its slot (8): the kernel's
    tiles fit the chip's fast memory and its blocks the tiling."""
    from triton_client_tpu.ops import block_attention

    _as_on_the_chip(monkeypatch)
    bf = jnp.bfloat16
    text = _compile(
        lambda q, k, v, positions: block_attention.prefill_attention(q, (k, v), positions, block, 128**-0.5, window),
        one_chip, ((tokens, heads, 128), bf), ((rows, 512), bf), ((rows, 512), bf), ((tokens,), jnp.int32))
    assert "lm_extend_attention_window" in text if window else "lm_extend_attention" in text
    assert f"bf16[{tokens},{heads * 128}]" in text


def _smallthinker_launch(one_chip, model: dict, slots: int, slot_len: int, launch: dict, monkeypatch=None):
    """One launch shape of ``family: smallthinker`` compiled as
    ``ParamLauncher`` launches it: weights and the two key/value caches
    as arguments, the caches donated and row-major on both sides; with
    ``monkeypatch`` an extend launch's attention as on the chip.
    Returns the executable's text and the configuration."""
    from jax.experimental.layout import Format, Layout
    from triton_client_tpu.models import smallthinker
    from triton_client_tpu.pipelines import lm

    if monkeypatch is not None:
        _as_on_the_chip(monkeypatch)

    cfg = smallthinker.Config.from_dict(model)
    placed = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    weights = placed(jax.eval_shape(
        lambda: smallthinker.stack_layers(smallthinker.init_params(jax.random.PRNGKey(0), cfg), cfg)))
    cache = placed(jax.eval_shape(lambda: smallthinker.empty_cache(cfg, slots, slot_len)))
    row_major = jax.tree_util.tree_map(
        lambda x: Format(Layout(major_to_minor=tuple(range(x.ndim))), one_chip), cache)
    ((kind, size),) = launch.items()
    inputs = placed({k: jnp.asarray(v) for k, v in lm.launch_inputs(kind, size).items()})
    device_fn = lm.make_device_fn.__wrapped__(smallthinker, cfg)  # traced here, with the probe as steered

    def run(inputs, weights, cache):
        out = dict(device_fn(inputs, {"weights": weights, lm.STATE_KEY: cache}))
        return out, out.pop(lm.STATE_KEY)

    return jax.jit(
        run, donate_argnums=(2,), in_shardings=(None, None, row_major), out_shardings=(None, row_major),
    ).lower(inputs, weights, cache).compile().as_text(), cfg


def _smallthinker_config() -> dict:
    import json
    import pathlib

    return json.loads(
        (pathlib.Path(__file__).resolve().parents[1] / "benchmarks/configs/smallthinker21b-ep1-l12.json").read_text())


@pytest.mark.parametrize("launch", ({"extend": 32}, {"extend": 64}, {"step": 8}))
def test_smallthinker_launch_kinds_lower_at_the_tiny_preset(one_chip, launch):
    """Both launch kinds of the benchmark configuration's rehearsal: the
    scan over periods with its inner scan over window layers, the ring."""
    doc = _smallthinker_config()
    model = {**doc["model"], **doc["rehearsal"]["model"]}
    slot_len = model.pop("slot_len")
    model.pop("max_tokens")
    text, cfg = _smallthinker_launch(one_chip, model, 16, slot_len, launch)
    ((kind, size),) = launch.items()
    assert f"f32[{size if kind == 'step' else 1},{cfg.vocab_size}]" in text


@pytest.mark.parametrize("launch", ({"extend": 2048}, {"extend": 1024}, {"step": 16}))
def test_smallthinker_launches_update_both_geometries_in_place(one_chip, launch, monkeypatch):
    """At the served widths (one period of the three, an eighth of the
    vocabulary) both key/value caches keep their row-major layout through
    the scans: no launch begins or ends with a copy of the full layers'
    rows or of the window layers' rings, and none copies a layer's
    experts out of the stack whole for a step launch. An extend launch's
    text names the attention kernel twice, the full layers' instance and
    the window layers', and writes no block of scores out."""
    model = {**_smallthinker_config()["model"], "num_hidden_layers": 4, "vocab_size": 18992}
    model["layer_types"] = model["layer_types"][:4]
    slot_len = model.pop("slot_len")
    model.pop("max_tokens")
    text, cfg = _smallthinker_launch(one_chip, model, 16, slot_len, launch, monkeypatch)
    width = cfg.num_key_value_heads * cfg.head_dim
    assert ("lm_extend_attention_window" in text) == ("lm_extend_attention." in text) == ("extend" in launch)
    assert not _score_blocks_outside_the_kernel(text)
    for whole in (f"bf16[1,16,{slot_len},{width}]", f"bf16[3,16,{cfg.window_ring},{width}]"):
        assert whole in text
        assert not [line for line in text.splitlines() if f"= {whole}" in line and " copy(" in line]
    if "step" in launch:
        experts = f"bf16[{cfg.moe_num_primary_experts},{cfg.hidden_size},{cfg.moe_ffn_hidden_size}]"
        assert not [line for line in text.splitlines() if f"= {experts}" in line]
