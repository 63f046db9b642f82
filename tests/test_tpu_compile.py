"""Ask the TPU's compiler before the chip is asked.

Every Pallas kernel on the default TPU route, at its real serving
shapes, and three whole serving launchers (yolov5 b8, fused SECOND,
yolov5 over a four-chip mesh), compiled by the installed libtpu for a
DESCRIBED v5e (no device attached) — what Mosaic or XLA:TPU would
refuse on the chip, it refuses here, at no chip time. Interpret mode
cannot show this: ``sorted_segment_mean_pallas`` passed every
interpret-mode test and still failed to lower ("cannot statically
prove that index in dimension 1 is a multiple of 128"), and the mesh
launcher failed with "Mosaic kernels cannot be automatically
partitioned".

A compile that passes is not a chip run: nothing executes here, so
these tests say nothing about results or times (``chip_smoke.py`` does).

Rules this file keeps (on-chip-measurement guide, section 2): the
topology is described inside a module-scoped fixture, never at import
and never in a ``parametrize``/``skipif`` argument — only one process
may hold libtpu, and every xdist worker imports every test file; the
compiles run in the test's own process; the persistent compilation
cache is off around them (an entry compiled for an unattached chip
cannot be read back); and they all live in this ONE file, so one
worker holds the library for all of them.
"""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

N_POINTS = 131072  # the largest served point bucket


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


def _compile(fn, one_chip, *shapes, **static):
    """Compile ``fn`` for the described chip; returns the executable's
    text. ``shapes``: (shape, dtype) pairs placed on that chip."""
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    return jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static
    ).compile().as_text()


def test_described_chip_is_a_v5e(topo):
    from triton_client_tpu.obs.roofline import DEVICE_PEAKS

    # the kind the peak table is keyed by is the kind the compiler
    # targets here and the chip reports there
    assert topo.devices[0].device_kind in DEVICE_PEAKS


@pytest.mark.parametrize("num_slots", [40000, 16000])
@pytest.mark.parametrize("pipeline", ["grid", "manual"])
def test_voxel_segment_mean_lowers(one_chip, pipeline, num_slots):
    """Both pipelining forms at the KITTI SECOND (40k) and PointPillars
    (16k) voxel budgets over the 131072-point bucket. The whole
    (8, v_out) accumulator is VMEM-resident: ~1.3 MB at 40k slots."""
    from triton_client_tpu.ops.pallas_voxel import sorted_segment_mean_pallas

    text = _compile(
        sorted_segment_mean_pallas, one_chip,
        ((8, N_POINTS), jnp.float32), ((N_POINTS,), jnp.int32),
        num_slots=num_slots, pipeline=pipeline,
    )
    assert "tpu_custom_call" in text


def _decode_nms_2d_text(one_chip, batch):
    from triton_client_tpu.ops.pallas_decode import fused_decode_nms_2d

    k = 1024  # Detect2DConfig.max_nms
    return _compile(
        lambda b, s, c, v: fused_decode_nms_2d(b, s, c, v, max_det=300),
        one_chip,
        ((batch, k, 4), jnp.float32), ((batch, k), jnp.float32),
        ((batch, k), jnp.int32), ((batch, k), jnp.bool_),
    )


def test_decode_nms_2d_lowers_b8(one_chip):
    """One whole group: eight frames, one a sublane, one grid step."""
    assert "tpu_custom_call" in _decode_nms_2d_text(one_chip, 8)


def test_decode_nms_2d_lowers_b11(one_chip):
    """A second group padded with five frames that hold no candidate."""
    assert "tpu_custom_call" in _decode_nms_2d_text(one_chip, 11)


def test_decode_tail_3d_lowers_b2(one_chip):
    from triton_client_tpu.ops.pallas_decode import (
        fused_residual_decode,
        fused_suppress_pack_3d,
    )

    k = 256  # Detect3DConfig.pre_max

    def tail(deltas, anchors, dir_bin, scores, labels):
        boxes = jax.vmap(
            lambda d, a, b: fused_residual_decode(
                d, a, b, num_dir_bins=2, dir_offset=0.78539
            )
        )(deltas, anchors, dir_bin)
        return jax.vmap(
            lambda b, s, l: fused_suppress_pack_3d(b, s, l, max_det=128)
        )(boxes, scores, labels)

    text = _compile(
        tail, one_chip,
        ((2, k, 7), jnp.float32), ((2, k, 7), jnp.float32),
        ((2, k), jnp.int32), ((2, k), jnp.float32), ((2, k), jnp.int32),
    )
    assert text.count("tpu_custom_call") >= 2


def test_segment_sum_lowers(one_chip):
    from triton_client_tpu.parallel.ragged_kernels import segment_sum_pallas

    text = _compile(
        lambda v, ids: segment_sum_pallas(v, ids, num_segments=8),
        one_chip, ((1024, 64), jnp.float32), ((1024,), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_sparse_selection_kernels_lower_at_the_served_slot(one_chip):
    """The three kernels of an extend launch of ``family: deepseek_v32``
    at ``examples/dsv32_ep32``'s sizes: 4,096 queries against a slot of
    34,048 positions (index scores, the threshold of each row, attention
    under the selection a segment of 4,864 positions at a time)."""
    from triton_client_tpu.ops import latent_attention, sparse_index

    t, s_len, h = 4096, 34048, 128
    assert sparse_index.kernel_fits(t, s_len, 64, 128) and sparse_index.kth_kernel_fits(t, s_len)
    assert latent_attention.selected_kernel_fits(t, s_len, 128, 128)

    def selected(q, w, keys, positions, q_nope, q_rope, rows, kv_b):
        scores = sparse_index.extend_scores(q, w, keys, positions, kernel=True)
        tau = sparse_index.kth_largest(scores, jnp.minimum(positions + 1, 2048), last=positions[-1], kernel=True)
        return latent_attention.expanded_attention(
            q_nope, q_rope, rows, positions, kv_b, 0.1, 128, (scores, tau), kernel=True)

    text = _compile(
        selected, one_chip,
        ((t, 64, 128), jnp.bfloat16), ((t, 64), jnp.float32), ((s_len, 128), jnp.bfloat16), ((t,), jnp.int32),
        ((t, h, 128), jnp.bfloat16), ((t, h, 64), jnp.bfloat16), ((s_len, 640), jnp.bfloat16),
        ((512, h, 256), jnp.bfloat16),
    )
    assert text.count("tpu_custom_call") >= 3


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


def _ling_launch(one_chip, model: dict, slots: int, slot_len: int, launch: dict, monkeypatch,
                 family: str = "bailing_hybrid"):
    """One launch shape of ``family: bailing_hybrid`` (or of another
    ``family`` whose layers attend over latent rows) compiled as
    ``ParamLauncher`` launches it: weights and the three cache arrays as
    arguments, the cache donated and row-major on both sides, the KDA
    core and a step's latent attention as on the chip (the Pallas
    kernels, not their plain or interpreted forms).
    Returns the executable's text and the configuration."""
    from jax.experimental.layout import Format, Layout
    from triton_client_tpu.ops import delta_attention, latent_attention
    from triton_client_tpu.pipelines import lm

    monkeypatch.setattr(delta_attention, "on_chip", lambda: True)
    monkeypatch.setattr(latent_attention, "on_chip", lambda: True)
    ling = lm.MODULES[family]
    cfg = ling.Config.from_dict(model)
    placed = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    weights = placed(jax.eval_shape(lambda: ling.stack_layers(ling.init_params(jax.random.PRNGKey(0), cfg), cfg)))
    cache = placed(jax.eval_shape(lambda: ling.empty_cache(cfg, slots, slot_len)))
    row_major = jax.tree_util.tree_map(
        lambda x: Format(Layout(major_to_minor=tuple(range(x.ndim))), one_chip), cache)
    ((kind, size),) = launch.items()
    inputs = placed({k: jnp.asarray(v) for k, v in lm.launch_inputs(kind, size).items()})
    device_fn = lm.make_device_fn.__wrapped__(ling, cfg)  # traced here, with the probe steered: not the memoized one

    def run(inputs, weights, cache):
        out = dict(device_fn(inputs, {"weights": weights, lm.STATE_KEY: cache}))
        return out, out.pop(lm.STATE_KEY)

    return jax.jit(
        run, donate_argnums=(2,), in_shardings=(None, None, row_major), out_shardings=(None, row_major),
    ).lower(inputs, weights, cache).compile().as_text(), cfg


def _ling_config(name: str = "ling3flash-ep8-l13") -> dict:
    import json
    import pathlib

    return json.loads((pathlib.Path(__file__).resolve().parents[1] / f"benchmarks/configs/{name}.json").read_text())


@pytest.mark.parametrize("launch", ({"extend": 128}, {"extend": 256}, {"step": 8}))
def test_ling_launch_kinds_lower_at_the_tiny_preset(one_chip, launch, monkeypatch):
    """Both launch kinds of the benchmark configuration's rehearsal (heads
    of 16 values: the chunkwise form in plain XLA, ``kernel_fits`` says
    no), the scan over periods with its inner scan over KDA layers."""
    doc = _ling_config()
    model = {**doc["model"], **doc["rehearsal"]["model"]}
    slot_len = model.pop("slot_len")
    model.pop("max_tokens")
    text, cfg = _ling_launch(one_chip, model, 8, slot_len, launch, monkeypatch)
    ((kind, size),) = launch.items()
    assert f"f32[{size if kind == 'step' else 1},{cfg.vocab_size}]" in text


def test_lm_kda_chunk_lowers_at_the_served_head_size(one_chip):
    """The Pallas kernel of an extend launch of ``examples/ling3_ep8``:
    4,096 positions of 32 heads of 128 values, a head's state resident
    across its 64 chunks."""
    from triton_client_tpu.ops import delta_attention

    t, h, d = 4096, 32, 128
    assert delta_attention.kernel_fits(d) and not delta_attention.kernel_fits(16)
    text = _compile(
        lambda q, k, v, g, beta, s0: delta_attention.extend(q, k, v, g, beta, s0, kernel=True), one_chip,
        *[((t, h, d), jnp.float32)] * 4, ((t, h), jnp.float32), ((h, d, d), jnp.float32),
    )
    assert "tpu_custom_call" in text and "lm_kda_chunk" in text


@pytest.mark.parametrize("launch", ({"extend": 1024}, {"step": 8}))
def test_ling_launches_update_the_three_caches_in_place(one_chip, launch, monkeypatch):
    """At the served widths and the served slots (one dense layer and one
    period of a KDA and an MLA layer of the 13) the latent rows, the
    recurrent state and the convolution tails are donated together and
    keep their row-major layouts through both scans: no launch begins or
    ends with a copy of any of them."""
    model = {**_ling_config()["model"], "num_hidden_layers": 3, "layer_types": ["kda", "kda", "mla"]}
    slot_len = model.pop("slot_len")
    model.pop("max_tokens")
    text, cfg = _ling_launch(one_chip, model, 8, slot_len, launch, monkeypatch)
    assert ("lm_kda_chunk/pallas_call" in text) == ("extend" in launch)  # the kernel, under its own name
    for whole in (f"bf16[1,8,{slot_len},640]", "f32[2,8,32,128,128]", "bf16[2,8,36864]"):
        assert whole in text
        assert not [line for line in text.splitlines() if f"= {whole}" in line and " copy(" in line]


def test_ling_step_launch_reads_single_experts_in_place(one_chip, monkeypatch):
    """At the served widths (the dense layer and one whole period of the
    13 layers: five KDA layers under the inner scan, an MLA layer under
    the outer one) the step launch yields no layer's experts: no copy,
    slice or fusion whose result is ``[64, 2560, 768]``; what it slices
    from the stacks is ONE expert at (layer, expert), inside the fusion
    of the product that reads it. A layer's slice handed to the loop over
    the chosen experts would be written out first, 757 MB a layer."""
    import re

    model = {**_ling_config()["model"], "num_hidden_layers": 7, "layer_types": ["kda"] * 6 + ["mla"]}
    slot_len = model.pop("slot_len")
    model.pop("max_tokens")
    text, cfg = _ling_launch(one_chip, model, 8, slot_len, {"step": 8}, monkeypatch)
    e, d, f = cfg.experts_here, cfg.hidden_size, cfg.moe_intermediate_size
    whole = re.compile(rf"= bf16\[(1,)?{e},({d},{f}|{f},{d})\]\S* (copy|fusion|dynamic-slice|bitcast)\(")
    assert not [line for line in text.splitlines() if whole.search(line)]
    assert text.count(f"dynamic_slice_sizes={{1,1,{d},{f}}}") >= 4  # gate and up, under either scan
    for stack in (f"bf16[5,{e},{d},{f}]", f"bf16[1,{e},{d},{f}]"):  # a fused slice: the stack in, one matrix out
        assert [line for line in text.splitlines()
                if line.startswith("%fused_computation") and f": {stack}" in line and f"-> bf16[{d},{f}]" in line]


@pytest.mark.parametrize("name, rows, cut", [
    ("ling3flash-ep8-l13", 8, {"num_hidden_layers": 7, "layer_types": ["kda"] * 6 + ["mla"]}),
    ("dsv32-ep32-l6", 8, {"num_hidden_layers": 2}),
    ("axk1-ep16-l6", 16, {"num_hidden_layers": 2}),
])
def test_step_launches_read_latent_rows_in_place(one_chip, monkeypatch, name, rows, cut):
    """At the served widths, slots and slot lengths of the three
    configurations whose step launch runs ``absorbed_attention`` (a
    dense layer and one layer, or one period, of each: 32, 128 and 64
    heads): where the slot is long (62,720 and 34,048 positions) the
    kernel ``lm_latent_decode`` lowers, and no op of the launch yields a
    slot's rows: nothing of ``[slot_len, cache_row]`` (or of its latent
    part) outside a fusion, where the parent's
    ``dynamic-slice_bitcast_fusion`` wrote 80 MB a row out before
    anything was multiplied. The slot of 4,352 positions is taken whole,
    by the form it always had: no kernel, and the slice is there."""
    import re

    doc = _ling_config(name)
    model = {**doc["model"], **cut}
    slot_len = model.pop("slot_len")
    model.pop("max_tokens")
    text, cfg = _ling_launch(one_chip, model, doc["max_batch_size"], slot_len, {"step": rows}, monkeypatch, doc["family"])
    block, layers = cfg.step_key_blocks(slot_len)
    assert (block == slot_len) == (name == "axk1-ep16-l6") and slot_len % block == 0 and layers
    whole = re.compile(rf"= bf16\[(1,)*{slot_len},({cfg.cache_row}|{cfg.kv_lora_rank})\]")
    fused, written = False, []
    for line in text.splitlines():
        if line.endswith("{"):  # a computation opens: a fusion's body, or one whose ops each run
            fused = line.lstrip("%").startswith("fused_computation")
        elif not fused and whole.search(line) and " parameter(" not in line:
            written.append(line.strip()[:160])
    assert ("lm_latent_decode" in text) == (block < slot_len) == (not written)


def test_nms_pallas_lowers(one_chip):
    from triton_client_tpu.ops.pallas_nms import nms_pallas

    text = _compile(
        nms_pallas, one_chip,
        ((1024, 4), jnp.float32), ((1024,), jnp.float32), max_det=300,
    )
    assert "tpu_custom_call" in text


def _registered(model_dir):
    from triton_client_tpu.runtime.disk_repository import build_model
    from triton_client_tpu.runtime.repository import ModelRepository

    model = build_model(model_dir)
    repo = ModelRepository()
    repo.register(model.spec, model.infer_fn, device_fn=model.device_fn)
    return repo, repo.get(model.spec.name, model.spec.version)


def _compile_launcher(channel, model, structs):
    """Compile the serving channel's OWN launcher for a model (the jit
    the channel caches: donation split, named module, shardings) at
    the given argument structs."""
    launcher, donate, _ = channel._make_launcher(model)
    return launcher.lower(
        {k: v for k, v in structs.items() if k in donate},
        {k: v for k, v in structs.items() if k not in donate},
    ).compile()


def _launcher_text(model_dir, inputs, one_chip):
    from triton_client_tpu.channel.tpu_channel import TPUChannel

    repo, model = _registered(model_dir)
    structs = {
        name: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for name, (shape, dtype) in inputs.items()
    }
    return model, _compile_launcher(TPUChannel(repo), model, structs).as_text()


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


@pytest.fixture()
def tpu_route(monkeypatch):
    """``fused: auto`` asks ``jax.default_backend()``, which says cpu
    here; steer the one probe every kernel call site shares so the
    pipelines build the route they take on the chip — compiled
    kernels, not interpreted ones. The program gets no option."""
    from triton_client_tpu.ops import fused

    monkeypatch.setattr(fused, "fused_interpret", lambda: False)


def test_yolov5_b8_launcher_compiles(one_chip, tpu_route):
    """examples/yolov5_crop as served: 512x512, b8, the fused
    decode+NMS tail inside the real program."""
    model, text = _launcher_text(
        "examples/yolov5_crop",
        {"images": ((8, 512, 512, 3), jnp.float32)},
        one_chip,
    )
    assert model.spec.extra["fused_stages"] == ["decode_nms"]
    assert "tpu_custom_call" in text


def test_yolov5_mesh_launcher_compiles_for_four_chips(topo, tpu_route):
    """``serve --mesh data=4``: the sharded channel's launcher over the
    four described chips. Under plain SPMD partitioning this fails with
    "Mosaic kernels cannot be automatically partitioned. Please wrap
    the call in a shard_map" — the channel runs a fused body per
    shard. Each device then holds 2 of the 8 frames, the kernel, and
    no collective."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from triton_client_tpu.channel.sharded_channel import ShardedTPUChannel
    from triton_client_tpu.parallel.mesh import MeshConfig

    repo, model = _registered("examples/yolov5_crop")
    channel = ShardedTPUChannel(
        repo, mesh_config=MeshConfig(data=4), devices=list(topo.devices)
    )
    frames = jax.ShapeDtypeStruct(
        (8, 512, 512, 3), jnp.float32,
        sharding=NamedSharding(channel.fetch_channel(), P("data")),
    )
    compiled = _compile_launcher(channel, model, {"images": frames})
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not any(
        op in text for op in ("all-reduce", "all-gather", "all-to-all")
    )
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device == 2 * 512 * 512 * 3 * 4


def test_second_fused_launcher_compiles(one_chip, tpu_route):
    """examples/second_iou at the 131072-point bucket with BOTH fused
    stages — the launcher that held the kernel Mosaic refused — so
    VMEM and HBM limits are met at full width with the kernels inside
    the real program."""
    model, text = _launcher_text(
        "examples/second_iou",
        {
            "points": ((N_POINTS, 4), jnp.float32),
            "num_points": ((), jnp.int32),
        },
        one_chip,
    )
    assert model.spec.extra["fused_stages"] == [
        "voxelize_scatter", "decode_nms",
    ]
    # voxelize_scatter + residual decode + suppress/pack
    assert text.count("tpu_custom_call") >= 3
