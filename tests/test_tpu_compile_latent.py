"""Ask the TPU's compiler before the chip is asked: the token families
whose layers attend over latent rows (A.X-K1, DeepSeek-V3.2, Ling).

The kernels of their launches at the served shapes (the sparse
selection, ``lm_kda_chunk``, ``lm_latent_decode``) and whole launch
programs as ``ParamLauncher`` launches them, compiled by the installed
libtpu for a DESCRIBED v5e (no device attached): what Mosaic or XLA:TPU
would refuse on the chip, it refuses here, at no chip time, and the
executable's text shows what a launch copies.

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
