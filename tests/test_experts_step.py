"""A step launch that is given the layers' stacks reads the held experts
its rows chose (ops/experts.py ``routed_experts``, launches of at most
``DENSE_TOKENS`` tokens): that loop against the plain product over every
held expert, which a launch given a layer's own experts still takes;
what the lowered programs hold; and the server's count of the experts
chosen (runtime/sessions.py ``lm_step_experts_chosen`` / ``_held``).
"""

from __future__ import annotations

import json
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_client_tpu.channel.base import InferRequest
from triton_client_tpu.ops import experts as experts_op
from triton_client_tpu.pipelines import lm
from triton_client_tpu.runtime.sessions import TokenSessions

ROOT = pathlib.Path(__file__).resolve().parents[1]
D, F = 32, 16  # reduced widths


def _dense(x, valid, idx, gates, experts, expert_offset):
    """Every held expert over every row, weighted by the gates."""
    held = experts["gate"].shape[0]
    local = idx - expert_offset
    here = (local >= 0) & (local < held) & valid[:, None]
    weight = jnp.sum(
        jnp.where(here[:, :, None] & (local[:, :, None] == jnp.arange(held)), gates[:, :, None], 0.0), axis=1)
    act = jax.nn.silu(jnp.einsum("td,edf->etf", x, experts["gate"])) * jnp.einsum("td,edf->etf", x, experts["up"])
    y = jnp.einsum("etf,efd->etd", act, experts["down"]).astype(jnp.float32)
    return jnp.einsum("etd,te->td", y, weight), jnp.sum(weight > 0, axis=0, dtype=jnp.int32)


def _experts(held, lead=(), seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    draw = lambda key, shape, scale: (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.bfloat16)
    return {"gate": draw(k[0], (*lead, held, D, F), D**-0.5), "up": draw(k[1], (*lead, held, D, F), D**-0.5),
            "down": draw(k[2], (*lead, held, F, D), F**-0.5)}


def _routing(t, k, held, total, seed):
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.permutation(total)[:k] for _ in range(t)]).astype(np.int32)
    return idx, rng.uniform(0.1, 1.0, (t, k)).astype(np.float32)


# (held, the model's experts, offset, rows, top-k, valid rows or None for all, what the rows choose)
CASES = {
    "no_row_routed_here": (8, 32, 8, 8, 4, None, "elsewhere"),
    "every_slot_routed_here": (8, 8, 0, 8, 4, None, "random"),
    "several_rows_on_one_expert": (12, 48, 0, 8, 4, None, "one"),
    "pad_rows_choose_experts": (12, 48, 0, 8, 4, 3, "pads_here"),
    "one_row": (8, 32, 0, 1, 4, None, "random"),
    "one_row_of_a_padded_launch": (64, 512, 0, 8, 8, 1, "random"),
    "offset_above_zero": (8, 32, 16, 8, 4, None, "random"),
    "held_8": (8, 64, 0, 8, 8, 5, "random"),
    "held_12": (12, 192, 24, 40, 8, 10, "random"),
    "held_64": (64, 512, 0, 8, 8, 4, "random"),
    "held_64_all_chosen": (64, 64, 0, 64, 8, None, "random"),
}


@pytest.mark.parametrize("stacked", [False, True], ids=["a_layers_own", "from_the_stack"])
@pytest.mark.parametrize("case", CASES)
def test_the_chosen_experts_alone_give_the_dense_product(case, stacked):
    """The sum and the rows each expert saw equal the product over every
    held expert, whatever the rows chose. From the stack, every expert
    that no valid row chose is NaN in what the loop is given: had it
    been read into the sum, the sum would say so. (A layer's own experts
    all go through the product: they stay as they are.)"""
    held, total, offset, t, k, n_valid, choice = CASES[case]
    idx, gates = _routing(t, k, held, total, seed=len(case))
    valid = np.arange(t) < (t if n_valid is None else n_valid)
    if choice == "elsewhere":
        idx = np.where((idx >= offset) & (idx < offset + held), (idx + held) % total, idx).astype(np.int32)
    elif choice == "one":
        idx[:, 0] = offset + 5  # every row's first choice
        idx[:, 1:] = np.where(idx[:, 1:] == offset + 5, offset + held, idx[:, 1:])
    elif choice == "pads_here":
        idx[~valid] = offset + np.arange(k)  # the pad rows choose experts 0..k-1 held here
        idx[valid] = np.where(idx[valid] < offset + k, idx[valid] + held, idx[valid])  # no valid row does
    experts = _experts(held, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(1), (t, D), jnp.float32).astype(jnp.bfloat16)
    want, want_rows = jax.jit(_dense, static_argnums=5)(
        x, jnp.asarray(valid), jnp.asarray(idx), jnp.asarray(gates), experts, offset)
    local = idx - offset
    chosen = np.unique(local[(local >= 0) & (local < held) & valid[:, None]])
    poison = jnp.asarray(~np.isin(np.arange(held), chosen))[:, None, None]
    given = experts
    if stacked:
        given = {n: jnp.where(poison, jnp.nan, a).astype(a.dtype) for n, a in experts.items()}
        others = _experts(held, (3,), seed=4)
        given = {n: others[n].at[1].set(a) for n, a in given.items()}
        run = jax.jit(lambda layer: experts_op.routed_experts(
            x, jnp.asarray(valid), jnp.asarray(idx), jnp.asarray(gates), given, offset, layer=layer))
        got, rows = run(jnp.int32(1))
    else:
        got, rows = jax.jit(lambda: experts_op.routed_experts(
            x, jnp.asarray(valid), jnp.asarray(idx), jnp.asarray(gates), given, offset))()
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(want_rows))
    assert np.count_nonzero(np.asarray(rows)) == len(chosen)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)
    if choice == "elsewhere":
        assert not np.asarray(got).any() and not np.asarray(rows).any()
    if choice == "pads_here":
        assert not np.asarray(rows)[:k].any()  # chosen by pad rows alone: not counted, and (NaN) not read


def _ops(text: str, name: str) -> list[str]:
    return [line for line in text.splitlines() if f"stablehlo.{name}" in line or f"chlo.{name}" in line]


def _lowered(fn, *args) -> str:
    """``fn`` as it is lowered for a TPU (no device needed): there a
    grouped product is one ``ragged_dot``, which a CPU's lowering takes
    apart."""
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()


def test_the_step_program_of_ling3_ep8_holds_no_product_over_all_held_experts():
    """The lowered step launch of ``examples/ling3_ep8`` at the
    rehearsal's sizes: no product takes a layer's experts ``[E, D, F]``
    whole, and no operation yields them (a layer's slice of the stack
    handed to the loop would be written out first): an expert is sliced
    from the stack of all layers at (layer, expert). Its extend launch
    still slices a layer's experts for the grouped product."""
    from triton_client_tpu.models import ling

    doc = json.loads((ROOT / "benchmarks/configs/ling3flash-ep8-l13.json").read_text())
    model = {**doc["model"], **doc["rehearsal"]["model"]}
    slot_len = model.pop("slot_len")
    model.pop("max_tokens")
    cfg = ling.LingConfig.from_dict(model)
    weights = jax.eval_shape(lambda: ling.stack_layers(ling.init_params(jax.random.PRNGKey(0), cfg), cfg))
    cache = jax.eval_shape(lambda: ling.empty_cache(cfg, 8, slot_len))
    device_fn = lm.make_device_fn(ling, cfg)
    run = lambda inputs, weights, cache: device_fn(inputs, {"weights": weights, lm.STATE_KEY: cache})

    def text(kind, size):
        inputs = {k: jnp.asarray(v) for k, v in lm.launch_inputs(kind, size).items()}
        return _lowered(run, inputs, weights, cache)

    e, d, f = cfg.experts_here, cfg.hidden_size, cfg.moe_intermediate_size
    layer = re.compile(rf"tensor<(1x)?{e}x({d}x{f}|{f}x{d})xbf16>")
    one = re.compile(rf"-> tensor<1x1x({d}x{f}|{f}x{d})xbf16>")
    step = text("step", 8)
    assert not [line for line in _ops(step, "dot_general") if layer.search(line)]
    assert not [line for line in step.splitlines() if layer.search(line.split("->")[-1]) and "->" in line
                and "func.func" not in line and "stablehlo.while" not in line]
    assert len([line for line in _ops(step, "dynamic_slice") if one.search(line)]) >= 3
    assert not _ops(step, "ragged_dot")
    extend = text("extend", 128)
    assert _ops(extend, "ragged_dot") and not [line for line in _ops(extend, "dynamic_slice") if one.search(line)]


@pytest.mark.parametrize("stacked", [False, True], ids=["a_layers_own", "from_the_stack"])
def test_what_a_small_launch_lowers_to(stacked):
    """Given a layer's own experts: products over all of them, no loop
    (the branch as it was; a loop over single experts of a slice would
    have the slice written out first). Given the stacks and the layer's
    place: a loop whose products take one expert, sliced at (layer,
    expert), and no product over a layer's experts."""
    held, t, k = 8, 8, 4
    idx, gates = _routing(t, k, held, 32, seed=1)
    x = jnp.zeros((t, D), jnp.bfloat16)
    args = (jnp.ones(t, bool), jnp.asarray(idx), jnp.asarray(gates))
    whole = re.compile(rf"tensor<{held}x({D}x{F}|{F}x{D})xbf16>")
    if stacked:
        text = _lowered(lambda x, s, i: experts_op.routed_experts(x, *args, s, 0, layer=i), x, _experts(held, (3,)), jnp.int32(1))
        assert "stablehlo.while" in text and not [line for line in _ops(text, "dot_general") if whole.search(line)]
        assert len([line for line in _ops(text, "dynamic_slice") if f"-> tensor<1x1x{D}x{F}xbf16>" in line]) == 2
    else:
        text = _lowered(lambda x, e: experts_op.routed_experts(x, *args, e, 0), x, _experts(held))
        assert "stablehlo.while" not in text and len([line for line in _ops(text, "dot_general") if whole.search(line)]) == 3


@pytest.mark.parametrize("t", [experts_op.DENSE_TOKENS + 64, 2048])
def test_a_launch_of_more_tokens_lowers_to_the_grouped_product_as_before(t):
    """More than ``DENSE_TOKENS`` tokens: three grouped products over a
    layer's experts, no loop over single experts (a loop only over
    chunks of rows, where the token-slots pass ``chunk_rows``), and given
    the stack and the layer's place the operations of the program that is given
    the layer's slice."""
    held, k, chunk = 8, 4, 1024
    idx, gates = _routing(t, k, held, 32, seed=t)
    x = jnp.zeros((t, D), jnp.bfloat16)
    args = (jnp.ones(t, bool), jnp.asarray(idx), jnp.asarray(gates))
    own = _lowered(lambda x, e: experts_op.routed_experts(x, *args, e, 0, chunk), x, _experts(held))
    assert len(_ops(own, "ragged_dot")) == 3 and not _ops(own, "dot_general")
    assert ("stablehlo.while" in own) == (t * k > chunk)
    assert not [line for line in _ops(own, "dynamic_slice") if f"x{D}x{F}xbf16" in line or f"x{F}x{D}xbf16" in line]
    stack = _experts(held, (3,))
    placed = _lowered(lambda x, s, i: experts_op.routed_experts(x, *args, s, 0, chunk, layer=i), x, stack, jnp.int32(1))
    sliced = _lowered(lambda x, s, i: experts_op.routed_experts(
        x, *args, {n: jax.lax.dynamic_index_in_dim(a, i, 0, False) for n, a in s.items()}, 0, chunk), x, stack, jnp.int32(1))
    # the same operations on the same types, whatever order the tracer met them in
    ops = lambda text: sorted(
        (m.group(1), line.rpartition(" : ")[2]) for line in text.splitlines()
        if (m := re.search(r"((?:stablehlo|chlo)\.[a-z_]+)", line)) and "constant" not in m.group(1))
    assert ops(placed) == ops(sliced)


# -- the server's count ----------------------------------------------------------------


def _send(state, sid, tokens, rows, **kw):
    request, ticket = state.open(InferRequest("m", {"tokens": tokens, **kw.pop("inputs", {})}, sequence_id=sid, **kw))
    answers = request.inputs["tokens"].shape[0]
    state.close(ticket, {"logits": np.zeros((answers, 4), np.float32), TokenSessions.EXPERT_ROWS: np.asarray(rows)})
    return ticket.kind


def test_step_launches_alone_count_the_experts_they_read():
    """``lm_step_experts_chosen``: held experts (summed over expert layers)
    that some row of a STEP launch chose; ``lm_step_experts_held``:
    expert layers x experts held, a step launch. A turn of many tokens
    (the grouped product) and a launch of plain arrays (a compile) count
    in neither; ``expert_rows`` counts them all."""
    state = TokenSessions(2, 64, 32, lm.token_bucket, lambda n: lm.step_bucket(n, 2), time_fn=lambda: 0.0)
    ids = lambda n: np.zeros((1, n), np.int32)
    turn = np.full((3, 4), 6)
    assert _send(state, "a", ids(12), turn, sequence_start=True) == "lm_prefill"
    assert (state.stats()["lm_step_experts_chosen"], state.stats()["lm_step_experts_held"]) == (0, 0)
    assert _send(state, "a", ids(1), [[1, 0, 0, 2], [0, 0, 0, 0], [0, 3, 0, 0]]) == "lm_step"
    assert (state.stats()["lm_step_experts_chosen"], state.stats()["lm_step_experts_held"]) == (3, 12)
    assert _send(state, "a", ids(1), np.zeros((3, 4), int)) == "lm_step"  # routed elsewhere: held, none read
    assert (state.stats()["lm_step_experts_chosen"], state.stats()["lm_step_experts_held"]) == (3, 24)
    assert _send(state, "a", ids(8), turn) == "lm_prefill"
    stats = state.stats()
    assert (stats["lm_step_experts_chosen"], stats["lm_step_experts_held"], stats["lm_step_launches"]) == (3, 24, 2)
    assert np.asarray(stats["expert_rows"]).sum() == 2 * turn.sum() + 6


def test_block_launches_count_no_step_experts():
    state = TokenSessions(slots=8, slot_len=16, max_tokens=8, token_bucket=lm.token_bucket,
                          step_bucket=lambda n: lm.step_bucket(n, 8), block=4)
    ids = lambda n: np.zeros((1, n), np.int32)
    rows = np.ones((2, 4), int)
    assert _send(state, "a", ids(8), rows, sequence_start=True) == "lm_prefill"
    assert _send(state, "a", ids(4), rows, inputs={"commit": np.ones((1, 1), np.int32)}) == "lm_block"
    stats = state.stats()
    assert (stats["lm_step_experts_chosen"], stats["lm_step_experts_held"], stats["lm_block_launches"]) == (0, 0, 1)
    assert np.asarray(stats["expert_rows"]).sum() == 16
