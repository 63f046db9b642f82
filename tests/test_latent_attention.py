"""``ops/latent_attention.absorbed_attention`` (a step launch's form)
against the whole-slot form written out plainly: every row's slot
sliced out whole, scores over all of its positions, the mask afterwards,
float32 throughout. A long slot is read in place by one kernel, a block
of positions at a time up to the block that holds the row's position
(``step_block``), a short one is taken whole, so what is held here is
that no position a row may attend to is left out and none past it comes
in: at a block's edges, in a slot taken whole and in one of several
blocks, with and without a selection, for pad rows alone and beside
real ones, and with garbage in the slot past a row's position. Beside it the account of what a step
launch fetches (runtime/sessions.py ``lm_step_keys_fetched`` /
``_whole``), by the block the model's ``Config`` states. CPU, tiny sizes."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from triton_client_tpu.channel.base import InferRequest  # noqa: E402
from triton_client_tpu.models import axk1, ling  # noqa: E402
from triton_client_tpu.ops import latent_attention  # noqa: E402
from triton_client_tpu.pipelines import lm  # noqa: E402
from triton_client_tpu.runtime.sessions import TokenSessions  # noqa: E402

LAYERS, SLOTS, S_LEN, ROW = 2, 8, 512, 128
ROWS, HEADS, NOPE, ROPE, RANK, V = 8, 4, 16, 8, 16, 16
BLOCK = 128  # the block of the "several" cases: the slot is four of them
TOP_K = 40
SCALE = 0.3
LAYER = 1


def whole_slot_attention(q_nope, q_rope, kv, layer, slots, positions, kv_b, select=None):
    """The form every step launch ran until PR 49, in float32."""
    f32 = lambda a: np.asarray(a, np.float32)
    q_nope, q_rope, kv, kv_b = f32(q_nope), f32(q_rope), f32(kv), f32(kv_b)
    out = np.zeros((len(slots), HEADS, V), np.float32)
    for b, (slot, pos) in enumerate(zip(slots, positions)):
        q = np.concatenate([np.einsum("hd,chd->hc", q_nope[b], kv_b[..., :NOPE]), q_rope[b]], axis=-1)
        rows = kv[layer, slot]
        scores = q @ rows[:, : RANK + ROPE].T * SCALE
        keep = np.arange(S_LEN) <= pos
        if select is not None:
            keep = keep & (np.asarray(select[0][b]) >= np.asarray(select[1][b]))
        scores = np.where(keep, scores, -np.inf)
        w = np.exp(scores - scores.max(axis=-1, keepdims=True))
        w = np.where(keep, w / w.sum(axis=-1, keepdims=True), 0.0)
        out[b] = np.einsum("hc,chd->hd", w[:, keep] @ rows[keep, :RANK], kv_b[..., NOPE:])
    return out


@functools.lru_cache(maxsize=None)
def _program(blocks: str, selected: bool):
    """One jitted program a block rule and form: traced at its first
    call, under the ``STEP_BLOCK`` that the calling case patched in."""
    return jax.jit(lambda q_nope, q_rope, kv, slots, positions, kv_b, *select: latent_attention.absorbed_attention(
        q_nope, q_rope, kv, LAYER, slots, positions, kv_b, SCALE, NOPE, select or None))


def _inputs(seed: int):
    rng = np.random.default_rng(seed)
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)
    kv = rng.normal(size=(LAYERS, SLOTS, S_LEN, ROW)).astype(np.float32)
    kv[..., RANK + ROPE :] = 0.0  # a cache row's zero tail
    return (
        bf(rng.normal(size=(ROWS, HEADS, NOPE))), bf(rng.normal(size=(ROWS, HEADS, ROPE))), bf(kv),
        bf(rng.normal(size=(RANK, HEADS, NOPE + V)) * RANK**-0.5), rng,
    )


def _selection(rng, positions):
    """Index scores ``[B, S]`` (minus infinity past a row's position) and
    each row's threshold: its ``min(pos + 1, TOP_K)``-th largest."""
    scores = rng.normal(size=(len(positions), S_LEN)).astype(np.float32)
    scores = np.where(np.arange(S_LEN)[None] <= np.asarray(positions)[:, None], scores, -np.inf)
    tau = np.array([np.sort(row[: p + 1])[::-1][min(p + 1, TOP_K) - 1] for row, p in zip(scores, positions)], np.float32)
    return jnp.asarray(scores), jnp.asarray(tau)


# positions of the eight rows; a pad row is slot 0, position 0 (pipelines/lm.py ``launch_inputs``)
EDGES = {"first": 0, "block_end": BLOCK - 1, "block_start": BLOCK, "slot_end": S_LEN - 1}
CASES = [*EDGES, "pads_alone", "pads_beside", "garbage"]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("selected", [False, True], ids=["dense", "selected"])
@pytest.mark.parametrize("blocks", ["whole", "several"])
def test_absorbed_attention_reads_what_the_whole_slot_form_reads(monkeypatch, blocks, selected, case):
    if blocks == "several":  # the kernel: the slot counts as long, and is four blocks
        monkeypatch.setattr(latent_attention, "SEGMENT_ROWS", 2 * BLOCK)
        monkeypatch.setattr(latent_attention, "STEP_BLOCK", BLOCK)
    assert latent_attention.step_block(S_LEN) == (BLOCK if blocks == "several" else S_LEN)
    q_nope, q_rope, kv, kv_b, rng = _inputs(len(case) + 2 * selected)
    slots = np.arange(ROWS, dtype=np.int32)[::-1].copy()
    real = ROWS
    if case in EDGES:
        # the edge itself beside positions around it and elsewhere in the slot
        positions = np.array([EDGES[case], 1, BLOCK - 2, BLOCK + 1, 2 * BLOCK - 1, 2 * BLOCK, 300, S_LEN - 2], np.int32)
    elif case == "pads_alone":
        real, slots, positions = 0, np.zeros(ROWS, np.int32), np.zeros(ROWS, np.int32)
    else:
        positions = np.array([0, 77, BLOCK - 1, BLOCK, 201, 2 * BLOCK + 5, 3 * BLOCK, S_LEN - 1], np.int32)
    if case == "pads_beside":
        real = 3
        slots[real:], positions[real:] = 0, 0
    select = _selection(rng, positions) if selected else ()
    run = _program(blocks, selected)
    got = np.asarray(run(q_nope, q_rope, kv, slots, positions, kv_b, *select), np.float32)
    assert got.shape == (ROWS, HEADS, V) and np.isfinite(got).all()
    want = whole_slot_attention(q_nope, q_rope, kv, LAYER, slots, positions, kv_b, select or None)
    # bfloat16 into both products and out of them: 2**-8 a value, a few of them in a row
    assert np.abs(got - want).max() <= 0.03 * want.std()
    if case == "pads_beside":
        # the real rows of a launch are what they are beside other neighbours
        others = np.arange(ROWS, dtype=np.int32)[::-1].copy()
        elsewhere = np.array([0, 77, BLOCK - 1, 9, 9, 9, 9, 9], np.int32)
        again = _selection(np.random.default_rng(5), elsewhere) if selected else ()
        if selected:
            again = (again[0].at[:real].set(select[0][:real]), again[1].at[:real].set(select[1][:real]))
        beside = np.asarray(run(q_nope, q_rope, kv, others, elsewhere, kv_b, *again), np.float32)
        np.testing.assert_array_equal(got[:real], beside[:real])
    if case == "garbage":
        # what a slot holds past its row's position reaches nothing: an earlier session's rows, and for the
        # kernel NaN too (the form that takes a slot whole multiplies them by a weight of zero, as it always did)
        past = np.arange(S_LEN)[None, :] > positions[np.argsort(slots)][:, None]  # by slot
        dirty = jnp.where(jnp.asarray(past)[None, :, :, None], jnp.nan if blocks == "several" else 1e4, kv)
        assert not np.array_equal(np.asarray(dirty, np.float32), np.asarray(kv, np.float32), equal_nan=True)
        np.testing.assert_array_equal(
            got, np.asarray(run(q_nope, q_rope, dirty, slots, positions, kv_b, *select), np.float32))


@pytest.mark.parametrize("slot_len, block", [
    (4352, 4352), (8192, 8192), (62720, 1792), (34048, 2432), (S_LEN, S_LEN), (8192 + 64, 8192 + 64),
], ids=str)
def test_the_block_follows_the_slots_shape_alone(slot_len, block):
    """Whole up to ``SEGMENT_ROWS`` positions; else the largest part of
    whole 128-lane tiles of at most ``STEP_BLOCK`` that divides the slot
    (one that no such part divides stays whole)."""
    assert latent_attention.step_block(slot_len) == block
    assert slot_len % block == 0 and (block == slot_len or block % 128 == 0)
    # what the sessions count by is what the device program reads by
    assert axk1.AXK1Config().step_key_blocks(slot_len) == (block, 6)
    assert ling.LingConfig(layer_types=("kda",) * 6 + ("mla",), num_hidden_layers=7).step_key_blocks(slot_len) == (block, 1)


def test_step_launches_count_the_positions_their_rows_fetch():
    """``lm_step_keys_fetched``: for every row of a step launch's shape,
    pad rows too, the blocks up to its position x the block x the layers
    that attend; ``lm_step_keys_whole``: its rows x the slot x those
    layers. A turn of many tokens, a launch of plain arrays (a compile)
    and a launch that failed count in neither; a model that states no
    block counts nothing."""
    def sessions(**kw):
        return TokenSessions(4, S_LEN, 256, lm.token_bucket, lambda n: lm.step_bucket(n, 4), time_fn=lambda: 0.0, **kw)

    def send(state, sid, n, failed=False, **kw):
        request, ticket = state.open(InferRequest("m", {"tokens": np.zeros((1, n), np.int32)}, sequence_id=sid, **kw))
        state.close(ticket, None if failed else {"logits": np.zeros((request.inputs["tokens"].shape[0], 4), np.float32)},
                    failed=failed)
        return request.inputs

    state = sessions(step_keys=(BLOCK, 2))
    counted = lambda s: (s.stats()["lm_step_keys_fetched"], s.stats()["lm_step_keys_whole"])
    send(state, "a", 200, sequence_start=True)
    send(state, "b", BLOCK - 1, sequence_start=True)
    assert counted(state) == (0, 0)
    launch = send(state, "a", 1)  # position 200: two blocks; three pad rows of one each
    assert launch["positions"].tolist() == [200, 0, 0, 0]
    assert counted(state) == (2 * 5 * BLOCK, 2 * 4 * S_LEN)
    send(state, "b", 1)  # position 127: the last of its first block
    send(state, "b", 1)  # position 128: the first of its second
    assert counted(state) == (2 * (5 + 4 + 5) * BLOCK, 3 * 2 * 4 * S_LEN)
    send(state, "a", 1, failed=True)
    state.close(state.open(InferRequest("m", dict(lm.launch_inputs("step", 4))))[1], {"logits": np.zeros((4, 4), np.float32)})
    assert counted(state) == (2 * 14 * BLOCK, 3 * 2 * 4 * S_LEN) and state.stats()["lm_step_launches"] == 3
    plain = sessions()
    send(plain, "a", 12, sequence_start=True)
    send(plain, "a", 1)
    assert counted(plain) == (0, 0) and plain.stats()["lm_step_launches"] == 1
