"""Probe: what does a step launch pay for its latent attention?

``ops/latent_attention.absorbed_attention`` alone, at the shapes of the
three served configurations whose step launch runs it, one JSON line a
shape, form and block:

  * ``ling``: ``examples/ling3_ep8``, 8 rows of which 3-4 are sessions
    at 13k-62k positions of a slot of 62,720, 32 heads, 2 layers;
  * ``dsv32``: ``examples/dsv32_ep32``, 8 rows of which 3-4 are sessions
    at 9k-34k of 34,048, 128 heads, 6 layers, under a selection of 2,048
    positions a row;
  * ``axk1``: ``examples/axk1_ep16``, 16 rows of which 11 are sessions
    at 1k-4.3k of 4,352, 64 heads, 6 layers.

Forms: ``whole``, every row's slot sliced out whole, scores over all of
its positions and the mask afterwards (every step launch until PR 49,
and a slot of at most ``SEGMENT_ROWS`` positions since: the package's
``_whole_slot_attention``); ``loop``, the blocks of the served form as a
``fori_loop`` a row in plain XLA, each block sliced inside the product
that reads it (what PR 49 tried first; kept HERE alone), at the served
block; and ``kernel``, the Pallas kernel ``lm_latent_decode`` that
serves a slot of more than ``SEGMENT_ROWS`` positions (``SEGMENT_ROWS``
is set to 0 here, so that the A.X-K1 slot, which is served by ``whole``,
goes through it too), once at the served block (``swept: null``) and
once for every other block that ``--blocks`` names (``STEP_BLOCK`` set
to it: the block is then the slot's largest part of whole lane tiles at
most that long). A pad row is slot 0, position 0, as ``pipelines/lm.py``
forms it.

``ms`` is the median over ``--reps`` of the host clock around a jitted
scan over the layers whose result is waited for (the first call, which
compiles, is left out); ``fetched_mb`` the rows the form fetches (pad
rows too), ``needed_mb`` those of the sessions' own positions, ``gb_s``
the fetched over the time; ``worst`` the largest difference from
``whole`` over the spread of its output.

Run it on the chip (``chiprun -- python perf/profile_latent_step.py``);
on the CPU only as a rehearsal (``--rehearse``: tiny sizes): a CPU timing
is not a speed. Lines also go to ``chiprun_out/profile_latent_step.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

#: rows, sessions among them, heads, layers, slots, slot_len, the sessions' positions from-to, selected positions
SHAPES = {
    "ling": dict(rows=8, sessions=(3, 4), heads=32, layers=2, slots=8, slot_len=62720, span=(13312, 62719), topk=0),
    "dsv32": dict(rows=8, sessions=(3, 4), heads=128, layers=6, slots=8, slot_len=34048, span=(9216, 34047), topk=2048),
    "axk1": dict(rows=16, sessions=(11,), heads=64, layers=6, slots=40, slot_len=4352, span=(1024, 4351), topk=0),
}
REHEARSAL = dict(rows=8, sessions=(3,), heads=4, layers=2, slots=8, slot_len=1024, span=(100, 1023), topk=0)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--reps", type=int, default=9)
    p.add_argument("--shapes", default="ling,dsv32,axk1")
    p.add_argument("--blocks", default="256,640,896,1280,1792,2176,2432,4480,4864,6272,8960,12544,17024",
                   help="blocks to sweep beside the served one (those that divide the shape's slot)")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from triton_client_tpu.ops import latent_attention

    device = jax.devices()[0]
    print(json.dumps({"device": f"{device.platform} ({device.device_kind})"}), flush=True)
    nope, rope, rank, vd, row = (16, 8, 16, 16, 128) if args.rehearse else (128, 64, 512, 128, 640)
    shapes = {"rehearsal": REHEARSAL} if args.rehearse else {n: SHAPES[n] for n in args.shapes.split(",")}
    sweep = [128, 256] if args.rehearse else [int(b) for b in args.blocks.split(",")]
    scale = (nope + rope) ** -0.5
    bf = jnp.bfloat16

    def absorbed_query(q_nope, q_rope, kv, kv_b, nope):
        """``kv_b`` folded into the query, the rotated part and a cache row's zero tail beside it."""
        q_lat = jnp.einsum("bhd,chd->bhc", q_nope, kv_b[..., :nope])
        tail = kv.shape[-1] - kv_b.shape[0] - q_rope.shape[-1]
        return jnp.concatenate([q_lat, q_rope, jnp.zeros((*q_rope.shape[:-1], tail), q_rope.dtype)], axis=-1).astype(kv.dtype)

    def whole(q_nope, q_rope, kv, layer, slots, positions, kv_b, scale, nope, select=None):
        """The form that slices every row's slot out whole, at any slot length."""
        out_lat = latent_attention._whole_slot_attention(
            absorbed_query(q_nope, q_rope, kv, kv_b, nope), kv, layer, slots, positions, kv_b.shape[0], scale, select)
        return jnp.einsum("bhc,chd->bhd", out_lat, kv_b[..., nope:])

    def loop(q_nope, q_rope, kv, layer, slots, positions, kv_b, scale, nope, select=None):
        """The served form's blocks as a loop a row in plain XLA."""
        rank, h = kv_b.shape[0], q_nope.shape[1]
        s_len, row = kv.shape[2:]
        block = latent_attention.step_block(s_len)
        q = absorbed_query(q_nope, q_rope, kv, kv_b, nope)

        def one(args):
            q_row, slot, pos, *picked = args

            def keys(j, carry):
                top, total, acc = carry
                lo = j * block
                seen = lo + jnp.arange(block) <= pos
                rows = jax.lax.dynamic_slice(kv, (layer, slot, lo, 0), (1, 1, block, row))[0, 0]
                scores = jnp.einsum("hc,sc->hs", q_row, rows, preferred_element_type=jnp.float32) * scale
                keep = seen
                if picked:
                    keep = seen & (jax.lax.dynamic_slice_in_dim(picked[0], lo, block) >= picked[1])
                scores = jnp.where(keep, scores, -jnp.inf)
                new_top = jnp.maximum(top, scores.max(axis=-1))
                w = jnp.exp(scores - new_top[:, None])
                shrink = jnp.exp(top - new_top)
                values = jnp.where(seen[:, None], jax.lax.dynamic_slice(kv, (layer, slot, lo, 0), (1, 1, block, rank))[0, 0], 0)
                acc = acc * shrink[:, None] + jnp.einsum(
                    "hs,sc->hc", w.astype(values.dtype), values, preferred_element_type=jnp.float32)
                return new_top, total * shrink + w.sum(axis=-1), acc

            state = (jnp.full((h,), -1e30, jnp.float32), jnp.zeros((h,), jnp.float32), jnp.zeros((h, rank), jnp.float32))
            _, total, acc = jax.lax.fori_loop(0, pos // block + 1, keys, state)
            return (acc / total[:, None]).astype(kv.dtype)

        out_lat = jax.lax.map(one, (q, slots, positions, *(select or ())))
        return jnp.einsum("bhc,chd->bhd", out_lat, kv_b[..., nope:])

    def program(form, layers):
        """The form over the layers, as a launch's scan runs it: the layer a traced index."""
        def run(q_nope, q_rope, kv, slots, positions, kv_b, *select):
            def body(acc, layer):
                out = form(q_nope, q_rope, kv, layer, slots, positions, kv_b, scale, nope, select or None)
                return acc + out.astype(jnp.float32), None

            return jax.lax.scan(body, jnp.zeros((q_nope.shape[0], q_nope.shape[1], vd), jnp.float32),
                                jnp.arange(layers, dtype=jnp.int32))[0]

        return jax.jit(run)

    def timed(fn, *a):
        out = jax.block_until_ready(fn(*a))
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            times.append(time.perf_counter() - t0)
        return statistics.median(times) * 1e3, np.asarray(out)

    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    log = (out_dir / "profile_latent_step.jsonl").open("a")
    served, latent_attention.SEGMENT_ROWS = latent_attention.STEP_BLOCK, 0
    for name, shape in shapes.items():
        b, h, layers, s_len = shape["rows"], shape["heads"], shape["layers"], shape["slot_len"]
        keys = jax.random.split(jax.random.PRNGKey(49), 5)
        draw = lambda key, dims, std=1.0: jax.jit(lambda: (jax.random.normal(key, dims, jnp.float32) * std).astype(bf))()
        kv = draw(keys[0], (layers, shape["slots"], s_len, row))
        q_nope, q_rope = draw(keys[1], (b, h, nope)), draw(keys[2], (b, h, rope))
        kv_b = draw(keys[3], (rank, h, nope + vd), rank**-0.5)
        for sessions in shape["sessions"]:
            positions = np.zeros(b, np.int32)
            positions[:sessions] = np.linspace(*shape["span"], sessions).astype(np.int32)
            slots = np.where(np.arange(b) < sessions, (np.arange(b) + 1) % shape["slots"], 0).astype(np.int32)
            select = ()
            if shape["topk"]:
                scores = jax.random.normal(keys[4], (b, s_len), jnp.float32)
                scores = jnp.where(jnp.arange(s_len)[None] <= positions[:, None], scores, -jnp.inf)
                wanted = jnp.minimum(jnp.asarray(positions) + 1, shape["topk"])
                select = (scores, -jnp.sort(-scores, axis=-1)[jnp.arange(b), wanted - 1])
            inputs = (q_nope, q_rope, kv, jnp.asarray(slots), jnp.asarray(positions), kv_b, *select)
            base_ms, base = timed(program(whole, layers), *inputs)
            needed = layers * int((positions[:sessions] + 1).sum()) * row * 2

            def say(form, block, swept, ms, got):
                fetched = layers * (b * s_len if form == "whole" else int((positions // block + 1).sum()) * block) * row * 2
                line = {
                    "shape": name, "rows": b, "sessions": sessions, "heads": h, "layers": layers, "slot_len": s_len,
                    "positions": positions[:sessions].tolist(), "form": form, "block": block, "swept": swept,
                    "ms": round(ms, 3), "fetched_mb": round(fetched / 1e6, 1), "needed_mb": round(needed / 1e6, 1),
                    "gb_s": round(fetched / 1e6 / ms, 1), "worst": float(np.abs(got - base).max() / base.std()),
                }
                print(json.dumps(line), flush=True)
                log.write(json.dumps(line) + "\n")

            say("whole", s_len, None, base_ms, base)
            say("loop", latent_attention.step_block(s_len), None, *timed(program(loop, layers), *inputs))
            for limit in [None, *[x for x in sweep if s_len % x == 0 and x != latent_attention.step_block(s_len)]]:
                latent_attention.STEP_BLOCK = served if limit is None else limit
                block = latent_attention.step_block(s_len)
                if limit is not None and block != limit:
                    continue
                ms, got = timed(program(latent_attention.absorbed_attention, layers), *inputs)  # traced anew: a new jit
                say("kernel", block, limit, ms, got)
            latent_attention.STEP_BLOCK = served
        del kv
    log.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
