"""Probe: what does an extend launch pay for its grouped-query attention?

``ops/block_attention.prefill_attention`` alone, on seeded rows, at every
rung the two cells that run it serve, one JSON line a rung, form and
tile:

  * ``full``: ``examples/smallthinker_ep1``'s full layers, 28 heads on 4
    key/value heads, launches of 1,024 and 2,048 tokens on 0-13k of
    context in a slot of 16,384 rows, causal;
  * ``window``: its window layers, the same launches under a window of
    4,096 in a ring of 6,144 rows;
  * ``sdar``: ``examples/sdar30b_ep8``'s prompts, 32 heads on 4, launches
    of 512, 1,024 and 2,048 tokens on an empty slot of 2,048 rows, under
    the block mask (4).

Forms: ``loop``, the running softmax in plain XLA that every extend
launch ran until PR 50 (a ``lax.map`` over query blocks of 512 around a
``fori_loop`` over key blocks of 512, a key block's scores ``[G, H / G *
512, 512]`` float32, which XLA fuses into the two products rather than
writing them out; kept HERE alone), and
``kernel``, the Pallas kernel ``lm_extend_attention`` that serves now,
once at the served tiles (``swept: null``) and, at the rungs ``--sweep``
names, once for every pair of ``--query-blocks`` x ``--key-blocks``
(``QUERY_BLOCK`` and ``KEY_BLOCK`` set to it; a pair the compiler refuses
says why).

``ms`` is the median over ``--reps`` of the host clock around a jitted
scan over ``--layers`` layers whose result is waited for (each layer's
rows sliced out of a stack, as a launch's scan hands them over; the first
call, which compiles, is left out), a layer. ``steps`` the (query tile,
key block) pairs some query sees a layer, every key/value head at once,
``us_a_step`` the time over them, ``products_us`` what a step's two
products take at the chip's peak (19 us at 28 heads and 512 x 512);
``worst`` the largest difference from ``loop`` over the spread of its
output.

Run it on the chip (``chiprun -- python perf/profile_extend_attention.py``);
on the CPU only as a rehearsal (``--rehearse``: tiny sizes, the kernel
interpreted): a CPU timing is not a speed. Lines also go to
``chiprun_out/profile_extend_attention.jsonl``.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks import peaks  # noqa: E402

PEAK_FLOPS = peaks.peaks("TPU v5 lite")["flops_per_s"]["bf16"]  # the chip the served shapes are for
#: heads, key/value heads, slot rows, block, window, (tokens, context) rungs
SHAPES = {
    "full": dict(heads=28, groups=4, rows=16384, block=1, window=0,
                 rungs=[(1024, 0), (2048, 1024), (2048, 3072), (2048, 7168), (1024, 13312), (2048, 13312)]),
    "window": dict(heads=28, groups=4, rows=6144, block=1, window=4096,
                   rungs=[(1024, 0), (2048, 1024), (2048, 3072), (2048, 7168), (1024, 13312), (2048, 13312)]),
    "sdar": dict(heads=32, groups=4, rows=2048, block=4, window=0, rungs=[(512, 0), (1024, 0), (2048, 0)]),
}
REHEARSAL = {
    "full": dict(heads=14, groups=2, rows=256, block=1, window=0, rungs=[(64, 0), (64, 100)]),
    "window": dict(heads=14, groups=2, rows=192, block=1, window=64, rungs=[(64, 0), (128, 300)]),
    "sdar": dict(heads=16, groups=2, rows=128, block=4, window=0, rungs=[(64, 0)]),
}


def loop_attention(q, rows, positions, block, scale, window=0, tiles=(512, 512)):
    """``ops/block_attention.prefill_attention`` as it was until PR 50:
    a running softmax in plain XLA over query blocks and key blocks of
    ``tiles``."""
    import jax
    import jax.numpy as jnp

    n, h, d = q.shape
    s_len, g = rows[0].shape[0], rows[0].shape[1] // d
    r = h // g
    qb, kb = min(n, tiles[0]), math.gcd(s_len, tiles[1])
    last_visible = (positions // block + 1) * block - 1
    sight = (last_visible, jnp.maximum(positions - (window - 1), 0)) if window else (last_visible,)
    keys, values = (jnp.moveaxis(a.reshape(s_len, g, d), 0, 1) for a in rows)  # [G, S, d]

    def one(xs):
        qq, limit, *floor = xs
        qq = jnp.moveaxis(qq.reshape(qb, g, r, d), 0, 2).reshape(g, r * qb, d)
        limit_rows = jnp.tile(limit, r)
        floor_rows = jnp.tile(floor[0], r) if window else None

        def take(j, carry):
            top, total, acc = carry
            lo = j * kb
            at = lo % s_len if window else lo
            scores = jnp.einsum("gqd,gkd->gqk", qq, jax.lax.dynamic_slice_in_dim(keys, at, kb, axis=1),
                                preferred_element_type=jnp.float32) * scale
            keep = (lo + jnp.arange(kb))[None, None, :] <= limit_rows[None, :, None]
            if window:
                keep &= (lo + jnp.arange(kb))[None, None, :] >= floor_rows[None, :, None]
            scores = jnp.where(keep, scores, -jnp.inf)
            new_top = jnp.maximum(top, scores.max(axis=-1))
            w = jnp.exp(scores - new_top[..., None])
            shrink = jnp.exp(top - new_top)
            acc = acc * shrink[..., None] + jnp.einsum(
                "gqk,gkd->gqd", w.astype(values.dtype), jax.lax.dynamic_slice_in_dim(values, at, kb, axis=1),
                preferred_element_type=jnp.float32)
            return new_top, total * shrink + w.sum(axis=-1), acc

        first = floor[0][0] // kb if window else 0
        blocks = limit[-1] // kb + 1 if window else jnp.clip(limit[-1] // kb + 1, 1, s_len // kb)
        state = (jnp.full((g, r * qb), -1e30, jnp.float32), jnp.zeros((g, r * qb), jnp.float32),
                 jnp.zeros((g, r * qb, d), jnp.float32))
        _, total, acc = jax.lax.fori_loop(first, blocks, take, state)
        out = (acc / total[..., None]).reshape(g, r, qb, d)
        return jnp.moveaxis(out, 2, 0).reshape(qb, h * d).astype(values.dtype)

    split = lambda a: a.reshape(n // qb, qb, *a.shape[1:])
    return jax.lax.map(one, (split(q), *map(split, sight))).reshape(n, h * d)

def profile_launch(spec: str, reps: int, top: int, log, rehearse: bool = False) -> None:
    """``--launch <config>:<tokens>:<context>``: ONE whole extend launch
    of a benchmark configuration (``benchmarks/configs/<config>.json`` at
    its served widths, seeded weights drawn leaf by leaf on the device)
    under the profiler, with the deleted loop in ``prefill_attention``'s
    place and with the kernel: a launch's device time and its ``top``
    ops by device time (a ``while`` holds the ops of its body, which are
    listed too), so that what an extend launch is made of beside its
    attention can be read."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from benchmarks import trace_reduce
    from triton_client_tpu.ops import block_attention
    from triton_client_tpu.pipelines import lm

    name, tokens, context = spec.split(":")
    doc = json.loads((ROOT / "benchmarks/configs" / f"{name}.json").read_text())
    model_doc = {**doc["model"], **(doc["rehearsal"]["model"] if rehearse else {})}
    slot_len = model_doc.pop("slot_len")
    model_doc.pop("max_tokens", None)
    model = lm.MODULES[doc["family"]]
    cfg = model.Config.from_dict(model_doc)
    shapes = jax.eval_shape(lambda: model.stack_layers(model.init_params(jax.random.PRNGKey(0), cfg), cfg))
    leaves, tree = jax.tree_util.tree_flatten(shapes)
    draw = lambda i, leaf: jax.jit(lambda: (0.02 * jax.random.normal(jax.random.PRNGKey(i), leaf.shape, jnp.float32)).astype(leaf.dtype))()
    weights = jax.tree_util.tree_unflatten(tree, [draw(i, leaf) for i, leaf in enumerate(leaves)])
    inputs = {k: jnp.asarray(v) for k, v in lm.launch_inputs("extend", int(tokens)).items()}
    inputs["tokens"] = jax.random.randint(jax.random.PRNGKey(50), inputs["tokens"].shape, 0, cfg.vocab_size, jnp.int32)
    inputs["slots"], inputs["positions"] = inputs["slots"] + 3, inputs["positions"] + int(context)
    inputs["lengths"] = inputs["lengths"] + int(tokens)
    served = block_attention.prefill_attention
    for form, fn in (("loop", loop_attention), ("kernel", served)):
        block_attention.prefill_attention = fn
        device_fn = lm.make_device_fn.__wrapped__(model, cfg)  # traced anew a form: not the memoized one

        def run(inputs, weights, cache):
            out = dict(device_fn(inputs, {"weights": weights, lm.STATE_KEY: cache}))
            return out.pop(lm.STATE_KEY), out["logits"]

        step = jax.jit(run, donate_argnums=(2,))
        cache = model.empty_cache(cfg, doc["max_batch_size"], slot_len)
        cache, logits = step(inputs, weights, cache)
        jax.block_until_ready(logits)
        with tempfile.TemporaryDirectory() as trace_dir:
            with jax.profiler.trace(trace_dir):
                for _ in range(reps):
                    cache, logits = step(inputs, weights, cache)
                jax.block_until_ready(logits)
            planes = trace_reduce.read_xplane(next(pathlib.Path(trace_dir).rglob("*.xplane.pb")))
        del cache
        plane = next((lines for plane, lines in planes.items() if trace_reduce.DEVICE_PLANE.match(plane)), None)
        if plane is None:  # a rehearsal: the CPU's trace has no device plane
            print(json.dumps({"launch": spec, "form": form, "device_plane": None}), flush=True)
            continue
        launches = [dur for _, _, dur in plane[trace_reduce.MODULES_LINE]]
        ops: dict = {}
        for event, _, dur in plane[trace_reduce.OPS_LINE]:
            ops[trace_reduce.op_name(event)] = ops.get(trace_reduce.op_name(event), 0) + dur
        line = {"launch": spec, "form": form, "launches": len(launches), "ms_a_launch": round(sum(launches) / len(launches) / 1e6, 3),
                "ops_ms_a_launch": {k: round(v / reps / 1e6, 3) for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]}}
        print(json.dumps(line), flush=True)
        log.write(json.dumps(line) + "\n")
    block_attention.prefill_attention = served


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--reps", type=int, default=7)
    p.add_argument("--layers", type=int, default=3)
    p.add_argument("--shapes", default="full,window,sdar")
    p.add_argument("--sweep", default="full:2048:13312,window:2048:13312,sdar:1024:0", help="rungs swept: shape:tokens:context")
    p.add_argument("--query-blocks", default="256,512,1024")
    p.add_argument("--key-blocks", default="256,512,1024,2048")
    p.add_argument("--launch", default="", help="<config>:<tokens>:<context>: profile one whole extend launch instead")
    p.add_argument("--top", type=int, default=40, help="ops a --launch line lists")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from triton_client_tpu.ops import block_attention

    device = jax.devices()[0]
    print(json.dumps({"device": f"{device.platform} ({device.device_kind})"}), flush=True)
    d = 128
    shapes = REHEARSAL if args.rehearse else SHAPES
    served, loop_tiles = (block_attention.QUERY_BLOCK, block_attention.KEY_BLOCK), (512, 512)
    if args.rehearse:
        served = loop_tiles = block_attention.QUERY_BLOCK, block_attention.KEY_BLOCK = 32, 32
        args.sweep, args.query_blocks, args.key_blocks = "full:64:100", "16,32", "32,64"
    swept = {tuple(r.split(":")[:1] + [int(x) for x in r.split(":")[1:]]) for r in args.sweep.split(",") if r}
    pairs = [(int(a), int(b)) for a in args.query_blocks.split(",") for b in args.key_blocks.split(",")]

    def program(form, shape):
        """The form over the layers, as a launch's scan runs it: each layer's rows sliced out of a stack."""
        def run(q, keys, values, positions):
            def body(acc, layer):
                rows = tuple(jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False) for a in (keys, values))
                out = form(q, rows, positions, shape["block"], d**-0.5, shape["window"])
                return acc + out.astype(jnp.float32), None

            return jax.lax.scan(body, jnp.zeros((q.shape[0], q.shape[1] * d), jnp.float32),
                                jnp.arange(args.layers, dtype=jnp.int32))[0]

        return jax.jit(run)

    def timed(fn, *a):
        out = jax.block_until_ready(fn(*a))
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            times.append(time.perf_counter() - t0)
        return statistics.median(times) * 1e3 / args.layers, np.asarray(out)

    def steps(shape, tokens, context, qb, kb):
        """(query tile, key block) pairs in some query's sight."""
        count = 0
        for start in range(context, context + tokens, qb):
            limit = ((start + qb - 1) // shape["block"] + 1) * shape["block"] - 1
            first = max(start - shape["window"] + 1, 0) // kb if shape["window"] else 0
            last = limit // kb if shape["window"] else min(limit // kb, shape["rows"] // kb - 1)
            count += last - first + 1
        return count

    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    log = (out_dir / "profile_extend_attention.jsonl").open("a")
    if args.launch:
        profile_launch(args.launch, args.reps, args.top, log, args.rehearse)
        log.close()
        return 0
    bf = jnp.bfloat16
    for name in args.shapes.split(","):
        shape = shapes[name]
        h, g, s_len = shape["heads"], shape["groups"], shape["rows"]
        k0, k1, k2 = jax.random.split(jax.random.PRNGKey(50), 3)
        draw = lambda key, dims: jax.jit(lambda: jax.random.normal(key, dims, jnp.float32).astype(bf))()
        keys, values = draw(k0, (args.layers, s_len, g * d)), draw(k1, (args.layers, s_len, g * d))
        for tokens, context in shape["rungs"]:
            q = draw(k2, (tokens, h, d))
            inputs = (q, keys, values, jnp.arange(context, context + tokens, dtype=jnp.int32))
            block_attention.QUERY_BLOCK, block_attention.KEY_BLOCK = served
            base_ms, base = timed(program(functools.partial(loop_attention, tiles=loop_tiles), shape), *inputs)

            def say(form, tiles, was_swept, ms, got):
                qb, kb = min(tokens, tiles[0]), math.gcd(s_len, tiles[1])
                n_steps = steps(shape, tokens, context, qb, kb)
                line = {
                    "shape": name, "tokens": tokens, "context": context, "heads": h, "rows": s_len, "window": shape["window"],
                    "block": shape["block"], "form": form, "query_block": qb, "key_block": kb, "swept": was_swept,
                    "ms": round(ms, 4), "steps": n_steps, "us_a_step": round(1e3 * ms / n_steps, 2),
                    "products_us": round(1e6 * 2 * 2 * h * qb * kb * d / PEAK_FLOPS, 2),
                    "worst": None if got is None else float(np.abs(got - base).max() / base.std()),
                }
                print(json.dumps(line), flush=True)
                log.write(json.dumps(line) + "\n")

            say("loop", loop_tiles, None, base_ms, base)
            for tiles in [None, *(pairs if (name, tokens, context) in swept else [])]:
                if tiles == served or (tiles and (tokens % min(tokens, tiles[0]) or math.gcd(s_len, tiles[1]) != tiles[1])):
                    continue
                block_attention.QUERY_BLOCK, block_attention.KEY_BLOCK = tiles or served
                try:
                    ms, got = timed(program(block_attention.prefill_attention, shape), *inputs)  # traced anew: a new jit
                except Exception as e:  # what the chip's compiler refuses (a tile too large for its fast memory)
                    line = {"shape": name, "tokens": tokens, "context": context, "form": "kernel", "swept": tiles,
                            "refused": str(e).splitlines()[0][:200]}
                    print(json.dumps(line), flush=True)
                    log.write(json.dumps(line) + "\n")
                    continue
                say("kernel", tiles or served, tiles, ms, got)
            block_attention.QUERY_BLOCK, block_attention.KEY_BLOCK = served
        del keys, values
    log.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
