"""Probe: how should attention read only the positions an indexer selected?

``family: deepseek_v32`` (models/axk1.py with ``index_topk`` > 0) scores
every cached position for every query, keeps the 2,048 best and attends
to those. This script times, at the served sizes on one chip, the
candidates for each part and each launch kind, one JSON line a variant
(``ms``: median over ``--reps`` of the host clock around a jitted call
whose result is waited for; the first call, which compiles, is left out):

  * ``scores.*``: the index scores of an extend launch, the Pallas kernel
    against plain XLA (ops/sparse_index.py), and how far they differ;
  * ``select.*``: the threshold of each row by bisection over the float's
    bits (in plain XLA, and in a Pallas kernel that keeps whole rows in
    VMEM through the 32 passes) against a ``lax.top_k`` (a sort) of the
    row, and whether they agree;
  * ``attend.extend.*``: the expanded form under the selection's mask in
    plain XLA against the Pallas kernel of the same blocks, against the
    same form unmasked (what the mask costs), and a row
    gather of 2,048 cache rows a query for a few queries (what a gather
    form would pay before it multiplies anything);
  * ``attend.step.*``: the absorbed form under the mask over the whole
    slot against a gather of each session's 2,048 rows;
  * ``launch.*``: whole launches of the served program on seeded weights.

Run it on the chip (``chiprun -- python perf/profile_sparse_attention.py``);
on the CPU only as a rehearsal (``--rehearse``: tiny sizes, no kernel): a
CPU timing is not a speed. Lines also go to
``chiprun_out/profile_sparse_attention.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--contexts", default="4096,16384,29696")
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--skip-launches", action="store_true")
    p.add_argument("--attend-tiles", default="", help="builder's sweep of the selected-attention kernel's tiles: "
                   "comma-separated <queries>x<keys>; times only that kernel, a line a pair")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import server_child as sc
    from triton_client_tpu.models import axk1
    from triton_client_tpu.ops import latent_attention, sparse_index
    from triton_client_tpu.pipelines import lm

    doc = sc.load_json(ROOT / "benchmarks/configs/dsv32-ep32-l6.json")
    if args.rehearse:
        doc = sc.apply_rehearsal(doc)
    elif jax.default_backend() != "tpu":
        sys.exit("profile_sparse_attention: needs a TPU (or --rehearse)")
    model = dict(doc["model"])
    slot_len, tokens = model.pop("slot_len"), model.pop("max_tokens")
    cfg = axk1.AXK1Config.from_dict(model)
    contexts = [int(c) for c in args.contexts.split(",")] if not args.rehearse else [16, 48]
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    sink = open(out_dir / "profile_sparse_attention.jsonl", "w")

    def say(**row):
        line = json.dumps(row)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        took = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(*a))
            took.append((time.perf_counter() - t0) * 1e3)
        return statistics.median(took), out

    hi, di, topk = cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk
    h, nope, rp, rank = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 10)
    bf = jnp.bfloat16
    q_i = jax.random.normal(ks[0], (tokens, hi, di), bf)
    w_i = jax.random.normal(ks[1], (tokens, hi), jnp.float32) * (hi * di) ** -0.5
    keys_i = jax.random.normal(ks[2], (slot_len, di), bf)
    rows = jax.random.normal(ks[3], (slot_len, cfg.cache_row), bf)
    q_nope = jax.random.normal(ks[4], (tokens, h, nope), bf)
    q_rope = jax.random.normal(ks[5], (tokens, h, rp), bf)
    kv_b = (jax.random.normal(ks[6], (rank, h, nope + cfg.v_head_dim), jnp.float32) * rank**-0.5).astype(bf)
    use_kernel = not args.rehearse and sparse_index.kernel_fits(tokens, slot_len, hi, di)

    for context in contexts:
        pos = jnp.arange(context, context + tokens, dtype=jnp.int32)
        xla = jax.jit(lambda q, w, k, p: sparse_index.extend_scores(q, w, k, p, kernel=False))
        ms_xla, scores = timed(xla, q_i, w_i, keys_i, pos)
        say(variant="scores.extend.xla", context=context, ms=ms_xla)
        if use_kernel:
            pallas = jax.jit(lambda q, w, k, p: sparse_index.extend_scores(q, w, k, p, kernel=True))
            ms_k, scores_k = timed(pallas, q_i, w_i, keys_i, pos)
            seen = jnp.isfinite(scores)
            say(variant="scores.extend.pallas", context=context, ms=ms_k,
                same_mask=bool(jnp.all(seen == jnp.isfinite(scores_k))),
                max_abs_diff=float(jnp.max(jnp.where(seen, jnp.abs(scores - scores_k), 0.0))),
                score_std=float(jnp.std(jnp.where(seen, scores, 0.0))))
            scores = scores_k
        wanted = jnp.minimum(pos + 1, topk)
        bisect = jax.jit(lambda s, k, last: sparse_index.kth_largest(s, k, last=last, kernel=False))
        ms_b, tau = timed(bisect, scores, wanted, pos[-1])
        say(variant="select.extend.bisection", context=context, ms=ms_b)
        if not args.rehearse and sparse_index.kth_kernel_fits(tokens, slot_len):
            in_vmem = jax.jit(lambda s, k, last: sparse_index.kth_largest(s, k, last=last, kernel=True))
            ms_v, tau_v = timed(in_vmem, scores, wanted, pos[-1])
            say(variant="select.extend.bisection_kernel", context=context, ms=ms_v,
                thresholds_equal=bool(np.array_equal(np.asarray(tau), np.asarray(tau_v))))
        sort = jax.jit(lambda s: jax.lax.top_k(s, topk)[0][:, -1])
        ms_s, tau_sort = timed(sort, scores)
        full = np.asarray(wanted) == topk
        say(variant="select.extend.top_k", context=context, ms=ms_s,
            thresholds_equal=bool(np.array_equal(np.asarray(tau)[full], np.asarray(tau_sort)[full])))
        picked = jnp.sum(scores >= tau[:, None], axis=1)
        say(variant="select.extend.count", context=context, exact=bool(jnp.all(picked == wanted)),
            most=int(picked.max()), least=int(picked.min()))
        masked = jax.jit(lambda a, b, r, p, s, t: latent_attention.expanded_attention(
            a, b, r, p, kv_b, cfg.softmax_scale, nope, (s, t), kernel=False))
        ms_m, plain = timed(masked, q_nope, q_rope, rows, pos, scores, tau)
        say(variant="attend.extend.expanded_masked", context=context, ms=ms_m)
        for pair in filter(None, args.attend_tiles.split(",")):
            latent_attention.SELECTED_QUERY_TILE, latent_attention.SELECTED_KEY_TILE = map(int, pair.split("x"))
            swept = jax.jit(lambda a, b, r, p, s, t: latent_attention.expanded_attention(
                a, b, r, p, kv_b, cfg.softmax_scale, nope, (s, t), kernel=True))
            try:
                ms_t, got = timed(swept, q_nope, q_rope, rows, pos, scores, tau)
                say(variant="attend.extend.selected_kernel", tiles=pair, context=context, ms=ms_t,
                    max_abs_diff=float(jnp.max(jnp.abs(got.astype(jnp.float32) - plain.astype(jnp.float32)))))
            except Exception as e:  # a pair Mosaic refuses (VMEM) is a line, not the end of the sweep
                say(variant="attend.extend.selected_kernel", tiles=pair, context=context, error=repr(e)[:300])
        if args.attend_tiles:
            continue
        if not args.rehearse and latent_attention.selected_kernel_fits(tokens, slot_len, nope, cfg.v_head_dim):
            fused = jax.jit(lambda a, b, r, p, s, t: latent_attention.expanded_attention(
                a, b, r, p, kv_b, cfg.softmax_scale, nope, (s, t), kernel=True))
            ms_f, got = timed(fused, q_nope, q_rope, rows, pos, scores, tau)
            say(variant="attend.extend.selected_kernel", context=context, ms=ms_f,
                max_abs_diff=float(jnp.max(jnp.abs(got.astype(jnp.float32) - plain.astype(jnp.float32)))),
                out_std=float(jnp.std(plain.astype(jnp.float32))))
        dense = jax.jit(lambda a, b, r, p: latent_attention.expanded_attention(
            a, b, r, p, kv_b, cfg.softmax_scale, nope))
        ms_d, _ = timed(dense, q_nope, q_rope, rows, pos)
        say(variant="attend.extend.expanded_dense", context=context, ms=ms_d)
        few = min(64, tokens)
        gather = jax.jit(lambda r, s: r[jax.lax.top_k(s[:few], min(topk, slot_len))[1]])
        ms_g, _ = timed(gather, rows, scores)
        say(variant="attend.extend.gather_rows", context=context, queries=few, ms=ms_g,
            ms_scaled_to_launch=ms_g * tokens / few)

    if args.attend_tiles:
        return 0
    # a step launch: 8 sessions, each its own slot
    b, slots_n = 8, 8
    cache = jax.random.normal(ks[7], (1, slots_n, slot_len, cfg.cache_row), bf)
    index = jax.random.normal(ks[8], (1, slots_n, slot_len, di), bf)
    slots = jnp.arange(b, dtype=jnp.int32) % slots_n
    for context in contexts:
        pos = jnp.full((b,), context + tokens - 1, jnp.int32)
        step = jax.jit(lambda q, w, ik, p: sparse_index.step_scores(q, w, ik, 0, slots, p))
        ms, scores = timed(step, q_i[:b], w_i[:b], index, pos)
        say(variant="scores.step", context=int(pos[0]), ms=ms)
        wanted = jnp.minimum(pos + 1, topk)
        ms, tau = timed(jax.jit(sparse_index.kth_largest), scores, wanted)
        say(variant="select.step.bisection", context=int(pos[0]), ms=ms)
        ms, _ = timed(jax.jit(lambda s: jax.lax.top_k(s, min(topk, slot_len))[0][:, -1]), scores)
        say(variant="select.step.top_k", context=int(pos[0]), ms=ms)
        masked = jax.jit(lambda a, c, kv, p, s, t: latent_attention.absorbed_attention(
            a, c, kv, 0, slots, p, kv_b, cfg.softmax_scale, nope, (s, t)))
        ms, _ = timed(masked, q_nope[:b], q_rope[:b], cache, pos, scores, tau)
        say(variant="attend.step.absorbed_masked", context=int(pos[0]), ms=ms)

        def gathered(a, c, kv, s):
            idx = jax.lax.top_k(s, min(topk, slot_len))[1]  # [B, topk]
            picked = kv[0, slots[:, None], idx]  # [B, topk, row]
            q_lat = jnp.einsum("bhd,chd->bhc", a, kv_b[..., :nope])
            q = jnp.concatenate([q_lat, c, jnp.zeros((*c.shape[:-1], cfg.cache_row - rank - rp), bf)], -1)
            sc_ = jnp.einsum("bhc,bsc->bhs", q, picked, preferred_element_type=jnp.float32) * cfg.softmax_scale
            wts = jax.nn.softmax(sc_, axis=-1).astype(bf)
            return jnp.einsum("bhc,chd->bhd", jnp.einsum("bhs,bsc->bhc", wts, picked[..., :rank]), kv_b[..., nope:])

        ms, _ = timed(jax.jit(gathered), q_nope[:b], q_rope[:b], cache, scores)
        say(variant="attend.step.gather_topk", context=int(pos[0]), ms=ms)

    if args.skip_launches:
        return 0
    del cache, index, rows, q_i, keys_i, q_nope, q_rope
    weights = jax.jit(lambda k: axk1.stack_layers(axk1.init_params(k, cfg), cfg))(key)
    state = axk1.empty_cache(cfg, 8, slot_len)
    fn = lm.make_device_fn(axk1, cfg)
    program = jax.jit(lambda i, w, s: fn(i, {"weights": w, "cache": s}), donate_argnums=(2,))
    rng = np.random.default_rng(0)

    def launch(state, kind, size, position, sessions=1):
        inputs = lm.launch_inputs(kind, size)
        n = size if kind == "extend" else 1
        rows_n = 1 if kind == "extend" else sessions
        inputs["tokens"][:rows_n] = rng.integers(0, cfg.vocab_size, (rows_n, n))
        inputs["slots"][:rows_n] = np.arange(rows_n)
        inputs["positions"][:rows_n] = position
        inputs["lengths"][:rows_n] = n
        took = []
        for rep in range(args.reps + 1):
            t0 = time.perf_counter()
            out = program(inputs, weights, state)
            jax.block_until_ready(out["logits"])
            took.append((time.perf_counter() - t0) * 1e3)
            state = out[lm.STATE_KEY]
        return statistics.median(took[1:]), state

    for context in [0, *contexts]:
        size = tokens if context else max(2, tokens // 4)
        ms, state = launch(state, "extend", size, context)
        say(variant="launch.extend", tokens=size, context=context, ms=ms, us_per_token=1e3 * ms / size)
    for sessions in (4, 8):
        ms, state = launch(state, "step", 8, contexts[-1], sessions)
        say(variant="launch.step8", sessions=sessions, context=contexts[-1], ms=ms)
    stats = jax.devices()[0].memory_stats() or {}
    say(variant="memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"), bytes_limit=stats.get("bytes_limit"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
