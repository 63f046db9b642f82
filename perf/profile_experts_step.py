"""Probe: what does a step launch pay for its routed experts?

A launch of a few rows (``ops/experts.py`` ``DENSE_TOKENS``) that is
given the layers' stacks reads each held expert that some valid row
chose, once, from its place in the stack; given a layer's own experts it
runs every one of them over every row. This script times, on one chip, a scan over the expert
layers of one configuration (default: ``examples/ling3_ep8``, 12 layers
of 64 held experts of 3 x 2,560 x 768 bfloat16, 9.06 GB) that runs
nothing but ``routed_experts`` on rows routed uniformly over the model's
experts, one JSON line a variant and number of valid rows:

  * ``dense``: ``ops/experts.routed_experts`` given each layer's slice
    of the stacks: every held expert over every row, weighted by the
    gates (every step launch until PR 45; the A.X-K1 family's since);
  * ``chosen``: ``routed_experts`` given the stacks whole beside the
    layer's place (models/ling.py since PR 45);
  * ``chosen.slice``: the loop over the chosen experts on each layer's
    SLICE (a copy of the loop kept HERE): what it costs when the compiler
    writes the slice out first, and why a launch that is given a slice
    takes the dense product.

``ms`` is the median over ``--reps`` of the host clock around a jitted
call whose result is waited for (the first call, which compiles, is left
out); ``read_gb`` the bytes of the experts the variant has to read
(``chosen``: those of the distinct chosen experts, counted from the
returned ``rows``) and ``gb_s`` the one over the other; ``worst`` the
largest difference from ``dense``.

Run it on the chip (``chiprun -- python perf/profile_experts_step.py``);
on the CPU only as a rehearsal (``--rehearse``: tiny sizes): a CPU timing
is not a speed. Lines also go to ``chiprun_out/profile_experts_step.jsonl``.
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


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--reps", type=int, default=7)
    p.add_argument("--layers", type=int, default=12)
    p.add_argument("--held", type=int, default=64)
    p.add_argument("--experts", type=int, default=512, help="the model's experts, of which --held are here")
    p.add_argument("--top-k", type=int, default=8)
    p.add_argument("--hidden", type=int, default=2560)
    p.add_argument("--inter", type=int, default=768)
    p.add_argument("--rows", type=int, default=8, help="rows of a launch, pad rows included")
    p.add_argument("--valid", default="1,4,8")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    if args.rehearse:
        args.layers, args.held, args.experts, args.hidden, args.inter = 3, 8, 32, 128, 64

    import jax
    import jax.numpy as jnp
    import numpy as np

    from triton_client_tpu.ops import experts as experts_op

    device = jax.devices()[0]
    print(json.dumps({"device": f"{device.platform} ({device.device_kind})"}), flush=True)
    L, E, D, F, t, k = args.layers, args.held, args.hidden, args.inter, args.rows, args.top_k
    keys = jax.random.split(jax.random.PRNGKey(45), 3)
    draw = lambda key, shape, scale: (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.bfloat16)
    stacks = {
        "gate": jax.jit(lambda: draw(keys[0], (L, E, D, F), D**-0.5))(),
        "up": jax.jit(lambda: draw(keys[1], (L, E, D, F), D**-0.5))(),
        "down": jax.jit(lambda: draw(keys[2], (L, E, F, D), F**-0.5))(),
    }
    expert_bytes = 3 * D * F * 2

    def loop_on_slice(x, valid, idx, gates, experts):
        """The loop of ``routed_experts`` over the chosen experts, on a layer's slice."""
        held = experts["gate"].shape[0]
        here = (idx >= 0) & (idx < held) & valid[:, None]
        weight = jnp.sum(
            jnp.where(here[:, :, None] & (idx[:, :, None] == jnp.arange(held)), gates[:, :, None], 0.0), axis=1)
        rows = jnp.sum(weight > 0, axis=0, dtype=jnp.int32)
        chosen = jnp.argsort(rows == 0, stable=True).astype(jnp.int32)

        def add_expert(i, acc):
            e = chosen[i]
            at = lambda a: jax.lax.dynamic_index_in_dim(a, e, 0, False)
            act = jax.nn.silu(x @ at(experts["gate"])) * (x @ at(experts["up"]))
            return acc + (act @ at(experts["down"])).astype(jnp.float32) * jax.lax.dynamic_slice_in_dim(weight, e, 1, axis=1)

        return jax.lax.fori_loop(0, jnp.sum(rows > 0, dtype=jnp.int32), add_expert, jnp.zeros(x.shape, jnp.float32)), rows

    def scan(variant):
        def run(x, valid, idx, gates, stacks):
            def body(acc, xs):
                layer, idx_l = xs
                if variant == "chosen":
                    y, rows = experts_op.routed_experts(x, valid, idx_l, gates, stacks, 0, layer=layer)
                else:
                    sliced = {n: jax.lax.dynamic_index_in_dim(a, layer, 0, False) for n, a in stacks.items()}
                    form = loop_on_slice if variant == "chosen.slice" else lambda *a: experts_op.routed_experts(*a, 0)
                    y, rows = form(x, valid, idx_l, gates, sliced)
                return acc + y, rows

            return jax.lax.scan(body, jnp.zeros((t, D), jnp.float32), (jnp.arange(L, dtype=jnp.int32), idx))

        return jax.jit(run)

    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    log = (out / "profile_experts_step.jsonl").open("a")
    rng = np.random.default_rng(45)
    x = draw(jax.random.PRNGKey(7), (t, D), 1.0)
    variants = {name: scan(name) for name in ("dense", "chosen", "chosen.slice")}
    for n_valid in [int(v) for v in args.valid.split(",")]:
        # every row, pad rows too, draws its k of the model's experts: a pad row's must not be read
        idx = jnp.asarray(np.stack([
            np.stack([rng.permutation(args.experts)[:k] for _ in range(t)]) for _ in range(L)]), jnp.int32)
        gates = jnp.full((t, k), 2.5 / k, jnp.float32)
        valid = jnp.arange(t) < n_valid
        base = None
        for name, fn in variants.items():
            y, rows = jax.block_until_ready(fn(x, valid, idx, gates, stacks))
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x, valid, idx, gates, stacks))
                times.append(time.perf_counter() - t0)
            ms = statistics.median(times) * 1e3
            read = int(np.count_nonzero(np.asarray(rows))) if name != "dense" else L * E
            base = np.asarray(y) if base is None else base
            line = {
                "variant": name, "valid_rows": n_valid, "ms": round(ms, 3), "experts_read": read,
                "experts_held": L * E, "read_gb": round(read * expert_bytes / 1e9, 3),
                "gb_s": round(read * expert_bytes / 1e6 / ms, 1) if read else None,
                "worst": float(np.abs(np.asarray(y) - base).max()), "spread": float(base.std()),
            }
            print(json.dumps(line), flush=True)
            log.write(json.dumps(line) + "\n")
    log.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
