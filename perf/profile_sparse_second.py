"""Sparse SECOND at the reference 0.05 m grid — on-chip feasibility +
speed (VERDICT r2 #2).

Measures, with the chained-token in-jit rep methodology (_harness):
  1. primitive cost probe: large-table int32 gathers (the sparse
     conv's dominant primitive — is a TPU gather row-serialized like
     the scatter's ~15 ns/row, or bandwidth-bound?);
  2. the full sparse-SECOND pipeline at 0.05 m (synthetic structured
     scene, realistic ~60k occupancy): scans/s vs the >= 10 scans/s
     target, plus the 0.2 m dense config for context.

Run from the repo root on the chip: `python perf/profile_sparse_second.py`.
"""

import _harness  # noqa: F401  (sys.path bootstrap)

import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from _harness import timed


def probe_gather():
    print("== primitive probe: gathers from a 90M int32 table ==", flush=True)
    n_cells = 90_000_000
    table = jnp.zeros((n_cells,), jnp.int32)
    for n_q in (65_536, 27 * 65_536):
        idx = jnp.asarray(
            np.random.default_rng(0).integers(0, n_cells, n_q), jnp.int32
        )

        def fn(tok, table=table, idx=idx):
            out = table[(idx + tok.astype(jnp.int32) % 7)]
            return tok * 0.5 + jnp.sum(out).astype(jnp.float32) * 1e-9

        ms = timed(f"gather {n_q} int32 rows", fn, inner=8, trials=5)
        print(f"  gather {n_q:>9,} rows: {ms:7.3f} ms/call "
              f"({ms * 1e6 / n_q:6.1f} ns/row)", flush=True)

    # feature-row gather (the conv's actual shape): (65k, 64) f32
    feats = jnp.zeros((65_537, 64), jnp.float32)
    idx = jnp.asarray(
        np.random.default_rng(1).integers(0, 65_536, 27 * 65_536), jnp.int32
    )

    def fn2(tok):
        out = feats[(idx + tok.astype(jnp.int32) % 5)]
        return tok * 0.5 + jnp.sum(out) * 1e-9

    ms = timed("gather 27x65k feature rows", fn2, inner=8, trials=5)
    print(f"  gather 27x65k feature rows (64ch): {ms:7.3f} ms/call", flush=True)


def scene_points(n_target=131_072):
    """Structured synthetic scene (synthdata), padded to a fixed budget."""
    from triton_client_tpu.io.synthdata import synth_scene_frame

    rng = np.random.default_rng(0)
    pts, _ = synth_scene_frame(
        rng,
        pc_range=(0.0, -40.0, -3.0, 70.4, 40.0, 1.0),
        n_objects=10,
        n_clutter=n_target - 12_000,
    )
    out = np.zeros((n_target, 4), np.float32)
    m = min(len(pts), n_target)
    out[:m] = pts[:m]
    return out, m


def bench_pipeline(config_path, label):
    from triton_client_tpu.dataset_config import detect3d_from_yaml
    from triton_client_tpu.pipelines.detect3d import BUILDERS_3D

    name, mcfg, pcfg = detect3d_from_yaml(config_path)
    pipe, _, _ = BUILDERS_3D[name](
        jax.random.PRNGKey(0), model_cfg=mcfg, config=pcfg
    )
    pts, m = scene_points()
    from triton_client_tpu.ops.voxelize import pad_points

    padded, count = pad_points(pts[:m], 131_072)

    pts_dev = jnp.asarray(padded)
    count_dev = jnp.asarray(count)

    # drive the pipeline's own jitted fn exactly as serving does,
    # perturbing the input by the token so the loop can't hoist
    def fn(tok):
        dets, valid = pipe._jit(pts_dev + tok * 0.0, count_dev)
        return tok * 0.5 + jnp.sum(dets) * 1e-9 + jnp.sum(valid) * 1e-9

    print(f"== {label}: compiling (can take minutes) ==",
          flush=True)
    t0 = time.time()
    ms = timed(label, fn, inner=4, trials=6)
    print(f"  {label}: {ms:.2f} ms/scan -> {1000.0 / ms:.1f} scans/s "
          f"(first compile+run {time.time()-t0:.0f}s)", flush=True)
    return ms


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("probe", "all"):
        probe_gather()
    if which in ("probe2",):
        probe_lookup_alternatives()
    if which in ("sparse", "all"):
        bench_pipeline(
            "data/kitti_second_sparse005.yaml", "sparse SECOND 0.05 m"
        )
    if which in ("dense", "all"):
        bench_pipeline("data/kitti_second_dense01.yaml", "dense SECOND 0.10 m")




def probe_lookup_alternatives():
    """Neighbor-lookup reformulations: 90M-table gather vs searchsorted
    over the 65k sorted id array (cache-resident)."""
    print("== neighbor-lookup alternatives ==", flush=True)
    rng = np.random.default_rng(0)
    v = 65_536
    n_cells = 90_000_000
    ids = jnp.asarray(
        np.sort(rng.choice(n_cells, v, replace=False)), jnp.int32
    )
    queries = jnp.asarray(
        (np.asarray(ids)[None, :] + rng.integers(-2000, 2000, (27, 1)))
        .clip(0, n_cells - 1)
        .astype(np.int32)
    )  # (27, V) — offset-shifted sorted queries, like real neighbors

    def table_lookup(tok):
        # table built INSIDE the jit — the real encoder rebuilds it per
        # scan, and a 360 MB materialized constant has no place in a
        # compile request anyway
        table = jnp.full((n_cells + 1,), -1, jnp.int32).at[ids].set(
            jnp.arange(v, dtype=jnp.int32)
        )
        q = (queries + tok.astype(jnp.int32) % 3).clip(0, n_cells - 1)
        return tok * 0.5 + jnp.sum(table[q]).astype(jnp.float32) * 1e-9

    def search_lookup(tok):
        q = (queries + tok.astype(jnp.int32) % 3).clip(0, n_cells - 1)
        pos = jnp.searchsorted(ids, q.reshape(-1)).reshape(q.shape)
        hit = ids[jnp.clip(pos, 0, v - 1)] == q
        slot = jnp.where(hit, pos, -1)
        return tok * 0.5 + jnp.sum(slot).astype(jnp.float32) * 1e-9

    for name, fn in (("90M-table", table_lookup), ("searchsorted", search_lookup)):
        ms = timed(f"lookup {name}", fn, inner=8, trials=5)
        print(f"  27x65k neighbor lookup via {name}: {ms:7.3f} ms", flush=True)

    # feature gather batching: 27 sequential (65k, 64) gathers vs one
    # flat (27*65k, 64) gather
    feats = jnp.zeros((v + 1, 64), jnp.float32)
    slots = jnp.asarray(rng.integers(0, v, (27, v)), jnp.int32)

    def seq_gather(tok):
        def body(acc, s):
            return acc + jnp.sum(feats[s]), None
        out, _ = jax.lax.scan(body, jnp.float32(0.0), (slots + tok.astype(jnp.int32) % 2))
        return tok * 0.5 + out * 1e-9

    def flat_gather(tok):
        g = feats[(slots + tok.astype(jnp.int32) % 2).reshape(-1)]
        return tok * 0.5 + jnp.sum(g) * 1e-9

    for name, fn in (("27-seq", seq_gather), ("flat", flat_gather)):
        ms = timed(f"featgather {name}", fn, inner=8, trials=5)
        print(f"  27x(65k,64) feature gather {name}: {ms:7.3f} ms", flush=True)


if __name__ == "__main__":
    main()
