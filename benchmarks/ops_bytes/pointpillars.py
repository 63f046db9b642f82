"""Operations and least bytes of one PointPillars launch, from shapes.

Operations: two per multiply-add of the pillar encoder's linear layer
over the point bucket and of every convolution and transposed
convolution of the backbone and heads over the FULL canvas (the served
model is dense: it computes empty cells too). Scatter, top-k, decode
and NMS are left out: the count can only be too low. Least bytes: each
parameter once in the served dtype, the padded cloud once, the output
rows once. The served entry states float32 and runs its matmuls at the
backend's default precision, one bf16 pass on this chip, so the bf16
peak is the one a share is taken of."""

from __future__ import annotations

import numpy as np

from benchmarks.references import pointpillars as reference

DTYPE_BYTES = {"bf16": 2, "f32": 4}


def count(cfg: dict, rows: int) -> dict:
    import jax

    p = cfg["model"]["point_bucket"]
    flops = reference.flops_per_item(cfg) * rows
    calib = {
        "points": jax.ShapeDtypeStruct((1, p, 4), np.float32),
        "num_points": jax.ShapeDtypeStruct((1,), np.int32),
    }
    tree = jax.eval_shape(lambda c: reference.init_params(jax.random.PRNGKey(0), c, cfg), calib)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    pipe = cfg["pipeline"]
    io = rows * (p * 4 * 4 + 4 + pipe["max_det"] * (pipe["row_width"] * 4 + 1))
    return {
        "flops": flops,
        "bytes": n_params * DTYPE_BYTES[cfg["model"]["dtype"]] + io,
        "flops_dtype": "bf16",
    }
