"""Operations and least bytes of one YOLOv5 launch, from shapes.

Operations: two per multiply-add of every convolution of the forward
pass (the reference's own layer list, counted as it is traced). The
decode, gate and NMS tail is some 1e6 operations a frame and is left
out: the count can only be too low. Least bytes: each parameter read
once in the served dtype, each input frame read once as sent, each
output row written once; activations that a perfect schedule keeps on
chip count nothing."""

from __future__ import annotations

import numpy as np

from benchmarks.references import yolov5 as reference

DTYPE_BYTES = {"bf16": 2, "f32": 4}


def count(cfg: dict, rows: int) -> dict:
    import jax

    hw = cfg["model"]["input_hw"]
    flops = reference.flops_per_item(cfg) * rows
    calib = {"images": jax.ShapeDtypeStruct((1, hw[0], hw[1], 3), np.uint8)}
    tree = jax.eval_shape(lambda c: reference.init_params(jax.random.PRNGKey(0), c, cfg), calib)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    pipe = cfg["pipeline"]
    io = rows * (hw[0] * hw[1] * 3 + pipe["max_det"] * (pipe["row_width"] * 4 + 1))
    return {
        "flops": flops,
        "bytes": n_params * DTYPE_BYTES[cfg["model"]["dtype"]] + io,
        "flops_dtype": "bf16",
    }
