"""Pipeline parallelism: microbatched GPipe schedule over a mesh axis.

The reference's only "pipeline" is implicit — frame N+1's preprocessing
waits on frame N's RPC (SURVEY.md section 2.10). This module provides real
pipeline parallelism for deep homogeneous stacks (the BEV backbone's
repeated conv blocks, the attention neck's layers): the stack is split
into S stages laid out along the ``pipe`` mesh axis, microbatches
stream through, and activations hop stage-to-stage with
``lax.ppermute`` over ICI — the idiomatic TPU pipelining construction
(stacked per-stage params + shard_map, as in praxis/t5x), not a
port of any GPU framework's scheduler.

Schedule: plain GPipe. For M microbatches and S stages the loop runs
M + S - 1 ticks; at tick t, stage s computes microbatch t - s (when in
range). Bubble fraction is (S-1)/(M+S-1) — callers pick M >= S.
Every device executes every tick (SPMD), with masked no-ops in the
bubble; XLA overlaps the ppermute with the next tick's compute.

Constraints (inherent to ring pipelining, documented not hidden):
  * stage_fn must map (params_slice, x) -> y with y.shape == x.shape
    (homogeneous stages — true for residual stacks);
  * stage params are stacked on a leading axis of size S and sharded
    over the ``pipe`` axis.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from triton_client_tpu.parallel.mesh import PIPE_AXIS

StageFn = Callable[..., jnp.ndarray]


def stack_stage_params(param_trees) -> object:
    """Stack a list of per-stage param pytrees on a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *param_trees)


def _pipeline_kernel(
    params,
    xs: jnp.ndarray,
    *,
    stage_fn: StageFn,
    axis_name: str,
) -> jnp.ndarray:
    """Per-device body. params: stage slice (leading axis 1); xs: all
    microbatches (M, mb, ...) replicated (only stage 0 reads them)."""
    params = jax.tree.map(lambda p: p[0], params)
    stage = jax.lax.axis_index(axis_name)
    n_stages = jax.lax.psum(1, axis_name)
    n_micro = xs.shape[0]

    perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]
    recv = jnp.zeros_like(xs[0])
    outputs = jnp.zeros_like(xs)

    def tick(t, carry):
        recv, outputs = carry
        # stage 0 feeds from the microbatch queue; others from the ring
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        x_in = jnp.where(stage == 0, xs[mb_idx], recv)
        y = stage_fn(params, x_in)
        # last stage banks microbatch t - (S-1) once it's real
        out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        is_last = stage == n_stages - 1
        live = (t - (n_stages - 1) >= 0) & is_last
        outputs = jnp.where(
            live,
            outputs.at[out_idx].set(y),
            outputs,
        )
        recv = jax.lax.ppermute(y, axis_name, perm)
        return recv, outputs

    _, outputs = jax.lax.fori_loop(
        0, n_micro + n_stages - 1, tick, (recv, outputs)
    )
    return outputs[None]  # (1, M, mb, ...): stacked over pipe -> take [-1]


def pipeline_apply(
    stacked_params,
    microbatches: jnp.ndarray,
    stage_fn: StageFn,
    mesh: Mesh,
    *,
    axis: str = PIPE_AXIS,
) -> jnp.ndarray:
    """Run microbatches (M, mb, ...) through S pipelined stages.

    ``stacked_params``: pytree with leading axis S == mesh.shape[axis]
    (see stack_stage_params). Returns (M, mb, ...) — the last stage's
    outputs in microbatch order.
    """
    n_stages = mesh.shape[axis]
    lead = {leaf.shape[0] for leaf in jax.tree.leaves(stacked_params)}
    if lead != {n_stages}:
        raise ValueError(
            f"stacked params leading axes {lead} != pipe axis size {n_stages}"
        )
    if microbatches.shape[0] < n_stages:
        raise ValueError(
            f"{microbatches.shape[0]} microbatches < {n_stages} stages — "
            "the bubble would dominate; split the batch finer"
        )
    fn = shard_map(
        functools.partial(
            _pipeline_kernel, stage_fn=stage_fn, axis_name=axis
        ),
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(axis),
        check_vma=False,
    )
    return fn(stacked_params, microbatches)[-1]
