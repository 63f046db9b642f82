"""Device mesh + sharding policy.

The reference has no device parallelism at all (SURVEY.md section 2.10
— one blocking RPC per frame, NCCL/MPI absent). This package supplies
the TPU-native scale story: a named `jax.sharding.Mesh` (data / model /
seq / pipe axes) with XLA collectives over ICI/DCN, batch sharding for
multi-camera serving, and the sharded training step used for
fine-tuning.
"""

from triton_client_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
    SEQ_AXIS,
    MeshConfig,
    batch_sharding,
    make_mesh,
    replicated,
)
