"""Parallelism: device mesh, canonical shardings, collectives.

The reference has **no** parallelism of any kind (single-process
FastAPI app, SURVEY §2). This package supplies the TPU-native layer
the north star demands: a named device ``Mesh`` with ``data`` and
``model`` axes, ``NamedSharding`` annotations on params/batches, and
XLA-inserted collectives over ICI (gradient ``psum`` falls out of the
sharded ``jit`` — no hand-written NCCL/MPI-style transport).
"""

from mlapi_tpu.parallel.mesh import (  # noqa: F401
    DATA_AXIS,
    FSDP_AXIS,
    MODEL_AXIS,
    batch_shard_axes,
    batch_shard_size,
    create_mesh,
    mesh_for_config,
    model_on_mesh,
    params_for_model,
    place_params,
    place_train_state,
    replicate_for_mesh,
    shard_batch_for_mesh,
    state_shardings_like,
)
from mlapi_tpu.parallel.layout import (  # noqa: F401
    FSDP_MIN_SIZE,
    SpecLayout,
    fsdp_spec_tree,
)
from mlapi_tpu.parallel.distributed import (  # noqa: F401
    initialize_from_env,
    replica_endpoints_from_env,
)
