"""Device-mesh parallelism: dp over formations, ring exchange over agents,
multi-host wire-up and hybrid DCN x ICI meshes."""

from marl_distributedformation_tpu.parallel.distributed import (  # noqa: F401
    global_from_local,
    hetero_reset_batch_sharded,
    init_distributed,
    is_coordinator,
    local_formation_slice,
    make_hybrid_mesh,
    reset_batch_sharded,
)
from marl_distributedformation_tpu.parallel.mesh import (  # noqa: F401
    formation_sharding,
    make_dp_step,
    make_mesh,
    make_shard_fn,
    minibatch_sharding,
    replicate,
    replicated,
    shard_batch,
)
from marl_distributedformation_tpu.parallel.ring import (  # noqa: F401
    make_ring_step,
    place_ring_state,
)
