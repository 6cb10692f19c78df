"""paddle_tpu.distributed (reference: python/paddle/distributed/).

Single-controller jax model: one Python process drives every chip; "ranks"
live inside XLA programs.  Multi-host = same program launched per host via
`paddle_tpu.distributed.launch` → jax.distributed.initialize, with the mesh
spanning all hosts (collectives ride ICI within a pod, DCN across pods).
"""
from __future__ import annotations

import jax

from . import mesh  # noqa: F401
from .mesh import build_mesh, get_mesh, set_mesh  # noqa: F401
from .collective import (  # noqa: F401
    CollectiveTimeout, ReduceOp, all_reduce, all_gather, reduce_scatter,
    broadcast, scatter, alltoall, alltoall_single, barrier, ppermute,
    stream_synchronize, reduce, send, recv, isend, irecv,
    all_gather_object, broadcast_object_list, scatter_object_list,
    get_group, destroy_process_group, split, configure_collectives,
    collective_policy,
)
from . import launch  # noqa: F401
from .recompute import recompute  # noqa: F401
from .parallel_layers import (  # noqa: F401
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    ParallelCrossEntropy, shard_activation,
)
from .ring_attention import ring_attention, ring_attention_local  # noqa: F401
from .pipeline import PipelineLayer, gpipe_spmd, pipeline_apply  # noqa: F401
from .fleet_engine import DistributedTrainStep  # noqa: F401
from . import fleet  # noqa: F401
from . import sharding  # noqa: F401
from . import auto_parallel  # noqa: F401
from .auto_parallel import (  # noqa: F401
    ProcessMesh, Placement, Shard, Replicate, Partial, shard_tensor,
    reshard, dtensor_from_fn,
)

_env = {"initialized": False}


def init_parallel_env():
    """Multi-host init (reference: paddle.distributed.init_parallel_env).
    Within one host this is a no-op: jax already sees all local chips."""
    import os
    if _env["initialized"]:
        return
    # launched under a heartbeat-watching supervisor: start beating so
    # the launcher can tell a hang from a crash (no-op otherwise)
    from .launch.heartbeat import start_heartbeat
    start_heartbeat()
    # a process entry point of a job: JAX's compile cache goes where the
    # environment (or the checkout) says, the same for every rank
    from ..jit.compile_cache import place_jax_cache
    place_jax_cache()
    if os.environ.get("PT_COORDINATOR"):
        jax.distributed.initialize(
            coordinator_address=os.environ["PT_COORDINATOR"],
            num_processes=int(os.environ.get("PT_NUM_PROCESSES", "1")),
            process_id=int(os.environ.get("PT_PROCESS_ID", "0")))
    _env["initialized"] = True


def get_rank():
    return jax.process_index()


def get_world_size():
    return jax.process_count()


def is_initialized():
    return _env["initialized"]


def new_group(ranks=None, backend=None):
    from .fleet import _AxisGroup
    return _AxisGroup("dp")


def spawn(func, args=(), nprocs=1, **kwargs):
    """Single-controller: run inline (XLA already uses every chip)."""
    func(*args)


class ParallelEnv:
    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def device_id(self):
        return 0
