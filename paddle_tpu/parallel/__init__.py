"""paddle_tpu.parallel — SPMD parallelism over device meshes.

This package replaces ALL FOUR of the reference's distribution backends
(SURVEY.md §2.4) with sharding annotations + XLA collectives:

  * parallel_do / MultiGradientMachine (single-host data parallel threads,
    parallel_do_op.cc:112, MultiGradientMachine.h:168) -> shard the batch
    axis of the feeds over the mesh's 'dp' axis; the SPMD partitioner emits
    the gradient all-reduce over ICI that the reference implements with
    per-GPU TrainerThreads + NCCL.
  * ParallelNeuralNetwork (per-layer device placement) -> per-parameter
    sharding annotations (ParamAttr(sharding=...)) partitioning weights over
    the 'mp' axis (tensor parallelism).
  * pserver (C++/Go) + DistributeTranspiler/gRPC send/recv -> nothing to
    run: parameters live sharded in HBM and updates happen inside the
    compiled step; multi-host scaling = the same program with
    jax.distributed.initialize (see distributed.py).
  * NCCL ops (nccl_op.cc) -> XLA collectives (psum/all_gather/
    reduce_scatter) chosen by the partitioner; ICI within a slice, DCN
    across slices.
"""

from .mesh import (Mesh, current_mesh, make_mesh, mesh_guard, set_mesh,
                   feed_sharding, kernel_axes, state_sharding)
from .distributed import init_distributed
from .moe import switch_moe_call
from .pipeline import gpipe_call
from .transpiler import DistributeTranspiler
from .master import Task, TaskQueue, master_reader
from .master_service import MasterClient, MasterServer
from .coordinator import (CoordinatorServer, MembershipView, PodClient,
                          PodCoordinator, StaleGeneration, agree_verdicts)

__all__ = ["Mesh", "make_mesh", "mesh_guard", "set_mesh", "current_mesh",
           "feed_sharding", "kernel_axes", "state_sharding",
           "init_distributed",
           "DistributeTranspiler", "Task", "TaskQueue", "master_reader",
           "MasterClient", "MasterServer", "gpipe_call",
           "switch_moe_call", "CoordinatorServer", "MembershipView",
           "PodClient", "PodCoordinator", "StaleGeneration",
           "agree_verdicts"]
