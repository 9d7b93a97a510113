"""Parallelism subsystem — SPMD over TPU device meshes.

Replaces the reference's entire communication stack (SURVEY.md §2.4, §5
"Distributed communication backend": `src/kvstore/comm.h` CPU/GPU reduce,
`kvstore_nccl.h` NCCL, `kvstore_dist.h` ps-lite parameter server) with the
TPU-native design: one `jax.sharding.Mesh` whose named axes carry the
parallelism strategies, sharding annotations on arrays, and XLA-inserted
collectives riding ICI (intra-slice) / DCN (inter-slice).

Axes convention (any subset may be size 1):

* ``dp``   — data parallel (batch dim).  Reference: kvstore allreduce.
* ``fsdp`` — ZeRO-style parameter/optimizer sharding (net-new vs reference).
* ``tp``   — tensor (model) parallel.  Reference gap: `group2ctx` manual
  placement (`graph_executor.cc:909`) was its only model parallelism.
* ``pp``   — pipeline parallel (GPipe schedule over microbatches; net-new).
* ``sp``   — sequence/context parallel (ring attention; net-new).
* ``ep``   — expert parallel (MoE; net-new).
"""
from __future__ import annotations

from .mesh import (DeviceMesh, make_mesh, current_mesh, get_mesh,  # noqa: F401
                   local_mesh)
from .sharding import (ShardingRules, auto_shard, constraint,  # noqa: F401
                       param_sharding, shard_array)
from . import collectives  # noqa: F401
from .ring_attention import ring_attention, blockwise_attention  # noqa: F401
from .pipeline import pipeline_spmd  # noqa: F401
from .moe import expert_layer, moe_layer  # noqa: F401
