"""Parallelism layer: device meshes, sharding rules, and collectives.

This is the TPU-native replacement for the reference's entire distribution
stack (src/kvstore comm hierarchy + ps-lite + NCCL): instead of explicit
push/pull between processes, training steps are compiled over a
``jax.sharding.Mesh`` and XLA inserts the collectives (psum/all_gather/
reduce_scatter/ppermute) over ICI/DCN.

The mesh axes convention used across the framework:
  * ``dp`` — data parallel (batch sharding; gradient psum)
  * ``tp`` — tensor parallel (weight sharding within a layer)
  * ``pp`` — pipeline parallel (layer sharding across stages)
  * ``sp`` — sequence/context parallel (ring attention over the seq axis)
  * ``ep`` — expert/embedding parallel (row-sparse tables)

The reference only ships DP + manual model parallelism + sparse-PS semantics
(SURVEY §2.5); the extra axes come "for free" from this layer's design.
"""
from .mesh import (make_mesh, default_mesh, data_parallel_spec, replicated_spec,
                   local_device_count, MeshConfig)
from .collectives import (allreduce, allgather, reduce_scatter, ppermute_ring,
                          barrier_sync, axis_size, pmean, all_to_all, ppermute,
                          collective_counters, reset_collective_counters,
                          collective_totals)
from .data_parallel import make_data_parallel_train_step, shard_batch
from .zero import (init_shard_update_state, make_sharded_update_step,
                   quantized_reduce_scatter, padded_size, flatten_param,
                   unflatten_param, check_dp_divisible, check_flat_state,
                   param_meta, ParamMeta)
from .ring_attention import ring_attention, sequence_parallel_attention
from .pipeline import pipeline_apply, make_pipeline_step
from .ulysses import ulysses_attention_local, ulysses_parallel_attention
from .moe import (moe_apply, make_expert_parallel_moe, moe_held_apply,
                  route_top_k)
from .checkpoint import (save_sharded, restore_sharded,
                         SlicedCheckpointManager)
