"""Parallelism strategies over the global device mesh (SURVEY.md §2.4).

- ``rules``    THE declarative sharding authority (ISSUE 15): one
               regex-over-named-tree rule table produces the layout of
               the whole TrainState — Megatron TP pair shards over
               ``model``, ZeRO optimizer/EMA(/param) shards over
               ``fsdp``, replicate floor.
- ``dp``       data parallelism (+ mixed data×spatial) via sharding
               annotations on the jitted train step; GSPMD collectives.
- ``tp``       tensor parallelism: Megatron-style channel shards on the
               ResNet trunk's conv pairs over the ``model`` mesh axis
               (the tree builder is a shim over ``rules``).
- ``spatial``  sharding of H: GSPMD for the ops whose rows split alike,
               one shard_map (halo exchange + local VALID conv) for
               every reflect-padded convolution.
- ``temporal`` sequence parallelism over video frames for the vid2vid
               temporal discriminator.
- ``pp``       pipeline parallelism: GPipe fill/drain over the generator's
               residual trunk on the ``pipe`` mesh axis (stacked stage
               params, neighbor ppermute hand-offs, autodiff backward).
- ``halo``     the shared nearest-neighbor ppermute halo-exchange primitive.

Not applicable to this model family (documented, per SURVEY §2.4): expert
parallelism (no MoE), ring/Ulysses attention (no attention ops — the
spatial/temporal halo exchange is the conv equivalent).
"""

from p2p_tpu.parallel.dp import (
    make_parallel_eval_step,
    make_parallel_train_step,
    replicate_state,
    shard_batch,
)
from p2p_tpu.parallel.halo import halo_exchange, ring_shift
from p2p_tpu.parallel.pp import (
    gpipe_trunk,
    make_expand_block_apply,
    make_resnet_block_apply,
    place_trunk_pp,
    pp_expand_forward,
    pp_generator_forward,
    pp_split_state,
    stack_trunk,
)
from p2p_tpu.parallel.rules import (
    make_fsdp_rules,
    make_tp_rules,
    match_partition_rules,
    state_target_shardings,
    trainstate_rules,
)
from p2p_tpu.parallel.tp import place_state_tp, tp_sharding_tree
from p2p_tpu.parallel.spatial import (
    check_spatial_divisible,
    halo_conv,
    spatial_activation_sharding,
)
from p2p_tpu.parallel.temporal import (
    gather_frames,
    make_sharded_temporal_conv,
    sharded_temporal_conv3d,
    temporal_mean,
)

__all__ = [
    "make_parallel_eval_step",
    "make_parallel_train_step",
    "replicate_state",
    "shard_batch",
    "halo_exchange",
    "gpipe_trunk",
    "make_expand_block_apply",
    "make_resnet_block_apply",
    "place_trunk_pp",
    "pp_expand_forward",
    "pp_generator_forward",
    "pp_split_state",
    "stack_trunk",
    "make_fsdp_rules",
    "make_tp_rules",
    "match_partition_rules",
    "state_target_shardings",
    "trainstate_rules",
    "place_state_tp",
    "tp_sharding_tree",
    "ring_shift",
    "check_spatial_divisible",
    "halo_conv",
    "spatial_activation_sharding",
    "gather_frames",
    "make_sharded_temporal_conv",
    "sharded_temporal_conv3d",
    "temporal_mean",
]
