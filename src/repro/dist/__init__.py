"""repro.dist — sharding rules, mesh context, and robust DP collectives.

The layer between the pure model/aggregation math and the launch stack:

    rules  (sharding.py)    logical axis names -> PartitionSpecs
    ctx    (context.py)     ShardCtx: mesh + worker axes + TP axis
    comms  (collectives.py) CGC/Krum/median as shard_map collectives
    moe    (moe_sharding.py) tensor- and expert-parallel MoE
    fsdp   (fsdp.py)        param sharding + blockwise-CGC reduce-scatter
    echo   (echo_dp.py)     coefficient-space optimistic aggregation
"""
from .context import ShardCtx, make_shard_ctx
from .sharding import (DEFAULT_RULES, EP_RULES, Rule, spec_for,
                       tree_shardings, tree_specs)
from .collectives import (AGG_FNS, aggregate_pytree_cgc,
                          aggregate_pytree_cgc_sum,
                          aggregate_pytree_mean, inject_byzantine,
                          worker_index)
from .moe_sharding import moe_sharded
from . import collectives, echo_dp, fsdp

__all__ = [
    "AGG_FNS", "DEFAULT_RULES", "EP_RULES", "Rule", "ShardCtx",
    "aggregate_pytree_cgc", "aggregate_pytree_cgc_sum",
    "aggregate_pytree_mean", "collectives", "echo_dp", "fsdp",
    "inject_byzantine", "make_shard_ctx", "moe_sharded",
    "spec_for", "tree_shardings", "tree_specs", "worker_index",
]
