from .long_context import make_context_parallel_attention, sequence_parallel_attention
from .moe import (
    held_expert_ffn,
    init_held_experts,
    init_moe_ffn,
    moe_ffn,
    moe_shard_rules,
    route_top_k,
)
from .pipeline import (
    make_pipeline_forward,
    make_pipeline_train_step_1f1b,
    merge_microbatches,
    prepare_pipeline,
    split_into_stages,
    split_microbatches,
)
from .sharding import (
    FSDP_AXES,
    ShardingPlan,
    ShardingRules,
    canonicalize_spec,
    host_memory_kind,
    host_offload_supported,
    infer_param_specs,
    llama_tp_rules,
    make_host_offloaded_step,
    make_sharding_plan,
    offload_memory_kinds,
    offload_to_host,
    offload_tree_shardings,
    replicate,
    shard_like_params,
    shard_params,
    tree_specs_like,
    zero1_state_specs,
)
from .weight_update import (
    FusedZero1Incompatible,
    Zero1BucketPlan,
    build_bucket_plan,
    init_bucketed_opt_state,
    make_fused_zero1_update,
)
