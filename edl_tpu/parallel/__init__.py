from edl_tpu.parallel.mesh import (
    batch_sharding,
    device_put_global,
    device_put_local_rows,
    fsdp_shardings,
    make_hybrid_mesh,
    make_mesh,
    replicated,
    shard_batch,
    shard_params_fsdp,
)
from edl_tpu.parallel.pipeline import (
    pipeline_apply,
    pipeline_efficiency,
    stack_stage_params,
)
from edl_tpu.parallel.pipeline_1f1b import pipeline_1f1b_loss_and_grads
from edl_tpu.parallel.pipeline_lm import (
    LMStageParams,
    merge_lm_params,
    pipeline_lm_1f1b_grads,
    pipeline_lm_logits,
    pipeline_lm_loss,
    split_lm_params,
)
from edl_tpu.parallel.ring import ring_attention, ring_attention_sharded
from edl_tpu.parallel.ulysses import ulysses_attention, ulysses_attention_sharded
from edl_tpu.parallel.sharding_rules import (
    TRANSFORMER_TP_RULES,
    shard_params_by_rules,
    spec_for_path,
)

__all__ = [
    "device_put_global",
    "device_put_local_rows",
    "fsdp_shardings",
    "make_hybrid_mesh",
    "make_mesh",
    "batch_sharding",
    "replicated",
    "shard_batch",
    "shard_params_fsdp",
    "ring_attention",
    "ring_attention_sharded",
    "ulysses_attention",
    "ulysses_attention_sharded",
    "pipeline_apply",
    "pipeline_efficiency",
    "stack_stage_params",
    "LMStageParams",
    "split_lm_params",
    "merge_lm_params",
    "pipeline_lm_logits",
    "pipeline_lm_loss",
    "pipeline_lm_1f1b_grads",
    "pipeline_1f1b_loss_and_grads",
    "TRANSFORMER_TP_RULES",
    "shard_params_by_rules",
    "spec_for_path",
]
