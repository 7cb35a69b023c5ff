from k8s_llm_rca_tpu.parallel.ring_attention import ring_attention  # noqa: F401
from k8s_llm_rca_tpu.parallel.ulysses import ulysses_attention  # noqa: F401
from k8s_llm_rca_tpu.parallel.pipeline import (  # noqa: F401
    kv_cache_stage_specs, kv_scale_stage_specs, llama_pipeline_forward,
    paged_pp_decode_step, paged_pp_prefill, pipeline_apply,
    shard_stacked_layers, stack_llama_stages,
)
from k8s_llm_rca_tpu.parallel.moe import expert_parallel_moe  # noqa: F401
