from k8s_llm_rca_tpu.ops.norms import rms_norm, layer_norm  # noqa: F401
from k8s_llm_rca_tpu.ops.rope import rope_frequencies, apply_rope  # noqa: F401
from k8s_llm_rca_tpu.ops.attention import (  # noqa: F401
    causal_attention,
    decode_attention,
    repeat_kv,
)
from k8s_llm_rca_tpu.ops.quant_matmul import (  # noqa: F401
    qmm,
    qmm_experts,
    qmm_head,
    qmm_swiglu_experts,
    quant_matmul,
    quant_matmul_experts,
    quant_matmul_head,
    quant_swiglu_experts,
)
