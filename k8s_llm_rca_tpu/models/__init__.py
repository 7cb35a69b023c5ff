from k8s_llm_rca_tpu.models.llama import (  # noqa: F401
    KVCache,
    init_cache,
    forward,
    prefill,
    decode_step,
)
from k8s_llm_rca_tpu.models import encoder, mixtral  # noqa: F401


def init_params(cfg, key, tensor_transform=None):
    """Seeded weights from the builder of the configuration's family: a
    layer table (``cfg.layer_pattern``) is nemotron_h's, none is Llama's."""
    from k8s_llm_rca_tpu.models import llama, nemotron_h

    module = nemotron_h if cfg.layer_pattern else llama
    return module.init_params(cfg, key, tensor_transform=tensor_transform)
