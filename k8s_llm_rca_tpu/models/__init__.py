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
    model with a layer table (``cfg.layer_table``, however it is stated:
    ``nemotron_h``'s one mixer a layer, ``granitemoehybrid``'s mixer and
    MLP) is models/nemotron_h.py's, one without is Llama's."""
    from k8s_llm_rca_tpu.models import llama, nemotron_h

    module = nemotron_h if cfg.layer_table else llama
    return module.init_params(cfg, key, tensor_transform=tensor_transform)
