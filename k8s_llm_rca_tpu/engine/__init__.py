from k8s_llm_rca_tpu.engine.engine import SequenceResult  # noqa: F401
from k8s_llm_rca_tpu.engine.sampling import sample_tokens, SamplingParams  # noqa: F401


def make_engine(model_cfg, engine_cfg, params, tokenizer, **kw):
    """The engine: ``PagedInferenceEngine`` (page pool + preemption +
    prefix caching).  ``paged=False`` asked for the contiguous-slot
    engine, which is gone; it is refused here and read nowhere else."""
    if not engine_cfg.paged:
        raise ValueError(
            "EngineConfig(paged=False): the contiguous-slot engine was "
            "removed; the one engine is PagedInferenceEngine (page_size "
            "and num_pages size its pool)")
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine

    return PagedInferenceEngine(model_cfg, engine_cfg, params, tokenizer,
                                **kw)
