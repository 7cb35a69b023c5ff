"""ONE definition of the multi-process serving scenario, imported by BOTH
tests/_distributed_worker.py (which serves it over the 2-process mesh) and
tests/test_distributed.py (which serves it on a single-process unsharded
engine as the greedy reference) — so the parity assertion can never drift
into comparing two different configs.

Two generate calls per engine: a 2-prompt batch (batched admission) and a
single prompt (the single-request ``_admit`` path, whose device→host first-
token fetch must also survive a process-spanning mesh — engine.host_np).
"""

BATCH_PROMPTS = ["pod pending unschedulable", "pvc not bound"]
SINGLE_PROMPT = "node notready kubelet"
MAX_NEW = 6


def model_config():
    from k8s_llm_rca_tpu.config import TINY

    return TINY.replace(max_seq_len=64)


def engine_configs():
    """[(kind, EngineConfig)] for the serve parity legs: the pool cut
    into pages of 8 and of 16."""
    from k8s_llm_rca_tpu.config import EngineConfig

    return [(f"page{page}",
             EngineConfig(max_batch=2, max_seq_len=64,
                          prefill_buckets=(16, 32, 64),
                          max_new_tokens=MAX_NEW, temperature=0.0,
                          decode_chunk=4, page_size=page,
                          num_pages=256 // page, prefix_cache=False))
            for page in (8, 16)]


def serve_all(make):
    """{key: "tok,tok;..."} for every (engine, call-shape) leg.  ``make``
    builds an engine from (model_cfg, params, tokenizer, engine_cfg) —
    the worker passes a tp_mesh-sharded builder, the test an unsharded one."""
    import jax

    from k8s_llm_rca_tpu.models import llama
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = model_config()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    batch = [list(tok.encode(t, add_bos=True)) for t in BATCH_PROMPTS]
    single = [list(tok.encode(SINGLE_PROMPT, add_bos=True))]
    out = {}
    with jax.default_matmul_precision("float32"):
        for kind, ecfg in engine_configs():
            eng = make(cfg, params, tok, ecfg)
            for shape, prompts in (("batch", batch), ("single", single)):
                res = eng.generate([list(p) for p in prompts],
                                   max_new_tokens=MAX_NEW)
                out[f"{kind}/{shape}"] = ";".join(
                    ",".join(map(str, r.token_ids)) for r in res)
    return out
