"""What the latent pool spends on ONE cached token, every layer's row
together, padding included: the program's level
``engine.latent_cache_bytes_per_token``, which the engine sets when it builds
its pool from what the device says the pool took (a row that is no multiple
of 128 lanes is kept at one that is).  Beside it stands what keys and values
per head would take (``mla_costs.row_bytes`` is the unpadded row).  None
where the program has no such level."""

LAYER = "Engine tick (engine/paged.py)"
UNIT = "bytes"
MOVES = "gap_ms_p50"


def read(ctx):
    from k8s_llm_rca_tpu.utils.logging import METRICS

    return METRICS.count("engine.latent_cache_bytes_per_token") or None
