"""The check driver of a model with latent attention (``deepseek_v3``), whose
cache is pages of ONE row a token in every layer, the token's normed latent
and behind it the one rotated key all heads share, and no values: the paged
engine's own prefill and decode programs, called as the engine calls them, on
pages no request holds yet.

The contract is ``checks/paged_kv.py``'s (``run``, ``cached``,
``decode_once``), and ``run`` and ``decode_once`` are its own: the programs
take the same arguments whatever a page holds (prefill in the published form,
keys and values per head written out and dropped; decode absorbed, over the
rows).  What differs is ``cached``: the rows by token under the reference's
name for them (``reference/deepseek_v3.py``: ``latent``), read from
``PagePool.k`` through the prompt's pages as the decode kernel reads them, as
stored, and what they keep below the grain of a token's int8 grid
(``latent_grain``), whatever type the engine holds them in.  That the pool
has no values is the BUILT engine's to show (``engine.pool.v``): a pool that
has them is not this driver's.  The engine refuses chunked prefill for such a
model, so a prompt takes the single-row or the batched program, as its
admission would.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from benchmarks.checks import paged_kv
from benchmarks.reference.nemotron_h import below_int8

run = paged_kv.run
decode_once = paged_kv.decode_once


def cached(engine, prompts: Sequence[Sequence[int]], steps: int,
           rows: int = None, bucket: int = None
           ) -> List[Dict[str, np.ndarray]]:
    """After ``run`` with the same arguments: of each prompt and its fed
    tokens ``latent`` [layers, tokens, row] and its grain."""
    import jax
    import jax.numpy as jnp

    pool, page = engine.pool, engine.engine_cfg.page_size
    if pool.v is not None or pool.quantized:
        raise ValueError(
            "check driver 'paged_latent': the engine's pool holds values "
            "or scales beside its rows; a latent pool holds rows alone")

    @jax.jit
    def read(data, table):
        return jnp.take(data, table, axis=1).astype(jnp.float32)

    _, own = paged_kv._layout(engine, [len(p) for p in prompts], steps,
                              bucket)
    out = []
    for prompt, pages in zip(prompts, own):
        n = len(prompt) + steps
        table = jnp.asarray(pages[:-(-n // page)], jnp.int32)
        # the pool keeps a row in whole tiles of 128 lanes, zeros behind
        latent = np.asarray(read(pool.k, table)).reshape(
            pool.k.shape[0], -1, pool.k.shape[-1])[
                :, :n, :engine.model_cfg.latent_row]
        out.append({"latent": latent, "latent_grain": below_int8(latent)})
    return out
