"""The check driver of a model whose full-attention layers cache every
token's keys and values in pages and whose sliding-window layers keep the
last window in a ring of pages per decode slot (``exaone_moe``): the paged
engine's own prefill and decode programs, called as the engine calls them,
on pages no request holds yet and on the first decode slots of the idle
engine.

The contract is ``checks/paged_kv.py``'s (``run``, ``cached``,
``decode_once``); ``run`` is ``checks/paged_kv_state.py``'s, because the
prefill programs are told the slot as they are there: prompt ``i`` is
prefilled INTO slot ``i``, whose rings take the prompt's tail, and the decode
steps write every slot's ring in place.  What differs is ``cached``: layer by
layer in the model's order, a full layer's keys and values by token, read
from the pages as the engine's own gather reads them, and a window layer's
LAST WINDOW out of slot ``i``'s ring, each position from the ring page and
offset the engine's write put it at (``(p // page) % ring pages``, ``p %
page``), in position order, laid over as many rows as the sequence has tokens
as the reference lays its own (``reference/exaone_moe.py::tile_window``).
Which layers are window layers, and how wide the window is, is the BUILT
engine's to say (``engine.model_cfg``), not the file's: an engine that keeps
another window than the file states holds other tokens in those rows.  And
what both keep below the grain of a token's int8 grid (``below_int8``),
whatever type the engine holds them in.  The engine refuses chunked prefill
for such a model, so a prompt takes the single-row or the batched program, as
its admission would.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from benchmarks.checks import paged_kv, paged_kv_state
from benchmarks.reference.exaone_moe import tile_window
from benchmarks.reference.nemotron_h import below_int8

run = paged_kv_state.run
decode_once = paged_kv.decode_once


def cached(engine, prompts: Sequence[Sequence[int]], steps: int,
           rows: int = None, bucket: int = None
           ) -> List[Dict[str, np.ndarray]]:
    """After ``run`` with the same arguments: of each prompt and its fed
    tokens ``k`` and ``v`` [layers, tokens, width] (see the top of the
    file) and their grains."""
    import jax
    import jax.numpy as jnp

    from k8s_llm_rca_tpu.engine.paged import _pool_packed
    from k8s_llm_rca_tpu.models.llama import _dequant_layer

    cfg, page, pool = engine.model_cfg, engine.engine_cfg.page_size, engine.pool
    packed = _pool_packed(cfg, pool)
    windows = cfg.attn_windows

    @jax.jit
    def read(data, scale, table):
        return _dequant_layer(
            jnp.take(data, table, axis=1),
            None if scale is None else jnp.take(scale, table, axis=1),
            jnp.float32, packed)

    def by_token(src, table):
        """[layers of ``src``, tokens of ``table``'s pages, width]"""
        return {name: np.asarray(read(data, scale, table)).reshape(
                    data.shape[0], -1, cfg.kv_dim)
                for name, data, scale in (("k", src.k, src.k_scale),
                                          ("v", src.v, src.v_scale))}

    _, own = paged_kv._layout(engine, [len(p) for p in prompts], steps,
                              bucket)
    out = []
    for i, (prompt, pages) in enumerate(zip(prompts, own)):
        n = len(prompt) + steps
        full = by_token(pool, jnp.asarray(pages[:-(-n // page)], jnp.int32))
        ring = last = None
        if pool.ring is not None:
            r = cfg.ring_pages(page)
            ring = by_token(pool.ring, i * r + jnp.arange(r, dtype=jnp.int32))
            # the last window's positions, where the engine's write put them
            last = np.arange(n - min(cfg.attn_window, n), n)
            last = (last // page) % r * page + last % page
        held = {}
        for name in ("k", "v"):
            layers, ai, wi = [], 0, 0
            for window in windows:
                if window:
                    layers.append(tile_window(ring[name][wi][last], n))
                    wi += 1
                else:
                    layers.append(full[name][ai, :n])
                    ai += 1
            held[name] = np.stack(layers)
            held[name + "_grain"] = below_int8(held[name])
        out.append(held)
    return out
