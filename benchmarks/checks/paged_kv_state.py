"""The check driver of a model that keeps, beside pages of keys and values
for its attention layers, a recurrent state per decode slot for its Mamba-2
layers (``nemotron_h``): the paged engine's own prefill and decode programs,
called as the engine calls them, on pages no request holds yet and on the
first decode slots of the idle engine.

The contract is ``checks/paged_kv.py``'s (``run``, ``cached``,
``decode_once``).  What differs is what the cache is: prompt ``i`` is
prefilled INTO slot ``i`` (the prefill programs are told the slot, and
replace its state by the row's), the decode steps move every slot's state on
in place, and ``cached`` reads, beside the attention layers' keys and values
by token, slot ``i``'s ``ssm_state`` and ``conv_state`` as they stand after
the last fed token, laid out in rows as the reference lays its own out
(``reference/nemotron_h.py``, whose top says what each entry is and why:
``state_rows`` of the first Mamba layer's state, ``tail_rows`` of every
layer's convolution tail, turned from the engine's ``[kernel - 1,
channels]`` to the published ``[channels, kernel - 1]``), and what state and
pages keep below the grain of bfloat16 and of a token's int8 grid
(``below_bfloat16``, ``below_int8``), whatever type the engine holds them
in: the reference compares those where the file states float32 and
unquantized pages.  The engine refuses chunked prefill for such a model, so
a prompt takes the single-row or the batched program, as its admission
would.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmarks.checks import paged_kv
from benchmarks.reference.nemotron_h import (below_bfloat16, below_int8,
                                             state_rows, tail_rows)

decode_once = paged_kv.decode_once


def run(engine, prompts: Sequence[Sequence[int]], steps: int,
        rows: int = None, bucket: int = None
        ) -> Tuple[List[List[int]], List[np.ndarray]]:
    import jax.numpy as jnp

    from k8s_llm_rca_tpu.engine.paged import TRASH_PAGE

    cfg, ecfg = engine.model_cfg, engine.engine_cfg
    b, pps = ecfg.max_batch, engine.pages_per_seq
    n_seq = len(prompts)
    buckets, own = paged_kv._layout(engine, [len(p) for p in prompts], steps,
                                    bucket)
    first = [None] * n_seq
    if bucket is not None:
        groups = [(rows, bucket, list(range(n_seq)))]
    else:                                 # as ``_tick_admission`` groups them
        groups = []
        for bk in sorted(set(buckets)):
            members = [i for i, x in enumerate(buckets) if x == bk]
            n_rows = 1
            while n_rows < len(members):
                n_rows *= 2
            groups.append((n_rows, bk, members))
    for n_rows, bk, members in groups:
        for i, row in zip(members, _prefill(engine, prompts, n_rows, bk,
                                            members, own)):
            first[i] = row
    got = [[row] for row in first]
    seqs = [list(p) for p in prompts]

    tables = np.full((b, pps), TRASH_PAGE, np.int32)
    for i in range(n_seq):
        tables[i, :len(own[i])] = own[i]
    cur = np.zeros((b,), np.int32)
    pos = np.zeros((b,), np.int32)
    for _ in range(steps):
        for i in range(n_seq):
            seqs[i].append(int(np.argmax(got[i][-1])))
            cur[i] = seqs[i][-1]
            pos[i] = len(seqs[i]) - 1
        engine.pool, logits = engine._decode(
            cfg, engine.params, engine.pool, jnp.asarray(cur),
            jnp.asarray(pos), jnp.asarray(tables),
            use_kernel=engine.use_kernel)
        for i in range(n_seq):
            got[i].append(np.asarray(logits[i], np.float32))
    return seqs, [np.stack(g) for g in got]


def _prefill(engine, prompts, rows: int, bucket: int, members, own):
    """Prompts ``members`` in one prefill of ``rows`` x ``bucket``, prompt
    ``i`` into slot ``i``: alone the single-row program (``_admit``), else
    the batched one, padding rows repeating the last (``_admit_batch``)."""
    import jax.numpy as jnp

    n_pages = bucket // engine.engine_cfg.page_size
    tokens = np.zeros((rows, bucket), np.int32)
    lengths = np.zeros((rows,), np.int32)
    maps = np.zeros((rows, n_pages), np.int32)
    slots = np.zeros((rows,), np.int32)
    for r in range(rows):
        i = members[min(r, len(members) - 1)]
        tokens[r, :len(prompts[i])] = prompts[i]
        lengths[r] = len(prompts[i])
        maps[r] = own[i][:n_pages]
        slots[r] = i
    args = (engine.model_cfg, engine.params, engine.pool)
    if rows == 1 and engine._prefill is not None:
        engine.pool, logits = engine._prefill(
            *args, jnp.asarray(tokens), jnp.int32(lengths[0]),
            jnp.asarray(maps[0]), slots=jnp.asarray(slots))
    else:
        engine.pool, logits = engine._prefill_batch(
            *args, jnp.asarray(tokens), jnp.asarray(lengths),
            jnp.asarray(maps), slots=jnp.asarray(slots))
    return [np.asarray(logits[r], np.float32) for r in range(len(members))]


def cached(engine, prompts: Sequence[Sequence[int]], steps: int,
           rows: int = None, bucket: int = None
           ) -> List[Dict[str, np.ndarray]]:
    """After ``run`` with the same arguments: of each prompt and its fed
    tokens the attention layers' keys and values by token, read from the
    pages as the engine's own gather reads them, and its slot's two states
    (see the top of the file)."""
    import jax
    import jax.numpy as jnp

    from k8s_llm_rca_tpu.engine.paged import _pool_packed
    from k8s_llm_rca_tpu.models.llama import _dequant_layer

    cfg, page, pool = engine.model_cfg, engine.engine_cfg.page_size, engine.pool
    packed = _pool_packed(cfg, pool)

    @jax.jit
    def read(data, scale, table):
        return _dequant_layer(
            jnp.take(data, table, axis=1),
            None if scale is None else jnp.take(scale, table, axis=1),
            jnp.float32, packed)

    _, own = paged_kv._layout(engine, [len(p) for p in prompts], steps,
                              bucket)
    out = []
    for i, (prompt, pages) in enumerate(zip(prompts, own)):
        n = len(prompt) + steps
        table = jnp.asarray(pages[:-(-n // page)], jnp.int32)
        held = {
            name: np.asarray(read(data, scale, table)).reshape(
                cfg.n_kv_layers, -1, cfg.kv_dim)[:, :n]
            for name, data, scale in (("k", pool.k, pool.k_scale),
                                      ("v", pool.v, pool.v_scale))}
        state = np.asarray(pool.ssm_state[:, i].astype(jnp.float32))
        held["ssm_state"] = state_rows(state[:1], n)
        held["conv_state"] = tail_rows(np.swapaxes(np.asarray(
            pool.conv_state[:, i].astype(jnp.float32)), 1, 2), n)
        held["ssm_grain"] = below_bfloat16(state, n)
        held["k_grain"] = below_int8(held["k"])
        held["v_grain"] = below_int8(held["v"])
        out.append(held)
    return out
