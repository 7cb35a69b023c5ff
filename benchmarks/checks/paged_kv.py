"""The check driver of a model whose cache is pages of keys and values in
every layer: the paged engine's own prefill and decode programs, called as
the engine calls them (as ``chip_smoke.py`` does), on pages of the engine's
pool that no request holds yet.

A check driver is what ``lib/correct.py`` and ``lib/warmup.py`` know of a
model's cache.  An architecture's file names it (``"check": "paged_kv"``);
a model whose state is something else (a recurrent state per sequence, a
latent cache) brings a driver of its own as a new file beside this one:

- ``run(engine, prompts, steps, **shape) -> (seqs, logits)``: on the idle
  engine, prefill ``prompts`` (lists of token ids) with the engine's own
  programs through the engine's own cache, then decode ``steps`` tokens,
  each the engine's own greedy choice fed back.  For each prompt: the tokens
  fed (prompt + ``steps``) and float32 logits ``[steps + 1, vocab]``, one
  row after the prompt's last token and one after each fed token.
  ``shape`` is what the mix's ``check`` group holds beside
  ``prompt_tokens``: here ``rows`` and ``bucket``, the one prefill shape
  every prompt is padded into.  Without them each prompt takes the path the
  engine's admission takes for its length: alone in its bucket the
  single-row program, with others of its bucket one batched prefill padded
  to a power of two, and over ``prefill_chunk_budget`` (where the engine
  has one) the chunk program, one budget-sized chunk after another over the
  pages the earlier ones wrote.
- ``cached(engine, prompts, steps, **shape)``: after ``run`` with the same
  arguments, what the cache holds of each prompt and its fed tokens, read as
  the engine reads it: for each prompt ``{name: float32 [layers, tokens,
  width]}``, under the names the configuration's reference gives its own
  (``reference/decoder.py::forward``: ``k`` and ``v``).
- ``decode_once(engine)``: the stepwise decode program run once on an idle
  batch, for a mix whose warm list asks for it (``decode_step``).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


def run(engine, prompts: Sequence[Sequence[int]], steps: int,
        rows: int = None, bucket: int = None
        ) -> Tuple[List[List[int]], List[np.ndarray]]:
    import jax.numpy as jnp

    from k8s_llm_rca_tpu.engine.paged import TRASH_PAGE

    cfg, ecfg = engine.model_cfg, engine.engine_cfg
    b, pps = ecfg.max_batch, engine.pages_per_seq
    n_seq = len(prompts)
    buckets, own = _layout(engine, [len(p) for p in prompts], steps, bucket)

    if bucket is not None:
        first = _prefill_one_shape(engine, prompts, rows, bucket, own)
    else:
        first = _prefill_as_admitted(engine, prompts, buckets, own)
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


def _layout(engine, lens, steps: int, bucket: int = None):
    """Each prompt's bucket and its pages, 1.. of the idle pool."""
    ecfg = engine.engine_cfg
    page, pps = ecfg.page_size, engine.pages_per_seq
    if bucket is not None:
        buckets = [bucket] * len(lens)
    else:
        buckets = [min(engine._bucket(n), pps * page) for n in lens]
    step_pages = -(-steps // page)
    # a bucket's pages plus those for the steps, which in the top bucket lie
    # inside it (a table holds no more)
    own, at = [], 1
    for bk in buckets:
        own.append(at + np.arange(min(bk // page + step_pages, pps)))
        at += len(own[-1])
    if at > ecfg.num_pages or len(lens) > ecfg.max_batch:
        raise ValueError(f"the check's {len(lens)} prompts of {list(lens)} "
                         f"tokens need {at} pages and {len(lens)} slots; the "
                         f"pool has {ecfg.num_pages} and {ecfg.max_batch}")
    return buckets, own


def cached(engine, prompts: Sequence[Sequence[int]], steps: int,
           rows: int = None, bucket: int = None
           ) -> List[Dict[str, np.ndarray]]:
    """What the pool holds of each prompt and its ``steps`` fed tokens after
    ``run`` with the same arguments, read as the engine's own prefix
    prefill reads it (gathered by page, unpacked, times the token's scale)."""
    import jax
    import jax.numpy as jnp

    from k8s_llm_rca_tpu.engine.paged import _pool_packed
    from k8s_llm_rca_tpu.models.llama import _dequant_layer

    cfg, page, pool = engine.model_cfg, engine.engine_cfg.page_size, engine.pool
    packed = _pool_packed(cfg, pool)

    @jax.jit                  # one program a table length, not one an operation
    def read(data, scale, table):
        return _dequant_layer(
            jnp.take(data, table, axis=1),
            None if scale is None else jnp.take(scale, table, axis=1),
            jnp.float32, packed)

    _, own = _layout(engine, [len(p) for p in prompts], steps, bucket)
    out = []
    for prompt, pages in zip(prompts, own):
        n = len(prompt) + steps
        table = jnp.asarray(pages[:-(-n // page)], jnp.int32)
        out.append({
            name: np.asarray(read(data, scale, table)).reshape(
                cfg.n_layers, -1, cfg.kv_dim)[:, :n]
            for name, data, scale in (("k", pool.k, pool.k_scale),
                                      ("v", pool.v, pool.v_scale))})
    return out


def _prefill_one_shape(engine, prompts, rows: int, bucket: int, own):
    """Every prompt in one batched prefill of ``rows`` x ``bucket``."""
    import jax.numpy as jnp

    n_seq, n_pages = len(prompts), bucket // engine.engine_cfg.page_size
    tokens = np.zeros((rows, bucket), np.int32)
    lengths = np.zeros((rows,), np.int32)
    maps = np.zeros((rows, n_pages), np.int32)
    for i in range(rows):                 # padding rows repeat the last one
        j = min(i, n_seq - 1)
        tokens[i, :len(prompts[j])] = prompts[j]
        lengths[i] = len(prompts[j])
        maps[i] = own[j][:n_pages]
    engine.pool, logits = engine._prefill_batch(
        engine.model_cfg, engine.params, engine.pool, jnp.asarray(tokens),
        jnp.asarray(lengths), jnp.asarray(maps))
    return [np.asarray(logits[i], np.float32) for i in range(n_seq)]


def _prefill_as_admitted(engine, prompts, buckets, own):
    """Each prompt by the program the engine's admission dispatches for a
    prompt of its length on a prefix miss (``_tick_admission``)."""
    import jax.numpy as jnp

    from k8s_llm_rca_tpu.engine.paged import TRASH_PAGE

    cfg, page = engine.model_cfg, engine.engine_cfg.page_size
    budget = engine.engine_cfg.prefill_chunk_budget
    first = [None] * len(prompts)
    chunked = [i for i, p in enumerate(prompts) if budget and len(p) > budget]
    for i in chunked:                     # ``_advance_prefill``, chunk by chunk
        prompt, done = prompts[i], 0
        while done < len(prompt):
            n = min(budget, len(prompt) - done)
            pre, table_pages = done // page, 1
            while table_pages < pre:
                table_pages *= 2
            prefix_table = np.full((table_pages,), TRASH_PAGE, np.int32)
            prefix_table[:pre] = own[i][:pre]
            padded = np.zeros((1, budget), np.int32)
            padded[0, :n] = prompt[done:done + n]
            page_map = np.full((budget // page,), TRASH_PAGE, np.int32)
            n_new = -(-n // page)
            page_map[:n_new] = own[i][pre:pre + n_new]
            engine.pool, logits = engine._prefill_chunk(
                cfg, engine.params, engine.pool, jnp.asarray(padded),
                jnp.int32(n), jnp.int32(done), jnp.asarray(prefix_table),
                jnp.asarray(page_map))
            done += n
        first[i] = np.asarray(logits[0], np.float32)
    for bucket in sorted(set(buckets)):
        group = [i for i, bk in enumerate(buckets)
                 if bk == bucket and i not in chunked]
        n_pages = bucket // page
        if len(group) == 1 and engine._prefill is not None:    # ``_admit``
            i = group[0]
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :len(prompts[i])] = prompts[i]
            engine.pool, logits = engine._prefill(
                cfg, engine.params, engine.pool, jnp.asarray(padded),
                jnp.int32(len(prompts[i])), jnp.asarray(own[i][:n_pages]))
            first[i] = np.asarray(logits[0], np.float32)
        elif group:                                        # ``_admit_batch``
            rows = 1
            while rows < len(group):
                rows *= 2
            rows_first = _prefill_one_shape(
                engine, [prompts[i] for i in group], rows, bucket,
                [own[i] for i in group])
            for i, row in zip(group, rows_first):
                first[i] = row
    return first


def decode_once(engine) -> None:
    import jax.numpy as jnp

    from k8s_llm_rca_tpu.engine.paged import TRASH_PAGE

    b, pps = engine.engine_cfg.max_batch, engine.pages_per_seq
    engine.pool, _ = engine._decode(
        engine.model_cfg, engine.params, engine.pool,
        jnp.ones((b,), jnp.int32), jnp.ones((b,), jnp.int32),
        jnp.full((b, pps), TRASH_PAGE, jnp.int32),
        use_kernel=engine.use_kernel)
