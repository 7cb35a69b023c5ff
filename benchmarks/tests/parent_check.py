"""The logits check as the tree of PR 25 had it (``benchmarks/lib/correct.py``
at commit c3de84c, the function verbatim): it lays out pages of keys and
values itself and calls ``engine._prefill_batch`` and ``engine._decode``.
Kept as a fixture: ``test_checks.py`` holds the check that reaches the engine
through ``benchmarks/checks/paged_kv.py`` to this one's result, to the digit.
"""

from __future__ import annotations


import importlib
from typing import Any, Dict

import numpy as np

TOLERANCE = 0.06
STEPS = 8


def check(engine, conf: Dict[str, Any], rows: int, bucket: int,
          seed: int) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from k8s_llm_rca_tpu.engine.paged import TRASH_PAGE

    reference = importlib.import_module(
        "benchmarks.reference." + conf["reference"])
    cfg, ecfg = engine.model_cfg, engine.engine_cfg
    page, b, pps = ecfg.page_size, ecfg.max_batch, engine.pages_per_seq
    rng = np.random.default_rng(seed)
    n_seq = 2
    # fixed lengths (the reference compiles once per length), seeded content
    lens = np.array([bucket - bucket // 4, bucket - 2 * page])
    prompts = [rng.integers(3, cfg.vocab_size - 1, int(n)) for n in lens]
    n_pages = bucket // page
    # pages 1.. of the idle pool: the bucket's pages plus one for the steps
    own = [1 + i * (n_pages + 1) + np.arange(n_pages + 1)
           for i in range(n_seq)]

    tokens = np.zeros((rows, bucket), np.int32)
    lengths = np.zeros((rows,), np.int32)
    maps = np.zeros((rows, n_pages), np.int32)
    for i in range(rows):                 # padding rows repeat the last one
        j = min(i, n_seq - 1)
        tokens[i, :lens[j]] = prompts[j]
        lengths[i] = lens[j]
        maps[i] = own[j][:n_pages]
    engine.pool, logits = engine._prefill_batch(
        cfg, engine.params, engine.pool, jnp.asarray(tokens),
        jnp.asarray(lengths), jnp.asarray(maps))
    got = [[np.asarray(logits[i], np.float32)] for i in range(n_seq)]
    seqs = [list(p) for p in prompts]

    tables = np.full((b, pps), TRASH_PAGE, np.int32)
    for i in range(n_seq):
        tables[i, :n_pages + 1] = own[i]
    cur = np.zeros((b,), np.int32)
    pos = np.zeros((b,), np.int32)
    for _ in range(STEPS):
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

    errs: list = []
    ref_max = 0.0
    agree = total = 0
    for i in range(n_seq):
        first = int(lens[i]) - 1
        want = np.asarray(reference.logits(
            conf, engine.params, np.asarray(seqs[i], np.int32),
            np.arange(first, first + STEPS + 1)))
        have = np.stack(got[i])
        errs += [float(e) for e in np.max(np.abs(have - want), axis=-1)]
        ref_max = max(ref_max, float(np.max(np.abs(want))))
        agree += int(np.sum(have.argmax(-1) == want.argmax(-1)))
        total += STEPS + 1
    jax.block_until_ready(engine.pool)
    worst, middle = max(errs), float(np.median(errs))
    over = sum(1 for e in errs if not e <= TOLERANCE * ref_max)
    allowed = total // 3 if cfg.n_experts > 0 else 0
    return {"ok": bool(np.isfinite(worst) and over <= allowed
                       and middle <= TOLERANCE / 3 * ref_max),
            "max_abs_err": worst, "ref_max_abs": ref_max,
            "rel_err": worst / ref_max if ref_max else None,
            "median_rel_err": middle / ref_max if ref_max else None,
            "positions_over": over, "positions_allowed_over": allowed,
            "argmax_equal": agree, "positions": total,
            "prompt_tokens": [int(n) for n in lens]}
