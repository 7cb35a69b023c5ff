"""The set-up half of ``correct``: does the engine compute the model?

For two seeded prompts, the engine's own prefill program and eight steps of
its own decode program, through the engine's own cache, give logits at nine
positions each, and leave the prompts and the fed tokens in the cache; the
configuration's plain reference (``benchmarks/reference``) gives float32
logits, and float32 keys and values, for the same tokens from the same
stored weights.

How the engine's programs are reached is the CHECK DRIVER's, a module of
``benchmarks/checks/`` that the architecture's file names (``"check"``):
what a cache is made of (pages of keys and values, a state per sequence) and
which program prefills a prompt of which length is known there and not here.
What decides stays here: the seeded prompts, the call of the reference, the
tolerances, the steps, the median rule and who may have near-ties.

The lengths are the mix's to state (its ``check`` group): ``rows`` and
``bucket`` give one prefill shape, checked at ``bucket - bucket // 4`` and
``bucket - 2 * page`` tokens; ``prompt_tokens: [n, ...]`` gives the lengths
themselves, and the driver takes whatever path the engine takes for a
prompt of each length (one bucket, or chunks).  Fixed lengths, seeded
content: a length drawn from the seed would compile the reference anew for
every seed.

Logits and not tokens: with seeded random weights the logits are nearly flat
and greedy tokens flip on bf16 rounding (PERF.md, PR 21).

TOLERANCE: at each position the largest absolute difference may be at most 6%
of the largest reference logit, and the median over the positions at most a
third of that.  What it has to cover: bf16 activations and bf16-rounded
dequantized weights through every layer, on the engine's side only, against
float32 at ``highest``; and the int8 rounding of cached keys and values
(per-token scales, about 0.4% per element).  Measured on the chip (PERF.md
section 6, PR 22): 1.4-1.8% on mistral-7b-v0.3, 0.9% on mixtral-8x7b-d8.  A
dropped layer or a decode program that returns other logits moves every
position and lands far outside it (``tests/test_checks.py``).

CACHE_TOLERANCE: what the cache holds afterwards (the driver's ``cached``)
against the keys and values the reference computed on the way, token by
token: the distance as a share of the reference's norm of that token.  Over
the tokens a prefill wrote and, apart, over those the decode steps wrote, for
keys and for values, the median token of each layer; the worst layer's may be
at most ``CACHE_TOLERANCE``.  This is the number that holds the engine to the
cache precision the file states, which the logits do not: with the cache in
int4 under a file that states int8 (``benchmarks/control.py``, on the chip at
the cells' own sizes; PERF.md section 6, PR 27) the logits' median reads
1.66-1.77% against 1.35-1.47% sound on mistral-7b-v0.3 and 1.32-1.40% against
0.73-0.80% on mixtral-8x7b-d8, under the 2% it may; the engine's decode
against the engine's own prefill of the same tokens reads 2.2% against 1.3%
and 1.7% against 0.8%, because two bf16 paths through 32 layers differ by as
much as either does from float32.  A token's int8 rounding at one scale a
token is some 0.8% of its norm and int4's some 13%; PERF.md has the readings
the limit was set from.  A median, because a token whose router tie fell the
other way (below) is far off in every later layer and right all the same.

ROUTER NEAR-TIES: with seeded random weights a sparse-expert router's logits
are nearly flat, and where the second and third of them tie within bf16
rounding the engine and the float32 reference send that token to different
experts: its logits then differ by 7-20% though both sides are right (seen on
the chip in 5 of 6 seeds on mixtral-8x7b-d8, and on a CPU with the ``tiny``
configuration in bf16, where 7 of 8 seeds sit at 0.5-0.6% and one at 19.8%).
So such a model may have up to a third of its positions over the tolerance;
the median rule still holds it to the precision it states.  Which model is
such a model is the architecture's to state, with its reason: its file has
``near_ties``, a sentence that says which discrete choice is made from
nearly flat scores and where it was seen.  Any selector of that kind (a
router over experts, a top-k over blocks of context) may state one; an
architecture without the key is allowed no position over.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, Sequence

import numpy as np

from benchmarks.lib import build

TOLERANCE = 0.06
CACHE_TOLERANCE = 0.05
STEPS = 8


def check(engine, conf: Dict[str, Any], seed: int, rows: int = None,
          bucket: int = None, prompt_tokens: Sequence[int] = None
          ) -> Dict[str, Any]:
    import jax

    arch = build.architecture(conf)
    driver = build.check_driver(conf)
    reference = importlib.import_module(
        "benchmarks.reference." + conf["reference"])
    cfg, ecfg = engine.model_cfg, engine.engine_cfg
    rng = np.random.default_rng(seed)
    if (prompt_tokens is None) == (rows is None and bucket is None) or (
            (rows is None) != (bucket is None)):
        raise ValueError(
            "the mix's check group states either prompt_tokens or rows and "
            f"bucket: got prompt_tokens={prompt_tokens!r}, rows={rows!r}, "
            f"bucket={bucket!r}")
    # fixed lengths (the reference compiles once per length), seeded content
    if prompt_tokens is None:
        lens = np.array([bucket - bucket // 4, bucket - 2 * ecfg.page_size])
        shape = {"rows": rows, "bucket": bucket}
    else:
        lens = np.array([int(n) for n in prompt_tokens])
        shape = {}
    for n in lens:
        if n < 1 or n + STEPS > ecfg.max_seq_len:
            raise ValueError(
                f"check.prompt_tokens: {int(n)} tokens and {STEPS} decode "
                f"steps do not fit max_seq_len {ecfg.max_seq_len}")
    prompts = [rng.integers(3, cfg.vocab_size - 1, int(n)) for n in lens]
    seqs, got = driver.run(engine, [list(p) for p in prompts], STEPS, **shape)
    held = driver.cached(engine, [list(p) for p in prompts], STEPS, **shape)

    errs: list = []
    ref_max = 0.0
    agree = total = 0
    # of each thing a cache holds: every token's distance from the
    # reference's, [layers, tokens], the prompts' tokens (which a prefill
    # wrote) apart from the fed ones (which the decode steps wrote)
    token_errs: Dict[str, list] = {}
    for i in range(len(prompts)):
        first = int(lens[i]) - 1
        want, want_held = reference.forward(
            conf, engine.params, np.asarray(seqs[i], np.int32),
            np.arange(first, first + STEPS + 1))
        want = np.asarray(want)
        have = np.asarray(got[i], np.float32)
        errs += [float(e) for e in np.max(np.abs(have - want), axis=-1)]
        ref_max = max(ref_max, float(np.max(np.abs(want))))
        agree += int(np.sum(have.argmax(-1) == want.argmax(-1)))
        total += STEPS + 1
        for name, there in want_held.items():
            there = np.asarray(there)
            if held[i][name].shape != there.shape:
                raise ValueError(
                    f"check driver {arch['check']!r}: the cache holds "
                    f"{name} of shape {held[i][name].shape} where the "
                    f"reference {conf['reference']!r} holds {there.shape}")
            off = np.linalg.norm(held[i][name] - there, axis=-1) \
                / np.linalg.norm(there, axis=-1)
            token_errs.setdefault(name + ".prefill", []).append(
                off[:, :first + 1])
            token_errs.setdefault(name + ".decode", []).append(
                off[:, first + 1:])
    jax.block_until_ready(engine.pool)
    worst, middle = max(errs), float(np.median(errs))
    over = sum(1 for e in errs if not e <= TOLERANCE * ref_max)
    allowed = total // 3 if arch.get("near_ties") else 0
    rel = (lambda e: e / ref_max if ref_max else None)
    # the median token of the worst layer
    cache_errs = {name: float(np.max(np.median(np.concatenate(offs, axis=1),
                                               axis=1)))
                  for name, offs in token_errs.items()}
    # what decides, each number beside its limit; ``ok`` is nothing else
    compared = {
        "positions_over": {"value": over, "limit": allowed},
        "median_rel_err": {"value": rel(middle), "limit": TOLERANCE / 3},
        "cache_rel_err": {"value": max(cache_errs.values()),
                          "limit": CACHE_TOLERANCE},
        "positions_not_finite": {
            "value": sum(1 for e in errs if not np.isfinite(e)), "limit": 0}}
    return {"ok": all(c["value"] is not None and c["value"] <= c["limit"]
                      for c in compared.values()),
            "max_abs_err": worst, "ref_max_abs": ref_max,
            "rel_err": rel(worst), "median_rel_err": rel(middle),
            "positions_over": over, "positions_allowed_over": allowed,
            "argmax_equal": agree, "positions": total,
            "prompt_tokens": [int(n) for n in lens],
            "driver": arch["check"],
            "cache_rel_err": max(cache_errs.values()),
            "cache_rel_errs": cache_errs,
            "compared": compared}
