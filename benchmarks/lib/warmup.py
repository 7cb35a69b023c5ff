"""Warm up the programs a cell's traffic uses, and no others.

The paged engine compiles one program per shape: a prefix-miss prefill per
(rows, bucket), a prefix-hit prefill per (rows, suffix bucket, prefix-table
pages), the stepwise decode, the decode scan per length, the DFA scan.
Around each sit small programs of the same shapes (sampling, indexing, the
device-state edits).  Each shape is reached here the way traffic reaches it,
by submitting seeded requests of exactly that shape to the idle engine, so
that everything the engine runs for the shape is in its caches before the
window opens.  What is missed compiles inside the window and the run reports
``correct: false``.

Which shapes: a mix of independent unshared requests (``"derive": true`` in
the traffic file's ``warm`` group) does not list them.  They follow from the
mix's own length range (``prompt_tokens.min``/``max``), from how many
requests can wait for one admission (``arrivals.clients`` in a closed loop)
and from the engine's own arithmetic, read off the built engine: its bucket
of a length (``engine._bucket``), its prefix-miss group (at most
``MISS_GROUP_ROWS`` same-bucket requests, padded to a power of two) and its
scan lengths (the powers of two up to ``decode_chunk``).  ``derive`` holds
for a cell that runs without pool pressure: a preempted sequence prefills
again at prompt + answer length and after a prefix hit, which no length
range foretells, so the run's report line counts ``preemptions`` and a cell
is given a rate at which there are none (PERF.md section 4).  A mix whose
requests share prefixes lists what only its author knows:

- ``miss: [[rows, bucket], ...]``: ``rows`` unshared prompts of one length in
  the bucket arrive together and admit as one batched prefill.
- ``hit: [[rows, suffix bucket, table pages], ...]``: one long base prompt is
  prefilled first (it must fall in a listed miss bucket); then ``rows``
  prompts that share exactly the base's first n pages, n padding to
  ``table pages``, arrive together and admit as one batched chunk prefill.
- ``decode_scan: [steps]``: a scan's length is the largest power of two in
  ``decode_chunk`` that fits the sequence's allocated pages, so a prompt that
  ends ``steps`` short of its bucket first decodes by a scan of ``steps``
  (by the stepwise program where ``steps`` is 1) and by ``decode_chunk`` once
  its pages have grown: one unshared request per listed length.
- ``decode_step``: the stepwise program, run once on an idle batch by the
  architecture's check driver (``benchmarks/checks/``, ``decode_once``), for
  a mix whose own ramp runs interpreted grammars and so warms what surrounds
  it.
- ``dfa_schemas: ["module:function", ...]``: one request under each schema's
  compiled DFA, alone in the batch, which rides the DFA scan.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, List

import numpy as np


# engine/paged.py::_admission_group: a prefix-miss group is capped at 8
# requests (and by free slots and free pages, which only lower it);
# _admit_batch pads its rows to a power of two
MISS_GROUP_ROWS = 8


def powers_of_two(most: int) -> List[int]:
    out, n = [], 1
    while n <= most:
        out.append(n)
        n *= 2
    return out


def shapes(engine, traffic: Dict[str, Any]) -> Dict[str, Any]:
    """The traffic file's ``warm`` group with what ``derive`` stands for
    written out: the explicit lists ``warm`` works through."""
    spec = dict(traffic["warm"])
    if not spec.pop("derive", False):
        return spec
    ecfg = engine.engine_cfg
    lengths = traffic["prompt_tokens"]
    buckets = sorted({engine._bucket(n) for n in range(
        int(lengths["min"]), int(lengths["max"]) + 1)})
    waiting = int(traffic["arrivals"].get("clients", MISS_GROUP_ROWS))
    rows = powers_of_two(min(MISS_GROUP_ROWS, ecfg.max_batch,
                             max(1, waiting)))
    if rows[-1] < min(waiting, MISS_GROUP_ROWS, ecfg.max_batch):
        rows.append(2 * rows[-1])         # 3 callers pad to 4 rows
    spec["miss"] = [[r, b] for b in buckets for r in rows]
    spec["decode_scan"] = powers_of_two(ecfg.decode_chunk)
    return spec


def warm(engine, traffic: Dict[str, Any], driver, seed: int = 0) -> int:
    """``driver``: the architecture's check driver
    (``lib/build.py::check_driver``)."""
    import jax

    from k8s_llm_rca_tpu.engine.constrain import make_grammar

    spec = shapes(engine, traffic)
    cfg, ecfg = engine.model_cfg, engine.engine_cfg
    page = ecfg.page_size
    rng = np.random.default_rng(seed)

    def tokens(n: int) -> List[int]:
        return [int(t) for t in rng.integers(3, cfg.vocab_size - 1, n)]

    def arrive(prompts, max_new_tokens=1, grammar=None) -> None:
        for p in prompts:
            engine.submit(p, max_new_tokens=max_new_tokens, grammar=grammar)
        engine.run_to_completion()

    n = 0
    miss = [tuple(m) for m in spec.get("miss", [])]
    for rows, bucket in miss:
        arrive([tokens(bucket - 8) for _ in range(rows)])
        n += 1
    hits = spec.get("hit", [])
    if hits:
        # the base every hit shape shares its first pages with: long enough
        # for the largest table, and itself a listed miss shape
        most = max(t for _, _, t in hits)
        base = tokens((2 if most <= 2 else most // 2 + 2) * page + 1)
        if (1, engine._bucket(len(base))) not in miss:
            raise ValueError(f"the hit shapes need a base prompt of "
                             f"{len(base)} tokens, whose miss shape "
                             f"[1, {engine._bucket(len(base))}] the warm "
                             f"list does not hold")
        arrive([base])
        for rows, bucket, table in hits:
            n_cp = 2 if table <= 2 else table // 2 + 1    # pads to `table`
            shared = base[:n_cp * page + 3]
            arrive([shared + tokens(bucket - 8 - 3) for _ in range(rows)])
            n += 1
    for steps in spec.get("decode_scan", []):
        short = int(steps) if steps < ecfg.decode_chunk else 4 * page
        arrive([tokens(miss[0][1] - short)],
               max_new_tokens=int(steps) + ecfg.decode_chunk + 1)
        n += 1
    if spec.get("decode_step"):
        driver.decode_once(engine)
        n += 1
    for path in spec.get("dfa_schemas", []):
        module, function = path.split(":")
        grammar = make_grammar(
            getattr(importlib.import_module(module), function)(),
            engine.tokenizer, prefer_native=ecfg.native)
        if getattr(grammar, "tables", None) is None:
            raise RuntimeError(f"{path} compiles to no DFA at this "
                               f"vocabulary: it cannot warm the DFA scan")
        # a budget too short for a whole document: the scan closes it by
        # force, which is all a warm-up needs
        arrive([tokens(miss[0][1] - 4 * page)],
               max_new_tokens=ecfg.decode_chunk + 1, grammar=grammar)
        n += 1
    jax.block_until_ready(engine.pool)
    return n
