"""Speculative decoding drafts: n-gram prompt lookup and a draft MODEL.

Draft tokens are proposed either by matching the sequence's most recent
n-gram against its own earlier context (prompt lookup — no model), or by
a small draft Llama running ahead greedily (``ModelDraft``).  Either way
verification runs ONE multi-token decode step (paged.paged_decode_multi)
scoring all draft positions at once; the longest prefix of drafts that
matches the target model's own greedy choice is accepted, plus one bonus
token from the first mismatching position.  Output is therefore
IDENTICAL to plain greedy decoding — speculation only changes how many
tokens each engine tick commits, and the draft's quality only moves the
acceptance rate, never correctness.

Why it fits this workload: decode ticks are latency-bound (a fixed-cost
sweep over the layer stack), so scoring K+1 positions instead of 1 is
nearly free, and the RCA stages emit highly repetitive structured output
(JSON field names, kinds, kubectl phrases that already appear in the
prompt), which is exactly where prompt-lookup acceptance is high; a
distilled draft (rca/distill.py produces one) lifts acceptance on the
free-text spans the n-gram lookup cannot predict.  The reference has no
decoding loop to accelerate at all (tokens stream from the OpenAI
server, reference common/openai_generic_assistant.py:92-115).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple


def ngram_draft(context: Sequence[int], n: int, k: int) -> List[int]:
    """Propose up to ``k`` draft tokens by prompt lookup.

    Finds the most recent earlier occurrence of the last ``n`` tokens of
    ``context`` and returns the tokens that followed it.  Empty when the
    n-gram has no earlier occurrence (caller falls back to plain decode).
    """
    if n <= 0 or k <= 0 or len(context) <= n:
        return []
    pattern = list(context[-n:])
    # scan right-to-left over earlier windows; the most recent prior
    # occurrence predicts the continuation best
    for start in range(len(context) - n - 1, -1, -1):
        if list(context[start:start + n]) == pattern:
            cont = list(context[start + n:start + n + k])
            if cont:
                return cont
            return []
    return []


def _draft_scan(cfg, params, cache, cur_tokens, lengths, n_steps: int,
                eos_id: int):
    """``n_steps`` greedy decode steps of the draft model for the whole
    batch in one dispatch, over its own ``llama.KVCache``.  Returns
    (cache, tokens [n_steps, B]); a slot that emits ``eos_id`` stops
    advancing (its token repeats)."""
    import jax
    import jax.numpy as jnp

    from k8s_llm_rca_tpu.models import llama

    def body(carry, _):
        cache, cur, lens, done = carry
        cache, logits = llama.decode_step(cfg, params, cache, cur, lens)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        advance = jnp.logical_not(done)
        cur = jnp.where(advance, nxt, cur)
        lens = lens + advance.astype(jnp.int32)
        return (cache, cur, lens, done | (nxt == eos_id)), cur

    done0 = jnp.zeros_like(cur_tokens, dtype=bool)
    (cache, _, _, _), toks = jax.lax.scan(
        body, (cache, cur_tokens, lengths, done0), None, length=n_steps)
    return cache, toks


class ModelDraft:
    """Draft-model speculation state: a small Llama with its own
    contiguous cache (``llama.KVCache``) mirrors the target engine's
    slots and proposes k greedy tokens per tick (one ``_draft_scan``
    over the whole batch).

    Correctness never depends on the draft — the target verifies every
    token — so the draft cache tolerates two approximations:

    - **lazy slot sync**: each tick, a slot whose (seq_id, context
      length) key diverged from the draft's bookkeeping (admission,
      preemption-resume, interleaved non-speculative ticks) re-prefills
      its draft cache row from the authoritative context; on the
      steady-state speculative path ``advance`` keeps the key current so
      the re-prefill never fires;
    - **garbage past the committed length**: rejected draft positions
      leave stale KV above ``lengths``, which the next tick's sequential
      writes overwrite and attention masks out by length.

    Contexts longer than the draft's cache keep only their TAIL (draft
    quality degrades gracefully; verification is unaffected).
    """

    def __init__(self, cfg, params, engine_cfg):
        import jax
        import numpy as np

        from k8s_llm_rca_tpu.models import llama

        self.cfg = cfg
        self.params = params
        b = engine_cfg.max_batch
        self.k = engine_cfg.speculative_k
        self.max_seq = min(cfg.max_seq_len, engine_cfg.max_seq_len)
        self.cache = llama.init_cache(cfg, b, self.max_seq)
        self.lengths = np.zeros((b,), np.int64)
        self.cur = np.zeros((b,), np.int64)
        self._owner: Dict[int, Tuple[int, int]] = {}   # slot -> (seq, ctxlen)
        self.prefills = 0          # sync re-prefill count (diagnostics/tests)
        self._buckets = tuple(
            s for s in sorted(set(engine_cfg.prefill_buckets))
            if s <= self.max_seq) or (self.max_seq,)
        self._prefill = jax.jit(llama.prefill, static_argnums=0)
        self._scan = jax.jit(_draft_scan, static_argnums=(0, 5, 6))
        # owning engines hook this to account the draft scan's blocking
        # token fetch in their engine.d2h_syncs counter (docs/performance.md)
        self.on_sync = None

    def _bucket(self, n: int) -> int:
        for s in self._buckets:
            if n <= s:
                return s
        return self.max_seq

    def sync(self, slot: int, seq_id: int, context: Sequence[int]) -> None:
        import jax.numpy as jnp
        import numpy as np

        if self._owner.get(slot) == (seq_id, len(context)):
            return
        # tail-clip leaving a real DRAFTING WINDOW (a quarter of the
        # cache, at least one full k+1 scan): clipping to the cache edge
        # would leave no headroom, so the slot would re-prefill its full
        # tail every 1-2 ticks while drafting almost nothing — a pure
        # dispatch tax, worst on dispatch-bound hosts.  The shorter tail
        # only affects draft QUALITY; one re-prefill then buys ~window/c
        # drafting ticks
        window = max(self.k + 2, self.max_seq // 4)
        ctx = list(context[-max(2, self.max_seq - window):])
        n = len(ctx) - 1                               # cur token stays out
        if n <= 0:
            self.lengths[slot] = 0
            self.cur[slot] = ctx[-1] if ctx else 0
            self._owner[slot] = (seq_id, len(context))
            return
        padded = np.zeros((1, self._bucket(n)), np.int32)
        padded[0, :n] = ctx[:-1]
        self.prefills += 1
        self.cache, _ = self._prefill(self.cfg, self.params, self.cache,
                                      jnp.asarray(padded), jnp.int32(n),
                                      jnp.int32(slot))
        self.lengths[slot] = n
        self.cur[slot] = ctx[-1]
        self._owner[slot] = (seq_id, len(context))

    def draft(self, active_slots, k: int, eos_id: int):
        """One greedy scan for the whole batch; returns {slot: draft
        tokens} (empty for slots without cache room).

        The scan runs k+1 steps, one MORE than the k drafts returned:
        step j writes the KV of its INPUT token, so k steps would leave
        the LAST draft's KV unwritten — and on full acceptance ``advance``
        would then mark that never-written position as valid, silently
        corrupting the draft context exactly in the high-acceptance case
        this feature targets.  The k+1-th step writes it (its emitted
        token is discarded)."""
        import jax.numpy as jnp
        import numpy as np

        roomy = {s for s in active_slots
                 if int(self.lengths[s]) + k + 1 < self.max_seq}
        if not roomy:
            # no scan ran, so not even cur's KV gets written this tick —
            # drop the keys or the bonus-token commit would mark an
            # unwritten position as valid (same hole as above)
            for s in active_slots:
                self._owner.pop(s, None)
            return {s: [] for s in active_slots}
        self.cache, toks = self._scan(
            self.cfg, self.params, self.cache,
            jnp.asarray(self.cur, jnp.int32),
            jnp.asarray(self.lengths, jnp.int32), k + 1, eos_id)
        from k8s_llm_rca_tpu.engine.engine import host_np
        if self.on_sync is not None:
            self.on_sync()
        toks_host = host_np(toks)                      # [k+1, B]
        out = {}
        for s in active_slots:
            if s in roomy:
                out[s] = [int(toks_host[j, s]) for j in range(k)]
            else:
                out[s] = []
                self._owner.pop(s, None)       # force re-sync when room frees
        return out

    def advance(self, slot: int, seq_id: int,
                committed: Sequence[int]) -> None:
        """Record a verified commit: the accepted prefix's KV is already
        in the draft cache (those positions were written with the same
        tokens during the draft scan); the bonus token becomes the next
        cur.  Anything inconsistent just drops the key and re-syncs."""
        owner = self._owner.get(slot)
        if owner is None or not committed:
            return
        seq, ctxlen = owner
        if seq != seq_id:
            self._owner.pop(slot, None)
            return
        new_len = int(self.lengths[slot]) + len(committed)
        if new_len >= self.max_seq:
            self._owner.pop(slot, None)                # tail re-prefill later
            return
        self.lengths[slot] = new_len
        self.cur[slot] = committed[-1]
        self._owner[slot] = (seq, ctxlen + len(committed))
