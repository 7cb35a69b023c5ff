"""Paged KV cache: allocator invariants, model-path equivalence, engine
churn + preemption.

The allocator invariant tests are the "race detection" coverage SURVEY §5
requires the build to add (the reference is single-threaded and has no
cache to corrupt).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reference_greedy
from k8s_llm_rca_tpu.config import TINY, EngineConfig
from k8s_llm_rca_tpu.engine.paged import (
    TRASH_PAGE, AllocatorError, OutOfPages, PageAllocator,
    PagedInferenceEngine, init_paged_cache, paged_decode_step, paged_prefill,
)
from k8s_llm_rca_tpu.engine.prefix import PrefixCache
from k8s_llm_rca_tpu.utils.logging import METRICS
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer


class TestPageAllocator:
    def test_alloc_free_roundtrip(self):
        a = PageAllocator(16)
        pages = a.alloc(5, owner=1)
        assert len(set(pages)) == 5 and TRASH_PAGE not in pages
        a.free(pages, owner=1)
        a.check()
        assert a.n_free == 15

    def test_double_free_detected(self):
        a = PageAllocator(8)
        pages = a.alloc(2, owner=1)
        a.free(pages, owner=1)
        with pytest.raises(AllocatorError, match="double free"):
            a.free(pages, owner=1)

    def test_cross_owner_free_detected(self):
        a = PageAllocator(8)
        pages = a.alloc(2, owner=1)
        with pytest.raises(AllocatorError, match="owned by"):
            a.free(pages, owner=2)
        a.check()

    def test_exhaustion_raises(self):
        a = PageAllocator(4)          # 3 usable
        a.alloc(3, owner=1)
        with pytest.raises(OutOfPages):
            a.alloc(1, owner=2)

    def test_trash_page_never_allocated(self):
        a = PageAllocator(4)
        assert TRASH_PAGE not in a.alloc(3, owner=1)
        with pytest.raises(AllocatorError, match="trash"):
            a.free([TRASH_PAGE], owner=1)


def _allocators():
    """The Python allocator and, where it builds, its C++ twin: one
    behaviour, the ids included."""
    from k8s_llm_rca_tpu import native
    yield pytest.param(PageAllocator, id="python")
    yield pytest.param(
        lambda n: native.NativePageAllocator(n), id="native",
        marks=pytest.mark.skipif(not native.available(),
                                 reason="native toolchain unavailable"))


class TestTheFreeStoreKeepsPagesTogether:
    """Address-ordered with two ends: several pages are the lowest free
    ids, ascending; one page is the highest."""

    @pytest.mark.parametrize("make", _allocators())
    def test_a_bulk_allocation_is_the_lowest_and_a_single_the_highest(
            self, make):
        a = make(32)
        assert a.alloc(6, owner=1) == [1, 2, 3, 4, 5, 6]
        assert a.alloc(1, owner=2) == [31]
        assert a.alloc(1, owner=2) == [30]
        assert a.alloc(3, owner=3) == [7, 8, 9]
        a.free([2, 3, 5], owner=1)              # holes at the low end
        a.free([31], owner=2)                   # and the top
        assert a.alloc(4, owner=4) == [2, 3, 5, 10]
        assert a.alloc(1, owner=4) == [31]
        assert a.alloc(1, owner=4) == [29]
        assert a.alloc(0, owner=5) == []
        a.check()
        assert a.n_free == 31 - 3 - 3 - 4 - 3

    @pytest.mark.parametrize("make", _allocators())
    def test_the_two_ends_meet_and_the_pool_empties(self, make):
        a = make(6)
        assert a.alloc(2, owner=1) == [1, 2]
        assert a.alloc(1, owner=1) == [5]
        assert a.alloc(2, owner=1) == [3, 4]
        with pytest.raises(OutOfPages):
            a.alloc(1, owner=1)
        a.free([3], owner=1)
        assert a.alloc(1, owner=2) == [3]
        a.check()

    @pytest.mark.parametrize("make", _allocators())
    def test_copies_per_page_stay_flat_over_a_long_life(self, make):
        """The sixth cell's page arithmetic on the host alone (64 callers
        in a closed loop, prompts of 4-12k tokens in buckets of 6144 /
        8192 / 12288 whose pages come at admission, 1-2k tokens out a page
        at a time, 16 a tick, 40,896 pages of 16; a sequence's pages freed
        in table order): the copies the latent walk would start per page
        it visits stay where they began over 600 settled requests.  A free
        store that hands freed pages back in another order lets the share
        decay (a stack, in groups of four: 0.35 of the best's 0.25 after 64
        settled, 0.85 after 128)."""
        from k8s_llm_rca_tpu.ops.mla_attention import (
            mla_block_pages, mla_page_copies,
        )

        page, buckets, pps, slots = 16, (6144, 8192, 12288), 1024, 64
        block = mla_block_pages(page, pps)
        rng = np.random.default_rng(0)

        def lengths(median, sigma, lo, hi):
            return np.clip(np.exp(rng.normal(np.log(median), sigma, 800)),
                           lo, hi).astype(int)

        prompts = lengths(7168, 0.3, 4096, 12288)
        goals = prompts + lengths(1536, 0.25, 1024, 2048)
        a = make(40896)
        tables = np.zeros((slots, pps), np.int32)
        held, length = [0] * slots, [0] * slots     # pages, tokens
        owner = [None] * slots
        sent = settled = 0
        share = {}
        while settled < 600:
            for s in range(slots):
                if owner[s] is not None and length[s] >= goals[owner[s]]:
                    a.free([int(p) for p in tables[s, :held[s]]], owner[s])
                    tables[s], held[s], length[s], owner[s] = 0, 0, 0, None
                    settled += 1
                    if settled in (64, 128, 320, 600):
                        blocks = -(-(-(-np.asarray(length) // page))
                                   // block)
                        share[settled] = (
                            mla_page_copies(tables, blocks, block)
                            / (blocks.sum() * block))
            for s in range(slots):
                if owner[s] is None:
                    n = next(b for b in buckets if prompts[sent] <= b) // page
                    tables[s, :n] = a.alloc(n, owner=sent)
                    held[s], length[s], owner[s] = n, prompts[sent], sent
                    sent += 1
                while held[s] * page < length[s] + 16:
                    (tables[s, held[s]],) = a.alloc(1, owner=owner[s])
                    held[s] += 1
                length[s] += 16
        a.check()
        assert all(0.125 <= v < 0.25 for v in share.values()), share
        assert share[600] < share[64] + 0.03, share


class TestPagedModelPath:
    """paged prefill+decode must produce the same greedy tokens as the
    contiguous cache path."""

    def _greedy_contiguous(self, cfg, params, prompt, n_steps):
        cache = llama.init_cache(cfg, 1, cfg.max_seq_len)
        toks = jnp.asarray([prompt], jnp.int32)
        cache, logits = llama.prefill(cfg, params, cache, toks,
                                      jnp.int32(len(prompt)), jnp.int32(0))
        out = [int(jnp.argmax(logits[0]))]
        lengths = jnp.asarray([len(prompt)], jnp.int32)
        cur = jnp.asarray(out, jnp.int32)
        for _ in range(n_steps - 1):
            cache, logits = llama.decode_step(cfg, params, cache, cur, lengths)
            lengths = lengths + 1
            cur = jnp.asarray([int(jnp.argmax(logits[0]))], jnp.int32)
            out.append(int(cur[0]))
        return out

    def test_greedy_equivalence(self):
        cfg = TINY.replace(max_seq_len=64)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        page = 8
        prompt = list(range(5, 18))      # 13 tokens -> 2 pages
        ref = self._greedy_contiguous(cfg, params, prompt, 6)

        pool = init_paged_cache(cfg, 32, page)
        # non-contiguous scattered pages on purpose
        page_map = jnp.asarray([7, 3], jnp.int32)
        padded = jnp.zeros((1, 16), jnp.int32).at[0, :13].set(
            jnp.asarray(prompt))
        pool, logits = paged_prefill(
            cfg, params, pool, padded, jnp.int32(13), page_map)
        got = [int(jnp.argmax(logits[0]))]

        tables = np.full((1, 8), TRASH_PAGE, np.int32)
        tables[0, :2] = [7, 3]
        extra = [11, 5, 9, 2, 30, 29]     # pages for growth
        lengths = 13
        cur = got[0]
        for _ in range(5):
            if lengths % page == 0:
                tables[0, lengths // page] = extra.pop(0)
            pool, logits = paged_decode_step(
                cfg, params, pool,
                jnp.asarray([cur], jnp.int32),
                jnp.asarray([lengths], jnp.int32),
                jnp.asarray(tables), use_kernel=False)
            lengths += 1
            cur = int(jnp.argmax(logits[0]))
            got.append(cur)
        assert got == ref


def _parent_decode_step(cfg, params, pool, tokens, lengths, block_tables):
    """``paged_decode_step`` (gather path) as it was before the write went
    in place, the reference for it: a layer of every pool sliced out, the
    token's rows scattered into the slice, the slice set back."""
    from k8s_llm_rca_tpu.engine.paged import (
        PagePool, _gather_dequant_pages, _pool_packed,
    )
    from k8s_llm_rca_tpu.models.quant import gather_rows
    from k8s_llm_rca_tpu.ops.attention import decode_attention
    from k8s_llm_rca_tpu.ops.paged_attention import paged_attention_xla
    from k8s_llm_rca_tpu.ops.rope import rope_frequencies

    b, page = tokens.shape[0], pool.page_size
    dtype, packed = jnp.dtype(cfg.dtype), _pool_packed(cfg, pool)
    angles = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    x = gather_rows(params["embedding"], tokens[:, None]).astype(dtype)
    pids = jnp.take_along_axis(block_tables, (lengths // page)[:, None],
                               axis=1)[:, 0]
    offs = lengths % page
    fields = [f for f in pool if f is not None]
    for li, layer in enumerate(params["layers"]):
        q, k, v = llama._decode_qkv(cfg, layer, x, angles, lengths[:, None])
        rows = [k[:, 0].reshape(b, -1), v[:, 0].reshape(b, -1)]
        if pool.quantized:
            (rows[0], ks), (rows[1], vs) = (
                llama._quantize_kv(r, packed) for r in rows)
            rows += [ks, vs]
        one = [f[li].at[pids, offs].set(r) for f, r in zip(fields, rows)]
        fields = [f.at[li].set(o) for f, o in zip(fields, one)]
        if pool.quantized:
            k_all, v_all = (_gather_dequant_pages(
                one[i], one[i + 2], block_tables, cfg.n_kv_heads,
                cfg.head_dim, dtype, packed) for i in (0, 1))
            attn = decode_attention(q, k_all, v_all, lengths + 1)
        else:
            attn = paged_attention_xla(q[:, 0], *one, lengths + 1,
                                       block_tables)
        x = llama._decode_finish(cfg, layer, x,
                                 attn.reshape(b, 1, cfg.q_dim), None)
    return PagePool(*fields), llama._logits(cfg, params, x)[:, 0]


class TestDecodeWritesInPlace:
    """The decode step scatters its rows into the stacked pool with the
    layer as an index.  Against the sliced-layer formulation: the same
    logits and the same pool to the bit, and no byte of the pool changed
    outside the rows (layer, page_ids[b], offsets[b])."""

    PAGE, STEPS = 8, 4

    def _state(self, cfg, kv_dtype):
        """A pool of arbitrary bytes under four slots: one mid-page, one
        that holds no sequence (table on the trash page, a stale length),
        one whose next token opens a page, one idle at length 0."""
        pool = init_paged_cache(cfg, 32, self.PAGE, kv_dtype=kv_dtype)
        keys = iter(jax.random.split(jax.random.PRNGKey(28), 4))

        def arbitrary(f):
            if f.dtype == jnp.int8:
                return jax.random.randint(next(keys), f.shape, -127, 128,
                                          jnp.int32).astype(jnp.int8)
            return jax.random.uniform(next(keys), f.shape, jnp.float32,
                                      0.01, 0.05).astype(f.dtype)

        pool = type(pool)(*(None if f is None else arbitrary(f)
                            for f in pool))
        tables = np.full((4, 8), TRASH_PAGE, np.int32)
        tables[0, :3] = [7, 3, 11]
        tables[2, :4] = [5, 30, 9, 2]
        lengths = np.asarray([13, 57, 16, 0], np.int32)
        tokens = jnp.asarray([21, 4, 9, 0], jnp.int32)
        return pool, tokens, lengths, tables

    def _assert_only_rows_changed(self, before, after, lengths, tables,
                                  steps):
        written = np.zeros(before.k.shape[:3], bool)
        for s in range(steps):
            pos = lengths + s
            written[:, tables[np.arange(4), pos // self.PAGE],
                    pos % self.PAGE] = True
        assert written.sum() < written.size // 4
        for b, a in zip(before, after):
            if b is None:
                continue
            b, a = np.asarray(b), np.asarray(a)
            keep = ~written.reshape(written.shape + (1,) * (b.ndim - 3))
            assert np.array_equal(np.where(keep, a, 0), np.where(keep, b, 0))
            assert not np.array_equal(a, b)

    @pytest.mark.parametrize("program", ["step", "scan4"])
    @pytest.mark.parametrize("kv_dtype", [None, jnp.int8, "int4"],
                             ids=["bf16", "int8", "int4"])
    def test_bit_equal_to_the_sliced_layer_formulation(self, kv_dtype,
                                                       program):
        from k8s_llm_rca_tpu.engine.paged import paged_decode_scan
        from k8s_llm_rca_tpu.engine.sampling import SamplingParams

        cfg = TINY.replace(max_seq_len=64)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        pool, tokens, lengths, tables = self._state(cfg, kv_dtype)
        args = (tokens, jnp.asarray(lengths), jnp.asarray(tables))
        if program == "step":
            steps = 1
            got = jax.jit(paged_decode_step, static_argnums=0,
                          static_argnames="use_kernel")(
                cfg, params, pool, *args, use_kernel=False)
            ref = jax.jit(_parent_decode_step, static_argnums=0)(
                cfg, params, pool, *args)
        else:
            steps = self.STEPS
            scan = jax.jit(paged_decode_scan, static_argnums=(0, 7, 8, 9),
                           static_argnames=("use_kernel", "decode_fn"))
            # eos -1: every slot advances at every step
            tail = (jax.random.PRNGKey(1), steps, SamplingParams(), -1)
            got = scan(cfg, params, pool, *args, *tail, use_kernel=False)
            ref = scan(cfg, params, pool, *args, *tail,
                       decode_fn=_parent_decode_step)
            assert np.array_equal(np.asarray(got[2]), lengths + steps)
        for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            assert g.dtype == r.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
        self._assert_only_rows_changed(pool, got[0], lengths, tables, steps)


class TestPagedEngine:
    def _engine(self, **kw):
        cfg = TINY.replace(max_seq_len=64)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        # prefix_cache off: these tests pin exact page counts and engineer
        # pool-exhaustion scenarios; sharing would shift the arithmetic.
        # TestPrefixCaching covers the cache-on behavior.
        defaults = dict(max_batch=4, max_seq_len=64, page_size=8,
                        num_pages=64, prefill_buckets=(16, 32, 64),
                        max_new_tokens=8, temperature=0.0,
                        prefix_cache=False)
        defaults.update(kw)
        ecfg = EngineConfig(**defaults)
        tok = get_tokenizer()
        return (PagedInferenceEngine(cfg, ecfg, params, tok,
                                     use_kernel=False),
                params, tok, cfg)

    @pytest.mark.parametrize("page_size,decode_chunk", [
        (8, 16), (16, 16), (8, 1)])
    def test_matches_model_reference(self, page_size, decode_chunk):
        """Scans that cross page boundaries, scans inside one page and
        the stepwise tick all give the model's own greedy tokens."""
        paged, params, tok, cfg = self._engine(
            page_size=page_size, num_pages=512 // page_size,
            decode_chunk=decode_chunk)
        prompts = [tok.encode(t, add_bos=True) for t in
                   ["pod crashloop", "pvc pending why", "node notready"]]
        for res, prompt in zip(paged.generate(prompts, max_new_tokens=6),
                               prompts):
            assert res.token_ids == reference_greedy(cfg, params, prompt, 6)
            assert res.finish_reason == "length"
        paged.allocator.check()
        # everything returned
        assert paged.allocator.n_free == 512 // page_size - 1

    def test_churn_many_sequences(self):
        paged, _, tok, _ = self._engine(num_pages=32)
        prompts = [tok.encode(f"incident number {i} pod failing", add_bos=True)
                   for i in range(10)]
        results = paged.generate(prompts, max_new_tokens=5)
        assert len(results) == 10
        assert sorted(r.seq_id for r in results) == list(range(10))
        paged.allocator.check()
        assert paged.allocator.n_free == 31

    def test_lockstep_page_boundary_preemption(self):
        # regression: two sequences admitted with identical prompt lengths
        # hit a page boundary on the SAME tick with zero free pages; the
        # growth loop must skip the slot that _preempt_youngest() evicted
        # mid-loop instead of KeyError-ing on the stale snapshot.
        paged, _, tok, _ = self._engine(
            num_pages=5, max_batch=2, page_size=8, max_seq_len=32,
            prefill_buckets=(16,), max_new_tokens=10)
        prompt = tok.encode("0123456789abcde")   # 15 chars + BOS = 16 tokens
        prompt = [tok.bos_id] + prompt
        assert len(prompt) == 16
        results = paged.generate([prompt, list(prompt)], max_new_tokens=10)
        assert len(results) == 2
        paged.allocator.check()
        assert paged.allocator.n_free == 4

    def test_preemption_under_pressure(self):
        # pool barely holds one max sequence: concurrent seqs force preempts
        paged, _, tok, _ = self._engine(num_pages=12, max_batch=3,
                                        max_new_tokens=16)
        prompts = [tok.encode("a b c d e f g h i j k l m n o p", add_bos=True)
                   for _ in range(3)]
        results = paged.generate(prompts, max_new_tokens=16)
        assert len(results) == 3
        for r in results:
            assert r.completion_tokens >= 16 or r.finish_reason in (
                "eos", "stop", "length")
        paged.allocator.check()
        assert paged.allocator.n_free == 11


class TestPreemptionPolicy:
    def _engine(self, **kw):
        return TestPagedEngine()._engine(**kw)

    def test_admission_waits_instead_of_evicting(self):
        """A queued request that doesn't fit must NOT evict running work
        (regression: admission used to preempt the youngest active sequence,
        which was requeued at the queue front and instantly readmitted —
        one full re-prefill per generated token while the head-of-queue
        request starved)."""
        from k8s_llm_rca_tpu.utils.logging import METRICS

        # 5 usable pages, 2-page sequences at bucket 16 -> two admit
        # (4 pages), the third's admission raises OutOfPages and must wait.
        # 12-token prompts + 4 new tokens end exactly at the 16-slot bucket
        # edge, so growth never allocates and the only possible preemption
        # source is admission — the counter stays flat iff admission waits.
        paged, _, tok, _ = self._engine(num_pages=6, max_batch=3,
                                        page_size=8, max_seq_len=32,
                                        prefill_buckets=(16,),
                                        max_new_tokens=4)
        before = METRICS.count("engine.preemptions")
        prompts = [tok.encode("0123456789a", add_bos=True)   # 12 tokens
                   for _ in range(5)]
        assert all(len(p) == 12 for p in prompts)
        results = paged.generate(prompts, max_new_tokens=4)
        assert len(results) == 5
        assert METRICS.count("engine.preemptions") == before
        paged.allocator.check()
        assert paged.allocator.n_free == 5

    def test_stop_string_spans_resume_boundary(self):
        """Stop strings split by a preemption must still terminate the
        sequence: the match window sees pre-preemption tokens too.

        decode_chunk=1 pins step() to one generated token: the test pokes
        engine internals between steps, and on hardware the default chunked
        scan tick would decode the whole 8-token budget inside the first
        step() and retire the sequence before we can simulate a preemption.
        """
        paged, _, tok, _ = self._engine(decode_chunk=1)
        seq = paged.submit(tok.encode("x", add_bos=True),
                           max_new_tokens=8, stop_strings=("```",))
        paged.step()                      # admit; one token generated
        (slot, st), = paged._active.items()
        # simulate: two backticks generated, then the engine preempts
        st.generated = tok.encode("ab``")
        paged.lengths[slot] = st.prompt_tokens + len(st.generated)
        paged._preempt_slot(slot)
        assert paged._resumed[seq] == tok.encode("ab``")
        # resume; if the model doesn't emit the completing backtick itself,
        # feed one through _finish_reason by hand
        finished = paged.step()           # re-admit (re-prefill)
        if finished:
            (res,) = finished
        else:
            (slot, st), = paged._active.items()
            st.generated = tok.encode("`")
            reason = paged._finish_reason(st, tok.encode("`")[0],
                                          int(paged.lengths[slot]))
            assert reason == "stop"
            res = paged._retire(slot, reason)
        assert res.finish_reason == "stop"
        assert res.text == "ab"           # trimmed at the spanning stop string
        paged.allocator.check()


class TestPrefixCaching:
    """Prefix-cache behavior (engine/prefix.py): KV reuse across sequences
    sharing a prompt prefix, refcounts, eviction under pressure."""

    def _engine(self, **kw):
        cfg = TINY.replace(max_seq_len=64)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        defaults = dict(max_batch=4, max_seq_len=64, page_size=8,
                        num_pages=64, prefill_buckets=(16, 32, 64),
                        max_new_tokens=8, temperature=0.0,
                        prefix_cache=True)
        defaults.update(kw)
        ecfg = EngineConfig(**defaults)
        tok = get_tokenizer()
        return PagedInferenceEngine(cfg, ecfg, params, tok,
                                    use_kernel=False), tok, cfg, params

    def test_unit_match_insert_release_evict(self):
        a = PageAllocator(16)
        pc = PrefixCache(a, page_size=4)
        prompt = list(range(1, 12))                    # 11 tokens -> 2 full pages
        pages = a.alloc(2, owner=7)
        assert pc.match(prompt) == ([], 0)
        n_shared = pc.insert(prompt, pages, owner=7, n_matched_pages=0)
        assert n_shared == 2 and pc.n_resident == 2 and pc.n_evictable == 0
        # a second prompt sharing the first 8 tokens: both full pages hit
        other = prompt[:8] + [99, 98, 97]
        got, n = pc.match(other)
        assert n == 8 and got == pages
        # a third sharing only the first page's tokens
        third = prompt[:4] + [77, 76, 75, 74, 73]
        got3, n3 = pc.match(third)
        assert n3 == 4 and got3 == [pages[0]]
        pc.release(got3)
        pc.release(got)
        pc.release(pages)
        assert pc.n_evictable == 2
        assert pc.evict(10) == 2
        a.check()
        assert a.n_free == 15                 # everything back in the pool

    def test_admission_group_breaks_at_member_prefix_hit(self):
        """A batched-admission group must END before a member whose prompt
        already has cached prefix pages: batch-prefilling it would redo the
        cached work and allocate fresh pages for it (ADVICE r1).  The member
        must instead admit singly through the chunked path with a hit."""
        from k8s_llm_rca_tpu.utils.logging import METRICS

        eng, tok, _, _ = self._engine(max_batch=8)
        shared = tok.encode("kubelet failed to mount the configmap volume "
                            "for pod api-0", add_bos=True)
        assert len(shared) > 16
        # seed the cache, then drain
        eng.generate([list(shared)], max_new_tokens=4)
        base_hits = METRICS.counters.get("engine.prefix_hit_tokens", 0)
        # burst: a cold head + a prefix-hitting member + another cold one
        cold1 = tok.encode("node pressure eviction started on worker-3 xx",
                           add_bos=True)
        cold2 = tok.encode("pvc stuck pending storageclass missing here yy",
                           add_bos=True)
        ids = [eng.submit(list(cold1), max_new_tokens=4),
               eng.submit(list(shared), max_new_tokens=4),
               eng.submit(list(cold2), max_new_tokens=4)]
        results = {r.seq_id: r for r in eng.run_to_completion()}
        assert all(results[i].completion_tokens == 4 for i in ids)
        # the shared-prefix member went through the single-admit chunked
        # path and recorded its hit
        assert METRICS.counters.get("engine.prefix_hit_tokens", 0) \
            > base_hits
        eng.allocator.check()

    def test_second_submit_skips_cached_prefill(self):
        from k8s_llm_rca_tpu.utils.logging import METRICS

        eng, tok, _, _ = self._engine()
        prompt = tok.encode("kubelet failed to pull image from registry "
                            "backoff error", add_bos=True)
        assert len(prompt) > 16                        # > 2 pages of 8
        base_hits = METRICS.counters.get("engine.prefix_hit_tokens", 0)
        r1 = eng.generate([prompt], max_new_tokens=4)[0]
        assert METRICS.counters.get("engine.prefix_hit_tokens", 0) == base_hits
        r2 = eng.generate([list(prompt)], max_new_tokens=4)[0]
        hit = METRICS.counters.get("engine.prefix_hit_tokens", 0) - base_hits
        assert hit == (len(prompt) - 1) // 8 * 8       # full pages re-used
        assert r2.token_ids == r1.token_ids            # greedy: identical
        eng.allocator.check()
        # cached pages stay resident, everything else returned
        assert eng.allocator.n_free + eng.prefix_cache.n_resident == 63
        assert eng.prefix_cache.n_evictable == eng.prefix_cache.n_resident

    def test_shared_prefix_matches_uncached_output(self):
        eng, tok, cfg, params = self._engine()
        off, _, _, _ = self._engine(prefix_cache=False)
        common = tok.encode("incident: pod crashloop in namespace redis ",
                            add_bos=True)
        suffixes = ["why is it failing", "give the root cause",
                    "what should we check"]
        prompts = [common + tok.encode(s) for s in suffixes]
        # warm the cache with the common prefix, then submit the variants
        eng.generate([prompts[0]], max_new_tokens=4)
        got = eng.generate(prompts, max_new_tokens=6)
        ref = off.generate(prompts, max_new_tokens=6)
        for g, r in zip(got, ref):
            assert g.token_ids == r.token_ids, (g.token_ids, r.token_ids)
        eng.allocator.check()

    def test_eviction_under_pressure(self):
        # small pool: cached pages must be evicted (not deadlock) when new
        # sequences need the space
        eng, tok, _, _ = self._engine(num_pages=9, max_batch=2,
                                      prefill_buckets=(16,))
        for i in range(6):
            prompt = tok.encode(f"unique incident number {i} pod oom",
                                add_bos=True)
            res = eng.generate([prompt], max_new_tokens=4)
            assert len(res) == 1
        eng.allocator.check()
        assert eng.allocator.n_free + eng.prefix_cache.n_resident == 8

    def test_refcount_protects_in_use_pages(self):
        a = PageAllocator(8)
        pc = PrefixCache(a, page_size=4)
        prompt = list(range(1, 10))
        pages = a.alloc(2, owner=1)
        pc.insert(prompt, pages, owner=1, n_matched_pages=0)
        # still referenced by owner 1: nothing evictable
        assert pc.evict(10) == 0
        got, n = pc.match(prompt)                      # second user
        assert got == pages[:2] and n == 8
        pc.release(pages)                              # owner 1 done
        assert pc.evict(10) == 0                       # owner 2 still holds
        pc.release(got)
        assert pc.evict(10) == 2
        a.check()

    def test_preemption_resume_with_shared_pages(self):
        # pool under pressure with identical prompts: preempted sequences
        # resume via the cache without corrupting refcounts
        eng, tok, _, _ = self._engine(num_pages=12, max_batch=3,
                                      max_new_tokens=16)
        prompts = [tok.encode("a b c d e f g h i j k l m n o p",
                              add_bos=True) for _ in range(3)]
        results = eng.generate(prompts, max_new_tokens=16)
        assert len(results) == 3
        eng.allocator.check()


class TestRandomizedChurn:
    def test_prefix_cache_random_schedule_matches_cache_off(self):
        """Fuzz: 24 prompts with overlapping prefixes through a small pool
        (forced evictions + preemptions), cache-on vs cache-off — outputs
        must be identical and the allocator must end clean."""
        cfg = TINY.replace(max_seq_len=64)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tok = get_tokenizer()
        rng = np.random.default_rng(7)
        commons = [tok.encode(f"incident type {i} in namespace prod ",
                              add_bos=True) for i in range(3)]
        prompts = []
        for _ in range(24):
            base = commons[int(rng.integers(0, 3))]
            suffix = tok.encode("pod " + "x" * int(rng.integers(1, 12)))
            prompts.append(base + suffix)

        def run(prefix_cache):
            ecfg = EngineConfig(max_batch=3, max_seq_len=64, page_size=8,
                                num_pages=24, prefill_buckets=(16, 32, 64),
                                max_new_tokens=6, temperature=0.0,
                                prefix_cache=prefix_cache)
            eng = PagedInferenceEngine(cfg, ecfg, params, tok,
                                       use_kernel=False)
            out = eng.generate([list(p) for p in prompts], max_new_tokens=6)
            eng.allocator.check()
            if eng.prefix_cache is not None:
                assert (eng.allocator.n_free + eng.prefix_cache.n_resident
                        == 23)
                assert (eng.prefix_cache.n_evictable
                        == eng.prefix_cache.n_resident)
            else:
                assert eng.allocator.n_free == 23
            return [(r.token_ids, r.finish_reason) for r in out]

        assert run(True) == run(False)


class TestPagedScanTick:
    def test_chunk_on_off_identical_across_boundaries(self):
        """Paged scan ticks must produce identical greedy output to the
        stepwise path, including around page boundaries and eos."""
        cfg = TINY.replace(max_seq_len=64)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tok = get_tokenizer()
        prompts = [tok.encode("pod crashloop backoff", add_bos=True),
                   tok.encode("pvc stuck pending", add_bos=True)]

        def run(chunk):
            ecfg = EngineConfig(max_batch=2, max_seq_len=64, page_size=8,
                                num_pages=64, prefill_buckets=(16, 32, 64),
                                max_new_tokens=20, temperature=0.0,
                                decode_chunk=chunk, prefix_cache=False)
            eng = PagedInferenceEngine(cfg, ecfg, params, tok,
                                       use_kernel=False)
            out = eng.generate([list(p) for p in prompts],
                               max_new_tokens=20)
            eng.allocator.check()
            assert eng.allocator.n_free == 63
            return [(r.token_ids, r.finish_reason) for r in out]

        assert run(1) == run(16)


class TestQuantizedPool:
    """int8/int4 paged KV: pool shapes, numerics vs the bf16 pool, and the
    full engine loop (prefill, chunked prefix prefill, decode, speculative,
    scan ticks) over a quantized pool."""

    def _pools(self, cfg):
        return {
            "int8": init_paged_cache(cfg, 32, 8, kv_dtype=jnp.int8),
            "int4": init_paged_cache(cfg, 32, 8, kv_dtype="int4"),
        }

    def test_pool_shapes(self):
        cfg = TINY
        p8 = init_paged_cache(cfg, 32, 8, kv_dtype=jnp.int8)
        assert p8.quantized and p8.k.dtype == jnp.int8
        assert p8.k.shape == (cfg.n_layers, 32, 8, cfg.kv_dim)
        assert p8.k_scale.shape == (cfg.n_layers, 32, 8)
        p4 = init_paged_cache(cfg, 32, 8, kv_dtype="int4")
        assert p4.k.shape == (cfg.n_layers, 32, 8, cfg.kv_dim // 2)
        assert not init_paged_cache(cfg, 32, 8).quantized

    def test_quantized_decode_correlates_with_bf16(self):
        cfg = TINY.replace(max_seq_len=64)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        prompt = list(range(5, 18))
        page_map = jnp.asarray([7, 3], jnp.int32)
        padded = jnp.zeros((1, 16), jnp.int32).at[0, :13].set(
            jnp.asarray(prompt))
        tables = np.full((1, 8), TRASH_PAGE, np.int32)
        tables[0, :3] = [7, 3, 11]

        def run(pool):
            pool, logits = paged_prefill(cfg, params, pool, padded,
                                         jnp.int32(13), page_map)
            out = [np.asarray(logits[0])]
            lengths, cur = 13, int(np.argmax(out[-1]))
            for _ in range(5):
                pool, logits = paged_decode_step(
                    cfg, params, pool, jnp.asarray([cur], jnp.int32),
                    jnp.asarray([lengths], jnp.int32),
                    jnp.asarray(tables), use_kernel=False)
                lengths += 1
                cur = int(np.argmax(np.asarray(logits[0])))
                out.append(np.asarray(logits[0]))
            return np.stack(out)

        ref = run(init_paged_cache(cfg, 32, 8))
        for name, pool in self._pools(cfg).items():
            got = run(pool)
            assert np.isfinite(got).all()
            corr = np.corrcoef(ref.ravel(), got.ravel())[0, 1]
            floor = 0.99 if name == "int8" else 0.95
            assert corr > floor, (name, corr)

    def _engine(self, kv_dtype, **kw):
        cfg = TINY.replace(max_seq_len=64)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        defaults = dict(max_batch=4, max_seq_len=64, page_size=8,
                        num_pages=64, prefill_buckets=(16, 32, 64),
                        max_new_tokens=8, temperature=0.0,
                        kv_cache_dtype=kv_dtype)
        defaults.update(kw)
        tok = get_tokenizer()
        return PagedInferenceEngine(cfg, EngineConfig(**defaults), params,
                                    tok, use_kernel=False), tok

    def test_engine_generates_and_returns_pages(self):
        for kv_dtype in ("int8", "int4"):
            eng, tok = self._engine(kv_dtype, prefix_cache=False)
            res = eng.generate(
                [tok.encode("pod oom killed", add_bos=True),
                 tok.encode("pvc pending", add_bos=True)],
                max_new_tokens=12)
            assert all(r.completion_tokens == 12 for r in res), kv_dtype
            assert eng.pool.quantized
            eng.allocator.check()
            assert eng.allocator.n_free == 63

    def test_engine_prefix_cache_chunked_prefill(self):
        # second submit of a shared-prefix prompt drives the quantized
        # chunked-prefill path (gather+dequant of cached prefix pages).
        # No exact-token assertion: the re-submit attends over the
        # quantization-roundtripped prefix, so a greedy near-tie may
        # legitimately flip — the mechanics (full completion, no page
        # leaks, a recorded prefix hit) are the contract here.
        for kv_dtype in ("int8", "int4"):
            eng, tok = self._engine(kv_dtype, prefix_cache=True)
            prompt = tok.encode("kubelet failed to mount volume for pod "
                                "web-0 secret missing", add_bos=True)
            r1 = eng.generate([list(prompt)], max_new_tokens=6)[0]
            hits_before = METRICS.counters.get("engine.prefix_hit_tokens", 0)
            r2 = eng.generate([list(prompt)], max_new_tokens=6)[0]
            assert r1.completion_tokens == 6, kv_dtype
            assert r2.completion_tokens == 6, kv_dtype
            # strictly increased across THIS resubmit (the counter is
            # process-global; an absolute >0 check could pass on earlier
            # tests' hits)
            assert METRICS.counters.get("engine.prefix_hit_tokens", 0) \
                > hits_before, kv_dtype
            eng.allocator.check()

    def test_engine_scan_and_speculative_ticks(self):
        for kw in (dict(decode_chunk=8), dict(speculative_k=3)):
            for kv_dtype in ("int8", "int4"):
                eng, tok = self._engine(kv_dtype, prefix_cache=False, **kw)
                r = eng.generate(
                    [tok.encode("aaaa bbbb aaaa bbbb", add_bos=True)],
                    max_new_tokens=12)[0]
                assert r.completion_tokens == 12, (kw, kv_dtype)
                eng.allocator.check()


class TestPagedBatchedAdmission:
    def _mk(self, **kw):
        cfg = TINY.replace(max_seq_len=64)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        defaults = dict(max_batch=8, max_seq_len=64, page_size=8,
                        num_pages=64, prefill_buckets=(16, 32, 64),
                        max_new_tokens=6, temperature=0.0,
                        prefix_cache=False)
        defaults.update(kw)
        tok = get_tokenizer()
        return (PagedInferenceEngine(cfg, EngineConfig(**defaults), params,
                                     tok, use_kernel=False), tok)

    def test_batched_admission_matches_serial(self):
        # same-bucket prompts admit in one dispatch and must emit exactly
        # the tokens the serial (max_batch=1 -> singleton groups) run does
        texts = ["pod crashloop", "node notready", "pvc pending why",
                 "dns resolution fails"]
        eng, tok = self._mk()
        prompts = [tok.encode(t, add_bos=True) for t in texts]
        before = METRICS.counters.get("engine.batched_admissions", 0)
        batched = eng.generate([list(p) for p in prompts], max_new_tokens=6)
        # at least the same-bucket run batches (the odd-bucket prompt may
        # admit singly)
        assert METRICS.counters.get("engine.batched_admissions", 0) \
            >= before + 3
        eng.allocator.check()
        assert eng.allocator.n_free == 63

        serial, tok2 = self._mk(max_batch=1)
        for p, rb in zip(prompts, batched):
            rs = serial.generate([list(p)], max_new_tokens=6)[0]
            assert rs.token_ids == rb.token_ids

    def test_batched_admission_under_page_pressure(self):
        # regression: a group sized past the free list must not wedge the
        # engine (all-or-nothing batch alloc raising OutOfPages forever);
        # the admission group is bounded by free pages so the head admits
        eng, tok = self._mk(num_pages=9, max_batch=4, max_new_tokens=8)
        prompts = [tok.encode("incident %d pod oom" % i, add_bos=True)
                   for i in range(4)]
        res = eng.generate([list(p) for p in prompts], max_new_tokens=8)
        assert len(res) == 4
        eng.allocator.check()
        assert eng.allocator.n_free == 8

    def test_batched_admission_quantized_pool(self):
        for kv_dtype in ("int8", "int4"):
            eng, tok = self._mk(kv_cache_dtype=kv_dtype)
            prompts = [tok.encode(t, add_bos=True)
                       for t in ["pod oom", "pvc lost", "node gone"]]
            res = eng.generate([list(p) for p in prompts], max_new_tokens=6)
            assert all(r.completion_tokens == 6 for r in res), kv_dtype
            eng.allocator.check()

    def test_prefix_hit_still_takes_chunk_path(self):
        # head with a cached prefix must admit singly (chunked prefill),
        # not lose its hit to a batch
        eng, tok = self._mk(prefix_cache=True)
        prompt = tok.encode("kubelet failed to mount volume for pod web-0",
                           add_bos=True)
        eng.generate([list(prompt)], max_new_tokens=4)
        before = METRICS.counters.get("engine.prefix_hit_tokens", 0)
        eng.generate([list(prompt)], max_new_tokens=4)
        assert METRICS.counters.get("engine.prefix_hit_tokens", 0) > before
        eng.allocator.check()


class TestBatchedPrefixHitAdmission:
    """Equal-prefix HIT waves admit through ONE batched chunked prefill
    (paged_prefill_chunk_batch) instead of single-file — measured 5x
    faster for same-prefix waves on the dispatch-bound bench host —
    with exact greedy parity and intact pool accounting."""

    def _mk(self, prefix_cache, kv_dtype=None, max_batch=8):
        from k8s_llm_rca_tpu.config import TINY, EngineConfig
        from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
        from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

        cfg = TINY.replace(max_seq_len=128)
        ecfg = EngineConfig(max_batch=max_batch, max_seq_len=128,
                            page_size=8, num_pages=160,
                            prefill_buckets=(32, 64), max_new_tokens=6,
                            temperature=0.0, decode_chunk=1,
                            prefix_cache=prefix_cache,
                            kv_cache_dtype=kv_dtype)
        tok = get_tokenizer(vocab_size=cfg.vocab_size)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        return (PagedInferenceEngine(cfg, ecfg, params, tok,
                                     use_kernel=False), tok)

    def _wave(self, tok, n, seed=0):
        # shared 18-token prefix (2 full cacheable pages at page 8) +
        # distinct suffixes of VARYING length within one bucket
        base = tok.encode("incident pod crashloop ns prod", add_bos=True)
        rng = np.random.default_rng(seed)
        return [list(base)
                + list(rng.integers(1, 400, 6 + (i % 4)).astype(int))
                for i in range(n)]

    @pytest.mark.parametrize("kv_dtype", [None, "int8", "int4"])
    def test_wave_parity_and_batched_path(self, kv_dtype):
        from k8s_llm_rca_tpu.utils.logging import METRICS

        plain, tok = self._mk(prefix_cache=False, kv_dtype=kv_dtype)
        eng, _ = self._mk(prefix_cache=True, kv_dtype=kv_dtype)
        seed_wave = self._wave(tok, 2, seed=9)
        want_seed = plain.generate([list(p) for p in seed_wave],
                                   max_new_tokens=6)
        got_seed = eng.generate([list(p) for p in seed_wave],
                                max_new_tokens=6)   # seeds the cache
        for a, b in zip(want_seed, got_seed):
            assert a.token_ids == b.token_ids
        wave = self._wave(tok, 8, seed=1)
        want = plain.generate([list(p) for p in wave], max_new_tokens=6)
        before = METRICS.count("engine.prefix_batch_hit_admissions")
        got = eng.generate([list(p) for p in wave], max_new_tokens=6)
        for a, b in zip(want, got):
            assert a.token_ids == b.token_ids, kv_dtype
        # the wave really admitted through the BATCHED hit path
        assert METRICS.count("engine.prefix_batch_hit_admissions") \
            - before >= 8, kv_dtype
        eng.allocator.check()

    def test_heterogeneous_prefixes_split_groups(self):
        """Hits with DIFFERENT cached lengths must not share one batched
        chunk shape: interleaved waves over two distinct prefixes still
        match the plain engine exactly."""
        plain, tok = self._mk(prefix_cache=False)
        eng, _ = self._mk(prefix_cache=True)
        base_a = tok.encode("incident pod crashloop ns prod",
                            add_bos=True)
        base_b = tok.encode("node disk pressure", add_bos=True)
        rng = np.random.default_rng(3)
        mk = lambda base, s: list(base) + list(
            rng.integers(1, 400, 5 + s).astype(int))
        seed_wave = [mk(base_a, 0), mk(base_b, 1)]
        plain.generate([list(p) for p in seed_wave], max_new_tokens=6)
        eng.generate([list(p) for p in seed_wave], max_new_tokens=6)
        wave = [mk(base_a, 2), mk(base_a, 3), mk(base_b, 2),
                mk(base_b, 3), mk(base_a, 4), mk(base_b, 4)]
        want = plain.generate([list(p) for p in wave], max_new_tokens=6)
        got = eng.generate([list(p) for p in wave], max_new_tokens=6)
        for a, b in zip(want, got):
            assert a.token_ids == b.token_ids
        eng.allocator.check()

    def test_hit_wave_releases_refs_on_pool_exhaustion(self):
        """OutOfPages mid-hit-group releases every acquired match ref:
        after the queue drains (retirements free pages), the cache's
        evictable count equals its resident count again."""
        eng, tok = self._mk(prefix_cache=True, max_batch=4)
        seed_wave = self._wave(tok, 2, seed=9)
        eng.generate([list(p) for p in seed_wave], max_new_tokens=6)
        wave = self._wave(tok, 12, seed=2)   # > slots: forces retries
        eng.generate([list(p) for p in wave], max_new_tokens=6)
        eng.allocator.check()
        pc = eng.prefix_cache
        assert pc.n_evictable == pc.n_resident, (
            pc.n_evictable, pc.n_resident)

    def test_oversized_hit_group_does_not_livelock(self):
        """A hit group sized past the pool's free list must shrink (the
        free-page bound), not OutOfPages-retry forever: a tiny pool with
        8 equal-prefix pending hits still serves every request."""
        from k8s_llm_rca_tpu.config import TINY, EngineConfig
        from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
        from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

        cfg = TINY.replace(max_seq_len=128)
        # 40 pages total: one 8-member hit group at ~56-token suffix
        # buckets (8 pages each) cannot allocate all-or-nothing
        ecfg = EngineConfig(max_batch=8, max_seq_len=128,
                            page_size=8, num_pages=40,
                            prefill_buckets=(32, 64), max_new_tokens=4,
                            temperature=0.0, decode_chunk=1,
                            prefix_cache=True)
        tok = get_tokenizer(vocab_size=cfg.vocab_size)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        eng = PagedInferenceEngine(cfg, ecfg, params, tok,
                                   use_kernel=False)
        base = tok.encode("incident pod crashloop ns prod", add_bos=True)
        rng = np.random.default_rng(4)
        mk = lambda i: list(base) + list(
            rng.integers(1, 400, 40 + (i % 3)).astype(int))
        eng.generate([mk(0)], max_new_tokens=4)        # seed the cache
        res = eng.generate([mk(i) for i in range(1, 9)], max_new_tokens=4)
        assert len(res) == 8
        eng.allocator.check()


class TestEvictableAwareAdmissionCap:
    """ADVICE low #2: the prefix-HIT group cap must count free pages PLUS
    refcount-0 (evictable) prefix-cache pages — what _alloc_with_evict can
    actually satisfy — so a hit wave under pool pressure admits in ONE
    batched dispatch instead of splitting."""

    def _engine(self):
        cfg = TINY.replace(max_seq_len=64)
        ecfg = EngineConfig(max_batch=8, max_seq_len=64,
                            page_size=8, num_pages=24,
                            prefill_buckets=(16, 32), max_new_tokens=4,
                            temperature=0.0, decode_chunk=1,
                            prefix_cache=True)
        tok = get_tokenizer(vocab_size=cfg.vocab_size)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        return PagedInferenceEngine(cfg, ecfg, params, tok,
                                    use_kernel=False), tok

    def test_hit_wave_under_pool_pressure_forms_one_group(self):
        eng, tok = self._engine()
        rng = np.random.default_rng(5)
        prefix = list(rng.integers(1, 400, 16).astype(int))   # 2 full pages

        # seed the prefix chain (24-token prompt: 3 full pages chained)
        eng.generate([prefix + list(rng.integers(1, 400, 8).astype(int))],
                     max_new_tokens=2)
        # evictable ballast: a long unrelated prompt chains 6 more pages
        eng.generate([list(rng.integers(1, 400, 48).astype(int))],
                     max_new_tokens=2)
        evictable = eng.prefix_cache.n_evictable
        assert evictable >= 7                       # 1 (3rd P page) + 6 (Q)

        # drain the free list to 2 pages: per-member suffix needs 2 pages,
        # so the OLD free-only cap would be max(1, 2 // 2) = 1 (split into
        # single admits) while free+evictable serves the whole wave of 4
        drain = eng.allocator.n_free - 2
        held = eng.allocator.alloc(drain, owner=999)
        wave = [prefix + list(rng.integers(1, 400, 8).astype(int))
                for _ in range(4)]
        for w in wave:
            eng.submit(w, max_new_tokens=2)

        hits0 = METRICS.count("engine.prefix_batch_hit_admissions")
        dispatches0 = METRICS.snapshot().get("engine.prefill.count", 0.0)
        done = eng.step()                           # admission tick
        assert METRICS.count("engine.prefix_batch_hit_admissions") \
            - hits0 == 4, "hit wave split instead of admitting as one group"
        assert METRICS.snapshot().get("engine.prefill.count", 0.0) \
            - dispatches0 == 1, "hit wave took more than one prefill dispatch"

        results = {r.seq_id: r
                   for r in list(done) + eng.run_to_completion()}
        assert len(results) == 4
        eng.allocator.free(held, owner=999)
        eng.allocator.check()
