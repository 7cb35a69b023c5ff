"""Pallas kernels vs pure-XLA reference implementations.

Runs hermetically on CPU via the Pallas interpreter (auto-selected when
the backend is not TPU), so kernel logic is covered without hardware —
the CPU-fallback test path SURVEY §4 calls for.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_rca_tpu.ops.attention import causal_attention
from k8s_llm_rca_tpu.ops.flash_attention import flash_attention
from k8s_llm_rca_tpu.ops.paged_attention import (
    block_pages, paged_attention, paged_attention_xla,
)


def _mk_qkv(key, b, s, n_heads, n_kv, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, n_heads, d), dtype)
    k = jax.random.normal(kk, (b, s, n_kv, d), dtype)
    v = jax.random.normal(kv, (b, s, n_kv, d), dtype)
    return q, k, v


class TestFlashAttention:
    @pytest.mark.parametrize("n_heads,n_kv", [(4, 4), (8, 2)])
    def test_matches_reference(self, n_heads, n_kv):
        b, s, d = 2, 96, 64          # s deliberately not a block multiple
        q, k, v = _mk_qkv(jax.random.PRNGKey(0), b, s, n_heads, n_kv, d)
        seq_lens = jnp.array([96, 57], jnp.int32)

        ref = causal_attention(q, k, v, seq_lens)
        out = flash_attention(q, k, v, seq_lens, block_q=32, block_k=32)
        # rows past seq_len are padding garbage in both paths; compare valid
        for bi, n in enumerate([96, 57]):
            np.testing.assert_allclose(
                np.asarray(out)[bi, :n], np.asarray(ref)[bi, :n],
                rtol=2e-5, atol=2e-5)

    def test_chunked_prefill_offset(self):
        # queries for positions 32..63 attending to a 64-wide kv prefix
        b, d = 1, 64
        q, k, v = _mk_qkv(jax.random.PRNGKey(1), b, 64, 4, 4, d)
        q_chunk = q[:, 32:64]
        seq_lens = jnp.array([64], jnp.int32)
        off = jnp.array([32], jnp.int32)

        ref = causal_attention(q_chunk, k, v, seq_lens, q_offset=off)
        out = flash_attention(q_chunk, k, v, seq_lens, off,
                              block_q=32, block_k=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_bfloat16(self):
        b, s, d = 1, 64, 64
        q, k, v = _mk_qkv(jax.random.PRNGKey(2), b, s, 4, 4, d, jnp.bfloat16)
        seq_lens = jnp.array([64], jnp.int32)
        ref = causal_attention(q, k, v, seq_lens)
        out = flash_attention(q, k, v, seq_lens, block_q=32, block_k=32)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=3e-2, atol=3e-2)


def _scattered_tables(lengths, page, pages_per_seq, n_pages, seed=0):
    """A table of distinct, shuffled page ids (never page 0) for the live
    pages of every slot, 0 past them: the allocator's contract."""
    rng = np.random.default_rng(seed)
    free = list(rng.permutation(np.arange(1, n_pages)))
    tables = np.zeros((len(lengths), pages_per_seq), np.int32)
    for b, n in enumerate(lengths):
        for j in range(-(-n // page)):
            tables[b, j] = free.pop()
    return jnp.asarray(tables)


# (id, page, pages_per_seq, n_pages, n_heads, n_kv, d, lengths given the
# tokens of one kernel block at that page size and table width): what the
# live-context walk has to get right, one case each
WALK_CASES = [
    # a slot of length 0 beside live ones: no copy, no matmul, zeros out
    ("dead-slot-beside-live", 16, 32, 96, 4, 2, 64,
     lambda blk: [40, 0, blk + 7, 0]),
    # a length exactly on a block boundary, and one token past it
    ("block-boundary", 16, 48, 128, 4, 2, 64,
     lambda blk: [blk, blk + 1, 2 * blk, 2 * blk + 1]),
    # one live page in a table of 256
    ("one-page-of-256", 16, 256, 40, 4, 2, 64, lambda blk: [9, 16]),
    # tables whose width is no multiple of the block: the tail repeats
    # the last entry
    ("table-20-page-16", 16, 20, 64, 4, 2, 64, lambda blk: [320, 257, 17]),
    ("table-10-page-64", 64, 10, 40, 4, 2, 64,
     lambda blk: [10 * 64, 5 * 64 + 3, 1]),
    # the bench's table (tests/test_aot_compile.py), and one narrower
    # than a block: the block is the table
    ("table-12-page-64", 64, 12, 40, 4, 2, 64,
     lambda blk: [12 * 64, 4 * 64, 65]),
    ("table-12-page-16", 16, 12, 40, 4, 2, 64, lambda blk: [192, 100, 17]),
    # GQA 32/8 at 128 lanes a head: the benchmark's head shape, small pool
    ("gqa-32-8-d128", 16, 20, 48, 32, 8, 128,
     lambda blk: [300, 16, 0, 161]),
]
WALK_IDS = [c[0] for c in WALK_CASES]


def _walk_case(case):
    _, page, pages_per_seq, n_pages, n_heads, n_kv, d, lengths = case
    return (page, pages_per_seq, n_pages, n_heads, n_kv, d,
            lengths(block_pages(page, pages_per_seq) * page))


class TestPagedAttention:
    @pytest.mark.parametrize("page,pages_per_seq,pages", [
        (16, 256, 16),      # the benchmark's cells: 256 tokens a block
        (64, 12, 4),        # the bench's pool
        (16, 12, 12),       # a table narrower than a block: the table
        (8, 8, 8),
        (512, 4, 1),        # a page longer than a block: one page
    ])
    def test_block_pages_follow_page_and_table(self, page, pages_per_seq,
                                               pages):
        assert block_pages(page, pages_per_seq) == pages

    def _mk_pool(self, key, n_kv, n_pages, page, d):
        kk, kv = jax.random.split(key)
        kp = jax.random.normal(kk, (n_pages, page, n_kv * d))
        vp = jax.random.normal(kv, (n_pages, page, n_kv * d))
        return kp, vp

    @pytest.mark.parametrize("n_heads,n_kv", [(4, 4), (8, 2)])
    def test_matches_xla_reference(self, n_heads, n_kv):
        b, d, page, n_pages, pp_seq = 3, 64, 16, 32, 4
        key = jax.random.PRNGKey(3)
        q = jax.random.normal(key, (b, n_heads, d))
        kp, vp = self._mk_pool(jax.random.PRNGKey(4), n_kv, n_pages, page, d)
        # scattered, non-contiguous page assignments; unused entries = 0
        tables = jnp.array([[5, 9, 2, 0],
                            [7, 0, 0, 0],
                            [1, 30, 11, 21]], jnp.int32)
        lengths = jnp.array([3 * page + 5, page - 2, 4 * page], jnp.int32)

        ref = paged_attention_xla(q, kp, vp, lengths, tables)
        out = paged_attention(q, kp, vp, lengths, tables)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        assert tables.shape == (b, pp_seq)

    def test_single_token_sequence(self):
        b, n_heads, n_kv, d, page = 1, 4, 4, 64, 16
        q = jax.random.normal(jax.random.PRNGKey(5), (b, n_heads, d))
        kp, vp = self._mk_pool(jax.random.PRNGKey(6), n_kv, 8, page, d)
        tables = jnp.zeros((1, 2), jnp.int32).at[0, 0].set(3)
        lengths = jnp.array([1], jnp.int32)
        ref = paged_attention_xla(q, kp, vp, lengths, tables)
        out = paged_attention(q, kp, vp, lengths, tables)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("case", WALK_CASES, ids=WALK_IDS)
    def test_walks_the_live_context(self, case):
        page, pages_per_seq, n_pages, n_heads, n_kv, d, lengths = (
            _walk_case(case))
        q = jax.random.normal(jax.random.PRNGKey(11),
                              (len(lengths), n_heads, d))
        kp, vp = self._mk_pool(jax.random.PRNGKey(12), n_kv, n_pages, page,
                               d)
        tables = _scattered_tables(lengths, page, pages_per_seq, n_pages)
        lens = jnp.asarray(lengths, jnp.int32)
        out = np.asarray(paged_attention(q, kp, vp, lens, tables))
        ref = np.asarray(paged_attention_xla(q, kp, vp, lens, tables))
        live = np.asarray(lengths) > 0
        np.testing.assert_allclose(out[live], ref[live],
                                   rtol=2e-5, atol=2e-5)
        # nothing to attend: zeros, where the reference averages page 0
        assert not out[~live].any()


class TestPagedAttentionQuant:
    """Quantized-pool kernel vs a dense gather+dequant reference."""

    def _mk_quant_pool(self, key, n_kv, n_pages, page, d, packed):
        from k8s_llm_rca_tpu.models.llama import _quantize_kv

        kk, kv = jax.random.split(key)
        kd = jax.random.normal(kk, (n_pages, page, n_kv * d))
        vd = jax.random.normal(kv, (n_pages, page, n_kv * d))
        kq, ks = _quantize_kv(kd, packed)
        vq, vs = _quantize_kv(vd, packed)
        return kq, vq, ks, vs

    def _reference(self, q, kq, vq, ks, vs, lengths, tables, packed):
        from k8s_llm_rca_tpu.models.llama import _dequant_layer

        kd = _dequant_layer(kq, ks, jnp.float32, packed)
        vd = _dequant_layer(vq, vs, jnp.float32, packed)
        return paged_attention_xla(q, kd, vd, lengths, tables)

    @pytest.mark.parametrize("packed", [False, True])
    @pytest.mark.parametrize("n_heads,n_kv", [(4, 4), (8, 2)])
    def test_matches_dequant_reference(self, n_heads, n_kv, packed):
        from k8s_llm_rca_tpu.ops.paged_attention import paged_attention_quant

        b, d, page, n_pages = 3, 64, 16, 32
        q = jax.random.normal(jax.random.PRNGKey(7), (b, n_heads, d))
        kq, vq, ks, vs = self._mk_quant_pool(jax.random.PRNGKey(8), n_kv,
                                             n_pages, page, d, packed)
        # page ids straddle the (8, page) scale-block boundaries on purpose
        tables = jnp.array([[5, 9, 2, 0],
                            [7, 0, 0, 0],
                            [16, 30, 11, 23]], jnp.int32)
        lengths = jnp.array([3 * page + 5, page - 2, 4 * page], jnp.int32)

        ref = self._reference(q, kq, vq, ks, vs, lengths, tables, packed)
        out = paged_attention_quant(q, kq, vq, ks, vs, lengths, tables,
                                    packed=packed)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("packed", [False, True])
    def test_single_token_sequence(self, packed):
        from k8s_llm_rca_tpu.ops.paged_attention import paged_attention_quant

        b, n_heads, n_kv, d, page = 1, 4, 4, 64, 16
        q = jax.random.normal(jax.random.PRNGKey(9), (b, n_heads, d))
        kq, vq, ks, vs = self._mk_quant_pool(jax.random.PRNGKey(10), n_kv,
                                             9, page, d, packed)
        tables = jnp.zeros((1, 2), jnp.int32).at[0, 0].set(8)
        lengths = jnp.array([1], jnp.int32)
        ref = self._reference(q, kq, vq, ks, vs, lengths, tables, packed)
        out = paged_attention_quant(q, kq, vq, ks, vs, lengths, tables,
                                    packed=packed)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("packed", [False, True], ids=["int8", "int4"])
    @pytest.mark.parametrize("case", WALK_CASES, ids=WALK_IDS)
    def test_walks_the_live_context(self, case, packed):
        from k8s_llm_rca_tpu.ops.paged_attention import paged_attention_quant

        page, pages_per_seq, n_pages, n_heads, n_kv, d, lengths = (
            _walk_case(case))
        q = jax.random.normal(jax.random.PRNGKey(13),
                              (len(lengths), n_heads, d))
        kq, vq, ks, vs = self._mk_quant_pool(jax.random.PRNGKey(14), n_kv,
                                             n_pages, page, d, packed)
        # shuffled ids: a block's pages fall in different 128-lane scale
        # rows, at every position inside them
        tables = _scattered_tables(lengths, page, pages_per_seq, n_pages)
        lens = jnp.asarray(lengths, jnp.int32)
        out = np.asarray(paged_attention_quant(q, kq, vq, ks, vs, lens,
                                               tables, packed=packed))
        ref = np.asarray(self._reference(q, kq, vq, ks, vs, lens, tables,
                                         packed))
        live = np.asarray(lengths) > 0
        np.testing.assert_allclose(out[live], ref[live],
                                   rtol=2e-4, atol=2e-4)
        assert not out[~live].any()

    def test_pool_no_multiple_of_a_scale_row(self):
        """9 pages of 16 tokens are 144 scales, no multiple of the 128
        lanes a row of the kernel's scale pool has: the last page's row
        is padded, not dropped."""
        from k8s_llm_rca_tpu.ops.paged_attention import paged_attention_quant

        q = jax.random.normal(jax.random.PRNGKey(15), (2, 4, 64))
        kq, vq, ks, vs = self._mk_quant_pool(jax.random.PRNGKey(16), 2, 9,
                                             16, 64, False)
        tables = jnp.asarray([[8, 7, 0], [1, 0, 0]], jnp.int32)
        lens = jnp.asarray([30, 16], jnp.int32)
        ref = self._reference(q, kq, vq, ks, vs, lens, tables, False)
        out = paged_attention_quant(q, kq, vq, ks, vs, lens, tables)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_page_size_has_to_share_a_scale_row(self):
        from k8s_llm_rca_tpu.ops.paged_attention import paged_attention_quant

        z8 = jnp.zeros((4, 24, 128), jnp.int8)
        zs = jnp.zeros((4, 24), jnp.float32)
        with pytest.raises(ValueError, match="page_size"):
            paged_attention_quant(jnp.zeros((1, 2, 64)), z8, z8, zs, zs,
                                  jnp.asarray([3]), jnp.zeros((1, 2),
                                                              jnp.int32))

    def test_engine_decode_step_holds_no_sequence_is_told_zero(self):
        """A slot whose table row starts at the trash page enters the
        kernel at length 0 whatever stale length it carries; the live
        slot's logits are those of the gather path."""
        from k8s_llm_rca_tpu.config import TINY
        from k8s_llm_rca_tpu.engine.paged import (
            init_paged_cache, paged_decode_step, paged_prefill,
        )
        from k8s_llm_rca_tpu.models import llama

        cfg = TINY.replace(max_seq_len=64)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        pool = init_paged_cache(cfg, 32, 8, kv_dtype=jnp.int8)
        padded = jnp.zeros((1, 16), jnp.int32).at[0, :13].set(
            jnp.arange(5, 18))
        pool, _ = paged_prefill(cfg, params, pool, padded, jnp.int32(13),
                                jnp.asarray([7, 3], jnp.int32))
        tables = jnp.zeros((2, 8), jnp.int32).at[0, :3].set(
            jnp.asarray([7, 3, 11]))
        args = (jnp.asarray([21, 4], jnp.int32),
                jnp.asarray([13, 57], jnp.int32), tables)   # 57: stale
        _, lg_kernel = paged_decode_step(cfg, params, pool, *args,
                                         use_kernel=True)
        _, lg_xla = paged_decode_step(cfg, params, pool, *args,
                                      use_kernel=False)
        np.testing.assert_allclose(np.asarray(lg_kernel[0]),
                                   np.asarray(lg_xla[0]),
                                   rtol=2e-4, atol=2e-4)
        assert np.isfinite(np.asarray(lg_kernel)).all()

    def test_engine_decode_step_uses_kernel_path(self):
        # use_kernel=True on CPU runs the quant kernel in interpret mode;
        # its logits must match the gather+dequant path (use_kernel=False)
        from k8s_llm_rca_tpu.config import TINY
        from k8s_llm_rca_tpu.engine.paged import (
            init_paged_cache, paged_decode_step, paged_prefill,
        )
        from k8s_llm_rca_tpu.models import llama

        cfg = TINY.replace(max_seq_len=64)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        for kv_dtype in (jnp.int8, "int4"):
            pool = init_paged_cache(cfg, 32, 8, kv_dtype=kv_dtype)
            prompt = list(range(5, 18))
            padded = jnp.zeros((1, 16), jnp.int32).at[0, :13].set(
                jnp.asarray(prompt))
            pool, logits = paged_prefill(cfg, params, pool, padded,
                                         jnp.int32(13),
                                         jnp.asarray([7, 3], jnp.int32))
            tables = jnp.asarray([[7, 3, 11, 0, 0, 0, 0, 0]], jnp.int32)
            args = (jnp.asarray([int(jnp.argmax(logits[0]))], jnp.int32),
                    jnp.asarray([13], jnp.int32), tables)
            _, lg_kernel = paged_decode_step(cfg, params, pool, *args,
                                             use_kernel=True)
            _, lg_xla = paged_decode_step(cfg, params, pool, *args,
                                          use_kernel=False)
            np.testing.assert_allclose(np.asarray(lg_kernel),
                                       np.asarray(lg_xla),
                                       rtol=2e-4, atol=2e-4)


class TestPagedAttentionLayerIndexed:
    """``layer=`` over the engine's stacked pool: the kernel reads layer
    ``l`` of [L, n_pages, page, KV] where it lies, and gives what the
    3-D call gives on that layer sliced out."""

    @pytest.mark.parametrize("pages_per_seq", [32, 20],
                             ids=["table-32", "table-20-no-block-multiple"])
    @pytest.mark.parametrize("kind", ["bf16", "int8", "int4"])
    def test_equals_the_call_on_the_sliced_layer(self, kind, pages_per_seq):
        from k8s_llm_rca_tpu.models.llama import _quantize_kv
        from k8s_llm_rca_tpu.ops.paged_attention import paged_attention_quant

        n_layers, page, n_pages, n_heads, n_kv, d = 3, 16, 64, 4, 2, 64
        blk = block_pages(page, pages_per_seq) * page
        lengths = [blk + 7, 0, pages_per_seq * page, 17]
        kk, kv, kq = jax.random.split(jax.random.PRNGKey(17), 3)
        shape = (n_layers, n_pages, page, n_kv * d)
        q = jax.random.normal(kq, (len(lengths), n_heads, d))
        pools = (jax.random.normal(kk, shape), jax.random.normal(kv, shape))
        if kind == "bf16":
            pools = tuple(p.astype(jnp.bfloat16) for p in pools)
            call = paged_attention
        else:
            (k8, ks), (v8, vs) = (_quantize_kv(p, kind == "int4")
                                  for p in pools)
            pools = (k8, v8, ks, vs)
            call = functools.partial(paged_attention_quant,
                                     packed=kind == "int4")
        tables = _scattered_tables(lengths, page, pages_per_seq, n_pages)
        lens = jnp.asarray(lengths, jnp.int32)
        for l in range(n_layers):
            stacked = call(q, *pools, lens, tables, layer=l)
            sliced = call(q, *(p[l] for p in pools), lens, tables)
            np.testing.assert_array_equal(np.asarray(stacked),
                                          np.asarray(sliced))
        # the layers differ, so a kernel that ignored the index would show
        assert not np.array_equal(
            np.asarray(call(q, *pools, lens, tables, layer=0)),
            np.asarray(stacked))


class TestFlashSharded:
    """flash under TP (round-2 review item 7): the kernel runs PER HEAD SHARD
    inside shard_map instead of conceding sharded prefill to XLA."""

    def _mesh(self, cpu_devices):
        from k8s_llm_rca_tpu.config import MeshConfig
        from k8s_llm_rca_tpu.runtime.mesh import build_mesh

        return build_mesh(MeshConfig(data=2, model=2),
                          devices=cpu_devices[:4])

    def test_matches_xla_reference(self, cpu_devices):
        from k8s_llm_rca_tpu.ops.attention import causal_attention
        from k8s_llm_rca_tpu.ops.flash_attention import (
            flash_attention_sharded,
        )

        mesh = self._mesh(cpu_devices)
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(kq, (2, 1024, 4, 16), jnp.float32)
        k = jax.random.normal(kk, (2, 1024, 2, 16), jnp.float32)
        v = jax.random.normal(kv, (2, 1024, 2, 16), jnp.float32)
        lens = jnp.asarray([1024, 700], jnp.int32)
        with jax.default_matmul_precision("float32"):
            ref = causal_attention(q, k, v, lens)
            out = flash_attention_sharded(q, k, v, lens, mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=5e-4, atol=5e-4)

    def test_rejects_indivisible_heads(self, cpu_devices):
        from k8s_llm_rca_tpu.ops.flash_attention import (
            flash_attention_sharded,
        )

        mesh = self._mesh(cpu_devices)
        q = jnp.zeros((1, 16, 3, 8), jnp.float32)     # 3 heads, model=2
        kv = jnp.zeros((1, 16, 3, 8), jnp.float32)
        with pytest.raises(ValueError, match="not divisible"):
            flash_attention_sharded(q, kv, kv, jnp.asarray([16]), mesh)

    def test_tp_prefill_runs_the_sharded_kernel(self, cpu_devices):
        """llama.prefill with flash_mesh= on TP-sharded params (the path
        flash_prefill_plan selects on TPU) matches the plain XLA prefill
        token-for-token."""
        from k8s_llm_rca_tpu.config import TINY
        from k8s_llm_rca_tpu.models import llama
        from k8s_llm_rca_tpu.runtime.sharding import (
            llama_param_specs, shard_pytree,
        )

        mesh = self._mesh(cpu_devices)
        cfg = TINY.replace(max_seq_len=1024)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        sharded = shard_pytree(params, llama_param_specs(cfg), mesh)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 1024), 0,
                                    cfg.vocab_size)
        n = jnp.int32(900)
        with jax.default_matmul_precision("float32"):
            ref_cache = llama.init_cache(cfg, 2, 1024)
            ref_cache, ref_lg = llama.prefill(cfg, params, ref_cache,
                                              tokens, n, jnp.int32(0))
            fl_cache = llama.init_cache(cfg, 2, 1024)
            fl_cache, fl_lg = llama.prefill(cfg, sharded, fl_cache, tokens,
                                            n, jnp.int32(0), use_flash=True,
                                            flash_mesh=mesh)
        assert int(jnp.argmax(ref_lg)) == int(jnp.argmax(fl_lg))
        np.testing.assert_allclose(np.asarray(fl_cache.k[:, 0, :900]),
                                   np.asarray(ref_cache.k[:, 0, :900]),
                                   rtol=5e-4, atol=5e-4)

    def test_flash_prefill_plan_gating(self, cpu_devices, monkeypatch):
        from k8s_llm_rca_tpu.config import TINY
        from k8s_llm_rca_tpu.engine import engine as eng_mod
        from k8s_llm_rca_tpu.models import llama
        from k8s_llm_rca_tpu.runtime.sharding import (
            llama_param_specs, shard_pytree,
        )

        mesh = self._mesh(cpu_devices)
        cfg = TINY
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        sharded = shard_pytree(params, llama_param_specs(cfg), mesh)
        # CPU: no kernel anywhere
        assert eng_mod.flash_prefill_plan(params, None, cfg) == (False, None)
        assert eng_mod.flash_prefill_plan(sharded, mesh, cfg) == (False,
                                                                  None)
        # "TPU": plain kernel unsharded, per-shard kernel under TP
        monkeypatch.setattr(eng_mod.jax, "default_backend", lambda: "tpu")
        assert eng_mod.flash_prefill_plan(params, None, cfg) == (True, None)
        assert eng_mod.flash_prefill_plan(sharded, mesh, cfg) == (True,
                                                                  mesh)
        # indivisible heads: concede to XLA
        cfg3 = cfg.replace(n_heads=6, n_kv_heads=3)
        assert eng_mod.flash_prefill_plan(sharded, mesh, cfg3) == (False,
                                                                   None)
        # EP token sharding: concede to XLA even with a TP mesh present
        assert eng_mod.flash_prefill_plan(sharded, mesh, cfg,
                                          ep_mesh=mesh) == (False, None)
