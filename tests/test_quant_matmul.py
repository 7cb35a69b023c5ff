"""Fused weight-dequant matmul kernels (ops/quant_matmul.py).

Three layers of coverage, all hermetic on CPU:

- interpret-mode NUMERIC PARITY of every kernel variant against the
  ``dq()`` XLA reference — the full (bits x scale-layout x consumer-
  shape) matrix, with dims sized past the 256/512 block targets so the
  multi-block grid paths execute (the tests/test_kernels.py pattern);
- ENGINE greedy byte-parity with ``fused_quant_matmul=True`` (the shim
  falls back to the identical dq() expression off-TPU — the flag must be
  token-inert for single-device and GSPMD-TP serving), plus the
  chunked-prefill tick budget's byte-parity against monolithic prefill;
- LOUD EXCLUSIONS: every unsupported composition documented in
  ops/quant_matmul.py and the prefill_chunk_budget validation raises a
  ValueError with a matching test here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_rca_tpu.config import (
    TINY, TINY_MOE, EngineConfig, MeshConfig,
)
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.models.quant import (
    dq, quantize, quantize_params, repack_nibbles_grouped,
)
from k8s_llm_rca_tpu.ops.quant_matmul import (
    qmm, qmm_experts, qmm_head, qmm_swiglu_experts, quant_matmul,
    quant_matmul_experts, quant_matmul_head, quant_swiglu_experts,
)

pytestmark = pytest.mark.kernels


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


def _close(got, ref, dtype=jnp.float32):
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol * float(jnp.max(jnp.abs(ref))))


# ---------------------------------------------------------------------------
# interpret-mode kernel parity: (bits x scale layout x consumer shape)
# ---------------------------------------------------------------------------


class TestKernelParity:
    # dims deliberately exceed the block targets (bm/bn 256, bk 512) so
    # the (m, n, k) grids are multi-block — single-block shapes would
    # never exercise the accumulate-across-k scratch logic
    M, K, N = 320, 640, 384

    @pytest.mark.parametrize("bits", [8, 4])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_kn_per_column(self, bits, dtype):
        x = _rand(0, (self.M, self.K), dtype)
        w = quantize(_rand(1, (self.K, self.N)), axis=-1, bits=bits,
                     compute_dtype=dtype)
        _close(quant_matmul(x, w), x @ dq(w).astype(dtype), dtype)

    @pytest.mark.parametrize("bits", [8, 4])
    def test_kn_leading_batch_dims(self, bits):
        # [B, S, K] activations flatten through the same kernel
        x = _rand(2, (2, 5, self.K))
        w = quantize(_rand(3, (self.K, self.N)), axis=-1, bits=bits)
        _close(quant_matmul(x, w), x @ dq(w))

    @pytest.mark.parametrize("bits", [8, 4])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_nk_per_row_head(self, bits, dtype):
        # the lm-head layout: [V, K] table, per-ROW scales, x @ W^T
        x = _rand(4, (2, 3, self.K), dtype)
        w = quantize(_rand(5, (self.N, self.K)), axis=0, bits=bits,
                     compute_dtype=dtype)
        _close(quant_matmul_head(x, w),
               jnp.einsum("bsh,vh->bsv", x, dq(w).astype(dtype)), dtype)

    @pytest.mark.parametrize("bits", [8, 4])
    def test_experts_shared_x(self, bits):
        # "bsh,ehi->bsei": every token through every stacked expert
        e = 3
        x = _rand(6, (2, 4, self.K))
        w = quantize(_rand(7, (e, self.K, self.N)), axis=(0, -1),
                     bits=bits)
        _close(quant_matmul_experts(x, w),
               jnp.einsum("bsh,ehi->bsei", x, dq(w)))

    @pytest.mark.parametrize("bits", [8, 4])
    def test_experts_per_expert_x(self, bits):
        # "bsei,eih->bseh": per-expert activations (the down projection)
        e = 3
        x = _rand(8, (2, 4, e, self.N))
        w = quantize(_rand(9, (e, self.N, self.K)), axis=(0, -1),
                     bits=bits)
        _close(quant_matmul_experts(x, w),
               jnp.einsum("bsei,eih->bseh", x, dq(w)))

    @staticmethod
    def _swiglu_ref(x, wg, wu, wd):
        gate = jax.nn.silu(jnp.einsum("bsh,ehi->bsei", x, dq(wg)))
        up = jnp.einsum("bsh,ehi->bsei", x, dq(wu))
        return jnp.einsum("bsei,eih->bseh", gate * up, dq(wd))

    @pytest.mark.parametrize("tiles", ["one-tile", "many-tiles"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_swiglu_experts(self, dtype, tiles, monkeypatch):
        # the model's fused expert form: gate and up in one call, the
        # product on the float32 sums, down from the result as it lies.
        # "many-tiles" shrinks the tile targets so that the grid has
        # several M, N and K steps and a tile several sub-tiles
        import importlib
        mod = importlib.import_module("k8s_llm_rca_tpu.ops.quant_matmul")
        if tiles == "many-tiles":
            monkeypatch.setattr(mod, "_EKN4_BK", 256)
            monkeypatch.setattr(mod, "_EKN4_TILE_BYTES", 128 * 128)
            monkeypatch.setattr(mod, "_EKN4_SUB", 64)
            assert mod._ekn4_tiles(self.M, self.K, self.N // 2) == (
                160, 160, 96, 40)
        e = 3
        x = _rand(12, (2, self.M // 2, self.K), dtype) * 0.25
        wg, wu, wd = (
            quantize(_rand(13 + i, shape) * 0.1, axis=(0, -1), bits=4,
                     compute_dtype=dtype)
            for i, shape in enumerate([(e, self.K, self.N)] * 2
                                      + [(e, self.N, self.K)]))
        _close(quant_swiglu_experts(x, wg, wu, wd),
               self._swiglu_ref(x, wg, wu, wd), dtype)

    @pytest.mark.parametrize("rows", [6, 272, 530])
    def test_swiglu_experts_any_row_count(self, rows):
        # up to 256 rows are one tile whatever their number; more are cut
        # into equal tiles of a multiple of 16 and the last one padded
        # (272 -> 2 x 144, 530 -> 3 x 192), and the padding never shows
        import importlib
        mod = importlib.import_module("k8s_llm_rca_tpu.ops.quant_matmul")
        bm = mod._ekn4_tiles(rows, 128, 64)[0]
        assert bm == {6: 6, 272: 144, 530: 192}[rows]
        e = 2
        x = _rand(30, (1, rows, 128)) * 0.25
        wg, wu, wd = (
            quantize(_rand(31 + i, shape) * 0.1, axis=(0, -1), bits=4)
            for i, shape in enumerate([(e, 128, 256)] * 2 + [(e, 256, 128)]))
        got = quant_swiglu_experts(x, wg, wu, wd)
        assert got.shape == (1, rows, e, 128)
        _close(got, self._swiglu_ref(x, wg, wu, wd))

    @pytest.mark.parametrize("bits", [8, 4])
    def test_decode_row_shapes(self, bits):
        # the decode hot shape: M=1 token row (single-block M)
        x = _rand(10, (1, self.K))
        w = quantize(_rand(11, (self.K, self.N)), axis=-1, bits=bits)
        _close(quant_matmul(x, w), x @ dq(w))


# ---------------------------------------------------------------------------
# shim dispatch + loud exclusions
# ---------------------------------------------------------------------------


class TestShimsAndExclusions:
    def test_qmm_plain_array_falls_back(self):
        # unquantized weights take the XLA matmul byte-identically
        x, w = _rand(0, (2, 8)), _rand(1, (8, 6))
        assert jnp.array_equal(qmm(x, w), x @ w)

    def test_qmm_quant_cpu_falls_back_byte_identical(self):
        x = _rand(2, (2, 8))
        w = quantize(_rand(3, (8, 6)), axis=-1, bits=4)
        assert jnp.array_equal(qmm(x, w), x @ dq(w))

    def test_qmm_head_and_experts_fall_back_byte_identical(self):
        x = _rand(4, (1, 2, 8))
        head = quantize(_rand(5, (10, 8)), axis=0, bits=8)
        assert jnp.array_equal(
            qmm_head(x, head), jnp.einsum("bsh,vh->bsv", x, dq(head)))
        we = quantize(_rand(6, (3, 8, 6)), axis=(0, -1), bits=8)
        assert jnp.array_equal(
            qmm_experts(x, we), jnp.einsum("bsh,ehi->bsei", x, dq(we)))

    def test_qmm_swiglu_experts_falls_back_byte_identical(self):
        # off the TPU the fused form's shim IS the dense form's expression
        x = _rand(20, (1, 4, 32))
        wg, wu = (quantize(_rand(21 + i, (3, 32, 16)), axis=(0, -1), bits=4)
                  for i in range(2))
        wd = quantize(_rand(23, (3, 16, 32)), axis=(0, -1), bits=4)
        np.testing.assert_array_equal(
            qmm_swiglu_experts(x, wg, wu, wd),
            TestKernelParity._swiglu_ref(x, wg, wu, wd))

    @pytest.mark.parametrize("bad", ["int8", "plain", "2-D", "shape"])
    def test_swiglu_experts_rejects(self, bad):
        x = _rand(24, (1, 4, 32))
        w = lambda k, shape, bits=4: quantize(_rand(k, shape),
                                              axis=(0, -1), bits=bits)
        wg, wu, wd = w(25, (3, 32, 16)), w(26, (3, 32, 16)), w(27, (3, 16, 32))
        if bad == "int8":
            wu = w(26, (3, 32, 16), bits=8)
        elif bad == "plain":
            wd = _rand(27, (3, 16, 32))
        elif bad == "2-D":
            wg = quantize(_rand(25, (32, 16)), axis=-1, bits=4)
        else:
            wd = w(27, (3, 16, 64))
        with pytest.raises(ValueError, match="QuantTensor|int4|mismatch"):
            quant_swiglu_experts(x, wg, wu, wd)

    def test_quant_matmul_rejects_plain_array(self):
        with pytest.raises(ValueError, match="QuantTensor"):
            quant_matmul(_rand(0, (2, 8)), _rand(1, (8, 6)))

    def test_quant_matmul_rejects_stacked_weight(self):
        w = quantize(_rand(2, (3, 8, 6)), axis=(0, -1), bits=8)
        with pytest.raises(ValueError, match="quant_matmul_experts"):
            quant_matmul(_rand(3, (2, 8)), w)

    def test_quant_matmul_rejects_per_row_scale(self):
        w = quantize(_rand(4, (8, 6)), axis=0, bits=8)   # scale [8, 1]
        with pytest.raises(ValueError, match="quant_matmul_head"):
            quant_matmul(_rand(5, (2, 8)), w)

    def test_quant_matmul_head_rejects_per_column_scale(self):
        w = quantize(_rand(6, (10, 8)), axis=-1, bits=8)  # scale [1, 8]
        with pytest.raises(ValueError, match="per-row"):
            quant_matmul_head(_rand(7, (1, 2, 8)), w)

    def test_experts_rejects_2d_weight(self):
        w = quantize(_rand(8, (8, 6)), axis=-1, bits=8)
        with pytest.raises(ValueError, match="stacked"):
            quant_matmul_experts(_rand(9, (1, 2, 8)), w)

    def test_shape_mismatch_raises(self):
        w = quantize(_rand(10, (8, 6)), axis=-1, bits=8)
        with pytest.raises(ValueError, match="mismatch"):
            quant_matmul(_rand(11, (2, 12)), w)

    def test_grouped_repack_rejected_globally(self):
        # the shard-local grouped int4 layout must refuse GLOBAL
        # consumption everywhere: dq, gather_rows, and every qmm shim
        from k8s_llm_rca_tpu.models.quant import gather_rows

        w4 = quantize(_rand(12, (8, 16)), axis=-1, bits=4)
        grouped = repack_nibbles_grouped(w4, groups=2)
        x = _rand(13, (2, 8))
        for op in (lambda: dq(grouped),
                   lambda: gather_rows(grouped, jnp.array([0])),
                   lambda: qmm(x, grouped),
                   lambda: qmm_head(_rand(14, (1, 1, 16)), grouped),
                   lambda: qmm_experts(_rand(15, (1, 1, 8)), grouped),
                   lambda: quant_matmul(x, grouped)):
            with pytest.raises(ValueError, match="grouped-repacked"):
                op()

    def test_grouped_repack_rejected_by_quantize_params(self):
        w4 = quantize(_rand(16, (8, 16)), axis=-1, bits=4)
        grouped = repack_nibbles_grouped(w4, groups=2)
        with pytest.raises(ValueError, match="grouped"):
            quantize_params({"layers": [{"w": grouped}]})


# ---------------------------------------------------------------------------
# engine integration: fused_quant_matmul byte-parity (CPU fallback path)
# ---------------------------------------------------------------------------


def _quant_engine(model_cfg, bits=4, fused=False, params=None,
                  cp_mesh=None, pp_mesh=None, **ecfg_kw):
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    if params is None:
        params = quantize_params(
            llama.init_params(model_cfg, jax.random.PRNGKey(0)),
            compute_dtype=jnp.float32, bits=bits)
    defaults = dict(max_batch=2, max_seq_len=64, page_size=8,
                    num_pages=64, prefill_buckets=(16, 32, 64),
                    max_new_tokens=6, temperature=0.0, prefix_cache=False)
    defaults.update(ecfg_kw)
    cfg = model_cfg.replace(max_seq_len=64,
                            fused_quant_matmul=fused)
    tok = get_tokenizer(vocab_size=model_cfg.vocab_size)
    kw = {}
    if cp_mesh is not None:
        kw["cp_mesh"] = cp_mesh
    if pp_mesh is not None:
        kw["pp_mesh"] = pp_mesh
    return make_engine(cfg, EngineConfig(**defaults), params, tok,
                       use_kernel=False, **kw), tok


class TestEngineFusedFlagParity:
    # only the flagship int4 cell rides the tier-1 gate (each cell
    # compiles two engines, ~5-7 s); int8 runs under -m slow
    @pytest.mark.parametrize(
        "bits", [pytest.param(8, marks=pytest.mark.slow), 4])
    def test_greedy_byte_parity(self, bits):
        ref_eng, tok = _quant_engine(TINY, bits=bits)
        fused_eng, _ = _quant_engine(TINY, bits=bits, fused=True)
        prompts = [tok.encode(t, add_bos=True) for t in
                   ["pod crashloop backoff", "pvc pending why"]]
        ref = ref_eng.generate([list(p) for p in prompts],
                               max_new_tokens=6)
        got = fused_eng.generate([list(p) for p in prompts],
                                 max_new_tokens=6)
        for r, g in zip(ref, got):
            assert r.token_ids == g.token_ids
            assert r.finish_reason == g.finish_reason

    @pytest.mark.slow
    def test_moe_greedy_byte_parity(self):
        # stacked-expert einsums route through qmm_experts
        ref_eng, tok = _quant_engine(TINY_MOE, bits=4)
        fused_eng, _ = _quant_engine(TINY_MOE, bits=4, fused=True)
        p = tok.encode("node notready with pressure", add_bos=True)
        ref = ref_eng.generate([list(p)], max_new_tokens=6)
        got = fused_eng.generate([list(p)], max_new_tokens=6)
        assert ref[0].token_ids == got[0].token_ids

    def test_gspmd_tp_sharded_byte_parity(self, cpu_devices):
        # GSPMD-sharded quantized params: the shim falls back to the
        # dq() expression (pallas has no SPMD partitioning rule), so the
        # fused flag must be token-inert under TP too
        from k8s_llm_rca_tpu.runtime.mesh import build_mesh
        from k8s_llm_rca_tpu.runtime.sharding import (
            llama_param_specs, shard_pytree,
        )

        qp = quantize_params(
            llama.init_params(TINY.replace(max_seq_len=64),
                              jax.random.PRNGKey(0)),
            compute_dtype=jnp.float32, bits=4)
        mesh = build_mesh(MeshConfig(data=2, model=2),
                          devices=cpu_devices[:4])
        sharded = shard_pytree(qp, llama_param_specs(TINY), mesh)
        ref_eng, tok = _quant_engine(TINY, params=qp)
        fused_eng, _ = _quant_engine(TINY, params=sharded, fused=True)
        p = tok.encode("pod pending unschedulable", add_bos=True)
        ref = ref_eng.generate([list(p)], max_new_tokens=6)
        got = fused_eng.generate([list(p)], max_new_tokens=6)
        assert ref[0].token_ids == got[0].token_ids


# ---------------------------------------------------------------------------
# chunked-prefill tick budget
# ---------------------------------------------------------------------------


class TestPrefillChunkBudget:
    def _long_prompt(self, tok):
        p = tok.encode("pod crashloop backoff in namespace prod",
                       add_bos=True)
        # spans several 16-token chunks, but short enough that the
        # 64-token cache cap never truncates it (truncation would shift
        # the chunk count the counter test pins down)
        assert 32 < len(p) <= 64 - 6 - 1
        return p

    @pytest.mark.parametrize("overlap,fused", [
        (False, False), pytest.param(True, False, marks=pytest.mark.slow),
        # the fused flag and the budget together against neither (moved
        # here from the dryrun at PR 50)
        (False, True)])
    def test_byte_parity_vs_monolithic(self, overlap, fused):
        ref_eng, tok = _quant_engine(TINY, host_overlap=overlap)
        chunk_eng, _ = _quant_engine(TINY, fused=fused,
                                     prefill_chunk_budget=16,
                                     host_overlap=overlap)
        long_p = self._long_prompt(tok)
        short_p = tok.encode("node notready", add_bos=True)
        ref = ref_eng.generate([list(long_p), list(short_p)],
                               max_new_tokens=6)
        got = chunk_eng.generate([list(long_p), list(short_p)],
                                 max_new_tokens=6)
        for r, g in zip(ref, got):
            assert r.token_ids == g.token_ids
            assert r.finish_reason == g.finish_reason
        # every page returned (chunk tables cannot leak)
        chunk_eng.allocator.check()
        assert chunk_eng.allocator.n_free == ref_eng.allocator.n_free

    def test_prefill_chunks_counter_and_timeline(self):
        eng, tok = _quant_engine(TINY, prefill_chunk_budget=16)
        long_p = self._long_prompt(tok)
        eng.generate([list(long_p)], max_new_tokens=4)
        n_chunks = eng._counts.get("engine.prefill_chunks", 0)
        # ceil(len / 16) chunks, each counted once
        assert n_chunks == -(-len(long_p) // 16)
        # prefill token totals match the monolithic accounting exactly
        assert eng._counts.get("engine.prefill_tokens") == len(long_p)

    def test_short_prompt_admits_monolithically(self):
        eng, tok = _quant_engine(TINY, prefill_chunk_budget=32)
        p = tok.encode("node notready", add_bos=True)
        assert len(p) <= 32
        eng.generate([list(p)], max_new_tokens=4)
        assert eng._counts.get("engine.prefill_chunks", 0) == 0

    @pytest.mark.slow
    def test_prefix_cache_composes(self):
        # second submission shares the long prompt as a cached prefix;
        # parity must hold with the cache splitting chunk boundaries
        ref_eng, tok = _quant_engine(TINY, prefix_cache=True)
        chunk_eng, _ = _quant_engine(TINY, prefix_cache=True,
                                     prefill_chunk_budget=16)
        long_p = self._long_prompt(tok)
        tail = tok.encode("node notready")
        prompts = [list(long_p), list(long_p) + tail]
        ref = ref_eng.generate([list(p) for p in prompts],
                               max_new_tokens=6)
        got = chunk_eng.generate([list(p) for p in prompts],
                                 max_new_tokens=6)
        for r, g in zip(ref, got):
            assert r.token_ids == g.token_ids

    def test_cancel_mid_prefill_frees_pages(self):
        eng, tok = _quant_engine(TINY, prefill_chunk_budget=16)
        long_p = self._long_prompt(tok)
        n_free0 = eng.allocator.n_free
        seq = eng.submit(list(long_p), max_new_tokens=4)
        eng.step()                      # first chunk(s) dispatched
        assert eng._prefilling          # still mid-prefill
        assert eng.cancel_seq(seq)
        eng.allocator.check()
        assert eng.allocator.n_free == n_free0
        assert not eng.has_work

    def test_snapshot_mid_prefill_exports_pending_entry(self):
        eng, tok = _quant_engine(TINY, prefill_chunk_budget=16)
        long_p = self._long_prompt(tok)
        eng.submit(list(long_p), max_new_tokens=4)
        eng.step()
        assert eng._prefilling
        snap = eng.snapshot_sequences()
        (entry,) = snap["sequences"]
        assert entry["prompt_ids"] == list(long_p)
        assert entry["generated"] == []
        assert entry["remaining_new_tokens"] == 4

    @pytest.mark.parametrize("budget", [12, -8])   # 12 % 8 != 0; below 0
    def test_non_page_multiple_budget_rejects(self, budget):
        with pytest.raises(ValueError,
                           match="positive multiple of page_size"):
            _quant_engine(TINY, prefill_chunk_budget=budget)

    def test_cp_mesh_rejects_budget(self, cpu_devices):
        from k8s_llm_rca_tpu.runtime.mesh import build_mesh

        mesh = build_mesh(MeshConfig(seq=2), devices=cpu_devices[:2])
        with pytest.raises(ValueError, match="cp_mesh"):
            _quant_engine(TINY, prefill_chunk_budget=16,
                          prefix_cache=False, cp_mesh=mesh)

    def test_pp_mesh_rejects_budget(self, cpu_devices):
        from k8s_llm_rca_tpu.runtime.mesh import build_mesh

        mesh = build_mesh(MeshConfig(stage=2), devices=cpu_devices[:2])
        with pytest.raises(ValueError, match="pp_mesh"):
            _quant_engine(TINY, prefill_chunk_budget=16, pp_mesh=mesh)
