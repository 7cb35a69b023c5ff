"""Profiling/observability tests (CPU: MFU math, timers, memory stats
shape; trace capture is exercised for the no-crash property only)."""

import os

import pytest

from k8s_llm_rca_tpu.config import LLAMA3_8B, MIXTRAL_8X7B, TINY
from k8s_llm_rca_tpu.runtime import profiling


class TestFlopsModel:
    def test_param_count_llama3_8b(self):
        # public number: ~8.03B parameters
        n = profiling.decoder_param_count(LLAMA3_8B)
        assert 7.9e9 < n < 8.2e9, n

    def test_param_count_mixtral(self):
        # public number: ~46.7B total parameters
        n = profiling.decoder_param_count(MIXTRAL_8X7B)
        assert 45e9 < n < 48e9, n

    def test_decode_flops_scale_with_context(self):
        f1 = profiling.decode_flops_per_token(TINY, 128)
        f2 = profiling.decode_flops_per_token(TINY, 1024)
        assert f2 > f1
        # dense ~2*params FLOPs/token dominates at short context
        params = profiling.decoder_param_count(TINY)
        assert f1 == pytest.approx(2 * params, rel=0.35)

    def test_moe_flops_count_topk_not_all_experts(self):
        dense_equiv = MIXTRAL_8X7B.replace(n_experts=0)
        moe = profiling.decode_flops_per_token(MIXTRAL_8X7B, 128)
        dense = profiling.decode_flops_per_token(dense_equiv, 128)
        # top-2 of 8 experts ~= 2x one dense MLP, not 8x
        assert moe < 3 * dense

    def test_mfu_none_on_cpu(self):
        assert profiling.mfu(TINY, 1000.0, 128) is None  # tests run on CPU


class _FakeV5e:
    platform = "tpu"
    device_kind = "TPU v5 lite"


class _FakeV5p:
    platform = "tpu"
    device_kind = "TPU v5"


class TestRoofline:
    def test_none_on_cpu(self):
        assert profiling.roofline_decode_tps(TINY, 128, 8) is None

    def test_kind_lookup_is_exact(self):
        # a kind that merely shares a prefix with a table row ("TPU v5" vs
        # "TPU v5 lite") must not borrow that row's peaks
        assert profiling.chip_peaks(_FakeV5e()) == (197.0, 819.0)
        with pytest.raises(ValueError, match="TPU v5'"):
            profiling.roofline_decode_tps(TINY, 128, 8, device=_FakeV5p())

    def test_memory_bound_at_small_batch(self):
        # batch 1 streams ~the full weights per token (layer matmuls plus
        # ONE vocab table — the untied input embedding is a gather, not a
        # stream, so bytes land slightly under 2*param_count bf16 bytes)
        bpt = profiling.decode_bytes_per_token(LLAMA3_8B, 128, 1, 16, 16)
        full = profiling.decoder_param_count(LLAMA3_8B) * 2
        assert 0.8 * full < bpt < 1.02 * full

    def test_batch_amortizes_weight_traffic(self):
        b1 = profiling.decode_bytes_per_token(TINY, 128, 1, 16, 16)
        b64 = profiling.decode_bytes_per_token(TINY, 128, 64, 16, 16)
        assert b64 < b1 / 8            # weights dominate at short context

    def test_quantization_raises_roofline(self):
        bf16 = profiling.roofline_decode_tps(TINY, 896, 512, 16, 16,
                                             device=_FakeV5e())
        int4 = profiling.roofline_decode_tps(TINY, 896, 512, 4, 4,
                                             device=_FakeV5e())
        # int4 shrinks bytes; at batch 512 the compute leg caps both, so
        # int4 is >= bf16 but cannot exceed the compute ceiling
        compute = 197e12 / profiling.decode_flops_per_token(TINY, 896)
        assert bf16 <= int4 <= compute * 1.001

    def test_bench_config_roofline_is_finite_and_physical(self):
        # the r2 bench wall-clock (208k tok/s TinyLlama int4) must cap
        from k8s_llm_rca_tpu.config import MODEL_REGISTRY

        cfg = MODEL_REGISTRY["tinyllama-1.1b"]
        roof = profiling.roofline_decode_tps(cfg, 896, 512, 4, 4,
                                             device=_FakeV5e())
        assert 10_000 < roof < 208_000, roof


class TestTraceAndMemory:
    def test_memory_stats_shape(self):
        stats = profiling.device_memory_stats()
        assert isinstance(stats, dict)
        for v in stats.values():
            assert isinstance(v, float)

    def test_trace_capture_writes_files(self, tmp_path):
        import jax
        import jax.numpy as jnp

        d = str(tmp_path / "trace")
        with profiling.trace(d):
            with profiling.annotate("test.region"):
                (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
        # plugins/profile/<ts>/*.xplane.pb must exist
        found = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs]
        assert any(f.endswith(".xplane.pb") for f in found), found


class TestStageLocalCpVsTp:
    def test_tp_dominates_cp_below_the_gqa_limit(self):
        """The PP×CP exclusion's quantitative basis (docs/parallelism.md
        "a quantified no"): for n_intra <= n_kv_heads, spending a
        pipeline stage's intra-stage devices on TP beats CP on BOTH
        per-device decode FLOPs and HBM bytes at every context length —
        CP divides only the attention/KV terms while TP divides the
        matmul/weight terms too."""
        from k8s_llm_rca_tpu.config import LLAMA3_8B, TINYLLAMA_1B

        for cfg in (LLAMA3_8B, TINYLLAMA_1B):
            for n_intra in (2, 4, 8):
                if n_intra > cfg.n_kv_heads:
                    continue
                for s in (1024, 4096, 32768, 131072):
                    r = profiling.stage_local_cp_vs_tp(
                        cfg, s, batch=16, n_intra=n_intra,
                        weight_bits=4, kv_bits=4)
                    assert r["flops_cp_over_tp"] > 1.0, (cfg.name, s)
                    assert r["bytes_cp_over_tp"] > 1.0, (cfg.name, s)

    def test_cp_wins_kv_bytes_past_the_gqa_limit(self):
        """The model is honest about CP's genuine regime: past the GQA
        limit (n_intra > n_kv_heads) at long context, TP's KV stream
        replicates across the devices sharing a kv head while CP keeps
        dividing it — so CP wins on HBM bytes there (the case served by
        the non-PP CP×TP composition, docs/parallelism.md)."""
        from k8s_llm_rca_tpu.config import TINYLLAMA_1B

        assert TINYLLAMA_1B.n_kv_heads == 4
        r = profiling.stage_local_cp_vs_tp(TINYLLAMA_1B, 131072, batch=16,
                                           n_intra=8)
        assert r["bytes_cp_over_tp"] < 1.0, r
        # ... while matmul-replication still costs CP the FLOP axis
        assert r["flops_cp_over_tp"] > 1.0, r

    def test_ratio_shrinks_with_context_but_never_crosses(self):
        """CP's relative loss shrinks as attention dominates (its only
        asymptotic argument) yet stays >1 even at 1M tokens — the
        crossover never happens because weights are still streamed per
        seq shard."""
        from k8s_llm_rca_tpu.config import LLAMA3_8B

        prev = None
        for s in (4096, 65536, 1048576):
            r = profiling.stage_local_cp_vs_tp(LLAMA3_8B, s, batch=16,
                                               n_intra=4)
            if prev is not None:
                assert r["flops_cp_over_tp"] < prev
            assert r["flops_cp_over_tp"] > 1.0
            prev = r["flops_cp_over_tp"]
