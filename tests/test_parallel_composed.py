"""Parallelism-module tests on the virtual 8-device CPU mesh, continued from
tests/test_parallel.py: the COMPOSED meshes (CP x TP, CP over a
sequence-sharded pool, EP x TP x DP, CP x EP) and Megatron sequence
parallelism; every sharded path must match its single-device reference
exactly."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_rca_tpu.config import TINY, TINY_MOE, MeshConfig
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.ops.attention import causal_attention
from k8s_llm_rca_tpu.parallel import (
    expert_parallel_moe, pipeline_apply, ring_attention, ulysses_attention,
)
from k8s_llm_rca_tpu.runtime.mesh import build_mesh


@pytest.fixture(scope="module")
def seq_mesh(cpu_devices):
    return build_mesh(MeshConfig(seq=4), devices=cpu_devices[:4])


def test_cp_tp_requires_one_composed_mesh(cpu_devices):
    """CP×TP composes only on ONE mesh carrying both axes: two distinct
    mesh objects (which would each claim the cache layout) are rejected,
    as is a composed mesh whose head counts don't split over 'model'."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64)
    mesh_a = build_mesh(MeshConfig(data=1, model=2, seq=2),
                        devices=cpu_devices[:4])
    mesh_b = build_mesh(MeshConfig(data=1, model=2, seq=2),
                        devices=cpu_devices[4:8])
    ecfg = EngineConfig(max_batch=2, max_seq_len=64, prefill_buckets=(16,))
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="SAME composed mesh"):
        PagedInferenceEngine(cfg, ecfg, params, get_tokenizer(),
                             cp_mesh=mesh_a, tp_mesh=mesh_b)
    with pytest.raises(ValueError, match="not divisible by model"):
        # n_kv_heads=2 cannot split over model=4
        mesh4 = build_mesh(MeshConfig(data=1, model=4, seq=2),
                           devices=cpu_devices[:8])
        PagedInferenceEngine(cfg, ecfg, params, get_tokenizer(),
                             cp_mesh=mesh4, tp_mesh=mesh4)


@pytest.mark.parametrize("cp_mode,kv_dtype", [
    ("ring", None), ("ulysses", None), ("ring", "int8"), ("ulysses", "int8")])
def test_cp_tp_composed_engine_matches_plain(cpu_devices, cp_mode,
                                             kv_dtype):
    """CP×TP in ONE mesh (SURVEY §7 hard part 6 — the long-context 8B
    shape: TP heads within a node, sequence ring across): the TP-aware
    ring/Ulysses prefill runs per head shard and scatters into the
    seq×model sharded page pool (page axis over 'seq', merged kv over
    'model'; an int8 pool shards its per-token scales the same way);
    decode composes via GSPMD — exact greedy parity with the plain
    engine."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64)
    mesh = build_mesh(MeshConfig(data=2, model=2, seq=2),
                      devices=cpu_devices[:8])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    sharded = shard_pytree(params, llama_param_specs(cfg), mesh)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                        prefill_buckets=(16, 32), max_new_tokens=6,
                        page_size=16, num_pages=32, prefix_cache=False,
                        kv_cache_dtype=kv_dtype, decode_chunk=1)
    prompts = [tok.encode("pod crashloop kube-system", add_bos=True),
               tok.encode("node disk pressure taint", add_bos=True)]

    with jax.default_matmul_precision("float32"):
        ref = PagedInferenceEngine(cfg, ecfg, params, tok).generate(
            prompts, max_new_tokens=6)
        eng = PagedInferenceEngine(cfg, ecfg, sharded, tok, cp_mesh=mesh,
                                   tp_mesh=mesh, cp_mode=cp_mode)
        got = eng.generate(prompts, max_new_tokens=6)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids
    eng.allocator.check()
    # the pool is sharded on BOTH axes: pages over 'seq', kv over 'model'
    shard = eng.pool.k.sharding.shard_shape(eng.pool.k.shape)
    assert shard[1] == ecfg.num_pages // 2
    assert shard[3] == cfg.kv_dim // 2


def test_cp_paged_seq_sharded_pool(cpu_devices):
    """CP seq-sharded paged pool (page-aligned CP splits): each CP device
    owns the page RANGE covering its sequence shard, so the paged engine
    stores 1/P of a long context's KV per device.  Greedy parity with the
    plain engine through decode that GROWS across the partition boundary, plus
    pool-bytes-per-device and allocator-partition assertions."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine.paged import (
        PagedInferenceEngine, PartitionedPageAllocator, TRASH_PAGE,
    )
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=32)
    mesh = build_mesh(MeshConfig(seq=2), devices=cpu_devices[:2])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    # pages_per_seq = 4, partition boundary at page idx 2 (position 16):
    # a 12-token prompt + 12 new tokens crosses into partition 1 mid-decode
    ecfg = EngineConfig(max_batch=2, max_seq_len=32, page_size=8,
                        num_pages=16, prefill_buckets=(16,),
                        max_new_tokens=12, temperature=0.0,
                        prefix_cache=False, decode_chunk=1)
    prompts = [tok.encode("0123456789a", add_bos=True),   # 12 tokens
               tok.encode("pvc not bnd", add_bos=True)]
    assert all(len(p) == 12 for p in prompts)

    with jax.default_matmul_precision("float32"):
        ref = PagedInferenceEngine(cfg, ecfg, params, tok).generate(
            prompts, max_new_tokens=12)
        eng = PagedInferenceEngine(cfg, ecfg, params, tok, cp_mesh=mesh)
        # partition-aware allocation is active
        assert isinstance(eng.allocator, PartitionedPageAllocator)
        got = eng.generate(prompts, max_new_tokens=12)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids
        # every sequence decoded past position 16 (the partition boundary)
        assert r.prompt_tokens + r.completion_tokens > 16
    eng.allocator.check()
    assert eng.allocator.n_free == 15              # nothing leaked

    # 1/P pool bytes per device: page axis sharded over 'seq'
    shard = eng.pool.k.sharding.shard_shape(eng.pool.k.shape)
    assert shard[1] == ecfg.num_pages // 2

    # partition alignment invariant: after a fresh admission, the page
    # covering positions [16, 24) must come from partition 1's id range
    seq = eng.submit(tok.encode("0123456789a", add_bos=True),
                     max_new_tokens=12)
    for _ in range(40):
        if not eng.has_work:
            break
        eng.step()
        for slot, st in eng._active.items():
            table = eng.block_tables[slot]
            for j in range(eng.pages_per_seq):
                if table[j] != TRASH_PAGE:
                    assert eng.allocator.part_of(int(table[j])) == \
                        eng._page_part(j), (j, int(table[j]))
    eng.allocator.check()


@pytest.mark.parametrize("page_size", [8, 16])
def test_cp_speculative_matches_plain(cpu_devices, page_size):
    """Speculation composes with CP: the multi-token verify step runs
    over the seq-sharded page pool through GSPMD, with exact greedy
    parity against the non-speculative non-CP engine."""
    import dataclasses

    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=32)
    mesh = build_mesh(MeshConfig(seq=2), devices=cpu_devices[:2])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    ecfg = EngineConfig(max_batch=2, max_seq_len=32, prefill_buckets=(16,),
                        max_new_tokens=10, temperature=0.0,
                        page_size=page_size, num_pages=128 // page_size,
                        prefix_cache=False)
    prompts = [tok.encode("the pod the pod", add_bos=True),
               tok.encode("pvc bound pvc", add_bos=True)]
    with jax.default_matmul_precision("float32"):
        ref = make_engine(cfg, ecfg, params, tok,
                          use_kernel=False).generate(
            [list(p) for p in prompts], max_new_tokens=10)
        spec = make_engine(cfg, dataclasses.replace(ecfg, speculative_k=3),
                           params, tok, cp_mesh=mesh, use_kernel=False)
        got = spec.generate([list(p) for p in prompts], max_new_tokens=10)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids
    spec.allocator.check()


def test_cp_paged_partition_exhaustion_preempts_not_crashes(cpu_devices):
    """CP seq-sharded pool under PARTITION pressure: when the partition a
    growing slot needs is exhausted, evicting the youngest slot may free
    pages only in OTHER partitions — step() must keep evicting (and
    finally preempt the growing slot itself) instead of crashing on the
    unsatisfied retry (regression: the single-retry grow assumed any
    freed page could satisfy alloc, true only for the unpartitioned
    pool)."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.utils.logging import METRICS
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=32)
    mesh = build_mesh(MeshConfig(seq=2), devices=cpu_devices[:2])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    ecfg = EngineConfig(max_batch=2, max_seq_len=32, page_size=8,
                        num_pages=16, prefill_buckets=(16,),
                        max_new_tokens=12, temperature=0.0,
                        prefix_cache=False, decode_chunk=1)
    eng = PagedInferenceEngine(cfg, ecfg, params, tok, cp_mesh=mesh)
    # exhaust partition 1 (pages 8..15) so crossing position 16 cannot grow
    stolen = eng.allocator.alloc(8, owner=999, part=1)
    prompts = [tok.encode("0123456789a", add_bos=True) for _ in range(2)]
    assert all(len(p) == 12 for p in prompts)
    for p in prompts:
        eng.submit(p, max_new_tokens=12)
    before = METRICS.count("engine.preemptions")
    for _ in range(12):                      # churns, must not raise
        if eng.has_work:
            eng.step()
    assert METRICS.count("engine.preemptions") > before
    eng.allocator.check()
    # free the hostage partition: the sweep completes normally
    eng.allocator.free(stolen, owner=999)
    results = eng.run_to_completion()
    assert len(results) == 2
    eng.allocator.check()
    assert eng.allocator.n_free == 15


def test_ep_tp_dp_composed_engine_matches_dense(cpu_devices):
    """EP x TP x DP in ONE mesh (the v5e-16 Mixtral shape: experts across
    nodes, tensor-parallel heads within, batch replicas on top): the
    stacked expert weights shard over 'expert' AND their hidden dims over
    'model' (llama_param_specs composes both in one spec), the MoE MLPs
    dispatch all-to-all, and greedy output matches the dense single-device
    engine exactly."""
    from k8s_llm_rca_tpu.config import TINY_MOE, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY_MOE.replace(max_seq_len=64, n_experts=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    ecfg = EngineConfig(max_batch=4, max_seq_len=64,
                        prefill_buckets=(16, 32, 64), max_new_tokens=6,
                        temperature=0.0)
    prompts = [tok.encode("pod pending", add_bos=True),
               tok.encode("pvc not bound", add_bos=True),
               tok.encode("secret missing", add_bos=True)]
    ref = make_engine(cfg, ecfg, params, tok).generate(
        prompts, max_new_tokens=6)

    mesh = build_mesh(MeshConfig(data=2, expert=2, model=2),
                      devices=cpu_devices[:8])
    sharded = shard_pytree(params, llama_param_specs(cfg), mesh)
    eng = make_engine(cfg, ecfg, sharded, tok, ep_mesh=mesh)
    got = eng.generate(prompts, max_new_tokens=6)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids


def test_sp_forward_matches_and_shards_sequence(cpu_devices):
    """Megatron-style SP (SURVEY §2.2 SP row): under TP, constraining the
    residual stream's sequence dim over 'model' must not change the
    function, and the lowered module must actually carry the sequence
    sharding constraints (XLA then chooses reduce-scatter/all-gather or
    all-reduce+slice per its cost model — on TPU the former)."""
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )

    cfg = TINY.replace(max_seq_len=64)
    mesh = build_mesh(MeshConfig(data=1, model=4),
                      devices=cpu_devices[:4])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    sharded = shard_pytree(params, llama_param_specs(cfg), mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size)
    with jax.default_matmul_precision("float32"):
        ref = llama.forward(cfg, params, tokens)
        fn = jax.jit(lambda p, t: llama.forward(cfg, p, t, sp_mesh=mesh))
        got = fn(sharded, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=5e-4, atol=5e-4)
        lowered = fn.lower(sharded, tokens).as_text()
    # two constraints per layer on the [B, S, H] residual stream: the
    # seq (middle) dim sharded over the model axis (shardy dialect:
    # `sdy.sharding_constraint ... [{}, {"model"}, {}]`; pre-shardy:
    # `custom_call @Sharding`)
    n_sp = (lowered.count('sdy.sharding_constraint')
            + lowered.count('custom_call @Sharding'))
    assert n_sp >= 2 * cfg.n_layers, \
        f"expected >= {2 * cfg.n_layers} SP sharding constraints, " \
        f"found {n_sp}"
    assert ('[{}, {"model"}, {}]' in lowered
            or "Sharding" in lowered), \
        "no seq-over-model sharding annotation in the lowered module"


def test_sp_engine_matches_unsharded(cpu_devices):
    """sp=True: TP prefill with sequence-parallel activations emits the
    plain engine's greedy tokens."""
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64)
    mesh = build_mesh(MeshConfig(data=2, model=2),
                      devices=cpu_devices[:4])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    sharded = shard_pytree(params, llama_param_specs(cfg), mesh)
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    prompts = [tok.encode("pod crashloop kube-system", add_bos=True),
               tok.encode("node disk pressure taint", add_bos=True)]
    ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                        prefill_buckets=(16, 32), max_new_tokens=6,
                        temperature=0.0, page_size=16, num_pages=32,
                        prefix_cache=False, decode_chunk=1)
    with jax.default_matmul_precision("float32"):
        ref = make_engine(cfg, ecfg, params, tok,
                          use_kernel=False).generate(
            prompts, max_new_tokens=6)
        got = make_engine(cfg, ecfg, sharded, tok, tp_mesh=mesh,
                          sp=True, use_kernel=False).generate(
            prompts, max_new_tokens=6)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids


def test_sp_requires_tp(cpu_devices):
    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=64)
    with pytest.raises(ValueError, match="requires tp_mesh"):
        PagedInferenceEngine(
            cfg, EngineConfig(max_batch=2, max_seq_len=64,
                              prefill_buckets=(16,)),
            llama.init_params(cfg, jax.random.PRNGKey(0)),
            get_tokenizer(vocab_size=cfg.vocab_size), sp=True)


@pytest.mark.parametrize("cp_mode,page_size", [
    ("ring", 8), ("ulysses", 8), ("ring", 16)])
def test_cp_ep_composed_engine_matches_dense(cpu_devices, cp_mode,
                                             page_size):
    """CP×EP in ONE mesh (long-context MoE serving: experts across the
    expert axis, sequence ring over 'seq'): CP prefill shards MoE tokens
    over (seq, expert) — the sequence never moves, dispatch rides the
    expert all-to-all — and writes through the page-scatter path; decode
    tokens shard over (data, expert) against the seq-sharded pool.
    Exact greedy parity vs the dense engine."""
    from k8s_llm_rca_tpu.config import TINY_MOE, EngineConfig
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.models import mixtral
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY_MOE.replace(max_seq_len=64, n_experts=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    ecfg = EngineConfig(max_batch=2, max_seq_len=64, page_size=page_size,
                        num_pages=256 // page_size,
                        prefill_buckets=(16, 32, 64), max_new_tokens=6,
                        temperature=0.0, prefix_cache=False,
                        decode_chunk=1)
    prompts = [tok.encode("pod pending unschedulable node", add_bos=True),
               tok.encode("pvc not bound storageclass", add_bos=True)]

    mesh = mixtral.build_ep_mesh(2, n_data=1, n_seq=2,
                                 devices=cpu_devices[:4])
    sharded = mixtral.shard_params_ep(cfg, params, mesh)
    with jax.default_matmul_precision("float32"):
        ref = PagedInferenceEngine(cfg, ecfg, params, tok,
                                   use_kernel=False).generate(
            prompts, max_new_tokens=6)
        eng = PagedInferenceEngine(cfg, ecfg, sharded, tok, cp_mesh=mesh,
                                   ep_mesh=mesh, cp_mode=cp_mode,
                                   use_kernel=False)
        got = eng.generate(prompts, max_new_tokens=6)
    for r, g in zip(ref, got):
        assert r.token_ids == g.token_ids
    eng.allocator.check()
    # the pool is genuinely page-sharded across the composed mesh
    shard = eng.pool.k.sharding.shard_shape(eng.pool.k.shape)
    assert shard[1] == ecfg.num_pages // 2


def test_cp_ep_requires_one_composed_mesh(cpu_devices):
    """CP×EP composes only on ONE mesh; distinct mesh objects are
    rejected, and prefill buckets must split over seq*expert."""
    from k8s_llm_rca_tpu.config import TINY_MOE, EngineConfig
    from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
    from k8s_llm_rca_tpu.models import mixtral
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY_MOE.replace(max_seq_len=64, n_experts=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    mesh_a = mixtral.build_ep_mesh(2, n_seq=2, devices=cpu_devices[:4])
    mesh_b = mixtral.build_ep_mesh(2, n_seq=2, devices=cpu_devices[4:8])
    ecfg = EngineConfig(max_batch=2, max_seq_len=64, prefill_buckets=(16,))
    with pytest.raises(ValueError, match="SAME composed mesh"):
        PagedInferenceEngine(cfg, ecfg, params, get_tokenizer(),
                             cp_mesh=mesh_a, ep_mesh=mesh_b)
    with pytest.raises(ValueError, match="prefill token sharding"):
        # 18 splits over seq=2 but not over seq*expert=4
        PagedInferenceEngine(
            cfg, EngineConfig(max_batch=2, max_seq_len=64,
                              prefill_buckets=(18, 64)),
            params, get_tokenizer(), cp_mesh=mesh_a, ep_mesh=mesh_a)
