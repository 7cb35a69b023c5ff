"""Overlapped serving hot loop (``EngineConfig.host_overlap``,
docs/performance.md): exact greedy byte-parity against the plain tick
across the engine feature matrix, loud ValueError exclusions, and exact
host<->device traffic counter regressions.

Why counters, not timers: these tests run on the CPU, where a time says
nothing about the chip.  ``engine.h2d_uploads``/``engine.d2h_syncs``/
``engine.dispatches`` are exact event counts of the hot loop, so a
host-loop regression fails these tests loudly with zero timing flake.
"""

import dataclasses

import jax
import pytest

from conftest import tiny_on_a_tp_mesh
from k8s_llm_rca_tpu.config import TINY, EngineConfig, MeshConfig
from k8s_llm_rca_tpu.engine import make_engine
from k8s_llm_rca_tpu.engine.constrain import SchemaGrammar, make_grammar
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.runtime.mesh import build_mesh
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer


@pytest.fixture(scope="module")
def setup():
    cfg = TINY.replace(max_seq_len=128)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    return cfg, params, tok


def _ecfg(**over):
    base = dict(max_batch=4, max_seq_len=128, prefill_buckets=(16, 32, 64),
                max_new_tokens=12, temperature=0.0, decode_chunk=1,
                page_size=16, num_pages=96, prefix_cache=False)
    base.update(over)
    return EngineConfig(**base)


def _prompts(tok):
    return [tok.encode(s, add_bos=True) for s in
            ("secret not found", "configmap missing from pod spec",
             "stale NFS file handle on mount", "incident number 4",
             "exceeded quota: pods=50", "hello")]


def _run(cfg, params, tok, ecfg, prompts, grammars=()):
    """Generate the mixed workload; returns ([token_ids...], counters).
    ``grammars`` entries are (prompt, grammar_factory) appended to the
    plain prompts so constrained and unconstrained slots share ticks."""
    eng = make_engine(cfg, ecfg, params, tok, use_kernel=False)
    ids = [eng.submit(list(p), max_new_tokens=ecfg.max_new_tokens)
           for p in prompts]
    for p, gf in grammars:
        ids.append(eng.submit(list(p), max_new_tokens=ecfg.max_new_tokens,
                              grammar=gf()))
    res = {r.seq_id: r for r in eng.run_to_completion()}
    eng.allocator.check()
    return ([(res[i].token_ids, res[i].finish_reason) for i in ids],
            dict(eng._counts))


# ---------------------------------------------------------------------------
# byte-parity matrix: overlap on vs off must be invisible to every sequence
# ---------------------------------------------------------------------------


class TestOverlapParity:
    @pytest.mark.parametrize("kv_cache_dtype", [None, "int8"])
    @pytest.mark.parametrize("chunk,spec_k", [(1, 0), (8, 0), (1, 3)])
    def test_matrix_matches_plain(self, setup, kv_cache_dtype, chunk,
                                  spec_k):
        """model-dtype + int8 pool × stepwise/scan × n-gram speculation,
        with a DFA grammar slot and an interpreted python-FSM grammar slot
        sharing the batch with plain slots: byte parity, same finish
        reasons."""
        cfg, params, tok = setup
        prompts = _prompts(tok)
        gspec = [(tok.encode("emit json", add_bos=True),
                  lambda: make_grammar("json", tok)),
                 (tok.encode("diagnose:", add_bos=True),
                  lambda: SchemaGrammar({"type": "choice", "options": [
                      "verdict: missing secret",
                      "checked: node pressure"]}, tok))]
        ecfg = _ecfg(kv_cache_dtype=kv_cache_dtype, decode_chunk=chunk,
                     speculative_k=spec_k)
        plain, _ = _run(cfg, params, tok, ecfg, prompts, gspec)
        over, _ = _run(cfg, params, tok,
                       dataclasses.replace(ecfg, host_overlap=True),
                       prompts, gspec)
        assert plain == over

    def test_prefix_cache_hit_and_miss_admissions(self, setup):
        """Paged + prefix cache: the FIRST wave admits as misses, the
        SECOND wave of identical prompts admits through the chunked-hit
        path — both waves byte-identical with overlap on."""
        cfg, params, tok = setup
        prompts = _prompts(tok)[:4]

        def run(overlap):
            ecfg = _ecfg(prefix_cache=True, host_overlap=overlap)
            eng = make_engine(cfg, ecfg, params, tok, use_kernel=False)
            first = eng.generate([list(p) for p in prompts],
                                 max_new_tokens=12)
            second = eng.generate([list(p) for p in prompts],
                                  max_new_tokens=12)
            eng.allocator.check()
            hits = eng._counts.get("engine.prefix_hit_tokens", 0)
            return ([r.token_ids for r in first + second], hits)

        (plain, plain_hits), (over, over_hits) = run(False), run(True)
        assert plain == over
        assert over_hits == plain_hits and over_hits > 0

    @pytest.mark.parametrize("page_size", [16, 8])
    def test_model_draft_matches_plain(self, setup, page_size):
        """Draft-MODEL speculation under overlap: the draft scan's
        blocking token fetch stays accounted and greedy output is byte-
        identical to the non-overlapped speculative engine."""
        cfg, params, tok = setup
        prompts = _prompts(tok)[:3]
        ecfg = _ecfg(page_size=page_size, speculative_k=3, max_batch=2)

        def run(overlap):
            eng = make_engine(
                cfg, dataclasses.replace(ecfg, host_overlap=overlap),
                params, tok, draft_model=(cfg, params), use_kernel=False)
            return [r.token_ids for r in
                    eng.generate([list(p) for p in prompts],
                                 max_new_tokens=12)]

        assert run(False) == run(True)

    @pytest.mark.parametrize("page_size", [16, 4])
    def test_stop_strings_truncate_identically(self, setup, page_size):
        """Stop-string slots ride the lagged commit (post-hoc truncation
        at flush, like the chunked scan): same text, same finish reason,
        no sync fallback required.  At page 4 the lagged ticks cross
        page boundaries, so growth has to cover the device's length."""
        cfg, params, tok = setup
        prompt = tok.encode("hello", add_bos=True)
        ecfg = _ecfg(page_size=page_size)
        free = make_engine(cfg, ecfg, params, tok,
                           use_kernel=False).generate(
            [list(prompt)], max_new_tokens=12)[0]
        stop = free.text[2:5]

        def run(overlap):
            eng = make_engine(
                cfg, dataclasses.replace(ecfg, host_overlap=overlap),
                params, tok, use_kernel=False)
            return eng.generate([list(prompt)], max_new_tokens=12,
                                stop_strings=(stop,))[0]

        a, b = run(False), run(True)
        assert (a.text, a.token_ids, a.finish_reason) == \
            (b.text, b.token_ids, b.finish_reason)
        assert b.finish_reason == "stop" and stop not in b.text

    def test_snapshot_mid_overlap_restores_in_place(self, setup):
        """cancel/snapshot/restore barrier: snapshotting while tokens are
        in flight flushes them first, so the snapshot is a committed-
        prefix view and the restored run finishes byte-identically."""
        cfg, params, tok = setup
        prompts = _prompts(tok)[:2]
        ecfg = _ecfg(host_overlap=True)
        eng = make_engine(cfg, ecfg, params, tok, use_kernel=False)
        want = eng.generate([list(p) for p in prompts], max_new_tokens=12)
        sids = [eng.submit(list(p), max_new_tokens=12) for p in prompts]
        partial = []
        for _ in range(3):
            partial.extend(eng.step())
        snap = eng.snapshot_sequences()
        assert not eng._inflight          # the barrier drained the lag
        for s in snap["sequences"]:
            ref = want[sids.index(s["seq_id"])]
            assert s["generated"] == ref.token_ids[:len(s["generated"])]
        for s in list(snap["sequences"]):
            eng.cancel_seq(s["seq_id"])
        eng.restore_sequences(snap)
        results = list(partial)
        while eng.has_work:
            results.extend(eng.step())
        got = {r.seq_id: r for r in results}
        for sid, ref in zip(sids, want):
            assert got[sid].token_ids == ref.token_ids
        eng.allocator.check()


# ---------------------------------------------------------------------------
# composed meshes (GSPMD over virtual CPU is ~10x slower: marked slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_tp_sharded_overlap_matches_plain(setup, cpu_devices):
    """Serving TP under overlap: TP-sharded params, overlap on vs off,
    byte-identical greedy tokens."""
    from k8s_llm_rca_tpu.runtime.sharding import (
        llama_param_specs, shard_pytree,
    )

    cfg, params, tok = setup
    mesh = build_mesh(MeshConfig(data=2, model=2), devices=cpu_devices[:4])
    sharded = shard_pytree(params, llama_param_specs(cfg), mesh)
    prompts = _prompts(tok)[:3]
    ecfg = _ecfg(max_batch=2, max_new_tokens=6)
    with jax.default_matmul_precision("float32"):
        plain = make_engine(cfg, ecfg, sharded, tok,
                            use_kernel=False).generate(
            [list(p) for p in prompts], max_new_tokens=6)
        over = make_engine(
            cfg, dataclasses.replace(ecfg, host_overlap=True),
            sharded, tok, use_kernel=False).generate(
            [list(p) for p in prompts], max_new_tokens=6)
    for r, g in zip(plain, over):
        assert r.token_ids == g.token_ids


@pytest.mark.slow
def test_pp_tp_overlap_matches_plain(setup, cpu_devices):
    """PP×TP in one mesh under overlap (the multi-host pod serving
    shape): the fused overlap step routes through the stage-local
    pp_decode_fn and must keep exact greedy parity."""
    _, _, tok = setup
    cfg = TINY.replace(max_seq_len=128, n_layers=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    mesh = build_mesh(MeshConfig(stage=2, model=2), devices=cpu_devices[:4])
    prompts = _prompts(tok)[:3]
    ecfg = _ecfg(max_batch=2, max_new_tokens=6)
    with jax.default_matmul_precision("float32"):
        plain = make_engine(cfg, ecfg, params, tok, pp_mesh=mesh,
                            tp_mesh=mesh).generate(
            [list(p) for p in prompts], max_new_tokens=6)
        over = make_engine(
            cfg, dataclasses.replace(ecfg, host_overlap=True),
            params, tok, pp_mesh=mesh, tp_mesh=mesh).generate(
            [list(p) for p in prompts], max_new_tokens=6)
    for r, g in zip(plain, over):
        assert r.token_ids == g.token_ids


def test_tp_mesh_overlap_matches_plain_with_resident_state(cpu_devices):
    """The overlapped loop on a mesh (moved here from the dryrun at PR 50;
    its two neighbours above are ``slow``): the same GSPMD paged TP engine
    on dp2 x tp4 ticked stepwise with ``host_overlap`` off and on emits
    the same greedy tokens, while the overlapped run's counters show
    device-resident state (one dirty upload, no steady-state re-uploads)
    and at most half the d2h sync points."""
    cfg, params, tok, mesh = tiny_on_a_tp_mesh(cpu_devices, 21)
    prompts = [tok.encode(s, add_bos=True) for s in
               ("oom killed in payments", "dns resolution flaking",
                "pvc stuck terminating")]

    def run(overlap):
        ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                            prefill_buckets=(16, 32), max_new_tokens=8,
                            page_size=8, num_pages=32, decode_chunk=1,
                            host_overlap=overlap)
        eng = make_engine(cfg, ecfg, params, tok, tp_mesh=mesh,
                          use_kernel=False)
        with jax.default_matmul_precision("float32"):
            res = eng.generate(prompts, max_new_tokens=8)
        eng.allocator.check()
        return [r.token_ids for r in res], dict(eng._counts)

    plain, plain_counts = run(False)
    over, over_counts = run(True)
    assert plain == over
    assert over_counts["engine.h2d_uploads"] == 3    # one dirty upload
    assert over_counts["engine.h2d_uploads"] < plain_counts[
        "engine.h2d_uploads"]
    assert 2 * over_counts["engine.d2h_syncs"] <= plain_counts[
        "engine.d2h_syncs"]


def test_cp_composition_rejected_loudly(setup, cpu_devices):
    """host_overlap × CP is excluded: CP's multi-process host_np
    collectives must line up SPMD-identically, which a lagged commit
    would reorder — the engine refuses at construction."""
    cfg, params, tok = setup
    mesh = build_mesh(MeshConfig(seq=4), devices=cpu_devices[:4])
    with pytest.raises(ValueError, match="host_overlap"):
        make_engine(cfg, _ecfg(host_overlap=True), params, tok,
                    cp_mesh=mesh, use_kernel=False)


# ---------------------------------------------------------------------------
# exact-count regressions (the perf marker suite): h2d / d2h / dispatches
# ---------------------------------------------------------------------------


@pytest.mark.perf
class TestHostTrafficCounters:
    """Fixed scripted workload, exact counter assertions.  The plain
    stepwise tick re-uploads all three arrays (3 h2d) and blocks on
    one fetch per tick; overlap must hold h2d at the single initial
    upload and at least halve the sync points for the same tokens."""

    def _counts(self, setup, overlap, **over):
        """4 identical same-bucket prompts into 4 slots: exactly ONE
        batched prefill dispatch, all retirements on the same tick — the
        counter arithmetic below is exact, not approximate."""
        cfg, params, tok = setup
        prompts = [tok.encode("pod crashloop", add_bos=True)] * 4
        _, counts = _run(cfg, params, tok,
                         _ecfg(host_overlap=overlap, **over), prompts)
        for k in ("engine.h2d_uploads", "engine.d2h_syncs",
                  "engine.dispatches", "engine.decode_tokens"):
            counts.setdefault(k, 0.0)
        return counts

    def test_paged_exact_counts(self, setup):
        pc = self._counts(setup, False)
        oc = self._counts(setup, True)
        # same committed work either way
        assert oc["engine.decode_tokens"] == pc["engine.decode_tokens"] > 0
        # plain stepwise: with D decode dispatches after the single
        # prefill, every decode tick blocks on one fetch (D), plus ONE
        # coalesced drain of the deferred admission firsts — and re-
        # uploads all three arrays (3 h2d) per decode tick
        d_plain = pc["engine.dispatches"] - 1
        assert pc["engine.d2h_syncs"] == d_plain + 1
        assert pc["engine.h2d_uploads"] == 3 * d_plain
        # overlap: exactly ONE dirty materialisation of the three arrays
        # (zero steady-state per-tick h2d), and one coalesced fetch per
        # lag-2 flush — exactly half the dispatches
        d_over = oc["engine.dispatches"] - 1
        assert oc["engine.h2d_uploads"] == 3
        assert 2 * oc["engine.d2h_syncs"] == d_over
        # the acceptance ratio: >= 2x fewer sync points per decoded token
        assert 2 * oc["engine.d2h_syncs"] <= pc["engine.d2h_syncs"], (
            oc, pc)

    def test_scan_exact_counts(self, setup):
        """The chunked scan: the plain tick uploads the three arrays
        before every scan, overlap once (the scan's own outputs stay
        resident); both block on one fetch a scan, and the coalesced
        drain of the admission's first tokens."""
        pc = self._counts(setup, False, decode_chunk=8)
        oc = self._counts(setup, True, decode_chunk=8)
        assert oc["engine.decode_tokens"] == pc["engine.decode_tokens"] > 0
        scans = pc["engine.dispatches"] - 1
        assert oc["engine.dispatches"] == pc["engine.dispatches"]
        assert pc["engine.h2d_uploads"] == 3 * scans
        assert oc["engine.h2d_uploads"] == 3
        assert pc["engine.d2h_syncs"] == oc["engine.d2h_syncs"] == scans + 1

    def test_paged_steady_state_has_zero_h2d(self, setup):
        """Direct steady-state proof: once the resident state is
        materialised, further fast ticks dispatch without ANY h2d upload
        of cur_tokens/lengths/block_tables."""
        cfg, params, tok = setup
        eng = make_engine(cfg, _ecfg(host_overlap=True), params,
                          tok, use_kernel=False)
        eng.submit(list(_prompts(tok)[0]), max_new_tokens=12)
        for _ in range(3):                 # admission + state upload
            eng.step()
        h2d0 = eng._counts.get("engine.h2d_uploads", 0)
        disp0 = eng._counts.get("engine.dispatches", 0)
        for _ in range(3):
            eng.step()
        assert eng._counts["engine.dispatches"] > disp0
        assert eng._counts.get("engine.h2d_uploads", 0) == h2d0

    def test_plain_admission_coalesces_first_token_fetch(self, setup):
        """Satellite of the deferred-admission rework: even with
        host_overlap OFF, admission first tokens defer to ONE coalesced
        drain fetch per tick — two admission waves (different buckets)
        in one tick cost one sync, not two."""
        cfg, params, tok = setup
        eng = make_engine(cfg, _ecfg(), params, tok, use_kernel=False)
        eng.submit(tok.encode("short", add_bos=True), max_new_tokens=4)
        eng.submit(tok.encode(
            "a much longer prompt that lands in the next prefill bucket "
            "by repeating repeating repeating", add_bos=True),
            max_new_tokens=4)
        d2h0 = (eng._counts or {}).get("engine.d2h_syncs", 0)
        eng.step()                         # both admission waves
        prefills = eng._counts.get("engine.dispatches", 0)
        assert prefills >= 2               # two separate prefill buckets
        assert eng._counts.get("engine.d2h_syncs", 0) - d2h0 <= 2
        list(eng.run_to_completion())
        eng.allocator.check()
