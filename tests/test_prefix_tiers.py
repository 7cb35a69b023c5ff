"""Tiered prefix/KV cache (docs/performance.md "tiered prefix cache"):
HBM (L0) -> host-RAM PrefixStore (L1) -> disk (L2), with cross-replica
warm-start.

The headline invariant is BYTE PARITY: a prompt served from promoted
L1/L2 pages must produce exactly the tokens a cold re-prefill produces —
the promoted page holds the same KV bytes eviction demoted, so the
already-trusted prefix-hit prefill path computes the identical suffix.
Greedy decode makes this checkable without tolerance (temperature=0
argmax depends only on weights and committed KV; same rationale as
tests/test_overload.py).  The matrix composes the tiers with
host_overlap x prefill_chunk_budget x max_spilled_pages, and the
disk-robustness tests prove a torn/corrupt L2 entry is a silent cold
miss, never a crash.

Everything runs on the 8-virtual-device CPU platform the conftest pins;
engines are single-device (the ~10x GSPMD-on-virtual-CPU slowdown makes
sharded engines too slow for a parity matrix — the cluster warm-start
test uses one-device submeshes for the same reason).
"""

import os

import jax
import pytest

from conftest import tiny_on_a_tp_mesh
from k8s_llm_rca_tpu.config import TINY, EngineConfig, MeshConfig
from k8s_llm_rca_tpu.engine import make_engine
from k8s_llm_rca_tpu.engine.prefix import PrefixStore
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.utils.logging import METRICS
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

pytestmark = pytest.mark.prefix


@pytest.fixture(scope="module")
def setup():
    cfg = TINY.replace(max_seq_len=128)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    return cfg, params, tok


# the RCA-agent shape: one long shared preamble, short per-run suffixes
# (byte-level tokenizer: ~1 token/char; 75-token preamble = 4 full pages
# at page_size=16, and every prompt fits max_seq_len - max_new_tokens)
_PRE = "shared incident preamble " * 3
PROMPTS = (_PRE + "kubelet crashloop on node-7",
           _PRE + "etcd leader lost quorum",
           _PRE + "pvc unbound on nfs chain")


def _ecfg(**over):
    base = dict(max_batch=2, max_seq_len=128, prefill_buckets=(64, 128),
                max_new_tokens=16, temperature=0.0,
                page_size=16, num_pages=40, prefix_cache=True,
                decode_chunk=4)
    base.update(over)
    return EngineConfig(**base)


def _drive(eng, sids):
    out = {}
    while eng.has_work:
        for r in eng.step():
            out[r.seq_id] = r
    eng.allocator.check()
    resident = eng.prefix_cache.n_resident if eng.prefix_cache else 0
    assert (eng.allocator.n_free + resident
            == eng.engine_cfg.num_pages - 1)
    return [out[s].token_ids for s in sids]


def _run(eng, tok, prompts=PROMPTS):
    return _drive(eng, [eng.submit(tok.encode(p)) for p in prompts])


# ---------------------------------------------------------------------------
# tentpole: tiered parity matrix (cold vs L0 vs L1 vs L2 vs legacy)
# ---------------------------------------------------------------------------


class TestTieredParity:
    # tier shape x engine features; "disk" swaps in a tmp_path L2 dir
    MATRIX = {
        "l1": dict(prefix_host_pages=64),
        "l1_small": dict(prefix_host_pages=4),      # L1 overflow drops (no L2)
        "l2_only": dict(prefix_host_pages=0, disk=True),
        "l1_l2": dict(prefix_host_pages=4, disk=True),
        "l1_overlap": dict(prefix_host_pages=64, decode_chunk=1,
                           host_overlap=True),
        "l1_chunked": dict(prefix_host_pages=64, prefill_chunk_budget=32),
        "l1_spill": dict(prefix_host_pages=64, max_spilled_pages=64),
        "l1_all": dict(prefix_host_pages=64, decode_chunk=1,
                       host_overlap=True, prefill_chunk_budget=32,
                       max_spilled_pages=64),
    }

    @pytest.mark.parametrize("feature", sorted(MATRIX))
    def test_demote_promote_byte_parity(self, setup, tmp_path, feature):
        """Run shared-preamble prompts cold, demote EVERY resident page
        (evict with a store attached), re-run: outputs must be
        byte-identical to a legacy (discarding) engine's, and the tier
        counters must prove pages actually moved d2h and back."""
        cfg, params, tok = setup
        kw = dict(self.MATRIX[feature])
        if kw.pop("disk", False):
            kw["prefix_disk_dir"] = str(tmp_path / "l2")
        feature_kw = {k: v for k, v in kw.items()
                      if not k.startswith("prefix_")}

        legacy = make_engine(cfg, _ecfg(**feature_kw), params, tok,
                             use_kernel=False)
        cold = _run(legacy, tok)
        assert legacy.prefix_cache.evict(10 ** 6) > 0   # legacy discard
        assert _run(legacy, tok) == cold                # re-prefill parity

        eng = make_engine(cfg, _ecfg(**kw), params, tok, use_kernel=False)
        assert _run(eng, tok) == cold                   # tiers off hot path
        assert eng.prefix_cache.evict(10 ** 6) > 0      # demote everything
        assert _run(eng, tok) == cold                   # promoted parity
        c = eng._counts or {}
        assert c.get("engine.prefix_demotions", 0) > 0
        hits = (c.get("engine.prefix_hits_l1", 0)
                + c.get("engine.prefix_hits_l2", 0))
        if feature == "l1_small":
            # the 4-page L1 (no disk) dropped most demoted pages; hits
            # depend on whether the chain HEADS survived the LRU, so only
            # parity is guaranteed here — the dropped-page path IS the test
            return
        assert hits > 0, c
        assert c.get("engine.prefix_promoted_pages", 0) == hits
        assert c.get("engine.prefix_bytes_restored", 0) > 0

    def test_demote_promote_on_a_tp_mesh(self, cpu_devices):
        """The tiers on a mesh (moved here from the dryrun at PR 50; the
        matrix above is single-device): a GSPMD paged TP engine on
        dp2 x tp4 with a host store demotes every resident prefix page
        d2h at eviction and a warm re-run promotes them back by h2d page
        writes, tokens unchanged."""
        cfg, params, tok, mesh = tiny_on_a_tp_mesh(cpu_devices, 37)
        # 16 shared tokens = 2 full pages
        prompts = ["shared preamble " + s for s in ("node oom", "dns fail")]
        eng = make_engine(
            cfg, _ecfg(max_seq_len=64, prefill_buckets=(32, 64),
                       max_new_tokens=6, page_size=8, num_pages=32,
                       decode_chunk=1, prefix_host_pages=32),
            params, tok, tp_mesh=mesh, use_kernel=False)

        def run():
            with jax.default_matmul_precision("float32"):
                return _drive(eng, [eng.submit(tok.encode(p, add_bos=True))
                                    for p in prompts])

        cold = run()
        assert eng.prefix_cache.evict(10 ** 6) > 0
        assert run() == cold
        assert eng._counts.get("engine.prefix_demotions", 0) > 0
        assert eng._counts.get("engine.prefix_hits_l1", 0) > 0

    def test_l2_hits_after_l1_overflow(self, setup, tmp_path):
        """With a tiny L1, demotion overflows the early-chain pages to
        disk; a full re-run must still promote every page byte-
        identically.  The chain walk runs head->tail and each disk hit
        re-admits into the 2-page L1 (churning the old residents back
        out), so the hits legitimately read as L2 — the assertion is
        that the DISK tier carried the promotion, with nothing lost."""
        cfg, params, tok = setup
        eng = make_engine(
            cfg, _ecfg(prefix_host_pages=2,
                       prefix_disk_dir=str(tmp_path / "l2")),
            params, tok, use_kernel=False)
        cold = _run(eng, tok)
        eng.prefix_cache.evict(10 ** 6)
        assert eng.prefix_store.n_host == 2
        assert eng.prefix_store.n_disk > 0
        assert _run(eng, tok) == cold
        c = eng._counts or {}
        assert c.get("engine.prefix_hits_l2", 0) > 0
        hits = (c.get("engine.prefix_hits_l1", 0)
                + c.get("engine.prefix_hits_l2", 0))
        assert c.get("engine.prefix_promoted_pages", 0) == hits > 0

    def test_promotion_skipped_under_page_pressure(self, setup):
        """Promotion allocates WITHOUT evicting: when the pool is too
        full to host promoted pages the match quietly degrades to a
        cold re-prefill — never an error, still byte-identical."""
        cfg, params, tok = setup
        # pages_per_seq = 8, num_pages 9 = one sequence + trash: admission
        # drains the pool completely, so promotion can never allocate
        ecfg = _ecfg(max_batch=1, num_pages=9, prefix_host_pages=64)
        eng = make_engine(cfg, ecfg, params, tok, use_kernel=False)
        legacy = make_engine(
            cfg, _ecfg(max_batch=1, num_pages=9), params, tok,
            use_kernel=False)
        cold = _run(legacy, tok)
        assert _run(eng, tok) == cold
        eng.prefix_cache.evict(10 ** 6)
        assert _run(eng, tok) == cold
        eng.allocator.check()


# ---------------------------------------------------------------------------
# disk tier robustness: torn/corrupt entries are silent cold misses
# ---------------------------------------------------------------------------


class TestDiskRobustness:
    def _populated_dir(self, setup, tmp_path):
        cfg, params, tok = setup
        d = str(tmp_path / "l2")
        eng = make_engine(cfg, _ecfg(prefix_host_pages=0,
                                     prefix_disk_dir=d),
                          params, tok, use_kernel=False)
        cold = _run(eng, tok)
        eng.prefix_cache.evict(10 ** 6)
        entries = sorted(f for f in os.listdir(d) if f.endswith(".page"))
        assert entries
        return cfg, params, tok, d, cold, entries

    def test_corrupt_and_torn_entries_fall_back_cold(self, setup,
                                                     tmp_path):
        """Flip bytes in one entry, truncate another mid-frame: a fresh
        store re-indexes all of them, the CRC/torn-frame checks reject
        the damaged two at load, and the run still matches the cold
        output byte-for-byte (damaged pages simply re-prefill)."""
        cfg, params, tok, d, cold, entries = self._populated_dir(
            setup, tmp_path)
        with open(os.path.join(d, entries[0]), "r+b") as f:
            f.seek(12)
            f.write(b"\xa5\x5a\xa5\x5a")
        size = os.path.getsize(os.path.join(d, entries[1]))
        with open(os.path.join(d, entries[1]), "r+b") as f:
            f.truncate(size // 2)
        eng = make_engine(cfg, _ecfg(), params, tok, use_kernel=False,
                          prefix_store=PrefixStore(disk_dir=d))
        assert _run(eng, tok) == cold
        # damaged entries are dropped lazily (on first touch), never
        # crash the index; whatever the chain walk reached stays <= all
        assert eng.prefix_store.n_disk <= len(entries)

    def test_restart_reindexes_and_serves_l2(self, setup, tmp_path):
        """A brand-new PrefixStore pointed at the surviving directory
        (process restart) serves the same bytes from disk."""
        cfg, params, tok, d, cold, entries = self._populated_dir(
            setup, tmp_path)
        store = PrefixStore(host_pages=0, disk_dir=d)
        assert store.n_disk == len(entries)
        eng = make_engine(cfg, _ecfg(), params, tok, use_kernel=False,
                          prefix_store=store)
        assert _run(eng, tok) == cold
        assert (eng._counts or {}).get("engine.prefix_hits_l2", 0) > 0

    def test_foreign_files_ignored(self, tmp_path):
        d = str(tmp_path / "l2")
        os.makedirs(d)
        for name in ("notes.txt", "zzzz.page"):    # zzzz: non-hex digest
            with open(os.path.join(d, name), "w") as f:
                f.write("not a page record")
        assert PrefixStore(disk_dir=d).n_disk == 0

    def test_disk_cap_drops_oldest(self, setup, tmp_path):
        cfg, params, tok = setup
        d = str(tmp_path / "l2")
        eng = make_engine(cfg, _ecfg(prefix_host_pages=0,
                                     prefix_disk_dir=d,
                                     prefix_disk_pages=3),
                          params, tok, use_kernel=False)
        cold = _run(eng, tok)
        demoted = eng.prefix_cache.evict(10 ** 6)
        assert demoted > 3
        assert eng.prefix_store.n_disk == 3
        assert len([f for f in os.listdir(d) if f.endswith(".page")]) == 3
        # capped tier still serves what it kept; the rest re-prefills
        assert _run(eng, tok) == cold


# ---------------------------------------------------------------------------
# budget separation: store caps never interact with the spill budget
# ---------------------------------------------------------------------------


class TestBudgetSeparation:
    def test_demotions_do_not_consume_spill_budget(self, setup):
        """A store holding far more pages than max_spilled_pages must
        not trip the spill budget: demoted PREFIX pages are accounted by
        prefix_host_pages only, and _spilled_pages_total tracks spilled
        RUN pages only."""
        cfg, params, tok = setup
        eng = make_engine(cfg, _ecfg(prefix_host_pages=64,
                                     max_spilled_pages=2),
                          params, tok, use_kernel=False)
        _run(eng, tok)
        demoted = eng.prefix_cache.evict(10 ** 6)
        assert demoted > 2                       # exceeds the spill cap
        assert eng.prefix_store.n_host == demoted
        assert eng._spilled_pages_total == 0
        c = eng._counts or {}
        assert c.get("engine.spill_budget_fallbacks", 0) == 0
        assert c.get("engine.spilled_pages", 0) == 0

    def test_spill_parity_with_full_store(self, setup):
        """Forced preemption with spill enabled while the tiers are
        configured: the spill path still runs (its budget untouched by
        the store knobs) and outputs stay byte-identical to the
        re-prefill-fallback run."""
        cfg, params, tok = setup

        def _forced(ecfg):
            eng = make_engine(cfg, ecfg, params, tok, use_kernel=False)
            sids = [eng.submit(tok.encode(p), priority=pri)
                    for p, pri in zip(PROMPTS, (1, 2, 0))]
            out, tick = {}, 0
            while eng.has_work:
                if tick == 2:
                    assert eng._preempt_victim()
                for r in eng.step():
                    out[r.seq_id] = r
                tick += 1
            eng.allocator.check()
            return [out[s].token_ids for s in sids], dict(eng._counts or {})

        base, _ = _forced(_ecfg(max_spilled_pages=0))
        tiered, c = _forced(_ecfg(max_spilled_pages=64,
                                  prefix_host_pages=64))
        assert base == tiered
        assert c.get("engine.spilled_pages", 0) > 0
        assert c.get("engine.spill_budget_fallbacks", 0) == 0


# ---------------------------------------------------------------------------
# cross-replica warm-start (cluster/replica.py prefix_store=...)
# ---------------------------------------------------------------------------


class TestWarmStart:
    def test_shared_store_warm_starts_fresh_replica(self, setup,
                                                    cpu_devices):
        """Replica 0 serves a shared-preamble wave and flushes its
        resident pages; a FRESH replica sharing the store must emit
        byte-identical tokens while provably prefilling less (fewer
        engine.prefill dispatches, fewer prefill tokens, L1 hits > 0)."""
        from k8s_llm_rca_tpu.cluster.replica import build_replicas

        cfg, params, tok = setup
        store = PrefixStore(host_pages=256)
        # chunked prefill makes "dispatches saved" a robust signal: the
        # number of engine.prefill spans scales with prefilled TOKENS
        # (ceil(len/budget) chunks per admission), so promoted pages
        # provably remove whole chunks, not just shrink one bucket
        replicas = build_replicas(cfg, _ecfg(prefill_chunk_budget=32), 2,
                                  devices=cpu_devices[:2],
                                  prefix_store=store, use_kernel=False)
        eng0 = replicas[0].backend.engine
        eng1 = replicas[1].backend.engine
        assert eng0.prefix_store is store and eng1.prefix_store is store

        def _prefills(fn):
            # prefill dispatches, a chunk's among them
            def n():
                return METRICS.snapshot().get("engine.prefill.count", 0)

            before = n()
            out = fn()
            return out, n() - before

        cold, cold_prefills = _prefills(lambda: _run(eng0, tok))
        assert eng0.flush_prefix_store() > 0
        warm, warm_prefills = _prefills(lambda: _run(eng1, tok))
        assert warm == cold                      # byte-identical reports
        assert warm_prefills < cold_prefills     # dispatches actually saved
        c1 = eng1._counts or {}
        assert c1.get("engine.prefix_hits_l1", 0) > 0
        assert (c1.get("engine.prefill_tokens", 0)
                < (eng0._counts or {}).get("engine.prefill_tokens", 1))

    def test_supervisor_restart_inherits_store(self, setup, cpu_devices):
        """The rebuild recipe build_replicas records threads the SHARED
        store through engine_kw, so a supervisor-restarted incarnation
        warm-starts too (PR 9 restart path)."""
        from k8s_llm_rca_tpu.cluster.replica import build_replicas

        cfg, params, tok = setup
        store = PrefixStore(host_pages=256)
        (replica,) = build_replicas(cfg, _ecfg(), 1,
                                    devices=cpu_devices[:1],
                                    prefix_store=store, use_kernel=False)
        cold = _run(replica.backend.engine, tok)
        assert replica.backend.engine.flush_prefix_store() > 0
        rebuilt = replica.rebuild()
        assert rebuilt.engine.prefix_store is store
        assert _run(rebuilt.engine, tok) == cold
        assert (rebuilt.engine._counts or {}).get(
            "engine.prefix_hits_l1", 0) > 0

    def test_seeded_wave_warm_start_sweep(self, setup):
        """Scaled-down acceptance sweep (the 100-incident version runs in
        bench_prefix_leg): a seeded wave of shared-preamble incidents on
        a warm-started engine is byte-identical to the cold run with
        counter-proven prefill reduction."""
        import random

        cfg, params, tok = setup
        rng = random.Random(0)
        causes = ("oom", "dns", "quota", "netpol", "pv chain", "kubelet")
        wave = [_PRE + f"incident {i}: {rng.choice(causes)}"
                for i in range(6)]

        cold_eng = make_engine(cfg, _ecfg(), params, tok,
                               use_kernel=False)
        cold = _run(cold_eng, tok, wave)

        store = PrefixStore(host_pages=256)
        src = make_engine(cfg, _ecfg(), params, tok, use_kernel=False,
                          prefix_store=store)
        _run(src, tok, wave[:2])
        assert src.flush_prefix_store() > 0
        warm_eng = make_engine(cfg, _ecfg(), params, tok,
                               use_kernel=False, prefix_store=store)
        assert _run(warm_eng, tok, wave) == cold
        cw, cc = warm_eng._counts or {}, cold_eng._counts or {}
        assert cw.get("engine.prefix_hits_l1", 0) > 0
        assert (cw.get("engine.prefill_tokens", 0)
                < cc.get("engine.prefill_tokens", 0))


# ---------------------------------------------------------------------------
# snapshot/restore seam: the "mostly-HIT re-prefill" upgrades to
# restore-by-pages when a shared store holds the chains
# ---------------------------------------------------------------------------


class TestSnapshotRestoreByPages:
    def test_restore_into_fresh_engine_promotes_from_store(self, setup):
        """``restore_sequences`` re-admits by re-prefill THROUGH the
        tier-aware match: with the source's chains flushed to a shared
        store (what ``drain_replica`` does before snapshotting), the
        fresh engine's re-prefill becomes h2d page promotion — greedy
        output byte-identical to the uninterrupted run, with L1 hits
        proving pages were restored rather than recomputed."""
        cfg, params, tok = setup
        store = PrefixStore(host_pages=256)

        want = _run(make_engine(cfg, _ecfg(), params, tok,
                                use_kernel=False), tok)

        src = make_engine(cfg, _ecfg(), params, tok, use_kernel=False,
                          prefix_store=store)
        sids = [src.submit(tok.encode(p)) for p in PROMPTS]
        out = {}
        for _ in range(4):                 # interrupt mid-decode
            for r in src.step():
                out[r.seq_id] = r
        assert src.flush_prefix_store() > 0
        snap = src.snapshot_sequences()

        resume = make_engine(cfg, _ecfg(), params, tok,
                             use_kernel=False, prefix_store=store)
        resume.restore_sequences(snap)
        while resume.has_work:
            for r in resume.step():
                out[r.seq_id] = r
        resume.allocator.check()
        assert [out[s].token_ids for s in sids] == want
        assert (resume._counts or {}).get("engine.prefix_hits_l1", 0) > 0


# ---------------------------------------------------------------------------
# acceptance sweep: 100 seeded incidents, warm-started fresh replica
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestAcceptanceSweep:
    def test_100_incident_warm_started_replica_report_bytes(
            self, setup, cpu_devices):
        """The ISSUE acceptance bar: a seeded 100-incident shared-
        preamble sweep where a FRESH replica warm-starts from a store
        its sibling flushed produces ``report_bytes`` byte-identical to
        the all-re-prefill run, with a counter-proven prefill reduction
        (L1 hits > 0, fewer prefill spans than the cold run)."""
        import random

        from k8s_llm_rca_tpu.cluster.replica import build_replicas
        from k8s_llm_rca_tpu.faults.soak import report_bytes

        cfg, params, tok = setup
        rng = random.Random(17)
        causes = ("oom", "dns", "quota", "netpol", "pv chain", "kubelet",
                  "evicted", "taint", "crashloop", "rate limit")
        wave = [_PRE + f"incident {i}: {rng.choice(causes)}"
                for i in range(100)]
        ecfg = _ecfg(prefill_chunk_budget=32)

        def prefill_spans():
            return METRICS.snapshot().get("engine.prefill.count", 0)

        def sweep(eng):
            before = prefill_spans()
            toks = _run(eng, tok, wave)
            report = {"seed": 17, "n_incidents": len(wave),
                      "incidents": [
                          {"id": i, "token_ids": [int(t) for t in ts]}
                          for i, ts in enumerate(toks)]}
            return report, prefill_spans() - before

        # all-re-prefill baseline (no store: eviction discards)
        cold_report, cold_spans = sweep(
            make_engine(cfg, ecfg, params, tok, use_kernel=False))

        store = PrefixStore(host_pages=2048)
        replicas = build_replicas(cfg, ecfg, 2, devices=cpu_devices[:2],
                                  prefix_store=store, use_kernel=False)
        src = replicas[0].backend.engine
        _run(src, tok, wave[:10])          # sibling serves, then publishes
        assert src.flush_prefix_store() > 0

        warm_eng = replicas[1].backend.engine      # FRESH replica
        warm_report, warm_spans = sweep(warm_eng)

        assert report_bytes(warm_report) == report_bytes(cold_report)
        assert warm_spans < cold_spans
        c = warm_eng._counts or {}
        assert (c.get("engine.prefix_hits_l1", 0)
                + c.get("engine.prefix_hits_l2", 0)) > 0


# ---------------------------------------------------------------------------
# loud exclusions (mirror the spill exclusions, paged.py)
# ---------------------------------------------------------------------------


class TestExclusions:
    def test_cp_mesh_rejects_tiers(self, setup, cpu_devices):
        from k8s_llm_rca_tpu.runtime.mesh import build_mesh

        cfg, params, tok = setup
        mesh = build_mesh(MeshConfig(seq=2), devices=cpu_devices[:2])
        with pytest.raises(ValueError, match="cp_mesh"):
            make_engine(cfg, _ecfg(prefix_host_pages=8), params, tok,
                        use_kernel=False, cp_mesh=mesh)

    def test_pp_mesh_rejects_tiers(self, setup, cpu_devices):
        from k8s_llm_rca_tpu.runtime.mesh import build_mesh

        cfg, params, tok = setup
        mesh = build_mesh(MeshConfig(stage=2), devices=cpu_devices[:2])
        with pytest.raises(ValueError, match="pp_mesh"):
            make_engine(cfg, _ecfg(prefix_host_pages=8), params, tok,
                        use_kernel=False, pp_mesh=mesh)

    @pytest.mark.parametrize("knobs,refusal", [
        (dict(prefix_host_pages=-1), "must be >= 0"),
        (dict(prefix_disk_pages=4), "needs prefix_disk_dir"),
        (dict(prefix_cache=False, prefix_host_pages=8),
         "prefix_cache=True")])
    def test_negative_and_inconsistent_knobs_reject(self, setup, knobs,
                                                    refusal):
        cfg, params, tok = setup
        with pytest.raises(ValueError, match=refusal):
            make_engine(cfg, _ecfg(**knobs), params, tok, use_kernel=False)

    def test_store_validates_its_own_knobs(self):
        with pytest.raises(ValueError, match="must be >= 0"):
            PrefixStore(host_pages=-1)
        with pytest.raises(ValueError, match="needs disk_dir"):
            PrefixStore(disk_pages=4)


# ---------------------------------------------------------------------------
# store + codec units (no engine)
# ---------------------------------------------------------------------------


class TestStoreAndCodecUnits:
    def _rec(self, fill=1.0):
        import numpy as np

        return {"n_pages": 1,
                "k": np.full((2, 1, 4, 8), fill, np.float32),
                "v": np.full((2, 1, 4, 8), -fill, np.float32)}

    def test_codec_roundtrip_and_rejection(self):
        import numpy as np

        from k8s_llm_rca_tpu.utils.pages import (
            decode_page_record, encode_page_record,
        )

        rec = self._rec()
        frame = encode_page_record(rec)
        back = decode_page_record(frame)
        assert back is not None
        assert np.array_equal(back["k"], rec["k"])
        assert np.array_equal(back["v"], rec["v"])
        assert back["k"].dtype == rec["k"].dtype
        # torn tail and corrupt payload both answer None, never raise
        assert decode_page_record(frame[:-3]) is None
        bad = bytearray(frame)
        bad[-1] ^= 0xFF
        assert decode_page_record(bytes(bad)) is None
        assert decode_page_record(b"") is None
        assert decode_page_record(b"garbage that is not a frame") is None

    def test_l1_lru_and_overflow_order(self, tmp_path):
        d = str(tmp_path / "l2")
        store = PrefixStore(host_pages=2, disk_dir=d)
        store.put(b"a" * 20, self._rec(1))
        store.put(b"b" * 20, self._rec(2))
        got = store.get(b"a" * 20)
        assert got is not None and got[1] == 1    # refreshed: now newest
        store.put(b"c" * 20, self._rec(3))        # overflows LRU "b"
        assert store.n_host == 2 and store.n_disk == 1
        got_b = store.get(b"b" * 20)
        assert got_b is not None and got_b[1] == 2     # served from disk
        assert store.contains(b"c" * 20)

    def test_put_is_idempotent_per_digest(self, tmp_path):
        d = str(tmp_path / "l2")
        store = PrefixStore(host_pages=0, disk_dir=d)
        store.put(b"k" * 20, self._rec())
        mtime = os.path.getmtime(os.path.join(d, ("6b" * 20) + ".page"))
        store.put(b"k" * 20, self._rec())          # digest pins the bytes
        assert os.path.getmtime(
            os.path.join(d, ("6b" * 20) + ".page")) == mtime
        assert store.n_disk == 1
