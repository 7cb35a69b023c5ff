"""Overload hardening (docs/serving.md "overload & priorities"): priority
classes, per-run deadlines, and KV spill-to-host preemption.

The headline invariant is BYTE PARITY: a preempted sequence that spills
its KV pages to host and later restores them must produce exactly the
tokens an uninterrupted run produces — no re-prefill on the spill path,
across host_overlap x prefix_cache x prefill_chunk_budget.  Greedy decode
makes this checkable without tolerance: temperature=0 argmax depends only
on weights and committed KV, so any divergence is a real state-machine
bug, not noise (same rationale as tests/test_overlap.py).

Everything runs on the 8-virtual-device CPU platform the conftest pins.
"""

import dataclasses

import jax
import pytest

from conftest import tiny_on_a_tp_mesh
from k8s_llm_rca_tpu.config import TINY, EngineConfig, MeshConfig
from k8s_llm_rca_tpu.engine import make_engine
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

pytestmark = pytest.mark.overload


@pytest.fixture(scope="module")
def setup():
    cfg = TINY.replace(max_seq_len=128)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    return cfg, params, tok


PROMPTS = ("kubelet crashloop on node-7 gpu slice",
           "etcd leader lost quorum after upgrade",
           "kubelet crashloop on node-7 gpu slice then oom")


def _ecfg(**over):
    base = dict(max_batch=2, max_seq_len=128, prefill_buckets=(64, 128),
                max_new_tokens=24, temperature=0.0,
                page_size=16, num_pages=40, prefix_cache=False,
                decode_chunk=4)
    base.update(over)
    return EngineConfig(**base)


class _Clock:
    """Injectable engine clock (engine._now prefers ``self.clock``)."""

    def __init__(self):
        self.t = 0.0

    def time(self):
        return self.t


def _drive(eng, sids, preempt_at=None):
    """Tick to drain, optionally forcing one preemption; assert the
    engine releases every page (allocator.check + exact free count)."""
    out, tick = {}, 0
    while eng.has_work:
        if preempt_at is not None and tick == preempt_at:
            assert eng._preempt_victim(), "no preemption victim"
        for r in eng.step():
            out[r.seq_id] = r
        tick += 1
    eng.allocator.check()
    resident = eng.prefix_cache.n_resident if eng.prefix_cache else 0
    assert (eng.allocator.n_free + resident
            == eng.engine_cfg.num_pages - 1)
    return [(out[s].token_ids, out[s].finish_reason) for s in sids]


def _run(setup, ecfg, priorities=(1, 2, 0), preempt_at=None):
    cfg, params, tok = setup
    eng = make_engine(cfg, ecfg, params, tok, use_kernel=False)
    sids = [eng.submit(tok.encode(p), priority=pri)
            for p, pri in zip(PROMPTS, priorities)]
    return _drive(eng, sids, preempt_at=preempt_at), dict(eng._counts or {})


# ---------------------------------------------------------------------------
# tentpole: spill/restore byte-parity across the feature matrix
# ---------------------------------------------------------------------------


class TestSpillParity:
    MATRIX = {
        "plain": dict(),
        "prefix": dict(prefix_cache=True),
        "overlap": dict(decode_chunk=1, host_overlap=True),
        "overlap_prefix": dict(prefix_cache=True, decode_chunk=1,
                               host_overlap=True),
        "chunked": dict(prefill_chunk_budget=32),
    }

    @pytest.mark.parametrize("feature", sorted(MATRIX))
    def test_preempt_spill_restore_matches_uninterrupted(self, setup,
                                                         feature):
        """Mixed-priority batch, preemption forced mid-decode: the spill
        run must (a) actually move pages d2h and back (counters prove the
        restore path ran, not the re-prefill fallback) and (b) emit
        byte-identical outputs to the uninterrupted run."""
        kw = self.MATRIX[feature]
        base, _ = _run(setup, _ecfg(max_spilled_pages=0, **kw))
        spill, c = _run(setup, _ecfg(max_spilled_pages=64, **kw),
                        preempt_at=2)
        assert base == spill
        assert c.get("engine.spilled_pages", 0) > 0
        assert c.get("engine.restored_pages", 0) > 0
        assert c.get("engine.spill_budget_fallbacks", 0) == 0

    def test_preempt_spill_restore_on_a_tp_mesh(self, cpu_devices):
        """The same on a mesh (moved here from the dryrun at PR 50): a
        GSPMD paged TP engine on dp2 x tp4 spills the preempted victim's
        pages to host and restores them by h2d page writes; tokens are
        those of the never-preempted run."""
        cfg, params, tok, mesh = tiny_on_a_tp_mesh(cpu_devices, 31)
        ecfg = _ecfg(max_seq_len=64, prefill_buckets=(16, 32),
                     max_new_tokens=8, page_size=8, num_pages=32,
                     decode_chunk=1, max_spilled_pages=32)

        def run(preempt_at):
            eng = make_engine(cfg, ecfg, params, tok, tp_mesh=mesh,
                              use_kernel=False)
            sids = [eng.submit(tok.encode(p, add_bos=True), priority=pri)
                    for pri, p in enumerate(("api server latency spike",
                                             "registry pull rate limited",
                                             "coredns pods evicted"))]
            with jax.default_matmul_precision("float32"):
                return _drive(eng, sids, preempt_at), dict(eng._counts)

        base, _ = run(None)
        spill, c = run(2)
        assert base == spill
        assert c.get("engine.spilled_pages", 0) > 0
        assert c.get("engine.restored_pages", 0) > 0

    def test_re_prefill_fallback_parity(self, setup):
        """With spill disabled the same preemption takes the legacy
        free-and-re-prefill path — still byte-identical, zero spills."""
        base, _ = _run(setup, _ecfg())
        re_pre, c = _run(setup, _ecfg(), preempt_at=2)
        assert base == re_pre
        assert c.get("engine.spilled_pages", 0) == 0
        assert c.get("engine.preemptions", 0) >= 1

    def test_budget_fallback_counts_and_preserves_parity(self, setup):
        """max_spilled_pages smaller than the victim's footprint: the
        spill is refused (counted), the sequence re-prefills, and the
        output is still byte-identical."""
        cfg, params, tok = setup
        ecfg = _ecfg(max_batch=1, max_spilled_pages=32)
        eng0 = make_engine(cfg, ecfg, params, tok, use_kernel=False)
        s0 = eng0.submit(tok.encode(PROMPTS[1]), priority=2)
        (base,) = _drive(eng0, [s0])

        eng = make_engine(cfg, dataclasses.replace(ecfg,
                                                   max_spilled_pages=1),
                          params, tok, use_kernel=False)
        s1 = eng.submit(tok.encode(PROMPTS[1]), priority=2)
        eng.step()
        eng.step()
        assert eng._preempt_victim()
        c = eng._counts or {}
        assert c.get("engine.spill_budget_fallbacks", 0) == 1
        assert not eng._spilled
        (out,) = _drive(eng, [s1])
        assert out == base


# ---------------------------------------------------------------------------
# priority queue + victim selection determinism
# ---------------------------------------------------------------------------


class TestPriorityScheduling:
    def test_pending_queue_orders_by_class_then_fifo(self, setup):
        """The admission queue is a deterministic priority queue: classes
        ascend, and WITHIN a class arrival order is preserved (stable
        insert — an all-NORMAL workload degenerates to plain FIFO)."""
        cfg, params, tok = setup
        eng = make_engine(cfg, _ecfg(), params, tok, use_kernel=False)
        prompt = tok.encode(PROMPTS[0])
        sids = [eng.submit(list(prompt), priority=pri)
                for pri in (2, 1, 0, 1, 2, 0)]
        got = [(p.priority, p.seq_id) for p in eng._pending]
        assert got == [(0, sids[2]), (0, sids[5]),
                       (1, sids[1]), (1, sids[3]),
                       (2, sids[0]), (2, sids[4])]
        for sid in sids:
            eng.cancel_seq(sid)
        assert not eng.has_work

    def test_victim_is_lowest_priority_then_youngest(self, setup):
        """Preemption evicts the least-urgent active sequence; ties break
        toward the youngest (largest seq_id) so old work keeps its KV."""
        cfg, params, tok = setup
        eng = make_engine(cfg, _ecfg(max_spilled_pages=64), params, tok,
                          use_kernel=False)
        s_crit = eng.submit(tok.encode(PROMPTS[0]), priority=0)
        s_batch = eng.submit(tok.encode(PROMPTS[1]), priority=2)
        eng.step()
        eng.step()
        assert {st.seq_id for st in eng._active.values()} \
            == {s_crit, s_batch}
        assert eng._preempt_victim()
        survivors = {st.seq_id for st in eng._active.values()}
        assert survivors == {s_crit}, "victim must be the BATCH sequence"
        assert s_batch in eng._spilled
        _drive(eng, [s_crit, s_batch])

    def test_victim_tiebreak_youngest_within_class(self, setup):
        cfg, params, tok = setup
        eng = make_engine(cfg, _ecfg(max_spilled_pages=64), params, tok,
                          use_kernel=False)
        s_old = eng.submit(tok.encode(PROMPTS[0]), priority=1)
        s_young = eng.submit(tok.encode(PROMPTS[1]), priority=1)
        eng.step()
        eng.step()
        assert eng._preempt_victim()
        assert {st.seq_id for st in eng._active.values()} == {s_old}
        _drive(eng, [s_old, s_young])


# ---------------------------------------------------------------------------
# per-run deadlines: eager reap, same-tick page free
# ---------------------------------------------------------------------------


class TestDeadlines:
    def test_active_expiry_frees_pages_same_tick(self, setup):
        """A deadline that passes mid-decode surfaces an ``expired``
        result on the very NEXT step call, and that same tick returns the
        sequence's pages to the allocator — expired work never squats on
        KV while live traffic queues."""
        cfg, params, tok = setup
        eng = make_engine(cfg, _ecfg(), params, tok, use_kernel=False)
        clk = _Clock()
        eng.clock = clk
        s1 = eng.submit(tok.encode(PROMPTS[0]), deadline_s=5.0)
        s2 = eng.submit(tok.encode(PROMPTS[1]))
        eng.step()
        free_before = eng.allocator.n_free
        clk.t = 10.0
        res = eng.step()
        exp = [r for r in res if r.seq_id == s1]
        assert exp and exp[0].finish_reason == "expired"
        assert eng.allocator.n_free > free_before
        out = {r.seq_id: r for r in res}
        while eng.has_work:
            for r in eng.step():
                out[r.seq_id] = r
        assert out[s2].finish_reason in ("stop", "length")
        eng.allocator.check()
        assert eng.allocator.n_free == eng.engine_cfg.num_pages - 1

    def test_pending_expiry_never_admits(self, setup):
        """A queued sequence whose deadline passes before admission is
        reaped from the queue — zero prefill work spent on it."""
        cfg, params, tok = setup
        eng = make_engine(cfg, _ecfg(max_batch=1), params, tok,
                          use_kernel=False)
        clk = _Clock()
        eng.clock = clk
        s1 = eng.submit(tok.encode(PROMPTS[0]))
        s2 = eng.submit(tok.encode(PROMPTS[1]), deadline_s=3.0)
        eng.step()
        clk.t = 4.0
        out = {}
        while eng.has_work:
            for r in eng.step():
                out[r.seq_id] = r
        assert out[s2].finish_reason == "expired"
        assert out[s2].completion_tokens == 0
        assert out[s1].finish_reason in ("stop", "length")
        eng.allocator.check()
        assert eng.allocator.n_free == eng.engine_cfg.num_pages - 1

    def test_expired_spilled_record_is_dropped(self, setup):
        """Deadline reap of a SPILLED (preempted, waiting) sequence must
        free its host record and shared-prefix refs, not just its queue
        entry."""
        cfg, params, tok = setup
        eng = make_engine(cfg, _ecfg(max_batch=1, max_spilled_pages=64),
                          params, tok, use_kernel=False)
        clk = _Clock()
        eng.clock = clk
        s1 = eng.submit(tok.encode(PROMPTS[1]), deadline_s=5.0)
        eng.step()
        eng.step()
        assert eng._preempt_victim()
        assert s1 in eng._spilled
        clk.t = 10.0
        out = {}
        while eng.has_work:
            for r in eng.step():
                out[r.seq_id] = r
        assert out[s1].finish_reason == "expired"
        assert not eng._spilled and eng._spilled_pages_total == 0
        eng.allocator.check()
        assert eng.allocator.n_free == eng.engine_cfg.num_pages - 1


# ---------------------------------------------------------------------------
# composition: snapshot/restore while spilled
# ---------------------------------------------------------------------------


class TestSnapshotComposition:
    def test_snapshot_while_spilled_restores_byte_identical(self, setup):
        """A spilled sequence sits in _pending, so snapshot_sequences
        captures it (with priority + absolute deadline); restored on a
        FRESH engine it re-prefills and finishes byte-identical, and the
        abandoned donor engine still cancels back to a clean allocator."""
        cfg, params, tok = setup
        ecfg = _ecfg(max_batch=1, max_spilled_pages=32)

        eng0 = make_engine(cfg, ecfg, params, tok, use_kernel=False)
        s0 = eng0.submit(tok.encode(PROMPTS[1]), priority=2,
                         deadline_s=99.0)
        (base,) = _drive(eng0, [s0])

        e1 = make_engine(cfg, ecfg, params, tok, use_kernel=False)
        s1 = e1.submit(tok.encode(PROMPTS[1]), priority=2, deadline_s=99.0)
        e1.step()
        e1.step()
        assert e1._preempt_victim()
        assert e1._spilled
        snap = e1.snapshot_sequences()
        (entry,) = snap["sequences"]
        assert entry["priority"] == 2
        assert entry["deadline"] is not None

        e2 = make_engine(cfg, ecfg, params, tok, use_kernel=False)
        e2.restore_sequences(snap)
        assert e2._deadlines, "deadline must survive restore"
        out = None
        while e2.has_work:
            for r in e2.step():
                out = (r.token_ids, r.finish_reason)
        assert out == base

        e1.cancel_seq(s1)
        e1.allocator.check()
        assert e1.allocator.n_free == ecfg.num_pages - 1
        assert not e1._spilled and e1._spilled_pages_total == 0


# ---------------------------------------------------------------------------
# loud exclusions
# ---------------------------------------------------------------------------


class TestExclusions:
    def test_negative_budget_rejects(self, setup):
        cfg, params, tok = setup
        with pytest.raises(ValueError, match="must be >= 0"):
            make_engine(cfg, _ecfg(max_spilled_pages=-1), params, tok,
                        use_kernel=False)

    def test_cp_mesh_rejects_spill(self, setup, cpu_devices):
        from k8s_llm_rca_tpu.runtime.mesh import build_mesh

        cfg, params, tok = setup
        mesh = build_mesh(MeshConfig(seq=2), devices=cpu_devices[:2])
        with pytest.raises(ValueError, match="cp_mesh"):
            make_engine(cfg, _ecfg(max_spilled_pages=8), params, tok,
                        use_kernel=False, cp_mesh=mesh)

    def test_pp_mesh_rejects_spill(self, setup, cpu_devices):
        from k8s_llm_rca_tpu.runtime.mesh import build_mesh

        cfg, params, tok = setup
        mesh = build_mesh(MeshConfig(stage=2), devices=cpu_devices[:2])
        with pytest.raises(ValueError, match="pp_mesh"):
            make_engine(cfg, _ecfg(max_spilled_pages=8), params, tok,
                        use_kernel=False, pp_mesh=mesh)


# ---------------------------------------------------------------------------
# serve layer: EXPIRED terminal status, journal/recover agreement
# ---------------------------------------------------------------------------


class TestServeDeadlines:
    def test_run_expires_and_recovery_agrees(self, setup, tmp_path):
        """GenOptions.deadline_s flows into the engine reap; the run
        settles EXPIRED (typed terminal status, pages freed), the journal
        records it, and recovery replays EXPIRED verbatim — an expired
        run is never resurrected."""
        from k8s_llm_rca_tpu.faults.plan import VirtualClock
        from k8s_llm_rca_tpu.serve.api import AssistantService, RunStatus
        from k8s_llm_rca_tpu.serve.backend import (EngineBackend,
                                                   GenOptions, Priority)
        from k8s_llm_rca_tpu.serve.journal import RunJournal
        from k8s_llm_rca_tpu.serve.recover import recover_service

        cfg, params, tok = setup
        ecfg = _ecfg(max_new_tokens=200, max_spilled_pages=32)
        path = str(tmp_path / "serve.wal")

        eng = make_engine(cfg, ecfg, params, tok, use_kernel=False)
        clk = VirtualClock()
        eng.clock = clk
        svc = AssistantService(EngineBackend(eng), run_timeout_s=600.0,
                               clock=clk, journal=RunJournal(path))
        a = svc.create_assistant("analyze", "rca", model="tiny",
                                 gen=GenOptions(max_new_tokens=120))
        th = svc.create_thread()
        svc.add_message(th.id, "kubelet crashloop burning pages")
        run = svc.create_run(th.id, a.id, gen=GenOptions(
            max_new_tokens=120, deadline_s=0.5, priority=Priority.BATCH))
        assert eng._deadlines and len(eng._deadlines) == 1
        svc.retrieve_run(run.id)
        clk.sleep(1.0)
        r = svc.retrieve_run(run.id)
        assert r.status == RunStatus.EXPIRED
        assert "deadline" in (r.error or "")
        assert not eng.has_work
        eng.allocator.check()
        assert eng.allocator.n_free == ecfg.num_pages - 1
        svc._journal.close()

        eng2 = make_engine(cfg, ecfg, params, tok, use_kernel=False)
        clk2 = VirtualClock()
        eng2.clock = clk2
        svc2, report = recover_service(path, EngineBackend(eng2),
                                       run_timeout_s=600.0, clock=clk2)
        assert svc2.runs[run.id].status == RunStatus.EXPIRED
        assert not report["resubmitted"]


# ---------------------------------------------------------------------------
# cluster: priority-tiered shedding under saturation
# ---------------------------------------------------------------------------


@pytest.mark.cluster
class TestClusterSaturation:
    def test_batch_sheds_first_critical_always_completes(self):
        from k8s_llm_rca_tpu.faults.soak import run_saturation_scenario

        sat = run_saturation_scenario(n_replicas=2, max_inflight=2,
                                      n_requests=12)
        assert sat["shed_by_class"][0] == 0, "CRITICAL must never shed"
        assert sat["admitted_by_class"][0] == 4
        assert sat["shed_by_class"][2] >= sat["shed_by_class"][1]
        first_shed = next(o for o in sat["outcomes"] if not o["admitted"])
        assert first_shed["priority"] == 2, "BATCH sheds first"
        assert sat["completed"] == sum(sat["admitted_by_class"].values())
        for o in sat["outcomes"]:
            if not o["admitted"]:
                assert o["error"] == "RouterAdmissionError"
                assert "priority" in o["detail"]


# ---------------------------------------------------------------------------
# chaos soak: spill on/off byte-identity under scheduled faults
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestOverloadSoak:
    def _identity(self, n_runs):
        from k8s_llm_rca_tpu.faults.soak import (report_bytes,
                                                 run_overload_soak)

        on = run_overload_soak(seed=0, n_runs=n_runs, spill=True)
        off = run_overload_soak(seed=0, n_runs=n_runs, spill=False)
        assert report_bytes(on["report"]) == report_bytes(off["report"])
        assert on["stats"]["spilled_pages"] > 0
        assert on["stats"]["restored_pages"] > 0
        assert off["stats"]["spilled_pages"] == 0
        assert on["stats"]["engine_clean"]
        assert off["stats"]["engine_clean"]
        by_status = on["report"]["by_status"]
        assert sum(by_status.values()) == n_runs

    def test_soak_report_identical_spill_on_vs_off(self):
        """Preempt/oom fault schedule against a deep mixed-priority
        queue: the outcome report (per-run priority, finish reason, text,
        token count) is byte-identical whether preemption spills KV or
        re-prefills — sized to the tier-1 budget."""
        self._identity(24)

    @pytest.mark.slow
    def test_soak_100_incidents(self):
        """The full 100-incident soak from the issue spec."""
        self._identity(100)
