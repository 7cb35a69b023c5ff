"""Crash-safe serving: WAL codec, run journal, recovery replay, engine
sequence snapshot/restore, and the supervised kill/restart chaos proof.

The durability layer's contract (docs/durability.md): every mutation the
service acknowledged is on disk before the acknowledgement (fsync'd WAL
append), a crash at ANY byte offset leaves a journal whose intact prefix
replays to the exact pre-crash store, settled runs are never re-executed,
and interrupted runs are re-queued for a fresh prefill whose greedy output
is byte-identical to the never-interrupted run.  Everything here is
deterministic: greedy decode, seeded fault plans, virtual clocks.

The disarmed path is load-bearing too: a service built without a journal
must do ZERO journal work — asserted by monkeypatching the whole journal
surface to raise and driving every run path.
"""

import json
import os
import re

import jax
import pytest

from conftest import tiny_on_a_tp_mesh
from k8s_llm_rca_tpu.config import TINY, EngineConfig
from k8s_llm_rca_tpu.engine import make_engine
from k8s_llm_rca_tpu.faults import inject
from k8s_llm_rca_tpu.faults.plan import Fault, FaultPlan
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.serve.api import AssistantService, RunStatus
from k8s_llm_rca_tpu.serve.backend import (
    BudgetError, EchoBackend, EngineBackend, GenOptions,
)
from k8s_llm_rca_tpu.serve.journal import (
    RunJournal, decode_gen, encode_gen, read_journal,
)
from k8s_llm_rca_tpu.serve.recover import recover_service
from k8s_llm_rca_tpu.sweeps.run_file import scan_output
from k8s_llm_rca_tpu.utils import wal
from k8s_llm_rca_tpu.utils.logging import METRICS
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer


@pytest.fixture(autouse=True)
def _disarmed():
    """Never leak an armed plan into other tests."""
    yield
    if inject.active() is not None:
        inject.disarm()


@pytest.fixture(scope="module")
def tiny_engine():
    """One TINY paged engine shared by the engine-path durability tests
    (greedy decode: outputs depend only on weights/prompts, same rationale
    as test_faults.shared_engine)."""
    cfg = TINY.replace(max_seq_len=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    eng = make_engine(
        cfg, EngineConfig(max_batch=4, max_seq_len=64,
                          page_size=8, num_pages=24,
                          prefill_buckets=(16, 32), max_new_tokens=8,
                          temperature=0.0, decode_chunk=1,
                          prefix_cache=False),
        params, tok, use_kernel=False)
    return eng, tok


# ---------------------------------------------------------------------------
# WAL codec: framing, torn tails, corruption
# ---------------------------------------------------------------------------


class TestWal:
    def test_roundtrip_and_clean_end(self, tmp_path):
        path = str(tmp_path / "w.wal")
        payloads = [b"alpha", b"", b'{"k":1}' * 40]
        with open(path, "ab") as f:
            for p in payloads:
                wal.append_record(f, p)
        got, end = wal.scan_wal(path)
        assert got == payloads
        assert end == os.path.getsize(path)

    def test_torn_tail_recovers_prefix_and_truncates_atomically(
            self, tmp_path):
        path = str(tmp_path / "w.wal")
        with open(path, "ab") as f:
            wal.append_record(f, b"one")
            wal.append_record(f, b"two")
        clean_size = os.path.getsize(path)
        # the crash artifact: a frame cut mid-write
        with open(path, "ab") as f:
            f.write(wal.pack_record(b"torn-away")[:-3])
        got, end = wal.scan_wal(path)
        assert got == [b"one", b"two"] and end == clean_size
        # still un-truncated without the flag
        assert os.path.getsize(path) > clean_size
        got2, _ = wal.scan_wal(path, truncate_partial=True)
        assert got2 == [b"one", b"two"]
        assert os.path.getsize(path) == clean_size
        assert not os.path.exists(path + ".tmp")   # replaced, not left over
        # the truncated file appends cleanly at a record boundary
        with open(path, "ab") as f:
            wal.append_record(f, b"three")
        assert wal.scan_wal(path)[0] == [b"one", b"two", b"three"]

    def test_corrupt_checksum_stops_the_reader(self, tmp_path):
        path = str(tmp_path / "w.wal")
        with open(path, "ab") as f:
            wal.append_record(f, b"good")
            wal.append_record(f, b"flipped")
            wal.append_record(f, b"unreachable")
        data = bytearray(open(path, "rb").read())
        # flip one payload byte of record 2; everything after is suspect
        off = wal.HEADER_SIZE + 4 + wal.HEADER_SIZE
        data[off] ^= 0xFF
        open(path, "wb").write(bytes(data))
        got, end = wal.scan_wal(path)
        assert got == [b"good"]
        assert end == wal.HEADER_SIZE + 4

    def test_garbage_length_field_is_torn_tail_not_record(self, tmp_path):
        path = str(tmp_path / "w.wal")
        with open(path, "ab") as f:
            wal.append_record(f, b"real")
            f.write(wal._HEADER.pack(wal.MAX_RECORD_SIZE + 5, 0))
            f.flush()
        got, _ = wal.scan_wal(path)
        assert got == [b"real"]

    def test_oversized_record_rejected_at_write_time(self):
        with pytest.raises(ValueError, match="MAX_RECORD_SIZE"):
            wal.pack_record(b"x" * (wal.MAX_RECORD_SIZE + 1))

    def test_missing_and_empty_files(self, tmp_path):
        assert wal.scan_wal(str(tmp_path / "absent.wal")) == ([], 0)
        empty = tmp_path / "empty.wal"
        empty.touch()
        assert wal.scan_wal(str(empty), truncate_partial=True) == ([], 0)


# ---------------------------------------------------------------------------
# run journal: record codec + reopen discipline
# ---------------------------------------------------------------------------


class TestRunJournal:
    def test_append_read_roundtrip(self, tmp_path):
        path = str(tmp_path / "serve.wal")
        with RunJournal(path) as j:
            j.append("create_thread", id="thread_00000000")
            j.append("add_message", thread_id="thread_00000000",
                     id="msg_00000001", role="user", content="pod down",
                     created_at=12.5)
            assert j.appended == 2 and j.bytes_written > 0
        records, end = read_journal(path)
        assert [r["kind"] for r in records] == ["create_thread",
                                                "add_message"]
        assert records[1]["content"] == "pod down"
        assert end == os.path.getsize(path)

    def test_reopen_drops_torn_tail_then_appends(self, tmp_path):
        path = str(tmp_path / "serve.wal")
        with RunJournal(path) as j:
            j.append("create_thread", id="t_0")
        with open(path, "ab") as f:      # the crash artifact
            f.write(b"\x00\x00\x00\x07garbage-without-checksum")
        with RunJournal(path) as j:      # open truncates, then appends
            j.append("create_thread", id="t_1")
        records, end = read_journal(path)
        assert [r["id"] for r in records] == ["t_0", "t_1"]
        assert end == os.path.getsize(path)

    def test_gen_options_roundtrip_specs_only(self):
        gen = GenOptions(max_new_tokens=9, stop=("```",), forced_prefix="p",
                         suffix="s", grammar={"type": "object"},
                         assistant_name="a")
        assert decode_gen(encode_gen(gen)) == gen
        assert encode_gen(None) is None and decode_gen(None) is None

        class CompiledFsm:
            pass

        with pytest.raises(ValueError, match="spec"):
            encode_gen(GenOptions(grammar=CompiledFsm()))


# ---------------------------------------------------------------------------
# sweep output partial-tail tolerance (the layer of record above the WAL)
# ---------------------------------------------------------------------------


def _sweep_record(msg):
    return json.dumps({"error_message": msg, "analysis": []},
                      indent=4) + "\n"


class TestScanOutputPartialTail:
    def test_crash_tail_dropped_atomically_completed_survive(self, tmp_path):
        out = tmp_path / "rca.json"
        out.write_text(_sweep_record("a") + _sweep_record("b")
                       + '{\n    "error_message": "c", "anal')
        # without the flag: completed records found, file untouched
        msgs, end = scan_output(str(out))
        assert msgs == ["a", "b"]
        assert "anal" in out.read_text()
        # with the flag: tail gone, completed records byte-intact
        msgs, end2 = scan_output(str(out), truncate_partial=True)
        assert msgs == ["a", "b"] and end2 == end
        text = out.read_text()
        assert "c" not in text
        assert not os.path.exists(str(out) + ".tmp")
        # the truncated file is append-ready: a resumed sweep record parses
        with open(out, "a") as f:
            f.write(_sweep_record("c"))
        assert scan_output(str(out))[0] == ["a", "b", "c"]

    def test_empty_and_missing_files(self, tmp_path):
        assert scan_output(str(tmp_path / "absent.json")) == ([], 0)
        empty = tmp_path / "empty.json"
        empty.write_text("")
        assert scan_output(str(empty), truncate_partial=True) == ([], 0)

    def test_whitespace_only_tail_is_not_a_crash_artifact(self, tmp_path):
        out = tmp_path / "rca.json"
        out.write_text(_sweep_record("a") + "\n   \n")
        before = out.read_text()
        msgs, _ = scan_output(str(out), truncate_partial=True)
        assert msgs == ["a"]
        assert out.read_text() == before   # no pointless rewrite


# ---------------------------------------------------------------------------
# service journaling hooks + the disarmed path
# ---------------------------------------------------------------------------


def _drive_lifecycle(service, text="pod crashloop", wait=True):
    a = service.create_assistant("test", "t")
    th = service.create_thread()
    service.add_message(th.id, text)
    run = service.create_run(th.id, a.id,
                             gen=GenOptions(max_new_tokens=8))
    if wait:
        run = service.wait_run(run.id)
    return a, th, run


class TestServiceJournaling:
    def test_full_lifecycle_is_journaled(self, tmp_path):
        path = str(tmp_path / "serve.wal")
        tok = get_tokenizer()
        service = AssistantService(EchoBackend(tok, reply="the answer"),
                                   journal=RunJournal(path))
        _, _, run = _drive_lifecycle(service)
        assert run.status == RunStatus.COMPLETED
        service._journal.close()
        records, _ = read_journal(path)
        assert [r["kind"] for r in records] == [
            "create_assistant", "create_thread", "add_message",
            "run_submit", "run_settle"]
        submit, settle = records[3], records[4]
        assert submit["id"] == run.id
        assert "<|assistant|>" in submit["prompt"]   # the RENDERED prompt
        assert settle["status"] == RunStatus.COMPLETED
        assert settle["response"]["content"] == "the answer"

    def test_cancel_and_expiry_are_journaled_settles(self, tmp_path):
        path = str(tmp_path / "serve.wal")
        tok = get_tokenizer()
        service = AssistantService(EchoBackend(tok, delay_pumps=10 ** 9),
                                   journal=RunJournal(path))
        _, _, r_cancel = _drive_lifecycle(service, wait=False)
        service.cancel_run(r_cancel.id)
        _, _, r_expire = _drive_lifecycle(service, wait=False)
        got = service.wait_run(r_expire.id, timeout_s=0.0)
        assert got.status == RunStatus.EXPIRED
        service._journal.close()
        settles = {r["id"]: r for r in read_journal(path)[0]
                   if r["kind"] == "run_settle"}
        assert settles[r_cancel.id]["status"] == RunStatus.CANCELLED
        assert settles[r_expire.id]["status"] == RunStatus.EXPIRED
        assert settles[r_expire.id]["response"] is None

    def test_cancel_after_settle_is_a_noop(self, tmp_path):
        """A terminal run re-cancelled: no state change, no extra settle
        record (the journal must carry exactly one terminal transition)."""
        path = str(tmp_path / "serve.wal")
        tok = get_tokenizer()
        journal = RunJournal(path)
        service = AssistantService(EchoBackend(tok, reply="done"),
                                   journal=journal)
        _, _, run = _drive_lifecycle(service)
        assert run.status == RunStatus.COMPLETED
        appended = journal.appended
        got = service.cancel_run(run.id)
        assert got.status == RunStatus.COMPLETED    # not flipped
        assert journal.appended == appended         # nothing re-journaled
        journal.close()
        settles = [r for r in read_journal(path)[0]
                   if r["kind"] == "run_settle"]
        assert len(settles) == 1

    def test_disarmed_path_does_zero_journal_work(self, monkeypatch):
        """The inertness proof: with no journal configured, the whole
        journal surface is unreachable.  Every entry point is patched to
        raise; every run path (complete, cancel, expire) must still work."""
        import k8s_llm_rca_tpu.serve.journal as journal_mod

        def boom(*a, **k):
            raise AssertionError("journal I/O on the default path")

        monkeypatch.setattr(journal_mod.RunJournal, "__init__", boom)
        monkeypatch.setattr(journal_mod.RunJournal, "append", boom)
        monkeypatch.setattr(wal, "append_record", boom)
        tok = get_tokenizer()
        service = AssistantService(EchoBackend(tok, reply="ok"))
        _, _, run = _drive_lifecycle(service)
        assert run.status == RunStatus.COMPLETED
        slow = AssistantService(EchoBackend(tok, delay_pumps=10 ** 9))
        _, _, r_cancel = _drive_lifecycle(slow, wait=False)
        assert service.cancel_run is not None
        slow.cancel_run(r_cancel.id)
        _, _, r_expire = _drive_lifecycle(slow, wait=False)
        got = slow.wait_run(r_expire.id, timeout_s=0.0)
        assert got.status == RunStatus.EXPIRED


# ---------------------------------------------------------------------------
# recovery replay (echo backend)
# ---------------------------------------------------------------------------


class TestRecovery:
    def _crashed_journal(self, tmp_path, delay_pumps=10 ** 9):
        """Build a journaled service, leave one run in flight, 'crash'."""
        path = str(tmp_path / "serve.wal")
        tok = get_tokenizer()
        service = AssistantService(EchoBackend(tok, delay_pumps=delay_pumps),
                                   journal=RunJournal(path))
        a, th, run = _drive_lifecycle(service, wait=False)
        service._journal.close()         # process death
        return path, tok, service, run

    def test_interrupted_run_is_resubmitted_and_completes(self, tmp_path):
        path, tok, _, run = self._crashed_journal(tmp_path)
        svc, report = recover_service(path, EchoBackend(tok, reply="after"))
        assert report["resubmitted"] == [run.id]
        assert report["interrupted"] == 1
        assert svc.runs[run.id].status == RunStatus.IN_PROGRESS
        got = svc.wait_run(run.id)
        assert got.status == RunStatus.COMPLETED
        msgs = svc.list_messages(svc.runs[run.id].thread_id)
        assert msgs.data[0].raw_content == "after"

    def test_settled_run_replayed_not_reexecuted(self, tmp_path):
        path = str(tmp_path / "serve.wal")
        tok = get_tokenizer()
        service = AssistantService(EchoBackend(tok, reply="first answer"),
                                   journal=RunJournal(path))
        _, th, run = _drive_lifecycle(service)
        service._journal.close()

        class NeverStarts(EchoBackend):
            def start(self, prompt, opts):
                raise AssertionError("settled run re-executed")

        svc, report = recover_service(path, NeverStarts(tok))
        assert report["resubmitted"] == []
        got = svc.runs[run.id]
        assert got.status == RunStatus.COMPLETED
        assert got.usage == run.usage
        # the journaled response message is back in the thread
        texts = [m.raw_content for m in svc.threads[th.id].messages]
        assert "first answer" in texts

    def test_cancelled_before_crash_stays_cancelled(self, tmp_path):
        """Satellite: journal and recovery must agree on cancellation —
        a run cancelled pre-crash is NOT resurrected by replay."""
        path = str(tmp_path / "serve.wal")
        tok = get_tokenizer()
        service = AssistantService(EchoBackend(tok, delay_pumps=10 ** 9),
                                   journal=RunJournal(path))
        _, _, r_cancelled = _drive_lifecycle(service, wait=False)
        service.cancel_run(r_cancelled.id)
        _, _, r_inflight = _drive_lifecycle(service, wait=False)
        service._journal.close()
        svc, report = recover_service(path, EchoBackend(tok))
        assert svc.runs[r_cancelled.id].status == RunStatus.CANCELLED
        assert report["resubmitted"] == [r_inflight.id]
        assert svc.wait_run(r_inflight.id).status == RunStatus.COMPLETED

    def test_expired_before_crash_stays_expired(self, tmp_path):
        path = str(tmp_path / "serve.wal")
        tok = get_tokenizer()
        service = AssistantService(EchoBackend(tok, delay_pumps=10 ** 9),
                                   journal=RunJournal(path))
        _, _, run = _drive_lifecycle(service, wait=False)
        service.wait_run(run.id, timeout_s=0.0)
        service._journal.close()
        svc, report = recover_service(path, EchoBackend(tok))
        assert svc.runs[run.id].status == RunStatus.EXPIRED
        assert report["resubmitted"] == []

    def test_reconciliation_against_sweep_output(self, tmp_path):
        """An interrupted run whose incident is already durable in the
        sweep output is cancelled, not re-run (the output file is the
        layer of record above the journal)."""
        path, tok, _, run = self._crashed_journal(tmp_path)
        out = tmp_path / "rca.json"
        out.write_text(_sweep_record("pod crashloop"))
        svc, report = recover_service(path, EchoBackend(tok),
                                      sweep_output=str(out))
        assert report["reconciled"] == [run.id]
        assert report["resubmitted"] == []
        got = svc.runs[run.id]
        assert got.status == RunStatus.CANCELLED
        assert "already durable" in got.error

    def test_budget_rejected_resubmission_fails_the_run(self, tmp_path):
        path, tok, _, run = self._crashed_journal(tmp_path)

        class Shrunk(EchoBackend):
            def start(self, prompt, opts):
                raise BudgetError("prompt over the recovery budget")

        svc, report = recover_service(path, Shrunk(tok))
        assert report["failed_resubmit"] == [run.id]
        got = svc.runs[run.id]
        assert got.status == RunStatus.FAILED
        assert "resubmit rejected" in got.error

    def test_id_counter_resumes_past_journaled_ids(self, tmp_path):
        path, tok, service, run = self._crashed_journal(tmp_path)
        svc, _ = recover_service(path, EchoBackend(tok))
        top = max(int(m.group(1))
                  for r in read_journal(path)[0]
                  for m in [re.search(r"_(\d+)$", str(r.get("id", "")))]
                  if m)
        fresh = svc.create_thread()
        assert int(re.search(r"_(\d+)$", fresh.id).group(1)) > top

    def test_unknown_record_kind_refuses_to_replay(self, tmp_path):
        path = str(tmp_path / "serve.wal")
        with RunJournal(path) as j:
            j.append("frobnicate", id="x_1")
        with pytest.raises(ValueError, match="unknown journal record"):
            recover_service(path, EchoBackend(get_tokenizer()))


# ---------------------------------------------------------------------------
# engine sequence snapshot / restore
# ---------------------------------------------------------------------------


class TestEngineSnapshotRestore:
    def test_mid_decode_snapshot_restores_with_greedy_parity(
            self, tiny_engine):
        """The exact-resume proof at the engine layer: snapshot after a
        few decode ticks, abandon the device KV (cancel), restore, finish
        — tokens byte-identical to the never-interrupted run."""
        eng, tok = tiny_engine
        ids = [list(tok.encode(p, add_bos=True))
               for p in ("pod crashloop kube-system", "node disk pressure")]
        want = eng.generate([list(i) for i in ids], max_new_tokens=8)

        seq_ids = [eng.submit(list(i), max_new_tokens=8) for i in ids]
        partial = []
        for _ in range(3):
            partial.extend(eng.step())
        snap = eng.snapshot_sequences()
        by_id = {s["seq_id"]: s for s in snap["sequences"]}
        assert set(by_id) <= set(seq_ids)
        # snapshotted progress is a greedy prefix of the final output
        for sid, ref in zip(seq_ids, want):
            if sid in by_id:
                gen = by_id[sid]["generated"]
                assert gen == ref.token_ids[:len(gen)]
                assert by_id[sid]["prompt_ids"] == list(
                    ids[seq_ids.index(sid)])
        # the crash: device KV dies with the process
        for sid in list(by_id):
            eng.cancel_seq(sid)
        assert not eng.has_work
        eng.allocator.check()

        restored = eng.restore_sequences(snap)
        assert restored == sorted(by_id)
        results = list(partial)
        while eng.has_work:
            results.extend(eng.step())
        got = {r.seq_id: r for r in results}
        for sid, ref in zip(seq_ids, want):
            assert got[sid].token_ids == ref.token_ids
            assert got[sid].prompt_tokens == ref.prompt_tokens
            assert got[sid].text == ref.text
        eng.allocator.check()
        assert eng.allocator.n_free == eng.engine_cfg.num_pages - 1
        assert not eng._resumed                    # stitching bookkeeping drained

    def test_restore_collision_and_cap_overflow_fail_loudly(
            self, tiny_engine):
        eng, tok = tiny_engine
        ids = list(tok.encode("api server timeout", add_bos=True))
        sid = eng.submit(list(ids), max_new_tokens=4)
        snap = eng.snapshot_sequences()
        with pytest.raises(ValueError, match="collision"):
            eng.restore_sequences(snap)
        eng.cancel_seq(sid)
        assert not eng.has_work
        over = {"rng_key": [0, 0], "sequences": [{
            "seq_id": 10 ** 6, "prompt_ids": list(range(40)),
            "generated": list(range(30)), "remaining_new_tokens": 4,
            "stop_strings": [], "grammar": False}]}
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.restore_sequences(over)

    def test_mid_overlap_snapshot_restores_into_fresh_engine(self):
        """Device-resident follow-through (docs/performance.md): with the
        overlapped hot loop on, tokens live in flight between flushes —
        ``snapshot_sequences`` must barrier them into host state first,
        and the snapshot must restore into a FRESH overlapped engine
        (new device arrays, cold resident mirrors) with greedy output
        byte-identical to the never-interrupted run."""
        cfg = TINY.replace(max_seq_len=64)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tok = get_tokenizer(vocab_size=cfg.vocab_size)
        ecfg = EngineConfig(max_batch=4, max_seq_len=64,
                            page_size=8, num_pages=24,
                            prefill_buckets=(16, 32), max_new_tokens=8,
                            temperature=0.0, decode_chunk=1,
                            prefix_cache=False, host_overlap=True)

        def fresh():
            return make_engine(cfg, ecfg, params, tok, use_kernel=False)

        ids = [list(tok.encode(p, add_bos=True))
               for p in ("pod crashloop kube-system", "node disk pressure")]
        want = fresh().generate([list(i) for i in ids], max_new_tokens=8)

        crash = fresh()
        sids = [crash.submit(list(i), max_new_tokens=8) for i in ids]
        partial = []
        for _ in range(3):                 # mid-overlap: lag in flight
            partial.extend(crash.step())
        snap = crash.snapshot_sequences()
        assert not crash._inflight         # barrier drained the lag
        by_id = {s["seq_id"]: s for s in snap["sequences"]}
        for sid, ref in zip(sids, want):
            if sid in by_id:               # committed-prefix view only
                gen = by_id[sid]["generated"]
                assert gen == ref.token_ids[:len(gen)]
        # the crash: this engine's device state (including the resident
        # mirrors and any in-flight dispatches) dies with the process
        resume = fresh()
        resume.restore_sequences(snap)
        results = list(partial)
        while resume.has_work:
            results.extend(resume.step())
        got = {r.seq_id: r for r in results}
        for sid, ref in zip(sids, want):
            assert got[sid].token_ids == ref.token_ids
            assert got[sid].text == ref.text
        resume.allocator.check()

    def test_mid_decode_snapshot_restores_into_fresh_tp_mesh_engine(
            self, cpu_devices):
        """Crash-resume on a mesh (moved here from the dryrun at PR 50):
        a mid-decode snapshot of a GSPMD paged TP engine on dp2 x tp4
        restores into a FRESH engine over the same sharded params, which
        finishes byte-identically to the never-interrupted run."""
        cfg, sharded, tok, mesh = tiny_on_a_tp_mesh(cpu_devices, 18)
        ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                            prefill_buckets=(16, 32), max_new_tokens=8,
                            page_size=8, num_pages=32, decode_chunk=1)

        def fresh():
            return make_engine(cfg, ecfg, sharded, tok, tp_mesh=mesh,
                               use_kernel=False)

        ids = [tok.encode(p, add_bos=True)
               for p in ("pod crashloop kube-system", "node disk pressure")]
        with jax.default_matmul_precision("float32"):
            want = fresh().generate(ids, max_new_tokens=8)
            crash = fresh()
            for i in ids:
                crash.submit(i, max_new_tokens=8)
            results = []
            for _ in range(3):
                results.extend(crash.step())
            snap = crash.snapshot_sequences()
            assert snap["sequences"], "nothing live to snapshot"
            # the crash: this engine's device KV is abandoned
            resume = fresh()
            resume.restore_sequences(snap)
            while resume.has_work:
                results.extend(resume.step())
        resume.allocator.check()
        got = sorted(results, key=lambda r: r.seq_id)
        assert [r.token_ids for r in got] == [r.token_ids for r in want]
        assert [r.prompt_tokens for r in got] == [
            r.prompt_tokens for r in want]

    def test_restore_requires_fresh_fsm_for_grammar_sequences(
            self, tiny_engine):
        eng, _ = tiny_engine
        snap = {"rng_key": [0, 0], "sequences": [{
            "seq_id": 10 ** 6 + 1, "prompt_ids": [1, 2, 3],
            "generated": [], "remaining_new_tokens": 4,
            "stop_strings": [], "grammar": True}]}
        with pytest.raises(ValueError, match="grammar-constrained"):
            eng.restore_sequences(snap)
        assert not eng.has_work                    # nothing half-admitted

    def test_tick_crash_fault_preserves_greedy_output(self, tiny_engine):
        """The paged engine's 'crash' tick fault: every active sequence
        loses its device KV and requeues — output must not change."""
        eng, tok = tiny_engine
        ids = [list(tok.encode(p, add_bos=True))
               for p in ("pvc not bound storageclass", "dns nxdomain")]
        want = eng.generate([list(i) for i in ids], max_new_tokens=8)
        pre = METRICS.count("engine.crash_evictions")
        plan = FaultPlan([Fault(inject.SITE_ENGINE_TICK, 2, "crash")])
        with inject.armed(plan):
            got = eng.generate([list(i) for i in ids], max_new_tokens=8)
        assert [r.token_ids for r in got] == [r.token_ids for r in want]
        assert METRICS.count("engine.crash_evictions") > pre
        assert len(plan.fired) == 1
        eng.allocator.check()
        assert eng.allocator.n_free == eng.engine_cfg.num_pages - 1


# ---------------------------------------------------------------------------
# serve-level resume on the real engine
# ---------------------------------------------------------------------------


class TestServeEngineResume:
    def test_recovered_run_matches_uninterrupted_engine_run(
            self, tmp_path, tiny_engine):
        """End-to-end exact resume: journaled run interrupted mid-decode,
        backend torn down (engine slots cancelled, like a worker kill),
        recovery resubmits the journaled prompt onto a fresh backend —
        the completed reply is byte-identical to a never-interrupted run
        of the same prompt (greedy re-prefill parity)."""
        eng, tok = tiny_engine
        # the never-interrupted reference
        ref_svc = AssistantService(EngineBackend(eng))
        _, ref_th, ref_run = _drive_lifecycle(ref_svc)
        assert ref_run.status == RunStatus.COMPLETED
        ref_text = ref_svc.list_messages(ref_th.id).data[0].raw_content

        path = str(tmp_path / "serve.wal")
        backend = EngineBackend(eng)
        service = AssistantService(backend, journal=RunJournal(path))
        _, _, run = _drive_lifecycle(service, wait=False)
        service.retrieve_run(run.id)     # pump: prefill + some decode
        assert service.runs[run.id].status == RunStatus.IN_PROGRESS
        # the crash: journal handle and engine sequences die
        service._journal.close()
        for handle in list(backend._live):
            backend.cancel(handle)
        assert not eng.has_work

        svc, report = recover_service(path, EngineBackend(eng))
        assert report["resubmitted"] == [run.id]
        got = svc.wait_run(run.id)
        assert got.status == RunStatus.COMPLETED
        got_text = svc.list_messages(got.thread_id).data[0].raw_content
        assert got_text == ref_text
        eng.allocator.check()
        assert eng.allocator.n_free == eng.engine_cfg.num_pages - 1


# ---------------------------------------------------------------------------
# supervised kill/restart chaos proof
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestKillRestartChaos:
    def test_mid_sweep_crash_report_byte_identical(self, tmp_path):
        """The acceptance bar: a chaos soak killed and journal-recovered
        mid-sweep produces a report byte-identical to the uninterrupted
        same-seed run.  The supervisor polls its OWN plan, so the armed
        plan's fault schedule — and therefore the report — is untouched
        by the crash."""
        from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak
        from k8s_llm_rca_tpu.faults.supervisor import CrashSupervisor

        base = run_chaos_soak(seed=5, n_incidents=3, backend="oracle")
        sup = CrashSupervisor(
            FaultPlan([Fault(inject.SITE_PROCESS, 1, "crash")]),
            str(tmp_path / "serve.wal"))
        resumed = run_chaos_soak(seed=5, n_incidents=3, backend="oracle",
                                 durable_dir=str(tmp_path), supervisor=sup)
        assert sup.crashes == 1
        assert len(sup.recoveries) == 1
        assert sup.recoveries[0]["records"] > 0
        assert report_bytes(base) == report_bytes(resumed)
        assert resumed["failed"] == 0 and resumed["completed"] == 3
        # the journal survived the whole soak: it replays cleanly
        records, end = read_journal(str(tmp_path / "serve.wal"))
        assert records and end == os.path.getsize(
            str(tmp_path / "serve.wal"))

    def test_supervisor_requires_durable_dir(self):
        from k8s_llm_rca_tpu.faults.soak import run_chaos_soak
        from k8s_llm_rca_tpu.faults.supervisor import CrashSupervisor

        sup = CrashSupervisor(FaultPlan(), "/tmp/never-used.wal")
        with pytest.raises(ValueError, match="durable_dir"):
            run_chaos_soak(seed=0, n_incidents=1, backend="oracle",
                           supervisor=sup)

    def test_journaled_soak_report_matches_unjournaled(self, tmp_path):
        """Arming the journal alone (no supervisor) must not perturb the
        report: journaling adds no report fields and no clock reads."""
        from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak

        plain = run_chaos_soak(seed=7, n_incidents=2, backend="oracle")
        journaled = run_chaos_soak(seed=7, n_incidents=2, backend="oracle",
                                   durable_dir=str(tmp_path))
        assert report_bytes(plain) == report_bytes(journaled)
        assert os.path.getsize(str(tmp_path / "serve.wal")) > 0
