"""Grammar-constrained decoding tests.

The decisive property: a RANDOM-weight model decoding under the JSON
grammar must always produce ``json.loads``-able output — greedy or
stochastic, contiguous or paged engine, even when the token budget runs
out mid-structure (budget-aware force-close) or the sequence is preempted
and resumed.  This is what turns the reference's JSONDecodeError
retry-with-feedback loop (reference test_all.py:70-83) into dead code.
"""

import json

import jax
import pytest

from k8s_llm_rca_tpu.config import TINY, EngineConfig
from k8s_llm_rca_tpu.engine.constrain import (
    JsonCharAutomaton, JsonGrammar, make_grammar,
)
from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer


def feed(text):
    a = JsonCharAutomaton()
    for ch in text:
        if not a.accept(ch):
            return None
    return a


class TestJsonCharAutomaton:
    @pytest.mark.parametrize("text", [
        '{}', '[]', '"hi"', 'true', 'false', 'null', '0', '-12.5e+3',
        '{"a": 1}', '{"a": [1, 2, {"b": null}], "c": "x\\n"}',
        '[", \\" {] [", -0.5]', '{"u": "\\u00e9"}', '  { "k" : [ ] } ',
        '{"": 0}',
    ])
    def test_accepts_valid(self, text):
        a = feed(text)
        assert a is not None and a.can_terminate
        json.loads(text)   # sanity: stdlib agrees

    @pytest.mark.parametrize("text", [
        '{', '{"a" 1}', '{"a": 1,}', '[1 2]', '01', '1.', '1e', '--1',
        'tru', '{"a": }', '}', '"\\x"', '{"a": "b",}', '[1,]', 'nul ',
    ])
    def test_rejects_or_incomplete(self, text):
        a = feed(text)
        # either a character was rejected, or the value cannot end here
        assert a is None or not a.can_terminate

    def test_trailing_junk_rejected(self):
        a = feed('{"a": 1}')
        assert a.complete
        assert not a.accept('x')
        assert a.accept(' ')       # trailing whitespace is fine

    @pytest.mark.parametrize("prefix", [
        '', '{', '{"key', '{"key": ', '{"a": [1, {"b": "x', '-1.2e',
        '{"a": tr', '{"s": "esc\\',
    ])
    def test_minimal_completion_closes_any_prefix(self, prefix):
        a = feed(prefix)
        assert a is not None, prefix
        completion = a.minimal_completion()
        done = feed(prefix + completion)
        assert done is not None and done.can_terminate
        if prefix + completion:
            json.loads(prefix + completion)


class TestConstrainedEngine:
    def _engine(self, **ecfg_kw):
        cfg = TINY.replace(max_seq_len=256)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        defaults = dict(max_batch=4, max_seq_len=128, max_new_tokens=48,
                        prefill_buckets=(32, 64), temperature=0.0)
        defaults.update(ecfg_kw)
        ecfg = EngineConfig(**defaults)
        tok = get_tokenizer()
        eng = PagedInferenceEngine(cfg, ecfg, params, tok, use_kernel=False)
        return eng, tok

    def _run(self, eng, tok, prompts, **kw):
        ids = [eng.submit(tok.encode(p, add_bos=True),
                          grammar=JsonGrammar(tok), **kw) for p in prompts]
        results = {r.seq_id: r for r in eng.run_to_completion()}
        return [results[i] for i in ids]

    def test_greedy_random_model_emits_valid_json(self):
        eng, tok = self._engine()
        outs = self._run(eng, tok, ["report the incident as json",
                                    "another prompt entirely"])
        for r in outs:
            parsed = json.loads(r.text)   # must not raise
            assert parsed is not None or parsed is None  # any JSON value

    def test_stochastic_sampling_stays_in_grammar(self):
        eng, tok = self._engine(temperature=1.0, top_k=40)
        outs = self._run(eng, tok, ["a", "b", "c", "d"])
        for r in outs:
            json.loads(r.text)

    def test_budget_exhaustion_force_closes(self):
        # tiny budget: the FSM must close whatever structure it opened
        eng, tok = self._engine(temperature=1.0)
        outs = self._run(eng, tok, ["x", "y"], max_new_tokens=7)
        for r in outs:
            json.loads(r.text)
            assert len(r.token_ids) <= 7

    def test_paged_engine_with_preemption_keeps_grammar(self):
        # tight pool forces growth-path preemption mid-generation; the FSM
        # must survive the requeue/resume cycle
        eng, tok = self._engine(max_batch=3, max_seq_len=64,
                                page_size=8, num_pages=12,
                                prefill_buckets=(16,), temperature=1.0)
        outs = self._run(eng, tok, ["aaaaaaaaaaaa", "bbbbbbbbbbbb",
                                    "cccccccccccc"], max_new_tokens=24)
        assert len(outs) == 3
        for r in outs:
            json.loads(r.text)
        eng.allocator.check()

    def test_eos_finish_reason_and_no_trailing_garbage(self):
        eng, tok = self._engine()
        (r,) = self._run(eng, tok, ["emit json"])
        assert r.finish_reason in ("eos", "length")
        # json.loads only succeeds if the ENTIRE text is one JSON value
        # (plus whitespace) — parsing is itself the no-trailing-junk proof
        json.loads(r.text)


class TestBackendIntegration:
    def test_gen_options_grammar_roundtrip(self):
        from k8s_llm_rca_tpu.serve.api import AssistantService
        from k8s_llm_rca_tpu.serve.backend import EngineBackend, GenOptions

        cfg = TINY.replace(max_seq_len=256)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        ecfg = EngineConfig(max_batch=2, max_seq_len=128, max_new_tokens=32,
                            prefill_buckets=(32, 64))
        tok = get_tokenizer()
        backend = EngineBackend(PagedInferenceEngine(cfg, ecfg, params, tok))
        service = AssistantService(backend)
        asst = service.create_assistant(
            "emit json", "t", "m",
            gen=GenOptions(max_new_tokens=32, forced_prefix="```json\n",
                           suffix="\n```", grammar="json"))
        th = service.create_thread()
        service.add_message(th.id, "incident: pod failed")
        run = service.create_run(th.id, asst.id)
        run = service.wait_run(run.id)
        assert run.status == "completed"
        text = service.list_messages(th.id, limit=1).data[0] \
            .content[0].text.value
        assert text.startswith("```json\n") and text.endswith("\n```")
        body = text[len("```json\n"):-len("\n```")]
        json.loads(body)

    def test_make_grammar_rejects_unknown(self):
        with pytest.raises(ValueError):
            make_grammar("yaml", get_tokenizer())
        assert make_grammar(None, get_tokenizer()) is None


# ---------------------------------------------------------------------------
# schema-constrained decoding (structured outputs)
# ---------------------------------------------------------------------------

KINDS = ("ConfigMap", "Pod", "PodDisruptionBudget", "Secret", "nfs")

PLAN_SCHEMA = {"type": "object", "properties": [
    ("SourceKind", {"enum": list(KINDS)}),
    ("DestinationKind", {"enum": list(KINDS)}),
    ("RelevantResources", {"type": "array", "items": {"enum": list(KINDS)},
                           "min_items": 1, "max_items": 5}),
    ("PrimaryPath", {"type": "array", "min_items": 1, "max_items": 4,
                     "items": {"type": "object", "properties": [
                         ("Edge", {"type": "integer", "max_digits": 2}),
                         ("start", {"enum": list(KINDS)}),
                         ("end", {"enum": list(KINDS)})]}}),
]}


def schema_feed(schema, text):
    from k8s_llm_rca_tpu.engine.constrain import (
        SchemaAutomaton, _compile_schema,
    )

    a = SchemaAutomaton(_compile_schema(schema))
    for ch in text:
        if not a.accept(ch):
            return None
    return a


class TestSchemaAutomaton:
    def test_accepts_conforming_document(self):
        doc = ('{"SourceKind": "Pod", "DestinationKind": "Secret", '
               '"RelevantResources": ["Pod", "nfs"], '
               '"PrimaryPath": [{"Edge": 1, "start": "Pod", "end": "Secret"},'
               ' {"Edge": 12, "start": "PodDisruptionBudget", "end": "nfs"}]}')
        a = schema_feed(PLAN_SCHEMA, doc)
        assert a is not None and a.complete
        json.loads(doc)

    @pytest.mark.parametrize("doc", [
        '{"SourceKind": "Pox',                  # not an enum continuation
        '{"sourceKind',                         # wrong key
        '{"SourceKind": "Pod", "DestinationKind": "Pod", '
        '"RelevantResources": [], ',            # below min_items
        '{"SourceKind": "Pod", "DestinationKind": "Pod", '
        '"RelevantResources": ["Pod", "Pod", "Pod", "Pod", "Pod", "P',
        '{"SourceKind": 3',                     # wrong type
    ])
    def test_rejects_nonconforming(self, doc):
        assert schema_feed(PLAN_SCHEMA, doc) is None

    def test_enum_prefix_ambiguity(self):
        # "Pod" is a strict prefix of "PodDisruptionBudget": both the early
        # close and the continuation must be legal at the fork
        head = '{"SourceKind": "Pod'
        a = schema_feed(PLAN_SCHEMA, head)
        assert a.clone().accept('"')
        assert a.clone().accept('D')
        assert not a.clone().accept('X')

    @pytest.mark.parametrize("prefix", [
        '', '{', '{"SourceKind": "', '{"SourceKind": "PodD',
        '{"SourceKind": "Pod", "DestinationKind": "nfs", '
        '"RelevantResources": ["Secret"',
        '{"SourceKind": "Pod", "DestinationKind": "Pod", '
        '"RelevantResources": ["Pod"], "PrimaryPath": [{"Edge": 4',
    ])
    def test_minimal_completion_closes_any_prefix(self, prefix):
        a = schema_feed(PLAN_SCHEMA, prefix)
        assert a is not None, prefix
        completion = a.minimal_completion()
        done = schema_feed(PLAN_SCHEMA, prefix + completion)
        assert done is not None and done.complete
        parsed = json.loads(prefix + completion)
        assert parsed["DestinationKind"] in KINDS

    def test_integer_rules(self):
        schema = {"type": "object",
                  "properties": [("n", {"type": "integer", "max_digits": 3})]}
        assert schema_feed(schema, '{"n": 0}').complete
        assert schema_feed(schema, '{"n": 123}').complete
        assert schema_feed(schema, '{"n": 01') is None      # leading zero
        assert schema_feed(schema, '{"n": 1234') is None    # over max_digits

    def test_boolean_and_free_string(self):
        schema = {"type": "object", "properties": [
            ("ok", {"type": "boolean"}),
            ("note", {"type": "string", "max_len": 4})]}
        assert schema_feed(schema, '{"ok": true, "note": "ab"}').complete
        assert schema_feed(schema, '{"ok": false, "note": ""}').complete
        assert schema_feed(schema, '{"ok": maybe') is None
        assert schema_feed(schema, '{"ok": true, "note": "abcde') is None


class TestSchemaGrammar:
    def _random_walk(self, grammar, tok, budget, seed=0, pick="choice"):
        import numpy as np

        rng = np.random.default_rng(seed)
        out = []
        for step in range(budget):
            c = grammar.constraint(remaining=budget - step)
            if c.force is not None:
                t = c.force
            else:
                allowed = np.flatnonzero(c.allow)
                t = int(allowed[-1]) if pick == "last" \
                    else int(rng.choice(allowed))
            if t == tok.eos_id:
                return out
            grammar.advance(t)
            out.append(t)
        raise AssertionError("schema decode never terminated")

    def test_random_walk_parses_and_respects_enums(self):
        from k8s_llm_rca_tpu.engine.constrain import SchemaGrammar

        tok = get_tokenizer()
        for seed in range(3):
            g = SchemaGrammar(PLAN_SCHEMA, tok)
            ids = self._random_walk(g, tok, budget=600, seed=seed)
            parsed = json.loads(tok.decode(ids))
            assert set(parsed) == {"SourceKind", "DestinationKind",
                                   "RelevantResources", "PrimaryPath"}
            assert parsed["DestinationKind"] in KINDS
            assert all(r in KINDS for r in parsed["RelevantResources"])
            for edge in parsed["PrimaryPath"]:
                assert edge["start"] in KINDS and edge["end"] in KINDS

    def test_budget_force_close_still_parses(self):
        from k8s_llm_rca_tpu.engine.constrain import SchemaGrammar

        tok = get_tokenizer()
        g = SchemaGrammar(PLAN_SCHEMA, tok)
        lo = g.min_budget()
        for budget in (lo + 1, lo + 30):
            g = SchemaGrammar(PLAN_SCHEMA, tok)
            ids = self._random_walk(g, tok, budget=budget, pick="last")
            json.loads(tok.decode(ids))

    def test_min_budget_rejected_by_backend(self):
        from k8s_llm_rca_tpu.serve.api import AssistantService
        from k8s_llm_rca_tpu.serve.backend import EngineBackend, GenOptions

        cfg = TINY.replace(max_seq_len=256)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        ecfg = EngineConfig(max_batch=2, max_seq_len=256,
                            prefill_buckets=(64,))
        backend = EngineBackend(PagedInferenceEngine(cfg, ecfg, params,
                                                     get_tokenizer()))
        with pytest.raises(ValueError, match="minimal document"):
            backend.start("p", GenOptions(max_new_tokens=8,
                                          grammar=PLAN_SCHEMA))

    def test_engine_decode_under_schema(self):
        from k8s_llm_rca_tpu.engine.constrain import SchemaGrammar

        cfg = TINY.replace(max_seq_len=1024)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        ecfg = EngineConfig(max_batch=2, max_seq_len=1024,
                            prefill_buckets=(64,), max_new_tokens=512,
                            temperature=1.0)
        tok = get_tokenizer()
        eng = PagedInferenceEngine(cfg, ecfg, params, tok, use_kernel=False)
        sid = eng.submit(tok.encode("plan the incident", add_bos=True),
                         max_new_tokens=512,
                         grammar=SchemaGrammar(PLAN_SCHEMA, tok))
        (res,) = eng.run_to_completion()
        assert res.seq_id == sid
        parsed = json.loads(res.text)
        assert parsed["DestinationKind"] in KINDS

    def test_make_grammar_accepts_schema_dict(self):
        from k8s_llm_rca_tpu.engine.constrain import (
            DFAGrammar, SchemaGrammar,
        )

        g = make_grammar(PLAN_SCHEMA, get_tokenizer())
        # schemas compile to the DFA-backed grammar (SchemaGrammar is the
        # fallback for state-space blowups)
        assert isinstance(g, (DFAGrammar, SchemaGrammar))
        if isinstance(g, DFAGrammar):
            assert g.tables.n_states > 0


class TestCompiledDFA:
    """Schema grammars compiled to token-level DFA tables: on-device
    constrained decode (engine.decode_scan_dfa) with zero per-token host
    work.  The DFA must be constraint-for-constraint equivalent to the
    interpreted SchemaGrammar."""

    STRING_SCHEMA = {"type": "object", "properties": [
        ("note", {"type": "string", "max_len": 10}),
        ("n", {"type": "integer", "max_digits": 3}),
        ("ok", {"type": "boolean"})]}

    @staticmethod
    def _as_set(c):
        import numpy as np

        return ({int(c.force)} if c.force is not None
                else set(np.flatnonzero(c.allow).tolist()))

    @pytest.mark.parametrize("schema", [PLAN_SCHEMA, STRING_SCHEMA])
    def test_matches_interpreted_grammar(self, schema):
        import numpy as np

        from k8s_llm_rca_tpu.engine.constrain import (
            DFAGrammar, SchemaGrammar,
        )

        tok = get_tokenizer()
        for seed in range(3):
            rng = np.random.default_rng(seed)
            ref, dfa = SchemaGrammar(schema, tok), DFAGrammar(schema, tok)
            budget = 700
            for step in range(budget):
                sr = self._as_set(ref.constraint(remaining=budget - step))
                sd = self._as_set(dfa.constraint(remaining=budget - step))
                if len(sr) > 1 or len(sd) > 1:
                    # non-forced steps must agree exactly; forced closes
                    # may differ only in equally-minimal path choice
                    assert sr == sd, (seed, step, sorted(sr ^ sd)[:6])
                t = (next(iter(sr)) if len(sr) == 1
                     else int(rng.choice(sorted(sr))))
                if t == tok.eos_id:
                    break
                ref.advance(t)
                dfa.advance(t)
            else:
                raise AssertionError("walk never terminated")
            assert ref.done == dfa.done

    def test_make_grammar_compiles_schemas(self):
        from k8s_llm_rca_tpu.engine.constrain import DFAGrammar

        g = make_grammar(PLAN_SCHEMA, get_tokenizer())
        assert isinstance(g, DFAGrammar)
        assert g.tables.n_states > 100
        # tables are cached per tokenizer: same object on re-make
        tok = get_tokenizer()
        assert make_grammar(PLAN_SCHEMA, tok).tables \
            is make_grammar(PLAN_SCHEMA, tok).tables

    def test_engine_scan_mixed_grammar_and_free_slots(self):
        """A scan batch mixing one DFA-constrained slot with unconstrained
        slots: the FREE state row leaves free slots untouched."""
        tok = get_tokenizer()
        cfg = TINY.replace(max_seq_len=256)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        ecfg = EngineConfig(max_batch=3, max_seq_len=256,
                            prefill_buckets=(32,), max_new_tokens=200,
                            temperature=0.0, decode_chunk=8)
        eng = PagedInferenceEngine(cfg, ecfg, params, tok, use_kernel=False)
        gid = eng.submit(tok.encode("plan", add_bos=True),
                         grammar=make_grammar(PLAN_SCHEMA, tok),
                         max_new_tokens=200)
        fids = [eng.submit(tok.encode(p, add_bos=True), max_new_tokens=24)
                for p in ("free one", "free two")]
        # reference for the free slots: same engine config, no grammar slot
        ref_eng = PagedInferenceEngine(cfg, ecfg, params, tok,
                                       use_kernel=False)
        ref_ids = [ref_eng.submit(tok.encode(p, add_bos=True),
                                  max_new_tokens=24)
                   for p in ("free one", "free two")]
        res = {r.seq_id: r for r in eng.run_to_completion()}
        ref = {r.seq_id: r for r in ref_eng.run_to_completion()}
        json.loads(res[gid].text)
        for f, r in zip(fids, ref_ids):
            assert res[f].token_ids == ref[r].token_ids

    def test_engine_scan_fuses_heterogeneous_grammars(self):
        """Slots carrying DIFFERENT compiled schemas decode in ONE fused
        scan (offset-relabeled stacked tables) instead of degrading to
        stepwise ticks, and emit exactly what a stepwise engine emits.
        This is the shared-engine sweep shape: planner/reporter schemas
        from different workers in flight at once."""
        tok = get_tokenizer()
        other_schema = {"type": "object", "properties": [
            ("verdict", {"enum": ["healthy", "broken"]}),
            ("score", {"type": "integer", "max_digits": 2}),
        ]}
        cfg = TINY.replace(max_seq_len=256)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))

        def run(chunk):
            ecfg = EngineConfig(max_batch=3, max_seq_len=256,
                                prefill_buckets=(32,), max_new_tokens=200,
                                temperature=0.0, decode_chunk=chunk)
            eng = PagedInferenceEngine(cfg, ecfg, params, tok,
                                       use_kernel=False)
            a = eng.submit(tok.encode("plan", add_bos=True),
                           grammar=make_grammar(PLAN_SCHEMA, tok),
                           max_new_tokens=200)
            b = eng.submit(tok.encode("verdict", add_bos=True),
                           grammar=make_grammar(other_schema, tok),
                           max_new_tokens=64)
            c = eng.submit(tok.encode("free text", add_bos=True),
                           max_new_tokens=24)
            res = {r.seq_id: r for r in eng.run_to_completion()}
            return eng, (res[a], res[b], res[c])

        eng_scan, scan = run(chunk=8)
        _, step = run(chunk=1)
        for s, t in zip(scan, step):
            assert s.token_ids == t.token_ids
        assert json.loads(scan[0].text)["DestinationKind"] in KINDS
        v = json.loads(scan[1].text)
        assert v["verdict"] in ("healthy", "broken")
        # the fused path actually ran: one cache entry stacking BOTH tables
        fused = getattr(eng_scan, "_dfa_fused", {})
        assert any(len(key) == 2 for key in fused), list(fused)

    def test_engine_scan_continues_with_queued_admissions(self):
        """A full engine with pendings queued keeps taking chunked scan
        ticks (queued admissions no longer force per-token ticks); queued
        work still admits and completes, greedy-identical to stepwise."""
        tok = get_tokenizer()
        cfg = TINY.replace(max_seq_len=128)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))

        def run(chunk):
            ecfg = EngineConfig(max_batch=2, max_seq_len=128,
                                prefill_buckets=(32,), max_new_tokens=24,
                                temperature=0.0, decode_chunk=chunk)
            eng = PagedInferenceEngine(cfg, ecfg, params, tok,
                                       use_kernel=False)
            ids = [eng.submit(tok.encode(p, add_bos=True),
                              max_new_tokens=24)
                   for p in ("alpha", "beta", "gamma", "delta", "epsilon")]
            res = {r.seq_id: r for r in eng.run_to_completion()}
            return [res[i] for i in ids]

        scan, step = run(chunk=8), run(chunk=1)
        for s, t in zip(scan, step):
            assert s.token_ids == t.token_ids

    def test_engine_budget_force_close_on_device(self):
        """Tight budgets force-close THROUGH the scan: output still parses."""
        tok = get_tokenizer()
        cfg = TINY.replace(max_seq_len=512)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        ecfg = EngineConfig(max_batch=1, max_seq_len=512,
                            prefill_buckets=(32,), max_new_tokens=256,
                            temperature=1.0, top_k=40, decode_chunk=8)
        eng = PagedInferenceEngine(cfg, ecfg, params, tok, use_kernel=False)
        g = make_grammar(PLAN_SCHEMA, tok)
        budget = g.min_budget() + 8
        sid = eng.submit(tok.encode("x", add_bos=True), grammar=g,
                         max_new_tokens=budget)
        (res,) = eng.run_to_completion()
        assert res.seq_id == sid
        parsed = json.loads(res.text)
        assert parsed["DestinationKind"] in KINDS
        assert res.completion_tokens <= budget

    @pytest.mark.parametrize("page_size", [16, 8])
    def test_engine_chunked_scan_matches_stepwise(self, page_size):
        """The DFA rides inside the decode scan (chunk bounded by the
        slot's allocated pages): chunked greedy output == per-tick
        host-FSM output, and both parse + respect enums."""
        outs = {}
        tok = get_tokenizer()
        cfg = TINY.replace(max_seq_len=512)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        for chunk in (1, 8):
            ecfg = EngineConfig(max_batch=2, max_seq_len=512,
                                page_size=page_size,
                                num_pages=1280 // page_size,
                                prefill_buckets=(32,), max_new_tokens=256,
                                temperature=0.0, decode_chunk=chunk)
            eng = PagedInferenceEngine(cfg, ecfg, params, tok,
                                       use_kernel=False)
            ids = [eng.submit(tok.encode(p, add_bos=True),
                              grammar=make_grammar(PLAN_SCHEMA, tok),
                              max_new_tokens=256)
                   for p in ("plan a", "plan b")]
            res = {r.seq_id: r for r in eng.run_to_completion()}
            outs[chunk] = [res[i].text for i in ids]
            for text in outs[chunk]:
                parsed = json.loads(text)
                assert parsed["DestinationKind"] in KINDS
            eng.allocator.check()
        assert outs[1] == outs[8]

    def test_paged_scan_crosses_page_boundaries(self):
        """decode_chunk larger than page_size: the growth pass
        pre-allocates the scan window, the chunk crosses page boundaries
        inside one dispatch, and output is greedy-identical to stepwise
        (allocator invariants intact)."""
        outs = {}
        tok = get_tokenizer()
        cfg = TINY.replace(max_seq_len=256)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        for chunk in (1, 16):
            ecfg = EngineConfig(max_batch=2, max_seq_len=256,
                                page_size=4, num_pages=140,
                                prefill_buckets=(32,), max_new_tokens=48,
                                temperature=0.0, decode_chunk=chunk)
            eng = PagedInferenceEngine(cfg, ecfg, params, tok,
                                       use_kernel=False)
            ids = [eng.submit(tok.encode(p, add_bos=True),
                              max_new_tokens=48)
                   for p in ("free one", "free two")]
            res = {r.seq_id: r for r in eng.run_to_completion()}
            outs[chunk] = [res[i].token_ids for i in ids]
            eng.allocator.check()
        assert outs[1] == outs[16]

    def test_schema_string_escapes(self):
        """Opt-in escape pairs in schema strings: quoted kubectl/JSON
        content is expressible where the field declares escapes=True,
        rejected where it doesn't."""
        from k8s_llm_rca_tpu.engine.constrain import DFAGrammar

        schema = {"type": "object", "properties": [
            ("plain", {"type": "string", "max_len": 10}),
            ("cmd", {"type": "string", "max_len": 60, "escapes": True})]}
        ok = ('{"plain": "abc", '
              '"cmd": "kubectl -p \'{\\"a\\": \\"b\\"}\'"}')
        a = schema_feed(schema, ok)
        assert a is not None and a.complete
        import json as _json

        assert _json.loads(ok)["cmd"].count('"') == 4
        # a backslash in the non-escaping field is illegal
        assert schema_feed(schema, '{"plain": "a\\\\') is None
        # a lone backslash escapes the closing quote: the string (and the
        # document) must remain open
        dangling = schema_feed(schema, '{"plain": "abc", "cmd": "x\\"}')
        assert dangling is not None and not dangling.complete
        # an escaped backslash then quote closes it: valid JSON
        closed = schema_feed(schema, '{"plain": "abc", "cmd": "x\\\\"}')
        assert closed is not None and closed.complete
        # the DFA path accepts the same document
        tok = get_tokenizer()
        g = DFAGrammar(schema, tok)
        for t in tok.encode(ok):
            g.advance(t)
        assert g.done

    def test_report_schema_fits_32k_vocab_budget(self):
        """The RCA report schema must stay compilable to an on-device DFA
        at production vocab sizes (the on-device guarantee in docs/rca.md
        depends on it)."""
        from k8s_llm_rca_tpu.engine.constrain import (
            _DFA_MAX_TABLE_BYTES, _compile_schema, _enumerate_char_dfa,
        )
        from k8s_llm_rca_tpu.rca.auditor import report_schema

        tok = get_tokenizer()
        strings = [tok.decode([t]) for t in range(tok.vocab_size)]
        alphabet = sorted(set("".join(strings)))
        cn, _ = _enumerate_char_dfa(_compile_schema(report_schema()),
                                    alphabet, max_states=10**6)
        assert cn.shape[0] <= _DFA_MAX_TABLE_BYTES // (5 * 32000)


# ---------------------------------------------------------------------------
# raw-text template nodes (choice / seq) — the stage-2 Cypher skeleton
# grammar (rca/cyphergen.cypher_query_schema) is built from these
# ---------------------------------------------------------------------------


def test_choice_node_accepts_each_option_exactly():
    from k8s_llm_rca_tpu.engine.constrain import (
        SchemaAutomaton, _compile_schema,
    )

    schema = {"type": "choice", "options": ["MATCH (n:Pod)\nRETURN n",
                                            "MATCH (p:Node)\nRETURN p"]}
    for opt in schema["options"]:
        auto = SchemaAutomaton(_compile_schema(schema))
        for ch in opt:
            assert auto.accept(ch), (opt, ch)
        assert auto.complete
    # diverging from every option is rejected at the divergence point
    auto = SchemaAutomaton(_compile_schema(schema))
    for ch in "MATCH (":
        assert auto.accept(ch)
    assert not auto.accept("x")


def test_choice_node_rejects_prefix_pairs_and_empty():
    from k8s_llm_rca_tpu.engine.constrain import _compile_schema

    with pytest.raises(ValueError, match="prefix-free"):
        _compile_schema({"type": "choice", "options": ["ab", "abc"]})
    with pytest.raises(ValueError, match="non-empty"):
        _compile_schema({"type": "choice", "options": []})
    with pytest.raises(ValueError, match="non-empty"):
        _compile_schema({"type": "choice", "options": ["a", ""]})
    # a single option degrades to a literal
    assert _compile_schema({"type": "choice", "options": ["one"]}) == \
        ("lit", "one")


def test_seq_node_concatenates_raw():
    from k8s_llm_rca_tpu.engine.constrain import (
        SchemaAutomaton, _compile_schema,
    )

    schema = {"type": "seq", "items": [
        {"const": "score="},
        {"type": "integer", "max_digits": 2},
        {"const": ";"}]}
    auto = SchemaAutomaton(_compile_schema(schema))
    for ch in "score=42;":
        assert auto.accept(ch), ch
    assert auto.complete


def test_choice_engine_scan_emits_one_option_exactly():
    """A raw-text choice grammar through the REAL engine (DFA in-scan):
    random weights must emit one option verbatim, chunked == stepwise."""
    import jax

    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.models import llama

    cfg = TINY
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    schema = {"type": "choice", "options": [
        "MATCH (evt:EVENT)\nWHERE evt.message CONTAINS 'x'\nRETURN evt",
        "MATCH (pod:Pod)-[r1:HasEvent]->(evt:EVENT)\nRETURN pod, r1, evt"]}
    outs = {}
    for chunk in (1, 8):
        eng = PagedInferenceEngine(
            cfg, EngineConfig(max_batch=2, max_seq_len=256,
                              prefill_buckets=(16,), max_new_tokens=128,
                              decode_chunk=chunk), params, tok)
        rid = eng.submit(tok.encode("q:", add_bos=True), max_new_tokens=128,
                         grammar=make_grammar(schema, tok))
        res = {r.seq_id: r for r in eng.run_to_completion()}
        outs[chunk] = res[rid].text
    assert outs[1] == outs[8]
    assert outs[1] in schema["options"]


def test_cypher_schema_variants_compile_and_run():
    """cypher_query_schema's options are exactly the deterministic
    compiler's two alias styles, and BOTH execute against the stategraph
    (valid mini-Cypher, same records)."""
    from k8s_llm_rca_tpu.graph import InMemoryGraphExecutor
    from k8s_llm_rca_tpu.graph.fixtures import INCIDENTS, build_stategraph
    from k8s_llm_rca_tpu.rca import cyphergen

    mp = ("\n    HasEvent, Event, EVENT, metadata_uid;\n"
          "    ReferInternal, Event, Pod, involvedObject_uid;\n"
          "    ReferInternal, Pod, ConfigMap, spec_volumes_configMap_name;\n")
    msg = INCIDENTS[0].message
    schema = cyphergen.cypher_query_schema(mp, msg)
    assert schema["type"] == "choice" and len(schema["options"]) == 2
    ex = InMemoryGraphExecutor(build_stategraph())
    results = [ex.run_query(q) for q in schema["options"]]
    assert len(results[0]) == len(results[1])


def test_choice_dedups_by_value_and_seq_rejects_empty():
    from k8s_llm_rca_tpu.engine.constrain import _compile_schema

    s = "same option"
    assert _compile_schema({"type": "choice", "options": [s, s]}) == \
        ("lit", s)
    with pytest.raises(ValueError, match="non-empty"):
        _compile_schema({"type": "seq", "items": []})


def test_template_grammar_dfa_policy():
    """Small template (choice/seq) grammars now COMPILE to DFA tables so
    they ride the fused on-device scan (an interpreted slot would force
    the whole shared batch to stepwise host ticks); templates whose
    estimated table exceeds the one-shot budget still route to the
    interpreted FSM, which forces agreed spans O(1) per tick."""
    from k8s_llm_rca_tpu.engine.constrain import (
        _DFA_TEMPLATE_TABLE_BYTES, DFAGrammar, SchemaGrammar,
    )

    tok = get_tokenizer()
    schema = {"type": "choice", "options": ["alpha variant one",
                                            "beta variant two"]}
    g = make_grammar(schema, tok)
    assert isinstance(g, DFAGrammar)

    # oversized template: estimate (json chars x vocab x 5B) > budget
    n = _DFA_TEMPLATE_TABLE_BYTES // (tok.vocab_size * 5) + 64
    big = {"type": "choice", "options": ["x" * n, "y" * n]}
    g_big = make_grammar(big, tok)
    assert isinstance(g_big, SchemaGrammar)
    # after the first char narrows to one candidate, the span is forced
    g_big.advance(tok.encode("x")[0])
    c = g_big.constraint(4 * n)
    assert c.force is not None


# ---------------------------------------------------------------------------
# bounded any-JSON DFA (grammar="json" on the fast path)
# ---------------------------------------------------------------------------


def test_bounded_json_automaton_accepts_canonical_docs():
    from k8s_llm_rca_tpu.engine.constrain import (
        SchemaAutomaton, _compile_schema,
    )

    root = _compile_schema({"type": "json"})
    for doc in ['true', 'null', '"hi there"', '[]', '[1, 2, 3]', '[42]',
                '{}', '{"a": 1, "b": [true, "x"]}', '[{"k": null}]',
                '{"s": "with \\"esc\\" ok"}']:
        auto = SchemaAutomaton(root)
        assert all(auto.accept(ch) for ch in doc) and auto.complete, doc


def test_bounded_json_depth_cap_rejects():
    from k8s_llm_rca_tpu.engine.constrain import (
        SchemaAutomaton, _compile_schema,
    )

    auto = SchemaAutomaton(_compile_schema({"type": "json", "max_depth": 2}))
    assert not all(auto.accept(ch) for ch in "[[[[")


def test_json_grammar_compiles_to_dfa_and_scan_parity():
    """grammar="json" now rides the on-device DFA scan (round-2 review item
    6): chunked scan and stepwise host ticks emit identical parseable
    JSON from random weights."""
    import jax
    import json as jsonlib

    from k8s_llm_rca_tpu.config import TINY, EngineConfig
    from k8s_llm_rca_tpu.engine.constrain import DFAGrammar
    from k8s_llm_rca_tpu.models import llama

    cfg = TINY
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    outs = {}
    for chunk in (1, 8):
        eng = PagedInferenceEngine(
            cfg, EngineConfig(max_batch=2, max_seq_len=256,
                              prefill_buckets=(16,), max_new_tokens=64,
                              decode_chunk=chunk), params, tok)
        g = make_grammar("json", tok)
        assert isinstance(g, DFAGrammar)
        rid = eng.submit(tok.encode("emit json:", add_bos=True),
                         max_new_tokens=64, grammar=g)
        res = {r.seq_id: r for r in eng.run_to_completion()}
        outs[chunk] = res[rid].text
    assert outs[1] == outs[8]
    jsonlib.loads(outs[1])


def test_json_node_composes_inside_schema():
    """{"type": "json"} as a FIELD of a structured output: bounded free-
    form JSON inside a fixed envelope."""
    from k8s_llm_rca_tpu.engine.constrain import (
        SchemaAutomaton, _compile_schema,
    )

    schema = {"type": "object", "properties": [
        ("tag", {"enum": ["ok"]}),
        ("data", {"type": "json", "max_depth": 1})]}
    for doc in ('{"tag": "ok", "data": [1, true, "x"]}',
                # nested json keeps the bare-int child: the envelope's
                # closing brace is the delimiter that pops it
                '{"tag": "ok", "data": 7}'):
        auto = SchemaAutomaton(_compile_schema(schema))
        assert all(auto.accept(ch) for ch in doc) and auto.complete, doc
