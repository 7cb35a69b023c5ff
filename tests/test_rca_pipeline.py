"""End-to-end RCA pipeline tests — hermetic: in-memory graphs + scripted
oracle backend (BASELINE config[0]-style slice, no weights, no network)."""

import json

import pytest

from k8s_llm_rca_tpu.config import RCAConfig
from k8s_llm_rca_tpu.graph import InMemoryGraphExecutor
from k8s_llm_rca_tpu.graph.fixtures import (
    INCIDENTS, build_metagraph, build_stategraph,
)
from k8s_llm_rca_tpu.rca import RCAPipeline
from k8s_llm_rca_tpu.rca.cyphergen import (
    compile_metapath_query, parse_metapath_string,
)
from k8s_llm_rca_tpu.rca.oracle import OracleBackend
from k8s_llm_rca_tpu.serve.api import AssistantService
from k8s_llm_rca_tpu.utils import get_tokenizer


def make_pipeline(chaos=None) -> RCAPipeline:
    service = AssistantService(OracleBackend(get_tokenizer(), chaos=chaos))
    return RCAPipeline(
        service=service,
        meta_executor=InMemoryGraphExecutor(build_metagraph()),
        state_executor=InMemoryGraphExecutor(build_stategraph()),
        cfg=RCAConfig(),
    )


@pytest.fixture(scope="module")
def pipeline():
    return make_pipeline()


@pytest.mark.parametrize("incident", INCIDENTS, ids=lambda i: i.name)
def test_incident_end_to_end(pipeline, incident):
    result = pipeline.analyze_incident(incident.message)

    assert result["error_message"] == incident.message
    assert result["locator_attempts"] == 1
    assert result["time_cost"] > 0
    assert result["token_usage"]["total_tokens"] > 0
    assert result["analysis"], "no metapath produced an analysis"

    analysis = result["analysis"][0]
    assert "HasEvent, Event, EVENT, metadata_uid;" in analysis["extend_metapath"]
    assert analysis["statepath"], "no statepath records audited"

    sp = analysis["statepath"][0]
    report = json.loads(sp["report"])          # oracle emits strict JSON
    assert {"summary", "conclusion", "resolution"} <= set(report)
    assert "kubectl" in report["resolution"]

    clue_text = json.dumps(sp["clue"])
    for kind in incident.expect_missing_state:
        assert "there is not a STATE" in clue_text
        # the missing kind scores high in the summary
        scores = {s["kind"]: s["relevance_score"] for s in report["summary"]}
        assert scores.get(kind) == "9", scores
    audited = set(sp["clue"].keys())
    for kind in incident.expect_state_kinds:
        assert any(k.startswith(f"{kind}(") for k in audited), (kind, audited)


def test_fresh_threads_bound_prompt_growth():
    """cfg.fresh_threads re-anchors each incident on fresh, re-seeded
    stage threads: the locator's prompt size stays flat across a sweep
    (the reference-style shared thread grows monotonically and overflows
    a real engine's cache budget), while reports stay intact."""
    grown = make_pipeline()
    fresh = make_pipeline()
    fresh.cfg = RCAConfig(fresh_threads=True)

    def locator_prompts(p):
        svc = p.service
        runs = [r for r in svc.runs.values()
                if r.assistant_id == p.locator.assistant.id]
        return [r.usage["prompt_tokens"] for r in
                sorted(runs, key=lambda r: int(r.id.split("_")[1]))]

    m = INCIDENTS[0].message           # same incident: prompt size is then
    for _ in range(4):                 # a pure function of thread growth
        r_grown = grown.analyze_incident(m)
        r_fresh = fresh.analyze_incident(m)
        assert r_fresh["analysis"]
        # same analysis content either way: prompts are self-contained
        assert len(r_fresh["analysis"]) == len(r_grown["analysis"])
    pg, pf = locator_prompts(grown), locator_prompts(fresh)
    assert pg[-1] > pg[0], "shared thread should grow across incidents"
    assert pf == [pf[0]] * len(pf), \
        f"fresh threads should stay exactly flat, got {pf}"


def test_decoy_record_is_filtered(pipeline):
    """Incident 1 matches two Secrets; message compatibility must drop the
    decoy (reference :88-129)."""
    result = pipeline.analyze_incident(INCIDENTS[0].message)
    statepaths = result["analysis"][0]["statepath"]
    assert len(statepaths) == 1
    assert "Secret(sec-0001)" in statepaths[0]["clue"]
    assert "sec-0002" not in json.dumps(statepaths[0]["clue"])


def test_chaos_retry_with_feedback():
    """First oracle replies are malformed: the locator retries with the
    exception text fed back; the cypher stage falls back to the
    deterministic compiler.  The incident must still complete."""
    pipeline = make_pipeline(chaos={"plan": 1})
    result = pipeline.analyze_incident(INCIDENTS[0].message)
    assert result["locator_attempts"] == 2
    assert result["analysis"][0]["statepath"]
    # the feedback message is in the locator thread
    thread_text = " ".join(
        m.raw_content for m in pipeline.locator.thread.messages)
    assert "JSON Error occurred" in thread_text


def test_chaos_cypher_fallback():
    """Chaos hits planning once, then the cypher generator once: the
    deterministic compiler must still produce records."""
    pipeline = make_pipeline(chaos={"plan": 1, "cypher": 1})
    result = pipeline.analyze_incident(INCIDENTS[1].message)
    analysis = result["analysis"][0]
    assert analysis["cypher_attempts"] > 1 or "human_cypher_query" in analysis
    assert analysis["statepath"]


def test_deterministic_compiler_golden():
    metapath = """
    HasEvent, Event, EVENT, metadata_uid;
    ReferInternal, Event, Pod, involvedObject_uid;
    ReferInternal, Pod, Secret, spec_volumes_secret_secretName;
    """
    q = compile_metapath_query(metapath, 'secret "x" not found')
    assert q.splitlines()[0] == "MATCH (evt:EVENT)"
    assert "WHERE evt.message CONTAINS 'secret \"x\" not found'" in q
    assert "MATCH (n1:Event)-[r1:HasEvent]->(evt:EVENT)" in q
    assert "WHERE r2.key = 'involvedObject_uid'" in q
    assert q.rstrip().endswith("RETURN evt, r1, n1, r2, n2, r3, n3")


def test_metapath_string_roundtrip():
    edges = parse_metapath_string(
        "HasEvent, Event, EVENT, metadata_uid; "
        "ReferInternal, Event, Pod, involvedObject_uid;")
    assert edges == [
        ["HasEvent", "Event", "EVENT", "metadata_uid"],
        ["ReferInternal", "Event", "Pod", "involvedObject_uid"]]


def test_pipeline_on_real_engine_backend_is_crash_safe():
    """Chaos: the full pipeline driven by the REAL inference engine with
    random weights and grammar-constrained JSON.  Random weights produce
    valid-but-meaningless JSON, so the run must either complete with the
    result schema or exhaust its retry budget with the reference's
    RuntimeError — never hang, corrupt engine state, or die on a parse.
    """
    import jax

    from k8s_llm_rca_tpu.config import TINY, EngineConfig, RCAConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.graph import InMemoryGraphExecutor
    from k8s_llm_rca_tpu.graph.fixtures import (
        INCIDENTS, build_metagraph, build_stategraph,
    )
    from k8s_llm_rca_tpu.models import llama
    from k8s_llm_rca_tpu.rca import RCAPipeline
    from k8s_llm_rca_tpu.serve.api import AssistantService
    from k8s_llm_rca_tpu.serve.backend import EngineBackend
    from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

    cfg = TINY.replace(max_seq_len=512)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    engine = make_engine(
        cfg, EngineConfig(max_batch=2, max_seq_len=512,
                          page_size=16, num_pages=256,
                          prefill_buckets=(128, 256, 512),
                          max_new_tokens=48, temperature=0.0),
        params, tok, use_kernel=False)
    pipeline = RCAPipeline(
        AssistantService(EngineBackend(engine)),
        InMemoryGraphExecutor(build_metagraph()),
        InMemoryGraphExecutor(build_stategraph()),
        RCAConfig())
    try:
        result = pipeline.analyze_incident(INCIDENTS[0].message)
        # completed despite a nonsense model: schema must hold
        assert "error_message" in result and "time_cost" in result
        assert "locator_attempts" in result
    except RuntimeError as e:
        # reference behavior: budget exhausted after retry-with-feedback
        assert "attempts" in str(e)
    # engine state stays clean for the next run either way
    engine.allocator.check()
    assert not engine.has_work


@pytest.mark.parametrize("page_size", [64, 16])
def test_incident_completes_on_engine_backend(page_size):
    """round-1 review item 3: the full pipeline on the REAL engine with random
    weights must COMPLETE — not merely fail gracefully.  Stage 1 is
    schema-constrained to the kind vocabulary (structured outputs), so the
    plan always names real kinds; stage 2 falls back to the deterministic
    compiler; stage 3 audits are free text.  Content is garbage, structure
    is valid (the reference needs GPT-4 for the same guarantee,
    find_srckind_metapath_neo4j.py:20-45).  Exercises prefix caching
    (shared audit prefixes, at two page sizes: what is shared is whole
    pages) and the DFA scan through the whole agent loop."""
    import jax

    from k8s_llm_rca_tpu.config import TINY, EngineConfig, RCAConfig
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.models import llama
    from k8s_llm_rca_tpu.serve.backend import EngineBackend

    cfg = TINY.replace(max_seq_len=4096)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    engine = make_engine(
        cfg, EngineConfig(max_batch=4, max_seq_len=4096,
                          prefill_buckets=(512, 1024, 2048, 4096),
                          max_new_tokens=96, temperature=0.0,
                          page_size=page_size,
                          num_pages=420 * 64 // page_size, decode_chunk=8),
        params, tok, use_kernel=False)
    pipeline = RCAPipeline(
        AssistantService(EngineBackend(engine)),
        InMemoryGraphExecutor(build_metagraph()),
        InMemoryGraphExecutor(build_stategraph()),
        RCAConfig(cypher_max_new_tokens=96, analyzer_max_new_tokens=96))

    result = pipeline.analyze_incident(INCIDENTS[0].message)

    # structured stage 1 must succeed on the FIRST attempt: no JSON retry
    assert result["locator_attempts"] == 1
    assert result["error_message"] == INCIDENTS[0].message
    assert result["time_cost"] > 0
    assert result["token_usage"]["total_tokens"] > 0
    # the plan's DestinationKind was vocabulary-constrained, so the metapath
    # ladder ran; whatever it matched carries the full analysis schema
    for analysis in result["analysis"]:
        assert "extend_metapath" in analysis
        # stage 2 is skeleton-grammar-constrained (cypher_query_schema):
        # even random weights emit a valid query on the FIRST attempt, so
        # the reference's retry loop (test_all.py:99-122) is dead code here
        # like stage 1's.  (The zero-record fallback can still fire for
        # metapaths that genuinely match nothing — it then compiles the
        # SAME skeleton, so it must agree with the generated query.)
        assert analysis["cypher_attempts"] == 1
        assert analysis["cypher_query"] is not None
        if "human_cypher_query" in analysis:
            from k8s_llm_rca_tpu.rca import cyphergen as _cg

            assert analysis["cypher_query"] in (
                _cg.compile_metapath_query(
                    analysis["extend_metapath"], result["error_message"],
                    alias_style=s, quiet=True)
                for s in ("numeric", "kind"))
        for audited in analysis["statepath"]:
            # the reporter's schema grammar guarantees the report parses in
            # the reference shape even from random weights
            report = json.loads(audited["report"])
            assert {"summary", "conclusion", "resolution"} <= set(report)
            for item in report["summary"]:
                assert item["relevance_score"] in {str(i) for i in range(11)}
            assert isinstance(audited["clue"], dict)
    assert not engine.has_work
    engine.allocator.check()       # allocator-internal invariants
    # true no-leak check: after drain, every owned page belongs to the
    # prefix cache (retired sequences freed or transferred theirs)
    assert engine.allocator.n_free + engine.prefix_cache.n_resident \
        == engine.engine_cfg.num_pages - 1


def test_auditor_rejects_label_injection():
    """Cypher can't parameterize labels; kinds interpolated into label
    position must be identifier-whitelisted (round-1 review weak #7)."""
    from k8s_llm_rca_tpu.rca.auditor import (
        ad_hoc_find_entity_name, find_loose_states, find_strict_states,
    )

    for evil in ("Pod) MATCH (x", "Pod:Admin", "Pod`", "", "1Pod",
                 "Pod WITH x"):
        with pytest.raises(ValueError, match="unsafe entity kind"):
            find_strict_states(evil, "id-1", "2020-12-07T01:00:00Z")
        with pytest.raises(ValueError, match="unsafe entity kind"):
            find_loose_states(evil, "id-1", "t0", "t1")
        with pytest.raises(ValueError, match="unsafe entity kind"):
            ad_hoc_find_entity_name(evil, "id-1", None)
    # the whole fixture vocabulary is label-safe
    meta = InMemoryGraphExecutor(build_metagraph())
    from k8s_llm_rca_tpu.rca.locator import find_native_external_kinds
    native, external = find_native_external_kinds(meta)
    for kind in native + external:
        assert "MATCH" in find_strict_states(kind, "x", "t")


def test_cypher_budget_error_skips_retries_to_fallback():
    """A BudgetError (grammar's minimal document exceeds the effective
    budget) is futile to retry — compile_and_run must go STRAIGHT to the
    deterministic fallback on attempt 1 instead of burning the retry
    budget on identical failures."""
    from k8s_llm_rca_tpu.rca import cyphergen
    from k8s_llm_rca_tpu.serve.backend import BudgetError

    class BudgetBackend:
        def start(self, prompt, opts):
            raise BudgetError("budget 4 cannot hold the minimal document")

        def pump(self):
            return {}

        def busy(self, handle):
            return False

        def cancel(self, handle):
            pass

        def count_tokens(self, text):
            return len(text.split())

    pipeline = RCAPipeline.__new__(RCAPipeline)
    pipeline.cfg = RCAConfig()
    pipeline.state_executor = InMemoryGraphExecutor(build_stategraph())
    service = AssistantService(BudgetBackend())
    gen = cyphergen.setup_cypher_generator(service)
    pipeline.cypher_generator = gen

    mp = ("\n    HasEvent, Event, EVENT, metadata_uid;\n"
          "    ReferInternal, Event, Pod, involvedObject_uid;\n")
    analysis = {}
    records = pipeline.compile_and_run(mp, INCIDENTS[0].message, analysis)
    assert analysis["cypher_attempts"] == 1          # no futile retries
    assert "human_cypher_query" in analysis          # fallback fired
    assert isinstance(records, list)
