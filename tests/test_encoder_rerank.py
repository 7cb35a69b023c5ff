"""Encoder + embed/rerank tests (BASELINE config[4] path).

Invariants: bidirectionality (a late-token perturbation changes early
hidden states — the opposite of the decoder's causality test), padding
invariance (padded positions must not leak into the pooled embedding),
unit-norm pooling, deterministic rerank ordering, and the pipeline
integration (rerank_scores present and record order by score).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_rca_tpu.config import TINY_ENCODER, RCAConfig
from k8s_llm_rca_tpu.models import encoder
from k8s_llm_rca_tpu.rca.rerank import (
    Embedder, Reranker, cosine_rerank, _record_text,
)


@pytest.fixture(scope="module")
def enc_setup():
    cfg = TINY_ENCODER
    params = encoder.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_forward_shapes_and_finite(enc_setup):
    cfg, params = enc_setup
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    hidden = encoder.forward(cfg, params, tokens)
    assert hidden.shape == (2, 16, cfg.hidden_size)
    assert bool(jnp.all(jnp.isfinite(hidden)))


def test_bidirectional(enc_setup):
    """Perturbing a LATE token must change EARLY hidden states (no causal
    mask — this is the defining difference from the decoder)."""
    cfg, params = enc_setup
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 12), 0,
                                cfg.vocab_size)
    perturbed = tokens.at[0, 10].set((tokens[0, 10] + 1) % cfg.vocab_size)
    ha = encoder.forward(cfg, params, tokens)
    hb = encoder.forward(cfg, params, perturbed)
    assert not np.allclose(ha[0, :5], hb[0, :5], atol=1e-5)


def test_padding_invariance(enc_setup):
    """Same valid tokens under different pad widths -> same embedding."""
    cfg, params = enc_setup
    base = jax.random.randint(jax.random.PRNGKey(3), (1, 6), 0,
                              cfg.vocab_size)
    lengths = jnp.array([6], jnp.int32)
    short = jnp.zeros((1, 8), jnp.int32).at[:, :6].set(base)
    long = jnp.full((1, 16), 99, jnp.int32).at[:, :6].set(base)
    ea = encoder.embed(cfg, params, short, lengths)
    eb = encoder.embed(cfg, params, long, lengths)
    np.testing.assert_allclose(np.asarray(ea), np.asarray(eb),
                               rtol=1e-4, atol=1e-4)


def test_embed_unit_norm(enc_setup):
    cfg, params = enc_setup
    tokens = jax.random.randint(jax.random.PRNGKey(4), (3, 10), 0,
                                cfg.vocab_size)
    vecs = encoder.embed(cfg, params, tokens)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(vecs), axis=-1),
                               np.ones(3), rtol=1e-5)


def test_embedder_batches_and_buckets():
    emb = Embedder(buckets=(8, 16), batch_size=2)
    texts = ["pod failed", "a much longer message about a configmap that "
             "does not exist in the namespace", "x", "secret missing"]
    vecs = emb.encode(texts)
    assert vecs.shape == (4, emb.cfg.hidden_size)
    np.testing.assert_allclose(np.linalg.norm(vecs, axis=-1), np.ones(4),
                               rtol=1e-5)
    # per-text embedding must not depend on batch composition
    solo = emb.encode([texts[1]])
    np.testing.assert_allclose(vecs[1], solo[0], rtol=1e-4, atol=1e-4)


def test_cosine_rerank_orders_by_similarity():
    q = np.array([1.0, 0.0], np.float32)
    p = np.array([[0.6, 0.8], [1.0, 0.0], [0.0, 1.0]], np.float32)
    ranked = cosine_rerank(q, p)
    assert [i for i, _ in ranked] == [1, 0, 2]
    assert ranked[0][1] == pytest.approx(1.0)


def test_reranker_identical_passage_wins():
    """The passage equal to the query must embed closest to it."""
    rr = Reranker()
    query = "MountVolume failed for volume secret not found"
    passages = ["completely unrelated text about networking",
                query,
                "another unrelated row"]
    ranked = rr.rerank(query, passages)
    assert ranked[0][0] == 1


def test_record_text_flattens_graph_elements():
    from k8s_llm_rca_tpu.graph.store import Node

    n1 = Node("e1", ["Entity"], {"kind": "pod", "name2": "web-1"})
    n2 = Node("e2", ["Entity"], {"kind": "secret", "val": "db-cred"})
    text = _record_text([n1, n2])
    assert "pod" in text and "web-1" in text and "db-cred" in text


def test_pipeline_rerank_integration():
    """Full hermetic pipeline with a reranker: rerank_scores recorded,
    descending, and statepath audits still produce reports."""
    from k8s_llm_rca_tpu.graph import InMemoryGraphExecutor
    from k8s_llm_rca_tpu.graph.fixtures import (
        INCIDENTS, build_metagraph, build_stategraph,
    )
    from k8s_llm_rca_tpu.rca import RCAPipeline
    from k8s_llm_rca_tpu.rca.oracle import OracleBackend
    from k8s_llm_rca_tpu.serve.api import AssistantService
    from k8s_llm_rca_tpu.utils import get_tokenizer

    pipeline = RCAPipeline(
        AssistantService(OracleBackend(get_tokenizer())),
        InMemoryGraphExecutor(build_metagraph()),
        InMemoryGraphExecutor(build_stategraph()),
        RCAConfig(),
        reranker=Reranker())
    result = pipeline.analyze_incident(INCIDENTS[0].message)
    assert result["analysis"], "pipeline found no metapaths"
    audited = [sp for a in result["analysis"] for sp in a["statepath"]]
    assert audited, "no statepath audits ran"
    for analysis in result["analysis"]:
        scores = analysis.get("rerank_scores")
        if scores is not None:
            assert scores == sorted(scores, reverse=True)


def test_quantized_encoder_embeddings_correlate(enc_setup):
    # the encoder consumes weights through dq/gather_rows, so int8/int4
    # quantized params run the same code; pooled embeddings must stay
    # close to full precision (cosine similarity per row)
    from k8s_llm_rca_tpu.models.quant import quantize_params

    cfg, params = enc_setup
    tokens = jax.random.randint(jax.random.PRNGKey(5), (3, 12), 0,
                                cfg.vocab_size)
    ref = np.asarray(encoder.embed(cfg, params, tokens))
    for bits, floor in ((8, 0.999), (4, 0.98)):
        qp = quantize_params(params, compute_dtype=jnp.float32, bits=bits)
        got = np.asarray(encoder.embed(cfg, qp, tokens))
        cos = np.sum(ref * got, axis=-1)     # both unit-norm
        assert np.all(cos > floor), (bits, cos)


def test_project_fields_reranks_and_caps():
    """Field-level rerank fusion (BASELINE configs[4]): _project_fields
    keeps the top-k fields BY RELEVANCE (not list position), in stable
    field order, and the resulting prompt is strictly smaller."""
    from k8s_llm_rca_tpu.rca import auditor

    class FakeNode(dict):
        def __getitem__(self, k):
            return self.get(k)

    node = FakeNode(kind="POD", id="s1",
                    status={"phase": "Pending", "reason": "unschedulable"},
                    spec={"volumes": [{"secret": "db-cred"}]},
                    data={"huge": "x" * 200},
                    metadata={"name": "web-1"})

    class FakeReranker:
        def rerank(self, query, passages, top_k=None):
            # rank 'spec' and 'status' highest regardless of position
            order = sorted(range(len(passages)),
                           key=lambda i: (not passages[i].startswith("spec"),
                                          not passages[i].startswith("status")))
            return [(i, 1.0) for i in order[:top_k]]

    fields = auditor._project_fields(node, "secret not found",
                                     FakeReranker(), fields_top_k=2)
    assert fields == ["status", "spec"]       # stable IMPORTANT_FIELDS order
    full = auditor._semantic_prompt(node, "secret not found")
    slim = auditor._semantic_prompt(node, "secret not found", fields)
    assert len(slim) < len(full)
    assert "huge" not in slim and "x" * 50 not in slim
    # no reranker / top_k=0 / few fields: unchanged reference projection
    assert auditor._project_fields(node, "m") == ["status", "spec", "data",
                                                  "metadata"]
    assert auditor._project_fields(node, "m", FakeReranker(), 0) == \
        ["status", "spec", "data", "metadata"]


def test_rerank_fused_prompts_shrink_and_preserve_findings():
    """round-2 review item 8: with field-level rerank fusion ON, the analyzer
    reads FEWER prompt tokens for the same incident while the report's
    findings (clue labels, missing-STATE scores, report schema) are
    preserved."""
    import json

    from k8s_llm_rca_tpu.graph import InMemoryGraphExecutor
    from k8s_llm_rca_tpu.graph.fixtures import (
        INCIDENTS, build_metagraph, build_stategraph,
    )
    from k8s_llm_rca_tpu.rca import RCAPipeline
    from k8s_llm_rca_tpu.rca.oracle import OracleBackend
    from k8s_llm_rca_tpu.serve.api import AssistantService
    from k8s_llm_rca_tpu.utils import get_tokenizer

    def run(cfg):
        pipeline = RCAPipeline(
            AssistantService(OracleBackend(get_tokenizer())),
            InMemoryGraphExecutor(build_metagraph()),
            InMemoryGraphExecutor(build_stategraph()),
            cfg, reranker=Reranker())
        result = pipeline.analyze_incident(INCIDENTS[3].message)
        tokens = result["token_usage"]["prompt_tokens"]
        labels = sorted(k for a in result["analysis"]
                        for sp in a["statepath"] for k in sp["clue"])
        reports = [json.loads(sp["report"]) for a in result["analysis"]
                   for sp in a["statepath"]]
        return tokens, labels, reports

    base_tokens, base_labels, base_reports = run(RCAConfig())
    slim_tokens, slim_labels, slim_reports = run(
        RCAConfig(rerank_fields_top_k=2))

    assert slim_tokens < base_tokens, (slim_tokens, base_tokens)
    assert slim_labels == base_labels          # same entities audited
    for rep in slim_reports:                   # report contract preserved
        assert {"summary", "conclusion", "resolution"} <= set(rep)
