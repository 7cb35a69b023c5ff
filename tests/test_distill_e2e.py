"""Hermetic CONTENT-level validation: distill the oracle into TINY, then
run the RCA pipeline through the real engine with grammars OFF.

Every other e2e path either uses the scripted oracle directly or leans on
grammar-constrained decode to keep a random-weight model's output
structurally valid; `tests/test_real_weights.py` stays skipped in this
zero-egress image.  This test closes the content gap with zero external
weights: the model itself must produce the correct plan (right
DestinationKind), a working Cypher query (no deterministic fallback), and
a parseable scored report — tokenize -> train (engine/train.py on a mesh)
-> Orbax checkpoint (utils/checkpoint.py) -> safetensors export ->
models/loader.py reload -> serve (engine + assistants service) -> RCA.

SURVEY §4's deterministic-small-model prescription, upgraded from
"scripted backend" to "trained weights through the full serving stack".
"""

import json

import jax
import numpy as np

from k8s_llm_rca_tpu.config import TINY, EngineConfig, MeshConfig, RCAConfig
from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
from k8s_llm_rca_tpu.graph import InMemoryGraphExecutor
from k8s_llm_rca_tpu.graph.fixtures import (
    INCIDENTS, build_metagraph, build_stategraph,
)
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.models.loader import (
    llama_params_to_hf, load_llama, write_safetensors,
)
from k8s_llm_rca_tpu.rca.distill import (
    build_rows, collect_transcripts, distill,
)
from k8s_llm_rca_tpu.rca.pipeline import RCAPipeline
from k8s_llm_rca_tpu.runtime.mesh import build_mesh
from k8s_llm_rca_tpu.serve.api import AssistantService
from k8s_llm_rca_tpu.serve.backend import EngineBackend
from k8s_llm_rca_tpu.utils.checkpoint import restore_params, save_params
from k8s_llm_rca_tpu.utils.tokenizer import BPETokenizer


def test_distill_oracle_into_tiny_end_to_end(tmp_path, cpu_devices):
    incident = INCIDENTS[0]                       # secret-not-found
    # the SERVING config, used for recording too so the recorded prompts
    # and GenOptions equal the serving-time ones verbatim: fresh threads,
    # reference-serial audits, grammars OFF
    rca_cfg = RCAConfig(fresh_threads=True, concurrent_audits=False,
                        constrained=False, locator_max_new_tokens=256,
                        cypher_max_new_tokens=256,
                        analyzer_max_new_tokens=256)

    # 1. transcripts from the oracle-backed pipeline
    pairs = collect_transcripts(rca_cfg, incidents=[incident])
    assert len(pairs) >= 4                        # plan/cypher/audit/report

    # 2. in-tree BPE trained on the transcript corpus (save/load roundtrip)
    corpus = [t.prompt + t.opts.forced_prefix + t.body for t in pairs]
    bpe = BPETokenizer.train(corpus, vocab_size=2048)
    bpe.save(str(tmp_path / "bpe.json"))
    bpe = BPETokenizer.load(str(tmp_path / "bpe.json"))

    # 3. training rows rendered EXACTLY as the engine will see them
    cfg = TINY.replace(vocab_size=2048, max_seq_len=1024)
    ecfg = EngineConfig(max_batch=4, max_seq_len=1024,
                        prefill_buckets=(256, 512, 1024),
                        max_new_tokens=256, temperature=0.0,
                        decode_chunk=16)
    clamp_eng = PagedInferenceEngine(
        cfg, ecfg, llama.init_params(cfg, jax.random.PRNGKey(0)), bpe)
    rows, masks = build_rows(pairs, bpe, clamp_eng._clamp_prompt, 1024)

    # 4. fine-tune on a DP mesh until teacher-forced exact match == 1.0
    # (which implies greedy decode reproduces every target verbatim)
    mesh = build_mesh(MeshConfig(data=2), devices=cpu_devices[:2])
    params, match, steps = distill(cfg, rows, masks, mesh, max_steps=600,
                                   batch=4, lr=3e-3, eval_every=50)
    assert match == 1.0, f"distill failed to memorize after {steps} steps"

    # 5. Orbax checkpoint -> restore -> HF-interchange safetensors export
    # -> models/loader reload (the full weight lifecycle, zero egress)
    save_params(str(tmp_path / "orbax"), jax.tree.map(np.asarray, params))
    restored = restore_params(str(tmp_path / "orbax"))
    write_safetensors(str(tmp_path / "model.safetensors"),
                      llama_params_to_hf(cfg, restored))
    served = load_llama(cfg, str(tmp_path / "model.safetensors"))

    # 6. serve through the real engine, grammars OFF
    engine = PagedInferenceEngine(cfg, ecfg, served, bpe)
    pipeline = RCAPipeline(
        AssistantService(EngineBackend(engine)),
        InMemoryGraphExecutor(build_metagraph()),
        InMemoryGraphExecutor(build_stategraph()), rca_cfg)

    # the PLAN names the right destination kind, first attempt, no grammar
    plan, attempts = pipeline.plan_destination(incident.message,
                                               incident.src_kind)
    assert attempts == 1
    assert plan["DestinationKind"] == incident.dest_kind
    assert plan["SourceKind"] == incident.src_kind

    # full incident: the model's own Cypher runs (no deterministic
    # fallback) and the REPORT parses with the root cause named
    result = pipeline.analyze_incident(incident.message)
    assert result["locator_attempts"] == 1
    analysis = result["analysis"][0]
    assert analysis["cypher_attempts"] == 1
    assert "human_cypher_query" not in analysis
    report = json.loads(analysis["statepath"][0]["report"])
    assert {"summary", "conclusion", "resolution"} <= set(report)
    assert incident.dest_kind in report["conclusion"]
    scores = {e["kind"]: int(e["relevance_score"])
              for e in report["summary"]}
    assert scores.get(incident.dest_kind, 0) >= 8   # the missing Secret
