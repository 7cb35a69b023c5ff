"""Shared driver wiring: backend/executor construction from config.

Replaces the reference's copy-pasted hardcoded setup blocks (identical in
all six drivers, e.g. test_all.py:18-37): backends and graph endpoints are
chosen by config, and the hermetic in-memory backends are first-class.
"""

from __future__ import annotations

import argparse
from typing import Tuple

from k8s_llm_rca_tpu.config import MODEL_REGISTRY, EngineConfig
from k8s_llm_rca_tpu.graph import InMemoryGraphExecutor
from k8s_llm_rca_tpu.graph.fixtures import build_metagraph, build_stategraph
from k8s_llm_rca_tpu.rca.oracle import OracleBackend
from k8s_llm_rca_tpu.serve.api import AssistantService
from k8s_llm_rca_tpu.utils import get_tokenizer


def add_common_args(parser: argparse.ArgumentParser) -> None:
    parser.add_argument("--backend", default="oracle",
                        choices=["oracle", "engine"],
                        help="LM backend: scripted oracle (hermetic) or the "
                             "TPU inference engine")
    parser.add_argument("--model", default="tiny",
                        choices=sorted(MODEL_REGISTRY),
                        help="model preset for --backend engine")
    parser.add_argument("--max-batch", type=int, default=8)
    parser.add_argument("--max-seq-len", type=int, default=2048)
    parser.add_argument("--decode-chunk", type=int, default=None,
                        help="decode steps per device dispatch (default: "
                             "EngineConfig's; semantics identical to "
                             "stepwise — amortizes dispatch latency, and "
                             "DFA-grammar runs ride the scan)")
    quant = parser.add_mutually_exclusive_group()
    quant.add_argument("--int8", action="store_true",
                       help="weight-only int8 quantization")
    quant.add_argument("--int4", action="store_true",
                       help="weight-only int4 quantization (nibble-packed)")
    parser.add_argument("--kv-dtype", default=None,
                        choices=["int8", "int4"],
                        help="quantized KV cache (default: model dtype)")
    parser.add_argument("--weights", default=None,
                        help="HF safetensors file/dir to load real weights "
                             "from (default: random init)")
    parser.add_argument("--neo4j-meta", default=None,
                        help="bolt://host:port for a live metagraph "
                             "(default: canned in-memory fixture)")
    parser.add_argument("--neo4j-state", default=None,
                        help="bolt://host:port for a live stategraph")
    parser.add_argument("--neo4j-auth", default="neo4j:neo4j",
                        help="user:password for live Neo4j")
    parser.add_argument("--fresh-threads", action="store_true",
                        help="start each incident on fresh stage threads "
                             "(re-seeded templates/rules) instead of the "
                             "reference's ever-growing sweep threads — "
                             "recommended for --backend engine sweeps, "
                             "whose max_seq_len is a real KV budget")


def build_service(args) -> AssistantService:
    if args.backend == "oracle":
        return AssistantService(OracleBackend(get_tokenizer()))
    # engine backend: build the model + continuous-batching engine
    import jax

    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.models import init_params
    from k8s_llm_rca_tpu.models.quant import (
        quantize_params, quantizing_transform,
    )
    from k8s_llm_rca_tpu.runtime.compile_cache import enable_compile_cache
    from k8s_llm_rca_tpu.serve.backend import EngineBackend

    enable_compile_cache()
    model_cfg = MODEL_REGISTRY[args.model]
    # grammar masks are [tokenizer vocab] and meet logits of [model vocab]:
    # the byte tokenizer is padded to the model's width
    tokenizer = get_tokenizer(vocab_size=model_cfg.vocab_size)
    bits = 4 if args.int4 else 8 if args.int8 else None
    if args.weights:
        if model_cfg.layer_table:
            raise SystemExit(
                f"--weights: models/loader.py reads Llama-family "
                f"checkpoints; {model_cfg.name!r} has a layer table")
        from k8s_llm_rca_tpu.models.loader import load_llama

        params = load_llama(model_cfg, args.weights)
        if bits:
            params = quantize_params(params, bits=bits)
    else:
        # quantize each weight as it is created: a full bf16 llama3-8b is
        # 16 GB, the whole HBM of one v5e chip
        params = init_params(
            model_cfg, jax.random.PRNGKey(0),
            tensor_transform=quantizing_transform(bits=bits) if bits
            else None)
    # the CLI default (2048) may exceed a small preset's RoPE table; clamp
    # so `--backend engine` works out of the box for every --model
    max_seq = min(args.max_seq_len, model_cfg.max_seq_len)
    if max_seq < args.max_seq_len:
        from k8s_llm_rca_tpu.utils.logging import get_logger

        get_logger(__name__).warning(
            "clamping --max-seq-len %d to %s's model maximum %d",
            args.max_seq_len, model_cfg.name, max_seq)
    ecfg_kw = dict(max_batch=args.max_batch, max_seq_len=max_seq,
                   kv_cache_dtype=args.kv_dtype)
    if model_cfg.n_ssm_layers:
        # the engine refuses the prefix cache for a model that keeps a
        # recurrent state per slot (docs/serving.md, "Layer kinds")
        ecfg_kw["prefix_cache"] = False
    if args.decode_chunk is not None:
        ecfg_kw["decode_chunk"] = args.decode_chunk   # else EngineConfig's
    engine = make_engine(model_cfg, EngineConfig(**ecfg_kw),
                         params, tokenizer)
    return AssistantService(EngineBackend(engine))


def build_executors(args) -> Tuple[object, object]:
    if args.neo4j_meta or args.neo4j_state:
        from k8s_llm_rca_tpu.graph.executor import Neo4jQueryExecutor

        user, password = args.neo4j_auth.split(":", 1)
        meta = Neo4jQueryExecutor(args.neo4j_meta, user, password)
        state = Neo4jQueryExecutor(args.neo4j_state, user, password)
        return meta, state
    return (InMemoryGraphExecutor(build_metagraph()),
            InMemoryGraphExecutor(build_stategraph()))
