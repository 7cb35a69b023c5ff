"""The door from a configuration file to the program's model
(``lib/build.py::model_config`` over ``benchmarks/architectures/``): what it
gives the configurations that are there, what it refuses and by which name,
and that a new architecture is files and entries, no edit to the harness."""

import dataclasses
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "benchmarks")

from benchmarks.lib import build  # noqa: E402

# what the sixteen-key mapping of PR 22 to 24 gave, field for field
SHARED = dict(hidden_size=4096, n_heads=32, n_kv_heads=8, head_dim=128,
              intermediate_size=14336, rope_theta=1000000.0,
              rms_norm_eps=1e-05, max_seq_len=32768, dtype="bfloat16",
              tie_embeddings=False, fused_quant_matmul=False)
OLD_MAPPING = {
    "mistral-7b-v0.3": dict(SHARED, vocab_size=32768, n_layers=32,
                            n_experts=0, n_experts_per_tok=2),
    "mixtral-8x7b-d8": dict(SHARED, vocab_size=32000, n_layers=8,
                            n_experts=8, n_experts_per_tok=2),
    "tiny": dict(vocab_size=32768, hidden_size=128, n_layers=2, n_heads=4,
                 n_kv_heads=2, head_dim=32, intermediate_size=256,
                 rope_theta=10000.0, rms_norm_eps=1e-05, max_seq_len=4096,
                 dtype="float32", tie_embeddings=False, n_experts=4,
                 n_experts_per_tok=2, fused_quant_matmul=False),
}
# OLMoE-1B-7B-0125-Instruct as the model-configs catalog gives it
OLMOE = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
         "hidden_size": 2048, "intermediate_size": 1024,
         "max_position_embeddings": 4096, "model_type": "olmoe",
         "norm_topk_prob": False, "num_attention_heads": 16,
         "num_experts": 64, "num_experts_per_tok": 8,
         "num_hidden_layers": 16, "num_key_value_heads": 16,
         "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
         "tie_word_embeddings": False, "vocab_size": 50304}


def configuration(name):
    return build.load_json(os.path.join(BENCH, "configs", name + ".json"))


@pytest.mark.parametrize("name", sorted(OLD_MAPPING))
def test_known_configurations_build_what_the_old_mapping_built(name):
    from k8s_llm_rca_tpu.config import ModelConfig

    got = build.model_config(configuration(name), name)
    assert got == ModelConfig(name=name, **OLD_MAPPING[name])
    for field, want in OLD_MAPPING[name].items():
        assert type(getattr(got, field)) is type(want), field


def test_weights_come_from_the_function_the_architecture_names():
    from k8s_llm_rca_tpu.models import llama

    for name in OLD_MAPPING:
        assert build.init_params_fn(configuration(name)) is llama.init_params


@pytest.mark.parametrize("change, named", [
    ({"model_type": "olmoe"}, "'olmoe'"),
    ({"model_type": None}, "None"),
    ({"num_experts": 64}, "'num_experts'"),
    ({"qk_layernorm": True}, "'qk_layernorm'"),
    ({"hidden_act": "gelu"}, "hidden_act = 'gelu'"),
    ({"sliding_window": 4096}, "sliding_window = 4096"),
    ({"rope_scaling": {"type": "yarn", "factor": 4.0}}, "rope_scaling = "),
])
def test_what_the_program_cannot_build_is_refused_by_name(change, named):
    conf = dict(configuration("mistral-7b-v0.3"), **change)
    with pytest.raises(ValueError, match=re.escape(named)):
        build.model_config(conf, "changed")


def test_a_missing_key_is_named_not_defaulted():
    conf = configuration("mixtral-8x7b-d8")
    del conf["num_local_experts"]
    with pytest.raises(ValueError, match="'num_local_experts' is missing"):
        build.model_config(conf, "changed")


def test_shapeless_keys_are_dropped_in_the_open():
    conf = dict(configuration("mixtral-8x7b-d8"), use_cache=True,
                architectures=["MixtralForCausalLM"], bos_token_id=1,
                router_aux_loss_coef=0.02, attention_dropout=0.0)
    assert build.model_config(conf, "m") == build.model_config(
        configuration("mixtral-8x7b-d8"), "m")


def test_olmoe_as_published_is_refused_today_by_name():
    conf = dict(OLMOE, engine={}, reference="decoder", weight_quant_bits=8)
    with pytest.raises(ValueError, match="model_type 'olmoe'"):
        build.model_config(conf, "olmoe-1b-7b")


def test_a_new_architecture_is_a_file_not_an_edit(tmp_path, monkeypatch):
    """What a ``model_config`` PR brings: fields of the program's
    ``ModelConfig`` (stood in for here), and a file under
    ``architectures/`` that maps its published keys onto them."""
    from k8s_llm_rca_tpu import config

    @dataclasses.dataclass(frozen=True)
    class Extended(config.ModelConfig):
        norm_topk_prob: bool = True
        qk_norm: bool = False
        layer_types: tuple = ()

    arch = build.load_json(os.path.join(BENCH, "architectures",
                                        "mixtral.json"))
    del arch["fields"]["num_local_experts"]
    arch["fields"].update(num_experts="n_experts",
                          norm_topk_prob="norm_topk_prob",
                          layer_types="layer_types")
    arch["fixed"] = {"qk_norm": True}
    arch["ignored"] += ["attention_bias", "clip_qkv"]
    (tmp_path / "madeup.json").write_text(json.dumps(arch))
    monkeypatch.setattr(build, "ARCH_DIR", str(tmp_path))
    monkeypatch.setattr(config, "ModelConfig", Extended)

    conf = dict(OLMOE, model_type="madeup", head_dim=128,
                torch_dtype="bfloat16", layer_types=["full", "sliding"],
                engine={"fused_quant_matmul": False}, reference="madeup",
                weight_quant_bits=8, kv_cache_dtype="int8")
    got = build.model_config(conf, "madeup-1b-7b")
    assert got == Extended(
        name="madeup-1b-7b", vocab_size=50304, hidden_size=2048,
        n_layers=16, n_heads=16, n_kv_heads=16, head_dim=128,
        intermediate_size=1024, rope_theta=10000.0, rms_norm_eps=1e-05,
        max_seq_len=4096, dtype="bfloat16", tie_embeddings=False,
        n_experts=64, n_experts_per_tok=8, norm_topk_prob=False,
        qk_norm=True, layer_types=("full", "sliding"))
    assert isinstance(got.rope_theta, float) and hash(got) is not None
    # the file may name only what the program has
    arch["fixed"]["shared_expert_width"] = 2048
    (tmp_path / "madeup.json").write_text(json.dumps(arch))
    with pytest.raises(ValueError, match="no field 'shared_expert_width'"):
        build.model_config(conf, "madeup-1b-7b")


# published names that are not also a field of the program's ModelConfig
ARCHITECTURE_KEYS = ("num_local_experts", "num_experts", "sliding_window",
                     "kv_lora_rank", "num_hidden_layers",
                     "num_key_value_heads")


def test_no_harness_code_names_a_published_key():
    """A configuration's keys are read by its architecture's file and by its
    own reference (``reference/``), by nothing else."""
    found = []
    for folder, _, files in os.walk(BENCH):
        rel = os.path.relpath(folder, BENCH)
        if rel.split(os.sep)[0] in ("reference", "tests"):
            continue
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    text = f.read()
                found += [(os.path.join(rel, name), key)
                          for key in ARCHITECTURE_KEYS
                          if re.search(rf"\b{key}\b", text)]
    assert not found


def test_run_refuses_an_unknown_architecture_before_it_builds(tmp_path):
    conf = dict(configuration("tiny"), **OLMOE)
    del conf["num_local_experts"]
    (tmp_path / "olmoe.json").write_text(json.dumps(conf))
    bench = build.load_json(os.path.join(BENCH, "tests", "rehearsal.json"))
    bench["configs"][0]["file"] = str(tmp_path / "olmoe.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark",
         str(tmp_path / "BENCHMARK.json"), "--workload", "tiny.chat-open",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--allow-cpu"],
        capture_output=True, text=True, cwd=ROOT, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert "model_type 'olmoe'" in done.stderr
