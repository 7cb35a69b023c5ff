"""Partition-rule sharding layer tests (runtime/rules.py; ROADMAP item 1).

- **rule matcher units**: first-match-wins precedence, scalar skip, the
  loud no-match ValueError naming the param, None passthrough, and
  TWO-WAY coverage of every per-model table (every param matched, every
  rule used) under both the TP and the FSDP layouts — provable on
  shape-only templates, no devices touched;
- **layout pre-flight** (``validate_layout``): undefined axes,
  non-default mappings onto size-1 axes, and overlapping tier submeshes
  are named ValueErrors at build time;
- **page-record conversion** (``utils.pages.convert_page_record``): the
  deterministic page-size re-chunk the tier handoff rides, plus its
  loud refusals;
- **exact greedy parity** (virtual 8-device CPU mesh; ``slow`` but for
  the fsdp×tp engine and the short-cache fleet): fsdp and
  fsdp×tp sharded engines decode byte-identically
  to the plain single-device engine, a 1P+2D TierRouter fleet with
  DIFFERING per-tier KV page sizes settles byte-identically, and a
  mid-decode export adopts across the page-size boundary with the
  ``engine.handoff_kv_relayout`` counter asserted;
- **loud exclusions**: fsdp×CP/EP/PP/SP refusals, carve divisibility,
  proc-spec layout validation, and TierRouter kv-geometry refusals.
"""

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from k8s_llm_rca_tpu.config import TINY, TINY_MOE, EncoderConfig, \
    EngineConfig, MeshConfig
from k8s_llm_rca_tpu.runtime.mesh import build_mesh
from k8s_llm_rca_tpu.runtime.rules import (
    FSDP_LAYOUT, TP_LAYOUT, SpecLayout, encoder_param_template,
    encoder_rules, llama_param_template, llama_rules, match_partition_rules,
    unused_rules, validate_layout,
)
from k8s_llm_rca_tpu.serve.backend import GenOptions
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

pytestmark = pytest.mark.sharding

_ENC = EncoderConfig(vocab_size=64, hidden_size=32, n_layers=2, n_heads=4,
                     intermediate_size=64, max_seq_len=32)


def _arr(*shape):
    return jax.ShapeDtypeStruct(shape, np.float32)


# ---------------------------------------------------------------------------
# rule matcher units
# ---------------------------------------------------------------------------


class TestMatcher:
    def test_first_match_wins(self):
        # "w" matches wq before the more specific rule can: precedence
        # is table order, NOT specificity
        rules = [("w", P("model", None)), (r"wq$", P(None, "model"))]
        specs = match_partition_rules(rules, {"wq": _arr(4, 4)})
        assert specs["wq"] == P("model", None)

    def test_scalars_replicate_without_consulting_rules(self):
        # a table with NO rules still matches a tree of scalars/size-1
        specs = match_partition_rules([], {"step": _arr(), "one": _arr(1)})
        assert specs == {"step": P(), "one": P()}

    def test_no_match_is_a_loud_valueerror_naming_the_param(self):
        with pytest.raises(ValueError) as exc:
            match_partition_rules([(r"wq$", P(None))],
                                  {"layers": [{"mystery": _arr(4, 4)}]},
                                  table="llama")
        msg = str(exc.value)
        assert "layers/0/mystery" in msg
        assert "llama" in msg
        assert "never silently replicated" in msg

    def test_none_leaves_pass_through(self):
        specs = match_partition_rules([], {"opt": None})
        assert specs == {"opt": P()}

    @pytest.mark.parametrize("layout", [TP_LAYOUT, FSDP_LAYOUT])
    @pytest.mark.parametrize("name,rules_fn,tmpl_fn,cfg", [
        ("llama-dense", llama_rules, llama_param_template, TINY),
        ("llama-moe", llama_rules, llama_param_template, TINY_MOE),
        ("encoder", encoder_rules, encoder_param_template, _ENC),
    ])
    def test_two_way_coverage(self, layout, name, rules_fn, tmpl_fn, cfg):
        """Every param matched (no ValueError) AND every rule used (no
        dead pattern) for every per-model table under both layouts."""
        rules = rules_fn(cfg, layout)
        tmpl = tmpl_fn(cfg)
        match_partition_rules(rules, tmpl, table=name)   # must not raise
        assert unused_rules(rules, tmpl) == []

    def test_llama_specs_reproduce_historical_layout(self):
        from k8s_llm_rca_tpu.runtime.sharding import llama_param_specs

        specs = llama_param_specs(TINY)
        assert specs["layers"][0]["wq"] == P(None, "model")
        assert specs["layers"][0]["wo"] == P("model", None)
        assert specs["layers"][0]["w_down"] == P("model", None)
        assert specs["embedding"] == P(None, "model")
        assert specs["final_norm"] == P(None)
        fs = llama_param_specs(TINY, layout=FSDP_LAYOUT)
        assert fs["layers"][0]["wq"] == P("fsdp", "model")
        assert fs["layers"][0]["wo"] == P("model", "fsdp")
        assert fs["embedding"] == P("fsdp", "model")
        assert fs["final_norm"] == P(None)
        moe = llama_param_specs(TINY_MOE, layout=FSDP_LAYOUT)
        assert moe["layers"][0]["w_gate"] == P("expert", "fsdp", "model")
        assert moe["layers"][0]["w_down"] == P("expert", "model", "fsdp")
        assert moe["layers"][0]["router"] == P(None, None)

    def test_spec_layout_dict_round_trip(self):
        d = FSDP_LAYOUT.to_dict()
        assert SpecLayout.from_dict(d) == FSDP_LAYOUT
        with pytest.raises(ValueError, match="unknown logical axes"):
            SpecLayout.from_dict({"fsdp": "fsdp", "tensor": "model"})


# ---------------------------------------------------------------------------
# layout pre-flight
# ---------------------------------------------------------------------------


class TestValidateLayout:
    def test_undefined_axis_is_named(self, cpu_devices):
        mesh = build_mesh(MeshConfig(model=2), devices=cpu_devices[:2])
        with pytest.raises(ValueError, match="'nope'.*undefined"):
            validate_layout(SpecLayout(tp="nope"), mesh)

    def test_nondefault_mapping_onto_size1_axis_is_named(self, cpu_devices):
        mesh = build_mesh(MeshConfig(model=2), devices=cpu_devices[:2])
        with pytest.raises(ValueError, match="fsdp.*size 1"):
            validate_layout(FSDP_LAYOUT, mesh)

    def test_default_mapping_tolerates_size1_axes(self, cpu_devices):
        # the pervasive single-chip degenerate case: tp over model=1
        mesh = build_mesh(MeshConfig(), devices=cpu_devices[:1])
        assert validate_layout(TP_LAYOUT, mesh) is TP_LAYOUT

    def test_none_layout_defaults_to_tp(self, cpu_devices):
        mesh = build_mesh(MeshConfig(model=2), devices=cpu_devices[:2])
        assert validate_layout(None, mesh) == TP_LAYOUT

    def test_overlapping_peer_meshes_are_refused(self, cpu_devices):
        m1 = build_mesh(MeshConfig(model=2), devices=cpu_devices[:2])
        m2 = build_mesh(MeshConfig(model=2), devices=cpu_devices[1:3])
        with pytest.raises(ValueError, match="overlap"):
            validate_layout(TP_LAYOUT, m1, peers=[m2])
        disjoint = build_mesh(MeshConfig(model=2), devices=cpu_devices[2:4])
        validate_layout(TP_LAYOUT, m1, peers=[disjoint])


# ---------------------------------------------------------------------------
# page-record conversion (the handoff layout bridge)
# ---------------------------------------------------------------------------


class TestConvertPageRecord:
    def _rec(self, L=2, n=3, ps=4, kv=6, scales=False, seed=0):
        rng = np.random.default_rng(seed)
        rec = {"n_pages": n,
               "k": rng.standard_normal((L, n, ps, kv)).astype(np.float32),
               "v": rng.standard_normal((L, n, ps, kv)).astype(np.float32)}
        if scales:
            rec["k_scale"] = rng.standard_normal((L, n, ps)).astype(
                np.float32)
            rec["v_scale"] = rng.standard_normal((L, n, ps)).astype(
                np.float32)
        return rec

    def test_rechunk_preserves_valid_tokens_and_zero_pads(self):
        from k8s_llm_rca_tpu.utils.pages import convert_page_record

        rec = self._rec()
        out = convert_page_record(rec, 10, 8)
        assert out["n_pages"] == 2
        src = rec["k"].reshape(2, 12, 6)[:, :10]
        dst = out["k"].reshape(2, 16, 6)
        assert np.array_equal(dst[:, :10], src)
        assert not dst[:, 10:].any()          # deterministic zero tail
        back = convert_page_record(out, 10, 4)
        assert back["n_pages"] == 3
        assert np.array_equal(back["k"].reshape(2, 12, 6)[:, :10], src)

    def test_scale_fields_rechunk_alongside(self):
        from k8s_llm_rca_tpu.utils.pages import convert_page_record

        rec = self._rec(scales=True)
        out = convert_page_record(rec, 10, 8)
        assert out["k_scale"].shape == (2, 2, 8)
        assert np.array_equal(out["k_scale"].reshape(2, 16)[:, :10],
                              rec["k_scale"].reshape(2, 12)[:, :10])

    def test_same_page_size_is_identity(self):
        from k8s_llm_rca_tpu.utils.pages import convert_page_record

        rec = self._rec()
        assert convert_page_record(rec, 10, 4) is rec

    def test_refusals_are_loud(self):
        from k8s_llm_rca_tpu.utils.pages import convert_page_record

        rec = self._rec()
        with pytest.raises(ValueError, match="length=0"):
            convert_page_record(rec, 0, 8)
        with pytest.raises(ValueError, match="does not fit"):
            convert_page_record(rec, 13, 8)
        with pytest.raises(ValueError, match="dst_page_size"):
            convert_page_record(rec, 10, 0)
        torn = dict(rec, n_pages=5)
        with pytest.raises(ValueError, match="claims 5 pages"):
            convert_page_record(torn, 10, 8)


# ---------------------------------------------------------------------------
# loud exclusions: fsdp mesh validation, carve, proc specs, tier geometry
# ---------------------------------------------------------------------------


class TestFsdpExclusions:
    def _mesh(self, cpu_devices, **axes):
        return build_mesh(MeshConfig(**axes),
                          devices=cpu_devices[:MeshConfig(**axes).n_devices])

    def test_fsdp_refuses_cp_ep_pp_and_sp(self, cpu_devices):
        from k8s_llm_rca_tpu.engine.engine import validate_fsdp_mesh

        ecfg = EngineConfig(max_batch=2, max_seq_len=64)
        mesh = self._mesh(cpu_devices, fsdp=2)
        other = self._mesh(cpu_devices, model=2)
        for kw in ("cp_mesh", "ep_mesh", "pp_mesh"):
            with pytest.raises(ValueError, match="unsupported until"):
                validate_fsdp_mesh(mesh, TINY, ecfg, **{kw: other})
        with pytest.raises(ValueError, match="SP is unsupported"):
            validate_fsdp_mesh(mesh, TINY, ecfg, sp=True)

    def test_fsdp_and_tp_must_share_one_mesh(self, cpu_devices):
        from k8s_llm_rca_tpu.engine.engine import validate_fsdp_mesh

        ecfg = EngineConfig(max_batch=2, max_seq_len=64)
        mesh = self._mesh(cpu_devices, fsdp=2)
        other = self._mesh(cpu_devices, model=2)
        with pytest.raises(ValueError, match="SAME composed mesh"):
            validate_fsdp_mesh(mesh, TINY, ecfg, tp_mesh=other)

    def test_fsdp_divisibility_is_checked(self, cpu_devices):
        from k8s_llm_rca_tpu.engine.engine import validate_fsdp_mesh

        ecfg = EngineConfig(max_batch=2, max_seq_len=64)
        mesh = self._mesh(cpu_devices, fsdp=3)
        cfg = TINY.replace(vocab_size=512)    # hidden 128 % 3 != 0
        with pytest.raises(ValueError, match="hidden_size"):
            validate_fsdp_mesh(mesh, cfg, ecfg)

    def test_carve_refuses_indivisible_fsdp(self, cpu_devices):
        from k8s_llm_rca_tpu.cluster.submesh import carve_replica_meshes

        with pytest.raises(ValueError, match="fsdp axis of 3"):
            carve_replica_meshes(2, devices=cpu_devices[:8], fsdp=3)
        meshes = carve_replica_meshes(2, devices=cpu_devices[:8], fsdp=2)
        assert all(m.shape["fsdp"] == 2 for m in meshes)

    def test_proc_spec_layout_validation_is_parent_side(self):
        from k8s_llm_rca_tpu.cluster.proc import build_proc_replicas

        with pytest.raises(ValueError, match="kind='engine'"):
            build_proc_replicas(1, kind="oracle", layout=FSDP_LAYOUT)
        with pytest.raises(ValueError, match="data/fsdp/model axes only"):
            build_proc_replicas(1, kind="engine", mesh_shape={"seq": 2})
        with pytest.raises(ValueError, match="does not match"):
            build_proc_replicas(1, kind="engine", devices=4,
                                mesh_shape={"model": 2})
        with pytest.raises(ValueError, match="no fsdp axis"):
            build_proc_replicas(1, kind="engine", layout=FSDP_LAYOUT,
                                mesh_shape={"model": 2})
        with pytest.raises(ValueError, match="unknown logical axes"):
            build_proc_replicas(1, kind="engine", layout={"tensor": "model"})


class TestTierGeometry:
    def _replica(self, rid, kv_layout=None, layout=None, mesh=None):
        from k8s_llm_rca_tpu.cluster.replica import Replica
        from k8s_llm_rca_tpu.serve.backend import EchoBackend

        return Replica(rid, EchoBackend(get_tokenizer()), mesh=mesh,
                       layout=layout, kv_layout=kv_layout)

    def test_mismatched_kv_geometry_is_refused_at_construction(self):
        from k8s_llm_rca_tpu.cluster.disagg import TierRouter

        a = {"page_size": 16, "kv_dtype": None, "kv_dim": 64, "n_layers": 2}
        for field, val in (("kv_dtype", "int8"), ("kv_dim", 32),
                           ("n_layers", 4)):
            b = dict(a, **{field: val})
            with pytest.raises(ValueError, match=field):
                TierRouter([self._replica(0, kv_layout=a)],
                           [self._replica(1, kv_layout=b)])

    def test_differing_page_size_is_allowed(self):
        from k8s_llm_rca_tpu.cluster.disagg import TierRouter

        a = {"page_size": 16, "kv_dtype": None, "kv_dim": 64, "n_layers": 2}
        b = dict(a, page_size=32)
        TierRouter([self._replica(0, kv_layout=a)],
                   [self._replica(1, kv_layout=b)])

    def test_scripted_replicas_skip_geometry_checks(self):
        from k8s_llm_rca_tpu.cluster.disagg import TierRouter

        TierRouter([self._replica(0)], [self._replica(1)])

    def test_overlapping_tier_submeshes_are_refused(self, cpu_devices):
        from k8s_llm_rca_tpu.cluster.disagg import TierRouter

        m1 = build_mesh(MeshConfig(model=2), devices=cpu_devices[:2])
        m2 = build_mesh(MeshConfig(model=2), devices=cpu_devices[1:3])
        with pytest.raises(ValueError, match="overlap"):
            TierRouter([self._replica(0, layout=TP_LAYOUT, mesh=m1)],
                       [self._replica(1, layout=TP_LAYOUT, mesh=m2)])
        m3 = build_mesh(MeshConfig(model=2), devices=cpu_devices[2:4])
        TierRouter([self._replica(0, layout=TP_LAYOUT, mesh=m1)],
                   [self._replica(1, layout=TP_LAYOUT, mesh=m3)])

    def test_late_admission_runs_the_same_checks(self):
        from k8s_llm_rca_tpu.cluster.disagg import TIER_DECODE, TierRouter

        a = {"page_size": 16, "kv_dtype": None, "kv_dim": 64, "n_layers": 2}
        router = TierRouter([self._replica(0, kv_layout=a)],
                            [self._replica(1, kv_layout=dict(a))])
        bad = self._replica(2, kv_layout=dict(a, kv_dim=32))
        with pytest.raises(ValueError, match="kv_dim"):
            router.add_replica(bad, tier=TIER_DECODE)
        router.add_replica(
            self._replica(3, kv_layout=dict(a, page_size=64)),
            tier=TIER_DECODE)


# ---------------------------------------------------------------------------
# exact greedy parity on the virtual 8-device CPU mesh (slow tier)
# ---------------------------------------------------------------------------


def _plain_reference(cfg, ecfg, params, tok, prompt, opts):
    from k8s_llm_rca_tpu.engine import make_engine
    from k8s_llm_rca_tpu.serve.backend import EngineBackend

    ref = EngineBackend(make_engine(cfg, ecfg, params, tok,
                                    use_kernel=False))
    h = ref.start(prompt, opts)
    while True:
        res = ref.pump().get(h)
        if res is not None:
            assert res.error is None
            return res.text


class TestFsdpGreedyParity:
    """Byte-identical greedy decode for every fsdp composition: the
    params are rule-sharded and COMMITTED before the engine builds, so
    GSPMD inserts the all-gathers (committed-input propagation) whether
    or not the engine also receives the mesh for cache placement.  The
    fsdp x tp case runs in tier-1 since PR 50 (the dryrun's row, which
    no tier-1 test repeated); the others stay ``slow``."""

    @pytest.mark.parametrize("axes,pass_mesh", [
        # fsdp-only, params-committed
        pytest.param({"fsdp": 4}, False, marks=pytest.mark.slow),
        # fsdp-only + cache placement
        pytest.param({"fsdp": 4}, True, marks=pytest.mark.slow),
        ({"fsdp": 2, "model": 2}, True),      # fsdp×tp on one mesh
    ])
    def test_fsdp_matches_plain_engine(self, cpu_devices, axes, pass_mesh):
        from k8s_llm_rca_tpu.engine import make_engine
        from k8s_llm_rca_tpu.models import llama
        from k8s_llm_rca_tpu.runtime.sharding import (
            llama_param_specs, shard_pytree,
        )
        from k8s_llm_rca_tpu.serve.backend import EngineBackend

        cfg = TINY.replace(max_seq_len=64)
        knobs = dict(max_batch=2, max_seq_len=64, prefill_buckets=(32,),
                     max_new_tokens=8, temperature=0.0, prefix_cache=False,
                     page_size=8, num_pages=24)
        ecfg = EngineConfig(**knobs)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tok = get_tokenizer(vocab_size=cfg.vocab_size)
        prompt = "node notready on node-3"
        opts = GenOptions(max_new_tokens=8)
        want = _plain_reference(cfg, ecfg, params, tok, prompt, opts)

        mcfg = MeshConfig(**axes)
        mesh = build_mesh(mcfg, devices=cpu_devices[:mcfg.n_devices])
        layout = validate_layout(FSDP_LAYOUT, mesh)
        sharded = shard_pytree(params, llama_param_specs(cfg, layout),
                               mesh)
        kw = {"use_kernel": False}
        if pass_mesh:
            kw["fsdp_mesh"] = mesh
            if axes.get("model", 1) > 1:
                kw["tp_mesh"] = mesh
        backend = EngineBackend(make_engine(cfg, ecfg, sharded, tok, **kw))
        h = backend.start(prompt, opts)
        while True:
            res = backend.pump().get(h)
            if res is not None:
                break
        assert res.error is None
        assert res.text == want               # byte-identical greedy

    @pytest.mark.slow
    def test_fsdp_cp_composition_is_refused_loudly(self, cpu_devices):
        from k8s_llm_rca_tpu.engine import make_engine
        from k8s_llm_rca_tpu.models import llama

        cfg = TINY.replace(max_seq_len=64)
        ecfg = EngineConfig(max_batch=2, max_seq_len=64,
                            prefill_buckets=(32,), max_new_tokens=8,
                            temperature=0.0)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tok = get_tokenizer(vocab_size=cfg.vocab_size)
        mesh = build_mesh(MeshConfig(fsdp=2, seq=2),
                          devices=cpu_devices[:4])
        with pytest.raises(ValueError, match="fsdp×CP is unsupported"):
            make_engine(cfg, ecfg, params, tok, fsdp_mesh=mesh,
                        cp_mesh=mesh)


@pytest.mark.disagg
class TestPerTierLayoutParity:
    def _fleet(self, cpu_devices, page_size_decode, seq_len):
        from k8s_llm_rca_tpu.cluster.disagg import TierRouter
        from k8s_llm_rca_tpu.cluster.replica import build_replicas

        n_pages = 96 * seq_len // 512
        cfg = TINY.replace(max_seq_len=seq_len)
        ecfg = EngineConfig(max_batch=2, max_seq_len=seq_len,
                            prefill_buckets=(seq_len,), max_new_tokens=16,
                            temperature=0.0, page_size=16,
                            num_pages=n_pages, prefix_cache=False)
        ecfg_d = dataclasses.replace(
            ecfg, page_size=page_size_decode,
            num_pages=n_pages * 16 // page_size_decode)
        # prefill TP-heavy (tp4), decode KV-wide (tp2 × 2 replicas) —
        # same checkpoint, same seed, different per-tier layouts
        pre = build_replicas(cfg, ecfg, 1, devices=cpu_devices[:4],
                             use_kernel=False)
        dec = build_replicas(cfg, ecfg_d, 2, devices=cpu_devices[4:8],
                             use_kernel=False)
        for i, r in enumerate(dec):
            r.replica_id = i + 1
            r.backend.engine.obs_replica = i + 1
        return cfg, ecfg, TierRouter(pre, dec)

    # the short cache runs in tier-1 since PR 50 (the dryrun's row, which
    # no tier-1 test repeated)
    @pytest.mark.parametrize(
        "seq_len", [128, pytest.param(512, marks=pytest.mark.slow)])
    def test_1p2d_differing_kv_page_sizes_settle_byte_identically(
            self, cpu_devices, seq_len):
        from k8s_llm_rca_tpu.models import llama

        cfg, ecfg, router = self._fleet(cpu_devices, 32, seq_len)
        assert router.replicas[0].kv_layout["page_size"] == 16
        assert router.replicas[1].kv_layout["page_size"] == 32
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tok = get_tokenizer(vocab_size=cfg.vocab_size)
        prompt = "node notready on node-3"
        opts = GenOptions(max_new_tokens=8)
        want = _plain_reference(cfg, ecfg, params, tok, prompt, opts)
        h = router.start(prompt, opts)
        res = None
        for _ in range(300):
            res = router.pump().get(h)
            if res is not None:
                break
        assert res is not None and res.error is None
        assert res.text == want
        assert router.handoffs == 1

    @pytest.mark.slow
    def test_mid_decode_relayout_adopt_is_byte_identical(self):
        """The conversion path proper: export mid-decode from a
        page_size=8 engine, adopt on a page_size=4 engine — the record
        is re-chunked (relayout counter), never re-prefilled, and the
        finished text matches the uninterrupted run byte for byte."""
        from k8s_llm_rca_tpu.engine import make_engine
        from k8s_llm_rca_tpu.models import llama
        from k8s_llm_rca_tpu.serve.backend import EngineBackend

        cfg = TINY.replace(max_seq_len=64)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tok = get_tokenizer(vocab_size=cfg.vocab_size)
        knobs = dict(max_batch=2, max_seq_len=64, page_size=8,
                     num_pages=24, prefill_buckets=(16, 32),
                     max_new_tokens=8, temperature=0.0, decode_chunk=1,
                     prefix_cache=False)
        eng_a = make_engine(cfg, EngineConfig(**knobs), params, tok,
                            use_kernel=False)
        eng_b = make_engine(cfg, EngineConfig(**dict(knobs, page_size=4,
                                                     num_pages=48)),
                            params, tok, use_kernel=False)
        prompt = "node notready on node-3"
        opts = GenOptions(max_new_tokens=8)
        backend_a = EngineBackend(eng_a)
        ref_h = backend_a.start(prompt, opts)
        ref = {}
        while ref_h not in ref:
            ref.update(backend_a.pump())
        assert ref[ref_h].error is None

        h = backend_a.start(prompt, opts)
        frame = None
        for _ in range(6):
            assert h not in backend_a.pump()
            frame = backend_a.export_run(h)
            if frame is not None:
                break
        assert frame is not None and frame["kv"] is not None
        backend_b = EngineBackend(eng_b)
        h2 = backend_b.adopt_run(frame, opts)
        counts = eng_b._counts or {}
        assert counts.get("engine.handoff_kv_adopted") == 1
        assert counts.get("engine.handoff_kv_relayout") == 1
        assert counts.get("engine.handoff_kv_rejected") is None
        out = {}
        for _ in range(64):
            out.update(backend_b.pump())
            if h2 in out:
                break
        assert out[h2].error is None
        assert out[h2].text == ref[ref_h].text

    @pytest.mark.slow
    def test_incompatible_kv_dtype_is_a_loud_adopt_error(self):
        from k8s_llm_rca_tpu.engine import make_engine
        from k8s_llm_rca_tpu.models import llama
        from k8s_llm_rca_tpu.serve.backend import EngineBackend

        cfg = TINY.replace(max_seq_len=64)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tok = get_tokenizer(vocab_size=cfg.vocab_size)
        knobs = dict(max_batch=2, max_seq_len=64, page_size=8,
                     num_pages=24, prefill_buckets=(16, 32),
                     max_new_tokens=8, temperature=0.0, decode_chunk=1,
                     prefix_cache=False)
        eng_a = make_engine(cfg, EngineConfig(**knobs), params, tok,
                            use_kernel=False)
        eng_c = make_engine(cfg,
                            EngineConfig(**dict(knobs,
                                                kv_cache_dtype="int8")),
                            params, tok, use_kernel=False)
        backend_a = EngineBackend(eng_a)
        backend_c = EngineBackend(eng_c)
        opts = GenOptions(max_new_tokens=8)
        h = backend_a.start("node notready on node-3", opts)
        frame = None
        for _ in range(6):
            backend_a.pump()
            frame = backend_a.export_run(h)
            if frame is not None:
                break
        assert frame is not None and frame["kv"] is not None
        with pytest.raises(ValueError, match="misconfigured tier pair"):
            backend_c.adopt_run(frame, opts)
        assert not eng_c.has_work             # nothing half-adopted
