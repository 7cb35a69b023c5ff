"""Native (C++) runtime component tests: strict parity with the Python
implementations they replace.

The allocator must enforce identical invariants (same exception types on
double-free / foreign-free / trash-free / exhaustion) and the grammar
engine must be mask-for-mask identical with the Python FSM along random
decode trajectories — greedy decoding under either backend must therefore
produce byte-identical output.
"""

import json

import numpy as np
import pytest

from k8s_llm_rca_tpu import native
from k8s_llm_rca_tpu.engine.constrain import JsonGrammar
from k8s_llm_rca_tpu.engine.paged import (
    AllocatorError, OutOfPages, PageAllocator,
)
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native toolchain unavailable")


class TestNativeAllocator:
    def test_roundtrip_and_n_free(self):
        a = native.NativePageAllocator(16)
        pages = a.alloc(5, owner=1)
        assert len(set(pages)) == 5 and 0 not in pages
        assert a.n_free == 10
        assert a.pages_of(1) == sorted(pages)
        a.free(pages, owner=1)
        a.check()
        assert a.n_free == 15

    def test_error_parity_with_python(self):
        for cls in (PageAllocator, native.NativePageAllocator):
            a = cls(8)
            pages = a.alloc(2, owner=1)
            with pytest.raises(OutOfPages):
                a.alloc(99, owner=2)
            with pytest.raises(AllocatorError):
                a.free(pages, owner=2)          # foreign owner
            a.free(pages, owner=1)
            with pytest.raises(AllocatorError):
                a.free(pages, owner=1)          # double free
            with pytest.raises(AllocatorError):
                a.free([0], owner=1)            # trash page
            a.check()
            assert a.n_free == 7


    def test_transfer_parity_with_python(self):
        for cls in (PageAllocator, native.NativePageAllocator):
            a = cls(8)
            pages = a.alloc(3, owner=1)
            a.transfer(pages[:2], from_owner=1, to_owner=-2)
            assert sorted(a.pages_of(-2)) == sorted(pages[:2])
            assert sorted(a.pages_of(1)) == sorted(pages[2:])
            with pytest.raises(AllocatorError):      # wrong from_owner
                a.transfer(pages[:1], from_owner=1, to_owner=-2)
            with pytest.raises(AllocatorError):      # trash page
                a.transfer([0], from_owner=1, to_owner=-2)
            a.free(pages[:2], owner=-2)
            with pytest.raises(AllocatorError):      # free page transfer
                a.transfer(pages[:1], from_owner=-2, to_owner=1)
            a.free(pages[2:], owner=1)
            a.check()
            assert a.n_free == 7

    def test_interleaved_sequence_parity(self):
        """Drive both allocators through the same random alloc/free
        schedule: counts, failures and the ids handed out match exactly
        (one address-ordered store with two ends behind both)."""
        rng = np.random.default_rng(0)
        py, cc = PageAllocator(32), native.NativePageAllocator(32)
        held_py, held_cc = {}, {}
        for step in range(300):
            if rng.random() < 0.55 or not held_py:
                n = int(rng.integers(1, 5))
                owner = int(rng.integers(0, 6))
                try:
                    p1 = py.alloc(n, owner)
                    ok1 = True
                except OutOfPages:
                    ok1 = False
                try:
                    p2 = cc.alloc(n, owner)
                    ok2 = True
                except OutOfPages:
                    ok2 = False
                assert ok1 == ok2, f"step {step}"
                if ok1:
                    assert p1 == p2, f"step {step}"
                    assert p1 == sorted(p1), f"step {step}"
                    held_py.setdefault(owner, []).extend(p1)
                    held_cc.setdefault(owner, []).extend(p2)
            else:
                owner = list(held_py)[int(rng.integers(0, len(held_py)))]
                py.free(held_py.pop(owner), owner)
                cc.free(held_cc.pop(owner), owner)
            assert py.n_free == cc.n_free, f"step {step}"
        py.check()
        cc.check()


class TestNativeGrammar:
    def _pair(self):
        tok = get_tokenizer()
        return JsonGrammar(tok), native.NativeJsonGrammar(tok), tok

    def test_mask_parity_along_trajectories(self):
        """At every step of a random grammar-legal decode, the native and
        Python masks must be identical."""
        rng = np.random.default_rng(1)
        for trajectory in range(5):
            py, cc, tok = self._pair()
            for step in range(40):
                cp = py.constraint()
                cn = cc.constraint()
                assert (cp.force is None) == (cn.force is None), step
                if cp.force is not None:
                    assert cp.force == cn.force
                    token = cp.force
                else:
                    np.testing.assert_array_equal(cp.allow, cn.allow), step
                    legal = np.flatnonzero(cp.allow)
                    token = int(legal[rng.integers(0, len(legal))])
                if token == tok.eos_id:
                    break
                py.advance(token)
                cc.advance(token)
                assert py.done == cc.done

    def test_minimal_completion_parity(self):
        prefixes = ['', '{', '{"key', '{"key": ', '{"a": [1, {"b": "x',
                    '-1.2e', '{"a": tr', '{"s": "esc\\', '[[[',
                    '{"a": {"b": [0, ']
        for prefix in prefixes:
            py, cc, tok = self._pair()
            for ch in prefix:
                (t,) = tok.encode(ch)
                py.advance(t)
                cc.advance(t)
            assert py.auto.minimal_completion() == cc.minimal_completion(), \
                prefix

    def test_violation_raises_both(self):
        py, cc, tok = self._pair()
        (brace,) = tok.encode("}")
        with pytest.raises(ValueError):
            py.advance(brace)
        with pytest.raises(ValueError):
            cc.advance(brace)

    def test_greedy_decode_identical_under_both_backends(self):
        import jax

        from k8s_llm_rca_tpu.config import TINY, EngineConfig
        from k8s_llm_rca_tpu.engine.paged import PagedInferenceEngine
        from k8s_llm_rca_tpu.models import llama

        cfg = TINY.replace(max_seq_len=256)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        ecfg = EngineConfig(max_batch=2, max_seq_len=128, max_new_tokens=32,
                            prefill_buckets=(32,), temperature=0.0)
        tok = get_tokenizer()
        outs = {}
        for name, grammar_cls in (("py", JsonGrammar),
                                  ("cc", native.NativeJsonGrammar)):
            eng = PagedInferenceEngine(cfg, ecfg, params, tok)
            seq = eng.submit(tok.encode("emit json", add_bos=True),
                             grammar=grammar_cls(tok))
            (res,) = eng.run_to_completion()
            assert res.seq_id == seq
            json.loads(res.text)
            outs[name] = res.token_ids
        assert outs["py"] == outs["cc"]

    def test_engine_config_native_flag_selects_backend(self, monkeypatch):
        from k8s_llm_rca_tpu.engine import constrain
        from k8s_llm_rca_tpu.engine.constrain import make_grammar
        from k8s_llm_rca_tpu.engine.paged import make_allocator

        tok = get_tokenizer()
        # grammar="json" now compiles the BOUNDED-depth DFA first (it rides
        # the on-device scan); the native/python unbounded grammars are the
        # fallback when the tables don't fit
        assert isinstance(make_grammar("json", tok), constrain.DFAGrammar)
        monkeypatch.setattr(constrain, "_DFA_MAX_TABLE_BYTES", 1024)
        tok2 = get_tokenizer()            # fresh: no cached tables
        assert isinstance(make_grammar("json", tok2),
                          native.NativeJsonGrammar)
        assert isinstance(make_grammar("json", tok2, prefer_native=False),
                          JsonGrammar)
        assert isinstance(make_allocator(8), native.NativePageAllocator)
        assert isinstance(make_allocator(8, prefer_native=False),
                          PageAllocator)
