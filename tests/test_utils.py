"""utils/ coverage: tokenizer roundtrip properties and the METRICS sink;
and the test harness's own shared compile directory (tests/conftest.py)."""

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from conftest import COMPILE_CACHE_DIR
from k8s_llm_rca_tpu.utils.logging import Metrics
from k8s_llm_rca_tpu.utils.tokenizer import ByteTokenizer, get_tokenizer


class TestBPETokenizer:
    """In-tree trainable byte-level BPE (utils/tokenizer.BPETokenizer)."""

    def _tok(self):
        from k8s_llm_rca_tpu.utils.tokenizer import BPETokenizer

        corpus = ["MountVolume.SetUp failed for volume",
                  'secret "es-account-token" not found',
                  '{"DestinationKind": "Secret"}'] * 20
        return BPETokenizer.train(corpus, vocab_size=512)

    def test_roundtrip_exact(self):
        tok = self._tok()
        for text in ['secret "x" not found\n', "kubectl apply -f m.yaml",
                     '{"a": [1, 2], "b": "c\\"d"}', "päivää \u00e9\u00e9"]:
            assert tok.decode(tok.encode(text)) == text

    def test_compresses_vs_bytes(self):
        tok = self._tok()
        text = "MountVolume.SetUp failed for volume: secret not found"
        assert len(tok.encode(text)) < len(text.encode()) // 2

    def test_specials_and_framing(self):
        tok = self._tok()
        ids = tok.encode("pod", add_bos=True, add_eos=True)
        assert ids[0] == tok.bos_id and ids[-1] == tok.eos_id
        assert tok.decode(ids) == "pod"       # specials filtered on decode
        assert {tok.pad_id, tok.bos_id, tok.eos_id} == {0, 1, 2}

    def test_save_load_roundtrip(self, tmp_path):
        from k8s_llm_rca_tpu.utils.tokenizer import BPETokenizer

        tok = self._tok()
        path = str(tmp_path / "bpe.json")
        tok.save(path)
        tok2 = BPETokenizer.load(path)
        text = 'exceeded quota: pods=50'
        assert tok2.encode(text) == tok.encode(text)
        assert tok2.vocab_size == tok.vocab_size


class TestTokenizer:
    @pytest.mark.parametrize("text", [
        "kubelet Failed to pull image",
        "MountVolume.SetUp failed for volume \"pv-1\": ümlaut → 中文",
        "",
        "```json\n{\"a\": 1}\n```",
    ])
    def test_roundtrip(self, text):
        tok = get_tokenizer()
        assert tok.decode(tok.encode(text)) == text

    def test_bos_eos_framing(self):
        tok = get_tokenizer()
        ids = tok.encode("x", add_bos=True, add_eos=True)
        assert ids[0] == tok.bos_id and ids[-1] == tok.eos_id
        assert tok.bos_id != tok.eos_id

    def test_count_matches_encode(self):
        tok = get_tokenizer()
        text = "pod pending: unschedulable (0/3 nodes available)"
        assert tok.count(text) == len(tok.encode(text))

    def test_byte_fallback_handles_any_bytes(self):
        tok = ByteTokenizer()
        text = bytes(range(256)).decode("latin-1")
        assert tok.decode(tok.encode(text)) == text

    def test_ids_within_vocab(self):
        tok = get_tokenizer(vocab_size=256)
        ids = tok.encode("Error: ÿ boundary")
        assert all(0 <= i < 256 for i in ids)


class TestMetrics:
    def test_inc_and_timer(self):
        m = Metrics()
        m.inc("a")
        m.inc("a", 2)
        assert m.count("a") == 3
        with m.timer("t"):
            pass
        assert len(m.timings["t"]) == 1
        assert m.total("t") >= 0
        assert m.p50("t") == m.timings["t"][0]
        snap = m.snapshot()
        assert snap["a"] == 3 and "t.total_s" in snap


class TestSharedCompileCache:
    def test_an_entry_cut_short_is_a_miss_that_recompiles(self):
        """The gate guarded against its own cache: a worker killed at the
        limit while it writes an entry leaves the head of a file behind,
        and every later run finds it.  Reading it is a miss (JAX warns and
        compiles), never a failure."""
        def fresh():
            # a function object of its own each time, so nothing JAX holds
            # in memory stands in for the directory
            def _entry_cut_short_probe(x):
                return jnp.cumsum(x * 3.0) + 1.0
            return jax.jit(_entry_cut_short_probe)

        def entries():
            return glob.glob(os.path.join(
                COMPILE_CACHE_DIR, "jit__entry_cut_short_probe-*"))

        for path in entries():          # what a killed run of this test left
            os.remove(path)
        x = jnp.arange(8.0)
        want = fresh()(x)
        written = entries()
        assert written, "the tests' compiles are not kept"
        try:
            for path in written:
                with open(path, "r+b") as f:
                    f.truncate(os.path.getsize(path) // 2)
            with pytest.warns(UserWarning, match="Error reading persistent "
                                                 "compilation cache entry"):
                got = fresh()(x)
            assert got.tolist() == want.tolist()
        finally:
            # JAX never writes over an entry that exists: take ours away
            for path in entries():
                os.remove(path)
