"""Guards from the chip bring-up that need no chip.

1. The main-path kernels compiled by the installed TPU compiler for a
   DESCRIBED ``v5e:2x2`` chip at Llama-3-8B widths.  Interpret mode passes
   kernels Mosaic refuses (an int8 vector shift, a mis-tiled block): these
   compiles are what a later PR's kernel edit has to get through before it
   costs chip time.  Nothing runs, so nothing here says a result is right —
   tests/test_kernels.py and tests/test_quant_matmul.py do that in interpret
   mode, chip_smoke.py on the chip.  Whole engine steps (16-25 s each) stay
   in a scratch rehearsal (.claude/skills/verify/SKILL.md), but for one:
   the decode step at 2 layers, for what it must not do to the pool.
2. No fallback hides the device: an unknown TPU kind has no peaks, a missing
   TPU or an unknown kind of one stops chip_smoke.py before any model, an
   unknown ``--model`` is an argument error, and the compile cache is placed
   from outside.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import open_compile_cache
from k8s_llm_rca_tpu.config import LLAMA3_8B, MIXTRAL_8X7B
from k8s_llm_rca_tpu.models.quant import QuantTensor, QuantTensor4
from k8s_llm_rca_tpu.ops.flash_attention import flash_attention
from k8s_llm_rca_tpu.ops.paged_attention import (
    paged_attention, paged_attention_quant,
)
from k8s_llm_rca_tpu.ops.quant_matmul import (
    quant_matmul, quant_matmul_experts, quant_matmul_head,
    quant_swiglu_experts,
)
from k8s_llm_rca_tpu.runtime import compile_cache, profiling

CFG = LLAMA3_8B
H, KV, D = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
BF16, I8, I32, F32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def chip():
    """Shapes placed on one chip of a described (not attached) v5e:2x2,
    with the persistent compile cache off for this file (the tests' shared
    directory, tests/conftest.py, is opened again behind it): a
    described-chip executable can be written to the cache but not read back
    without a chip, and what this file proves is that Mosaic and XLA
    compile these programs NOW, never that an earlier run did."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    open_compile_cache()


def _described(chip, tree):
    """A tree of arrays or of ``eval_shape``'s shapes, placed on the chip."""
    return jax.tree.map(lambda s: chip(s.shape, s.dtype), tree)


def _kernel_calls(text, name):
    """The lines of the compiled module ``text`` that call the Pallas
    kernel ``name``."""
    import re

    return [line for line in text.splitlines()
            if re.search(rf"%{name}\S* = .*custom-call\(", line)
            and "tpu_custom_call" in line]


def _compiles_with_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


class TestAttentionKernelsCompileForV5e:
    # the bench's flagship pool (144 slots, page 64), the pool the sweep
    # CLI builds (16 slots, EngineConfig's page 16, 4096-token tables) and
    # the pool of BENCHMARK.json's cells (mistral-7b-v0.3: 32 slots, 3,072
    # pages; Mistral and Mixtral share Llama-3-8B's 32/8 heads of 128)
    POOLS = [pytest.param(144, 64, 1864, 12, id="b144-page64"),
             pytest.param(16, 16, 1024, 256, id="b16-page16"),
             pytest.param(32, 16, 3072, 256, id="b32-page16-cells")]

    @pytest.mark.parametrize("b,page,n_pages,pages_per_seq", POOLS)
    def test_paged_attention(self, chip, b, page, n_pages, pages_per_seq):
        pool = chip((n_pages, page, KV * D), BF16)
        _compiles_with_kernel(
            functools.partial(paged_attention, interpret=False),
            chip((b, H, D), BF16), pool, pool, chip((b,), I32),
            chip((b, pages_per_seq), I32))

    @pytest.mark.parametrize("packed", [False, True], ids=["int8", "int4"])
    @pytest.mark.parametrize("b,page,n_pages,pages_per_seq", POOLS)
    def test_paged_attention_quant(self, chip, b, page, n_pages,
                                   pages_per_seq, packed):
        pool = chip((n_pages, page, KV * D // (2 if packed else 1)), I8)
        scales = chip((n_pages, page), F32)
        _compiles_with_kernel(
            functools.partial(paged_attention_quant, packed=packed,
                              interpret=False),
            chip((b, H, D), BF16), pool, pool, scales, scales,
            chip((b,), I32), chip((b, pages_per_seq), I32))

    def test_flash_attention_512(self, chip):
        kv = chip((1, 512, KV, D), BF16)
        _compiles_with_kernel(
            functools.partial(flash_attention, interpret=False),
            chip((1, 512, H, D), BF16), kv, kv, chip((1,), I32))


    def test_flash_attention_lean_in_large_blocks(self, chip):
        """The full layers' prefill call of a model with window layers
        (K-EXAONE's 64 / 8 heads, one 6144-position row): the lean form in
        ``llama.FLASH_LEAN_BLOCK`` blocks fits Mosaic's VMEM."""
        from k8s_llm_rca_tpu.models import llama

        kv = chip((1, 6144, 8, D), BF16)
        _compiles_with_kernel(
            functools.partial(flash_attention, interpret=False, lean=True,
                              block_q=llama.FLASH_LEAN_BLOCK,
                              block_k=llama.FLASH_LEAN_BLOCK),
            chip((1, 6144, 64, D), BF16), kv, kv, chip((1,), I32))


class TestLatentAttentionKernelsCompileForV5e:
    """kanana-2-30b-a3b's widths (``benchmarks/configs/
    kanana-2-30b-a3b-d12.json``): the absorbed decode walk over rows of 576
    kept at 640 lanes (a pool of 576 lanes Mosaic refuses: "Slice shape
    along dimension 3 must be aligned to tiling (128), but is 576"), 64
    slots, pages of 16, tables of 16k tokens; and the prefill's flash call
    at a key width of 192 and a value width of 128, lean in 1024-blocks."""

    def test_the_absorbed_walk_over_the_cells_pool(self, chip):
        from k8s_llm_rca_tpu.ops.mla_attention import (
            mla_paged_attention, stored_lanes,
        )

        assert stored_lanes(576) == 640
        pool = chip((12, 4096, 16, 640), BF16)
        _compiles_with_kernel(
            lambda q, pool, lens, tables, layer: mla_paged_attention(
                q, pool, lens, tables, scale=192 ** -0.5, n_value=512,
                layer=layer, interpret=False),
            chip((64, 32, 576), BF16), pool, chip((64,), I32),
            chip((64, 1024), I32), chip((), I32))

    def test_a_pool_of_unpadded_rows_is_refused(self, chip):
        from k8s_llm_rca_tpu.ops.mla_attention import mla_paged_attention

        with pytest.raises(Exception, match="aligned to tiling"):
            jax.jit(lambda q, pool, lens, tables: mla_paged_attention(
                q, pool, lens, tables, scale=192 ** -0.5, n_value=512,
                layer=0, interpret=False)).lower(
                    chip((64, 32, 576), BF16), chip((1, 256, 16, 576), BF16),
                    chip((64,), I32), chip((64, 64), I32)).compile()

    def test_flash_attention_at_keys_of_192_and_values_of_128(self, chip):
        _compiles_with_kernel(
            functools.partial(flash_attention, interpret=False, lean=True,
                              block_q=1024, block_k=1024),
            chip((1, 8192, 32, 192), BF16), chip((1, 8192, 32, 192), BF16),
            chip((1, 8192, 32, 128), BF16), chip((1,), I32))


class TestStateKernelCompilesForV5e:
    # the two layer-table cells' pools of state: granite-4.0-h-micro (36
    # Mamba layers, one group of 64 heads, 2.1 MB a slot and layer: a tile
    # is a slot whole) and nemotron3-super-120b-d11 (5 layers, 8 groups of
    # 16 heads, 4.2 MB: two tiles of four groups a slot, the tile's heads
    # brought to the front by a lane rotation)
    @pytest.mark.parametrize("layers, heads, groups, tile", [
        (36, 64, 1, 64), (5, 128, 8, 64)])
    def test_ssm_state_update(self, chip, layers, heads, groups, tile):
        from k8s_llm_rca_tpu.ops import ssm

        slots, p, n = 64, 64, 128
        assert ssm.head_tile(heads, groups, p * n * 4) == tile

        def update(state, layer, x, dt, a, b, c, d, live):
            return ssm.ssm_state_update_in_place(
                state, layer, x, dt, a, b, c, d, ssm.live_slots(live),
                interpret=False)

        state = (layers, slots, heads, p, n)
        compiled = jax.jit(update, donate_argnums=0).lower(
            chip(state, F32), chip((), I32), chip((slots, heads, p), BF16),
            chip((slots, heads), F32), chip((heads,), F32),
            chip((slots, groups, n), BF16), chip((slots, groups, n), BF16),
            chip((heads,), F32), chip((slots,), jnp.bool_)).compile()
        text = compiled.as_text()
        shape = ",".join(map(str, state))
        calls = [line for line in text.splitlines()
                 if "custom-call(" in line and "tpu_custom_call" in line]
        # one call, under the name the benchmark's reader knows it by, on
        # the whole pool, which comes back in the buffer it was donated in
        assert len(calls) == 1 and "%ssm_state_update" in calls[0]
        assert f"f32[{shape}]" in calls[0]
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= 4 * layers * slots * heads * p * n
        assert mem.temp_size_in_bytes < 4 * heads * p * n    # one slot's


class TestScanKernelCompilesForV5e:
    """The prefill's chunked scan as its kernel, at the two layer-table
    cells' widths and their largest bucket: granite-4.0-h-micro (one group
    of 64 heads, chunk 256, 2,048 positions) and nemotron3-super-120b-d11
    (8 groups of 16 heads, chunk 128, 4,096).  What the XLA form writes
    out for every chunk, the float32 decay between the chunk's positions
    for each head (``[.., heads, chunk, chunk]``), is in no program that
    holds the kernel."""

    CELLS = {"granite": ("granite-4.0-h-micro", 2048, 512, 36),
             "nemotron": ("nemotron3-super-120b-d11", 4096, 1024, 5)}

    @staticmethod
    def _cfg(name):
        import os

        from benchmarks.lib import build

        return build.model_config(build.load_json(os.path.join(
            build.BENCH_DIR, "configs", name + ".json")), name)

    @staticmethod
    def _decay(cfg):
        """A float32 array that ends in ``heads (of a group), chunk,
        chunk``, as HLO text writes its shape."""
        rep, q = cfg.ssm_heads // cfg.ssm_groups, cfg.ssm_chunk
        return rf"f32\[[0-9,]*\b{rep},{q},{q}\]"

    @pytest.mark.parametrize("cell", sorted(CELLS))
    def test_ssm_chunk_scan(self, chip, cell):
        import re

        from k8s_llm_rca_tpu.ops import ssm

        name, positions, _, _ = self.CELLS[cell]
        cfg = self._cfg(name)
        h, p = cfg.ssm_heads, cfg.ssm_head_dim
        g, n = cfg.ssm_groups, cfg.ssm_state_size
        # thirty-two heads a grid step: half of granite's one group, two
        # of nemotron's eight
        assert ssm.head_tile(h, g, p * n * 4, ssm._SCAN_TILE_BYTES) == 32
        args = (chip((1, positions, h, p), BF16),
                chip((1, positions, h), F32), chip((h,), F32),
                chip((1, positions, g, n), BF16),
                chip((1, positions, g, n), BF16), chip((h,), F32))
        text = jax.jit(functools.partial(
            ssm.ssm_chunk_scan, chunk=cfg.ssm_chunk,
            interpret=False)).lower(*args).compile().as_text()
        assert len(_kernel_calls(text, "ssm_chunk_scan")) == 1
        assert not re.search(self._decay(cfg), text)
        xla = jax.jit(functools.partial(
            ssm.ssm_chunk_scan_xla, chunk=cfg.ssm_chunk)).lower(
                *args).compile().as_text()
        assert re.search(self._decay(cfg), xla)
        assert not _kernel_calls(xla, "ssm_chunk_scan")

    @pytest.mark.parametrize("cell", sorted(CELLS))
    def test_a_prefill_program_calls_it_once_a_mamba_layer(
            self, chip, monkeypatch, cell):
        """The cell's whole model, one row of its smallest bucket through
        ``paged_prefill_batch`` as the engine compiles it on the chip
        (``use_flash=True``: granite's 512 is under the flash call's 1,024
        positions and takes the scan's kernel all the same)."""
        import re

        from k8s_llm_rca_tpu.engine import paged
        from k8s_llm_rca_tpu.models import nemotron_h

        name, _, bucket, mamba_layers = self.CELLS[cell]
        cfg = self._cfg(name)
        assert cfg.n_ssm_layers == mamba_layers
        params = _described(chip, jax.eval_shape(
            lambda: nemotron_h.init_params(cfg, jax.random.PRNGKey(0))))
        pool = _described(chip, jax.eval_shape(
            lambda: paged.init_paged_cache(cfg, 16384, 16, n_slots=64)))
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        text = jax.jit(
            paged.paged_prefill_batch, static_argnums=0, donate_argnums=2,
            static_argnames="use_flash").lower(
                cfg, params, pool, chip((1, bucket), I32), chip((1,), I32),
                chip((1, bucket // 16), I32), slots=chip((1,), I32),
                use_flash=True).compile().as_text()
        calls = [re.match(r"\s*(?:ROOT )?%(\S+) = ", line).group(1)
                 for line in _kernel_calls(text, "ssm_chunk_scan")]
        assert len(calls) == len(set(calls)) == mamba_layers
        assert not re.search(self._decay(cfg), text)
        # the in-projection's output stays row-major: an operand of the
        # kernel with positions on lanes made XLA lay it out positions-
        # minor, and the convolution and out-projection ran a third slower
        width = cfg.ssm_inner + cfg.ssm_conv_dim + cfg.ssm_heads
        layouts = set(re.findall(
            rf"bf16\[1,{bucket},{width}\]\{{([0-9,]+)", text))
        assert layouts == {"2,1,0"}


class TestDecodeStepWritesThePoolInPlace:
    """The stepwise decode program at Mistral-7B widths (2 layers of the
    32, int8 weights, the int8 pool of ``mistral7b.chat-open``: 3,072
    pages of 16 tokens, 32 slots).  Until PR 28 every layer's pages were
    sliced out of the pool, scattered into and set back: a 50 MB copy
    each way, 64 times a step, 39% of that cell's device time.  What a
    later edit must not bring back, seen here without a chip: outside the
    kernel the compiled program holds no array of one layer's pages,
    keeps the pool in the buffers it was donated in, and needs less room
    for temporaries than one layer's pages (the parent: 46 such arrays,
    70 MB of temporaries)."""

    N_PAGES, PAGE, SLOTS, LAYERS = 3072, 16, 32, 2

    def test_no_layer_of_the_pool_is_copied(self, chip, monkeypatch):
        import re

        from k8s_llm_rca_tpu.config import ModelConfig
        from k8s_llm_rca_tpu.engine import paged
        from k8s_llm_rca_tpu.models import llama
        from k8s_llm_rca_tpu.models.quant import quantize_params

        cfg = ModelConfig(vocab_size=32768, hidden_size=4096,
                          intermediate_size=14336, n_layers=self.LAYERS,
                          n_heads=32, n_kv_heads=8, head_dim=128,
                          max_seq_len=4096, rope_theta=1e6,
                          tie_embeddings=False)

        params = _described(chip, jax.eval_shape(lambda: quantize_params(
            llama.init_params(cfg, jax.random.PRNGKey(0)), bits=8)))
        pool = _described(chip, jax.eval_shape(
            lambda: paged.init_paged_cache(cfg, self.N_PAGES, self.PAGE,
                                           "int8")))
        # the kernel's interpret=None asks the backend, which is the CPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = jax.jit(
            paged.paged_decode_step, static_argnums=0, donate_argnums=2,
            static_argnames="use_kernel").lower(
                cfg, params, pool, chip((self.SLOTS,), I32),
                chip((self.SLOTS,), I32),
                chip((self.SLOTS, cfg.max_seq_len // self.PAGE), I32),
                use_kernel=True).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= self.LAYERS

        # no array of one layer's pages, with or without its unit axis
        assert not re.search(
            rf"s8\[(1,)?{self.N_PAGES},{self.PAGE},{cfg.kv_dim}]", text)
        # and no second pool: k and v live on in the buffers they came in
        mem = compiled.memory_analysis()
        layer_bytes = self.N_PAGES * self.PAGE * cfg.kv_dim
        assert mem.alias_size_in_bytes >= 2 * self.LAYERS * layer_bytes
        assert mem.temp_size_in_bytes < layer_bytes


class TestDecodeStepUpdatesTheStateInPlace:
    """The stepwise decode program of ``nemotron3-super-d11.audit-report``
    at the configuration's own widths (2 of its 11 layers, a Mamba-2 mixer
    and the attention layer; 64 slots, the cell's pool).  Each slot keeps
    4.2 MB of recurrent state a Mamba layer, 268 MB over the slots: a step
    that sliced a layer's state out, updated it and set it back would copy
    that twice a layer (PR 28's lesson, for pages).  Seen here without a
    chip: the compiled program copies no array of the state's shape, keeps
    state and pages in the buffers they were donated in, and needs less
    room for temporaries than a tenth of one layer's state.  Each test in
    both forms of the update: XLA's two passes (``use_kernel=False``, which
    takes the attention kernel away too, so the gathered pages are its
    temporaries) and the one kernel on the pool (``use_kernel=True``)."""

    SLOTS, N_PAGES, PAGE = 64, 16384, 16

    def _decode_step(self, chip, cfg, params, pool, kernel, **compile_kw):
        from k8s_llm_rca_tpu.engine import paged

        return jax.jit(
            paged.paged_decode_step, static_argnums=0, donate_argnums=2,
            static_argnames="use_kernel").lower(
                cfg, params, pool, chip((self.SLOTS,), I32),
                chip((self.SLOTS,), I32),
                chip((self.SLOTS, cfg.max_seq_len // self.PAGE), I32),
                use_kernel=kernel).compile(**compile_kw)

    def _state_stays_where_it_is(self, compiled, cfg, state, state_bytes,
                                 temp_limit=None):
        """No array of the shape ``state`` is copied, state and pages keep
        their donated buffers, and the temporaries stay under
        ``temp_limit`` (not held for the decode step without the attention
        kernel: its temporaries are the pages every slot's table gathers,
        gigabytes at this pool, the form no chip runs)."""
        import re

        text = compiled.as_text()
        assert re.search(state, text)               # it is there, updated
        copies = [line for line in text.splitlines()
                  if re.search(rf"= {state}\S* copy\(", line)]
        assert not copies, copies[:2]
        mem = compiled.memory_analysis()
        pages_bytes = 2 * self.N_PAGES * self.PAGE * cfg.kv_dim * 2
        assert mem.alias_size_in_bytes >= state_bytes + pages_bytes
        if temp_limit is not None:
            assert mem.temp_size_in_bytes < temp_limit

    @pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
    def test_no_layer_of_the_state_is_copied(self, chip, monkeypatch,
                                             kernel):
        from k8s_llm_rca_tpu.config import ModelConfig
        from k8s_llm_rca_tpu.engine import paged
        from k8s_llm_rca_tpu.models import nemotron_h

        cfg = ModelConfig(
            name="nemotron-2-layers", vocab_size=32768, hidden_size=4096,
            n_layers=2, layer_pattern="M*", n_heads=32, n_kv_heads=2,
            head_dim=128, use_rope=False, max_seq_len=4096,
            dtype="bfloat16", tie_embeddings=False, ssm_heads=128,
            ssm_head_dim=64, ssm_groups=8, ssm_state_size=128)
        params = _described(chip, jax.eval_shape(
            lambda: nemotron_h.init_params(cfg, jax.random.PRNGKey(0))))
        pool = _described(chip, jax.eval_shape(
            lambda: paged.init_paged_cache(cfg, self.N_PAGES, self.PAGE,
                                           n_slots=self.SLOTS)))
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = self._decode_step(chip, cfg, params, pool, kernel)
        text = compiled.as_text()
        assert len(_kernel_calls(text, "paged_attention")) == kernel
        assert len(_kernel_calls(text, "ssm_state_update")) == kernel
        state_bytes = self.SLOTS * 128 * 64 * 128 * 4
        self._state_stays_where_it_is(
            compiled, cfg, rf"f32\[(1,)?{self.SLOTS},128,64,128\]",
            state_bytes, state_bytes // 10 if kernel else None)

    @staticmethod
    def _granite(layer_types):
        """granite-4.0-h-micro at its published widths, the given layers."""
        from k8s_llm_rca_tpu.config import ModelConfig

        return ModelConfig(
            name=f"granite-{len(layer_types)}-layers", vocab_size=100352,
            hidden_size=2048, n_layers=len(layer_types),
            mixer_types=tuple(layer_types), block_mlp_size=8192, n_heads=32,
            n_kv_heads=8, head_dim=64, use_rope=False, max_seq_len=4096,
            dtype="bfloat16", tie_embeddings=True, ssm_heads=64,
            ssm_head_dim=64, ssm_groups=1, ssm_state_size=128,
            ssm_chunk=256, embedding_multiplier=12.0,
            residual_multiplier=0.22, logits_scaling=8.0,
            attn_scale=0.015625)

    @pytest.mark.parametrize("program", ["decode", "decode-xla",
                                         "prefill-8x512"])
    def test_no_state_is_copied_behind_a_block_of_two_sublayers(
            self, chip, monkeypatch, program):
        """``granite4-h-micro.chat-open`` at the configuration's own widths
        (2 of its 40 layers: a Mamba-2 mixer and an attention layer, each
        with its gated MLP behind it; 64 slots, the cell's pool).  A slot
        keeps 2.1 MB of recurrent state a Mamba layer, 4.83 GB over 36
        layers and 64 slots: more than a third of what the chip holds
        beside the weights, so a program that copied it would not fit.
        The stepwise decode program (with the kernels and without) and the
        8 x 512 batched prefill (its rows' states scattered into their
        slots) copy no array of the slots' state, and keep state and pages
        in the buffers they were donated in."""

        from k8s_llm_rca_tpu.engine import paged
        from k8s_llm_rca_tpu.models import nemotron_h

        cfg = self._granite(("mamba", "attention"))
        params = _described(chip, jax.eval_shape(
            lambda: nemotron_h.init_params(cfg, jax.random.PRNGKey(0))))
        assert "lm_head" not in params
        pool = _described(chip, jax.eval_shape(
            lambda: paged.init_paged_cache(cfg, self.N_PAGES, self.PAGE,
                                           n_slots=self.SLOTS)))
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        state = rf"f32\[(1,)?{self.SLOTS},64,64,128\]"
        state_bytes = self.SLOTS * 64 * 64 * 128 * 4
        if program != "prefill-8x512":
            kernel = program == "decode"
            compiled = self._decode_step(chip, cfg, params, pool, kernel)
            text = compiled.as_text()
            assert len(_kernel_calls(text, "paged_attention")) == kernel
            assert len(_kernel_calls(text,
                                          "ssm_state_update")) == kernel
            # a step's activations
            self._state_stays_where_it_is(
                compiled, cfg, state, state_bytes,
                state_bytes // 10 if kernel else None)
            return
        compiled = jax.jit(
            paged.paged_prefill_batch, static_argnums=0,
            donate_argnums=2).lower(
                cfg, params, pool, chip((8, 512), I32), chip((8,), I32),
                chip((8, 512 // self.PAGE), I32),
                slots=chip((8,), I32)).compile()
        # one 512-row's activations, and the eight rows' states on their
        # way to the slots
        self._state_stays_where_it_is(compiled, cfg, state, state_bytes,
                                      state_bytes)

    @pytest.mark.parametrize("program", ["step", "scan-16"])
    def test_the_whole_model_updates_each_state_once_a_step(
            self, chip, monkeypatch, program):
        """``granite4-h-micro.chat-open`` whole: all 40 layers, 64 slots,
        13.4 GB of arguments.  Arguments and one more copy of the 4.8 GB
        state do not fit the chip together, and XLA's rematerialization
        pass, which counts every in-place update as a new buffer, then
        duplicates the first Mamba layer's update
        (``add_dynamic-update-slice_fusion.35.remat`` and ``.remat2``, both
        on the program's parameter): in place on the one donated buffer
        that state moves on twice a step (seen on the chip, PR 44).  A
        duplicated call of the aliasing kernel would square a decay just
        so.  With the options the engine compiles its decode programs with
        (``paged.decode_compiler_options``) nothing is rematerialized,
        nothing of the state's shape is copied, state and pages stay where
        they were donated, and each Mamba layer's ``ssm_state_update``
        call is in the program once, in the stepwise program and in the
        body of a scan of 16 steps.  (Without the kernels the whole model
        at 64 slots does not fit the chip: XLA's attention gathers every
        slot's pages.  The XLA update is held in place at two layers,
        above.)"""
        import re

        from k8s_llm_rca_tpu.config import TINY, TINY_GRANITE_HYBRID
        from k8s_llm_rca_tpu.engine import paged
        from k8s_llm_rca_tpu.engine.sampling import SamplingParams
        from k8s_llm_rca_tpu.models import nemotron_h

        cfg = self._granite((("mamba",) * 5 + ("attention",)
                             + ("mamba",) * 4) * 4)
        assert (cfg.n_layers, cfg.n_ssm_layers, cfg.n_kv_layers) == (40, 36,
                                                                     4)
        assert paged.decode_compiler_options(cfg) == {}       # a CPU here
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert paged.decode_compiler_options(TINY) == {}
        assert paged.decode_compiler_options(TINY_GRANITE_HYBRID)
        params = _described(chip, jax.eval_shape(
            lambda: nemotron_h.init_params(cfg, jax.random.PRNGKey(0))))
        pool = _described(chip, jax.eval_shape(
            lambda: paged.init_paged_cache(cfg, self.N_PAGES, self.PAGE,
                                           n_slots=self.SLOTS)))
        options = paged.decode_compiler_options(cfg)
        if program == "scan-16":
            compiled = jax.jit(
                paged.paged_decode_scan, static_argnums=(0, 7, 8, 9),
                donate_argnums=2, static_argnames="use_kernel").lower(
                    cfg, params, pool, chip((self.SLOTS,), I32),
                    chip((self.SLOTS,), I32),
                    chip((self.SLOTS, 4096 // self.PAGE), I32),
                    chip((2,), jnp.uint32), 16,
                    SamplingParams(temperature=0.0, top_k=0, top_p=1.0), 2,
                    use_kernel=True).compile(compiler_options=options)
        else:
            compiled = self._decode_step(chip, cfg, params, pool, True,
                                         compiler_options=options)
        text = compiled.as_text()
        assert not re.findall(r"%\S*remat\d* = ", text)
        state = rf"f32\[36,{self.SLOTS},64,64,128\]"
        calls = _kernel_calls(text, "ssm_state_update")
        updates = [re.match(r"\s*(?:ROOT )?%(\S+) = ", line).group(1)
                   for line in calls]
        assert len(updates) == len(set(updates)) == cfg.n_ssm_layers
        assert all(re.search(state, line) for line in calls)
        assert not re.search(r"add_dynamic-update-slice_fusion\S* = "
                             + state, text)
        assert not [line for line in text.splitlines()
                    if re.search(rf"= {state}\S* copy\(", line)]
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= sum(
            a.size * a.dtype.itemsize
            for a in jax.tree_util.tree_leaves(pool))
        assert mem.temp_size_in_bytes < 100e6       # a step's activations


class TestDecodeStepWritesTheRingInPlace:
    """The stepwise decode program of ``k-exaone-d5.longdump-reason`` at the
    configuration's own widths (2 of its 5 layers: the dense sliding layer
    and the full one, so both kinds of cache; 64 slots, the cell's pool of
    32,768 pages and its rings of 9 pages a slot).  A window layer's rings
    are 18.9 MB over the slots and a full layer's pages 1.07 GB: a step that
    sliced either out, wrote its token and set it back would copy it twice
    (PR 28's lesson).  Seen here without a chip: both decode kernels stand in
    the program under their own names, the compiled program holds no array
    of one layer's ring or pages outside them, keeps ring and pages in the
    buffers they were donated in, and needs less room for temporaries than
    one layer's pages."""

    SLOTS, N_PAGES, PAGE = 64, 32768, 16

    def test_no_layer_of_the_ring_is_copied(self, chip, monkeypatch):
        import re

        from k8s_llm_rca_tpu.config import ModelConfig
        from k8s_llm_rca_tpu.engine import paged
        from k8s_llm_rca_tpu.models import llama

        cfg = ModelConfig(
            name="k-exaone-2-layers", vocab_size=19200, hidden_size=6144,
            n_layers=2, n_heads=64, n_kv_heads=8, head_dim=128,
            intermediate_size=18432, max_seq_len=8192, rope_theta=1e6,
            dtype="bfloat16", tie_embeddings=False,
            attn_layer_types=("sliding_attention", "full_attention"),
            attn_window=128, n_dense_layers=2, qk_norm=True,
            rope_full_layers=False)
        params = _described(chip, jax.eval_shape(
            lambda: llama.init_params(cfg, jax.random.PRNGKey(0))))
        pool = _described(chip, jax.eval_shape(
            lambda: paged.init_paged_cache(cfg, self.N_PAGES, self.PAGE,
                                           n_slots=self.SLOTS)))
        ring_pages = self.SLOTS * cfg.ring_pages(self.PAGE)
        assert pool.ring.k.shape == (1, ring_pages, self.PAGE, cfg.kv_dim)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = jax.jit(
            paged.paged_decode_step, static_argnums=0, donate_argnums=2,
            static_argnames="use_kernel").lower(
                cfg, params, pool, chip((self.SLOTS,), I32),
                chip((self.SLOTS,), I32),
                chip((self.SLOTS, cfg.max_seq_len // self.PAGE), I32),
                use_kernel=True).compile()
        text = compiled.as_text()
        assert "window_paged_attention" in text
        assert re.search(r'(?<!window_)paged_attention', text)

        # no array of one layer's ring or pages, with or without its unit
        # axis, is copied
        for n_pages in (ring_pages, self.N_PAGES):
            shape = rf"bf16\[(1,)?{n_pages},{self.PAGE},{cfg.kv_dim}\]"
            copies = [line for line in text.splitlines()
                      if re.search(rf"= {shape}\S* copy\(", line)]
            assert not copies, copies[:2]
        mem = compiled.memory_analysis()
        layer_bytes = 2 * self.PAGE * cfg.kv_dim * 2       # k and v a page
        assert mem.alias_size_in_bytes >= (ring_pages + self.N_PAGES) \
            * layer_bytes
        assert mem.temp_size_in_bytes < self.N_PAGES * layer_bytes // 2


class TestPrefillRoutesEachTokenToItsExperts:
    """The batched prefill program of ``mixtral-d8.audit-prefill`` at its
    largest warm-up shape: 4 rows of 4096 at Mixtral-8x7B widths (2 layers
    of the cell's 8, int4 experts, the cell's int8 pool of 4,096 pages).
    Until PR 30 every expert ran on every position: three activations of
    ``[4, 4096, 8, 14336]`` (3.76 GB each) a layer, three quarters of them
    multiplied by zero.  What a
    later edit must not bring back, seen here without a chip: the program
    holds no per-expert activation of all 16,384 positions, its expert
    matmuls are the grouped kernel over the routed ``[32768, ...]`` rows,
    and its temporaries stay under the dense form's (PR 29's tree at this
    shape: 4.67 GB, and 5.24 GB at the cell's 8 layers; this program: 3.39
    and 3.97 GB, most of it one layer's experts dequantized whole, 0.94
    GB a weight, which the grouped kernel reads from HBM)."""

    ROWS, BUCKET, LAYERS, N_PAGES, PAGE = 4, 4096, 2, 4096, 16
    # the dense form's temporaries at this shape (AOT, PR 29's tree)
    DENSE_TEMP_BYTES = 4.67e9

    def test_no_activation_of_every_expert_is_live(self, chip, monkeypatch):
        import re

        from k8s_llm_rca_tpu.engine import paged
        from k8s_llm_rca_tpu.models import llama
        from k8s_llm_rca_tpu.models.quant import quantize_params

        cfg = MIXTRAL_8X7B.replace(n_layers=self.LAYERS, max_seq_len=4096,
                                   dtype="bfloat16")
        positions = self.ROWS * self.BUCKET
        assert llama.moe_grouped(cfg, positions)

        params = _described(chip, jax.eval_shape(lambda: quantize_params(
            llama.init_params(cfg, jax.random.PRNGKey(0)), bits=4)))
        pool = _described(chip, jax.eval_shape(
            lambda: paged.init_paged_cache(cfg, self.N_PAGES, self.PAGE,
                                           "int8")))
        # the kernels' interpret=None asks the backend, which is the CPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = jax.jit(
            paged.paged_prefill_batch, static_argnums=0, donate_argnums=2,
            static_argnames="use_flash").lower(
                cfg, params, pool, chip((self.ROWS, self.BUCKET), I32),
                chip((self.ROWS,), I32),
                chip((self.ROWS, self.BUCKET // self.PAGE), I32),
                use_flash=True).compile()
        text = compiled.as_text()

        e, k = cfg.n_experts, cfg.n_experts_per_tok
        h, inter = cfg.hidden_size, cfg.intermediate_size
        # no [..., 8, 14336] or [..., 8, 4096] activation over all 16,384
        # positions, flattened or as rows x bucket
        for rows in (rf"{positions}", rf"{self.ROWS},{self.BUCKET}"):
            assert not re.search(rf"\[{rows},{e},({inter}|{h})]", text)
        # gate, up and down of each layer: grouped matmuls of the routed
        # pairs against the stacked weight, which is what the benchmark's
        # expert_mlp_busy_share tells the expert MLP by
        grouped = [line for line in text.splitlines()
                   if "ragged-dot" in line and "custom-call(" in line
                   and f"bf16[{positions * k}," in line]
        assert len(grouped) == 3 * self.LAYERS
        assert all(f"[{e},{h},{inter}]" in line or f"[{e},{inter},{h}]"
                   in line for line in grouped)
        # at the tiles the chip measured best, not XLA's 512 x 512 x 512
        assert all('ragged_dot_tiling="512,1024,1024"' in line
                   for line in grouped)
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < 0.8 * self.DENSE_TEMP_BYTES

    @pytest.mark.parametrize("rows,tiling", [
        pytest.param(2 * 2048, "512,1024,1024", id="bucket-2048-our-tiles"),
        pytest.param(2 * 1552, "32,512,512", id="rows-512-does-not-divide"),
    ])
    def test_grouped_matmul_tiles(self, chip, rows, tiling):
        """XLA's kernel takes its tiles from the operation's attribute and
        refuses a row tile that does not divide the rows: a prefix-hit
        chunk of 1,552 positions has to keep XLA's own choice."""
        from k8s_llm_rca_tpu.models import llama

        e, h, inter = (MIXTRAL_8X7B.n_experts, MIXTRAL_8X7B.hidden_size,
                       MIXTRAL_8X7B.intermediate_size)
        text = jax.jit(llama._grouped_matmul).lower(
            chip((rows, h), BF16), chip((e, h, inter), BF16),
            chip((e,), I32)).compile().as_text()
        assert "tpu_custom_call" in text
        assert f'ragged_dot_tiling="{tiling}"' in text


class TestHeldShareCompactsItsExpertRows:
    """One expert layer's routed experts as a prefill row of the two cells
    that hold a share of their router's experts calls them, at the
    configurations' own widths: ``nemotron3-super-d11.audit-report`` (128
    of 512 squared-ReLU latent experts, 22 picks, a 3,072 bucket) and
    ``k-exaone-d5.longdump-reason`` (16 of 128 SwiGLU experts, 8 picks, a
    6,144 bucket).  Until PR 38 the grouped form gathered and multiplied a
    row for every pick, three quarters and seven eighths of them nobody's
    here.  Seen without a chip: the program keeps a conditional (XLA has
    not flattened it into a select that would run both), its compact
    branch's grouped matmuls have ``moe_compact_rows`` rows, a multiple of
    the kernel's row tile, the whole form behind it has every pair's, and
    the two share their temporaries."""

    @pytest.mark.parametrize("name,positions,rows", [
        pytest.param("nemotron", 3072, 33792, id="nemotron-3072"),
        pytest.param("exaone", 6144, 12288, id="exaone-6144"),
    ])
    def test_both_forms_behind_one_conditional(self, chip, name, positions,
                                               rows):
        import re

        from k8s_llm_rca_tpu.config import ModelConfig
        from k8s_llm_rca_tpu.models import llama

        if name == "nemotron":
            cfg = ModelConfig(
                name="nemotron-experts", hidden_size=4096, n_experts=128,
                router_width=512, n_experts_per_tok=22,
                router_kind="sigmoid", moe_latent_size=1024,
                moe_intermediate_size=2688, mlp_act="relu2",
                dtype="bfloat16")
        else:
            cfg = ModelConfig(
                name="exaone-experts", hidden_size=6144, n_experts=16,
                router_width=128, n_experts_per_tok=8,
                router_kind="sigmoid", moe_intermediate_size=2048,
                dtype="bfloat16")
        e, k = cfg.n_experts, cfg.n_experts_per_tok
        width, inter = (cfg.moe_latent_size or cfg.hidden_size,
                        cfg.moe_intermediate_size)
        pairs = positions * k
        assert llama.moe_compact_rows(cfg, positions) == rows
        assert rows % llama._GROUPED_MATMUL_TILES[0] == 0

        layer = {"w_up": chip((e, width, inter), BF16),
                 "w_down": chip((e, inter, width), BF16)}
        if cfg.mlp_act != "relu2":
            layer["w_gate"] = chip((e, width, inter), BF16)

        compiled = jax.jit(functools.partial(llama._experts, cfg)).lower(
            layer, chip((1, positions, width), BF16),
            chip((1, positions, k), I32), chip((1, positions, k), F32)
        ).compile()
        text = compiled.as_text()
        assert len(re.findall(r"\bconditional\(", text)) == 1
        grouped = [line for line in text.splitlines()
                   if "ragged-dot" in line
                   and re.search(r"= bf16\[\d+,\d+\]\S* custom-call\(", line)]
        per_form = 2 if cfg.mlp_act == "relu2" else 3
        assert sum(f"= bf16[{rows}," in line
                   for line in grouped) == per_form
        assert sum(f"= bf16[{pairs}," in line
                   for line in grouped) == per_form
        assert len(grouped) == 2 * per_form
        # at tiles that divide: the measured ones, or for 2,688 columns
        # the fitted ones, never XLA's 128-wide fallback
        tilings = {re.search(r'ragged_dot_tiling="([^"]*)"', line).group(1)
                   for line in grouped}
        assert tilings == ({"256,1024,2688", "256,2688,1024"}
                           if name == "nemotron" else {"512,1024,1024"})
        # the branches share their room: no more than the whole form's
        # activations (hidden and output of every pair) and the gathers
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < pairs * (2 * inter + 3 * width) * 2


class TestDecodeScanReadsItsExpertsPacked:
    """The decode scan of ``mixtral-d8.audit-prefill`` as the cell's engine
    binds it: 16 steps over 32 slots at Mixtral-8x7B widths (2 layers of
    the cell's 8, int4 experts, the cell's int8 pool of 4,096 pages), no
    mesh, so ``expert_kernel`` is on and the call's 32 positions choose the
    fused form.  Until PR 35 every step of every layer unpacked all eight
    experts' nibbles whole in HBM ahead of the einsums (``[8,4096,14336]``
    and ``[8,14336,4096]`` in int8: 59% of a 43.9 ms step on the chip).
    What a later edit must not bring back, seen here without a chip: the
    program's expert MLPs are the two packed kernels at the tiles the chip
    measured best, it holds no array of a whole dequantized expert stack,
    and its temporaries stay far under the parent's at this shape (the
    parent: 524.5 MB; this program: 69.3 MB)."""

    SLOTS, STEPS, LAYERS, N_PAGES, PAGE = 32, 16, 2, 4096, 16
    PARENT_TEMP_BYTES = 524.5e6

    def test_no_expert_is_dequantized_whole(self, chip, monkeypatch):
        import re

        from k8s_llm_rca_tpu.engine import paged
        from k8s_llm_rca_tpu.engine.sampling import SamplingParams
        from k8s_llm_rca_tpu.models import llama
        from k8s_llm_rca_tpu.models.quant import quantize_params
        from k8s_llm_rca_tpu.ops.quant_matmul import _ekn4_tiles

        cfg = MIXTRAL_8X7B.replace(n_layers=self.LAYERS, max_seq_len=4096,
                                   dtype="bfloat16")
        params = _described(chip, jax.eval_shape(lambda: quantize_params(
            llama.init_params(cfg, jax.random.PRNGKey(0)), bits=4)))
        pool = _described(chip, jax.eval_shape(
            lambda: paged.init_paged_cache(cfg, self.N_PAGES, self.PAGE,
                                           "int8")))
        assert llama.moe_fused(cfg, params["layers"][0], self.SLOTS)
        # the kernels' interpret=None asks the backend, which is the CPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = jax.jit(
            paged.paged_decode_scan, static_argnums=(0, 7, 8, 9),
            donate_argnums=2,
            static_argnames=("use_kernel", "expert_kernel")).lower(
                cfg, params, pool, chip((self.SLOTS,), I32),
                chip((self.SLOTS,), I32),
                chip((self.SLOTS, cfg.max_seq_len // self.PAGE), I32),
                chip((2,), jnp.uint32), self.STEPS,
                SamplingParams(temperature=0.0, top_k=0, top_p=1.0), 2,
                use_kernel=True, expert_kernel=True).compile()
        text = compiled.as_text()

        e, h, inter = cfg.n_experts, cfg.hidden_size, cfg.intermediate_size
        calls = [line for line in text.splitlines()
                 if "tpu_custom_call" in line and "quant_matmul_ekn4" in line]
        # gate and up share a call, down is the other; the scan's body is
        # compiled once
        assert len(calls) == 2 * self.LAYERS
        assert sum("quant_matmul_ekn4_swiglu" in line
                   for line in calls) == self.LAYERS
        # the stacked weights reach the kernels packed, which is also what
        # the benchmark's expert_mlp_busy_share tells the expert MLP by
        assert all(f"s8[{e},{h},{inter // 2}]" in line
                   or f"s8[{e},{inter},{h // 2}]" in line for line in calls)
        assert not re.search(rf"\[{e},{h},{inter}]|\[{e},{inter},{h}]", text)
        # the tiles of a decode call, from the shapes alone
        assert _ekn4_tiles(self.SLOTS, h, inter // 2) == GATE_UP_TILES
        assert _ekn4_tiles(self.SLOTS, inter, h // 2) == DOWN_TILES
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < 0.25 * self.PARENT_TEMP_BYTES


# (bm, bk, bnp, sub) of ops/quant_matmul.py's stacked int4 kernels at 32 rows
# of Mixtral-8x7B: the packed tiles the chip measured best (PR 35)
GATE_UP_TILES = (32, 4096, 1024, 512)
DOWN_TILES = (32, 3584, 1024, 512)


def _weight(chip, bits, shape, scale_shape):
    """A quantized weight of logical ``shape``: int4 packs the last dim."""
    if bits == 8:
        return QuantTensor(q=chip(shape, I8), scale=chip(scale_shape, BF16))
    return QuantTensor4(q=chip((*shape[:-1], shape[-1] // 2), I8),
                        scale=chip(scale_shape, BF16))


class TestFusedDequantMatmulsCompileForV5e:
    """int4 was refused before this file existed: ``arith.shli`` on
    ``vector<8x128x4xi8>`` — the nibble split now widens to int32 first."""

    @pytest.mark.parametrize("bits", [8, 4])
    @pytest.mark.parametrize("m", [144, 4096])
    @pytest.mark.parametrize("k,n", [
        (CFG.hidden_size, CFG.intermediate_size),
        (CFG.intermediate_size, CFG.hidden_size),
        (CFG.hidden_size, CFG.kv_dim)])
    def test_quant_matmul(self, chip, k, n, m, bits):
        _compiles_with_kernel(
            functools.partial(quant_matmul, interpret=False),
            chip((m, k), BF16), _weight(chip, bits, (k, n), (1, n)))

    @pytest.mark.parametrize("m", [144, 4096])
    def test_quant_matmul_head_int4(self, chip, m):
        v, k = CFG.vocab_size, CFG.hidden_size
        _compiles_with_kernel(
            functools.partial(quant_matmul_head, interpret=False),
            chip((m, k), BF16), _weight(chip, 4, (v, k), (v, 1)))

    def test_quant_matmul_experts_int4(self, chip):
        cfg = MIXTRAL_8X7B
        e, k, n = cfg.n_experts, cfg.hidden_size, cfg.intermediate_size
        _compiles_with_kernel(
            functools.partial(quant_matmul_experts, interpret=False),
            chip((1, 144, k), BF16), _weight(chip, 4, (e, k, n), (e, 1, n)))


    @pytest.mark.parametrize("m", [2, 8, 32, 256, 1024, 1168])
    def test_quant_swiglu_experts_int4(self, chip, m):
        """The fused expert form at the positions the rule may give it
        (up to ``llama.MOE_FUSED_MAX_POSITIONS``): a decode call of 2, 8 or
        32 slots, the check's 1,024-position prefill, and a prefix-hit
        chunk of 73 pages, whose 1,168 rows no tile divides."""
        cfg = MIXTRAL_8X7B
        e, k, n = cfg.n_experts, cfg.hidden_size, cfg.intermediate_size
        _compiles_with_kernel(
            functools.partial(quant_swiglu_experts, interpret=False),
            chip((1, m, k), BF16), _weight(chip, 4, (e, k, n), (e, 1, n)),
            _weight(chip, 4, (e, k, n), (e, 1, n)),
            _weight(chip, 4, (e, n, k), (e, 1, k)))


class _Device:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


class TestNoFallbackHidesTheDevice:
    def test_unknown_tpu_kind_has_no_peaks(self):
        with pytest.raises(ValueError, match="TPU v9"):
            profiling.chip_peaks(_Device("tpu", "TPU v9"))
        with pytest.raises(ValueError, match="TPU v9"):
            profiling.mfu(CFG, 100.0, 512, device=_Device("tpu", "TPU v9"))
        assert profiling.chip_peaks(_Device("cpu", "cpu")) is None

    def test_chip_smoke_exits_before_any_model_without_a_tpu(
            self, monkeypatch, capsys):
        """The one entry point that measures on a chip: where JAX finds no
        TPU it exits non-zero with no phase run, no model built and no
        verdict printed."""
        import chip_smoke

        def refuse(*_, **__):
            raise AssertionError("a phase ran without a TPU")

        monkeypatch.setattr(chip_smoke, "run_phase", refuse)
        # its meter would stay subscribed to jax.monitoring in this process
        monkeypatch.setattr(chip_smoke, "CompileMeter", lambda: None)
        # main() places the compile cache as every entry point does
        monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
        with pytest.raises(SystemExit) as e:
            chip_smoke.main([])
        assert e.value.code not in (0, None)
        said = capsys.readouterr()
        assert "JAX found no TPU" in said.err
        assert '"ok"' not in said.out and '"phase"' not in said.out

    def test_chip_smoke_unknown_tpu_kind_is_an_error(self, monkeypatch):
        import chip_smoke

        monkeypatch.setattr(jax, "devices",
                            lambda *_: [_Device("tpu", "TPU v9")])
        with pytest.raises(ValueError, match="TPU v9"):
            chip_smoke.phase_device(1)

    def test_unknown_model_is_an_argument_error(self, capsys):
        from k8s_llm_rca_tpu.sweeps import run_file

        with pytest.raises(SystemExit) as e:
            run_file.main(["--backend", "engine", "--model", "llama3-80b"])
        assert e.value.code == 2
        assert "invalid choice: 'llama3-80b'" in capsys.readouterr().err


class TestBuildService:
    def test_int4_weights_are_quantized_as_created(self):
        """``--int4`` through build_service: every matmul weight is a
        QuantTensor4, and the tokenizer is as wide as the model's vocab
        (grammar masks meet the logits)."""
        import argparse

        from k8s_llm_rca_tpu.sweeps.common import (
            add_common_args, build_service,
        )

        parser = argparse.ArgumentParser()
        add_common_args(parser)
        service = build_service(parser.parse_args(
            ["--backend", "engine", "--model", "tiny", "--int4",
             "--kv-dtype", "int4", "--max-seq-len", "256"]))
        engine = service.backend.engine
        weights = [leaf for leaf in jax.tree.leaves(
            engine.params, is_leaf=lambda x: isinstance(x, QuantTensor4))
            if getattr(leaf, "ndim", 0) >= 2]
        assert weights and all(isinstance(w, QuantTensor4) for w in weights)
        assert engine.tokenizer.vocab_size == engine.model_cfg.vocab_size
        assert engine.pool.k.dtype == jnp.int8          # packed int4 pages


class TestCompileCachePlacedFromOutside:
    @pytest.fixture
    def cache_dir_restored(self):
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_env_var_set_code_sets_nothing(self, monkeypatch,
                                           cache_dir_restored):
        monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before

    def test_env_var_unset_fixed_path_in_checkout(self, monkeypatch,
                                                  cache_dir_restored):
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert compile_cache.enable_compile_cache() == os.path.join(
            repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            repo, ".jax_cache")
