"""Latent attention's pieces (``ops/mla_attention.py``, the value width of
``ops/flash_attention.py``, the interleaved rotary pairs of ``ops/rope.py``):
the absorbed decode kernel, interpreted, against its XLA form over ragged
lengths; the flash call at unequal key and value widths against the masked
form; absorbed equals un-absorbed; the pairing."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_rca_tpu.config import TINY_KANANA_MOE
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.ops.attention import causal_attention
from k8s_llm_rca_tpu.ops.flash_attention import flash_attention
from k8s_llm_rca_tpu.ops.mla_attention import (
    RUN_PAGES, absorb_query, mla_block_pages, mla_page_copies,
    mla_paged_attention, mla_paged_attention_xla, stored_lanes,
    unabsorb_values,
)
from k8s_llm_rca_tpu.ops.rope import apply_rope, rope_frequencies

CFG = TINY_KANANA_MOE
PAGE, PPS, HEADS, ROW, VALUE = 8, 12, 4, 48, 32


def _pool(layers=2, n_pages=40, dtype=jnp.float32, seed=0):
    """Rows of 48 values at 128 stored lanes, zeros behind."""
    rows = jax.random.normal(jax.random.PRNGKey(seed),
                             (layers, n_pages, PAGE, ROW), jnp.float32)
    return jnp.pad(rows, ((0, 0),) * 3 + ((0, stored_lanes(ROW) - ROW),)
                   ).astype(dtype)


# length 0 (a slot that holds nothing), 1, a page boundary (8, 64), inside a
# page, the whole table (96), and lengths that end inside a kernel block
LENGTHS = (0, 1, 8, 37, 64, 96)


@pytest.mark.parametrize("block_tokens", [16, 32, 1024],
                         ids=["blocks-of-2", "blocks-of-4", "one-block"])
@pytest.mark.parametrize("layer", [None, 1], ids=["one-layer", "stacked"])
def test_the_kernel_equals_the_xla_form_over_ragged_lengths(block_tokens,
                                                            layer):
    """Float32 operands, so the two agree to float32's own rounding: a
    walk computed in bfloat16 would be off by 1e-2."""
    pool = _pool()
    rng = np.random.default_rng(1)
    q = jax.random.normal(jax.random.PRNGKey(2), (len(LENGTHS), HEADS, ROW))
    tables = jnp.asarray(rng.integers(1, 40, (len(LENGTHS), PPS)), jnp.int32)
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    scale = 1.0 / math.sqrt(40)
    got = mla_paged_attention(
        q, pool if layer is not None else pool[0], lengths, tables,
        scale=scale, n_value=VALUE, layer=layer, interpret=True,
        block_tokens=block_tokens)
    want = mla_paged_attention_xla(q, pool[layer or 0], lengths, tables,
                                   scale=scale, n_value=VALUE)
    assert got.shape == (len(LENGTHS), HEADS, VALUE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    assert not np.asarray(got[0]).any()          # nothing attended: zeros


def test_the_scale_is_an_argument_and_not_the_rows_width():
    pool, q = _pool(layers=1)[0], jnp.ones((1, HEADS, ROW))
    tables = jnp.arange(1, 1 + PPS, dtype=jnp.int32)[None]
    lengths = jnp.asarray([50], jnp.int32)
    outs = [np.asarray(mla_paged_attention(
        q, pool, lengths, tables, scale=s, n_value=VALUE, interpret=True))
        for s in (1.0 / math.sqrt(40), 1.0 / math.sqrt(ROW))]
    assert np.abs(outs[0] - outs[1]).max() > 1e-3


def test_bfloat16_rows_are_multiplied_as_stored():
    """bfloat16 operands, float32 accumulation: within the rounding of the
    probabilities to the rows' type (2^-8) of the float32 form over the
    same stored rows."""
    pool = _pool(dtype=jnp.bfloat16)
    q = jax.random.normal(jax.random.PRNGKey(3), (3, HEADS, ROW),
                          jnp.bfloat16)
    tables = jnp.asarray(np.random.default_rng(4).integers(1, 40, (3, PPS)),
                         jnp.int32)
    lengths = jnp.asarray([5, 40, 96], jnp.int32)
    got = mla_paged_attention(q, pool, lengths, tables, scale=0.2,
                              n_value=VALUE, layer=0, interpret=True)
    want = mla_paged_attention_xla(q, pool[0], lengths, tables, scale=0.2,
                                   n_value=VALUE)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_the_block_and_the_stored_width():
    assert mla_block_pages(16, 1024) == 64 and mla_block_pages(16, 8) == 8
    assert mla_block_pages(1024, 4) == 1
    assert [stored_lanes(n) for n in (48, 128, 576)] == [128, 128, 640]


# ------------------------------------------- adjacent pages are one copy

# one slot's table each, 24 entries over a pool of 40 pages: where a group
# of RUN_PAGES = 8 entries ascends by one the kernel starts one copy for it
TABLES = {
    "one-run": list(range(5, 29)),
    "scattered": [9, 3, 30, 17, 6, 26, 1, 12, 38, 21, 15, 33,
                  24, 4, 36, 19, 7, 28, 13, 39, 2, 31, 10, 22],
    # a run (1..11) that starts inside a group and crosses into the next,
    # where a stranger breaks it; the last group is a run
    "mixed": [20, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 33, 13, 14, 15,
              16, 17, 18, 19, 20, 21, 22, 23],
    "descending": list(range(28, 4, -1)),
    "run-to-the-pools-last-page": list(range(16, 40)),
    # pages held for 11 entries and the trash page's zeros behind them
    "trash-behind": list(range(7, 18)) + [0] * 13,
}
T_PPS = 24


def _copies_by_hand(table, n_block):
    """The kernel's rule in plain loops: whole blocks (the tail repeats the
    table's last entry), within a block groups of RUN_PAGES, the entries
    behind the last whole group one copy each."""
    table = list(table) + [table[-1]] * (-len(table) % n_block)
    copies = 0
    for at in range(0, len(table), n_block):
        block = table[at:at + n_block]
        whole = n_block - n_block % RUN_PAGES
        for g in range(0, whole, RUN_PAGES):
            group = block[g:g + RUN_PAGES]
            run = all(b == a + 1 for a, b in zip(group, group[1:]))
            copies += 1 if run else RUN_PAGES
        copies += n_block - whole
    return copies


@pytest.mark.parametrize("block_tokens", [64, 128, 1024],
                         ids=["blocks-of-8", "blocks-of-16", "one-block"])
@pytest.mark.parametrize("name", list(TABLES))
def test_the_kernel_equals_the_xla_form_wherever_the_pages_lie(name,
                                                               block_tokens):
    """The same table at five lengths, 0 among them: whichever branch a
    group takes, the rows land where the single copies put them.  Blocks
    of 16 leave the table's last block half behind its end."""
    assert RUN_PAGES == 8
    pool = _pool()
    lens = (0, 5, 67, 128, 192) if name != "trash-behind" else (0, 5, 65, 88)
    lengths = jnp.asarray(lens, jnp.int32)
    tables = jnp.tile(jnp.asarray(TABLES[name], jnp.int32), (len(lens), 1))
    q = jax.random.normal(jax.random.PRNGKey(5), (len(lens), HEADS, ROW))
    got = mla_paged_attention(q, pool, lengths, tables, scale=0.2,
                              n_value=VALUE, layer=1, interpret=True,
                              block_tokens=block_tokens)
    want = mla_paged_attention_xla(q, pool[1], lengths, tables, scale=0.2,
                                   n_value=VALUE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    assert not np.asarray(got[0]).any()
    n_block = mla_block_pages(PAGE, T_PPS, block_tokens)
    assert mla_page_copies(
        np.asarray(tables[:1]), np.asarray([-(-T_PPS // n_block)]),
        n_block) == _copies_by_hand(TABLES[name], n_block)


def test_the_copies_of_a_dispatch_by_the_kernels_rule():
    """``mla_page_copies`` in numbers: a run is one copy wherever it lies
    in the pool, ids that ascend across a group's edge are two groups'
    business, and only the blocks a slot's length reaches count."""
    by_name = {name: [_copies_by_hand(t, n) for n in (8, 16, 24)]
               for name, t in TABLES.items()}
    assert by_name == {
        "one-run": [3, 3 + 8, 3], "scattered": [24, 24 + 8, 24],
        "mixed": [17, 17 + 8, 17], "descending": [24, 24 + 8, 24],
        "run-to-the-pools-last-page": [3, 3 + 8, 3],
        "trash-behind": [17, 17 + 8, 17]}
    tables = np.asarray(list(TABLES.values()), np.int32)
    assert mla_page_copies(tables, np.full(6, 3), 8) == 3 + 24 + 17 + 24 + 3 \
        + 17
    assert mla_page_copies(tables, np.asarray([1, 2, 0, 0, 0, 3]), 8) == 1 \
        + 16 + 17
    assert mla_page_copies(tables, np.zeros(6, int), 8) == 0


@pytest.mark.parametrize("pps, lens", [(9, (0, 3, 72)), (5, (0, 40))],
                         ids=["a-group-and-one-entry", "under-a-group"])
def test_a_table_shorter_than_a_block(pps, lens):
    """Nine entries are one group and a single copy behind it; five are
    single copies alone."""
    pool = _pool(layers=1)[0]
    lengths = jnp.asarray(lens, jnp.int32)
    tables = jnp.tile(jnp.arange(11, 11 + pps, dtype=jnp.int32),
                      (len(lens), 1))
    q = jax.random.normal(jax.random.PRNGKey(6), (len(lens), HEADS, ROW))
    got = mla_paged_attention(q, pool, lengths, tables, scale=0.2,
                              n_value=VALUE, interpret=True)
    want = mla_paged_attention_xla(q, pool, lengths, tables, scale=0.2,
                                   n_value=VALUE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    assert mla_page_copies(np.asarray(tables[:1]), np.asarray([1]),
                           pps) == (2 if pps == 9 else 5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_same_rows_as_runs_and_scattered_are_equal_bit_for_bit(dtype):
    """One sequence's rows laid out twice, in adjacent pages and in
    scattered ones: the run's one copy and the single copies put the same
    rows at the same places of the buffer, so not a bit differs."""
    rows = _pool(layers=1, n_pages=T_PPS, dtype=dtype, seed=7)[0]
    adjacent = np.arange(10, 10 + T_PPS)
    scattered = np.asarray(TABLES["scattered"])
    pools = [jnp.zeros((40,) + rows.shape[1:], dtype).at[ids].set(rows)
             for ids in (adjacent, scattered)]
    lengths = jnp.asarray([192, 83, 7], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(8), (3, HEADS, ROW)).astype(
        dtype)
    outs = [np.asarray(mla_paged_attention(
        q, pool, lengths, jnp.tile(jnp.asarray(ids, jnp.int32), (3, 1)),
        scale=0.2, n_value=VALUE, interpret=True, block_tokens=64)
        .astype(jnp.float32)) for pool, ids in zip(pools,
                                                   (adjacent, scattered))]
    assert outs[0].any()
    np.testing.assert_array_equal(outs[0], outs[1])


# ------------------------------------------------------- the prefill's widths


@pytest.mark.parametrize("s, lean", [(1, False), (127, False), (300, False),
                                     (300, True), (1000, True)])
def test_flash_attention_at_unequal_key_and_value_widths(s, lean):
    """Keys of 40 and values of 16 (latent attention's 192 and 128 at toy
    widths), one kv head a query head, two rows of unequal length: the
    masked form, which takes the widths from its operands too."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(s), 3)
    q = jax.random.normal(kq, (2, s, 4, 40))
    k = jax.random.normal(kk, (2, s, 4, 40))
    v = jax.random.normal(kv, (2, s, 4, 16))
    lens = jnp.asarray([s, max(1, s - 3)], jnp.int32)
    kw = dict(lean=True, block_q=256, block_k=256) if lean else {}
    got = flash_attention(q, k, v, lens, interpret=True, **kw)
    want = causal_attention(q, k, v, lens)
    assert got.shape == (2, s, 4, 16)
    for row, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(np.asarray(got[row, :n]),
                                   np.asarray(want[row, :n]), atol=2e-5)


def test_equal_widths_trace_as_they_did():
    """The value width comes from the operands: at equal widths the call's
    jaxpr holds the same shapes it always did (the other families' HLO pins
    in tests/test_exaone_moe.py hold the whole programs)."""
    q = jnp.zeros((1, 256, 4, 32))
    text = str(jax.make_jaxpr(lambda q: flash_attention(
        q, q[:, :, :2], q[:, :, :2], jnp.asarray([256]), interpret=True))(q))
    assert "f32[1,4,256,32]" in text and "f32[128,32]" in text


# ------------------------------------------------- absorbed equals un-absorbed


def test_absorbed_decode_equals_the_published_form():
    """One query against 21 cached tokens: scores from each head's written
    out keys and the weighted sum of its written out values (the prefill's
    form), against the absorbed query over the rows and the attended
    latents through the value up-projection."""
    r, nope, rope, vd = 32, 24, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    w_up = jax.random.normal(ks[0], (r, HEADS, nope + vd)) / math.sqrt(r)
    rows = jax.random.normal(ks[1], (21, r + rope))
    q_nope = jax.random.normal(ks[2], (1, HEADS, nope))
    q_rope = jax.random.normal(ks[3], (1, HEADS, rope))
    scale = 1.0 / math.sqrt(nope + rope)

    k_nope = jnp.einsum("tr,rhd->thd", rows[:, :r], w_up[..., :nope])
    v = jnp.einsum("tr,rhd->thd", rows[:, :r], w_up[..., nope:])
    scores = (jnp.einsum("bhd,thd->bht", q_nope, k_nope)
              + jnp.einsum("bhd,td->bht", q_rope, rows[:, r:])) * scale
    want = jnp.einsum("bht,thd->bhd", jax.nn.softmax(scores, -1), v)

    q = jnp.concatenate([absorb_query(q_nope, w_up, nope), q_rope], -1)
    pages = jnp.pad(rows, ((0, 3), (0, 0))).reshape(3, 8, r + rope)
    o_latent = mla_paged_attention_xla(
        q, pages, jnp.asarray([21]), jnp.asarray([[0, 1, 2]]), scale=scale,
        n_value=r)
    got = unabsorb_values(o_latent, w_up, nope)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_the_decode_query_and_the_prefills_keys_meet():
    """``llama.latent_decode_query`` at position p scores a token's cached
    row as the published form scores that token's written-out key."""
    params = llama.init_params(CFG, jax.random.PRNGKey(5))
    layer, lcfg = params["layers"][1], CFG.layer_cfg(1)
    angles = rope_frequencies(CFG.head_dim, CFG.max_seq_len, CFG.rope_theta)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 9, CFG.hidden_size))
    positions = jnp.arange(9)[None]
    h = llama.rms_norm(x, layer["attn_norm"], CFG.rms_norm_eps)
    q_nope, q_rope, rows = llama._latent_project(lcfg, layer, h, angles,
                                                 positions)
    kv = (rows[..., :32] @ layer["w_kvb"]).reshape(1, 9, 4, 40)
    k = jnp.concatenate([kv[..., :24], jnp.broadcast_to(
        rows[:, :, None, 32:], (1, 9, 4, 16))], -1)
    want = jnp.einsum("hd,thd->ht", jnp.concatenate(
        [q_nope, q_rope], -1)[0, 8], k[0])
    q_abs, row = llama.latent_decode_query(lcfg, layer, x[:, 8:], angles,
                                           positions[:, 8:])
    np.testing.assert_allclose(np.asarray(row[0]), np.asarray(rows[0, 8]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(q_abs[0] @ rows[0].T),
                               np.asarray(want), atol=2e-5)


# ------------------------------------------------------------------ the pairing


def test_interleaved_pairs_are_deinterleaved_then_rotated_as_halves():
    """Values 2i and 2i + 1 are a pair, rotated by position x theta^(-2i/d),
    and leave in the order evens-then-odds; queries and keys permuted alike
    keep their products."""
    d, theta = 8, 10000.0
    angles = rope_frequencies(d, 32, theta)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 5, 2, d))
    pos = jnp.asarray([[0, 3, 7, 11, 30]])
    got = np.asarray(apply_rope(x, angles, pos, interleaved=True))
    xs = np.asarray(x)
    for t, p in enumerate(np.asarray(pos[0])):
        for i in range(d // 2):
            a = p * theta ** (-2 * i / d)
            even, odd = xs[0, t, :, 2 * i], xs[0, t, :, 2 * i + 1]
            np.testing.assert_allclose(
                got[0, t, :, i], even * np.cos(a) - odd * np.sin(a),
                atol=1e-5)
            np.testing.assert_allclose(
                got[0, t, :, d // 2 + i], odd * np.cos(a) + even * np.sin(a),
                atol=1e-5)
    plain = np.asarray(apply_rope(x, angles, pos))
    assert np.abs(plain - got).max() > 0.1        # the halves' pairing differs
    np.testing.assert_allclose(got[0, 0, :, :4], xs[0, 0, :, 0::2], atol=1e-6)
