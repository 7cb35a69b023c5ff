"""The Mamba-2 ops (ops/ssm.py): the chunked scan a prefill runs (its XLA
form and its kernel, interpreted) and the one-step update a decode step runs
equal the recurrence they stand for, a position whose ``dt`` is 0 leaves the
state alone, and the convolution's tail is the last true inputs.  On the
CPU: a correctness check, never a time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_rca_tpu.ops import ssm

H, P, G, N = 8, 4, 2, 16


def _inputs(batch, length, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (batch, length, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (batch, length, H)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.5))
    b = jax.random.normal(k[3], (batch, length, G, N))
    c = jax.random.normal(k[4], (batch, length, G, N))
    d = jax.random.normal(k[5], (H,))
    return x, dt, a, b, c, d


@pytest.mark.parametrize("length, chunk", [
    (32, 16), (48, 16), (16, 16),       # the chunk divides the length
    (37, 16), (5, 16), (129, 32),       # it does not
    (64, 128),                          # one chunk longer than the sequence
])
def test_chunked_scan_equals_the_recurrence(length, chunk):
    x, dt, a, b, c, d = _inputs(2, length, seed=length)
    want_y, want_h = ssm.ssm_recurrence(x, dt, a, b, c, d)
    with jax.default_matmul_precision("highest"):
        y, h = ssm.ssm_chunk_scan_xla(x, dt, a, b, c, d, chunk)
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h, want_h, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("true", [1, 7, 16, 23])
def test_a_pad_position_does_not_advance_the_state(true):
    """``dt`` masked to 0 past a row's true length: the state after a
    padded bucket is the state after the true tokens, whatever the pad
    positions hold."""
    x, dt, a, b, c, d = _inputs(1, 32, seed=true)
    _, want_h = ssm.ssm_recurrence(x[:, :true], dt[:, :true], a,
                                   b[:, :true], c[:, :true], d)
    masked = jnp.where(jnp.arange(32)[None, :, None] < true, dt, 0.0)
    with jax.default_matmul_precision("highest"):
        _, h = ssm.ssm_chunk_scan_xla(x, masked, a, b, c, d, 16)
    np.testing.assert_allclose(h, want_h, rtol=2e-4, atol=2e-4)


# ------------------------------------------ the prefill's kernel (interpret)

# heads, head_dim, groups, state, chunk: the two cells' geometry at a small
# state, and a tiny one
GRANITE = (64, 64, 1, 16, 256)
NEMOTRON = (128, 64, 8, 16, 128)
SMALL = (8, 4, 2, 16, 16)
# name -> (geometry, padded length, true length of each row, dtype of x, B
# and C, factor on dt)
SCAN_CASES = {
    "granite-geometry-ragged-length": (GRANITE, 300, (300,), "bfloat16", 1),
    "nemotron-geometry-whole-chunks": (NEMOTRON, 256, (256,), "bfloat16", 1),
    "length-under-one-chunk": (SMALL, 5, (5, 5), "bfloat16", 1),
    "float32-inputs": (SMALL, 37, (37, 37), "float32", 1),
    "tail-is-padding": (SMALL, 48, (21,), "bfloat16", 1),
    "pad-from-a-chunk-boundary": (SMALL, 64, (32,), "bfloat16", 1),
    "two-rows-two-lengths": (SMALL, 48, (48, 7), "bfloat16", 1),
    "decay-underflows-inside-a-chunk": (SMALL, 48, (48,), "bfloat16", 200),
}


def _scan_inputs(geometry, length, true, dtype, factor):
    """One row a true length, right-padded to ``length`` (``dt = 0`` at the
    pad positions), ``x``, ``B`` and ``C`` in ``dtype``, ``dt`` times
    ``factor``."""
    heads, p, groups, n, _ = geometry
    k = jax.random.split(jax.random.PRNGKey(length), 6)
    rows = len(true)
    x = jax.random.normal(k[0], (rows, length, heads, p)).astype(dtype)
    dt = factor * jax.nn.softplus(
        jax.random.normal(k[1], (rows, length, heads)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (heads,), minval=0.0, maxval=2.5))
    b = jax.random.normal(k[3], (rows, length, groups, n)).astype(dtype)
    c = jax.random.normal(k[4], (rows, length, groups, n)).astype(dtype)
    d = jax.random.normal(k[5], (heads,))
    dt = jnp.where(jnp.arange(length)[None, :, None]
                   < jnp.asarray(true)[:, None, None], dt, 0.0)
    return x, dt, a, b, c, d


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_scan_kernel_equals_the_recurrence_and_the_xla_form(case):
    """``ssm_chunk_scan`` (interpreted) against ``ssm_recurrence`` over each
    row's true positions and against ``ssm_chunk_scan_xla``, at float32's
    level: 2e-5 of the largest value where one bfloat16 rounding is 4e-3.
    A pad position (``dt = 0``) leaves the state the last true position's,
    and its ``y`` is what the recurrence gives there (the standing state
    against that position's ``C``, and ``D x``)."""
    geometry, length, true, dtype, factor = SCAN_CASES[case]
    (heads, p, _, n, chunk), rows = geometry, len(true)
    x, dt, a, b, c, d = _scan_inputs(geometry, length, true, dtype, factor)
    y, h = ssm.ssm_chunk_scan(x, dt, a, b, c, d, chunk, interpret=True)
    assert y.dtype == jnp.float32 and h.dtype == jnp.float32
    assert y.shape == (rows, length, heads, p)
    assert h.shape == (rows, heads, p, n)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(h).all())

    def close(got, want):
        np.testing.assert_allclose(
            got, want, rtol=2e-5,
            atol=2e-5 * max(1.0, float(jnp.max(jnp.abs(want)))))

    with jax.default_matmul_precision("highest"):
        xla_y, xla_h = ssm.ssm_chunk_scan_xla(x, dt, a, b, c, d, chunk)
    close(y, xla_y)
    close(h, xla_h)
    want_y, whole_h = ssm.ssm_recurrence(x, dt, a, b, c, d)
    close(y, want_y)
    for row, n_true in enumerate(true):
        # the state is the one the true positions alone leave
        want_h = whole_h[row:row + 1]
        if n_true < length:
            _, want_h = ssm.ssm_recurrence(
                x[row:row + 1, :n_true], dt[row:row + 1, :n_true], a,
                b[row:row + 1, :n_true], c[row:row + 1, :n_true], d)
        close(h[row:row + 1], want_h)


@pytest.mark.parametrize("heads_a_tile", [4, 2],
                         ids=["a-group-a-tile", "half-a-group-a-tile"])
def test_scan_kernel_walks_its_tiles_of_heads(monkeypatch, heads_a_tile):
    """With room for fewer heads than the model has, the grid walks tiles
    of heads (whole groups, or equal parts of one): each step rotates its
    tile's columns of ``cum`` and ``dt`` to the front and reads its own
    group's ``B`` and ``C``; the result is the one-tile result."""
    heads, p, groups, n, chunk = SMALL
    monkeypatch.setattr(ssm, "_SCAN_TILE_BYTES", heads_a_tile * p * n * 4)
    assert ssm.head_tile(heads, groups, p * n * 4,
                         ssm._SCAN_TILE_BYTES) == heads_a_tile
    x, dt, a, b, c, d = _scan_inputs(SMALL, 40, (29, 29), "bfloat16", 1)
    # the jitted entry point caches by its arguments, not by the budget
    y, h = ssm.ssm_chunk_scan.__wrapped__(x, dt, a, b, c, d, chunk,
                                          interpret=True)
    with jax.default_matmul_precision("highest"):
        want_y, want_h = ssm.ssm_chunk_scan_xla(x, dt, a, b, c, d, chunk)
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(h, want_h, rtol=2e-5, atol=2e-5)


def test_state_update_is_one_step_of_the_recurrence():
    x, dt, a, b, c, d = _inputs(3, 9)
    want_y, want_h = ssm.ssm_recurrence(x, dt, a, b, c, d)
    _, before = ssm.ssm_recurrence(x[:, :8], dt[:, :8], a, b[:, :8],
                                   c[:, :8], d)
    y, h = ssm.ssm_state_update(before, x[:, 8], dt[:, 8], a, b[:, 8],
                                c[:, 8], d)
    np.testing.assert_allclose(y, want_y[:, 8], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h, want_h, rtol=1e-5, atol=1e-5)


def test_state_update_keeps_the_state_dtype_and_head_groups():
    """Head ``h`` reads group ``h // (heads / groups)``: moving group 1's
    B moves the second half of the heads and not the first."""
    x, dt, a, b, c, d = _inputs(1, 1)
    h0 = jnp.zeros((1, H, P, N), jnp.bfloat16)
    _, h1 = ssm.ssm_state_update(h0, x[:, 0], dt[:, 0], a, b[:, 0],
                                 c[:, 0], d)
    assert h1.dtype == jnp.bfloat16
    moved = b[:, 0].at[:, 1].add(1.0)
    _, h2 = ssm.ssm_state_update(h0, x[:, 0], dt[:, 0], a, moved, c[:, 0],
                                 d)
    same = np.asarray(h1 == h2).reshape(H, -1).all(axis=1)
    assert same[:H // G].all() and not same[H // G:].any()


@pytest.mark.parametrize("length", [1, 2, 3, 10])
def test_convolution_steps_equal_the_sequence_form(length):
    k, ch = 4, 6
    keys = jax.random.split(jax.random.PRNGKey(length), 3)
    x = jax.random.normal(keys[0], (2, length, ch))
    w = jax.random.normal(keys[1], (k, ch))
    bias = jax.random.normal(keys[2], (ch,))
    want = ssm.causal_conv(x, w, bias)
    tail = jnp.zeros((2, k - 1, ch))
    for t in range(length):
        out, tail = ssm.conv_step(x[:, t], w, bias, tail)
        np.testing.assert_allclose(out, want[:, t], rtol=1e-5, atol=1e-5)
    # the tail a prefill leaves is the one the steps arrive at, cut at the
    # true length whatever the pad holds
    padded = jnp.concatenate([x, 9.0 * jnp.ones((2, 5, ch))], axis=1)
    np.testing.assert_allclose(
        ssm.conv_tail(padded, jnp.array([length, length]), k), tail)


# ------------------------------------- the decode step's kernel (interpret)

SHAPES = {"one-group": (8, 4, 1, 16), "groups-of-heads": (8, 4, 2, 16)}
MASKS = {"all-live": [True] * 4, "some-dead": [False, True, False, True],
         "all-dead": [False] * 4}
LAYERS, LAYER = 3, 2


def _pool_inputs(shape, dtype, length, seed=0):
    """A stacked state of ``LAYERS`` layers over four slots, and ``length``
    positions of operands for them."""
    heads, p, groups, n = shape
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    state = jax.random.normal(k[0], (LAYERS, 4, heads, p, n))
    x = jax.random.normal(k[1], (4, length, heads, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[2], (4, length, heads)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[3], (heads,), minval=0.0, maxval=2.5))
    b = jax.random.normal(k[4], (4, length, groups, n)).astype(dtype)
    c = jax.random.normal(k[5], (4, length, groups, n)).astype(dtype)
    d = jax.random.normal(k[6], (heads,))
    return state, x, dt, a, b, c, d


@pytest.mark.parametrize("mask", list(MASKS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernel_moves_the_live_slots_of_one_layer_and_nothing_else(
        shape, dtype, mask):
    """``ssm_state_update_in_place`` on layer 2 of a stacked state: a live
    slot's ``h'`` and ``y`` are the XLA form's, over three positions the
    recurrence's; a dead slot's state comes back bit for bit with ``y``
    zeros; no other layer is touched."""
    live = np.asarray(MASKS[mask])
    state, x, dt, a, b, c, d = _pool_inputs(SHAPES[shape], dtype, 3,
                                            seed=len(mask))
    slots = ssm.live_slots(jnp.asarray(live))
    assert int(slots.count[0]) == live.sum()
    assert sorted(np.asarray(slots.order)[:live.sum()]) == list(
        np.flatnonzero(live))
    want_y, want_h = ssm.ssm_state_update(state[LAYER], x[:, 0], dt[:, 0],
                                          a, b[:, 0], c[:, 0], d)
    # one program for the three positions, the layer a traced scalar
    step = jax.jit(lambda s, x, dt, b, c: ssm.ssm_state_update_in_place(
        s, jnp.int32(LAYER), x, dt, a, b, c, d, slots, interpret=True))
    y, new = step(state, x[:, 0], dt[:, 0], b[:, 0], c[:, 0])
    assert (y.dtype, new.dtype, new.shape) == (jnp.float32, state.dtype,
                                               state.shape)
    np.testing.assert_allclose(y[live], want_y[live], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new[LAYER][live], want_h[live], rtol=1e-6,
                               atol=1e-6)
    assert not np.asarray(y)[~live].any()
    np.testing.assert_array_equal(new[LAYER][~live], state[LAYER][~live])
    others = [i for i in range(LAYERS) if i != LAYER]
    np.testing.assert_array_equal(np.asarray(new)[others],
                                  np.asarray(state)[others])

    # two more positions: the recurrence from there
    ys = [y]
    for t in (1, 2):
        y, new = step(new, x[:, t], dt[:, t], b[:, t], c[:, t])
        ys.append(y)
    want_ys, want_h = ssm.ssm_recurrence(x, dt, a, b, c, d,
                                         h0=state[LAYER])
    np.testing.assert_allclose(jnp.stack(ys, 1)[live], want_ys[live],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(new[LAYER][live], want_h[live], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(new[LAYER][~live], state[LAYER][~live])


def test_kernel_keeps_a_bfloat16_state_and_tiles_inside_a_group(monkeypatch):
    """A state kept in bfloat16 is computed in float32 and stored back in
    its dtype; with room for two heads a tile, a group of four heads is
    two tiles, and the result is the same."""
    live = np.asarray(MASKS["some-dead"])
    state, x, dt, a, b, c, d = _pool_inputs(SHAPES["groups-of-heads"],
                                            "float32", 1)
    state = state.astype(jnp.bfloat16)
    want_y, want_h = ssm.ssm_state_update(state[LAYER], x[:, 0], dt[:, 0],
                                          a, b[:, 0], c[:, 0], d)
    monkeypatch.setattr(ssm, "_TILE_BYTES", 2 * 4 * 16 * 2)
    assert ssm.head_tile(8, 2, 4 * 16 * 2) == 2
    y, new = ssm.ssm_state_update_in_place.__wrapped__(
        state, LAYER, x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], d,
        ssm.live_slots(jnp.asarray(live)), interpret=True)
    assert new.dtype == jnp.bfloat16
    np.testing.assert_allclose(y[live], want_y[live], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(new[LAYER][live], want_h[live])
    np.testing.assert_array_equal(new[LAYER][~live], state[LAYER][~live])


@pytest.mark.parametrize("heads, groups, head_bytes, want", [
    (64, 1, 64 * 128 * 4, 64),      # granite-4.0-h-micro: a slot whole
    (128, 8, 64 * 128 * 4, 64),     # nemotron-3-super: four groups of 16
    (128, 8, 3 * 64 * 128 * 4, 16),  # a head three times the size: one
    (64, 1, 128 * 128 * 4, 32),     # twice the head: half the group
    (6, 2, 4 << 20, 1),             # a head over the budget: one head
])
def test_a_tile_is_whole_groups_or_an_equal_part_of_one(heads, groups,
                                                    head_bytes, want):
    assert ssm.head_tile(heads, groups, head_bytes) == want
