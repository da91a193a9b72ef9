"""The window in the flash kernels (``ops/pallas/flash_attention.py``):
the kernels in interpret mode against a dense masked softmax written
from the definition, outputs and the three gradients; that the blocks
below the band are in no grid cell; and the dispatch above the kernels
(``ops/attention.py``)."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention
from ray_tpu.util import tracing

fa = importlib.import_module("ray_tpu.ops.pallas.flash_attention")


def _dense(q, k, v, window):
    """Row t sees keys t - window < j <= t, from the definition."""
    t, d = q.shape[1], q.shape[-1]
    s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(d)
    row, col = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = col <= row
    if window is not None:
        seen &= col > row - window
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v)


def _qkvg(t, h, d, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    return [jax.random.normal(k, (2, t, h, d), jnp.float32) for k in keys]


@pytest.fixture
def notes(monkeypatch):
    """What the traced code says of itself, caught at the call: a train
    step's listener, once a test of this process has built one, takes
    the thread's notes away at the next trace."""
    said = {}
    monkeypatch.setattr(tracing, "note_trace", said.update)
    return said


def _out_and_grads(fn, q, k, v, g):
    out = fn(q, k, v)
    return (out, *jax.grad(lambda *a: (fn(*a) * g).sum(), (0, 1, 2))(q, k, v))


# (rows, block): 256 rows in blocks of 64 is the multi-block grid (192:
# three blocks of it), 128 in one block the single-block body
@pytest.mark.parametrize("t, block", [(256, 64), (128, 128), (192, 64)],
                         ids=["multi_block", "single_block",
                              "three_blocks"])
@pytest.mark.parametrize("h, d", [(1, 128), (2, 64)],
                         ids=["one_head_a_block", "two_heads_a_block"])
@pytest.mark.parametrize("window", [1, 24, 64, 65, 150, 4096],
                         ids=["itself", "under_a_block", "a_block",
                              "a_block_and_one", "several_blocks",
                              "the_whole_row"])
def test_windowed_kernels_are_the_dense_masked_softmax(window, h, d, t,
                                                       block, notes):
    q, k, v, g = _qkvg(t, h, d)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, window=window, block=block,
                                  interpret=True)
    got = _out_and_grads(flash, q, k, v, g)
    want = _out_and_grads(lambda *a: _dense(*a, window), q, k, v, g)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, atol=5e-5, err_msg=name)
    if window >= t:
        # the whole row: plain causal attention, the kernels' program
        # without a window
        assert notes["flash_window"] == "none"
        causal = _out_and_grads(
            lambda q, k, v: fa.flash_attention(
                q, k, v, block=block, interpret=True),
            q, k, v, g)
        for a, b in zip(got, causal):
            np.testing.assert_array_equal(a, b)
    else:
        assert notes["flash_window"] == window


@pytest.mark.parametrize("blocks", [2, 3, 4])
@pytest.mark.parametrize("h, d", [(1, 128), (2, 64)],
                         ids=["one_head_a_block", "two_heads_a_block"])
@pytest.mark.parametrize("window", [1, 24, 64, 65, 150, 4096],
                         ids=["itself", "under_a_block", "a_block",
                              "a_block_and_one", "several_blocks",
                              "the_whole_row"])
def test_multi_block_backward_under_a_window_is_the_dense_masked_softmaxs(
        window, h, d, blocks, notes):
    """The backward kernel where a q-block's first live key block is no
    longer block 0 and a key block's live q-blocks end: dq, dk, dv
    against the dense masked softmax's."""
    q, k, v, g = _qkvg(64 * blocks, h, d, seed=3)

    def grads(fn):
        return _out_and_grads(fn, q, k, v, g)[1:]

    got = grads(lambda q, k, v: fa.flash_attention(
        q, k, v, window=window, block=64, interpret=True))
    assert notes["flash_path"] == "multi_block"
    assert notes["flash_bwd_resident_rows"] == 64 * blocks
    for name, a, b in zip(("dq", "dk", "dv"), got,
                          grads(lambda *a: _dense(*a, window))):
        np.testing.assert_allclose(a, b, atol=5e-5, err_msg=name)


# -- the block a windowed call runs in ----

@pytest.mark.parametrize("t, window, block", [
    (16384, 512, 512),      # Laguna's sliding layers: the window's own
    (4096, 512, 512),       # Phi-4-mini-flash's
    (16384, 256, 256), (1152, 384, 384),
    (16384, 4096, 1024),    # SmallThinker's: a window of four blocks
    (16384, None, 1024), (16384, 1024, 1024),
    (16384, 500, 1024),     # no whole number of 128-row tiles
    (16384, 640, 1024),     # does not divide the row
    (1024, 512, 1024),      # a row of one block
    (64, 24, 64), (32, 8, 32)])    # the tiny presets'
def test_the_block_of_a_windowed_call(t, window, block):
    """``_window_block``: the window's size where a row of several
    blocks has a window shorter than ``_pick_block``'s block, in whole
    128-row tiles, that divides the row; ``_pick_block(t)`` in every
    other case, and ``_pick_block`` itself as it was (the benchmark's
    builders call it with the row alone)."""
    assert fa._window_block(t, window) == block
    picked = {16384: 1024, 4096: 1024, 1152: 576, 1024: 1024, 64: 64,
              32: 32}[t]
    assert fa._pick_block(t) == picked


def test_an_explicit_block_is_the_callers(notes):
    q, k, v, _ = _qkvg(2048, 1, 128)
    jax.eval_shape(lambda *a: fa.flash_attention(
        *a, window=512, block=1024, interpret=True), q, k, v)
    assert notes["flash_block_rows"] == 1024
    assert notes["flash_band_blocks"] == 3


@pytest.mark.parametrize("h, d", [(1, 128), (2, 64)],
                         ids=["one_head_a_block", "two_heads_a_block"])
@pytest.mark.parametrize("t, window", [(1152, 384), (1536, 384)],
                         ids=["three_blocks", "four_blocks"])
def test_the_rules_own_blocks_are_the_dense_masked_softmax(t, window, h, d,
                                                           notes):
    """No ``block=``: the call runs in blocks of its window, every live
    cell masked (one on the diagonal, one on the band's lower edge, none
    wholly seen); output and the three gradients against the dense
    masked softmax, and against the same call in ``_pick_block``'s
    blocks."""
    q, k, v, g = _qkvg(t, h, d, seed=5)

    def flash(block):
        return jax.jit(lambda q, k, v, g: _out_and_grads(
            lambda *a: fa.flash_attention(*a, window=window, block=block,
                                          interpret=True), q, k, v, g))
    got = flash(None)(q, k, v, g)
    n = t // window
    assert notes["flash_path"] == "multi_block"
    assert notes["flash_block_rows"] == window
    assert notes["flash_band_blocks"] == 2 * n - 1
    assert notes["flash_band_area"] == pytest.approx(2.0, abs=3e-3)
    want = _out_and_grads(lambda *a: _dense(*a, window), q, k, v, g)
    large = flash(fa._pick_block(t))(q, k, v, g)
    assert notes["flash_block_rows"] == fa._pick_block(t) > window
    for name, a, b, c in zip(("out", "dq", "dk", "dv"), got, want, large):
        np.testing.assert_allclose(a, b, atol=5e-5, err_msg=name)
        np.testing.assert_allclose(a, c, atol=5e-5, err_msg=name)


@pytest.mark.parametrize("t, block, window, said", [
    # Laguna's cell, now and before; SmallThinker's; Phi-4-mini-flash's
    (16384, None, 512, (512, 63, 2.0)),
    (16384, 1024, 512, (1024, 31, 3.936)),
    (16384, None, 4096, (1024, 70, 1.25)),
    (4096, None, 512, (512, 15, 1.999)),
    (4096, 1024, 512, (1024, 7, 3.733)),
    # a tiny preset's: one block, the square with the band masked in it
    (64, None, 24, (64, 1, 3.251)),
    # a window no shorter than the row: the causal call's, in the grid
    # and in a single block's four causal slabs
    (16384, None, 16384, (1024, 136, 1.062)),
    (1024, None, 4096, (1024, 1, 1.249))])
def test_a_windowed_call_says_its_block_and_its_dead_area(
        t, block, window, said, notes):
    """``flash_block_rows``, ``flash_band_blocks`` at the block that
    ran, and ``flash_band_area``: the entries of the walked block pairs
    over the entries the mask lets through, from the definition."""
    x = jax.ShapeDtypeStruct((1, t, 1, 128), jnp.bfloat16)
    jax.eval_shape(lambda *a: fa.flash_attention(
        *a, window=window, block=block), x, x, x)
    rows, pairs, area = said
    assert notes["flash_block_rows"] == rows
    assert notes["flash_band_blocks"] == pairs
    assert notes["flash_band_area"] == pytest.approx(area, abs=1e-3)
    seen = sum(min(row + 1, window) for row in range(t))
    if rows < t:     # a grid's pairs are computed whole
        assert notes["flash_band_area"] == pytest.approx(
            pairs * rows * rows / seen, abs=1e-3)
    assert notes["flash_window"] == ("none" if window >= t else window)


def test_a_call_without_a_window_leaves_the_notes_it_left(notes):
    q, k, v, _ = _qkvg(128, 1, 128)
    fa.flash_attention(q, k, v, block=64, interpret=True)
    assert notes["flash_path"] == "multi_block"
    assert not {"flash_window", "flash_band_blocks", "flash_block_rows",
                "flash_band_area"} & set(notes)


@pytest.mark.parametrize("t, block, window, cells, walked", [
    (16384, 1024, 4096, 5, 70),     # the cell: 70 of the causal 136
    (16384, 512, 4096, 9, 252),     # 63 blocks of 1,024's worth
    (16384, 1024, None, 16, 136),
    (256, 64, 24, 2, 7), (256, 64, 64, 2, 7), (256, 64, 65, 2, 7),
    (256, 64, 66, 3, 9), (256, 64, 1, 1, 4), (256, 64, 150, 4, 10)])
def test_blocks_below_the_band_are_in_no_grid_cell(t, block, window, cells,
                                                   walked):
    """``_band``: the innermost grid dimension is the band's cells, the
    most live key blocks any q-block has (the forward's key cells) and,
    blocks being square, the most live q-blocks any key block has (the
    backward's query cells), and the block pairs a head walks are the
    live ones alone; against a count from the definition. Then the
    backward kernel's grid walked as the chip walks it (``_bwd_blocks``):
    every live pair met once, key blocks ascending for a q-block; a dead cell on the
    blocks of the live cell next to it; ``o`` fetched once a q-block, in the
    cell that meets its first live key block; ``dq``'s block that of the
    q-block whose last live key block the cell is, its index never
    going back."""
    band_cells, pairs = fa._band(t, block, window)
    assert (band_cells, pairs) == (cells, walked)
    n = t // block
    live = np.zeros((n, n), bool)
    w = t if window is None else window
    for i in range(n):
        for j in range(n):
            rows = np.arange(i * block, (i + 1) * block)[:, None]
            cols = np.arange(j * block, (j + 1) * block)[None, :]
            live[i, j] = ((cols <= rows) & (cols > rows - w)).any()
    assert pairs == live.sum()
    if window is not None:
        assert band_cells == live.sum(1).max() == live.sum(0).max()
        for i in range(n):      # the index maps: first + cell, clamped
            first, last = (int(x) for x in fa._keys_of(i, block, window))
            assert list(np.flatnonzero(live[i])) == list(
                range(first, last + 1))
        for j in range(n):
            first, last = (int(x) for x in fa._queries_of(j, block,
                                                          window, n))
            assert list(np.flatnonzero(live[:, j])) == list(
                range(first, last + 1))
    grid_cells, q_block, o_block, dq_block = fa._bwd_blocks(
        n, block, True, window)
    assert grid_cells == band_cells
    met, o_fetched, dq_at = [], [], []
    for j in range(n):
        cells_of_j = []
        for i in range(grid_cells):
            qb, is_live = fa._query_cell(j, i, blk=block, causal=True,
                                         window=window, nb=n)
            at = (int(q_block(j, i)), int(o_block(j, i)),
                  int(dq_block(j, i)))
            cells_of_j.append((bool(is_live), at))
            if bool(is_live):
                assert at[0] == int(qb) and live[at[0], j]
                met.append((at[0], j))
                if j == np.flatnonzero(live[at[0]])[0]:
                    assert at[1] == at[0]       # delta is made here
                if j == np.flatnonzero(live[at[0]])[-1]:
                    assert at[2] == at[0]       # dq leaves here
            if not o_fetched or o_fetched[-1] != at[1]:
                o_fetched.append(at[1])
            if not dq_at or dq_at[-1] != at[2]:
                dq_at.append(at[2])
        # a dead cell is clamped to the live cell next to it (the
        # diagonal's after it in the causal grid, whose dead cells open
        # a key block's; the last live one before it under a window,
        # where they close it), so it fetches and writes back nothing
        alive = [i for i, (is_live, _) in enumerate(cells_of_j) if is_live]
        for i, (is_live, at) in enumerate(cells_of_j):
            if not is_live:
                assert at == cells_of_j[
                    min(alive, key=lambda a: abs(a - i))][1]
    assert sorted(met) == sorted(zip(*np.nonzero(live)))
    assert len(met) == pairs
    assert o_fetched == list(range(n)) == dq_at


def test_the_windowed_grid_is_the_band_and_the_note_counts_it(notes):
    """The lowered forward call's grid: (batch, lane blocks, q-blocks,
    the band's cells), not the row's key blocks."""
    q, k, v, _ = _qkvg(256, 1, 128)
    fa.flash_attention(q, k, v, window=70, block=64, interpret=True)
    assert notes["flash_band_blocks"] == fa._band(256, 64, 70)[1] == 9
    assert notes["flash_path"] == "multi_block"
    jaxpr = jax.make_jaxpr(lambda q, k, v: fa._flash_fwd(
        q, k, v, scale=1.0, causal=True, blk=64, d=128, hpb=1,
        interpret=False, window=70))(
            *(x.reshape(2, 256, 128) for x in (q, k, v)))
    grids = [e.params["jaxpr"].eqns[0].params["grid_mapping"].grid
             for e in jaxpr.eqns if e.primitive.name in ("pjit", "jit")]
    assert grids == [(2, 1, 4, 3)]
    # the backward: one call over (batch, lane blocks, key blocks, the
    # band's q-cells)
    assert notes["flash_bwd_resident_rows"] == 256
    x = q.reshape(2, 256, 128)
    jaxpr = jax.make_jaxpr(lambda q, k, v, o, lse: fa._flash_bwd(
        q, k, v, o, lse, o, scale=1.0, causal=True, blk=64, d=128, hpb=1,
        interpret=False, window=70))(
            x, x, x, x, jnp.zeros((2, 1, 4, 1, 64), jnp.float32))
    [call] = [e for e in jaxpr.eqns if e.primitive.name in ("pjit", "jit")]
    assert [e.params["grid_mapping"].grid
            for e in call.params["jaxpr"].eqns
            if e.primitive.name == "pallas_call"] == [(2, 1, 4, 3)]


def test_a_window_needs_a_causal_row():
    q, k, v, _ = _qkvg(128, 1, 128)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, causal=False, window=8, interpret=True)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, window=0, interpret=True)


# -- the dispatch above the kernels ----

@pytest.mark.parametrize("window", [1, 24, 64, 1000])
def test_causal_attention_takes_the_window_on_the_xla_path(window):
    """On the CPU ``causal_attention`` is XLA's
    ``dot_product_attention``: a query sees exactly ``window`` keys."""
    q, k, v, _ = _qkvg(64, 2, 16)
    got = attention.causal_attention(q, k, v, window=window)
    np.testing.assert_allclose(got, _dense(q, k, v, window), atol=2e-5)


def test_the_kernel_gets_the_window_where_it_is_eligible(monkeypatch):
    q, k, v, _ = _qkvg(256, 1, 128)
    asked = {}

    def flash(q, k, v, **kw):
        asked.update(kw)
        return q
    monkeypatch.setattr(attention, "_flash_ok", lambda *a: True)
    monkeypatch.setattr(fa, "flash_attention", flash)
    attention.causal_attention(q, k, v, force_flash=True, window=24)
    assert asked["window"] == 24 and asked["causal"] is True
    attention.causal_attention(q, k, v, force_flash=True)
    assert asked["window"] is None


def test_a_window_on_a_mesh_that_splits_the_sequence_is_refused():
    from ray_tpu.parallel.mesh import make_mesh
    devices = jax.devices()[:4]
    mesh = make_mesh({"dp": 2, "sp": 2}, devices=devices)
    with pytest.raises(NotImplementedError, match="halo"):
        attention.make_sharded_causal_attention(mesh, window=16)
    assert attention.make_sharded_causal_attention(mesh) is not None
    # dp alone: each chip's own rows under shard_map, with the window
    mesh = make_mesh({"dp": 4}, devices=devices)
    q, k, v, _ = _qkvg(64, 2, 16)
    q, k, v = (jnp.concatenate([x, x]) for x in (q, k, v))
    got = attention.make_sharded_causal_attention(mesh, window=24)(q, k, v)
    np.testing.assert_allclose(got, _dense(q, k, v, 24), atol=2e-5)
