"""The LM head's forward kernel (``ops/pallas/ce_lse.py``) against the
scan it replaces, interpreted on the CPU; the hand-over of its ``lse``
to the unchanged backward; and ``ce_path``'s decisions."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt2
from ray_tpu.ops.pallas import ce_lse
from ray_tpu.parallel.mesh import make_mesh

IGNORE = -1


def _case(n, chunk, e, v, seed=0, ignored=0.0):
    """rows [n, chunk, e], table [v, e], targets [n, chunk] (a share of
    them ``IGNORE``), float32."""
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.normal(size=(n, chunk, e)), jnp.float32)
    emb = jnp.asarray(rng.normal(size=(v, e)) * 0.3, jnp.float32)
    tgt = rng.integers(0, v, size=(n, chunk))
    tgt[rng.random(size=tgt.shape) < ignored] = IGNORE
    return rows, emb, jnp.asarray(tgt, jnp.int32)


# (rows as [n, chunk], E, V, row block, tile, share of rows ignored)
CASES = {
    # 7 x 128: a prime number of lane tiles, the last tile ragged
    "prime_tiles_ragged_last": (2, 64, 128, 7 * 128, 64, 256, 0.0),
    "prime_tiles_one_lane_tile": (2, 64, 128, 7 * 128, 128, 128, 0.0),
    # 3 x k, as 393 = 3 x 131: a tile that divides, one that does not
    "three_k_tiles_dividing": (2, 64, 128, 6 * 128, 64, 384, 0.0),
    "three_k_tiles_ragged": (2, 64, 128, 9 * 128, 32, 512, 0.0),
    # rows past the array (padding) and rows to skip are ``IGNORE``
    "ignored_rows": (3, 32, 128, 5 * 128, 32, 256, 0.3),
    "all_of_a_block_ignored": (2, 16, 128, 3 * 128, 16, 128, 1.0),
    "several_lane_tiles_of_e": (1, 128, 384, 5 * 128, 64, 256, 0.1),
    "one_block_one_tile": (1, 48, 256, 4 * 128, None, None, 0.1),
}


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_kernel_equals_the_scan(case, monkeypatch):
    """``lse`` and ``picked`` of every row, and the two sums, as the
    scan over chunks makes them, to float32 rounding."""
    n, chunk, e, v, block_rows, tile, ignored = CASES[case]
    rows, emb, tgt = _case(n, chunk, e, v, ignored=ignored)
    (want_tot, want_cnt), want_lse = gpt2._chunked_ce_fwd_scan(
        rows, emb, tgt, IGNORE)
    safe = jnp.where(tgt != IGNORE, tgt, 0).reshape(-1)
    logits = jnp.einsum("ne,ve->nv", rows.reshape(-1, e), emb,
                        precision="highest")
    want_picked = jnp.take_along_axis(logits, safe[:, None], 1)[:, 0]

    lse, picked = ce_lse.ce_lse_fwd(
        rows.reshape(-1, e), emb, safe, block_rows=block_rows, tile=tile,
        interpret=True)
    np.testing.assert_allclose(lse, want_lse.reshape(-1), rtol=1e-6)
    np.testing.assert_allclose(picked, want_picked, rtol=1e-6,
                               atol=1e-6 * float(jnp.abs(logits).max()))

    # the model's own call, at these blocks
    monkeypatch.setattr(ce_lse, "blocks", lambda *_: (
        block_rows or n * chunk, tile or v))
    monkeypatch.setattr(ce_lse, "ce_lse_fwd", functools.partial(
        ce_lse.ce_lse_fwd, interpret=True))
    (tot, cnt), lse_c = gpt2._chunked_ce_fwd(
        rows, emb, tgt, IGNORE, "pallas_lse")
    assert lse_c.shape == want_lse.shape
    np.testing.assert_allclose(lse_c, want_lse, rtol=1e-6)
    assert int(cnt) == int(want_cnt)
    np.testing.assert_allclose(float(tot), float(want_tot), rtol=1e-6,
                               atol=1e-6)


def test_blocks_follow_the_shapes():
    """A divisor of the table in whole lane tiles where a good one
    exists, else a ragged last tile; the row block the largest the
    operands' budget allows."""
    got = {name: ce_lse.blocks(n, e, v) for name, (n, e, v) in {
        "gpt2": (32768, 768, 50304), "olmoe": (16384, 2048, 50304),
        "zaya": (16384, 2048, 32896), "kimi": (16384, 2304, 20480),
        "smallthinker": (16384, 2560, 19072), "joyai": (8192, 2048, 16384),
        "nemotron": (8192, 2688, 16384), "phi4": (4096, 2560, 25088),
        "tiny": (48, 128, 384)}.items()}
    for name, (rows, tile) in got.items():
        assert tile % 128 == 0, name
    # 393 = 3 x 131 and 257, 149 primes: no good divisor, a ragged tile
    assert got["gpt2"][1] == got["zaya"][1] == got["smallthinker"][1]
    assert 50304 % got["gpt2"][1] and 32896 % got["zaya"][1]
    # 128, 160, 196 lane tiles: a divisor
    assert 16384 % got["joyai"][1] == 0 and 20480 % got["kimi"][1] == 0
    assert 25088 % got["phi4"][1] == 0 and got["phi4"][1] >= 512
    assert got["tiny"] == (48, 384)
    for name, (n, e, v) in {"gpt2": (32768, 768, 50304),
                            "nemotron": (8192, 2688, 16384)}.items():
        rows, tile = got[name]
        assert n % rows == 0 and rows % ce_lse._STRIP == 0
        assert 4 * e * (rows + tile) <= ce_lse._OPERAND_BYTES


def test_refuses_what_it_cannot_tile():
    rows, emb, tgt = _case(1, 16, 128, 200)
    with pytest.raises(ValueError, match="whole tiles"):
        ce_lse.ce_lse_fwd(rows[0], emb, tgt[0], interpret=True)
    rows, emb, tgt = _case(1, 48, 128, 256)
    with pytest.raises(ValueError, match="do not tile"):
        ce_lse.ce_lse_fwd(rows[0], emb, tgt[0], block_rows=32,
                          interpret=True)


@pytest.mark.parametrize("rows_of, chunk", [((2, 40), 32), ((3, 64), 64),
                                            ((1, 48), 2048)],
                         ids=["padded_rows", "whole_chunks", "one_chunk"])
def test_gradients_with_the_kernels_lse_equal_the_scans(
        rows_of, chunk, monkeypatch):
    """The backward is the scan's own whichever forward made ``lse``:
    loss and both gradients of ``chunked_cross_entropy`` agree to
    float32 rounding, rows past the last chunk and ignored rows
    included."""
    b, s = rows_of
    e, v = 128, 5 * 128
    rows, emb, tgt = _case(b, s, e, v, seed=3, ignored=0.2)

    def loss(h, w):
        return gpt2.chunked_cross_entropy(
            h, w, tgt, ignore_index=IGNORE, chunk_size=chunk)

    want, (want_dh, want_dw) = jax.value_and_grad(loss, (0, 1))(rows, emb)

    said = {}
    monkeypatch.setattr(gpt2.tracing, "note_trace", said.update)
    monkeypatch.setattr(gpt2, "ce_path", lambda *a, **k: "pallas_lse")
    monkeypatch.setattr(ce_lse, "_TILE", 512)      # of 640: ragged
    monkeypatch.setattr(ce_lse, "_STRIP", 16)
    monkeypatch.setattr(ce_lse, "_ROWS", 32)
    monkeypatch.setattr(ce_lse, "ce_lse_fwd", functools.partial(
        ce_lse.ce_lse_fwd, interpret=True))
    got, (dh, dw) = jax.value_and_grad(loss, (0, 1))(rows, emb)
    assert said["ce_path"] == "pallas_lse"
    assert said["ce_fwd_tile"] == 512 and said["ce_fwd_rows"] <= 32
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for g, w in ((dh, want_dh), (dw, want_dw)):
        np.testing.assert_allclose(g, w, rtol=1e-6,
                                   atol=1e-6 * float(jnp.abs(w).max()))


def _mesh(axes):
    n = int(np.prod(list(axes.values())))
    return make_mesh(axes, devices=jax.devices()[:n])


GPT2_HEAD = dict(shape=(32, 1024, 768), vocab=50304, dtype=jnp.bfloat16)
PATHS = {
    # name: (backend, devices of the process, mesh axes, overrides, path)
    "tpu_one_device_no_mesh": ("tpu", 1, None, {}, "pallas_lse"),
    "tpu_one_device_mesh": ("tpu", 8, {"dp": 1}, {}, "pallas_lse"),
    "tpu_dp4_rows_of_a_chip": ("tpu", 8, {"dp": 4}, {}, "pallas_lse"),
    "tpu_dp2_sp2": ("tpu", 8, {"dp": 2, "sp": 2}, {}, "pallas_lse"),
    "zaya_head": ("tpu", 1, None, dict(shape=(2, 8192, 2048), vocab=32896),
                  "pallas_lse"),
    "short_rows_one_chunk": ("tpu", 1, None, dict(shape=(1, 48, 768)),
                             "pallas_lse"),
    "cpu": ("cpu", 1, None, {}, "xla_scan"),
    "float32_rows": ("tpu", 1, None, dict(dtype=jnp.float32), "xla_scan"),
    "e_off_the_lanes": ("tpu", 1, None, dict(shape=(32, 1024, 800)),
                        "xla_scan"),
    "vocab_off_the_lanes": ("tpu", 1, None, dict(vocab=50257), "xla_scan"),
    "rows_off_the_sublanes": ("tpu", 1, None, dict(shape=(1, 24, 768)),
                              "xla_scan"),
    "several_devices_no_mesh": ("tpu", 8, None, {}, "xla_scan"),
    "vocab_over_tp": ("tpu", 8, {"tp": 2}, {}, "xla_scan"),
    "dp2_tp2": ("tpu", 8, {"dp": 2, "tp": 2}, {}, "xla_scan"),
    "batch_dp_does_not_divide": ("tpu", 8, {"dp": 4},
                                 dict(shape=(2, 1024, 768)), "xla_scan"),
}


@pytest.mark.parametrize("case", list(PATHS), ids=list(PATHS))
def test_ce_path_decides_from_what_it_observes(case, monkeypatch):
    backend, devices, axes, overrides, want = PATHS[case]
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    mesh = _mesh(axes) if axes else None
    head = {**GPT2_HEAD, **overrides}
    assert gpt2.ce_path(head["shape"], head["vocab"], head["dtype"],
                        mesh) == want


# -- each row's loss out, a cotangent a row in ----

def _dense_rows(h, w, tgt):
    """Each row's loss from a dense float32 log-softmax, an ignored
    row's 0."""
    logp = jax.nn.log_softmax(jnp.einsum("bse,ve->bsv", h, w,
                                         precision="highest"), axis=-1)
    mask = tgt != IGNORE
    picked = jnp.take_along_axis(
        logp, jnp.where(mask, tgt, 0)[..., None], -1)[..., 0]
    return jnp.where(mask, -picked, 0.0)


@pytest.mark.parametrize("path", ["xla_scan", "pallas_lse"])
@pytest.mark.parametrize("rows_of, chunk", [
    ((3, 40), 32), ((2, 64), 32), ((1, 48), 2048)],
    ids=["padded_rows", "whole_chunks", "one_chunk"])
def test_rows_and_their_gradients_under_a_cotangent_a_row(
        rows_of, chunk, path, monkeypatch):
    """``chunked_cross_entropy_rows`` against dense float32 log-softmax
    rows: the values, and ``d hidden`` and ``d head`` under a random
    cotangent a row, ignored rows and rows past the last chunk included,
    on both forward paths (the kernel interpreted)."""
    b, s = rows_of
    e, v = 128, 5 * 128
    rows, emb, tgt = _case(b, s, e, v, seed=5, ignored=0.2)
    weight = jnp.asarray(np.random.default_rng(6).normal(size=(b, s)),
                         jnp.float32)
    said = {}
    monkeypatch.setattr(gpt2.tracing, "note_trace", said.update)
    monkeypatch.setattr(gpt2.tracing, "count_trace", said.update)
    monkeypatch.setattr(gpt2, "ce_path", lambda *a, **k: path)
    monkeypatch.setattr(ce_lse, "_TILE", 512)
    monkeypatch.setattr(ce_lse, "_STRIP", 16)
    monkeypatch.setattr(ce_lse, "_ROWS", 32)
    monkeypatch.setattr(ce_lse, "ce_lse_fwd", functools.partial(
        ce_lse.ce_lse_fwd, interpret=True))

    def ours(h, w):
        out = gpt2.chunked_cross_entropy_rows(
            h, w, tgt, ignore_index=IGNORE, chunk_size=chunk)
        return (out * weight).sum(), out

    def dense(h, w):
        out = _dense_rows(h, w, tgt)
        return (out * weight).sum(), out

    (_, got), (dh, dw) = jax.value_and_grad(ours, (0, 1), has_aux=True)(
        rows, emb)
    (_, want), (want_dh, want_dw) = jax.value_and_grad(
        dense, (0, 1), has_aux=True)(rows, emb)
    assert said["ce_path"] == path
    assert said["ce_rows"] == -(-b * s // min(chunk, b * s)) * min(chunk,
                                                                  b * s)
    assert got.shape == (b, s) and got.dtype == jnp.float32
    assert bool((got[tgt == IGNORE] == 0).all())
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for g, w in ((dh, want_dh), (dw, want_dw)):
        np.testing.assert_allclose(g, w, rtol=1e-5,
                                   atol=1e-5 * float(jnp.abs(w).max()))
    # the mean is the rows' sum over the count, to a sum's reordering
    mean = gpt2.chunked_cross_entropy(rows, emb, tgt, ignore_index=IGNORE,
                                      chunk_size=chunk)
    np.testing.assert_allclose(
        float(mean), float(want.sum() / (tgt != IGNORE).sum()), rtol=1e-6)


def test_rows_on_a_dp_mesh_come_back_sharded_as_the_targets():
    """Under ``shard_map`` over the token axes each chip makes its own
    rows' losses: the same values, the head's gradient reduced once."""
    b, s, e, v = 4, 32, 128, 3 * 128
    rows, emb, tgt = _case(b, s, e, v, seed=7, ignored=0.1)
    weight = jnp.asarray(np.random.default_rng(8).normal(size=(b, s)),
                         jnp.float32)
    mesh = _mesh({"dp": 4})

    def ours(h, w, mesh):
        return (gpt2.chunked_cross_entropy_rows(
            h, w, tgt, ignore_index=IGNORE, chunk_size=16, mesh=mesh)
            * weight).sum()

    want, want_g = jax.value_and_grad(ours, (0, 1))(rows, emb, None)
    got, got_g = jax.jit(jax.value_and_grad(ours, (0, 1)),
                         static_argnums=2)(rows, emb, mesh)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(g, w, rtol=1e-5,
                                   atol=1e-6 * float(jnp.abs(w).max()))
