"""Laguna (``models/laguna.py``): the system against the benchmark's
plain reference on seeded random weights, the per-layer lists (kind, head
count, MLP), the two position tables, the gate, and the shares of the
experts against the uncut layer."""

import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import matmuls, primitives

from ray_tpu import train
from ray_tpu.models import Laguna, LagunaConfig
from ray_tpu.models import laguna as model_file
from ray_tpu.models.laguna import Attention, Block, MoE, laguna_loss_fn
from ray_tpu.models.llama import rope_freqs, yarn_freqs
from ray_tpu.util import tracing

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmark"))
from benchlib import manifest as mf  # noqa: E402

F32 = dict(dtype=jnp.float32)
RTOL = 2.0 ** -10       # the cell's limit


def _spec(cfg, **kw):
    return {**mf.load_builder("laguna").reference_spec(cfg), **kw}


def _jittered(params, seed, by=0.1):
    """Every leaf moved off its initial value, so that the norms'
    scales and the routers' biases say something."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return tree.unflatten([
        x + by * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


def _batch(seed, cfg, rows=2):
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, cfg.seq_len), dtype=np.int32)
    return {"tokens": jnp.asarray(toks),
            "targets": jnp.asarray(np.roll(toks, -1, 1))}


def _leaves_with_names(tree):
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in
            jax.tree_util.tree_leaves_with_path(tree)]


# -- the system against the plain reference ----

@pytest.mark.parametrize("seed, held, remat", [
    (0, (4, 4), False), (1, (0, 4), True), (2, None, False)],
    ids=["second_quarter", "first_quarter_remat", "all_held"])
def test_loss_every_gradient_leaf_and_the_routes_are_the_references(
        seed, held, remat):
    cfg = LagunaConfig.tiny(experts_held=held, remat=remat, **F32)
    model = Laguna(cfg)
    params = _jittered(model.init_params(jax.random.key(seed)), seed)
    batch = _batch(seed, cfg)
    ref = mf.load_reference("laguna")
    with jax.default_matmul_precision("highest"):
        (loss, report), grads = jax.jit(jax.value_and_grad(
            laguna_loss_fn(model, ce_chunk=32), has_aux=True))(params, batch)
    want, want_grads, loads = ref.loss_and_grads(params, batch, _spec(cfg))
    if held is None:    # the reference's forward pass in one piece too
        with jax.default_matmul_precision("highest"):
            logits = jax.jit(model.apply)({"params": params},
                                          batch["tokens"])
        want_logits, whole, _ = ref.forward(params, batch["tokens"],
                                            _spec(cfg))
        np.testing.assert_array_equal(whole, loads)
        np.testing.assert_allclose(logits, want_logits, atol=5e-5)
    assert float(loss) == pytest.approx(want["loss"], rel=1e-5)
    assert float(optax.global_norm(grads)) == pytest.approx(
        want["grad_norm"], rel=1e-4)
    np.testing.assert_array_equal(report["moe_load"], loads)
    assert float(report["moe_absent_route_share"]) == pytest.approx(
        want["moe_absent_route_share"], abs=1e-6)
    assert float(report["attn_window_out_rms"]) == pytest.approx(
        want["attn_window_out_rms"], rel=1e-5)
    # four routed layers of five: layer 0's MLP is dense
    assert loads.shape == (4, cfg.num_experts)
    assert float(loads.sum()) == 4 * 2 * cfg.seq_len * cfg.top_k
    want_leaves = dict(_leaves_with_names(want_grads))
    for name, got in _leaves_with_names(grads):
        scale = max(float(np.abs(want_leaves[name]).max()), 1e-3)
        np.testing.assert_allclose(got, want_leaves[name],
                                   atol=2e-4 * scale, err_msg=name)
    assert len(want_leaves) == len(jax.tree_util.tree_leaves(grads))


def test_the_reference_takes_parameters_that_wait_on_the_host():
    """As the cell hands them over: numpy, a block's on the device while
    the block runs; with ``adamw`` the optimizer's first step too."""
    cfg = LagunaConfig.tiny(**F32)
    params = _jittered(Laguna(cfg).init_params(jax.random.key(3)), 3)
    batch = _batch(3, cfg)
    ref = mf.load_reference("laguna")
    want = ref.loss_and_grad_norm(params, batch, _spec(cfg))
    adamw = dict(learning_rate=2e-5, b1=0.9, b2=0.95, eps=1e-8,
                 weight_decay=0.1, clip_global_norm=1.0)
    load = []
    got = ref.loss_and_grad_norm(jax.device_get(params), batch,
                                 _spec(cfg, adamw=adamw), load=load)
    assert set(got) == set(want) | {"update_norm"}
    assert set(want) == {"loss", "grad_norm", "moe_absent_route_share",
                         "attn_window_out_rms"}
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-6)
    assert 0 < got["update_norm"] < 1
    assert np.asarray(load).shape == (4, cfg.num_experts)


def test_float8_operands_fail_at_least_one_key_of_the_cells():
    """The reference with its matmul operands rounded to
    ``float8_e4m3fn``, the precision under the configuration's bfloat16,
    is not correct at the cell's limit."""
    cfg = LagunaConfig.tiny(**F32)
    params = _jittered(Laguna(cfg).init_params(jax.random.key(12)), 12)
    batch = _batch(12, cfg)
    ref = mf.load_reference("laguna")
    want = ref.loss_and_grad_norm(params, batch, _spec(cfg))
    low = ref.loss_and_grad_norm(
        params, batch, _spec(cfg, operand_dtype="float8_e4m3fn"))
    off = {k: abs(low[k] - want[k]) / abs(want[k]) for k in want}
    assert max(off.values()) > RTOL, off


# -- the per-layer lists ----

def test_parameter_shapes_follow_each_layers_own_head_count():
    """``W_q``, ``W_o`` and ``W_g`` at 48 and at 64 heads in one tree,
    K and V at 8 on both; layer 0's MLP dense, the others routed with
    the held experts; the counts are the configuration file's."""
    cut = LagunaConfig.laguna_xs_2(n_layer=5, experts_held=(0, 32),
                                   vocab_size=12544, seq_len=16384)
    params = jax.eval_shape(Laguna(cut).init_params, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) \
        == cut.num_params()
    for i, heads in enumerate((48, 64, 64, 64, 48)):
        attn = params[f"h_{i}"]["attn"]
        assert attn["q"]["kernel"].shape == (2048, heads * 128)
        assert attn["out"]["kernel"].shape == (heads * 128, 2048)
        assert attn["g"]["kernel"].shape == (2048, heads)
        assert attn["k"]["kernel"].shape == attn["v"]["kernel"].shape \
            == (2048, 8 * 128)
    assert set(params["h_0"]["mlp"]) == {"gate", "up", "down"}
    assert params["h_0"]["mlp"]["up"]["kernel"].shape == (2048, 8192)
    for i in range(1, 5):
        mlp = params[f"h_{i}"]["mlp"]
        assert mlp["gate"]["kernel"].shape == (2048, 256)
        assert mlp["experts"]["gate_proj"].shape == (32, 2048, 512)
        assert mlp["shared"]["down"]["kernel"].shape == (512, 2048)
    assert "lm_head" in params          # untied
    per = [sum(cut.layer_params(i).values()) for i in range(5)]
    assert per[0] == pytest.approx(79.8e6, rel=1e-3)
    assert per[1] == per[2] == per[3] == pytest.approx(142.2e6, rel=1e-3)
    assert per[4] == pytest.approx(133.8e6, rel=1e-3)
    assert cut.num_params() == pytest.approx(691.6e6, rel=1e-4)
    assert cut.num_params() * 14 == pytest.approx(9.68e9, rel=1e-3)
    whole = LagunaConfig.laguna_xs_2()
    assert whole.layer_kinds == "FSSS" * 10
    assert whole.num_params() == pytest.approx(33.4e9, rel=5e-3)  # "33B"
    uncut = LagunaConfig.laguna_xs_2(n_layer=5).layer_params(1)
    assert sum(uncut.values()) == pytest.approx(846.9e6, rel=1e-3)


def test_a_layer_reads_its_own_entry_of_each_list_and_no_period():
    """Lists that follow no period: the model runs them as given."""
    from ray_tpu.models.laguna import DENSE, FULL, SLIDING, SPARSE
    odd = LagunaConfig.tiny(
        n_layer=3, layer_types=(SLIDING, SLIDING, FULL),
        heads_per_layer=(2, 8, 4), mlp_layer_types=(SPARSE, DENSE, SPARSE),
        **F32)
    assert odd.layer_kinds == "SSF" and odd.routed_layers == (0, 2)
    params = jax.eval_shape(Laguna(odd).init_params, jax.random.key(0))
    assert [params[f"h_{i}"]["attn"]["g"]["kernel"].shape[1]
            for i in range(3)] == [2, 8, 4]
    assert "experts" in params["h_0"]["mlp"] and "experts" in \
        params["h_2"]["mlp"] and "experts" not in params["h_1"]["mlp"]
    with pytest.raises(ValueError, match="entries for 6 layers"):
        LagunaConfig.tiny(n_layer=6)
    with pytest.raises(ValueError, match="key/value heads"):
        LagunaConfig.tiny(heads_per_layer=(6, 8, 7, 8, 6))
    with pytest.raises(ValueError, match="layer 1"):
        LagunaConfig.tiny(layer_types=(FULL, "chunked", SLIDING, SLIDING,
                                       FULL))


def _attention_rows(cfg, layer):
    """d(sum of output row t) / d(input rows): which rows of the layer's
    input a row of its attention reads, [t, rows] bool."""
    sliding = cfg.sliding(layer)
    attn = Attention(cfg, cfg.heads(layer), sliding)
    h = jax.random.normal(jax.random.key(0), (1, cfg.seq_len, cfg.n_embd))
    angles, amplitude = Laguna(cfg).position_tables(cfg.seq_len)[sliding]
    params = attn.init(jax.random.key(1), h, angles, amplitude)

    def out(h):
        return attn.apply(params, h, angles, amplitude,
                          mutable=["stats"])[0][0].sum(-1)
    # the gate and the projections read row t alone: not counted twice
    return np.abs(np.asarray(jax.jit(jax.jacobian(out))(h))[:, 0]).sum(-1) > 0


def test_a_sliding_layers_row_sees_window_keys_and_a_full_layers_all():
    cfg = LagunaConfig.tiny(**F32)        # 64 rows, a window of 24
    t = np.arange(cfg.seq_len)
    reads = _attention_rows(cfg, 1)
    want = ((t[None, :] <= t[:, None])
            & (t[None, :] > t[:, None] - cfg.window))
    np.testing.assert_array_equal(reads, want)
    assert reads[-1].sum() == cfg.window == 24
    assert reads[10].sum() == 11                # the row's start cuts it
    np.testing.assert_array_equal(_attention_rows(cfg, 0),
                                  t[None, :] <= t[:, None])
    np.testing.assert_array_equal(_attention_rows(cfg, 4),
                                  t[None, :] <= t[:, None])


# -- the two position tables ----

def test_yarn_is_the_definitions_count_at_the_published_numbers():
    """``models/llama.py::yarn_freqs`` at Laguna-XS.2's
    ``rope_parameters.full_attention`` against the equations written
    out in numpy: ``low``, ``high``, every ``inv_i``, the amplitude."""
    d, theta, factor, length = 64, 500000.0, 64.0, 4096
    i = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2 * i / d)

    def c(n):
        return d * np.log(length / (2 * np.pi * n)) / (2 * np.log(theta))
    low, high = np.floor(c(64)), np.ceil(c(1))
    assert (low, high) == (5, 16)
    r = np.clip((i - low) / (high - low), 0, 1)
    inv = f / factor * r + f * (1 - r)
    angles, amplitude = yarn_freqs(
        d, 16384, theta, factor=factor, original_len=length, beta_fast=64,
        beta_slow=1)
    assert angles.shape == (16384, 32)
    np.testing.assert_allclose(angles[1], inv, rtol=1e-6)
    np.testing.assert_allclose(angles[16383], 16383 * inv, rtol=1e-6)
    assert float(angles[1, 0]) == 1.0                   # the fastest pair
    assert float(angles[1, 5]) == pytest.approx(f[5], rel=1e-6)   # kept
    assert float(angles[1, 16]) == pytest.approx(f[16] / 64, rel=1e-6)
    assert float(angles[1, 31]) == pytest.approx(
        theta ** (-62 / 64) / 64, rel=1e-6)             # the slowest
    assert f[10] / 64 < float(angles[1, 10]) < f[10]    # on the ramp
    assert amplitude == pytest.approx(1.4158883083359672, rel=1e-12)
    assert amplitude == 0.1 * math.log(64) + 1
    given = yarn_freqs(d, 8, theta, factor=factor, original_len=length,
                       beta_fast=64, beta_slow=1, attention_factor=1.25)[1]
    assert given == 1.25
    # the reference's own count, written apart
    want, lo, hi = mf.load_reference("laguna").yarn_inv_freq(
        d, theta, factor, length, 64, 1)
    assert (lo, hi) == (5, 16)
    np.testing.assert_allclose(angles[1], want, rtol=1e-6)
    # the model hands the full layers this table, the sliding ones the
    # default one over every lane
    cfg = LagunaConfig.laguna_xs_2(n_layer=5)
    tables = Laguna(cfg).position_tables(128)
    np.testing.assert_array_equal(tables[False][0], angles[:128])
    assert tables[False][1] == amplitude and tables[True][1] == 1.0
    np.testing.assert_array_equal(tables[True][0],
                                  rope_freqs(128, 128, 10000.0))
    assert tables[True][0].shape == (128, 64)
    assert tables[False][0].shape == (128, 32) and cfg.rotated_lanes == 64


def _block_out(cfg, layer, x, key=1):
    block = Block(cfg, layer)
    angles, amplitude = Laguna(cfg).position_tables(
        cfg.seq_len)[cfg.sliding(layer)]
    params = block.init(jax.random.key(key), x, angles, amplitude)
    return block.apply(params, x, angles, amplitude,
                       mutable=["moe", "stats"])[0]


def test_a_full_layer_is_moved_by_its_own_theta_alone_and_a_sliding_by_its():
    """One block at a time on the same input and weights under another
    ``full_theta``, another ``sliding_theta``, another YaRN factor."""
    a = LagunaConfig.tiny(**F32)
    x = jax.random.normal(jax.random.key(0), (2, a.seq_len, a.n_embd))
    for change, moves in (({"full_theta": 500.0}, "F"),
                          ({"sliding_theta": 7.0}, "S"),
                          ({"yarn_factor": 16.0}, "F")):
        b = LagunaConfig.tiny(**change, **F32)
        for layer, kind in enumerate(a.layer_kinds):
            same = np.array_equal(_block_out(a, layer, x),
                                  _block_out(b, layer, x))
            assert same == (kind != moves), (change, layer)


def test_a_full_layer_leaves_the_second_half_of_a_heads_lanes_unrotated():
    from ray_tpu.models.laguna import _rotate
    cfg = LagunaConfig.tiny(**F32)          # 16 lanes, 8 of them rotated
    tables = Laguna(cfg).position_tables(cfg.seq_len)
    x = jax.random.normal(jax.random.key(0), (1, cfg.seq_len, 3, 16))
    full = _rotate(x, *tables[False])
    np.testing.assert_array_equal(full[..., 8:], x[..., 8:])
    assert float(jnp.abs(full[:, 1:, :, :8] - x[:, 1:, :, :8]).max()) > 0.1
    # position 0 turns nothing: the amplitude alone is on the lanes
    amplitude = 0.1 * math.log(4.0) + 1
    np.testing.assert_allclose(full[:, 0, :, :8], amplitude * x[:, 0, :, :8],
                               rtol=1e-6)
    # lane i pairs with lane i + 4 of the rotated eight: a rotation
    # keeps each pair's length, times the amplitude
    pairs = full[..., :4] ** 2 + full[..., 4:8] ** 2
    np.testing.assert_allclose(
        pairs, amplitude ** 2 * (x[..., :4] ** 2 + x[..., 4:8] ** 2),
        rtol=1e-5)
    sliding = _rotate(x, *tables[True])
    assert float(jnp.abs(sliding[:, 1:, :, 8:] - x[:, 1:, :, 8:]).max()) > 0.1
    np.testing.assert_allclose(
        sliding[..., :8] ** 2 + sliding[..., 8:] ** 2,
        x[..., :8] ** 2 + x[..., 8:] ** 2, rtol=1e-5)


# -- the gate ----

def test_the_gate_at_zero_passes_nothing_and_at_one_the_cores_output():
    """``W_g`` = 0 gives every head half of its core's output (sigmoid
    of 0); a bias of the input far below zero shuts every head, far
    above passes the core's output whole; one head's column alone moves
    that head's lanes."""
    cfg = LagunaConfig.tiny(**F32)
    heads = cfg.heads(1)
    attn = Attention(cfg, heads, True)
    h = jax.random.normal(jax.random.key(0), (2, cfg.seq_len, cfg.n_embd))
    angles, amplitude = Laguna(cfg).position_tables(cfg.seq_len)[True]
    params = attn.init(jax.random.key(1), h, angles, amplitude)["params"]

    def out(gate_kernel, h=h):
        p = {**params, "g": {"kernel": gate_kernel}}
        return attn.apply({"params": p}, h, angles, amplitude,
                          mutable=["stats"])[0]

    def ungated():
        """``o W_o`` with the gate left out, from the same weights."""
        from ray_tpu.models.laguna import _rotate
        from ray_tpu.ops.attention import causal_attention
        b, t = h.shape[:2]
        q = (h @ params["q"]["kernel"]).reshape(b, t, heads, 16)
        k = (h @ params["k"]["kernel"]).reshape(b, t, 2, 16)
        v = (h @ params["v"]["kernel"]).reshape(b, t, 2, 16)
        q, k = (_rotate(z, angles, amplitude) for z in (q, k))
        k, v = (jnp.repeat(z, heads // 2, axis=2) for z in (k, v))
        o = causal_attention(q, k, v, window=cfg.window)
        return o, o.reshape(b, t, -1) @ params["out"]["kernel"]

    zero = jnp.zeros((cfg.n_embd, heads))
    o, whole = ungated()
    np.testing.assert_allclose(out(zero), 0.5 * whole, atol=1e-6)
    # a constant lane of the input and a large weight on it: g -> 0, 1
    hc = h.at[..., 0].set(1.0)
    h = hc
    o, whole = ungated()
    shut = zero.at[0].set(-40.0)
    np.testing.assert_allclose(out(shut, hc), 0.0, atol=1e-12)
    np.testing.assert_allclose(out(-shut, hc), whole, atol=1e-5)
    one_head = shut.at[0, 3].set(40.0)       # head 3 alone open
    want = (o[:, :, 3].reshape(2, cfg.seq_len, 16)
            @ params["out"]["kernel"][3 * 16:4 * 16])
    np.testing.assert_allclose(out(one_head, hc), want, atol=1e-5)


# -- the share of the experts ----

def test_four_shares_of_four_add_up_to_the_uncut_reference_layer():
    """The guide's share test at the tiny preset's 16 experts, top-3:
    the program's routed layer under ``experts_held = (0, 4)``, ``(4,
    4)``, ``(8, 4)`` and ``(12, 4)``, each given its own experts'
    weights, with the shared expert counted once, adds up to what the
    reference gives for the whole layer of 16."""
    joyai = mf.load_reference("joyai")
    cfg = LagunaConfig.tiny(experts_held=None, **F32)
    d, f, e = cfg.n_embd, cfg.expert_width, cfg.num_experts
    ks = jax.random.split(jax.random.key(7), 9)
    h = jax.random.normal(ks[0], (2, cfg.seq_len, d))

    def dense(key, rows, cols):
        return {"kernel": jax.random.normal(key, (rows, cols)) * 0.2}
    whole = {
        "gate": {"kernel": jax.random.normal(ks[1], (d, e)),
                 "e_score_correction_bias":
                     jax.random.normal(ks[2], (e,)) * 0.3},
        "experts": {"gate_proj": jax.random.normal(ks[3], (e, d, f)) * 0.2,
                    "up_proj": jax.random.normal(ks[4], (e, d, f)) * 0.2,
                    "down_proj": jax.random.normal(ks[5], (e, f, d)) * 0.2},
        "shared": {"gate": dense(ks[6], d, cfg.shared_width),
                   "up": dense(ks[7], d, cfg.shared_width),
                   "down": dense(ks[8], cfg.shared_width, d)}}
    spec = {**_spec(cfg), "experts_held": (0, e)}
    same = lambda v: v      # noqa: E731 — the reference's "no rounding"
    with jax.default_matmul_precision("highest"):
        want, load = joyai._moe(whole, h, spec, same)
        shared = joyai._swiglu(whole["shared"], h, same)
        parts = []
        for first in range(0, e, 4):
            share = dict(whole, experts={
                k: v[first:first + 4] for k, v in whole["experts"].items()})
            held = LagunaConfig.tiny(experts_held=(first, 4), **F32)
            y, sown = MoE(held).apply({"params": share}, h, mutable=["moe"])
            parts.append(y - shared)                # the routed part
            np.testing.assert_array_equal(sown["moe"]["load"][0], load)
    assert float(load.sum()) == 2 * cfg.seq_len * cfg.top_k
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=1e-4,
                               atol=1e-4)
    assert all(float(jnp.abs(p).max()) > 0.01 for p in parts)
    # no single share is the layer, and routing mattered
    assert float(jnp.abs(parts[0] + shared - want).max()) > 0.01
    assert float(jnp.abs(want - shared).max()) > 0.1


# -- the step, its notes, a mesh ----

def test_a_train_step_runs_and_reports_the_load_of_every_routed_layer():
    cfg = LagunaConfig.tiny(remat=True, **F32)  # the CPU has no bf16 dot
    model = Laguna(cfg)
    opt = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-3))
    state = train.init_train_state(
        jax.jit(model.init_params)(jax.random.key(0)), opt, None)
    step = train.make_train_step(laguna_loss_fn(model, ce_chunk=32), opt)
    losses = []
    for i in range(3):
        state, metrics = step(state, _batch(i, cfg))
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert losses[0] == pytest.approx(np.log(cfg.vocab_size), abs=0.5)
    assert metrics["moe_load"].shape == (4, cfg.num_experts)
    assert float(metrics["moe_held_route_share"]
                 + metrics["moe_absent_route_share"]) == pytest.approx(1.0)
    assert float(metrics["moe_load_max_over_mean"]) >= 1.0
    assert float(metrics["attn_window_out_rms"]) > 0
    assert float(metrics["lm_loss"]) == float(metrics["loss"])


# ``blocks_remat_keeps``: the routers' five, ``W_o``'s product, q, k and
# v, the dense and shared MLPs' two, the cores' two
KEEPS_NOTE = ("moe_router_logits,moe_router_experts,moe_router_weights,"
              "moe_router_counts,moe_router_lse,attn_out_proj,attn_q,attn_k,"
              "attn_v,mlp_gate,mlp_up,attn_out,attn_lse")


@pytest.mark.parametrize("remat, keeps", [
    (True, KEEPS_NOTE), (False, "")], ids=["recomputed", "kept_whole"])
def test_a_recomputed_block_says_what_its_policy_keeps(remat, keeps,
                                                       monkeypatch):
    """``blocks_remat_keeps`` beside ``blocks_remat``: the names a
    recomputed block's policy keeps (``_BLOCK_KEEPS``, then the
    attention cores' output and row statistics), and every block's
    checkpoint carries a policy; nothing where the blocks are not
    recomputed."""
    cfg = LagunaConfig.tiny(remat=remat, **F32)
    model = Laguna(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    traced = jax.make_jaxpr(lambda p, t: model.apply(
        {"params": p}, t, return_hidden=True,
        mutable=["moe", "stats"])[0])(params, _batch(0, cfg)["tokens"])
    assert notes["blocks_remat"] is remat
    assert notes["blocks_remat_keeps"] == keeps
    with_policy = [e for e in traced.jaxpr.eqns
                   if e.primitive.name == "remat2" and e.params["policy"]]
    assert len(with_policy) == (cfg.n_layer if remat else 0)


def test_a_recomputed_stack_gives_the_numbers_of_the_one_kept_whole():
    """Loss, report and every gradient leaf with ``remat`` against
    without, on the same parameters in float32, each one jitted program:
    a kept array is the value the second pass would have made again, and
    two programs may fuse their sums in another order (1e-6 of a leaf's
    largest entry). The routes are the same routes."""
    got = {}
    for remat in (False, True):
        cfg = LagunaConfig.tiny(remat=remat, **F32)
        model = Laguna(cfg)
        params = _jittered(model.init_params(jax.random.key(5)), 5)
        got[remat] = jax.jit(jax.value_and_grad(
            laguna_loss_fn(model, ce_chunk=32), has_aux=True))(
                params, _batch(5, cfg))
    (want, want_report), want_grads = got[False]
    (loss, report), grads = got[True]
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_array_equal(report["moe_load"],
                                  want_report["moe_load"])
    assert float(report["attn_window_out_rms"]) == pytest.approx(
        float(want_report["attn_window_out_rms"]), rel=1e-6)
    want_leaves = dict(_leaves_with_names(want_grads))
    assert len(want_leaves) > 40
    for name, leaf in _leaves_with_names(grads):
        scale = max(float(np.abs(want_leaves[name]).max()), 1e-3)
        np.testing.assert_allclose(leaf, want_leaves[name],
                                   atol=1e-6 * scale, err_msg=name)


@functools.cache
def _traced_gradient(remat, without=()):
    """The tiny model's loss and gradient, traced (once a case), with
    the names ``without`` taken off the blocks' policy: ``F S S S F`` at
    6 and 8 heads of 16 over 2 key/value heads, layer 0's MLP dense at
    160, four routed layers with a shared expert at 48 (so that its
    products have another shape than k's and v's)."""
    cfg = LagunaConfig.tiny(remat=remat, shared_width=48, **F32)
    model = Laguna(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(model_file, "_BLOCK_KEEPS", tuple(
            k for k in model_file._BLOCK_KEEPS if k not in without))
        return cfg, jax.make_jaxpr(jax.value_and_grad(
            laguna_loss_fn(model, ce_chunk=32), has_aux=True))(
                params, _batch(0, cfg))


# (what, its names, (rows' shape, weight's shape)..., how many a step):
# d = 64, 2 x 64 tokens
_KEPT_PRODUCTS = [
    ("router", ("moe_router_logits",), [((128, 64), (64, 16))], 4),
    ("attn_out", ("attn_out_proj",),
     [((2, 64, 96), (96, 64)), ((2, 64, 128), (128, 64))], 5),
    ("attn_q", ("attn_q",),
     [((2, 64, 64), (64, 96)), ((2, 64, 64), (64, 128))], 5),
    ("attn_k_v", ("attn_k", "attn_v"),
     [((2, 64, 64), (64, 32))], 10),
    ("dense_gate_up", ("mlp_gate", "mlp_up"),
     [((2, 64, 64), (64, 160))], 2),
    ("shared_gate_up", ("mlp_gate", "mlp_up"),
     [((2, 64, 64), (64, 48))], 8)]


@pytest.mark.parametrize("names, shapes, n", [c[1:] for c in _KEPT_PRODUCTS],
                         ids=[c[0] for c in _KEPT_PRODUCTS])
def test_a_recomputed_block_runs_each_kept_product_once(names, shapes, n):
    """The forward matmuls of one product's shapes in the gradient's
    jaxpr: as many with ``remat`` as in the stack kept whole (once a
    layer that has the product); with its names off the policy, twice."""
    assert [matmuls(_traced_gradient(remat)[1], *shapes)
            for remat in (False, True)] == [n, n]
    assert matmuls(_traced_gradient(True, names)[1], *shapes) == 2 * n


@pytest.mark.parametrize("without, runs", [
    ((), 1), (("moe_router_experts", "moe_router_weights",
               "moe_router_counts"), 2)], ids=["kept", "product_alone"])
def test_a_recomputed_router_chooses_once(without, runs):
    """``top_k`` in the gradient's jaxpr: once a routed layer where the
    policy lists the routers' names, twice where it lists the product
    alone (the choice and the chosen scores are made again from the kept
    product)."""
    cfg, traced = _traced_gradient(True, without)
    assert primitives(traced, "top_k") == len(cfg.routed_layers) * runs


def test_the_notes_say_the_stack_the_tables_the_gate_and_the_share(
        monkeypatch):
    cfg = LagunaConfig.tiny(**F32)
    model = Laguna(cfg)
    params = model.init_params(jax.random.key(0))
    notes = {}      # a step's listener, once installed, takes them away
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    model.apply({"params": params}, _batch(0, cfg)["tokens"],
                return_hidden=True, mutable=["moe", "stats"])
    assert notes["attn_kind"] == "window_global"
    assert notes["attn_layers"] == "FSSSF"
    assert notes["attn_heads"] == "6,8,8,8,6" and notes["attn_window"] == 24
    assert notes["attn_gate"] == "headwise_sigmoid"
    assert notes["rope_kind"] == "yarn_half|default"
    assert notes["rope_attention_factor"] == pytest.approx(
        0.1 * math.log(4.0) + 1)
    assert notes["blocks_remat"] is False and notes["dense_layers"] == 1
    assert notes["moe_router"] == "sigmoid"
    assert notes["moe_expert_kind"] == "swiglu"
    assert notes["moe_experts_held"] == [4, 4] and notes["moe_top_k"] == 3
    assert notes["moe_rows_sorted"] > 0
    # which row moves the held path compiled: off the TPU the plain form
    assert notes["moe_rows_path"] == "xla"
    with pytest.raises(ValueError, match="64 positions"):
        model.apply({"params": params}, jnp.zeros((1, 128), jnp.int32))


def test_a_mesh_over_the_batch_gives_the_one_device_loss_and_sp_is_refused():
    from ray_tpu.parallel import make_mesh
    cfg = LagunaConfig.tiny(**F32)
    params = Laguna(cfg).init_params(jax.random.key(0))
    batch = _batch(0, cfg, rows=4)
    one, _ = jax.jit(laguna_loss_fn(Laguna(cfg), ce_chunk=32))(params, batch)
    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    with mesh:
        many, report = jax.jit(laguna_loss_fn(
            Laguna(cfg, mesh=mesh), ce_chunk=32))(params, batch)
    assert float(many) == pytest.approx(float(one), rel=1e-5)
    assert float(report["moe_load"].sum()) \
        == 4 * 4 * cfg.seq_len * cfg.top_k
    sp = make_mesh({"sp": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="halo"):
        Laguna(cfg, mesh=sp).init_params(jax.random.key(0))
