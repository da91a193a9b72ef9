"""Xing4.0-29B-A4B on ``models/joyai.py``: the DeepSeek-V3-shaped stack
under four residual streams (``ops/hyper_connections.py``) and YaRN's
scale, at test size against the benchmark's plain float32 reference
(``benchmark/references/xing.py``: the maps with the Sinkhorn loop
written out on a [rows, seq, n, n] array, attention as a masked softmax
at the YaRN scale, every held expert on every token). Faults are planted
in the program and the comparison has to fail; ``hc_mult`` 1 has to be
the block it was; eight shares of eight experts sum to the uncut layer."""

import dataclasses
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu import train
from ray_tpu.models import joyai
from ray_tpu.models.joyai import (
    JoyAI, JoyAIConfig, MoE, YarnScaling, joyai_loss_fn,
)
from ray_tpu.ops import hyper_connections as hc
from ray_tpu.train.step import _group_norms
from ray_tpu.parallel import make_mesh

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
RTOL = 2.0 ** -10       # the cell's limit (configs/xing4.0-29b-a4b.json)
GROUPS = {"grad_norm_hc": r"(^|/)hc_(attn|mlp)/(phi|b|alpha)$"}


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, BENCHMARK)       # the reference borrows olmoe's rounder
    path = os.path.join(BENCHMARK, "references", "xing.py")
    spec = importlib.util.spec_from_file_location("reference_xing", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    yield mod
    sys.path.remove(BENCHMARK)


def _small(**kw):
    """``tiny_xing`` cut further for the CPU's compile time (the 20
    unrolled normalisations and their backward, twice a block): one
    block, dense by default, rows of 32, 3 normalisations, float32."""
    return JoyAIConfig.tiny_xing(**{**dict(
        n_layer=1, dense_layers=1, seq_len=32, hc_sinkhorn_iters=3,
        dtype=jnp.float32), **kw})


def _spec(cfg) -> dict:
    spec = {k: getattr(cfg, k) for k in (
        "n_layer", "dense_layers", "mtp_depth", "mtp_weight", "n_head",
        "kv_rank", "nope_dim", "rope_dim", "rope_theta", "top_k",
        "norm_topk_prob", "route_scale", "rms_eps", "hc_mult",
        "hc_sinkhorn_iters", "hc_eps", "hc_res_clamp")}
    spec["experts_held"] = cfg.experts_span
    spec["rope_scaling"] = (dataclasses.asdict(cfg.rope_scaling)
                            if cfg.rope_scaling else None)
    spec["grad_groups"] = GROUPS
    return spec


def _batch(cfg, rows=2, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, cfg.seq_len), dtype=np.int32)
    return {"tokens": jnp.asarray(toks),
            "targets": jnp.asarray(np.roll(toks, -1, 1))}


def _params(cfg, seed=1, sharp: float = 1.0):
    """Initialised; then every router's selection bias off zero and
    every gate of the residual maps of order 1, so that all three maps
    depend on the state; ``sharp`` scales queries and keys, so that the
    softmax is far from uniform and its scale matters."""
    params = jax.jit(JoyAI(cfg).init_params)(jax.random.key(seed))
    blocks = [params[f"h_{i}"] for i in range(cfg.n_layer)]
    if cfg.mtp_depth:
        blocks.append(params["mtp"]["h"])
    for i, block in enumerate(blocks):
        if "gate" in block["mlp"] and "experts" in block["mlp"]:
            block["mlp"]["gate"]["e_score_correction_bias"] = (
                0.2 * jax.random.normal(jax.random.key(i),
                                        (cfg.num_experts,)))
        for name in ("hc_attn", "hc_mlp"):
            if name in block:
                block[name]["alpha"] = jnp.asarray([0.8, -0.6, 0.5])
                block[name]["phi"] = block[name]["phi"] * 10.0
        attn = block["attn"]
        for part, names in (("q_up", ("nope", "rope")), ("kv_up", ("k",))):
            for name in names:
                attn[part][name] = attn[part][name] * sharp
        attn["kv_down"]["proj"]["kernel"] = (
            attn["kv_down"]["proj"]["kernel"] * sharp)
    return params


def _program(cfg, params, batch):
    """What the step's first dispatch reports, and the gradients."""
    (loss, report), grads = jax.jit(jax.value_and_grad(
        joyai_loss_fn(JoyAI(cfg), ce_chunk=32), has_aux=True))(params, batch)
    return {"loss": loss, **report, "grad_norm": optax.global_norm(grads),
            **_group_norms(grads, GROUPS)}, grads


def _forward(cfg, params, batch):
    """The keys of the report that need no gradient."""
    loss, report = jax.jit(joyai_loss_fn(JoyAI(cfg), ce_chunk=32))(
        params, batch)
    return {"loss": loss, **report}


def _off(got: dict, want: dict) -> dict:
    return {k: abs(float(got[k]) - want[k]) / abs(want[k])
            for k in want if k in got}


# -- a recomputed block's kept products --------------------------------------

@pytest.mark.parametrize("routed", [False, True], ids=["dense", "routed"])
def test_the_blocks_kept_products_change_no_number(routed):
    """Loss, report and every gradient leaf of one block under four
    streams with ``remat`` (PR 70: the block keeps, beside its routers'
    and its maps', each sub-layer's output as ``post`` reads it,
    ``out_proj``'s product, ``down``'s and the routed sum, and the dense
    or shared MLP's ``gate`` and ``up``) against without, under one
    ``jit`` each."""
    got = {}
    for remat in (False, True):
        cfg = _small(remat=remat, mtp_depth=0,
                     dense_layers=0 if routed else 1)
        got[remat] = _program(cfg, _params(cfg), _batch(cfg))
    (want, want_grads), (report, grads) = got[False], got[True]
    assert set(report) == set(want)
    for key in want:
        assert float(report[key]) == pytest.approx(
            float(want[key]), rel=1e-6, abs=1e-9), key
    leaves = jax.tree_util.tree_leaves_with_path
    want_leaves = {jax.tree_util.keystr(p): z for p, z in leaves(want_grads)}
    assert len(want_leaves) == len(leaves(grads)) > 20
    for path, leaf in leaves(grads):
        name = jax.tree_util.keystr(path)
        scale = max(float(np.abs(want_leaves[name]).max()), 1e-3)
        np.testing.assert_allclose(leaf, want_leaves[name],
                                   atol=1e-5 * scale, err_msg=name)


# -- the model against the reference ----------------------------------------

@pytest.mark.parametrize("overrides", [
    {"remat": True, "n_layer": 0, "dense_layers": 0},
    {"mtp_depth": 0, "hc_sinkhorn_iters": 20}],
    ids=["mtp_alone_remat", "dense_no_mtp_no_remat_20_iterations"])
def test_tiny_xing_in_float32_is_the_reference(ref, overrides):
    """Loss, both heads' losses, the absent routes' share, the streams'
    spread, the gradient norm, the maps' own and every gradient leaf
    (``phi``, ``b`` and ``alpha`` among them, with gates of order 1):
    the tokens-in-the-lanes maps, the lane-sliced mixes, the YaRN scale
    as a kernel argument and the recomputed block compute what the
    written-out equations compute. One block each, for the CPU's
    compile time: the MTP module's routed block alone, recomputed, on
    the embeddings' sum; and a dense block with all 20 iterations."""
    cfg = _small(**overrides)
    params, batch = _params(cfg), _batch(cfg)
    with jax.default_matmul_precision("highest"):
        got, grads = _program(cfg, params, batch)
        want, wants = ref.loss_and_grads(params, batch, _spec(cfg))
    assert set(want) == {"loss", "lm_loss", "grad_norm", "grad_norm_hc",
                         "hc_stream_spread"} | (
        {"mtp_loss", "moe_absent_route_share"} if cfg.mtp_depth else set())
    if not cfg.n_layer:     # copies of the embedding: no spread, both
        assert want.pop("hc_stream_spread") == got["hc_stream_spread"] == 0.0
    else:
        assert want["hc_stream_spread"] > 0.01      # the streams differ
    for key, d in _off(got, want).items():
        assert d < 2e-5, (key, d)
    assert float(got["hc_res_row_err"]) < 1e-4
    assert want["grad_norm_hc"] > 1e-4 * want["grad_norm"]
    flat = dict(jax.tree_util.tree_leaves_with_path(wants))
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        w = flat.pop(path)
        np.testing.assert_allclose(
            g, w, rtol=2e-4, atol=2e-5 * float(jnp.abs(w).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path))
    assert not flat


def _loud(scaling):
    """YaRN's own amplitude on cos and sin where the model's is 1."""
    class Loud(YarnScaling):
        amplitude = property(lambda self: self._m(1.0))
    return Loud(**dataclasses.asdict(scaling))


def _transposed(x, y, h_post, h_res):
    return hc.hc_post.__wrapped__(x, y, h_post, jnp.swapaxes(h_res, 0, 1))


def _unit_post(x, y, h_post, h_res):
    return hc.hc_post.__wrapped__(x, y, h_post / 2.0, h_res)


def _rows_first(a, iters, eps):
    m = jnp.exp(a)
    for _ in range(iters):
        m = m / (m.sum(1, keepdims=True) + eps)
        m = m / (m.sum(0, keepdims=True) + eps)
    return m


FAULTS = {
    "h_res_transposed": ("hc_post", _transposed),
    "h_post_without_its_2": ("hc_post", _unit_post),
    "rows_normalised_before_columns": ("sinkhorn", _rows_first),
    "yarn_scale_left_out": ("config", lambda c: dataclasses.replace(
        c, rope_scaling=dataclasses.replace(c.rope_scaling,
                                            mscale_all_dim=0.0, mscale=0.0))),
    "amplitude_on_cos_and_sin": ("config", lambda c: dataclasses.replace(
        c, rope_scaling=_loud(c.rope_scaling))),
}


@pytest.fixture(scope="module")
def faultless(ref):
    """The program's and the reference's numbers with nothing planted:
    the comparison the cell makes, which passes. A fresh model's
    mechanisms are near inert (scores of 1e-3, gates of 0.01, and 20
    normalisations reach the same matrix in either order), so the
    gates are of order 1, queries and keys 100 times the initialisers'
    and the normalisations 3."""
    cfg = _small(mtp_depth=0, seq_len=16)
    params = _params(cfg, seed=3, sharp=100.0)
    batch = _batch(cfg, seed=4)
    with jax.default_matmul_precision("highest"):
        got, _ = _program(cfg, params, batch)
        want, _ = ref.loss_and_grads(params, batch, _spec(cfg),
                                     keep_grads=False)
    off = _off(got, want)
    assert set(off) == set(want) and max(off.values()) < RTOL / 20
    return cfg, params, batch, want


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_comparison(faultless, monkeypatch, fault):
    """Each fault, planted in the program (``b`` normal(1.0) as the
    initialisers make it: no two streams equal, ``H_res`` not
    symmetric), moves a compared key past the cell's limit: one of the
    three that need no gradient, which is what the CPU's compile time
    allows five times; the gradient's keys move further."""
    cfg, params, batch, want = faultless
    where, what = FAULTS[fault]
    if where == "config":
        cfg = what(cfg)
    else:
        plain = getattr(hc, where)
        what.__wrapped__ = plain
        monkeypatch.setattr(hc, where, what)
    with jax.default_matmul_precision("highest"):
        off = _off(_forward(cfg, params, batch), want)
    assert set(off) == {"loss", "lm_loss", "hc_stream_spread"}
    assert max(off.values()) > 2 * RTOL, off


# -- hc_mult 1 is the block it was -------------------------------------------

def test_one_stream_builds_the_block_of_before():
    """No map parameter, no ``hc_*`` scope, no ``stats`` collection, the
    parameter tree of the JoyAI preset as it was (its names and shapes),
    and a report without the ``hc_*`` keys."""
    cfg = JoyAIConfig.tiny(dtype=jnp.float32)
    assert cfg.hc_mult == 1 and cfg.rope_scaling is None and not cfg.remat
    assert cfg.hc_params() == 0
    model = JoyAI(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    assert set(params["h_0"]) == {"attn", "attn_norm", "mlp", "mlp_norm"}
    assert set(params["mtp"]["h"]) == {"attn", "attn_norm", "mlp",
                                       "mlp_norm"}
    assert set(params["mtp"]) == {"enorm", "hnorm", "eh_proj", "h"}
    batch = _batch(cfg)
    loss = joyai_loss_fn(model, ce_chunk=32)
    text = jax.jit(jax.grad(lambda p, b: loss(p, b)[0])).lower(
        params, batch).as_text(debug_info=True)
    assert "hc_" not in text
    report = jax.eval_shape(loss, params, batch)[1]
    assert not [k for k in report if k.startswith("hc_")]
    assert cfg.mla_scale == pytest.approx((16 + 8) ** -0.5, rel=1e-12)


def test_parameters_and_scales_are_counted_as_the_config_says():
    cfg = _small()
    params = jax.eval_shape(JoyAI(cfg).init_params, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) \
        == cfg.num_params()
    maps = params["h_0"]["hc_mlp"]
    assert {k: v.shape for k, v in maps.items()} == {
        "phi": (4 * cfg.n_embd, 24), "b": (24,), "alpha": (3,)}
    assert set(params["mtp"]["h"]) >= {"hc_attn", "hc_mlp"}
    # the published model: 0.688 M of maps a block, MLA 28.41 M, a dense
    # block 128.20 M, a whole routed one 745 M; the cell's cut with the
    # MTP module 913.5 M (12.79 GB at 14 bytes), without it 759.3 M
    big = JoyAIConfig.xing4_0_29b_a4b()
    per = big.layer_params()
    assert big.hc_params() == 688182
    assert per["mla"] == pytest.approx(28.411e6, rel=1e-4)
    assert per["dense"] == pytest.approx(128.20e6, rel=1e-4)
    assert per["routed"] == pytest.approx(745e6, rel=1e-3)
    cut = dict(n_layer=5, dense_layers=1, experts_held=(0, 8),
               vocab_size=16384)
    assert JoyAIConfig.xing4_0_29b_a4b(**cut).num_params() * 14 \
        == pytest.approx(12.79e9, rel=1e-3)
    assert JoyAIConfig.xing4_0_29b_a4b(mtp_depth=0, **cut).num_params() \
        == pytest.approx(759.3e6, rel=1e-4)
    # YaRN by the DeepSeek-V3 code: amplitude m(1) / m(1), scale x m^2
    m = 0.1 * np.log(64.0) + 1.0
    assert big.rope_scaling.amplitude == 1.0
    assert big.mla_scale == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    assert m * m == pytest.approx(2.00474, rel=1e-5)
    with pytest.raises(ValueError, match="hc_mult=0"):
        JoyAIConfig.tiny(hc_mult=0)


# -- the share ---------------------------------------------------------------

def test_eight_shares_of_eight_sum_to_the_uncut_reference_layer(ref):
    """The guide's tie of the cut to the model: 64 experts, top-4, eight
    shares of eight. Each share is the program's ``MoE`` module (the
    held experts' routed part plus the shared expert); the routed parts
    of all eight, with the shared expert counted once, are the uncut
    reference's layer output."""
    kw = dict(num_experts=64, top_k=4, route_scale=2.0, dtype=jnp.float32)
    cfg = JoyAIConfig.tiny_xing(experts_held=None, **kw)
    d, f = cfg.n_embd, cfg.expert_width
    ks = jax.random.split(jax.random.key(7), 9)
    h = jax.random.normal(ks[0], (1, 64, d))

    def dense(key, rows, cols):
        return {"kernel": jax.random.normal(key, (rows, cols)) * 0.2}
    whole = {
        "gate": {"kernel": jax.random.normal(ks[1], (d, 64)),
                 "e_score_correction_bias":
                     jax.random.normal(ks[2], (64,)) * 0.3},
        "experts": {"gate_proj": jax.random.normal(ks[3], (64, d, f)) * 0.2,
                    "up_proj": jax.random.normal(ks[4], (64, d, f)) * 0.2,
                    "down_proj": jax.random.normal(ks[5], (64, f, d)) * 0.2},
        "shared": {"gate": dense(ks[6], d, cfg.shared_width),
                   "up": dense(ks[7], d, cfg.shared_width),
                   "down": dense(ks[8], cfg.shared_width, d)}}
    spec = {**_spec(cfg), "experts_held": (0, 64)}
    same = lambda v: v      # noqa: E731 — the reference's "no rounding"
    with jax.default_matmul_precision("highest"):
        want, load = ref._joyai()._moe(whole, h, spec, same)
        shared = ref._joyai()._swiglu(whole["shared"], h, same)
        total = 0.0
        for first in range(0, 64, 8):
            share = dict(whole, experts={
                k: v[first:first + 8] for k, v in whole["experts"].items()})
            held = JoyAIConfig.tiny_xing(experts_held=(first, 8), **kw)
            y, sown = MoE(held).apply({"params": share}, h, mutable=["moe"])
            total = total + (y - shared)            # the routed part
            np.testing.assert_array_equal(sown["moe"]["load"][0], load)
    assert float(load.sum()) == 64 * 4
    np.testing.assert_allclose(total + shared, want, rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(want - shared).max()) > 0.1    # routing mattered


# -- scopes, notes, the step's report ----------------------------------------

def test_the_step_carries_the_residual_paths_scopes_notes_and_report(
        monkeypatch):
    """Through ``make_train_step`` with the cell's ``grad_groups``:
    ``hc_attn`` and ``hc_mlp`` beside ``attn`` and ``mlp`` under each
    ``h_i`` and under ``mtp/h`` with ``maps``, ``pre`` and ``post``
    beneath, ``hc_expand`` under ``embed``, ``hc_collapse`` under
    ``blocks``; no ``attn`` or ``mlp`` path part above an ``hc_*`` one
    (the readers of those match whole parts); the notes; and
    ``hc_res_row_err``, ``hc_stream_spread`` and ``grad_norm_hc`` beside
    the losses."""
    from ray_tpu.util import tracing
    cfg = _small(remat=True, dense_layers=0)
    model = JoyAI(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    opt = optax.adamw(1e-3)
    state = jax.eval_shape(
        lambda p: train.init_train_state(p, opt, None), params)
    step = train.make_train_step(joyai_loss_fn(model, ce_chunk=32), opt,
                                 grad_groups=GROUPS)
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    lowered = step.lower(state, _batch(cfg))
    assert {k: notes[k] for k in (
        "hc_mult", "hc_sinkhorn_iters", "hc_state_dtype", "hc_maps_path",
        "hc_maps_block", "rope_kind", "blocks_remat",
        "blocks_remat_keeps")} == {
        "hc_mult": 4, "hc_sinkhorn_iters": 3, "hc_state_dtype": "float32",
        "hc_maps_path": "xla", "hc_maps_block": 0,
        "rope_kind": "yarn", "blocks_remat": True,
        "blocks_remat_keeps": "moe_router_logits,moe_router_experts,"
                              "moe_router_weights,moe_router_counts,"
                              "moe_router_lse,hc_maps_pre,hc_maps_post,"
                              "hc_maps_res,hc_maps_m,hc_maps_r,"
                              "mixer_out_proj,mlp_down,moe_routed_out,"
                              "mlp_gate,mlp_up,attn_out,attn_lse"}
    assert notes["mla_scale"] == pytest.approx(cfg.mla_scale)
    assert cfg.mla_scale == pytest.approx(
        24 ** -0.5 * (0.1 * np.log(4.0) + 1.0) ** 2)
    text = lowered.as_text(debug_info=True)
    for scope in ("embed/hc_expand", "blocks/hc_collapse",
                  "h_0/hc_attn/maps", "h_0/hc_attn/pre", "h_0/hc_attn/post",
                  "h_0/hc_mlp/maps", "h_0/hc_mlp/pre", "h_0/hc_mlp/post",
                  "mtp/h/hc_attn/maps", "mtp/h/hc_mlp/post",
                  "mtp/hc_expand", "mtp/hc_collapse", "h_0/attn/core",
                  "h_0/mlp/experts"):
        assert scope in text, scope
    for under in ("attn/hc_", "mlp/hc_", "attn_norm/hc_", "mlp_norm/hc_"):
        assert under not in text, under
    metrics = jax.eval_shape(step, state, _batch(cfg))[1]
    assert {"loss", "lm_loss", "mtp_loss", "grad_norm", "grad_norm_hc",
            "hc_res_row_err", "hc_stream_spread",
            "moe_absent_route_share"} <= set(metrics)


# -- meshes ------------------------------------------------------------------

def test_a_batch_sharded_over_dp_trains_as_one_device_does():
    cfg = _small(mtp_depth=0, seq_len=16)
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    params = _params(cfg)
    batch = _batch(cfg, rows=4)

    def run(model):
        return jax.jit(jax.value_and_grad(
            lambda p, b: joyai_loss_fn(model, ce_chunk=32)(p, b)[0]))(
                params, batch)
    with jax.default_matmul_precision("highest"):
        want, wants = run(JoyAI(cfg))
        got, gots = run(JoyAI(cfg, mesh=mesh))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(jax.tree_util.tree_leaves(gots),
                    jax.tree_util.tree_leaves(wants)):
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=1e-5 * float(jnp.abs(w).max()) + 1e-9)


@pytest.mark.parametrize("axis, says", [
    ("tp", "lanes split over chips"), ("sp", "sequence split over chips")])
def test_tensor_and_sequence_axes_are_refused_by_name(axis, says):
    """By the residual path, before a layer is built."""
    mesh = make_mesh({axis: 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError,
                       match=f"hyper-connections.*{axis}=2") as err:
        jax.eval_shape(JoyAI(_small(), mesh=mesh).init_params,
                       jax.random.key(0))
    assert says in str(err.value)
    assert joyai.hc is hc
