"""Nemotron-H (``models/nemotron_h.py``): what ``ops/moe.py::routed_ffn``
learned for it (the sigmoid router with its selection bias, the relu^2
expert, a held share of the experts), the model at its tiny preset
against the benchmark's plain float32 reference
(``benchmark/references/nemotron_h.py``: the recurrence as a scan over
time, every held expert on every token, attention as a masked softmax),
and the tie of the share to the model: the shares' routed parts sum, with
the shared expert counted once, to the uncut layer."""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu import train
from ray_tpu.models.nemotron_h import (
    MoE, NemotronH, NemotronHConfig, nemotron_h_loss_fn,
)
from ray_tpu.ops import moe
from ray_tpu.ops.moe import held_route_share, held_rows, routed_ffn
from ray_tpu.parallel import make_mesh

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, BENCHMARK)       # the reference borrows olmoe's rounder
    path = os.path.join(BENCHMARK, "references", "nemotron_h.py")
    spec = importlib.util.spec_from_file_location("reference_nemotron", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    yield mod
    sys.path.remove(BENCHMARK)


def _spec(cfg) -> dict:
    spec = {k: getattr(cfg, k) for k in (
        "pattern", "mamba_heads", "mamba_head_dim", "ssm_state",
        "ssm_groups", "n_head", "n_kv_head", "head_dim", "top_k",
        "norm_topk_prob", "route_scale", "rms_eps")}
    spec["experts_held"] = cfg.experts_span
    return spec


def _batch(cfg, rows=2, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, cfg.seq_len), dtype=np.int32)
    return {"tokens": jnp.asarray(toks),
            "targets": jnp.asarray(np.roll(toks, -1, 1))}


# -- the router, the expert and the share, on the layer alone -------------

T, D, F, E, K = 4096, 16, 24, 16, 3


def _layer_inputs(seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(ks[0], (2, T // 2, D)),
            jax.random.normal(ks[1], (D, E)),
            jax.random.normal(ks[2], (E, D, F)) * 0.3,
            jax.random.normal(ks[3], (E, F, D)) * 0.3,
            jax.random.normal(ks[4], (E,)) * 0.5)


def _dense(x, rw, up, down, bias, scale=2.5, norm=True):
    """Every expert on every token: the sigmoid router's choice by
    ``s + bias``, weights ``s`` without it, renormalised and scaled."""
    s = jax.nn.sigmoid(x @ rw)
    _, chosen = jax.lax.top_k(s + bias, K)
    w = jnp.take_along_axis(s, chosen, -1)
    if norm:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    mix = (jax.nn.one_hot(chosen, E) * (w * scale)[..., None]).sum(-2)
    h = jnp.square(jax.nn.relu(jnp.einsum("btd,edf->ebtf", x, up)))
    return jnp.einsum("ebtf,efd,bte->btd", h, down, mix), chosen


def _sigmoid_layer(x, rw, up, down, bias, **kw):
    kw = {"norm_topk_prob": True, "route_scale": 2.5, **kw}
    return routed_ffn(x, rw, None, up, down, top_k=K, router="sigmoid",
                      select_bias=bias, expert="relu2", **kw)


def test_sigmoid_router_chooses_with_the_bias_and_weighs_without_it():
    x, rw, up, down, bias = _layer_inputs()
    with jax.default_matmul_precision("highest"):
        want, chosen = _dense(x, rw, up, down, bias)
        y, aux, z, load = _sigmoid_layer(x, rw, up, down, bias)
        unbiased, other = _dense(x, rw, up, down, jnp.zeros((E,)))
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    # the bias moved the choice (so the test sees it), not the weights
    assert float((chosen != other).mean()) > 0.05
    assert float(jnp.abs(want - unbiased).max()) > 0.1
    np.testing.assert_array_equal(
        load, jax.nn.one_hot(chosen, E).sum((0, 1, 2)))
    assert float(aux) == 0.0 and float(z) == 0.0    # no such losses


@pytest.mark.parametrize("norm, scale", [(True, 2.5), (False, 1.0)],
                         ids=["renormalised_and_scaled", "raw_scores"])
def test_sigmoid_weights_renormalise_and_scale(norm, scale):
    x, rw, up, down, bias = _layer_inputs(1)
    with jax.default_matmul_precision("highest"):
        want, _ = _dense(x, rw, up, down, bias, scale=scale, norm=norm)
        y, *_ = _sigmoid_layer(x, rw, up, down, bias, norm_topk_prob=norm,
                               route_scale=scale)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)


def test_the_selection_bias_takes_no_gradient():
    x, rw, up, down, bias = _layer_inputs(2)
    g = jax.jit(jax.grad(lambda b: jnp.sum(
        _sigmoid_layer(x, rw, up, down, b)[0] ** 2)))(bias)
    np.testing.assert_array_equal(g, np.zeros(E, np.float32))


def test_relu2_experts_under_the_softmax_router():
    """The two-matrix expert is the expert's business, not the
    router's: OLMoE's router over relu^2 experts."""
    x, rw, up, down, _ = _layer_inputs(3)
    probs = jax.nn.softmax(x @ rw, -1)
    top, chosen = jax.lax.top_k(probs, K)
    mix = (jax.nn.one_hot(chosen, E) * top[..., None]).sum(-2)
    with jax.default_matmul_precision("highest"):
        h = jnp.square(jax.nn.relu(jnp.einsum("btd,edf->ebtf", x, up)))
        want = jnp.einsum("ebtf,efd,bte->btd", h, down, mix)
        y, *_ = routed_ffn(x, rw, None, up, down, top_k=K, expert="relu2")
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)


def test_the_expert_kind_has_to_match_the_matrices():
    x, rw, up, down, _ = _layer_inputs()
    with pytest.raises(ValueError, match="relu2"):
        routed_ffn(x, rw, up, up, down, top_k=K, expert="relu2")
    with pytest.raises(ValueError, match="swiglu"):
        routed_ffn(x, rw, None, up, down, top_k=K)
    with pytest.raises(ValueError, match="experts_held"):
        routed_ffn(x, rw, None, up[:3], down[:3], top_k=K, expert="relu2",
                   experts_held=(0, 4))


@pytest.mark.parametrize("held", [4, 2])
def test_the_shares_sum_to_the_whole_layer(held):
    """Each share routes over all 16, computes its own experts' part;
    the parts add up to the layer, and every share reports the load over
    all 16."""
    x, rw, up, down, bias = _layer_inputs(4)
    assert held_rows(T * K, held, E) == T * K * held // E * 2
    with jax.default_matmul_precision("highest"):
        want, chosen = _dense(x, rw, up, down, bias)
        total = 0.0
        for first in range(0, E, held):
            y, _, _, load = _sigmoid_layer(
                x, rw, up[first:first + held], down[first:first + held],
                bias, experts_held=(first, held))
            total = total + y
            assert float(load.sum()) == T * K
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=2e-5)
    counts = jax.nn.one_hot(chosen, E).sum((0, 1, 2))
    assert float(held_route_share(load, (2, 2))) == pytest.approx(
        float(counts[2:4].sum()) / (T * K))


def test_no_route_is_dropped_when_more_land_here_than_a_slab_holds():
    """A bias that sends two of every token's three routes to the two
    held experts: 8,192 routes against slabs of 3,072, so three
    slabs run. Values and every gradient against the dense layer."""
    x, rw, up, down, _ = _layer_inputs(5)
    bias = jnp.where(jnp.arange(E) < 2, 10.0, 0.0)
    pad_up = jnp.zeros((E - 2, D, F))
    pad_down = jnp.zeros((E - 2, F, D))

    def program(x, rw, u, d):
        y, _, _, load = _sigmoid_layer(x, rw, u, d, bias,
                                       experts_held=(0, 2))
        return jnp.sum(y ** 2), load

    def dense(x, rw, u, d):
        return jnp.sum(_dense(x, rw, jnp.concatenate([u, pad_up]),
                              jnp.concatenate([d, pad_down]), bias)[0] ** 2)

    args = (x, rw, up[:2], down[:2])
    with jax.default_matmul_precision("highest"):
        (got, load), grads = jax.jit(jax.value_and_grad(
            program, range(4), has_aux=True))(*args)
        want, wants = jax.jit(jax.value_and_grad(dense, range(4)))(*args)
    assert float(load[:2].sum()) == 2 * T > held_rows(T * K, 2, E)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(grads, wants):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(w).max()))


def test_the_held_layer_notes_what_it_is_at_trace_time(monkeypatch):
    from ray_tpu.util import tracing
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    x, rw, up, down, bias = _layer_inputs()
    jax.jit(lambda *a: _sigmoid_layer(*a, experts_held=(4, 2))[0]).trace(
        x, rw, up[4:6], down[4:6], bias)
    assert notes == {
        "moe_tokens": T, "moe_experts": E, "moe_top_k": K,
        "moe_routes": T * K, "moe_path": "ragged_dot", "moe_axes": [],
        "moe_router": "sigmoid", "moe_expert_kind": "relu2",
        "moe_experts_held": [4, 2], "moe_rows_sorted": T * K // 4,
        "moe_rows_path": "xla", "moe_router_path": "xla"}


def test_held_experts_route_their_own_tokens_on_a_dp_mesh():
    """Under ``shard_map`` each chip walks its own tokens' routes; the
    load is the global batch's."""
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    x, rw, up, down, bias = _layer_inputs(6)
    with jax.default_matmul_precision("highest"):
        want, _, _, load1 = _sigmoid_layer(
            x, rw, up[:4], down[:4], bias, experts_held=(0, 4))
        got, _, _, load2 = jax.jit(lambda *a: _sigmoid_layer(
            *a, experts_held=(0, 4), mesh=mesh))(
                x, rw, up[:4], down[:4], bias)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(load1, load2)


# -- the share and the model's expert layer -------------------------------

def test_sixteen_shares_of_eight_sum_to_the_uncut_reference_layer(ref):
    """The guide's tie of the cut to the model: 128 experts, top-6,
    sixteen shares of eight. Each share is the program's ``MoE`` module
    (the held experts' routed part plus the shared expert); the routed
    parts of all sixteen, with the shared expert counted once, are the
    uncut reference's layer output."""
    cfg = NemotronHConfig.tiny(num_experts=128, top_k=6, experts_held=None,
                               dtype=jnp.float32)
    d, f = cfg.n_embd, cfg.expert_width
    ks = jax.random.split(jax.random.key(7), 6)
    h = jax.random.normal(ks[0], (2, 128, d))
    whole = {
        "gate": {"kernel": jax.random.normal(ks[1], (d, 128)),
                 "e_score_correction_bias":
                     jax.random.normal(ks[2], (128,)) * 0.3},
        "experts": {"up_proj": jax.random.normal(ks[3], (128, d, f)) * 0.2,
                    "down_proj": jax.random.normal(ks[4], (128, f, d)) * 0.2},
        "shared": {"up": {"kernel": jax.random.normal(
                       ks[5], (d, cfg.shared_width)) * 0.2},
                   "down": {"kernel": jax.random.normal(
                       ks[0], (cfg.shared_width, d)) * 0.2}}}
    spec = {**_spec(cfg), "experts_held": (0, 128)}
    with jax.default_matmul_precision("highest"):
        want, load = ref._moe(whole, h, spec, lambda v: v)
        sh = whole["shared"]
        shared = jnp.square(jax.nn.relu(
            h @ sh["up"]["kernel"])) @ sh["down"]["kernel"]
        total = 0.0
        for first in range(0, 128, 8):
            share = dict(whole, experts={
                k: v[first:first + 8] for k, v in whole["experts"].items()})
            held = NemotronHConfig.tiny(
                num_experts=128, top_k=6, experts_held=(first, 8),
                dtype=jnp.float32)
            y, sown = MoE(held).apply({"params": share}, h, mutable=["moe"])
            total = total + (y - shared)            # the routed part
            np.testing.assert_array_equal(sown["moe"]["load"][0], load)
    assert float(load.sum()) == 2 * 128 * 6
    np.testing.assert_allclose(total + shared, want, rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(want - shared).max()) > 0.1    # routing mattered


# -- the model against the reference --------------------------------------

def _program(cfg, params, batch):
    model = NemotronH(cfg)
    (loss, report), grads = jax.jit(jax.value_and_grad(
        nemotron_h_loss_fn(model, ce_chunk=32), has_aux=True))(params, batch)
    return {"loss": loss, **report,
            "grad_norm": optax.global_norm(grads)}, grads


@pytest.mark.parametrize("overrides", [
    {}, {"pattern": "M*EME", "seq_len": 40, "experts_held": None},
    {"pattern": "EM", "experts_held": (12, 4), "norm_topk_prob": False}],
    ids=["tiny", "ragged_chunks_all_experts", "last_share_raw_scores"])
def test_tiny_nemotron_h_in_float32_is_the_reference(ref, overrides):
    """Loss, the absent routes' share, the gradient norm and every
    gradient leaf: the chunked scan and its recomputing backward, the
    sorted slabs and grouped matmuls, the kernels' attention compute
    what the recurrence, "every held expert on every token" and a
    masked softmax compute. The selection bias is not zero."""
    cfg = NemotronHConfig.tiny(dtype=jnp.float32, **overrides)
    params = jax.jit(NemotronH(cfg).init_params)(jax.random.key(1))
    for i, kind in enumerate(cfg.pattern):
        if kind == "E":
            gate = params[f"h_{i}"]["mlp"]["gate"]
            gate["e_score_correction_bias"] = 0.2 * jax.random.normal(
                jax.random.key(i), (cfg.num_experts,))
    batch = _batch(cfg)
    with jax.default_matmul_precision("highest"):
        got, grads = _program(cfg, params, batch)
        want, wants = ref.loss_and_grads(params, batch, _spec(cfg))
    for key in want:
        assert float(got[key]) == pytest.approx(want[key], rel=1e-5), key
    assert set(got) - set(want) == {"moe_load_max_over_mean",
                                    "moe_held_route_share"}
    assert float(got["moe_held_route_share"]) == pytest.approx(
        1.0 - want["moe_absent_route_share"], abs=1e-6)
    flat = dict(jax.tree_util.tree_leaves_with_path(wants))
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        w = flat.pop(path)
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=1e-5 * float(jnp.abs(w).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path))
    assert not flat


def test_parameters_are_counted_and_initialised_as_the_config_says():
    cfg = NemotronHConfig.tiny()
    params = jax.jit(NemotronH(cfg).init_params)(jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) \
        == cfg.num_params()
    m = params["h_0"]["mamba"]
    dt = jax.nn.softplus(m["dt_bias"])
    assert float(dt.min()) >= cfg.time_step_min * 0.999
    assert float(dt.max()) <= cfg.time_step_max * 1.001
    a = jnp.exp(m["A_log"])
    assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0
    np.testing.assert_array_equal(m["D"], np.ones(cfg.mamba_heads))
    assert float(jnp.abs(m["conv"]["kernel"]).max()) <= 0.5
    bias = params["h_1"]["mlp"]["gate"]["e_score_correction_bias"]
    np.testing.assert_array_equal(bias, np.zeros(cfg.num_experts))
    # the published model: 31.6B, of which a Mamba layer 38.74 M
    big = NemotronHConfig.nemotron_3_nano_30b_a3b()
    assert big.pattern.count("M") == big.pattern.count("E") == 23
    assert big.pattern.count("*") == 6 and big.n_layer == 52
    assert big.num_params() == pytest.approx(31.58e9, rel=1e-3)
    assert big.layer_params()["M"] == pytest.approx(38.74e6, rel=1e-3)
    assert big.layer_params()["*"] == pytest.approx(23.40e6, rel=1e-3)


def test_a_pattern_letter_or_positions_it_does_not_know_is_refused():
    with pytest.raises(ValueError, match="pattern"):
        NemotronHConfig.tiny(pattern="MXE")
    with pytest.raises(NotImplementedError, match="positional"):
        NemotronHConfig.tiny(positions="rope")


def test_the_step_reports_and_notes_what_the_layers_are(monkeypatch):
    """Through ``make_train_step``: the report's three scalars ride in
    the step's metrics, and the trace's notes carry the ``ssm_*`` keys,
    the routed layer's and the pattern."""
    from ray_tpu.util import tracing
    cfg = NemotronHConfig.tiny(dtype=jnp.float32)
    model = NemotronH(cfg)
    params = jax.jit(model.init_params)(jax.random.key(0))
    opt = optax.adamw(1e-3)
    state = train.init_train_state(params, opt, None)
    step = train.make_train_step(nemotron_h_loss_fn(model, ce_chunk=32), opt)
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    step.trace(state, _batch(cfg))
    assert {k: notes[k] for k in (
        "layer_pattern", "ssm_tokens", "ssm_heads", "ssm_state",
        "ssm_chunk", "ssm_path", "gate_norm_path", "moe_router",
        "moe_expert_kind", "moe_experts_held")} == {
        "layer_pattern": "MEM*E", "ssm_tokens": 128, "ssm_heads": 8,
        "ssm_state": 16, "ssm_chunk": 16, "ssm_path": "chunked_xla",
        "gate_norm_path": "xla", "moe_router": "sigmoid", "moe_expert_kind": "relu2",
        "moe_experts_held": [4, 4]}
    state, metrics = step(state, _batch(cfg))
    assert {"loss", "lm_loss", "moe_held_route_share",
            "moe_absent_route_share", "moe_load_max_over_mean",
            "grad_norm"} <= set(metrics)
    assert float(metrics["lm_loss"]) == float(metrics["loss"])
    assert 0.0 < float(metrics["moe_held_route_share"]) < 1.0


def test_a_batch_sharded_over_dp_trains_as_one_device_does():
    cfg = NemotronHConfig.tiny(dtype=jnp.float32)
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    params = jax.jit(NemotronH(cfg).init_params)(jax.random.key(0))
    batch = _batch(cfg, rows=4)
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(nemotron_h_loss_fn(NemotronH(cfg), ce_chunk=32))(
            params, batch)
        got, _ = jax.jit(nemotron_h_loss_fn(
            NemotronH(cfg, mesh=mesh), ce_chunk=32))(params, batch)
    assert float(got) == pytest.approx(float(want), rel=1e-5)


def test_a_sequence_split_over_chips_is_refused_by_name():
    cfg = NemotronHConfig.tiny()
    mesh = make_mesh({"sp": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="state passed"):
        jax.eval_shape(NemotronH(cfg, mesh=mesh).init_params,
                       jax.random.key(0))
    # attention and experts alone have nothing against it
    jax.eval_shape(NemotronH(NemotronHConfig.tiny(pattern="*E"),
                             mesh=mesh).init_params, jax.random.key(0))
