"""JoyAI-LLM-Flash (``models/joyai.py``): the model at its tiny preset
against the benchmark's plain float32 reference
(``benchmark/references/joyai.py``: attention as a masked softmax over
the concatenated keys, every held expert on every token, the MTP module
written out), the combination ``ops/moe.py::routed_ffn`` had not run
(sigmoid router + SwiGLU experts + a held share), and the tie of the
share to the model: sixteen shares' routed parts sum, with the shared
expert counted once, to the uncut layer."""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu import models, train
from ray_tpu.models.joyai import (
    JoyAI, JoyAIConfig, MoE, joyai_loss_fn, mtp_targets,
)
from ray_tpu.ops.moe import held_rows, routed_ffn
from ray_tpu.parallel import make_mesh

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, BENCHMARK)       # the reference borrows olmoe's rounder
    path = os.path.join(BENCHMARK, "references", "joyai.py")
    spec = importlib.util.spec_from_file_location("reference_joyai", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    yield mod
    sys.path.remove(BENCHMARK)


def _spec(cfg) -> dict:
    spec = {k: getattr(cfg, k) for k in (
        "n_layer", "dense_layers", "mtp_depth", "mtp_weight", "n_head",
        "kv_rank", "nope_dim", "rope_dim", "rope_theta", "top_k",
        "norm_topk_prob", "route_scale", "rms_eps")}
    spec["experts_held"] = cfg.experts_span
    return spec


def _batch(cfg, rows=2, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, cfg.seq_len), dtype=np.int32)
    return {"tokens": jnp.asarray(toks),
            "targets": jnp.asarray(np.roll(toks, -1, 1))}


def _params(cfg, seed=1):
    """Initialised, then every router's selection bias moved off zero."""
    params = jax.jit(JoyAI(cfg).init_params)(jax.random.key(seed))
    blocks = [params[f"h_{i}"] for i in range(cfg.dense_layers, cfg.n_layer)]
    if cfg.mtp_depth:
        blocks.append(params["mtp"]["h"])
    for i, block in enumerate(blocks):
        block["mlp"]["gate"]["e_score_correction_bias"] = (
            0.2 * jax.random.normal(jax.random.key(i), (cfg.num_experts,)))
    return params


# -- sigmoid + SwiGLU + held, on the layer alone ----------------------------

T, D, F, E, K = 4096, 32, 16, 16, 2


def _layer_inputs(seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(ks[0], (T, D)), jax.random.normal(ks[1], (D, E)),
            jax.random.normal(ks[2], (E, D, F)) * 0.3,
            jax.random.normal(ks[3], (E, D, F)) * 0.3,
            jax.random.normal(ks[4], (E, F, D)) * 0.3)


def _dense_swiglu(x, rw, gate, up, down, bias):
    """Every expert on every token, times the router's weight or zero."""
    s = jax.nn.sigmoid(x @ rw)
    _, chosen = jax.lax.top_k(s + bias, K)
    w = jnp.take_along_axis(s, chosen, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * 2.5
    mix = (jax.nn.one_hot(chosen, E) * w[..., None]).sum(1)     # [T, E]
    h = jax.nn.silu(jnp.einsum("td,edf->etf", x, gate)) * jnp.einsum(
        "td,edf->etf", x, up)
    return jnp.einsum("etf,efd,te->td", h, down, mix)


def test_sigmoid_swiglu_held_drops_no_route_when_a_slab_overflows():
    """A bias that sends both of every token's routes to the two held
    experts: 8,192 routes against slabs of 2,048, so four slabs run, on
    three grouped matmuls an expert (``_slab`` with a ``w_gate``).
    Values and every gradient against the dense layer."""
    x, rw, gate, up, down = _layer_inputs()
    bias = jnp.where(jnp.arange(E) < 2, 10.0, 0.0)
    zeros = jnp.zeros((E - 2, D, F))

    def program(x, rw, g, u, d):
        y, _, _, load = routed_ffn(
            x, rw, g, u, d, top_k=K, norm_topk_prob=True, router="sigmoid",
            select_bias=bias, route_scale=2.5, expert="swiglu",
            experts_held=(0, 2))
        return jnp.sum(y ** 2), load

    def dense(x, rw, g, u, d):
        full = (jnp.concatenate([g, zeros]), jnp.concatenate([u, zeros]),
                jnp.concatenate([d, jnp.zeros((E - 2, F, D))]))
        return jnp.sum(_dense_swiglu(x, rw, *full, bias) ** 2)

    args = (x, rw, gate[:2], up[:2], down[:2])
    with jax.default_matmul_precision("highest"):
        (got, load), grads = jax.jit(jax.value_and_grad(
            program, range(5), has_aux=True))(*args)
        want, wants = jax.jit(jax.value_and_grad(dense, range(5)))(*args)
    assert float(load[:2].sum()) == K * T > held_rows(T * K, 2, E)
    assert held_rows(T * K, 2, E) == 2048
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(grads, wants):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(w).max()))


def test_the_cells_slab_is_twice_the_even_share():
    """256 experts, top-8, 16 held, 8,192 tokens: 65,536 routes of which
    4,096 land here at an even load; a slab gathers 8,192 sorted rows."""
    assert held_rows(8192 * 8, 16, 256) == 8192


def test_the_held_layer_notes_the_combination_at_trace_time(monkeypatch):
    from ray_tpu.util import tracing
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    x, rw, gate, up, down = _layer_inputs()
    jax.jit(lambda *a: routed_ffn(
        *a, top_k=K, router="sigmoid", expert="swiglu",
        experts_held=(4, 2))[0]).trace(x, rw, gate[4:6], up[4:6], down[4:6])
    assert {k: notes[k] for k in ("moe_router", "moe_expert_kind",
                                  "moe_experts_held", "moe_rows_sorted")} == {
        "moe_router": "sigmoid", "moe_expert_kind": "swiglu",
        "moe_experts_held": [4, 2], "moe_rows_sorted": T * K // 4}


# -- the share and the model's routed layer ---------------------------------

def test_sixteen_shares_of_sixteen_sum_to_the_uncut_reference_layer(ref):
    """The guide's tie of the cut to the model: 256 experts, top-8,
    sixteen shares of sixteen. Each share is the program's ``MoE``
    module (the held experts' routed part plus the shared expert); the
    routed parts of all sixteen, with the shared expert counted once,
    are the uncut reference's layer output."""
    kw = dict(num_experts=256, top_k=8, dtype=jnp.float32)
    cfg = JoyAIConfig.tiny(experts_held=None, **kw)
    d, f = cfg.n_embd, cfg.expert_width
    ks = jax.random.split(jax.random.key(7), 9)
    h = jax.random.normal(ks[0], (2, 128, d))

    def dense(key, rows, cols):
        return {"kernel": jax.random.normal(key, (rows, cols)) * 0.2}
    whole = {
        "gate": {"kernel": jax.random.normal(ks[1], (d, 256)),
                 "e_score_correction_bias":
                     jax.random.normal(ks[2], (256,)) * 0.3},
        "experts": {"gate_proj": jax.random.normal(ks[3], (256, d, f)) * 0.2,
                    "up_proj": jax.random.normal(ks[4], (256, d, f)) * 0.2,
                    "down_proj": jax.random.normal(ks[5], (256, f, d)) * 0.2},
        "shared": {"gate": dense(ks[6], d, cfg.shared_width),
                   "up": dense(ks[7], d, cfg.shared_width),
                   "down": dense(ks[8], cfg.shared_width, d)}}
    spec = {**_spec(cfg), "experts_held": (0, 256)}
    same = lambda v: v      # noqa: E731 — the reference's "no rounding"
    with jax.default_matmul_precision("highest"):
        want, load = ref._moe(whole, h, spec, same)
        shared = ref._swiglu(whole["shared"], h, same)
        total = 0.0
        for first in range(0, 256, 16):
            share = dict(whole, experts={
                k: v[first:first + 16] for k, v in whole["experts"].items()})
            held = JoyAIConfig.tiny(experts_held=(first, 16), **kw)
            y, sown = MoE(held).apply({"params": share}, h, mutable=["moe"])
            total = total + (y - shared)            # the routed part
            np.testing.assert_array_equal(sown["moe"]["load"][0], load)
    assert float(load.sum()) == 2 * 128 * 8
    np.testing.assert_allclose(total + shared, want, rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(want - shared).max()) > 0.1    # routing mattered


# -- the model against the reference ----------------------------------------

def _program(cfg, params, batch):
    model = JoyAI(cfg)
    (loss, report), grads = jax.jit(jax.value_and_grad(
        joyai_loss_fn(model, ce_chunk=32), has_aux=True))(params, batch)
    return {"loss": loss, **report,
            "grad_norm": optax.global_norm(grads)}, grads


@pytest.mark.parametrize("clip", [1.0, 100.0], ids=["clipped", "unclipped"])
def test_the_references_first_adamw_step_is_optaxs(ref, clip):
    """``adamw_first_change`` (the reference's own optimizer step, which
    the benchmark's cell holds the program's first update against)
    changes the parameters by what ``optax``'s clipped AdamW does from
    zero moments, with the gradient kept on the host as the cell keeps
    it; a bfloat16 first moment moves the norm by rounding alone."""
    cfg = JoyAIConfig.tiny(dtype=jnp.float32)
    params, batch = _params(cfg), _batch(cfg)
    o = {"learning_rate": 2e-5, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
         "weight_decay": 0.1, "clip_global_norm": clip}
    with jax.default_matmul_precision("highest"):
        out, grads = ref.loss_and_grads(params, batch, _spec(cfg))
    assert all(isinstance(g, np.ndarray)
               for g in jax.tree_util.tree_leaves(grads))
    assert (out["grad_norm"] > 1.0) and (out["grad_norm"] < 100.0)
    got = ref.adamw_first_change(params, grads, out["grad_norm"], o)
    for mu_dtype, rel in ((jnp.float32, 1e-5), (jnp.bfloat16, 1e-4)):
        opt = optax.chain(
            optax.clip_by_global_norm(clip),
            optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                        eps=o["eps"], weight_decay=o["weight_decay"],
                        mu_dtype=mu_dtype))
        updates, _ = opt.update(grads, opt.init(params), params)
        assert got == pytest.approx(float(optax.global_norm(updates)),
                                    rel=rel)
    both = ref.loss_and_grad_norm(params, batch, {**_spec(cfg), "adamw": o})
    assert both["update_norm"] == pytest.approx(got, rel=1e-6)
    assert "update_norm" not in ref.loss_and_grad_norm(
        params, batch, _spec(cfg))


@pytest.mark.parametrize("overrides", [
    {}, {"experts_held": None, "seq_len": 40},
    {"n_layer": 2, "dense_layers": 0, "experts_held": (12, 4),
     "norm_topk_prob": False, "mtp_weight": 1.0},
    {"mtp_depth": 0, "dense_layers": 2, "n_layer": 2}],
    ids=["tiny", "all_experts_short_rows", "no_dense_last_share",
         "no_mtp_no_routed"])
def test_tiny_joyai_in_float32_is_the_reference(ref, overrides):
    """Loss, both heads' losses, the absent routes' share, the gradient
    norm and every gradient leaf: latent attention with its recomputing
    backward, the sorted slabs and grouped matmuls, the MTP module and
    the two chunked cross-entropies compute what a masked softmax over
    concatenated keys, "every held expert on every token" and the
    module written out compute. The selection biases are not zero."""
    cfg = JoyAIConfig.tiny(dtype=jnp.float32, **overrides)
    params = _params(cfg)
    batch = _batch(cfg)
    with jax.default_matmul_precision("highest"):
        got, grads = _program(cfg, params, batch)
        want, wants = ref.loss_and_grads(params, batch, _spec(cfg))
    for key in want:
        assert float(got[key]) == pytest.approx(want[key], rel=1e-5), key
    routed = cfg.n_layer > cfg.dense_layers or cfg.mtp_depth
    assert set(got) - set(want) == ({"moe_load_max_over_mean",
                                     "moe_held_route_share"}
                                    if routed else set())
    assert ("mtp_loss" in want) == bool(cfg.mtp_depth)
    flat = dict(jax.tree_util.tree_leaves_with_path(wants))
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        w = flat.pop(path)
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=1e-5 * float(jnp.abs(w).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path))
    assert not flat


def test_logits_of_both_heads_are_the_references(ref):
    cfg = JoyAIConfig.tiny(dtype=jnp.float32)
    params = _params(cfg, seed=2)
    batch = _batch(cfg, seed=3)
    with jax.default_matmul_precision("highest"):
        main, mtp = JoyAI(cfg).apply({"params": params}, batch["tokens"],
                                     batch["targets"])
        want_main, want_mtp = ref.logits(params, batch["tokens"],
                                         batch["targets"], _spec(cfg))
    assert main.shape == mtp.shape == (2, cfg.seq_len, cfg.vocab_size)
    np.testing.assert_allclose(main, want_main, atol=2e-5)
    np.testing.assert_allclose(mtp, want_mtp, atol=2e-5)
    assert float(jnp.abs(main - mtp).max()) > 0.01


def test_the_mtp_loss_is_the_mean_over_positions_with_a_second_next_token():
    """Position i of the module predicts ``targets[i + 1]``; a row's
    last position has none and is left out of the mean."""
    cfg = JoyAIConfig.tiny(dtype=jnp.float32)
    params = _params(cfg)
    batch = _batch(cfg)
    np.testing.assert_array_equal(
        mtp_targets(batch["targets"])[:, :-1], batch["targets"][:, 1:])
    assert (np.asarray(mtp_targets(batch["targets"]))[:, -1] == -1).all()
    with jax.default_matmul_precision("highest"):
        _, report = jax.jit(joyai_loss_fn(JoyAI(cfg), ce_chunk=32))(
            params, batch)
        _, logits = JoyAI(cfg).apply({"params": params}, batch["tokens"],
                                     batch["targets"])
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    want = -jnp.take_along_axis(
        logp, batch["targets"][:, 1:, None], -1).mean()
    assert float(report["mtp_loss"]) == pytest.approx(float(want), rel=1e-5)


def test_parameters_are_counted_and_initialised_as_the_config_says():
    cfg = JoyAIConfig.tiny()
    params = jax.jit(JoyAI(cfg).init_params)(jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) \
        == cfg.num_params()
    assert set(params) == {"wte", "h_0", "h_1", "h_2", "norm_f", "lm_head",
                           "mtp", "mtp_norm"}
    assert set(params["h_0"]["mlp"]) == {"gate", "up", "down"}    # dense
    assert set(params["h_1"]["mlp"]) == {"gate", "experts", "shared"}
    attn = params["h_1"]["attn"]
    h = cfg.n_head
    assert attn["q_up"]["nope"].shape == (cfg.q_rank, h * cfg.nope_dim)
    assert attn["q_up"]["rope"].shape == (cfg.q_rank, h * cfg.rope_dim)
    assert attn["kv_down"]["proj"]["kernel"].shape == (
        cfg.n_embd, cfg.kv_rank + cfg.rope_dim)
    assert attn["kv_up"]["v"].shape == (cfg.kv_rank, h * cfg.v_dim)
    assert float(jnp.std(attn["q_up"]["nope"])) == pytest.approx(0.02,
                                                                 rel=0.1)
    np.testing.assert_array_equal(attn["q_down"]["norm"]["scale"],
                                  np.ones(cfg.q_rank))
    bias = params["mtp"]["h"]["mlp"]["gate"]["e_score_correction_bias"]
    np.testing.assert_array_equal(bias, np.zeros(cfg.num_experts))
    # the published model: 48B; latent attention 26.35 M, the dense
    # layer 70.39 M, a whole routed layer 1,240 M
    big = JoyAIConfig.joyai_llm_flash()
    per = big.layer_params()
    assert per["mla"] == pytest.approx(26.35e6, rel=1e-3)
    assert per["dense"] == pytest.approx(70.39e6, rel=1e-3)
    assert per["routed"] == pytest.approx(1240e6, rel=1e-3)
    assert big.num_params() == pytest.approx(50.2e9, rel=2e-3)
    # the benchmark's cut: 681.4 M, 9.54 GB at 14 bytes a parameter
    cut = JoyAIConfig.joyai_llm_flash(n_layer=5, experts_held=(0, 16),
                                      vocab_size=16384)
    assert cut.layer_params()["routed"] == pytest.approx(107.09e6, rel=1e-4)
    assert cut.layer_params()["mtp"] == pytest.approx(115.49e6, rel=1e-4)
    assert cut.num_params() == pytest.approx(681.4e6, rel=1e-4)
    assert cut.num_params() * 14 == pytest.approx(9.54e9, rel=1e-3)


def test_the_model_is_exported_and_refuses_what_it_cannot_build():
    assert models.JoyAI is JoyAI and models.JoyAIConfig is JoyAIConfig
    with pytest.raises(NotImplementedError, match="mtp_depth=2"):
        JoyAIConfig.tiny(mtp_depth=2)
    with pytest.raises(ValueError, match="dense layers"):
        JoyAIConfig.tiny(dense_layers=4)


def test_the_step_reports_both_losses_and_notes_what_the_layers_are(
        monkeypatch):
    """Through ``make_train_step``: the report's five scalars ride in
    the step's metrics, and the trace's notes carry the ``mla_*`` and
    ``mtp_*`` keys beside the routed layer's and the kernel's."""
    from ray_tpu.util import tracing
    cfg = JoyAIConfig.tiny(dtype=jnp.float32)
    model = JoyAI(cfg)
    params = jax.jit(model.init_params)(jax.random.key(0))
    opt = optax.adamw(1e-3)
    state = train.init_train_state(params, opt, None)
    step = train.make_train_step(joyai_loss_fn(model, ce_chunk=32), opt)
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    step.trace(state, _batch(cfg))
    assert {k: notes[k] for k in (
        "attn_kind", "mla_ranks", "mla_qk_dims", "mla_v_dim", "mla_saved",
        "flash_path", "flash_layout", "mtp_depth", "mtp_weight",
        "dense_layers", "moe_router", "moe_expert_kind",
        "moe_experts_held")} == {
        "attn_kind": "mla", "mla_ranks": [48, 32], "mla_qk_dims": [16, 8],
        "mla_v_dim": 16, "mla_saved": "latents", "flash_path": "xla",
        "flash_layout": "concatenated", "mtp_depth": 1, "mtp_weight": 0.3,
        "dense_layers": 1, "moe_router": "sigmoid",
        "moe_expert_kind": "swiglu", "moe_experts_held": [4, 4]}
    state, metrics = step(state, _batch(cfg))
    assert {"loss", "lm_loss", "mtp_loss", "moe_held_route_share",
            "moe_absent_route_share", "moe_load_max_over_mean",
            "grad_norm"} <= set(metrics)
    assert float(metrics["loss"]) == pytest.approx(
        float(metrics["lm_loss"]) + 0.3 * float(metrics["mtp_loss"]),
        rel=1e-6)
    assert 0.0 < float(metrics["moe_held_route_share"]) < 1.0


def test_the_mtp_module_has_its_own_scopes_under_blocks_and_loss():
    """``blocks/mtp/proj``, ``blocks/mtp/h/attn`` and ``loss/mtp`` are in
    the lowered step's locations (what ``benchlib/path_trace.py`` and the
    ``attn`` / ``mlp`` readers key on), as are latent attention's seven
    scopes."""
    cfg = JoyAIConfig.tiny(dtype=jnp.float32)
    model = JoyAI(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    text = jax.jit(jax.grad(
        lambda p, b: joyai_loss_fn(model, ce_chunk=32)(p, b)[0])).lower(
            params, _batch(cfg)).as_text(debug_info=True)
    for scope in ("blocks/mtp/proj", "blocks/mtp/h/attn/core",
                  "blocks/mtp/h/mlp/shared", "loss/mtp",
                  "h_1/attn/q_down", "h_1/attn/q_up", "h_1/attn/kv_down",
                  "h_1/attn/kv_up", "h_1/attn/rope", "h_1/attn/core",
                  "h_1/attn/out_proj", "h_0/mlp", "h_1/mlp/experts"):
        assert scope in text, scope


def test_a_batch_sharded_over_dp_trains_as_one_device_does():
    cfg = JoyAIConfig.tiny(dtype=jnp.float32)
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    params = jax.jit(JoyAI(cfg).init_params)(jax.random.key(0))
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
    ("ep", "expert axis"), ("tp", "heads split over chips"),
    ("sp", "sequence split over chips")])
def test_expert_tensor_and_sequence_axes_are_refused_by_name(axis, says):
    """By the first layer that meets the mesh, latent attention
    (``ops/mla.py::mla_path``); ``routed_ffn`` refuses ep and tp too."""
    mesh = make_mesh({axis: 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match=f"{axis}=2") as err:
        jax.eval_shape(JoyAI(JoyAIConfig.tiny(), mesh=mesh).init_params,
                       jax.random.key(0))
    assert says in str(err.value)
