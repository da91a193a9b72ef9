"""OLMoE through ``models/llama.py``: the dropless top-k routed layer
(``ops/moe.py::routed_ffn``), QK-norm, half-split RoPE and the loss
function's report, against the benchmark's plain float32 reference
(``benchmark/references/olmoe.py``: every expert on every token, no
sort, no groups), on the CPU at the tiny preset."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu import train
from ray_tpu.models.llama import (
    Llama, LlamaConfig, apply_rope_half, llama_loss_fn, rope_freqs,
)
from ray_tpu.ops.moe import routed_ffn
from ray_tpu.parallel import make_mesh

SCALARS = ("loss", "lm_loss", "moe_aux_loss", "moe_z_loss",
           "moe_load_max_over_mean")


BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
with open(os.path.join(BENCHMARK, "configs", "olmoe-1b-7b.json")) as _f:
    RTOL = json.load(_f)["reference"]["rtol"]    # the limit in use


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(BENCHMARK, "references", "olmoe.py")
    spec = importlib.util.spec_from_file_location("reference_olmoe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _spec(cfg) -> dict:
    return {k: getattr(cfg, k) for k in (
        "n_layer", "n_head", "top_k", "norm_topk_prob", "rms_eps",
        "rope_theta", "aux_loss_coef", "z_loss_coef")}


def _batch(cfg, rows=4, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, cfg.seq_len), dtype=np.int32)
    return {"tokens": jnp.asarray(toks),
            "targets": jnp.asarray(np.roll(toks, -1, 1))}


def _program(cfg, params, batch):
    """(the step's scalars, the gradient tree) of the program."""
    model = Llama(cfg)
    (loss, report), grads = jax.jit(jax.value_and_grad(
        llama_loss_fn(model, ce_chunk=64), has_aux=True))(params, batch)
    return {"loss": loss, **report,
            "grad_norm": optax.global_norm(grads)}, grads


@pytest.mark.parametrize("overrides", [
    {}, {"top_k": 1}, {"norm_topk_prob": True}],
    ids=["top2", "top1", "norm_topk_prob"])
def test_tiny_olmoe_in_float32_is_the_reference(ref, overrides):
    """Loss, the four reported scalars, the gradient norm and every
    gradient leaf to 1e-5: the sort, the grouped matmuls, the un-sort
    and the hand-written backward gathers compute what "every expert
    on every token, times the top-k weight or zero" computes."""
    cfg = LlamaConfig.tiny_olmoe(dtype=jnp.float32, **overrides)
    params = jax.jit(Llama(cfg).init_params)(jax.random.key(1))
    batch = _batch(cfg)
    with jax.default_matmul_precision("highest"):
        got, grads = _program(cfg, params, batch)
    want, want_grads = ref.loss_and_grads(params, batch, _spec(cfg))
    for k in (*SCALARS, "grad_norm"):
        assert float(got[k]) == pytest.approx(want[k], rel=1e-5), k
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.abs(w).max())
        assert float(jnp.abs(g - w).max()) <= 1e-5 * scale, \
            jax.tree_util.keystr(path)


def test_tiny_olmoe_in_bfloat16_is_near_the_reference(ref):
    """The configuration's types (bf16 compute, f32 parameters) against
    the float32 reference. The tolerance is the configuration's own
    ``reference.rtol`` (2^-7, as for the other configurations): one
    bf16 rounding is 2^-9 relative, and a loss or a norm sums many of
    them with mixed signs; a wrong formula (weights renormalised, the
    z-loss left out, a route dropped) moves these numbers by far more
    (see the next tests). The load is a count: bf16 activations into
    the float32 router flip a few second-against-third choices, and
    at this size the largest expert has 64 routes on average, so one
    flip is 2^-6 of it (at the published size one is 1/2,048): three
    flips are allowed here."""
    cfg = LlamaConfig.tiny_olmoe()
    params = jax.jit(Llama(cfg).init_params)(jax.random.key(2))
    batch = _batch(cfg, seed=2)
    got, _ = _program(cfg, params, batch)
    want = ref.loss_and_grad_norm(params, batch, _spec(cfg))
    for k in (*SCALARS[:-1], "grad_norm"):
        assert float(got[k]) == pytest.approx(want[k], rel=RTOL), k
    assert float(got[SCALARS[-1]]) == pytest.approx(
        want[SCALARS[-1]], abs=3 / 64)


def test_what_the_six_keys_can_tell(ref):
    """What the comparison is for, at the limit the configuration
    states (``reference.rtol``). Renormalised top-k weights move the
    gradient norm outside it once the experts weigh in the residual
    stream as they do at the published widths (there the routed
    output's scale is the embedding's; at the tiny widths it is a
    tenth of it, so the experts are scaled up here). A z-loss left out
    of the total moves ``loss`` by 0.001 z, a thousandth of it and
    inside the limit — which is why the reference returns
    ``moe_z_loss`` itself and the step has to report it."""
    cfg = LlamaConfig.tiny_olmoe(dtype=jnp.float32)
    params = jax.jit(Llama(cfg).init_params)(jax.random.key(3))
    for i in range(cfg.n_layer):
        ex = params[f"h_{i}"]["mlp"]["experts"]
        params[f"h_{i}"]["mlp"]["experts"] = jax.tree_util.tree_map(
            lambda w: w * 6.0, ex)
    batch = _batch(cfg, seed=3)
    want = ref.loss_and_grad_norm(params, batch, _spec(cfg))

    def off(key, **wrong):
        got, _ = _program(LlamaConfig.tiny_olmoe(
            dtype=jnp.float32, **wrong), params, batch)
        return abs(float(got[key]) - want[key]) / abs(want[key])

    assert off("grad_norm") < 1e-5
    assert off("grad_norm", norm_topk_prob=True) > RTOL
    assert 1e-4 < off("loss", z_loss_coef=0.0) < RTOL
    assert want["moe_z_loss"] > 4.0      # (ln 8)^2 and more: never absent


def _layer_inputs(t=96, d=32, e=8, f=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(ks[0], (4, t // 4, d), dtype),
            jax.random.normal(ks[1], (d, e)) * 0.5,
            jax.random.normal(ks[2], (e, d, f)) * 0.2,
            jax.random.normal(ks[3], (e, d, f)) * 0.2,
            jax.random.normal(ks[4], (e, f, d)) * 0.2)


def _plain_layer(ref, x, rw, wg, wu, wd, top_k):
    probs = jax.nn.softmax(x @ rw, axis=-1)
    top, chosen = jax.lax.top_k(probs, top_k)
    mix = (jax.nn.one_hot(chosen, rw.shape[-1]) * top[..., None]).sum(-2)
    return ref._experts(x, mix, wg, wu, wd)


def test_a_skewed_router_loses_no_route(ref):
    """Dropless: a router biased so that one expert is in every token's
    top-2 (half of all routes, four times an even share) computes
    every route; the output is the reference's."""
    x, rw, wg, wu, wd = _layer_inputs()
    x = x + 1.0                      # a common component to route on
    rw = rw.at[:, 3].set(0.5)        # ... which expert 3 answers to
    with jax.default_matmul_precision("highest"):
        y, aux, z, load = jax.jit(
            lambda *a: routed_ffn(*a, top_k=2))(x, rw, wg, wu, wd)
        want = _plain_layer(ref, x, rw, wg, wu, wd, 2)
    assert int(load.sum()) == 96 * 2
    assert int(load[3]) == 96 and float(load.max() / load.mean()) == 4.0
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    assert float(aux) > 2.0          # an even load gives top_k = 2


def _loss_of_layer(mesh):
    def f(x, rw, wg, wu, wd):
        y, aux, z, load = routed_ffn(x, rw, wg, wu, wd, top_k=2, mesh=mesh)
        return (y * y).sum() + aux + 0.1 * z, (y, aux, z, load)
    return jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4), has_aux=True)


@pytest.mark.parametrize("axes,held", [
    ({"dp": 4}, P("dp")), ({"dp": 2, "sp": 2}, P("dp", "sp"))],
    ids=["dp4", "dp2-sp2"])
def test_on_a_mesh_each_chip_sorts_its_own_tokens(axes, held):
    """The layer under shard_map equals the layer with no mesh (output,
    both losses, the load, every gradient), and the compiled program
    gathers no tokens: a sort over a dimension sharded over dp (or sp)
    would gather the global batch to every chip (PR 26's trap)."""
    args = _layer_inputs(t=128)
    mesh = make_mesh(axes, devices=jax.devices()[:4])
    placed = (jax.device_put(args[0], NamedSharding(mesh, held)),
              *(jax.device_put(a, NamedSharding(mesh, P()))
                for a in args[1:]))
    sharded = jax.jit(_loss_of_layer(mesh))
    with jax.default_matmul_precision("highest"):
        (l1, out1), g1 = sharded(*placed)
        (l0, out0), g0 = jax.jit(_loss_of_layer(None))(*args)
    assert float(l1) == pytest.approx(float(l0), rel=1e-5)
    for a, b in zip((*out1, *g1), (*out0, *g0)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    assert len(out1[0].sharding.device_set) == 4
    text = sharded.lower(*placed).compile().as_text()
    assert "all-gather" not in text and "all-to-all" not in text
    assert "all-reduce" in text      # the statistics and the gradients


@pytest.mark.parametrize("axes", [{"dp": 2, "ep": 2}, {"dp": 2, "tp": 2}],
                         ids=["ep2", "tp2"])
def test_a_mesh_that_shards_the_experts_is_refused_by_name(axes):
    """The layer replicates its experts: under ep or tp the shard_map
    would gather every expert onto every chip each step, silently."""
    mesh = make_mesh(axes, devices=jax.devices()[:4])
    (axis, n), = [(a, n) for a, n in axes.items() if a != "dp"]
    with pytest.raises(NotImplementedError, match=f"{axis}={n}"):
        routed_ffn(*_layer_inputs(), top_k=2, mesh=mesh)


def test_rotate_half_rope_is_the_hand_written_rotation():
    """Element i pairs with i + D/2 and the pair turns by position x
    theta^(-2i/D): written out with numpy for one head."""
    d, t, theta = 8, 5, 10000.0
    x = np.random.default_rng(0).normal(size=(1, t, 1, d)).astype(np.float32)
    got = np.asarray(apply_rope_half(jnp.asarray(x),
                                     rope_freqs(d, t, theta)))
    for pos in range(t):
        for i in range(d // 2):
            a = pos * theta ** (-2 * i / d)
            x1, x2 = x[0, pos, 0, i], x[0, pos, 0, i + d // 2]
            assert got[0, pos, 0, i] == pytest.approx(
                x1 * np.cos(a) - x2 * np.sin(a), abs=1e-5)
            assert got[0, pos, 0, i + d // 2] == pytest.approx(
                x2 * np.cos(a) + x1 * np.sin(a), abs=1e-5)


def test_the_step_reports_what_the_loss_function_reports():
    """``make_train_step`` puts a ``(loss, report)`` loss function's
    scalars beside the loss; the total is LM + 0.01 aux + 0.001 z."""
    cfg = LlamaConfig.tiny_olmoe()
    model = Llama(cfg)
    opt = optax.adamw(1e-3)
    state = train.init_train_state(
        jax.jit(model.init_params)(jax.random.key(0)), opt)
    step = train.make_train_step(llama_loss_fn(model, ce_chunk=64), opt)
    state, m = step(state, _batch(cfg))
    assert set(m) == {"loss", "grad_norm", "lm_loss", "moe_aux_loss",
                      "moe_z_loss", "moe_load_max_over_mean"}
    assert float(m["loss"]) == pytest.approx(
        float(m["lm_loss"]) + 0.01 * float(m["moe_aux_loss"])
        + 0.001 * float(m["moe_z_loss"]), rel=1e-5)
    assert float(m["moe_load_max_over_mean"]) >= 1.0


def test_a_scalar_loss_lowers_to_the_text_it_lowered_to_before():
    """The step of a loss function that returns a scalar is the program
    it was before a loss function could return a report: the same
    lowered text as the old body, written out here."""
    from ray_tpu.models import GPT2, GPT2Config
    from ray_tpu.models.gpt2 import gpt2_loss_fn
    from ray_tpu.train.step import TrainState

    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    opt = optax.adamw(3e-4)
    loss_fn = gpt2_loss_fn(model, ce_chunk=64)
    state = train.init_train_state(
        jax.jit(model.init_params)(jax.random.key(0)), opt)
    batch = _batch(cfg)

    def step(state, batch):          # train/step.py before PR 27
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        metrics = {"loss": loss}
        with jax.named_scope("optimizer"):
            updates, new_opt = opt.update(grads, state.opt_state,
                                          state.params)
            new_params = optax.apply_updates(state.params, updates)
            metrics["grad_norm"] = optax.global_norm(grads)
            new_state = TrainState(step=state.step + 1, params=new_params,
                                   opt_state=new_opt, extra=state.extra)
        return new_state, metrics

    old = jax.jit(step, donate_argnums=(0,)).lower(state, batch).as_text()
    new = train.make_train_step(loss_fn, opt).lower(state, batch).as_text()
    assert new == old


def test_the_routed_layer_notes_its_path_at_trace_time(monkeypatch):
    """What the step's ``train.compile`` trace span will carry (the
    step's listener hands the notes over when the trace ends, so they
    are caught here on their way in)."""
    from ray_tpu.util import tracing
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    jax.jit(lambda *a: routed_ffn(*a, top_k=2)[0]).trace(*_layer_inputs())
    assert notes == {"moe_tokens": 96, "moe_experts": 8, "moe_top_k": 2,
                     "moe_routes": 192, "moe_path": "ragged_dot",
                     "moe_axes": [], "moe_router_path": "xla"}


def test_sharding_patterns_name_the_routed_parameters():
    from ray_tpu.parallel.sharding import shard_params
    cfg = LlamaConfig.tiny_olmoe()
    shapes = jax.eval_shape(Llama(cfg).init_params, jax.random.key(0))
    mesh = make_mesh({"fsdp": 2, "tp": 2}, devices=jax.devices()[:4])
    sh = shard_params(shapes, mesh)["h_0"]
    assert sh["mlp"]["experts"]["gate_proj"].spec == P(None, "fsdp", "tp")
    assert sh["mlp"]["experts"]["down_proj"].spec == P(None, "tp", "fsdp")
    assert sh["mlp"]["gate"]["kernel"].spec == P("fsdp")
    assert sh["attn"]["q_norm"]["scale"].spec == P()
