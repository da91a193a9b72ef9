"""ZAYA1 (``models/zaya.py``): the system against the benchmark's plain
reference on seeded random weights, the share of the experts against
the uncut layer, the router state that runs from layer to layer, and
``ops/moe.py``'s two public forms against each other, which pins the
three older models' routed layer through the split."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu import train
from ray_tpu.models import Zaya, ZayaConfig
from ray_tpu.models.zaya import MoE, zaya_loss_fn
from ray_tpu.ops import moe

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmark"))
from benchlib import manifest as mf  # noqa: E402

F32 = dict(dtype=jnp.float32)


def _spec(cfg, **kw):
    return {**mf.load_builder("zaya").reference_spec(cfg), **kw}


def _jittered(params, seed):
    """Every leaf moved off its initial value, so that the scales, the
    biases, ``gamma``, the temperature and ``b`` all say something."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return tree.unflatten([
        x + 0.1 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


def _batch(seed, cfg, rows=2):
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, cfg.seq_len), dtype=np.int32)
    return {"tokens": jnp.asarray(toks),
            "targets": jnp.asarray(np.roll(toks, -1, 1))}


def _leaves_with_names(tree):
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in
            jax.tree_util.tree_leaves_with_path(tree)]


# -- the system against the plain reference -----------------------------------

@pytest.mark.parametrize("seed, held", [(0, (4, 4)), (1, (0, 4)),
                                        (2, None)],
                         ids=["upper_half", "lower_half", "all_held"])
def test_loss_every_gradient_leaf_and_the_routes_are_the_references(
        seed, held):
    cfg = ZayaConfig.tiny(experts_held=held, **F32)
    model = Zaya(cfg)
    params = _jittered(model.init_params(jax.random.key(seed)), seed)
    batch = _batch(seed, cfg)
    ref = mf.load_reference("zaya")
    with jax.default_matmul_precision("highest"):
        (loss, report), grads = jax.jit(jax.value_and_grad(
            zaya_loss_fn(model, ce_chunk=32), has_aux=True))(params, batch)
        logits = jax.jit(model.apply)({"params": params}, batch["tokens"])
    want, want_grads = ref.loss_and_grads(params, batch, _spec(cfg))
    want_logits, _, loads = ref.forward(params, batch["tokens"], _spec(cfg))
    assert float(loss) == pytest.approx(want["loss"], rel=1e-5)
    assert float(optax.global_norm(grads)) == pytest.approx(
        want["grad_norm"], rel=1e-4)
    np.testing.assert_allclose(logits, want_logits, atol=2e-5)
    np.testing.assert_array_equal(report["moe_load"], loads)
    assert float(report["moe_absent_route_share"]) == pytest.approx(
        want["moe_absent_route_share"], abs=1e-6)
    assert loads.shape == (cfg.n_layer, cfg.num_experts)
    assert float(loads.sum()) == cfg.n_layer * 2 * cfg.seq_len      # top-1
    want_leaves = dict(_leaves_with_names(want_grads))
    for name, got in _leaves_with_names(grads):
        scale = max(float(np.abs(want_leaves[name]).max()), 1e-3)
        np.testing.assert_allclose(got, want_leaves[name],
                                   atol=2e-4 * scale, err_msg=name)
    assert len(want_leaves) == len(jax.tree_util.tree_leaves(grads))
    # the balancing bias moves the choice and takes no gradient
    for i in range(cfg.n_layer):
        assert not np.any(grads[f"h_{i}"]["mlp"]["router"]["balance_bias"])


def test_parameters_are_the_configs_count_and_the_published_models():
    cfg = ZayaConfig.tiny()
    params = jax.eval_shape(Zaya(cfg).init_params, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) \
        == cfg.num_params()
    whole = ZayaConfig.zaya1_8b()
    per = whole.layer_params()
    assert per["experts"] == pytest.approx(201.3e6, rel=1e-3)
    assert sum(per.values()) - per["experts"] == pytest.approx(6.25e6,
                                                               rel=3e-3)
    assert whole.vocab_size * whole.n_embd == pytest.approx(537e6, rel=1e-3)
    cut = ZayaConfig.zaya1_8b(n_layer=5, experts_held=(0, 8),
                              vocab_size=32896)
    assert cut.num_params() == pytest.approx(602.0e6, rel=1e-4)
    with pytest.raises(ValueError, match="key/value heads"):
        ZayaConfig.tiny(n_kv_head=1)


# -- the share of the experts ---------------------------------------------------

def test_two_shares_of_eight_add_up_to_the_uncut_references_layer():
    """The guide's share test: the routed layer under ``experts_held =
    (0, 8)`` and ``(8, 8)``, each given its own experts' weights, adds up
    to what the reference gives for the whole layer of 16 (there is no
    shared expert to count once)."""
    cfg = ZayaConfig.tiny(num_experts=16, experts_held=None, **F32)
    h = jax.random.normal(jax.random.key(0), (2, cfg.seq_len, cfg.n_embd))
    state = 0.3 * jax.random.normal(jax.random.key(1),
                                    (2, cfg.seq_len, cfg.router_width))
    layer = MoE(cfg)
    params = _jittered(layer.init(jax.random.key(2), h, state)["params"], 3)
    # a router that spreads its routes: the last layer's weights at 0.02
    # leave its bias to choose for every token
    params["router"]["fc3"]["kernel"] = 3.0 * jax.random.normal(
        jax.random.key(4), params["router"]["fc3"]["kernel"].shape)

    def share(first, count):
        held = ZayaConfig.tiny(num_experts=16, experts_held=(first, count),
                               **F32)
        own = {**params, "experts": jax.tree_util.tree_map(
            lambda w: w[first:first + count], params["experts"])}
        (y, new_state), sown = MoE(held).apply(
            {"params": own}, h, state, mutable=["moe"])
        return y, new_state, sown["moe"]["load"][0]

    with jax.default_matmul_precision("highest"):
        lower, s0, load0 = share(0, 8)
        upper, s1, load1 = share(8, 8)
    ref = mf.load_reference("zaya")
    want, want_state, want_load = ref._moe(
        params, h, state, _spec(cfg), lambda x: x)
    np.testing.assert_allclose(lower + upper, want, atol=1e-5)
    np.testing.assert_allclose(s0, want_state, atol=1e-5)
    np.testing.assert_array_equal(s0, s1)       # every chip routes alike
    np.testing.assert_array_equal(load0, want_load)
    np.testing.assert_array_equal(load0, load1)
    assert float(jnp.abs(lower).max()) > 0 and float(jnp.abs(upper).max()) > 0
    # a token's one route lands in one share: the other adds nothing there
    assert not np.any(np.abs(lower).sum(-1) * np.abs(upper).sum(-1))


# -- the router state ---------------------------------------------------------------

def test_the_router_state_of_a_layer_depends_on_the_layer_befores():
    cfg = ZayaConfig.tiny(**F32)
    model = Zaya(cfg)
    params = model.init_params(jax.random.key(0))
    tokens = _batch(0, cfg)["tokens"]
    ref = mf.load_reference("zaya")

    def states(p):
        return ref.forward(p, tokens, _spec(cfg))[1]

    def with_router(p, layer, **leaves):
        router = {**p[layer]["mlp"]["router"], **leaves}
        return {**p, layer: {**p[layer], "mlp": {
            **p[layer]["mlp"], "router": router}}}

    base = states(params)
    bias = params["h_0"]["mlp"]["router"]["down"]["bias"] + 1.0
    moved = states(with_router(params, "h_0", down={
        **params["h_0"]["mlp"]["router"]["down"], "bias": bias}))
    # layer 0's own state moves by the bias; layer 1's by gamma times it;
    # layer 2's by gamma^2 (exponential depth averaging at gamma = 0.5),
    # each but for what the changed routes do to the stream they read
    np.testing.assert_allclose(moved[0] - base[0], 1.0, atol=1e-5)
    np.testing.assert_allclose(moved[1] - base[1], 0.5, atol=0.03)
    np.testing.assert_allclose(moved[2] - base[2], 0.25, atol=0.03)
    # with layer 1's gamma at zero the chain is cut there
    zero = jnp.zeros_like(params["h_1"]["mlp"]["router"]["gamma"])
    cut = with_router(params, "h_1", gamma=zero)
    cut_moved = with_router(cut, "h_0", down={
        **params["h_0"]["mlp"]["router"]["down"], "bias": bias})
    np.testing.assert_allclose(states(cut_moved)[1], states(cut)[1],
                               atol=0.03)
    # the layer alone, its input held still: the state that goes on is
    # the projection plus gamma times the state that came in, exactly
    h = jax.random.normal(jax.random.key(3), (2, cfg.seq_len, cfg.n_embd))
    came = jax.random.normal(jax.random.key(4),
                             (2, cfg.seq_len, cfg.router_width))
    p1 = params["h_1"]["mlp"]
    (_, a), _ = MoE(cfg).apply({"params": p1}, h, came, mutable=["moe"])
    (_, b), _ = MoE(cfg).apply({"params": p1}, h, 0 * came, mutable=["moe"])
    np.testing.assert_allclose(a - b, p1["router"]["gamma"] * came,
                               atol=1e-5)
    # and the program's loss feels the first layer's gamma only through
    # the later layers' routes: its gradient is there, layer 0's is zero
    grads = jax.jit(jax.grad(lambda p: zaya_loss_fn(model, ce_chunk=32)(
        p, _batch(0, cfg))[0]))(params)
    assert not np.any(grads["h_0"]["mlp"]["router"]["gamma"])   # r_{-1} = 0
    assert np.any(grads["h_1"]["mlp"]["router"]["gamma"])
    assert np.any(grads["h_0"]["mlp"]["router"]["down"]["kernel"])


# -- ops/moe.py: routes in, against the matrix forms ----------------------------

T_, D_, E_, F_ = 48, 32, 8, 24


def _layer(seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(ks[0], (2, T_, D_)),
            0.5 * jax.random.normal(ks[1], (D_, E_)),
            0.2 * jax.random.normal(ks[2], (E_, D_, F_)),
            0.2 * jax.random.normal(ks[3], (E_, D_, F_)),
            0.2 * jax.random.normal(ks[4], (E_, F_, D_)),
            0.3 * jax.random.normal(ks[5], (E_,)))


@pytest.mark.parametrize("router, top_k, held, expert", [
    ("softmax", 2, None, "swiglu"), ("softmax", 1, (2, 4), "swiglu"),
    ("sigmoid", 3, (4, 4), "swiglu"), ("sigmoid", 2, None, "relu2"),
    ("sigmoid", 1, (0, 2), "relu2")],
    ids=["olmoe_form", "softmax_top1_held", "joyai_form", "relu2_all_held",
         "nemotron_form_top1"])
def test_routed_experts_given_routes_is_routed_ffn_given_the_matrix(
        router, top_k, held, expert):
    """The split moved code, not work: the routes that ``_route`` or
    ``_route_sigmoid`` make from the matrix, handed to
    ``routed_experts``, give ``routed_ffn``'s output, load and
    gradients (to the tokens, the experts, and through the weights to
    the router's matrix)."""
    x, rw, wg, wu, wd, bias = _layer()
    first, count = held or (0, E_)
    own = [w[first:first + count] for w in (wg, wu, wd)]
    if expert == "relu2":
        own[0] = None
    kw = dict(top_k=top_k, norm_topk_prob=True)
    if router == "sigmoid":
        kw.update(select_bias=bias, route_scale=2.5)

    def matrix(x, rw, *ws):
        y, _, _, load = moe.routed_ffn(
            x, rw, *ws, router=router, expert=expert, experts_held=held,
            **kw)
        return (y * y).sum(), (y, load)

    def given(x, rw, *ws):
        flat = x.reshape(-1, D_)
        if router == "sigmoid":
            weights, experts, *_ = moe._route_sigmoid(
                flat, rw, bias, top_k, True, 2.5)
        else:
            weights, experts, *_ = moe._route(flat, rw, top_k, True)
        y, load = moe.routed_experts(
            x, weights.reshape(2, T_, top_k), experts.reshape(2, T_, top_k),
            *ws, num_experts=E_, experts_held=held)
        return (y * y).sum(), (y, load)

    args = (x, rw, *own)
    diff = tuple(i for i, a in enumerate(args) if a is not None)
    (la, (ya, load_a)), ga = jax.jit(jax.value_and_grad(
        matrix, argnums=diff, has_aux=True))(*args)
    (lb, (yb, load_b)), gb = jax.jit(jax.value_and_grad(
        given, argnums=diff, has_aux=True))(*args)
    np.testing.assert_allclose(ya, yb, atol=1e-6)
    np.testing.assert_array_equal(load_a, load_b)
    assert float(load_a.sum()) == 2 * T_ * top_k
    for a, b in zip(ga, gb):
        np.testing.assert_allclose(a, b, atol=1e-5)
    assert np.any(ga[1])        # the router's matrix has a gradient


def test_top_1_through_the_dropless_path_drops_nothing_at_any_skew():
    """Every token on one held expert: ``held_rows`` (twice the even
    share) is a half of the routes, so a second slab runs, and the
    output is still that expert on every token times its weight."""
    _, _, wg, wu, wd, _ = _layer()
    rows = 1024
    tokens = 2 * rows
    x = jax.random.normal(jax.random.key(8), (2, rows, D_))
    experts = jnp.full((2, rows, 1), 3, jnp.int32)
    weights = jax.random.uniform(jax.random.key(9), (2, rows, 1)) + 0.5
    held = (2, 2)
    assert moe.held_rows(tokens, 2, E_) == tokens // 2      # two slabs
    with jax.default_matmul_precision("highest"):
        y, load = moe.routed_experts(
            x, weights, experts, wg[2:4], wu[2:4], wd[2:4], num_experts=E_,
            experts_held=held)
        want = (jax.nn.silu(x @ wg[3]) * (x @ wu[3])) @ wd[3] * weights
    np.testing.assert_allclose(y, want, atol=1e-5)
    assert float(load[3]) == tokens and float(load.sum()) == tokens
    # and on an absent expert: nothing is computed, nothing is dropped
    y, load = moe.routed_experts(
        x, weights, jnp.full((2, rows, 1), 7, jnp.int32), wg[2:4], wu[2:4],
        wd[2:4], num_experts=E_, experts_held=held)
    assert not np.any(y) and float(load[7]) == tokens


def test_routed_experts_checks_its_share_and_notes_the_callers_router():
    from ray_tpu.util import tracing
    x, _, wg, wu, wd, _ = _layer()
    routes = (jnp.ones((2, T_, 1)), jnp.zeros((2, T_, 1), jnp.int32))
    with pytest.raises(ValueError, match="experts_held"):
        moe.routed_experts(x, *routes, wg[:3], wu[:3], wd[:3],
                           num_experts=E_, experts_held=(0, 4))
    notes = {}
    was = tracing.note_trace
    tracing.note_trace = notes.update
    try:
        moe.routed_experts(x, *routes, wg[:4], wu[:4], wd[:4],
                           num_experts=E_, experts_held=(0, 4))
    finally:
        tracing.note_trace = was
    assert notes["moe_router"] == "caller" and notes["moe_top_k"] == 1
    assert notes["moe_experts_held"] == [0, 4]
    assert notes["moe_expert_kind"] == "swiglu"


# -- the train path -------------------------------------------------------------

def test_a_train_step_runs_and_reports_the_load_of_every_layer():
    cfg = ZayaConfig.tiny(**F32)    # the CPU has no bf16 x bf16 = f32 dot
    model = Zaya(cfg)
    opt = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-3))
    state = train.init_train_state(
        jax.jit(model.init_params)(jax.random.key(0)), opt, None)
    step = train.make_train_step(zaya_loss_fn(model, ce_chunk=32), opt)
    losses = []
    for i in range(3):
        state, metrics = step(state, _batch(i, cfg))
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert losses[0] == pytest.approx(np.log(cfg.vocab_size), abs=0.5)
    assert metrics["moe_load"].shape == (cfg.n_layer, cfg.num_experts)
    assert float(metrics["moe_held_route_share"]
                 + metrics["moe_absent_route_share"]) == pytest.approx(1.0)
    assert float(metrics["moe_load_max_over_mean"]) >= 1.0
    assert float(metrics["lm_loss"]) == float(metrics["loss"])


def test_the_table_is_tied_and_takes_both_gradients():
    """No ``lm_head``: the logits are the final hidden states against
    the embedding, and its gradient is the lookup's plus the head's."""
    cfg = ZayaConfig.tiny(**F32)
    model = Zaya(cfg)
    params = model.init_params(jax.random.key(0))
    assert "lm_head" not in params
    batch = _batch(0, cfg)
    grads = jax.jit(jax.grad(lambda p: zaya_loss_fn(model, ce_chunk=32)(
        p, batch)[0]))(params)
    unseen = np.setdiff1d(np.arange(cfg.vocab_size),
                          np.asarray(batch["tokens"]))
    assert unseen.size      # rows no token looked up still get the head's
    assert np.all(np.abs(grads["wte"]["embedding"][unseen]).sum(-1) > 0)


def test_a_mesh_over_the_batch_gives_the_one_device_loss_and_sp_is_refused():
    from ray_tpu.parallel import make_mesh
    cfg = ZayaConfig.tiny(**F32)
    params = Zaya(cfg).init_params(jax.random.key(0))
    batch = _batch(0, cfg, rows=4)
    one, _ = zaya_loss_fn(Zaya(cfg), ce_chunk=32)(params, batch)
    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    with mesh:
        many, report = jax.jit(zaya_loss_fn(Zaya(cfg, mesh=mesh),
                                            ce_chunk=32))(params, batch)
    assert float(many) == pytest.approx(float(one), rel=1e-5)
    assert float(report["moe_load"].sum()) == cfg.n_layer * 4 * cfg.seq_len
    sp = make_mesh({"sp": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="halo"):
        Zaya(cfg, mesh=sp).init_params(jax.random.key(0))
