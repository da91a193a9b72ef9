"""SmallThinker (``models/smallthinker.py``): the system against the
benchmark's plain reference on seeded random weights, the layout over
the layers (which see a window, which rotate), the router that reads
the block's input, ReGLU through ``ops/moe.py``, and the shares of the
experts against the uncut layer."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu import train
from ray_tpu.models import SmallThinker, SmallThinkerConfig
from ray_tpu.models.smallthinker import (
    Attention,
    Experts,
    Router,
    smallthinker_loss_fn,
)
from ray_tpu.ops import moe
from ray_tpu.ops.attention import causal_attention
from ray_tpu.util import tracing

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmark"))
from benchlib import manifest as mf  # noqa: E402

F32 = dict(dtype=jnp.float32)


def _spec(cfg, **kw):
    return {**mf.load_builder("smallthinker").reference_spec(cfg), **kw}


def _jittered(params, seed, by=0.1):
    """Every leaf moved off its initial value, so that the norms'
    scales say something and the routers spread their routes."""
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

@pytest.mark.parametrize("seed, held", [(0, (4, 4)), (1, (0, 4)),
                                        (2, None)],
                         ids=["upper_half", "lower_half", "all_held"])
def test_loss_every_gradient_leaf_and_the_routes_are_the_references(
        seed, held):
    cfg = SmallThinkerConfig.tiny(experts_held=held, **F32)
    model = SmallThinker(cfg)
    params = _jittered(model.init_params(jax.random.key(seed)), seed)
    batch = _batch(seed, cfg)
    ref = mf.load_reference("smallthinker")
    with jax.default_matmul_precision("highest"):
        (loss, report), grads = jax.jit(jax.value_and_grad(
            smallthinker_loss_fn(model, ce_chunk=32), has_aux=True))(
                params, batch)
        logits = jax.jit(model.apply)({"params": params}, batch["tokens"])
    want, want_grads, per_layer = ref.loss_and_grads(params, batch,
                                                     _spec(cfg))
    want_logits, (want_w, want_e), loads = ref.forward(
        params, batch["tokens"], _spec(cfg))
    np.testing.assert_array_equal(per_layer, loads)
    assert float(loss) == pytest.approx(want["loss"], rel=1e-5)
    assert float(optax.global_norm(grads)) == pytest.approx(
        want["grad_norm"], rel=1e-4)
    np.testing.assert_allclose(logits, want_logits, atol=5e-5)
    np.testing.assert_array_equal(report["moe_load"], loads)
    assert float(report["moe_absent_route_share"]) == pytest.approx(
        want["moe_absent_route_share"], abs=1e-6)
    assert float(report["attn_window_out_rms"]) == pytest.approx(
        want["attn_window_out_rms"], rel=1e-5)
    assert loads.shape == (cfg.n_layer, cfg.num_experts)
    assert float(loads.sum()) == cfg.n_layer * 2 * cfg.seq_len * cfg.top_k
    assert want_w.shape == (cfg.n_layer, 2, cfg.seq_len, cfg.top_k)
    np.testing.assert_allclose(want_w.sum(-1), 1.0, atol=1e-6)
    want_leaves = dict(_leaves_with_names(want_grads))
    for name, got in _leaves_with_names(grads):
        scale = max(float(np.abs(want_leaves[name]).max()), 1e-3)
        np.testing.assert_allclose(got, want_leaves[name],
                                   atol=2e-4 * scale, err_msg=name)
    assert len(want_leaves) == len(jax.tree_util.tree_leaves(grads))
    assert want_e.shape == want_w.shape


def test_parameters_are_the_configs_count_and_the_published_models():
    cfg = SmallThinkerConfig.tiny()
    params = jax.eval_shape(SmallThinker(cfg).init_params, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) \
        == cfg.num_params()
    assert "lm_head" in params          # untied
    whole = SmallThinkerConfig.smallthinker_21b_a3b()
    per = whole.layer_params()
    assert per["attn"] == pytest.approx(20.97e6, rel=1e-3)
    assert per["experts"] == pytest.approx(377.5e6, rel=1e-3)
    assert sum(per.values()) == pytest.approx(398.6e6, rel=1e-3)
    assert whole.num_params() == pytest.approx(21.5e9, rel=2e-3)
    at_work = 52 * (sum(per.values()) - 58 * 3 * 2560 * 768)
    assert at_work == pytest.approx(3.0e9, rel=0.03)        # "21B-A3B"
    assert whole.layer_kinds == "gWWW" * 13
    cut = SmallThinkerConfig.smallthinker_21b_a3b(
        n_layer=4, experts_held=(0, 16), vocab_size=19072)
    assert cut.num_params() == pytest.approx(559.7e6, rel=1e-4)
    assert cut.num_params() * 14 == pytest.approx(7.84e9, rel=1e-3)
    with pytest.raises(ValueError, match="key/value heads"):
        SmallThinkerConfig.tiny(n_head=3)


# -- the layout over the layers ----

def _attention_rows(cfg, windowed, rotate):
    """d(sum of output row t) / d(input rows): which rows of the
    layer's input a row of its attention reads, [t, rows] bool."""
    from ray_tpu.models.llama import rope_freqs
    layer = Attention(cfg, windowed)
    h = jax.random.normal(jax.random.key(0), (1, cfg.seq_len, cfg.n_embd))
    angles = (rope_freqs(cfg.head_dim, cfg.seq_len, cfg.rope_theta)
              if rotate else None)
    fns = SmallThinker(cfg)._attn_fns()
    params = layer.init(jax.random.key(1), h, fns[windowed], angles)

    def out(h):
        return layer.apply(params, h, fns[windowed], angles)[0].sum(-1)
    return np.abs(np.asarray(jax.jacobian(out)(h))[:, 0]).sum(-1) > 0


def test_a_query_sees_exactly_window_keys_in_a_windowed_layer():
    cfg = SmallThinkerConfig.tiny(**F32)        # 64 rows, a window of 24
    reads = _attention_rows(cfg, windowed=True, rotate=True)
    t = np.arange(cfg.seq_len)
    want = ((t[None, :] <= t[:, None])
            & (t[None, :] > t[:, None] - cfg.window))
    np.testing.assert_array_equal(reads, want)
    assert reads[-1].sum() == cfg.window == 24
    assert reads[10].sum() == 11                # the row's start cuts it
    whole = _attention_rows(cfg, windowed=False, rotate=False)
    np.testing.assert_array_equal(whole, t[None, :] <= t[:, None])


def test_the_report_has_the_rms_of_what_a_whole_window_hands_on():
    """``attn_window_out_rms``: sown by the windowed layers alone, over
    the rows that see ``window`` keys; it moves with the window, and a
    stack with no windowed layer reports 0 (the reference holds the
    value itself, in the test of every gradient leaf)."""
    cfg = SmallThinkerConfig.tiny(**F32)
    model = SmallThinker(cfg)
    params = _jittered(model.init_params(jax.random.key(2)), 2)
    batch = _batch(2, cfg)
    _, sown = model.apply({"params": params}, batch["tokens"],
                          return_hidden=True, mutable=["stats"])
    assert sorted(sown["stats"]) == ["h_1", "h_2", "h_3"]
    sq = [float(sown["stats"][f"h_{i}"]["attn"]["out_sq"][0])
          for i in (1, 2, 3)]

    def rms(**kw):
        other = SmallThinker(SmallThinkerConfig.tiny(**F32, **kw))
        return float(smallthinker_loss_fn(other, ce_chunk=32)(
            params, batch)[1]["attn_window_out_rms"])
    assert rms() == pytest.approx(np.sqrt(np.mean(sq)), rel=1e-6)
    assert abs(rms(window=16) / rms() - 1) > 0.02
    assert rms(window_period=(0, 0, 0, 0)) == 0.0
    # a row shorter than the window: its last row stands for it
    short = {k: v[:, :16] for k, v in batch.items()}
    assert float(smallthinker_loss_fn(model, ce_chunk=16)(
        params, short)[1]["attn_window_out_rms"]) > 0


def test_layer_0_is_unmoved_by_rope_theta_and_layers_1_to_3_are_not():
    """One block at a time on the same input under two values of
    ``rope_theta``: a layer whose entry of ``rope_period`` is 0 rotates
    nothing."""
    from ray_tpu.models.smallthinker import Block
    a = SmallThinkerConfig.tiny(**F32)
    b = SmallThinkerConfig.tiny(rope_theta=500.0, **F32)
    assert [a.rotated(i) for i in range(4)] == [False, True, True, True]
    assert [a.windowed(i) for i in range(4)] == [False, True, True, True]
    x = jax.random.normal(jax.random.key(0), (2, a.seq_len, a.n_embd))
    from ray_tpu.models.llama import rope_freqs
    for layer in range(4):
        outs = []
        for cfg in (a, b):
            block = Block(cfg, layer)
            fns = SmallThinker(cfg)._attn_fns()
            angles = rope_freqs(cfg.head_dim, cfg.seq_len, cfg.rope_theta)
            params = block.init(jax.random.key(1), x, fns, angles)
            outs.append(block.apply(params, x, fns, angles,
                                    mutable=["moe"])[0])
        if layer == 0:
            np.testing.assert_array_equal(outs[0], outs[1])
        else:
            assert float(jnp.abs(outs[0] - outs[1]).max()) > 1e-4
    # and the whole model's layout follows the periods, not the defaults
    odd = SmallThinkerConfig.tiny(window_period=(1, 0),
                                  rope_period=(0, 0, 1), **F32)
    assert odd.layer_kinds == "wgWg"


# -- the router reads the block's input ----

def test_the_routes_do_not_change_when_the_attention_weights_do():
    """The load each expert draws in layer 0, and the routes, are a
    function of the block's input alone: other attention weights move
    the block's output and the next layer's routes, not this layer's;
    another router matrix moves them."""
    cfg = SmallThinkerConfig.tiny(n_layer=2, **F32)
    model = SmallThinker(cfg)
    params = _jittered(model.init_params(jax.random.key(0)), 0)
    tokens = _batch(0, cfg)["tokens"]

    def loads(p):
        hidden, sown = model.apply({"params": p}, tokens, return_hidden=True,
                                   mutable=["moe"])
        return hidden, [sown["moe"][f"h_{i}"]["mlp"]["load"][0]
                        for i in range(2)]
    other = jax.tree_util.tree_map(lambda x: x, params)
    other["h_0"] = {**params["h_0"], "attn": _jittered(
        params["h_0"]["attn"], 7, by=0.5)}
    h_a, (l0_a, l1_a) = loads(params)
    h_b, (l0_b, l1_b) = loads(other)
    np.testing.assert_array_equal(l0_a, l0_b)
    assert float(jnp.abs(h_a - h_b).max()) > 1e-3
    assert np.any(np.asarray(l1_a) != np.asarray(l1_b))
    # the same from the reference: its layer-0 routes under both trees
    ref = mf.load_reference("smallthinker")
    _, (w_a, e_a), _ = ref.forward(params, tokens, _spec(cfg))
    _, (w_b, e_b), _ = ref.forward(other, tokens, _spec(cfg))
    np.testing.assert_array_equal(e_a[0], e_b[0])
    np.testing.assert_array_equal(w_a[0], w_b[0])
    assert np.any(np.asarray(e_a[1]) != np.asarray(e_b[1]))
    moved = jax.tree_util.tree_map(lambda x: x, params)
    moved["h_0"] = {**params["h_0"], "router": _jittered(
        params["h_0"]["router"], 9, by=0.5)}
    assert np.any(np.asarray(loads(moved)[1][0]) != np.asarray(l0_a))


def test_the_router_is_the_references_top_k_then_softmax():
    cfg = SmallThinkerConfig.tiny(**F32)
    h = jax.random.normal(jax.random.key(0), (2, cfg.seq_len, cfg.n_embd))
    router = Router(cfg)
    params = _jittered(router.init(jax.random.key(1), h), 2, by=0.5)
    weights, experts = router.apply(params, h)
    ref = mf.load_reference("smallthinker")
    with jax.default_matmul_precision("highest"):
        want_w, want_e = ref.routes(params["params"]["kernel"], h,
                                    _spec(cfg))
    np.testing.assert_array_equal(experts, want_e)
    np.testing.assert_allclose(weights, want_w, atol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-6)
    assert weights.shape == (2, cfg.seq_len, cfg.top_k)


# -- ReGLU ----

def _plain_reglu(x, wg, wu, wd, weights, experts, first=0):
    """Every expert on every token, times its weight or zero."""
    y = jnp.zeros_like(x)
    for e in range(wg.shape[0]):
        w = jnp.where(experts == first + e, weights, 0.0).sum(-1)
        y += (jax.nn.relu(x @ wg[e]) * (x @ wu[e])) @ wd[e] * w[..., None]
    return y


@pytest.mark.parametrize("held", [None, (2, 4)], ids=["sorted", "slabs"])
def test_reglu_is_its_plain_form_through_the_experts_and_the_slabs(held):
    t, d, f, e, k = 48, 32, 24, 8, 3
    keys = jax.random.split(jax.random.key(0), 6)
    x = jax.random.normal(keys[0], (2, t, d))
    wg, wu = (0.3 * jax.random.normal(kk, (e, d, f)) for kk in keys[1:3])
    wd = 0.3 * jax.random.normal(keys[3], (e, f, d))
    rw = jax.random.normal(keys[4], (d, e))
    weights, experts = moe.route_softmax(x, rw, top_k=k, norm_topk_prob=True)
    first, count = held or (0, e)
    own = [w[first:first + count] for w in (wg, wu, wd)]

    def layer(x, weights, *ws):
        return moe.routed_experts(x, weights, experts, *ws, num_experts=e,
                                  experts_held=held, expert="reglu")[0]

    def plain(x, weights, *ws):
        return _plain_reglu(x, *ws, weights, experts, first)
    notes = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(tracing, "note_trace", notes.update)
        got = layer(x, weights, *own)
    assert notes["moe_expert_kind"] == "reglu"
    np.testing.assert_allclose(got, plain(x, weights, *own), atol=2e-5)
    g = jax.random.normal(keys[5], got.shape)
    grads = [jax.jit(jax.grad(lambda *a: (fn(*a) * g).sum(), range(5)))(
        x, weights, *own) for fn in (layer, plain)]
    for a, b in zip(*grads):
        np.testing.assert_allclose(a, b, atol=2e-4)
    # relu, not silu: a gate below zero passes nothing
    silu = moe.routed_experts(x, weights, experts, *own, num_experts=e,
                              experts_held=held, expert="swiglu")[0]
    assert float(jnp.abs(silu - got).max()) > 1e-3
    # _experts itself, on rows already sorted by expert
    rows = jax.random.normal(keys[5], (16, d))
    counts = jnp.array([4, 0, 6, 0, 2, 4, 0, 0], jnp.int32)
    ys = moe._experts(rows, wg, wu, wd, counts, 2, "reglu")
    owner = np.repeat(np.arange(e), np.asarray(counts))
    for i, o in enumerate(owner):
        want = (jax.nn.relu(rows[i] @ wg[o]) * (rows[i] @ wu[o])) @ wd[o]
        np.testing.assert_allclose(ys[i], want, atol=2e-5)


def test_reglu_at_full_skew_walks_a_second_slab_and_is_the_references():
    """Every token chooses the same three experts, two of them held (a
    collapsed router, which the preset's normal(0.02) embedding gives
    under uniform tokens): the held routes are more than ``held_rows``
    (twice the even share), so the loop over further slabs runs, forward
    and backward, and the output and all five gradients are still the
    reference's ``experts_part``: nothing dropped, nothing counted
    twice."""
    t, d, f, e = 512, 32, 24, 8
    held, chosen = (2, 2), (2, 3, 5)
    keys = jax.random.split(jax.random.key(3), 6)
    x = jax.random.normal(keys[0], (2, t, d))
    wg, wu = (0.3 * jax.random.normal(kk, (2, d, f)) for kk in keys[1:3])
    wd = 0.3 * jax.random.normal(keys[3], (2, f, d))
    weights = jax.nn.softmax(jax.random.normal(keys[4], (2, t, 3)), -1)
    experts = jnp.broadcast_to(jnp.array(chosen, jnp.int32), (2, t, 3))
    rows = moe.held_rows(2 * t * 3, held[1], e)
    assert rows < 2 * t * 2 <= 2 * rows       # two slabs, no more
    ref = mf.load_reference("smallthinker")

    def layer(x, weights, *ws):
        return moe.routed_experts(x, weights, experts, *ws, num_experts=e,
                                  experts_held=held, expert="reglu")

    def plain(x, weights, wg, wu, wd):
        return ref.experts_part(
            {"gate_proj": wg, "up_proj": wu, "down_proj": wd}, x, weights,
            experts, held, lambda z: z)
    with jax.default_matmul_precision("highest"):
        got, load = layer(x, weights, wg, wu, wd)
        want = plain(x, weights, wg, wu, wd)
        g = jax.random.normal(keys[5], got.shape)
        grads = [jax.jit(jax.grad(lambda *a: (fn(*a) * g).sum(), range(5)))(
            x, weights, wg, wu, wd)
            for fn in (lambda *a: layer(*a)[0], plain)]
    assert load.tolist() == [2 * t if i in chosen else 0 for i in range(e)]
    np.testing.assert_allclose(got, want, atol=2e-5)
    for a, b in zip(*grads):
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.abs(b).max()))


def test_an_expert_kind_has_to_fit_its_matrices():
    x = jnp.zeros((1, 4, 8))
    w = jnp.zeros((2, 8, 8))
    weights, experts = jnp.ones((1, 4, 1)), jnp.zeros((1, 4, 1), jnp.int32)
    with pytest.raises(ValueError, match="reglu"):
        moe.routed_experts(x, weights, experts, None, w, w, num_experts=2,
                           expert="reglu")
    with pytest.raises(ValueError, match="gelu"):
        moe.routed_experts(x, weights, experts, w, w, w, num_experts=2,
                           expert="gelu")


# -- the share of the experts ----

def test_four_shares_of_sixteen_add_up_to_the_uncut_reference_layer():
    """The guide's share test: the routed layer under ``experts_held =
    (0, 16)``, ``(16, 16)``, ``(32, 16)`` and ``(48, 16)``, each given
    its own experts' weights and the same routes, adds up to what the
    reference gives for the whole layer of 64 (there is no shared expert
    to count once)."""
    base = dict(num_experts=64, top_k=6, **F32)
    cfg = SmallThinkerConfig.tiny(experts_held=None, **base)
    h = jax.random.normal(jax.random.key(0), (2, cfg.seq_len, cfg.n_embd))
    before = jax.random.normal(jax.random.key(1), h.shape)
    rw = jax.random.normal(jax.random.key(2), (cfg.n_embd, 64))
    weights, experts = moe.route_softmax(
        before, rw, top_k=6, norm_topk_prob=True)
    params = _jittered(Experts(cfg).init(
        jax.random.key(3), h, weights, experts)["params"], 4)

    def share(first):
        held = SmallThinkerConfig.tiny(experts_held=(first, 16), **base)
        own = jax.tree_util.tree_map(lambda w: w[first:first + 16], params)
        y, sown = Experts(held).apply({"params": own}, h, weights, experts,
                                      mutable=["moe"])
        return y, sown["moe"]["load"][0]

    with jax.default_matmul_precision("highest"):
        parts = [share(first) for first in (0, 16, 32, 48)]
        ref = mf.load_reference("smallthinker")
        want_w, want_e = ref.routes(rw, before, _spec(cfg))
        want = ref.experts_part(params, h, want_w, want_e, (0, 64),
                                lambda x: x)
    np.testing.assert_array_equal(experts, want_e)
    np.testing.assert_allclose(sum(y for y, _ in parts), want, atol=2e-5)
    for y, load in parts:
        assert float(jnp.abs(y).max()) > 0
        np.testing.assert_array_equal(load, parts[0][1])   # all route alike
    assert float(parts[0][1].sum()) == 2 * cfg.seq_len * 6
    # no single share is the layer
    assert float(jnp.abs(parts[0][0] - want).max()) > 1e-3


# -- the step, the head, a mesh ----

def test_a_train_step_runs_and_reports_the_load_of_every_layer():
    cfg = SmallThinkerConfig.tiny(**F32)    # the CPU has no bf16 dot
    model = SmallThinker(cfg)
    opt = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-3))
    state = train.init_train_state(
        jax.jit(model.init_params)(jax.random.key(0)), opt, None)
    step = train.make_train_step(smallthinker_loss_fn(model, ce_chunk=32),
                                 opt)
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


def test_the_step_reports_the_norms_of_the_groups_of_leaves_it_is_given():
    """``make_train_step(grad_groups=...)``: each group's norm is the
    reference's over the same leaves (what the benchmark's cell holds
    the windowed and the global layers' projections to), the groups of
    a partition add up to ``grad_norm``, and a pattern that finds no
    leaf is refused when the step is traced."""
    cfg = SmallThinkerConfig.tiny(**F32)
    model = SmallThinker(cfg)
    opt = optax.adamw(1e-3)
    params = _jittered(jax.jit(model.init_params)(jax.random.key(1)), 1)
    batch = _batch(1, cfg)
    groups = {"attn_window": "^h_[123]/attn/", "attn_global": "^h_0/attn/",
              "rest": "^(?!h_[0-3]/attn/)"}
    loss_fn = smallthinker_loss_fn(model, ce_chunk=32)
    want = mf.load_reference("smallthinker").loss_and_grad_norm(
        params, batch, _spec(cfg, grad_groups=groups))
    step = train.make_train_step(loss_fn, opt, donate=False,
                                 grad_groups=groups)
    with jax.default_matmul_precision("highest"):
        _, metrics = step(train.init_train_state(params, opt, None), batch)
    for name in groups:
        assert float(metrics[name]) == pytest.approx(want[name], rel=1e-4)
    assert sum(float(metrics[name]) ** 2 for name in groups) \
        == pytest.approx(float(metrics["grad_norm"]) ** 2, rel=1e-5)
    assert float(metrics["attn_window"]) != float(metrics["attn_global"])
    plain = train.make_train_step(loss_fn, opt, donate=False)
    assert set(metrics) - set(plain(train.init_train_state(
        params, opt, None), batch)[1]) == set(groups)
    with pytest.raises(ValueError, match="finds none of"):
        train.make_train_step(loss_fn, opt, grad_groups={"x": "^nowhere"})(
            train.init_train_state(params, opt, None), batch)


def test_the_notes_say_the_layout_the_router_and_the_expert_kind(
        monkeypatch):
    cfg = SmallThinkerConfig.tiny(**F32)
    model = SmallThinker(cfg)
    params = model.init_params(jax.random.key(0))
    notes = {}      # a step's listener, once installed, takes them away
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    model.apply({"params": params}, _batch(0, cfg)["tokens"],
                return_hidden=True, mutable=["moe"])
    assert notes["attn_kind"] == "window_global"
    assert notes["attn_layers"] == "gWWW" and notes["attn_window"] == 24
    assert notes["moe_router_input"] == "pre_attention"
    assert notes["moe_router"] == "caller"
    assert notes["moe_expert_kind"] == "reglu"
    assert notes["moe_experts_held"] == [4, 4] and notes["moe_top_k"] == 3
    # which row moves the held path compiled: off the TPU the plain form
    assert notes["moe_rows_path"] == "xla"


def test_a_mesh_over_the_batch_gives_the_one_device_loss_and_sp_is_refused():
    from ray_tpu.parallel import make_mesh
    cfg = SmallThinkerConfig.tiny(**F32)
    params = SmallThinker(cfg).init_params(jax.random.key(0))
    batch = _batch(0, cfg, rows=4)
    one, _ = smallthinker_loss_fn(SmallThinker(cfg), ce_chunk=32)(
        params, batch)
    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    with mesh:
        many, report = jax.jit(smallthinker_loss_fn(
            SmallThinker(cfg, mesh=mesh), ce_chunk=32))(params, batch)
    assert float(many) == pytest.approx(float(one), rel=1e-5)
    assert float(report["moe_load"].sum()) \
        == cfg.n_layer * 4 * cfg.seq_len * cfg.top_k
    sp = make_mesh({"sp": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="halo"):
        SmallThinker(cfg, mesh=sp).init_params(jax.random.key(0))
    # a windowed layer on one device is XLA's windowed attention here
    q = jax.random.normal(jax.random.key(0), (1, 64, 2, 16))
    assert causal_attention(q, q, q, window=24).shape == q.shape
