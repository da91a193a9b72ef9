"""Qwen3-Next (``models/qwen3_next.py``): the system against the
benchmark's plain reference on seeded random weights, the mixer kinds over
the layers, what the keys of the cell's comparison see of a planted
fault, and the shares of the experts against the uncut layer."""

import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import matmuls, primitives

from ray_tpu import train
from ray_tpu.models import Qwen3Next, Qwen3NextConfig
from ray_tpu.models import qwen3_next
from ray_tpu.models.qwen3_next import MoE, qwen3_next_loss_fn
from ray_tpu.ops import kda
from ray_tpu.util import tracing

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmark"))
from benchlib import manifest as mf  # noqa: E402

F32 = dict(dtype=jnp.float32)
RTOL = 2.0 ** -10       # the cell's limit (configs/qwen3-next-80b-a3b.json)
GROUPS = {
    "grad_norm_gdn_gates": "^h_[0-9]+/gdn/(A_log|dt_bias|ba/kernel)$",
    "grad_norm_attn_qk":
    "^h_[0-9]+/attn/(q/kernel|k/kernel|q_norm|k_norm)$"}
KEYS = ("loss", "grad_norm", "moe_absent_route_share", "gdn_out_rms",
        *GROUPS)


@pytest.fixture(scope="module")
def ref():
    return mf.load_reference("qwen3_next")


def _spec(cfg, **kw):
    return {**mf.load_builder("qwen3_next").reference_spec(cfg), **kw}


def _jittered(params, seed, by=0.1):
    """Every leaf moved off its initial value, so that the norms'
    scales say something."""
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
    return [("/".join(k.key for k in path), leaf) for path, leaf in
            jax.tree_util.tree_leaves_with_path(tree)]


def _numbers(cfg, params, batch):
    """The keys the cell compares, from one jitted gradient of the
    program's own loss function (the step's ``grad_groups`` are these
    norms: ``test_the_step_reports...`` holds them to it)."""
    model = Qwen3Next(cfg)
    with jax.default_matmul_precision("highest"):
        (loss, report), grads = jax.jit(jax.value_and_grad(
            qwen3_next_loss_fn(model, ce_chunk=32), has_aux=True))(
                params, batch)
    squares = {name: float(jnp.sum(z * z))
               for name, z in _leaves_with_names(grads)}
    out = {"loss": float(loss),
           "grad_norm": math.sqrt(sum(squares.values())),
           "moe_absent_route_share": float(
               report["moe_absent_route_share"]),
           "gdn_out_rms": float(report["gdn_out_rms"])}
    for name, pattern in GROUPS.items():
        out[name] = math.sqrt(sum(
            sq for path, sq in squares.items() if re.search(pattern, path)))
    return out, grads


# -- the system against the plain reference ----

@pytest.mark.parametrize("seed, overrides", [
    (0, {}), (1, {"experts_held": None, "remat": True}),
    (2, {"seq_len": 40})],
    ids=["upper_quarter", "all_held_blocks_recomputed",
         "rows_not_whole_chunks"])
def test_loss_every_gradient_leaf_and_the_routes_are_the_references(
        ref, seed, overrides):
    cfg = Qwen3NextConfig.tiny(**F32, **overrides)
    params = _jittered(Qwen3Next(cfg).init_params(jax.random.key(seed)),
                       seed)
    batch = _batch(seed, cfg)
    got, grads = _numbers(cfg, params, batch)
    spec = _spec(cfg, grad_groups=GROUPS)
    want, want_grads, loads = ref.loss_and_grads(params, batch, spec)
    for key in KEYS:
        assert got[key] == pytest.approx(want[key], rel=1e-4, abs=1e-7), key
    assert loads.shape == (4, cfg.num_experts)
    assert float(loads.sum()) == 4 * 2 * cfg.seq_len * cfg.top_k
    want_leaves = dict(_leaves_with_names(want_grads))
    for name, leaf in _leaves_with_names(grads):
        scale = max(float(np.abs(want_leaves[name]).max()), 1e-3)
        np.testing.assert_allclose(leaf, want_leaves[name],
                                   atol=2e-4 * scale, err_msg=name)
    assert len(want_leaves) == len(jax.tree_util.tree_leaves(grads))


def test_the_whole_forward_pass_is_the_references(ref):
    cfg = Qwen3NextConfig.tiny(**F32)
    model = Qwen3Next(cfg)
    params = _jittered(model.init_params(jax.random.key(5)), 5)
    toks = _batch(5, cfg)["tokens"]
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, t: model.apply({"params": p}, t))(
            params, toks)
    want, loads, out_sq = ref.forward(params, toks, _spec(cfg))
    np.testing.assert_allclose(logits, want, atol=2e-4)
    assert out_sq.shape == (3,) and loads.shape == (4, cfg.num_experts)


def test_parameters_are_the_configs_count_and_the_published_models():
    cfg = Qwen3NextConfig.tiny()
    params = jax.eval_shape(Qwen3Next(cfg).init_params, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) \
        == cfg.num_params()
    assert "lm_head" in params          # untied
    whole = Qwen3NextConfig.qwen3_next_80b_a3b()
    per = whole.layer_params()
    assert per["gdn"] == 33_718_464 and per["attn"] == 27_263_488
    assert per["moe"] == 4_196_352 and per["expert"] == 3_145_728
    assert whole.num_params() == pytest.approx(79.7e9, rel=5e-3)
    cut = Qwen3NextConfig.qwen3_next_80b_a3b(
        n_layer=4, experts_held=(0, 32), vocab_size=19072)
    assert cut.num_params() == 625_994_816
    import ray_tpu.models as zoo
    assert zoo.Qwen3Next is Qwen3Next
    with pytest.raises(ValueError, match="key heads"):
        Qwen3NextConfig.tiny(gdn_value_heads=3)


def test_the_mixer_kinds_follow_the_interval_and_the_norms_start_at_zero():
    whole = Qwen3NextConfig.qwen3_next_80b_a3b()
    assert whole.layer_kinds == "LLLF" * 12 and whole.rotated_lanes == 64
    cfg = Qwen3NextConfig.tiny()
    assert cfg.layer_kinds == "LLLF" and cfg.rotated_lanes == 8
    params = Qwen3Next(cfg).init_params(jax.random.key(0))
    for i, kind in enumerate(cfg.layer_kinds):
        block = params[f"h_{i}"]
        assert ("attn" in block) == (kind == "F")
        assert ("gdn" in block) == (kind == "L")
        assert block["mlp"]["experts"]["up_proj"].shape[0] == cfg.held
        assert block["mlp"]["gate"]["kernel"].shape[-1] == cfg.num_experts
        assert not np.asarray(block["attn_norm"]["scale"]).any()
    assert not np.asarray(params["norm_f"]["scale"]).any()
    assert not np.asarray(params["h_3"]["attn"]["q_norm"]).any()
    gdn = params["h_0"]["gdn"]
    assert np.asarray(gdn["norm"]).all()        # the plain form: ones
    assert np.asarray(gdn["dt_bias"]).all()
    assert gdn["A_log"].shape == (cfg.gdn_value_heads,)
    assert gdn["conv"].shape == (4, 2 * cfg.gdn_key_inner + cfg.gdn_inner)


# -- what the cell's keys see of a planted fault ----

def _tiled(z, rep):
    """Value head ``j`` reading key head ``j % Hk``."""
    return jnp.tile(z, (1, rep) + (1,) * (z.ndim - 2))


def _plain_scale(x, scale, eps):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + eps) * scale


# name -> (module, attribute, the faulty thing) or config overrides
FAULTS = {
    "the_decay_left_out": (qwen3_next, "_decay", lambda orig: lambda *a: (
        jnp.zeros_like(orig(*a)[0]), orig(*a)[1])),
    "beta_left_out": (qwen3_next, "_decay", lambda orig: lambda *a: (
        orig(*a)[0], jnp.ones_like(orig(*a)[1]))),
    "value_head_j_reads_key_head_j_mod_16": (
        kda, "_for_value_heads", lambda orig: _tiled),
    "sigmoid_for_silu_in_the_output_gate": (
        qwen3_next, "_OUT_GATE", lambda orig: "sigmoid"),
    "the_norm_not_zero_centred": (
        qwen3_next, "_centred_norm", lambda orig: _plain_scale),
    "every_lane_rotated": (
        Qwen3NextConfig, "rotated_lanes",
        lambda orig: property(lambda self: self.head_dim)),
    "the_shared_expert_ungated": (
        qwen3_next, "_shared_gate", lambda orig: jnp.ones_like),
    "the_ten_not_renormalised": {"norm_topk_prob": False},
}


@pytest.fixture(scope="module")
def fault_case():
    cfg = Qwen3NextConfig.tiny(**F32)
    params = _jittered(Qwen3Next(cfg).init_params(jax.random.key(11)), 11)
    batch = _batch(11, cfg)
    return cfg, params, batch, _numbers(cfg, params, batch)[0]


def test_the_sound_program_is_inside_the_limit_on_every_key(ref, fault_case):
    cfg, params, batch, got = fault_case
    want = ref.loss_and_grad_norm(params, batch,
                                  _spec(cfg, grad_groups=GROUPS))
    for key in KEYS:
        assert abs(got[key] - want[key]) <= RTOL * abs(want[key]), key


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_moves_a_compared_key_past_the_limit(
        fault, fault_case, monkeypatch):
    """Each fault of ISSUE 67's list, planted while the program is
    traced: a compared key leaves the limit (the sound program's numbers
    stand in for the reference's, which the test above holds them to)."""
    cfg, params, batch, sound = fault_case
    plant = FAULTS[fault]
    if isinstance(plant, dict):
        cfg = Qwen3NextConfig.tiny(**F32, **plant)
    else:
        where, name, make = plant
        monkeypatch.setattr(where, name, make(getattr(where, name)))
    got, _ = _numbers(cfg, params, batch)
    off = {k: abs(got[k] - sound[k]) / abs(sound[k]) for k in KEYS}
    assert max(off.values()) > 2 * RTOL, off


def test_float8_operands_fail_at_least_one_key_of_the_cells(ref):
    """The reference with its matmul operands rounded to
    ``float8_e4m3fn``, the precision under the configuration's bfloat16,
    is not correct at the cell's limit."""
    cfg = Qwen3NextConfig.tiny(**F32)
    params = _jittered(Qwen3Next(cfg).init_params(jax.random.key(12)), 12)
    batch = _batch(12, cfg)
    spec = _spec(cfg, grad_groups=GROUPS)
    want = ref.loss_and_grad_norm(params, batch, spec)
    low = ref.loss_and_grad_norm(
        params, batch, {**spec, "operand_dtype": "float8_e4m3fn"})
    off = {k: abs(low[k] - want[k]) / abs(want[k]) for k in want}
    assert max(off.values()) > RTOL, off


def test_the_references_recurrence_is_the_tests_own(ref):
    """Two row-by-row recurrences written apart agree, key heads under
    value heads and across the reference's recomputed blocks."""
    from test_kda import head_operands, recurrence, widened
    args = head_operands(9, 1, 2 * ref.TOKEN_BLOCK + 64, 2, 4, 16, 16)
    np.testing.assert_allclose(ref.recurrence(*args),
                               widened(recurrence)(*args), atol=1e-6)


def test_the_references_head_groups_are_bookkeeping(ref, monkeypatch):
    """A layer's key heads one at a time give what both at once give."""
    cfg = Qwen3NextConfig.tiny(**F32)
    params = _jittered(Qwen3Next(cfg).init_params(jax.random.key(13)), 13)
    toks = _batch(13, cfg)["tokens"]
    want, _, want_sq = ref.forward(params, toks, _spec(cfg))
    monkeypatch.setattr(ref, "HEAD_GROUP", 1)
    got, _, got_sq = ref.forward(params, toks, _spec(cfg))
    np.testing.assert_allclose(got, want, atol=2e-4)     # a sum's order
    np.testing.assert_allclose(got_sq, want_sq, rtol=1e-5)


# -- the guide's tie of the cut to the model ----

def test_sixteen_shares_of_thirty_two_add_up_to_the_uncut_reference_layer(
        ref):
    """512 experts, top-10, sixteen shares of thirty-two. Each share is
    the program's ``MoE`` module under this config (the held experts'
    routed part plus the gated shared expert); the routed parts of all
    sixteen, with the shared expert counted once, are the uncut
    reference's layer output."""
    joyai = mf.load_reference("joyai")
    kw = dict(num_experts=512, top_k=10, **F32)
    cfg = Qwen3NextConfig.tiny(experts_held=None, **kw)
    d, f = cfg.n_embd, cfg.expert_width
    ks = jax.random.split(jax.random.key(7), 9)
    h = jax.random.normal(ks[0], (2, 128, d))

    def dense(key, rows, cols):
        return {"kernel": jax.random.normal(key, (rows, cols)) * 0.2}
    whole = {
        "gate": {"kernel": jax.random.normal(ks[1], (d, 512))},
        "experts": {"gate_proj": jax.random.normal(ks[3], (512, d, f)) * 0.2,
                    "up_proj": jax.random.normal(ks[4], (512, d, f)) * 0.2,
                    "down_proj": jax.random.normal(ks[5], (512, f, d)) * 0.2},
        "shared": {"gate": dense(ks[6], d, cfg.shared_width),
                   "up": dense(ks[7], d, cfg.shared_width),
                   "down": dense(ks[8], cfg.shared_width, d)},
        "shared_gate": dense(ks[2], d, 1)}
    spec = {**_spec(cfg), "experts_held": (0, 512)}
    same = lambda v: v      # noqa: E731 — the reference's "no rounding"
    with jax.default_matmul_precision("highest"):
        want, load = ref._moe(whole, h, spec, same)
        shared = jax.nn.sigmoid(h @ whole["shared_gate"]["kernel"]) \
            * joyai._swiglu(whole["shared"], h, same)
        total = 0.0
        for first in range(0, 512, 32):
            share = dict(whole, experts={
                k: v[first:first + 32] for k, v in whole["experts"].items()})
            held = Qwen3NextConfig.tiny(experts_held=(first, 32), **kw)
            y, sown = MoE(held).apply({"params": share}, h, mutable=["moe"])
            total = total + (y - shared)            # the routed part
            np.testing.assert_array_equal(sown["moe"]["load"][0], load)
    assert float(load.sum()) == 2 * 128 * 10
    np.testing.assert_allclose(total + shared, want, rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(want - shared).max()) > 0.1    # routing mattered


# -- the step, its notes and its scopes ----

def test_the_step_reports_the_keys_and_notes_what_the_layers_are(
        monkeypatch, fault_case):
    cfg, params, batch, sound = fault_case
    model = Qwen3Next(cfg)
    opt = optax.sgd(0.0)
    state = train.init_train_state(
        jax.tree_util.tree_map(jnp.copy, params), opt, None)
    step = train.make_train_step(qwen3_next_loss_fn(model, ce_chunk=32),
                                 opt, grad_groups=GROUPS)
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    step.trace(state, batch)
    assert {k: notes[k] for k in (
        "attn_kind", "attn_layers", "gdn_path", "gdn_chunk", "gdn_heads",
        "gdn_state", "gdn_gate_path", "conv_path",
        "moe_router", "moe_expert_kind", "moe_experts_held", "moe_top_k",
        "norm_kind", "rope_lanes")} == {
        "attn_kind": "gdn_gated", "attn_layers": "LLLF",
        "gdn_path": "xla_chunked", "gdn_chunk": 16, "gdn_heads": [2, 4],
        "gdn_state": [16, 16], "gdn_gate_path": "xla", "conv_path": "xla",
        "moe_router": "softmax",
        "moe_expert_kind": "swiglu", "moe_experts_held": [4, 4],
        "moe_top_k": 3, "norm_kind": "zero_centred", "rope_lanes": 8}
    assert "kda_path" not in notes and "kda_gate_path" not in notes
    with jax.default_matmul_precision("highest"):
        _, metrics = step(state, batch)
    for key in KEYS:
        assert float(metrics[key]) == pytest.approx(sound[key], rel=1e-5)
    assert {"lm_loss", "moe_held_route_share",
            "moe_load_max_over_mean"} <= set(metrics)


@pytest.mark.parametrize("remat, keeps", [
    (True, "moe_router_logits,moe_router_experts,moe_router_weights,"
     "moe_router_counts,moe_router_lse,mixer_out_proj,gdn_gated_out,"
     "kda_scan_out,kda_scan_states,gdn_in_proj,attn_out,attn_lse"),
    (False, "")],
    ids=["recomputed", "kept_whole"])
def test_a_recomputed_block_says_what_its_policy_keeps(remat, keeps,
                                                       monkeypatch):
    cfg = Qwen3NextConfig.tiny(remat=remat, **F32)
    model = Qwen3Next(cfg)
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


@pytest.mark.parametrize("listed", [True, False],
                         ids=["kept", "off_the_policy"])
def test_a_recomputed_block_routes_and_projects_once(monkeypatch, listed):
    """The softmax routers' float32 product (``[128, 64] x [64, 16]``)
    and the Gated DeltaNet layers' input projection in the gradient's
    jaxpr: as often with ``remat`` as in the stack kept whole; with the
    names off the policy, twice. ``top_k`` runs twice either way: its
    values are differentiated through its own indices, which no name
    reaches (``ops/moe.py::_route`` has what the other way costs)."""
    if not listed:
        monkeypatch.setattr(qwen3_next, "_BLOCK_KEEPS", ())

    def runs(remat):
        cfg = Qwen3NextConfig.tiny(remat=remat, **F32)
        model = Qwen3Next(cfg)
        params = jax.eval_shape(model.init_params, jax.random.key(0))
        traced = jax.make_jaxpr(jax.value_and_grad(
            qwen3_next_loss_fn(model, ce_chunk=32), has_aux=True))(
                params, _batch(0, cfg))
        return (matmuls(traced, ((128, 64), (64, 16))),
                primitives(traced, "top_k"),
                matmuls(traced, ((2, 64, 64), (64, 192))))

    assert runs(False) == (4, 4, 3)
    assert runs(True) == ((4, 8, 3) if listed else (8, 8, 6))


def test_each_mixer_has_its_own_scopes_and_the_mlp_its_six():
    """``blocks/h_i/gdn`` with its scopes in an ``L`` layer,
    ``blocks/h_3/attn`` with its seven in the ``F`` layer, ``mlp`` with
    the routed layer's four, ``shared`` and ``shared_gate``: in the
    lowered step's locations, which the readers key on."""
    cfg = Qwen3NextConfig.tiny(**F32)
    model = Qwen3Next(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    batch = jax.eval_shape(lambda: _batch(0, cfg))
    text = jax.jit(jax.grad(lambda p, b: qwen3_next_loss_fn(
        model, ce_chunk=32)(p, b)[0])).lower(params, batch).as_text(
            debug_info=True)
    for scope in ("qkvz", "ba", "conv", "qk_norm", "decay", "scan",
                  "out_gate", "out"):
        assert re.search(rf"blocks/h_0/gdn/(checkpoint/)?"
                         rf"(rematted_computation/)?{scope}/", text), scope
    for scope in ("qkv", "qk_norm", "rope", "repeat", "core", "gate", "out"):
        assert f"blocks/h_3/attn/{scope}/" in text, scope
    for scope in ("router", "dispatch", "experts", "combine", "shared",
                  "shared_gate"):
        assert f"blocks/h_1/mlp/{scope}/" in text, scope
    assert "h_3/gdn/" not in text and "h_0/attn/" not in text


def test_a_mesh_over_the_batch_gives_the_one_device_loss_and_sp_is_refused():
    from ray_tpu.parallel import make_mesh
    cfg = Qwen3NextConfig.tiny(**F32)
    params = Qwen3Next(cfg).init_params(jax.random.key(0))
    batch = _batch(0, cfg)
    want, _ = jax.jit(qwen3_next_loss_fn(Qwen3Next(cfg), ce_chunk=32))(
        params, batch)
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    got, _ = jax.jit(qwen3_next_loss_fn(
        Qwen3Next(cfg, mesh=mesh), ce_chunk=32))(params, batch)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    sp = make_mesh({"sp": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="sp=2"):
        qwen3_next_loss_fn(Qwen3Next(cfg, mesh=sp), ce_chunk=32)(
            params, batch)
