"""Kimi-Linear (``models/kimi_linear.py``): the system against the
benchmark's plain reference on seeded random weights, the mixer kinds
over the layers, latent attention without a query latent or a rotation,
what the two KDA keys of the cell's comparison see of a planted fault,
and the shares of the experts against the uncut layer."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import live_kernel_calls, matmuls, primitives

from ray_tpu import train
from ray_tpu.models import KimiLinear, KimiLinearConfig
from ray_tpu.models.joyai import MoE
from ray_tpu.models.kimi_linear import kimi_linear_loss_fn
from ray_tpu.models.llama import rope_freqs
from ray_tpu.ops import kda, mla
from ray_tpu.util import tracing

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmark"))
from benchlib import manifest as mf  # noqa: E402

F32 = dict(dtype=jnp.float32)
RTOL = 2.0 ** -10       # the cell's limit (configs/kimi-linear-48b-a3b.json)
GATES = {"grad_norm_kda_gates":
         "^h_[0-9]+/kda/(f_a/kernel|f_b|A_log|dt_bias|b/kernel)$"}


@pytest.fixture(scope="module")
def ref():
    return mf.load_reference("kimi_linear")


def _spec(cfg, **kw):
    return {**mf.load_builder("kimi_linear").reference_spec(cfg), **kw}


def _jittered(params, seed, by=0.1):
    """Every leaf moved off its initial value, so that the norms'
    scales and the biases say something."""
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

@pytest.mark.parametrize("seed, overrides", [
    (0, {}), (1, {"experts_held": (0, 4)}), (2, {"experts_held": None}),
    (3, {"remat": True}), (4, {"seq_len": 40})],
    ids=["upper_quarter", "lower_quarter", "all_held", "blocks_recomputed",
         "rows_not_whole_chunks"])
def test_loss_every_gradient_leaf_and_the_routes_are_the_references(
        ref, seed, overrides):
    cfg = KimiLinearConfig.tiny(**F32, **overrides)
    model = KimiLinear(cfg)
    params = _jittered(model.init_params(jax.random.key(seed)), seed)
    batch = _batch(seed, cfg)
    with jax.default_matmul_precision("highest"):
        (loss, report), grads = jax.jit(jax.value_and_grad(
            kimi_linear_loss_fn(model, ce_chunk=32), has_aux=True))(
                params, batch)
        logits = model.apply({"params": params}, batch["tokens"])
    spec = _spec(cfg, grad_groups=GATES)
    want, want_grads, loads = ref.loss_and_grads(params, batch, spec)
    want_logits, loads_fwd, out_sq = ref.forward(params, batch["tokens"],
                                                 spec)
    np.testing.assert_array_equal(loads, loads_fwd)
    assert float(loss) == pytest.approx(want["loss"], rel=1e-5)
    assert float(optax.global_norm(grads)) == pytest.approx(
        want["grad_norm"], rel=1e-4)
    np.testing.assert_allclose(logits, want_logits, atol=5e-5)
    assert float(report["moe_absent_route_share"]) == pytest.approx(
        want["moe_absent_route_share"], abs=1e-6)
    assert float(report["kda_out_rms"]) == pytest.approx(
        want["kda_out_rms"], rel=1e-5)
    assert out_sq.shape == (4,)             # the four KDA layers
    assert loads.shape == (4, cfg.num_experts)      # the four routed ones
    assert float(loads.sum()) == 4 * 2 * cfg.seq_len * cfg.top_k
    want_leaves = dict(_leaves_with_names(want_grads))
    for name, got in _leaves_with_names(grads):
        scale = max(float(np.abs(want_leaves[name]).max()), 1e-3)
        np.testing.assert_allclose(got, want_leaves[name],
                                   atol=2e-4 * scale, err_msg=name)
    assert len(want_leaves) == len(jax.tree_util.tree_leaves(grads))


def test_parameters_are_the_configs_count_and_the_published_models():
    cfg = KimiLinearConfig.tiny()
    params = jax.eval_shape(KimiLinear(cfg).init_params, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) \
        == cfg.num_params()
    assert "lm_head" in params          # untied
    whole = KimiLinearConfig.kimi_linear_48b_a3b()
    per = whole.layer_params()
    assert per["kda"] == pytest.approx(39.51e6, rel=1e-3)
    assert per["mla"] == pytest.approx(29.11e6, rel=1e-3)
    assert whole.num_params() == pytest.approx(49.1e9, rel=2e-3)
    cut = KimiLinearConfig.kimi_linear_48b_a3b(
        n_layer=5, experts_held=(0, 8), vocab_size=20480)
    assert cut.num_params() == pytest.approx(602.4e6, rel=1e-3)
    import ray_tpu.models as zoo
    assert zoo.KimiLinear is KimiLinear
    with pytest.raises(ValueError, match="dense layers"):
        KimiLinearConfig.tiny(dense_layers=9)


def test_the_mixer_kinds_follow_the_published_list():
    whole = KimiLinearConfig.kimi_linear_48b_a3b()
    assert whole.layer_kinds == "KKKM" * 6 + "KKM"
    assert whole.layer_kinds.count("M") == 7
    cfg = KimiLinearConfig.tiny()
    assert cfg.layer_kinds == "KKKMK"
    params = jax.eval_shape(KimiLinear(cfg).init_params, jax.random.key(0))
    for i, kind in enumerate(cfg.layer_kinds):
        block = params[f"h_{i}"]
        assert ("attn" in block) == (kind == "M")
        assert ("kda" in block) == (kind == "K")
        assert ("experts" in block["mlp"]) == (i >= cfg.dense_layers)
    # no query latent: W_q reads the block's input; nothing is "q_down"
    attn = params["h_3"]["attn"]
    assert set(attn) == {"kv_down", "q_up", "kv_up", "out_proj"}
    assert attn["q_up"]["nope"].shape == (cfg.n_embd,
                                          cfg.n_head * cfg.nope_dim)
    other = KimiLinearConfig.tiny(mla_layers=(1, 3), n_layer=3)
    assert other.layer_kinds == "MKM"


def test_the_mla_layer_has_no_positions_but_the_kda_layers_before_it():
    """``rope_theta`` moves nothing. Rows permuted under the last one:
    an MLA layer alone gives the last row what it gave it before (it
    sees a set of rows), one with a KDA layer before it does not."""
    cfg = KimiLinearConfig.tiny(**F32)
    model = KimiLinear(cfg)
    params = _jittered(model.init_params(jax.random.key(5)), 5)
    toks = _batch(5, cfg, rows=1)["tokens"]
    out = model.apply({"params": params}, toks)
    np.testing.assert_array_equal(
        out, KimiLinear(KimiLinearConfig.tiny(
            rope_theta=123.0, **F32)).apply({"params": params}, toks))

    perm = np.random.default_rng(0).permutation(cfg.seq_len - 1)
    mixed = jnp.concatenate([toks[:, perm], toks[:, -1:]], 1)   # last stays

    def last(cfg):
        model = KimiLinear(cfg)
        p = _jittered(model.init_params(jax.random.key(6)), 6)
        with jax.default_matmul_precision("highest"):
            return (model.apply({"params": p}, toks)[0, -1],
                    model.apply({"params": p}, mixed)[0, -1])

    # one MLA layer: the last row attends to the *set* of rows before it
    only_mla = KimiLinearConfig.tiny(n_layer=1, mla_layers=(1,),
                                     dense_layers=1, **F32)
    a, b = last(only_mla)
    np.testing.assert_allclose(a, b, atol=2e-5)
    a, b = last(KimiLinearConfig.tiny(n_layer=2, mla_layers=(2,),
                                      dense_layers=2, **F32))
    assert float(jnp.abs(a - b).max()) > 1e-3


# -- latent attention without a query latent or a rotation ----

def _dense_masked(c_q, c_kv, k_r, up, h):
    b, t, _ = c_q.shape
    dn, dr = up.q_nope.shape[-1] // h, up.q_rope.shape[-1] // h
    q = jnp.concatenate([(c_q @ up.q_nope).reshape(b, t, h, dn),
                         (c_q @ up.q_rope).reshape(b, t, h, dr)], -1)
    k = jnp.concatenate([(c_kv @ up.k_nope).reshape(b, t, h, dn),
                         jnp.broadcast_to(k_r[:, :, None], (b, t, h, dr))],
                        -1)
    v = (c_kv @ up.v).reshape(b, t, h, -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dn + dr)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                      v).reshape(b, t, -1)


@pytest.mark.parametrize("interpret", [True, False],
                         ids=["kernels_interpreted", "xla_path"])
def test_latent_attention_without_angles_is_a_dense_masked_softmax(
        interpret, monkeypatch):
    h, dn, dr, d, rkv, t = 2, 128, 64, 48, 16, 256
    ks = jax.random.split(jax.random.key(3), 8)
    up = mla.UpProjections(
        jax.random.normal(ks[0], (d, h * dn)) * 0.2,
        jax.random.normal(ks[1], (d, h * dr)) * 0.2,
        jax.random.normal(ks[2], (rkv, h * dn)) * 0.2,
        jax.random.normal(ks[3], (rkv, h * dn)) * 0.2)
    c_q = jax.random.normal(ks[4], (1, t, d))
    c_kv = jax.random.normal(ks[5], (1, t, rkv))
    k_r = jax.random.normal(ks[6], (1, t, dr))
    w = jax.random.normal(ks[7], (1, t, h * dn))
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)

    def loss(fn):
        return jax.value_and_grad(
            lambda *a: (fn(*a) * w).sum(), argnums=(0, 1, 2, 3))

    with jax.default_matmul_precision("highest"):
        want, wants = loss(lambda *a: _dense_masked(*a, h))(
            c_q, c_kv, k_r, up)
        got, gots = loss(lambda *a: mla.latent_attention(
            *a, None, n_head=h, interpret=interpret))(c_q, c_kv, k_r, up)
        rotated, _ = loss(lambda *a: mla.latent_attention(
            *a, rope_freqs(dr, t, 10000.0), n_head=h,
            interpret=interpret))(c_q, c_kv, k_r, up)
    assert notes["mla_positions"] == "rope"     # the last call's
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    assert abs(float(rotated) - float(want)) > 1e-3 * abs(float(want))
    for g, x in zip(jax.tree_util.tree_leaves(gots),
                    jax.tree_util.tree_leaves(wants)):
        np.testing.assert_allclose(g, x, atol=1e-4 * float(jnp.abs(x).max()))
    lowered = jax.jit(lambda *a: mla.latent_attention(
        *a, None, n_head=h, interpret=interpret)).lower(
            c_q, c_kv, k_r, up).as_text(debug_info=True)
    assert "/rope/" not in lowered and "/q_up/" in lowered
    assert notes["mla_positions"] == "none"


# -- what the cell's two KDA keys see of a planted fault ----

def _numbers(cfg, params, batch):
    """The keys the cell compares, from the program's own step."""
    model = KimiLinear(cfg)
    opt = optax.sgd(0.0)
    step = train.make_train_step(kimi_linear_loss_fn(model, ce_chunk=32),
                                 opt, grad_groups=GATES)
    with jax.default_matmul_precision("highest"):
        # the step donates its state: a copy of the parameters goes in
        _, metrics = step(train.init_train_state(
            jax.tree_util.tree_map(jnp.copy, params), opt, None), batch)
    return {k: float(metrics[k]) for k in (
        "loss", "grad_norm", "kda_out_rms", "grad_norm_kda_gates")}


def _no_decay_inside(orig):
    return lambda q, k, G, sub: orig(q, k, jnp.zeros_like(G), sub)


def _head_mean_decay(orig):
    def scan(q, k, v, g, beta, **kw):
        return orig(q, k, v, jnp.broadcast_to(
            g.mean(-1, keepdims=True), g.shape), beta, **kw)
    return scan


def _no_beta_in_correction(orig):
    return lambda A, beta, sub: orig(A, jnp.ones_like(beta), sub)


def _one_chunk_late(orig):
    def read_out(terms, entering, U):
        late = jnp.concatenate(
            [jnp.zeros_like(entering[:, :, :1]), entering[:, :, :-1]], 2)
        return orig(terms, late, U)
    return read_out


FAULTS = {
    "decay_left_out_inside_the_chunk": ("_scores", _no_decay_inside),
    "a_heads_mean_decay_for_its_channels": ("kda_scan", _head_mean_decay),
    "beta_left_out_of_the_correction": ("_solve", _no_beta_in_correction),
    "the_correction_reads_the_undecayed_state":
        ("_state_read", lambda orig: lambda k, G: k),
    "a_chunks_state_handed_on_one_chunk_late": ("_read_out", _one_chunk_late),
}


@pytest.fixture(scope="module")
def fault_case():
    cfg = KimiLinearConfig.tiny(**F32)
    params = _jittered(KimiLinear(cfg).init_params(jax.random.key(11)), 11)
    batch = _batch(11, cfg)
    return cfg, params, batch, _numbers(cfg, params, batch)


def test_the_sound_program_is_inside_the_limit_on_every_key(ref, fault_case):
    cfg, params, batch, got = fault_case
    want = ref.loss_and_grad_norm(params, batch,
                                  _spec(cfg, grad_groups=GATES))
    for key, value in got.items():
        assert abs(value - want[key]) <= RTOL * abs(want[key]), key


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_moves_one_of_the_two_kda_keys_past_the_limit(
        fault, fault_case, monkeypatch):
    """Each fault of ISSUE 45's list, planted in ``ops/kda.py`` while
    the program is traced: ``kda_out_rms`` or ``grad_norm_kda_gates``
    leaves the limit (the sound program's numbers stand in for the
    reference's, which the test above holds them to)."""
    cfg, params, batch, sound = fault_case
    name, make = FAULTS[fault]
    monkeypatch.setattr(kda, name, make(getattr(kda, name)))
    got = _numbers(cfg, params, batch)
    off = {k: abs(got[k] - sound[k]) / abs(sound[k])
           for k in ("kda_out_rms", "grad_norm_kda_gates")}
    assert max(off.values()) > 2 * RTOL, off


def test_float8_operands_fail_at_least_one_key_of_the_cells(ref):
    """The reference with its matmul operands rounded to
    ``float8_e4m3fn``, the precision under the configuration's bfloat16,
    is not correct at the cell's limit."""
    cfg = KimiLinearConfig.tiny(**F32)
    params = _jittered(KimiLinear(cfg).init_params(jax.random.key(12)), 12)
    batch = _batch(12, cfg)
    spec = _spec(cfg, grad_groups=GATES)
    want = ref.loss_and_grad_norm(params, batch, spec)
    low = ref.loss_and_grad_norm(
        params, batch, {**spec, "operand_dtype": "float8_e4m3fn"})
    off = {k: abs(low[k] - want[k]) / abs(want[k]) for k in want}
    assert max(off.values()) > RTOL, off


def test_the_references_recurrence_is_the_tests_own(ref):
    """Two token-by-token recurrences written apart (the reference's
    three updates in order, ``tests/test_kda.py``'s decay-read-write)
    agree, also across the reference's recomputed blocks."""
    from test_kda import operands, recurrence
    args = operands(9, 1, 2 * ref.TOKEN_BLOCK + 64, 2, 16, 16)
    np.testing.assert_allclose(ref.recurrence(*args), recurrence(*args),
                               atol=1e-6)


def test_the_references_head_groups_are_bookkeeping(ref, monkeypatch):
    """A KDA layer's heads one at a time give what both at once give."""
    cfg = KimiLinearConfig.tiny(**F32)
    params = _jittered(KimiLinear(cfg).init_params(jax.random.key(13)), 13)
    toks = _batch(13, cfg)["tokens"]
    want, _, want_sq = ref.forward(params, toks, _spec(cfg))
    monkeypatch.setattr(ref, "HEAD_GROUP", 1)
    got, _, got_sq = ref.forward(params, toks, _spec(cfg))
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(got_sq, want_sq, rtol=1e-5)


# -- the guide's tie of the cut to the model ----

def test_thirty_two_shares_of_eight_add_up_to_the_uncut_reference_layer():
    """256 experts, top-8, thirty-two shares of eight. Each share is the
    program's ``MoE`` module under this config (the held experts'
    routed part plus the shared expert); the routed parts of all
    thirty-two, with the shared expert counted once, are the uncut
    reference's layer output."""
    joyai = mf.load_reference("joyai")
    kw = dict(num_experts=256, top_k=8, **F32)
    cfg = KimiLinearConfig.tiny(experts_held=None, **kw)
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
        want, load = joyai._moe(whole, h, spec, same)
        shared = joyai._swiglu(whole["shared"], h, same)
        total = 0.0
        for first in range(0, 256, 8):
            share = dict(whole, experts={
                k: v[first:first + 8] for k, v in whole["experts"].items()})
            held = KimiLinearConfig.tiny(experts_held=(first, 8), **kw)
            y, sown = MoE(held).apply({"params": share}, h, mutable=["moe"])
            total = total + (y - shared)            # the routed part
            np.testing.assert_array_equal(sown["moe"]["load"][0], load)
    assert float(load.sum()) == 2 * 128 * 8
    np.testing.assert_allclose(total + shared, want, rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(want - shared).max()) > 0.1    # routing mattered


# -- the step, its notes and its scopes ----

def test_the_step_reports_the_kda_keys_and_notes_what_the_layers_are(
        monkeypatch):
    cfg = KimiLinearConfig.tiny(**F32)
    model = KimiLinear(cfg)
    params = jax.jit(model.init_params)(jax.random.key(0))
    opt = optax.adamw(1e-3)
    state = train.init_train_state(params, opt, None)
    step = train.make_train_step(kimi_linear_loss_fn(model, ce_chunk=32),
                                 opt, grad_groups=GATES)
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    step.trace(state, _batch(0, cfg))
    assert {k: notes[k] for k in (
        "attn_kind", "attn_layers", "kda_path", "kda_chunk", "kda_heads",
        "kda_state", "kda_gate_path", "mla_positions", "mla_saved", "dense_layers",
        "flash_path", "moe_router", "moe_expert_kind",
        "moe_experts_held")} == {
        "attn_kind": "kda_mla", "attn_layers": "KKKMK",
        "kda_path": "xla_chunked", "kda_chunk": 16, "kda_heads": 2,
        "kda_state": [16, 16], "kda_gate_path": "xla",
        "mla_positions": "none",
        "mla_saved": "latents", "dense_layers": 1, "flash_path": "xla",
        "moe_router": "sigmoid", "moe_expert_kind": "swiglu",
        "moe_experts_held": [4, 4]}
    state, metrics = step(state, _batch(0, cfg))
    assert {"loss", "lm_loss", "kda_out_rms", "grad_norm_kda_gates",
            "moe_held_route_share", "moe_absent_route_share",
            "moe_load_max_over_mean", "grad_norm"} <= set(metrics)
    assert float(metrics["kda_out_rms"]) > 0
    assert 0 < float(metrics["grad_norm_kda_gates"]) \
        < float(metrics["grad_norm"])


@pytest.mark.parametrize("axes", [None, {"dp": 2}], ids=["no_mesh", "dp"])
def test_a_mixer_hands_its_mesh_to_the_one_output_gate(monkeypatch, axes):
    """``ops/gated_norm.py::sigmoid_gated_head_rms_norm`` is the one entry and
    decides from the mesh it is given; here, on the CPU, it is the XLA
    function, which the comparison with the reference above holds to
    the formula in the loss and every gradient leaf."""
    from ray_tpu.models.kimi_linear import KDAMixer
    from ray_tpu.ops import gated_norm
    from ray_tpu.parallel import make_mesh
    mesh = axes and make_mesh(axes, devices=jax.devices()[:2])
    seen, gate = [], gated_norm.sigmoid_gated_head_rms_norm
    monkeypatch.setattr(
        gated_norm, "sigmoid_gated_head_rms_norm",
        lambda *a, **kw: seen.append(kw) or gate(*a, **kw))
    xla = []
    monkeypatch.setattr(
        gated_norm, "_sigmoid_gated_head_rms_norm_xla",
        lambda o, gate, *a: xla.append(o.shape) or gate)
    cfg = KimiLinearConfig.tiny(**F32)
    mixer = KDAMixer(cfg, mesh)
    h = jax.ShapeDtypeStruct((2, cfg.seq_len, cfg.n_embd), jnp.float32)
    jax.eval_shape(lambda h: mixer.init_with_output(jax.random.key(0), h), h)
    assert seen == [{"mesh": mesh}]
    assert xla == [(2, cfg.seq_len, cfg.kda_inner)]


@pytest.mark.parametrize("remat, keeps", [
    (True, "kda_gated_out,kda_scan_out,kda_scan_states,moe_router_logits,"
     "moe_router_experts,moe_router_weights,moe_router_counts,"
     "moe_router_lse,mixer_in_proj,mixer_stream,mlp_gate,mlp_up,attn_out,"
     "attn_lse"),
    (False, "")],
    ids=["recomputed", "kept_whole"])
def test_a_recomputed_block_says_what_its_policy_keeps(remat, keeps,
                                                       monkeypatch):
    """``blocks_remat_keeps`` beside ``blocks_remat``: the names a
    recomputed block's policy keeps (the KDA mixers' gated output and
    their recurrence's two named results first, then the routers'
    product and choice, then the latent core's output and row
    statistics), and every block's checkpoint carries a policy; nothing
    where the blocks are not recomputed."""
    cfg = KimiLinearConfig.tiny(remat=remat, **F32)
    model = KimiLinear(cfg)
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
def test_a_recomputed_block_routes_once(monkeypatch, listed):
    """The routers' float32 product (``[128, 64] x [64, 16]``, the four
    routed layers) and ``top_k`` in the gradient's jaxpr: as often with
    ``remat`` as in the stack kept whole; with the routers' names off
    the policy, twice."""
    from ray_tpu.models import kimi_linear
    if not listed:
        monkeypatch.setattr(kimi_linear, "_BLOCK_KEEPS", tuple(
            n for n in kimi_linear._BLOCK_KEEPS
            if not n.startswith("moe_router")))

    def routes(remat):
        cfg = KimiLinearConfig.tiny(remat=remat, **F32)
        model = KimiLinear(cfg)
        params = jax.eval_shape(model.init_params, jax.random.key(0))
        traced = jax.make_jaxpr(jax.value_and_grad(
            kimi_linear_loss_fn(model, ce_chunk=32), has_aux=True))(
                params, _batch(0, cfg))
        return (matmuls(traced, ((128, 64), (64, 16))),
                primitives(traced, "top_k"))

    assert routes(False) == (4, 4)
    assert routes(True) == ((4, 4) if listed else (8, 8))


@pytest.mark.parametrize("listed", [True, False],
                         ids=["pr_70s_list", "pr_66s_list"])
def test_the_blocks_kept_products_change_no_number(monkeypatch, listed):
    """Loss, report and every gradient leaf of the tiny stack (``KKKMK``,
    a dense and four routed layers) with ``remat`` against without, under
    one ``jit`` each: with the literal as it is (PR 70: a KDA mixer's
    three input products, the stream behind either mixer, the dense
    and shared MLPs' ``gate`` and ``up`` kept by name) and with those
    names off it. A kept value is a buffer of what the second pass would
    have made again."""
    from ray_tpu.models import kimi_linear
    if not listed:
        monkeypatch.setattr(kimi_linear, "_BLOCK_KEEPS", tuple(
            n for n in kimi_linear._BLOCK_KEEPS if n not in (
                "mixer_in_proj", "mixer_stream", "mlp_gate", "mlp_up")))
    got = {}
    for remat in (False, True):
        cfg = KimiLinearConfig.tiny(remat=remat, **F32)
        model = KimiLinear(cfg)
        params = _jittered(model.init_params(jax.random.key(7)), 7)
        got[remat] = jax.jit(jax.value_and_grad(
            kimi_linear_loss_fn(model, ce_chunk=32), has_aux=True))(
                params, _batch(7, cfg))
    ((want, want_report), want_grads), ((loss, report), grads) = (
        got[False], got[True])
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    for key in want_report:
        assert float(report[key]) == pytest.approx(
            float(want_report[key]), rel=1e-6), key
    want_leaves = dict(_leaves_with_names(want_grads))
    assert len(want_leaves) == len(jax.tree_util.tree_leaves(grads)) > 100
    for name, leaf in _leaves_with_names(grads):
        scale = max(float(np.abs(want_leaves[name]).max()), 1e-3)
        np.testing.assert_allclose(leaf, want_leaves[name],
                                   atol=1e-5 * scale, err_msg=name)


def _at_the_kernels_widths(remat):
    """Two KDA layers with dense MLPs at the kernels' widths: heads of
    128, chunks of 64, rows of 128 tokens. -> (model, loss function)."""
    model = KimiLinear(KimiLinearConfig.tiny(
        n_layer=2, mla_layers=(), dense_layers=2, kda_heads=2,
        kda_head_dim=128, kda_chunk=64, seq_len=128, remat=remat, **F32))
    return model, kimi_linear_loss_fn(model, ce_chunk=32)


@functools.cache
def _kernel_case():
    model, _ = _at_the_kernels_widths(False)
    params = _jittered(model.init_params(jax.random.key(5)), 5)
    return params, _batch(5, model.config, rows=1)


@pytest.fixture
def on_the_kernels(monkeypatch):
    """``_at_the_kernels_widths`` with the recurrence on
    ``ops/pallas/kda_scan.py``'s kernels, interpreted: ``kda_path`` is
    told what a TPU would answer. -> (remat -> (model, loss function),
    parameters, a batch of one row)."""
    from ray_tpu.ops.pallas import kda_scan as kernels
    monkeypatch.setattr(kda, "kda_path", lambda *a, **kw: "pallas_chunked")
    monkeypatch.setattr(kernels, "kda_scan", functools.partial(
        kernels.kda_scan, interpret=True))
    return (_at_the_kernels_widths, *_kernel_case())


def test_recomputed_blocks_on_the_kernels_give_the_kept_blocks_numbers(
        on_the_kernels):
    """The loss, the report and every gradient leaf with ``remat`` (the
    blocks keep the recurrence's ``o`` and states and never run its
    forward kernel again) against without (``_kda_core``'s checkpoint
    runs it again): the same kernels on the same operands."""
    made, params, batch = on_the_kernels
    (want, want_report), want_grads = jax.jit(jax.value_and_grad(
        made(False)[1], has_aux=True))(params, batch)
    (loss, report), grads = jax.jit(jax.value_and_grad(
        made(True)[1], has_aux=True))(params, batch)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    assert float(report["kda_out_rms"]) == pytest.approx(
        float(want_report["kda_out_rms"]), rel=1e-6)
    want_leaves = dict(_leaves_with_names(want_grads))
    assert len(want_leaves) == len(jax.tree_util.tree_leaves(grads))
    for name, got in _leaves_with_names(grads):
        scale = max(float(np.abs(want_leaves[name]).max()), 1e-3)
        np.testing.assert_allclose(got, want_leaves[name],
                                   atol=1e-5 * scale, err_msg=name)


@pytest.mark.parametrize("remat, keeps, forwards", [
    (True, None, 1), (True, ("kda_gated_out",), 2), (False, None, 2)],
    ids=["recomputed", "recomputed_without_the_scans_names", "kept_whole"])
def test_the_recurrences_forward_kernel_runs_once_a_layer_under_remat(
        on_the_kernels, monkeypatch, remat, keeps, forwards):
    """In the gradient's jaxpr, with what nothing reads taken out as
    lowering takes it out: a recomputed block runs the recurrence's
    forward kernel once a KDA layer (2 results: ``o`` and the states)
    and its backward once (5); a policy that loses the kernels' two
    names (PR 58's, the gated output alone) runs the forward twice, and
    so does ``remat=False``, where ``_kda_core``'s checkpoint keeps the
    six projections and nothing of the recurrence."""
    from ray_tpu.models import kimi_linear
    if keeps:
        monkeypatch.setattr(kimi_linear, "_BLOCK_KEEPS", tuple(
            n for n in kimi_linear._BLOCK_KEEPS
            if not n.startswith("kda_") or n in keeps))
    made, params, batch = on_the_kernels
    model, loss_fn = made(remat)
    traced = jax.make_jaxpr(jax.value_and_grad(loss_fn, has_aux=True))(
        params, batch)
    layers = model.config.layer_kinds.count("K")
    assert layers == 2
    assert live_kernel_calls(traced) == [2] * forwards * layers + [5] * layers


def test_a_kda_layer_has_its_own_scopes_and_the_mla_layer_joyais():
    """``blocks/h_i/kda`` with its seven scopes in a KDA layer;
    ``blocks/h_3/attn`` with ``q_up``, ``kv_down``, ``kv_up``, ``core``
    and ``out_proj`` and neither ``q_down`` nor ``rope`` in the MLA
    layer: in the lowered step's locations, which the readers key on."""
    import re
    cfg = KimiLinearConfig.tiny(**F32)
    model = KimiLinear(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    batch = jax.eval_shape(lambda: _batch(0, cfg))
    text = jax.jit(jax.grad(lambda p, b: kimi_linear_loss_fn(
        model, ce_chunk=32)(p, b)[0])).lower(params, batch).as_text(
            debug_info=True)
    for scope in ("qkv", "conv", "qk_norm", "decay", "scan", "out_gate",
                  "out"):
        assert re.search(rf"blocks/h_0/kda/(checkpoint/)?"
                         rf"(rematted_computation/)?{scope}/", text), scope
    for scope in ("q_up", "kv_down", "kv_up", "core", "out_proj"):
        assert f"blocks/h_3/attn/{scope}" in text, scope
    assert "attn/rope" not in text and "q_down" not in text
    assert "h_3/kda/" not in text and "h_0/attn/" not in text


def test_a_mesh_over_the_batch_gives_the_one_device_loss_and_sp_is_refused():
    from ray_tpu.parallel import make_mesh
    cfg = KimiLinearConfig.tiny(**F32)
    params = KimiLinear(cfg).init_params(jax.random.key(0))
    batch = _batch(0, cfg)
    want, _ = kimi_linear_loss_fn(KimiLinear(cfg), ce_chunk=32)(params, batch)
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    got, _ = jax.jit(kimi_linear_loss_fn(
        KimiLinear(cfg, mesh=mesh), ce_chunk=32))(params, batch)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    sp = make_mesh({"sp": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="sp=2"):
        kimi_linear_loss_fn(KimiLinear(cfg, mesh=sp), ce_chunk=32)(
            params, batch)
