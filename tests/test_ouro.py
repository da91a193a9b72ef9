"""Ouro (``models/ouro.py``): the looped stack against the benchmark's
plain reference on seeded random weights (one jitted gradient of the whole
tiny model, shared by the cases), the parameter counts, one pass as the
plain stack, the planted faults, the float8 reading, a ``dp`` mesh, and
the meshes that are refused."""

import dataclasses
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import equations, matmuls, same_bits

from ray_tpu import train
from ray_tpu.models import Ouro, OuroConfig
from ray_tpu.models import gpt2, ouro
from ray_tpu.models.ouro import ouro_loss_fn
from ray_tpu.parallel import make_mesh
from ray_tpu.util import tracing

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmark"))
from benchlib import manifest as mf  # noqa: E402

F32 = dict(dtype=jnp.float32)
RTOL = 2.0 ** -7        # the cell's limit
GROUPS = {"grad_norm_blocks": "^h_[0-9]+/", "grad_norm_head": "^lm_head/"}
PASS_KEYS = [f"lm_loss_ut_{t}" for t in (1, 2, 3, 4)]
HELD = {"loss", "grad_norm", *PASS_KEYS, "exit_mean_step", "exit_entropy",
        *GROUPS}


def _spec(cfg, **kw):
    return {**mf.load_builder("ouro").reference_spec(cfg),
            "grad_groups": GROUPS, **kw}


def _jittered(params, seed, by=0.1):
    """Every leaf moved off its initial value, so that the norms' scales
    and the gate's bias say something."""
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


def _by_path(tree) -> dict:
    return {"/".join(k.key for k in path): np.asarray(leaf) for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _numbers(loss, report, grads) -> dict:
    """The cell's keys from the program's side, as the step reports
    them."""
    import re
    leaves = _by_path(grads)
    out = {"loss": float(loss), **{k: float(v) for k, v in report.items()},
           "grad_norm": float(optax.global_norm(grads))}
    for name, pattern in GROUPS.items():
        out[name] = float(np.sqrt(sum(
            float((g.astype(np.float64) ** 2).sum())
            for path, g in leaves.items() if re.search(pattern, path))))
    return out


def _program(cfg, params, batch, model=None):
    model = model or Ouro(cfg)
    with jax.default_matmul_precision("highest"):
        (loss, report), grads = jax.jit(jax.value_and_grad(
            ouro_loss_fn(model, ce_chunk=32), has_aux=True))(params, batch)
    return _numbers(loss, report, grads), grads


def _off(got: dict, want: dict) -> dict:
    return {k: abs(got[k] - want[k]) / abs(want[k]) for k in HELD}


@pytest.fixture(scope="module")
def faultless():
    """(config, parameters, batch, the reference's numbers and gradient
    tree, the program's): one jitted gradient of the whole tiny model."""
    cfg = OuroConfig.tiny(remat=True, **F32)
    params = _jittered(Ouro(cfg).init_params(jax.random.key(0)), 0)
    batch = _batch(0, cfg)
    want, want_grads = mf.load_reference("ouro").loss_and_grads(
        params, batch, _spec(cfg))
    got, grads = _program(cfg, params, batch)
    return cfg, params, batch, want, want_grads, got, grads


# -- the system against the plain reference ----

def test_loss_every_gradient_leaf_and_every_reported_key_are_the_references(
        faultless):
    cfg, params, batch, want, want_grads, got, grads = faultless
    assert set(want) == HELD
    assert set(got) == HELD
    assert max(_off(got, want).values()) < 1e-4, _off(got, want)
    want_leaves = _by_path(want_grads)
    for name, leaf in _by_path(grads).items():
        scale = max(float(np.abs(want_leaves[name]).max()), 1e-3)
        np.testing.assert_allclose(leaf, want_leaves[name],
                                   atol=2e-4 * scale, err_msg=name)
    assert len(want_leaves) == len(jax.tree_util.tree_leaves(grads))
    # the four passes read differently, the distribution is one
    assert len({round(want[k], 4) for k in PASS_KEYS}) == 4
    assert 1.0 < want["exit_mean_step"] < 4.0
    assert 0.0 < want["exit_entropy"] < np.log(4.0)


def test_the_forward_pass_in_one_piece_is_the_references(faultless):
    cfg, params, batch, *_ = faultless
    with jax.default_matmul_precision("highest"):
        logits, gate = jax.jit(Ouro(cfg).apply)({"params": params},
                                                batch["tokens"])
    want_logits, want_lam = mf.load_reference("ouro").forward(
        params, batch["tokens"], _spec(cfg))
    assert logits.shape == (2, 4, cfg.seq_len, cfg.vocab_size)
    np.testing.assert_allclose(jnp.moveaxis(logits, 1, 0), want_logits,
                               atol=5e-5)
    np.testing.assert_allclose(jax.nn.sigmoid(jnp.moveaxis(gate, 1, 0)),
                               want_lam, atol=1e-6)
    p = jnp.exp(ouro.exit_distribution(gate))
    np.testing.assert_allclose(p.sum(1), 1.0, atol=1e-6)
    np.testing.assert_allclose(
        jnp.moveaxis(p, 1, 0),
        mf.load_reference("ouro").exit_distribution(want_lam), atol=1e-6)


def test_the_reference_takes_parameters_that_wait_on_the_host(faultless):
    """As the cell hands them over: numpy, a block's on the device while
    the block runs; with ``adamw`` the optimizer's first step too."""
    cfg, params, batch, want, *_ = faultless
    adamw = dict(learning_rate=3e-4, b1=0.9, b2=0.95, eps=1e-8,
                 weight_decay=0.1, clip_global_norm=1.0)
    got = mf.load_reference("ouro").loss_and_grad_norm(
        jax.device_get(params), batch, _spec(cfg, adamw=adamw))
    assert set(got) == HELD | {"update_norm"}
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-6)
    assert 0 < got["update_norm"] < 1


# -- the tree and the counts ----

def test_parameters_are_counted_as_the_configuration_says():
    """51,388,416 a layer at the published widths; ``L`` blocks in the
    tree whatever ``R``; the cell's cut 612,438,017."""
    pub = OuroConfig.ouro_2_6b()
    assert pub.layer_params() == {"attn": 16777216, "mlp": 34603008,
                                  "norms": 8192}
    assert sum(pub.layer_params().values()) == 51388416
    assert (pub.n_layer, pub.ut_steps, pub.n_embd, pub.n_head,
            pub.n_kv_head, pub.head_dim, pub.intermediate, pub.vocab_size,
            pub.seq_len, pub.rope_theta, pub.rms_eps) == (
        48, 4, 2048, 16, 16, 128, 5632, 49152, 65536, 1e6, 1e-6)
    assert OuroConfig.ouro_2_6b(n_layer=8).num_params() == 612438017
    assert pub.num_params() == 48 * 51388416 + 201326592 + 2048 + 2049
    trees = {}
    for r in (1, 4):
        cfg = OuroConfig.tiny(ut_steps=r, **F32)
        shapes = jax.eval_shape(Ouro(cfg).init_params, jax.random.key(0))
        assert sorted(shapes) == ["exit_gate", "h_0", "h_1", "lm_head",
                                  "norm_f", "wte"]
        assert sorted(shapes["h_0"]) == ["attn", "attn_norm",
                                         "attn_post_norm", "mlp", "mlp_norm",
                                         "mlp_post_norm"]
        assert sum(x.size for x in jax.tree_util.tree_leaves(
            shapes)) == cfg.num_params()
        trees[r] = jax.tree_util.tree_map(lambda x: x.shape, shapes)
    assert trees[1] == trees[4]
    with pytest.raises(ValueError, match="ut_steps"):
        OuroConfig.tiny(ut_steps=0)


def test_one_pass_is_the_one_pass_stack(faultless):
    """``R`` = 1: ``p_1`` = 1 and the entropy 0, so the loss is the plain
    mean cross-entropy of the one pass's logits and the gate gets no
    gradient."""
    _, params, batch, *_ = faultless
    cfg = OuroConfig.tiny(ut_steps=1, **F32)
    model = Ouro(cfg)
    (loss, report), grads = jax.jit(jax.value_and_grad(
        ouro_loss_fn(model, ce_chunk=32), has_aux=True))(params, batch)
    logits, _ = model.apply({"params": params}, batch["tokens"])
    assert float(loss) == pytest.approx(float(gpt2.cross_entropy_loss(
        logits[:, 0], batch["targets"])), rel=1e-5)
    assert float(loss) == pytest.approx(float(report["lm_loss_ut_1"]),
                                        rel=1e-6)
    assert float(report["exit_entropy"]) == 0.0
    assert float(report["exit_mean_step"]) == 1.0
    assert float(optax.global_norm(grads["exit_gate"])) == 0.0


# -- planted faults ----

def _norm_left_out_between_passes(mdl, h, angles):
    cfg = mdl.config
    for i in range(cfg.n_layer):
        h = ouro.Block(cfg, mdl.mesh, name=f"h_{i}")(h, angles)
    out = ouro._norm(cfg)(name="norm_f")(h)
    return h, (out, ouro.ExitGate(cfg, name="exit_gate")(out))


def _gradient_cut_between_passes(mdl, h, angles):
    return _one_pass(mdl, jax.lax.stop_gradient(h), angles)


def _gate_reads_the_unnormed_stream(mdl, h, angles):
    cfg = mdl.config
    for i in range(cfg.n_layer):
        h = ouro.Block(cfg, mdl.mesh, name=f"h_{i}")(h, angles)
    gate = ouro.ExitGate(cfg, name="exit_gate")(h)
    h = ouro._norm(cfg)(name="norm_f")(h)
    return h, (h, gate)


class _PostNormAfterTheAdd(nn.Module):
    config: OuroConfig
    mesh: object = None

    @nn.compact
    def __call__(self, x, angles):
        cfg = self.config
        norm = ouro._norm(cfg)
        x = norm(name="attn_post_norm")(x + ouro.Attention(
            cfg, self.mesh, name="attn")(norm(name="attn_norm")(x), angles))
        return norm(name="mlp_post_norm")(x + ouro.SwiGLU(
            cfg, name="mlp")(norm(name="mlp_norm")(x)))


def _last_pass_gated(gate):
    g = gate.astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-g), axis=1)
    before = jnp.pad(stay[:, :-1], ((0, 0), (1, 0), (0, 0)))
    return jax.nn.log_sigmoid(g) + before       # p_R = lambda_R S_{R-1}


def _p_detached(gate):
    return jax.lax.stop_gradient(_exit_distribution(gate))


def _rows_without_their_cotangent(*a, **kw):
    """The rows' values, and a backward that takes the mean of the
    cotangents for every row's (what a scalar interface would hand it)."""
    rows = _rows(*a, **kw)
    return jax.lax.stop_gradient(rows) + (
        rows.mean() - jax.lax.stop_gradient(rows.mean()))


_one_pass = ouro._one_pass
_exit_distribution = ouro.exit_distribution
_rows = gpt2.chunked_cross_entropy_rows
FAULTS = {
    "final_norm_left_out_between_passes":
        (ouro, "_one_pass", _norm_left_out_between_passes, {}),
    "gradient_cut_between_passes":
        (ouro, "_one_pass", _gradient_cut_between_passes, {}),
    "gate_reads_the_unnormed_stream":
        (ouro, "_one_pass", _gate_reads_the_unnormed_stream, {}),
    "post_norm_after_the_add": (ouro, "Block", _PostNormAfterTheAdd, {}),
    "last_pass_gated_not_the_remainder":
        (ouro, "exit_distribution", _last_pass_gated, {}),
    "p_detached_from_the_loss": (ouro, "exit_distribution", _p_detached, {}),
    "row_cotangent_left_out_of_the_loss":
        (gpt2, "chunked_cross_entropy_rows", _rows_without_their_cotangent,
         {}),
    "entropy_sign_turned": (None, None, None, {"exit_beta": -0.05}),
    "entropy_left_out": (None, None, None, {"exit_beta": 0.0}),
    "three_passes_for_four": (None, None, None, {"ut_steps": 3}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_comparison(faultless, monkeypatch, fault):
    """Each fault, planted in the program, moves at least one held key
    past twice the cell's limit (the entropy's term left out past the
    limit once: at ``beta`` 0.05 it is 1.0% of the loss here, and 0.54%
    at the cell's size, under the 2^-7 that 32 applications in bfloat16
    need: PERF.md section 7); ``row_cotangent_left_out`` leaves every
    value right and the gradient wrong."""
    cfg, params, batch, want, *_ = faultless
    where, name, what, changed = FAULTS[fault]
    if where is not None:
        monkeypatch.setattr(where, name, what)
    faulty = dataclasses.replace(cfg, remat=False, **changed)
    got, _ = _program(faulty, params, batch)
    if fault == "three_passes_for_four":
        got = {"lm_loss_ut_4": 0.0, **got}      # the key is not there
    off = _off(got, want)
    times = 1 if fault == "entropy_left_out" else 2
    assert max(off.values()) > times * RTOL, off
    if fault == "row_cotangent_left_out_of_the_loss":
        values = HELD - {"grad_norm", *GROUPS}
        assert max(off[k] for k in values) < 1e-5, off
        assert off["grad_norm_blocks"] > 2 * RTOL


class _AStackAPass(Ouro):
    """The passes unrolled, each with a stack, a final norm and a gate of
    its own (``p<t>_h_<i>``, ...): what the looped model is not."""

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.n_embd, name="wte",
                     dtype=cfg.dtype, param_dtype=cfg.param_dtype)(tokens)
        angles = ouro.rope_freqs(cfg.head_dim, tokens.shape[1],
                                 cfg.rope_theta)
        hs, gates = [], []
        for t in range(cfg.ut_steps):
            for i in range(cfg.n_layer):
                x = ouro.Block(cfg, name=f"p{t}_h_{i}")(x, angles)
            x = ouro._norm(cfg)(name=f"p{t}_norm_f")(x)
            hs.append(x)
            gates.append(ouro.ExitGate(cfg, name=f"p{t}_exit_gate")(x))
        return jnp.stack(hs, 1), jnp.stack(gates, 1)


def test_passes_with_parameters_of_their_own_hold_a_share_of_the_gradient(
        faultless):
    """The shared leaf's gradient is the sum over the four applications:
    given a copy a pass, the copies' gradients add up to the reference's
    leaf, and any one copy's alone fails the comparison."""
    cfg, params, batch, want, want_grads, *_ = faultless
    in_loop = [k for k in params if k not in ("wte", "lm_head")]
    own = {"wte": params["wte"], "lm_head": params["lm_head"],
           **{f"p{t}_{k}": params[k] for k in in_loop
              for t in range(cfg.ut_steps)}}
    got, grads = _program(cfg, own, batch, model=_AStackAPass(cfg))
    assert got["loss"] == pytest.approx(want["loss"], rel=1e-5)
    norms = np.zeros(cfg.ut_steps)
    for name, leaf in _by_path({k: want_grads[k] for k in in_loop}).items():
        copies = [_by_path(grads[f"p{t}_" + name.split("/")[0]])[
            name.split("/", 1)[1]] for t in range(cfg.ut_steps)]
        scale = max(float(np.abs(leaf).max()), 1e-3)
        np.testing.assert_allclose(sum(copies), leaf, atol=2e-4 * scale,
                                   err_msg=name)
        if name.startswith("h_"):
            norms += [float((c.astype(np.float64) ** 2).sum())
                      for c in copies]
    for one in np.sqrt(norms):
        assert abs(one - want["grad_norm_blocks"]) \
            > 2 * RTOL * want["grad_norm_blocks"]


def test_float8_operands_fail_at_least_one_key_of_the_cells(faultless):
    """The reference with its matmul operands rounded to
    ``float8_e4m3fn``, the precision under the configuration's bfloat16,
    is not correct at the cell's limit."""
    cfg, params, batch, want, *_ = faultless
    low = mf.load_reference("ouro").loss_and_grad_norm(
        params, batch, _spec(cfg, operand_dtype="float8_e4m3fn"))
    off = _off(low, want)
    assert max(off.values()) > RTOL, off


# -- meshes ----

def test_a_batch_sharded_over_dp_trains_as_one_device_does():
    cfg = OuroConfig.tiny(**F32)
    params = Ouro(cfg).init_params(jax.random.key(1))
    batch = _batch(1, cfg, rows=4)
    opt = optax.adamw(1e-3)
    out = {}
    for name, mesh in (("one", None), ("dp", make_mesh(
            {"dp": 4}, devices=jax.devices()[:4]))):
        model = Ouro(cfg, mesh=mesh)
        step = train.make_train_step(ouro_loss_fn(model, ce_chunk=32), opt,
                                     donate=False)
        state = train.init_train_state(params, opt, mesh)
        placed = batch if mesh is None else jax.device_put(
            batch, jax.sharding.NamedSharding(
                mesh, train.step.batch_spec(mesh)))
        for _ in range(2):
            state, metrics = step(state, placed)
        out[name] = (float(metrics["loss"]), float(metrics["grad_norm"]),
                     float(metrics["exit_mean_step"]),
                     jax.device_get(state.params))
    assert out["dp"][:3] == pytest.approx(out["one"][:3], rel=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(out["dp"][3]),
                    jax.tree_util.tree_leaves(out["one"][3])):
        np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("axis", ["sp", "tp"])
def test_a_split_sequence_or_split_lanes_are_refused_by_name(axis):
    cfg = OuroConfig.tiny(**F32)
    mesh = make_mesh({axis: 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match=f"{axis}=2"):
        Ouro(cfg, mesh=mesh).init_params(jax.random.key(0))


# -- what a recomputed block keeps ----

@pytest.mark.parametrize("remat, keeps", [
    (True, "mlp_down,mlp_up,mlp_gate[1:],attn_out,attn_lse"), (False, "")],
    ids=["recomputed", "kept_whole"])
def test_a_recomputed_block_says_what_its_policy_keeps(remat, keeps,
                                                       monkeypatch):
    """``blocks_remat_keeps`` beside ``blocks_remat``: the names a
    recomputed block's policy keeps (its MLP's matmul products, the last
    of them in the second half of the layers alone: ``name[k:]``; then the
    attention core's output and row statistics), and every block of the
    loop's body is a checkpoint that carries a policy; nothing where the
    blocks are not recomputed."""
    cfg = OuroConfig.tiny(remat=remat, **F32)
    model = Ouro(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    traced = jax.make_jaxpr(lambda p, t: model.apply(
        {"params": p}, t, return_hidden=True)[0])(
            params, _batch(0, cfg)["tokens"])
    assert notes["blocks_remat"] is remat
    assert notes["blocks_remat_keeps"] == keeps
    with_policy = [e for e in equations(traced.jaxpr)
                   if e.primitive.name == "remat2" and e.params["policy"]]
    assert len(with_policy) == (cfg.n_layer if remat else 0)


def _mlp_forwards(remat) -> int:
    """The MLPs' forward matmuls (``gate``, ``up``, ``down``) in the
    traced loss and gradient of the tiny model: the loop's body counts
    once, so ``3 n_layer`` where each runs once."""
    cfg = OuroConfig.tiny(remat=remat, **F32)
    model = Ouro(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    t, d, f = cfg.seq_len, cfg.n_embd, cfg.intermediate
    traced = jax.make_jaxpr(jax.value_and_grad(
        ouro_loss_fn(model, ce_chunk=16), has_aux=True))(
            params, _batch(0, cfg))
    return matmuls(traced, ((2, t, d), (d, f)), ((2, t, f), (f, d)))


def test_a_recomputed_block_runs_the_kept_matmuls_once(monkeypatch):
    """With ``remat`` the gradient holds the MLPs' forward matmuls once
    in the layers that keep their three products (``_mlp_keeps``;
    ``down``'s is read by the norm on the branch) and ``gate``'s once
    more in the layers that keep two (the first half).
    With every layer keeping all three the count is that of the stack
    kept whole; under the policy without any of the names each block
    holds one more forward of all three."""
    n = OuroConfig.tiny().n_layer
    assert (_mlp_forwards(False), _mlp_forwards(True)) == (
        3 * n, 3 * n + n // 2)
    monkeypatch.setattr(ouro, "_mlp_keeps", lambda cfg: (
        "mlp_down", "mlp_up", "mlp_gate"))
    assert _mlp_forwards(True) == 3 * n
    monkeypatch.setattr(ouro, "_mlp_keeps", lambda cfg: ())
    assert _mlp_forwards(True) == 6 * n


def test_a_recomputed_stack_gives_the_bits_of_the_one_kept_whole():
    """Loss, report and every gradient leaf with ``remat`` are the same
    bits as without: kept and recomputed products come from the same
    matmuls (``conftest.same_bits``: no ``jit``)."""
    got = {}
    for remat in (False, True):
        cfg = OuroConfig.tiny(remat=remat, **F32)
        model = Ouro(cfg)
        params = _jittered(model.init_params(jax.random.key(5)), 5)
        got[remat] = jax.value_and_grad(
            ouro_loss_fn(model, ce_chunk=16), has_aux=True)(
                params, _batch(5, cfg))
    assert len(jax.tree_util.tree_leaves(got[False])) > 30
    assert same_bits(got[True], got[False])
