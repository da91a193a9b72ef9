"""granite-4.0-h-micro (``models/granite.py``) under ``nn.remat``: the
recomputed stack against the one kept whole, what each block's policy
keeps (the scan's forward kernel and the flash forward once a layer, the
MLP's ``gate_up`` once), the step's report and notes, the scopes, the
meshes. The model against the reference is ``test_granite.py``'s."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import live_kernel_calls, matmuls
from test_granite import (
    F32, GROUPS, KEYS, _batch, _jittered, _leaves_with_names)

from ray_tpu import train
from ray_tpu.models import Granite, GraniteHybridConfig
from ray_tpu.models import granite as model_file
from ray_tpu.models.granite import granite_loss_fn
from ray_tpu.ops import remat, ssm
from ray_tpu.ops.pallas import program
from ray_tpu.ops.remat import IN_PROJ_PARTS
from ray_tpu.util import tracing

# ``blocks_remat_keeps`` with every name kept by every layer
KEEPS_NOTE = ("mlp_gate_up,mamba_z,mamba_xbc,mamba_dt,mixer_stream,"
              "ssd_scan_out,ssd_scan_states,attn_out,attn_lse")


def test_the_step_reports_the_keys_and_notes_what_the_layers_are(
        monkeypatch):
    cfg = GraniteHybridConfig.tiny(**F32, remat=True)
    model = Granite(cfg)
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    opt = optax.adamw(1e-3)
    step = train.make_train_step(granite_loss_fn(model, ce_chunk=16), opt,
                                 grad_groups=GROUPS)
    state = train.init_train_state(model.init_params(jax.random.key(0)), opt,
                                   None)
    _, metrics = step(state, _batch(0, cfg))
    assert set(KEYS) | {"lm_loss"} <= set(metrics)
    assert all(np.isfinite(float(metrics[k])) for k in KEYS)
    assert notes["layer_pattern"] == "MM*M"
    assert notes["attn_kind"] == "gqa_nope_scaled"
    assert notes["attn_scale"] == 0.125
    assert notes["ssm_tokens"] == 2 * 64 and notes["ssm_heads"] == 16
    assert (notes["ssm_groups"], notes["ssm_chunk"], notes["ssm_state"]) == (
        1, 16, 16)
    assert notes["ssm_path"] == "chunked_xla"
    assert notes["ssm_blocks_per_group"] == 1      # the XLA path's einsum
    assert notes["gate_norm_path"] == "xla" and notes["conv_path"] == "xla"
    assert notes["blocks_remat"] is True
    assert notes["blocks_remat_keeps"] == KEEPS_NOTE


def _kept_from(first: int, *names):
    """``granite._BLOCK_KEEPS`` with ``names`` kept from layer ``first``
    on alone (``ops/remat.py::layer_keeps``'s mapping)."""
    return {n: first if n in names else 0 for n in model_file._BLOCK_KEEPS}


@pytest.mark.parametrize("note, names", [
    (KEEPS_NOTE.replace("mlp_gate_up,", "mlp_gate_up[2:],"),
     ("mlp_gate_up",)),
    (KEEPS_NOTE.replace("mamba_z,mamba_xbc,mamba_dt",
                        "mamba_z[2:],mamba_xbc[2:],mamba_dt[2:]"),
     IN_PROJ_PARTS)],
    ids=["gate_up", "in_proj"])
def test_the_keeps_note_says_from_which_layer_a_name_is_kept(note, names):
    """A name that memory gives to the layers from 2 on alone reads
    ``name[2:]`` in the note and is listed by those layers' policies;
    the stream after the mixer and the scan's two are every layer's."""
    keeps = model_file._BLOCK_KEEPS
    assert remat.keeps_note(True, keeps) == KEEPS_NOTE
    every = set(remat.layer_keeps(keeps, 0))
    assert every == set(KEEPS_NOTE.split(",")) - {"attn_out", "attn_lse"}
    keeps = _kept_from(2, *names)
    assert remat.keeps_note(True, keeps) == note
    assert set(remat.layer_keeps(keeps, 1)) == every - set(names)
    assert set(remat.layer_keeps(keeps, 2)) == every


@pytest.mark.parametrize("remat", [True, False],
                         ids=["recomputed", "kept_whole"])
def test_every_recomputed_blocks_checkpoint_carries_a_policy(remat,
                                                             monkeypatch):
    """At the top of the traced stack: a checkpoint with a policy a
    block where the blocks are recomputed; kept whole, the XLA scan's own
    (``ops/ssm.py::mamba2_scan``, one a Mamba layer) and no block's."""
    cfg = GraniteHybridConfig.tiny(remat=remat, **F32)
    model = Granite(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    traced = jax.make_jaxpr(lambda p, t: model.apply(
        {"params": p}, t, return_hidden=True,
        mutable=["stats"])[0])(params, _batch(0, cfg)["tokens"])
    assert notes["blocks_remat"] is remat
    assert bool(notes["blocks_remat_keeps"]) is remat
    with_policy = [e for e in traced.jaxpr.eqns
                   if e.primitive.name == "remat2" and e.params["policy"]]
    assert len(with_policy) == (
        cfg.n_layer if remat else cfg.layer_types.count("mamba"))


def test_a_recomputed_stack_gives_the_numbers_of_the_one_kept_whole():
    """Loss, report and every gradient leaf with ``remat`` against
    without, each one jitted program: kept and recomputed products come
    from the same operations, and two programs may fuse them in another
    order (1e-6 of a leaf's largest entry)."""
    got = {}
    for remat in (False, True):
        cfg = GraniteHybridConfig.tiny(
            remat=remat, layer_types=("mamba", "attention"), **F32)
        model = Granite(cfg)
        params = _jittered(model.init_params(jax.random.key(5)), 5)
        got[remat] = jax.jit(jax.value_and_grad(
            granite_loss_fn(model, ce_chunk=16), has_aux=True))(
                params, _batch(5, cfg))
    (want, want_report), want_grads = got[False]
    (loss, report), grads = got[True]
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    assert float(report["mamba_out_rms"]) == pytest.approx(
        float(want_report["mamba_out_rms"]), rel=1e-6)
    want_leaves = dict(_leaves_with_names(want_grads))
    assert len(want_leaves) > 20
    for name, leaf in _leaves_with_names(grads):
        scale = max(float(np.abs(want_leaves[name]).max()), 1e-3)
        np.testing.assert_allclose(leaf, want_leaves[name],
                                   atol=1e-6 * scale, err_msg=name)


def test_a_recomputed_block_runs_its_gate_up_matmul_once(monkeypatch):
    """With ``remat`` the gradient holds as many ``gate_up`` forward
    matmuls as without it; with the name off the policy, one more a
    block."""
    def forwards(remat):
        cfg = GraniteHybridConfig.tiny(remat=remat, mlp_width=48, **F32)
        model = Granite(cfg)
        params = jax.eval_shape(model.init_params, jax.random.key(0))
        t, d, f = cfg.seq_len, cfg.n_embd, cfg.mlp_width
        traced = jax.make_jaxpr(jax.value_and_grad(
            granite_loss_fn(model, ce_chunk=16), has_aux=True))(
                params, _batch(0, cfg))
        return matmuls(traced, ((2, t, d), (d, 2 * f)))

    n = GraniteHybridConfig.tiny().n_layer
    assert (forwards(False), forwards(True)) == (n, n)
    monkeypatch.setattr(model_file, "_BLOCK_KEEPS",
                        _kept_from(n, "mlp_gate_up"))
    assert forwards(True) == 2 * n


def _off_the_policy(monkeypatch, what):
    """Take ``in_proj``'s names, or the stream's, off every layer's
    policy."""
    if what == "in_proj":
        monkeypatch.setattr(model_file, "_BLOCK_KEEPS", _kept_from(
            GraniteHybridConfig.tiny().n_layer, *IN_PROJ_PARTS))
    else:       # un-named: the policy's name is on no value
        monkeypatch.setattr(model_file, "checkpoint_name", lambda x, _: x)


@pytest.mark.parametrize("off, rows_by, by, whole, again", [
    ("in_proj", 64, 128 + 160 + 16, 3, 3),
    ("stream", 128, 64, 3, 3),
    ("stream", 64, 64, 2, 1)],
    ids=["mamba_in_proj", "mamba_out_proj", "attention_o"])
def test_a_recomputed_block_runs_its_mixers_projections_once(
        monkeypatch, off, rows_by, by, whole, again):
    """The forward matmuls of one shape in the gradient's jaxpr (three
    Mamba layers and one attention layer, whose ``q`` and ``o`` are both
    ``[64, 64]``): ``in_proj``'s and ``out_proj``'s are as many with
    ``remat`` as without, and ``o``'s with ``q``'s one more (``q`` is
    projected again, ``o`` is not); with ``in_proj``'s names off the
    policy, or the stream after the mixer not kept, ``again`` more."""
    def forwards(remat):
        cfg = GraniteHybridConfig.tiny(remat=remat, mlp_width=48, **F32)
        model = Granite(cfg)
        params = jax.eval_shape(model.init_params, jax.random.key(0))
        traced = jax.make_jaxpr(jax.value_and_grad(
            granite_loss_fn(model, ce_chunk=16), has_aux=True))(
                params, _batch(0, cfg))
        return matmuls(traced, ((2, cfg.seq_len, rows_by), (rows_by, by)))

    q_again = 1 if (rows_by, by) == (64, 64) else 0
    assert (forwards(False), forwards(True)) == (whole, whole + q_again)
    _off_the_policy(monkeypatch, off)
    assert forwards(True) == whole + q_again + again


# the scan's kernels, interpreted: 16 heads of 16 in one group (two head
# blocks), state 128, chunks of 128; flash attention is XLA's here

def _at_the_kernels_widths(remat):
    cfg = GraniteHybridConfig.tiny(
        layer_types=("mamba", "attention", "mamba"), n_embd=128,
        mamba_heads=16, mamba_head_dim=16, ssm_state=128, chunk=128,
        seq_len=256, n_head=4, n_kv_head=2, remat=remat, **F32)
    model = Granite(cfg)
    return model, granite_loss_fn(model, ce_chunk=64)


@functools.cache
def _kernel_case():
    model, _ = _at_the_kernels_widths(False)
    params = _jittered(model.init_params(jax.random.key(5)), 5, by=0.02)
    return params, _batch(5, model.config, rows=1)


@pytest.fixture
def on_the_kernels(monkeypatch):
    """``_at_the_kernels_widths`` with the scan on
    ``ops/pallas/ssd_scan.py``'s kernels, interpreted: ``scan_path`` is
    told what a TPU would answer."""
    from ray_tpu.ops.pallas import ssd_scan as kernels
    monkeypatch.setattr(ssm, "scan_path",
                        lambda *a, **kw: "pallas_chunked")
    monkeypatch.setattr(program, "batch_axes", lambda *a: ())
    monkeypatch.setattr(kernels, "ssd_scan", functools.partial(
        kernels.ssd_scan, interpret=True))
    return (_at_the_kernels_widths, *_kernel_case())


@pytest.mark.parametrize("remat, keeps, forwards", [
    (True, None, 1), (True, ("ssd_scan_out",), 2), (False, None, 1)],
    ids=["recomputed", "recomputed_without_the_states_name", "kept_whole"])
def test_the_scans_forward_kernel_runs_once_a_layer_under_remat(
        on_the_kernels, monkeypatch, remat, keeps, forwards):
    """In the gradient's jaxpr, with what nothing reads taken out as
    lowering takes it out: a recomputed block runs the scan's forward
    kernel once a Mamba layer (2 results: ``y`` and the entering states)
    and its backward once (6); a policy that loses one of the two names
    runs the forward twice. Kept whole, the ``custom_vjp`` holds its own
    residuals: once."""
    if keeps:       # the scan's states off the policy
        monkeypatch.setattr(model_file, "_BLOCK_KEEPS", tuple(
            n for n in model_file._BLOCK_KEEPS
            if not n.startswith("ssd_scan") or n in keeps))
    made, params, batch = on_the_kernels
    model, loss_fn = made(remat)
    traced = jax.make_jaxpr(jax.value_and_grad(loss_fn, has_aux=True))(
        params, batch)
    layers = model.config.layer_types.count("mamba")
    assert layers == 2
    assert live_kernel_calls(traced) == [2] * forwards * layers + [6] * layers


def test_recomputed_blocks_on_the_kernels_give_the_kept_blocks_numbers(
        on_the_kernels):
    made, params, batch = on_the_kernels
    (want, want_report), want_grads = jax.jit(jax.value_and_grad(
        made(False)[1], has_aux=True))(params, batch)
    (loss, report), grads = jax.jit(jax.value_and_grad(
        made(True)[1], has_aux=True))(params, batch)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    assert float(report["mamba_out_rms"]) == pytest.approx(
        float(want_report["mamba_out_rms"]), rel=1e-6)
    want_leaves = dict(_leaves_with_names(want_grads))
    for name, got in _leaves_with_names(grads):
        scale = max(float(np.abs(want_leaves[name]).max()), 1e-3)
        np.testing.assert_allclose(got, want_leaves[name],
                                   atol=1e-5 * scale, err_msg=name)


def test_the_flash_forward_is_traced_once_under_the_policy(monkeypatch):
    """The attention core on the flash kernels, interpreted, at the
    scale ``attention_multiplier``: in the recomputed layer's gradient
    the forward kernel once and the backward's, as in the layer kept
    whole; with the core's two names off the policy, the forward twice."""
    from ray_tpu.ops.pallas import flash_attention as kernel_fn
    scales = []

    def flash(q, k, v, scale):
        scales.append(scale)
        return kernel_fn(q, k, v, scale=scale, block=64, interpret=True)
    monkeypatch.setattr(model_file, "causal_attention", flash)

    def calls(remat):
        cfg = GraniteHybridConfig.tiny(
            layer_types=("attention",), n_embd=128, n_head=2, n_kv_head=1,
            mamba_heads=32, seq_len=128, attention_multiplier=1 / 64,
            remat=remat, **F32)
        model = Granite(cfg)
        params = jax.eval_shape(model.init_params, jax.random.key(0))
        return live_kernel_calls(jax.make_jaxpr(jax.value_and_grad(
            granite_loss_fn(model, ce_chunk=64), has_aux=True))(
                params, _batch(0, cfg, rows=1)))

    whole = calls(False)
    assert len(whole) >= 2 and calls(True) == whole
    assert set(scales) == {1 / 64}
    monkeypatch.setattr(remat, "remat_policy",
                        lambda *names: jax.checkpoint_policies
                        .save_only_these_names(*names))
    assert len(calls(True)) == len(whole) + 1


def test_every_kind_of_layer_has_its_own_scopes():
    cfg = GraniteHybridConfig.tiny(**F32, remat=True)
    model = Granite(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    batch = {k: jax.ShapeDtypeStruct((2, cfg.seq_len), jnp.int32)
             for k in ("tokens", "targets")}
    text = jax.jit(jax.grad(
        lambda p, b: granite_loss_fn(model, ce_chunk=16)(p, b)[0])).lower(
            params, batch).as_text(debug_info=True)
    for scope in (
            "embed", "blocks/h_0/mamba/in_proj", "blocks/h_0/mamba/conv",
            "blocks/h_0/mamba/scan", "blocks/h_0/mamba/gate_norm",
            "blocks/h_0/mamba/out_proj", "blocks/h_2/attn/qkv",
            "blocks/h_2/attn/repeat", "blocks/h_2/attn/core",
            "blocks/h_2/attn/out", "blocks/h_2/mlp/gate_up",
            "blocks/h_3/mlp/down", "blocks/norm_f", "loss"):
        assert scope in text, scope
    assert "h_2/mamba" not in text and "h_0/attn" not in text


def test_a_mesh_over_the_batch_gives_the_one_device_loss_and_sp_tp_refused():
    from ray_tpu.parallel.mesh import make_mesh
    cfg = GraniteHybridConfig.tiny(**F32)
    params = Granite(cfg).init_params(jax.random.key(0))
    batch = _batch(0, cfg)
    with jax.default_matmul_precision("highest"):
        want = granite_loss_fn(Granite(cfg), ce_chunk=16)(params, batch)
        mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
        got = jax.jit(granite_loss_fn(Granite(cfg, mesh=mesh),
                                      ce_chunk=16))(params, batch)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for axis in ("sp", "tp"):
        bad = make_mesh({axis: 2}, devices=jax.devices()[:2])
        with pytest.raises(NotImplementedError, match=f"{axis}=2"):
            granite_loss_fn(Granite(cfg, mesh=bad), ce_chunk=16)(params,
                                                                 batch)
