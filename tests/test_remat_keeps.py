"""What a recomputed block keeps, model by model, against
``ops/remat.py``: a policy that lists a name no value of the block
carries keeps nothing for it, and says nothing. So every name a model's
literal lists is looked for among the ``name`` equations of its traced
loss and gradient (the eight presets whose blocks are recomputed in a
cell, at test size), and ``blocks_remat_keeps`` reads what it read when
each model rendered it itself (PR 69's parent).

The tiny presets run XLA's paths here, and six names are the kernels'
forward rules' alone: those a model lists are named in
``ON_THE_KERNELS``, exactly, and the rules themselves are traced in the
second test."""

import jax
import jax.numpy as jnp
import pytest
from conftest import equations

from ray_tpu import models as M
from ray_tpu.models import (
    granite, joyai, kimi_linear, laguna, ouro, phi4flash, qwen3_next)
from ray_tpu.ops import remat
from ray_tpu.ops.remat import (
    ATTN_LSE, ATTN_OUT, KDA_SCAN_OUT, KDA_SCAN_STATES, MAMBA1_SCAN_OUT,
    MAMBA1_SCAN_STATES, MAPS_KEEPS, ROUTER_COUNTS, ROUTER_EXPERTS, ROUTER_LSE,
    ROUTER_WEIGHTS, SSD_SCAN_OUT, SSD_SCAN_STATES)
from ray_tpu.util import tracing

ROUTERS = ("moe_router_logits,moe_router_experts,moe_router_weights,"
           "moe_router_counts,moe_router_lse")
# the softmax router's XLA lines take ``top_k``'s own values: its choice
# is named by the kernel pair alone (``ops/moe.py::_route``)
SOFTMAX_CHOICE = {ROUTER_EXPERTS, ROUTER_WEIGHTS, ROUTER_LSE}
# the sigmoid router has no logsumexp: ``ROUTER_KEEPS``'s fifth name is
# on no value of such a model, on either path (PR 68 listed it for all)
NO_LSE = {ROUTER_LSE}

# preset -> (model, config preset, loss, the model's literal,
#            ``blocks_remat_keeps`` at the parent, what of the literal
#            the XLA paths of this preset do not name)
PRESETS = {
    "granite": (
        M.Granite, M.GraniteHybridConfig.tiny, granite.granite_loss_fn,
        lambda cfg: granite._BLOCK_KEEPS,
        "mlp_gate_up,mamba_z,mamba_xbc,mamba_dt,mixer_stream,ssd_scan_out,"
        "ssd_scan_states,attn_out,attn_lse",
        {SSD_SCAN_OUT, SSD_SCAN_STATES}),
    "kimi_linear": (
        M.KimiLinear, M.KimiLinearConfig.tiny,
        kimi_linear.kimi_linear_loss_fn,
        lambda cfg: kimi_linear._BLOCK_KEEPS,
        f"kda_gated_out,kda_scan_out,kda_scan_states,{ROUTERS},mixer_in_proj,"
        "mixer_stream,mlp_gate,mlp_up,attn_out,attn_lse",
        {KDA_SCAN_OUT, KDA_SCAN_STATES} | NO_LSE),
    "laguna": (
        M.Laguna, M.LagunaConfig.tiny, laguna.laguna_loss_fn,
        lambda cfg: laguna._BLOCK_KEEPS,
        f"{ROUTERS},attn_out_proj,attn_q,attn_k,attn_v,mlp_gate,mlp_up,"
        "attn_out,attn_lse",
        NO_LSE),
    "ouro": (
        M.Ouro, M.OuroConfig.tiny, ouro.ouro_loss_fn, ouro._mlp_keeps,
        "mlp_down,mlp_up,mlp_gate[1:],attn_out,attn_lse", set()),
    "phi4flash": (
        M.Phi4Flash, M.Phi4FlashConfig.tiny, phi4flash.phi4flash_loss_fn,
        lambda cfg: phi4flash._BLOCK_KEEPS,
        "mlp_gate_up,mixer_in_proj,mixer_stream,attn_q,attn_k,attn_v,"
        "mamba1_scan_out,mamba1_scan_states,attn_out,attn_lse",
        {MAMBA1_SCAN_OUT, MAMBA1_SCAN_STATES}),
    "qwen3_next": (
        M.Qwen3Next, M.Qwen3NextConfig.tiny, qwen3_next.qwen3_next_loss_fn,
        lambda cfg: qwen3_next._BLOCK_KEEPS,
        f"{ROUTERS},mixer_out_proj,gdn_gated_out,kda_scan_out,"
        "kda_scan_states,gdn_in_proj,attn_out,attn_lse",
        {KDA_SCAN_OUT, KDA_SCAN_STATES} | SOFTMAX_CHOICE),
    "xing": (
        M.JoyAI, M.JoyAIConfig.tiny_xing, joyai.joyai_loss_fn, joyai._keeps,
        f"{ROUTERS},hc_maps_pre,hc_maps_post,hc_maps_res,hc_maps_m,"
        "hc_maps_r,mixer_out_proj,mlp_down,moe_routed_out,mlp_gate,mlp_up,"
        "attn_out,attn_lse",
        set(MAPS_KEEPS) | NO_LSE),
    "joyai": (
        M.JoyAI, M.JoyAIConfig.tiny, joyai.joyai_loss_fn, joyai._keeps,
        f"{ROUTERS},attn_out,attn_lse", NO_LSE),
}


def _names(traced) -> set[str]:
    return {e.params["name"] for e in equations(traced.jaxpr)
            if e.primitive.name == "name"}


@pytest.mark.parametrize("preset", list(PRESETS))
def test_every_listed_name_is_on_a_value_and_the_note_reads_as_it_did(
        preset, monkeypatch):
    cls, config, loss_fn, literal, note, on_the_kernels = PRESETS[preset]
    cfg = config(remat=True, dtype=jnp.float32)
    model = cls(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    batch = {k: jax.ShapeDtypeStruct((2, cfg.seq_len), jnp.int32)
             for k in ("tokens", "targets")}
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)

    def loss(params, batch):
        out = loss_fn(model, ce_chunk=16)(params, batch)
        return out[0] if isinstance(out, tuple) else out

    carried = _names(jax.make_jaxpr(jax.grad(loss))(params, batch))
    assert notes["blocks_remat"] is True
    assert notes["blocks_remat_keeps"] == note
    listed = remat._first_layers(literal(cfg))
    assert note == remat.keeps_note(True, literal(cfg))
    # every layer index is one of the model's
    assert all(0 <= first < cfg.n_layer for first in listed.values())
    assert set(listed) - carried == on_the_kernels
    # and every name is one of the table's
    table = [v for k, v in vars(remat).items() if k.isupper()]
    every = {n for v in table for n in ((v,) if isinstance(v, str) else v)}
    assert set(listed) <= every


# what PR 70 listed, a model: (the module, its literal's attribute, the
# names)
LISTED_IN_PR_70 = {
    "kimi_linear": (kimi_linear, "_BLOCK_KEEPS", (
        "mixer_in_proj", "mixer_stream", "mlp_gate", "mlp_up")),
    "phi4flash": (phi4flash, "_BLOCK_KEEPS", (
        "mixer_in_proj", "mixer_stream", "attn_q", "attn_k", "attn_v")),
    "xing": (joyai, "_UNDER_MAPS", (
        "mixer_out_proj", "mlp_down", "moe_routed_out", "mlp_gate",
        "mlp_up"))}


@pytest.mark.parametrize("preset", list(LISTED_IN_PR_70))
def test_a_newly_listed_name_is_kept_and_read(preset, monkeypatch):
    """Kept *and read*: a policy keeps a name's own result, and a name
    nothing in the backward pass reads (one behind an operation whose
    backward reads that operation's own output, or on a product that is
    only added to the stream) is pruned from the gradient's residuals
    with the rest of what nobody uses. So with any one of the names PR
    70 listed taken off the model's literal,
    ``jax._src.ad_checkpoint.saved_residuals`` of the tiny model's loss
    holds fewer arrays beside its arguments than with the literal as it
    is."""
    from jax._src.ad_checkpoint import saved_residuals
    cls, config, loss_fn, literal, _, _ = PRESETS[preset]
    module, attribute, names = LISTED_IN_PR_70[preset]
    cfg = config(remat=True, dtype=jnp.float32)
    model = cls(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    batch = {k: jax.ShapeDtypeStruct((2, cfg.seq_len), jnp.int32)
             for k in ("tokens", "targets")}

    def kept() -> int:
        def loss(params, batch):
            out = loss_fn(model, ce_chunk=16)(params, batch)
            return out[0] if isinstance(out, tuple) else out
        # the arguments are held whoever reads them (a projection's bias
        # is read by its recomputation alone)
        return sum("from the argument" not in why
                   for _, why in saved_residuals(loss, params, batch))

    whole = getattr(module, attribute)
    assert set(names) <= set(whole) <= set(literal(cfg))
    with_all = kept()
    for name in names:
        monkeypatch.setattr(module, attribute,
                            tuple(n for n in whole if n != name))
        assert name not in literal(cfg)
        assert kept() < with_all, name


def _scan(kernels, out, states):
    def trace():
        x = jnp.ones((1, 256, 16, 16), jnp.float32)
        dt = jnp.ones((1, 256, 16), jnp.float32)
        bc = jnp.ones((1, 256, 1, 128), jnp.float32)
        return jax.make_jaxpr(jax.grad(lambda x: kernels.ssd_scan(
            x, dt, -jnp.ones((16,)), bc, bc, jnp.ones((16,)), chunk=256,
            interpret=True).sum()))(x)
    return trace, {out, states}


def _delta(gdn: bool):
    from ray_tpu.ops.pallas import kda_scan as kernels

    def trace():
        q = jnp.ones((1, 128, 2, 128), jnp.float32)
        g = -jnp.ones((1, 128, 2) if gdn else q.shape, jnp.float32)
        beta = jnp.ones((1, 128, 2), jnp.float32)
        scan = kernels.gdn_scan if gdn else kernels.kda_scan
        return jax.make_jaxpr(jax.grad(lambda q: scan(
            q, q, q, g, beta, interpret=True).sum()))(q)
    return trace, {KDA_SCAN_OUT, KDA_SCAN_STATES}


def _maps():
    from ray_tpu.ops.pallas import hc_maps as kernels

    def trace():
        n, d = 4, 8
        x = jnp.ones((1, 1024, n * d), jnp.float32)
        phi = jnp.ones((n * d, n * n + 2 * n), jnp.float32)
        return jax.make_jaxpr(jax.grad(lambda x: sum(
            m.sum() for m in kernels.hc_maps(
                x, phi, jnp.ones((n * n + 2 * n,)), jnp.ones((3,)), n=n,
                iters=2, eps=1e-6, clamp=5.0, interpret=True))))(x)
    return trace, set(MAPS_KEEPS)


def _router(activation: str, names):
    from ray_tpu.ops.pallas import router_choice as kernels

    def trace():
        logits = jnp.ones((256, 128), jnp.float32)
        bias = None if activation == "softmax" else jnp.zeros((128,))
        return jax.make_jaxpr(jax.grad(lambda z: kernels.router_choice(
            z, bias, top_k=2, activation=activation,
            interpret=True)[0].sum()))(logits)
    return trace, names


def _flash():
    from ray_tpu.ops.pallas.flash_attention import flash_attention

    def trace():
        q = jnp.ones((1, 128, 2, 64), jnp.float32)
        return jax.make_jaxpr(jax.grad(lambda q: flash_attention(
            q, q, q, block=64, interpret=True).sum()))(q)
    return trace, {ATTN_OUT, ATTN_LSE}


def _latent():
    from ray_tpu.ops.mla import UpProjections, latent_attention

    def trace():
        c = jnp.ones((1, 128, 32), jnp.float32)
        up = UpProjections(*(jnp.ones((32, 2 * w), jnp.float32)
                             for w in (128, 64, 128, 128)))
        return jax.make_jaxpr(jax.grad(lambda c: latent_attention(
            c, c, jnp.ones((1, 128, 64)), up, None, n_head=2,
            interpret=True).sum()))(c)
    return trace, {ATTN_OUT, ATTN_LSE}


def _ssd():
    from ray_tpu.ops.pallas import ssd_scan
    return _scan(ssd_scan, SSD_SCAN_OUT, SSD_SCAN_STATES)


def _mamba1():
    from ray_tpu.ops.pallas import mamba1_scan as kernels

    def trace():
        x = jnp.ones((1, 64, 1024), jnp.float32)
        bc = jnp.ones((1, 64, 8), jnp.float32)
        return jax.make_jaxpr(jax.grad(lambda x: kernels.mamba1_scan(
            x, x, -jnp.ones((1024, 8)), bc, bc, jnp.ones((1024,)),
            interpret=True).sum()))(x)
    return trace, {MAMBA1_SCAN_OUT, MAMBA1_SCAN_STATES}


RULES = {
    "ssd_scan": _ssd, "mamba1_scan": _mamba1,
    "kda_scan": lambda: _delta(False),
    "gdn_scan": lambda: _delta(True), "hc_maps": _maps,
    "router_softmax": lambda: _router("softmax", {
        ROUTER_EXPERTS, ROUTER_WEIGHTS, ROUTER_COUNTS, ROUTER_LSE}),
    "router_sigmoid": lambda: _router("sigmoid", {
        ROUTER_EXPERTS, ROUTER_WEIGHTS, ROUTER_COUNTS}),
    "flash_core": _flash, "latent_core": _latent}


@pytest.mark.parametrize("rule", list(RULES))
def test_a_kernels_forward_rule_names_its_results(rule):
    """The names the tiny presets cannot show: each kernel's forward
    rule, traced under differentiation (interpreted, not run), carries
    exactly its names of ``ops/remat.py``'s table."""
    trace, names = RULES[rule]()
    assert _names(trace()) == names
