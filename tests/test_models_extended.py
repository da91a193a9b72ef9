"""Llama / MoE / ViT model-family tests on the virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import (
    Llama, LlamaConfig, MoEConfig, MoETransformer, ViT, ViTConfig,
)
from ray_tpu.models.llama import apply_rope, llama_loss_fn, rope_freqs
from ray_tpu.models.moe import moe_loss_fn
from ray_tpu.models.vit import vit_loss_fn
from ray_tpu.parallel import make_mesh
from ray_tpu.train import init_train_state, make_train_step, shard_batch


def _lm_batch(cfg, batch=8, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size,
                          (batch, cfg.seq_len)).astype(np.int32)
    return {"tokens": tokens, "targets": np.roll(tokens, -1, 1)}


# ---------- llama ----------

def test_rope_preserves_norm():
    angles = rope_freqs(16, 32, 10000.0)
    x = jax.random.normal(jax.random.key(0), (2, 32, 4, 16))
    rx = apply_rope(x, angles)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(rx), axis=-1), rtol=1e-5)
    # position 0 is unrotated
    np.testing.assert_allclose(np.asarray(x[:, 0]),
                               np.asarray(rx[:, 0]), rtol=1e-6)


def test_llama_forward_and_gqa():
    cfg = LlamaConfig.tiny()          # n_head=4, n_kv_head=2 (GQA)
    model = Llama(cfg)
    params = model.init_params(jax.random.key(0))
    batch = _lm_batch(cfg, batch=2)
    logits = model.apply({"params": params}, batch["tokens"])
    assert logits.shape == (2, cfg.seq_len, cfg.vocab_size)
    # K/V projections are genuinely grouped (smaller than Q).
    assert params["h_0"]["attn"]["k"]["kernel"].shape[1] == \
        cfg.n_kv_head * cfg.head_dim


def test_llama_train_step_loss_decreases():
    cfg = LlamaConfig.tiny()
    mesh = make_mesh({"dp": 4, "tp": 2})
    model = Llama(cfg, mesh=mesh)
    params = model.init_params(jax.random.key(0))
    opt = optax.adamw(1e-2)
    state = init_train_state(params, opt, mesh)
    step = make_train_step(llama_loss_fn(model), opt)
    batch = shard_batch(_lm_batch(cfg), mesh)
    state, m0 = step(state, batch)
    for _ in range(8):
        state, m = step(state, batch)
    assert float(m["loss"]) < float(m0["loss"])


def test_llama_ulysses_matches_dense():
    mesh = make_mesh({"dp": 2, "sp": 4})
    cfg_d = LlamaConfig.tiny(attn_impl="dense")
    cfg_u = LlamaConfig.tiny(attn_impl="ulysses")
    m_dense = Llama(cfg_d)
    m_uly = Llama(cfg_u, mesh=mesh)
    params = m_dense.init_params(jax.random.key(0))
    batch = _lm_batch(cfg_d, batch=4)
    logits_d = m_dense.apply({"params": params}, batch["tokens"])
    sharded = shard_batch(batch, mesh, seq_sharded=True)
    logits_u = jax.jit(
        lambda p, t: m_uly.apply({"params": p}, t)
    )(params, sharded["tokens"])
    np.testing.assert_allclose(np.asarray(logits_u),
                               np.asarray(logits_d),
                               atol=2e-2, rtol=2e-2)


# ---------- moe ----------

def test_moe_forward_and_loss():
    cfg = MoEConfig.tiny()
    model = MoETransformer(cfg)
    params = model.init_params(jax.random.key(0))
    batch = _lm_batch(cfg, batch=2)
    logits = model.apply({"params": params}, batch["tokens"])
    assert logits.shape == (2, cfg.seq_len, cfg.vocab_size)
    loss = moe_loss_fn(model)(params,
                              {k: jnp.asarray(v)
                               for k, v in batch.items()})
    assert np.isfinite(float(loss))
    # expert params exist on MoE blocks only (every 2nd block)
    assert "moe" in params["h_1"] and "mlp" in params["h_0"]


def test_moe_train_step_with_ep_mesh():
    cfg = MoEConfig.tiny()
    mesh = make_mesh({"dp": 2, "ep": 4})
    model = MoETransformer(cfg, mesh=mesh)
    params = model.init_params(jax.random.key(0))
    opt = optax.adamw(1e-2)
    state = init_train_state(params, opt, mesh)
    # experts dim really sharded over ep
    w_up = state.params["h_1"]["moe"]["w_up"]
    assert "ep" in str(w_up.sharding.spec)
    step = make_train_step(moe_loss_fn(model), opt)
    batch = shard_batch(_lm_batch(cfg), mesh)
    state, m0 = step(state, batch)
    for _ in range(8):
        state, m = step(state, batch)
    assert float(m["loss"]) < float(m0["loss"])


# ---------- the loss on a mesh, all three language models ----------

@pytest.mark.parametrize("family, axes", [
    ("llama", {"dp": 4}), ("llama", {"dp": 2, "sp": 2}),
    ("moe", {"dp": 4}), ("moe", {"dp": 2, "ep": 2})],
    ids=["llama-dp4", "llama-dp2-sp2", "moe-dp4", "moe-dp2-ep2"])
def test_lm_loss_on_a_mesh_equals_the_loss_without(family, axes):
    """``llama_loss_fn`` and ``moe_loss_fn`` hand their model's mesh to
    the chunked cross-entropy, which then scans each chip's rows: the
    loss and the head's gradient are those of the model with no mesh
    (up to the attention path, which the mesh changes too)."""
    model_cls, cfg, loss_of = {
        "llama": (Llama, LlamaConfig.tiny(), llama_loss_fn),
        "moe": (MoETransformer, MoEConfig.tiny(), moe_loss_fn)}[family]
    n = int(np.prod(list(axes.values())))
    mesh = make_mesh(axes, devices=jax.devices()[:n])
    plain = model_cls(cfg)
    params = plain.init_params(jax.random.key(0))
    batch = _lm_batch(cfg, batch=4)
    want, want_g = jax.jit(jax.value_and_grad(
        loss_of(plain, ce_chunk=64)))(params, batch)
    got, got_g = jax.jit(jax.value_and_grad(
        loss_of(model_cls(cfg, mesh=mesh), ce_chunk=64)))(
            params, shard_batch(batch, mesh, seq_sharded=True))
    np.testing.assert_allclose(float(got), float(want), rtol=2e-3)
    np.testing.assert_allclose(
        np.asarray(got_g["wte"]["embedding"], np.float32),
        np.asarray(want_g["wte"]["embedding"], np.float32),
        rtol=5e-2, atol=2e-4)


# ---------- vit ----------

def test_vit_forward_and_train():
    cfg = ViTConfig.tiny()
    model = ViT(cfg)
    params = model.init_params(jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {
        "images": rng.standard_normal(
            (8, cfg.image_size, cfg.image_size, 3)).astype(np.float32),
        "labels": rng.integers(0, cfg.num_classes, 8).astype(np.int32),
    }
    logits = model.apply({"params": params}, batch["images"])
    assert logits.shape == (8, cfg.num_classes)

    mesh = make_mesh({"dp": 8})
    model_m = ViT(cfg, mesh=mesh)
    opt = optax.adamw(3e-3)
    state = init_train_state(params, opt, mesh)
    step = make_train_step(vit_loss_fn(model_m), opt)
    sbatch = shard_batch(batch, mesh)
    state, m0 = step(state, sbatch)
    for _ in range(8):
        state, m = step(state, sbatch)
    assert float(m["loss"]) < float(m0["loss"])
