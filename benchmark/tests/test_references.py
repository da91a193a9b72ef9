"""The plain float32 references against the program's own loss
functions at the models' tiny presets, on the CPU: run in float32 the
program lands on the reference to rounding; in bfloat16, as it trains,
well inside the configurations' rtol; and a loss with a term dropped
does not."""

import json
import os
import sys

import pytest

from benchlib import manifest as mf

sys.path.insert(0, mf.ROOT)
RTOL = 2 ** -7


def _config(name):
    return mf.load_json(os.path.join(mf.BENCH_DIR, "configs", f"{name}.json"))


def test_the_configurations_name_their_reference_and_tolerance():
    for name in ("gpt2-124m", "resnet50"):
        ref = _config(name)["reference"]
        assert ref["rtol"] == RTOL
        assert hasattr(mf.load_reference(ref["module"]), "loss_and_grad_norm")


@pytest.fixture(scope="module")
def jax_cpu():
    import jax
    if jax.default_backend() != "cpu":
        pytest.skip("a CPU test")
    return jax


def _rel(got, want):
    return {k: abs(got[k] - want[k]) / abs(want[k]) for k in want}


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", RTOL)])
def test_gpt2_reference(jax_cpu, dtype, tol):
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from ray_tpu.models import GPT2, GPT2Config
    from ray_tpu.models.gpt2 import gpt2_loss_fn

    jax = jax_cpu
    cfg = GPT2Config.tiny(dtype=jnp.dtype(dtype))
    model = GPT2(cfg)
    params = jax.jit(model.init_params)(jax.random.key(5))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, cfg.seq_len), dtype=np.int32))
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, 1)}
    loss, grads = jax.jit(jax.value_and_grad(
        gpt2_loss_fn(model, ce_chunk=128)))(params, batch)
    program = {"loss": float(loss),
               "grad_norm": float(optax.global_norm(grads))}
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    ref = mf.load_reference("gpt2")
    plain = ref.loss_and_grad_norm(params, batch, mesh, cfg.n_layer, 2)
    assert max(_rel(program, plain).values()) < tol, (program, plain)
    # micro-batches of any size give the batch's mean
    whole = ref.loss_and_grad_norm(params, batch, mesh, cfg.n_layer, 8)
    assert max(_rel(whole, plain).values()) < 1e-5
    # a dropped term is seen: no position embedding
    params["wpe"]["embedding"] = jnp.zeros_like(params["wpe"]["embedding"])
    loss, grads = jax.jit(jax.value_and_grad(
        gpt2_loss_fn(model, ce_chunk=128)))(params, batch)
    broken = {"loss": float(loss),
              "grad_norm": float(optax.global_norm(grads))}
    assert max(_rel(broken, plain).values()) > RTOL


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", RTOL)])
def test_resnet_reference(jax_cpu, dtype, tol):
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import ResNet, ResNet50Config
    from ray_tpu.models.resnet import resnet_loss_fn

    jax = jax_cpu
    cfg = ResNet50Config.tiny(dtype=jnp.dtype(dtype), stage_sizes=(1, 2))
    model = ResNet(cfg)
    v = jax.jit(lambda k: model.init_variables(k, 32))(jax.random.key(3))
    batch = {"image": jax.random.normal(jax.random.key(1), (16, 32, 32, 3)),
             "label": jax.random.randint(jax.random.key(2), (16,), 0, 10)}
    (loss, _), grads = jax.jit(jax.value_and_grad(
        resnet_loss_fn(model), has_aux=True))(
            v["params"], v["batch_stats"], batch)
    program = {"loss": float(loss),
               "grad_norm": float(optax.global_norm(grads))}
    plain = mf.load_reference("resnet").loss_and_grad_norm(
        v["params"], batch, tuple(cfg.stage_sizes))
    assert max(_rel(program, plain).values()) < tol, (program, plain)


def test_the_host_data_lasts_a_program_at_the_published_peak():
    builder = mf.load_builder("gpt2")
    cfg = _config("gpt2-124m")
    traffic = mf.load_json(mf.traffic_path("dp4-b128-t1024"))
    # 32 x 1,024 tokens a chip x 6 x 132.97 M operations over 197e12
    assert builder._least_step_s(cfg, traffic, False) == pytest.approx(
        0.1327, rel=1e-3)
    tiny = mf.effective_traffic(traffic, True)
    assert builder._least_step_s(cfg, tiny, True) == tiny["least_step_ms"] / 1e3
    data = builder.host_dataset(cfg, tiny, 4, 7, True, 0.1)
    # ceil(0.1 s / 2 ms) + 32 spare dispatches of 4 x 4 rows
    assert data["tokens"].shape == ((50 + 32) * 16, cfg["tiny"]["seq_len"])
    assert "max_dispatches_per_s" not in json.dumps(traffic)
