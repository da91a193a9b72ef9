"""GPT-2 through the program's own train path.

``host_dataset`` runs in the driver process (numpy only); ``build``
runs in the worker that holds the chips. Everything the step is made of
is the program's: ``GPT2``, ``gpt2_loss_fn``, ``init_train_state``,
``make_train_step``, ``Dataset.iter_device_batches``. The benchmark
only wires them together the way ``chip_smoke.py`` does, except that
the parameters are made under ``jax.jit`` from the seed.
"""

from __future__ import annotations


SPARE_DISPATCHES = 32    # warm-up, the traced tail, the last in flight


def _batch(t: dict, chips: int) -> int:
    if t["steps_per_dispatch"] != 1:
        raise ValueError(
            "the gpt2 builder feeds one step a dispatch; a fused "
            "K-step cell needs a stacked input path added with it")
    return t["batch_per_chip"] * chips


def _least_step_s(cfg: dict, traffic: dict, tiny: bool) -> float:
    """No program takes less for a step: the operations one chip's
    share of the batch needs (``benchlib/flops.py``) at the highest
    published peak in ``benchlib/peaks.py``. The one pass of data is
    sized by it, so a faster program never runs dry. A rehearsal's
    tiny model says its own floor in the traffic file's ``tiny``."""
    if tiny:
        return traffic["least_step_ms"] / 1e3
    from benchlib import flops, peaks

    m = cfg["model"]
    per_chip = traffic["batch_per_chip"] * m["seq_len"] \
        * flops.gpt2_train_flops_per_token(
            m["n_layer"], m["n_embd"], m["seq_len"],
            cfg["loss"]["uniform_over"])
    return per_chip / max(p["bf16_flops"] for p in peaks.PEAKS.values())


def _uniform_over(cfg: dict, tiny: bool) -> int:
    return (cfg["tiny"] if tiny else cfg["loss"])["uniform_over"]


def _model_config(cfg: dict, tiny: bool):
    from ray_tpu.models import GPT2Config

    m = cfg["model"]
    mcfg = getattr(
        GPT2Config, cfg["tiny"]["preset"] if tiny else m["preset"])()
    if not tiny:
        ran = {"n_layer": mcfg.n_layer, "n_embd": mcfg.n_embd,
               "n_head": mcfg.n_head, "seq_len": mcfg.seq_len,
               "vocab_size": mcfg.vocab_size}
        want = {k: m[k] for k in ran}
        if ran != want:
            raise ValueError(f"the program's preset {ran} is not the "
                             f"configuration file's {want}")
    return mcfg


def host_dataset(cfg: dict, traffic: dict, chips: int, seed: int,
                 tiny: bool, seconds: float) -> dict:
    """Uniform tokens over the real vocabulary, from the seed: every
    seed gives the same shapes and the same number of rows. One pass
    has to last the run (a second pass over a streamed shard crashes
    the worker, PERF.md section 7), so the data is sized for a program
    that runs at the chip's published peak (``_least_step_s``)."""
    import math

    import numpy as np

    seq_len = cfg["tiny" if tiny else "model"]["seq_len"]
    vocab = _uniform_over(cfg, tiny)
    rng = np.random.default_rng(seed)
    dispatches = (math.ceil(seconds / _least_step_s(cfg, traffic, tiny))
                  + SPARE_DISPATCHES)
    toks = rng.integers(0, vocab,
                        (dispatches * _batch(traffic, chips), seq_len),
                        dtype=np.int32)
    return {"tokens": toks, "targets": np.roll(toks, -1, 1)}


def build(cfg: dict, traffic: dict, mesh, seed: int, tiny: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.models import GPT2
    from ray_tpu.models.gpt2 import gpt2_loss_fn

    from benchlib import flops, manifest

    chips = mesh.devices.size
    batch = _batch(traffic, chips)
    mcfg = _model_config(cfg, tiny)
    o = cfg["optimizer"]
    opt = optax.adamw(o["learning_rate"], weight_decay=o["weight_decay"],
                      mu_dtype=jnp.dtype(o["mu_dtype"]))
    model = GPT2(mcfg, mesh=mesh)

    def init_state():
        params = jax.jit(model.init_params)(jax.random.key(seed))
        return train.init_train_state(params, opt, mesh)

    step = train.make_train_step(
        gpt2_loss_fn(model, ce_chunk=cfg["ce_chunk"]), opt)

    def batches():
        yield from train.get_dataset_shard("train").iter_device_batches(
            batch, mesh)
        raise RuntimeError(
            "the dataset ran out before the window closed: the steps "
            f"took under {_least_step_s(cfg, traffic, tiny) * 1e3:.1f} ms,"
            " which the published peak does not allow")

    ref = manifest.load_reference(cfg["reference"]["module"])

    def keep_for_reference(state, first_batch):
        """The initial parameters, copied before the first dispatch
        donates them, and the first batch."""
        return {"params": jax.tree_util.tree_map(jnp.copy, state.params),
                "batch": first_batch}

    def reference(kept):
        return ref.loss_and_grad_norm(
            kept["params"], kept["batch"], mesh, mcfg.n_layer,
            cfg["reference"]["micro_rows_per_chip"])

    uniform_over = _uniform_over(cfg, tiny)
    return {
        "init_state": init_state, "step": step, "batches": batches,
        # the step reports loss and grad_norm: its first dispatch is
        # what the reference is held against, no probe needed
        "keep_for_reference": keep_for_reference, "reference": reference,
        "samples_per_step": batch * mcfg.seq_len,
        "uniform_over": uniform_over,
        "flops_per_sample": flops.gpt2_train_flops_per_token(
            mcfg.n_layer, mcfg.n_embd, mcfg.seq_len, uniform_over),
        "kernel_cost_per_step": flops.flash_attention_train_cost(
            batch // chips, mcfg.n_head, mcfg.seq_len, mcfg.head_dim,
            mcfg.n_layer),
        "shapes": {"model": f"gpt2 L{mcfg.n_layer} d{mcfg.n_embd} "
                            f"h{mcfg.n_head}x{mcfg.head_dim} "
                            f"v{mcfg.vocab_size}",
                   "n_params": mcfg.num_params(), "seq_len": mcfg.seq_len,
                   "global_batch": batch},
    }
