"""OLMoE through the program's own train path.

As ``builders/gpt2.py``: ``host_dataset`` runs in the driver process
(numpy only), ``build`` in the worker that holds the chips, and
everything the step is made of is the program's — ``Llama`` with
``LlamaConfig.olmoe_1b_7b`` (QK-norm, half-split RoPE, an untied head
and the dropless routed experts), ``llama_loss_fn`` (the LM loss plus
the two router losses, and a report of four scalars that rides in the
step's metrics), ``init_train_state``, ``make_train_step``,
``Dataset.iter_device_batches``. The parameters are made under
``jax.jit`` from the seed.
"""

from __future__ import annotations


SPARE_DISPATCHES = 32    # warm-up, the traced tail, the last in flight
WIDTHS = ("n_embd", "n_head", "n_kv_head", "head_dim", "num_experts",
          "top_k", "expert_width", "norm_topk_prob", "seq_len",
          "vocab_size")


def _batch(t: dict, chips: int) -> int:
    if t["steps_per_dispatch"] != 1:
        raise ValueError("the olmoe builder feeds one step a dispatch")
    return t["batch_per_chip"] * chips


def _flops_per_token(c) -> float:
    from benchlib import flops_moe
    return flops_moe.routed_decoder_train_flops_per_token(
        c.n_layer, c.n_embd, c.n_head, c.head_dim, c.n_kv_head,
        c.num_experts, c.top_k, c.expert_width, c.seq_len, c.vocab_size)


def model_config(cfg: dict, tiny: bool):
    """The program's preset at the file's depth; refused where one of
    its widths is not the file's."""
    from ray_tpu.models import LlamaConfig

    if tiny:
        # float32: at 32 routes an expert one route flipped by a bf16
        # activation would decide the load's comparison, and a
        # rehearsal checks the plumbing, not the types
        import jax.numpy as jnp
        return getattr(LlamaConfig, cfg["tiny"]["preset"])(
            dtype=jnp.float32)
    m = cfg["model"]
    mcfg = getattr(LlamaConfig, m["preset"])(n_layer=m["n_layer"])
    ran = {k: getattr(mcfg, k) for k in WIDTHS}
    want = {k: m[k] for k in WIDTHS}
    if ran != want:
        raise ValueError(f"the program's preset {ran} is not the "
                         f"configuration file's {want}")
    return mcfg


def reference_spec(cfg: dict, mcfg) -> dict:
    """What ``references/olmoe.py`` needs to know of the model."""
    return {"n_layer": mcfg.n_layer, "n_head": mcfg.n_head,
            "top_k": mcfg.top_k, "norm_topk_prob": mcfg.norm_topk_prob,
            "rms_eps": mcfg.rms_eps, "rope_theta": mcfg.rope_theta,
            "aux_loss_coef": cfg["reference"]["aux_loss_coef"],
            "z_loss_coef": cfg["reference"]["z_loss_coef"]}


def _least_step_s(cfg: dict, traffic: dict, tiny: bool) -> float:
    """No program takes less for a step (``builders/gpt2.py``): the
    required operations at the highest published peak."""
    if tiny:
        return traffic["least_step_ms"] / 1e3
    from benchlib import peaks

    mcfg = model_config(cfg, tiny)
    per_chip = (traffic["batch_per_chip"] * mcfg.seq_len
                * _flops_per_token(mcfg))
    return per_chip / max(p["bf16_flops"] for p in peaks.PEAKS.values())


def host_dataset(cfg: dict, traffic: dict, chips: int, seed: int,
                 tiny: bool, seconds: float) -> dict:
    """Uniform tokens over the vocabulary, from the seed; one pass,
    sized for a program that runs at the chip's published peak."""
    import math

    import numpy as np

    seq_len = cfg["tiny" if tiny else "model"]["seq_len"]
    vocab = (cfg["tiny"] if tiny else cfg["loss"])["uniform_over"]
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
    from ray_tpu.models import Llama
    from ray_tpu.models.llama import llama_loss_fn

    from benchlib import flops, flops_moe, manifest

    chips = mesh.devices.size
    batch = _batch(traffic, chips)
    mcfg = model_config(cfg, tiny)
    o = cfg["optimizer"]
    opt = optax.chain(
        optax.clip_by_global_norm(o["clip_global_norm"]),
        optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                    weight_decay=o["weight_decay"],
                    mu_dtype=jnp.dtype(o["mu_dtype"])))
    model = Llama(mcfg, mesh=mesh)

    def init_state():
        params = jax.jit(model.init_params)(jax.random.key(seed))
        return train.init_train_state(params, opt, mesh)

    step = train.make_train_step(
        llama_loss_fn(model, ce_chunk=cfg["ce_chunk"]), opt)

    def batches():
        yield from train.get_dataset_shard("train").iter_device_batches(
            batch, mesh)
        raise RuntimeError(
            "the dataset ran out before the window closed: the steps "
            f"took under {_least_step_s(cfg, traffic, tiny) * 1e3:.1f} ms,"
            " which the published peak does not allow")

    ref = manifest.load_reference(cfg["reference"]["module"])
    spec = reference_spec(cfg, mcfg)

    def keep_for_reference(state, first_batch):
        """The initial parameters, copied before the first dispatch
        donates them, and the first batch."""
        return {"params": jax.tree_util.tree_map(jnp.copy, state.params),
                "batch": first_batch}

    def reference(kept):
        """Runs beside the live train state; what the device peaked at
        by then goes to the worker's log (PERF.md keeps the figure)."""
        import sys
        out = ref.loss_and_grad_norm(kept["params"], kept["batch"], spec)
        peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in mesh.devices.flat), default=0)
        print(f"olmoe reference done; device peak {peak / 1e9:.2f} GB",
              file=sys.stderr, flush=True)
        return out

    uniform_over = (cfg["tiny"] if tiny else cfg["loss"])["uniform_over"]
    tokens = batch // chips * mcfg.seq_len
    return {
        "init_state": init_state, "step": step, "batches": batches,
        # the step reports all six numbers: its first dispatch is what
        # the reference is held against, no probe needed
        "keep_for_reference": keep_for_reference, "reference": reference,
        "samples_per_step": batch * mcfg.seq_len,
        "uniform_over": uniform_over,
        "flops_per_sample": _flops_per_token(mcfg),
        "kernel_cost_per_step": flops.flash_attention_train_cost(
            batch // chips, mcfg.n_head, mcfg.seq_len, mcfg.head_dim,
            mcfg.n_layer),
        "shapes": {"model": f"olmoe L{mcfg.n_layer} d{mcfg.n_embd} "
                            f"h{mcfg.n_head}x{mcfg.head_dim} "
                            f"e{mcfg.num_experts}x{mcfg.expert_width} "
                            f"top{mcfg.top_k} v{mcfg.vocab_size}",
                   "n_params": mcfg.num_params(), "seq_len": mcfg.seq_len,
                   "global_batch": batch,
                   "moe_cost_per_step": flops_moe.grouped_matmul_train_cost(
                       tokens, mcfg.top_k, mcfg.n_embd, mcfg.expert_width,
                       mcfg.num_experts, mcfg.n_layer)},
    }
