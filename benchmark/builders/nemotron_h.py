"""Nemotron-H through the program's own train path.

As ``builders/olmoe.py``: ``host_dataset`` runs in the driver process
(numpy only), ``build`` in the worker that holds the chips, and
everything the step is made of is the program's — ``NemotronH`` with
``NemotronHConfig.nemotron_3_nano_30b_a3b`` cut as the configuration
file says (nine layers of the pattern, the held experts, the slice of
the vocabulary), ``nemotron_h_loss_fn`` (the LM loss and a report of
three scalars that rides in the step's metrics), ``init_train_state``,
``make_train_step``, ``Dataset.iter_device_batches``. The parameters
are made under ``jax.jit`` from the seed (``make_params``: the
initialisers, then the routers' selection biases brought into balance).
"""

from __future__ import annotations


SPARE_DISPATCHES = 32    # warm-up, the traced tail, the last in flight
WIDTHS = ("pattern", "n_embd", "mamba_heads", "mamba_head_dim",
          "ssm_state", "ssm_groups", "conv_kernel", "chunk", "n_head",
          "n_kv_head", "head_dim", "positions", "num_experts",
          "experts_held", "top_k", "expert_width", "shared_width",
          "norm_topk_prob", "route_scale", "seq_len", "vocab_size")


def _batch(t: dict, chips: int) -> int:
    if t["steps_per_dispatch"] != 1:
        raise ValueError("the nemotron_h builder feeds one step a dispatch")
    return t["batch_per_chip"] * chips


def model_config(cfg: dict, tiny: bool):
    """The program's preset under the file's cut; refused where one of
    its widths is not the file's."""
    from ray_tpu.models.nemotron_h import NemotronHConfig

    if tiny:
        # float32, as the OLMoE rehearsal: at 12 routes an expert one
        # route flipped by a bf16 activation would decide the share's
        # comparison, and a rehearsal checks the plumbing, not the types
        import jax.numpy as jnp
        return getattr(NemotronHConfig, cfg["tiny"]["preset"])(
            dtype=jnp.float32)
    m = cfg["model"]
    mcfg = getattr(NemotronHConfig, m["preset"])(
        pattern=m["pattern"], experts_held=tuple(m["experts_held"]),
        vocab_size=m["vocab_size"], seq_len=m["seq_len"])
    ran = {k: getattr(mcfg, k) for k in WIDTHS}
    want = {k: tuple(m[k]) if isinstance(m[k], list) else m[k]
            for k in WIDTHS}
    if ran != want:
        raise ValueError(f"the program's preset {ran} is not the "
                         f"configuration file's {want}")
    return mcfg


def reference_spec(mcfg) -> dict:
    """What ``references/nemotron_h.py`` needs to know of the model."""
    spec = {k: getattr(mcfg, k) for k in (
        "pattern", "mamba_heads", "mamba_head_dim", "ssm_state",
        "ssm_groups", "n_head", "n_kv_head", "head_dim", "top_k",
        "norm_topk_prob", "route_scale", "rms_eps")}
    spec["experts_held"] = mcfg.experts_span
    return spec


BALANCE_ROUNDS = 48
BALANCE_FIRST, BALANCE_DECAY = 0.1, 0.85


def make_params(model, seed: int):
    """The initial parameters of a run, on the device, from the seed:
    the config's initialisers, then the routers brought into balance.

    At these initialisers most tokens choose the same few experts (the
    Mamba layers' outputs share a large token-independent part, so the
    router's scores carry a fixed offset an expert: the load's max over
    mean is 4-7), and which of them fall among the 8 held is a lottery:
    the held share was 4.4-7.2% over seven seeds, a layer's above 12.5%
    in one, and the step's time followed it (PERF.md 6, PR 32). A
    deployment's routers are in balance: that is what the selection
    bias is for. So each expert layer's ``e_score_correction_bias`` is
    brought there by the published rule (arXiv:2412.19437, auxiliary-
    loss-free balancing: ``b_e += g * sign(mean load - load_e)``),
    ``BALANCE_ROUNDS`` rounds on one sequence of uniform tokens made
    from the seed, ``g`` from ``BALANCE_FIRST`` shrinking by
    ``BALANCE_DECAY`` a round. The bias takes no gradient and is not
    touched again; the reference reads it from the tree."""
    import jax
    import jax.numpy as jnp

    mcfg = model.config
    params = jax.jit(model.init_params)(jax.random.key(seed))
    layers = [f"h_{i}" for i, kind in enumerate(mcfg.pattern) if kind == "E"]
    if not layers:
        return params
    tokens = jax.random.randint(jax.random.key(seed + 1), (1, mcfg.seq_len),
                                0, mcfg.vocab_size, jnp.int32)

    def with_biases(params, biases):
        return {**params, **{
            name: {**params[name], "mlp": {**params[name]["mlp"], "gate": {
                **params[name]["mlp"]["gate"],
                "e_score_correction_bias": bias}}}
            for name, bias in zip(layers, biases)}}

    @jax.jit
    def round_(params, biases, gain):
        _, sown = model.apply({"params": with_biases(params, biases)}, tokens,
                              return_hidden=True, mutable=["moe"])
        loads = [sown["moe"][name]["mlp"]["load"][0] for name in layers]
        return [b + gain * jnp.sign(load.mean() - load)
                for b, load in zip(biases, loads)]

    biases = [params[name]["mlp"]["gate"]["e_score_correction_bias"]
              for name in layers]
    for k in range(BALANCE_ROUNDS):
        biases = round_(params, biases,
                        jnp.float32(BALANCE_FIRST * BALANCE_DECAY ** k))
    return with_biases(params, biases)


def _least_step_s(cfg: dict, traffic: dict, tiny: bool) -> float:
    """No program takes less for a step (``builders/gpt2.py``): the
    required operations at the highest published peak."""
    if tiny:
        return traffic["least_step_ms"] / 1e3
    from benchlib import flops_nemotron, peaks

    mcfg = model_config(cfg, tiny)
    per_chip = (traffic["batch_per_chip"] * mcfg.seq_len
                * flops_nemotron.train_flops_per_token(mcfg))
    return per_chip / max(p["bf16_flops"] for p in peaks.PEAKS.values())


def host_dataset(cfg: dict, traffic: dict, chips: int, seed: int,
                 tiny: bool, seconds: float) -> dict:
    """Uniform tokens over the held slice of the vocabulary, from the
    seed; one pass, sized for a program that runs at the chip's
    published peak."""
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
    from ray_tpu.models.nemotron_h import NemotronH, nemotron_h_loss_fn

    from benchlib import flops, flops_nemotron, manifest

    chips = mesh.devices.size
    batch = _batch(traffic, chips)
    mcfg = model_config(cfg, tiny)
    o = cfg["optimizer"]
    opt = optax.chain(
        optax.clip_by_global_norm(o["clip_global_norm"]),
        optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                    weight_decay=o["weight_decay"],
                    mu_dtype=jnp.dtype(o["mu_dtype"])))
    model = NemotronH(mcfg, mesh=mesh)

    def init_state():
        return train.init_train_state(make_params(model, seed), opt, mesh)

    step = train.make_train_step(
        nemotron_h_loss_fn(model, ce_chunk=cfg["ce_chunk"]), opt)

    def batches():
        yield from train.get_dataset_shard("train").iter_device_batches(
            batch, mesh)
        raise RuntimeError(
            "the dataset ran out before the window closed: the steps "
            f"took under {_least_step_s(cfg, traffic, tiny) * 1e3:.1f} ms,"
            " which the published peak does not allow")

    ref = manifest.load_reference(cfg["reference"]["module"])
    spec = reference_spec(mcfg)

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
        print(f"nemotron_h reference done; device peak {peak / 1e9:.2f} GB",
              file=sys.stderr, flush=True)
        return out

    uniform_over = (cfg["tiny"] if tiny else cfg["loss"])["uniform_over"]
    tokens = batch // chips * mcfg.seq_len
    return {
        "init_state": init_state, "step": step, "batches": batches,
        # the step reports every number the reference returns: its first
        # dispatch is what the reference is held against, no probe needed
        "keep_for_reference": keep_for_reference, "reference": reference,
        "samples_per_step": batch * mcfg.seq_len,
        "uniform_over": uniform_over,
        "flops_per_sample": flops_nemotron.train_flops_per_token(mcfg),
        "kernel_cost_per_step": flops.flash_attention_train_cost(
            batch // chips, mcfg.n_head, mcfg.seq_len, mcfg.head_dim,
            mcfg.pattern.count("*")),
        "shapes": {"model": f"nemotron_h {mcfg.pattern} d{mcfg.n_embd} "
                            f"ssm{mcfg.mamba_heads}x{mcfg.mamba_head_dim}"
                            f"x{mcfg.ssm_state} "
                            f"h{mcfg.n_head}/{mcfg.n_kv_head}x{mcfg.head_dim} "
                            f"e{mcfg.held}of{mcfg.num_experts}"
                            f"x{mcfg.expert_width} top{mcfg.top_k} "
                            f"v{mcfg.vocab_size}",
                   "n_params": mcfg.num_params(), "seq_len": mcfg.seq_len,
                   "global_batch": batch,
                   "moe_cost_per_step":
                       flops_nemotron.held_experts_train_cost(mcfg, tokens),
                   "ssm_cost_per_step":
                       flops_nemotron.ssm_scan_train_cost(mcfg, tokens)},
    }
