"""Qwen3-Next-80B-A3B-Instruct through the program's own train path.

As ``builders/kimi_linear.py``: ``host_dataset`` runs in the driver
process (numpy only), ``build`` in the worker that holds the chips, and
everything the step is made of is the program's: ``Qwen3Next`` with
``Qwen3NextConfig.qwen3_next_80b_a3b`` cut as the configuration file
says (layers 0-3, one period ``L L L F``; the held experts; the slice of
the two tables; the blocks recomputed in the backward pass),
``qwen3_next_loss_fn`` (the loss against the untied head and a report
that rides in the step's metrics), ``init_train_state``,
``make_train_step``, ``Dataset.iter_device_batches``. The parameters are
made under ``jax.jit`` from the seed by the config's initialisers.

**The optimizer's first step is held to the reference's too**
(``update_norm``, as ``builders/joyai.py``), **and three numbers of the
mixers by themselves**: the program's report carries ``gdn_out_rms`` and
the step is made with the file's ``reference.grad_groups``
(``grad_norm_gdn_gates``: what only the decay and the step size reach;
``grad_norm_attn_qk``: what the scale, the partial rotation and the
zero-centred head norms move); the reference returns all three under
the same names. The initial parameters wait on the host, as
``builders/kimi_linear.py`` keeps them (``with_first_change`` is that
file's).

The cell is refused where the recurrences, the convolution, the output
gate or the attention core ran anything but the path the file's
``kernel`` group names.
"""

from __future__ import annotations


SPARE_DISPATCHES = 32    # warm-up, the traced tail, the last in flight
WIDTHS = ("n_layer", "n_embd", "rms_eps", "full_attention_interval",
          "gdn_key_heads", "gdn_value_heads", "gdn_head_dim", "conv_kernel",
          "gdn_chunk", "n_head", "n_kv_head", "head_dim", "rope_theta",
          "partial_rotary", "num_experts", "experts_held", "top_k",
          "expert_width", "shared_width", "norm_topk_prob", "remat",
          "seq_len", "vocab_size")
# the file's top-level keys (the source's names) that the model's group
# repeats under the program's names: they have to agree
SOURCE_KEYS = {
    "num_hidden_layers": "n_layer", "hidden_size": "n_embd",
    "rms_norm_eps": "rms_eps",
    "full_attention_interval": "full_attention_interval",
    "linear_num_key_heads": "gdn_key_heads",
    "linear_num_value_heads": "gdn_value_heads",
    "linear_key_head_dim": "gdn_head_dim",
    "linear_value_head_dim": "gdn_head_dim",
    "linear_conv_kernel_dim": "conv_kernel",
    "num_attention_heads": "n_head", "num_key_value_heads": "n_kv_head",
    "head_dim": "head_dim", "rope_theta": "rope_theta",
    "partial_rotary_factor": "partial_rotary",
    "num_experts_per_tok": "top_k", "moe_intermediate_size": "expert_width",
    "shared_expert_intermediate_size": "shared_width",
    "norm_topk_prob": "norm_topk_prob", "vocab_size": "vocab_size"}
REDUCED = ("num_hidden_layers", "num_experts", "vocab_size")


def _other(name: str):
    from benchlib import manifest
    return manifest.load_builder(name)


def model_config(cfg: dict, tiny: bool):
    """The program's preset under the file's cut; refused where one of
    its widths is not the file's, or the file's own keys disagree."""
    from ray_tpu.models.qwen3_next import Qwen3NextConfig

    if tiny:
        # float32, as the other rehearsals: one route flipped by a bf16
        # activation would decide the share's comparison at this size
        import jax.numpy as jnp
        return getattr(Qwen3NextConfig, cfg["tiny"]["preset"])(
            dtype=jnp.float32)
    m = cfg["model"]
    mcfg = getattr(Qwen3NextConfig, m["preset"])(
        n_layer=m["n_layer"], experts_held=tuple(m["experts_held"]),
        vocab_size=m["vocab_size"], seq_len=m["seq_len"], remat=m["remat"])
    ran = {k: getattr(mcfg, k) for k in WIDTHS}
    want = {k: tuple(m[k]) if isinstance(m[k], list) else m[k]
            for k in WIDTHS}
    if ran != want:
        raise ValueError(f"the program's preset {ran} is not the "
                         f"configuration file's {want}")
    off = {k: (cfg[k], m[name]) for k, name in SOURCE_KEYS.items()
           if cfg[k] != m[name]}
    published = cfg["published"]
    cut = {k for k in published if cfg[k] != published[k]}
    if (off or cut != set(REDUCED)
            or cfg["num_experts"] != m["experts_held"][1]
            or published["num_experts"] != m["num_experts"]
            or cfg["tie_word_embeddings"] or cfg["mlp_only_layers"]
            or cfg["decoder_sparse_step"] != 1):
        raise ValueError(f"the file's own keys disagree: {off}; cut "
                         f"{sorted(cut)}")
    return mcfg


def reference_spec(mcfg) -> dict:
    """What ``references/qwen3_next.py`` needs to know of the model."""
    spec = {k: getattr(mcfg, k) for k in (
        "n_layer", "full_attention_interval", "gdn_key_heads",
        "gdn_value_heads", "n_head", "n_kv_head", "head_dim",
        "rotated_lanes", "rope_theta", "top_k", "norm_topk_prob",
        "rms_eps")}
    spec["experts_held"] = mcfg.experts_span
    return spec


def program(cfg: dict, tiny: bool, mesh=None):
    """(the model's config, the model, its loss function): what the
    step differentiates, for ``build`` and for
    ``tools/qwen3_next_limit.py``."""
    from ray_tpu.models.qwen3_next import Qwen3Next, qwen3_next_loss_fn

    mcfg = model_config(cfg, tiny)
    model = Qwen3Next(mcfg, mesh=mesh)
    return mcfg, model, qwen3_next_loss_fn(model, ce_chunk=cfg["ce_chunk"])


def _least_step_s(cfg: dict, traffic: dict, tiny: bool) -> float:
    """No program takes less for a step (``builders/gpt2.py``): the
    required operations at the highest published peak."""
    if tiny:
        return traffic["least_step_ms"] / 1e3
    from benchlib import flops_qwen3_next, peaks

    mcfg = model_config(cfg, tiny)
    per_chip = (traffic["batch_per_chip"] * mcfg.seq_len
                * flops_qwen3_next.train_flops_per_token(mcfg))
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
    batch = _other("kimi_linear")._batch(traffic, chips)
    toks = rng.integers(0, vocab, (dispatches * batch, seq_len),
                        dtype=np.int32)
    return {"tokens": toks, "targets": np.roll(toks, -1, 1)}


def refuse_unless_the_files_kernels(notes: dict, kernel: dict):
    """Raises where the step's notes do not say that the recurrences,
    the convolution, the output gate and the attention core each ran the
    path the file's ``kernel`` group names."""
    want = {k: kernel[k] for k in ("gdn_path", "gdn_gate_path", "conv_path",
                                   "flash_path", "flash_lanes_per_block")}
    got = {k: notes.get(k) for k in want}
    if got != want:
        raise RuntimeError(
            f"the mixers ran as {got} (layout "
            f"{notes.get('flash_layout')!r}), not as {want}: this cell "
            "measures those")


def build(cfg: dict, traffic: dict, mesh, seed: int, tiny: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train

    from benchlib import flops_qwen3_next as fq, manifest

    kimi = _other("kimi_linear")
    chips = mesh.devices.size
    batch = kimi._batch(traffic, chips)
    mcfg, model, loss_fn = program(cfg, tiny, mesh)
    o = cfg["optimizer"]
    opt = optax.chain(
        optax.clip_by_global_norm(o["clip_global_norm"]),
        optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                    eps=o["eps"], weight_decay=o["weight_decay"],
                    mu_dtype=jnp.dtype(o["mu_dtype"])))

    def init_state():
        return train.init_train_state(kimi.make_params(model, seed), opt,
                                      mesh)

    kept: dict = {}     # keep_for_reference fills it before dispatch 0
    groups = cfg["reference"]["grad_groups"]
    step = kimi.with_first_change(
        train.make_train_step(loss_fn, opt, grad_groups=groups), kept)

    def batches():
        yield from train.get_dataset_shard("train").iter_device_batches(
            batch, mesh)
        raise RuntimeError(
            "the dataset ran out before the window closed: the steps "
            f"took under {_least_step_s(cfg, traffic, tiny) * 1e3:.1f} ms,"
            " which the published peak does not allow")

    ref = manifest.load_reference(cfg["reference"]["module"])
    spec = {**reference_spec(mcfg), "adamw": o, "grad_groups": groups}

    def keep_for_reference(state, first_batch):
        """The initial parameters, taken to the host before the first
        dispatch donates them, and the first batch."""
        kept.update(params=jax.device_get(state.params), batch=first_batch)
        return kept

    def reference(kept):
        """Refuses the run where a mixer did not run as the file names,
        then runs the float32 reference beside the live train state (the
        parameters stay on the host: the reference takes a half block's
        to the device while it runs that half); its routes by layer and
        what the device peaked at by then go to the worker's log."""
        import json
        import sys
        if not tiny:
            refuse_unless_the_files_kernels(_other("joyai").step_notes(),
                                            cfg["kernel"])
        load: list = []
        out = ref.loss_and_grad_norm(kept["params"], kept["batch"], spec,
                                     load=load)
        by_layer = _other("smallthinker").routes_by_layer(
            load, mcfg.experts_span)
        print("qwen3_next routes by layer: " + json.dumps(by_layer),
              file=sys.stderr, flush=True)
        peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in mesh.devices.flat), default=0)
        print(f"qwen3_next reference done; device peak {peak / 1e9:.2f} GB",
              file=sys.stderr, flush=True)
        return out

    uniform_over = (cfg["tiny"] if tiny else cfg["loss"])["uniform_over"]
    rows = batch // chips
    tokens = rows * mcfg.seq_len
    return {
        "init_state": init_state, "step": step, "batches": batches,
        # the step reports every number the reference returns: its first
        # dispatch is what the reference is held against, no probe needed
        "keep_for_reference": keep_for_reference, "reference": reference,
        "samples_per_step": batch * mcfg.seq_len,
        "uniform_over": uniform_over,
        "flops_per_sample": fq.train_flops_per_token(mcfg),
        "kernel_cost_per_step": fq.flash_core_train_cost(mcfg, rows),
        "shapes": {"model": f"qwen3_next {mcfg.layer_kinds} d{mcfg.n_embd} "
                            f"gdn h{mcfg.gdn_key_heads}/"
                            f"{mcfg.gdn_value_heads}x{mcfg.gdn_head_dim} "
                            f"c{mcfg.gdn_chunk} attn h{mcfg.n_head}/"
                            f"{mcfg.n_kv_head}x{mcfg.head_dim} "
                            f"rot{mcfg.rotated_lanes} "
                            f"e{mcfg.held}of{mcfg.num_experts}"
                            f"x{mcfg.expert_width} top{mcfg.top_k} "
                            f"v{mcfg.vocab_size} untied",
                   "n_params": mcfg.num_params(), "seq_len": mcfg.seq_len,
                   "global_batch": batch,
                   "moe_cost_per_step":
                       fq.held_experts_train_cost(mcfg, tokens),
                   "gdn_scan_cost_per_step":
                       fq.gdn_scan_train_cost(mcfg, rows)},
    }
