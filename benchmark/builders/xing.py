"""Xing4.0-29B-A4B through the program's own train path.

As ``builders/joyai.py`` (whose model file it shares): ``host_dataset``
runs in the driver process (numpy only), ``build`` in the worker that
holds the chips, and everything the step is made of is the program's:
``JoyAI`` with ``JoyAIConfig.xing4_0_29b_a4b`` cut as the configuration
file says (one dense layer, four routed layers and the MTP module; the
held experts; the slice of the two tables; four residual streams under
their maps; the blocks recomputed in the backward pass),
``joyai_loss_fn`` (the two losses and a report that rides in the step's
metrics, ``hc_stream_spread`` and ``hc_res_row_err`` in it),
``init_train_state``, ``make_train_step``,
``Dataset.iter_device_batches``. The parameters are made under
``jax.jit`` from the seed by the config's initialisers.

**The optimizer's first step is held to the reference's too**
(``update_norm``, as ``builders/joyai.py``). **The residual maps'
gradient by itself is reported and not compared**: the step is made
with the file's ``reference.reported_grad_groups``
(``make_train_step(grad_groups=...)``: ``grad_norm_hc``, the norm over
``phi``, ``b`` and ``alpha`` of every sub-layer, a thousandth of the
whole norm) and the reference is not asked for it, because on the chip
thirty scalars (the gates) carry most of that norm and a scalar's
gradient is noise at bfloat16: the program read 1.8% over the float32
reference on one seed and 0.5% under on another, twenty times the limit
that the float8 reading has to fail (the file's ``rtol_why``). What is
compared of the mechanism is ``hc_stream_spread``, and ``grad_norm``,
which the maps' backward reaches through the state's cotangent.

**The initial parameters wait on the host**, as
``builders/kimi_linear.py``'s do and with its functions: the step leaves
no room for a second copy of them on the chip.

The cell is refused where latent attention did not reach its kernel
(``flash_path``), where the step's notes do not say that the residual
path ran with the file's number of streams, or where the rotation was
not YaRN's.
"""

from __future__ import annotations


WIDTHS = ("n_layer", "n_embd", "n_head", "q_rank", "kv_rank", "nope_dim",
          "rope_dim", "v_dim", "rope_theta", "rms_eps", "dense_layers",
          "dense_width", "num_experts", "experts_held", "top_k",
          "expert_width", "shared_width", "norm_topk_prob", "route_scale",
          "mtp_depth", "mtp_weight", "hc_mult", "hc_sinkhorn_iters",
          "hc_eps", "hc_res_clamp", "remat", "seq_len", "vocab_size")
# the file's top-level keys (the source's names) that the model's group
# repeats under the program's names: they have to agree
SOURCE_KEYS = {
    "num_hidden_layers": "n_layer", "hidden_size": "n_embd",
    "num_attention_heads": "n_head", "q_lora_rank": "q_rank",
    "kv_lora_rank": "kv_rank", "qk_nope_head_dim": "nope_dim",
    "qk_rope_head_dim": "rope_dim", "v_head_dim": "v_dim",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
    "first_k_dense_replace": "dense_layers",
    "intermediate_size": "dense_width", "num_experts_per_tok": "top_k",
    "moe_intermediate_size": "expert_width",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "route_scale",
    "num_nextn_predict_layers": "mtp_depth", "vocab_size": "vocab_size",
    "hc_mult": "hc_mult", "hc_sinkhorn_iters": "hc_sinkhorn_iters",
    "hc_eps": "hc_eps", "mhc_h_res_clamp_max": "hc_res_clamp"}
# rope_scaling's keys under YarnScaling's names
YARN_KEYS = {"factor": "factor",
             "original_max_position_embeddings": "original_len",
             "beta_fast": "beta_fast", "beta_slow": "beta_slow",
             "mscale": "mscale", "mscale_all_dim": "mscale_all_dim"}


def _other(name: str):
    from benchlib import manifest
    return manifest.load_builder(name)


def _batch(t: dict, chips: int) -> int:
    if t["steps_per_dispatch"] != 1:
        raise ValueError("the xing builder feeds one step a dispatch")
    return t["batch_per_chip"] * chips


def model_config(cfg: dict, tiny: bool):
    """The program's preset under the file's cut; refused where one of
    its widths is not the file's."""
    import dataclasses

    from ray_tpu.models.joyai import JoyAIConfig

    if tiny:
        # float32, as the other rehearsals: one route flipped by a bf16
        # activation would decide the share's comparison at this size
        import jax.numpy as jnp
        return getattr(JoyAIConfig, cfg["tiny"]["preset"])(dtype=jnp.float32)
    m = cfg["model"]
    mcfg = getattr(JoyAIConfig, m["preset"])(
        n_layer=m["n_layer"], dense_layers=m["dense_layers"],
        experts_held=tuple(m["experts_held"]), vocab_size=m["vocab_size"],
        seq_len=m["seq_len"], mtp_depth=m["mtp_depth"],
        mtp_weight=m["mtp_weight"], remat=m["remat"])
    ran = {k: getattr(mcfg, k) for k in WIDTHS}
    want = {k: tuple(m[k]) if isinstance(m[k], list) else m[k]
            for k in WIDTHS}
    yarn = dataclasses.asdict(mcfg.rope_scaling)
    if ran != want or yarn != m["rope_scaling"]:
        raise ValueError(f"the program's preset {ran}, {yarn} is not the "
                         f"configuration file's {want}, "
                         f"{m['rope_scaling']}")
    off = {k: (cfg[k], m[name]) for k, name in SOURCE_KEYS.items()
           if cfg[k] != m[name]}
    off.update({k: (cfg["rope_scaling"][k], yarn[name])
                for k, name in YARN_KEYS.items()
                if cfg["rope_scaling"][k] != yarn[name]})
    if (off or cfg["n_routed_experts"] != m["experts_held"][1]
            or cfg["published"]["n_routed_experts"] != m["num_experts"]
            or cfg["n_shared_experts"] * m["expert_width"]
            != m["shared_width"]
            or cfg["mhc_h_res_clamp_min"] != -m["hc_res_clamp"]
            or cfg["rope_scaling"]["type"] != "yarn"):
        raise ValueError(f"the file's own keys disagree: {off}")
    return mcfg


def reference_spec(mcfg) -> dict:
    """What ``references/xing.py`` needs to know of the model."""
    import dataclasses
    spec = {k: getattr(mcfg, k) for k in (
        "n_layer", "dense_layers", "mtp_depth", "mtp_weight", "n_head",
        "kv_rank", "nope_dim", "rope_dim", "rope_theta", "top_k",
        "norm_topk_prob", "route_scale", "rms_eps", "hc_mult",
        "hc_sinkhorn_iters", "hc_eps", "hc_res_clamp")}
    spec["experts_held"] = mcfg.experts_span
    spec["rope_scaling"] = (dataclasses.asdict(mcfg.rope_scaling)
                            if mcfg.rope_scaling else None)
    return spec


def program(cfg: dict, tiny: bool, mesh=None):
    """(the model's config, the model, its loss function): what the
    step differentiates, for ``build`` and for ``tools/limit.py``."""
    from ray_tpu.models.joyai import JoyAI, joyai_loss_fn

    mcfg = model_config(cfg, tiny)
    model = JoyAI(mcfg, mesh=mesh)
    return mcfg, model, joyai_loss_fn(model, ce_chunk=cfg["ce_chunk"])


def make_params(model, seed: int):
    """The initial parameters of a run, on the device, from the seed."""
    import jax
    return jax.jit(model.init_params)(jax.random.key(seed))


def _least_step_s(cfg: dict, traffic: dict, tiny: bool) -> float:
    """No program takes less for a step (``builders/gpt2.py``): the
    required operations at the highest published peak."""
    if tiny:
        return traffic["least_step_ms"] / 1e3
    from benchlib import flops_xing, peaks

    mcfg = model_config(cfg, tiny)
    per_chip = (traffic["batch_per_chip"] * mcfg.seq_len
                * flops_xing.train_flops_per_token(mcfg))
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
                  + _other("joyai").SPARE_DISPATCHES)
    toks = rng.integers(0, vocab,
                        (dispatches * _batch(traffic, chips), seq_len),
                        dtype=np.int32)
    return {"tokens": toks, "targets": np.roll(toks, -1, 1)}


def refuse_unless_as_the_file_says(notes: dict, cfg: dict):
    """Raises where the step's notes do not say that latent attention
    ran in the file's flash kernel, under YaRN at the file's scale, and
    the residual path with the file's streams and iterations."""
    m = cfg["model"]
    want = {"flash_path": cfg["kernel"]["flash_path"], "rope_kind": "yarn",
            "hc_mult": m["hc_mult"],
            "hc_sinkhorn_iters": m["hc_sinkhorn_iters"],
            "hc_state_dtype": cfg["assumed"]["compute_dtype"]}
    got = {k: notes.get(k) for k in want}
    if got != want or abs(notes.get("mla_scale", 0.0)
                          - m["mla_scale"]) > 1e-6 * m["mla_scale"]:
        raise RuntimeError(
            f"the step ran as {got} at scale {notes.get('mla_scale')}, not "
            f"as {want} at {m['mla_scale']}: this cell measures those")


def build(cfg: dict, traffic: dict, mesh, seed: int, tiny: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train

    from benchlib import flops_xing as fx, manifest

    joyai, kimi = _other("joyai"), _other("kimi_linear")
    chips = mesh.devices.size
    batch = _batch(traffic, chips)
    mcfg, model, loss_fn = program(cfg, tiny, mesh)
    o = cfg["optimizer"]
    opt = optax.chain(
        optax.clip_by_global_norm(o["clip_global_norm"]),
        optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                    eps=o["eps"], weight_decay=o["weight_decay"],
                    mu_dtype=jnp.dtype(o["mu_dtype"])))

    def init_state():
        return train.init_train_state(make_params(model, seed), opt, mesh)

    kept: dict = {}     # keep_for_reference fills it before dispatch 0
    step = kimi.with_first_change(
        train.make_train_step(
            loss_fn, opt,
            grad_groups=cfg["reference"]["reported_grad_groups"]), kept)

    def batches():
        yield from train.get_dataset_shard("train").iter_device_batches(
            batch, mesh)
        raise RuntimeError(
            "the dataset ran out before the window closed: the steps "
            f"took under {_least_step_s(cfg, traffic, tiny) * 1e3:.1f} ms,"
            " which the published peak does not allow")

    ref = manifest.load_reference(cfg["reference"]["module"])
    spec = {**reference_spec(mcfg), "adamw": o}

    def keep_for_reference(state, first_batch):
        """The initial parameters, taken to the host before the first
        dispatch donates them, and the first batch."""
        kept.update(params=jax.device_get(state.params), batch=first_batch)
        return kept

    def reference(kept):
        """Refuses the run where the step did not run as the file says,
        then runs the float32 reference beside the live train state (the
        parameters stay on the host: the reference takes a block's to
        the device while it runs that block); what the device peaked at
        by then goes to the worker's log."""
        import sys
        if not tiny:
            refuse_unless_as_the_file_says(joyai.step_notes(), cfg)
        out = ref.loss_and_grad_norm(kept["params"], kept["batch"], spec)
        peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in mesh.devices.flat), default=0)
        print(f"xing reference done; device peak {peak / 1e9:.2f} GB",
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
        "flops_per_sample": fx.train_flops_per_token(mcfg),
        "kernel_cost_per_step": fx.latent_attention_train_cost(mcfg, rows),
        "shapes": {"model": f"xing {mcfg.dense_layers}d+"
                            f"{mcfg.n_layer - mcfg.dense_layers}r+"
                            f"{mcfg.mtp_depth}mtp d{mcfg.n_embd} "
                            f"hc{mcfg.hc_mult}x{mcfg.hc_sinkhorn_iters} "
                            f"mla{mcfg.q_rank}/{mcfg.kv_rank} "
                            f"h{mcfg.n_head}x({mcfg.nope_dim}+"
                            f"{mcfg.rope_dim})/{mcfg.v_dim} yarn "
                            f"e{mcfg.held}of{mcfg.num_experts}"
                            f"x{mcfg.expert_width} top{mcfg.top_k} "
                            f"v{mcfg.vocab_size}",
                   "n_params": mcfg.num_params(), "seq_len": mcfg.seq_len,
                   "global_batch": batch,
                   "moe_cost_per_step":
                       fx.held_experts_train_cost(mcfg, tokens),
                   "hc_cost_per_step": fx.hc_train_cost(mcfg, tokens)},
    }
