"""Laguna-XS.2 through the program's own train path.

As ``builders/kimi_linear.py``: ``host_dataset`` runs in the driver
process (numpy only), ``build`` in the worker that holds the chips, and
everything the step is made of is the program's: ``Laguna`` with
``LagunaConfig.laguna_xs_2`` cut as the configuration file says (layers
0-4 as published, ``F S S S F``; the held experts; the slice of the two
tables; the blocks recomputed in the backward pass), ``laguna_loss_fn``
(the loss against the untied head and a report that rides in the step's
metrics), ``init_train_state``, ``make_train_step``,
``Dataset.iter_device_batches``. The parameters are made under
``jax.jit`` from the seed by the config's initialisers as they are (the
routing does not collapse at normal(0.02) here, so SmallThinker's
unit-scale embedding is not taken: the file's ``assumed.weights`` has
the readings); the routers' selection biases stay at zero.

**The optimizer's first step is held to the reference's too**
(``update_norm``, as ``builders/joyai.py``), and the report's
``attn_window_out_rms``, the sliding cores' output by itself
(SmallThinker's sixth key, for its reason: the whole gradient's norm is
the head's and the tables' before it is anything else).

**The initial parameters wait on the host**, as Kimi-Linear's: the step
leaves no room for a second copy of them beside it
(``builders/kimi_linear.py::with_first_change``).

The cell is refused where the sliding layers did not reach the kernel
with the band skipped (``builders/smallthinker.py::
refuse_unless_band_skipped``: ``flash_path`` ``multi_block``,
``flash_window`` 512, fewer ``flash_band_blocks`` than the causal grid
walks), where the routed layers did not say that they hold the file's
share of the experts (``moe_experts_held``), or where the step's notes
do not carry the stack the file describes (the layers' kinds and head
counts).
"""

from __future__ import annotations


SPARE_DISPATCHES = 32    # warm-up, the traced tail, the last in flight
PER_LAYER = ("layer_types", "heads_per_layer", "mlp_layer_types")
WIDTHS = ("n_layer", "n_embd", "n_kv_head", "head_dim", "window",
          "sliding_theta", "full_theta", "full_rotary", "yarn_factor",
          "yarn_original_len", "yarn_beta_fast", "yarn_beta_slow",
          "yarn_attention_factor", "rms_eps", "dense_width", "num_experts",
          "experts_held", "top_k", "expert_width", "shared_width",
          "norm_topk_prob", "route_scale", "remat", "seq_len", "vocab_size")
# the file's top-level keys (the source's names) that the model's group
# repeats under the program's names: they have to agree
SOURCE_KEYS = {
    "num_hidden_layers": "n_layer", "hidden_size": "n_embd",
    "num_key_value_heads": "n_kv_head", "head_dim": "head_dim",
    "sliding_window": "window", "rms_norm_eps": "rms_eps",
    "intermediate_size": "dense_width", "num_experts_per_tok": "top_k",
    "moe_intermediate_size": "expert_width",
    "shared_expert_intermediate_size": "shared_width",
    "moe_routed_scaling_factor": "route_scale",
    "partial_rotary_factor": "full_rotary", "vocab_size": "vocab_size"}
# rope_parameters.full_attention's keys under the program's names
YARN_KEYS = {
    "rope_theta": "full_theta", "factor": "yarn_factor",
    "original_max_position_embeddings": "yarn_original_len",
    "beta_fast": "yarn_beta_fast", "beta_slow": "yarn_beta_slow",
    "attention_factor": "yarn_attention_factor",
    "partial_rotary_factor": "full_rotary"}


def _builder(name: str):
    """Another builder's pieces that are the same here: ``joyai``
    (``step_notes``), ``kimi_linear`` (``with_first_change`` with the
    parameters on the host), ``smallthinker`` (the band's refusal, the
    routes by layer)."""
    from benchlib import manifest
    return manifest.load_builder(name)


def _batch(t: dict, chips: int) -> int:
    if t["steps_per_dispatch"] != 1:
        raise ValueError("the laguna builder feeds one step a dispatch")
    return t["batch_per_chip"] * chips


def model_config(cfg: dict, tiny: bool):
    """The program's preset under the file's cut; refused where one of
    its widths or one layer's entry of its three lists is not the
    file's."""
    from ray_tpu.models.laguna import LagunaConfig

    if tiny:
        # float32, as the other rehearsals: one route flipped by a bf16
        # activation would decide the share's comparison at this size
        import jax.numpy as jnp
        return getattr(LagunaConfig, cfg["tiny"]["preset"])(
            dtype=jnp.float32)
    m = cfg["model"]
    n = m["n_layer"]
    mcfg = getattr(LagunaConfig, m["preset"])(
        n_layer=n, experts_held=tuple(m["experts_held"]),
        vocab_size=m["vocab_size"], seq_len=m["seq_len"], remat=m["remat"])
    ran = {**{k: getattr(mcfg, k) for k in WIDTHS},
           **{k: getattr(mcfg, k)[:n] for k in PER_LAYER}}
    want = {k: tuple(m[k]) if isinstance(m[k], list) else m[k]
            for k in WIDTHS + PER_LAYER}
    if ran != want:
        raise ValueError(f"the program's preset {ran} is not the "
                         f"configuration file's {want}")
    off = {k: (cfg[k], m[name]) for k, name in SOURCE_KEYS.items()
           if cfg[k] != m[name]}
    rope = cfg["rope_parameters"]
    off.update({f"rope_parameters.full_attention.{k}": (v, m[YARN_KEYS[k]])
                for k, v in rope["full_attention"].items()
                if k in YARN_KEYS and v != m[YARN_KEYS[k]]})
    if (off or cfg["num_experts"] != m["experts_held"][1]
            or cfg["published"]["num_experts"] != m["num_experts"]
            or cfg["layer_types"][:n] != m["layer_types"]
            or cfg["num_attention_heads_per_layer"][:n]
            != m["heads_per_layer"]
            or cfg["mlp_layer_types"][:n] != m["mlp_layer_types"]
            or rope["full_attention"]["rope_type"] != "yarn"
            or rope["sliding_attention"]
            != {"rope_type": "default", "rope_theta": m["sliding_theta"],
                "partial_rotary_factor": 1}
            or cfg["gating"] is not True or cfg["attention_bias"]
            or cfg["moe_apply_router_weight_on_input"]
            or cfg["tie_word_embeddings"] or m["tied"]):
        raise ValueError(f"the file's own keys disagree: {off}")
    return mcfg


def reference_spec(mcfg) -> dict:
    """What ``references/laguna.py`` needs to know of the model: the
    stack's first ``n_layer`` entries of the published lists (a layer's
    head count it reads off the layer's own ``W_q``)."""
    n = mcfg.n_layer
    spec = {k: getattr(mcfg, k) for k in (
        "n_layer", "n_kv_head", "head_dim", "window", "sliding_theta",
        "full_theta", "rotated_lanes", "top_k", "norm_topk_prob",
        "route_scale", "rms_eps")}
    spec.update(
        layer_types=list(mcfg.layer_types[:n]),
        mlp_layer_types=list(mcfg.mlp_layer_types[:n]),
        yarn={"factor": mcfg.yarn_factor,
              "original_len": mcfg.yarn_original_len,
              "beta_fast": mcfg.yarn_beta_fast,
              "beta_slow": mcfg.yarn_beta_slow,
              "attention_factor": mcfg.yarn_attention_factor},
        experts_held=mcfg.experts_span)
    return spec


def program(cfg: dict, tiny: bool, mesh=None):
    """(the model's config, the model, its loss function): what the
    step differentiates, for ``tools/limit.py``, which turns every
    value of the report into a float: the report's scalars alone
    (``build``'s step carries ``moe_load``, a row a routed layer, as
    well)."""
    from ray_tpu.models.laguna import Laguna, laguna_loss_fn

    mcfg = model_config(cfg, tiny)
    model = Laguna(mcfg, mesh=mesh)
    whole = laguna_loss_fn(model, ce_chunk=cfg["ce_chunk"])

    def scalars(params, batch):
        loss, report = whole(params, batch)
        return loss, {k: v for k, v in report.items() if v.ndim == 0}
    return mcfg, model, scalars


def make_params(model, seed: int):
    """The initial parameters of a run, on the device, from the seed."""
    import jax
    return jax.jit(model.init_params)(jax.random.key(seed))


def _least_step_s(cfg: dict, traffic: dict, tiny: bool) -> float:
    """No program takes less for a step (``builders/gpt2.py``): the
    required operations at the highest published peak."""
    if tiny:
        return traffic["least_step_ms"] / 1e3
    from benchlib import flops_laguna, peaks

    mcfg = model_config(cfg, tiny)
    per_chip = (traffic["batch_per_chip"] * mcfg.seq_len
                * flops_laguna.train_flops_per_token(mcfg))
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


def refuse_unless_the_files_stack(notes: dict, mcfg, cfg: dict):
    """Raises where the step's notes do not say the stack the file
    describes: the layers' kinds and head counts, the window and the
    gate (the model's notes), and the held share of the experts
    (``routed_ffn``'s)."""
    n = mcfg.n_layer
    want = {"attn_layers": "".join(
                "S" if kind == "sliding_attention" else "F"
                for kind in cfg["layer_types"][:n]),
            "attn_heads": ",".join(
                str(h) for h in cfg["num_attention_heads_per_layer"][:n]),
            "attn_window": cfg["sliding_window"],
            "attn_gate": "headwise_sigmoid",
            "moe_router": "sigmoid",
            "moe_experts_held": list(mcfg.experts_span)}
    got = {k: notes.get(k) for k in want}
    if got != want:
        raise RuntimeError(f"the step ran {got}, the file says {want}")


def build(cfg: dict, traffic: dict, mesh, seed: int, tiny: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.models.laguna import laguna_loss_fn

    from benchlib import flops_laguna as fl, manifest

    chips = mesh.devices.size
    batch = _batch(traffic, chips)
    mcfg, model, _ = program(cfg, tiny, mesh)
    loss_fn = laguna_loss_fn(model, ce_chunk=cfg["ce_chunk"])
    o = cfg["optimizer"]
    opt = optax.chain(
        optax.clip_by_global_norm(o["clip_global_norm"]),
        optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                    eps=o["eps"], weight_decay=o["weight_decay"],
                    mu_dtype=jnp.dtype(o["mu_dtype"])))

    def init_state():
        return train.init_train_state(make_params(model, seed), opt, mesh)

    kept: dict = {}     # keep_for_reference fills it before dispatch 0
    step = _builder("kimi_linear").with_first_change(
        train.make_train_step(loss_fn, opt), kept)

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
        """Refuses the run where the sliding layers did not run in the
        kernel with the band skipped or the step was not the file's
        stack, then runs the float32 reference beside the live train
        state; its routes by layer and what the device peaked at by then
        go to the worker's log."""
        import json
        import sys
        smallthinker = _builder("smallthinker")
        if not tiny:
            from ray_tpu.ops.pallas.flash_attention import _pick_block
            blocks = mcfg.seq_len // _pick_block(mcfg.seq_len)
            notes = _builder("joyai").step_notes()
            smallthinker.refuse_unless_band_skipped(
                notes, cfg["kernel"], blocks * (blocks + 1) // 2)
            refuse_unless_the_files_stack(notes, mcfg, cfg)
        load: list = []
        # the parameters stay on the host: the reference takes a block's
        # to the device while it runs that block
        out = ref.loss_and_grad_norm(kept["params"], kept["batch"], spec,
                                     load=load)
        print("laguna routes by routed layer: " + json.dumps(
            smallthinker.routes_by_layer(load, mcfg.experts_span)),
            file=sys.stderr, flush=True)
        peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in mesh.devices.flat), default=0)
        print(f"laguna reference done; device peak {peak / 1e9:.2f} GB",
              file=sys.stderr, flush=True)
        return out

    uniform_over = (cfg["tiny"] if tiny else cfg["loss"])["uniform_over"]
    rows = batch // chips
    tokens = rows * mcfg.seq_len
    n = mcfg.n_layer
    return {
        "init_state": init_state, "step": step, "batches": batches,
        # the step reports every number the reference returns: its first
        # dispatch is what the reference is held against, no probe needed
        "keep_for_reference": keep_for_reference, "reference": reference,
        "samples_per_step": batch * mcfg.seq_len,
        "uniform_over": uniform_over,
        "flops_per_sample": fl.train_flops_per_token(mcfg),
        "kernel_cost_per_step": fl.flash_cores_train_cost(mcfg, rows),
        "shapes": {"model": f"laguna {mcfg.layer_kinds} d{mcfg.n_embd} "
                            f"h{'/'.join(map(str, mcfg.heads_per_layer[:n]))}"
                            f"over{mcfg.n_kv_head}x{mcfg.head_dim} "
                            f"w{mcfg.window} yarn{mcfg.rotated_lanes} gated "
                            f"e{mcfg.held}of{mcfg.num_experts}"
                            f"x{mcfg.expert_width}+shared top{mcfg.top_k} "
                            f"v{mcfg.vocab_size} untied",
                   "n_params": mcfg.num_params(), "seq_len": mcfg.seq_len,
                   "global_batch": batch,
                   "moe_cost_per_step":
                       fl.held_experts_train_cost(mcfg, tokens),
                   "window_cost_per_step":
                       fl.window_cores_train_cost(mcfg, rows),
                   "global_cost_per_step":
                       fl.full_cores_train_cost(mcfg, rows)},
    }
