"""granite-4.0-h-micro through the program's own train path.

As ``builders/phi4flash.py``: ``host_dataset`` runs in the driver process
(numpy only), ``build`` in the worker that holds the chips, and
everything the step is made of is the program's: ``Granite`` with
``GraniteHybridConfig.granite_4_0_h_micro`` cut as the configuration file
says (one period of ``layer_types``, the slice of the tied table, the
blocks recomputed in the backward pass), ``granite_loss_fn`` (the loss
against the tied table under ``logits_scaling`` and a report that rides
in the step's metrics), ``init_train_state``, ``make_train_step``,
``Dataset.iter_device_batches``. The parameters are made under
``jax.jit`` from the seed by the config's initialisers.

**The preset is held to the file three ways** (``model_config``): the
published preset's ``hf_config()`` is the file's ``published`` key for
key; the preset as cut gives the file's top-level keys (``layer_types``
is left whole there, and the cut runs its first ``num_hidden_layers``);
and the program's own names under ``model`` are what ran.

**The optimizer's first step is held to the reference's too**
(``update_norm``, as ``builders/joyai.py``), **and four numbers of the
parts by themselves**: the program's report carries ``mamba_out_rms`` and
the step is made with the file's ``reference.grad_groups``
(``grad_norm_mamba_ssm``, ``grad_norm_table``, ``grad_norm_attn``); the
reference returns all four under the same names. The initial parameters
wait on the host, as ``builders/kimi_linear.py`` keeps them: the step
peaks at 13.15 GB of the chip's 16.91 and a copy of the parameters is
3.19.

The cell is refused where the scans did not run the kernels at the
file's chunk and group count, or the convolution, the gated norm or the
attention core ran anything but the path the file's ``kernel`` group
names.
"""

from __future__ import annotations


SPARE_DISPATCHES = 32    # warm-up, the traced tail, the last in flight
WIDTHS = ("layer_types", "n_embd", "rms_eps", "embedding_multiplier",
          "residual_multiplier", "attention_multiplier", "logits_scaling",
          "mamba_heads", "mamba_head_dim", "mamba_expand", "ssm_state",
          "ssm_groups", "conv_kernel", "chunk", "n_head", "n_kv_head",
          "head_dim", "positions", "mlp_width", "num_experts", "remat",
          "seq_len", "vocab_size")
REDUCED = ("num_hidden_layers", "vocab_size")


def _other(name: str):
    from benchlib import manifest
    return manifest.load_builder(name)


def _batch(t: dict, chips: int) -> int:
    if t["steps_per_dispatch"] != 1:
        raise ValueError("the granite builder feeds one step a dispatch")
    return t["batch_per_chip"] * chips


def model_config(cfg: dict, tiny: bool):
    """The program's preset under the file's cut; refused where the
    preset, the file's source keys and its ``model`` group disagree."""
    from ray_tpu.models.granite import GraniteHybridConfig

    if tiny:
        # float32, as the other rehearsals
        import jax.numpy as jnp
        return getattr(GraniteHybridConfig, cfg["tiny"]["preset"])(
            dtype=jnp.float32)
    m = cfg["model"]
    preset = getattr(GraniteHybridConfig, m["preset"])
    published = preset().hf_config()
    if published != cfg["published"]:
        off = {k for k in {*published, *cfg["published"]}
               if published.get(k) != cfg["published"].get(k)}
        raise ValueError(f"the program's preset and the file's `published` "
                         f"differ in {sorted(off)}")
    mcfg = preset(layer_types=tuple(m["layer_types"]),
                  vocab_size=m["vocab_size"], seq_len=m["seq_len"],
                  chunk=m["chunk"], remat=m["remat"])
    ran = {k: getattr(mcfg, k) for k in WIDTHS}
    want = {k: tuple(m[k]) if isinstance(m[k], list) else m[k]
            for k in WIDTHS}
    if ran != want:
        raise ValueError(f"the program's preset {ran} is not the "
                         f"configuration file's {want}")
    # the source's keys at the file's top level are the configuration as
    # it is run: the published ones but for the cut, layer_types whole
    as_run = {**mcfg.hf_config(), "layer_types": published["layer_types"]}
    off = {k: (cfg.get(k), v) for k, v in as_run.items() if cfg.get(k) != v}
    cut = {k for k in published if cfg[k] != published[k]}
    if (off or cut != set(REDUCED) or list(mcfg.layer_types)
            != cfg["layer_types"][:cfg["num_hidden_layers"]]):
        raise ValueError(f"the file's own keys disagree with what runs: "
                         f"{off}; cut {sorted(cut)}")
    return mcfg


def reference_spec(mcfg) -> dict:
    """What ``references/granite.py`` needs to know of the model."""
    return {k: getattr(mcfg, k) for k in (
        "layer_types", "mamba_heads", "mamba_head_dim", "ssm_state",
        "ssm_groups", "n_head", "n_kv_head", "head_dim", "rms_eps",
        "embedding_multiplier", "residual_multiplier",
        "attention_multiplier", "logits_scaling")}


def program(cfg: dict, tiny: bool, mesh=None):
    """(the model's config, the model, its loss function): what the
    step differentiates, for ``build`` and for ``tools/granite_limit.py``."""
    from ray_tpu.models.granite import Granite, granite_loss_fn

    mcfg = model_config(cfg, tiny)
    model = Granite(mcfg, mesh=mesh)
    return mcfg, model, granite_loss_fn(model, ce_chunk=cfg["ce_chunk"])


def make_params(model, seed: int):
    """The initial parameters of a run, on the device, from the seed."""
    import jax
    return jax.jit(model.init_params)(jax.random.key(seed))


def _least_step_s(cfg: dict, traffic: dict, tiny: bool) -> float:
    """No program takes less for a step (``builders/gpt2.py``): the
    required operations at the highest published peak."""
    if tiny:
        return traffic["least_step_ms"] / 1e3
    from benchlib import flops_granite, peaks

    mcfg = model_config(cfg, tiny)
    per_chip = (traffic["batch_per_chip"] * mcfg.seq_len
                * flops_granite.train_flops_per_token(mcfg))
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


def refuse_unless_the_files_kernels(notes: dict, kernel: dict, model: dict):
    """Raises where the step's notes do not say that the scans ran the
    file's path at the file's chunk and groups, and the convolution, the
    gated norm and the attention core theirs."""
    want = {"ssm_path": kernel["ssm_path"], "ssm_chunk": model["chunk"],
            "ssm_groups": model["ssm_groups"],
            "conv_path": kernel["conv_path"],
            "gate_norm_path": kernel["gate_norm_path"],
            "flash_path": kernel["flash_path"]}
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

    from benchlib import flops_granite as fg, manifest

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
    groups = cfg["reference"]["grad_groups"]
    step = _other("kimi_linear").with_first_change(
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
        then runs the float32 reference beside the live train state
        (the parameters stay on the host: the reference takes a block's
        to the device while it runs that block)."""
        import sys
        if not tiny:
            refuse_unless_the_files_kernels(_other("joyai").step_notes(),
                                            cfg["kernel"], cfg["model"])
        out = ref.loss_and_grad_norm(kept["params"], kept["batch"], spec)
        peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in mesh.devices.flat), default=0)
        print(f"granite reference done; device peak {peak / 1e9:.2f} GB",
              file=sys.stderr, flush=True)
        return out

    uniform_over = (cfg["tiny"] if tiny else cfg["loss"])["uniform_over"]
    rows = batch // chips
    kinds = "".join("*" if k == "attention" else "M"
                    for k in mcfg.layer_types)
    return {
        "init_state": init_state, "step": step, "batches": batches,
        # the step reports every number the reference returns: its first
        # dispatch is what the reference is held against, no probe needed
        "keep_for_reference": keep_for_reference, "reference": reference,
        "samples_per_step": batch * mcfg.seq_len,
        "uniform_over": uniform_over,
        "flops_per_sample": fg.train_flops_per_token(mcfg),
        "kernel_cost_per_step": fg.flash_core_train_cost(mcfg, rows),
        "shapes": {"model": f"granite {kinds} d{mcfg.n_embd} "
                            f"ssm{mcfg.mamba_heads}x{mcfg.mamba_head_dim}"
                            f"x{mcfg.ssm_state} g{mcfg.ssm_groups} "
                            f"chunk{mcfg.chunk} "
                            f"h{mcfg.n_head}/{mcfg.n_kv_head}x{mcfg.head_dim} "
                            f"mlp{mcfg.mlp_width} v{mcfg.vocab_size} tied",
                   "n_params": mcfg.num_params(), "seq_len": mcfg.seq_len,
                   "global_batch": batch,
                   "ssm_cost_per_step":
                       fg.ssm_scan_train_cost(mcfg, rows * mcfg.seq_len)},
    }
