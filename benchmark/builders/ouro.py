"""Ouro-2.6B through the program's own train path.

As ``builders/xing.py``: ``host_dataset`` runs in the driver process
(numpy only), ``build`` in the worker that holds the chips, and
everything the step is made of is the program's: ``Ouro`` with
``OuroConfig.ouro_2_6b`` cut as the configuration file says (8 of the 48
layers, every one run four times on one parameter tree; every width, the
whole vocabulary and both tables as published; the blocks recomputed in
the backward pass), ``ouro_loss_fn`` (the loss weighted row by row by the
exit distribution and a report that rides in the step's metrics: the mean
``l_t`` a pass, the mean exit step, the mean entropy),
``init_train_state``, ``make_train_step``,
``Dataset.iter_device_batches``. The parameters are made under
``jax.jit`` from the seed by the config's initialisers.

**The optimizer's first step is held to the reference's too**
(``update_norm``, as ``builders/joyai.py``), and two groups of the
gradient by themselves (the file's ``reference.grad_groups``, on both
sides: ``grad_norm_blocks``, every shared leaf, each a sum over four
applications; ``grad_norm_head``). **The gate's own gradient is reported
and not compared** (``reference.reported_grad_groups``:
``grad_norm_exit_gate``, 2,049 entries, each one cancelling sum over the
rows: PERF.md section 7 (18) has why a small leaf's norm is not held).

**The initial parameters wait on the host**, as
``builders/kimi_linear.py``'s do and with its function: the step leaves
no room to spare for a second copy of them on the chip.

The cell is refused where the attention cores did not reach the file's
flash kernel or the step's notes do not say the file's passes.
"""

from __future__ import annotations


WIDTHS = ("n_layer", "ut_steps", "n_embd", "n_head", "n_kv_head", "head_dim",
          "intermediate", "rope_theta", "rms_eps", "exit_beta", "remat",
          "seq_len", "vocab_size")
# the file's top-level keys (the source's names) that the model's group
# repeats under the program's names: they have to agree
SOURCE_KEYS = {
    "num_hidden_layers": "n_layer", "total_ut_steps": "ut_steps",
    "hidden_size": "n_embd", "num_attention_heads": "n_head",
    "num_key_value_heads": "n_kv_head", "head_dim": "head_dim",
    "intermediate_size": "intermediate", "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps", "vocab_size": "vocab_size"}


def _other(name: str):
    from benchlib import manifest
    return manifest.load_builder(name)


def _batch(t: dict, chips: int) -> int:
    if t["steps_per_dispatch"] != 1:
        raise ValueError("the ouro builder feeds one step a dispatch")
    return t["batch_per_chip"] * chips


def model_config(cfg: dict, tiny: bool):
    """The program's preset under the file's cut; refused where one of
    its widths is not the file's."""
    from ray_tpu.models.ouro import OuroConfig

    if tiny:
        # float32, as the other rehearsals
        import jax.numpy as jnp
        return getattr(OuroConfig, cfg["tiny"]["preset"])(dtype=jnp.float32)
    m = cfg["model"]
    mcfg = getattr(OuroConfig, m["preset"])(
        n_layer=m["n_layer"], seq_len=m["seq_len"], remat=m["remat"],
        exit_beta=m["exit_beta"])
    ran = {k: getattr(mcfg, k) for k in WIDTHS}
    want = {k: m[k] for k in WIDTHS}
    if ran != want:
        raise ValueError(f"the program's preset {ran} is not the "
                         f"configuration file's {want}")
    off = {k: (cfg[k], m[name]) for k, name in SOURCE_KEYS.items()
           if cfg[k] != m[name]}
    if (off or cfg["tie_word_embeddings"] or cfg["rope_scaling"] is not None
            or cfg["sliding_window"] is not None
            or set(cfg["layer_types"]) != {"full_attention"}
            or cfg["hidden_act"] != "silu"):
        raise ValueError(f"the file's own keys disagree: {off}")
    return mcfg


def reference_spec(mcfg) -> dict:
    """What ``references/ouro.py`` needs to know of the model."""
    return {k: getattr(mcfg, k) for k in (
        "n_layer", "ut_steps", "head_dim", "rope_theta", "rms_eps",
        "exit_beta")}


def program(cfg: dict, tiny: bool, mesh=None):
    """(the model's config, the model, its loss function): what the
    step differentiates, for ``build`` and for ``tools/ouro_limit.py``."""
    from ray_tpu.models.ouro import Ouro, ouro_loss_fn

    mcfg = model_config(cfg, tiny)
    model = Ouro(mcfg, mesh=mesh)
    return mcfg, model, ouro_loss_fn(model, ce_chunk=cfg["ce_chunk"])


def make_params(model, seed: int):
    """The initial parameters of a run, on the device, from the seed."""
    import jax
    return jax.jit(model.init_params)(jax.random.key(seed))


def _least_step_s(cfg: dict, traffic: dict, tiny: bool) -> float:
    """No program takes less for a step (``builders/gpt2.py``): the
    required operations at the highest published peak."""
    if tiny:
        return traffic["least_step_ms"] / 1e3
    from benchlib import flops_ouro, peaks

    mcfg = model_config(cfg, tiny)
    per_chip = (traffic["batch_per_chip"] * mcfg.seq_len
                * flops_ouro.train_flops_per_token(mcfg))
    return per_chip / max(p["bf16_flops"] for p in peaks.PEAKS.values())


def host_dataset(cfg: dict, traffic: dict, chips: int, seed: int,
                 tiny: bool, seconds: float) -> dict:
    """Uniform tokens over the whole vocabulary, from the seed; one
    pass, sized for a program that runs at the chip's published peak."""
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
    """Raises where the step's notes do not say that the cores ran in the
    file's flash kernel and the stack the file's passes, as one loop."""
    m = cfg["model"]
    want = {"flash_path": cfg["kernel"]["flash_path"],
            "attn_kind": "looped_full", "ut_steps": m["ut_steps"],
            "ut_path": cfg["kernel"]["ut_path"],
            "ce_rows": m["ut_steps"] * m["seq_len"]}
    got = {k: notes.get(k) for k in want}
    if got != want:
        raise RuntimeError(f"the step ran as {got}, not as {want}: this "
                           f"cell measures those")


def build(cfg: dict, traffic: dict, mesh, seed: int, tiny: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train

    from benchlib import flops_ouro as fo, manifest

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

    held = cfg["reference"]["grad_groups"]
    kept: dict = {}     # keep_for_reference fills it before dispatch 0
    step = kimi.with_first_change(
        train.make_train_step(
            loss_fn, opt,
            grad_groups={**held, **cfg["reference"]["reported_grad_groups"]}),
        kept)

    def batches():
        yield from train.get_dataset_shard("train").iter_device_batches(
            batch, mesh)
        raise RuntimeError(
            "the dataset ran out before the window closed: the steps "
            f"took under {_least_step_s(cfg, traffic, tiny) * 1e3:.1f} ms,"
            " which the published peak does not allow")

    ref = manifest.load_reference(cfg["reference"]["module"])
    spec = {**reference_spec(mcfg), "adamw": o, "grad_groups": held}

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
        print(f"ouro reference done; device peak {peak / 1e9:.2f} GB",
              file=sys.stderr, flush=True)
        return out

    uniform_over = (cfg["tiny"] if tiny else cfg["loss"])["uniform_over"]
    rows = batch // chips
    return {
        "init_state": init_state, "step": step, "batches": batches,
        # the step reports every number the reference returns: its first
        # dispatch is what the reference is held against, no probe needed
        "keep_for_reference": keep_for_reference, "reference": reference,
        "samples_per_step": batch * mcfg.seq_len,
        "uniform_over": uniform_over,
        "flops_per_sample": fo.train_flops_per_token(mcfg),
        "kernel_cost_per_step": fo.flash_cores_train_cost(mcfg, rows),
        "shapes": {"model": f"ouro {mcfg.n_layer}x{mcfg.ut_steps} "
                            f"d{mcfg.n_embd} h{mcfg.n_head}over"
                            f"{mcfg.n_kv_head}x{mcfg.head_dim} "
                            f"mlp{mcfg.intermediate} sandwich "
                            f"v{mcfg.vocab_size} untied",
                   "n_params": mcfg.num_params(), "seq_len": mcfg.seq_len,
                   "global_batch": batch,
                   "applications": fo.applications(mcfg),
                   "norm_cost_per_step":
                       fo.norms_train_cost(mcfg, rows * mcfg.seq_len)},
    }
