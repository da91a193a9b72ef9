"""Phi-4-mini-flash-reasoning through the program's own train path.

As ``builders/kimi_linear.py``: ``host_dataset`` runs in the driver
process (numpy only), ``build`` in the worker that holds the chips, and
everything the step is made of is the program's: ``Phi4Flash`` with
``Phi4FlashConfig.phi_4_mini_flash_reasoning`` cut as the configuration
file says (the architecture's rule at eight layers, ``M S M S M* F G
X``; the slice of the tied table; the blocks recomputed in the backward
pass), ``phi4flash_loss_fn`` (the loss against the tied table and a
report that rides in the step's metrics), ``init_train_state``,
``make_train_step``, ``Dataset.iter_device_batches``. The parameters are
made under ``jax.jit`` from the seed by the config's initialisers.

**The optimizer's first step is held to the reference's too**
(``update_norm``, as ``builders/joyai.py``), **and four numbers of the
new mechanisms by themselves**: the program's report carries
``mamba_out_rms`` and the step is made with the file's
``reference.grad_groups`` (``make_train_step(grad_groups=...)``:
``grad_norm_mamba_ssm``, ``grad_norm_attn_diff``, ``grad_norm_yoco_kv``);
the reference returns all four under the same names.

**The initial parameters wait on the host**, as
``builders/kimi_linear.py`` keeps them (its ``with_first_change``): the
step is 12.6 GB of the chip's 15.75 and a copy of the parameters 3.7.

The cell is refused where the attention layers did not reach the
multi-block flash kernels, the windowed ones under the file's window,
or the scans ran anything but a chunked path: the step's ``trace`` span
has to carry the ``flash_path``, ``flash_window`` and an ``ssm_path``
that the file's ``kernel`` group names.
"""

from __future__ import annotations


SPARE_DISPATCHES = 32    # warm-up, the traced tail, the last in flight
WIDTHS = ("n_layer", "n_embd", "ln_eps", "mb_per_layer", "n_head",
          "n_kv_head", "head_dim", "window", "mlp_width", "mamba_inner",
          "ssm_state", "conv_kernel", "dt_rank", "ssm_chunk", "remat",
          "seq_len", "vocab_size")
# the file's top-level keys (the source's names) that the model's group
# repeats under the program's names: they have to agree
SOURCE_KEYS = {
    "num_hidden_layers": "n_layer", "hidden_size": "n_embd",
    "layer_norm_eps": "ln_eps", "mb_per_layer": "mb_per_layer",
    "num_attention_heads": "n_head", "num_key_value_heads": "n_kv_head",
    "sliding_window": "window", "intermediate_size": "mlp_width",
    "vocab_size": "vocab_size"}


def _other(name: str):
    from benchlib import manifest
    return manifest.load_builder(name)


def _batch(t: dict, chips: int) -> int:
    if t["steps_per_dispatch"] != 1:
        raise ValueError("the phi4flash builder feeds one step a dispatch")
    return t["batch_per_chip"] * chips


def model_config(cfg: dict, tiny: bool):
    """The program's preset under the file's cut; refused where one of
    its widths is not the file's."""
    from ray_tpu.models.phi4flash import Phi4FlashConfig

    if tiny:
        # float32, as the other rehearsals
        import jax.numpy as jnp
        return getattr(Phi4FlashConfig, cfg["tiny"]["preset"])(
            dtype=jnp.float32)
    m = cfg["model"]
    mcfg = getattr(Phi4FlashConfig, m["preset"])(
        n_layer=m["n_layer"], vocab_size=m["vocab_size"],
        seq_len=m["seq_len"], remat=m["remat"])
    ran = {k: getattr(mcfg, k) for k in WIDTHS}
    want = {k: m[k] for k in WIDTHS}
    if ran != want or mcfg.layer_kinds != m["layer_kinds"]:
        raise ValueError(f"the program's preset {ran} ({mcfg.layer_kinds}) "
                         f"is not the configuration file's {want}")
    off = {k: (cfg[k], m[name]) for k, name in SOURCE_KEYS.items()
           if cfg[k] != m[name]}
    if (off or not cfg["tie_word_embeddings"] or cfg["mlp_bias"]
            or cfg["lm_head_bias"] or cfg["hidden_act"] != "silu"
            or cfg["hidden_size"] != m["n_head"] * m["head_dim"]):
        raise ValueError(f"the file's own keys disagree: {off}")
    return mcfg


def reference_spec(mcfg) -> dict:
    """What ``references/phi4flash.py`` needs to know of the model."""
    return {k: getattr(mcfg, k) for k in (
        "n_layer", "n_head", "n_kv_head", "head_dim", "window", "ssm_state",
        "dt_rank", "ln_eps")}


def program(cfg: dict, tiny: bool, mesh=None):
    """(the model's config, the model, its loss function): what the
    step differentiates, for ``build`` and for ``tools/limit.py``."""
    from ray_tpu.models.phi4flash import Phi4Flash, phi4flash_loss_fn

    mcfg = model_config(cfg, tiny)
    model = Phi4Flash(mcfg, mesh=mesh)
    return mcfg, model, phi4flash_loss_fn(model, ce_chunk=cfg["ce_chunk"])


def make_params(model, seed: int):
    """The initial parameters of a run, on the device, from the seed."""
    import jax
    return jax.jit(model.init_params)(jax.random.key(seed))


def _least_step_s(cfg: dict, traffic: dict, tiny: bool) -> float:
    """No program takes less for a step (``builders/gpt2.py``): the
    required operations at the highest published peak."""
    if tiny:
        return traffic["least_step_ms"] / 1e3
    from benchlib import flops_phi4flash, peaks

    mcfg = model_config(cfg, tiny)
    per_chip = (traffic["batch_per_chip"] * mcfg.seq_len
                * flops_phi4flash.train_flops_per_token(mcfg))
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


def refuse_unless_band_and_chunked(notes: dict, kernel: dict):
    """Raises where the step's notes do not say that attention ran in
    the file's flash kernel, the windowed layers under the file's
    window, and the scans on a path whose name has the file's
    ``ssm_path`` in it (``chunked``: ``xla_chunked`` today, a kernel's
    ``pallas_chunked`` tomorrow)."""
    got = {k: notes.get(k) for k in ("flash_path", "flash_window",
                                     "ssm_path")}
    if (got["flash_path"] != kernel["flash_path"]
            or got["flash_window"] != kernel["flash_window"]
            or kernel["ssm_path"] not in (got["ssm_path"] or "")):
        raise RuntimeError(
            f"the mixers ran as {got} (layout "
            f"{notes.get('flash_layout')!r}), not the "
            f"{kernel['flash_path']!r} kernel under a window of "
            f"{kernel['flash_window']} keys and a {kernel['ssm_path']!r} "
            "scan: this cell measures those")


def build(cfg: dict, traffic: dict, mesh, seed: int, tiny: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train

    from benchlib import flops_phi4flash as fp, manifest

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
            refuse_unless_band_and_chunked(_other("joyai").step_notes(),
                                           cfg["kernel"])
        out = ref.loss_and_grad_norm(kept["params"], kept["batch"], spec)
        peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in mesh.devices.flat), default=0)
        print(f"phi4flash reference done; device peak {peak / 1e9:.2f} GB",
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
        "flops_per_sample": fp.train_flops_per_token(mcfg),
        "kernel_cost_per_step": fp.flash_cores_train_cost(mcfg, rows),
        "shapes": {"model": f"phi4flash {mcfg.layer_kinds} d{mcfg.n_embd} "
                            f"mamba1 c{mcfg.mamba_inner}x{mcfg.ssm_state} "
                            f"chunk{mcfg.ssm_chunk} diff "
                            f"h{mcfg.n_head}/{mcfg.n_kv_head}x{mcfg.head_dim} "
                            f"w{mcfg.window} mlp{mcfg.mlp_width} "
                            f"v{mcfg.vocab_size} tied",
                   "n_params": mcfg.num_params(), "seq_len": mcfg.seq_len,
                   "global_batch": batch,
                   "window_cost_per_step":
                       fp.window_cores_train_cost(mcfg, rows),
                   "ssm_cost_per_step": fp.ssm_scan_train_cost(mcfg, rows)},
    }
