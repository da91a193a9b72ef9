"""JoyAI-LLM-Flash through the program's own train path.

As ``builders/nemotron_h.py``: ``host_dataset`` runs in the driver
process (numpy only), ``build`` in the worker that holds the chips, and
everything the step is made of is the program's — ``JoyAI`` with
``JoyAIConfig.joyai_llm_flash`` cut as the configuration file says (the
dense layer, four routed layers and the MTP module; the held experts;
the slice of the vocabulary), ``joyai_loss_fn`` (the two losses and a
report of five scalars that rides in the step's metrics),
``init_train_state``, ``make_train_step``,
``Dataset.iter_device_batches``. The parameters are made under
``jax.jit`` from the seed by the config's initialisers; the routers'
selection biases stay at zero (the file's ``assumed`` says why).

**The optimizer's first step is held to the reference's too.** The
reference comparison sees the loss and the gradient at the initial
parameters, and this cell's loss does not decline inside a window at a
warm-up's rate (the file's ``loss.why``), so the step's own update is
compared: the step this builder hands to the loop is ``make_train_step``'s
with one thing added to the metrics of its first dispatch,
``update_norm``, the norm of what that dispatch changed in the
parameters (its result against the copy kept for the reference); the
reference takes one float32 AdamW step from its own gradient and returns
the same norm. A state left unchanged, a rate of 0 or of another size,
or an update that is not Adam's fails it. No later dispatch is touched.

The cell is refused where latent attention did not reach its kernel:
the step's ``trace`` span has to carry the ``flash_path`` that the file's
``kernel`` group names (``checks.py``'s count of custom calls is above
zero from the experts' grouped matmuls alone, so it cannot tell).
"""

from __future__ import annotations


SPARE_DISPATCHES = 32    # warm-up, the traced tail, the last in flight
WIDTHS = ("n_layer", "n_embd", "n_head", "q_rank", "kv_rank", "nope_dim",
          "rope_dim", "v_dim", "rope_theta", "rms_eps", "dense_layers",
          "dense_width", "num_experts", "experts_held", "top_k",
          "expert_width", "shared_width", "norm_topk_prob", "route_scale",
          "mtp_depth", "mtp_weight", "seq_len", "vocab_size")
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
    "num_nextn_predict_layers": "mtp_depth", "vocab_size": "vocab_size"}


def _batch(t: dict, chips: int) -> int:
    if t["steps_per_dispatch"] != 1:
        raise ValueError("the joyai builder feeds one step a dispatch")
    return t["batch_per_chip"] * chips


def model_config(cfg: dict, tiny: bool):
    """The program's preset under the file's cut; refused where one of
    its widths is not the file's."""
    from ray_tpu.models.joyai import JoyAIConfig

    if tiny:
        # float32, as the other rehearsals: one route flipped by a bf16
        # activation would decide the share's comparison at this size
        import jax.numpy as jnp
        return getattr(JoyAIConfig, cfg["tiny"]["preset"])(dtype=jnp.float32)
    m = cfg["model"]
    mcfg = getattr(JoyAIConfig, m["preset"])(
        n_layer=m["n_layer"], experts_held=tuple(m["experts_held"]),
        vocab_size=m["vocab_size"], seq_len=m["seq_len"],
        mtp_weight=m["mtp_weight"])
    ran = {k: getattr(mcfg, k) for k in WIDTHS}
    want = {k: tuple(m[k]) if isinstance(m[k], list) else m[k]
            for k in WIDTHS}
    if ran != want:
        raise ValueError(f"the program's preset {ran} is not the "
                         f"configuration file's {want}")
    off = {k: (cfg[k], m[name]) for k, name in SOURCE_KEYS.items()
           if cfg[k] != m[name]}
    if (off or cfg["n_routed_experts"] != m["experts_held"][1]
            or cfg["published"]["n_routed_experts"] != m["num_experts"]
            or cfg["n_shared_experts"] * m["expert_width"]
            != m["shared_width"]):
        raise ValueError(f"the file's own keys disagree: {off}")
    return mcfg


def reference_spec(mcfg) -> dict:
    """What ``references/joyai.py`` needs to know of the model."""
    spec = {k: getattr(mcfg, k) for k in (
        "n_layer", "dense_layers", "mtp_depth", "mtp_weight", "n_head",
        "kv_rank", "nope_dim", "rope_dim", "rope_theta", "top_k",
        "norm_topk_prob", "route_scale", "rms_eps")}
    spec["experts_held"] = mcfg.experts_span
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
    from benchlib import flops_mla, peaks

    mcfg = model_config(cfg, tiny)
    per_chip = (traffic["batch_per_chip"] * mcfg.seq_len
                * flops_mla.train_flops_per_token(mcfg))
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


def step_notes() -> dict:
    """What the step said of itself when it was traced: the notes on
    the newest ``train.compile`` span of kind ``trace`` that carries
    ``attn_kind`` (the model's), in this worker's fit."""
    from ray_tpu.train import session
    for span in reversed(session.get_session().spans):
        a = span.attributes
        if (span.name == "train.compile" and a.get("kind") == "trace"
                and "attn_kind" in a):
            return a
    return {}


def with_first_change(step, kept: dict):
    """``step`` as the loop sees it (called, lowered, its compiles
    counted), with ``update_norm`` among the metrics of its first
    dispatch: the global norm of that dispatch's parameters less
    ``kept["params"]``, the copy made before them. Queued behind the
    step on the device; the next dispatch may donate the parameters."""
    import jax
    import optax

    @jax.jit
    def change_norm(before, after):
        return optax.global_norm(
            jax.tree_util.tree_map(lambda a, b: b - a, before, after))

    def first_then_plain(state, batch):
        new, metrics = step(state, batch)
        if not kept.get("compared"):
            kept["compared"] = True
            metrics = {**metrics, "update_norm": change_norm(
                kept["params"], new.params)}
        return new, metrics

    first_then_plain.lower = step.lower
    first_then_plain._cache_size = step._cache_size
    return first_then_plain


def build(cfg: dict, traffic: dict, mesh, seed: int, tiny: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train

    from benchlib import flops_mla, manifest

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
    step = with_first_change(train.make_train_step(loss_fn, opt), kept)

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
        """The initial parameters, copied before the first dispatch
        donates them, and the first batch."""
        kept.update(params=jax.tree_util.tree_map(jnp.copy, state.params),
                    batch=first_batch)
        return kept

    def reference(kept):
        """Refuses the run where the step's attention was not the
        kernel the file names, then runs the float32 reference beside
        the live train state; what the device peaked at by then goes to
        the worker's log (PERF.md keeps the figure)."""
        import sys
        want, notes = cfg["kernel"]["flash_path"], step_notes()
        if not tiny and notes.get("flash_path") != want:
            raise RuntimeError(
                f"latent attention ran as {notes.get('flash_path')!r} "
                f"(layout {notes.get('flash_layout')!r}), not the "
                f"{want!r} kernel: this cell measures the kernel")
        out = ref.loss_and_grad_norm(kept["params"], kept["batch"], spec)
        peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in mesh.devices.flat), default=0)
        print(f"joyai reference done; device peak {peak / 1e9:.2f} GB",
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
        "flops_per_sample": flops_mla.train_flops_per_token(mcfg),
        "kernel_cost_per_step": flops_mla.latent_attention_train_cost(
            mcfg, batch // chips),
        "shapes": {"model": f"joyai {mcfg.dense_layers}d+"
                            f"{mcfg.n_layer - mcfg.dense_layers}r+"
                            f"{mcfg.mtp_depth}mtp d{mcfg.n_embd} "
                            f"mla{mcfg.q_rank}/{mcfg.kv_rank} "
                            f"h{mcfg.n_head}x({mcfg.nope_dim}+"
                            f"{mcfg.rope_dim})/{mcfg.v_dim} "
                            f"e{mcfg.held}of{mcfg.num_experts}"
                            f"x{mcfg.expert_width} top{mcfg.top_k} "
                            f"v{mcfg.vocab_size}",
                   "n_params": mcfg.num_params(), "seq_len": mcfg.seq_len,
                   "global_batch": batch,
                   "moe_cost_per_step":
                       flops_mla.held_experts_train_cost(mcfg, tokens)},
    }
