"""Kimi-Linear-48B-A3B-Instruct through the program's own train path.

As ``builders/joyai.py``: ``host_dataset`` runs in the driver process
(numpy only), ``build`` in the worker that holds the chips, and
everything the step is made of is the program's: ``KimiLinear`` with
``KimiLinearConfig.kimi_linear_48b_a3b`` cut as the configuration file
says (layers 1-5: KDA with the dense MLP, KDA, KDA, MLA, KDA; the held
experts; the slice of the two tables; the blocks recomputed in the
backward pass), ``kimi_linear_loss_fn`` (the loss against the untied
head and a report that rides in the step's metrics),
``init_train_state``, ``make_train_step``,
``Dataset.iter_device_batches``. The parameters are made under
``jax.jit`` from the seed by the config's initialisers; the routers'
selection biases stay at zero (the file's ``assumed`` says why).

**The optimizer's first step is held to the reference's too**
(``update_norm``, as ``builders/joyai.py``), **and two numbers of the
recurrences by themselves**: the program's report carries
``kda_out_rms`` and the step is made with the file's
``reference.grad_groups`` (``make_train_step(grad_groups=...)``:
``grad_norm_kda_gates``, the norm of the decays' and step sizes'
gradients in every KDA layer); the reference returns both under the
same names.

**The initial parameters wait on the host.** The step is 13.6 GB of the
chip's 15.75 and a copy of the parameters 2.4: the copy that the
reference and ``update_norm`` need is taken to the host before the
first dispatch donates the state, comes back to the device once behind
that dispatch (for the change's norm; the loop has not dispatched the
second yet), and a block at a time for the reference after the window.

The cell is refused where the MLA layer did not reach its kernel or the
KDA layers ran anything but a chunked path: the step's ``trace`` span
has to carry the ``flash_path`` and a ``kda_path`` that the file's
``kernel`` group names.
"""

from __future__ import annotations


SPARE_DISPATCHES = 32    # warm-up, the traced tail, the last in flight
WIDTHS = ("n_layer", "n_embd", "rms_eps", "mla_layers", "kda_heads",
          "kda_head_dim", "conv_kernel", "kda_rank", "kda_chunk", "n_head",
          "kv_rank", "nope_dim", "rope_dim", "v_dim", "rope_theta",
          "dense_layers", "dense_width", "num_experts", "experts_held",
          "top_k", "expert_width", "shared_width", "norm_topk_prob",
          "route_scale", "remat", "seq_len", "vocab_size")
# the file's top-level keys (the source's names) that the model's group
# repeats under the program's names: they have to agree
SOURCE_KEYS = {
    "num_hidden_layers": "n_layer", "hidden_size": "n_embd",
    "rms_norm_eps": "rms_eps", "num_attention_heads": "n_head",
    "kv_lora_rank": "kv_rank", "qk_nope_head_dim": "nope_dim",
    "qk_rope_head_dim": "rope_dim", "v_head_dim": "v_dim",
    "rope_theta": "rope_theta", "first_k_dense_replace": "dense_layers",
    "intermediate_size": "dense_width",
    "num_experts_per_token": "top_k",
    "moe_intermediate_size": "expert_width",
    "moe_renormalize": "norm_topk_prob",
    "routed_scaling_factor": "route_scale", "vocab_size": "vocab_size"}


def _joyai():
    """``builders/joyai.py``: the pieces that are the same for any cell
    whose first dispatch is held to the reference's optimizer step."""
    from benchlib import manifest
    return manifest.load_builder("joyai")


def _batch(t: dict, chips: int) -> int:
    if t["steps_per_dispatch"] != 1:
        raise ValueError("the kimi_linear builder feeds one step a dispatch")
    return t["batch_per_chip"] * chips


def model_config(cfg: dict, tiny: bool):
    """The program's preset under the file's cut; refused where one of
    its widths is not the file's."""
    from ray_tpu.models.kimi_linear import KimiLinearConfig

    if tiny:
        # float32, as the other rehearsals: one route flipped by a bf16
        # activation would decide the share's comparison at this size
        import jax.numpy as jnp
        return getattr(KimiLinearConfig, cfg["tiny"]["preset"])(
            dtype=jnp.float32)
    m = cfg["model"]
    mcfg = getattr(KimiLinearConfig, m["preset"])(
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
    linear = cfg["linear_attn_config"]
    if (off or cfg["num_experts"] != m["experts_held"][1]
            or cfg["published"]["num_experts"] != m["num_experts"]
            or cfg["num_shared_experts"] * m["expert_width"]
            != m["shared_width"]
            or linear["full_attn_layers"] != m["mla_layers"]
            or (linear["num_heads"], linear["head_dim"],
                linear["short_conv_kernel_size"])
            != (m["kda_heads"], m["kda_head_dim"], m["conv_kernel"])
            or cfg["q_lora_rank"] is not None or not cfg["mla_use_nope"]
            or cfg["moe_router_activation_func"] != "sigmoid"
            or cfg["tie_word_embeddings"]):
        raise ValueError(f"the file's own keys disagree: {off}")
    return mcfg


def reference_spec(mcfg) -> dict:
    """What ``references/kimi_linear.py`` needs to know of the model."""
    spec = {k: getattr(mcfg, k) for k in (
        "n_layer", "dense_layers", "mla_layers", "kda_heads", "n_head",
        "kv_rank", "nope_dim", "rope_dim", "top_k", "norm_topk_prob",
        "route_scale", "rms_eps")}
    spec["experts_held"] = mcfg.experts_span
    return spec


def program(cfg: dict, tiny: bool, mesh=None):
    """(the model's config, the model, its loss function): what the
    step differentiates, for ``build`` and for ``tools/limit.py``."""
    from ray_tpu.models.kimi_linear import KimiLinear, kimi_linear_loss_fn

    mcfg = model_config(cfg, tiny)
    model = KimiLinear(mcfg, mesh=mesh)
    return mcfg, model, kimi_linear_loss_fn(model, ce_chunk=cfg["ce_chunk"])


def make_params(model, seed: int):
    """The initial parameters of a run, on the device, from the seed."""
    import jax
    return jax.jit(model.init_params)(jax.random.key(seed))


def _least_step_s(cfg: dict, traffic: dict, tiny: bool) -> float:
    """No program takes less for a step (``builders/gpt2.py``): the
    required operations at the highest published peak."""
    if tiny:
        return traffic["least_step_ms"] / 1e3
    from benchlib import flops_kimi_linear, peaks

    mcfg = model_config(cfg, tiny)
    per_chip = (traffic["batch_per_chip"] * mcfg.seq_len
                * flops_kimi_linear.train_flops_per_token(mcfg))
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


def refuse_unless_kernel_and_chunked(notes: dict, kernel: dict):
    """Raises where the step's notes do not say that the MLA layer ran
    in the file's flash kernel and the KDA layers on a path whose name
    has the file's ``kda_path`` in it (``chunked``: ``xla_chunked``
    today, a kernel's ``pallas_chunked`` tomorrow)."""
    got = {k: notes.get(k) for k in ("flash_path", "kda_path")}
    if (got["flash_path"] != kernel["flash_path"]
            or kernel["kda_path"] not in (got["kda_path"] or "")):
        raise RuntimeError(
            f"the mixers ran as {got} (layout "
            f"{notes.get('flash_layout')!r}), not the "
            f"{kernel['flash_path']!r} kernel and a "
            f"{kernel['kda_path']!r} recurrence: this cell measures those")


def with_first_change(step, kept: dict):
    """``builders/joyai.py::with_first_change`` for a step that leaves
    no room for a second copy of the parameters beside it: the initial
    ones come back from the host (``kept["params"]``, numpy) behind the
    first dispatch, ``update_norm`` is taken and waited for, and the
    copy is dropped before the loop dispatches again."""
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
            jax.block_until_ready(new.params)
            before = jax.device_put(kept["params"],
                                    jax.tree_util.tree_map(
                                        lambda x: x.sharding, new.params))
            norm = jax.block_until_ready(change_norm(before, new.params))
            for leaf in jax.tree_util.tree_leaves(before):
                leaf.delete()
            metrics = {**metrics, "update_norm": norm}
        return new, metrics

    first_then_plain.lower = step.lower
    first_then_plain._cache_size = step._cache_size
    return first_then_plain


def build(cfg: dict, traffic: dict, mesh, seed: int, tiny: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train

    from benchlib import flops_kimi_linear as fk, manifest

    joyai = _joyai()
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
    step = with_first_change(
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
        then runs the float32 reference beside the live train state; its
        routes by layer and what the device peaked at by then go to the
        worker's log."""
        import json
        import sys
        if not tiny:
            refuse_unless_kernel_and_chunked(joyai.step_notes(),
                                             cfg["kernel"])
        load: list = []
        # the parameters stay on the host: the reference takes a block's
        # to the device while it runs that block
        out = ref.loss_and_grad_norm(kept["params"], kept["batch"], spec,
                                     load=load)
        by_layer = manifest.load_builder("smallthinker").routes_by_layer(
            load, mcfg.experts_span)
        print("kimi_linear routes by routed layer: " + json.dumps(by_layer),
              file=sys.stderr, flush=True)
        peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in mesh.devices.flat), default=0)
        print(f"kimi_linear reference done; device peak {peak / 1e9:.2f} GB",
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
        "flops_per_sample": fk.train_flops_per_token(mcfg),
        "kernel_cost_per_step": fk.mla_core_train_cost(mcfg, rows),
        "shapes": {"model": f"kimi_linear {mcfg.layer_kinds} d{mcfg.n_embd} "
                            f"kda h{mcfg.kda_heads}x{mcfg.kda_head_dim} "
                            f"c{mcfg.kda_chunk} mla-/{mcfg.kv_rank} "
                            f"h{mcfg.n_head}x({mcfg.nope_dim}+"
                            f"{mcfg.rope_dim})/{mcfg.v_dim} nope "
                            f"e{mcfg.held}of{mcfg.num_experts}"
                            f"x{mcfg.expert_width} top{mcfg.top_k} "
                            f"v{mcfg.vocab_size} untied",
                   "n_params": mcfg.num_params(), "seq_len": mcfg.seq_len,
                   "global_batch": batch,
                   "moe_cost_per_step":
                       fk.held_experts_train_cost(mcfg, tokens),
                   "kda_scan_cost_per_step":
                       fk.kda_scan_train_cost(mcfg, rows)},
    }
