"""SmallThinker-21BA3B-Instruct through the program's own train path.

As ``builders/zaya.py``: ``host_dataset`` runs in the driver process
(numpy only), ``build`` in the worker that holds the chips, and
everything the step is made of is the program's: ``SmallThinker`` with
``SmallThinkerConfig.smallthinker_21b_a3b`` cut as the configuration
file says (one period of four layers; the held experts; the slice of
the two tables), ``smallthinker_loss_fn`` (the loss against the untied
head and a report that rides in the step's metrics),
``init_train_state``, ``make_train_step``,
``Dataset.iter_device_batches``. The parameters are made under
``jax.jit`` from the seed by the config's initialisers, the embedding's
rows at unit scale (``make_params`` says why); no rule acts on the
routers: this model has no bias to balance (the file's
``assumed.balance``).

**The optimizer's first step is held to the reference's too**, with
``builders/joyai.py``'s wrapper (``with_first_change``): the first
dispatch's change of the parameters is compared with the reference's
own AdamW step. **And two numbers of the attention cores by
themselves**: the step is made with the file's ``reference.grad_groups``
(``make_train_step(grad_groups=...)``: ``grad_norm_attn_qk``, the norm
of every layer's q and k projections' gradients), and the program's
report carries ``attn_window_out_rms``; the reference returns both
under the same names. The whole gradient's norm is the head's and the
tables' before it is anything else; the first key is what a wrong
band moves, in either pass, the second what a precision a step lower
moves (``reference.rtol_why`` has the readings).

The cell is refused where the windowed layers did not reach the kernel
with the band skipped: the step's ``trace`` span has to carry the
``flash_path`` and the ``flash_window`` that the file's ``kernel`` group
names, and fewer ``flash_band_blocks`` than the causal grid walks
(``checks.py``'s count of custom calls is above zero from the experts'
grouped matmuls alone, and a kernel that masks without skipping would
read the same there).

What the seeds do to the routers is written to the worker's log after
the reference: per layer the held share of the routes and the largest
expert's load over the mean, from the reference's own float32 routes.
"""

from __future__ import annotations


SPARE_DISPATCHES = 32    # warm-up, the traced tail, the last in flight
WIDTHS = ("n_layer", "n_embd", "n_head", "n_kv_head", "head_dim", "window",
          "window_period", "rope_period", "rope_theta", "rms_eps",
          "num_experts", "experts_held", "top_k", "expert_width",
          "norm_topk_prob", "seq_len", "vocab_size")
# the file's top-level keys (the source's names) that the model's group
# repeats under the program's names: they have to agree
SOURCE_KEYS = {
    "num_hidden_layers": "n_layer", "hidden_size": "n_embd",
    "num_attention_heads": "n_head", "num_key_value_heads": "n_kv_head",
    "head_dim": "head_dim", "sliding_window_size": "window",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
    "moe_num_active_primary_experts": "top_k",
    "moe_ffn_hidden_size": "expert_width",
    "norm_topk_prob": "norm_topk_prob",
    "max_position_embeddings": "seq_len", "vocab_size": "vocab_size"}


def _joyai():
    """``builders/joyai.py``: the pieces that are the same for any cell
    whose first dispatch is held to the reference's optimizer step."""
    from benchlib import manifest
    return manifest.load_builder("joyai")


def _batch(t: dict, chips: int) -> int:
    if t["steps_per_dispatch"] != 1:
        raise ValueError("the smallthinker builder feeds one step a dispatch")
    return t["batch_per_chip"] * chips


def model_config(cfg: dict, tiny: bool):
    """The program's preset under the file's cut; refused where one of
    its widths is not the file's."""
    from ray_tpu.models.smallthinker import SmallThinkerConfig

    if tiny:
        # float32, as the other rehearsals: one route flipped by a bf16
        # activation would decide the share's comparison at this size
        import jax.numpy as jnp
        return getattr(SmallThinkerConfig, cfg["tiny"]["preset"])(
            dtype=jnp.float32)
    m = cfg["model"]
    mcfg = getattr(SmallThinkerConfig, m["preset"])(
        n_layer=m["n_layer"], experts_held=tuple(m["experts_held"]),
        vocab_size=m["vocab_size"])
    ran = {k: getattr(mcfg, k) for k in WIDTHS}
    want = {k: tuple(m[k]) if isinstance(m[k], list) else m[k]
            for k in WIDTHS}
    if ran != want:
        raise ValueError(f"the program's preset {ran} is not the "
                         f"configuration file's {want}")
    off = {k: (cfg[k], m[name]) for k, name in SOURCE_KEYS.items()
           if cfg[k] != m[name]}
    n, period = m["n_layer"], len(m["window_period"])
    if (off or cfg["moe_num_primary_experts"] != m["experts_held"][1]
            or cfg["published"]["moe_num_primary_experts"] != m["num_experts"]
            or not cfg["moe_primary_router_apply_softmax"]
            or cfg["tie_word_embeddings"] or m["tied"]
            or cfg["sliding_window_layout"][:n]
            != (m["window_period"] * n)[:n]
            or cfg["rope_layout"][:n] != (m["rope_period"] * n)[:n]
            or n % period):
        raise ValueError(f"the file's own keys disagree: {off}")
    return mcfg


def reference_spec(mcfg) -> dict:
    """What ``references/smallthinker.py`` needs to know of the model."""
    spec = {k: getattr(mcfg, k) for k in (
        "n_layer", "n_head", "n_kv_head", "head_dim", "window",
        "window_period", "rope_period", "rope_theta", "rms_eps",
        "num_experts", "top_k")}
    spec["experts_held"] = mcfg.experts_span
    return spec


def program(cfg: dict, tiny: bool, mesh=None):
    """(the model's config, the model, its loss function): what the
    step differentiates, for ``tools/limit.py``, which turns every
    value of the report into a float: the report's scalars alone
    (``build``'s step carries ``moe_load``, a row a layer, as well)."""
    from ray_tpu.models.smallthinker import (
        SmallThinker,
        smallthinker_loss_fn,
    )

    mcfg = model_config(cfg, tiny)
    model = SmallThinker(mcfg, mesh=mesh)
    whole = smallthinker_loss_fn(model, ce_chunk=cfg["ce_chunk"])

    def scalars(params, batch):
        loss, report = whole(params, batch)
        return loss, {k: v for k, v in report.items() if v.ndim == 0}
    return mcfg, model, scalars


EMBED_STD = 1.0      # the embedding's rows; every other weight 0.02


def make_params(model, seed: int, embed_std: float = EMBED_STD):
    """The initial parameters of a run, on the device, from the seed:
    the config's initialisers, and the embedding's rows at unit scale
    (``embed_std``: ``tools/smallthinker_limit.py`` reads the preset's
    own 0.02 too).

    At the preset's normal(0.02) a row of the embedding has an RMS of
    0.02 and the residual stream of random tokens is what the layers
    add to it: attention's running mean of the values, alike in every
    row. From the third layer on every token then chooses the same six
    experts (the largest expert drew 6.7-10.5 times the mean, of a
    possible 10.67) and whether they are among the 16 held is a lottery
    by seed and by layer: held shares of 8-30% in a layer, the step
    414-429 ms (my chip run, PR 40). This model has no bias to balance
    and the benchmark adds no rule; a stream that carries its tokens'
    identities is what a trained model has. At unit scale (the norm's
    output scale, what an embedding multiplied by sqrt(d) gives) the
    routers of all four layers spread their routes (the file's
    ``assumed.weights`` has the readings)."""
    import jax

    def init(key):
        params = model.init_params(key)
        scale = embed_std / 0.02
        return {**params, "wte": {
            "embedding": params["wte"]["embedding"] * scale}}
    return jax.jit(init)(jax.random.key(seed))


def _least_step_s(cfg: dict, traffic: dict, tiny: bool) -> float:
    """No program takes less for a step (``builders/gpt2.py``): the
    required operations at the highest published peak."""
    if tiny:
        return traffic["least_step_ms"] / 1e3
    from benchlib import flops_smallthinker, peaks

    mcfg = model_config(cfg, tiny)
    per_chip = (traffic["batch_per_chip"] * mcfg.seq_len
                * flops_smallthinker.train_flops_per_token(mcfg))
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


def refuse_unless_band_skipped(notes: dict, kernel: dict, causal_blocks: int):
    """Raises where the step's notes do not say that the windowed layers
    ran in the multi-block kernel under the file's window with fewer
    block pairs than the causal grid walks."""
    got = {k: notes.get(k) for k in ("flash_path", "flash_window",
                                     "flash_band_blocks")}
    if (got["flash_path"] != kernel["flash_path"]
            or got["flash_window"] != kernel["flash_window"]
            or not 0 < (got["flash_band_blocks"] or 0) < causal_blocks):
        raise RuntimeError(
            f"the windowed layers ran as {got} (layout "
            f"{notes.get('flash_layout')!r}), not the "
            f"{kernel['flash_path']!r} kernel under a window of "
            f"{kernel['flash_window']} with the band's blocks alone "
            f"(under {causal_blocks}): this cell measures that kernel")


def routes_by_layer(load, experts_held) -> list[dict]:
    """Per layer, from the routes each expert drew [L, E]: the held
    share of the routes and the largest expert's load over the mean (of
    all E, and of the held ones alone; None where none of them drew a
    route)."""
    import numpy as np
    load = np.asarray(load, dtype=np.float64)
    first, held = experts_held
    own = load[:, first:first + held]
    return [{"held_route_share": float(own[i].sum() / load[i].sum()),
             "load_max_over_mean": float(load[i].max() / load[i].mean()),
             "held_max_over_mean": (float(own[i].max() / own[i].mean())
                                    if own[i].any() else None)}
            for i in range(load.shape[0])]


def build(cfg: dict, traffic: dict, mesh, seed: int, tiny: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train

    from benchlib import flops_smallthinker as fs, manifest

    joyai = _joyai()
    chips = mesh.devices.size
    batch = _batch(traffic, chips)
    from ray_tpu.models.smallthinker import smallthinker_loss_fn

    mcfg, model, _ = program(cfg, tiny, mesh)
    loss_fn = smallthinker_loss_fn(model, ce_chunk=cfg["ce_chunk"])
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
    step = joyai.with_first_change(
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
        """The initial parameters, copied before the first dispatch
        donates them, and the first batch."""
        kept.update(params=jax.tree_util.tree_map(jnp.copy, state.params),
                    batch=first_batch)
        return kept

    def reference(kept):
        """Refuses the run where the windowed layers did not run in the
        kernel with the band skipped, then runs the float32 reference
        beside the live train state; its routes by layer and what the
        device peaked at by then go to the worker's log."""
        import json
        import sys
        if not tiny:
            from ray_tpu.ops.pallas.flash_attention import _pick_block
            blk = _pick_block(mcfg.seq_len)
            n = mcfg.seq_len // blk
            refuse_unless_band_skipped(joyai.step_notes(), cfg["kernel"],
                                       n * (n + 1) // 2)
        load: list = []
        out = ref.loss_and_grad_norm(kept["params"], kept["batch"], spec,
                                     load=load)
        print("smallthinker routes by layer: " + json.dumps(routes_by_layer(
            load, mcfg.experts_span)), file=sys.stderr, flush=True)
        peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in mesh.devices.flat), default=0)
        print(f"smallthinker reference done; device peak {peak / 1e9:.2f} GB",
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
        "flops_per_sample": fs.train_flops_per_token(mcfg),
        "kernel_cost_per_step": fs.flash_cores_train_cost(mcfg, rows),
        "shapes": {"model": f"smallthinker {mcfg.layer_kinds} d{mcfg.n_embd} "
                            f"h{mcfg.n_head}/{mcfg.n_kv_head}x{mcfg.head_dim} "
                            f"w{mcfg.window} "
                            f"e{mcfg.held}of{mcfg.num_experts}"
                            f"x{mcfg.expert_width} top{mcfg.top_k} reglu "
                            f"v{mcfg.vocab_size} untied",
                   "n_params": mcfg.num_params(), "seq_len": mcfg.seq_len,
                   "global_batch": batch,
                   "moe_cost_per_step":
                       fs.held_experts_train_cost(mcfg, tokens),
                   "window_cost_per_step":
                       fs.window_cores_train_cost(mcfg, rows),
                   "global_cost_per_step":
                       fs.global_cores_train_cost(mcfg, rows)},
    }
