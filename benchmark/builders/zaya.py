"""ZAYA1-8B through the program's own train path.

As ``builders/joyai.py``: ``host_dataset`` runs in the driver process
(numpy only), ``build`` in the worker that holds the chips, and
everything the step is made of is the program's: ``Zaya`` with
``ZayaConfig.zaya1_8b`` cut as the configuration file says (five
layers; the held experts; the slice of the tied vocabulary),
``zaya_loss_fn`` (the loss against the tied table and a report that
rides in the step's metrics), ``init_train_state``,
``make_train_step``, ``Dataset.iter_device_batches``. The parameters
are made under ``jax.jit`` from the seed by the config's initialisers,
and the routers' balancing biases are brought into balance once, at
set-up (``make_params``; the file's ``assumed`` says why).

**The optimizer's first step is held to the reference's too**, for
``builders/joyai.py``'s reason and with its wrapper
(``with_first_change``): this cell's loss does not decline inside a
window at the rate that leaves the routers where the initialisers put
them (the file's ``loss.why``), so the first dispatch's change of the
parameters is compared with the reference's own AdamW step.

The cell is refused where attention did not reach the kernel the
file's ``kernel`` group names: the step's ``trace`` span has to carry
that ``flash_path`` (``checks.py``'s count of custom calls is above
zero from the experts' grouped matmuls alone).
"""

from __future__ import annotations


SPARE_DISPATCHES = 32    # warm-up, the traced tail, the last in flight
WIDTHS = ("n_layer", "n_embd", "n_head", "n_kv_head", "head_dim",
          "conv_taps", "rotary_dim", "rope_theta", "rms_eps", "num_experts",
          "experts_held", "expert_width", "router_width", "seq_len",
          "vocab_size")
# the file's top-level keys (the source's names) that the model's group
# repeats under the program's names: they have to agree
SOURCE_KEYS = {
    "num_hidden_layers": "n_layer", "hidden_size": "n_embd",
    "num_attention_heads": "n_head", "num_key_value_heads": "n_kv_head",
    "head_dim": "head_dim", "rms_norm_eps": "rms_eps",
    "moe_intermediate_size": "expert_width",
    "router_hidden_size": "router_width", "vocab_size": "vocab_size"}


def _joyai():
    """``builders/joyai.py``: the pieces that are the same for any cell
    whose first dispatch is held to the reference's optimizer step."""
    from benchlib import manifest
    return manifest.load_builder("joyai")


def _batch(t: dict, chips: int) -> int:
    if t["steps_per_dispatch"] != 1:
        raise ValueError("the zaya builder feeds one step a dispatch")
    return t["batch_per_chip"] * chips


def model_config(cfg: dict, tiny: bool):
    """The program's preset under the file's cut; refused where one of
    its widths is not the file's."""
    from ray_tpu.models.zaya import ZayaConfig

    if tiny:
        # float32, as the other rehearsals: one route flipped by a bf16
        # activation would decide the share's comparison at this size
        import jax.numpy as jnp
        return getattr(ZayaConfig, cfg["tiny"]["preset"])(dtype=jnp.float32)
    m = cfg["model"]
    mcfg = getattr(ZayaConfig, m["preset"])(
        n_layer=m["n_layer"], experts_held=tuple(m["experts_held"]),
        vocab_size=m["vocab_size"], seq_len=m["seq_len"])
    ran = {k: getattr(mcfg, k) for k in WIDTHS}
    want = {k: tuple(m[k]) if isinstance(m[k], list) else m[k]
            for k in WIDTHS}
    if ran != want:
        raise ValueError(f"the program's preset {ran} is not the "
                         f"configuration file's {want}")
    rope = cfg["rope_parameters"]["hybrid"]
    off = {k: (cfg[k], m[name]) for k, name in SOURCE_KEYS.items()
           if cfg[k] != m[name]}
    if (off or cfg["num_experts"] != m["experts_held"][1]
            or cfg["published"]["num_experts"] != m["num_experts"]
            or cfg["num_experts_per_tok"] != 1
            or [cfg["cca_time0"], cfg["cca_time1"]] != list(m["conv_taps"])
            or rope["rope_theta"] != m["rope_theta"]
            or rope["partial_rotary_factor"] * m["head_dim"]
            != m["rotary_dim"]
            or not cfg["tie_word_embeddings"]):
        raise ValueError(f"the file's own keys disagree: {off}")
    return mcfg


def reference_spec(mcfg) -> dict:
    """What ``references/zaya.py`` needs to know of the model."""
    spec = {k: getattr(mcfg, k) for k in (
        "n_layer", "n_head", "n_kv_head", "head_dim", "rotary_dim",
        "rope_theta", "rms_eps", "num_experts")}
    spec.update(experts_held=mcfg.experts_span, l2_eps=1e-6)
    return spec


def program(cfg: dict, tiny: bool, mesh=None):
    """(the model's config, the model, its loss function): what the
    step differentiates, for ``tools/limit.py``, which turns every
    value of the report into a float: the report's scalars alone
    (``build``'s step carries ``moe_load``, a row a layer, as well)."""
    from ray_tpu.models.zaya import Zaya, zaya_loss_fn

    mcfg = model_config(cfg, tiny)
    model = Zaya(mcfg, mesh=mesh)
    whole = zaya_loss_fn(model, ce_chunk=cfg["ce_chunk"])

    def scalars(params, batch):
        loss, report = whole(params, batch)
        return loss, {k: v for k, v in report.items() if v.ndim == 0}
    return mcfg, model, scalars


BALANCE_ROUNDS = 48
BALANCE_FIRST, BALANCE_DECAY = 0.01, 0.85


def make_params(model, seed: int):
    """The initial parameters of a run, on the device, from the seed:
    the config's initialisers, then the routers brought into balance,
    as ``builders/nemotron_h.py::make_params`` does and for its reason.

    A fresh router's top-1 choice is skewed (the largest expert draws
    2-4 times the mean) and which experts fall among the 8 held is a
    lottery: the held share was 46.5-54.6% over nine seeds and
    ``step_ms_p90`` followed it over 0.7% (PERF.md section 6, PR 38),
    against the 0.5% a new cell's runs may spread. A deployment's
    routers are in balance: that is what the balancing bias is for. So
    each layer's ``balance_bias`` is brought there by the rule of the
    sigmoid routers' bias (``b_e += g * sign(mean load - load_e)``: a
    controller on the load, outside the gradient), ``BALANCE_ROUNDS``
    rounds on one sequence of uniform tokens made from the seed, ``g``
    from ``BALANCE_FIRST`` (probabilities of 16 experts lie round
    1/16) shrinking by ``BALANCE_DECAY`` a round. The bias takes no
    gradient and is not touched again; the reference reads it from the
    tree. The tokens are an argument of the jitted round, so a second
    run finds it in the compile cache."""
    import jax
    import jax.numpy as jnp

    mcfg = model.config
    params = jax.jit(model.init_params)(jax.random.key(seed))
    layers = [f"h_{i}" for i in range(mcfg.n_layer)]
    tokens = jax.random.randint(jax.random.key(seed + 1), (1, mcfg.seq_len),
                                0, mcfg.vocab_size, jnp.int32)

    def with_biases(params, biases):
        return {**params, **{
            name: {**params[name], "mlp": {**params[name]["mlp"], "router": {
                **params[name]["mlp"]["router"], "balance_bias": bias}}}
            for name, bias in zip(layers, biases)}}

    @jax.jit
    def round_(params, biases, gain, tokens):
        _, sown = model.apply({"params": with_biases(params, biases)}, tokens,
                              return_hidden=True, mutable=["moe"])
        loads = [sown["moe"][name]["mlp"]["load"][0] for name in layers]
        return [b + gain * jnp.sign(load.mean() - load)
                for b, load in zip(biases, loads)]

    biases = [params[name]["mlp"]["router"]["balance_bias"]
              for name in layers]
    for k in range(BALANCE_ROUNDS):
        biases = round_(params, biases,
                        jnp.float32(BALANCE_FIRST * BALANCE_DECAY ** k),
                        tokens)
    return with_biases(params, biases)


def _least_step_s(cfg: dict, traffic: dict, tiny: bool) -> float:
    """No program takes less for a step (``builders/gpt2.py``): the
    required operations at the highest published peak."""
    if tiny:
        return traffic["least_step_ms"] / 1e3
    from benchlib import flops_zaya, peaks

    mcfg = model_config(cfg, tiny)
    per_chip = (traffic["batch_per_chip"] * mcfg.seq_len
                * flops_zaya.train_flops_per_token(mcfg))
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

    from benchlib import flops_zaya, manifest

    joyai = _joyai()
    chips = mesh.devices.size
    batch = _batch(traffic, chips)
    from ray_tpu.models.zaya import zaya_loss_fn

    mcfg, model, _ = program(cfg, tiny, mesh)
    loss_fn = zaya_loss_fn(model, ce_chunk=cfg["ce_chunk"])
    o = cfg["optimizer"]
    opt = optax.chain(
        optax.clip_by_global_norm(o["clip_global_norm"]),
        optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                    eps=o["eps"], weight_decay=o["weight_decay"],
                    mu_dtype=jnp.dtype(o["mu_dtype"])))

    def init_state():
        return train.init_train_state(make_params(model, seed), opt, mesh)

    kept: dict = {}     # keep_for_reference fills it before dispatch 0
    step = joyai.with_first_change(train.make_train_step(loss_fn, opt), kept)

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
        the worker's log."""
        import sys
        want, notes = cfg["kernel"]["flash_path"], joyai.step_notes()
        if not tiny and notes.get("flash_path") != want:
            raise RuntimeError(
                f"CCA's attention ran as {notes.get('flash_path')!r} "
                f"(layout {notes.get('flash_layout')!r}), not the "
                f"{want!r} kernel: this cell measures the kernel")
        out = ref.loss_and_grad_norm(kept["params"], kept["batch"], spec)
        peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in mesh.devices.flat), default=0)
        print(f"zaya reference done; device peak {peak / 1e9:.2f} GB",
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
        "flops_per_sample": flops_zaya.train_flops_per_token(mcfg),
        "kernel_cost_per_step": flops_zaya.flash_cores_train_cost(
            mcfg, batch // chips),
        "shapes": {"model": f"zaya {mcfg.n_layer}L d{mcfg.n_embd} "
                            f"cca h{mcfg.n_head}/{mcfg.n_kv_head}"
                            f"x{mcfg.head_dim} conv{mcfg.conv_taps} "
                            f"e{mcfg.held}of{mcfg.num_experts}"
                            f"x{mcfg.expert_width} top1 "
                            f"r{mcfg.router_width} v{mcfg.vocab_size} tied",
                   "n_params": mcfg.num_params(), "seq_len": mcfg.seq_len,
                   "global_batch": batch,
                   "moe_cost_per_step":
                       flops_zaya.held_experts_train_cost(mcfg, tokens),
                   "cca_mix_cost_per_step":
                       flops_zaya.cca_mix_train_cost(mcfg, tokens)},
    }
