"""ResNet-50 through the program's own train path, as
``bench.py::resnet50_main`` wires it: ``make_multi_train_step`` with
batch-norm state as ``extra``, K steps fused a dispatch, and the input
stack made on the device by the ``DevicePrefetcher``'s ``place``
function. The parameters are made under ``jax.jit`` from the seed.
"""

from __future__ import annotations


def host_dataset(cfg: dict, traffic: dict, chips: int, seed: int,
                 tiny: bool, seconds: float) -> None:
    if traffic["input"] != "device":
        raise ValueError("the resnet builder makes its input on the "
                         "device; a host-input cell brings its own path")
    return None


def build(cfg: dict, traffic: dict, mesh, seed: int, tiny: bool) -> dict:
    import functools
    import itertools

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding

    from ray_tpu import train
    from ray_tpu.models import ResNet, ResNet50Config
    from ray_tpu.models.resnet import resnet_loss_fn
    from ray_tpu.train.step import batch_spec

    from benchlib import flops, manifest

    m = cfg["model"]
    chips = mesh.devices.size
    if tiny:
        mcfg = getattr(ResNet50Config, cfg["tiny"]["preset"])()
        image_size = cfg["tiny"]["image_size"]
    else:
        mcfg = ResNet50Config()
        image_size = m["image_size"]
        ran = {"stage_sizes": list(mcfg.stage_sizes), "width": mcfg.width,
               "num_classes": mcfg.num_classes}
        want = {k: m[k] for k in ran}
        if ran != want:
            raise ValueError(f"the program's ResNet50Config {ran} is not "
                             f"the configuration file's {want}")
    k_steps, bsz = traffic["steps_per_dispatch"], traffic["batch_per_chip"] * chips
    o = cfg["optimizer"]
    opt = optax.sgd(o["learning_rate"], momentum=o["momentum"],
                    nesterov=o["nesterov"])
    model = ResNet(mcfg)

    def init_state():
        v = jax.jit(lambda key: model.init_variables(key, image_size))(
            jax.random.key(seed))
        return train.init_train_state(v["params"], opt, mesh,
                                      extra=v["batch_stats"])

    make = (train.make_multi_train_step if k_steps > 1
            else train.make_train_step)
    step = make(resnet_loss_fn(model), opt, has_extra=True, grad_norm=False)

    lead = 1 if k_steps > 1 else 0
    stack_sh = NamedSharding(mesh, batch_spec(mesh, batch_dim=lead))
    shape = ((k_steps, bsz) if k_steps > 1 else (bsz,))

    @functools.partial(jax.jit, out_shardings={"image": stack_sh,
                                               "label": stack_sh})
    def device_stack(key):
        k1, k2 = jax.random.split(key)
        return {"image": jax.random.normal(
                    k1, (*shape, image_size, image_size, 3), jnp.float32),
                "label": jax.random.randint(
                    k2, shape, 0, mcfg.num_classes, jnp.int32)}

    def batches():
        base = jax.random.key(seed)
        keys = (jax.random.fold_in(base, i) for i in itertools.count(1))
        with train.DevicePrefetcher(
                keys, place=device_stack,
                depth=traffic.get("prefetch_depth", 2)) as pf:
            yield from pf

    ref = manifest.load_reference(cfg["reference"]["module"])
    loss_fn = resnet_loss_fn(model)

    def keep_for_reference(state, first_batch):
        """The initial parameters and batch statistics, copied before
        the first dispatch donates them, and the first rows of the
        first step's images (batch statistics are over those rows on
        both sides)."""
        rows = min(cfg["reference"]["rows"], bsz)
        first = {k: (v[0] if k_steps > 1 else v)[:rows]
                 for k, v in first_batch.items()}
        return {"params": jax.tree_util.tree_map(jnp.copy, state.params),
                "extra": jax.tree_util.tree_map(jnp.copy, state.extra),
                "batch": first}

    def reference(kept):
        return ref.loss_and_grad_norm(kept["params"], kept["batch"],
                                      tuple(mcfg.stage_sizes))

    def program_probe(kept):
        """The fused step reports its tenth step's loss and no gradient
        norm, so the program's own loss function, in the types it is
        trained in, is differentiated once on the reference's rows."""
        (loss, _), grads = jax.jit(
            jax.value_and_grad(loss_fn, has_aux=True))(
                kept["params"], kept["extra"], kept["batch"])
        return {"loss": float(loss),
                "grad_norm": float(optax.global_norm(grads))}

    return {
        "init_state": init_state, "step": step, "batches": batches,
        "keep_for_reference": keep_for_reference, "reference": reference,
        "program_probe": program_probe,
        "samples_per_step": bsz,
        "uniform_over": mcfg.num_classes,
        "flops_per_sample": flops.resnet_train_flops_per_image(
            mcfg.stage_sizes, mcfg.width, image_size, mcfg.num_classes),
        "kernel_cost_per_step": None,
        "shapes": {"model": f"resnet stages{list(mcfg.stage_sizes)} "
                            f"w{mcfg.width} c{mcfg.num_classes}",
                   "image_size": image_size, "global_batch": bsz,
                   "steps_per_dispatch": k_steps},
    }
