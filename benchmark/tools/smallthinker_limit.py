"""What the SmallThinker cell's comparison sees, read on the device
this runs on: ``tools/limit.py``'s two readings with the norm of every
gradient leaf beside them, the program with a fault planted in it, and
the witnesses of the preset's own embedding scale.

    python3 benchmark/tools/smallthinker_limit.py --seeds 11,12,13,14 \\
        [--low-seeds 2] [--fault-seeds 1] [--preset-init-seeds 2,3] \\
        [--tiny] [--out FILE]

``tools/limit.py`` reads the program and the float8 reference on the
numbers of a forward pass and the whole gradient's norm. The cell's own
step also reports the norm of each group of leaves that the file's
``reference.grad_groups`` names, and what a wrong band moves is there
and not in the whole norm. So this tool reads, for each seed, with
weights and tokens made as the cell makes them:

- ``program``: the program's loss function differentiated once, every
  leaf's gradient norm taken;
- ``low`` (the first ``--low-seeds``): the reference with every matmul
  operand rounded to ``float8_e4m3fn``;
- ``fault:<name>`` (the first ``--fault-seeds``): the program again
  with one thing wrong in it (``FAULTS``): the windowed layers' mask
  causal only, the window a block wider or narrower, layer 0 rotated,
  the kernels' backward alone under a window a block wider;
- for ``--preset-init-seeds``, with the embedding drawn at the preset's
  normal(0.02) (the scale ISSUE 40 assumed, at which PR 40's first chip
  run read ``correct`` false): ``program``; ``one_slab``, the program
  with a slab that holds every route, so that the loop over further
  slabs never runs; ``bf16_operands``, the reference with its matmul
  operands rounded to bfloat16; ``f32_program``, the program computing
  in float32 (a block recomputed in the backward pass, so that it
  fits), its matmuls at the backend's default precision, which on a
  TPU is one bfloat16 pass (at the highest precision the backward
  kernels ask for more VMEM than the chip has).

Each reading is its distance from the float32 reference of the same
seed and scale, key by key, as a share of it: ``loss``, ``grad_norm``,
``moe_absent_route_share``, the file's groups, and ``leaf:<path>`` for
every leaf. ``correct`` is what ``checks.py`` would say of the keys the
cell compares at the file's ``rtol`` (``update_norm``, which takes the
optimizer, is read in the cell's own runs). One JSON line a reading;
all of it also goes to ``--out``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

LOW = "float8_e4m3fn"
PRESET_STD = 0.02


def _block(mcfg) -> int:
    from ray_tpu.ops.pallas.flash_attention import _pick_block
    return _pick_block(mcfg.seq_len)


def _backward_a_block_wider():
    """``flash_attention._flash_core`` with the forward as it is and the
    two backward kernels under a window one key block wider: a fault no
    number of the forward pass can show."""
    import importlib

    import jax

    fa = importlib.import_module("ray_tpu.ops.pallas.flash_attention")

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def core(q, k, v, static):
        return fa._flash_fwd(q, k, v, **static._asdict())[0]

    def fwd(q, k, v, static):
        out, lse = fa._flash_fwd(q, k, v, **static._asdict())
        return out, (q, k, v, out, lse)

    def bwd(static, res, g):
        if static.window is not None:
            static = static._replace(window=static.window + static.bk)
        return fa._flash_bwd(*res, g, **static._asdict())

    core.defvjp(fwd, bwd)
    return core


# name -> (what to replace in the model's config, the module attributes
# to patch while the program is traced)
FAULTS = {
    "causal_only": (lambda c: {"window_period": (0,) * len(c.window_period)},
                    {}),
    "window_plus_block": (lambda c: {"window": c.window + _block(c)}, {}),
    "window_minus_block": (lambda c: {"window": c.window - _block(c)}, {}),
    "rope_in_layer_0": (lambda c: {"rope_period": (1,) * len(c.rope_period)},
                        {}),
    "backward_window_plus_block": (
        lambda c: {}, {"ray_tpu.ops.pallas.flash_attention._flash_core":
                       _backward_a_block_wider}),
}
ONE_SLAB = {"ray_tpu.ops.moe._HELD_ROOM": lambda: 1 << 20}


def _remat_block():
    """The model's ``Block`` recomputed in the backward pass: float32
    activations of all four layers at once are more than the chip holds
    (16.7 GB compiled for a v5e), those of one are not."""
    import flax.linen as nn

    from ray_tpu.models import smallthinker
    return nn.remat(smallthinker.Block, static_argnums=(2,))


F32_BY_BLOCK = {"ray_tpu.models.smallthinker.Block": _remat_block}


def _patched(patches: dict):
    """Context: each dotted attribute set to what its factory makes."""
    import contextlib
    import importlib

    @contextlib.contextmanager
    def cm():
        was = []
        for dotted, make in patches.items():
            module, name = dotted.rsplit(".", 1)
            module = importlib.import_module(module)
            was.append((module, name, getattr(module, name)))
            setattr(module, name, make())
        try:
            yield
        finally:
            for module, name, value in was:
                setattr(module, name, value)
    return cm()


def program_numbers(mcfg, ce_chunk: int, patches: dict):
    """(params, batch) -> (the loss and the report's scalars, every
    gradient leaf's squared norm by its path), jitted; the model traced
    under ``patches``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.smallthinker import (
        SmallThinker,
        smallthinker_loss_fn,
    )
    loss_fn = smallthinker_loss_fn(SmallThinker(mcfg), ce_chunk=ce_chunk)

    @jax.jit
    def numbers(params, batch):
        (loss, report), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        squares = {
            "/".join(k.key for k in path): jnp.sum(jnp.square(
                g.astype(jnp.float32)))
            for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]}
        return ({"loss": loss, **{k: v for k, v in report.items()
                                  if v.ndim == 0}}, squares)

    def run(params, batch):
        with _patched(patches):
            scalars, squares = numbers(params, batch)
        return ({k: float(v) for k, v in scalars.items()},
                {k: float(v) for k, v in squares.items()})
    run.jitted = numbers
    return run


def keyed(scalars: dict, squares: dict, groups: dict) -> dict:
    """The comparison's keys, the groups and every leaf from a reading's
    scalars and its leaves' squared norms."""
    out = {k: scalars[k] for k in ("loss", "moe_absent_route_share",
                                   "attn_window_out_rms")}
    out["grad_norm"] = math.sqrt(sum(squares.values()))
    for name, pattern in groups.items():
        out[name] = math.sqrt(sum(
            sq for path, sq in squares.items() if re.search(pattern, path)))
    out.update({"leaf:" + path: math.sqrt(sq)
                for path, sq in squares.items()})
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="")
    ap.add_argument("--low-seeds", type=int, default=2)
    ap.add_argument("--fault-seeds", type=int, default=1)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--preset-init-seeds", default="")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(HERE)), "chiprun_out",
        "smallthinker_limit.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchlib import manifest

    cell = manifest.find_cell(manifest.load_manifest(),
                              "smallthinker-21b-a3b.b1-t16384")
    cfg, traffic = cell["config_file"], cell["traffic_file"]
    builder = manifest.load_builder(cfg["builder"])
    ref = manifest.load_reference(cfg["reference"]["module"])
    rtol = cfg["reference"]["rtol"]
    groups = cfg["reference"]["grad_groups"]
    mcfg, model, _ = builder.program(cfg, args.tiny)
    rows = (traffic["tiny"] if args.tiny else traffic)["batch_per_chip"]
    vocab = (cfg["tiny"] if args.tiny else cfg["loss"])["uniform_over"]
    on_tpu = jax.default_backend() == "tpu"
    compared = ("loss", "grad_norm", "moe_absent_route_share",
                "attn_window_out_rms", *groups)
    every_leaf = {"leaf:" + "/".join(k.key for k in path): "^" + re.escape(
        "/".join(k.key for k in path)) + "$"
        for path, _ in jax.tree_util.tree_flatten_with_path(
            jax.eval_shape(model.init_params, jax.random.key(0)))[0]}

    programs: dict = {}

    def program(name, replace=None, patches=None):
        if name not in programs:
            programs[name] = program_numbers(
                dataclasses.replace(mcfg, **(replace or {})),
                cfg["ce_chunk"], patches or {})
        return programs[name]

    def reference(params, batch, operand_dtype=None):
        spec = {**builder.reference_spec(mcfg),
                "grad_groups": {**groups, **every_leaf}}
        if operand_dtype:
            spec["operand_dtype"] = operand_dtype
        return ref.loss_and_grad_norm(params, batch, spec)

    lines = []
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    def say(seed, init, reading, got, want):
        off = {k: (abs(got[k] - want[k]) / abs(want[k]) if want[k] else None)
               for k in want}
        line = {"seed": seed, "embed_std": init, "reading": reading,
                "correct": all(off[k] <= rtol for k in compared),
                "compared": {k: off[k] for k in compared},
                "load_max_over_mean": got.get("moe_load_max_over_mean"),
                "off": off, "values": {k: got[k] for k in want}}
        lines.append(line)
        print(json.dumps({k: v for k, v in line.items()
                          if k not in ("off", "values")}), flush=True)
        with open(args.out, "w") as f:
            json.dump({"device": jax.devices()[0].device_kind, "rtol": rtol,
                       "groups": groups, "low": LOW, "lines": lines}, f)

    def made(seed, embed_std):
        params = builder.make_params(model, seed, embed_std)
        toks = np.random.default_rng(seed).integers(
            0, vocab, (rows, mcfg.seq_len), dtype=np.int32)
        return params, {"tokens": jnp.asarray(toks),
                        "targets": jnp.asarray(np.roll(toks, -1, 1))}

    def read_program(name, params, batch, **how):
        scalars, squares = program(name, **how)(params, batch)
        return {**scalars, **keyed(scalars, squares, groups)}

    seeds = [int(s) for s in args.seeds.split(",") if s]
    for n, seed in enumerate(seeds):
        params, batch = made(seed, builder.EMBED_STD)
        want = reference(params, batch)
        say(seed, builder.EMBED_STD, "reference", want, want)
        say(seed, builder.EMBED_STD, "program",
            read_program("sound", params, batch), want)
        if n < args.low_seeds:
            say(seed, builder.EMBED_STD, "low",
                reference(params, batch, LOW), want)
        if n < args.fault_seeds:
            for name in args.faults.split(","):
                replace, patches = FAULTS[name]
                if patches and not on_tpu:
                    continue        # the kernels' own: the chip's path
                say(seed, builder.EMBED_STD, "fault:" + name,
                    read_program(name, params, batch,
                                 replace=replace(mcfg), patches=patches),
                    want)

    for seed in (int(s) for s in args.preset_init_seeds.split(",") if s):
        params, batch = made(seed, PRESET_STD)
        want = reference(params, batch)
        say(seed, PRESET_STD, "program",
            read_program("sound", params, batch), want)
        say(seed, PRESET_STD, "one_slab",
            read_program("one_slab", params, batch, patches=ONE_SLAB), want)
        say(seed, PRESET_STD, "bf16_operands",
            reference(params, batch, "bfloat16"), want)
        say(seed, PRESET_STD, "f32_program",
            read_program("f32", params, batch, replace={"dtype": jnp.float32},
                         patches=F32_BY_BLOCK), want)


if __name__ == "__main__":
    main()
