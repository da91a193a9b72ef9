"""The two readings that the Qwen3-Next configuration's ``reference.rtol``
is set between, taken on the device this runs on: ``tools/phi4flash_limit.py``
for a reference whose ``loss_and_grads`` also returns its routes and a
program whose report carries an array (``moe_load``).

    python3 benchmark/tools/qwen3_next_limit.py --seeds 11,12 \\
        [--low-seeds 2] [--tiny] [--out FILE]

For each seed, at the configuration's widths with weights and tokens made
from the seed as the cell makes them: the numbers of the program's loss
function differentiated once (what the step's first dispatch reports;
``update_norm``, which takes the optimizer too, is read in the cell's own
runs), the norm of each group of ``reference.grad_groups`` among them
(``grad_norm_gdn_gates``, ``grad_norm_attn_qk``), and of the configuration's
reference in float32 at the highest precision. For the first
``--low-seeds`` of them also the reference with every matmul operand rounded
to ``float8_e4m3fn``, the precision under the configuration's bfloat16, which
has to come out as not correct. Each reading is given as its distance from
the float32 reference, key by key, as a share of it, with the verdict
``checks.py`` would give at the configuration's ``rtol``. One JSON line a
seed, then one of the largest distances; all of it also goes to ``--out``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

CELL = "qwen3-next-80b-a3b.b1-t16384"
LOW = "float8_e4m3fn"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--low-seeds", type=int, default=2)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(HERE)), "chiprun_out",
        "qwen3_next_limit.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchlib import manifest

    cell = manifest.find_cell(manifest.load_manifest(), CELL)
    cfg, traffic = cell["config_file"], cell["traffic_file"]
    builder = manifest.load_builder(cfg["builder"])
    ref = manifest.load_reference(cfg["reference"]["module"])
    rtol, groups = cfg["reference"]["rtol"], cfg["reference"]["grad_groups"]
    mcfg, model, loss_fn = builder.program(cfg, args.tiny)
    spec = {**builder.reference_spec(mcfg), "grad_groups": groups}
    rows = (traffic["tiny"] if args.tiny else traffic)["batch_per_chip"]
    vocab = (cfg["tiny"] if args.tiny else cfg["loss"])["uniform_over"]
    make_params = manifest.load_builder("kimi_linear").make_params

    @jax.jit
    def program(params, batch):
        (loss, report), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        squares = jax.tree_util.tree_map(
            lambda g: jnp.sum(jnp.square(g.astype(jnp.float32))), grads)
        return {"loss": loss, **{k: v for k, v in report.items()
                                 if v.ndim == 0 and k != "lm_loss"}}, squares

    def numbers(scalars: dict, squares) -> dict:
        """The cell's keys from the program's scalars and its leaves'
        squared norms: the whole norm and a norm a group."""
        by_path = {"/".join(k.key for k in path): float(leaf) for path, leaf
                   in jax.tree_util.tree_flatten_with_path(squares)[0]}
        return {**{k: float(v) for k, v in scalars.items()},
                "grad_norm": math.sqrt(sum(by_path.values())),
                **{name: math.sqrt(sum(
                    sq for path, sq in by_path.items()
                    if re.search(pattern, path)))
                   for name, pattern in groups.items()}}

    def off(got: dict, want: dict) -> dict:
        return {k: abs(got[k] - want[k]) / abs(want[k]) for k in want}

    out = {"cell": CELL, "device": jax.devices()[0].device_kind,
           "rtol": rtol, "low": LOW, "seeds": {}}
    worst = {"program": {}, "low": {}}
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        params = make_params(model, seed)
        toks = np.random.default_rng(seed).integers(
            0, vocab, (rows, mcfg.seq_len), dtype=np.int32)
        batch = {"tokens": jnp.asarray(toks),
                 "targets": jnp.asarray(np.roll(toks, -1, 1))}
        got = numbers(*program(params, batch))
        host = jax.device_get(params)
        del params
        want = ref.loss_and_grads(host, batch, spec, keep_grads=False)[0]
        line = {"reference": want, "program": off(got, want)}
        if n < args.low_seeds:
            line["low"] = off(ref.loss_and_grads(
                host, batch, {**spec, "operand_dtype": LOW},
                keep_grads=False)[0], want)
        for reading in ("program", "low"):
            if reading in line:
                line[reading + "_correct"] = all(
                    d <= rtol for d in line[reading].values())
                for k, d in line[reading].items():
                    worst[reading][k] = max(worst[reading].get(k, 0.0), d)
        out["seeds"][seed] = line
        print(json.dumps({"seed": seed, **line}), flush=True)
        out["largest"] = worst
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({"rtol": rtol, "largest": worst}), flush=True)


if __name__ == "__main__":
    main()
