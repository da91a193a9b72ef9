"""The two readings that a configuration's ``reference.rtol`` is set
between, taken on the device this runs on, for any cell whose builder
has ``program(cfg, tiny) -> (model config, model, loss function)``
beside ``reference_spec`` and ``make_params`` (``builders/joyai.py``;
``tools/nemotron_limit.py`` and ``tools/olmoe_limit.py`` are this file
with their models' names written in: the next ``benchmark`` PR can give
their builders the function and keep one tool).

    python3 benchmark/tools/limit.py --cell joyai-llm-flash.b1-t8192 \\
        --seeds 11,12,13 [--low-seeds 2] [--tiny] [--out FILE]

For each seed, at the configuration's widths with weights and tokens
made from the seed as the cell makes them: the numbers of the program's
loss function differentiated once (what the step's first dispatch
reports; ``update_norm``, which takes the optimizer too, is read in the
cell's own runs), and of the configuration's reference
in float32 at the highest precision. For
the first ``--low-seeds`` of them also the reference with every matmul
operand rounded to ``float8_e4m3fn``, the precision under the
configuration's bfloat16. Each reading is given as its distance from
the float32 reference, key by key, as a share of it, with the verdict
``checks.py`` would give at the configuration's ``rtol``: the program
has to pass on every seed, and the low reading should fail. One JSON
line a seed, then one of the largest distances; all of it also goes to
``--out`` (``chiprun_out/limit.json``).

The program's gradient lives beside the parameters here and nothing
else does (no optimizer state), so both fit the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

LOW = "float8_e4m3fn"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--low-seeds", type=int, default=2)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(HERE)), "chiprun_out",
        "limit.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchlib import manifest

    cell = manifest.find_cell(manifest.load_manifest(), args.cell)
    cfg, traffic = cell["config_file"], cell["traffic_file"]
    builder = manifest.load_builder(cfg["builder"])
    ref = manifest.load_reference(cfg["reference"]["module"])
    rtol = cfg["reference"]["rtol"]
    mcfg, model, loss_fn = builder.program(cfg, args.tiny)
    spec = builder.reference_spec(mcfg)
    rows = (traffic["tiny"] if args.tiny else traffic)["batch_per_chip"]
    vocab = (cfg["tiny"] if args.tiny else cfg["loss"])["uniform_over"]

    @jax.jit
    def program(params, batch):
        (loss, report), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return {"loss": loss, "grad_norm": optax.global_norm(grads),
                **report}

    def off(got: dict, want: dict) -> dict:
        return {k: abs(got[k] - want[k]) / abs(want[k]) for k in want}

    out = {"cell": args.cell, "device": jax.devices()[0].device_kind,
           "rtol": rtol,
           "low": LOW, "seeds": {}}
    worst = {"program": {}, "low": {}}
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        params = builder.make_params(model, seed)
        toks = np.random.default_rng(seed).integers(
            0, vocab, (rows, mcfg.seq_len), dtype=np.int32)
        batch = {"tokens": jnp.asarray(toks),
                 "targets": jnp.asarray(np.roll(toks, -1, 1))}
        got = {k: float(v) for k, v in program(params, batch).items()}
        want = ref.loss_and_grad_norm(params, batch, spec)
        line = {"reference": want, "program": off(got, want),
                "program_load_max_over_mean": got["moe_load_max_over_mean"]}
        if n < args.low_seeds:
            line["low"] = off(ref.loss_and_grad_norm(
                params, batch, {**spec, "operand_dtype": LOW}), want)
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
