"""The two readings that ``configs/olmoe-1b-7b.json``'s
``reference.rtol`` is set between, taken on the device this runs on.

    python3 benchmark/tools/olmoe_limit.py --seeds 11,12,13 \\
        [--low-seeds 2] [--tiny] [--out FILE]

For each seed, at the configuration's widths with weights and tokens
made from the seed as the cell makes them: the six numbers of the
program's loss function differentiated once (what the step's first
dispatch reports), and of ``references/olmoe.py`` in float32 at the
highest precision. For the first ``--low-seeds`` of them also the
reference with every matmul operand rounded to ``float8_e4m3fn``,
the precision under the configuration's bfloat16. Each reading is given as
its distance from the float32 reference, key by key, as a share of it,
with the verdict ``checks.py`` would give at the configuration's
``rtol``: the program has to pass on every seed, and the low reading
should fail. On the v5e it fails on 12 seeds of 18, by the load alone
(PERF.md 6: the six numbers are means and norms, which zero-mean
rounding noise moves little). One JSON line a seed, then one of the
largest distances; all of it also goes to ``--out``
(``chiprun_out/olmoe_limit.json``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

CELL = "olmoe-1b-7b.b4-t4096"
LOW = "float8_e4m3fn"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--low-seeds", type=int, default=2)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(HERE)), "chiprun_out",
        "olmoe_limit.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchlib import manifest
    from ray_tpu.models import Llama
    from ray_tpu.models.llama import llama_loss_fn

    cell = manifest.find_cell(manifest.load_manifest(), CELL)
    cfg, traffic = cell["config_file"], cell["traffic_file"]
    builder = manifest.load_builder(cfg["builder"])
    ref = manifest.load_reference(cfg["reference"]["module"])
    rtol = cfg["reference"]["rtol"]
    mcfg = builder.model_config(cfg, args.tiny)
    spec = builder.reference_spec(cfg, mcfg)
    model = Llama(mcfg)
    rows = (traffic["tiny"] if args.tiny else traffic)["batch_per_chip"]
    vocab = (cfg["tiny"] if args.tiny else cfg["loss"])["uniform_over"]
    program = jax.jit(jax.value_and_grad(
        llama_loss_fn(model, ce_chunk=cfg["ce_chunk"]), has_aux=True))

    def off(got: dict, want: dict) -> dict:
        return {k: abs(got[k] - want[k]) / abs(want[k]) for k in want}

    out = {"device": jax.devices()[0].device_kind, "rtol": rtol,
           "low": LOW, "seeds": {}}
    worst = {"program": {}, "low": {}}
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        params = jax.jit(model.init_params)(jax.random.key(seed))
        toks = np.random.default_rng(seed).integers(
            0, vocab, (rows, mcfg.seq_len), dtype=np.int32)
        batch = {"tokens": jnp.asarray(toks),
                 "targets": jnp.asarray(np.roll(toks, -1, 1))}
        (loss, report), grads = program(params, batch)
        got = {"loss": float(loss),
               "grad_norm": float(optax.global_norm(grads)),
               **{k: float(v) for k, v in report.items()}}
        del grads
        want = ref.loss_and_grad_norm(params, batch, spec)
        line = {"reference": want, "program": off(got, want)}
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
