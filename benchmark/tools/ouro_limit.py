"""The two readings that the Ouro-2.6B configuration's ``reference.rtol``
is set between, taken on the device this runs on:
``tools/phi4flash_limit.py`` with this cell's name written in (no routed
layer, **the configuration's** ``reference.grad_groups`` **on both
sides**, and ``reference.reported_grad_groups`` on both sides too, given
and not judged: the gate's own gradient).

    python3 benchmark/tools/ouro_limit.py --seeds 11,12 \\
        [--low-seeds 2] [--leaves] [--tiny] [--out FILE]

For each seed, at the configuration's widths with weights and tokens made
from the seed as the cell makes them: the numbers of the program's loss
function differentiated once (what the step's first dispatch reports;
``update_norm``, which takes the optimizer too, is read in the cell's own
runs), the norm of each group of ``grad_groups`` among them, and of the
configuration's reference in float32 at the highest precision. For the
first ``--low-seeds`` of them also the reference with every matmul operand
rounded to ``float8_e4m3fn``, the precision under the configuration's
bfloat16. Each reading is given as its distance from the float32
reference, key by key, as a share of it, with the verdict ``checks.py``
would give at the configuration's ``rtol``: the program has to pass on
every seed, and the low reading should fail. With ``--leaves`` the same
distance for the norm of every gradient leaf: what says whether a group is
well-conditioned before it is named as a key (a scalar's or a small leaf's
gradient is not: PERF.md section 6, PR 48). One JSON line a seed, then one
of the largest distances; all of it also goes to ``--out``.
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

CELL = "ouro-2.6b.b1-t4096"
LOW = "float8_e4m3fn"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--low-seeds", type=int, default=2)
    ap.add_argument("--leaves", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(HERE)), "chiprun_out",
        "ouro_limit.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchlib import manifest

    cell = manifest.find_cell(manifest.load_manifest(), CELL)
    cfg, traffic = cell["config_file"], cell["traffic_file"]
    builder = manifest.load_builder(cfg["builder"])
    ref = manifest.load_reference(cfg["reference"]["module"])
    rtol, held = cfg["reference"]["rtol"], cfg["reference"]["grad_groups"]
    groups = {**held, **cfg["reference"]["reported_grad_groups"]}
    mcfg, model, loss_fn = builder.program(cfg, args.tiny)
    spec = {**builder.reference_spec(mcfg), "grad_groups": groups}
    rows = (traffic["tiny"] if args.tiny else traffic)["batch_per_chip"]
    vocab = (cfg["tiny"] if args.tiny else cfg["loss"])["uniform_over"]

    @jax.jit
    def program(params, batch):
        (loss, report), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return {"loss": loss, **report}, jax.tree_util.tree_map(
            lambda g: jnp.sum(jnp.square(g.astype(jnp.float32))), grads)

    def by_path(tree) -> dict:
        return {"/".join(k.key for k in path): float(np.sum(np.square(
            np.asarray(leaf, np.float64)))) if np.ndim(leaf) else float(leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}

    def numbers(scalars: dict, squares: dict) -> dict:
        """The cell's keys from a side's scalars and its leaves' squared
        norms: the whole norm and a norm a group."""
        return {**scalars, "grad_norm": math.sqrt(sum(squares.values())),
                **{name: math.sqrt(sum(
                    sq for path, sq in squares.items()
                    if re.search(pattern, path)))
                   for name, pattern in groups.items()}}

    def off(got: dict, want: dict) -> dict:
        return {k: abs(got[k] - want[k]) / abs(want[k]) for k in want}

    reported = set(cfg["reference"]["reported_grad_groups"])

    def reference(params, batch, low: bool):
        out, grads = ref.loss_and_grads(
            params, batch, {**spec, **({"operand_dtype": LOW} if low else {})})
        return out, by_path(grads)

    out = {"cell": CELL, "device": jax.devices()[0].device_kind,
           "rtol": rtol, "low": LOW, "seeds": {}}
    worst = {"program": {}, "low": {}}
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        params = builder.make_params(model, seed)
        toks = np.random.default_rng(seed).integers(
            0, vocab, (rows, mcfg.seq_len), dtype=np.int32)
        batch = {"tokens": jnp.asarray(toks),
                 "targets": jnp.asarray(np.roll(toks, -1, 1))}
        scalars, squares = program(params, batch)
        got_sq = by_path(squares)
        got = numbers({k: float(v) for k, v in scalars.items()}, got_sq)
        host = jax.device_get(params)
        want, want_sq = reference(host, batch, low=False)
        line = {"reference": want, "program": off(got, want)}
        sides = {"program": got_sq}
        if n < args.low_seeds:
            low, low_sq = reference(host, batch, low=True)
            line["low"] = off(low, want)
            sides["low"] = low_sq
        for reading in ("program", "low"):
            if reading in line:
                line[reading + "_correct"] = all(
                    d <= rtol for k, d in line[reading].items()
                    if k not in reported)
                for k, d in line[reading].items():
                    worst[reading][k] = max(worst[reading].get(k, 0.0), d)
        if args.leaves:
            line["leaves"] = {
                path: {"reference": math.sqrt(sq), **{
                    side: abs(math.sqrt(got[path]) - math.sqrt(sq))
                    / math.sqrt(sq) for side, got in sides.items()}}
                for path, sq in want_sq.items() if sq > 0}
        out["seeds"][seed] = line
        print(json.dumps({"seed": seed, **{k: v for k, v in line.items()
                                           if k != "leaves"}}), flush=True)
        out["largest"] = worst
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({"rtol": rtol, "largest": worst}), flush=True)


if __name__ == "__main__":
    main()
