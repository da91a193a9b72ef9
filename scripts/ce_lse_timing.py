"""The LM head's forward pass at each language cell's head shape, timed
alone on the device this runs on: ``models/gpt2.py``'s XLA scan over
chunks of 2,048 rows (``xla``: a chunk's matmul writes float32 logits,
``logsumexp`` reads them back) against the kernel of
``ops/pallas/ce_lse.py`` at the blocks ``blocks`` gives it (``pallas``)
or at others (``pallas:<rows>:<tile>:<strip>``, ``auto`` for one that
``blocks`` gives); each variant's largest
distance from the first one's ``lse`` and ``picked`` beside its time.

    python3 scripts/ce_lse_timing.py [--shapes gpt2,zaya] [--variants a,b]
    python3 scripts/ce_lse_timing.py --tiny --interpret    # the CPU
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# cell -> (rows a chip holds a step, E, rows of the table)
SHAPES = {
    "gpt2": (32768, 768, 50304),            # both GPT-2 cells, a chip
    "olmoe": (16384, 2048, 50304),
    "zaya": (16384, 2048, 32896),
    "kimi_linear": (16384, 2304, 20480),
    "smallthinker": (16384, 2560, 19072),
    "joyai": (8192, 2048, 16384),           # twice a step: loss, loss/mtp
    "nemotron": (8192, 2688, 16384),
    "phi4flash": (4096, 2560, 25088),
}
CHUNK = 2048


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--variants", default="xla,pallas")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--tiny", action="store_true",
                    help="every shape cut to 512 rows, E 256 and a table "
                    "of a sixteenth: a rehearsal")
    ap.add_argument("--interpret", action="store_true",
                    help="the kernel interpreted: a rehearsal on the CPU")
    ap.add_argument("--out", default="chiprun_out/ce_lse/lines.jsonl")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import gpt2
    from ray_tpu.ops.pallas import ce_lse

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    out = open(args.out, "a")
    bf16 = jnp.bfloat16
    for cell in [c for c in args.shapes.split(",") if c]:
        n, e, v = SHAPES[cell]
        chunk = CHUNK
        if args.tiny:
            n, e, v, chunk = 512, 256, v // 16 // 128 * 128, 256
        rng = np.random.default_rng(0)
        # rows as a final norm leaves them, a table at the models' scale
        rows = jnp.asarray(rng.normal(size=(n, e)), bf16)
        emb = jnp.asarray(rng.normal(size=(v, e)) * 0.02, bf16)
        tgt = jnp.asarray(rng.integers(0, v, size=(n,)), jnp.int32)
        base = None
        for name in [x for x in args.variants.split(",") if x]:
            kind, *rest = name.split(":")
            if kind == "xla":
                def run(rows, emb, tgt, chunk=chunk):
                    _, lse = gpt2._chunked_ce_fwd_scan(
                        rows.reshape(-1, chunk, e), emb,
                        tgt.reshape(-1, chunk), -1)
                    return lse.reshape(-1), None
                fn = jax.jit(run)
            else:
                br, tile = ce_lse.blocks(n, e, v)
                strip = ce_lse._STRIP
                auto = (br, tile, strip)
                if rest:
                    br, tile, strip = (a if z == "auto" else int(z)
                                       for z, a in zip(rest, auto))
                if br % strip:
                    strip = br
                fn = jax.jit(lambda rows, emb, tgt, br=br, tile=tile,
                             strip=strip: ce_lse._ce_lse_fwd(
                    rows, emb, tgt, block_rows=br, tile=min(tile, v),
                    strip=strip, interpret=args.interpret))
            t0 = time.monotonic()
            try:
                lse, picked = jax.block_until_ready(fn(rows, emb, tgt))
            except Exception as ex:  # noqa: BLE001 — a variant refused
                line = {"cell": cell, "variant": name,
                        "error": str(ex)[-400:]}
                print(json.dumps(line), flush=True)
                out.write(json.dumps(line) + "\n")
                continue
            compile_s = time.monotonic() - t0
            best = 1e9
            for _ in range(args.reps):
                t0 = time.monotonic()
                jax.block_until_ready(fn(rows, emb, tgt))
                best = min(best, time.monotonic() - t0)
            if picked is None:
                picked = jnp.sum(rows.astype(jnp.float32)
                                 * emb[tgt].astype(jnp.float32), axis=-1)
            base = base or (lse, picked)
            line = {
                "cell": cell, "rows": n, "e": e, "v": v, "variant": name,
                "device": jax.devices()[0].device_kind,
                "ms": best * 1e3, "compile_s": compile_s,
                # the matmul alone at the chip's published bfloat16 peak
                "mxu_peak_ms": 2 * n * e * v / 197e12 * 1e3,
                "lse_off": float(jnp.abs(lse - base[0]).max()),
                "picked_off": float(jnp.abs(picked - base[1]).max()),
                "lse_mean": float(lse.mean())}
            if kind != "xla":
                line.update(block_rows=br, tile=min(tile, v), strip=strip)
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
    out.close()


if __name__ == "__main__":
    main()
