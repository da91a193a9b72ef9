"""One windowed attention layer's flash kernels, forward and forward +
backward, at the shapes of the cells that have a window (Laguna's
sliding layer 1 x 16,384 x 64 heads of 128 under 512 keys,
Phi-4-mini-flash's ``S`` layer 1 x 4,096 x 80 heads of 64 under 512,
SmallThinker's 1 x 16,384 x 28 heads of 128 under 4,096; bfloat16),
timed on the device this runs on, in blocks of the rule's choice
(``rule``: ``flash_attention.py::_window_block``) or of a given number of
rows (``512``, ``1024``). A line a (shape, block): ms a layer from the
device's operations in a ``jax.profiler`` trace of ``--reps`` calls
(``both_ms``: all of them, the cotangents' reshapes too; ``kernels_ms``:
the two custom calls alone, ``_flash_fwd`` and ``_flash_bwd``), the
block pairs a head walks, us of the kernels a walked pair (over heads x
pairs, forward + backward), the notes the call left, and each variant's
largest distance from the first one's output and gradients.
``*_wall_ms`` is the host's clock round one call.

    python3 scripts/flash_window_timing.py [--shapes laguna,phi,smallthinker]
    python3 scripts/flash_window_timing.py --shapes 1x4096x8x128x512 --blocks rule,1024
    python3 scripts/flash_window_timing.py --tiny --interpret    # the CPU
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from conv_timing import _timed  # noqa: E402  (beside this file: the
# host's best of ``reps`` calls and the device's operations in a trace)

# (batch, rows, heads, head width, window)
SHAPES = {"laguna": (1, 16384, 64, 128, 512), "phi": (1, 4096, 80, 64, 512),
          "smallthinker": (1, 16384, 28, 128, 4096)}
TINY = {"laguna": (1, 2048, 1, 128, 512), "phi": (1, 2048, 2, 64, 512),
        "smallthinker": (1, 4096, 1, 128, 2048)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="laguna,phi",
                    help="of " + ", ".join(SHAPES)
                    + ", or batch x rows x heads x width x window")
    ap.add_argument("--blocks", default="1024,512,rule",
                    help="rows of a block: numbers, or `rule`")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/flash_window_timing.jsonl")
    ap.add_argument("--tiny", action="store_true",
                    help="the named shapes with few rows and heads, which "
                    "the CPU interprets")
    ap.add_argument("--interpret", action="store_true",
                    help="the kernels interpreted: a rehearsal on the CPU")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.util import tracing
    fa = importlib.import_module("ray_tpu.ops.pallas.flash_attention")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    out = open(args.out, "a")
    for name in [s for s in args.shapes.split(",") if s]:
        shape = ((TINY if args.tiny else SHAPES).get(name)
                 or tuple(int(n) for n in name.split("x")))
        b, t, h, d, window = shape
        rng = np.random.default_rng(0)
        q, k, v, g = (jnp.asarray(rng.normal(size=(b, t, h, d)),
                                  jnp.bfloat16) for _ in range(4))
        base = None
        for block in [x for x in args.blocks.split(",") if x]:
            rows = None if block == "rule" else int(block)
            if rows and (t % rows or rows >= t):
                continue    # not a multi-block grid at this row

            def attend(q, k, v, rows=rows):
                return fa.flash_attention(q, k, v, window=window,
                                          block=rows,
                                          interpret=args.interpret)

            def both(q, k, v, g, attend=attend):
                o, vjp = jax.vjp(attend, q, k, v)
                return (o, *vjp(g))

            fwd, both = jax.jit(attend), jax.jit(both)
            tracing.take_trace_notes()
            t0 = time.monotonic()
            jax.block_until_ready(fwd(q, k, v))
            notes = {k_: v_ for k_, v_ in tracing.take_trace_notes().items()
                     if k_.startswith("flash_")}
            got = jax.block_until_ready(both(q, k, v, g))
            compile_s = time.monotonic() - t0
            got = dict(zip(("o", "dq", "dk", "dv"),
                           (x.astype(jnp.float32) for x in got)))
            base = base or got
            forward = _timed("forward", lambda: fwd(q, k, v), args.reps)
            whole = _timed("both", lambda: both(q, k, v, g), args.reps)
            pairs = notes.get("flash_band_blocks", 0)
            kernels = {k_: sum(ms for op, ms in whole["both_ops"].items()
                               if k_ in op)
                       for k_ in ("_flash_fwd", "_flash_bwd")}
            line = {
                "shape": list(shape), "block": block, "notes": notes,
                "device": jax.devices()[0].device_kind,
                **forward, **whole, "compile_s": compile_s,
                "kernels_ms": kernels, "pairs_a_head": pairs,
                "us_a_pair": (sum(kernels.values()) * 1e3
                              / (b * h * pairs) if pairs else None),
                # the largest difference, over the first variant's
                # largest entry
                "off": {k_: float(jnp.abs(got[k_] - base[k_]).max()
                                  / jnp.abs(base[k_]).max()) for k_ in got}}
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()


if __name__ == "__main__":
    main()
