"""The depthwise causal convolution + SiLU at the three cells' shapes
(Kimi-Linear's 1 x 16,384 x 4,096 without a bias, Nemotron's 1 x 8,192
x 6,144 and Phi-4-mini-flash's 1 x 4,096 x 5,120 with one; bfloat16
rows, 4 float32 taps), forward and forward + backward, timed on the
device this runs on: ``ops/conv1d.py``'s XLA function (``xla``) against the
kernel pair of ``ops/pallas/causal_conv.py`` at its own blocks
(``pallas``) or at given ones (``pallas:<rows>:<strip>:<width>[:<lanes>]``,
0 for the kernel's own); each variant's largest distance from the first
one's ``y`` and three cotangents beside its times. The times are the
device's, summed over the operations of a ``jax.profiler`` trace of
``--reps`` calls; ``*_wall_ms`` is the host's clock round one call.

    python3 scripts/conv_timing.py [--shapes kimi,nemotron,phi] [--bias both]
    python3 scripts/conv_timing.py --variants xla,pallas,pallas:512:64:128
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHAPES = {"kimi": (1, 16384, 4096), "nemotron": (1, 8192, 6144),
          "phi": (1, 4096, 5120)}
# what the cell's model does
BIAS = {"kimi": False, "nemotron": True, "phi": True}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="kimi,nemotron,phi",
                    help="of " + ", ".join(SHAPES) + ", or b,t,c")
    ap.add_argument("--bias", choices=("cell", "both", "yes", "no"),
                    default="both")
    ap.add_argument("--variants", default="xla,pallas")
    ap.add_argument("--taps", type=int, default=4)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/conv_timing.jsonl")
    ap.add_argument("--interpret", action="store_true",
                    help="the kernels interpreted: a rehearsal on the CPU")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import conv1d
    from ray_tpu.ops.pallas import causal_conv

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    out = open(args.out, "a")
    bf16, f32 = jnp.bfloat16, jnp.float32
    for name in [s for s in args.shapes.split(",") if s]:
        shape = SHAPES.get(name) or tuple(int(n) for n in name.split("x"))
        biases = {"cell": [BIAS.get(name, True)], "both": [False, True],
                  "yes": [True], "no": [False]}[args.bias]
        rng = np.random.default_rng(0)
        x, dy = (jnp.asarray(rng.normal(size=shape), bf16) for _ in range(2))
        w = jnp.asarray(rng.uniform(-0.5, 0.5, (args.taps, shape[-1])), f32)
        for bias in biases:
            b = (jnp.asarray(rng.uniform(-0.5, 0.5, shape[-1:]), f32)
                 if bias else None)
            base = None
            for variant in [v for v in args.variants.split(",") if v]:
                kind, *numbers = variant.split(":")
                if kind == "xla":
                    conv = conv1d._causal_conv1d_silu_xla
                else:
                    blocks = {k: int(n) for k, n in zip(
                        ("rows", "strip", "width", "lanes"), numbers)
                        if int(n)}

                    def conv(x, w, b, blocks=blocks):
                        return causal_conv.causal_conv(
                            x, w, b, interpret=args.interpret, **blocks)

                def both(x, w, b, dy, conv=conv):
                    y, vjp = jax.vjp(lambda x, w, b: conv(x, w, b), x, w, b)
                    return (y, *vjp(dy))

                fwd, both = jax.jit(conv), jax.jit(both)
                t0 = time.monotonic()
                jax.block_until_ready(fwd(x, w, b))
                got = jax.block_until_ready(both(x, w, b, dy))
                compile_s = time.monotonic() - t0
                got = dict(zip(("y", "dx", "dw", "dbias"),
                               (g.astype(f32) for g in got if g is not None)))
                base = base or got
                line = {
                    "shape": list(shape), "bias": bias, "variant": variant,
                    "device": jax.devices()[0].device_kind,
                    **_timed("forward", lambda: fwd(x, w, b), args.reps),
                    **_timed("both", lambda: both(x, w, b, dy), args.reps),
                    "compile_s": compile_s,
                    # the largest difference, over the first variant's
                    # largest entry
                    "off": {k: float(jnp.abs(got[k] - base[k]).max()
                                     / jnp.abs(base[k]).max()) for k in got}}
                print(json.dumps(line), flush=True)
                out.write(json.dumps(line) + "\n")
                out.flush()


def _timed(name: str, run, reps: int) -> dict:
    """``run()``'s time a call: the host's clock at its best of
    ``reps``, and the device's operations in a trace of ``reps`` calls
    (their sum, their number, and the longest by name)."""
    import jax
    wall = 1e9
    for _ in range(reps):
        t0 = time.monotonic()
        jax.block_until_ready(run())
        wall = min(wall, time.monotonic() - t0)
    with tempfile.TemporaryDirectory(dir=os.environ.get("TMPDIR")) as d:
        with jax.profiler.trace(d):
            for _ in range(reps):
                jax.block_until_ready(run())
        files = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        profile = jax.profiler.ProfileData.from_file(files[0])
    ms = collections.Counter()
    for plane in profile.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    ms[e.name.split(" = ")[0]] += e.duration_ns / 1e6 / reps
    return {f"{name}_ms": sum(ms.values()), f"{name}_wall_ms": wall * 1e3,
            f"{name}_n_ops": len(ms),
            f"{name}_ops": {k: round(v, 4) for k, v in ms.most_common(6)}}


if __name__ == "__main__":
    main()
