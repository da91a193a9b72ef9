"""Kimi Delta Attention's output gate at the Kimi-Linear cell's shape (1 x
16,384 rows x 32 heads of 128; ``o`` float32 as the recurrence's kernel
writes it, or ``--o-dtype bfloat16``; a bfloat16 ``gate``, a float32
``scale`` of 128), forward and forward + backward, timed on the device
this runs on: ``ops/gated_norm.py``'s XLA function (``xla``) against the second
kernel pair of ``ops/pallas/gated_norm.py`` (``pallas``); with
``scope:`` in front the whole of a mixer's ``out_gate`` scope behind
``g_a``: ``gate = g_low @ g_b + g_bias`` from a ``[rows, 128]`` ``g_low``
and then the function, differentiated down to ``g_low``, ``g_b`` and
``g_bias``. Beside each variant's times its distance from the first
one's numbers: ``y``'s RMS and each gradient's norm (``norm_off``, a
share of the first's) and the largest entry's difference over the
first's largest entry (``off``). The times are the device's, summed over
the operations of a ``jax.profiler`` trace of ``--reps`` calls
(``conv_timing.py::_timed``).

    python3 scripts/gate_timing.py [--rows 16384] [--heads 32]
    python3 scripts/gate_timing.py --variants xla,pallas,scope:xla,scope:pallas
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=16384)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--width", type=int, default=128, help="of a head")
    ap.add_argument("--rank", type=int, default=128, help="of g_low")
    ap.add_argument("--o-dtype", default="float32")
    ap.add_argument("--variants", default="xla,pallas,scope:xla,scope:pallas")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/gate_timing.jsonl")
    ap.add_argument("--interpret", action="store_true",
                    help="the kernels interpreted: a rehearsal on the CPU")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from conv_timing import _timed
    from ray_tpu.ops import gated_norm as norms
    from ray_tpu.ops.pallas import gated_norm

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    out = open(args.out, "a")
    bf16, f32 = jnp.bfloat16, jnp.float32
    heads, c, eps = args.heads, args.heads * args.width, 1e-5
    shape = (1, args.rows, c)
    rng = np.random.default_rng(0)
    o, gate, dy = (jnp.asarray(rng.normal(size=shape), dtype)
                   for dtype in (jnp.dtype(args.o_dtype), bf16, bf16))
    scale = jnp.asarray(1 + 0.5 * rng.normal(size=(args.width,)), f32)
    g_low = jnp.asarray(rng.normal(size=(1, args.rows, args.rank)), bf16)
    g_b = jnp.asarray(rng.normal(size=(args.rank, c)) * 0.1, f32)
    g_bias = jnp.asarray(rng.normal(size=(c,)) * 0.1, f32)

    def norm_of(kind):
        if kind == "xla":
            return lambda o, gate, scale: (
                norms._sigmoid_gated_head_rms_norm_xla(o, gate, scale, heads,
                                                       eps))
        return lambda o, gate, scale: gated_norm.head_gate_norm(
            o, gate, scale, heads=heads, eps=eps, interpret=args.interpret)

    base = {}
    for variant in [v for v in args.variants.split(",") if v]:
        scope, _, kind = variant.rpartition(":")
        norm = norm_of(kind)
        if scope:       # as ``models/kimi_linear.py::_kda_core`` writes it
            def fun(o, g_low, g_b, g_bias, scale, norm=norm):
                gate = g_low @ g_b.astype(bf16) + g_bias.astype(bf16)
                return norm(o, gate, scale)
            operands = (o, g_low, g_b, g_bias, scale)
            names = ("y", "do", "dg_low", "dg_b", "dg_bias", "dscale")
        else:
            fun, operands = norm, (o, gate, scale)
            names = ("y", "do", "dgate", "dscale")

        def both(*a, fun=fun):
            y, vjp = jax.vjp(fun, *a[:-1])
            return (y, *vjp(a[-1]))

        fwd, both = jax.jit(fun), jax.jit(both)
        t0 = time.monotonic()
        jax.block_until_ready(fwd(*operands))
        got = jax.block_until_ready(both(*operands, dy))
        compile_s = time.monotonic() - t0
        got = {k: g.astype(f32) for k, g in zip(names, got)}
        first = base.setdefault(bool(scope), got)

        def size(k, x):     # the RMS of ``y``, the norm of a gradient
            return float(jnp.sqrt(jnp.mean(x * x) if k == "y"
                                  else jnp.sum(x * x)))

        line = {
            "shape": list(shape), "heads": heads, "o": str(o.dtype),
            "variant": variant,
            "device": jax.devices()[0].device_kind,
            **_timed("forward", lambda: fwd(*operands), args.reps),
            **_timed("both", lambda: both(*operands, dy), args.reps),
            "compile_s": compile_s,
            "size": {k: size(k, got[k]) for k in got},
            "norm_off": {k: abs(size(k, got[k]) / size(k, first[k]) - 1)
                         for k in got},
            "off": {k: float(jnp.abs(got[k] - first[k]).max()
                             / jnp.abs(first[k]).max()) for k in got}}
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()


if __name__ == "__main__":
    main()
