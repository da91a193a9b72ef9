"""One sub-layer's residual maps at the Xing4.0 cell's shape (a state of 1
x 4,096 tokens x 4 streams of 3,584 in bfloat16, ``phi`` ``[14336, 24]``,
20 Sinkhorn iterations), forward and forward + backward (down to the
state's, ``phi``'s, ``b``'s and ``alpha``'s cotangents), timed on the
device this runs on: ``ops/hyper_connections.py``'s ``jax.numpy``
function (``xla``) against the kernel pair of ``ops/pallas/hc_maps.py``
(``pallas``). The kernels' edge is the chain from the product and the
norm's factor to the maps and back; XLA reads the state on both paths
(PERF.md section 6, PR 60, has the readings of a wider pair that read it
itself, faster here and slower in the step, and of blocks of 16 and 32
rows, no different). Beside each variant's times: the time of the bytes that
no edge can avoid (the state read once forward; read once and its
cotangent written once backward), the operations a call runs, and its
distance from the first variant's numbers: each map's and each
gradient's largest entry's difference over the first's largest entry
(``off``). The times are the device's, summed over the operations of a
``jax.profiler`` trace of ``--reps`` calls (``conv_timing.py::_timed``).

    python3 scripts/hc_maps_timing.py [--tokens 4096] [--width 3584]
    python3 scripts/hc_maps_timing.py --variants pallas
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_PER_S = 819e9     # one v5e chip (benchmark/benchlib/peaks.py)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--tokens", type=int, default=4096)
    ap.add_argument("--width", type=int, default=3584, help="of a stream")
    ap.add_argument("--streams", type=int, default=4)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--dtype", default="bfloat16", help="of the state")
    ap.add_argument("--variants", default="xla,pallas")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/hc_maps_timing.jsonl")
    ap.add_argument("--interpret", action="store_true",
                    help="the kernels interpreted: a rehearsal on the CPU")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from conv_timing import _timed
    from ray_tpu.ops import hyper_connections as hc
    from ray_tpu.ops.pallas import hc_maps as kernels

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    out = open(args.out, "a")
    f32, dtype = jnp.float32, jnp.dtype(args.dtype)
    n, width = args.streams, hc.map_width(args.streams)
    shape = (args.batch, args.tokens, n * args.width)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=shape), dtype)
    phi = jnp.asarray(rng.normal(size=(shape[-1], width)) * 0.02, f32)
    b = jnp.asarray(rng.normal(size=(width,)), f32)
    alpha = jnp.asarray([0.5, 0.7, 0.9], f32)  # large enough to be seen
    tokens = shape[:2]
    cotangents = tuple(jnp.asarray(rng.normal(size=s), f32) for s in (
        (n, *tokens), (n, *tokens), (n, n, *tokens)))
    static = dict(n=n, iters=args.iters, eps=1e-6, clamp=30.0, norm_eps=1e-6)
    state_s = x.size * dtype.itemsize / HBM_BYTES_PER_S

    base = None
    for variant in [v for v in args.variants.split(",") if v]:
        if variant == "xla":
            def maps(*a):
                return hc._hc_maps_xla(*a, **static)
        else:
            def maps(*a):
                return kernels.hc_maps(*a, interpret=args.interpret, **static)

        def both(*a, maps=maps):
            got, vjp = jax.vjp(maps, *a[:4])
            return (*got, *vjp(tuple(a[4:])))

        fwd, both = jax.jit(maps), jax.jit(both)
        operands = (x, phi, b, alpha)
        t0 = time.monotonic()
        jax.block_until_ready(fwd(*operands))
        got = jax.block_until_ready(both(*operands, *cotangents))
        compile_s = time.monotonic() - t0
        got = {k: g.astype(f32) for k, g in zip(
            ("h_pre", "h_post", "h_res", "dx", "dphi", "db", "dalpha"), got)}
        base = base or got
        line = {
            "shape": list(shape), "streams": n, "iters": args.iters,
            "variant": variant, "device": jax.devices()[0].device_kind,
            **_timed("forward", lambda: fwd(*operands), args.reps),
            **_timed("both", lambda: both(*operands, *cotangents),
                     args.reps),
            # what every edge pays: the state read; read again and its
            # cotangent written
            "forward_bytes_ms": state_s * 1e3,
            "both_bytes_ms": 3 * state_s * 1e3,
            "compile_s": compile_s,
            "off": {k: float(jnp.abs(got[k] - base[k]).max()
                             / jnp.abs(base[k]).max()) for k in got}}
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()


if __name__ == "__main__":
    main()
