"""One Mamba-1 layer's selective scan at the Phi-4-mini-flash cell's shape
(1 x 4,096 rows of 5,120 channels, 16 states), forward and forward +
backward, timed on the device this runs on: ``ops/mamba1.py``'s XLA path at
its chunk (``xla:<chunk>``) against the kernel pair of
``ops/pallas/mamba1_scan.py`` at a row block and an unroll
(``pallas:<rows>:<unroll>``); each variant's largest distance from the
first one's ``y`` and six cotangents beside its times.

    python3 scripts/mamba1_timing.py [--rows 4096] [--variants a,b,...]
    python3 scripts/mamba1_timing.py --variants xla:4,pallas:64:2
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=4096)
    ap.add_argument("--channels", type=int, default=5120)
    ap.add_argument("--states", type=int, default=16)
    ap.add_argument("--variants",
                    default="xla:4,pallas:32:2,pallas:64:1,pallas:64:2,"
                    "pallas:64:4")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--profile", action="store_true",
                    help="trace a variant's forward + backward and list its "
                    "longest device operations")
    ap.add_argument("--interpret", action="store_true",
                    help="the kernels interpreted: a rehearsal on the CPU")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import mamba1
    from ray_tpu.ops.pallas import mamba1_scan as kernels

    t, c, n = args.rows, args.channels, args.states
    rng = np.random.default_rng(0)
    bf16, f32 = jnp.bfloat16, jnp.float32
    # the rows as ``models/phi4flash.py::Mamba`` hands them over
    inputs = {
        "x": jnp.asarray(rng.normal(size=(1, t, c)) * 0.5, bf16),
        "dt": jnp.asarray(np.log1p(np.exp(rng.normal(size=(1, t, c)) - 4.0)),
                          f32),
        "A": jnp.asarray(-np.broadcast_to(np.arange(1.0, n + 1), (c, n)), f32),
        "B": jnp.asarray(rng.normal(size=(1, t, n)), bf16),
        "C": jnp.asarray(rng.normal(size=(1, t, n)), bf16),
        "D": jnp.ones((c,), f32)}
    w = jnp.asarray(rng.normal(size=(1, t, c)), f32)
    base = None
    for name in [v for v in args.variants.split(",") if v]:
        kind, *numbers = name.split(":")
        numbers = [int(z) for z in numbers]
        if kind == "xla":
            scan = functools.partial(mamba1._mamba1_xla_chunked,
                                     chunk=numbers[0])
        else:
            scan = functools.partial(kernels.mamba1_scan, rows=numbers[0],
                                     unroll=numbers[1],
                                     interpret=args.interpret)

        def loss(a, w):
            y = scan(**a)
            return jnp.sum(y * w), y

        fwd = jax.jit(lambda a, w: loss(a, w)[1])
        both = jax.jit(jax.value_and_grad(loss, has_aux=True))
        t0 = time.monotonic()
        jax.block_until_ready(fwd(inputs, w))
        (_, y), grads = jax.block_until_ready(both(inputs, w))
        compile_s = time.monotonic() - t0

        def timed(fn):
            best = 1e9
            for _ in range(args.reps):
                t0 = time.monotonic()
                jax.block_until_ready(fn(inputs, w))
                best = min(best, time.monotonic() - t0)
            return best * 1e3
        got = {"y": y, **{f"d{k}": v.astype(f32) for k, v in grads.items()}}
        base = base or got
        print(json.dumps({
            "variant": name, "device": jax.devices()[0].device_kind,
            "forward_ms": timed(fwd), "both_ms": timed(both),
            "compile_s": compile_s,
            # the largest difference, over the first variant's largest entry
            "off": {k: float(jnp.abs(got[k] - base[k]).max()
                             / jnp.abs(base[k]).max()) for k in got},
            "peak_gb": (jax.devices()[0].memory_stats() or {}).get(
                "peak_bytes_in_use", 0) / 1e9}), flush=True)
        if args.profile:
            print(json.dumps({"variant": name, "device_ms": _device_ms(
                lambda: jax.block_until_ready(both(inputs, w)))}), flush=True)


def _device_ms(run, top: int = 12) -> dict:
    """The device's longest operations in one ``run()``, milliseconds by
    the operation's name, from a ``jax.profiler`` trace."""
    import collections
    import glob
    import tempfile

    import jax
    with tempfile.TemporaryDirectory(dir=os.environ.get("TMPDIR")) as d:
        with jax.profiler.trace(d):
            run()
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
                    ms[e.name.split(" = ")[0]] += e.duration_ns / 1e6
    return dict(ms.most_common(top))


if __name__ == "__main__":
    main()
