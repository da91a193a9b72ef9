"""One KDA layer's recurrence at the Kimi-Linear cell's shape, forward
and backward, timed on the device this runs on: the XLA path under
variants of ``ops/kda.py``'s two knobs (rows a recomputed group, matmul
precision) and the kernel pair (``pallas``, ``ops/pallas/kda_scan.py``);
each variant's distance from the first one's numbers beside its time.

A variant ``rows:<name>`` is norm + recurrence from the bfloat16 rows a
mixer's convolutions leave, differentiated down to those rows:
``ops/kda.py::unit_rows`` in XLA in front of ``<name>``, or, for
``rows:pallas_fused``, the norm in the kernels' cells (``normalize_qk``).
Their distances are from the first ``rows:`` variant's numbers.

The variant ``fwd_states`` is the forward kernel alone on those rows
(``_kda_fwd``, the norm in its cells), with and without the states
entering every chunk among its results: what a step pays for the
forward that differentiation runs (``keep_states``) over the one it
would run for ``o`` alone.

    python3 scripts/kda_timing.py [--rows 16384] [--variants a,b,...]
    python3 scripts/kda_timing.py --variants \
        rows:g512_highest,rows:pallas,rows:pallas_fused,fwd_states
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
    ap.add_argument("--rows", type=int, default=16384)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--variants", default="")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--interpret", action="store_true",
                    help="the kernels interpreted: a rehearsal on the CPU")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from ray_tpu.ops import kda
    from ray_tpu.ops.pallas import kda_scan as kernels

    P = lax.Precision
    variants = {
        "g512_highest": (512, P.HIGHEST),
        "g512_high": (512, P.HIGH),
        "g256_high": (256, P.HIGH),
        "g128_high": (128, P.HIGH),
        "g1024_high": (1024, P.HIGH),
        "g512_default": (512, P.DEFAULT),
        "pallas": None,
        "pallas_fused": None,       # ``rows:`` only: the norm in the kernels
    }
    names = [v for v in args.variants.split(",") if v] or [
        v for v in variants if v != "pallas_fused"] + ["fwd_states"]
    t, h, k = args.rows, args.heads, 128
    rng = np.random.default_rng(0)
    # q and k as the convolutions leave them, and their unit rows
    rows = [jnp.asarray(rng.normal(size=(1, t, h, k)) * 0.3, jnp.bfloat16)
            for _ in range(2)]
    units = [jax.jit(kda.unit_rows)(z) for z in rows]
    units[0] = units[0] * k ** -0.5
    v = jnp.asarray(rng.normal(size=(1, t, h, k)) * 0.3, jnp.bfloat16)
    g = jnp.asarray(-0.05 * np.log1p(np.exp(rng.normal(size=(1, t, h, k)))),
                    jnp.float32)
    beta = jnp.asarray(1 / (1 + np.exp(-rng.normal(size=(1, t, h)))),
                       jnp.float32)
    w = jnp.asarray(rng.normal(size=(1, t, h, k)), jnp.float32)
    def best_ms(fn, *operands):
        best = 1e9
        for _ in range(args.reps):
            t0 = time.monotonic()
            jax.block_until_ready(fn(*operands))
            best = min(best, time.monotonic() - t0)
        return best * 1e3

    bases = {}
    for name in names:
        if name == "fwd_states":
            # the kernels' own layout: a head a 128-lane block of a row
            flat = [z.reshape(1, t, h * k) for z in (*rows, g, v)]
            steps = jnp.swapaxes(beta, 1, 2).reshape(1, h, t // 128, 1, 128)
            ms = {}
            for keep in (False, True):
                fn = functools.partial(
                    kernels._kda_fwd, keep_states=keep, normalize=True,
                    interpret=args.interpret)
                jax.block_until_ready(fn(*flat, steps))
                ms[keep] = best_ms(fn, *flat, steps)
            print(json.dumps({
                "variant": name, "device": jax.devices()[0].device_kind,
                "forward_ms": ms[False], "forward_keep_states_ms": ms[True]}),
                flush=True)
            continue
        from_rows, _, variant = name.rpartition(":")
        fused = variant == "pallas_fused"
        if fused and not from_rows:
            ap.error("pallas_fused takes the rows: rows:pallas_fused")
        if variants[variant] is None:
            scan = functools.partial(kernels.kda_scan, normalize_qk=fused,
                                     interpret=args.interpret)
        else:
            kda.GROUP_ROWS, precision = variants[variant]
            kda._matmul = functools.partial(
                jnp.einsum, precision=precision,
                preferred_element_type=jnp.float32)
            scan = functools.partial(kda._xla_chunked, chunk=64)
        q, kk = rows if from_rows else units

        def loss(q, kk, v, g, beta):
            if from_rows and not fused:
                q, kk = kda.unit_rows(q) * k ** -0.5, kda.unit_rows(kk)
            o = scan(q, kk, v, g, beta)
            return jnp.sum(o * w), jnp.sqrt(jnp.mean(o * o))

        fwd = jax.jit(lambda *a: loss(*a)[1])
        both = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                          has_aux=True))
        t0 = time.monotonic()
        jax.block_until_ready(fwd(q, kk, v, g, beta))
        (_, rms), grads = jax.block_until_ready(both(q, kk, v, g, beta))
        compile_s = time.monotonic() - t0

        def timed(fn):
            return best_ms(fn, q, kk, v, g, beta)
        nums = {"rms": float(rms), **{
            f"d{n}": float(jnp.sqrt(jnp.sum(jnp.square(
                x.astype(jnp.float32)))))
            for n, x in zip(("q", "k", "v", "g", "beta"), grads)}}
        base = bases.setdefault(from_rows, nums)
        print(json.dumps({
            "variant": name, "device": jax.devices()[0].device_kind,
            "forward_ms": timed(fwd), "both_ms": timed(both),
            "compile_s": compile_s,
            "off": {n: abs(nums[n] - base[n]) / abs(base[n]) for n in nums},
            "peak_gb": (jax.devices()[0].memory_stats() or {}).get(
                "peak_bytes_in_use", 0) / 1e9}), flush=True)


if __name__ == "__main__":
    main()
