"""One routed layer's router at the cells' shapes, forward and both
passes, timed on the device this runs on: ``ops/moe.py::_route`` and
``_route_sigmoid`` (the float32 product at the highest precision, the
choice, the counts) on XLA's lines (``xla``: ``top_k``, the gather of
the chosen scores, the scatter-adds) and on the kernel pair of
``ops/pallas/router_choice.py`` (``pallas``), beside the product alone
and the pair alone on a product already made. Each row says how far
its numbers are from the ``xla`` row's and whether the routes are the
same routes.

    python3 scripts/router_timing.py
    python3 scripts/router_timing.py --shapes 16384x512x10,16384x64x8 \
        --interpret      # a rehearsal on the CPU: small shapes
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (tokens, experts, top_k): Qwen3-Next's, Laguna's / JoyAI's, Nemotron's,
# OLMoE's (SmallThinker's at top-6)
SHAPES = "16384x512x10,16384x256x8,8192x128x6,16384x64x8"


def variants(k: int, activation: str, kernel: str):
    """name -> (a scalar function of its operands, the operands' names,
    whether it also returns the routes): the router on XLA's lines and
    on the kernels, the product alone, the pair alone on a product
    already made. Every array is an operand, none a constant of the
    program."""
    import jax.numpy as jnp

    from ray_tpu.ops import moe
    from ray_tpu.ops.pallas import router_choice

    def read(weights, prob_sum, z_sum, counts, cw, ce):
        """A loss that reads every differentiable result."""
        t = weights.shape[0]
        return (jnp.sum(weights * cw) + 1e-3 * z_sum / t
                + jnp.sum(counts.astype(jnp.float32) * prob_sum * ce) / t)

    def layer(path, x, w, bias, cw, ce):
        if activation == "softmax":
            got = moe._route(x, w, k, True, path)
        else:
            got = moe._route_sigmoid(x, w, bias, k, True, 2.5, path)
        weights, experts, prob_sum, z_sum, counts = got
        if counts is None:      # ``_routed_ffn_local``'s line
            counts = jnp.zeros((w.shape[-1],), jnp.int32).at[
                experts.reshape(-1)].add(1)
        return read(weights, prob_sum, z_sum, counts, cw, ce), experts

    def product(x, w, c):
        return jnp.sum(moe._logits(x, w) * c)

    def pair(logits, bias, cw, ce):
        weights, _, counts, prob_sum, lse = router_choice.router_choice(
            logits, bias, top_k=k, activation=activation,
            interpret=kernel == "interpret")
        return read(weights, prob_sum, jnp.sum(lse * lse), counts, cw, ce)

    whole = ("x", "w", "bias", "cw", "ce")
    return {"xla": (functools.partial(layer, "xla"), whole, True),
            kernel: (functools.partial(layer, kernel), whole, True),
            "product": (product, ("x", "w", "c"), False),
            "pair_alone": (pair, ("logits", "bias", "cw", "ce"), False)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=SHAPES)
    ap.add_argument("--width", type=int, default=2048)
    ap.add_argument("--variants", default="",
                    help="of xla, pallas, product, pair_alone (all)")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--interpret", action="store_true",
                    help="the kernels interpreted: a rehearsal on the CPU")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import moe

    kernel = "interpret" if args.interpret else "pallas"
    device = jax.devices()[0].device_kind

    def ms(fn, *operands):
        jax.block_until_ready(fn(*operands))
        best = 1e9
        for _ in range(3):
            t0 = time.monotonic()
            for _ in range(args.reps):
                out = fn(*operands)
            jax.block_until_ready(out)
            best = min(best, (time.monotonic() - t0) / args.reps)
        return best * 1e3

    for shape in args.shapes.split(","):
        t, e, k = (int(n) for n in shape.split("x"))
        rng = np.random.default_rng(t + e + k)
        have = {
            "x": jnp.asarray(rng.normal(size=(t, args.width)), jnp.bfloat16),
            "w": jnp.asarray(rng.normal(size=(args.width, e)) * 0.02,
                             jnp.float32),
            "bias": jnp.asarray(rng.normal(size=(e,)) * 0.01, jnp.float32),
            "cw": jnp.asarray(rng.normal(size=(t, k)), jnp.float32),
            "ce": jnp.asarray(rng.normal(size=(e,)), jnp.float32),
            "c": jnp.asarray(rng.normal(size=(t, e)), jnp.float32)}
        have["logits"] = jax.jit(moe._logits)(have["x"], have["w"])
        for activation in ("softmax", "sigmoid"):
            base = None
            for name, (fn, names, aux) in variants(
                    k, activation, kernel).items():
                if args.variants and name not in args.variants.split(","):
                    continue
                operands = [have[n] for n in names]
                wrt = tuple(i for i, n in enumerate(names)
                            if n in ("x", "w", "logits"))
                fwd = jax.jit(fn)
                both = jax.jit(jax.value_and_grad(fn, wrt, has_aux=aux))
                out, grads = jax.block_until_ready(both(*operands))
                line = {"shape": shape, "activation": activation,
                        "variant": name, "device": device,
                        "forward_ms": ms(fwd, *operands),
                        "both_ms": ms(both, *operands)}
                if aux:
                    nums = [float(out[0])] + [
                        float(jnp.sqrt(jnp.sum(jnp.square(
                            g.astype(jnp.float32))))) for g in grads]
                    base = base or (nums, out[1])
                    line["off"] = [abs(a - b) / abs(b)
                                   for a, b in zip(nums, base[0])]
                    line["routes_differ"] = int(
                        jnp.sum(out[1] != base[1]))
                print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
