"""The held experts' row moves alone on the chip, at the four cells'
shapes: XLA's gather and float32 scatter-add of a whole slab (what
``ops/moe.py::_slab`` ran up to PR 41) against ``take_rows`` /
``sum_rows`` of ``ops/pallas/route_rows.py`` on each row path, forward
and backward, and two pieces of the product path alone (the slab's
sort into token order, the gather by it). Prints one JSON line a timing, ``ns_per_live_row``
among its keys, and writes them to ``chiprun_out/route_rows/``.

    chiprun --chips 1 -- python scripts/route_rows_timing.py
    JAX_PLATFORMS=cpu python scripts/route_rows_timing.py --tiny   # here
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.pallas import route_rows as rr

# cell: tokens, top_k, width, slab rows, live rows (PERF.md section 6, PR 43)
SHAPES = {
    "smallthinker": (16384, 6, 2560, 49152, 24477),
    "zaya": (16384, 1, 2048, 16384, 7919),
    "joyai": (8192, 8, 2048, 8192, 4319),
    "nemotron": (8192, 6, 2688, 6144, 3116),
}
TINY = {"tiny": (256, 6, 256, 1024, 390)}


def slab_of(t, k, rows, live, seed):
    """A sorted order in which ``live`` routes landed on four held
    experts, and the slab of its first ``rows`` places."""
    rng = np.random.default_rng(seed)
    routes = t * k
    landed = np.zeros(routes, bool)
    landed[rng.choice(routes, live, replace=False)] = True
    key = np.where(landed, rng.integers(0, 4, routes), 4)
    order = np.argsort(key, kind="stable").astype(np.int32)
    pos = np.empty(routes, np.int32)
    pos[order] = np.arange(routes, dtype=np.int32)
    order = np.pad(order, (0, -routes % rows))
    return rr.Slab(jnp.asarray(order[:rows]), jnp.asarray(pos.reshape(t, k)),
                   jnp.int32(0), jnp.int32(live))


def old_take(x, slab):
    idx = slab.sorted_index()
    return jnp.where((idx >= 0)[:, None], x[slab.route // slab.pos.shape[-1]],
                     jnp.zeros((), x.dtype))


def old_sum(ys, w, slab):
    """PR 41's combine: mask, weigh, float32 scatter-add, cast."""
    k = slab.pos.shape[-1]
    live = (slab.sorted_index() >= 0)[:, None]
    ys = jnp.where(live, ys, jnp.zeros((), ys.dtype)) \
        * w.reshape(-1)[slab.route][:, None].astype(ys.dtype)
    t = slab.pos.shape[0]
    return jnp.zeros((t, ys.shape[-1]), jnp.float32).at[
        slab.route // k].add(ys.astype(jnp.float32)).astype(ys.dtype)


def timed(name, fn, *args, calls=8, **said):
    fn = jax.jit(fn)
    out = jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    ms = (time.perf_counter() - t0) / calls * 1e3
    line = dict(what=name, ms=round(ms, 4), **said)
    if "live" in said:
        line["ns_per_live_row"] = round(ms * 1e6 / max(said["live"], 1), 2)
    print(json.dumps(line), flush=True)
    return out, line


def same(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def run(cell, shape, rows_path, seed, lines):
    t, k, d, rows, live = shape
    slab = slab_of(t, k, rows, live, seed)
    kx, ky, kw, kd = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(kx, (t, d), jnp.bfloat16)
    ys = jax.random.normal(ky, (rows, d), jnp.bfloat16)
    w = jax.random.uniform(kw, (t, k), jnp.float32)
    dy = jax.random.normal(kd, (t, d), jnp.bfloat16)
    said = dict(cell=cell, tokens=t, top_k=k, width=d, rows=rows, live=live)

    def note(name, fn, *args):
        out, line = timed(name, fn, *args, **said)
        lines.append(line)
        return out

    def loss(take, add):
        def f(x, w):
            xs = take(x)
            return add(xs * xs, w)       # something between the two moves
        return f

    old = loss(lambda x: old_take(x, slab), lambda ys, w: old_sum(ys, w, slab))
    new = {p: loss(lambda x, p=p: rr.take_rows(x, slab, p),
                   lambda ys, w, p=p: rr.sum_rows(ys, w, slab, p))
           for p in ("xla", rows_path)}

    def both(f):
        def g(x, w, dy):
            y, pull = jax.vjp(f, x, w)
            return (y,) + pull(dy)
        return g

    ref = note("old.take", lambda x: old_take(x, slab), x)
    ref_sum = note("old.sum", lambda ys, w: old_sum(ys, w, slab), ys, w)
    ref_all = note("old.fwd_bwd", both(old), x, w, dy)
    for p, f in new.items():
        got = note(f"{p}.take", lambda x, p=p: rr.take_rows(x, slab, p), x)
        lines[-1]["max_abs_diff_from_old"] = same(got, ref)
        got = note(f"{p}.sum",
                   lambda ys, w, p=p: rr.sum_rows(ys, w, slab, p), ys, w)
        lines[-1]["max_abs_diff_from_old"] = same(got, ref_sum)
        got = note(f"{p}.fwd_bwd", both(f), x, w, dy)
        lines[-1]["max_abs_diff_from_old"] = [
            same(a, b) for a, b in zip(got, ref_all)]
        print(json.dumps(lines[-1]), flush=True)
    if rows_path == "xla":
        return
    # the product path's pieces
    from jax import lax
    key = jnp.where(slab.sorted_index() >= 0, slab.route,
                    jnp.iinfo(jnp.int32).max)
    note("piece.sort_rows", lambda key: lax.sort(
        (key, jnp.arange(rows, dtype=jnp.int32)), num_keys=1), key)
    note("piece.gather_rows", lambda ys, at: ys[at], ys,
         jnp.argsort(key).astype(jnp.int32))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="a rehearsal on the CPU, the kernel interpreted")
    ap.add_argument("--cells", default="")
    ap.add_argument("--seed", type=int, default=4300000001)
    args = ap.parse_args()
    shapes = TINY if args.tiny else SHAPES
    if args.cells:
        shapes = {c: shapes[c] for c in args.cells.split(",")}
    path = ("interpret" if args.tiny
            else "tgmm" if jax.default_backend() == "tpu" else "xla")
    dev = jax.devices()[0]
    print(json.dumps(dict(device=dev.device_kind, platform=dev.platform,
                          rows_path=path)), flush=True)
    lines = []
    for i, (cell, shape) in enumerate(shapes.items()):
        run(cell, shape, path, args.seed % (1 << 31) + i, lines)
    out = os.path.join("chiprun_out", "route_rows")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"timing_{dev.platform}.jsonl"), "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
