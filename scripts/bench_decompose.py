"""On-chip step-time decomposition for the GPT-2 headline bench.

Times each component of the 124M train step at the exact bench shapes
(batch 32, seq 1024), so headline work targets measured sinks instead
of guesses.

Measurement discipline: every probe is a K-iteration ``lax.scan``
inside ONE jit whose scalar output is synced with ``float()`` —
identical to how the production bench times its multi-step, so
per-dispatch overhead is paid once per K. The empty-scan dispatch
floor is measured and subtracted.

Run in the one process that holds the chip, on an otherwise idle host:
    PYTHONPATH=. python scripts/bench_decompose.py

Prints one JSON line; nothing is banked — an engineering probe, not
an artifact.
"""

from __future__ import annotations

import argparse
import json
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.util import compile_cache
    compile_cache.enable()

    from ray_tpu.models import GPT2, GPT2Config
    from ray_tpu.models.gpt2 import chunked_cross_entropy, gpt2_loss_fn
    from ray_tpu.ops.attention import causal_attention
    from ray_tpu.parallel import make_mesh
    from ray_tpu.train import init_train_state, shard_batch

    K = args.iters
    out: dict[str, float] = {"batch": args.batch, "iters": K}
    n_dev = len(jax.devices())
    mesh = make_mesh({"dp": n_dev})
    cfg = GPT2Config.small()
    bsz = args.batch * n_dev
    rng = np.random.default_rng(0)
    model = GPT2(cfg, mesh=mesh)
    params0 = model.init_params(jax.random.key(0))
    opt = optax.adamw(3e-4, weight_decay=0.1, mu_dtype=jnp.bfloat16)
    state = init_train_state(params0, opt, mesh)
    params = state.params
    loss_fn = gpt2_loss_fn(model)

    toks = rng.integers(0, cfg.vocab_size,
                        (bsz, cfg.seq_len)).astype(np.int32)
    batch1 = shard_batch({"tokens": toks,
                          "targets": np.roll(toks, -1, 1)}, mesh)

    def timed_scan(make_body, init_carry, *operands, reps: int = 3,
                   k: int = K) -> float:
        """Median wall time of jit(scan(body, length=k)) -> scalar,
        synced by float(). ``operands`` are passed as jit ARGUMENTS
        (a closure capture would bake the 124M parameters into the
        HLO as constants)."""

        def prog(carry, *ops):
            c, _ = jax.lax.scan(lambda c, _: make_body(c, *ops),
                                carry, None, length=k)
            return c

        f = jax.jit(prog)
        float(np.asarray(f(init_carry, *operands)).ravel()[0])
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            float(np.asarray(f(init_carry, *operands)).ravel()[0])
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2]

    # dispatch floor: empty scan body
    t_floor = timed_scan(lambda c: (c + 1.0, None), jnp.zeros(()))
    out["dispatch_floor_ms"] = round(t_floor * 1e3, 2)

    def per_iter_ms(t: float) -> float:
        return round((t - t_floor) / K * 1e3, 2)

    # matmul achievable peak (shared helper; see its docstring for
    # the hoisting/two-point-fit invariants that earlier inline
    # revisions of this probe got wrong twice)
    from ray_tpu.util.mm_probe import achievable_matmul_tflops
    tf = achievable_matmul_tflops()
    out["matmul_tflops"] = round(tf, 1)
    from ray_tpu.util.device_peaks import peak_bf16_flops
    out["matmul_frac_peak"] = round(
        tf * 1e12 / peak_bf16_flops(jax.devices()[0].device_kind), 3)

    # forward only (chunked-CE loss path). The tokens are PERTURBED
    # BY THE CARRY: with loop-invariant (params, batch), XLA's
    # while-loop invariant code motion hoists the whole body out of
    # the scan and the probe reads ~K-times fast.
    def vary(b, c):
        shift = (c.astype(jnp.int32) % 7)
        return {"tokens": (b["tokens"] + shift) % cfg.vocab_size,
                "targets": b["targets"]}

    def fwd_body(c, params, batch1):
        return c + loss_fn(params, vary(batch1, c)), None

    out["fwd_ms"] = per_iter_ms(
        timed_scan(fwd_body, jnp.zeros(()), params, batch1))

    # fwd + bwd (value_and_grad, no optimizer) — carry touches one
    # grad leaf; the whole grad program still runs.
    def fb_body(c, params, batch1):
        loss, grads = jax.value_and_grad(
            lambda p, b: loss_fn(p, b))(params, vary(batch1, c))
        g0 = jax.tree_util.tree_leaves(grads)[0]
        return c + loss + g0.astype(jnp.float32).ravel()[0], None

    out["fwd_bwd_ms"] = per_iter_ms(
        timed_scan(fb_body, jnp.zeros(()), params, batch1))

    # attention alone x n_layer (fwd+bwd through the flash kernel)
    q = jnp.asarray(rng.standard_normal(
        (bsz, cfg.seq_len, cfg.n_head, cfg.head_dim)), jnp.bfloat16)

    def attn_loss(q):
        y = q
        for _ in range(cfg.n_layer):
            y = causal_attention(y, y, y)
        return jnp.sum(y.astype(jnp.float32))

    def attn_body(c, q):
        g = jax.grad(attn_loss)(q * c.astype(jnp.bfloat16))
        return c + g.astype(jnp.float32).ravel()[0], None

    out["attn_12L_fwd_bwd_ms"] = per_iter_ms(
        timed_scan(attn_body, jnp.ones(()), q))

    # chunked CE alone (hidden -> loss, fwd+bwd)
    hid = jnp.asarray(rng.standard_normal(
        (bsz, cfg.seq_len, cfg.n_embd)), jnp.bfloat16)
    emb = params["wte"]["embedding"]
    tgt = jnp.asarray(rng.integers(
        0, cfg.vocab_size, (bsz, cfg.seq_len)), jnp.int32)

    def ce_body(c, hid, emb, tgt):
        dh, de = jax.grad(
            lambda h, e: chunked_cross_entropy(h, e, tgt),
            argnums=(0, 1))(hid * c.astype(jnp.bfloat16), emb)
        return (c + dh.astype(jnp.float32).ravel()[0]
                + de.astype(jnp.float32).ravel()[0]), None

    out["ce_fwd_bwd_ms"] = per_iter_ms(
        timed_scan(ce_body, jnp.ones(()), hid, emb, tgt))

    # optimizer update alone (HBM-bound): carry the opt state through
    # the scan so iterations depend on each other.
    grads = jax.tree_util.tree_map(jnp.zeros_like, params)

    def opt_prog(c0, grads, params, opt_state):
        def opt_body(carry, _):
            s, c = carry
            updates, s2 = opt.update(grads, s, params)
            u0 = jax.tree_util.tree_leaves(updates)[0]
            return (s2, c + u0.astype(jnp.float32).ravel()[0]), None

        (s, c), _ = jax.lax.scan(
            opt_body, (opt_state, c0), None, length=K)
        return c

    f = jax.jit(opt_prog)
    float(np.asarray(f(jnp.zeros(()), grads, params,
                       state.opt_state)).ravel()[0])
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(np.asarray(f(jnp.zeros(()), grads, params,
                           state.opt_state)).ravel()[0])
        ts.append(time.perf_counter() - t0)
    ts.sort()
    out["opt_update_ms"] = per_iter_ms(ts[len(ts) // 2])

    # embedding fwd+bwd alone (token gather + scatter-add bwd)
    def emb_body(c, emb, tgt):
        g = jax.grad(lambda e: jnp.sum(
            (e * c.astype(e.dtype))[tgt].astype(jnp.float32)))(emb)
        return c + g.astype(jnp.float32).ravel()[0], None

    out["embed_gather_scatter_ms"] = per_iter_ms(
        timed_scan(emb_body, jnp.ones(()), emb, tgt))

    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
