"""Long-context on-chip probe: flash attention at 8k/16k tokens.

Single-chip evidence for the long-context story (SURVEY §5.7): the
Pallas flash kernel's memory footprint is linear in T (no [T, T]
score materialization), so sequence lengths whose dense attention
would blow HBM train fine. Measures a 4-layer d=512 model's training
step at seq 2048/8192/16384 and reports tok/s + the attention
backend engaged. Multi-chip sequence parallelism (ring/ulysses over
an `sp` axis) is exercised separately by the virtual-mesh tests and
the driver's dryrun; this probe is the single-chip kernel leg.

Run on an idle host: PYTHONPATH=. python scripts/bench_longctx.py
"""

from __future__ import annotations

import json
import time


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.util import compile_cache
    compile_cache.enable()

    from ray_tpu.models import GPT2, GPT2Config
    from ray_tpu.models.gpt2 import gpt2_loss_fn
    from ray_tpu.ops.attention import _flash_ok
    from ray_tpu.parallel import make_mesh
    from ray_tpu.train import (
        init_train_state, make_multi_train_step, shard_batch,
    )

    mesh = make_mesh({"dp": len(jax.devices())})
    rows = []
    for seq_len, batch in ((2048, 4), (8192, 1), (16384, 1)):
        cfg = GPT2Config(n_layer=4, n_head=8, n_embd=512,
                         seq_len=seq_len, vocab_size=32768)
        model = GPT2(cfg, mesh=mesh)
        params = model.init_params(jax.random.key(0))
        opt = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
        state = init_train_state(params, opt, mesh)
        k_steps = 8
        step = make_multi_train_step(gpt2_loss_fn(model), opt,
                                     grad_norm=False)
        rng = np.random.default_rng(0)

        def stack():
            toks = rng.integers(
                0, cfg.vocab_size,
                (k_steps, batch, seq_len)).astype(np.int32)
            return shard_batch({"tokens": toks,
                                "targets": np.roll(toks, -1, 2)},
                               mesh, batch_dim=1)

        try:
            for _ in range(2):
                state, m = step(state, stack())
            float(m["loss"])
            t0 = time.perf_counter()
            state, m = step(state, stack())
            float(m["loss"])
            dt = time.perf_counter() - t0
            probe = jnp.zeros((1, seq_len, cfg.n_head,
                               cfg.head_dim), jnp.bfloat16)
            rows.append({
                "seq_len": seq_len, "batch": batch,
                "tok_per_s": round(batch * seq_len * k_steps / dt),
                "step_ms": round(dt / k_steps * 1e3, 1),
                "flash_engaged": bool(_flash_ok(probe, probe,
                                                probe)),
            })
        except Exception as e:  # noqa: BLE001
            rows.append({"seq_len": seq_len, "batch": batch,
                         "error": f"{type(e).__name__}: {e}"[:160]})
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"longctx": rows}), flush=True)


if __name__ == "__main__":
    main()
