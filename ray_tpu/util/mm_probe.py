"""Achievable-matmul probe: delivered bf16 matmul rate of this chip.

Measures what fraction of the published bf16 rate
(``ray_tpu.util.device_peaks``) the current chip/window actually
sustains on a pure 8192^3 matmul chain —
the honest denominator for MFU claims (r5 decomposition: ~150-174
TF/s, 76-88%, on idle windows; at that rate the GPT-2 headline step
is fully matmul-bound).

Correctness invariants (each produced a bogus reading before it was
enforced):
- The scan carry must be MATRIX-valued and feed the matmul: with a
  scalar carry c, (c*A)@A == c*(A@A) and XLA's while-loop invariant
  code motion hoists the matmul out of the loop (one revision read an
  impossible 360 TF/s exactly this way).
- The rate comes from a TWO-POINT fit (long minus short chain), so a
  fixed per-dispatch overhead cancels instead of swamping a single
  short chain.
- A non-positive or sub-floor time difference (a host stall absorbed
  by the short run) marks the probe INVALID (returns 0.0) instead of
  publishing an absurd number.
"""

from __future__ import annotations

import time


def achievable_matmul_tflops(m: int = 8192, k_short: int = 5,
                             k_long: int = 25) -> float:
    """Delivered bf16 TF/s on an m^3 matmul chain; 0.0 = invalid."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)
    a = jnp.eye(m, dtype=jnp.bfloat16) + 0.01 * jnp.asarray(
        rng.standard_normal((m, m)).astype(np.float32), jnp.bfloat16)
    r0 = jnp.asarray(
        rng.standard_normal((m, m)).astype(np.float32), jnp.bfloat16)

    @functools.partial(jax.jit, static_argnums=(2,))
    def prog(r, a, kk):
        def body(r, _):
            r2 = r @ a
            return (r2 / jnp.maximum(
                jnp.abs(r2).max(), 1e-6)).astype(jnp.bfloat16), None
        r, _ = jax.lax.scan(body, r, None, length=kk)
        return r.astype(jnp.float32).ravel()[0]

    def timed(kk: int, reps: int = 2) -> float:
        """Best of ``reps``: a host stall inflating the SHORT chain's
        time shrinks the two-point difference and overstates the rate
        (one window read an impossible 255 TF/s that way) — min() is
        the stall-robust estimator for a fixed-work measurement."""
        float(np.asarray(prog(r0, a, kk)).ravel()[0])     # compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            float(np.asarray(prog(r0, a, kk)).ravel()[0])
            best = min(best, time.perf_counter() - t0)
        return best

    diff = timed(k_long) - timed(k_short)
    n_mm = k_long - k_short
    # Sanity bounds, both directions: n_mm matmuls cannot run FASTER
    # than the chip's published bf16 peak (a reading above it means
    # the short chain absorbed a stall the long one didn't — one
    # loaded capture published an impossible 251.5 TF/s that way),
    # nor take more than ~20x the peak time (probe swamped by load).
    from ray_tpu.util.device_peaks import peak_bf16_flops
    peak = peak_bf16_flops(jax.devices()[0].device_kind) / 1e12
    rate = 2 * m**3 * n_mm / max(diff, 1e-9) / 1e12
    if rate > peak or rate < peak / 20:
        return 0.0
    return rate
