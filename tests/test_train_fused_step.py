"""Fused donated train step: correctness + compile-count contract.

The perf story of the fused step (one XLA program: fwd + bwd + psum +
optimizer update, param/opt-state buffers donated) is only worth
anything if (a) donation changes NOTHING about the math — the
loss/grad trajectory must match the unfused reference step for step —
and (b) the executable count stays put after warmup (a growing count
means every dispatch pays a compile; the documented warmup double
compile must never become a triple). Both claims are cheap to pin on
the CPU backend, so they are pinned here, plus unit coverage of the
DevicePrefetcher that feeds the step in the bench hot loops and
``Dataset.iter_device_batches``.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from ray_tpu.models import GPT2, GPT2Config  # noqa: E402
from ray_tpu.models.gpt2 import gpt2_loss_fn  # noqa: E402
from ray_tpu.train import (  # noqa: E402
    DevicePrefetcher,
    buffers_donated,
    compile_count,
    init_train_state,
    make_train_step,
    prefetch_to_device,
)

N_STEPS = 10


def _tiny_setup():
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init_params(jax.random.key(0))
    opt = optax.adamw(1e-3)
    loss_fn = gpt2_loss_fn(model, ce_chunk=64)
    return cfg, model, params, opt, loss_fn


def _batches(cfg, n=N_STEPS, bsz=2, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        toks = rng.integers(0, cfg.vocab_size,
                            (bsz, cfg.seq_len)).astype(np.int32)
        out.append({"tokens": toks, "targets": np.roll(toks, -1, 1)})
    return out


def test_fused_donated_step_matches_unfused_reference():
    """10-step loss AND grad-norm trajectory of the donated fused step
    == the undonated reference within fp32 tolerance (donation is a
    buffer-aliasing declaration, never a numeric change)."""
    cfg, model, params, opt, loss_fn = _tiny_setup()
    batches = _batches(cfg)

    trajectories = {}
    finals = {}
    for donate in (False, True):
        state = init_train_state(params, opt)
        step = make_train_step(loss_fn, opt, donate=donate)
        losses, gnorms = [], []
        for b in batches:
            state, m = step(state, b)
            losses.append(float(m["loss"]))
            gnorms.append(float(m["grad_norm"]))
        trajectories[donate] = (losses, gnorms)
        finals[donate] = jax.tree_util.tree_map(np.asarray,
                                                state.params)

    np.testing.assert_allclose(trajectories[True][0],
                               trajectories[False][0],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(trajectories[True][1],
                               trajectories[False][1],
                               rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(finals[True]),
                    jax.tree_util.tree_leaves(finals[False])):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    # Loss must actually move — a frozen trajectory would make the
    # equality above vacuous.
    assert trajectories[True][0][-1] != trajectories[True][0][0]


def test_fused_step_compile_count_stable_and_donates():
    """Exactly ONE executable after warmup at fixed shapes (<=2 ever:
    initial layouts + at most one donated-layout recompile), stable
    across 10 further dispatches; param/opt-state buffers really
    consumed."""
    cfg, model, params, opt, loss_fn = _tiny_setup()
    state = init_train_state(params, opt)
    step = make_train_step(loss_fn, opt, grad_norm=False)

    prev_params, prev_opt = state.params, state.opt_state
    batches = _batches(cfg, n=2 + N_STEPS)
    state, _ = step(state, batches[0])
    # Donation proof: the pre-step param AND opt-state buffers are
    # gone (the update happened in place, no re-materialized copy).
    assert buffers_donated(prev_params)
    assert buffers_donated(prev_opt)

    state, _ = step(state, batches[1])
    settled = compile_count(step)
    if settled is None:
        pytest.skip("jax runtime exposes no _cache_size introspection")
    assert settled <= 2, f"warmup compiled {settled} executables"
    for b in batches[2:]:
        state, _ = step(state, b)
    assert compile_count(step) == settled, (
        "fused step recompiled after warmup — every dispatch would "
        "pay a compile on-chip")


def test_undonated_step_keeps_buffers():
    """Control for buffers_donated: without donation the old state
    must still be alive (proves the assertion above can fail)."""
    cfg, model, params, opt, loss_fn = _tiny_setup()
    state = init_train_state(params, opt)
    step = make_train_step(loss_fn, opt, donate=False, grad_norm=False)
    prev_params = state.params
    state, _ = step(state, _batches(cfg, n=1)[0])
    assert not buffers_donated(prev_params)


# ---------------------------------------------------------------------------
# DevicePrefetcher


def test_prefetcher_preserves_order_and_counts():
    src = list(range(20))
    pf = DevicePrefetcher(iter(src), place=lambda x: x * 10, depth=3)
    assert list(pf) == [x * 10 for x in src]
    assert pf.counters["batches"] == len(src)
    pf.close()


def test_prefetcher_overlaps_slow_source():
    """With a slow producer and a slow consumer, total wall time must
    approach max(produce, consume), not their sum — the overlap IS the
    feature. Generous 1.5x bound: scheduling on a loaded 1-core box."""
    n, delay = 6, 0.05

    def slow_src():
        for i in range(n):
            time.sleep(delay)
            yield i

    t0 = time.perf_counter()
    pf = DevicePrefetcher(slow_src(), depth=2)
    got = []
    for item in pf:
        time.sleep(delay)          # consumer "compute"
        got.append(item)
    wall = time.perf_counter() - t0
    pf.close()
    assert got == list(range(n))
    serial = 2 * n * delay
    assert wall < serial * 0.9 + 3 * delay, (
        f"no overlap: wall {wall:.3f}s vs serial {serial:.3f}s")


def test_prefetcher_propagates_source_error():
    def bad():
        yield 1
        raise RuntimeError("boom in producer")

    pf = DevicePrefetcher(bad())
    assert next(pf) == 1
    with pytest.raises(RuntimeError, match="boom in producer"):
        for _ in range(5):
            next(pf)
    pf.close()


def test_prefetcher_close_unblocks_full_queue():
    """close() must not deadlock against a producer blocked on a full
    queue, and must join the thread."""
    def endless():
        i = 0
        while True:
            yield i
            i += 1

    pf = DevicePrefetcher(endless(), depth=1)
    assert next(pf) == 0
    pf.close()
    assert not pf._thread.is_alive()


def test_prefetcher_rejects_bad_depth():
    with pytest.raises(ValueError):
        DevicePrefetcher(iter([]), depth=0)


def test_prefetch_to_device_places_on_device():
    batches = [{"x": np.arange(4, dtype=np.float32) + i}
               for i in range(3)]
    with prefetch_to_device(iter(batches)) as pf:
        out = list(pf)
    assert len(out) == 3
    for i, b in enumerate(out):
        assert isinstance(b["x"], jax.Array)
        np.testing.assert_allclose(np.asarray(b["x"]),
                                   np.arange(4) + i)


def test_prefetcher_feeds_donated_step():
    """End-to-end: prefetcher -> donated fused step; every yielded
    batch consumed, state advances, zero leaks of queue references
    (the donated state chain keeps working across all batches)."""
    cfg, model, params, opt, loss_fn = _tiny_setup()
    state = init_train_state(params, opt)
    step = make_train_step(loss_fn, opt, grad_norm=False)
    n = 5
    pf = prefetch_to_device(iter(_batches(cfg, n=n)))
    for b in pf:
        state, m = step(state, b)
    pf.close()
    assert pf.counters["batches"] == n
    assert int(state.step) == n
    assert np.isfinite(float(m["loss"]))
