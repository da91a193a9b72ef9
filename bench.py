"""Headline benchmark with a hang-proof watchdog harness.

Prints ONE JSON line:
  {"metric": "gpt2_tokens_per_sec_per_chip", "value": N,
   "unit": "tokens/s/chip", "vs_baseline": R, "extra": {...}}

The parent process never imports jax: a chip belongs to one process
at a time, so the probe child and each benchmark child take it one
after another and nothing else may hold it meanwhile. Backend init
runs in a child under a hard timeout (a backend can *hang* rather
than raise). Probe attempts: 2 with backoff; a dead backend yields the
error JSON line in well under 90 s. Each benchmark then runs in its
own child with a generous timeout, so a mid-run wedge still produces
the error line.

Sub-benchmarks (children of this same file):
  --probe     init backend, report device count/platform
  --gpt2      GPT-2 124M training throughput (tokens/s/chip)
  --resnet50  ResNet-50 training throughput (images/s/chip); reference
              harness shape: release/air_tests/air_benchmarks/
              mlperf-train/resnet50_ray_air.py:186-203,357
  --scaling   8-device virtual-CPU dp=1 vs dp=8 step-time ratio at a
              fixed global batch (sharding-overhead proxy; the only
              multi-chip stand-in this single-chip environment allows)
  --profile   device-trace slice breakdown of the warm fused step
              (top-5 matmul / non-matmul slices, observability.xplane)
  --smoke     CPU correctness lane (tier-1): fused step donates,
              compile count stable, prefetcher feeds it, xplane parses

vs_baseline for gpt2 compares against the north-star reference from
BASELINE.json: GPT-2 124M pretraining on one A100-80GB with bf16 +
flash attention sustains ~1.78e5 tokens/s. ResNet-50's baseline is the
A100 bf16 train recipe (~2.5e3 images/s/GPU) from the same class of
harness the reference's release tests use.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

A100_GPT2_TOKENS_PER_S = 178_000.0
A100_RESNET50_IMAGES_PER_S = 2_500.0

HEADLINE = "gpt2_tokens_per_sec_per_chip"

# Watchdog budget: two probe attempts + backoff stays < 90 s even when
# every attempt hangs to its full timeout.
PROBE_TIMEOUTS = (45.0, 30.0)
PROBE_BACKOFF_S = 3.0
BENCH_TIMEOUT_S = 600.0
SCALING_TIMEOUT_S = 420.0
# Global wall-clock target for the whole orchestration. The driver's
# own timeout was observed near ~570 s; finishing (with whatever
# completed) beats being killed holding an unprinted result. Callers
# with a known larger budget raise it via RAY_TPU_BENCH_DEADLINE.
DEADLINE_S = 540.0


def _env_f(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def _run_child(flag: str, timeout: float, extra_env: dict | None = None):
    """Run `python bench.py <flag>` in a new session; parse the last
    JSON line of stdout. Returns (dict|None, error_str|None). On
    timeout the whole process group is killed (jax spawns threads that
    can survive a plain terminate while wedged in backend init)."""
    env = dict(os.environ)
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), flag],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        start_new_session=True, env=env, text=True)
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        proc.wait()
        return None, f"timeout after {timeout:.0f}s"
    for line in reversed(out.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line), None
            except json.JSONDecodeError:
                continue
    tail = (err or out or "").strip().splitlines()[-3:]
    return None, f"rc={proc.returncode}: " + " | ".join(tail)[:300]


def _probe() -> tuple[dict | None, str]:
    """Backend init under watchdog, with retry."""
    timeouts = [
        _env_f("RAY_TPU_BENCH_PROBE_TIMEOUT", t) for t in PROBE_TIMEOUTS]
    errs = []
    for i, t in enumerate(timeouts):
        res, err = _run_child("--probe", t)
        if res and res.get("ok"):
            return res, ""
        errs.append(err or str(res))
        if i + 1 < len(timeouts):
            time.sleep(_env_f("RAY_TPU_BENCH_PROBE_BACKOFF", PROBE_BACKOFF_S))
    return None, "; ".join(e for e in errs if e)


def _emit(value: float, vs_baseline: float, extra: dict,
          error: str | None = None, rc: int = 0) -> None:
    line = {
        "metric": HEADLINE, "value": value, "unit": "tokens/s/chip",
        "vs_baseline": vs_baseline,
    }
    if error:
        line["error"] = error[:500]
    if extra:
        line["extra"] = extra
    print(json.dumps(line), flush=True)
    sys.exit(rc)


def orchestrate() -> None:
    t_start = time.monotonic()
    deadline = _env_f("RAY_TPU_BENCH_DEADLINE", DEADLINE_S)

    def budget(want: float) -> float:
        """Clamp a child timeout to the global deadline; <=0 = skip."""
        return min(want, deadline - (time.monotonic() - t_start) - 5.0)

    extra: dict = {}
    probe, perr = _probe()
    if probe is None:
        _emit(0.0, 0.0, extra,
              error=f"backend init failed/hung: {perr}", rc=1)
    extra["platform"] = probe.get("platform")
    extra["n_chips"] = probe.get("n_devices")

    bench_timeout = _env_f("RAY_TPU_BENCH_TIMEOUT", BENCH_TIMEOUT_S)
    # ResNet gets a RESERVED slice of the deadline (VERDICT r4 weak
    # #2: it ran on gpt2's leftovers and timed out in 4/5 captures).
    # gpt2's budget is capped so the reservation survives even a slow
    # headline run + retry.
    skip_resnet = bool(os.environ.get("RAY_TPU_BENCH_SKIP_RESNET"))
    # 260 s: the r5 on-chip captures needed 214 s with a cold
    # compile, 148 s with a warm persistent compilation cache.
    resnet_reserve = 0.0 if skip_resnet else _env_f(
        "RAY_TPU_BENCH_RESNET_RESERVE", 260.0)

    def gpt2_budget() -> float:
        return max(budget(bench_timeout) - resnet_reserve, 60.0)

    gpt2, gerr = _run_child("--gpt2", gpt2_budget())
    if gpt2 and "error" in gpt2:
        gpt2, gerr = None, gpt2["error"]
    if gpt2 is None and budget(bench_timeout) - resnet_reserve > 120:
        # One retry: the probe proved the backend alive, so a single
        # child failure is plausibly transient — a red headline
        # artifact is the costliest outcome.
        extra["gpt2_first_error"] = str(gerr)[:200]
        gpt2, gerr = _run_child("--gpt2", gpt2_budget())
        if gpt2 and "error" in gpt2:
            gpt2, gerr = None, gpt2["error"]

    # Profiler slice breakdown: a SEPARATE short child after the
    # headline (its compile is a cache hit on the gpt2 child's
    # executable; a wedged jax.profiler can only cost this slice, not
    # the throughput number). Clamped so ResNet's reservation
    # survives. RAY_TPU_BENCH_NO_PROFILE kills it.
    if gpt2 is not None and \
            not os.environ.get("RAY_TPU_BENCH_NO_PROFILE"):
        t = min(_env_f("RAY_TPU_BENCH_PROFILE_TIMEOUT", 120.0),
                budget(bench_timeout) - resnet_reserve)
        if t > 45:
            prof, perr2 = _run_child("--profile", t)
            if prof and "error" not in prof:
                extra["profile_slices"] = prof.get("extra")
            else:
                extra["profile_error"] = (perr2 or (prof or {}).get(
                    "error", ""))[:200]
        else:
            extra["profile_error"] = "skipped: deadline"

    # Secondary benches run serially AFTER the headline (no host
    # contention in its timed region); ResNet spends its reserved
    # slice first, the scaling proxy runs on true leftovers.
    if not skip_resnet:
        t = budget(bench_timeout)
        if t > 45:
            resnet, rerr = _run_child("--resnet50", t)
            if resnet and "error" not in resnet:
                extra["resnet50_images_per_s"] = resnet.get("value")
                extra["resnet50"] = resnet.get("extra")
            else:
                extra["resnet50_error"] = (rerr or (resnet or {}).get(
                    "error", ""))[:200]
        else:
            extra["resnet50_error"] = "skipped: deadline"

    if not os.environ.get("RAY_TPU_BENCH_SKIP_SCALING"):
        t = budget(_env_f("RAY_TPU_BENCH_SCALING_TIMEOUT",
                          SCALING_TIMEOUT_S))
        if t > 45:
            scaling, serr = _run_child("--scaling", t)
            if scaling and "error" not in scaling:
                extra["dp8_scaling_efficiency_proxy"] = scaling.get(
                    "value")
                extra["scaling"] = scaling.get("extra")
            else:
                extra["scaling_error"] = (serr or (scaling or {}).get(
                    "error", ""))[:200]
        else:
            extra["scaling_error"] = "skipped: deadline"

    if gpt2 is None:
        _emit(0.0, 0.0, extra, error=f"gpt2 bench failed: {gerr}", rc=1)
    extra.update(gpt2.get("extra") or {})
    _emit(gpt2["value"], gpt2.get("vs_baseline", 0.0), extra)


# ---------------------------------------------------------------------------
# Children


def probe_main() -> None:
    if os.environ.get("RAY_TPU_BENCH_FAKE_HANG"):
        time.sleep(3600)  # simulated wedged backend init
    if os.environ.get("RAY_TPU_BENCH_FAKE_FAIL"):
        raise RuntimeError("simulated backend init failure")
    _maybe_cpu_smoke()
    t0 = time.time()
    import jax

    devs = jax.devices()
    print(json.dumps({
        "ok": True, "n_devices": len(devs),
        "platform": jax.default_backend(),
        "init_s": round(time.time() - t0, 1),
    }), flush=True)


def _gpt2_measure(model, cfg, opt, mesh, n_dev, batch_per_chip,
                  k_steps, ce_chunk, n_calls, warm=3) -> dict:
    """One fused-donated-prefetched GPT-2 throughput measurement.

    The hot loop is the production shape: host batch stacks are
    produced + placed by a DevicePrefetcher thread (overlapped with
    device compute), the jitted multi-step donates the param and
    opt-state buffers (in-place HBM update — token inputs can't
    donate: no output aliases an int32 batch leaf), and the timing
    barrier is float(loss) of the last dispatch (state carries the
    data dependency across every step). Donation and
    compile-count evidence is captured in-band so the BENCH artifact
    can prove the fused path really ran (not just claim it).
    """
    import jax
    import numpy as np

    from ray_tpu.models.gpt2 import gpt2_loss_fn
    from ray_tpu.train import (
        DevicePrefetcher, buffers_donated, compile_count,
        init_train_state, make_multi_train_step,
    )
    from ray_tpu.train.step import shard_batch

    state = init_train_state(model.init_params(jax.random.key(0)),
                             opt, mesh)
    # K optimizer steps per dispatch (lax.scan over a fresh-data
    # stack): same math as K single steps, amortizing per-dispatch
    # overhead. grad_norm off: the benchmark recipe does not clip.
    step = make_multi_train_step(
        gpt2_loss_fn(model, ce_chunk=ce_chunk), opt, grad_norm=False)

    bsz = batch_per_chip * n_dev
    rng = np.random.default_rng(0)

    def host_stack():
        toks = rng.integers(
            0, cfg.vocab_size,
            (k_steps, bsz, cfg.seq_len)).astype(np.int32)
        return {"tokens": toks, "targets": np.roll(toks, -1, 2)}

    depth = max(1, int(os.environ.get("RAY_TPU_BENCH_PREFETCH", 2)))
    pf = DevicePrefetcher(
        (host_stack() for _ in range(warm + n_calls)),
        place=lambda b: shard_batch(b, mesh, batch_dim=1),
        depth=depth)
    try:
        # Warmup (up to two compiles: initial placement vs
        # donated-output layouts) then settle. The first call doubles
        # as the donation proof: its inputs must come back deleted.
        init_params = state.params
        state, metrics = step(state, next(pf))
        donated = buffers_donated(init_params)
        for _ in range(warm - 1):
            state, metrics = step(state, next(pf))
        float(metrics["loss"])
        compiles_warm = compile_count(step)
        stall0 = pf.stall_s

        t0 = time.perf_counter()
        for _ in range(n_calls):
            state, metrics = step(state, next(pf))
        final_loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        stall_s = pf.stall_s - stall0
    finally:
        pf.close()
    compiles = compile_count(step)

    n_steps = n_calls * k_steps
    tokens_per_s = bsz * cfg.seq_len * n_steps / dt
    return {
        "batch_per_chip": batch_per_chip,
        "per_chip": tokens_per_s / n_dev,
        "step_time_ms": round(dt / n_steps * 1e3, 2),
        "loss": final_loss,
        "donated": bool(donated),
        "fused_step_compiles": compiles,
        # Steady-state contract: the executable count after the timed
        # region equals the post-warmup count (the warmup double
        # compile must not keep growing — tripled = every dispatch
        # recompiles).
        "compiles_stable": (compiles is None or compiles_warm is None
                            or compiles == compiles_warm),
        "input_stall_ms_per_step": round(stall_s * 1e3 / n_steps, 3),
        "prefetch_depth": depth,
    }


def gpt2_main() -> None:
    smoke = _maybe_cpu_smoke()
    import dataclasses

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import GPT2, GPT2Config
    from ray_tpu.parallel import make_mesh

    n_dev = len(jax.devices())
    mesh = make_mesh({"dp": n_dev})

    cfg = GPT2Config.tiny() if smoke else GPT2Config.small()  # 124M
    # Remat sweep knob: RAY_TPU_BENCH_REMAT=<policy> turns per-block
    # remat ON under that jax.checkpoint policy ("nothing" | "dots" |
    # "dots_no_batch" | "everything"); unset keeps remat off (the
    # measured default — 124M at batch 32 fits HBM without it).
    remat = os.environ.get("RAY_TPU_BENCH_REMAT", "")
    if remat:
        cfg = dataclasses.replace(cfg, remat=True, remat_policy=remat)
    # Default 32: the r5 on-chip sweep measured 8→122.9k, 16→122.8k,
    # 32→127.1k, 48→121.9k tok/s/chip (HBM fits 32 at seq 1024; the
    # MXU prefers the bigger GEMMs).
    batch_per_chip = 2 if smoke else int(
        os.environ.get("RAY_TPU_BENCH_BATCH", 32))
    model = GPT2(cfg, mesh=mesh)
    # bf16 first moment: halves Adam's mu HBM traffic; second moment
    # stays f32 (bf16 variance underflows small squared grads).
    opt = optax.adamw(3e-4, weight_decay=0.1, mu_dtype=jnp.bfloat16)
    k_steps = 20
    ce_chunk = int(os.environ.get("RAY_TPU_CE_CHUNK", 2048))

    # RAY_TPU_BENCH_SWEEP="32,48,64": tuning lane — measure each batch
    # (shorter: one timed dispatch each, every config pays its own
    # compile) and promote the winner to the headline, with the full
    # table in extra.sweep. Off by default: the standard artifact runs
    # ONE config long enough to trust.
    sweep_env = "" if smoke else os.environ.get("RAY_TPU_BENCH_SWEEP", "")
    sweep_rows = None
    if sweep_env:
        batches = [int(x) for x in sweep_env.replace(";", ",").split(",")
                   if x.strip()]
        runs = [_gpt2_measure(model, cfg, opt, mesh, n_dev, b,
                              k_steps, ce_chunk, n_calls=1)
                for b in batches]
        meas = max(runs, key=lambda r: r["per_chip"])
        sweep_rows = [{"batch_per_chip": r["batch_per_chip"],
                       "tokens_per_s_per_chip": round(r["per_chip"], 1),
                       "step_time_ms": r["step_time_ms"]}
                      for r in runs]
    else:
        meas = _gpt2_measure(model, cfg, opt, mesh, n_dev,
                             batch_per_chip, k_steps, ce_chunk,
                             n_calls=2)
    per_chip = meas["per_chip"]
    batch_per_chip = meas["batch_per_chip"]
    final_loss = meas["loss"]

    # Model FLOP utilisation against the chip's published bf16 peak
    # (ray_tpu/util/device_peaks.py, keyed by device_kind; an unknown
    # chip is an error): ~6*N FLOPs per token per fwd+bwd. The CPU
    # smoke has no device rate to report.
    n_params = cfg.num_params()
    device_kind = jax.devices()[0].device_kind
    mfu = None
    if not smoke:
        from ray_tpu.util.device_peaks import peak_bf16_flops
        mfu = 6 * n_params * per_chip / peak_bf16_flops(device_kind)

    # Achievable-matmul probe (ray_tpu/util/mm_probe.py): what the
    # chip/window actually delivers vs the published rate. r5
    # decomposition measured ~150-174 TF/s (76-88%) idle — at that
    # rate the 257 ms step is fully matmul-bound (blocks ~111 ms +
    # CE ~67 ms + attention ~57 ms at its head_dim-64 MXU bound):
    # the headline sits at the chip's delivered ceiling, not at a
    # software gap.
    achievable_tflops = 0.0
    if not smoke and not os.environ.get("RAY_TPU_BENCH_NO_MM_PROBE"):
        try:
            from ray_tpu.util.mm_probe import achievable_matmul_tflops
            achievable_tflops = achievable_matmul_tflops()
        except Exception:  # noqa: BLE001 — probe must never kill the bench
            achievable_tflops = 0.0

    # Which attention impl actually ran (VERDICT r4 task 1: assert the
    # Pallas kernel is engaged at bench shapes, don't trust "auto").
    # Mirrors the model's actual dispatch: a model given a mesh
    # routes through make_sharded_causal_attention — the bare kernel
    # on a one-device mesh, the same kernel as the per-device local
    # block on a larger one, under the same shape predicate — so
    # shape-eligibility alone decides engagement.
    from ray_tpu.ops.attention import flash_eligible
    from ray_tpu.ops.pallas.flash_attention import resolved_flash_config
    flash_engaged = bool(flash_eligible(cfg.seq_len, cfg.head_dim)
                         and not os.environ.get("RAY_TPU_ATTN_KERNEL"))
    if not smoke and not flash_engaged and \
            not os.environ.get("RAY_TPU_ATTN_KERNEL"):
        raise RuntimeError(
            "flash kernel not engaged at bench shapes — the headline "
            "would silently measure the XLA fallback")

    print(json.dumps({
        "metric": HEADLINE,
        "value": round(per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(per_chip / A100_GPT2_TOKENS_PER_S, 4),
        "extra": {
            "batch_per_chip": batch_per_chip,
            "seq_len": cfg.seq_len,
            "model": "gpt2-tiny-smoke" if smoke else "gpt2-124M",
            "loss": round(final_loss, 4),
            "step_time_ms": meas["step_time_ms"],
            # Fused-step evidence: the artifact proves donation and a
            # stable executable count instead of asserting them.
            "donated": meas["donated"],
            "fused_step_compiles": meas["fused_step_compiles"],
            "compiles_stable": meas["compiles_stable"],
            "input_stall_ms_per_step": meas["input_stall_ms_per_step"],
            "prefetch_depth": meas["prefetch_depth"],
            "remat": (cfg.remat_policy if cfg.remat else "off"),
            **({"sweep": sweep_rows} if sweep_rows else {}),
            "device_kind": device_kind,
            # The key keeps the name the BENCH_* records carry; the
            # one chip in the peak table is the v5e.
            "mfu_vs_v5e_peak": None if mfu is None else round(mfu, 4),
            # MFU formula disclosure (VERDICT r4 weak #8): counts
            # 6*N_total FLOPs/token (N incl. the 38M embedding rows,
            # whose bwd is a scatter) and EXCLUDES attention
            # score/value FLOPs; at seq 1024 the two roughly offset.
            "mfu_formula": "6*N_total*tok_per_s/peak_bf16_flops"
                           "(device_kind)",
            # Delivered (not paper) matmul rate of this chip/window,
            # and utilization against it: the honest denominator.
            "achievable_matmul_tflops": round(achievable_tflops, 1),
            "mfu_vs_achievable": round(
                6 * n_params * per_chip / (achievable_tflops * 1e12),
                4) if achievable_tflops else None,
            "attn_impl": (os.environ.get("RAY_TPU_ATTN_KERNEL")
                          or ("pallas_flash" if flash_engaged
                              else "xla_dense")),
            # The tiling that actually ran (env knobs resolved), so a
            # sweep winner is reproducible from the artifact alone.
            "attn_blocks": (resolved_flash_config(cfg.seq_len)
                            if flash_engaged else None),
            "ce_impl": f"chunked_fused(chunk={ce_chunk})",
        },
    }), flush=True)


def _maybe_cpu_smoke() -> bool:
    """RAY_TPU_BENCH_CPU=1 pins the child to the virtual CPU backend —
    a correctness smoke for environments without the chip."""
    _enable_compile_cache()
    if not os.environ.get("RAY_TPU_BENCH_CPU"):
        return False
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 1)
    return True


def _enable_compile_cache() -> None:
    """Persistent XLA compilation cache for every bench child (the
    ResNet child's full-model compile was the top cause of its
    timeouts, VERDICT r4 weak #2): warm captures skip straight to
    execution. The directory is ray_tpu.util.compile_cache's."""
    if os.environ.get("RAY_TPU_BENCH_NO_COMPILE_CACHE"):
        return
    from ray_tpu.util import compile_cache

    compile_cache.enable()


def resnet50_main() -> None:
    smoke = _maybe_cpu_smoke()
    import jax
    import optax

    from ray_tpu.models import ResNet, ResNet50Config
    from ray_tpu.models.resnet import resnet_loss_fn
    from ray_tpu.parallel import make_mesh
    from ray_tpu.train import init_train_state, make_multi_train_step

    n_dev = len(jax.devices())
    mesh = make_mesh({"dp": n_dev})

    if smoke:
        cfg = ResNet50Config.tiny()
        batch_per_chip, image_size = 4, 32
    else:
        cfg = ResNet50Config()        # full ResNet-50, 1000 classes
        batch_per_chip, image_size = 128, 224
    model = ResNet(cfg)
    variables = model.init_variables(jax.random.key(0), image_size)
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt = optax.sgd(0.1, momentum=0.9, nesterov=True)
    state = init_train_state(params, opt, mesh, extra=batch_stats)
    k_steps = 10
    # Same fused contract as the GPT-2 path: params/opt-state/
    # batch_stats updated in place via donation (the ~770 MB input
    # stacks can't alias an output, so they are not donated).
    step = make_multi_train_step(resnet_loss_fn(model), opt,
                                 has_extra=True, grad_norm=False)

    bsz = batch_per_chip * n_dev

    # Synthetic inputs are generated ON DEVICE: a (k_steps, bsz, 224,
    # 224, 3) float32 stack is ~770 MB of host RNG plus an H2D push
    # per stack. Content doesn't matter for a throughput
    # bench; a real input pipeline overlaps transfers (data/iter_
    # device_batches), which is a separate measurement.
    from jax.sharding import NamedSharding
    from ray_tpu.train.step import batch_spec

    stack_sh = NamedSharding(mesh, batch_spec(mesh, batch_dim=1))

    import functools

    @functools.partial(jax.jit,
                       out_shardings={"image": stack_sh,
                                      "label": stack_sh})
    def device_stack(key):
        import jax.numpy as jnp
        k1, k2 = jax.random.split(key)
        return {
            "image": jax.random.normal(
                k1, (k_steps, bsz, image_size, image_size, 3),
                dtype=jnp.float32),
            "label": jax.random.randint(
                k2, (k_steps, bsz), 0, cfg.num_classes,
                dtype=jnp.int32),
        }

    # Stack production rides the same DevicePrefetcher as the GPT-2
    # path: the background thread dispatches device_stack(key) (an
    # async on-device RNG program — ``place`` is only a dispatch) so
    # generation of stack N+1 queues behind — and overlaps — step N's
    # compute on the device FIFO.
    from ray_tpu.train import (
        DevicePrefetcher, buffers_donated, compile_count,
    )

    warm, n_calls = 2, 2
    depth = max(1, int(os.environ.get("RAY_TPU_BENCH_PREFETCH", 2)))
    pf = DevicePrefetcher(
        (jax.random.key(i) for i in range(warm + n_calls)),
        place=device_stack, depth=depth)
    try:
        init_params = state.params
        state, metrics = step(state, next(pf))
        donated = buffers_donated(init_params)
        for _ in range(warm - 1):
            state, metrics = step(state, next(pf))
        float(metrics["loss"])
        compiles_warm = compile_count(step)
        stall0 = pf.stall_s

        t0 = time.perf_counter()
        for _ in range(n_calls):
            state, metrics = step(state, next(pf))
        final_loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        stall_s = pf.stall_s - stall0
    finally:
        pf.close()
    compiles = compile_count(step)

    n_steps = n_calls * k_steps
    images_per_s = bsz * n_steps / dt
    per_chip = images_per_s / n_dev

    print(json.dumps({
        "metric": "resnet50_images_per_s",
        "value": round(per_chip, 1),
        "unit": "images/s/chip",
        "vs_baseline": round(per_chip / A100_RESNET50_IMAGES_PER_S, 4),
        "extra": {
            "batch_per_chip": batch_per_chip,
            "image_size": image_size,
            "loss": round(final_loss, 4),
            "step_time_ms": round(dt / n_steps * 1e3, 2),
            "donated": bool(donated),
            "fused_step_compiles": compiles,
            "compiles_stable": (compiles is None
                                or compiles_warm is None
                                or compiles == compiles_warm),
            "input_stall_ms_per_step": round(
                stall_s * 1e3 / n_steps, 3),
            "prefetch_depth": depth,
        },
    }), flush=True)


def scaling_main() -> None:
    """Iso-resource dp8 sharding-overhead proxy on 8 virtual devices.

    Round-4 review: comparing a dp=1 mesh (one virtual device) against
    dp=8 is NOT iso-resource on a shared-core host — the dp=1 run
    doesn't use the same cores/thread pools, so the ratio measured
    resource allocation (and reported an impossible efficiency > 1).

    Revision 3 runs the SAME dp8-sharded training step twice over the
    SAME 8-device mesh in ONE process, differing ONLY in the
    communication machinery:
    - no-collective: the step body shard_mapped with an (unchecked)
      replicated out-spec — each device updates its own param copy,
      zero collectives. (Numerically divergent, which is irrelevant
      for a timing probe; shapes/FLOPs identical.)
    - with-collective: the production pjit step — sharding
      propagation inserts the gradient psum (and activation
      constraints), exactly what a real dp job pays.

        efficiency = t(no-collective) / t(with-collective)  <= 1
        by construction: the numerator's program is the
        denominator's minus its collectives.

    1 - efficiency is the fraction of the sharded step spent on
    partition + collective machinery. Interleaved step-by-step
    timing with medians, because serial A-then-B runs on this
    shared-core host drift ~20% with background load (the other
    root of round 4's >1 readings).
    """
    import jax

    _enable_compile_cache()
    # Pinned through jax.config before first device use, whatever
    # JAX_PLATFORMS says (same recipe as tests/conftest.py).
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    devs = jax.devices()
    assert len(devs) >= 8, f"need 8 virtual devices, got {len(devs)}"

    import numpy as np
    import optax

    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import GPT2, GPT2Config
    from ray_tpu.models.gpt2 import gpt2_loss_fn
    from ray_tpu.parallel import make_mesh
    from ray_tpu.train import init_train_state, make_train_step

    import statistics

    from ray_tpu.train.step import _step_body

    rng = np.random.default_rng(0)
    mesh = make_mesh({"dp": 8})
    compute = GPT2Config.tiny(n_embd=128, n_layer=4, n_head=4,
                              seq_len=256, vocab_size=512)
    global_batch = 8
    opt = optax.adamw(3e-4)
    sh = NamedSharding(mesh, P("dp"))

    def batch():
        toks = rng.integers(
            0, compute.vocab_size,
            (global_batch, compute.seq_len)).astype(np.int32)
        return {
            "tokens": jax.device_put(toks, sh),
            "targets": jax.device_put(np.roll(toks, -1, 1), sh),
        }

    def build(collective: bool):
        model = GPT2(compute, mesh=mesh if collective else None)
        params = model.init_params(jax.random.key(0))
        state = init_train_state(params, opt, mesh)
        loss_fn = gpt2_loss_fn(model)
        if collective:
            step = make_train_step(loss_fn, opt, grad_norm=False)
        else:
            body = _step_body(loss_fn, opt, False, False)
            local = jax.shard_map(
                body, mesh=mesh, in_specs=(P(), P("dp")),
                out_specs=(P(), P()), check_vma=False)
            step = jax.jit(local, donate_argnums=(0,))
        return [state], step

    local_run = build(collective=False)
    psum_run = build(collective=True)
    for box, step in (local_run, psum_run):     # warm: 2 compiles
        for _ in range(2):
            box[0], m = step(box[0], batch())
        float(np.asarray(m["loss"]).ravel()[0])

    def timed_step(box, step) -> float:
        b = batch()
        t0 = time.perf_counter()
        box[0], m = step(box[0], b)
        float(np.asarray(m["loss"]).ravel()[0])   # sync
        return time.perf_counter() - t0

    # INTERLEAVED rounds: serial A-then-B runs on this shared-core
    # host drift ~20% with background load (the other root of round
    # 4's >1 readings); alternating step-by-step exposes both
    # programs to the same load profile, medians kill stragglers.
    ts_local: list[float] = []
    ts_psum: list[float] = []
    for _ in range(7):
        ts_psum.append(timed_step(*psum_run))
        ts_local.append(timed_step(*local_run))
    t_local = statistics.median(ts_local)
    t_psum = statistics.median(ts_psum)
    eff = t_local / t_psum
    print(json.dumps({
        "metric": "dp8_scaling_efficiency_proxy",
        "value": round(eff, 4),
        "unit": "median t(dp8 no-collective) / t(dp8 with-psum)",
        "vs_baseline": round(eff, 4),
        "extra": {
            # rev 3 (see scaling_main docstring): same program, same
            # 8-device mesh, same process -- the numerator strips
            # ONLY the collectives, so the ratio is <= 1 by
            # construction and 1-eff is the collective+partition
            # share of the sharded step. (rev 2, rounds <=4,
            # compared a dp=1 mesh from a separate serial run -- not
            # iso-resource, reported an impossible 1.16.)
            "proxy_rev": 3,
            "compute_cfg": {
                "model": "gpt2 d128 L4 seq256",
                "global_batch": global_batch,
                "no_collective_step_ms": round(t_local * 1e3, 2),
                "with_psum_step_ms": round(t_psum * 1e3, 2),
                "samples": len(ts_local),
            },
            "n_virtual_devices": 8,
        },
    }), flush=True)


def profile_main() -> None:
    """Capture a device trace of the WARM fused GPT-2 step and print
    its slice breakdown (total / matmul / non-matmul ms + top-5 each
    way, parsed by observability.xplane — no tensorflow).

    Runs as its own orchestrator child AFTER the headline so a wedged
    jax.profiler can never poison the throughput number; the
    persistent compile cache makes the re-compile here a
    cache hit on the gpt2 child's executable (same shapes/options).
    """
    smoke = _maybe_cpu_smoke()
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import GPT2, GPT2Config
    from ray_tpu.models.gpt2 import gpt2_loss_fn
    from ray_tpu.observability.xplane import summarize_trace
    from ray_tpu.parallel import make_mesh
    from ray_tpu.train import init_train_state, make_multi_train_step
    from ray_tpu.train.step import shard_batch

    n_dev = len(jax.devices())
    mesh = make_mesh({"dp": n_dev})
    cfg = GPT2Config.tiny() if smoke else GPT2Config.small()
    batch_per_chip = 2 if smoke else int(
        os.environ.get("RAY_TPU_BENCH_BATCH", 32))
    k_steps = 20   # same executable as the headline child (cache hit)
    ce_chunk = int(os.environ.get("RAY_TPU_CE_CHUNK", 2048))
    model = GPT2(cfg, mesh=mesh)
    opt = optax.adamw(3e-4, weight_decay=0.1, mu_dtype=jnp.bfloat16)
    state = init_train_state(model.init_params(jax.random.key(0)),
                             opt, mesh)
    step = make_multi_train_step(
        gpt2_loss_fn(model, ce_chunk=ce_chunk), opt, grad_norm=False)
    bsz = batch_per_chip * n_dev
    rng = np.random.default_rng(0)

    def stack():
        toks = rng.integers(
            0, cfg.vocab_size,
            (k_steps, bsz, cfg.seq_len)).astype(np.int32)
        return shard_batch(
            {"tokens": toks, "targets": np.roll(toks, -1, 2)}, mesh,
            batch_dim=1)

    for _ in range(2):
        state, metrics = step(state, stack())
    float(metrics["loss"])

    logdir = tempfile.mkdtemp(prefix="ray_tpu_bench_trace_")
    b = stack()
    with jax.profiler.trace(logdir):
        state, metrics = step(state, b)
        float(metrics["loss"])
    summary = summarize_trace(logdir, top_k=5, steps=k_steps)
    shutil.rmtree(logdir, ignore_errors=True)
    print(json.dumps({
        "metric": "profile_slices",
        "value": summary.get("ms_per_step", 0.0),
        "unit": "device ms/step",
        "extra": summary,
    }), flush=True)


def smoke_main() -> None:
    """`bench.py --smoke`: CPU correctness lane (tier-1, no chip, no
    device-time claims). Proves, on a tiny GPT-2, that the fused step
    (a) keeps a stable executable count after warmup (the documented
    double-compile must not triple), (b) really donates the param and
    opt-state buffers, (c) consumes its input through the
    DevicePrefetcher, and (d) the xplane parser reads back a real
    capture of that step. One JSON line; rc!=0 on any violated claim.
    """
    os.environ["RAY_TPU_BENCH_CPU"] = "1"
    _maybe_cpu_smoke()
    import shutil
    import tempfile

    import jax
    import numpy as np
    import optax

    from ray_tpu.models import GPT2, GPT2Config
    from ray_tpu.models.gpt2 import gpt2_loss_fn
    from ray_tpu.observability.xplane import summarize_trace
    from ray_tpu.parallel import make_mesh
    from ray_tpu.train import (
        DevicePrefetcher, buffers_donated, compile_count,
        init_train_state, make_multi_train_step,
    )
    from ray_tpu.train.step import shard_batch

    mesh = make_mesh({"dp": 1})
    cfg = GPT2Config.tiny()
    model = GPT2(cfg, mesh=mesh)
    opt = optax.adamw(1e-3)
    state = init_train_state(model.init_params(jax.random.key(0)),
                             opt, mesh)
    step = make_multi_train_step(
        gpt2_loss_fn(model, ce_chunk=64), opt, grad_norm=False)
    k_steps, bsz, n_stacks = 2, 2, 5
    rng = np.random.default_rng(0)

    def host_stack():
        toks = rng.integers(
            0, cfg.vocab_size,
            (k_steps, bsz, cfg.seq_len)).astype(np.int32)
        return {"tokens": toks, "targets": np.roll(toks, -1, 2)}

    pf = DevicePrefetcher(
        (host_stack() for _ in range(n_stacks)),
        place=lambda b: shard_batch(b, mesh, batch_dim=1), depth=2)
    init_params = state.params
    state, metrics = step(state, next(pf))
    donated = buffers_donated(init_params)
    state, metrics = step(state, next(pf))
    compiles_settled = compile_count(step)   # after the relayout call
    for b in pf:
        state, metrics = step(state, b)
    loss = float(metrics["loss"])
    consumed = pf.batches
    pf.close()
    compiles = compile_count(step)

    logdir = tempfile.mkdtemp(prefix="ray_tpu_smoke_trace_")
    with jax.profiler.trace(logdir):
        state, metrics = step(
            state, shard_batch(host_stack(), mesh, batch_dim=1))
        float(metrics["loss"])
    try:
        slices = summarize_trace(logdir, steps=k_steps)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)

    checks = {
        "donated": bool(donated),
        # <=2: one compile for fresh inputs + at most one relayout for
        # donated-output layouts; must not grow past settling.
        "compiles_stable": (compiles is not None and compiles <= 2
                            and compiles == compiles_settled),
        "prefetched_all": consumed == n_stacks,
        "xplane_parsed": bool(slices.get("top_non_matmul")
                              or slices.get("top_matmul")),
        "loss_finite": bool(np.isfinite(loss)),
    }
    ok = all(checks.values())
    print(json.dumps({
        "metric": "bench_smoke",
        "value": 1.0 if ok else 0.0,
        "unit": "ok",
        "ok": ok,
        "extra": {**checks,
                  "fused_step_compiles": compiles,
                  "loss": round(loss, 4),
                  "profile_ms_per_step": slices.get("ms_per_step")},
    }), flush=True)
    if not ok:
        sys.exit(1)


def main() -> None:
    arg = sys.argv[1] if len(sys.argv) > 1 else ""
    child = {"--probe": probe_main, "--gpt2": gpt2_main,
             "--resnet50": resnet50_main, "--scaling": scaling_main,
             "--profile": profile_main, "--smoke": smoke_main}
    if arg in child:
        try:
            child[arg]()
        except Exception as e:  # noqa: BLE001
            print(json.dumps({
                "metric": arg.lstrip("-"), "value": 0.0,
                "error": f"{type(e).__name__}: {e}"[:500],
            }), flush=True)
            sys.exit(1)
        return
    try:
        orchestrate()
    except SystemExit:
        raise
    except BaseException as e:  # noqa: BLE001
        # The driver contract is ONE JSON line no matter what.
        print(json.dumps({
            "metric": HEADLINE, "value": 0.0,
            "unit": "tokens/s/chip", "vs_baseline": 0.0,
            "error": f"orchestrator: {type(e).__name__}: {e}"[:500],
        }), flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
