"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # one worker x four chips, dp=4

Trains GPT-2 124M (``GPT2Config.small()``, nothing reduced) for a few
steps through the entry points a user calls: ``ray_tpu.init()`` ->
``JaxTrainer(loop, ScalingConfig(num_workers=1, tpu_chips_per_worker=N),
datasets={"train": ds}).fit()``, the loop reading
``get_dataset_shard("train").iter_device_batches(batch, mesh)`` into
``make_train_step(gpt2_loss_fn(model, ce_chunk=2048), opt)`` at batch 32
per chip and reporting through ``train.report``. Tokens come from
``--seed``; nothing is read from the network.

One JSON line per phase goes to stdout, so the tail of a failed run
names the phase that failed; the last line is
``{"ok": ..., "device": {"platform", "kind", "count"}}`` with the device
as the worker's jax reports it. Any failed phase, any ``Result.error``,
any platform other than ``tpu`` ends the run with a non-zero exit.

One process holds the chip: the TPU worker. This parent and the head
never initialise a jax backend (the ``driver`` phase checks that). The
worker's phase lines reach this process three ways: printed (to this
process's *stderr*, live, through the log monitor), through
``train.report`` (the path under test), and appended to a side file as
each phase starts and ends — what stdout is printed from, so that a
phase which hung or took the worker down with it is still named.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import signal
import sys
import time
import traceback

PLATFORM = "tpu"
STEPS = 5
BATCH_PER_CHIP = 32
CE_CHUNK = 2048
VOCAB = 50257            # GPT-2's real vocabulary (the model pads to 50304)
DEADLINE_S = 1080        # the contract allows 1200 s, compilation included
# What "agree" means for the four-chip comparison: two ulps of bf16
# (8 mantissa bits), relative, on the loss and on the gradient norm.
BF16_RTOL = 2.0 ** -7


def model_config():
    from ray_tpu.models import GPT2Config
    return GPT2Config.small()


def kernel_facts(lowered_text: str, cfg) -> dict:
    """Is the Pallas flash kernel in the program (not the XLA path of
    ops/attention.py)? Asserted here, not assumed from the backend."""
    from ray_tpu.ops.attention import flash_eligible
    n = lowered_text.count("tpu_custom_call")
    eligible = bool(flash_eligible(cfg.seq_len, cfg.head_dim))
    return {"ok": n > 0 and eligible, "tpu_custom_calls": n,
            "flash_eligible": eligible}


# ---------------------------------------------------------------------------
# worker side: runs inside the one process that holds the chip(s)


class _Reporter:
    """Phase lines from the worker: printed (live, to the parent's
    stderr), sent through ``train.report``, and appended to
    ``phase_log`` when the phase starts and when it ends."""

    def __init__(self, phase_log: str):
        self.phase_log = phase_log
        self.compile_s = 0.0
        self.cache = {"hits": 0, "misses": 0}
        import jax.monitoring as mon

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs

        def on_event(event, **_):
            for k in self.cache:
                if event == f"/jax/compilation_cache/cache_{k}":
                    self.cache[k] += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def _log(self, line: dict) -> None:
        with open(self.phase_log, "a") as f:
            f.write(json.dumps(line) + "\n")

    @contextlib.contextmanager
    def phase(self, name: str):
        from ray_tpu import train
        facts: dict = {}
        ok = False
        self._log({"phase": name, "started": True})
        t0 = time.perf_counter()
        try:
            yield facts
            ok = bool(facts.pop("ok", True))
        except Exception as e:  # noqa: BLE001 — reported, then re-raised
            facts["error"] = f"{type(e).__name__}: {e}"[-1500:]
            raise
        finally:
            line = {"phase": name, "ok": ok,
                    "phase_s": round(time.perf_counter() - t0, 2), **facts}
            print(json.dumps(line), flush=True)
            self._log(line)
            train.report(line)
        if not ok:
            raise RuntimeError(f"phase {name!r} failed: {facts}")


def _memory(devs) -> list[dict]:
    out = []
    for d in devs:
        ms = d.memory_stats() or {}
        out.append({"id": d.id,
                    "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
                    "bytes_in_use": ms.get("bytes_in_use"),
                    "bytes_limit": ms.get("bytes_limit")})
    return out


def _span(tree) -> list[int]:
    """[min, max] over the leaves of how many devices each spans."""
    import jax
    n = [len(x.sharding.device_set)
         for x in jax.tree_util.tree_leaves(tree)]
    return [min(n), max(n)]


def _run_steps(rep, step, state, batches, first) -> tuple:
    """``STEPS`` blocking steps; returns (state, facts)."""
    import numpy as np
    from ray_tpu import train

    c0 = rep.compile_s
    losses, walls, compiles_warm = [], [], None
    batch = first
    for i in range(STEPS):
        t0 = time.perf_counter()
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
        walls.append(round(time.perf_counter() - t0, 4))
        if i == 1:      # initial layouts + at most one donated relayout
            compiles_warm = train.compile_count(step)
        if i + 1 < STEPS:
            batch = next(batches)
    compiles = train.compile_count(step)
    steps_done = int(state.step)
    facts = {
        "loss": [round(x, 4) for x in losses], "step_wall_s": walls,
        "compile_s": round(rep.compile_s - c0, 2),
        "persistent_cache": dict(rep.cache),
        "compile_count_warm": compiles_warm, "compile_count": compiles,
        "state_step": steps_done,
        "ok": (all(np.isfinite(losses))
               and abs(losses[0] - math.log(VOCAB)) < 1.0
               and steps_done == STEPS
               and compiles == compiles_warm),
    }
    return state, facts


def train_loop(config: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import ray_tpu
    from ray_tpu import train
    from ray_tpu.models import GPT2
    from ray_tpu.models.gpt2 import gpt2_loss_fn
    from ray_tpu.parallel import make_mesh

    chips, seed = config["chips"], config["seed"]
    rep = _Reporter(config["phase_log"])

    with rep.phase("worker") as f:
        devs = jax.devices()
        f.update(platform=devs[0].platform, kind=devs[0].device_kind,
                 count=len(devs),
                 jax_platforms=os.environ.get("JAX_PLATFORMS"),
                 compilation_cache_dir=jax.config.jax_compilation_cache_dir,
                 tpu_ids=ray_tpu.get_tpu_ids(), pid=os.getpid())
        f["ok"] = devs[0].platform == PLATFORM and len(devs) == chips

    cfg = model_config()
    # The GPT-2 cells' optimizer (benchmark/configs/gpt2-124m.json):
    # bf16 first moment.
    opt = optax.adamw(3e-4, weight_decay=0.1, mu_dtype=jnp.bfloat16)
    mesh = make_mesh({"dp": chips})
    batch_size = BATCH_PER_CHIP * chips

    def fresh(mesh):
        model = GPT2(cfg, mesh=mesh)
        state = train.init_train_state(
            model.init_params(jax.random.key(seed)), opt, mesh)
        step = train.make_train_step(
            gpt2_loss_fn(model, ce_chunk=CE_CHUNK), opt)
        return state, step

    def setup():
        state, step = fresh(mesh)
        batches = train.get_dataset_shard("train").iter_device_batches(
            batch_size, mesh)
        first = next(batches)
        kernel = kernel_facts(step.lower(state, first).as_text(), cfg)
        return state, step, batches, first, kernel

    shapes = {"model": f"gpt2 L{cfg.n_layer} d{cfg.n_embd} "
                       f"h{cfg.n_head}x{cfg.head_dim} v{cfg.vocab_size}",
              "n_params": cfg.num_params(), "seq_len": cfg.seq_len,
              "global_batch": batch_size, "mesh": dict(mesh.shape)}

    if chips == 1:
        with rep.phase("kernel") as f:
            state, step, batches, first, kernel = setup()
            f.update(kernel)
        with rep.phase("train") as f:
            f.update(shapes)
            state, facts = _run_steps(rep, step, state, batches, first)
            f.update(facts, memory=_memory(devs))
        return

    with rep.phase("sharded") as f:
        state, step, batches, first, kernel = setup()
        spans = {"params": _span(state.params), "batch": _span(first)}
        state, facts = _run_steps(rep, step, state, batches, first)
        mem = _memory(devs)
        f.update(shapes, **kernel)
        f.update(facts, devices_spanned=spans, memory=mem)
        f["ok"] = (kernel["ok"] and facts["ok"]
                   and spans == {"params": [chips, chips],
                                 "batch": [chips, chips]}
                   # every chip holds at least its copy of the f32 params
                   and all((m["bytes_in_use"] or 0) >= 4 * cfg.num_params()
                           for m in mem))
    del state, step, batches, first

    with rep.phase("compare") as f:
        # One step from the same seed at global batch 32, on the dp=N
        # mesh (32/N per chip) and on a one-device mesh (32 on one
        # chip): N x 32 sequences do not fit one chip.
        rng = np.random.default_rng(seed + 1)
        toks = rng.integers(0, VOCAB, (BATCH_PER_CHIP, cfg.seq_len),
                            dtype=np.int32)
        host = {"tokens": toks, "targets": np.roll(toks, -1, 1)}
        got, kernels_ok = {}, True
        for name, m in (("dp", mesh), ("one", make_mesh({"dp": 1}))):
            state, step = fresh(m)
            batch = train.shard_batch(host, m)
            kernel = kernel_facts(step.lower(state, batch).as_text(), cfg)
            kernels_ok = kernels_ok and kernel["ok"]
            state, metrics = step(state, batch)
            got[name] = {"loss": float(metrics["loss"]),
                         "grad_norm": float(metrics["grad_norm"]),
                         "devices": _span(state.params)[1],
                         "tpu_custom_calls": kernel["tpu_custom_calls"]}
            del state, step, batch, metrics

        def rel(k):
            return abs(got["dp"][k] - got["one"][k]) / abs(got["one"][k])

        f.update(global_batch=BATCH_PER_CHIP, **got,
                 rel_diff={"loss": rel("loss"),
                           "grad_norm": rel("grad_norm")},
                 rtol=BF16_RTOL, memory=_memory(devs))
        f["ok"] = (got["dp"]["devices"] == chips
                   and got["one"]["devices"] == 1
                   and kernels_ok
                   and rel("loss") <= BF16_RTOL
                   and rel("grad_norm") <= BF16_RTOL)


# ---------------------------------------------------------------------------
# parent side: never touches a device


class SmokeFailed(Exception):
    pass


def emit(phase: str, ok: bool, **facts) -> None:
    print(json.dumps({"phase": phase, "ok": ok, **facts}), flush=True)
    if not ok:
        raise SmokeFailed(phase)


def detect(chips: int) -> None:
    """What the head will advertise and why, before any worker exists."""
    facts: dict = {
        "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS"),
        "JAX_COMPILATION_CACHE_DIR": os.environ.get(
            "JAX_COMPILATION_CACHE_DIR"),
    }
    try:
        import ray_tpu
        from ray_tpu.core.accelerator import detect_tpu_chips_with_source
        from ray_tpu.native.build import ensure_built
        from ray_tpu.util import compile_cache

        found, source = detect_tpu_chips_with_source()
        facts.update(detect_tpu_chips=found, source=source,
                     compilation_cache_dir=compile_cache.cache_dir())
        t0 = time.perf_counter()
        lib = ensure_built()
        facts.update(native_library=lib or "absent (build failed)",
                     native_build_s=round(time.perf_counter() - t0, 2))
        if found < chips:
            raise RuntimeError(
                f"need {chips} TPU chip(s), detect_tpu_chips() found "
                f"{found} (source: {source})")
        if lib is None:
            # A user's cluster would carry on with the Python store;
            # here a checkout that cannot build what it ships is a fault.
            raise RuntimeError("native library did not build")
        # The log monitor writes to the stdout it finds at init: hand
        # it stderr, so worker prints never follow the last line.
        with contextlib.redirect_stdout(sys.stderr):
            ray_tpu.init()
        facts["cluster_resources"] = ray_tpu.cluster_resources()
    except Exception as e:  # noqa: BLE001 — reported as the phase
        traceback.print_exc()
        emit("detect", False, **facts, error=f"{type(e).__name__}: {e}")
    emit("detect", True, **facts)


def read_phase_log(path: str) -> tuple[list[dict], str | None]:
    """(the lines of the phases that ended, the phase that started and
    never ended or None), as the worker appended them."""
    ended, open_phase = [], None
    with open(path) as f:
        for raw in f:
            line = json.loads(raw)
            if line.get("started"):
                open_phase = line["phase"]
            else:
                ended.append(line)
                open_phase = None
    return ended, open_phase


def run(chips: int, seed: int) -> dict:
    detect(chips)
    import tempfile

    import numpy as np

    import ray_tpu
    from ray_tpu import data, train

    seq_len = model_config().seq_len
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, VOCAB, (STEPS * BATCH_PER_CHIP * chips, seq_len),
                        dtype=np.int32)
    ds = data.from_numpy({"tokens": toks, "targets": np.roll(toks, -1, 1)})
    t0 = time.perf_counter()
    reported, error = None, None
    with tempfile.NamedTemporaryFile(prefix="chip_smoke_",
                                     suffix=".jsonl") as phase_log:
        trainer = train.JaxTrainer(
            train_loop,
            train_loop_config={"chips": chips, "seed": seed,
                               "phase_log": phase_log.name},
            scaling_config=train.ScalingConfig(
                num_workers=1, tpu_chips_per_worker=chips),
            datasets={"train": ds})
        try:
            result = trainer.fit()
            reported, error = result.metrics_history, result.error
        except Exception as e:  # noqa: BLE001 — the deadline lands here too
            traceback.print_exc()
            error = f"{type(e).__name__}: {e}"
        fit_s = round(time.perf_counter() - t0, 1)
        # Printed from the side file, not from what fit() returned: a
        # run cut short by the deadline or by the worker's death still
        # names every phase that ended and the one that did not.
        phases, open_phase = read_phase_log(phase_log.name)
    for line in phases:
        print(json.dumps(line), flush=True)
    if open_phase:
        print(json.dumps({"phase": open_phase, "ok": False,
                          "error": "started in the worker and never "
                                   "ended"}), flush=True)
    if not error and json.dumps(reported) != json.dumps(phases):
        error = (f"train.report delivered {len(reported)} of the "
                 f"{len(phases)} phase lines the worker wrote")
    if error or not phases or not all(p["ok"] for p in phases):
        emit("fit", False, fit_s=fit_s,
             error=(error or "a phase failed")[-3000:])
    ray_tpu.shutdown()

    import jax._src.xla_bridge as xb
    touched = xb.backends_are_initialized()
    emit("driver", not touched, fit_s=fit_s, backends_initialized=touched)
    worker = phases[0]
    return {"platform": worker["platform"], "kind": worker["kind"],
            "count": worker["count"]}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    def on_deadline(*_):
        raise TimeoutError(f"chip_smoke exceeded {DEADLINE_S} s")

    signal.signal(signal.SIGALRM, on_deadline)
    signal.alarm(DEADLINE_S)
    ok, device = False, None
    try:
        device = run(args.chips, args.seed)
        ok = device["platform"] == PLATFORM and device["count"] == args.chips
    except SmokeFailed:
        pass
    except Exception:  # noqa: BLE001 — the last line must still be printed
        traceback.print_exc()
    finally:
        signal.alarm(0)
        try:
            import ray_tpu
            ray_tpu.shutdown()      # stops every worker it started
        except ImportError:
            pass
        print(json.dumps({"ok": ok, "device": device}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
