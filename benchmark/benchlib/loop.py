"""The measured loop. Runs inside the one worker process that holds the
chips, as the ``train_loop_per_worker`` of ``JaxTrainer.fit``.

Every time is ``time.monotonic()`` read in this process (the same
clock, system-wide, that ``run.py`` read when it started). The loop
never lets the device queue run dry to take a time: after dispatching
step i+1 it blocks on the loss of step i and stamps the clock. What a
window of stamps means is ``intervals.py``'s business; this file only
produces them, with the facts ``checks.py`` and the per-layer readers
need, and writes ``worker.json`` and ``intervals.json`` into the run's
directory.
"""

from __future__ import annotations

import json
import os
import time

WARM_STABLE_STAMPS = 3   # stamps in a row with the compile count still
TRACE_SETTLE_STAMPS = 2  # stamps let go by after start_trace
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _Compiles:
    """Backend compiles (and cache loads) of this process, by count and
    seconds, and the persistent cache's hits and misses."""

    def __init__(self):
        import jax.monitoring as mon
        self.count, self.seconds = 0, 0.0
        self.cache = {"hits": 0, "misses": 0}
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += secs

    def _on_event(self, event, **_):
        for k in self.cache:
            if event == f"/jax/compilation_cache/cache_{k}":
                self.cache[k] += 1


def _device_span(tree) -> list[int]:
    """[min, max] over the leaves of how many devices each spans."""
    import jax
    n = [len(x.sharding.device_set) for x in jax.tree_util.tree_leaves(tree)]
    return [min(n), max(n)]


def _program_bytes(compiled) -> dict:
    """What the compiler says the step needs on each device."""
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    got = {k: int(getattr(ma, f"{k}_size_in_bytes", 0))
           for k in ("argument", "output", "temp", "alias",
                     "generated_code")}
    # Donated arguments are reused for the outputs (alias), so the
    # program holds arguments + temporaries + what of the outputs is
    # not an argument's buffer.
    got["total"] = (got["argument"] + got["temp"]
                    + max(0, got["output"] - got["alias"]))
    return got


def train_loop(config: dict) -> None:
    facts: dict = {"t_enter": time.monotonic(), "phase": "enter"}
    try:
        _measure(config, facts)
        facts["phase"] = "done"
    finally:
        facts["t_exit"] = time.monotonic()
        with open(os.path.join(config["out_dir"], "worker.json"), "w") as f:
            json.dump(facts, f)


def _measure(config: dict, facts: dict) -> None:
    import jax

    from ray_tpu import train
    from ray_tpu.parallel import make_mesh

    from benchlib import manifest

    chips, seed, tiny = config["chips"], config["seed"], config["tiny"]
    traffic = config["traffic"]
    k_steps = traffic["steps_per_dispatch"]
    out_dir = config["out_dir"]

    def phase(name: str) -> None:
        facts["phase"] = name
        with open(os.path.join(out_dir, "progress.jsonl"), "a") as f:
            f.write(json.dumps({"phase": name, "t": time.monotonic()}) + "\n")

    # Every program of the run goes to the persistent cache, also the
    # small ones (input stack, init) that compile in under a second.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = _Compiles()

    phase("backend")
    t0 = time.monotonic()
    devs = jax.devices()
    facts.update(backend_init_s=time.monotonic() - t0,
                 platform=devs[0].platform, kind=devs[0].device_kind,
                 count=len(devs),
                 compilation_cache_dir=jax.config.jax_compilation_cache_dir)
    want = "cpu" if tiny else "tpu"
    if devs[0].platform != want or (len(devs) != chips and not tiny):
        raise RuntimeError(
            f"the cell asks for {chips} {want} device(s); this worker "
            f"holds {len(devs)} x {devs[0].platform}")
    devs = devs[:chips]
    mesh = make_mesh(dict(traffic["mesh"]), devices=devs)

    phase("build")
    t0 = time.monotonic()
    built = manifest.load_builder(config["config"]["builder"]).build(
        config["config"], traffic, mesh, seed, tiny)
    state = jax.block_until_ready(built["init_state"]())
    facts["state_init_s"] = time.monotonic() - t0
    step = built["step"]
    batches = built["batches"]()
    t0 = time.monotonic()
    batch = next(batches)
    facts["first_batch_s"] = time.monotonic() - t0

    kept = built["keep_for_reference"](state, batch)
    facts.update(
        devices_spanned={"params": _device_span(state.params),
                         "batch": _device_span(batch)},
        shapes=built["shapes"], steps_per_dispatch=k_steps,
        samples_per_step=built["samples_per_step"],
        uniform_over=built["uniform_over"],
        flops_per_sample=built["flops_per_sample"],
        kernel_cost_per_step=built["kernel_cost_per_step"])

    phase("loop")
    span = jax.profiler.TraceAnnotation
    clock = time.monotonic
    seconds = float(config["seconds"])
    trace_dir = os.path.join(out_dir, "trace") if config["trace"] else None
    trace_n = traffic["trace_dispatches"]
    stamps: list[float] = []
    losses: list[float] = []
    host: dict[str, list[float]] = {
        "input": [], "dispatch": [], "sync": [], "report": []}
    open_i = close_i = trace_started = trace_from = trace_to = None
    last_cc, stable = None, 0
    window_span = None
    pending = None          # the newest dispatch's loss, not yet read
    first_metrics = None    # of dispatch 0: the step at the initial state
    dispatched = 0
    while True:
        a = clock()
        if dispatched:
            with span("bench.input"):
                batch = next(batches)
        b = clock()
        with span("bench.dispatch"):
            state, metrics = step(state, batch)
        c = clock()
        if not dispatched:
            first_metrics = metrics
        dispatched += 1
        prev, pending = pending, metrics["loss"]
        if prev is not None:
            with span("bench.sync"):
                loss = float(prev)
            t = clock()
            with span("bench.report"):
                train.report({"dispatch": len(stamps), "loss": loss})
            e = clock()
            i = len(stamps)
            stamps.append(t)
            losses.append(loss)
            for key, v in (("input", b - a), ("dispatch", c - b),
                           ("sync", t - c), ("report", e - t)):
                host[key].append(v)
            if open_i is None:
                cc = train.compile_count(step)
                if cc is None:
                    cc = compiles.count
                stable = stable + 1 if cc == last_cc else 0
                last_cc = cc
                if stable >= WARM_STABLE_STAMPS:
                    open_i = i
                    facts["compiles_at_open"] = compiles.count
                    facts["compile_s_at_open"] = compiles.seconds
                    facts["step_compile_count"] = cc
            elif close_i is None:
                if t >= stamps[open_i] + seconds:
                    close_i = i
                    facts["compiles_at_close"] = compiles.count
                    if trace_dir is None:
                        break
                    import jax.profiler as prof
                    opts = prof.ProfileOptions()
                    opts.python_tracer_level = 0
                    prof.start_trace(trace_dir, profiler_options=opts)
                    trace_started = i
            elif trace_from is None:
                if i >= trace_started + TRACE_SETTLE_STAMPS:
                    trace_from = i
                    window_span = span("bench.window")
                    window_span.__enter__()
            elif i >= trace_from + trace_n:
                trace_to = i
                window_span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                break

    phase("drain")
    losses.append(float(pending))
    batches.close()             # stops the prefetcher's thread
    train.report({"dispatch": len(stamps), "loss": losses[-1]})
    facts.update(
        open_i=open_i, close_i=close_i, trace_from=trace_from,
        trace_to=trace_to, stamps=stamps, losses=losses, host_s=host,
        dispatched=dispatched, state_step=int(state.step),
        reports_sent=len(losses),
        compiles_total=compiles.count, compile_s_total=compiles.seconds,
        persistent_cache=dict(compiles.cache),
        memory_stats_peak_bytes=max(
            ((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs), default=0))
    t_open = stamps[open_i]
    with open(os.path.join(out_dir, "intervals.json"), "w") as f:
        json.dump({"steps_per_dispatch": k_steps, "open": open_i,
                   "close": close_i, "trace_from": trace_from,
                   "trace_to": trace_to,
                   "stamps_s": [s - t_open for s in stamps],
                   "loss": losses, "host_s": host}, f)

    # The kernel check and the program's size, from the lowering the
    # loop's own dispatches made: the same state and batch types find
    # jit's cached lowering and its loaded executable, so nothing is
    # traced, compiled or loaded again, and none of it is set-up.
    phase("lowered")
    t0 = clock()
    lowered = step.lower(state, batch)
    facts["tpu_custom_calls"] = lowered.as_text().count("tpu_custom_call")
    facts["program_bytes"] = _program_bytes(lowered.compile())
    facts["lower_compile_s"] = clock() - t0
    facts["compiles_after_lowering"] = compiles.count

    # The plain float32 reference, after the window and outside set-up:
    # loss and gradient norm at the initial parameters on the first
    # batch, against the program's own (checks.py holds them together).
    phase("reference")
    t0 = clock()
    plain = built["reference"](kept)
    probe = built.get("program_probe")
    program = (probe(kept) if probe
               else {k: float(first_metrics[k]) for k in plain})
    facts["reference"] = {
        "plain_f32": plain, "program": program,
        "program_from": "probe" if probe else "first dispatch",
        "seconds": clock() - t0}
