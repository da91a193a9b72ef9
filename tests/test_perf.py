"""Microbenchmark harness sanity (ray_perf analog).

Thresholds are deliberately far below the recorded numbers
(round 2: ~3k sync tasks/s, ~4k sync actor calls/s on a 1-core
host vs the reference bar of 952 / 1,950 from SURVEY §6) — this guards
against order-of-magnitude control-plane regressions, not noise.
"""

import math

import pytest

import ray_tpu
from ray_tpu.perf import run_all


@pytest.mark.slow
def test_microbench_floors(rt):
    # Load-gated: floors relax 4x on a contended host and the test
    # skips outright past hard oversubscription (the documented
    # runner must be green on a busy 1-core box — absolute floors
    # there measure the neighbors, not the runtime).
    from conftest import perf_floor_gate
    relax = perf_floor_gate()
    results = {r["metric"]: r["value"] for r in run_all(quick=True)}
    assert results["single_client_tasks_sync"] > 300 / relax
    assert results["1_1_actor_calls_sync"] > 500 / relax
    assert results["1_1_actor_calls_async"] > 1000 / relax
    assert results["single_client_put_calls_1KiB"] > 1000 / relax
    # Direct actor-call plane: the worker->worker bypass must beat
    # the head-routed baseline measured in the SAME run on the same
    # machine (the whole point of taking the head off the per-call
    # critical path).
    assert results["actor_calls_direct_1_1"] >= \
        results["actor_calls_head_routed_1_1"], (
        f"direct path slower than head routing: "
        f"{results['actor_calls_direct_1_1']} vs "
        f"{results['actor_calls_head_routed_1_1']} calls/s")
    # Wire-hardening no-fault guardrail: the checksum/seq/heartbeat
    # envelope must not regress the steady-state rows vs the
    # pre-hardening round (round 7: direct 12.0k/s, sync tasks
    # 5.75k/s). Floors at 0.85x absorb quick-mode jitter; the strict
    # <2% contract is verified on idle-host medians by
    # scripts/perf_snapshot.py (WIRE_METRICS). heartbeat_overhead is
    # the isolated per-roundtrip envelope tax — single-digit us, or
    # something hot-path broke.
    assert results["actor_calls_direct_1_1"] > 0.85 * 12000 / relax
    assert results["single_client_tasks_sync"] > 0.85 * 5754 / relax
    assert results["heartbeat_overhead"] < 15.0 * relax, (
        f"wire envelope tax {results['heartbeat_overhead']}us — "
        f"hot path regressed")
    # Scale-envelope rows (PR 13): order-of-magnitude pins on the
    # indexed pending-queue paths. Measured on this 1-core box:
    # ~7 actors/s created+called, ~2.4k tasks/s drained, PG create
    # near-instant — floors sit far below so only a regression back
    # to an O(n) scan (or worse) trips them.
    assert results["actors_create_call_100"] > 1.0 / relax
    assert results["task_drain_5k"] > 300 / relax
    assert results["pg_create_50"] > 5.0 / relax
    # Signals-plane rows (PR 19): the head's per-interval sampling
    # tick over a 100-series registry and a deliberately oversized
    # 1k-rule SLO evaluation. Order-of-magnitude floors only — a trip
    # means a linear path went quadratic, not host jitter.
    assert results["signals_ingest_overhead"] > 20 / relax
    assert results["slo_eval_1k_rules"] > 2 / relax


@pytest.mark.slow
def test_serve_retry_plane_disabled_path_overhead(rt):
    """Zero-loss serving guardrail: with the retry plane DISABLED the
    proxy echo path must be the pre-retry fast path — the enabled
    path's throughput must stay within 5% of it (load-relaxed; the
    idle-host contract is tracked by the serve_proxy_echo /
    serve_proxy_echo_noretry pair in PERF snapshots)."""
    from conftest import perf_floor_gate
    relax = perf_floor_gate()
    from ray_tpu.perf import run_serve_bench
    rows = {r["metric"]: r for r in run_serve_bench(quick=True)}
    on = rows["serve_proxy_echo"]["value"]
    off = rows["serve_proxy_echo_noretry"]["value"]
    assert on >= 0.95 * off / relax, (
        f"retry plane costs more than 5% on the proxy echo path: "
        f"{on} req/s enabled vs {off} req/s disabled")
    # The mini soak inside the bench kills a replica mid-stream; the
    # zero-loss contract is no failed requests.
    soak = rows["serve_soak_p99"]
    assert soak["extra"]["failed"] == 0, soak
    assert soak["value"] > 0


def test_direct_calls_zero_head_frames_steady_state(rt):
    """Direct-call plane guardrail: once a handle's lease is warm, a
    burst of N calls must add ZERO submit frames on the head's client
    channel (the head op counter is the oplog-side proof; the
    caller-side counter proves the calls really took the bypass)."""
    from ray_tpu.core import protocol as P

    @ray_tpu.remote(num_cpus=0)
    class Bounce:
        def hit(self, i):
            return i

    @ray_tpu.remote(num_cpus=1)
    def burst(handle, n):
        import time as _t
        runtime = ray_tpu.core.api.get_runtime()
        deadline = _t.monotonic() + 15
        while _t.monotonic() < deadline:
            before = runtime.actor_calls_direct
            ray_tpu.get(handle.hit.remote(-1), timeout=60)
            if runtime.actor_calls_direct > before:
                break
            _t.sleep(0.2)
        d0 = runtime.actor_calls_direct
        vals = ray_tpu.get([handle.hit.remote(i) for i in range(n)],
                           timeout=120)
        return vals, runtime.actor_calls_direct - d0

    a = Bounce.remote()
    ray_tpu.get(burst.remote(a, 5), timeout=120)      # warm caller
    rt_obj = ray_tpu.core.api.get_runtime()
    before = {op: rt_obj.client_op_counts.get(op, 0)
              for op in (P.OP_SUBMIT_ACTOR_OWNED, P.OP_SUBMIT_ACTOR)}
    vals, direct = ray_tpu.get(burst.remote(a, 60), timeout=120)
    assert vals == list(range(60))
    assert direct >= 60, "burst did not take the direct path"
    for op, n0 in before.items():
        assert rt_obj.client_op_counts.get(op, 0) == n0, (
            f"steady-state direct calls sent {op} frames to the head")


def test_batched_get_wire_round_guardrail(rt):
    """A worker-side get of N remote refs must stay within
    1 + ceil(N / get_many_batch_size) blocking wire rounds — the
    vectorized object plane's core promise. A regression back to the
    per-ref OP_GET loop (N rounds) trips this immediately."""
    from ray_tpu.core.config import get_config

    n = 40
    refs = [ray_tpu.put(b"g%d" % i) for i in range(n)]

    @ray_tpu.remote(num_cpus=1)
    def counted_get(ref_lists):
        from ray_tpu.core.api import get_runtime
        runtime = get_runtime()
        inner = ref_lists[0]
        before = runtime.wire_rounds
        vals = ray_tpu.get(inner)
        return runtime.wire_rounds - before, len(vals)

    rounds, count = ray_tpu.get(counted_get.remote([refs]),
                                timeout=120)
    assert count == n
    batch = get_config().get_many_batch_size
    assert rounds <= 1 + math.ceil(n / batch), (
        f"{rounds} wire rounds for a {n}-ref batched get "
        f"(budget {1 + math.ceil(n / batch)})")


def test_task_event_recording_disabled_near_zero():
    """Observability guardrail: with reporting disabled the task-event
    record call on the execution hot path must be a bare flag check —
    budget 2µs/op on this deliberately slow box (the real cost is
    ~100ns; a regression that formats/locks/allocates per call lands
    well above the bound)."""
    import time

    from ray_tpu.observability import task_events as te

    te.set_recording(False)
    try:
        n = 50_000
        tid = b"\x01" * 16
        record = te.record_task_event
        t0 = time.perf_counter()
        for _ in range(n):
            record(tid, "guardrail", "RUNNING")
        per_op = (time.perf_counter() - t0) / n
        assert per_op < 2e-6, (
            f"disabled task-event record costs {per_op * 1e9:.0f}ns/op"
        )
        assert te.pending_events() == 0, \
            "disabled recording must not buffer events"
    finally:
        te.set_recording(True)


def test_admission_disabled_check_near_zero():
    """Overload-control guardrail: with admission disabled the only
    hot-path presence on every client submit is one flag read in
    ``AdmissionController.check`` — budget 2µs/op on this slow box
    (same contract as the task-event / profiler / tracing flags)."""
    import time

    from ray_tpu.core.admission import AdmissionController
    from ray_tpu.core.config import env_overrides, get_config

    with env_overrides(admission_enabled=False):
        ac = AdmissionController(get_config())
    assert ac.check(10 ** 9, "flooder") is None, \
        "disabled admission must admit everything"
    n = 50_000
    check = ac.check
    t0 = time.perf_counter()
    for _ in range(n):
        check(0, "driver")
    per_op = (time.perf_counter() - t0) / n
    assert per_op < 2e-6, (
        f"disabled admission check costs {per_op * 1e9:.0f}ns/op")
    assert ac.rejected == 0


def test_signals_disabled_tick_near_zero(rt):
    """Signals-plane guardrail: with sampling disabled the head loop's
    per-lap presence is one flag read in ``signals_tick`` — budget
    2µs/op (same contract as the admission / tracing flags)."""
    import time

    plane = ray_tpu.core.api.get_runtime().observability
    was = plane.signals_enabled
    plane.signals_enabled = False
    try:
        assert plane.signals_tick() is False
        n = 50_000
        tick = plane.signals_tick
        t0 = time.perf_counter()
        for _ in range(n):
            tick()
        per_op = (time.perf_counter() - t0) / n
        assert per_op < 2e-6, (
            f"disabled signals tick costs {per_op * 1e9:.0f}ns/op")
    finally:
        plane.signals_enabled = was


def test_head_pipeline_disabled_skips_store(rt):
    """With the plane disabled, the head-side task hot path must not
    feed the event store (the other half of the near-zero-overhead
    contract)."""
    rt_obj = ray_tpu.core.api.get_runtime()
    plane = rt_obj.observability
    plane.set_enabled(False)
    try:
        @ray_tpu.remote(num_cpus=1)
        def noop():
            return 1

        assert ray_tpu.get(noop.remote(), timeout=60) == 1
        head_events = [
            e for row in plane.task_events.rows()
            if row["name"] == "noop"
            for e in row["events"] if e["src"] == "head"]
        assert not head_events, head_events
    finally:
        plane.set_enabled(True)


def test_profiler_inactive_near_zero():
    """Introspection guardrail: with no profile session active the
    plane's only hot-path presence is the ``is_active`` flag read —
    budget 2µs/op on this slow box (a regression that takes a lock
    or walks frames per check lands far above it), and no sampler
    thread may linger."""
    import threading
    import time

    from ray_tpu.observability import profiler

    assert profiler.is_active() is False
    n = 50_000
    check = profiler.is_active
    t0 = time.perf_counter()
    for _ in range(n):
        check()
    per_op = (time.perf_counter() - t0) / n
    assert per_op < 2e-6, (
        f"inactive profiler check costs {per_op * 1e9:.0f}ns/op")
    assert not any(t.name == "profile_fanout"
                   for t in threading.enumerate())


def test_tracing_disabled_zero_span_frames(rt):
    """Causal-tracing guardrail: with tracing OFF (the default), a
    warm direct-call burst must send ZERO span-flush frames to the
    head and record ZERO spans in either process's ring — the
    disabled path is a flag check, not a sampling decision."""
    from ray_tpu.core import protocol as P

    @ray_tpu.remote(num_cpus=0)
    class Bounce:
        def hit(self, i):
            return i

    @ray_tpu.remote(num_cpus=1)
    def burst(handle, n):
        import time as _t

        from ray_tpu.util.tracing import get_tracer
        runtime = ray_tpu.core.api.get_runtime()
        deadline = _t.monotonic() + 15
        while _t.monotonic() < deadline:
            before = runtime.actor_calls_direct
            ray_tpu.get(handle.hit.remote(-1), timeout=60)
            if runtime.actor_calls_direct > before:
                break
            _t.sleep(0.2)
        d0 = runtime.actor_calls_direct
        vals = ray_tpu.get([handle.hit.remote(i) for i in range(n)],
                           timeout=120)
        tr = get_tracer()
        return (vals, runtime.actor_calls_direct - d0,
                tr.enabled, len(tr.get_spans()))

    a = Bounce.remote()
    ray_tpu.get(burst.remote(a, 5), timeout=120)      # warm caller
    rt_obj = ray_tpu.core.api.get_runtime()
    spans0 = rt_obj.client_op_counts.get(P.OP_SPANS, 0)
    vals, direct, enabled, ring = ray_tpu.get(burst.remote(a, 60),
                                              timeout=120)
    assert vals == list(range(60))
    assert direct >= 60, "burst did not take the direct path"
    assert enabled is False, "tracing enabled without opt-in"
    assert ring == 0, f"{ring} spans recorded with tracing disabled"
    assert rt_obj.client_op_counts.get(P.OP_SPANS, 0) == spans0, (
        "tracing-disabled burst flushed span frames to the head")


def test_tracing_disabled_ctx_read_near_zero():
    """The submit-path presence of tracing when disabled is one
    ``current_context`` read (flag + contextvar) — budget 2µs/op on
    this
    slow box, same contract as the task-event and profiler flags."""
    import time

    from ray_tpu.util.tracing import get_tracer

    tr = get_tracer()
    tr.disable()
    # The global ring may hold spans from earlier tests in this
    # process — the contract here is that the reads record NOTHING
    # new, not that history is empty.
    ring0 = len(tr.get_spans())
    try:
        n = 50_000
        read = tr.current_context
        t0 = time.perf_counter()
        for _ in range(n):
            read()
        per_op = (time.perf_counter() - t0) / n
        assert per_op < 2e-6, (
            f"disabled trace-ctx read costs {per_op * 1e9:.0f}ns/op")
        assert len(tr.get_spans()) == ring0
    finally:
        tr.disable()


def test_memory_summary_1k_objects_bounded(rt):
    """memory_summary over a 1000-object directory must stay a
    lock-scoped snapshot + sort — budget 0.5s/call on this box (the
    perf row memory_summary_1k_objects records the real rate)."""
    import time

    import ray_tpu as rtpu
    refs = [rtpu.put(b"p" * 64) for _ in range(1000)]
    rt_obj = rtpu.core.api.get_runtime()
    rt_obj.memory_summary(top_n=20)          # warm
    t0 = time.perf_counter()
    ms = rt_obj.memory_summary(top_n=20)
    dt = time.perf_counter() - t0
    assert ms["totals"]["objects"] >= 1000
    assert len(ms["top_objects"]) == 20
    assert dt < 0.5, f"memory_summary took {dt:.3f}s for 1k objects"
    del refs


# ---------------------------------------------------------------------------
# Fused donated train step: step-time guardrails (PR 9)


def _fused_step_time_ms(build, n_timed=3):
    """Warm a fused donated step (2 calls), then median-of-n step
    time. Returns (ms_per_step, compile_count_after)."""
    import statistics
    import time

    from ray_tpu.train import compile_count

    state, step, batches = build()
    for b in batches[:2]:
        state, m = step(state, b)
    float(m["loss"])
    times = []
    for b in batches[2:2 + n_timed]:
        t0 = time.perf_counter()
        state, m = step(state, b)
        float(m["loss"])
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1e3, compile_count(step)


def test_gpt2_fused_step_time_guardrail():
    """Tiny-GPT-2 fused donated step on the CPU backend: order-of-
    magnitude guardrail (load-gated) + the compile-count pin on the
    exact step construction the benchmark's GPT-2 cells time. Catches an accidentally
    unfused/recompiling hot loop, not noise."""
    from conftest import perf_floor_gate
    relax = perf_floor_gate()
    jax = pytest.importorskip("jax")
    import numpy as np
    import optax

    from ray_tpu.models import GPT2, GPT2Config
    from ray_tpu.models.gpt2 import gpt2_loss_fn
    from ray_tpu.train import init_train_state, make_train_step

    def build():
        cfg = GPT2Config.tiny()
        model = GPT2(cfg)
        state = init_train_state(
            model.init_params(jax.random.key(0)), optax.adamw(1e-3))
        step = make_train_step(gpt2_loss_fn(model, ce_chunk=64),
                               optax.adamw(1e-3), grad_norm=False)
        rng = np.random.default_rng(0)
        batches = []
        for _ in range(6):
            toks = rng.integers(0, cfg.vocab_size,
                                (2, cfg.seq_len)).astype(np.int32)
            batches.append({"tokens": toks,
                            "targets": np.roll(toks, -1, 1)})
        return state, step, batches

    ms, compiles = _fused_step_time_ms(build)
    # Measured ~5-15 ms/step on this 1-core box; 150 ms = 10-30x
    # headroom before the guardrail trips.
    assert ms < 150 * relax, f"tiny-GPT-2 fused step {ms:.1f}ms"
    assert compiles is None or compiles <= 2, (
        f"fused step compiled {compiles} executables at one shape")


def test_resnet_fused_step_time_guardrail():
    """Same contract for the ResNet bench path (donated fused step
    with batch_stats extra): load-gated step-time ceiling + stable
    compile count."""
    from conftest import perf_floor_gate
    relax = perf_floor_gate()
    jax = pytest.importorskip("jax")
    import numpy as np
    import optax

    from ray_tpu.models import ResNet, ResNet50Config
    from ray_tpu.models.resnet import resnet_loss_fn
    from ray_tpu.train import init_train_state, make_train_step

    def build():
        cfg = ResNet50Config.tiny()
        model = ResNet(cfg)
        variables = model.init_variables(jax.random.key(0), 32)
        opt = optax.sgd(0.1, momentum=0.9)
        state = init_train_state(variables["params"], opt,
                                 extra=variables["batch_stats"])
        step = make_train_step(resnet_loss_fn(model), opt,
                               has_extra=True, grad_norm=False)
        rng = np.random.default_rng(0)
        batches = []
        for _ in range(6):
            batches.append({
                "image": rng.standard_normal(
                    (4, 32, 32, 3)).astype(np.float32),
                "label": rng.integers(
                    0, cfg.num_classes, (4,)).astype(np.int32),
            })
        return state, step, batches

    ms, compiles = _fused_step_time_ms(build)
    # Measured ~10-30 ms/step here; 300 ms = ~10-30x headroom.
    assert ms < 300 * relax, f"tiny-ResNet fused step {ms:.1f}ms"
    assert compiles is None or compiles <= 2, (
        f"fused step compiled {compiles} executables at one shape")
