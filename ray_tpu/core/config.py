"""Config flag system.

Analog of the reference's ``RAY_CONFIG(type, name, default)`` X-macro list
(``src/ray/common/ray_config_def.h``): every flag is declared once with a
type and default, is overridable via a ``RAY_TPU_<NAME>`` environment
variable, and can be overridden per-session via
``ray_tpu.init(_system_config={...})`` — the whole local cluster sees one
consistent config (tests use this to crank failure timeouts down, same
pattern as the reference's ``_system_config`` injection).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, fields
from typing import Any

_ENV_PREFIX = "RAY_TPU_"


def _coerce(value: str, typ: type) -> Any:
    if typ is bool:
        return value.lower() in ("1", "true", "yes", "on")
    return typ(value)


@dataclass
class Config:
    # --- scheduling ---
    # Max worker processes for task execution (0 = num_cpus).
    max_workers: int = 0
    # Seconds an idle pooled worker survives before being reaped
    # (reference: worker_pool idle reaping, worker_pool.cc).
    idle_worker_ttl_s: float = 60.0
    # Workers to prestart at init (reference: WorkerPool::PrestartWorkers).
    prestart_workers: int = 0
    # Lease reuse: a leased worker is retained per scheduling key for this
    # long awaiting more same-shape tasks (reference: NormalTaskSubmitter
    # lease caching, normal_task_submitter.cc).
    lease_reuse_timeout_s: float = 10.0
    # Hybrid scheduling: pack onto earlier nodes until CPU utilization
    # crosses this fraction, then spread to the least-loaded node
    # (reference: RAY_scheduler_spread_threshold = 0.5,
    # hybrid_scheduling_policy.cc).
    scheduler_spread_threshold: float = 0.5
    # Lease pipelining: when a worker receives a task, up to depth-1
    # additional SAME-sched-class, dependency-free, DEFAULT-scheduled
    # pending tasks are queued onto it under the same resource
    # acquisition; the worker runs them serially and the lease's
    # resources release when the last one finishes (reference: one
    # lease executes many same-shape tasks,
    # normal_task_submitter.cc lease reuse by SchedulingKey). 1
    # disables pipelining.
    worker_pipeline_depth: int = 4

    # --- objects ---
    # Objects at or above this size go to the shared-memory store instead
    # of the in-process memory store (reference: plasma threshold).
    max_direct_call_object_size: int = 100 * 1024
    # Shared-memory object store capacity in bytes (0 = 30% of RAM,
    # like the reference's default object_store_memory).
    object_store_memory: int = 0
    # Directory for object spilling (reference: local_object_manager).
    spill_dir: str = "/tmp/ray_tpu_spill"
    # Begin spilling when the store is this full.
    object_spilling_threshold: float = 0.8
    # Object-manager transfer plane (reference: ObjectBufferPool
    # chunking + PullManager, object_manager.h:117): objects shipped
    # to clients that cannot map the shm arena are pulled in chunks
    # of this size so one huge object never head-of-line blocks the
    # client channel.
    object_transfer_chunk_bytes: int = 4 * 1024 * 1024
    # Inline (single-message) ship objects up to this size; larger
    # ones go through the chunked pull protocol.
    object_transfer_inline_max: int = 8 * 1024 * 1024
    # Pipelined chunk pulls: chunks k+1..k+W are requested while
    # chunk k is being assembled (reference: PullManager keeps
    # multiple chunk requests in flight per pull). 1 = serial
    # req/resp per chunk (the pre-vectorized behavior).
    object_transfer_window: int = 8
    # Bounded width of the remote-pull fan-out inside a batched get:
    # node-homed refs in one get([...]) are fetched on up to this
    # many threads instead of a serial loop.
    get_parallelism: int = 8
    # Max refs per OP_GET_MANY wire round; a larger get([...]) is
    # split client-side so one reply frame stays bounded. The wire-
    # round guardrail in tests/test_perf.py is ceil(N/this) + 1.
    get_many_batch_size: int = 512
    # Per-process deserialization cache (immutable objects only):
    # repeated get() of the same ObjectID returns the cached value
    # instead of re-deserializing. 0 disables the cache.
    deser_cache_max_bytes: int = 256 * 1024 * 1024
    # Only objects at or above this size are cached — matches the
    # shm threshold by default, so only shared-memory-resident
    # (read-only page-backed) objects are ever served from cache.
    deser_cache_min_bytes: int = 100 * 1024

    # --- direct actor calls (reference: direct actor call path +
    # the ownership model taking the GCS out of steady-state actor
    # submission, core_worker actor task submission; NSDI'21
    # "Ownership" §3) ---
    # Master switch: after a handle's first (head-routed) call
    # resolves the actor's location, later calls go worker->worker
    # over a peer connection, sending ZERO frames to the head. Off =
    # every call takes the head-routed path (the pre-PR behavior).
    direct_calls_enabled: bool = True
    # Args at or under this pickled size ride inline in the direct
    # call frame; larger calls fall back to head routing (which
    # resolves/stages args through the object plane).
    direct_call_inline_threshold: int = 100 * 1024
    # Max unacked direct calls in flight per (caller, actor) channel;
    # submits past the window block until acks drain (back-pressure,
    # and a bound on the fallback replay buffer).
    direct_call_window: int = 256
    # Executed direct-call results retained per hosting worker for
    # at-most-once replay dedupe (a fallback replay of an
    # already-executed seqno gets the cached result, not a re-run).
    direct_call_result_cache: int = 4096

    # --- fault tolerance ---
    # Default task max retries (reference: max_retries=3 default).
    task_max_retries: int = 3
    # Lineage reconstruction (reference: ObjectRecoveryManager,
    # object_recovery_manager.h:41): re-execute the creating task when
    # a stored object is lost with its node. The lineage cache retains
    # task specs up to this many bytes of pickled args (reference:
    # lineage bytes cap, task_manager.h:215-222); 0 disables
    # reconstruction entirely.
    lineage_cache_max_bytes: int = 256 * 1024 * 1024
    # Max re-executions of one task for object recovery.
    max_reconstructions: int = 3
    # Recently-consumed escape-nonce window (reordering tolerance
    # between the exec and client channels); evictions under heavy
    # borrow traffic can leave conservative permanent pins.
    preconsumed_window: int = 65536
    # Default actor max restarts.
    actor_max_restarts: int = 0
    # Health-check period for actor/worker processes.
    health_check_period_s: float = 1.0
    # Missed health checks before a process is declared dead
    # (reference: GcsHealthCheckManager thresholds, ray_config_def.h:847).
    health_check_failure_threshold: int = 5
    # Resource-view sync period: how often the head checks for (and,
    # only on change, broadcasts) the versioned cluster resource
    # snapshot daemons serve resource queries from; also the daemons'
    # load-report cadence (reference: ray_syncer periodic snapshots,
    # ray_syncer.h:88).
    rview_period_s: float = 1.0

    # --- node drain / preemption (reference: DrainNode protocol,
    # gcs_node_manager.cc DrainNode + autoscaler termination hooks) ---
    # Grace window for in-flight tasks on a draining node before they
    # are preempted and retried elsewhere (preemption refunds the
    # attempt — an anticipated failure must not burn retry budget).
    drain_grace_period_s: float = 5.0
    # Default total drain deadline when the caller (or the preemption
    # notice) does not specify one: object evacuation, actor
    # migration, and task preemption must all finish inside it.
    drain_deadline_s: float = 30.0

    # --- memory monitor / OOM killer (reference: MemoryMonitor
    # memory_monitor.h:52 + worker_killing_policy_retriable_fifo) ---
    # Kill a retriable task when system memory usage crosses this
    # fraction (0 disables the monitor).
    memory_usage_threshold: float = 0.95
    # Seconds between memory polls.
    memory_monitor_refresh_s: float = 1.0

    # --- wire hardening (ray_tpu/core/wire.py — heartbeats,
    # deadlines, frame checksums on every long-lived channel;
    # reference: gRPC keepalive/deadline args + GcsHealthCheckManager
    # probes) ---
    # Ping a monitored channel after this long without ANY received
    # frame (traffic itself proves liveness, so busy channels never
    # pay a heartbeat frame).
    heartbeat_interval_s: float = 5.0
    # A monitored channel silent this long (pings unanswered) is
    # declared dead: the socket is shut down, waking blocked readers
    # into the existing reconnect/replay/fallback recovery paths.
    heartbeat_timeout_s: float = 20.0
    # Connect AND auth-handshake deadline for every dial site
    # (client->head, daemon->head, worker->worker direct, object
    # peer, CLI) — an unreachable peer raises a ConnectionError
    # naming it instead of blocking uninterruptibly.
    connect_timeout_s: float = 10.0
    # Dial attempts (jittered exponential backoff between them).
    connect_retries: int = 3
    # CRC32 frame checksums: corrupted frames are refused before
    # unpickling and surface as a channel reset + retry.
    wire_checksum_enabled: bool = True
    # Master switch for heartbeat monitoring (checksums/seq stay on).
    wire_heartbeat_enabled: bool = True

    # --- timeouts ---
    get_timeout_default_s: float = 0.0  # 0 = no timeout
    actor_creation_timeout_s: float = 120.0
    # How long a client's async-submit drainer waits for an ack
    # before treating the op as lost and replaying it (dd-deduped)
    # through the reconnect fence. Drain/preemption tests and
    # flaky-head deployments tighten this.
    client_ack_replay_timeout_s: float = 300.0

    # --- logging / events ---
    # Task lifecycle events ring-buffer capacity per worker
    # (reference: TaskEventBuffer, task_event_buffer.h:220).
    task_event_buffer_size: int = 10000
    log_dir: str = "/tmp/ray_tpu_sessions/logs"

    # --- observability (reference: metrics_report_interval_ms +
    # task_events_report_interval_ms feeding the per-node metrics
    # agent and GcsTaskManager, SURVEY.md §5.5) ---
    # Master switch for the cluster metrics/event pipeline: worker
    # exporters, head-side ingestion, and task-event recording. Off =
    # near-zero hot-path overhead (guardrail in tests/test_perf.py).
    metrics_export_enabled: bool = True
    # Seconds between exporter flushes (registry snapshot + buffered
    # task events + finished spans -> one OP_METRICS_PUSH frame).
    metrics_report_interval_s: float = 5.0
    # Max task events / spans shipped per flush frame; the remainder
    # stays ring-buffered for the next interval.
    metrics_flush_batch: int = 2048

    # --- signals plane / SLO alerting (head-side time series over
    # the aggregator's merged registry; reference: the dashboard's
    # Prometheus-backed series + SRE-workbook multiwindow burn-rate
    # alerts, done in-process) ---
    # Master switch for head-side sampling + SLO evaluation. Off =
    # no sampling thread and a bare flag check per tick (guardrail
    # in tests/test_perf.py). Requires metrics_export_enabled too.
    signals_enabled: bool = True
    # Seconds between head samples of the merged registry into the
    # per-series ring buffers.
    signals_sample_interval_s: float = 1.0
    # Raw-tier retention: queries with windows inside it read
    # full-resolution points.
    signals_retention_s: float = 600.0
    # Coarse tier keeps every Nth sample for signals_coarse_retention_s
    # — longer windows downsample instead of growing memory.
    signals_coarse_factor: int = 10
    signals_coarse_retention_s: float = 7200.0
    # Hard cap on tracked (name, tag-set) series; overflow is counted
    # (series_dropped), never grown.
    signals_max_series: int = 2048
    # Per-deployment serve p99 SLO target in milliseconds; > 0 auto-
    # creates a burn-rate rule per deployment seen in the latency
    # histogram. 0 disables the serve auto-rules.
    slo_serve_p99_target_ms: float = 0.0
    # Multiwindow burn-rate shape: both windows must burn for a rule
    # to leave OK — fast catches sudden regressions, slow suppresses
    # blips. WARN at burn_warn x target, PAGE at burn_page x.
    slo_window_fast_s: float = 60.0
    slo_window_slow_s: float = 300.0
    slo_burn_warn: float = 1.0
    slo_burn_page: float = 2.0

    # --- causal tracing (reference: tracing_helper.py span
    # propagation around every .remote(); Dapper-style head-side
    # assembly) ---
    # Probability a new trace root is head-sampled. Roots that lose
    # the roll are still recorded but marked deferred; the head keeps
    # them only under the two rules below. Workers inherit this via
    # RAY_TPU_TRACE_SAMPLE_RATE in their spawn env.
    trace_sample_rate: float = 1.0
    # Keep a deferred trace anyway if any span in it errored.
    trace_sample_on_error: bool = True
    # Keep a deferred trace anyway if its wall time crossed this many
    # milliseconds (tail-latency force sampling; 0 = off).
    trace_force_sample_ms: float = 0.0
    # Open an ingress root span per proxied serve request (HTTP and
    # gRPC), with router dispatch / retry attempts and replica
    # execution as children. Off by default so the serve hot path
    # stays span-free; sampling knobs above apply when on.
    trace_serve_requests: bool = False
    # Head-side TraceStore bounds: max assembled traces retained, how
    # long a trace waits for missing parents before orphans are
    # adopted, and idle TTL before a trace is swept.
    trace_store_max_traces: int = 512
    trace_orphan_grace_s: float = 3.0
    trace_ttl_s: float = 900.0

    # --- serve request plane (reference: serve/_private/{router,
    # replica,proxy}.py — request retries, deployment health checks,
    # graceful draining, and proxy back-pressure) ---
    # Master switch for the request retry/replay plane. Off = the
    # pre-retry behavior: one dispatch, no request ids, no pending
    # accounting (the ≤5% disabled-path guardrail in tests/test_perf.py
    # measures this path against the enabled one).
    serve_retry_enabled: bool = True
    # Re-dispatch attempts after the first (so 3 = up to 4 total
    # executions attempted) when a replica dies, is stopping, or
    # sheds the request (reference: handle max_retries semantics).
    serve_request_max_retries: int = 3
    # Base of the jittered exponential backoff between re-dispatches.
    serve_retry_backoff_s: float = 0.05
    # How long a request waits out an EMPTY routing table (rolling
    # redeploy gap: old replicas stopped, new ones not yet ready)
    # before failing; does not consume retry attempts.
    serve_no_replica_wait_s: float = 10.0
    # Router long-poll: max time one listen_for_change call camps on
    # the controller before re-arming (was hardcoded 60 s).
    serve_longpoll_timeout_s: float = 60.0
    # Router blocking refresh of the routing table (was hardcoded 30 s).
    serve_refresh_timeout_s: float = 30.0
    # Power-of-two-choices queue-depth probe of two candidate
    # replicas (was hardcoded 5 s).
    serve_queue_probe_timeout_s: float = 5.0
    # Bound on one replica call from the proxies when the request
    # carries no deadline of its own (was hardcoded 120 s).
    serve_call_timeout_s: float = 120.0
    # Controller-driven replica health probes: cadence, per-probe
    # timeout, and consecutive failures before the replica is ejected
    # from the pushed routing table and replaced (reference:
    # DeploymentState health-check constants).
    serve_health_check_period_s: float = 1.0
    serve_health_check_timeout_s: float = 5.0
    serve_health_check_failure_threshold: int = 3
    # A spawned replica that never passes its first probe (readiness
    # gate) within this window is torn down and respawned.
    serve_replica_startup_timeout_s: float = 60.0
    # Default end-to-end request deadline (0 = none). Proxies also
    # honor per-request deadlines (X-Request-Timeout-S header / gRPC
    # client deadline), which override this.
    serve_request_deadline_s: float = 0.0
    # Bounded per-replica request queue: a replica already holding
    # this many accepted requests sheds new ones back to the router
    # (deployments override via max_ongoing_requests).
    serve_max_queue_len_per_replica: int = 64
    # Proxy-side in-flight cap across all deployments: past it, HTTP
    # answers 503 + Retry-After and gRPC answers UNAVAILABLE without
    # touching the routing plane.
    serve_proxy_max_inflight: int = 256
    # Stopping replicas: total drain deadline, and the minimum grace
    # during which a stopping replica still ACCEPTS new requests so
    # routers on a stale table don't see errors (then it sheds with
    # ReplicaStoppingError and the retry plane moves the traffic).
    serve_drain_deadline_s: float = 30.0
    serve_drain_min_grace_s: float = 2.0
    # Executed-response ledger entries per replica for duplicate
    # re-dispatch dedupe (mirrors direct_call_result_cache).
    serve_result_ledger_size: int = 2048

    # --- head admission / backpressure (reference: raylet
    # backpressure + serve's 503/Retry-After semantics, applied to
    # the task/actor/PG control planes; SURVEY §L2) ---
    # Master switch. Off = pre-admission behavior: every submit is
    # accepted, queues grow without bound (the ≈0-overhead disabled
    # path is guardrailed in tests/test_perf.py).
    admission_enabled: bool = True
    # High-water mark on the head's pending task queue: a submit-class
    # op arriving past it is answered ST_BUSY + retry-after instead of
    # being enqueued. Sized so ordinary bursts (thousands of tasks)
    # never see pushback — backpressure is for floods.
    head_pending_high_water: int = 20000
    # Hard cap as a multiple of the high-water mark: light clients
    # (under their fair share) are still admitted between high and
    # high*hard_factor, so one flooder can't lock everyone out the
    # moment it fills the queue.
    admission_hard_factor: float = 1.25
    # Fairness: with 2+ active clients, one client may hold at most
    # max(high*fair_fraction, high/active_clients) pending tasks
    # before ITS submits shed while lighter clients' still land.
    admission_fair_fraction: float = 0.5
    # Base retry-after hint (seconds) in busy replies; scaled up with
    # overload depth, jittered client-side.
    admission_retry_after_s: float = 0.05
    # Sync (blocking) client ops give up with ConnectionError after
    # retrying busy replies for this long.
    admission_client_max_wait_s: float = 120.0
    # Driver-local submits (no wire to push back on) BLOCK while the
    # queue sits at the high-water mark — at most this long, then
    # admit anyway (a bounded wait can't deadlock dependency chains).
    admission_driver_block_s: float = 30.0
    # Reject client dials (server-sent busy hint + close, honored by
    # wire.dial backoff) once depth crosses high*this factor — only
    # under severe overload; exec/node channels are never rejected.
    admission_dial_reject_factor: float = 2.0
    # Debug invariant check on the pending-queue bookkeeping (count ==
    # sum of per-class counts == sum of structure lengths), verified
    # on every mutation. Costs O(classes) per enqueue — tests only.
    debug_pending_invariants: bool = False

    # --- TPU / device ---
    # Treat a multi-host TPU slice as an atomic gang-scheduled unit.
    gang_schedule_slices: bool = True
    # Coordinator port for jax.distributed rendezvous.
    coordinator_port: int = 8476

    @classmethod
    def from_env(cls, overrides: dict[str, Any] | None = None) -> "Config":
        kwargs: dict[str, Any] = {}
        for f in fields(cls):
            env_key = _ENV_PREFIX + f.name.upper()
            if env_key in os.environ:
                kwargs[f.name] = _coerce(os.environ[env_key], f.type
                                         if isinstance(f.type, type)
                                         else type(f.default))
        if overrides:
            valid = {f.name for f in fields(cls)}
            for k, v in overrides.items():
                if k not in valid:
                    raise ValueError(f"unknown config flag: {k}")
                kwargs[k] = v
        return cls(**kwargs)


_global: Config | None = None
_lock = threading.Lock()


def get_config() -> Config:
    global _global
    with _lock:
        if _global is None:
            _global = Config.from_env()
        return _global


def set_config(cfg: Config) -> None:
    global _global
    with _lock:
        _global = cfg


def reset_config() -> None:
    global _global
    with _lock:
        _global = None


from contextlib import contextmanager  # noqa: E402


@contextmanager
def env_overrides(**flags):
    """Scoped config injection for an already-running process AND any
    child processes it spawns inside the scope.

    Sets the ``RAY_TPU_<FLAG>`` env vars (daemons/workers started in
    the scope inherit them at their own ``Config.from_env``) and
    atomically swaps this process's cached config; both are restored
    on exit. This is the supported way for tests to crank timeouts
    down — reaching into the private cached global is not (reference:
    per-test ``_system_config`` via conftest,
    python/ray/tests/conftest.py:131).

        with env_overrides(health_check_period_s=0.2):
            cluster = Cluster(...)
    """
    valid = {f.name for f in fields(Config)}
    for k in flags:
        if k not in valid:
            raise ValueError(f"unknown config flag: {k}")
    saved_env: dict[str, str | None] = {}
    for k, v in flags.items():
        key = _ENV_PREFIX + k.upper()
        saved_env[key] = os.environ.get(key)
        os.environ[key] = str(v)
    global _global
    with _lock:
        saved_cfg = _global
        _global = Config.from_env()
    try:
        yield get_config()
    finally:
        for key, old in saved_env.items():
            if old is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = old
        with _lock:
            _global = saved_cfg
