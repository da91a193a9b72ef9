"""JaxTrainer — the DataParallelTrainer analog, TPU-first.

Reference call stack being re-based (SURVEY.md §3.4): BaseTrainer.fit →
WorkerGroup of actors → backend process-group setup → per-worker loop →
report()/checkpoint → poll. Differences by design:

- the "backend" is jax.distributed over the gang (coordinator address
  rendezvous), after which ALL collectives are compiled into the user's
  jitted step over ICI — no NCCL process group object to babysit;
- a worker = one host of the slice, owning its local chips; a
  single-worker trainer runs SPMD over every local chip via the mesh,
  so data-parallelism inside one host needs no worker group at all;
- failure handling restarts the whole gang from the latest checkpoint
  (SPMD slice semantics: one host down ⇒ slice restart, SURVEY.md
  §7.3.2), driven by FailureConfig(max_failures).
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable

from ray_tpu.train.config import RunConfig, ScalingConfig
from ray_tpu.train.worker_group import WorkerGroup
from ray_tpu.util import tracing


@dataclass
class Result:
    metrics: dict[str, Any]
    checkpoint_dir: str | None
    path: str
    metrics_history: list[dict[str, Any]] = field(default_factory=list)
    error: str | None = None
    # When RunConfig.storage_path is a URI: the mirrored location of
    # the final checkpoint in remote storage.
    remote_checkpoint_uri: str | None = None
    # The fit's phases on the driver and in every worker, under one
    # trace id (``tracing.Span.to_dict``; docs/observability.md). Also
    # in ``<path>/fit_trace.json`` and in ``tracing.get_spans()``.
    spans: list[dict] = field(default_factory=list)

    @property
    def checkpoint(self):
        from ray_tpu.train.session import Checkpoint
        if self.checkpoint_dir is None:
            return None
        return Checkpoint(self.checkpoint_dir)


class JaxTrainer:
    """Distributed data-parallel (and beyond) JAX training.

    train_loop_per_worker runs inside each gang worker; it uses
    ``ray_tpu.train.get_context()`` for rank/size and
    ``ray_tpu.train.report(metrics, checkpoint=...)`` to stream results.

    For the device hot loop, use the same fused-step/prefetch plumbing
    the benchmark's cells measure (docs/training_perf.md): build the
    step with
    ``train.make_train_step`` / ``make_multi_train_step`` (optimizer
    update jitted into the step, param/opt-state buffers donated in
    place) and feed it from
    ``get_dataset_shard(name).iter_device_batches(batch_size, mesh)``
    — or ``train.prefetch_to_device`` for a custom source — so host
    input staging overlaps device compute instead of serializing with
    it. ``DataContext.prefetch_batches`` is the overlap depth.

    The loop finds jax started: each worker has joined
    ``jax.distributed`` (a gang of more than one) and opened the
    accelerator backend (the span ``train.worker.backend_init``) before
    the loop is called. ``jax.config`` options read at use are set in
    the loop as anywhere; what jax reads when the backend opens
    (``XLA_FLAGS``, ``jax_num_cpu_devices``) comes with the worker's
    environment, ``init(runtime_env={"env_vars": ...})``
    (docs/QUICKSTART.md).
    """

    # Backend hook: which TrainWorker method builds the collective
    # group (jax.distributed here; torch gloo in train.torch).
    _backend_setup = "setup_distributed"
    _setup_single_worker = False
    # Whether each worker opens jax's backend ahead of the user's loop,
    # under the span ``train.worker.backend_init``.
    _opens_jax_backend = True

    def __init__(self,
                 train_loop_per_worker: Callable,
                 *,
                 train_loop_config: dict | None = None,
                 scaling_config: ScalingConfig | None = None,
                 run_config: RunConfig | None = None,
                 datasets: dict | None = None,
                 dataset_config=None):
        self.train_loop = train_loop_per_worker
        self.loop_config = train_loop_config or {}
        self.scaling = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        # {name: Dataset} — streaming_split per worker at fit();
        # workers read via train.get_dataset_shard(name)
        # (reference: DataParallelTrainer datasets= + DataConfig).
        self.datasets = datasets or {}
        from ray_tpu.train.config import DataConfig
        self.dataset_config = dataset_config or DataConfig()

    # -- public API --

    def fit(self) -> Result:
        name = self.run_config.name or f"train_{int(time.time())}"
        from ray_tpu.util.storage import is_uri
        remote_uri = None
        if is_uri(self.run_config.storage_path):
            # Remote storage_path (reference: StorageContext's
            # fs/S3/GS URIs, storage.py:352): run against a local
            # staging dir, mirror the trial tree to the URI at every
            # exit — a TPU pod's results and checkpoints land
            # off-host. Workers still write to the staging dir
            # (single host or shared FS), exactly the reference's
            # local-then-upload flow.
            from ray_tpu.util.storage import stage_dir, uri_join
            remote_uri = uri_join(self.run_config.storage_path, name)
            trial_dir = stage_dir(
                "/tmp/ray_tpu_sessions/experiments_staging", name)
        else:
            trial_dir = os.path.join(self.run_config.storage_path,
                                     name)
        os.makedirs(trial_dir, exist_ok=True)

        # This fit's spans, the driver's phases and (from the poll
        # reply that tells each worker's end) the workers';
        # ``_fit_once`` adds to them through the instance.
        spans: list[tracing.Span] = []
        self._spans = spans
        with tracing.train_span("train.fit", {
                "trial_dir": trial_dir,
                "workers": self.scaling.num_workers,
                "chips": self.scaling.num_workers
                * self.scaling.worker_resources().get("TPU", 0)},
                sink=spans) as root:
            result = self._fit_with_restarts(trial_dir)
        # Into the process ring, so that tracing.get_spans() holds the
        # fit after it returns and after ray_tpu.shutdown().
        tracing.get_tracer().add_spans([s.to_dict() for s in spans])
        # The whole cold start: what the ring holds of this process
        # before the fit (``core.init``, ``native.build``), then the fit.
        spans = tracing.process_spans(root.mono_start) + spans
        result.spans = [s.to_dict() for s in spans]
        with open(os.path.join(trial_dir, "fit_trace.json"), "w") as f:
            json.dump(tracing.chrome_events(spans), f)
        return self._mirror(trial_dir, remote_uri, result)

    def _fit_with_restarts(self, trial_dir: str) -> Result:
        max_failures = self.run_config.failure_config.max_failures
        attempt = 0
        restored: str | None = None
        # Checkpoints already in the trial dir belong to a previous
        # run reusing this name — never silently resume from them.
        try:
            preexisting = frozenset(os.listdir(trial_dir))
        except OSError:
            preexisting = frozenset()
        drain_restarts = 0
        while True:
            try:
                if attempt == 0 and drain_restarts == 0:
                    # Before the first gang only: a restart may find
                    # its slice drained and the replacement on its way.
                    why = _unmeetable_tpu_request(self.scaling)
                    if why:
                        raise _WorkerGroupError(why, None)
                return self._fit_once(trial_dir, restored)
            except _WorkerGroupError as e:
                # A drain-triggered interruption (the gang's node was
                # preempted/scaled down WITH notice — worker deaths
                # carry a "drained" reason) is an anticipated,
                # checkpoint-covered migration: restart elastically
                # from the latest checkpoint WITHOUT consuming the
                # FailureConfig.max_failures budget, which is
                # reserved for real crashes. Bounded only by a large
                # safety cap against a pathological drain loop.
                drained = _is_drain_interruption(e.error)
                if drained:
                    drain_restarts += 1
                else:
                    attempt += 1
                # Workers persist checkpoints to storage before the
                # driver polls the matching report, so on actor death
                # the on-disk record can be ahead of e.latest_ckpt —
                # recover from whichever is newest.
                latest = _latest_complete_checkpoint(
                    trial_dir, e.latest_ckpt, exclude=preexisting,
                    world_size=self.scaling.num_workers)
                exhausted = (max_failures >= 0
                             and attempt > max_failures)
                if (exhausted and not drained) or drain_restarts > 100:
                    return Result(
                        metrics={}, checkpoint_dir=latest,
                        path=trial_dir, metrics_history=e.history,
                        error=e.error)
                # Elastic slice restart from the latest checkpoint.
                restored = latest

    def _mirror(self, trial_dir: str, remote_uri: str | None,
                result: Result) -> Result:
        if remote_uri is None:
            return result
        from ray_tpu.util.storage import mirror_dir, uri_join
        err = mirror_dir(trial_dir, remote_uri)
        if err:
            # A failed mirror must NOT discard a finished Result —
            # everything still exists locally; surface the problem
            # on the result instead of raising away hours of work.
            result.error = ((result.error or "") + " " + err).strip()
            return result
        result.path = remote_uri
        if result.checkpoint_dir:
            rel = os.path.relpath(result.checkpoint_dir, trial_dir)
            if not rel.startswith(".."):
                result.remote_checkpoint_uri = uri_join(remote_uri,
                                                        rel)
        return result

    # -- internals --

    def _fit_once(self, trial_dir: str, restored: str | None) -> Result:
        latest_ckpt: str | None = restored
        history: list[dict] = []
        spans: list[tracing.Span] = self._spans

        def phase(name: str, attributes: dict | None = None):
            return tracing.train_span(f"train.fit.{name}", attributes,
                                      sink=spans)

        group = None
        try:
            # Placement group, actor creation, worker boot and imports,
            # barrier: ``WorkerGroup`` records the two parts.
            with phase("gang_start"):
                try:
                    # A gang that is not placed in time is a
                    # worker-group failure like any other
                    # (FailureConfig).
                    group = WorkerGroup(
                        num_workers=self.scaling.num_workers,
                        resources_per_worker=(
                            self.scaling.worker_resources()),
                        placement_strategy=(
                            self.scaling.placement_strategy),
                        spans=spans,
                    )
                except TimeoutError as e:
                    raise _WorkerGroupError(str(e), latest_ckpt) from e
            if self.scaling.num_workers > 1 or self._setup_single_worker:
                with phase("backend_setup"):
                    # Rank 0 advertises the rendezvous point from its
                    # own (possibly remote) host — the driver's
                    # loopback means nothing to a gang spanning node
                    # daemons.
                    coordinator = group.coordinator()
                    payload = coordinator
                    extra = getattr(self, "_backend_setup_extra", None)
                    if extra:
                        # backend knobs (e.g. TorchConfig.timeout_s)
                        # ride the rendezvous payload
                        payload = (coordinator, extra)
                    group.run(self._backend_setup, payload,
                              timeout=120)
            with phase("start_loop") as span:
                ctx_kwargs = {
                    "experiment_name": os.path.basename(trial_dir),
                    "storage_path": self.run_config.storage_path,
                    "trial_dir": trial_dir,
                    "restored_checkpoint_dir": restored,
                    # the workers' spans go under the fit's root
                    "trace_ctx": (span.trace_id, span.parent_id),
                    "open_backend": self._opens_jax_backend,
                }
                if self.datasets:
                    # DataConfig.datasets_to_split: "all" or a list of
                    # names; unsplit datasets replicate — every worker
                    # iterates the full stream (reference: DataConfig).
                    to_split = self.dataset_config.datasets_to_split
                    ctx_kwargs["dataset_shards_all"] = {
                        name: (ds.streaming_split(group.num_workers)
                               if (to_split == "all" or name in to_split)
                               else [ds.iterator()] * group.num_workers)
                        for name, ds in self.datasets.items()}
                group.run("start_loop",
                          (self.train_loop, self.loop_config),
                          ctx_kwargs, timeout=120)

            final_metrics: dict = {}
            done = [False] * group.num_workers
            # report_to_poll: from report() in a worker to the poll
            # that drained it, on that worker's clock.
            with phase("poll", {"polls": 0, "reports": 0,
                                "report_to_poll_s_sum": 0.0,
                                "report_to_poll_s_max": 0.0}) as span:
                seen = span.attributes
                while not all(done):
                    polls = group.run("poll", timeout=600)
                    seen["polls"] += 1
                    for i, p in enumerate(polls):
                        # Results first: what a worker reported before
                        # it failed is kept on the error Result.
                        for r in p["results"]:
                            if r["rank"] == 0:
                                history.append(r["metrics"])
                                final_metrics = r["metrics"]
                            if r["checkpoint_dir"]:
                                latest_ckpt = r["checkpoint_dir"]
                            seen["reports"] += 1
                            seen["report_to_poll_s_sum"] += r["waited_s"]
                            seen["report_to_poll_s_max"] = max(
                                seen["report_to_poll_s_max"],
                                r["waited_s"])
                        spans.extend(tracing.Span(**d)
                                     for d in p.get("spans", ()))
                        if p["error"]:
                            raise _WorkerGroupError(
                                p["error"], latest_ckpt, history)
                        done[i] = p["done"]
                    if not all(done):
                        time.sleep(0.05)
            return Result(metrics=final_metrics,
                          checkpoint_dir=latest_ckpt, path=trial_dir,
                          metrics_history=history)
        except _WorkerGroupError:
            raise
        except Exception as e:  # noqa: BLE001 — actor/infra failure
            raise _WorkerGroupError(str(e), latest_ckpt, history) from e
        finally:
            if group is not None:
                with phase("shutdown"):
                    group.shutdown()


def _unmeetable_tpu_request(scaling: ScalingConfig) -> str | None:
    """Why the gang's TPU request cannot be met, where that is known
    without waiting: the alive nodes hold fewer chips than it asks for
    and no autoscaler is attached that could add a slice. None where
    it can be met, or may yet be."""
    per_worker = scaling.worker_resources().get("TPU", 0)
    want = scaling.num_workers * per_worker
    if not want:
        return None
    import ray_tpu
    from ray_tpu.util.state import cluster_status
    total = ray_tpu.cluster_resources()
    have = total.get("TPU", 0)
    if want <= have or cluster_status()["autoscaler"]["attached"]:
        return None
    return (f"the worker group asks for {want:g} TPU chips "
            f"({scaling.num_workers} workers x {per_worker:g}) but the "
            f"cluster's nodes hold {have:g} (cluster_resources() = "
            f"{total}) and no autoscaler is attached; chips come from "
            f"core.accelerator.detect_tpu_chips() or init(num_tpus=...)")


def _is_drain_interruption(error: str | None) -> bool:
    """True when a worker-group failure was caused by a graceful
    node drain (ActorDiedError carries a ``node ... drained: ...``
    reason from the runtime's drain path) rather than a crash."""
    return bool(error) and "drained" in error


def _latest_complete_checkpoint(
        trial_dir: str, polled: str | None, *,
        exclude: frozenset[str] = frozenset(),
        world_size: int = 1) -> str | None:
    """Newest on-disk checkpoint that finished persisting, preferring
    disk over the lossy polled report stream. Complete = rank 0's
    marker exists AND, when the save is sharded (any ``rank_N/``
    present), ALL ``world_size`` ranks have their markers — a rank
    that died before even creating its shard directory must not make
    the checkpoint look complete. Rank-0-only checkpoints (replicated
    state) have no rank dirs and stay accepted. ``exclude`` filters
    out checkpoints from a previous run reusing the name."""
    from ray_tpu.train.session import checkpoint_index

    def complete(d: str) -> bool:
        path = os.path.join(trial_dir, d)
        if not os.path.exists(os.path.join(path, ".complete_rank_0")):
            return False
        try:
            entries = os.listdir(path)
        except OSError:
            return False
        sharded = any(e.startswith("rank_") and e[5:].isdigit()
                      for e in entries)
        if not sharded:
            return True
        return all(f".complete_rank_{r}" in entries
                   for r in range(world_size))

    best = polled
    try:
        names = sorted(
            d for d in os.listdir(trial_dir)
            if d.startswith("checkpoint_") and d not in exclude
            and complete(d))
    except OSError:
        return best
    if names and checkpoint_index(names[-1]) > checkpoint_index(best):
        best = os.path.join(trial_dir, names[-1])
    return best


class _WorkerGroupError(Exception):
    def __init__(self, error: str, latest_ckpt: str | None,
                 history: list[dict] | None = None):
        super().__init__(error)
        self.error = error
        self.latest_ckpt = latest_ckpt
        self.history = history or []
