"""Worker group: the gang of training actors.

Analog of the reference's WorkerGroup + BackendExecutor
(python/ray/train/_internal/worker_group.py:102,
backend_executor.py:68,135,451): N actors created inside one placement
group (STRICT_PACK = the ICI-slice gang), each running the user loop in
a background thread while its actor loop stays responsive for result
polling — the same split as the reference's _TrainSession thread.
"""

from __future__ import annotations

import queue
import threading
import time
import traceback
from typing import Any, Callable

import ray_tpu
from ray_tpu.core.placement_group import (
    PlacementGroupSchedulingStrategy,
)
from ray_tpu.train.prefetch import collect_counters
from ray_tpu.train.stall import StallWatch, hbm_sample
from ray_tpu.util import tracing


@ray_tpu.remote
class TrainWorker:
    """One rank of the training gang."""

    def __init__(self, rank: int, world_size: int, env_vars: dict):
        import os
        os.environ.update(env_vars)
        self.rank = rank
        self.world_size = world_size
        self._t_init = time.monotonic()
        self._thread: threading.Thread | None = None
        self._done = threading.Event()
        self._error: str | None = None
        self._session = None

    def get_coordinator(self) -> str:
        """Advertise a rendezvous address on THIS worker's host, so a
        gang spanning node daemons on different hosts forms one
        jax.distributed world (reference: TorchConfig picks the master
        addr from worker 0's node, torch/config.py:66). The driver
        must never pick the address — it may not even share a machine
        with rank 0."""
        import socket
        host = _routable_ip()
        with socket.socket() as s:
            s.bind(("", 0))
            port = s.getsockname()[1]
        return f"{host}:{port}"

    def setup_distributed(self, coordinator: str) -> bool:
        """jax.distributed rendezvous (the TorchConfig
        master-addr/port analog, reference torch/config.py:66)."""
        if self.world_size > 1:
            import jax
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=self.world_size,
                process_id=self.rank)
        return True

    def setup_torch_distributed(self, coordinator) -> bool:
        """torch.distributed gloo process group (reference:
        _setup_torch_process_group, torch/config.py:115). The payload
        is the rendezvous address, optionally tupled with backend
        knobs ({"timeout_s": ...} from TorchConfig)."""
        import os

        import torch.distributed as dist
        extra: dict = {}
        if isinstance(coordinator, tuple):
            coordinator, extra = coordinator
        addr, port = coordinator.rsplit(":", 1)
        os.environ["MASTER_ADDR"] = addr
        os.environ["MASTER_PORT"] = port
        os.environ.setdefault("RANK", str(self.rank))
        os.environ.setdefault("WORLD_SIZE", str(self.world_size))
        if not dist.is_initialized():
            kwargs = {}
            if extra.get("timeout_s"):
                from datetime import timedelta
                kwargs["timeout"] = timedelta(
                    seconds=float(extra["timeout_s"]))
            dist.init_process_group(
                "gloo", rank=self.rank, world_size=self.world_size,
                **kwargs)
        return True

    def start_loop(self, fn_and_config: tuple, context_kwargs: dict) -> bool:
        from ray_tpu.train.session import (
            TrainContext, init_session,
        )
        fn, loop_config = fn_and_config
        context_kwargs = dict(context_kwargs)
        # Trainer datasets arrive as the FULL per-name shard lists
        # (identical args to every worker); each worker keeps only
        # its rank's DataIterator.
        shards_all = context_kwargs.pop("dataset_shards_all", None)
        shards = ({name: lst[self.rank]
                   for name, lst in shards_all.items()}
                  if shards_all else {})
        # The fit's trace: (trace id, the driver's ``train.fit`` span).
        trace_ctx = context_kwargs.pop("trace_ctx", None)
        # A JaxTrainer's worker opens the accelerator backend itself,
        # before the user's loop.
        open_backend = context_kwargs.pop("open_backend", False)
        ctx = TrainContext(world_rank=self.rank,
                           world_size=self.world_size,
                           local_rank=self.rank,
                           loop_config=loop_config or {},
                           dataset_shards=shards,
                           **context_kwargs)
        session = self._session = init_session(ctx, trace_ctx)
        target = {"parent": trace_ctx, "sink": session.spans}
        if tracing.process_start is not None:
            # Interpreter, imports, the dial back and the actor's
            # construction (in a process taken warm from the pool, its
            # wait there too); it ends where the boot begins.
            import os
            tracing.record_train_span(
                "train.worker.process", tracing.process_start,
                self._t_init, {"rank": self.rank, "pid": os.getpid()},
                **target)
        tracing.record_train_span(
            "train.worker.boot", self._t_init, time.monotonic(),
            {"rank": self.rank}, **target)

        def run():
            try:
                with collect_counters() as input_totals, \
                        tracing.train_span("train.worker.loop",
                                           {"rank": self.rank},
                                           **target) as span:
                    # beside the loop for as long as it runs: the
                    # stalled steps, each a ``train.stall`` under this
                    # span, their totals on it, and what the devices'
                    # allocators held (``hbm_*``)
                    watch = StallWatch(
                        session, (span.trace_id, span.span_id),
                        input_totals)
                    try:
                        if open_backend:
                            session.devices = _open_backend(session.spans)
                        if _takes_config(fn):
                            fn(loop_config or {})
                        else:
                            fn()
                    finally:
                        span.attributes.update(watch.stop())
                        span.attributes.update(input_totals())
                        # what the loop's thread told the session and
                        # made no span of: each prefetcher's first
                        # batch, the first report
                        for ends in session.first_batches:
                            tracing.record_train_span(
                                "train.input.first_batch", *ends,
                                sink=session.spans)
                        if session.t_first_report is not None:
                            span.attributes["first_report_s"] = (
                                session.t_first_report - span.mono_start)
            except BaseException:  # noqa: BLE001
                self._error = traceback.format_exc()
            finally:
                self._done.set()

        self._thread = threading.Thread(target=run, daemon=True,
                                        name=f"train_loop_rank{self.rank}")
        self._thread.start()
        return True

    def poll(self, max_results: int = 16) -> dict:
        """Drain queued results; report completion/errors. The end of
        the loop is read BEFORE the drain and told only once the queue
        is empty, so everything the loop reported reaches the trainer
        ahead of its completion or its error."""
        done, error = self._done.is_set(), self._error
        out = []
        drained = True
        now = time.monotonic()
        if self._session is not None:
            while len(out) < max_results:
                try:
                    r = self._session.results.get_nowait()
                except queue.Empty:
                    break
                out.append({"metrics": r.metrics,
                            "checkpoint_dir": r.checkpoint_dir,
                            "rank": r.rank, "index": r.index,
                            # report() to this poll, on this worker's
                            # clock
                            "waited_s": now - r.t_report})
            drained = self._session.results.empty()
        reply = {"results": out,
                 "done": done and drained,
                 "error": error if drained else None}
        if done and drained and self._session is not None:
            # The first reply that tells the end carries this worker's
            # train-path spans, handed over once: the trainer polls a
            # finished worker again until the slowest is done.
            spans = self._session.spans
            reply["spans"] = [s.to_dict() for s in spans]
            del spans[:len(reply["spans"])]
        return reply

    def ping(self) -> str:
        return "ok"


def _routable_ip() -> str:
    """This host's address as seen by peers. Prefer the route toward
    the cluster head (RAY_TPU_HEAD_IP, set by the node daemon for its
    workers) — an address this process's host provably reaches, which
    also yields the right interface on air-gapped networks where the
    8.8.8.8 probe has no route. The UDP connect performs only a route
    lookup, no packets. Single-machine clusters correctly resolve to
    loopback through the head probe."""
    import os
    import socket
    probes = []
    head_ip = os.environ.get("RAY_TPU_HEAD_IP")
    if head_ip:
        probes.append(head_ip)
    probes.append("8.8.8.8")
    for target in probes:
        try:
            with socket.socket(socket.AF_INET,
                               socket.SOCK_DGRAM) as s:
                s.connect((target, 80))
                return s.getsockname()[0]
        except OSError:
            continue
    try:
        return socket.gethostbyname(socket.gethostname())
    except OSError:
        return "127.0.0.1"


def _open_backend(spans: list) -> list:
    """Open the accelerator backend (6-15 s on a TPU) in the loop's
    thread, ahead of the user's loop: its first ``jax.devices()`` is
    then a lookup, and the seconds are the program's own span (under
    ``train.worker.loop``, in the worker's list ``spans``) and not the
    user's code. So the loop finds jax started (``JaxTrainer``'s
    docstring): what jax reads when the backend opens comes with the
    worker's environment, not from the loop. A backend that does not
    open (a worker pinned to a platform that is not there) leaves
    ``error`` on the span and nothing else: the loop's own first use
    of jax raises as it always did, and a loop that never touches jax
    runs.

    Returns this process's devices (none where the backend did not
    open), whose memory the stall watch samples. Where their allocator
    counts, the span says how much there is and what was on the chip
    before this job put anything there: ``hbm_limit_bytes`` (the
    smallest ``bytes_limit``), ``hbm_in_use_at_open_bytes`` (the
    largest ``bytes_in_use``: a warm process's previous fit, another
    tenant)."""
    try:
        with tracing.train_span("train.worker.backend_init",
                                sink=spans) as span:
            import jax
            devices = jax.devices()
            span.attributes.update(platform=devices[0].platform,
                                   device_kind=devices[0].device_kind,
                                   devices=len(devices))
            local = jax.local_devices()
            sample = hbm_sample(local)
            for name, key, of in (
                    ("hbm_limit_bytes", "bytes_limit", min),
                    ("hbm_in_use_at_open_bytes", "bytes_in_use", max)):
                given = [s[key] for s in sample if key in s]
                if given:
                    span.attributes[name] = of(given)
            return local
    except RuntimeError:
        return []


def _takes_config(fn: Callable) -> bool:
    import inspect
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return False
    return len(sig.parameters) >= 1


class WorkerGroup:
    def __init__(self, num_workers: int,
                 resources_per_worker: dict[str, float],
                 placement_strategy: str = "STRICT_PACK",
                 env_vars: dict | None = None,
                 spans: list | None = None):
        """The gang, placed, created and answering: two train-path
        spans, kept in ``spans`` (the fit's list), else in the process
        ring."""
        self.num_workers = num_workers
        self.workers: list = []
        bundles = [dict(resources_per_worker) for _ in range(num_workers)]
        with tracing.train_span("train.fit.gang_start.placement", {
                "bundles": len(bundles), "strategy": placement_strategy},
                sink=spans):
            # The group is always created, also where no node can place
            # it yet: an unplaced bundle is the demand an autoscaler
            # reads, and a slice that was drained may be on its way
            # back.
            self.pg = ray_tpu.placement_group(
                bundles, strategy=placement_strategy)
            if not self.pg.ready(timeout=120):
                ray_tpu.remove_placement_group(self.pg)
                raise TimeoutError(
                    f"placement group {bundles} ({placement_strategy}) "
                    f"was not placed within 120 s; available_resources()"
                    f" = {ray_tpu.available_resources()}")
        strategy = PlacementGroupSchedulingStrategy(self.pg)
        # Actor creation, each worker process's start and imports,
        # ``TrainWorker.__init__``, and the barrier that waits for all.
        with tracing.train_span("train.fit.gang_start.actors",
                                {"workers": num_workers}, sink=spans):
            try:
                for rank in range(num_workers):
                    self.workers.append(TrainWorker.options(
                        num_cpus=resources_per_worker.get("CPU", 1),
                        num_tpus=resources_per_worker.get("TPU", 0) or None,
                        resources={
                            k: v for k, v in resources_per_worker.items()
                            if k not in ("CPU", "TPU")},
                        scheduling_strategy=strategy,
                    ).remote(rank, num_workers, env_vars or {}))
                self.barrier()
            except BaseException:
                self.shutdown()     # nobody else holds the group yet
                raise

    def barrier(self, timeout: float = 120.0) -> None:
        ray_tpu.get([w.ping.remote() for w in self.workers],
                    timeout=timeout)

    def coordinator(self, timeout: float = 60.0) -> str:
        """Rendezvous address chosen by rank 0 from its own host."""
        return ray_tpu.get(
            self.workers[0].get_coordinator.remote(), timeout=timeout)

    def run(self, method: str, *args, timeout: float | None = None,
            **kwargs) -> list:
        refs = [getattr(w, method).remote(*args, **kwargs)
                for w in self.workers]
        return ray_tpu.get(refs, timeout=timeout)

    def shutdown(self) -> None:
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:  # noqa: BLE001
                pass
        try:
            ray_tpu.remove_placement_group(self.pg)
        except Exception:  # noqa: BLE001
            pass
