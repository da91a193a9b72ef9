"""Async input pipeline: overlap host batch production and H2D
transfer with device compute.

The train-step hot loop must never wait on the host. A synchronous
loop pays, per step: host batch production (RNG / dataset decode) +
``device_put`` dispatch + the step itself. :class:`DevicePrefetcher`
moves the first two off the critical path: a background thread pulls
host batches from the source, places them on device (``device_put``
only *dispatches* the transfer — the copy itself proceeds async under
the runtime), and parks up to ``depth`` device-resident batches in a
bounded queue. The consuming loop pops a ready batch and immediately
dispatches the next step, so step N's compute overlaps step N+1's
input production and transfer (classic double buffering at
``depth=2``).

This is the single input-overlap implementation for the framework:
``Dataset.iter_device_batches`` (the train.fit() path via
``get_dataset_shard``) and user loops through
``ray_tpu.train.prefetch_to_device`` both ride it.
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from typing import Any, Callable, Iterable, Iterator

from ray_tpu.train import session
from ray_tpu.util import tracing
from ray_tpu.util.tracing import annotation

__all__ = ["DevicePrefetcher", "prefetch_to_device", "collect_counters"]

_SENTINEL = object()

# The ``counters`` of every prefetcher made while a collector is open
# (``fit()``'s worker opens one around the user's loop). The dicts
# outlive their prefetchers and hold no batch.
_collected: list[dict] | None = None


@contextlib.contextmanager
def collect_counters():
    """Yields a function that gives the counters of every prefetcher
    this process made since, summed: ``{"input.stall_s": ...}``."""
    global _collected
    made = _collected = []

    def totals() -> dict[str, float]:
        out: dict[str, float] = {}
        for counters in made:
            for k, v in counters.items():
                out[f"input.{k}"] = out.get(f"input.{k}", 0) + v
        return out
    try:
        yield totals
    finally:
        _collected = None


class DevicePrefetcher:
    """Iterator yielding device-placed batches produced ahead of
    consumption by a background thread.

    Parameters
    ----------
    source:
        Iterable (or iterator) of host batches. May block (dataset
        reads) — that is exactly the work being overlapped.
    place:
        ``batch -> device batch``; ``None`` passes batches through
        (source already yields device-resident values, e.g. a jitted
        on-device generator). Runs on the background thread.
    depth:
        Max batches in flight past the one being consumed. 2 = double
        buffering; larger depths absorb burstier sources at the cost
        of live-batch memory.

    ``counters``, always on (cumulative; inside ``fit()`` summed onto
    the ``train.worker.loop`` span): ``batches``; ``stall_s``, the
    seconds the consumer blocked waiting (~0 means the input is fully
    hidden); ``source_s`` and ``place_s``, the
    producer's seconds in ``next(source)`` and in ``place``. Under a
    device profile the same three are the spans ``train.input.wait``
    (consumer thread), ``train.input.source`` and ``train.input.place``
    (this prefetcher's thread). The first batch alone is a span kept
    in Python, ``train.input.first_batch``: from this constructor to
    the batch's hand-over, with that batch's three times.
    """

    def __init__(self, source: Iterable | Iterator,
                 place: Callable[[Any], Any] | None = None,
                 depth: int = 2):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._source = iter(source)
        self._place = place
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._err: BaseException | None = None
        self.depth = depth
        self.counters = {"batches": 0, "stall_s": 0.0, "source_s": 0.0,
                         "place_s": 0.0}
        if _collected is not None:
            _collected.append(self.counters)
        # Until the first batch is handed over: when this was made. From
        # the producer: that batch's seconds in source and in place.
        self._t_made: float | None = time.monotonic()
        self._first_s = (0.0, 0.0)
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="device_prefetch")
        self._thread.start()

    # -- background producer --

    def _run(self) -> None:
        counters = self.counters
        first = True
        try:
            while not self._stop.is_set():
                t0 = time.perf_counter()
                try:
                    with annotation("train.input.source"):
                        batch = next(self._source)
                except StopIteration:
                    break
                t1 = time.perf_counter()
                counters["source_s"] += t1 - t0
                if self._place is not None:
                    with annotation("train.input.place"):
                        batch = self._place(batch)
                    counters["place_s"] += time.perf_counter() - t1
                if first:       # nothing was placed before it
                    first = False
                    self._first_s = (t1 - t0, counters["place_s"])
                # Bounded put, polling the stop flag so close() never
                # deadlocks against a full queue.
                while not self._stop.is_set():
                    try:
                        self._q.put(batch, timeout=0.1)
                        break
                    except queue.Full:
                        continue
        except BaseException as e:  # noqa: BLE001 — surfaced on next()
            self._err = e
        finally:
            while not self._stop.is_set():
                try:
                    self._q.put(_SENTINEL, timeout=0.1)
                    break
                except queue.Full:
                    continue

    # -- consumer side --

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        with annotation("train.input.wait"):
            item = self._q.get()
        self.counters["stall_s"] += time.perf_counter() - t0
        if item is _SENTINEL:
            if self._err is not None:
                err, self._err = self._err, None
                raise err
            raise StopIteration
        self.counters["batches"] += 1
        if self._t_made is not None:
            self._record_first_batch()
        return item

    def _record_first_batch(self) -> None:
        """``train.input.first_batch``, once. Inside a fit the session
        keeps its ends and the worker records it under
        ``train.worker.loop``; outside it goes to the process ring,
        under the train-path span open in the consumer's thread."""
        t_made, self._t_made = self._t_made, None
        source_s, place_s = self._first_s
        ends = (t_made, time.monotonic(),
                {"source_s": source_s, "place_s": place_s,
                 "stall_s": self.counters["stall_s"]})
        if not session.hold_first_batch(ends):
            tracing.record_train_span("train.input.first_batch", *ends)

    def close(self) -> None:
        """Stop the producer and release queued batches."""
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def prefetch_to_device(batches: Iterable, mesh=None, *, depth: int = 2,
                       batch_dim: int = 0, seq_sharded: bool = False,
                       place: Callable[[Any], Any] | None = None):
    """Wrap an iterable of host batches in a :class:`DevicePrefetcher`
    that shards each batch across ``mesh`` (via
    ``train.step.shard_batch``) ahead of consumption.

    ``place`` overrides the placement function entirely (ignoring
    ``mesh``); ``mesh=None`` without ``place`` dispatches a plain
    ``jax.device_put``.
    """
    if place is None:
        if mesh is not None:
            from ray_tpu.train.step import shard_batch

            def place(b):  # noqa: E306
                return shard_batch(b, mesh, seq_sharded=seq_sharded,
                                   batch_dim=batch_dim)
        else:
            import jax

            def place(b):  # noqa: E306
                return jax.tree_util.tree_map(jax.device_put, b)
    return DevicePrefetcher(batches, place=place, depth=depth)
