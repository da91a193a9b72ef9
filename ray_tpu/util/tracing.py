"""Distributed tracing: spans that follow tasks across processes.

Reference analog (SURVEY.md §5.1): OpenTelemetry tracing wraps every
``.remote()`` (tracing_helper.py:293) and serializes the span context
into task metadata, re-hydrated in the executing worker; exporters are
pluggable. Here: a process-local tracer with contextvar propagation;
the driver injects (trace_id, parent_span_id) into the task wire
message, the worker parents its spans under it and ships finished
spans back over the client channel — so one trace spans driver and
workers. Export as a span list or Chrome-trace JSON (the same
``chrome://tracing`` surface as ``ray.timeline``).

Device profiling: ``profile_device()`` wraps ``jax.profiler.trace``
(the nsight-plugin analog for TPU — SURVEY.md §5.1 TPU mapping).

The train path (``train_span``, ``record_train_span``, ``annotation``;
docs/observability.md) needs no ``enable()``: the dozen phases of one
``fit()``, and what the process did before it (``core.init``,
``native.build``), are always recorded. ``time.monotonic()`` is the
clock every process of one host shares, the one a benchmark's own
stamps are on; a span that may be open during a device profile
(``train_span``) is also a ``jax.profiler.TraceAnnotation`` of the same
name, on the host plane of the profile, on the profiler's clock beside
the device operations. What happens every step
is an ``annotation`` alone: nothing is kept unless a profile runs.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import random
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field

_current: contextvars.ContextVar = contextvars.ContextVar(
    "ray_tpu_current_span", default=None)

# Root-span attribute marking a trace whose sampling decision is
# deferred: it was below ``trace_sample_rate`` at the root, but may
# still be kept by the head if it errored (sample-on-error) or crossed
# the tail-latency threshold (force-sample-above-ms). The TraceStore
# drops deferred traces that earn neither at finalize time.
DEFERRED_ATTR = "trace.deferred"


@dataclass
class Span:
    name: str
    trace_id: str
    span_id: str
    parent_id: str | None
    start: float
    end: float = 0.0
    attributes: dict = field(default_factory=dict)
    process: str = ""
    # time.monotonic() at both ends; 0.0 on the control plane's and
    # Serve's spans, which stamp time.time() alone.
    mono_start: float = 0.0
    mono_end: float = 0.0

    def to_dict(self) -> dict:
        return {
            "name": self.name, "trace_id": self.trace_id,
            "span_id": self.span_id, "parent_id": self.parent_id,
            "start": self.start, "end": self.end,
            "attributes": dict(self.attributes),
            "process": self.process,
            "mono_start": self.mono_start, "mono_end": self.mono_end,
        }


class _RemoteParent:
    """Context carrier for a span started in ANOTHER process.

    Not a recordable span: it exists only so ``span()`` parents its
    children under the real remote (trace_id, span_id). The old
    implementation faked this with a ``Span(name="<remote-parent>",
    parent_id=None)``, which could leak a bogus root into exports and
    broke assembled trees at every process hop.
    """

    __slots__ = ("trace_id", "span_id", "deferred")

    def __init__(self, trace_id: str, span_id: str,
                 deferred: bool = False):
        self.trace_id = trace_id
        self.span_id = span_id
        self.deferred = deferred


class Tracer:
    def __init__(self, maxlen: int = 100_000):
        self.enabled = False
        self._spans: deque = deque(maxlen=maxlen)
        self._lock = threading.Lock()
        # Spans silently lost to ring overflow (or to a bounded
        # requeue after a failed export) — surfaced as the
        # ``ray_tpu_tracing_spans_dropped`` plane self-metric so a
        # span-heavy workload can see its trace is incomplete.
        self.spans_dropped = 0
        # Probabilistic head sampling: roots rolled out by the rate
        # are still recorded but carry DEFERRED_ATTR; the head's
        # TraceStore keeps them only on error or tail latency.
        try:
            self.sample_rate = float(
                os.environ.get("RAY_TPU_TRACE_SAMPLE_RATE", "1.0"))
        except ValueError:
            self.sample_rate = 1.0

    def _append_locked(self, span: "Span") -> None:
        if (self._spans.maxlen is not None
                and len(self._spans) >= self._spans.maxlen):
            self.spans_dropped += 1
        self._spans.append(span)

    # -- lifecycle --

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    # -- span API --

    @contextlib.contextmanager
    def span(self, name: str, attributes: dict | None = None):
        if not self.enabled:
            yield None
            return
        parent = _current.get()
        attrs = dict(attributes or {})
        if parent is None:
            # New root: roll the sampling dice once per trace. The
            # span is still recorded either way — a deferred root lets
            # the head apply error/tail keep rules before dropping.
            if self.sample_rate < 1.0 and random.random() >= self.sample_rate:
                attrs[DEFERRED_ATTR] = True
        s = Span(
            name=name,
            trace_id=(parent.trace_id if parent
                      else uuid.uuid4().hex[:16]),
            span_id=uuid.uuid4().hex[:16],
            parent_id=parent.span_id if parent else None,
            start=time.time(),
            attributes=attrs,
            process=f"pid:{os.getpid()}",
        )
        token = _current.set(s)
        try:
            yield s
        except BaseException as e:
            # Error tagging: sample-on-error and verdict joins need to
            # see failures in the tree, and the span must still close.
            s.attributes.setdefault("error", type(e).__name__)
            raise
        finally:
            _current.reset(token)
            s.end = time.time()
            with self._lock:
                self._append_locked(s)

    def current_context(self) -> tuple[str, str] | None:
        """(trace_id, span_id) to inject into an outgoing task."""
        s = _current.get()
        return (s.trace_id, s.span_id) if s else None

    @contextlib.contextmanager
    def remote_parent(self, ctx: tuple[str, str] | None):
        """Re-hydrate a propagated context in the executing worker.

        Installs a :class:`_RemoteParent` carrier so spans opened here
        parent under the REAL remote span id — the tree joins cleanly
        across the process hop instead of breaking at a fake
        ``<remote-parent>`` root.
        """
        if ctx is None or not self.enabled:
            yield
            return
        trace_id, span_id = ctx[0], ctx[1]
        token = _current.set(_RemoteParent(trace_id, span_id))
        try:
            yield
        finally:
            _current.reset(token)

    # -- collection / export --

    def add_spans(self, span_dicts: list[dict]) -> None:
        with self._lock:
            for d in span_dicts:
                self._append_locked(Span(**d))

    def drain_dicts(self) -> list[dict]:
        """Take all finished spans (worker-side flush)."""
        with self._lock:
            out = [s.to_dict() for s in self._spans]
            self._spans.clear()
        return out

    def requeue_dicts(self, span_dicts: list[dict]) -> int:
        """Put drained spans BACK after a failed export so they ride
        the next flush instead of vanishing (reference: exporter
        retry queues). Bounded by the ring's free space — the oldest
        re-queued spans are dropped (and counted) first so live
        recording is never displaced. Returns how many were kept."""
        if not span_dicts:
            return 0
        with self._lock:
            if self._spans.maxlen is None:
                space = len(span_dicts)
            else:
                space = self._spans.maxlen - len(self._spans)
            keep = span_dicts[-space:] if space > 0 else []
            self.spans_dropped += len(span_dicts) - len(keep)
            for d in reversed(keep):
                try:
                    self._spans.appendleft(Span(**d))
                except TypeError:
                    self.spans_dropped += 1
        return len(keep)

    def get_spans(self, trace_id: str | None = None) -> list[Span]:
        with self._lock:
            spans = list(self._spans)
        if trace_id is not None:
            spans = [s for s in spans if s.trace_id == trace_id]
        return spans

    def chrome_trace(self) -> list[dict]:
        out = []
        for s in self.get_spans():
            out.append({
                "name": s.name, "ph": "X",
                "pid": s.process or "driver", "tid": s.trace_id,
                "ts": s.start * 1e6, "dur": (s.end - s.start) * 1e6,
                "args": s.attributes,
            })
        return out


_tracer = Tracer()


def get_tracer() -> Tracer:
    return _tracer


def enable() -> None:
    """Turn on tracing in this process (driver: call before submitting
    work; propagation to workers is automatic)."""
    _tracer.enable()


def disable() -> None:
    _tracer.disable()


def set_sample_rate(rate: float) -> None:
    """Probability a new trace root is head-sampled (0..1). Roots
    rolled out are still recorded but marked deferred; the head keeps
    them only on error or tail latency."""
    _tracer.sample_rate = max(0.0, min(1.0, float(rate)))


def span(name: str, attributes: dict | None = None):
    return _tracer.span(name, attributes)


def get_spans(trace_id: str | None = None):
    return _tracer.get_spans(trace_id)


def chrome_trace() -> list[dict]:
    return _tracer.chrome_trace()


# -- the train path ---------------------------------------------------

_TraceAnnotation = None


def annotation(name: str):
    """A ``jax.profiler.TraceAnnotation``: a span that exists only in
    a running device profile (one atomic read otherwise). For what
    happens every step — nothing is recorded on the Python side."""
    global _TraceAnnotation
    if _TraceAnnotation is None:    # jax is not imported at start-up
        from jax.profiler import TraceAnnotation
        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name)


# ``time.monotonic()`` at the first line of this process's entry module,
# where that module told it (``core/worker_entry.py``): the start of a
# worker's ``train.worker.process`` span.
process_start: float | None = None

# The train-path span open in this thread: what the next one nests
# under (``_current`` may hold a task's or a request's span instead).
_train_current: contextvars.ContextVar = contextvars.ContextVar(
    "ray_tpu_train_span", default=None)


def _new_train_span(name: str, attributes: dict | None,
                    parent: tuple[str, str] | None) -> Span:
    """A span under the train-path span open in this thread, else
    under ``parent`` (trace id, span id), else under the thread's
    current span of the control plane, else the root of a new trace.
    So a task's span around the call (tracing enabled) never takes a
    worker's spans out of their fit's trace."""
    cur = _train_current.get() or (None if parent else _current.get())
    if cur is not None:
        parent = (cur.trace_id, cur.span_id)
    trace_id, parent_id = parent or (uuid.uuid4().hex[:16], None)
    return Span(name=name, trace_id=trace_id,
                span_id=uuid.uuid4().hex[:16], parent_id=parent_id,
                start=0.0, attributes=dict(attributes or {}),
                process=f"pid:{os.getpid()}")


def _keep(span: Span, sink: list | None) -> None:
    if sink is not None:
        sink.append(span)
    else:
        with _tracer._lock:
            _tracer._append_locked(span)


@contextlib.contextmanager
def train_span(name: str, attributes: dict | None = None, *,
               parent: tuple[str, str] | None = None,
               sink: list | None = None):
    """Record one phase of the train path, whether or not tracing is
    enabled: both clocks, and an annotation for a running profile.
    The finished span goes to ``sink`` (a worker's spans ride its last
    poll reply) or, without one, into the process tracer's ring."""
    s = _new_train_span(name, attributes, parent)
    tokens = _current.set(s), _train_current.set(s)
    s.start, s.mono_start = time.time(), time.monotonic()
    try:
        with annotation(name):
            yield s
    except BaseException as e:
        s.attributes.setdefault("error", type(e).__name__)
        raise
    finally:
        s.end, s.mono_end = time.time(), time.monotonic()
        _current.reset(tokens[0])
        _train_current.reset(tokens[1])
        _keep(s, sink)


def record_train_span(name: str, mono_start: float, mono_end: float,
                      attributes: dict | None = None, *,
                      parent: tuple[str, str] | None = None,
                      sink: list | None = None) -> Span:
    """A train-path span whose ends were taken elsewhere, on
    ``time.monotonic()`` (a phase that began before its trace was
    known; a compile reported by its duration)."""
    s = _new_train_span(name, attributes, parent)
    to_wall = time.time() - time.monotonic()
    s.mono_start, s.mono_end = mono_start, mono_end
    s.start, s.end = mono_start + to_wall, mono_end + to_wall
    _keep(s, sink)
    return s


# What a process records of itself before any fit, each the root of a
# trace of its own.
PROCESS_SPANS = ("core.init", "native.build")


def process_spans(before: float) -> list[Span]:
    """What this process did before a fit began: of the ring's
    ``PROCESS_SPANS`` that ended by ``before`` (``time.monotonic()``),
    the newest of each name. A fit writes them beside its own spans, so
    that one file holds a cold start from ``init()`` on."""
    newest: dict[str, Span] = {}
    for s in _tracer.get_spans():
        if (s.name in PROCESS_SPANS and s.parent_id is None
                and 0.0 < s.mono_end <= before):
            newest[s.name] = s      # the ring is in order of arrival
    return sorted(newest.values(), key=lambda s: s.mono_start)


# What code that runs while jax traces a program has to say about
# the program it is building, by thread: ``train/step.py`` clears it
# when the thread's outermost trace begins and puts it on that trace's
# ``train.compile`` span when it ends.
_trace_notes = threading.local()


def note_trace(**attributes) -> None:
    """Called at trace time (a decision taken from shapes and the
    mesh): attributes for the ``train.compile`` span of the trace in
    progress in this thread. Costs a dict update; kept by nobody
    where no train step listens."""
    vars(_trace_notes).setdefault("notes", {}).update(attributes)


def count_trace(**counts) -> None:
    """As ``note_trace``, for what a trace does several times: each
    number is added to what the trace in progress has noted under that
    key (the custom calls of a head that a step meets twice)."""
    notes = vars(_trace_notes).setdefault("notes", {})
    for key, n in counts.items():
        notes[key] = notes.get(key, 0) + n


def take_trace_notes() -> dict:
    """This thread's notes, handed over once."""
    return vars(_trace_notes).pop("notes", {})


def covered_s(spans, start: float, end: float) -> float:
    """Seconds of ``[start, end]`` on the monotonic clock that the
    spans cover, overlaps counted once."""
    covered, at = 0.0, start
    for c in sorted(spans, key=lambda c: c.mono_start):
        a, b = max(c.mono_start, at), min(c.mono_end, end)
        if b > a:
            covered, at = covered + (b - a), b
    return covered


def self_seconds(spans: list[Span]) -> dict[str, float]:
    """span id -> the span's own seconds on the monotonic clock: its
    time less what its direct children cover, and less what siblings
    of its own process that began after it cover of it (all as one
    union, clipped to the span). Where two siblings overlap (a cache
    load inside its ``backend`` compile; the first batch, whose
    consumer waits while the producer's thread compiles) the seconds
    under both are the later one's alone, so a process's self times
    sum to no more than its wall."""
    children: dict[str | None, list[Span]] = {}
    for s in spans:
        children.setdefault(s.parent_id, []).append(s)
    place = {}          # span id -> where it stands among its siblings
    for group in children.values():
        group.sort(key=lambda c: c.mono_start)
        place.update((c.span_id, i) for i, c in enumerate(group))
    out = {}
    for s in spans:
        over = list(children.get(s.span_id, ()))
        if s.parent_id is not None:
            for c in children[s.parent_id][place[s.span_id] + 1:]:
                if c.mono_start >= s.mono_end:
                    break
                if c.process == s.process:
                    over.append(c)
        out[s.span_id] = (s.mono_end - s.mono_start) - covered_s(
            over, s.mono_start, s.mono_end)
    return out


def chrome_events(spans: list[Span]) -> list[dict]:
    """Spans of the train path in chrome-trace form (the surface of
    ``ray_tpu.timeline()``), timed on the monotonic clock so that the
    processes of one host line up."""
    self_s = self_seconds(spans)
    return [{"name": s.name, "ph": "X", "pid": s.process or "driver",
             "tid": s.trace_id, "ts": s.mono_start * 1e6,
             "dur": (s.mono_end - s.mono_start) * 1e6,
             "args": {**s.attributes, "span_id": s.span_id,
                      "parent_id": s.parent_id, "unix_start": s.start,
                      "self_s": self_s[s.span_id]}}
            for s in spans]


@contextlib.contextmanager
def profile_device(logdir: str = "/tmp/ray_tpu_profile"):
    """Capture an XLA device profile around a code region
    (TensorBoard-compatible; the TPU answer to the reference's nsight
    runtime-env plugin)."""
    import jax
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()
