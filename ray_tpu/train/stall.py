"""``train.stall``: the step that straggles, put down to a cause.

A watch thread runs beside the user's loop for as long as a fit's
``train.worker.loop`` is open (``worker_group.py`` starts and stops
it; nothing outside a fit does). ``session.report()`` appends each
report-to-report interval to a deque and does nothing else; the thread
reads them, holds each against the median of those before it
(:func:`stall_limit`), and records a stalled one as a ``train.stall``
span under ``train.worker.loop`` with the evidence of what the
interval went to (docs/observability.md, "Reading a straggler"):

- its own **beats**, ten a second: one that is late while the process
  used no CPU says that no thread of this process ran, so the process
  or the machine was stopped, not the loop (:func:`frozen_between`);
- what the kernel counts, sampled once a second and again when a
  stall is found (:func:`kernel_sample`): suspension, steal, the loop
  thread's run-queue wait, pressure, major faults, involuntary
  switches;
- what the program counts: seconds in ``gc``, in ``train.compile``
  spans, in the prefetchers' queues, in the previous ``report()``;
- where the loop's thread stood while the interval was open and
  already too long (:func:`where_of`).

The same thread takes the fit's few samples of device memory, which
``stop()`` hands to ``train.worker.loop`` with the stalls' totals
(docs/observability.md, "Reading a step that does not fit"): at the
first beat after the loop's first report and after its eighth
(``HBM_AT_REPORTS``), and once at the end. The loop's thread and
``session.report()`` run no line for it.

The rule, the cause and the readers of ``/proc`` are pure functions of
numbers handed in: ``tests/test_train_stall.py`` holds them by table,
``tests/test_train_memory.py`` the samples.
"""

from __future__ import annotations

import gc
import logging
import os
import statistics
import sys
import threading
import time
import traceback
from collections import deque
from typing import Callable, Iterable, Sequence

from ray_tpu.util import tracing

try:
    import resource
except ImportError:     # not Unix: no faults, no switches
    resource = None

PERIOD_S = 0.1          # between beats
KERNEL_EVERY_S = 1.0    # between samples of the kernel's counters
START_INTERVALS = 8     # intervals known before one is judged
MEDIAN_OVER = 64        # the median is of this many intervals before it
FLOOR_S = 0.1           # a stall exceeds the median by this and by half
MAX_SPANS = 256         # train.stall spans (and warnings) kept a loop
SILENT_S = 60.0         # an open interval this long (and ten medians)
SILENT_MEDIANS = 10     # gets one warning with the loop's stack
STOP_WAIT_S = 5.0       # ``stop()`` waits this long for the thread
# Device memory is sampled once this many reports have come: at the first
# the step's executable is loaded and its arguments are live, by the
# eighth donated outputs have come back and every relayout has compiled.
HBM_AT_REPORTS = (1, 8)
# The allocator's keys that are read, where the backend gives them.
HBM_KEYS = ("bytes_limit", "bytes_in_use", "peak_bytes_in_use",
            "bytes_reserved", "peak_bytes_reserved", "largest_alloc_size")

# The program's own causes, in the order a tie is settled.
NAMED = (("gc", "gc_s"), ("compile", "compile_s"),
         ("input", "input_wait_s"), ("report", "report_s"))

log = logging.getLogger("ray_tpu.train")

_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _is_jax(filename: str) -> bool:
    return f"{os.sep}jax{os.sep}" in filename or (
        f"{os.sep}jaxlib{os.sep}" in filename)


# -- the rule -------------------------------------------------------------

def stall_limit(before: Sequence[float]) -> tuple[float, float] | None:
    """(median, limit) of the intervals before one: the interval is a
    stall when it is over ``limit``, the median plus its half and plus
    ``FLOOR_S``, whichever is more. None until ``START_INTERVALS`` are
    known."""
    if len(before) < START_INTERVALS:
        return None
    median = statistics.median(list(before)[-MEDIAN_OVER:])
    return median, max(1.5 * median, median + FLOOR_S)


def frozen_between(late: Iterable[tuple[float, float, float]],
                   start: float, end: float) -> float:
    """Seconds of ``[start, end]`` in which no thread of the process
    ran. ``late``: (when a beat was due, when it came, the CPU seconds
    the process used from the beat before) of each beat that came more
    than a period late. A late beat under which the process was busy
    for half the lateness or more was a thread that held the
    interpreter lock (a collection, a C call), not a freeze."""
    frozen = 0.0
    for due, woke, cpu_s in late:
        lo, hi = max(due, start), min(woke, end)
        if hi > lo and cpu_s < (woke - due) / 2:
            frozen += hi - lo
    return frozen


def cause_of(evidence: dict, blocked_in: str | None = None) -> str:
    """``frozen`` where the beats were late for half of the excess or
    more; else the largest of the program's own counters where it
    covers half (a tie goes to the first of ``NAMED``); else where the
    loop's thread was seen: ``device`` (waiting inside jax) or ``loop``
    (the user's code); else ``unnamed``."""
    half = evidence["excess_s"] / 2
    if evidence["frozen_s"] >= half:
        return "frozen"
    name, key = max(NAMED, key=lambda nk: evidence.get(nk[1], 0.0))
    if evidence.get(key, 0.0) >= half:
        return name
    return {"jax": "device", "user": "loop"}.get(blocked_in, "unnamed")


def describe(a: dict) -> str:
    """The worker's warning for one ``train.stall``."""
    head = (f"train: step {a['step']} took {a['interval_s']:.2f} s "
            f"against a median of {a['median_s']:.3g}: ")
    cause = a["cause"]
    if cause == "frozen":
        whose = ", ".join(
            f"{key[:-2]} {abs(a[key]):.2f}"
            for key in ("sched_wait_s", "steal_s", "boot_gap_s") if key in a)
        return head + f"frozen {a['frozen_s']:.2f} s" + (
            f" ({whose})" if whose else "")
    for name, key in NAMED:
        if cause == name:
            return head + f"{name} {a[key]:.2f} s"
    if cause == "unnamed":
        parts = ", ".join(f"{n} {a[k]:.2f}" for n, k in
                          (("frozen", "frozen_s"), *NAMED))
        return head + f"unnamed ({parts})"
    what = (f"the host waited inside jax ({a.get('jax_frame', '?')})"
            if cause == "device" else "in the loop's own code")
    return head + f"{what}, at {a.get('where', '?')}"


# -- what the kernel counts -----------------------------------------------

def _steal(text: str) -> float:
    # cpu  user nice system idle iowait irq softirq steal ...
    return int(text.split(None, 9)[8]) / os.sysconf("SC_CLK_TCK")


def _run_queue_wait(text: str) -> float:
    return int(text.split()[1]) / 1e9       # ns on the cpu, ns waiting


def _pressure(text: str) -> float:
    # some avg10=0.00 avg60=0.00 avg300=0.00 total=<us>
    return int(text.split("total=", 1)[1].split()[0]) / 1e6


def kernel_sample(tid: int, proc: str = "/proc") -> dict:
    """The kernel's cumulative counters, so that two samples either
    side of an interval give its share. A file that is not there (no
    PSI, not Linux) leaves its key out; nothing raises."""
    got: dict = {}
    boottime = getattr(time, "CLOCK_BOOTTIME", None)
    if boottime is not None:    # a suspended machine: boottime runs on
        got["boot_gap_s"] = (time.clock_gettime(boottime)
                             - time.clock_gettime(time.CLOCK_MONOTONIC))
    if resource is not None:
        usage = resource.getrusage(resource.RUSAGE_SELF)
        got.update(major_faults=usage.ru_majflt,
                   involuntary_switches=usage.ru_nivcsw)
    for key, path, parse in (
            ("steal_s", "stat", _steal),
            ("sched_wait_s", f"self/task/{tid}/schedstat", _run_queue_wait),
            ("psi_cpu_s", "pressure/cpu", _pressure),
            ("psi_io_s", "pressure/io", _pressure),
            ("psi_memory_s", "pressure/memory", _pressure)):
        try:
            with open(os.path.join(proc, path)) as f:
                got[key] = parse(f.readline())
        except (OSError, ValueError, IndexError):
            pass
    return got


# -- what the device's allocator counts -----------------------------------

def hbm_sample(devices) -> list[dict]:
    """``memory_stats()`` of each of the process's devices, cut to
    ``HBM_KEYS``, with the device's ``id``. A backend that keeps no such
    counters (the CPU's ``memory_stats()`` is None) yields nothing for
    that device, and a key it does not give is left out: no made-up 0.
    Nothing raises, and nothing here opens a backend: the devices are
    those ``worker_group.py::_open_backend`` got."""
    out = []
    for device in devices:
        try:
            stats = device.memory_stats()
        except Exception:  # noqa: BLE001 — a backend without the call
            stats = None
        if stats:
            out.append({"id": device.id,
                        **{k: stats[k] for k in HBM_KEYS if k in stats}})
    return out


def hbm_held(sample: dict) -> int | None:
    """What one device holds for the job: the bytes in use plus the
    bytes the allocator has reserved (on a TPU, the loaded programs'
    temporaries, which ``bytes_in_use`` and its peak do not count; the
    bytes in use alone where the backend gives no ``bytes_reserved``).
    None where it does not give ``bytes_in_use``: no made-up 0."""
    if "bytes_in_use" not in sample:
        return None
    return sample["bytes_in_use"] + sample.get("bytes_reserved", 0)


# -- where the loop was ---------------------------------------------------

def where_of(frame) -> dict:
    """Where the loop's thread stands, from its innermost frame.
    ``where``: the innermost frame outside jax, jaxlib and ray_tpu as
    ``file:function:line`` (None where there is none). ``blocked_in``:
    ``jax`` where the innermost frame is jax's (the host waits on the
    device; ``jax_frame`` then names that frame, ``array.py:_value``
    for a value read back, so that jax's own work, a profile's start,
    tells itself apart), ``input`` under the prefetcher's ``__next__``,
    ``report`` under the session's ``report``, else ``user``."""
    where, blocked_in, told = None, None, {}
    if frame is not None and _is_jax(frame.f_code.co_filename):
        blocked_in = "jax"
        told["jax_frame"] = (f"{os.path.basename(frame.f_code.co_filename)}"
                             f":{frame.f_code.co_name}")
    while frame is not None:
        code = frame.f_code
        ours = code.co_filename.startswith(_PACKAGE + os.sep)
        if blocked_in is None and ours:
            if code.co_name == "__next__" and code.co_filename.endswith(
                    "prefetch.py"):
                blocked_in = "input"
            elif code.co_name == "report" and code.co_filename.endswith(
                    "session.py"):
                blocked_in = "report"
        if where is None and not ours and not _is_jax(code.co_filename):
            where = f"{code.co_filename}:{code.co_name}:{frame.f_lineno}"
        frame = frame.f_back
    return {"where": where, "blocked_in": blocked_in or "user", **told}


# -- the watch ------------------------------------------------------------

class StallWatch:
    """The thread beside one worker's loop. Made by the loop's own
    thread where ``train.worker.loop`` begins (it hands over its ids),
    stopped by it where that span ends; ``stop()`` gives the totals
    for that span: ``stalls``, ``stalled_s``, ``stall_frozen_s`` of the
    stalled intervals, and ``frozen_s``, every second of the loop's
    life in which no thread of the process ran (before the first
    report too: a backend's opening that stops the machine); and,
    where the session has devices whose allocator counts, the ``hbm_*``
    of :meth:`_hbm_totals`."""

    def __init__(self, session, parent: tuple[str, str],
                 input_totals: Callable[[], dict]):
        self._session = session
        self._target = {"parent": parent, "sink": session.spans}
        self._input_totals = input_totals
        self._ident = threading.get_ident()
        self._tid = threading.get_native_id()
        self._halt = threading.Event()
        self._history: deque = deque(maxlen=MEDIAN_OVER)
        self._late: deque = deque(maxlen=64)
        self._gc_s, self._gc_t0 = 0.0, 0.0
        self._kernel = kernel_sample(self._tid)
        # what was counted when the newest report was read: the base
        # of the next interval's evidence
        self._base = (self._counters(time.process_time()), self._kernel)
        # the open interval seen past its limit: {"t", "annotation",
        # "where", "blocked_in", "jax_frame", "warned"}
        self._open: dict | None = None
        # ``frozen_s``: over the loop's whole life, set-up included
        self.totals = {"stalls": 0, "stalled_s": 0.0, "stall_frozen_s": 0.0,
                       "frozen_s": 0.0}
        self._reports = 0       # reports read so far, after the first
        self._hbm_at = list(HBM_AT_REPORTS)     # sample points to come
        self._hbm_samples = 0
        self._hbm_held: dict[int, int] = {}     # device id -> most held
        self._hbm_last: list[dict] = []         # the newest sample
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="train_stall_watch")
        self._thread.start()

    def stop(self) -> dict:
        self._halt.set()
        self._thread.join(timeout=STOP_WAIT_S)
        gc.callbacks.remove(self._on_gc)
        if not self._thread.is_alive():     # what came since its last beat
            self._read_reports(time.process_time())
            self._close_open()
            # not beside a thread that may be held inside a sample of
            # its own (a runtime that does not answer): the loop's error
            # is then told without the end's sample
            self._sample_hbm()
        return {**self.totals, **self._hbm_totals()}

    def _on_gc(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.monotonic()
        else:
            self._gc_s += time.monotonic() - self._gc_t0

    def _counters(self, cpu_s: float) -> dict:
        return {"gc_s": self._gc_s, "cpu_s": cpu_s,
                "input_wait_s": self._input_totals().get(
                    "input.stall_s", 0.0)}

    def _run(self) -> None:
        try:
            beat, cpu = time.monotonic(), time.process_time()
            sampled = beat
            while not self._halt.wait(PERIOD_S):
                now, cpu_now = time.monotonic(), time.process_time()
                due = beat + PERIOD_S
                on_time = now - due <= PERIOD_S
                if not on_time:
                    late = (due, now, cpu_now - cpu)
                    self._late.append(late)
                    self.totals["frozen_s"] += frozen_between(
                        (late,), due, now)
                beat, cpu = now, cpu_now
                self._read_reports(cpu_now)
                self._sample_hbm_at_reports()
                self._look_at_open(now, on_time)
                if now - sampled >= KERNEL_EVERY_S:
                    self._kernel, sampled = kernel_sample(self._tid), now
        except Exception:  # noqa: BLE001 — the loop runs on unwatched
            log.exception("train: the stall watch stopped")
        finally:
            self._end_annotation(self._open)    # in the thread that began it

    def _sample_hbm(self) -> None:
        """One sample of the devices ``_open_backend`` left on the
        session (none: no call is made), kept as the newest and in each
        device's most held. A device that does not say its bytes in use
        is left out of it."""
        sample = hbm_sample(self._session.devices)
        held = {s["id"]: h for s in sample if (h := hbm_held(s)) is not None}
        if held:
            self._hbm_samples += 1
            self._hbm_last = sample
            for i, h in held.items():
                self._hbm_held[i] = max(self._hbm_held.get(i, 0), h)

    def _sample_hbm_at_reports(self) -> None:
        # the first report appends nothing to ``session.reports``
        reports = self._reports + (self._session.t_first_report is not None)
        if self._hbm_at and reports >= self._hbm_at[0]:
            self._hbm_at = [n for n in self._hbm_at if n > reports]
            self._sample_hbm()

    def _hbm_totals(self) -> dict:
        """For ``train.worker.loop``: ``hbm_held_bytes``, the most any
        device held at any sample; that device's high-water marks at the
        last sample, each where the allocator gives the key;
        ``hbm_held_by_device``, the most each held by its id, where there
        is more than one; ``hbm_samples``. Nothing where no sample found
        a counter."""
        held = dict(sorted(self._hbm_held.items()))
        if not held:
            return {}
        fullest = max(held, key=held.get)
        last = next((s for s in self._hbm_last if s["id"] == fullest), {})
        told = {"hbm_held_bytes": held[fullest],
                **{name: last[key] for name, key in (
                    ("hbm_peak_in_use_bytes", "peak_bytes_in_use"),
                    ("hbm_peak_reserved_bytes", "peak_bytes_reserved"),
                    ("hbm_largest_alloc_bytes", "largest_alloc_size"))
                   if key in last},
                "hbm_samples": self._hbm_samples}
        if len(held) > 1:    # keys as a trace's JSON keeps them
            told["hbm_held_by_device"] = {str(i): b for i, b in held.items()}
        return told

    def _read_reports(self, cpu_s: float) -> None:
        reports = self._session.reports
        if not reports:
            return
        counters = self._counters(cpu_s)
        while reports:
            step, t, interval, report_s = reports.popleft()
            self._reports += 1
            seen = self._close_open()
            # no median is taken for an interval that cannot be a stall
            held = stall_limit(self._history) if interval > FLOOR_S else None
            self._history.append(interval)
            if held is not None and interval > held[1]:
                self._record(step, t, interval, held[0], report_s,
                             counters, seen)
            self._base = (counters, self._kernel)

    def _record(self, step: int, t: float, interval: float, median: float,
                report_s: float, counters: dict, seen: dict | None) -> None:
        start, excess = t - interval, interval - median
        frozen = frozen_between(self._late, start, t)
        self.totals["stalls"] += 1
        self.totals["stalled_s"] += excess
        self.totals["stall_frozen_s"] += min(frozen, excess)
        if self.totals["stalls"] > MAX_SPANS:
            return      # the totals go on counting
        was, kernel_was = self._base
        self._kernel = kernel = kernel_sample(self._tid)
        # a cache load lies inside its backend span
        compiles = [s for s in list(self._session.spans)
                    if s.name == "train.compile"
                    and s.attributes.get("kind") != "cache_load"]
        a = {"step": step, "interval_s": interval, "median_s": median,
             "excess_s": excess, "frozen_s": frozen,
             **{k: kernel[k] - kernel_was[k]
                for k in kernel if k in kernel_was},
             **{k: counters[k] - was[k] for k in counters},
             "compile_s": tracing.covered_s(compiles, start, t),
             "report_s": report_s}
        if seen:    # where the loop stood, if a beat saw it
            a.update({k: seen[k] for k in ("where", "blocked_in", "jax_frame")
                      if k in seen})
        a["cause"] = cause_of(a, a.get("blocked_in"))
        tracing.record_train_span("train.stall", start, t, a, **self._target)
        log.warning(describe(a) + (
            f" (the loop's stall {MAX_SPANS}: later ones are counted on "
            f"train.worker.loop and not kept)"
            if self.totals["stalls"] == MAX_SPANS else ""))

    def _look_at_open(self, now: float, on_time: bool) -> None:
        """The interval still open: once it is past the limit, an
        annotation for a running profile until the report that ends it,
        the loop's stack read once (on a beat that was itself on time),
        and one warning if it goes on for ``SILENT_S``."""
        last = self._session.last_report_ts
        if last is None or now - last <= FLOOR_S:
            return
        held = stall_limit(self._history)
        if held is None or now - last <= held[1]:
            return
        if self._open is None or self._open["t"] != last:
            self._close_open()
            annotation = tracing.annotation("train.stall")
            annotation.__enter__()
            self._open = {"t": last, "annotation": annotation}
        seen = self._open
        if on_time and "blocked_in" not in seen:
            frame = sys._current_frames().get(self._ident)
            seen.update(where_of(frame))
        if ("warned" not in seen and now - last > SILENT_S
                and now - last > SILENT_MEDIANS * held[0]):
            seen["warned"] = True
            frame = sys._current_frames().get(self._ident)
            log.warning(
                "train: no report for %.0f s against a median of %.3g; "
                "the loop's thread is at\n%s", now - last, held[0],
                "".join(traceback.format_stack(frame)) if frame else "?")

    def _close_open(self) -> dict | None:
        seen, self._open = self._open, None
        self._end_annotation(seen)
        return seen

    @staticmethod
    def _end_annotation(seen: dict | None) -> None:
        annotation = seen.pop("annotation", None) if seen else None
        if annotation is not None:
            annotation.__exit__(None, None, None)
