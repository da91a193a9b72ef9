"""``train.stall`` (``ray_tpu/train/stall.py``; docs/observability.md,
"Reading a straggler"): the rule, the cause, the kernel's counters and the
loop's stack as pure functions over tables of recorded numbers (nothing is
slept for); the watch over a hand-fed session; the benchmark's three
readers over hand-made spans and stamps; and two real tiny fits whose
loop stalls for a second, once in its own code and once because its
process was stopped.
"""

import os
import sys
import types

import pytest

from ray_tpu.train import session as train_session
from ray_tpu.train import stall
from ray_tpu.util import tracing

# -- the rule -------------------------------------------------------------

STEADY = [0.36] * 8


@pytest.mark.parametrize("before, interval, median", [
    # eight intervals are known before one is judged
    ([0.36] * 7, 5.0, None),
    (STEADY, 5.0, 0.36),
    # over the median by half and by 0.1 s: at 0.36 the half governs
    (STEADY, 0.53, None), (STEADY, 0.55, 0.36),
    # at 0.05 the floor does (0.15), at 0.2 the two meet (0.3)
    ([0.05] * 8, 0.14, None), ([0.05] * 8, 0.16, 0.05),
    ([0.2] * 8, 0.3, None), ([0.2] * 8, 0.31, 0.2),
    # a level shift of 2.3% (904.9 -> 925.9 ms) is no stall
    ([0.9049] * 64, 0.9259, None),
    # the median is of the last 64: a slow start has left it
    ([3.0] * 40 + [0.3] * 64, 0.6, 0.3),
    ([3.0] * 40 + [0.3] * 30, 0.6, None),
    # one stall in ten (a checkpoint) does not move the median
    (([0.1] * 9 + [2.0]) * 6, 2.0, 0.1),
])
def test_an_interval_is_held_against_the_median_of_those_before(
        before, interval, median):
    held = stall.stall_limit(before)
    found = held is not None and interval > held[1]
    assert found == (median is not None)
    if found:
        assert held[0] == pytest.approx(median)


@pytest.mark.parametrize("late, frozen_s", [
    ([], 0.0),
    # a beat due at 10.1 that came at 12.5 with no CPU used: 2.4 s
    ([(10.1, 12.5, 0.01)], 2.4),
    # cut to the interval at both ends
    ([(9.0, 10.5, 0.0), (12.8, 14.0, 0.0)], 0.5 + 0.2),
    # outside it
    ([(5.0, 7.0, 0.0), (13.5, 15.0, 0.0)], 0.0),
    # a late beat under which the process was busy: a thread held the
    # interpreter lock, nothing was frozen
    ([(10.1, 12.5, 2.3)], 0.0), ([(10.1, 12.5, 1.3)], 0.0),
    ([(10.1, 12.5, 1.1)], 2.4),
])
def test_frozen_seconds_are_the_late_beats_of_an_idle_process(late, frozen_s):
    assert stall.frozen_between(late, 10.0, 13.0) == pytest.approx(frozen_s)


def _evidence(**seconds):
    return {"excess_s": 2.0, "frozen_s": 0.0, "gc_s": 0.0, "compile_s": 0.0,
            "input_wait_s": 0.0, "report_s": 0.0, **seconds}


@pytest.mark.parametrize("evidence, blocked_in, cause", [
    (_evidence(frozen_s=2.4), None, "frozen"),
    (_evidence(frozen_s=1.0), "user", "frozen"),        # half is enough
    (_evidence(frozen_s=0.99), None, "unnamed"),
    # the beats win over the program's counters
    (_evidence(frozen_s=1.2, gc_s=1.9), "jax", "frozen"),
    (_evidence(gc_s=1.5), None, "gc"),
    (_evidence(compile_s=1.9, gc_s=0.3), "user", "compile"),
    (_evidence(input_wait_s=1.0), "input", "input"),
    (_evidence(report_s=1.8), "report", "report"),
    # the largest that covers half; a tie goes by the order gc,
    # compile, input, report
    (_evidence(report_s=1.1, input_wait_s=1.2), None, "input"),
    (_evidence(gc_s=1.0, compile_s=1.0), None, "gc"),
    (_evidence(input_wait_s=1.5, report_s=1.5), None, "input"),
    # none covers half: where the loop's thread was seen
    (_evidence(gc_s=0.4, input_wait_s=0.9), "jax", "device"),
    (_evidence(), "user", "loop"),
    # seen under the prefetcher or report() with counters that do not
    # agree, or not seen at all
    (_evidence(input_wait_s=0.3), "input", "unnamed"),
    (_evidence(), "report", "unnamed"),
    (_evidence(), None, "unnamed"),
])
def test_each_cause_from_its_evidence(evidence, blocked_in, cause):
    assert stall.cause_of(evidence, blocked_in) == cause


def test_the_warning_says_whose_freeze_it_was():
    a = {"step": 412, "interval_s": 2.81, "median_s": 0.362,
         "excess_s": 2.448, "frozen_s": 2.44, "sched_wait_s": 0.02,
         "steal_s": 2.39, "boot_gap_s": -4e-7, "gc_s": 0.0, "compile_s": 0.0,
         "input_wait_s": 0.01, "report_s": 0.0, "cause": "frozen"}
    assert stall.describe(a) == (
        "train: step 412 took 2.81 s against a median of 0.362: frozen "
        "2.44 s (sched_wait 0.02, steal 2.39, boot_gap 0.00)")
    # a kernel that gives none of the three
    bare = {k: v for k, v in a.items()
            if k not in ("sched_wait_s", "steal_s", "boot_gap_s")}
    assert stall.describe(bare).endswith(": frozen 2.44 s")
    assert stall.describe({**a, "cause": "report", "report_s": 2.4}).endswith(
        ": report 2.40 s")
    assert stall.describe(
        {**a, "cause": "loop", "where": "loop.py:_measure:204"}).endswith(
        ": in the loop's own code, at loop.py:_measure:204")
    assert stall.describe(
        {**a, "cause": "device", "where": "x.py:f:1",
         "jax_frame": "array.py:_value"}).endswith(
        "the host waited inside jax (array.py:_value), at x.py:f:1")
    assert stall.describe({**a, "cause": "unnamed"}).endswith(
        "unnamed (frozen 2.44, gc 0.00, compile 0.00, input 0.01, "
        "report 0.00)")


# -- what the kernel counts -----------------------------------------------

def _proc(tmp_path, files: dict) -> str:
    for path, text in files.items():
        full = tmp_path / path
        full.parent.mkdir(parents=True, exist_ok=True)
        full.write_text(text)
    return str(tmp_path)


PROC = {
    "stat": "cpu  100 0 50 9000 7 0 3 250 0 0\ncpu0 50 0 25 4500 3 0 1 125\n",
    "self/task/77/schedstat": "5000000000 1500000000 321\n",
    "pressure/cpu": ("some avg10=0.00 avg60=0.00 avg300=0.00 total=2500000\n"
                     "full avg10=0.00 avg60=0.00 avg300=0.00 total=9\n"),
    "pressure/io": "some avg10=1.50 avg60=0.20 avg300=0.00 total=750000\n",
    "pressure/memory": "some avg10=0.00 avg60=0.00 avg300=0.00 total=0\n",
}


def test_kernel_sample_reads_each_cumulative_counter(tmp_path):
    got = stall.kernel_sample(77, _proc(tmp_path, PROC))
    ticks = os.sysconf("SC_CLK_TCK")
    assert got["steal_s"] == 250 / ticks
    assert got["sched_wait_s"] == 1.5
    assert (got["psi_cpu_s"], got["psi_io_s"], got["psi_memory_s"]) == (
        2.5, 0.75, 0.0)
    assert got["major_faults"] >= 0 and got["involuntary_switches"] >= 0
    assert abs(got["boot_gap_s"]) < 365 * 86400.0


@pytest.mark.parametrize("missing", sorted(PROC))
def test_a_missing_proc_file_leaves_its_key_out(tmp_path, missing):
    keys = {"stat": "steal_s", "self/task/77/schedstat": "sched_wait_s",
            "pressure/cpu": "psi_cpu_s", "pressure/io": "psi_io_s",
            "pressure/memory": "psi_memory_s"}
    files = {k: v for k, v in PROC.items() if k != missing}
    got = stall.kernel_sample(77, _proc(tmp_path, files))
    assert keys[missing] not in got
    assert set(keys.values()) - {keys[missing]} <= set(got)


def test_a_proc_file_that_does_not_parse_raises_nothing(tmp_path):
    files = {**PROC, "stat": "cpu 1 2\n", "pressure/io": "nothing\n",
             "self/task/77/schedstat": "\n"}
    got = stall.kernel_sample(77, _proc(tmp_path, files))
    assert not {"steal_s", "psi_io_s", "sched_wait_s"} & set(got)
    assert got["psi_cpu_s"] == 2.5
    # nor does no /proc at all (not Linux)
    assert "steal_s" not in stall.kernel_sample(77, str(tmp_path / "none"))


def test_the_real_proc_gives_this_machines_counters():
    import threading
    got = stall.kernel_sample(threading.get_native_id())
    assert all(isinstance(v, (int, float)) for v in got.values())
    if os.path.exists("/proc/stat"):
        assert got["steal_s"] >= 0 and got["sched_wait_s"] >= 0


# -- where the loop was ---------------------------------------------------

def _stack(*frames):
    """A chain of frames, innermost first: (file, function, line)."""
    outer = None
    for filename, name, line in reversed(frames):
        outer = types.SimpleNamespace(
            f_code=types.SimpleNamespace(co_filename=filename, co_name=name),
            f_lineno=line, f_back=outer)
    return outer


PKG = os.path.dirname(os.path.dirname(os.path.abspath(stall.__file__)))
RUN = (f"{PKG}/train/worker_group.py", "run", 150)
LIB = "/usr/lib/python3.12"
SITE = f"{LIB}/site-packages"


@pytest.mark.parametrize("frames, where, blocked_in", [
    # float(loss): the innermost frame is jax's
    ([(f"{SITE}/jax/_src/array.py", "_value", 630),
      (f"{SITE}/jax/_src/array.py", "__float__", 300),
      ("/job/loop.py", "_measure", 204), RUN],
     "/job/loop.py:_measure:204", "jax"),
    ([(f"{SITE}/jaxlib/xla_client.py", "execute", 10),
      ("/job/loop.py", "train", 7), RUN], "/job/loop.py:train:7", "jax"),
    # next(batches): the queue's wait under the prefetcher's __next__
    ([(f"{LIB}/threading.py", "wait", 355), (f"{LIB}/queue.py", "get", 171),
      (f"{PKG}/train/prefetch.py", "__next__", 163),
      ("/job/loop.py", "train", 9), RUN],
     f"{LIB}/threading.py:wait:355", "input"),
    # a checkpoint's copy under report()
    ([(f"{LIB}/shutil.py", "copytree", 600),
      (f"{PKG}/train/session.py", "persist", 230),
      (f"{PKG}/train/session.py", "report", 120),
      (f"{PKG}/train/session.py", "report", 175),
      ("/job/loop.py", "train", 12), RUN],
     f"{LIB}/shutil.py:copytree:600", "report"),
    # the user's own code, also under a model of this package that jax
    # is tracing
    ([("/job/data.py", "decode", 41), ("/job/loop.py", "train", 5), RUN],
     "/job/data.py:decode:41", "user"),
    ([(f"{PKG}/models/gpt2.py", "__call__", 88),
      (f"{SITE}/jax/_src/pjit.py", "trace", 40),
      ("/job/loop.py", "train", 6), RUN], "/job/loop.py:train:6", "user"),
    ([RUN], None, "user"),
    ([], None, "user"),
])
def test_where_the_loop_was_and_what_it_was_blocked_in(
        frames, where, blocked_in):
    told = stall.where_of(_stack(*frames))
    assert (told["where"], told["blocked_in"]) == (where, blocked_in)
    # jax's innermost frame by name: a value read back, not a profile
    assert told.get("jax_frame") == {
        630: "array.py:_value", 10: "xla_client.py:execute"}.get(
        frames[0][2] if blocked_in == "jax" else None)


def test_where_of_reads_a_live_frame():
    frame = sys._getframe()
    line = frame.f_lineno + 1
    assert stall.where_of(frame) == {
        "where": f"{__file__}:test_where_of_reads_a_live_frame:{line}",
        "blocked_in": "user"}


# -- the watch over a hand-fed session ------------------------------------

def _watched():
    """A session outside a fit and the watch beside it, made in this
    thread as the worker's loop makes it."""
    sess = train_session.init_session(train_session.TrainContext(),
                                      trace_ctx=("a" * 16, "b" * 16))
    watch = stall.StallWatch(sess, ("a" * 16, "c" * 16),
                             lambda: {"input.stall_s": 0.0})
    return sess, watch


def test_the_span_cap_with_totals_that_go_on(monkeypatch):
    """A job that checkpoints every tenth step makes one in ten
    'stalls', cause ``report`` by name: 256 spans a loop are kept."""
    warned = []
    monkeypatch.setattr(stall.log, "warning", warned.append)
    sess, watch = _watched()
    try:
        t, n = 1000.0, 0
        for i in range(3000):
            long = i % 10 == 9
            t += 2.1 if long else 0.1
            # the report before the long interval took the two seconds
            sess.reports.append((i, t, 2.1 if long else 0.1,
                                 2.0 if long else 0.0))
            n += long
    finally:
        totals = watch.stop()
        train_session.shutdown_session()
    assert n == 300
    # the first of them ends the tenth interval, with nine before it
    assert totals["stalls"] == 300
    assert totals["stalled_s"] == pytest.approx(300 * 2.0)
    assert totals["stall_frozen_s"] == 0.0
    spans = [s for s in sess.spans if s.name == "train.stall"]
    assert len(spans) == stall.MAX_SPANS == 256
    assert {s.attributes["cause"] for s in spans} == {"report"}
    assert [s.attributes["step"] for s in spans[:3]] == [9, 19, 29]
    first = spans[0]
    assert (first.mono_start, first.mono_end) == (
        pytest.approx(1000.9), pytest.approx(1003.0))
    assert first.attributes["median_s"] == pytest.approx(0.1)
    assert first.attributes["excess_s"] == pytest.approx(2.0)
    assert (first.trace_id, first.parent_id) == ("a" * 16, "c" * 16)
    assert len(warned) == 256
    assert warned[0].startswith(
        "train: step 9 took 2.10 s against a median of 0.1: report 2.00 s")
    assert "later ones are counted" in warned[-1]


def test_a_stall_carries_every_piece_of_evidence_this_kernel_gives():
    sess, watch = _watched()
    try:
        for i in range(9):
            sess.reports.append((i, 50.0 + i, 1.0, 0.0))
        sess.reports.append((9, 62.0, 3.0, 0.0))
    finally:
        totals = watch.stop()
        train_session.shutdown_session()
    assert totals == {"stalls": 1, "stalled_s": pytest.approx(2.0),
                      "stall_frozen_s": 0.0, "frozen_s": 0.0}
    (span,) = [s for s in sess.spans if s.name == "train.stall"]
    import threading
    kernel = set(stall.kernel_sample(threading.get_native_id()))
    assert set(span.attributes) == kernel | {
        "step", "interval_s", "median_s", "excess_s", "frozen_s", "cpu_s",
        "gc_s", "compile_s", "input_wait_s", "report_s", "cause"}
    assert span.attributes["cause"] == "unnamed"    # nothing was seen
    assert span.attributes["step"] == 9


def test_a_compile_inside_the_interval_is_its_cause():
    sess, watch = _watched()
    try:
        for i in range(9):
            sess.reports.append((i, 50.0 + i, 1.0, 0.0))
        # a step that recompiled: trace, lower and the backend compile
        # with its cache load inside it, one of them partly before
        for a, b, kind in ((58.5, 59.5, "trace"), (59.5, 60.0, "lower"),
                           (60.0, 61.5, "backend"),
                           (60.2, 61.4, "cache_load")):
            tracing.record_train_span("train.compile", a, b, {"kind": kind},
                                      **train_session.trace_target())
        sess.reports.append((9, 62.0, 3.0, 0.0))
    finally:
        watch.stop()
        train_session.shutdown_session()
    (span,) = [s for s in sess.spans if s.name == "train.stall"]
    assert span.attributes["compile_s"] == pytest.approx(2.5)
    assert span.attributes["cause"] == "compile"


def test_the_watch_leaves_nothing_behind():
    import gc
    import threading
    before = list(gc.callbacks)
    sess, watch = _watched()
    assert len(gc.callbacks) == len(before) + 1
    gc.collect()
    watch.stop()
    train_session.shutdown_session()
    assert gc.callbacks == before
    assert watch._gc_s > 0.0
    assert not any(t.name == "train_stall_watch" and t.is_alive()
                   for t in threading.enumerate())


def test_report_appends_one_entry_and_nothing_else():
    """The loop's thread pays one deque append a report: the index, the
    time, the interval, and what the report before took."""
    sess = train_session.init_session(train_session.TrainContext())
    try:
        for i in range(5):
            train_session.report({"i": i})
    finally:
        train_session.shutdown_session()
    assert [r[0] for r in sess.reports] == [1, 2, 3, 4]    # not the first
    for (_, t0, _, _), (_, t1, interval, report_s) in zip(
            sess.reports, list(sess.reports)[1:]):
        assert t1 - t0 == pytest.approx(interval)
        assert 0.0 < report_s <= interval
    assert sess.reports[-1][1] == sess.last_report_ts
    assert sess.reports.maxlen == 4096      # nobody reads them outside a fit


def test_a_stall_under_a_profile_is_an_annotation_on_the_watchs_line(
        tmp_path):
    """From the beat that sees the open interval pass its limit to the
    report that ends it, the watch thread holds ``train.stall`` open on
    the host plane of a running profile, beside ``train.report`` on the
    loop's line: what names a device idle gap inside a stall."""
    import time

    import jax
    from jax.profiler import ProfileData

    from ray_tpu.observability import xplane
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    sess, watch = _watched()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for i in range(12):
            time.sleep(1.0 if i == 10 else 0.02)
            train_session.report({"i": i})
    finally:
        watch.stop()
        train_session.shutdown_session()
        jax.profiler.stop_trace()
    spans = [s for s in sess.spans if s.name == "train.stall"]
    assert [s.attributes["step"] for s in spans][:1] == [10]
    assert spans[0].attributes["cause"] == "loop"
    found: dict[str, list] = {}         # name -> [(thread, seconds)]
    path = xplane.trace_files(str(tmp_path))[-1]
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in ("train.stall", "train.report"):
                    found.setdefault(ev.name, []).append(
                        ((p, i), ev.duration_ns / 1e9))
    (thread, seconds), = found["train.stall"]
    # opened once the interval was half a median and 0.1 s over, closed
    # by the report: most of the second, on a line of its own
    assert 0.4 < seconds < 2.0
    assert thread not in {line for line, _ in found["train.report"]}


# -- the benchmark's three readers ----------------------------------------

def _readers():
    """``benchmark/layer_metrics/step.stall*.py`` and the module they
    read the fit's spans through."""
    bench = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark")
    sys.path.insert(0, bench)
    try:
        from benchlib import manifest, program_trace
    finally:
        sys.path.pop(0)
    return program_trace, {
        name: manifest.load_reader(f"step.{name}_ms_per_step")
        for name in ("stalled", "stall_frozen", "stall_unnamed")}


def _span(name, start, end, **attributes):
    return tracing.Span(name=name, trace_id="t", span_id=f"{name}{start}",
                        parent_id=None, start=0.0, end=0.0,
                        attributes=attributes, mono_start=start, mono_end=end)


def _stall(start, end, excess_s, frozen_s=0.0, cause="loop"):
    return _span("train.stall", start, end, excess_s=excess_s,
                 frozen_s=frozen_s, cause=cause)


# Stamps a second apart from 100; the window opens at stamp 2 (102.0) and
# closes at stamp 8 (108.0): its intervals end at stamps 3 to 8. report()
# follows each stamp by 20 us.
STAMPS = [100.0 + i for i in range(12)]
LOOP = _span("train.worker.loop", 90.0, 120.0, stalls=0, stalled_s=0.0,
             stall_frozen_s=0.0)
AFTER = 2e-5


def _run(steps_per_dispatch=1):
    return types.SimpleNamespace(
        worker={"stamps": STAMPS, "open_i": 2, "close_i": 8},
        window={"steps": 6 * steps_per_dispatch})


@pytest.mark.parametrize("spans, k, want", [
    # a window with no stall reads 0.0 three times
    ([LOOP], 1, (0.0, 0.0, 0.0)),
    # one frozen stall of 2.4 s in six steps, its freeze cut to its excess
    ([LOOP, _stall(104.0 + AFTER, 105.0 + AFTER, 2.4, 2.6, "frozen")], 1,
     (400.0, 400.0, 0.0)),
    # ten fused steps a dispatch: one report, ten steps
    ([LOOP, _stall(104.0 + AFTER, 105.0 + AFTER, 2.4, 0.3, "unnamed")], 10,
     (40.0, 5.0, 40.0)),
    # the first and the last interval of the window count
    ([LOOP, _stall(102.0 + AFTER, 103.0 + AFTER, 0.6, 0.6, "frozen"),
      _stall(107.0 + AFTER, 108.0 + AFTER, 0.3, 0.0, "unnamed")], 1,
     (150.0, 100.0, 50.0)),
    # a stall that ends on the opening stamp (PR 45's), one in the
    # interval in which the profiler starts, one before the window and
    # the drain's report after the last stamp are outside
    ([LOOP, _stall(101.0 + AFTER, 102.0 + AFTER, 2.8, 2.8, "frozen"),
      _stall(108.0 + AFTER, 109.0 + AFTER, 1.0),
      _stall(100.0 + AFTER, 101.0 + AFTER, 1.0),
      _stall(111.0 + AFTER, 114.0, 2.0)], 1, (0.0, 0.0, 0.0)),
])
def test_the_three_readers_over_hand_made_spans_and_stamps(
        monkeypatch, spans, k, want):
    program_trace, readers = _readers()
    monkeypatch.setattr(program_trace, "fit_spans", lambda: list(spans))
    got = tuple(readers[name](_run(k)) for name in
                ("stalled", "stall_frozen", "stall_unnamed"))
    assert got == pytest.approx(want)


def test_an_untraced_windows_drain_report_is_outside(monkeypatch):
    """Without a traced tail the last stamp is the closing one, and the
    report the loop makes after it (the drain, the prefetcher's close)
    ends after that stamp without holding it."""
    program_trace, readers = _readers()
    spans = [LOOP, _stall(108.0 + AFTER, 109.5, 1.2)]
    monkeypatch.setattr(program_trace, "fit_spans", lambda: spans)
    run = _run()
    run.worker["stamps"] = STAMPS[:9]
    assert readers["stalled"](run) == 0.0


@pytest.mark.parametrize("spans", [
    None, [], [_span("train.fit", 80.0, 130.0)],
    # a program from before the span: the loop has no ``stalls``
    [_span("train.fit", 80.0, 130.0),
     _span("train.worker.loop", 90.0, 120.0, rank=0)],
], ids=["no spans", "empty", "no loop", "no stalls attribute"])
def test_the_readers_say_nothing_of_a_program_without_the_span(
        monkeypatch, spans):
    program_trace, readers = _readers()
    monkeypatch.setattr(program_trace, "fit_spans", lambda: spans)
    assert [read(_run()) for read in readers.values()] == [None] * 3


# -- two real tiny fits ---------------------------------------------------

STEP_S, STEPS, SLOW = 0.02, 20, 11      # the twelfth step stalls


def _sleeping_loop(config):
    import time as _time

    from ray_tpu import train
    for i in range(STEPS):
        _time.sleep(STEP_S)
        if i == SLOW:
            _time.sleep(1.0)
        train.report({"i": i})


def _stopped_loop(config):
    """The twelfth step starts a helper that stops this process for a
    second and lets it go on."""
    import os as _os
    import subprocess
    import sys as _sys
    import time as _time

    from ray_tpu import train
    helper = None
    for i in range(STEPS):
        _time.sleep(STEP_S)
        if i == SLOW:
            helper = subprocess.Popen([_sys.executable, "-c", (
                "import os, signal, sys, time\n"
                "pid = int(sys.argv[1])\n"
                "os.kill(pid, signal.SIGSTOP)\n"
                "time.sleep(1.0)\n"
                "os.kill(pid, signal.SIGCONT)\n"), str(_os.getpid())])
            # until the stop has come and gone: the clock jumps
            t0 = last = _time.monotonic()
            while last - t0 < 30.0:
                _time.sleep(0.005)
                now = _time.monotonic()
                if now - last > 0.5:
                    break
                last = now
        train.report({"i": i})
    helper.wait(timeout=30)


def _fit(loop, tmp_path):
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    ray_tpu.init(num_cpus=4, ignore_reinit_error=False)
    try:
        result = JaxTrainer(
            loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="fit", storage_path=str(tmp_path)),
        ).fit()
    finally:
        ray_tpu.shutdown()
    assert result.error is None
    return result


def _stalls(result):
    loop = next(s for s in result.spans if s["name"] == "train.worker.loop")
    stalls = [s for s in result.spans if s["name"] == "train.stall"]
    # on a loaded machine another step may straggle too: the one asked
    # for is the longest
    assert stalls and loop["attributes"]["stalls"] == len(stalls)
    assert all(s["parent_id"] == loop["span_id"] for s in stalls)
    assert loop["attributes"]["stalled_s"] == pytest.approx(
        sum(s["attributes"]["excess_s"] for s in stalls))
    return loop, max(stalls, key=lambda s: s["attributes"]["excess_s"])


def test_a_loop_that_sleeps_in_its_twelfth_step_is_a_stall_of_the_loops(
        tmp_path):
    result = _fit(_sleeping_loop, tmp_path)
    loop, span = _stalls(result)
    a = span["attributes"]
    assert a["step"] == SLOW
    assert 0.5 < a["excess_s"] < 2.0 and a["interval_s"] > 1.0
    assert a["median_s"] < 0.5
    assert a["cause"] == "loop" and a["blocked_in"] == "user"
    assert a["frozen_s"] < a["excess_s"] / 2
    assert a["where"].startswith(f"{__file__}:_sleeping_loop:")
    # from the report before to the report that ended it, on the clock
    # of every other span of the fit, inside the loop's span
    assert span["mono_end"] - span["mono_start"] == pytest.approx(
        a["interval_s"])
    assert loop["mono_start"] < span["mono_start"]
    assert span["mono_end"] < loop["mono_end"]
    assert loop["attributes"]["stall_frozen_s"] <= (
        loop["attributes"]["stalled_s"])
    # and in the fit's trace file, like every other span
    import json
    with open(os.path.join(result.path, "fit_trace.json")) as f:
        events = json.load(f)
    (event,) = [e for e in events if e["name"] == "train.stall"
                and e["args"]["step"] == SLOW]
    assert event["args"]["cause"] == "loop"


@pytest.mark.skipif(not hasattr(os, "fork"), reason="needs SIGSTOP")
def test_a_process_that_was_stopped_is_a_stall_of_the_machines(tmp_path):
    result = _fit(_stopped_loop, tmp_path)
    loop, span = _stalls(result)
    a = span["attributes"]
    assert a["step"] == SLOW and a["cause"] == "frozen"
    # the stop was a second: within a factor of two
    assert 0.5 < a["frozen_s"] < 2.0
    assert a["frozen_s"] >= a["excess_s"] / 2
    assert a["cpu_s"] < a["frozen_s"]
    assert 0.5 * a["frozen_s"] < loop["attributes"]["stall_frozen_s"]
    # the loop's whole life stood still for that long at least
    assert loop["attributes"]["frozen_s"] >= a["frozen_s"] - 1e-9
