"""Device memory on the train path's own spans (docs/observability.md,
"Reading a step that does not fit").

``train/stall.py::hbm_sample`` reads each device's ``memory_stats()``;
``worker_group.py::_open_backend`` writes the limit and the bytes at
open on ``train.worker.backend_init``; the stall watch samples beside
the loop (after its first report, after its eighth, at the end) and
``train.worker.loop`` carries what the fullest device held. The devices
here are stand-ins whose ``memory_stats()`` returns scripted dicts: the
suite's own backend, the CPU, has no such counters, and a fit on it must
write no ``hbm_*`` key.
"""

import json
import os
import threading
import time

import jax
import pytest

from ray_tpu.train import session as train_session
from ray_tpu.train import stall, worker_group

GB = 10 ** 9
LIMIT = 16_909_000_000


class Device:
    """A stand-in device: ``memory_stats()`` gives the script's entries
    in turn and then its last one again; an entry that is an exception
    is raised."""
    platform, device_kind = "tpu", "TPU v5 lite"

    def __init__(self, id_, script):
        self.id, self.script, self.calls = id_, list(script), 0

    def memory_stats(self):
        entry = self.script[min(self.calls, len(self.script) - 1)]
        self.calls += 1
        if isinstance(entry, Exception):
            raise entry
        return None if entry is None else dict(entry)


def _stats(in_use, reserved=None, peak=None, peak_reserved=None,
           largest=None, limit=LIMIT):
    got = {"bytes_limit": limit, "bytes_in_use": in_use,
           "peak_bytes_in_use": in_use if peak is None else peak,
           "bytes_reserved": reserved, "peak_bytes_reserved": peak_reserved,
           "largest_alloc_size": largest,
           "num_allocs": 7}         # a key outside the fixed list
    return {k: v for k, v in got.items() if v is not None}


# -- the sample, by table -------------------------------------------------

@pytest.mark.parametrize("scripts, want", [
    ([[_stats(5, 7, 6, 8, 3)]],
     [{"id": 0, "bytes_limit": LIMIT, "bytes_in_use": 5,
       "peak_bytes_in_use": 6, "bytes_reserved": 7,
       "peak_bytes_reserved": 8, "largest_alloc_size": 3}]),
    # a key the backend does not give is left out, not made 0
    ([[_stats(5)]],
     [{"id": 0, "bytes_limit": LIMIT, "bytes_in_use": 5,
       "peak_bytes_in_use": 5}]),
    # the CPU: no counters, no entry; beside a device that has them
    ([[None]], []),
    ([[None], [_stats(1, 2)]],
     [{"id": 1, "bytes_limit": LIMIT, "bytes_in_use": 1,
       "peak_bytes_in_use": 1, "bytes_reserved": 2}]),
    ([[{}]], []),
    # a backend without the call
    ([[NotImplementedError("memory_stats")]], []),
    ([], []),
], ids=["every key", "some keys", "none", "none beside some", "empty",
        "raises", "no devices"])
def test_hbm_sample_gives_the_fixed_keys_the_backend_gives(scripts, want):
    devices = [Device(i, s) for i, s in enumerate(scripts)]
    assert stall.hbm_sample(devices) == want
    assert [d.calls for d in devices] == [1] * len(devices)


@pytest.mark.parametrize("sample, held", [
    ({"bytes_in_use": 6, "bytes_reserved": 10}, 16),
    ({"bytes_in_use": 6}, 6),       # no reserved key: the bytes in use
    # no bytes in use: nothing, not a sum from a made-up 0
    ({"bytes_reserved": 10, "bytes_limit": LIMIT}, None),
    ({}, None),
])
def test_held_is_in_use_plus_reserved(sample, held):
    assert stall.hbm_held(sample) == held


def test_the_cpu_of_this_suite_has_no_counters():
    assert stall.hbm_sample(jax.local_devices()) == []


# -- a worker's loop, run in this process ---------------------------------

def _wait(what, timeout=20.0):
    t0 = time.monotonic()
    while not what():
        assert time.monotonic() - t0 < timeout, "the watch took no sample"
        time.sleep(0.01)


def _run_worker(monkeypatch, devices, *, open_backend=True, raises=False,
                opens=True):
    """``TrainWorker.start_loop`` in this process over stand-in devices:
    a loop of eight reports that waits after the first and the eighth
    until the watch has sampled, so that each scripted entry is one
    known sample (at open, first report, eighth report, stop). Returns
    the worker's spans by name and what the last poll said."""
    from ray_tpu import train

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices",
                        (lambda: devices) if opens else no_backend)
    monkeypatch.setattr(jax, "local_devices", lambda: devices)
    watched = bool(open_backend and opens and devices)

    def calls():
        return min((d.calls for d in devices), default=0)

    def loop():
        train.report({"i": 0})
        if watched:
            _wait(lambda: calls() >= 2)
        for i in range(1, 8):
            train.report({"i": i})
        if watched:
            _wait(lambda: calls() >= 3)
        if raises:
            raise MemoryError("RESOURCE_EXHAUSTED: 0.4 GB too large")

    worker = worker_group.TrainWorker._cls(0, 1, {})
    try:
        worker.start_loop((loop, None), {
            "trace_ctx": ("a" * 16, "b" * 16), "open_backend": open_backend})
        assert worker._done.wait(30.0)
        reply = worker.poll(max_results=64)
    finally:
        train_session.shutdown_session()
    assert reply["done"] and len(reply["results"]) == 8
    return {s["name"]: s for s in reply["spans"]}, reply


def _hbm(span) -> dict:
    return {k: v for k, v in span["attributes"].items() if "hbm" in k}


ONE = [[_stats(1 * GB),                                         # at open
        _stats(6 * GB, 9 * GB, 6 * GB, 9 * GB, 2 * GB),         # 1st report
        _stats(6 * GB, 10 * GB, 7 * GB, 10 * GB, 3 * GB),       # 8th report
        _stats(5 * GB, 4 * GB, 8 * GB, 11 * GB, 3 * GB)]]       # at the end
TWO = [ONE[0], [_stats(2 * GB, limit=LIMIT - 1),
                _stats(6 * GB, 9 * GB),
                _stats(7 * GB, 10 * GB, 7 * GB, 10 * GB, 4 * GB),
                _stats(1 * GB, 0, 9 * GB, 12 * GB, 5 * GB)]]
NO_RESERVED = [[_stats(0), _stats(6 * GB), _stats(7 * GB, peak=8 * GB),
                _stats(2 * GB, peak=8 * GB, largest=GB)]]
# the backend gives the counters late: nothing at open, then all
LATE = [[None, _stats(6 * GB, 9 * GB, 6 * GB, 9 * GB, 2 * GB),
         _stats(6 * GB, 9 * GB, 6 * GB, 9 * GB, 2 * GB)]]
# a device that never says its bytes in use, beside one that does
NO_IN_USE = [[{"bytes_limit": LIMIT, "bytes_reserved": 12 * GB}], ONE[0]]


@pytest.mark.parametrize(
    "scripts, kwargs, opened, looped, calls", [
        (ONE, {},
         {"hbm_limit_bytes": LIMIT, "hbm_in_use_at_open_bytes": 1 * GB},
         {"hbm_held_bytes": 16 * GB,
          "hbm_peak_in_use_bytes": 8 * GB, "hbm_peak_reserved_bytes": 11 * GB,
          "hbm_largest_alloc_bytes": 3 * GB, "hbm_samples": 3}, [4]),
        # the fullest device's marks, and each one's most by its id
        (TWO, {},
         {"hbm_limit_bytes": LIMIT - 1, "hbm_in_use_at_open_bytes": 2 * GB},
         {"hbm_held_bytes": 17 * GB,
          "hbm_peak_in_use_bytes": 9 * GB, "hbm_peak_reserved_bytes": 12 * GB,
          "hbm_largest_alloc_bytes": 5 * GB, "hbm_samples": 3,
          "hbm_held_by_device": {"0": 16 * GB, "1": 17 * GB}}, [4, 4]),
        # ids that do not start at 0 and come in another order
        (TWO, {"ids": (5, 4)},
         {"hbm_limit_bytes": LIMIT - 1, "hbm_in_use_at_open_bytes": 2 * GB},
         {"hbm_held_bytes": 17 * GB,
          "hbm_peak_in_use_bytes": 9 * GB, "hbm_peak_reserved_bytes": 12 * GB,
          "hbm_largest_alloc_bytes": 5 * GB, "hbm_samples": 3,
          "hbm_held_by_device": {"4": 17 * GB, "5": 16 * GB}}, [4, 4]),
        # a loop that raises still carries them, beside ``error``
        (ONE, {"raises": True},
         {"hbm_limit_bytes": LIMIT, "hbm_in_use_at_open_bytes": 1 * GB},
         {"hbm_held_bytes": 16 * GB,
          "hbm_peak_in_use_bytes": 8 * GB, "hbm_peak_reserved_bytes": 11 * GB,
          "hbm_largest_alloc_bytes": 3 * GB, "hbm_samples": 3}, [4]),
        # no ``bytes_reserved``: the bytes in use alone, and no peak of it
        (NO_RESERVED, {},
         {"hbm_limit_bytes": LIMIT, "hbm_in_use_at_open_bytes": 0},
         {"hbm_held_bytes": 7 * GB,
          "hbm_peak_in_use_bytes": 8 * GB, "hbm_largest_alloc_bytes": GB,
          "hbm_samples": 3}, [4]),
        (LATE, {}, {},
         {"hbm_held_bytes": 15 * GB,
          "hbm_peak_in_use_bytes": 6 * GB, "hbm_peak_reserved_bytes": 9 * GB,
          "hbm_largest_alloc_bytes": 2 * GB, "hbm_samples": 3}, [4]),
        # a device without ``bytes_in_use`` holds nothing that is known:
        # the other's numbers alone, and no list of one
        (NO_IN_USE, {},
         {"hbm_limit_bytes": LIMIT, "hbm_in_use_at_open_bytes": 1 * GB},
         {"hbm_held_bytes": 16 * GB,
          "hbm_peak_in_use_bytes": 8 * GB, "hbm_peak_reserved_bytes": 11 * GB,
          "hbm_largest_alloc_bytes": 3 * GB, "hbm_samples": 3}, [4, 4]),
        # the CPU: every call answers None, no key is written
        ([[None]], {}, {}, {}, [4]),
        ([[RuntimeError("no such call")]], {}, {}, {}, [4]),
        # nothing below ``_open_backend`` opens a backend or asks a device
        (ONE, {"open_backend": False}, None, {}, [0]),
        (ONE, {"opens": False}, {}, {}, [0]),
    ], ids=["one device", "two devices", "ids out of order", "loop raises",
            "no reserved key", "counters come late", "no bytes in use",
            "no counters", "memory_stats raises",
            "backend never opened", "backend did not open"])
def test_a_workers_spans_carry_what_the_allocator_held(
        monkeypatch, scripts, kwargs, opened, looped, calls):
    ids = kwargs.pop("ids", range(len(scripts)))
    devices = [Device(i, s) for i, s in zip(ids, scripts)]
    spans, reply = _run_worker(monkeypatch, devices, **kwargs)
    loop = spans["train.worker.loop"]
    assert _hbm(loop) == looped
    assert {"stalls", "frozen_s", "first_report_s"} <= set(
        loop["attributes"])
    if opened is None:
        assert "train.worker.backend_init" not in spans
    else:
        init = spans["train.worker.backend_init"]
        assert _hbm(init) == opened
        assert init["parent_id"] == loop["span_id"]
    assert [d.calls for d in devices] == calls
    if kwargs.get("raises"):
        assert loop["attributes"]["error"] == "MemoryError"
        assert "RESOURCE_EXHAUSTED" in reply["error"]
    else:
        assert reply["error"] is None and "error" not in loop["attributes"]


def test_the_loops_thread_takes_no_sample_while_it_runs(monkeypatch):
    """Every ``memory_stats()`` between the open and the end is the
    watch thread's: ``report()`` and the loop run no line for it."""
    threads = []

    class Told(Device):
        def memory_stats(self):
            threads.append(threading.current_thread().name)
            return super().memory_stats()

    _run_worker(monkeypatch, [Told(0, ONE[0])])
    assert threads == ["train_loop_rank0", "train_stall_watch",
                       "train_stall_watch", "train_loop_rank0"]


# -- the watch beside a stall, and one that does not stop ------------------

@pytest.mark.parametrize("scripts", [
    [[_stats(6 * GB, 9 * GB)], [_stats(7 * GB, 9 * GB)]], [[None]], [],
], ids=["two devices", "no counters", "no devices"])
def test_a_stall_takes_no_sample_and_carries_no_memory_key(
        monkeypatch, scripts):
    """A ``train.stall`` is evidence of where the time went: the watch
    asks no device for it, and the loop's samples stay the three."""
    monkeypatch.setattr(stall, "PERIOD_S", 60.0)    # no beat: ``stop()`` reads
    sess = train_session.init_session(train_session.TrainContext(),
                                      trace_ctx=("a" * 16, "b" * 16))
    sess.devices = [Device(i, s) for i, s in enumerate(scripts)]
    watch = stall.StallWatch(sess, ("a" * 16, "c" * 16),
                             lambda: {"input.stall_s": 0.0})
    try:
        for i in range(9):
            sess.reports.append((i, 50.0 + i, 1.0, 0.0))
        sess.reports.append((9, 62.0, 3.0, 0.0))
    finally:
        totals = watch.stop()
        train_session.shutdown_session()
    (span,) = [s for s in sess.spans if s.name == "train.stall"]
    assert totals["stalls"] == 1
    assert not [k for k in span.attributes if "hbm" in k]
    assert [d.calls for d in sess.devices] == [1] * len(scripts)  # the end's
    assert totals.get("hbm_samples") == (1 if len(scripts) == 2 else None)


def test_a_watch_held_inside_a_sample_is_not_joined_by_a_second(monkeypatch):
    """A runtime that does not answer ``memory_stats()`` holds the watch
    thread, not the loop's: ``stop()`` gives up on the thread, takes no
    sample of its own beside it, and the loop's end is told."""
    monkeypatch.setattr(stall, "STOP_WAIT_S", 0.3)
    release, threads = threading.Event(), []

    class Wedged(Device):
        def memory_stats(self):
            threads.append(threading.current_thread().name)
            release.wait(30.0)
            return super().memory_stats()

    sess = train_session.init_session(train_session.TrainContext(),
                                      trace_ctx=("a" * 16, "b" * 16))
    sess.devices = [Wedged(0, ONE[0])]
    watch = stall.StallWatch(sess, ("a" * 16, "c" * 16),
                             lambda: {"input.stall_s": 0.0})
    try:
        sess.t_first_report = time.monotonic()
        _wait(lambda: threads)
        t0 = time.monotonic()
        totals = watch.stop()
        assert time.monotonic() - t0 < 5.0
    finally:
        release.set()
        watch._thread.join(10.0)
        train_session.shutdown_session()
    assert threads == ["train_stall_watch"]
    assert not [k for k in totals if "hbm" in k] and totals["stalls"] == 0


# -- whole fits -------------------------------------------------------------

def _fit(loop, tmp_path):
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    ray_tpu.init(num_cpus=4, ignore_reinit_error=False)
    try:
        return JaxTrainer(
            loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="fit", storage_path=str(tmp_path)),
        ).fit()
    finally:
        ray_tpu.shutdown()


def _loop_that_does_not_fit(config):
    """Puts two stand-in chips where ``_open_backend`` left the CPU's
    devices, reports once and fails as a step that does not fit does."""
    import time as _time

    from ray_tpu import train
    from ray_tpu.train.session import get_session

    class Chip:
        def __init__(self, id_):
            self.id, self.calls = id_, 0

        def memory_stats(self):
            self.calls += 1
            return {"bytes_limit": 16_909_000_000,
                    "bytes_in_use": 6_120_000_000 + self.id,
                    "peak_bytes_in_use": 6_200_000_000,
                    "bytes_reserved": 9_700_000_000,
                    "largest_alloc_size": 400_000_000}

    chips = [Chip(0), Chip(1)]
    get_session().devices = chips
    train.report({"i": 0})
    t0 = _time.monotonic()
    while chips[0].calls < 1 and _time.monotonic() - t0 < 20.0:
        _time.sleep(0.01)
    raise MemoryError("RESOURCE_EXHAUSTED: 0.4 GB too large")


def test_a_fit_whose_loop_raises_leaves_the_numbers_beside_the_error(
        tmp_path):
    result = _fit(_loop_that_does_not_fit, tmp_path)
    assert "RESOURCE_EXHAUSTED" in result.error
    with open(os.path.join(tmp_path, "fit", "fit_trace.json")) as f:
        events = json.load(f)
    (loop,) = [e["args"] for e in events if e["name"] == "train.worker.loop"]
    assert loop["error"] == "MemoryError"
    assert loop["hbm_held_bytes"] == 6_120_000_001 + 9_700_000_000
    assert loop["hbm_largest_alloc_bytes"] == 400_000_000
    assert loop["hbm_peak_in_use_bytes"] == 6_200_000_000
    assert "hbm_peak_reserved_bytes" not in loop    # the key was not given
    assert loop["hbm_held_by_device"] == {"0": 15_820_000_000,
                                          "1": 15_820_000_001}
    assert loop["hbm_samples"] == 2                 # first report, the end


def _cpu_loop(config):
    import jax.numpy as jnp

    from ray_tpu import train
    for i in range(9):
        train.report({"i": i, "x": float(jnp.ones(()) + i)})


def test_a_fit_on_the_cpu_writes_no_hbm_key_and_nothing_fails(tmp_path):
    result = _fit(_cpu_loop, tmp_path)
    assert result.error is None
    by_name = {s["name"]: s for s in result.spans}
    assert by_name["train.worker.backend_init"]["attributes"][
        "platform"] == "cpu"
    for s in result.spans:
        assert not [k for k in s["attributes"] if "hbm" in k], s["name"]
    with open(os.path.join(result.path, "fit_trace.json")) as f:
        assert "hbm" not in f.read()


def test_every_hbm_attribute_is_told_in_the_docs():
    """docs/observability.md's span table names each attribute this
    file asserts, and the allocator's key it comes from."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "docs", "observability.md")) as f:
        docs = f.read()
    for name in ("hbm_limit_bytes", "hbm_in_use_at_open_bytes",
                 "hbm_held_bytes",
                 "hbm_peak_in_use_bytes", "hbm_peak_reserved_bytes",
                 "hbm_largest_alloc_bytes", "hbm_held_by_device",
                 "hbm_samples", *stall.HBM_KEYS):
        assert f"`{name}`" in docs, name
