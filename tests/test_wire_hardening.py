"""Unit tests for the hardened wire layer (ray_tpu/core/wire.py):
frame checksums/sequencing, heartbeat filtering, connect deadlines,
the chaos fault plan, and the ResourceKiller determinism contract.

These are process-local (socketpair-based) — the cluster-level
partition scenarios live in tests/test_partition_chaos.py.
"""

from __future__ import annotations

import os
import socket
import threading
import time
from multiprocessing import Pipe

import pytest

from ray_tpu.core import wire


@pytest.fixture
def clean_plan():
    plan = wire.fault_plan()
    plan.clear()
    yield plan
    plan.clear()
    plan._file_sig = None


def _pair(kind="wiretest", checksum=True):
    a, b = Pipe(duplex=True)
    wa = wire.WireConnection(a, kind=kind, peer="peer-b",
                             checksum=checksum)
    wb = wire.WireConnection(b, kind=kind, peer="peer-a",
                             checksum=checksum)
    return wa, wb


def test_frame_roundtrip(clean_plan):
    wa, wb = _pair()
    msgs = [("hello", 1), {"k": b"v" * 1000}, [None, 2.5],
            ("blob", os.urandom(64 << 10))]
    for m in msgs:
        wa.send(m)
    got = [wb.recv() for _ in msgs]
    assert got == msgs
    # And the other direction, interleaved with more a->b traffic.
    wb.send(("reply", 1))
    wa.send(("more", 2))
    assert wa.recv() == ("reply", 1)
    assert wb.recv() == ("more", 2)
    wa.close()
    wb.close()


def test_corrupt_frame_detected_not_deserialized(clean_plan):
    """A corrupted frame must raise FrameCorruptionError (an OSError,
    so recv loops reset the channel) BEFORE any unpickling."""
    wa, wb = _pair()
    before = wire.COUNTERS["corrupt_frames"]
    clean_plan.install(wire.FaultRule("corrupt", kind="wiretest",
                                      direction="send"))
    wa.send(("payload", 123))
    with pytest.raises(wire.FrameCorruptionError):
        wb.recv()
    assert wire.COUNTERS["corrupt_frames"] == before + 1
    assert isinstance(wire.FrameCorruptionError("x"), OSError)
    # The channel is dead after a reset — both ends observe it.
    with pytest.raises((OSError, EOFError)):
        wb.recv()
    wa.close()
    wb.close()


def test_dropped_frame_surfaces_as_desync(clean_plan):
    wa, wb = _pair()
    rid = clean_plan.install(wire.FaultRule("drop", kind="wiretest",
                                            direction="send"))
    wa.send(("lost", 0))          # swallowed, no error to the sender
    clean_plan.remove(rid)
    wa.send(("next", 1))
    with pytest.raises(wire.ChannelDesyncError) as ei:
        wb.recv()
    assert "1 frame(s) lost" in str(ei.value)
    wa.close()
    wb.close()


def test_duplicated_frame_delivered_once(clean_plan):
    wa, wb = _pair()
    before = wire.COUNTERS["dup_frames_dropped"]
    rid = clean_plan.install(wire.FaultRule("dup", kind="wiretest",
                                            direction="send"))
    wa.send(("dup-me", 1))
    clean_plan.remove(rid)
    wa.send(("after", 2))
    assert wb.recv() == ("dup-me", 1)
    assert wb.recv() == ("after", 2)
    assert wire.COUNTERS["dup_frames_dropped"] == before + 1
    wa.close()
    wb.close()


def test_delay_preserves_ordering(clean_plan):
    wa, wb = _pair()
    clean_plan.install(wire.FaultRule("delay", kind="wiretest",
                                      direction="send", prob=0.5,
                                      delay_s=0.02, seed=7))
    for i in range(20):
        wa.send(("seq", i))
    got = [wb.recv() for _ in range(20)]
    assert got == [("seq", i) for i in range(20)]
    wa.close()
    wb.close()


class _Channel:
    """A pair and what a test hangs on it: reader threads and heartbeat
    monitors."""

    def __init__(self):
        self.a, self.b = _pair()
        self.readers = []

    def read(self, conn, into=lambda frame: None):
        """Pump ``conn``'s application frames into ``into`` until the
        connection dies."""
        def pump():
            try:
                while True:
                    into(conn.recv())
            except (EOFError, OSError):
                pass

        self.readers.append(threading.Thread(target=pump, daemon=True))
        self.readers[-1].start()

    def shut(self):
        for conn in (self.a, self.b):
            conn.kill()     # deregisters its monitor, wakes its reader
        for reader in self.readers:
            reader.join(5)
            assert not reader.is_alive()


@pytest.fixture
def channel(clean_plan):
    """Pass or fail, a test leaves no monitor on the process's one
    heartbeater and no reader behind: both ends are shut down (which
    wakes a blocked reader; a bare ``close`` does not) and the readers
    joined before the descriptors' numbers can go to the next test's
    pair. A reader that outlived its pair read the length prefix of the
    next pair's first frame off the reused number (PR 56: the frozen
    channel's peer then broke on a short frame one heartbeat in, before
    any deadline)."""
    ch = _Channel()
    yield ch
    ch.shut()


def test_heartbeats_absorbed_and_answered(channel):
    """Pings are auto-ponged inside recv and neither direction's
    application stream ever sees a heartbeat frame."""
    wa, wb = channel.a, channel.b
    got_b = []
    done = threading.Event()
    channel.read(wb, lambda frame: (got_b.append(frame), done.set()))
    before_sent = wire.COUNTERS["heartbeats_sent"]
    wa.ping()                      # -> b absorbs it and pongs back
    wa.send(("app", 1))
    assert done.wait(5)
    assert got_b == [("app", 1)]   # ping never surfaced to b's app
    wb.send(("flush", 2))
    # a's next recv absorbs the queued pong, then returns the real
    # frame — heartbeats are invisible to the application stream.
    assert wa.recv() == ("flush", 2)
    assert wire.COUNTERS["heartbeats_sent"] == before_sent + 1


def test_heartbeater_kills_frozen_channel(channel, clean_plan):
    """The silent-partition primitive: one direction frozen (reads
    hang, no RST) must be detected within the liveness deadline and
    converted into an explicit connection error for blocked
    readers."""
    wa, wb = channel.a, channel.b
    # a stops hearing ANYTHING (pongs included) — but its sends still
    # leave, exactly like a one-way link.
    clean_plan.install(wire.FaultRule("freeze", kind="wiretest",
                                      direction="recv", peer="peer-b"))
    # keep b pumping so pings would be answered if they arrived
    channel.read(wb)
    before = wire.COUNTERS["heartbeats_missed"]
    wire.heartbeater().register(wa, interval=0.1, timeout=0.5,
                                expecting=lambda: True,
                                name="frozen-test")
    began = time.monotonic()
    with pytest.raises((EOFError, OSError)) as woke:
        wa.recv()                  # blocked reader wakes with error
    assert wire.COUNTERS["heartbeats_missed"] == before + 1, (
        f"woken {time.monotonic() - began:.3f} s in by {woke.value!r}, "
        f"not by the deadline")
    assert time.monotonic() - began >= 0.5      # the deadline, no sooner


def test_quiescent_exemption_no_pings_when_idle(channel):
    """A monitor with a false ``expecting`` predicate must send zero
    heartbeat frames no matter how idle the channel is: nothing reaches
    the other end (the process's counter would also count the pings of
    whatever else the process monitors)."""
    wa, wb = channel.a, channel.b
    wire.heartbeater().register(wa, interval=0.05, timeout=10.0,
                                expecting=lambda: False,
                                name="idle-test")
    time.sleep(0.5)
    assert not wb.poll(0)           # not one frame in ten intervals
    assert not wa.closed
    wa.ping()                       # and one ping is one frame there
    assert wb.poll(5)


def test_dial_refused_names_peer():
    # Grab a port that is certainly closed.
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    with pytest.raises(ConnectionError) as ei:
        wire.dial(("127.0.0.1", port), family="AF_INET",
                  authkey=b"x", peer="test-head", timeout=1.0,
                  retries=2)
    msg = str(ei.value)
    assert "test-head" in msg and "attempt" in msg


def test_dial_handshake_deadline():
    """A peer that accepts the TCP connection but never completes the
    auth handshake must not hang the dial past connect_timeout_s."""
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)
    accepted = []

    def acceptor():
        try:
            while True:
                c, _ = srv.accept()
                accepted.append(c)   # hold open, never speak
        except OSError:
            pass

    threading.Thread(target=acceptor, daemon=True).start()
    t0 = time.monotonic()
    with pytest.raises(ConnectionError) as ei:
        wire.dial(srv.getsockname(), family="AF_INET",
                  authkey=b"secret", peer="mute-head", timeout=0.5,
                  retries=1)
    assert time.monotonic() - t0 < 5.0
    assert "mute-head" in str(ei.value)
    srv.close()
    for c in accepted:
        c.close()


def test_plan_file_roundtrip(tmp_path, monkeypatch, clean_plan):
    path = str(tmp_path / "chaos.json")
    monkeypatch.setenv("RAY_TPU_CHAOS_FILE", path)
    rule = wire.FaultRule("freeze", kind="node", node="n-abc",
                          direction="send", id="r1")
    wire.write_plan_file(path, [rule])
    clean_plan.maybe_refresh(force=True)
    assert len(clean_plan.rules) == 1
    r = clean_plan.rules[0]
    assert (r.action, r.kind, r.node, r.direction) == \
        ("freeze", "node", "n-abc", "send")
    wire.write_plan_file(path, [])
    clean_plan.maybe_refresh(force=True)
    assert clean_plan.rules == ()


def test_node_scoped_rules_skip_same_host_channels(clean_plan):
    """A node partition must sever only channels flagged as crossing
    node boundaries — never same-host unix links."""
    wire.set_local_node("n-1")
    try:
        a, b = Pipe(duplex=True)
        local = wire.WireConnection(a, kind="client", peer="head",
                                    crosses_nodes=False)
        c, d = Pipe(duplex=True)
        remote = wire.WireConnection(c, kind="node", peer="head",
                                     peer_node="head",
                                     crosses_nodes=True)
        clean_plan.install(wire.FaultRule("freeze", node="n-1",
                                          direction="send"))
        local.send(("ok", 1))
        assert wire.WireConnection(
            b, kind="client", peer="x").recv() == ("ok", 1)
        remote.send(("swallowed", 2))      # silently dropped
        assert not wire.WireConnection(
            d, kind="node", peer="x").poll(0.2)
        for conn in (local, remote):
            conn.close()
        b.close()
        d.close()
    finally:
        wire.set_local_node("")


def test_wire_counters_on_metrics_registry(clean_plan):
    """Injected-fault and reset counters must be visible to the
    metrics registry (and therefore the cluster Prometheus scrape
    via the worker exporters)."""
    wa, wb = _pair()
    clean_plan.install(wire.FaultRule("corrupt", kind="wiretest",
                                      direction="send"))
    wa.send(("x",))
    with pytest.raises(wire.FrameCorruptionError):
        wb.recv()
    from ray_tpu.util.metrics import collect_all
    names = set(collect_all())
    assert "ray_tpu_wire_corrupt_frames_total" in names
    assert "ray_tpu_wire_faults_injected_total" in names
    wa.close()
    wb.close()


# ---------------------------------------------------------------------------
# steady-state fast path: zero heartbeat frames


def test_direct_fast_path_zero_heartbeat_frames():
    """Heartbeats must cost the direct-call fast path NOTHING: while
    acks flow, traffic itself proves liveness (no pings), and an idle
    channel with no unacked calls is quiescent-exempt (no pings
    either). Asserted as a zero-frame count in the caller worker with
    the heartbeat interval cranked far below both phases."""
    from conftest import LOAD_SOFT, host_load_factor
    if host_load_factor() > LOAD_SOFT:
        pytest.skip("host contended: pacing-sensitive zero-frame "
                    "assertion would measure the neighbors")
    import ray_tpu
    from ray_tpu.core.config import env_overrides
    with env_overrides(heartbeat_interval_s=0.5,
                       heartbeat_timeout_s=30.0):
        ray_tpu.init(num_cpus=2)
        try:
            @ray_tpu.remote(num_cpus=0)
            class Bounce:
                def hit(self, i):
                    return i

            @ray_tpu.remote(num_cpus=1)
            def burst(handle):
                import time as _t

                from ray_tpu.core import wire as w
                rt_c = ray_tpu.core.api.get_runtime()
                deadline = _t.monotonic() + 20
                while rt_c.actor_calls_direct == 0 \
                        and _t.monotonic() < deadline:
                    ray_tpu.get(handle.hit.remote(-1), timeout=60)
                    _t.sleep(0.05)
                assert rt_c.actor_calls_direct > 0, "never warmed"
                before = w.COUNTERS["heartbeats_sent"]
                t_end = _t.monotonic() + 1.5
                i = 0
                while _t.monotonic() < t_end:   # steady traffic
                    assert ray_tpu.get(handle.hit.remote(i),
                                       timeout=60) == i
                    i += 1
                _t.sleep(1.6)       # idle: quiescent-exempt window
                return w.COUNTERS["heartbeats_sent"] - before

            a = Bounce.remote()
            assert ray_tpu.get(burst.remote(a), timeout=120) == 0
        finally:
            ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# ResourceKiller determinism (same seed => same schedule)


class _StubRuntime:
    def __init__(self, node_ids):
        self._ids = node_ids
        self.drained = []
        self.removed = []

    def nodes(self):
        return [{"NodeID": n, "Alive": True, "IsHead": False,
                 "Draining": False} for n in self._ids]

    def drain_node(self, node_id, **kw):
        self.drained.append(node_id)
        return True

    def remove_node(self, node_id):
        self.removed.append(node_id)


@pytest.mark.chaos
def test_resource_killer_partition_schedule_deterministic(tmp_path):
    from ray_tpu.util.chaos import ResourceKiller
    ids = [f"node-{i}" for i in range(5)]

    def schedule(seed):
        rt = _StubRuntime(ids)
        rk = ResourceKiller(kind="partition", seed=seed, runtime=rt,
                            partition_duration_s=0.01,
                            plan_file=str(tmp_path / f"p{seed}.json"))
        for _ in range(8):
            rk._kill_one()
        return rk.decisions

    s1, s2, s3 = schedule(42), schedule(42), schedule(7)
    assert s1 == s2                     # same seed => same schedule
    assert s1 != s3                     # different seed diverges
    assert all(d[0] == "partition" and d[1] in ids
               and d[2] in ("both", "send", "recv") for d in s1)


@pytest.mark.chaos
def test_resource_killer_preempt_schedule_deterministic():
    from ray_tpu.util.chaos import ResourceKiller
    ids = [f"node-{i}" for i in range(4)]

    def schedule(seed):
        rt = _StubRuntime(ids)
        rk = ResourceKiller(kind="preempt", seed=seed, runtime=rt)
        for _ in range(6):
            rk._kill_one()
        return rk.decisions, rt.drained

    assert schedule(3) == schedule(3)


def test_resource_killer_partition_requires_plan_file(monkeypatch):
    from ray_tpu.util.chaos import ResourceKiller
    monkeypatch.delenv("RAY_TPU_CHAOS_FILE", raising=False)
    with pytest.raises(ValueError):
        ResourceKiller(kind="partition", runtime=_StubRuntime([]))
