"""Graceful node drain / TPU preemption handling.

Reference analogs: the DrainNode protocol (gcs_node_manager.cc) and
the autoscaler's drain-before-terminate hooks. The contract under
test: an ANTICIPATED failure (preemption notice, SIGTERM, scale-down)
is a zero-loss migration — in-flight tasks finish or retry elsewhere
with their attempt refunded, restartable actors move without
consuming restart budget, primary object copies are evacuated ahead
of the kill, and NO lineage reconstruction fires.
"""

import os
import signal
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.util.chaos import ResourceKiller
from ray_tpu.util.scheduling_strategies import (
    NodeAffinitySchedulingStrategy,
)

pytestmark = pytest.mark.chaos


@pytest.fixture
def rt():
    ray_tpu.init(num_cpus=2)
    yield ray_tpu.core.api.get_runtime()
    ray_tpu.shutdown()


@pytest.fixture
def cluster():
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    yield c
    c.shutdown()


def _soft_pin(node_id):
    return NodeAffinitySchedulingStrategy(node_id, soft=True)


# ---------------------------------------------------------------------------
# drain state + scheduling exclusion
# ---------------------------------------------------------------------------

def test_draining_node_excluded_from_scheduling(rt):
    nid = rt.add_node({"CPU": 4.0})
    assert rt.drain_node(nid, reason="maintenance")
    # Visible in nodes() and the state API.
    row = next(n for n in ray_tpu.nodes() if n["NodeID"] == nid)
    assert row["Alive"] and row["Draining"]
    assert row["DrainReason"] == "maintenance"
    from ray_tpu.util import state
    srow = next(r for r in state.list_nodes() if r["node_id"] == nid)
    assert srow["state"] == "DRAINING"

    # New work never lands on the draining node.
    @ray_tpu.remote(num_cpus=1)
    def where():
        return ray_tpu.get_runtime_context().get_node_id()

    homes = ray_tpu.get([where.remote() for _ in range(6)],
                        timeout=60)
    assert nid not in homes

    # Hard affinity to a draining node fails fast instead of hanging.
    from ray_tpu.core.exceptions import TaskError
    with pytest.raises(TaskError, match="draining"):
        ray_tpu.get(where.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                nid, soft=False)).remote(), timeout=60)

    # Soft affinity falls back to a schedulable node.
    home = ray_tpu.get(where.options(
        scheduling_strategy=_soft_pin(nid)).remote(), timeout=60)
    assert home != nid


def test_drain_refunds_preempted_task_attempts():
    """max_retries=0 tasks survive a drain that preempts them: the
    interrupted attempt is refunded, so retry budget stays reserved
    for real crashes."""
    from ray_tpu.core.config import env_overrides
    with env_overrides(drain_grace_period_s=0.2):
        ray_tpu.init(num_cpus=2)
        try:
            rt = ray_tpu.core.api.get_runtime()
            nid = rt.add_node({"CPU": 2.0})

            @ray_tpu.remote(num_cpus=1)
            def slow(i):
                time.sleep(1.5)
                return i

            refs = [slow.options(scheduling_strategy=_soft_pin(nid),
                                 max_retries=0).remote(i)
                    for i in range(4)]
            time.sleep(0.4)              # a wave lands on the node
            recon0 = rt.lineage_reconstructions
            assert rt.drain_node(nid, reason="preempt",
                                 deadline_s=20, remove=True)
            assert sorted(ray_tpu.get(refs, timeout=60)) == \
                list(range(4))
            assert rt.drain_tasks_preempted >= 1
            assert rt.lineage_reconstructions == recon0
        finally:
            ray_tpu.shutdown()


def test_drain_config_knobs_exist():
    from ray_tpu.core.config import Config, env_overrides
    cfg = Config()
    assert cfg.drain_grace_period_s > 0
    assert cfg.drain_deadline_s > 0
    assert cfg.client_ack_replay_timeout_s == 300.0
    with env_overrides(client_ack_replay_timeout_s=7.5) as c:
        assert c.client_ack_replay_timeout_s == 7.5


# ---------------------------------------------------------------------------
# the acceptance scenario: in-flight tasks + stored primary objects +
# a restartable actor drain with zero loss and zero reconstructions
# ---------------------------------------------------------------------------

def test_drain_zero_loss_full_surface(cluster):
    n2 = cluster.add_node(num_cpus=2)
    rt = ray_tpu.core.api.get_runtime()
    pin = _soft_pin(n2.node_id)

    # A primary object copy homed in the node's local store.
    @ray_tpu.remote(num_cpus=1)
    def produce():
        return np.arange(200_000, dtype=np.int64)   # ~1.6 MB

    big = produce.options(scheduling_strategy=pin).remote()
    ray_tpu.wait([big], timeout=60)
    assert rt._obj_locations.get(big.id) == ("node", n2.node_id)

    # A restartable actor on the node.
    @ray_tpu.remote(num_cpus=1)
    class Counter:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1
            return self.n

    a = Counter.options(max_restarts=1,
                        scheduling_strategy=pin).remote()
    assert ray_tpu.get(a.bump.remote(), timeout=60) == 1
    arec = rt._actors[a._actor_id]
    assert arec.node_id == n2.node_id

    # In-flight tasks on the node.
    @ray_tpu.remote(num_cpus=1)
    def slow(i):
        time.sleep(0.4)
        return i

    refs = [slow.options(scheduling_strategy=pin,
                         max_retries=0).remote(i) for i in range(4)]
    time.sleep(0.15)

    recon0 = rt.lineage_reconstructions
    assert rt.drain_node(n2.node_id, reason="preemption notice",
                         deadline_s=25, remove=True)

    # Zero user-visible failures: every get succeeds.
    assert sorted(ray_tpu.get(refs, timeout=90)) == list(range(4))
    val = ray_tpu.get(big, timeout=60)          # evacuated, not lost
    assert int(val[123_456]) == 123_456
    assert ray_tpu.get(a.bump.remote(), timeout=90) >= 1

    # The actor MOVED, for free (anticipated failure ≠ restart).
    assert arec.node_id != n2.node_id
    assert arec.restart_count == 0
    # Proactive paths ran; lineage reconstruction did not.
    assert rt.drain_objects_evacuated >= 1
    assert rt.drain_actors_migrated >= 1
    assert rt.lineage_reconstructions == recon0
    row = next(n for n in ray_tpu.nodes()
               if n["NodeID"] == n2.node_id)
    assert not row["Alive"]


def test_drain_kills_non_restartable_actor_with_reason(cluster):
    n2 = cluster.add_node(num_cpus=1)
    rt = ray_tpu.core.api.get_runtime()

    @ray_tpu.remote(num_cpus=1)
    class Pinned:
        def ping(self):
            return "ok"

    a = Pinned.options(
        scheduling_strategy=_soft_pin(n2.node_id)).remote()
    assert ray_tpu.get(a.ping.remote(), timeout=60) == "ok"
    assert rt._actors[a._actor_id].node_id == n2.node_id

    assert rt.drain_node(n2.node_id, reason="spot reclaim",
                         deadline_s=15, remove=True)
    from ray_tpu.core.exceptions import ActorDiedError
    with pytest.raises(ActorDiedError, match="drained"):
        ray_tpu.get(a.ping.remote(), timeout=60)


# ---------------------------------------------------------------------------
# daemon-initiated drain: SIGTERM and the preemption-notice watcher
# ---------------------------------------------------------------------------

def test_sigterm_triggers_graceful_drain(cluster):
    """SIGTERM on the daemon = termination notice: the node drains
    through ND_DRAIN (work retried elsewhere, zero loss) and the
    daemon exits cleanly instead of dropping its sockets."""
    n2 = cluster.add_node(num_cpus=2)
    rt = ray_tpu.core.api.get_runtime()

    @ray_tpu.remote(num_cpus=1)
    def slow(i):
        time.sleep(0.3)
        return i

    refs = [slow.options(scheduling_strategy=_soft_pin(n2.node_id),
                         max_retries=0).remote(i) for i in range(6)]
    time.sleep(0.15)
    recon0 = rt.lineage_reconstructions
    os.kill(n2.proc.pid, signal.SIGTERM)

    assert sorted(ray_tpu.get(refs, timeout=90)) == list(range(6))
    deadline = time.time() + 45
    while time.time() < deadline:
        n = rt._nodes.get(n2.node_id)
        if n is not None and not n.alive \
                and n2.proc.poll() is not None:
            break
        time.sleep(0.1)
    n = rt._nodes.get(n2.node_id)
    assert n is not None and not n.alive
    assert n2.proc.poll() == 0          # clean exit, not a crash
    assert rt.lineage_reconstructions == recon0


def test_preemption_watcher_injectable_probe():
    """The watcher turns the first truthy probe answer into ONE
    request_drain — same injectable-transport pattern as gce_tpu's
    runner, zero egress."""
    from ray_tpu.core.node_daemon import PreemptionWatcher

    class FakeDaemon:
        _shutdown = False

        def __init__(self):
            self.calls = []

        def request_drain(self, reason, deadline_s=None):
            self.calls.append((reason, deadline_s))

    d = FakeDaemon()
    answers = iter([None, None, "spot reclaim"])
    w = PreemptionWatcher(d, probe=lambda: next(answers),
                          interval_s=0.02, deadline_s=7.5).start()
    deadline = time.monotonic() + 5
    while not d.calls and time.monotonic() < deadline:
        time.sleep(0.02)
    w.stop()
    assert d.calls == [("spot reclaim", 7.5)]


def test_gce_preemption_probe_offline_is_none():
    # No metadata server on the test box: reads as "no notice",
    # never as an exception.
    from ray_tpu.core.node_daemon import gce_preemption_probe
    assert gce_preemption_probe() is None


# ---------------------------------------------------------------------------
# rolling-drain chaos: ResourceKiller kind="preempt"
# ---------------------------------------------------------------------------

def test_rolling_preempt_chaos_zero_loss(cluster):
    """Drain-preempt nodes one after another under a fan-out task +
    actor workload: every call succeeds, nothing reconstructs."""
    n2 = cluster.add_node(num_cpus=2)
    n3 = cluster.add_node(num_cpus=2)
    rt = ray_tpu.core.api.get_runtime()

    @ray_tpu.remote(num_cpus=1)
    def work(i):
        time.sleep(0.1)
        return i

    @ray_tpu.remote(num_cpus=1)
    class Sink:
        def __init__(self):
            self.total = 0

        def add(self, x):
            self.total += x
            return self.total

    sink = Sink.options(
        max_restarts=4,
        scheduling_strategy=_soft_pin(n2.node_id)).remote()
    assert ray_tpu.get(sink.add.remote(0), timeout=60) == 0

    recon0 = rt.lineage_reconstructions
    killer = ResourceKiller(kind="preempt", interval_s=0.6,
                            max_kills=2, seed=7,
                            drain_deadline_s=12.0).start()
    try:
        results = []
        for batch in range(4):
            pins = [None, _soft_pin(n2.node_id),
                    _soft_pin(n3.node_id)]
            refs = [work.options(
                scheduling_strategy=pins[i % 3] or "DEFAULT",
                max_retries=0).remote(i) for i in range(9)]
            # Interleave actor calls with the fan-out.
            acks = [sink.add.remote(1) for _ in range(3)]
            results.extend(ray_tpu.get(refs, timeout=120))
            ray_tpu.get(acks, timeout=120)
    finally:
        kills = killer.stop()

    assert sorted(results) == sorted(list(range(9)) * 4)
    assert kills >= 1, "chaos never preempted a node"
    # Zero reconstructions: every migration was proactive.
    assert rt.lineage_reconstructions == recon0
    # The preempted nodes really are gone once in-flight drains
    # settle (killer.stop() can return with a drain still running).
    deadline = time.time() + 30
    while time.time() < deadline:
        alive = [n for n in ray_tpu.nodes()
                 if n["Alive"] and not n["IsHead"]]
        if (rt.drains_started == rt.drains_completed
                and not any(n["Draining"] for n in alive)):
            break
        time.sleep(0.2)
    assert rt.drains_started >= 1
    assert len(alive) == 2 - rt.drains_started


# ---------------------------------------------------------------------------
# train: drain-triggered gang interruption is budget-free
# ---------------------------------------------------------------------------

def test_drain_gang_restart_does_not_consume_max_failures(
        tmp_path, monkeypatch):
    from ray_tpu.train.config import FailureConfig, RunConfig
    from ray_tpu.train.trainer import (
        JaxTrainer,
        Result,
        _WorkerGroupError,
    )

    trainer = JaxTrainer(
        lambda: None,
        run_config=RunConfig(storage_path=str(tmp_path),
                             failure_config=FailureConfig(
                                 max_failures=0)))
    calls = []

    def fake_fit_once(trial_dir, restored):
        calls.append(restored)
        if len(calls) == 1:
            raise _WorkerGroupError(
                "actor abc is dead: node node_0003 drained: "
                "preemption notice", None)
        return Result(metrics={"ok": 1}, checkpoint_dir=None,
                      path=trial_dir)

    monkeypatch.setattr(trainer, "_fit_once", fake_fit_once)
    res = trainer.fit()
    # max_failures=0 would normally fail on the first interruption;
    # the drain-triggered one restarts for free.
    assert res.error is None
    assert res.metrics == {"ok": 1}
    assert len(calls) == 2


def test_real_crash_still_consumes_max_failures(tmp_path, monkeypatch):
    from ray_tpu.train.config import FailureConfig, RunConfig
    from ray_tpu.train.trainer import JaxTrainer, _WorkerGroupError

    trainer = JaxTrainer(
        lambda: None,
        run_config=RunConfig(storage_path=str(tmp_path),
                             failure_config=FailureConfig(
                                 max_failures=0)))

    def fake_fit_once(trial_dir, restored):
        raise _WorkerGroupError("worker process died (pid=1)", None)

    monkeypatch.setattr(trainer, "_fit_once", fake_fit_once)
    res = trainer.fit()
    assert res.error is not None          # budget consumed, surfaced


def _tpu_loop(config):
    import json
    import tempfile

    from ray_tpu.train import Checkpoint, get_context, report

    ctx = get_context()
    start = 0
    if ctx.restored_checkpoint_dir:
        with open(os.path.join(ctx.restored_checkpoint_dir,
                               "state.json")) as f:
            start = json.load(f)["step"] + 1
    for i in range(start, config["steps"]):
        d = tempfile.mkdtemp()
        with open(os.path.join(d, "state.json"), "w") as f:
            json.dump({"step": i}, f)
        report({"step": i, "restored": bool(ctx.restored_checkpoint_dir)},
               checkpoint=Checkpoint.from_directory(d))
        if not ctx.restored_checkpoint_dir:
            time.sleep(0.2)     # the first slice is slow enough to drain


def test_tpu_gang_restarts_onto_a_slice_that_arrives_after_the_drain(
        rt, tmp_path):
    """The one TPU node is drained mid-run and its replacement comes up
    only seconds later: while the cluster holds no chip the restarted
    gang must wait on its placement group — the demand an autoscaler
    reads — and resume from the checkpoint, with max_failures=0
    untouched. A check of the chips alive right now would fail it."""
    import threading

    from ray_tpu import train
    from ray_tpu.train.config import FailureConfig, RunConfig

    first = rt.add_node({"CPU": 2.0, "TPU": 1.0})
    trial = tmp_path / "tpu_drain"
    seen = {}

    def preempt_then_replace():
        deadline = time.time() + 60
        while time.time() < deadline and not any(
                p.name.startswith("checkpoint_")
                for p in trial.glob("*")):
            time.sleep(0.05)
        seen["drained"] = rt.drain_node(
            first, reason="preemption notice", deadline_s=10,
            remove=True)
        seen["tpus_between"] = ray_tpu.cluster_resources().get("TPU", 0)
        time.sleep(2.0)
        seen["demand"] = rt.resource_demand()
        rt.add_node({"CPU": 2.0, "TPU": 1.0})

    chaos = threading.Thread(target=preempt_then_replace, daemon=True)
    chaos.start()
    result = train.JaxTrainer(
        _tpu_loop, train_loop_config={"steps": 40},
        scaling_config=train.ScalingConfig(
            num_workers=1, tpu_chips_per_worker=1),
        run_config=RunConfig(
            name="tpu_drain", storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=0))).fit()
    chaos.join(timeout=30)
    assert seen["drained"] and seen["tpus_between"] == 0, seen
    # The waiting gang was visible as demand while no node held a chip.
    assert {"CPU": 1.0, "TPU": 1.0} in seen["demand"], seen
    assert result.error is None, result.error
    assert result.metrics == {"step": 39, "restored": True}


# ---------------------------------------------------------------------------
# serve: replicas leave a draining node ahead of the kill
# ---------------------------------------------------------------------------

def test_serve_drain_replaces_replica():
    ray_tpu.init(num_cpus=4)
    try:
        rt = ray_tpu.core.api.get_runtime()
        # Two nodes carry the replica-only resource; the deployment
        # must land on one of them, and the replacement on the other.
        n2 = rt.add_node({"CPU": 2.0, "R2": 1.0})
        n3 = rt.add_node({"CPU": 2.0, "R2": 1.0})
        from ray_tpu import serve

        @serve.deployment(num_replicas=1,
                          ray_actor_options={"resources": {"R2": 1.0}})
        class Echo:
            def __call__(self, x):
                return x

        handle = serve.run(Echo.bind())
        assert ray_tpu.get(handle.remote(7), timeout=90) == 7

        def replica_nodes():
            return {rec.node_id for rec in rt._actors.values()
                    if rec.cls_name == "Replica"
                    and rec.state == "ALIVE"}

        homes = replica_nodes()
        assert homes and homes <= {n2, n3}
        victim = homes.pop()
        other = n3 if victim == n2 else n2

        assert rt.drain_node(victim, reason="scale-down",
                             deadline_s=20)
        # The controller's reconcile loop replaces the replica on a
        # surviving node; requests keep succeeding throughout.
        deadline = time.time() + 60
        moved = False
        while time.time() < deadline:
            assert ray_tpu.get(handle.remote(1), timeout=90) == 1
            if replica_nodes() == {other}:
                moved = True
                break
            time.sleep(0.25)
        assert moved, (
            f"replica never moved off draining node: "
            f"{replica_nodes()}")
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
