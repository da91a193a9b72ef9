"""Scale envelope: the one-host production-scale contract.

Small-N variants run in tier-1 (100 actors / 5k tasks / 50 PGs /
8 logical nodes); the full envelope (1,000 actors, 100k tasks,
500 PGs, 32 nodes over 8 daemons, 1 GiB broadcast, chaos overlay)
runs behind ``-m scale`` via scripts/run_scale.sh
(scripts/scale_driver.py).

Also here: the admission/backpressure contract (ST_BUSY engages at a
low watermark, queue depth stays bounded, light clients progress
through a flood) and the pending-queue bookkeeping invariant audit
(config.debug_pending_invariants) guarding the inline hand-back /
re-enqueue paths.
"""

import threading
import time

import pytest

import ray_tpu
from ray_tpu.core import protocol as P
from ray_tpu.core.api import get_runtime
from ray_tpu.core.config import env_overrides
from ray_tpu.core.worker import ClientRuntime

# ---------------------------------------------------------------------------
# shared waves (small-N tier-1 and full-N -m scale use the same code)
# ---------------------------------------------------------------------------


@ray_tpu.remote(num_cpus=1)
def _echo_task(i):
    return i


@ray_tpu.remote(num_cpus=0)
class _EchoActor:
    def ping(self, i):
        return i


def _drain_tasks(n: int, timeout: float, chunk: int = 20000) -> None:
    """Submit n tasks (in bounded chunks) and assert every result."""
    done = 0
    while done < n:
        k = min(chunk, n - done)
        refs = [_echo_task.remote(done + j) for j in range(k)]
        vals = ray_tpu.get(refs, timeout=timeout)
        assert vals == list(range(done, done + k)), \
            f"task drain lost results in chunk at {done}"
        done += k


def _actor_waves(n: int, wave: int, timeout: float) -> None:
    """Create n actors in waves, call each once, assert, kill."""
    done = 0
    while done < n:
        k = min(wave, n - done)
        handles = [_EchoActor.remote() for _ in range(k)]
        vals = ray_tpu.get(
            [h.ping.remote(done + j) for j, h in enumerate(handles)],
            timeout=timeout)
        assert vals == list(range(done, done + k)), \
            f"actor wave lost calls at {done}"
        for h in handles:
            ray_tpu.kill(h)
        done += k


def _pg_waves(n: int, wave: int) -> None:
    from ray_tpu.util import placement_group, remove_placement_group
    made = 0
    while made < n:
        k = min(wave, n - made)
        pgs = [placement_group([{"CPU": 0.001}]) for _ in range(k)]
        for pg in pgs:
            assert pg.ready(timeout=120), "pg never became ready"
        for pg in pgs:
            remove_placement_group(pg)
        made += k


def _assert_quiescent(rt_obj) -> None:
    """Post-wave bookkeeping: queues empty, per-client admission
    accounting drained, invariants hold."""
    deadline = time.monotonic() + 30
    while rt_obj.pending_count() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert rt_obj.pending_count() == 0
    with rt_obj._res_cv:
        rt_obj._check_pending_invariants_locked()
    # note_dequeued pops empty keys; a leak here means admission
    # accounting drifted from the queues.
    assert not rt_obj.admission.client_pending, \
        rt_obj.admission.client_pending


# ---------------------------------------------------------------------------
# tier-1 small-N envelope
# ---------------------------------------------------------------------------

def test_task_drain_5k_zero_loss(rt):
    _drain_tasks(5000, timeout=600)
    _assert_quiescent(get_runtime())


def test_actors_create_call_100_zero_loss(rt):
    _actor_waves(100, wave=25, timeout=300)
    _assert_quiescent(get_runtime())


def test_pg_create_50(rt):
    _pg_waves(50, wave=50)
    rt_obj = get_runtime()
    assert not rt_obj._pgs, "placement groups leaked"
    _assert_quiescent(rt_obj)


def test_logical_nodes_8_spread(rt):
    rt_obj = get_runtime()
    for i in range(8):
        rt_obj.add_node({"CPU": 2.0}, labels={"scale": f"n{i}"})
    assert sum(1 for n in ray_tpu.nodes() if n["Alive"]) >= 9
    _drain_tasks(48, timeout=300)
    _assert_quiescent(rt_obj)


# ---------------------------------------------------------------------------
# admission / backpressure
# ---------------------------------------------------------------------------

def test_admission_fairness_policy():
    """Policy unit contract: per-client fair share below the
    watermark, light-clients-only between high and hard, everything
    sheds at the hard cap."""
    from ray_tpu.core.admission import AdmissionController
    from ray_tpu.core.config import get_config

    with env_overrides(head_pending_high_water=40,
                       admission_hard_factor=1.25,
                       admission_fair_fraction=0.5):
        ac = AdmissionController(get_config())
    assert (ac.high, ac.hard) == (40, 50)
    ac.client_pending = {"flooder": 30, "light": 2}
    # Over the watermark: flooder (30 >= 40//2) sheds, light lands.
    assert ac.check(45, "flooder", P.OP_SUBMIT) is not None
    assert ac.check(45, "light", P.OP_SUBMIT) is None
    # At the hard cap everything submit-class sheds.
    assert ac.check(50, "light", P.OP_SUBMIT) is not None
    # Below the watermark a hog sheds early while others are active.
    assert ac.check(30, "flooder", P.OP_SUBMIT) is not None
    assert ac.check(30, "light", P.OP_SUBMIT) is None
    # Retry hints scale with overload depth.
    assert ac.check(80, "light", P.OP_SUBMIT) > \
        ac.check(50, "light", P.OP_SUBMIT)
    # One active client alone is never fairness-shed under the mark.
    ac.client_pending = {"solo": 39}
    assert ac.check(39, "solo", P.OP_SUBMIT) is None


def test_backpressure_engages_and_bounds_queue():
    """With a low watermark, a wire-client flood must see ST_BUSY
    (retried transparently by the client), the head queue must stay
    near the hard cap, and every task must still complete."""
    with env_overrides(head_pending_high_water=60,
                       admission_retry_after_s=0.01,
                       admission_driver_block_s=0.5):
        ray_tpu.init(num_cpus=2)
        try:
            rt_obj = get_runtime()

            @ray_tpu.remote(num_cpus=1)
            def slow(i):
                time.sleep(0.005)
                return i

            from ray_tpu.core.remote_function import make_task_options
            fn_id, fn_blob = rt_obj.register_function(slow._fn)
            client = ClientRuntime(rt_obj.client_address)
            peak = [0]
            stop = threading.Event()

            def sample():
                while not stop.wait(0.002):
                    peak[0] = max(peak[0], rt_obj.pending_count())

            t = threading.Thread(target=sample, daemon=True)
            t.start()
            try:
                refs = []
                for i in range(400):
                    refs.extend(client.submit_task(
                        fn_id, fn_blob, "slow", (i,), {},
                        make_task_options()))
                vals = client.get(refs, timeout=300)
                assert vals == list(range(400)), \
                    "backpressure lost submits"
            finally:
                stop.set()
                t.join(timeout=2)
                client.shutdown()
            assert rt_obj.admission.rejected > 0, \
                "flood never tripped admission"
            # Bounded: hard cap plus in-flight slack (decisions read
            # the depth lock-free; a batch already on the wire lands).
            assert peak[0] <= rt_obj.admission.hard + 128, (
                f"queue peaked at {peak[0]} with hard cap "
                f"{rt_obj.admission.hard}")
            _assert_quiescent(rt_obj)
        finally:
            ray_tpu.shutdown()


def test_fairness_light_client_progresses_through_flood():
    """While one client floods a low-watermark head, a second client
    submitting a single task must complete it while the flood is
    still draining — light clients keep making progress."""
    with env_overrides(head_pending_high_water=40,
                       admission_retry_after_s=0.01):
        ray_tpu.init(num_cpus=2)
        try:
            rt_obj = get_runtime()

            @ray_tpu.remote(num_cpus=1)
            def slow(i):
                time.sleep(0.02)
                return i

            from ray_tpu.core.remote_function import make_task_options
            fn_id, fn_blob = rt_obj.register_function(slow._fn)
            flooder = ClientRuntime(rt_obj.client_address)
            light = ClientRuntime(rt_obj.client_address)
            flood_refs: list = []
            flood_err: list = []

            def flood():
                try:
                    for i in range(400):
                        flood_refs.extend(flooder.submit_task(
                            fn_id, fn_blob, "slow", (i,), {},
                            make_task_options()))
                except Exception as e:  # noqa: BLE001
                    flood_err.append(e)

            ft = threading.Thread(target=flood, daemon=True)
            ft.start()
            try:
                # Let the flood saturate the watermark first.
                deadline = time.monotonic() + 30
                while (rt_obj.pending_count() < 40
                       and time.monotonic() < deadline):
                    time.sleep(0.01)
                ref = light.submit_task(
                    fn_id, fn_blob, "slow", (9999,), {},
                    make_task_options())[0]
                assert light.get(ref, timeout=120) == 9999
                # Progress THROUGH the flood, not after it.
                assert rt_obj.pending_count() > 0 or ft.is_alive(), \
                    "flood finished before the light client — " \
                    "fairness unobserved"
            finally:
                ft.join(timeout=120)
                assert not flood_err, flood_err
                vals = flooder.get(flood_refs, timeout=300)
                assert vals == list(range(400)), \
                    "fairness flood lost submits"
                flooder.shutdown()
                light.shutdown()
            assert rt_obj.admission.rejected > 0
            _assert_quiescent(rt_obj)
        finally:
            ray_tpu.shutdown()


def test_status_surfaces_head_admission_state(rt):
    """cluster_status carries the head section (queue depth,
    admission state, watermark, loop lag) and the CLI renderer shows
    it — the ``ray_tpu status`` surface."""
    rt_obj = get_runtime()
    cs = rt_obj.cluster_status()
    h = cs["head"]
    assert h["state"] in ("OK", "BUSY")
    assert h["high_water"] >= 1
    assert h["queue_depth"] == rt_obj.pending_count()
    assert "loop_lag_ms" in h
    from ray_tpu.observability.introspect import format_cluster_status
    text = format_cluster_status(cs)
    assert "admission=" in text and "head:" in text


# ---------------------------------------------------------------------------
# chaos overlay: zero loss with a node killed mid-drain
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_zero_loss_drain_under_node_kill():
    """Kill a daemon node DURING a task drain: every task still
    returns its value (retries + lineage cover the loss)."""
    from ray_tpu.cluster_utils import Cluster
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2})
    try:
        node = cluster.add_node(num_cpus=2)
        rt_obj = get_runtime()

        @ray_tpu.remote(num_cpus=1)
        def work(i):
            time.sleep(0.02)
            return i

        refs = [work.remote(i) for i in range(300)]
        # Let a wave land on the doomed node, then kill it cold.
        time.sleep(0.5)
        rt_obj.remove_node(node.node_id)
        vals = ray_tpu.get(refs, timeout=300)
        assert sorted(vals) == list(range(300)), \
            "node kill lost tasks"
        _assert_quiescent(rt_obj)
    finally:
        cluster.shutdown()


# ---------------------------------------------------------------------------
# pending-queue bookkeeping: invariant audit + hand-back regression
# ---------------------------------------------------------------------------

def test_inline_hand_back_requeues_without_drift(rt):
    """Regression for the inline-dispatch hand-back: a picked record
    returned to the queue front must restore every bookkeeping view
    (count, per-class totals, admission accounting) and still run."""
    rt_obj = get_runtime()

    @ray_tpu.remote(num_cpus=1, resources={"widget": 1})
    def needs_widget():
        return 42

    ref = needs_widget.remote()
    deadline = time.monotonic() + 30
    while not rt_obj.pending_count() and time.monotonic() < deadline:
        time.sleep(0.01)
    with rt_obj._res_cv:
        assert rt_obj._ready_classes, "task never queued"
        klass, q = next(iter(rt_obj._ready_classes.items()))
        rec = rt_obj._ready_pop_locked(klass, q)
        # The hand-back path under test: re-enqueue at the front.
        rt_obj._pending_readd_front_locked(rec)
        rt_obj._check_pending_invariants_locked()
        assert rt_obj._pending_count == 1
    rt_obj.add_node({"CPU": 1.0, "widget": 1.0})
    assert ray_tpu.get(ref, timeout=120) == 42
    _assert_quiescent(rt_obj)


def test_pending_invariant_audit_under_flood():
    """debug_pending_invariants=True turns on the per-mutation audit;
    a concurrent flood + dep chains + cancels must finish with every
    view of the pending set agreeing (drift raises AssertionError
    inside the scheduler the moment it happens)."""
    with env_overrides(debug_pending_invariants=True):
        ray_tpu.init(num_cpus=2)
        try:
            rt_obj = get_runtime()

            @ray_tpu.remote(num_cpus=1)
            def leaf(i):
                return i

            @ray_tpu.remote(num_cpus=1)
            def join(a, b):
                return a + b

            @ray_tpu.remote(num_cpus=1, resources={"never": 1})
            def unplaceable():
                return -1

            refs = []
            for i in range(0, 60, 2):
                refs.append(join.remote(leaf.remote(i),
                                        leaf.remote(i + 1)))
            doomed = [unplaceable.remote() for _ in range(10)]
            for d in doomed:
                ray_tpu.cancel(d)
            vals = ray_tpu.get(refs, timeout=300)
            assert vals == [i + i + 1 for i in range(0, 60, 2)]
            for d in doomed:
                with pytest.raises(Exception):
                    ray_tpu.get(d, timeout=30)
            _assert_quiescent(rt_obj)
            assert not rt_obj._pending_classes
        finally:
            ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# full-N envelope (scripts/run_scale.sh: pytest -m scale)
# ---------------------------------------------------------------------------

@pytest.mark.scale
@pytest.mark.slow
def test_scale_task_drain_100k(rt):
    _drain_tasks(100_000, timeout=1800)
    _assert_quiescent(get_runtime())


@pytest.mark.scale
@pytest.mark.slow
def test_scale_actors_1000(rt):
    _actor_waves(1000, wave=50, timeout=600)
    _assert_quiescent(get_runtime())


@pytest.mark.scale
@pytest.mark.slow
def test_scale_pgs_500(rt):
    _pg_waves(500, wave=100)
    rt_obj = get_runtime()
    assert not rt_obj._pgs
    _assert_quiescent(rt_obj)


@pytest.mark.scale
@pytest.mark.slow
def test_scale_nodes_32_over_8_daemons():
    """32 logical nodes over 8 daemon processes, all schedulable."""
    from ray_tpu.cluster_utils import Cluster
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2})
    try:
        for _ in range(8):
            cluster.add_node(num_cpus=1)
        rt_obj = get_runtime()
        for i in range(23):
            rt_obj.add_node({"CPU": 1.0},
                            labels={"scale": f"logical{i}"})
        assert sum(1 for n in ray_tpu.nodes() if n["Alive"]) >= 32
        _drain_tasks(200, timeout=600)
        _assert_quiescent(rt_obj)
    finally:
        cluster.shutdown()
