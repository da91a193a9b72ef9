"""Cluster observability plane tests.

Reference analogs: python/ray/tests/test_metrics_agent.py (worker ->
agent -> Prometheus pipeline), test_task_events.py (TaskEventBuffer ->
GcsTaskManager), test_state_api.py (detail listings, timeline).

Covers: worker->head metric flush (same-host and daemon-node workers),
cross-process histogram bucket merge, golden Prometheus exposition,
series staleness after drain_node, the cluster timeline's remote
events/spans, the metric re-registration satellite, and the NodeAgent
sampling-thread hardening.
"""

import time

import pytest

import ray_tpu
from ray_tpu.core.config import env_overrides
from ray_tpu.util import state as state_api
from ray_tpu.util.metrics import (
    Counter, Gauge, Histogram, reset_registry,
)


def _wait_for(fn, timeout=20.0, interval=0.25):
    """Poll fn() until truthy; return its last value.

    Load-gated (same signal as conftest.perf_floor_gate): on an
    oversubscribed host the exporter flush threads are starved of
    scheduler slices, so the asserted state arrives late, not never —
    stretch the deadline instead of flaking (tier-1 seed failure:
    cluster-scrape timing out under driver load)."""
    from conftest import LOAD_SOFT, host_load_factor
    if host_load_factor() > LOAD_SOFT:
        timeout *= 4.0
    deadline = time.monotonic() + timeout
    val = fn()
    while not val and time.monotonic() < deadline:
        time.sleep(interval)
        val = fn()
    return val


@pytest.fixture
def obs_rt():
    """Single-node multiprocess runtime with a fast exporter flush."""
    with env_overrides(metrics_report_interval_s=0.2):
        ray_tpu.init(num_cpus=4)
        yield ray_tpu.core.api.get_runtime()
        ray_tpu.shutdown()


@pytest.fixture
def obs_cluster():
    """Head + one daemon-backed node, fast exporter flush."""
    from ray_tpu.cluster_utils import Cluster
    with env_overrides(metrics_report_interval_s=0.2):
        cluster = Cluster(head_node_args={"num_cpus": 2})
        node = cluster.add_node(num_cpus=2)
        yield cluster, node
        cluster.shutdown()


# ---------------- worker -> head flush ----------------

def test_worker_counter_reaches_cluster_scrape(obs_rt):
    @ray_tpu.remote(num_cpus=1)
    def bump():
        Counter("pipeline_probe_total", "probe").inc()
        return 1

    assert sum(ray_tpu.get([bump.remote() for _ in range(3)],
                           timeout=60)) == 3
    def probe_line():
        return next((ln for ln in
                     obs_rt.observability.prometheus_text().splitlines()
                     if ln.startswith("pipeline_probe_total{")), "")

    # The three increments may come from up to three worker processes,
    # each with its own exporter and flush phase: the series appears
    # with the first push and reaches 3 with the last, so wait for the
    # value, not for the series.
    def probe_value():
        return float((probe_line() or "x 0").rsplit(" ", 1)[1])

    _wait_for(lambda: probe_value() == 3.0)
    line = probe_line()
    assert line, "worker counter never reached the head aggregator"
    # Attribution: the series carries the node that ran the task.
    assert 'node_id="' in line
    # All three increments survived the cumulative merge.
    assert float(line.rsplit(" ", 1)[1]) == 3.0


def test_remote_node_counter_and_task_detail(obs_cluster):
    """Acceptance: a counter incremented inside a remote (non-head)
    task appears in the cluster scrape tagged with that node's id,
    and list_tasks(detail=True) shows lifecycle events for the task
    including worker-side execution events from that node."""
    cluster, node = obs_cluster
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    @ray_tpu.remote(num_cpus=1)
    def remote_bump():
        Counter("remote_node_probe_total", "probe").inc()
        return ray_tpu.get_runtime_context().get_node_id()

    pin = NodeAffinitySchedulingStrategy(node.node_id)
    ran_on = ray_tpu.get(
        [remote_bump.options(scheduling_strategy=pin).remote()
         for _ in range(2)], timeout=120)
    assert set(ran_on) == {node.node_id}

    rt = ray_tpu.core.api.get_runtime()

    head = f'remote_node_probe_total{{node_id="{node.node_id}"}}'

    def probe_line():
        lines = rt.observability.prometheus_text().splitlines()
        return next((ln for ln in lines if ln.startswith(head)), None)

    assert _wait_for(probe_line), \
        "remote node's counter never reached the head"
    # The two tasks may run in two workers whose exporters flush apart:
    # the line reads 1 between the two pushes, so wait for the merge.
    _wait_for(lambda: float(probe_line().rsplit(" ", 1)[1]) >= 2.0)
    assert float(probe_line().rsplit(" ", 1)[1]) == 2.0

    def remote_detail():
        rows = state_api.list_tasks(detail=True)
        for row in rows:
            if row["name"] != "remote_bump":
                continue
            if any(e["src"] == "worker"
                   and e["node_id"] == node.node_id
                   for e in row["events"]):
                return row
        return None

    row = _wait_for(remote_detail)
    assert row, "no worker-side lifecycle events for the remote task"
    assert row["node_id"] == node.node_id
    states = {e["state"] for e in row["events"]}
    assert {"RUNNING", "FINISHED"} <= states


def test_cross_process_histogram_bucket_merge(obs_rt):
    """Two actor processes observe into the same histogram; the
    cluster scrape must show the bucket-summed series."""
    @ray_tpu.remote(num_cpus=1)
    class Observer:
        def observe(self, values):
            h = Histogram("merge_probe_s", "probe",
                          boundaries=[0.1, 1.0])
            for v in values:
                h.observe(v)
            import os
            return os.getpid()

    a, b = Observer.remote(), Observer.remote()
    pids = ray_tpu.get([a.observe.remote([0.05, 0.5]),
                        b.observe.remote([0.5, 5.0])], timeout=120)
    assert pids[0] != pids[1], "need two distinct processes"

    rt = obs_rt

    def merged_count():
        text = rt.observability.prometheus_text()
        for ln in text.splitlines():
            if ln.startswith("merge_probe_s_count{"):
                if float(ln.rsplit(" ", 1)[1]) == 4.0:
                    return text
        return None

    text = _wait_for(merged_count)
    assert text, "histogram never merged to 4 observations"
    lines = {ln.rsplit(" ", 1)[0]: float(ln.rsplit(" ", 1)[1])
             for ln in text.splitlines()
             if ln.startswith("merge_probe_s")}
    nid = rt.head_node_id
    assert lines[f'merge_probe_s_bucket{{le="0.1",node_id="{nid}"}}'] \
        == 1
    assert lines[f'merge_probe_s_bucket{{le="1.0",node_id="{nid}"}}'] \
        == 3
    assert lines[
        f'merge_probe_s_bucket{{le="+Inf",node_id="{nid}"}}'] == 4
    assert lines[f'merge_probe_s_sum{{node_id="{nid}"}}'] == \
        pytest.approx(6.05)


# ---------------- aggregator unit: golden exposition ----------------

def test_prometheus_exposition_golden():
    from ray_tpu.observability.aggregator import (
        ClusterMetricsAggregator,
    )
    agg = ClusterMetricsAggregator()
    counter_row = {
        "name": "req_total", "type": "counter", "desc": "requests",
        "series": [((("route", "/a"),), 2.0)],
    }
    hist_row = {
        "name": "lat_s", "type": "histogram", "desc": "latency",
        "boundaries": [0.1, 1.0],
        "series": [((), [1, 1, 0], 0.55, 2)],
    }
    gauge_row = {
        "name": "depth", "type": "gauge", "desc": "queue depth",
        "series": [((), 3.0)],
    }
    agg.ingest("nodeA", "w1", [counter_row, hist_row, gauge_row], 1.0)
    # Second worker on the same node: counters/histograms sum, the
    # newer gauge wins.
    gauge_row2 = dict(gauge_row, series=[((), 7.0)])
    agg.ingest("nodeA", "w2", [counter_row, hist_row, gauge_row2], 2.0)
    golden = "\n".join([
        '# HELP depth queue depth',
        '# TYPE depth gauge',
        'depth{node_id="nodeA"} 7',
        '# HELP lat_s latency',
        '# TYPE lat_s histogram',
        'lat_s_bucket{le="0.1",node_id="nodeA"} 2',
        'lat_s_bucket{le="1.0",node_id="nodeA"} 4',
        'lat_s_bucket{le="+Inf",node_id="nodeA"} 4',
        'lat_s_sum{node_id="nodeA"} 1.1',
        'lat_s_count{node_id="nodeA"} 4',
        '# HELP req_total requests',
        '# TYPE req_total counter',
        'req_total{node_id="nodeA",route="/a"} 4',
    ]) + "\n"
    assert agg.prometheus_text() == golden


def test_aggregator_stale_and_revive():
    from ray_tpu.observability.aggregator import (
        ClusterMetricsAggregator,
    )
    agg = ClusterMetricsAggregator()
    row = {"name": "m_total", "type": "counter", "desc": "",
           "series": [((), 1.0)]}
    agg.ingest("nodeA", "w1", [row], 1.0)
    assert "m_total" in agg.prometheus_text()
    agg.mark_node_stale("nodeA")
    assert "m_total{" not in agg.prometheus_text()
    assert agg.stale_series_count() == 1
    agg.mark_node_live("nodeA")
    assert 'm_total{node_id="nodeA"} 1' in agg.prometheus_text()


# ---------------- staleness after drain ----------------

def test_series_stale_after_drain_node(obs_cluster):
    cluster, node = obs_cluster
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    @ray_tpu.remote(num_cpus=1)
    def bump():
        Counter("drain_probe_total", "probe").inc()
        return 1

    pin = NodeAffinitySchedulingStrategy(node.node_id)
    assert ray_tpu.get(
        bump.options(scheduling_strategy=pin).remote(), timeout=120) \
        == 1
    rt = ray_tpu.core.api.get_runtime()
    series = f'drain_probe_total{{node_id="{node.node_id}"}}'
    assert _wait_for(
        lambda: series in rt.observability.prometheus_text()), \
        "probe series never appeared before the drain"

    assert rt.drain_node(node.node_id, reason="test drain",
                         deadline_s=30.0, remove=True)
    assert node.node_id in rt.observability.aggregator.stale_nodes()
    text = rt.observability.prometheus_text()
    assert series not in text, \
        "drained node's series still in the scrape"


# ---------------- cluster timeline ----------------

def test_cluster_timeline_remote_events_and_spans(obs_cluster):
    cluster, node = obs_cluster
    from ray_tpu.util import tracing
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    tracing.enable()
    try:
        @ray_tpu.remote(num_cpus=1)
        def traced_work(x):
            time.sleep(0.01)
            return x

        pin = NodeAffinitySchedulingStrategy(node.node_id)
        with tracing.span("driver_root"):
            vals = ray_tpu.get(
                [traced_work.options(
                    scheduling_strategy=pin).remote(i)
                 for i in range(2)], timeout=120)
        assert vals == [0, 1]

        rt = ray_tpu.core.api.get_runtime()

        def remote_slice():
            return [e for e in rt.timeline()
                    if e.get("cat") == "worker_task"
                    and e.get("pid") == node.node_id
                    and e.get("name") == "traced_work"]

        evs = _wait_for(remote_slice)
        assert evs, "no remote worker execution slices in timeline"
        assert all(e["ph"] == "X" and e["dur"] >= 0 for e in evs)

        def remote_span():
            return [e for e in rt.timeline()
                    if e.get("cat") == "span"
                    and "traced_work" in str(e.get("name"))]

        spans = _wait_for(remote_span)
        assert spans, "remote task span missing from cluster timeline"
    finally:
        tracing.disable()


# ---------------- serve built-in instrumentation ----------------

def test_serve_latency_histogram_in_cluster_metrics(obs_rt):
    from ray_tpu import serve

    @serve.deployment(num_replicas=1)
    class Echo:
        def __call__(self, x):
            return x

    handle = serve.run(Echo.bind())
    try:
        assert ray_tpu.get(handle.remote(42), timeout=60) == 42
        rt = obs_rt

        def scraped():
            text = rt.observability.prometheus_text()
            if ("ray_tpu_serve_request_latency_s_bucket{" in text
                    and 'deployment="Echo"' in text
                    and "ray_tpu_serve_router_requests_total" in text):
                return text
            return None

        text = _wait_for(scraped)
        assert text, "serve metrics never reached the cluster scrape"
        line = next(
            ln for ln in text.splitlines()
            if ln.startswith("ray_tpu_serve_request_latency_s_count")
            and 'deployment="Echo"' in ln)
        assert float(line.rsplit(" ", 1)[1]) >= 1
        assert 'node_id="' in line
    finally:
        serve.shutdown()


# ---------------- satellites ----------------

def test_metric_reregistration_preserves_values():
    reset_registry()
    try:
        c1 = Counter("rereg_total", "first")
        c1.inc(3)
        c2 = Counter("rereg_total", "second")
        c2.inc()
        # Shared accumulators: both views see all 4 increments.
        assert sum(v for _t, v in c1.collect()) == 4.0
        assert sum(v for _t, v in c2.collect()) == 4.0
        h1 = Histogram("rereg_lat_s", "", boundaries=[0.5])
        h1.observe(0.1)
        h2 = Histogram("rereg_lat_s", "")
        h2.observe(0.2)
        assert h2.boundaries == [0.5]
        (_tags, (buckets, s, n)), = h2.collect_histogram().items()
        assert n == 2 and buckets[0] == 2
        with pytest.raises(ValueError):
            Gauge("rereg_total", "type clash")
    finally:
        reset_registry()


def test_node_agent_survives_raising_report_fn():
    from ray_tpu.dashboard.agent import NodeAgent

    calls = []

    def report(stats):
        calls.append(stats)
        if len(calls) <= 2:
            raise RuntimeError("transient sink failure")

    agent = NodeAgent(report, node_id="t", interval_s=0.05)
    agent.start()
    try:
        deadline = time.monotonic() + 20
        while len(calls) < 4 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert len(calls) >= 4, \
            "sampling thread died after report_fn raised"
        assert agent._thread.is_alive()
    finally:
        agent.stop()


def test_cli_metrics_cluster_and_local(obs_rt):
    import os
    import subprocess
    import sys

    @ray_tpu.remote(num_cpus=1)
    def bump():
        Counter("cli_probe_total", "probe").inc()
        return 1

    assert ray_tpu.get(bump.remote(), timeout=60) == 1
    # A driver-process metric: proves the head's own live registry is
    # merged into the cluster scrape alongside worker snapshots.
    Counter("cli_driver_probe_total", "driver probe").inc()
    rt = obs_rt
    assert _wait_for(
        lambda: "cli_probe_total" in
        rt.observability.prometheus_text())
    env = dict(os.environ)
    env["PYTHONPATH"] = ":".join(p for p in sys.path if p)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "metrics",
         "--address", rt.client_address],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr[-1500:]
    assert "cli_probe_total" in out.stdout       # worker snapshot
    assert "cli_driver_probe_total" in out.stdout  # head registry
    # --local: only the calling process's registry (empty here).
    out = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "metrics",
         "--local"],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr[-1500:]
    assert "cli_probe_total" not in out.stdout


def test_dashboard_metrics_and_v1_timeline(obs_rt):
    import json as _json
    import urllib.request

    from ray_tpu.dashboard.head import start_dashboard

    @ray_tpu.remote(num_cpus=1)
    def dash_work():
        Counter("dash_probe_total", "probe").inc()
        return 1

    assert ray_tpu.get(dash_work.remote(), timeout=60) == 1
    rt = obs_rt
    assert _wait_for(
        lambda: "dash_probe_total" in
        rt.observability.prometheus_text())
    dash = start_dashboard(port=0)
    try:
        text = urllib.request.urlopen(
            dash.url + "/metrics", timeout=10).read().decode()
        assert "dash_probe_total{" in text
        assert 'node_id="' in text
        evs = _json.loads(urllib.request.urlopen(
            dash.url + "/api/v1/timeline", timeout=10).read())
        assert any(e.get("name") == "dash_work"
                   and e.get("ph") == "X" for e in evs)
    finally:
        dash.stop()
