"""Introspection & profiling plane tests (PR-4).

Covers: memory_summary agreeing with actual object counts/bytes
(including after a drain evacuates node-homed primaries),
cluster_status reflecting draining nodes and pending demand, the
worker-side OP_STATE verbs, the remote profiler round trip capturing
a known hot function from another process, speedscope/collapsed
golden-format checks, overlapping-session refusal, stack dumps, the
tracing requeue/drop satellite, histogram quantiles, and offset-
resumed log tailing.
"""

import threading
import time

import pytest

import ray_tpu
from ray_tpu.observability import profiler
from ray_tpu.util import state as state_api


def _wait_for(fn, timeout=20.0, interval=0.2):
    deadline = time.monotonic() + timeout
    val = fn()
    while not val and time.monotonic() < deadline:
        time.sleep(interval)
        val = fn()
    return val


@pytest.fixture
def intro_rt(rt):
    yield ray_tpu.core.api.get_runtime()


@pytest.fixture
def intro_cluster():
    """Head + one daemon-backed node (fast load reports so
    memory_summary sees the node store promptly)."""
    from ray_tpu.core.config import env_overrides
    from ray_tpu.cluster_utils import Cluster
    with env_overrides(rview_period_s=0.2):
        cluster = Cluster(head_node_args={"num_cpus": 2})
        node = cluster.add_node(num_cpus=2)
        yield cluster, node
        cluster.shutdown()


# ---------------- memory_summary ----------------

def test_memory_summary_counts_and_bytes(intro_rt):
    big = ray_tpu.put(b"B" * 300_000)          # -> shm
    small = ray_tpu.put(b"s" * 100)            # -> mem
    ms = intro_rt.memory_summary(top_n=10)
    assert ms["totals"]["objects"] >= 2
    assert ms["totals"]["bytes"] >= 300_000
    by_id = {r["object_id"]: r for r in ms["top_objects"]}
    big_row = by_id[big.id.hex()]
    assert big_row["location"] == "shm"
    assert big_row["size"] >= 300_000
    assert big_row["pinned"] and big_row["pins"]["local_refs"] == 1
    assert big_row["primary"]
    # The head node row attributes the bytes.
    head_row = [n for n in ms["nodes"] if n["is_head"]][0]
    assert head_row["objects"] >= 2
    assert head_row["object_bytes"] >= 300_000
    assert head_row["store_used_bytes"] >= 300_000
    del small


def test_memory_summary_release_removes_rows(intro_rt):
    ref = ray_tpu.put(b"x" * 200_000)
    oid_hex = ref.id.hex()
    assert any(r["object_id"] == oid_hex
               for r in intro_rt.memory_summary(
                   top_n=10_000)["top_objects"])
    del ref
    import gc
    gc.collect()
    assert _wait_for(lambda: not any(
        r["object_id"] == oid_hex
        for r in intro_rt.memory_summary(
            top_n=10_000)["top_objects"])), \
        "released object still in memory_summary"


def test_memory_summary_node_homed_and_drain_evacuation(
        intro_cluster):
    cluster, node = intro_cluster
    rt = ray_tpu.core.api.get_runtime()
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    @ray_tpu.remote(num_cpus=1)
    def make_big():
        return b"N" * (1 << 20)                # > shm threshold

    pin = NodeAffinitySchedulingStrategy(node.node_id, soft=False)
    ref = make_big.options(scheduling_strategy=pin).remote()
    ray_tpu.wait([ref], timeout=60)
    ms = rt.memory_summary(top_n=50)
    row = [r for r in ms["top_objects"]
           if r["object_id"] == ref.id.hex()][0]
    assert row["location"] == "node"
    assert row["node_id"] == node.node_id
    assert row["size"] >= (1 << 20)
    node_row = [n for n in ms["nodes"]
                if n["node_id"] == node.node_id][0]
    assert node_row["object_bytes"] >= (1 << 20)
    # Daemon load reports carry the local store occupancy.
    assert _wait_for(lambda: [
        n for n in rt.memory_summary(top_n=1)["nodes"]
        if n["node_id"] == node.node_id][0]
        .get("store_used_bytes", 0) >= (1 << 20), timeout=10)

    # Drain: the primary evacuates (zero-loss) and the summary
    # re-homes the bytes off the draining node.
    rt.drain_node(node.node_id, reason="introspection test",
                  deadline_s=30.0, remove=True)
    ms2 = rt.memory_summary(top_n=50)
    row2 = [r for r in ms2["top_objects"]
            if r["object_id"] == ref.id.hex()][0]
    assert row2["node_id"] != node.node_id
    assert row2["size"] >= (1 << 20)
    assert ray_tpu.get(ref, timeout=60) == b"N" * (1 << 20)


# ---------------- cluster_status ----------------

def test_cluster_status_counts_and_pending_demand(intro_rt):
    @ray_tpu.remote(num_cpus=1)
    def quick():
        return 1

    assert ray_tpu.get(quick.remote(), timeout=60) == 1

    # Saturate the 4 CPUs so the overflow tasks are visibly pending
    # demand (the autoscaler-intent block of cluster_status).
    @ray_tpu.remote(num_cpus=1)
    def blocker(seconds):
        import time as _t
        _t.sleep(seconds)
        return 1

    refs = [blocker.remote(30.0) for _ in range(8)]
    assert _wait_for(
        lambda: (lambda t: t["pending"] >= 1 and t["running"] >= 1)(
            intro_rt.cluster_status()["tasks"]),
        timeout=30), "no pending+running overflow mix observed"
    cs = intro_rt.cluster_status()
    assert cs["tasks"]["finished"] >= 1
    assert cs["tasks"]["running"] >= 1
    assert cs["autoscaler"]["demand_count"] >= 1
    shapes = [d["shape"] for d in cs["autoscaler"]["pending_demand"]]
    assert any(s.get("CPU") for s in shapes)
    head = [n for n in cs["nodes"] if n["is_head"]][0]
    assert head["state"] == "ALIVE"
    assert head["resources_total"].get("CPU", 0) > 0
    # Don't wait the blockers out — cancel them; the fixture's
    # shutdown reaps whatever force-cancel already killed.
    for r in refs:
        try:
            intro_rt.cancel(r, force=True)
        except Exception:  # noqa: BLE001
            pass


def test_cluster_status_reflects_draining_node(intro_cluster):
    cluster, node = intro_cluster
    rt = ray_tpu.core.api.get_runtime()
    done = threading.Event()

    # Drain WITHOUT remove so the DRAINING state is observable.
    def _drain():
        rt.drain_node(node.node_id, reason="status test",
                      deadline_s=20.0, remove=False)
        done.set()

    threading.Thread(target=_drain, daemon=True).start()
    assert _wait_for(lambda: any(
        n["state"] == "DRAINING" and n["drain_reason"] == "status test"
        for n in rt.cluster_status()["nodes"]), timeout=15), \
        "draining node not visible in cluster_status"
    done.wait(30)


def test_worker_side_state_verbs(intro_rt):
    """memory_summary/cluster_status reach worker-side clients over
    OP_STATE (the acceptance-criteria path: a remote client
    interrogating a live cluster)."""
    marker = ray_tpu.put(b"W" * 150_000)

    @ray_tpu.remote(num_cpus=1)
    def probe(oid_hex):
        from ray_tpu.util import state as state_api
        ms = state_api.memory_summary(top_n=10_000)
        cs = state_api.cluster_status()
        return (
            any(r["object_id"] == oid_hex
                for r in ms["top_objects"]),
            len(cs["nodes"]),
            cs["workers"]["total"],
        )

    found, n_nodes, n_workers = ray_tpu.get(
        probe.remote(marker.id.hex()), timeout=120)
    assert found, "worker-side memory_summary missed a live object"
    assert n_nodes >= 1
    assert n_workers >= 1
    del marker


# ---------------- remote profiler ----------------

@ray_tpu.remote(num_cpus=1)
def _burn(seconds):
    # The named inner frame is what the sampled flame graph must
    # show; cloudpickle ships the closure by value, so no import of
    # the test module is needed inside the worker.
    def _intro_hot_fn(secs):
        t0 = time.time()
        x = 0
        while time.time() - t0 < secs:
            x += 1
        return x

    return _intro_hot_fn(seconds)


def test_remote_profiler_captures_hot_function(intro_rt):
    ref = _burn.remote(8.0)
    # RUNNING is stamped at dispatch — additionally wait for the
    # worker process itself to boot and register as profilable.
    assert _wait_for(lambda: any(
        r["state"] == "RUNNING"
        for r in state_api.list_tasks()), timeout=30)
    assert _wait_for(lambda: intro_rt._profile_peers, timeout=30), \
        "no worker registered for profiling"
    res = intro_rt.profile_cluster(duration_s=0.8, hz=50)
    kinds = {p["kind"] for p in res["procs"] if p["ok"]}
    assert "head" in kinds and "worker" in kinds, res["procs"]
    hot = [s for s in res["collapsed"] if "_intro_hot_fn" in s]
    assert hot, ("worker hot function absent from merged flame "
                 "graph: %r" % list(res["collapsed"])[:5])
    # Per-proc attribution prefix survives the merge.
    assert all(s.split(";", 1)[0].startswith(("head:", "worker:",
                                              "daemon:"))
               for s in res["collapsed"])
    assert ray_tpu.get(ref, timeout=60) > 0


def test_profiler_round_trip_daemon_node(intro_cluster):
    cluster, node = intro_cluster
    rt = ray_tpu.core.api.get_runtime()
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    pin = NodeAffinitySchedulingStrategy(node.node_id, soft=False)
    ref = _burn.options(scheduling_strategy=pin).remote(8.0)
    assert _wait_for(lambda: any(
        r["state"] == "RUNNING"
        for r in state_api.list_tasks()), timeout=30)
    # Wait for the daemon-hosted worker's profile registration to
    # ride the client-channel splice up to the head.
    assert _wait_for(lambda: any(
        p["node_id"] == node.node_id
        for p in rt._profile_peers.values()), timeout=30), \
        "daemon-hosted worker never registered for profiling"
    res = rt.profile_cluster(duration_s=0.8, hz=50,
                             target=node.node_id)
    ok = [p for p in res["procs"] if p["ok"]]
    assert {p["kind"] for p in ok} == {"daemon", "worker"}, ok
    assert any("_intro_hot_fn" in s for s in res["collapsed"])
    # speedscope export of a real capture validates.
    doc = profiler.to_speedscope(
        [("merged", res["collapsed"], res["hz"])])
    assert doc["$schema"].startswith("https://www.speedscope.app")
    assert doc["profiles"][0]["samples"]
    assert ray_tpu.get(ref, timeout=60) > 0


def test_stack_dump_targets(intro_rt):
    rows = intro_rt.stack_dump(target="head")
    assert len(rows) == 1 and rows[0]["kind"] == "head"
    assert rows[0]["ok"]
    # The dump shows real frames of this process.
    assert "thread" in rows[0]["stacks"]
    assert f"pid {rows[0]['pid']}" in rows[0]["stacks"]


def test_profiler_refuses_overlapping_sessions():
    started = threading.Event()

    def long_sample():
        orig = profiler._fold_stack

        def folded(*a, **k):
            started.set()
            return orig(*a, **k)

        profiler._fold_stack = folded
        try:
            return profiler.sample_stacks(duration_s=1.2, hz=50)
        finally:
            profiler._fold_stack = orig

    t = threading.Thread(target=long_sample, daemon=True)
    t.start()
    assert started.wait(5), "sampler never ticked"
    assert profiler.is_active()
    with pytest.raises(profiler.ProfilerBusyError):
        profiler.sample_stacks(duration_s=0.1, hz=50)
    t.join(10)
    assert not profiler.is_active()
    # After the session ends, sampling works again.
    out = profiler.sample_stacks(duration_s=0.05, hz=100)
    assert out["samples"] >= 1


# ---------------- export format goldens ----------------

def test_collapsed_text_golden_and_round_trip():
    collapsed = {
        "thread:MainThread;outer (a.py:1);inner (a.py:9)": 3,
        "thread:MainThread;outer (a.py:1)": 1,
    }
    text = profiler.collapsed_text(collapsed)
    assert text.splitlines() == [
        "thread:MainThread;outer (a.py:1);inner (a.py:9) 3",
        "thread:MainThread;outer (a.py:1) 1",
    ]
    assert profiler.parse_collapsed(text) == collapsed
    merged = profiler.merge_collapsed(
        [collapsed, {"thread:MainThread;outer (a.py:1)": 2}])
    assert merged["thread:MainThread;outer (a.py:1)"] == 3


def test_speedscope_golden_shape():
    collapsed = {"thread:t;f (m.py:1);g (m.py:2)": 4,
                 "thread:t;f (m.py:1)": 1}
    doc = profiler.to_speedscope([("p0", collapsed, 100.0)],
                                 name="golden")
    assert doc["$schema"] == (
        "https://www.speedscope.app/file-format-schema.json")
    assert doc["name"] == "golden"
    frames = [f["name"] for f in doc["shared"]["frames"]]
    assert frames == ["thread:t", "f (m.py:1)", "g (m.py:2)"]
    prof = doc["profiles"][0]
    assert prof["type"] == "sampled" and prof["unit"] == "seconds"
    # Two stacks: [0,1] weight 1*0.01 and [0,1,2] weight 4*0.01.
    assert sorted(map(tuple, prof["samples"])) == [(0, 1), (0, 1, 2)]
    assert prof["endValue"] == pytest.approx(0.05)
    assert sum(prof["weights"]) == pytest.approx(0.05)
    import json
    json.dumps(doc)                 # must be JSON-serializable


# ---------------- satellites ----------------

def test_tracer_requeue_and_drop_counter():
    from ray_tpu.util.tracing import Tracer
    tr = Tracer(maxlen=4)
    tr.enable()
    for i in range(4):
        with tr.span(f"s{i}"):
            pass
    assert tr.spans_dropped == 0
    with tr.span("overflow"):
        pass
    assert tr.spans_dropped == 1            # ring overflow counted
    drained = tr.drain_dicts()
    assert len(drained) == 4
    # Failed export: everything fits back (ring is empty).
    assert tr.requeue_dicts(drained) == 4
    assert len(tr.drain_dicts()) == 4
    # Partial space: only the newest requeued spans survive, the
    # overflow is counted.
    with tr.span("live"):
        pass
    dropped_before = tr.spans_dropped
    assert tr.requeue_dicts(drained) == 3
    assert tr.spans_dropped == dropped_before + 1
    names = [d["name"] for d in tr.drain_dicts()]
    assert names[-1] == "live" and len(names) == 4


def test_exporter_requeues_spans_on_failed_push():
    from ray_tpu.observability.exporter import MetricsExporter
    from ray_tpu.util.tracing import get_tracer

    tr = get_tracer()
    tr.enable()
    try:
        with tr.span("will_survive_failure"):
            pass

        def bad_push(snap):
            raise ConnectionError("head gone")

        exp = MetricsExporter(bad_push, interval_s=60)
        with pytest.raises(ConnectionError):
            exp.flush_once()
        # The drained span went back instead of vanishing.
        spans = tr.drain_dicts()
        assert any(d["name"] == "will_survive_failure"
                   for d in spans)
    finally:
        tr.disable()


def test_histogram_quantiles_and_exposition():
    from ray_tpu.observability.aggregator import (
        ClusterMetricsAggregator,
    )
    from ray_tpu.util.metrics import (
        histogram_quantile,
        histogram_quantiles,
    )
    bounds = [1.0, 2.0, 4.0]
    counts = [2, 2, 4, 0]       # 8 observations, none above 4.0
    assert histogram_quantile(0.25, bounds, counts) == \
        pytest.approx(1.0)
    assert histogram_quantile(0.5, bounds, counts) == \
        pytest.approx(2.0)
    # p75 -> rank 6: 2 past the 2.0 edge, half through the 4-wide
    # third bucket's 4 entries -> 2 + 2*0.5 = 3.0.
    assert histogram_quantile(0.75, bounds, counts) == \
        pytest.approx(3.0)
    # In the +Inf bucket -> highest finite boundary.
    assert histogram_quantile(0.99, bounds, [0, 0, 0, 5]) == \
        pytest.approx(4.0)
    qs = histogram_quantiles(bounds, counts)
    assert set(qs) == {0.5, 0.95, 0.99}

    agg = ClusterMetricsAggregator()
    agg.ingest("nodeA", "w1", [{
        "name": "lat_s", "type": "histogram", "desc": "latency",
        "boundaries": bounds,
        "series": [((), counts, 18.0, 8)],
    }], 1.0)
    # Default exposition unchanged (golden-compat)…
    assert "lat_s_p50" not in agg.prometheus_text()
    # …quantile rendering is the aggregation path's opt-in.
    text = agg.prometheus_text(quantiles=True)
    assert '# TYPE lat_s_p50 gauge' in text
    assert 'lat_s_p50{node_id="nodeA"} 2' in text
    assert "lat_s_p95" in text and "lat_s_p99" in text


def test_cli_metrics_renders_quantiles(intro_rt):
    from ray_tpu.scripts.cli import main as cli_main
    from ray_tpu.util.metrics import Histogram
    h = Histogram("intro_cli_lat", "cli quantile probe",
                  boundaries=[0.1, 1.0])
    for v in (0.05, 0.5, 0.9):
        h.observe(v)
    import io
    import sys as _sys
    buf = io.StringIO()
    old = _sys.stdout
    _sys.stdout = buf
    try:
        rc = cli_main(["metrics", "--local"])
    finally:
        _sys.stdout = old
    assert rc == 0
    out = buf.getvalue()
    assert "intro_cli_lat_p50" in out
    assert "intro_cli_lat_p99" in out


def test_tail_log_file_offset_resume(tmp_path):
    from ray_tpu.util.logdir import tail_log_file
    log_dir = str(tmp_path)
    path = tmp_path / "w.log"
    path.write_bytes(b"first\n")
    out = tail_log_file(log_dir, "w.log", 1024)
    assert out["content"] == "first\n"
    assert out["offset"] == 6 and out["size"] == 6
    # Nothing new -> empty delta, same offset.
    out2 = tail_log_file(log_dir, "w.log", offset=out["offset"])
    assert out2["content"] == "" and out2["offset"] == 6
    # Append -> only the delta ships.
    with open(path, "ab") as f:
        f.write(b"second\n")
    out3 = tail_log_file(log_dir, "w.log", offset=out2["offset"])
    assert out3["content"] == "second\n"
    assert out3["offset"] == 13
    # max_bytes bounds one poll; truncated flags the remainder.
    with open(path, "ab") as f:
        f.write(b"0123456789")
    out4 = tail_log_file(log_dir, "w.log", max_bytes=4,
                         offset=out3["offset"])
    assert out4["content"] == "0123" and out4["truncated"]
    out5 = tail_log_file(log_dir, "w.log", offset=out4["offset"])
    assert out5["content"] == "456789"
    # Truncation/rotation under the poller restarts from 0.
    path.write_bytes(b"new\n")
    out6 = tail_log_file(log_dir, "w.log", offset=out5["offset"])
    assert out6["content"] == "new\n" and out6["offset"] == 4


# ---------------- CLI against a live daemon-backed cluster ----------

def test_cli_status_memory_stack_live_cluster(intro_cluster, capsys):
    """Acceptance: ray_tpu status / memory / stack work against a
    live multi-node (daemon-backed) cluster through the client
    protocol (the same socket a worker-side client dials)."""
    cluster, node = intro_cluster
    big = ray_tpu.put(b"C" * 400_000)
    from ray_tpu.scripts.cli import main as cli_main
    # Without --address the CLI attaches to the NEWEST session socket
    # on the machine: under xdist another worker's cluster.
    here = ["--address", ray_tpu.core.api.get_runtime().client_address]

    assert cli_main(["status", *here]) == 0
    out = capsys.readouterr().out
    assert "ray_tpu cluster status" in out
    assert "2 alive / 2 total" in out

    assert cli_main(["memory", "--top", "5", *here]) == 0
    out = capsys.readouterr().out
    assert "ray_tpu memory" in out
    assert "shm" in out

    assert cli_main(["stack", *here]) == 0
    out = capsys.readouterr().out
    assert "==== head" in out
    assert "==== daemon" in out
    del big


def test_cli_profile_writes_speedscope(intro_rt, tmp_path, capsys):
    import json

    from ray_tpu.scripts.cli import main as cli_main
    out_path = str(tmp_path / "prof.speedscope.json")
    assert cli_main(["profile", "--duration", "0.4", "--hz", "50",
                     "-o", out_path,
                     "--address", intro_rt.client_address]) == 0
    capsys.readouterr()
    with open(out_path) as f:
        doc = json.load(f)
    assert doc["$schema"].endswith("file-format-schema.json")
    assert doc["profiles"] and doc["shared"]["frames"]


def test_dashboard_v1_endpoints(intro_rt):
    import json
    import urllib.request

    from ray_tpu.dashboard.head import start_dashboard
    dash = start_dashboard(port=0, runtime=intro_rt)
    try:
        base = dash.url
        status = json.loads(urllib.request.urlopen(
            base + "/api/v1/status", timeout=30).read())
        assert status["nodes"] and "tasks" in status
        held = ray_tpu.put(b"D" * 200_000)
        mem = json.loads(urllib.request.urlopen(
            base + "/api/v1/memory?top=5", timeout=30).read())
        assert mem["totals"]["objects"] >= 1
        assert any(r["object_id"] == held.id.hex()
                   for r in mem["top_objects"])
        stack = json.loads(urllib.request.urlopen(
            base + "/api/v1/stack?target=head", timeout=30).read())
        assert stack and stack[0]["ok"]
        prof = json.loads(urllib.request.urlopen(
            base + "/api/v1/profile?duration_s=0.3&hz=50",
            timeout=60).read())
        assert prof["$schema"].endswith("file-format-schema.json")
        assert prof["profiles"][0]["type"] == "sampled"
    finally:
        dash.stop()
