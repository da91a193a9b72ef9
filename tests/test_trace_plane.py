"""End-to-end causal tracing plane.

The trace plane's contract surface, bottom-up:

- **TraceStore semantics** (unit): critical-path golden trees (self-
  times along the path sum to the wall for nested chains), orphan
  grace -> adoption, deferred-sampling finalize (sample-on-error and
  tail-latency force-keep), span dedupe under replay, bounded
  retention, and the Chrome/Perfetto export envelopes.
- **Tracer propagation** (unit): the sampling roll marks only trace
  ROOTS deferred (deterministic at rate 0.0 / 1.0), and
  ``remote_parent`` links children under the REAL remote span id with
  no fake ``<remote-parent>`` span recorded.
- **Wire shape** (unit): an untraced direct call is the exact 6-tuple
  frame (zero extra bytes); a traced one rides the optional 7th
  element.
- **Cross-process assembly** (integration): a head-routed task trace
  contains the driver submit span, the head's dispatch/resource-scan
  spans, and the worker execute span in ONE tree; a direct actor-call
  stream over a dropped peer connection (seqno replay through the
  head, ledger dedupe) yields exactly one span per executed call; a
  proxied HTTP request with a forced replica_busy retry assembles
  proxy -> router -> failed attempt (verdict) -> retry attempt ->
  replica execute, retrievable by the stable request id, with the
  critical path accounting for the wall time.
- **Edge joins**: 504 deadline answers carry ``X-Request-Id`` so a
  failed request can be joined to its trace.
"""

import itertools
import json
import sys
import threading
import time
import urllib.error
import urllib.request
from collections import deque

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.observability.tracestore import TraceStore
from ray_tpu.util import tracing
from ray_tpu.util.tracing import DEFERRED_ATTR, Tracer


def setup_function(_fn):
    # Tests toggle the process-global tracer; start each one clean.
    tracing.disable()
    tracing.get_tracer().drain_dicts()


def teardown_function(_fn):
    tracing.disable()
    tracing.get_tracer().drain_dicts()


def _span(name, tid, sid, parent, start, end, attrs=None,
          process="test"):
    return {"name": name, "trace_id": tid, "span_id": sid,
            "parent_id": parent, "start": start, "end": end,
            "attributes": dict(attrs or {}), "process": process}


def _walk(node):
    yield node
    for c in node.get("children", ()):
        yield from _walk(c)


# ---------------------------------------------------------------------------
# TraceStore unit semantics
# ---------------------------------------------------------------------------

def test_critical_path_golden_linear_chain():
    """Nested chain root(100ms) > mid(80ms) > leaf(30ms): path follows
    the chain and the per-span self-times sum exactly to the wall."""
    t = 1000.0
    st = TraceStore()
    st.add_spans([
        _span("root", "tr1", "a", None, t, t + 0.100),
        _span("mid", "tr1", "b", "a", t + 0.010, t + 0.090),
        _span("leaf", "tr1", "c", "b", t + 0.020, t + 0.050),
    ], now=t + 0.2)
    tr = st.get_trace("tr1", now=t + 0.2)
    assert tr is not None and tr["complete"]
    assert [p["name"] for p in tr["critical_path"]] == \
        ["root", "mid", "leaf"]
    selfs = {p["name"]: p["self_time_ms"] for p in tr["critical_path"]}
    assert selfs["root"] == pytest.approx(20.0, abs=0.01)
    assert selfs["mid"] == pytest.approx(50.0, abs=0.01)
    assert selfs["leaf"] == pytest.approx(30.0, abs=0.01)
    assert tr["critical_path_self_ms"] == \
        pytest.approx(tr["duration_ms"], rel=1e-6)


def test_critical_path_follows_child_finishing_last():
    """Fan-out: the path descends into the BLOCKING child (latest
    end), and sibling overlap is not double-counted in self-time."""
    t = 2000.0
    st = TraceStore()
    st.add_spans([
        _span("root", "tr2", "a", None, t, t + 0.100),
        _span("fast", "tr2", "b", "a", t + 0.010, t + 0.040),
        _span("slow", "tr2", "c", "a", t + 0.020, t + 0.090),
    ], now=t + 0.2)
    tr = st.get_trace("tr2", now=t + 0.2)
    assert [p["name"] for p in tr["critical_path"]] == ["root", "slow"]
    # root self = 100 - union([10,40]∪[20,90] = [10,90]) = 20ms.
    assert tr["critical_path"][0]["self_time_ms"] == \
        pytest.approx(20.0, abs=0.01)
    assert tr["critical_path_self_ms"] == pytest.approx(90.0, abs=0.05)


def test_orphan_grace_then_adoption():
    t = 3000.0
    st = TraceStore(orphan_grace_s=1.0)
    st.add_spans([
        _span("root", "tr3", "a", None, t, t + 0.05),
        _span("stray", "tr3", "x", "missing-parent", t + 0.01,
              t + 0.02),
    ], now=t)
    # Within grace: incomplete, the stray is pending (maybe its parent
    # is still in flight from another process).
    within = st.get_trace("tr3", now=t + 0.2)
    assert within["complete"] is False
    assert within["pending_orphans"] == 1
    assert within["orphans_adopted"] == 0
    # Grace expired: adopted under the root, tagged, tree complete.
    after = st.get_trace("tr3", now=t + 2.0)
    assert after["complete"] is True
    assert after["orphans_adopted"] == 1
    adopted = [s for s in _walk(after["tree"])
               if s["attributes"].get("orphan")]
    assert [s["name"] for s in adopted] == ["stray"]


def test_deferred_sampling_dropped_at_finalize():
    t = 4000.0
    st = TraceStore(orphan_grace_s=0.5)
    st.add_spans([_span("root", "trd", "a", None, t, t + 0.01,
                        {DEFERRED_ATTR: True})], now=t)
    assert st.get_trace("trd", now=t + 0.1) is not None
    st.add_spans([], now=t + 1.0)       # sweep past the grace window
    assert st.get_trace("trd", now=t + 1.0) is None
    assert st.traces_sampled_out == 1


def test_deferred_trace_kept_on_error():
    t = 5000.0
    st = TraceStore(orphan_grace_s=0.5, sample_on_error=True)
    st.add_spans([
        _span("root", "tre", "a", None, t, t + 0.01,
              {DEFERRED_ATTR: True}),
        _span("boom", "tre", "b", "a", t, t + 0.005,
              {"error": "ValueError"}),
    ], now=t)
    st.add_spans([], now=t + 1.0)
    kept = st.get_trace("tre", now=t + 1.0)
    assert kept is not None and kept["errors"] == ["b"]
    assert st.traces_sampled_out == 0


def test_deferred_trace_kept_on_tail_latency():
    t = 6000.0
    st = TraceStore(orphan_grace_s=0.5, sample_on_error=False,
                    force_sample_ms=50.0)
    st.add_spans([_span("slow", "trs", "a", None, t, t + 0.1,
                        {DEFERRED_ATTR: True})], now=t)
    st.add_spans([_span("fast", "trf", "b", None, t, t + 0.01,
                        {DEFERRED_ATTR: True})], now=t)
    st.add_spans([], now=t + 1.0)
    assert st.get_trace("trs", now=t + 1.0) is not None   # 100ms >= 50
    assert st.get_trace("trf", now=t + 1.0) is None       # 10ms < 50
    assert st.traces_sampled_out == 1


def test_store_dedupes_replayed_spans():
    t = 7000.0
    spans = [_span("root", "trr", "a", None, t, t + 0.01),
             _span("kid", "trr", "b", "a", t, t + 0.005)]
    st = TraceStore()
    st.add_spans(spans, now=t)
    st.add_spans(spans, now=t + 0.1)        # replayed feed: no-op
    assert st.spans_ingested == 2
    assert st.get_trace("trr", now=t + 0.1)["num_spans"] == 2


def test_bounded_retention_evicts_oldest():
    st = TraceStore(max_traces=2, ttl_s=1e9)
    for i, tid in enumerate(("t-old", "t-mid", "t-new")):
        st.add_spans([_span("r", tid, f"s{i}", None,
                            8000.0 + i, 8000.5 + i)], now=8000.0 + i)
    assert st.get_trace("t-old", now=8002.0) is None
    assert st.get_trace("t-mid", now=8002.0) is not None
    assert st.get_trace("t-new", now=8002.0) is not None
    assert st.traces_evicted == 1


def test_trace_export_envelopes():
    t = 9000.0
    st = TraceStore()
    st.add_spans([
        _span("root", "trx", "a", None, t, t + 0.01, {"k": "v"}),
        _span("kid", "trx", "b", "a", t, t + 0.005),
    ], now=t)
    events = st.chrome_trace("trx")
    assert [e["name"] for e in events] == ["root", "kid"]
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in events)
    assert events[0]["args"] == {"k": "v"}
    perfetto = st.perfetto_trace("trx")
    assert perfetto["traceEvents"] == events
    assert perfetto["displayTimeUnit"] == "ms"
    json.dumps(perfetto)                    # must be JSON-serializable


# ---------------------------------------------------------------------------
# Tracer propagation units
# ---------------------------------------------------------------------------

def test_sampling_rate_marks_only_roots_deferred():
    tr = Tracer()
    tr.enable()
    tr.sample_rate = 0.0                    # deterministic: always out
    with tr.span("root") as root:
        with tr.span("child") as child:
            pass
    assert root.attributes.get(DEFERRED_ATTR) is True
    assert DEFERRED_ATTR not in child.attributes

    tr2 = Tracer()
    tr2.enable()
    tr2.sample_rate = 1.0                   # deterministic: always in
    with tr2.span("root") as root2:
        pass
    assert DEFERRED_ATTR not in root2.attributes


def test_remote_parent_links_real_span_id():
    """The propagated context parents children under the REAL remote
    span id — and no fake ``<remote-parent>`` span is ever recorded."""
    tr = Tracer()
    tr.enable()
    with tr.remote_parent(("t" * 16, "p" * 16)):
        assert tr.current_context() == ("t" * 16, "p" * 16)
        with tr.span("child") as s:
            pass
    assert s.trace_id == "t" * 16
    assert s.parent_id == "p" * 16
    names = [sp.name for sp in tr.get_spans()]
    assert names == ["child"]


def test_direct_call_frame_shape_untraced_vs_traced():
    """Zero-extra-bytes contract on the wire: the untraced steady
    state keeps the exact 6-tuple OP_CALL_DIRECT frame; a traced call
    rides the context as an OPTIONAL 7th element, and the unacked
    replay entry carries it either way."""
    from ray_tpu.core import protocol as P
    from ray_tpu.core.worker import _DirectChannel

    ch = _DirectChannel.__new__(_DirectChannel)      # no dial
    ch._cv = threading.Condition()
    ch.dead = False
    ch.window = 64
    ch._seq = itertools.count()
    ch.unacked = {}
    ch._outbox = deque()
    ch._out_ev = threading.Event()

    ch.submit(b"t" * 16, "f", b"args", 1, [b"r0"], [b"n0"])
    frame = ch._outbox.popleft()
    assert frame[0] == P.OP_CALL_DIRECT
    assert len(frame) == 6
    assert ch.unacked[frame[1]][6] is None

    ctx = ("tid0", "sid0")
    ch.submit(b"t" * 16, "f", b"args", 1, [b"r1"], [b"n1"],
              trace_ctx=ctx)
    frame = ch._outbox.popleft()
    assert len(frame) == 7
    assert frame[6] == ctx
    assert ch.unacked[frame[1]][6] == ctx


def test_error_response_carries_request_id():
    from ray_tpu.serve.exceptions import (
        DeploymentOverloadedError,
        RequestDeadlineError,
    )
    from ray_tpu.serve.proxy import error_response

    status, headers, _ = error_response(
        DeploymentOverloadedError("full"), "rid-503")
    assert status == 503
    assert headers["X-Request-Id"] == "rid-503"
    assert headers["Retry-After"]

    status, headers, _ = error_response(
        RequestDeadlineError("late"), "rid-504")
    assert status == 504
    assert headers["X-Request-Id"] == "rid-504"

    status, headers, _ = error_response(ValueError("boom"), "rid-500")
    assert status == 500
    assert headers["X-Request-Id"] == "rid-500"

    _, headers, _ = error_response(ValueError("boom"))
    assert "X-Request-Id" not in headers


# ---------------------------------------------------------------------------
# Cross-process assembly (integration)
# ---------------------------------------------------------------------------

def _poll_trace(rt_obj, tid, pred, deadline_s=20.0):
    end = time.monotonic() + deadline_s
    last = None
    while time.monotonic() < end:
        last = rt_obj.get_trace(tid)
        if last is not None and pred(last):
            return last
        time.sleep(0.2)
    return last


def test_task_trace_assembles_across_head_and_worker(rt):
    """One head-routed task = one tree: driver submit span (root),
    the head's resource-scan + dispatch spans, and the worker's
    execute span — stitched from three processes."""
    tracing.enable()
    try:
        @ray_tpu.remote(num_cpus=1)
        def traced_add(x):
            return x + 1

        assert ray_tpu.get(traced_add.remote(1), timeout=60) == 2
        subs = [s for s in tracing.get_spans()
                if s.name == "submit::traced_add"]
        assert subs, "driver submit span missing"
        tid = subs[-1].trace_id

        rt_obj = ray_tpu.core.api.get_runtime()

        def assembled(t):
            names = {s["name"] for s in _walk(t["tree"])}
            return {"submit::traced_add", "task::traced_add",
                    "head.dispatch"} <= names
        t = _poll_trace(rt_obj, tid, assembled)
        assert t is not None, "trace never assembled"
        names = [s["name"] for s in _walk(t["tree"])]
        assert t["tree"]["name"] == "submit::traced_add"
        assert "task::traced_add" in names
        assert "head.dispatch" in names
        assert "head.resource_scan" in names
        # Everything hangs off the real root — no orphan scars.
        t_done = _poll_trace(rt_obj, tid, lambda x: x["complete"])
        assert t_done["complete"], t_done
        # The same tree is reachable through the state API surface.
        from ray_tpu.util import state as state_api
        via_state = state_api.get_trace(tid)
        assert via_state["trace_id"] == tid
        assert any(r["trace_id"] == tid
                   for r in state_api.list_traces(limit=50))
    finally:
        tracing.disable()


@ray_tpu.remote(num_cpus=0)
class Echo:
    def __init__(self):
        self.order = []
        self.execs = {}

    def ping(self):
        return "pong"

    def f(self, i):
        self.order.append(i)
        self.execs[i] = self.execs.get(i, 0) + 1
        return i * 2

    def drop_peers_and_f(self, i):
        # Sever the direct-call connections from INSIDE the hosting
        # worker with this very call's ack in flight: the caller
        # replays the unacked window through the head.
        self.order.append(i)
        self.execs[i] = self.execs.get(i, 0) + 1
        import ray_tpu.core.worker as W
        if W._direct_server is not None:
            W._direct_server.drop_connections()
        return i * 2

    def stats(self):
        return list(self.order), dict(self.execs)


def _ensure_direct(handle, deadline_s: float = 15.0) -> bool:
    rt = ray_tpu.core.api.get_runtime()
    end = time.monotonic() + deadline_s
    while time.monotonic() < end:
        before = rt.actor_calls_direct
        ray_tpu.get(handle.ping.remote(), timeout=60)
        if rt.actor_calls_direct > before:
            return True
        time.sleep(0.2)
    return False


def test_direct_actor_replay_emits_no_duplicate_spans(rt):
    """At-most-once tracing across the seqno-replay path: a dropped
    peer connection mid-stream replays the unacked window through the
    head with the ORIGINAL trace context; the callee's ledger answers
    replays without re-executing — so the assembled trace holds
    exactly ONE execute span per call."""
    n = 12
    tracing.enable()
    try:
        @ray_tpu.remote(num_cpus=1)
        def caller(handle, n):
            assert _ensure_direct(handle)
            refs = []
            for i in range(n):
                m = (handle.drop_peers_and_f if i == n // 2
                     else handle.f)
                refs.append(m.remote(i))
            return ray_tpu.get(refs, timeout=120)

        a = Echo.remote()
        ray_tpu.get(a.ping.remote(), timeout=60)
        assert ray_tpu.get(caller.remote(a, n), timeout=180) == \
            [i * 2 for i in range(n)]
        order, execs = ray_tpu.get(a.stats.remote(), timeout=60)
        assert all(v == 1 for v in execs.values()), execs

        subs = [s for s in tracing.get_spans()
                if s.name == "submit::caller"]
        assert subs
        tid = subs[-1].trace_id
        rt_obj = ray_tpu.core.api.get_runtime()

        def all_calls_in(t):
            names = [s["name"] for s in _walk(t["tree"])]
            return (names.count("actor::f") >= n - 1
                    and names.count("actor::drop_peers_and_f") >= 1)
        t = _poll_trace(rt_obj, tid, all_calls_in)
        assert t is not None, "actor-call spans never assembled"
        names = [s["name"] for s in _walk(t["tree"])]
        # Exactly one span per executed call — a replay that re-emitted
        # spans would show as > n-1 / > 1 here.
        assert names.count("actor::f") == n - 1, names
        assert names.count("actor::drop_peers_and_f") == 1, names
    finally:
        tracing.disable()


@pytest.fixture
def serve_rt(rt):
    yield rt
    serve.shutdown()


def _post(url, body, headers=None, timeout=60):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers=headers or {}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def test_serve_http_retry_assembles_one_trace(serve_rt, tmp_path):
    """The acceptance trace: a proxied request whose first replica
    sheds (forced one-shot ReplicaStoppingError) assembles into ONE
    tree — ingress > router > failed attempt (verdict=replica_busy) >
    retry attempt > replica execute — retrievable by the stable
    request id, with the critical path accounting for the wall."""
    from ray_tpu.core.config import env_overrides

    flag = str(tmp_path / "failed_once")
    http_port = 18761
    rid = "trace-join-rid-1"

    with env_overrides(trace_serve_requests=True):
        @serve.deployment(num_replicas=2)
        class FlakyOnce:
            def __call__(self, x):
                import os
                import time as _t

                from ray_tpu.serve.exceptions import (
                    ReplicaStoppingError,
                )
                if not os.path.exists(flag):
                    with open(flag, "w") as f:
                        f.write("1")
                    raise ReplicaStoppingError("test one-shot drain")
                _t.sleep(0.5)
                return {"ok": x}

        serve.run(FlakyOnce.bind(), http_port=http_port)
        status, _, body = _post(f"http://127.0.0.1:{http_port}/",
                                {"v": 1}, {"X-Request-Id": rid})
        assert status == 200, body

        rt_obj = ray_tpu.core.api.get_runtime()

        def find_trace():
            for row in rt_obj.list_traces(limit=50):
                t = rt_obj.get_trace(row["trace_id"])
                if t and t["root"]["name"] == "serve.ingress" and \
                        t["root"]["attributes"].get(
                            "request_id") == rid:
                    return t
            return None

        t = None
        end = time.monotonic() + 20.0
        while time.monotonic() < end:
            t = find_trace()
            if t is not None and t["complete"] and any(
                    s["name"] == "serve.replica.execute"
                    for s in _walk(t["tree"])):
                break
            time.sleep(0.2)
        assert t is not None, "serve trace never assembled"

        spans = list(_walk(t["tree"]))
        names = [s["name"] for s in spans]
        assert t["tree"]["name"] == "serve.ingress"
        assert "serve.router" in names
        attempts = [s for s in spans if s["name"] == "serve.attempt"]
        assert len(attempts) >= 2, names
        verdicts = [s["attributes"].get("verdict") for s in attempts]
        assert "replica_busy" in verdicts, verdicts
        # One successful execute; the failed attempt's execute span
        # (if its replica got far enough to open one) is error-tagged.
        executes = [s for s in spans
                    if s["name"] == "serve.replica.execute"]
        clean = [s for s in executes
                 if "error" not in s["attributes"]]
        assert len(clean) == 1, [
            (s["name"], s["attributes"]) for s in executes]
        assert t["complete"], t

        # Critical path: follows the RETRY attempt (the failed one is
        # off-path), so its self-times cover the wall minus that
        # failed attempt's duration, within 10% of the wall.
        failed = [a for a in attempts
                  if a["attributes"].get("verdict")]
        off_path_ms = sum(a["duration_ms"] for a in failed)
        cp = t["critical_path_self_ms"]
        dur = t["duration_ms"]
        assert cp <= 1.05 * dur, (cp, dur)
        assert cp >= dur - off_path_ms - 0.10 * dur, \
            (cp, dur, off_path_ms)
        path_names = [p["name"] for p in t["critical_path"]]
        assert path_names[:2] == ["serve.ingress", "serve.router"]
        assert "serve.replica.execute" in path_names

        # The same trace must come back through the other two
        # acceptance surfaces: the dashboard endpoint and the CLI.
        from ray_tpu.dashboard.head import start_dashboard
        dash = start_dashboard(port=0, runtime=rt_obj)
        try:
            rows = json.loads(urllib.request.urlopen(
                dash.url + "/api/v1/traces", timeout=30).read())
            assert any(r["trace_id"] == t["trace_id"] for r in rows)
            one = json.loads(urllib.request.urlopen(
                dash.url + f"/api/v1/traces/{t['trace_id']}",
                timeout=30).read())
            assert one["tree"]["name"] == "serve.ingress"
            chrome = json.loads(urllib.request.urlopen(
                dash.url + f"/api/v1/traces/{t['trace_id']}"
                "?format=chrome", timeout=30).read())
            assert any(e.get("name") == "serve.replica.execute"
                       for e in chrome)
        finally:
            dash.stop()

        import io
        from ray_tpu.scripts.cli import main as cli_main
        buf = io.StringIO()
        old = sys.stdout
        sys.stdout = buf
        # Without --address the CLI attaches to the NEWEST session
        # socket on the machine — under xdist that is whichever
        # worker's cluster came up last, which never saw this trace.
        here = ["--address", rt_obj.client_address]
        try:
            assert cli_main(["trace", t["trace_id"], *here]) == 0
            assert cli_main(["traces", "--slowest", *here]) == 0
        finally:
            sys.stdout = old
        out = buf.getvalue()
        assert "serve.ingress" in out
        assert "verdict=replica_busy" in out
        assert "critical path" in out
        assert t["trace_id"] in out


def test_http_deadline_504_carries_request_id(serve_rt):
    http_port = 18762
    rid = "rid-504-join"

    @serve.deployment(num_replicas=1)
    class Slow:
        def __call__(self, x):
            time.sleep(1.5)
            return {"ok": True}

    serve.run(Slow.bind(), http_port=http_port)
    status, headers, body = _post(
        f"http://127.0.0.1:{http_port}/", {"v": 1},
        {"X-Request-Timeout-S": "0.2", "X-Request-Id": rid})
    assert status == 504, body
    assert headers.get("X-Request-Id") == rid
