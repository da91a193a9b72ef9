"""The train path's own instrumentation (docs/observability.md):

- program scopes inside the compiled step (``embed``, ``blocks``,
  ``loss``, ``optimizer``), read from the HLO's ``op_name`` metadata;
- the phases of one ``fit()`` as spans on two clocks, under one trace;
- the input pipeline's per-step annotations, live only under a profile,
  and its always-on counters;
- the process-wide compile listener;
- ``observability/xplane.py::summarize_trace`` on a capture whose
  events are named by whole HLO instructions.
"""

import json
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.observability import xplane
from ray_tpu.train import session as train_session
from ray_tpu.train import step as train_step
from ray_tpu.train.prefetch import DevicePrefetcher, collect_counters
from ray_tpu.util import tracing

SCOPES = ("embed", "blocks", "loss", "optimizer")
# Instructions that compute nothing on their own: arguments, constants
# and their broadcasts, tuple plumbing.
PLUMBING = {"parameter", "constant", "broadcast", "get-tuple-element",
            "tuple", "bitcast"}
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\(.*op_name=\"([^\"]*)\"")


# -- (a) program scopes in the compiled step -----------------------------

def _gpt2_step_hlo(fused_ce: bool = True) -> str:
    from ray_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_loss_fn
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    opt = optax.adamw(1e-3)
    state = train_step.init_train_state(
        model.init_params(jax.random.key(0)), opt)
    step = train_step.make_train_step(
        gpt2_loss_fn(model, fused_ce=fused_ce, ce_chunk=64), opt)
    tokens = jnp.zeros((4, cfg.seq_len), jnp.int32)
    return step.lower(state, {"tokens": tokens,
                              "targets": tokens}).compile().as_text()


def _resnet_step_hlo() -> str:
    from ray_tpu.models.resnet import ResNet, ResNet50Config, resnet_loss_fn
    model = ResNet(ResNet50Config.tiny())
    v = model.init_variables(jax.random.key(0), image_size=32)
    opt = optax.sgd(0.1, momentum=0.9, nesterov=True)
    state = train_step.init_train_state(v["params"], opt,
                                        extra=v["batch_stats"])
    step = train_step.make_multi_train_step(resnet_loss_fn(model), opt,
                                            has_extra=True)
    batch = {"image": jnp.zeros((2, 4, 32, 32, 3)),
             "label": jnp.zeros((2, 4), jnp.int32)}
    return step.lower(state, batch).compile().as_text()


@pytest.fixture(scope="module")
def step_hlo():
    return {"gpt2": _gpt2_step_hlo(), "gpt2-unfused": _gpt2_step_hlo(False),
            "resnet": _resnet_step_hlo()}


def _computing(hlo: str) -> list[tuple[str, str, str]]:
    """(name, opcode, op_name) of the instructions of the jitted
    program that compute something."""
    out = []
    for line in hlo.splitlines():
        m = INSTRUCTION.match(line)
        if (m and m.group(3).startswith("jit(")
                and m.group(2) not in PLUMBING):
            out.append(m.groups())
    return out


@pytest.mark.parametrize("model", ["gpt2", "gpt2-unfused", "resnet"])
def test_step_instructions_fall_under_the_four_scopes(step_hlo, model):
    rows = _computing(step_hlo[model])
    assert len(rows) > 200
    unscoped = [r for r in rows
                if xplane.scope_path(r[2]) == "unscoped"]
    assert len(unscoped) <= 0.02 * len(rows), unscoped[:20]
    seen = {xplane.scope_path(r[2]).split("/")[0] for r in rows}
    assert seen >= set(SCOPES)


def test_chunked_cross_entropy_whiles_are_under_loss(step_hlo):
    whiles = [r for r in _computing(step_hlo["gpt2"]) if r[1] == "while"]
    assert len(whiles) == 2                     # forward and backward
    assert {xplane.scope_path(r[2]) for r in whiles} == {"loss/loss"}
    assert any("transpose(" in r[2] for r in whiles)    # the backward's


@pytest.mark.parametrize("model", ["gpt2", "resnet"])
def test_no_bare_primitive_is_left_from_the_optimizer(step_hlo, model):
    """Before the scope the update, the apply and the gradient norm were
    ``jit(step)/add``, ``mul``, ``sqrt``...: now the only operations
    with no scope are the fused steps' own scan (slicing the batch
    stack, the counter)."""
    scan = {"dynamic_slice", "dynamic_update_slice", "while", "add", "lt"}
    rows = _computing(step_hlo[model])
    bare = [r for r in rows if xplane.scope_path(r[2]) == "unscoped"
            and not (model == "resnet" and "/while" in r[2] + "/"
                     and r[2].rsplit("/", 1)[-1] in scan)
            and not r[2].endswith("/dynamic_slice")]
    assert bare == []
    under = [r for r in rows
             if xplane.scope_path(r[2]).startswith("optimizer")]
    assert len(under) > 50
    assert {"mul", "add"} <= {r[2].rsplit("/", 1)[-1] for r in under}


def test_gpt2_blocks_keep_their_module_names(step_hlo):
    paths = {xplane.scope_path(r[2]) for r in _computing(step_hlo["gpt2"])}
    assert {"blocks/h_*/attn", "blocks/h_*/mlp", "blocks/h_*/ln_*",
            "blocks/ln_f", "embed/wte", "embed/wpe"} <= paths


@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/transpose(jvp(GPT2))/blocks/h_11/attn/pallas_call",
     "blocks/h_*/attn"),
    ("jit(step)/jvp(GPT2)/blocks/h_3/mlp/fc/dot_general", "blocks/h_*/mlp"),
    ("jit(step)/transpose(jvp(loss))/loss/while/body/dot_general",
     "loss/loss/while"),
    ("jit(step)/optimizer/mul", "optimizer"),
    ("jit(multi)/while/body/closed_call/jvp(ResNet)/blocks/stage1_block0/"
     "conv2/conv_general_dilated", "blocks/stage*_block*/conv*"),
    ("jit(step)/jvp()/while/body/dot_general", "unscoped"),
    ("jit(loss)/mul", "unscoped"),
    ("", "unscoped"),
])
def test_scope_path(op_name, want):
    assert xplane.scope_path(op_name) == want


# -- (b) one fit(): phases under one trace, on two clocks -----------------

def _fit_loop(config):
    import jax as _jax
    import numpy as _np
    import optax as _optax

    from ray_tpu import train
    from ray_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_loss_fn
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    opt = _optax.adamw(1e-3)
    step = train.make_train_step(gpt2_loss_fn(model, ce_chunk=64), opt)
    state = train.init_train_state(
        _jax.jit(model.init_params)(_jax.random.key(0)), opt)
    tokens = _np.zeros((4, cfg.seq_len), _np.int32)
    batches = train.prefetch_to_device(
        {"tokens": tokens, "targets": tokens} for _ in range(config["steps"]))
    for batch in batches:
        state, metrics = step(state, batch)
        train.report({"loss": float(metrics["loss"])})
    batches.close()


@pytest.fixture(scope="module")
def fit(tmp_path_factory):
    """One single-worker fit; the cluster is down again when the tests
    look at what it left behind."""
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    storage = tmp_path_factory.mktemp("fit_trace")
    ray_tpu.init(num_cpus=4, ignore_reinit_error=False)
    try:
        result = JaxTrainer(
            _fit_loop, train_loop_config={"steps": 4},
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="fit", storage_path=str(storage)),
        ).fit()
    finally:
        ray_tpu.shutdown()
    assert result.error is None
    return result


PHASES = ["train.fit", "train.fit.gang_start", "train.fit.start_loop",
          "train.fit.poll", "train.fit.shutdown", "train.worker.boot",
          "train.worker.loop"]


def _own(result) -> list[dict]:
    """The fit's own spans: ``Result.spans`` less what the process did
    before it (``core.init``, ``native.build``, each a root of its
    own)."""
    root = next(s for s in result.spans if s["name"] == "train.fit")
    return [s for s in result.spans if s["trace_id"] == root["trace_id"]]


def test_fit_result_holds_every_phase_under_one_trace(fit):
    names = [s["name"] for s in fit.spans]
    for phase in PHASES:
        assert names.count(phase) == 1, (phase, names)
    assert "train.fit.backend_setup" not in names      # one worker: not run
    outside = [s for s in fit.spans if s not in _own(fit)]
    assert {s["name"] for s in outside} <= {"core.init", "native.build"}
    assert all(s["parent_id"] is None for s in outside)
    assert len({s["process"] for s in fit.spans}) == 2  # driver and worker
    root = next(s for s in fit.spans if s["name"] == "train.fit")
    assert root["parent_id"] is None
    assert root["attributes"]["workers"] == 1
    assert root["attributes"]["trial_dir"] == fit.path
    kinds = {s["attributes"]["kind"] for s in fit.spans
             if s["name"] == "train.compile"}
    assert kinds >= {"trace", "lower", "backend"}
    assert len(fit.spans) < 100         # phases and compiles, not steps


def test_fit_spans_nest_on_the_monotonic_clock(fit):
    by_id = {s["span_id"]: s for s in fit.spans}
    for s in fit.spans:
        assert 0 < s["mono_start"] <= s["mono_end"]
        assert s["start"] <= s["end"]
        # both clocks tell the same duration
        assert (s["end"] - s["start"]) == pytest.approx(
            s["mono_end"] - s["mono_start"], abs=0.05)
        if s["parent_id"] is not None:
            parent = by_id[s["parent_id"]]
            assert parent["mono_start"] <= s["mono_start"]
            assert s["mono_end"] <= parent["mono_end"]
    order = [next(s for s in fit.spans if s["name"] == n)
             for n in ("train.fit.gang_start", "train.fit.start_loop",
                       "train.fit.poll", "train.fit.shutdown")]
    for a, b in zip(order, order[1:]):
        assert a["mono_end"] <= b["mono_start"]
    loop = next(s for s in fit.spans if s["name"] == "train.worker.loop")
    compiles = [s for s in fit.spans if s["name"] == "train.compile"]
    assert all(s["parent_id"] == loop["span_id"] for s in compiles)


def test_fit_self_times_sum_to_no_more_than_the_fit(fit):
    spans = [tracing.Span(**s) for s in _own(fit)]
    self_s = tracing.self_seconds(spans)
    root = next(s for s in spans if s.name == "train.fit")
    assert all(v >= -1e-9 for v in self_s.values())
    # the driver's phases and the worker's run side by side: each
    # process's self times fit in the fit
    for process in {s.process for s in spans}:
        total = sum(self_s[s.span_id] for s in spans
                    if s.process == process)
        assert total <= (root.mono_end - root.mono_start) + 1e-6
    assert self_s[root.span_id] < root.mono_end - root.mono_start


def test_fit_poll_and_loop_attributes(fit):
    poll = next(s for s in fit.spans if s["name"] == "train.fit.poll")
    a = poll["attributes"]
    assert a["reports"] == 4 and a["polls"] >= 1
    assert 0 <= a["report_to_poll_s_max"] <= a["report_to_poll_s_sum"]
    assert a["report_to_poll_s_max"] < 5.0
    loop = next(s for s in fit.spans if s["name"] == "train.worker.loop")
    a = loop["attributes"]
    assert a["input.batches"] == 4
    assert a["input.source_s"] >= 0 and a["input.place_s"] > 0
    assert a["input.stall_s"] >= 0


def test_fit_trace_json_is_written_in_chrome_form(fit):
    with open(os.path.join(fit.path, "fit_trace.json")) as f:
        events = json.load(f)
    assert sorted(e["name"] for e in events) == sorted(
        s["name"] for s in fit.spans)
    for e in events:
        assert e["ph"] == "X" and e["dur"] >= 0
        assert {"span_id", "parent_id", "self_s"} <= set(e["args"])


def test_get_spans_holds_the_fit_after_shutdown(fit):
    own = _own(fit)
    held = tracing.get_spans(own[0]["trace_id"])
    assert sorted(s.span_id for s in held) == sorted(
        s["span_id"] for s in own)
    # and what came before the fit, each once, under its own trace
    ring = [s.span_id for s in tracing.get_spans()]
    for s in fit.spans:
        assert ring.count(s["span_id"]) == 1, s["name"]


# -- the set-up timeline: every second before the first report ------------

SETUP_SPANS = ["train.fit.gang_start.placement",
               "train.fit.gang_start.actors", "train.worker.process",
               "train.worker.backend_init"]


@pytest.mark.parametrize("name, parent, attributes", [
    ("train.fit.gang_start.placement", "train.fit.gang_start",
     {"bundles": 1, "strategy": "STRICT_PACK"}),
    ("train.fit.gang_start.actors", "train.fit.gang_start", {"workers": 1}),
    ("train.worker.process", "train.fit", {"rank": 0}),
    ("train.worker.backend_init", "train.worker.loop",
     {"platform": "cpu", "device_kind": "cpu"}),
    ("train.input.first_batch", "train.worker.loop", {}),
])
def test_fit_holds_each_set_up_span_once_under_its_parent(
        fit, name, parent, attributes):
    own = _own(fit)
    found = [s for s in own if s["name"] == name]
    assert len(found) == 1, [s["name"] for s in own]
    span, = found
    above = next(s for s in own if s["name"] == parent)
    assert span["parent_id"] == above["span_id"]
    assert above["mono_start"] <= span["mono_start"] <= span["mono_end"]
    if parent != "train.fit":
        assert span["mono_end"] <= above["mono_end"]
    assert attributes.items() <= span["attributes"].items()


def test_gang_start_is_its_two_parts_and_little_else(fit):
    by_name = {s["name"]: s for s in _own(fit)}
    gang = by_name["train.fit.gang_start"]
    placed = by_name["train.fit.gang_start.placement"]
    actors = by_name["train.fit.gang_start.actors"]
    assert gang["mono_start"] <= placed["mono_start"]
    assert placed["mono_end"] <= actors["mono_start"]
    assert actors["mono_end"] <= gang["mono_end"]
    parts = sum(s["mono_end"] - s["mono_start"] for s in (placed, actors))
    assert (gang["mono_end"] - gang["mono_start"]) - parts < 0.05


def test_worker_process_ends_where_boot_begins(fit):
    by_name = {s["name"]: s for s in _own(fit)}
    process, boot = by_name["train.worker.process"], by_name[
        "train.worker.boot"]
    assert process["mono_end"] == boot["mono_start"]
    assert process["process"] == boot["process"] == (
        f"pid:{process['attributes']['pid']}")
    # the process began after the driver asked for it, and its imports
    # are the bulk of the actors' part
    actors = by_name["train.fit.gang_start.actors"]
    assert actors["mono_start"] < process["mono_start"] < process["mono_end"]
    assert process["mono_end"] <= actors["mono_end"]


def test_backend_opens_before_the_users_loop_touches_jax(fit):
    by_name = {s["name"]: s for s in _own(fit)}
    loop, opened = by_name["train.worker.loop"], by_name[
        "train.worker.backend_init"]
    assert opened["attributes"]["devices"] >= 1
    first_compile = min(s["mono_start"] for s in _own(fit)
                        if s["name"] == "train.compile")
    assert loop["mono_start"] <= opened["mono_start"]
    assert opened["mono_end"] <= first_compile


def test_a_backend_that_does_not_open_is_left_to_the_users_loop(
        monkeypatch):
    """A worker pinned to a platform that is not there: the span tells
    the error, and the loop's own first jax call raises as it always
    did (tests/test_chip_smoke.py holds that end)."""
    from ray_tpu.train import worker_group

    def no_chip():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_chip)
    sink: list = []
    with tracing.train_span("train.worker.loop", sink=sink) as loop:
        worker_group._open_backend(sink)
    opened = sink[0]
    assert opened.name == "train.worker.backend_init"
    assert opened.parent_id == loop.span_id
    assert opened.attributes == {"error": "RuntimeError"}


@pytest.mark.parametrize("name", ["train.worker.backend_init",
                                  "train.worker.loop"])
def test_a_backend_without_memory_counters_leaves_no_hbm_attribute(
        fit, name):
    """The CPU's ``memory_stats()`` is None: the two spans that carry
    the allocator's counters on a TPU (tests/test_train_memory.py) say
    nothing of memory, and no key holds a made-up 0."""
    span = next(s for s in _own(fit) if s["name"] == name)
    assert not [k for k in span["attributes"] if k.startswith("hbm_")]


def test_first_batch_span_holds_that_batchs_times(fit):
    by_name = {s["name"]: s for s in _own(fit)}
    first = by_name["train.input.first_batch"]
    a = first["attributes"]
    assert set(a) == {"source_s", "place_s", "stall_s"}
    assert a["place_s"] > 0 and a["source_s"] >= 0
    seconds = first["mono_end"] - first["mono_start"]
    assert a["stall_s"] <= seconds + 1e-3
    # that batch's times, not the four batches'
    totals = by_name["train.worker.loop"]["attributes"]
    assert a["place_s"] <= totals["input.place_s"]
    assert a["stall_s"] <= totals["input.stall_s"] + 1e-9


def test_first_report_s_is_told_on_the_loop_span(fit):
    by_name = {s["name"]: s for s in _own(fit)}
    loop = by_name["train.worker.loop"]
    told = loop["attributes"]["first_report_s"]
    assert 0 < told < loop["mono_end"] - loop["mono_start"]
    # after the step compiled, before the poll that drained the last
    step_traced = min(s["mono_end"] for s in _own(fit)
                      if s["name"] == "train.compile"
                      and s["attributes"]["fun_name"] == "step")
    assert loop["mono_start"] + told > step_traced


def test_first_report_is_set_once_a_session():
    sess = train_session.init_session(train_session.TrainContext())
    try:
        assert sess.t_first_report is None
        before = time.monotonic()
        train_session.report({"i": 0})
        first = sess.t_first_report
        assert before <= first <= time.monotonic()
        for i in range(1, 4):
            train_session.report({"i": i})
        assert sess.t_first_report == first < sess.last_report_ts
    finally:
        train_session.shutdown_session()


def test_fit_holds_the_cold_start_before_it(fit):
    """``core.init`` (and ``native.build`` where this process compiled
    the library) ride the fit: ``Result.spans`` and ``fit_trace.json``
    show a job from ``init()`` on."""
    inits = [s for s in fit.spans if s["name"] == "core.init"]
    assert len(inits) == 1      # the newest: this module ran one init
    init, = inits
    root = next(s for s in fit.spans if s["name"] == "train.fit")
    assert init["parent_id"] is None
    assert init["trace_id"] != root["trace_id"]
    assert init["attributes"] == {"address": "local", "nodes": 1}
    assert 0 < init["mono_start"] < init["mono_end"] <= root["mono_start"]
    with open(os.path.join(fit.path, "fit_trace.json")) as f:
        events = json.load(f)
    assert [e["name"] for e in events].count("core.init") == 1
    assert fit.spans[0]["mono_start"] == min(
        s["mono_start"] for s in fit.spans)


def test_process_spans_are_the_newest_of_each_name_before_the_fit(
        monkeypatch):
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    t = time.monotonic()
    for name, a, b in (("core.init", t - 9, t - 8), ("core.init", t - 5, t - 4),
                       ("native.build", t - 7, t - 6),
                       ("core.init", t + 1, t + 2),        # after "the fit"
                       ("train.compile", t - 3, t - 2)):   # not the process's
        tracing.record_train_span(name, a, b)
    with tracing.train_span("train.fit", sink=[]):
        tracing.record_train_span("core.init", t - 1.5, t - 1.0)    # a child
    got = tracing.process_spans(t)
    assert [(s.name, s.mono_start) for s in got] == [
        ("native.build", t - 7), ("core.init", t - 5)]


def test_native_build_is_a_span_only_where_it_compiles(monkeypatch):
    from ray_tpu.native import build
    before = len(tracing.get_spans())
    monkeypatch.setattr(build, "_needs_build", lambda: False)
    assert build.ensure_built() == build.lib_path()
    assert tracing.get_spans()[before:] == []
    monkeypatch.setattr(build, "_needs_build", lambda: True)
    monkeypatch.setattr(build, "_build", lambda: time.sleep(0.01))
    assert build.ensure_built() is None
    span, = tracing.get_spans()[before:]
    assert span.name == "native.build" and span.parent_id is None
    assert span.attributes == {"built": False}
    assert span.mono_end - span.mono_start >= 0.01


def test_a_span_without_a_sink_goes_to_the_ring_whatever_is_open_around_it():
    """A list is handed on, never inherited: ``WorkerGroup`` is given
    the fit's for the two parts of ``gang_start``; a span that is given
    none nests under the one open around it and is kept in the ring."""
    ring = len(tracing.get_spans())
    sink: list = []
    with tracing.train_span("train.fit.gang_start", sink=sink) as gang:
        with tracing.train_span("train.fit.gang_start.placement",
                                sink=sink) as placed:
            pass
        with tracing.train_span("train.fit.gang_start.actors") as actors:
            pass
        compiled = tracing.record_train_span("train.compile", 1.0, 2.0)
    assert sink == [placed, gang]
    assert tracing.get_spans()[ring:] == [actors, compiled]
    assert {s.parent_id for s in (placed, actors, compiled)} == {gang.span_id}


def test_self_seconds_count_overlapping_siblings_once():
    """Two siblings of one process that overlap (a cache load inside its
    ``backend`` compile; the first batch, whose consumer waits while
    the producer's thread compiles): the seconds under both are the
    later one's, the parent loses their union once, and a process's
    self times sum to its wall. Another process's spans run beside."""
    def span(name, a, b, parent, process="pid:2"):
        return tracing.Span(name=name, trace_id="t", span_id=name,
                            parent_id=parent, start=0.0, mono_start=a,
                            mono_end=b, process=process)
    spans = [span("fit", 0.0, 20.0, None, "pid:1"),
             span("poll", 2.0, 19.0, "fit", "pid:1"),
             span("loop", 2.0, 18.0, "fit"),
             span("trace", 3.0, 6.0, "loop"),
             span("first_batch", 4.0, 11.0, "loop"),
             span("backend", 7.0, 12.0, "loop"),
             span("cache_load", 8.0, 10.0, "loop")]
    assert tracing.self_seconds(spans) == {
        "fit": 3.0,             # 20 less poll and loop, side by side
        "poll": 17.0,           # the worker's loop is not the driver's
        "loop": 16.0 - 9.0,     # its children cover 3-12
        "trace": 1.0,           # 3-4: the first batch began over it
        "first_batch": 3.0,     # 4-7: then the backend compile
        "backend": 3.0,         # 7-8 and 10-12: less its load
        "cache_load": 2.0}
    worker = sum(v for k, v in tracing.self_seconds(spans).items()
                 if k not in ("fit", "poll"))
    assert worker == 16.0


def test_first_batch_outside_a_fit_goes_to_the_process_ring():
    ring = len(tracing.get_spans())
    pf = DevicePrefetcher(iter([1, 2, 3]), place=lambda b: b + 1, depth=1)
    time.sleep(0.05)            # the producer runs ahead of the consumer
    assert tracing.get_spans()[ring:] == []     # made, nothing handed over
    with tracing.train_span("train.worker.loop") as loop:
        assert next(pf) == 2
    span, around = tracing.get_spans()[ring:]
    assert list(pf) == [3, 4]
    pf.close()
    assert len(tracing.get_spans()) == ring + 2     # the first batch alone
    assert span.name == "train.input.first_batch" and around is loop
    # under the span open in the consumer's thread, kept in the ring
    assert (span.trace_id, span.parent_id) == (loop.trace_id, loop.span_id)
    assert span.mono_end - span.mono_start >= 0.05
    assert span.attributes["stall_s"] < 0.05 <= span.mono_end - span.mono_start
    assert span.attributes["place_s"] >= 0 and span.attributes["source_s"] >= 0


def _configuring_loop(config):
    import jax as _jax
    import jax.numpy as _jnp

    from ray_tpu import train
    told = {"open": _jax._src.xla_bridge.backends_are_initialized()}
    # an option jax reads when it is used: set in the loop as ever
    _jax.config.update("jax_enable_x64", True)
    told["dtype"] = str(_jnp.ones(()).dtype)
    # one it reads when the backend opens: jax refuses it now
    try:
        _jax.config.update("jax_num_cpu_devices", 2)
    except RuntimeError as e:
        told["refused"] = str(e)
    told["devices"] = len(_jax.devices())
    train.report(told)


def test_a_loop_that_configures_jax_finds_the_backend_open(tmp_path):
    """The contract of a ``JaxTrainer`` loop since the program opens the
    backend ahead of it (docs/QUICKSTART.md): an option jax reads at
    use takes effect from the loop; one it reads at the opening comes
    with the worker's environment, and jax refuses it in the loop by
    name."""
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    ray_tpu.init(num_cpus=4, ignore_reinit_error=False, runtime_env={
        "env_vars": {"XLA_FLAGS": "--xla_force_host_platform_device_count=3"}})
    try:
        result = JaxTrainer(
            _configuring_loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="fit", storage_path=str(tmp_path)),
        ).fit()
    finally:
        ray_tpu.shutdown()
    assert result.error is None, result.error
    told = result.metrics
    assert told["open"] is True
    assert told["dtype"] == "float64"
    assert "before backends are initialized" in told["refused"]
    assert told["devices"] == 3
    opened, = [s for s in result.spans
               if s["name"] == "train.worker.backend_init"]
    assert opened["attributes"]["devices"] == 3


def _uneven_loop(config):
    from ray_tpu import train
    if train.get_context().world_rank == 1:
        time.sleep(1.0)         # rank 0 is polled some 20 times more
    train.report({"rank": train.get_context().world_rank})


@pytest.fixture(scope="module")
def uneven_fit(tmp_path_factory):
    """A two-worker fit whose ranks end a second apart, with tracing
    enabled: the actor method that starts the loop then runs inside a
    task's span."""
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    storage = tmp_path_factory.mktemp("uneven_fit")
    ray_tpu.init(num_cpus=4, ignore_reinit_error=False)
    tracing.enable()
    try:
        result = JaxTrainer(
            _uneven_loop, scaling_config=ScalingConfig(num_workers=2),
            run_config=RunConfig(name="fit", storage_path=str(storage)),
        ).fit()
    finally:
        tracing.disable()
        ray_tpu.shutdown()
    assert result.error is None, result.error
    return result


def test_a_finished_worker_hands_its_spans_over_once(uneven_fit):
    ids = [s["span_id"] for s in uneven_fit.spans]
    assert len(ids) == len(set(ids))
    names = [s["name"] for s in uneven_fit.spans]
    for phase in PHASES + ["train.fit.backend_setup", *SETUP_SPANS]:
        assert names.count(phase) == (
            2 if phase.startswith("train.worker.") else 1), (phase, names)
    ranks = sorted(s["attributes"]["rank"] for s in uneven_fit.spans
                   if s["name"] == "train.worker.loop")
    assert ranks == [0, 1]
    poll = next(s for s in uneven_fit.spans
                if s["name"] == "train.fit.poll")
    assert poll["attributes"]["polls"] >= 10    # rank 0 was asked again
    with open(os.path.join(uneven_fit.path, "fit_trace.json")) as f:
        assert len(json.load(f)) == len(ids)


def test_worker_spans_stay_in_the_fit_with_tracing_enabled(uneven_fit):
    root = next(s for s in uneven_fit.spans if s["name"] == "train.fit")
    in_fit = [s for s in uneven_fit.spans if s["name"].startswith("train.")]
    assert {s["trace_id"] for s in in_fit} == {root["trace_id"]}
    loops = {s["span_id"] for s in in_fit
             if s["name"] == "train.worker.loop"}
    for s in in_fit:
        if s["name"] == "train.worker.backend_init":
            assert s["parent_id"] in loops
        elif s["name"].startswith("train.worker."):
            assert s["parent_id"] == root["span_id"]


# -- the identity of the set-up's parts, on hand-made spans ---------------

def _setup_trace():
    """``benchmark/benchlib/setup_trace.py``: the yardstick's reader of
    these spans (the program does not import the benchmark)."""
    import sys
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark"))
    try:
        from benchlib import setup_trace
    finally:
        sys.path.pop(0)
    return setup_trace


def _rec(name, start, end, process="pid:1", **attributes):
    return {"name": name, "start": start, "end": end, "process": process,
            "attributes": attributes}


# t_start 100, train.fit from 103, the loop from 108 with its first report
# 20 s in (128), the window opens at 131.5.
_CONTAINERS = [
    _rec("train.fit", 103.0, 160.0),
    _rec("train.fit.poll", 108.5, 159.0),
    _rec("train.worker.loop", 108.0, 158.0, "pid:2", first_report_s=20.0),
]
_DRIVER = [_rec("train.fit.gang_start", 103.0, 107.0),
           _rec("train.fit.gang_start.actors", 103.1, 107.0),
           _rec("train.fit.start_loop", 107.5, 108.5)]
_WORKER = [_rec("train.worker.process", 103.5, 106.5, "pid:2"),
           _rec("train.worker.backend_init", 108.0, 114.0, "pid:2"),
           _rec("train.compile", 116.0, 120.0, "pid:2", kind="trace"),
           _rec("train.input.first_batch", 119.0, 122.0, "pid:2"),
           _rec("train.compile", 124.0, 129.5, "pid:2", kind="backend")]


@pytest.mark.parametrize("spans, named_s, unnamed_s", [
    # the containers cover everything and name nothing
    (_CONTAINERS, 0.0, 25.0),
    # the driver's spans alone: gang_start with its part inside it counted
    # once (4), start_loop (1)
    (_CONTAINERS + _DRIVER, 5.0, 20.0),
    # the worker's alone: 3 + 6 + the trace and the first batch overlapping
    # (116-122: 6) + the backend compile cut at the first report (4)
    (_CONTAINERS + _WORKER, 19.0, 6.0),
    # both processes on the one clock: the worker's process lies inside
    # the driver's gang_start, its backend_init overlaps start_loop
    (_CONTAINERS + _DRIVER + _WORKER, 20.5, 4.5),
], ids=["containers", "driver", "worker", "merged"])
def test_set_up_identity_on_hand_made_spans(spans, named_s, unnamed_s):
    st = _setup_trace()
    parts = st.cut(spans, 100.0, 131.5)
    assert parts["before_fit_s"] == pytest.approx(3.0)
    assert parts["warmup_s"] == pytest.approx(3.5)
    assert parts["named_s"] == pytest.approx(named_s)
    assert parts["unnamed_s"] == pytest.approx(unnamed_s)
    assert (parts["before_fit_s"] + parts["named_s"] + parts["unnamed_s"]
            + parts["warmup_s"]) == pytest.approx(parts["setup_s"], abs=1e-9)
    assert parts["setup_s"] == 31.5
    # the same seconds span by span, and the stretches under none
    a, b = parts["t_fit"], parts["t_first_report"]
    assert sum(st.named_by_label(spans, a, b).values()) == pytest.approx(
        named_s, abs=1e-9)
    assert sum(hi - lo for lo, hi in st.gaps(spans, a, b)) == pytest.approx(
        unnamed_s, abs=1e-9)


def test_set_up_parts_go_to_the_innermost_span_and_name_the_gaps():
    st = _setup_trace()
    spans = _CONTAINERS + _DRIVER + _WORKER
    by = st.named_by_label(spans, 103.0, 128.0)
    assert by == {
        "train.fit.gang_start": pytest.approx(0.1),
        "train.fit.gang_start.actors": pytest.approx(0.9),
        "train.worker.process": pytest.approx(3.0),
        "train.fit.start_loop": pytest.approx(0.5),
        "train.worker.backend_init": pytest.approx(6.0),
        "train.compile:trace": pytest.approx(3.0),
        "train.input.first_batch": pytest.approx(3.0),
        "train.compile:backend": pytest.approx(4.0)}
    assert st.gaps(spans, 103.0, 128.0) == [
        (107.0, 107.5), (114.0, 116.0), (122.0, 124.0)]


def test_set_up_readers_say_nothing_of_a_program_without_the_spans():
    st = _setup_trace()
    old_program = [_rec("train.fit", 103.0, 160.0),
                   _rec("train.worker.loop", 108.0, 158.0, "pid:2")]
    assert st.cut([], 100.0, 131.5) is None
    assert st.cut(old_program, 100.0, 131.5) == {
        "setup_s": 31.5, "before_fit_s": 3.0}
    assert st.span_s(old_program, "train.worker.process") is None
    # the newest fit of a process that made two; a span that repeats
    twice = old_program + [_rec("train.fit", 203.0, 260.0),
                           _rec("train.input.first_batch", 210.0, 211.0),
                           _rec("train.input.first_batch", 220.0, 220.5)]
    assert st.cut(twice, 200.0, 231.5)["before_fit_s"] == 3.0
    assert st.span_s(twice, "train.input.first_batch") == 1.0
    assert st.span_s(twice, "train.input.first_batch", first=True) == 1.0
    # workers side by side: the longest, not N times the wall; a gang
    # that was started again: the newest's
    gangs = [_rec("train.fit.gang_start", 103.0, 107.0),
             _rec("train.worker.process", 103.5, 106.5, "pid:2"),
             _rec("train.fit.gang_start", 140.0, 145.0),
             _rec("train.worker.process", 140.5, 144.0, "pid:4"),
             _rec("train.worker.process", 140.6, 144.5, "pid:5"),
             _rec("train.input.first_batch", 150.0, 150.5, "pid:5"),
             _rec("train.input.first_batch", 149.0, 149.2, "pid:4")]
    assert st.span_s(gangs, "train.worker.process") == pytest.approx(3.9)
    assert st.span_s(gangs, "train.input.first_batch",
                     first=True) == pytest.approx(0.2)


def test_set_up_records_read_a_fits_own_trace_file(fit):
    """``fit_trace.json`` and ``Result.spans`` cut the same way: the tool
    reads the file, the readers the tracer."""
    st = _setup_trace()
    with open(os.path.join(fit.path, "fit_trace.json")) as f:
        from_file = st.records_from_chrome(json.load(f))
    from_spans = st.records([tracing.Span(**s) for s in fit.spans])
    root = next(s for s in fit.spans if s["name"] == "train.fit")
    t_start, t_open = root["mono_start"] - 1.0, root["mono_end"]
    a, b = st.cut(from_file, t_start, t_open), st.cut(from_spans, t_start,
                                                      t_open)
    assert a == {k: pytest.approx(v, abs=1e-5) for k, v in b.items()}
    assert a["before_fit_s"] == pytest.approx(1.0, abs=1e-5)
    assert 0 <= a["unnamed_s"] < a["t_first_report"] - a["t_fit"]
    assert (a["before_fit_s"] + a["named_s"] + a["unnamed_s"]
            + a["warmup_s"]) == pytest.approx(a["setup_s"], abs=1e-9)


# -- tracing.py's train-path helpers --------------------------------------

def test_train_span_is_recorded_with_tracing_disabled():
    assert not tracing.get_tracer().enabled
    sink: list = []
    with tracing.train_span("train.fit", {"k": 1}, sink=sink) as root:
        with tracing.train_span("train.fit.poll", sink=sink) as child:
            time.sleep(0.01)
        tracing.record_train_span("train.compile", child.mono_start,
                                  child.mono_end, {"kind": "trace"},
                                  sink=sink)
    assert [s.name for s in sink] == ["train.fit.poll", "train.compile",
                                      "train.fit"]
    assert {s.trace_id for s in sink} == {root.trace_id}
    assert sink[0].parent_id == sink[1].parent_id == root.span_id
    assert root.mono_end - root.mono_start >= 0.01
    assert root.end - root.start == pytest.approx(
        root.mono_end - root.mono_start, abs=0.01)
    self_s = tracing.self_seconds(sink)
    # the two children overlap entirely: counted once
    assert self_s[root.span_id] == pytest.approx(
        (root.mono_end - root.mono_start)
        - (child.mono_end - child.mono_start))


def test_train_span_without_a_sink_goes_to_the_ring_and_tags_errors():
    before = len(tracing.get_spans())
    with pytest.raises(ValueError):
        with tracing.train_span("train.fit.gang_start",
                                parent=("t" * 16, "p" * 16)):
            raise ValueError("no gang")
    added = tracing.get_spans()[before:]
    assert [s.name for s in added] == ["train.fit.gang_start"]
    assert added[0].trace_id == "t" * 16 and added[0].parent_id == "p" * 16
    assert added[0].attributes["error"] == "ValueError"


def test_an_explicit_parent_wins_over_a_tasks_span():
    """With tracing enabled a worker's method runs inside its task's
    span, of whatever trace: the fit's (trace id, root) still holds,
    and train-path spans still nest in each other."""
    tracing.enable()
    try:
        sink: list = []
        fit = ("f" * 16, "r" * 16)
        with tracing.span("task::start_loop") as task:
            assert task.trace_id != fit[0]
            tracing.record_train_span("train.worker.boot", 1.0, 2.0,
                                      parent=fit, sink=sink)
            with tracing.train_span("train.worker.loop", parent=fit,
                                    sink=sink) as loop:
                tracing.record_train_span("train.compile", 1.0, 2.0,
                                          parent=fit, sink=sink)
                with tracing.span("submit::f") as inner:
                    pass
            with tracing.train_span("train.fit", sink=sink) as joined:
                pass
    finally:
        tracing.disable()
    boot, compiled = sink[0], sink[1]
    assert {boot.trace_id, loop.trace_id, compiled.trace_id} == {fit[0]}
    assert boot.parent_id == loop.parent_id == fit[1]
    assert compiled.parent_id == loop.span_id
    # the control plane's spans nest under an open train-path span, and
    # a fit with no parent joins the trace around it
    assert (inner.trace_id, inner.parent_id) == (fit[0], loop.span_id)
    assert (joined.trace_id, joined.parent_id) == (task.trace_id,
                                                   task.span_id)


def test_step_time_histogram_tells_steps_apart():
    b = train_session._step_time_buckets()
    assert b[0] == 0.005 and b[-1] == 120.0
    assert all(hi / lo <= 1.5 for lo, hi in zip(b, b[1:]))
    # the GPT-2 step (250 ms) and ten fused ResNet steps (476 ms), and a
    # step 60% slower than either, each have a bucket of their own

    def bucket(x):
        return sum(x > edge for edge in b)
    assert len({bucket(0.250), bucket(0.476), bucket(0.250 * 1.6),
                bucket(0.476 * 1.6)}) == 4


# -- (c) the input pipeline under a profile -------------------------------

@pytest.fixture(scope="module")
def prefetch_profile(tmp_path_factory):
    """A profile around a DevicePrefetcher loop whose source and place
    take known times, and the prefetcher's counters after it."""
    from jax.profiler import ProfileData
    logdir = str(tmp_path_factory.mktemp("prefetch_profile"))

    def source():
        for i in range(12):
            time.sleep(0.010)
            yield np.full((4,), i, np.float32)

    def place(x):
        time.sleep(0.005)
        return jax.device_put(x)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        pf = DevicePrefetcher(source(), place=place, depth=2)
        got = [int(x[0]) for x in pf]
        pf.close()
    finally:
        jax.profiler.stop_trace()
    assert got == list(range(12))
    path = xplane.trace_files(logdir)[-1]
    by_name: dict[str, list] = {}       # name -> [(thread, seconds)]
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        for i, line in enumerate(plane.lines):      # one line a thread
            for ev in line.events:
                if ev.name.startswith("train.input."):
                    by_name.setdefault(ev.name, []).append(
                        ((p, i), ev.duration_ns / 1e9))
    return pf, by_name


def test_input_spans_lie_on_their_threads(prefetch_profile):
    _, by_name = prefetch_profile
    assert set(by_name) == {"train.input.source", "train.input.place",
                            "train.input.wait"}
    source = {line for line, _ in by_name["train.input.source"]}
    place = {line for line, _ in by_name["train.input.place"]}
    wait = {line for line, _ in by_name["train.input.wait"]}
    assert len(source) == len(place) == len(wait) == 1
    assert source == place and source != wait
    assert len(by_name["train.input.place"]) == 12
    assert len(by_name["train.input.wait"]) == 13      # and the sentinel's


@pytest.mark.parametrize("counter, span", [
    ("source_s", "train.input.source"), ("place_s", "train.input.place"),
    ("stall_s", "train.input.wait")])
def test_input_counters_agree_with_the_spans(prefetch_profile, counter,
                                             span):
    pf, by_name = prefetch_profile
    spans_s = sum(d for _, d in by_name[span])
    assert pf.counters["batches"] == 12
    assert pf.counters[counter] == pytest.approx(spans_s, rel=0.2)
    assert pf.counters[counter] >= {"source_s": 0.12, "place_s": 0.06,
                                    "stall_s": 0.1}[counter]


# -- (d) the compile listener ----------------------------------------------

def _compile_spans_since(n: int, fun: str) -> list:
    return [s for s in tracing.get_spans()[n:]
            if s.name == "train.compile"
            and fun in s.attributes.get("fun_name", "")]


def test_a_new_step_yields_compile_spans_and_a_repeat_none():
    def quadratic_loss(params, batch):
        return jnp.sum((params["w"] * batch["x"] - 1.0) ** 2)

    opt = optax.sgd(0.1)
    step = train_step.make_train_step(quadratic_loss, opt)
    state = train_step.init_train_state({"w": jnp.ones((8,))}, opt)
    batch = {"x": jnp.arange(8.0)}
    before = len(tracing.get_spans())
    state, _ = step(state, batch)
    first = _compile_spans_since(before, "step")
    assert {s.attributes["kind"] for s in first} >= {"trace", "lower",
                                                     "backend"}
    for s in first:
        assert s.attributes["fun_name"] in ("step", "jit(step)")
        assert 0 < s.mono_start <= s.mono_end <= time.monotonic()
    # donated outputs come back in the layouts the compiler chose: at
    # most one more executable, then none
    state, _ = step(state, batch)
    before = len(tracing.get_spans())
    for _ in range(3):
        state, _ = step(state, batch)
    assert _compile_spans_since(before, "step") == []


def test_nested_jits_fold_into_the_outermost_trace():
    inner = jax.jit(lambda x: x * 2.0)

    def outer_fn(x):
        return inner(inner(x)) + jnp.sum(x)

    train_step._listen_for_compiles()
    x = jnp.ones((4,))
    before = len(tracing.get_spans())
    jax.jit(outer_fn)(x)
    traces = [s for s in tracing.get_spans()[before:]
              if s.name == "train.compile"
              and s.attributes["kind"] == "trace"]
    assert [s.attributes["fun_name"] for s in traces] == ["outer_fn"]


def test_trace_notes_reach_their_trace_span_and_no_other():
    """What traced code says of itself (``tracing.note_trace``: the
    chunked cross-entropy's ``ce_rows_local`` / ``ce_axes``) lands on
    the ``trace`` span of the program being traced: not on its lower
    or backend span, not on the next program's, and a note left where
    nobody listened does not reach a later trace."""
    def noted(x):
        tracing.note_trace(rows=7, axes=["dp"])
        for _ in range(2):          # what a trace does twice adds up
            tracing.count_trace(calls=1)
        return x + 1.0

    train_step._listen_for_compiles()
    x = jnp.ones((2,))
    tracing.note_trace(stale=True)
    before = len(tracing.get_spans())
    jax.jit(noted)(x)
    jax.jit(lambda x: x * 3.0)(x)
    spans = [s.attributes for s in tracing.get_spans()[before:]
             if s.name == "train.compile"]
    with_notes = [a for a in spans if set(a) - {"kind", "fun_name", "cache"}]
    assert with_notes == [{"kind": "trace", "fun_name": "noted",
                           "rows": 7, "axes": ["dp"], "calls": 2}]
    assert [a["fun_name"] for a in spans if a["kind"] == "trace"] == [
        "noted", "<lambda>"]


@pytest.mark.parametrize("n_head, layout, lanes", [
    (2, "bthd", 128), (3, "folded", 64)],
    ids=["gpt2_even_heads", "odd_heads"])
def test_flash_notes_reach_both_traces_of_the_step_and_no_other_span(
        monkeypatch, n_head, layout, lanes):
    """Which layout the flash kernel took (``flash_attention``'s note:
    the projections' own [B, T, H*D], or the fold of a shape that
    cannot be blocked on 128 lanes) is on the step's ``trace`` span
    and on no other; and on the span of a second trace too, which
    finds the jitted kernel functions' own traces cached: the note is
    made outside them. The kernel is steered into interpret mode and
    the dispatch onto it here, in the test (no chip in the sandbox)."""
    import functools
    import importlib

    from jax.sharding import Mesh

    from ray_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_loss_fn
    from ray_tpu.ops import attention

    fa = importlib.import_module("ray_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(attention, "flash_eligible",
                        fa.flash_attention_shapes_ok)
    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=True))
    cfg = GPT2Config.tiny(n_head=n_head, n_embd=64 * n_head, seq_len=128,
                          n_layer=2)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape((1,) * 6),
                ("pp", "dp", "fsdp", "ep", "sp", "tp"))
    model = GPT2(cfg, mesh=mesh)
    opt = optax.sgd(0.1)
    state = train_step.init_train_state(
        model.init_params(jax.random.key(0)), opt, mesh)
    tokens = jnp.zeros((2, cfg.seq_len), jnp.int32)
    batch = {"tokens": tokens, "targets": tokens}
    want = {"flash_layout": layout, "flash_lanes_per_block": lanes,
            "flash_path": "single_block", "flash_causal_slabs": 1}
    for _ in range(2):      # the second: a new jit of the same step
        before = len(tracing.get_spans())
        step = train_step.make_train_step(
            gpt2_loss_fn(model, ce_chunk=64), opt, donate=False)
        _, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        spans = _compile_spans_since(before, "step")
        kinds = [s.attributes["kind"] for s in spans]
        assert {"trace", "lower", "backend"} <= set(kinds)
        for s in spans:
            noted = {k: v for k, v in s.attributes.items()
                     if k.startswith("flash_")}
            assert noted == (want if s.attributes["kind"] == "trace"
                             else {}), s.attributes


@pytest.mark.parametrize("steered", [False, True],
                         ids=["cpu_scan", "kernel_interpreted"])
def test_the_loss_says_which_forward_it_compiled(monkeypatch, steered):
    """``chunked_cross_entropy``'s notes on the step's ``trace`` span:
    ``ce_path``, and where that is the kernel's (steered onto it here,
    interpreted: no chip in the sandbox) the row block, the vocabulary
    tile and the custom calls a step; on the CPU ``xla_scan`` and no
    ``ce_fwd_*`` key; on both ``ce_rows``, the rows that reached the core. No other span of the step carries them."""
    import functools

    from ray_tpu.models import gpt2
    from ray_tpu.ops.pallas import ce_lse

    if steered:
        monkeypatch.setattr(gpt2, "ce_path", lambda *a, **k: "pallas_lse")
        monkeypatch.setattr(ce_lse, "ce_lse_fwd", functools.partial(
            ce_lse.ce_lse_fwd, interpret=True))
    cfg = gpt2.GPT2Config.tiny(n_head=2, n_embd=128, n_layer=1)
    model = gpt2.GPT2(cfg)
    opt = optax.sgd(0.1)
    state = train_step.init_train_state(
        model.init_params(jax.random.key(0)), opt)
    tokens = jnp.zeros((2, cfg.seq_len), jnp.int32)
    batch = {"tokens": tokens, "targets": tokens}
    want = {"ce_path": "xla_scan", "ce_rows": 128}
    if steered:     # 128 rows in chunks of 64: one block; 256 columns
        want = {"ce_path": "pallas_lse", "ce_fwd_rows": 128,
                "ce_fwd_tile": 256, "ce_fwd_calls": 1, "ce_rows": 128}
    before = len(tracing.get_spans())
    step = train_step.make_train_step(
        gpt2.gpt2_loss_fn(model, ce_chunk=64), opt, donate=False)
    _, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    spans = _compile_spans_since(before, "step")
    assert "trace" in [s.attributes["kind"] for s in spans]
    for s in spans:
        noted = {k: v for k, v in s.attributes.items()
                 if k.startswith("ce_")}
        assert noted == (want if s.attributes["kind"] == "trace"
                         else {}), s.attributes


def test_the_listener_is_installed_once_a_process():
    from jax._src import monitoring
    for _ in range(3):
        train_step.make_train_step(lambda p, b: jnp.sum(p["w"]),
                                   optax.sgd(0.1))
    listeners = monitoring.get_event_duration_listeners()
    assert listeners.count(train_step._on_compile_seconds) == 1
    assert monitoring.get_event_listeners().count(
        train_step._on_cache_event) == 1


def test_a_cache_hit_tells_what_its_compile_took(tmp_path):
    """A load from the persistent cache is a ``cache_load`` span inside
    its ``backend`` span, with ``compiled_in_s``: the seconds of the
    compile that wrote the entry, to hold against the load's own."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def fresh_jit():        # the same program twice, two jit caches
        def cached_twice(x):
            return jnp.tanh(x) * 3.0 + jnp.cumsum(x)
        return jax.jit(cached_twice)

    train_step._listen_for_compiles()
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    prior = {n: getattr(jax.config, n) for n in names}
    try:
        for n, v in zip(names, (str(tmp_path), 0.0, 0)):
            jax.config.update(n, v)
        cc.reset_cache()
        x = jnp.arange(7.0)
        before = len(tracing.get_spans())
        fresh_jit()(x)
        missed = _compile_spans_since(before, "cached_twice")
        before = len(tracing.get_spans())
        fresh_jit()(x)
        hit = _compile_spans_since(before, "cached_twice")
    finally:
        for n, v in prior.items():
            jax.config.update(n, v)
        cc.reset_cache()
    assert [s.attributes["kind"] for s in missed] == [
        "trace", "lower", "backend"]
    assert missed[-1].attributes["cache"] == "miss"
    assert [s.attributes["kind"] for s in hit] == [
        "trace", "lower", "cache_load", "backend"]
    load, backend = hit[2], hit[3]
    assert backend.attributes["cache"] == load.attributes["cache"] == "hit"
    assert backend.mono_start <= load.mono_start <= load.mono_end
    assert load.mono_end <= backend.mono_end
    # jax tells the compile's seconds less the load's: the sum is the
    # compile's own as the entry holds it, in whole seconds
    told = load.attributes["compiled_in_s"]
    compiled = missed[-1].mono_end - missed[-1].mono_start
    assert told == pytest.approx(int(compiled), abs=1e-6)
    assert "compiled_in_s" not in backend.attributes
    assert all("compiled_in_s" not in s.attributes for s in missed)


def test_compiled_in_s_is_the_saving_plus_the_load():
    """The listener's arithmetic, on the events as jax sends them."""
    train_step._listen_for_compiles()
    before = len(tracing.get_spans())
    train_step._on_compile_begins(
        "/jax/core/compile/backend_compile_duration", 0.0)
    train_step._on_cache_event("/jax/compilation_cache/cache_hits")
    train_step._on_compile_seconds(train_step._SAVED_EVENT, -1.25)
    train_step._on_compile_seconds(
        "/jax/compilation_cache/cache_retrieval_time_sec", 2.0)
    train_step._on_compile_seconds(
        "/jax/core/compile/backend_compile_duration", 2.5,
        fun_name="jit(slow_to_load)")
    load, backend = _compile_spans_since(before, "slow_to_load")
    assert load.attributes["kind"] == "cache_load"
    # a load that cost more than its compile: 2.0 s for 0.75
    assert load.attributes["compiled_in_s"] == pytest.approx(0.75)
    assert load.mono_end - load.mono_start == pytest.approx(2.0)
    assert backend.mono_end - backend.mono_start == pytest.approx(2.5)
    # the saving is handed over once: a miss after it carries none
    train_step._on_compile_begins(
        "/jax/core/compile/backend_compile_duration", 0.0)
    train_step._on_compile_seconds(
        "/jax/core/compile/backend_compile_duration", 0.1,
        fun_name="jit(slow_to_load)")
    assert "compiled_in_s" not in _compile_spans_since(
        before, "slow_to_load")[-1].attributes


def test_compile_spans_inside_a_session_go_to_its_list():
    sess = train_session.init_session(train_session.TrainContext(),
                                      trace_ctx=("a" * 16, "b" * 16))
    try:
        train_step._listen_for_compiles()
        jax.jit(lambda x: x - 3.0)(jnp.ones((3,)))
    finally:
        train_session.shutdown_session()
    kinds = [s.attributes["kind"] for s in sess.spans]
    assert "trace" in kinds and "backend" in kinds
    assert {s.trace_id for s in sess.spans} == {"a" * 16}
    assert {s.parent_id for s in sess.spans} == {"b" * 16}


# -- (e) nothing is kept per step when no profile runs --------------------

def test_ten_thousand_steps_allocate_no_span(monkeypatch):
    made = []

    class CountingSpan(tracing.Span):
        def __init__(self, *a, **kw):
            made.append(1)
            super().__init__(*a, **kw)

    monkeypatch.setattr(tracing, "Span", CountingSpan)
    ring = len(tracing.get_spans())
    sess = train_session.init_session(train_session.TrainContext())
    try:
        with collect_counters() as input_totals:
            pf = DevicePrefetcher(iter(range(10_000)), depth=4)
            for i, item in enumerate(pf):
                train_session.report({"i": item})
            pf.close()
    finally:
        train_session.shutdown_session()
    assert i == 9_999 and sess.results.qsize() == 10_000
    assert made == [] and sess.spans == []
    assert len(tracing.get_spans()) == ring
    # the first batch's ends and the first report's time wait in the
    # session for the worker, which makes the span when the loop returns
    (t_made, t_handed, told), = sess.first_batches
    assert t_made <= t_handed <= sess.t_first_report
    assert set(told) == {"source_s", "place_s", "stall_s"}
    assert sess.t_first_report <= sess.last_report_ts
    assert input_totals()["input.batches"] == 10_000
    # outside a collector a prefetcher is kept by nobody
    assert DevicePrefetcher(iter(()), depth=1).counters["batches"] == 0
    assert input_totals()["input.batches"] == 10_000


# -- summarize_trace on events named by whole HLO instructions -----------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


GOLDEN_OPS = {  # instruction -> (the event's name, op_name)
    "fusion.165": (
        "%fusion.165 = bf16[32,3,1024,12,64]{2,4,0,3,1:T(8,128)(2,1)} "
        "fusion(bf16[3,12,64]{2,1,0:T(8,128)(2,1)S(1)} %copy-done.499), "
        "kind=kOutput, calls=%fused_computation.365",
        "jit(step)/jvp(GPT2)/blocks/h_0/attn/bte,eshd->bsthd/dot_general"),
    "attn.33": (
        "%attn.33 = (bf16[384,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, "
        "f32[384,1024,1]{2,1,0:T(8,128)}) custom-call(bf16[384,1024,64]"
        "{2,1,0:T(8,128)(2,1)} %bitcast.2028), "
        "custom_call_target=\\\"tpu_custom_call\\\"",
        "jit(step)/jvp(GPT2)/blocks/h_0/attn/pallas_call"),
    "while.26": (
        "%while.26 = (s32[]{:T(128)}, f32[50304,768]{1,0:T(8,128)}) "
        "while((s32[]{:T(128)}) %tuple.7), condition=%c, body=%b",
        "jit(step)/transpose(jvp(loss))/loss/while"),
    "convolution_add_fusion.26": (
        "%convolution_add_fusion.26 = f32[50304,768]{1,0:T(8,128)} "
        "fusion(bf16[2048,50304]{1,0} %p), kind=kLoop, calls=%fc.26",
        "jit(step)/transpose(jvp(loss))/loss/while/body/dot_general"),
    "fusion.9": (
        "%fusion.9 = f32[768]{0} fusion(f32[768]{0} %p), kind=kLoop, "
        "calls=%fused_computation.9", "jit(step)/optimizer/mul"),
    "all-reduce.3": (
        "%all-reduce.3 = f32[768]{0} all-reduce(f32[768]{0} %g), "
        "replica_groups={{0,1,2,3}}, to_apply=%add", ""),
    # added by the compiler, no op_name: read by fusion.9
    "copy-done.7": (
        "%copy-done.7 = f32[768]{0} copy-done((f32[768]{0}) %copy-start.7)",
        ""),
}
GOLDEN_OPERANDS = {"fusion.9": ["copy-done.7"]}


@pytest.fixture(scope="module")
def golden_capture(tmp_path_factory):
    return _capture(tmp_path_factory.mktemp("golden_capture"), GOLDEN_OPS)


def _capture(logdir, ops: dict) -> str:
    from jax.profiler import ProfileData
    us = 1_000_000

    def event(meta, start, dur):
        return (f"events {{ metadata_id: {meta} offset_ps: {start * us} "
                f"duration_ps: {dur * us} }}")

    ids = {name: i + 1 for i, name in enumerate(ops)}
    metas = "".join(
        f'event_metadata {{ key: {ids[n]} value {{ id: {ids[n]} '
        f'name: "{text}" }} }} ' for n, (text, _) in ops.items())
    metas += ('event_metadata { key: 50 value { id: 50 '
              'name: "jit_step(7)" } } ')
    device = (
        'planes { name: "/device:TPU:0" ' + metas
        + 'lines { name: "XLA Modules" timestamp_ns: 0 '
        + event(50, 0, 2000) + ' } '
        # the line's own origin: 1 us after the plane's
        + 'lines { name: "XLA Ops" timestamp_ns: 1000 ' + " ".join([
            event(ids["fusion.165"], 0, 100),
            event(ids["attn.33"], 100, 50),
            event(ids["while.26"], 200, 400),       # holds 300 of:
            event(ids["convolution_add_fusion.26"], 250, 300),
            event(ids["copy-done.7"], 690, 10),
            event(ids["fusion.9"], 700, 60),
            event(ids["all-reduce.3"], 800, 40)])
        + ' } lines { name: "Async XLA Ops" timestamp_ns: 1000 '
        + event(ids["fusion.9"], 0, 1000) + ' } }')
    insts = b"".join(
        _msg(2, _msg(1, name.encode()) + _msg(2, b"fusion")
             + (_msg(7, _msg(2, op.encode())) if op else b"")
             + _varint(35 << 3) + _varint(ids[name])
             + _msg(36, b"".join(_varint(ids[o]) for o in
                                 GOLDEN_OPERANDS.get(name, []))))
        for name, (_, op) in ops.items())
    hlo = _msg(1, _msg(1, b"jit_step") + _msg(3, _msg(1, b"main") + insts))
    octal = "".join(f"\\{b:03o}" for b in hlo)
    metadata = ('planes { name: "/host:metadata" event_metadata { key: 1 '
                'value { id: 1 name: "jit_step(7)" stats { metadata_id: 1 '
                f'bytes_value: "{octal}" }} }} }} }}')
    # the benchmark's reader counts what lies inside this span
    host = ('planes { name: "/host:CPU" event_metadata { key: 1 value { '
            'id: 1 name: "bench.window" } } lines { name: "main" '
            f'timestamp_ns: 0 {event(1, 0, 3000)} }} }} ')
    (logdir / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(
            host + device + metadata))
    return str(logdir)


def test_summarize_trace_classes_by_opcode_and_fusion_kind(golden_capture):
    got = xplane.summarize_trace(golden_capture, steps=2)
    assert got["plane"] == "/device:TPU:0" and got["files"] == 1
    # self times: the while's body is not counted twice, and the
    # asynchronous line is not counted at all
    assert got["total_ms"] == pytest.approx(0.660)
    assert got["ms_per_step"] == pytest.approx(0.330)
    assert got["class_ms"] == {
        # the kOutput fusion, and the one with a convolution in its name
        "mxu": pytest.approx(0.050 + 0.150),
        "kernel": pytest.approx(0.025),
        "collective": pytest.approx(0.020),
        # the while's own time, fusion.9, the compiler's copy
        "other": pytest.approx(0.050 + 0.030 + 0.005)}
    assert got["matmul_share"] == pytest.approx(400 / 660, abs=1e-4)
    assert [r["name"] for r in got["top_matmul"]] == [
        "convolution_add_fusion.26", "fusion.165"]
    assert got["top_non_matmul"][0] == {
        "name": "while.26", "ms": pytest.approx(0.050),
        "share": pytest.approx(100 / 660, abs=1e-4)}


def test_summarize_trace_groups_by_program_scope(golden_capture):
    got = xplane.summarize_trace(golden_capture, steps=2)
    assert got["scope_ms"] == {
        "loss/loss/while": pytest.approx(0.150),
        "blocks/h_*/attn": pytest.approx(0.075),
        "loss/loss": pytest.approx(0.050),
        # fusion.9 and the copy it reads, which has no op_name of its own
        "optimizer": pytest.approx(0.030 + 0.005),
        "unscoped": pytest.approx(0.020)}
    assert sum(got["scope_ms"].values()) == pytest.approx(
        got["ms_per_step"])


def test_summarize_trace_and_the_benchmark_agree_by_scope(golden_capture):
    """The operator's table and the benchmark's scope metrics restate
    the same rules in two places (the program does not import the
    benchmark, the yardstick does not lean on the program's parser):
    one capture through both gives the same self time under each scope,
    the compiler's copy under its user's included."""
    import sys
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark"))
    try:
        from benchlib import program_trace
    finally:
        sys.path.pop(0)
    steps = 2
    theirs = program_trace.reduce_file(
        xplane.trace_files(golden_capture)[0], steps)
    ours = xplane.summarize_trace(golden_capture, steps=steps)
    by_scope: dict[str, float] = {}
    for path, ms in ours["scope_ms"].items():
        top = path.split("/")[0]
        by_scope[top] = by_scope.get(top, 0.0) + ms
    assert by_scope == {
        scope: pytest.approx(seconds / steps * 1e3)
        for scope, seconds in theirs["scope_s"].items()}
    assert set(by_scope) == {"blocks", "loss", "optimizer", "unscoped"}
    assert ours["scope_ms"]["blocks/h_*/attn"] == pytest.approx(
        theirs["blocks_s"]["attn"] / steps * 1e3)
    assert theirs["inherited_s"] == pytest.approx(10e-6)
    assert program_trace.SCOPES == xplane.SCOPES
    assert "scope_note" not in ours


def test_summarize_trace_says_when_the_executable_has_no_scopes(
        tmp_path):
    """The step as compiled before the scopes, which is also what a
    compile-cache hit on such an entry loads."""
    before = {name: (text, op.replace("/blocks", "")
                     .replace("(loss))/loss", "())")
                     .replace("/optimizer", ""))
              for name, (text, op) in GOLDEN_OPS.items()}
    assert before["fusion.9"][1] == "jit(step)/mul"
    got = xplane.summarize_trace(_capture(tmp_path, before), steps=2)
    assert list(got["scope_ms"]) == ["unscoped"]
    assert "compile cache" in got["scope_note"]
    assert got["class_ms"]["mxu"] == pytest.approx(0.200)


@pytest.mark.parametrize("text, want", [
    (GOLDEN_OPS["fusion.165"][0], ("fusion.165", "fusion", "kOutput")),
    (GOLDEN_OPS["while.26"][0], ("while.26", "while", "")),
    ("%all-gather.31 = bf16[64,2048,768]{2,1,0} all-gather(bf16[16] %x)",
     ("all-gather.31", "all-gather", "")),
    ("dot_general", ("dot_general", "", "")),
])
def test_parse_hlo(text, want):
    assert xplane.parse_hlo(text.replace('\\"', '"')) == want


@pytest.mark.parametrize("args, want", [
    (("fusion.1", "fusion", "kOutput"), "mxu"),
    (("convolution_add_fusion.26", "fusion", "kLoop"), "mxu"),
    (("fusion.9", "fusion", "kLoop"), "other"),
    (("attn.33", "custom-call", ""), "kernel"),
    (("all-reduce-start.1", "all-reduce-start", ""), "collective"),
    (("dot.3", "", ""), "mxu"),                 # a CPU event's plain name
    (("convert_element_type", "", ""), "other"),
])
def test_classify(args, want):
    assert xplane.classify(*args) == want
