"""``program_trace.py`` on a small synthetic profile whose scope, host-span
and gap numbers are known (an XSpace with a ``/host:metadata`` plane that
holds a hand-made ``HloProto``), on synthetic ``train.fit`` spans, and on
runs that have neither: every new reader then returns None."""

import os

import pytest

from benchlib import manifest as mf
from benchlib import program_trace as pt
from benchlib import report

US = 1_000_000      # picoseconds in a microsecond

NEW = ["model.blocks_ms_per_step", "model.attention_ms_per_step",
       "model.mlp_ms_per_step", "model.loss_ms_per_step",
       "step.optimizer_ms_per_step", "model.unscoped_ms_per_step",
       "input.source_ms_per_step", "input.place_ms_per_step",
       "input.stall_ms_per_step", "device.idle_unnamed_pct",
       "fit.gang_start_s", "fit.loop_start_s", "step.trace_lower_s",
       "step.cache_load_s"]


# -- a hand-made HloProto: name and op_name of each instruction ---------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _hlo_proto(instructions: dict[str, str],
               operands: dict[str, list[str]]) -> bytes:
    ids = {name: 100 + i for i, name in enumerate(instructions)}
    insts = b"".join(
        _msg(2, _msg(1, name.encode()) + _msg(2, b"fusion")
             + _varint(5 << 3) + _varint(7)       # a varint field to skip
             + (_msg(7, _msg(1, b"type") + _msg(2, op.encode()))
                if op else b"")
             + _varint(35 << 3) + _varint(ids[name])
             + _msg(36, b"".join(_varint(ids[o])
                                 for o in operands.get(name, []))))
        for name, op in instructions.items())
    computation = _msg(1, b"main") + insts
    return _msg(1, _msg(1, b"jit_step") + _msg(3, computation))


OP_NAMES = {
    "fusion.1": "jit(step)/jvp(GPT2)/blocks/h_0/attn/dot_general",
    "attn.3": "jit(step)/transpose(jvp(GPT2))/blocks/h_1/attn/pallas_call",
    "fusion.30": "jit(step)/jvp(GPT2)/blocks/h_0/mlp/fc/dot_general",
    "fusion.31": "jit(step)/jvp(GPT2)/blocks/ln_f/mul",
    "while.7": "jit(step)/transpose(jvp(loss))/loss/while",
    "fusion.9": "jit(step)/transpose(jvp(loss))/loss/while/body/dot_general",
    "fusion.20": "jit(step)/optimizer/mul",
    "fusion.40": "jit(step)/jvp(GPT2)/embed/wte/jit(_take)/gather",
    "copy.4": "",                       # no op_name, no user: unscoped
    "copy-start.5": "",                 # no op_name either, but read by
    "copy-done.5": "",                  # copy-done.5, which fusion.1 reads
}
OPERANDS = {"copy-done.5": ["copy-start.5"],
            "fusion.1": ["copy-done.5", "fusion.40"]}
DEVICE_NAMES = {
    1: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput, calls=%f1",
    2: "%attn.3 = (bf16[8]{0}, f32[8]{0}) custom-call(bf16[8]{0} %q)",
    3: "%fusion.30 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput, calls=%f3",
    4: "%fusion.31 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f4",
    5: "%while.7 = (s32[]{:T(128)}, f32[8]{0}) while((s32[]) %t), body=%b",
    6: "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput, calls=%f9",
    7: "%fusion.20 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f2",
    8: "%fusion.40 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f5",
    9: "%copy.4 = f32[8]{0} copy(f32[8]{0} %p)",
    10: "%copy-done.5 = f32[8]{0} copy-done((f32[8]{0}) %copy-start.5)"}


def _event(meta: int, start_us: float, dur_us: float) -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_us * US)} "
            f"duration_ps: {int(dur_us * US)} }}")


def _line(name: str, events: list[str]) -> str:
    return f'lines {{ name: "{name}" timestamp_ns: 0 {" ".join(events)} }}'


def _plane(name: str, names: dict, lines: list[str], extra: str = "") -> str:
    metas = "".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }} '
        for k, v in names.items())
    return f'planes {{ name: "{name}" {metas} {extra} {" ".join(lines)} }}'


def _device(n: int) -> str:
    # the window is 1000..2000 us; everything runs in module jit_step(1)
    names = {**DEVICE_NAMES, 20: "jit_step(1)"}
    return _plane(f"/device:TPU:{n}", names, [
        _line("XLA Modules", [_event(20, 900, 1100)]),
        _line("XLA Ops", [
            _event(8, 900, 150),            # embed, clipped to 1000..1050
            _event(1, 1050, 100),           # blocks / attn
            _event(2, 1150, 50),            # blocks / attn (kernel)
            _event(3, 1200, 100),           # blocks / mlp
            _event(4, 1300, 20),            # blocks, neither (ln_f)
            _event(10, 1320, 5),            # blocks through its user
            # idle 1325..1330: under 20 us, not counted
            _event(5, 1330, 300),           # loss: while 1330..1630, holds
            _event(6, 1400, 200),           #   loss, 200
            # idle 1630..1700: 70 us under train.input.wait
            _event(7, 1700, 100),           # optimizer
            # idle 1800..1850: 50 us under no train.* span
            _event(9, 1850, 150),           # unscoped, to 2000
        ])])


HOST_NAMES = {1: "bench.window", 2: "train.input.wait", 3: "train.report",
              4: "train.input.source", 5: "train.input.place", 6: "other"}
HOST = _plane("/host:CPU", HOST_NAMES, [
    _line("main", [_event(1, 1000, 1000), _event(2, 1600, 120),
                   _event(3, 1720, 10), _event(2, 900, 150)]),
    _line("device_prefetch", [_event(4, 1100, 300), _event(5, 1400, 60),
                              _event(6, 1790, 100)])])


def _metadata_plane(op_names: dict[str, str] = OP_NAMES) -> str:
    octal = "".join(f"\\{b:03o}"
                    for b in _hlo_proto(op_names, OPERANDS))
    return ('planes { name: "/host:metadata" '
            'stat_metadata { key: 1 value { id: 1 name: "Hlo Proto" } } '
            'event_metadata { key: 1 value { id: 1 name: "jit_step(1)" '
            f'stats {{ metadata_id: 1 bytes_value: "{octal}" }} }} }} }}')


@pytest.fixture(scope="module")
def xspace() -> bytes:
    from jax.profiler import ProfileData
    return ProfileData.text_proto_to_serialized_xspace(
        HOST + _device(0) + _device(1) + _metadata_plane())


@pytest.fixture(scope="module")
def reduced(xspace):
    from jax.profiler import ProfileData
    return pt.reduce_profile(ProfileData.from_serialized_xspace(xspace),
                             pt.op_names(xspace), steps=2)


def test_op_names_from_the_metadata_plane(xspace):
    got = pt.op_names(xspace)
    own = {k: (v, False) for k, v in OP_NAMES.items() if v}
    lent = (OP_NAMES["fusion.1"], True)     # from the nearest user
    assert got == {"jit_step(1)": {**own, "copy-start.5": lent,
                                   "copy-done.5": lent}}


@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/jvp(GPT2)/blocks/h_3/attn/dot_general",
     ("blocks", ("h_3", "attn", "dot_general"))),
    ("jit(step)/transpose(jvp(GPT2))/blocks/h_3/mlp/fc/dot_general",
     ("blocks", ("h_3", "mlp", "fc", "dot_general"))),
    ("jit(step)/transpose(jvp(loss))/loss/while", ("loss", ("loss", "while"))),
    ("jit(step)/jvp(loss)/convert_element_type",
     ("loss", ("convert_element_type",))),
    ("jit(multi)/while/body/closed_call/optimizer/mul", ("optimizer", ("mul",))),
    ("jit(multi)/while/body/jvp(ResNet)/embed/conv_init/conv_general_dilated",
     ("embed", ("conv_init", "conv_general_dilated"))),
    ("jit(step)/jvp()/while/body/dot_general", ("unscoped", ())),
    ("jit(loss)/mul", ("unscoped", ())),        # a jitted function's name
    ("", ("unscoped", ())),
])
def test_scope_of(op_name, want):
    assert pt.scope_of(op_name) == want


def test_scope_seconds_are_self_times_per_chip(reduced):
    s = {k: v * 1e6 for k, v in reduced["scope_s"].items()}
    assert reduced["devices"] == 2 and reduced["steps"] == 2
    assert reduced["window_s"] == pytest.approx(1000e-6)
    assert s["embed"] == pytest.approx(50)          # clipped to the window
    assert s["blocks"] == pytest.approx(100 + 50 + 100 + 20 + 5)
    assert reduced["inherited_s"] * 1e6 == pytest.approx(5)
    assert s["loss"] == pytest.approx(300)          # the while: 100 + 200
    assert s["optimizer"] == pytest.approx(100)
    assert s["unscoped"] == pytest.approx(150)
    assert sum(s.values()) == pytest.approx(1000 - 5 - 70 - 50)    # busy
    within = {k: v * 1e6 for k, v in reduced["blocks_s"].items()}
    assert within == {"attn": pytest.approx(155), "mlp": pytest.approx(100)}


def test_host_spans_of_every_thread_clipped_to_the_window(reduced):
    h = {k: v * 1e6 for k, v in reduced["host_s"].items()}
    assert h == {"train.input.wait": pytest.approx(120 + 50),
                 "train.report": pytest.approx(10),
                 "train.input.source": pytest.approx(300),
                 "train.input.place": pytest.approx(60)}


def test_idle_gaps_named_by_the_train_span_over_them(reduced):
    g = {k: v * 1e6 for k, v in reduced["idle_by_span_s"].items()}
    assert g == {"train.input.wait": pytest.approx(70),
                 "no_train_span": pytest.approx(50)}


def test_no_window_or_no_device_plane_is_none():
    from jax.profiler import ProfileData
    only_host = ProfileData.from_text_proto(HOST)
    assert pt.reduce_profile(only_host, {}, 1) is None
    no_window = ProfileData.from_text_proto(_device(0))
    assert pt.reduce_profile(no_window, {}, 1) is None


# -- the spans of fit(), and the readers end to end -----------------------

def _run(worker=None, traced=True):
    man = mf.load_manifest()
    cell = mf.find_cell(man, "gpt2-124m.b32-t1024")
    return report.Run(cell, worker or {}, {}, {},
                      {"steps": 2} if traced else None)


@pytest.fixture
def fit_in_ring(tmp_path, xspace):
    return _fit_in_ring(tmp_path, xspace)


def _fit_in_ring(tmp_path, xspace: bytes) -> dict:
    """A finished fit in the process tracer's ring, as ``JaxTrainer.fit``
    leaves it, with its profile where ``run.py`` would have put it."""
    from ray_tpu.util import tracing
    trace_dir = tmp_path / "trace" / "plugins" / "profile" / "x"
    trace_dir.mkdir(parents=True)
    (trace_dir / "host.xplane.pb").write_bytes(xspace)
    tid = os.urandom(8).hex()

    def span(name, a, b, parent="root", **attributes):
        return tracing.Span(
            name=name, trace_id=tid, span_id=os.urandom(8).hex(),
            parent_id=None if name == "train.fit" else parent, start=a,
            end=b, attributes=attributes, mono_start=a, mono_end=b)

    # newer than any fit an earlier test of this process left behind
    t0 = max([s.mono_end for s in tracing.get_spans()
              if s.name == "train.fit"], default=0.0) + 1000.0
    spans = [
        span("train.fit", t0, t0 + 100,
             trial_dir=str(tmp_path / "experiments" / "fit")),
        span("train.fit.gang_start", t0 + 1, t0 + 9),
        span("train.fit.start_loop", t0 + 9, t0 + 9.5),
        span("train.compile", t0 + 10, t0 + 14, kind="trace"),
        span("train.compile", t0 + 13, t0 + 15, kind="lower"),  # overlaps
        span("train.compile", t0 + 15, t0 + 18, kind="backend"),
        span("train.compile", t0 + 15, t0 + 17.5, kind="cache_load"),
        span("train.compile", t0 + 40, t0 + 41, kind="trace"),  # in window
    ]
    tracing.get_tracer().add_spans([s.to_dict() for s in spans])
    return {"stamps": [t0 + 20, t0 + 30, t0 + 50], "open_i": 1}


def test_readers_on_a_fit_and_its_profile(fit_in_ring):
    run = _run(fit_in_ring)
    got = {name: mf.load_reader(name)(run) for name in NEW}
    assert got == {
        "model.blocks_ms_per_step": pytest.approx(0.275 / 2),
        "model.attention_ms_per_step": pytest.approx(0.155 / 2),
        "model.mlp_ms_per_step": pytest.approx(0.100 / 2),
        "model.loss_ms_per_step": pytest.approx(0.300 / 2),
        "step.optimizer_ms_per_step": pytest.approx(0.100 / 2),
        "model.unscoped_ms_per_step": pytest.approx(0.150 / 2),
        "input.source_ms_per_step": pytest.approx(0.300 / 2),
        "input.place_ms_per_step": pytest.approx(0.060 / 2),
        "input.stall_ms_per_step": pytest.approx(0.170 / 2),
        "device.idle_unnamed_pct": pytest.approx(5.0),
        "fit.gang_start_s": pytest.approx(8.0),
        "fit.loop_start_s": pytest.approx(0.5),
        "step.trace_lower_s": pytest.approx(5.0),       # 10..15, once
        "step.cache_load_s": pytest.approx(2.5),
    }


def test_scope_readers_are_none_on_an_executable_without_scopes(tmp_path):
    """The step as the parent compiled it (flax's module names alone, a
    bare optimizer), as a compile-cache hit brings it back: nothing to
    split by scope; what the host's spans say still stands."""
    from jax.profiler import ProfileData
    before = {k: v.replace("/blocks", "").replace("/embed", "")
              .replace("/optimizer", "").replace("(loss))/loss", "())")
              for k, v in OP_NAMES.items()}
    assert before["fusion.20"] == "jit(step)/mul"
    assert before["while.7"] == "jit(step)/transpose(jvp())/while"
    run = _run(_fit_in_ring(
        tmp_path, ProfileData.text_proto_to_serialized_xspace(
            HOST + _device(0) + _metadata_plane(before))))
    for name in NEW[:6]:
        assert mf.load_reader(name)(run) is None, name
    assert mf.load_reader("input.source_ms_per_step")(run) == \
        pytest.approx(0.300 / 2)
    assert mf.load_reader("device.idle_unnamed_pct")(run) == \
        pytest.approx(5.0)


def test_device_readers_are_none_without_a_trace(fit_in_ring):
    run = _run(fit_in_ring, traced=False)
    for name in NEW[:10]:
        assert mf.load_reader(name)(run) is None, name


@pytest.mark.parametrize("name", NEW)
def test_reader_is_none_without_a_fit_span(name, monkeypatch):
    """A program from before these spans: the ring holds no train.fit."""
    from ray_tpu.util import tracing
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    assert mf.load_reader(name)(_run({"stamps": [0.0], "open_i": 0})) is None


def test_manifest_holds_the_fourteen_and_passes():
    man = mf.load_manifest()
    assert mf.check_manifest(man) == []
    names = [m["name"] for m in man["per_layer"]]
    assert names[-14:] == NEW and len(names) == 32
    sources = {m["name"]: m["source"] for m in man["per_layer"]}
    assert {sources[n] for n in NEW} == {"device_trace", "program_span"}
