"""The trace reduction on a small synthetic XSpace (text proto, read by
``jax.profiler.ProfileData``), where every number is known, and on event
names copied from a trace recorded on the chip."""

import os

import pytest

from benchlib import trace

HERE = os.path.dirname(os.path.abspath(__file__))

US = 1_000_000      # picoseconds in a microsecond


def _event(meta: int, start_us: float, dur_us: float) -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_us * US)} "
            f"duration_ps: {int(dur_us * US)} }}")


def _plane(name: str, line: str, names: dict, events: list[str]) -> str:
    metas = "".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }} '
        for k, v in names.items())
    return (f'planes {{ name: "{name}" {metas} '
            f'lines {{ name: "{line}" timestamp_ns: 0 {" ".join(events)} }} }}')


# named as the v5e's trace names them: by the whole HLO instruction
DEVICE_NAMES = {
    1: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput, calls=%fc.1",
    2: "%attn.3 = (bf16[8]{0}, f32[8]{0}) custom-call(bf16[8]{0} %q)",
    3: "%while.7 = (s32[]{:T(128)}, f32[8]{0}) while((s32[]) %t), body=%b",
    4: "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fc.9",
    5: "%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %g)",
    6: "%all-reduce-done.1 = f32[8]{0} all-reduce-done(f32[8]{0} %s)",
    7: "%all-gather.2 = bf16[32]{0} all-gather(bf16[8]{0} %h), dimensions={0}",
    8: "%copy.4 = f32[8]{0} copy(f32[8]{0} %p)"}


def _device(n: int) -> str:
    # window is 1000..2000 us (the host's bench.window)
    return _plane(f"/device:TPU:{n}", "XLA Ops", DEVICE_NAMES, [
        _event(1, 900, 200),   # clipped to 1000..1100
        _event(2, 1100, 100),          # kernel
        _event(3, 1200, 300),                # 1200..1500, holds:
        _event(4, 1250, 100),          #   other, 100
        _event(1, 1350, 100),   #   mxu, 100
        # idle 1500..1510 (under 20 us)
        _event(5, 1510, 10),            # async start
        _event(8, 1520, 80),       # compute meanwhile
        _event(6, 1600, 100),           # done: 1600..1700
        # idle 1700..1800: 100 us, under bench.input
        _event(7, 1800, 50),            # sync collective
        _event(4, 1850, 150),          # to 2000
    ])


HOST = _plane("/host:CPU", "python3", {1: "bench.window", 2: "bench.input",
                                        3: "bench.sync", 4: "other"}, [
    _event(1, 1000, 1000), _event(3, 1000, 690), _event(2, 1690, 120),
    _event(4, 1500, 20)])


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(HOST + _device(0) + _device(1))


def test_busy_idle_and_window(profile):
    got = trace.reduce_profile(profile, steps=2)
    assert got["devices"] == 2
    assert got["window_s"] == pytest.approx(1000e-6)
    assert got["busy_s"] == pytest.approx(890e-6)     # 1000 - 10 - 100


def test_classes_by_self_time(profile):
    c = trace.reduce_profile(profile, steps=2)["class_s"]
    assert c["kernel"] == pytest.approx(100e-6)
    assert c["mxu"] == pytest.approx(200e-6)           # clipped 100 + nested 100
    # while self 100 + nested fusion 100 + copy 80 + last fusion 150
    assert c["other"] == pytest.approx(430e-6)
    assert c["collective"] == pytest.approx(160e-6)    # 10 + 100 + 50
    assert sum(c.values()) == pytest.approx(890e-6)    # == busy: no double count


def test_collectives_total_and_exposed(profile):
    got = trace.reduce_profile(profile, steps=2)
    # async pair 1510..1700 (190) + all-gather 50
    assert got["collective_s"] == pytest.approx(240e-6)
    # the copy hides 80 of the pair
    assert got["collective_exposed_s"] == pytest.approx(160e-6)


def test_idle_gaps_are_named_by_the_host_span(profile):
    got = trace.reduce_profile(profile, steps=2)
    gaps = got["idle_by_span_s"]
    assert gaps["bench.input"] == pytest.approx(100e-6)
    assert gaps["gaps_under_20_us"] == pytest.approx(10e-6)
    assert dict(map(tuple, got["idle_gaps"])) == pytest.approx(gaps)
    assert got["device_ops"][0][0].startswith("fusion.9")


def test_a_trace_without_a_device_plane_is_an_error():
    from jax.profiler import ProfileData
    with pytest.raises(ValueError, match="no /device:TPU"):
        trace.reduce_profile(ProfileData.from_text_proto(HOST), steps=1)


def test_interval_helpers():
    assert trace.merge([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.classify("convert.3", "") == "other"
    assert trace.classify("all-reduce.1", "") == "collective"
    assert trace.parse_hlo("bench.window") == ("bench.window", "", "")


# Event names as the v5e's trace gives them (my chip run, PR 24),
# shortened in the operand lists.
REAL = [
    ("%while.2 = (s32[]{:T(128)}, f32[50304,768]{1,0:T(8,128)}, "
     "bf16[16,2048,768]{2,1,0:T(8,128)(2,1)}) while((s32[]{:T(128)}, "
     "f32[50304,768]{1,0:T(8,128)}) %tuple.753), "
     "condition=%wide.region_60.72.clone, body=%wide.region_59.71.clone.sunk",
     ("while.2", "while", ""), "other"),
    ("%convolution_add_fusion.26 = f32[50304,768]{1,0:T(8,128)} "
     "fusion(bf16[2048,50304]{1,0:T(8,128)(2,1)} %fusion.2363, "
     "pred[]{:T(512)S(6)} %compare.6), kind=kOutput, "
     "calls=%fused_computation.35.clone.clone",
     ("convolution_add_fusion.26", "fusion", "kOutput"), "mxu"),
    ("%fusion.2353 = (f32[2048]{0:T(1024)S(1)}, f32[2048,50304]{1,0:T(8,128)}) "
     "fusion(bf16[50304,768]{1,0:T(8,128)(2,1)S(1)} %get-tuple-element.1224), "
     "kind=kOutput, calls=%fused_computation.3085.clone.clone",
     ("fusion.2353", "fusion", "kOutput"), "mxu"),
    ("%exponential_reduce_fusion.2 = f32[2048]{0:T(1024)S(1)} "
     "fusion(f32[2048,50304]{1,0:T(8,128)} %get-tuple-element.1186), "
     "kind=kLoop, calls=%fused_computation.6.clone.clone",
     ("exponential_reduce_fusion.2", "fusion", "kLoop"), "other"),
    ("%attn.46 = (bf16[384,1024,64]{2,1,0:T(8,128)(2,1)}, "
     "bf16[384,1024,64]{2,1,0:T(8,128)(2,1)}) custom-call("
     "bf16[384,1024,64]{2,1,0:T(8,128)(2,1)} %bitcast.1981), "
     'custom_call_target="tpu_custom_call"',
     ("attn.46", "custom-call", ""), "kernel"),
    ("%all-reduce-start.3 = f32[768]{0:T(1024)} all-reduce-start("
     "f32[768]{0:T(1024)} %x), replica_groups={{0,1,2,3}}",
     ("all-reduce-start.3", "all-reduce-start", ""), "collective"),
    ("%copy-done.300 = f32[768]{0:T(1024)S(1)} copy-done((f32[768]{0:T(1024)"
     "S(1)}, f32[768]{0:T(1024)}, u32[]{:S(2)}) %copy-start.300)",
     ("copy-done.300", "copy-done", ""), "other"),
]


@pytest.mark.parametrize("text,parsed,cls", REAL,
                         ids=[r[1][0] for r in REAL])
def test_event_names_of_a_real_trace_parse(text, parsed, cls):
    assert trace.parse_hlo(text) == parsed
    assert trace.classify(*parsed) == cls
