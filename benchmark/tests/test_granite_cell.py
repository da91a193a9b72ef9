"""What the granite-4.0-h-micro cell adds to the benchmark:
``flops_granite.py`` against counts by hand at the cell's shapes, the two
new readers and the ones the cell joins on a small synthetic profile whose
numbers are known (built with ``test_program_trace.py``'s helpers) and on
runs with nothing to read, the manifest's entries wherever they stand in
their lists, the configuration file against the catalog's keys, the limit
against its readings, the builder's refusals, and the rehearsal of the
cell end to end."""

import json
import os
import subprocess
import sys
import types

import pytest

import test_program_trace as tp
from benchlib import flops, flops_granite as fg
from benchlib import manifest as mf, report

CELL = "granite-4.0-h-micro.b1-t8192"
CONFIG = "granite-4.0-h-micro"
NEW = ["model.mamba_proj_ms_per_step", "ssm.score_squares_per_group"]
JOINED = ["model.attention_ms_per_step", "model.mlp_ms_per_step",
          "kernel.attn_flash_ms_per_step", "attn_flash_roofline",
          "model.attn_repeat_ms_per_step", "model.mamba_ms_per_step",
          "model.ssm_scan_ms_per_step", "ssm_scan_roofline"]
KEYS = {"loss", "grad_norm", "update_norm", "mamba_out_rms",
        "grad_norm_mamba_ssm", "grad_norm_table", "grad_norm_attn"}


def _cfg():
    return mf.find_cell(mf.load_manifest(), CELL)["config_file"]


def _mcfg():
    return mf.load_builder("granite").model_config(_cfg(), tiny=False)


# -- flops_granite.py against counts by hand ----

def test_parameters_of_each_part_and_of_the_cut():
    cut = _mcfg()
    per = fg.layer_params(cut)
    assert per == cut.layer_params()
    assert per["mamba"] == (2048 * 8512 + 5 * 4352 + 192 + 4096
                            + 4096 * 2048) == 25_847_232
    assert per["attention"] == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert per["mlp"] == 3 * 2048 * 8192 and per["norms"] == 4096
    assert fg.num_params(cut) == cut.num_params() == 797_850_560
    assert fg.num_params(cut) * 14 == pytest.approx(11.17e9, rel=1e-3)


def test_required_operations_per_token_and_per_step():
    c = _mcfg()
    per = fg.forward_flops_per_token(c)
    assert per["mamba_proj"] == 9 * 2 * (2048 * 8512 + 4096 * 2048)
    # the square once a group, its product with x a head, two state matmuls
    assert fg.scan_forward_flops_per_token(c) == (
        256 * 128 + 64 * 256 * 64 + 4 * 64 * 64 * 128) == 3_178_496
    assert per["mamba_scan"] == 9 * 3_178_496
    assert per["attn_proj"] == 2 * (2 * 2048 * 2048 + 2 * 2048 * 512)
    assert per["attn_core"] == 2 * 8192 * 2048
    assert per["mlp"] == 10 * 2 * 3 * 2048 * 8192
    assert per["head"] == 2 * 2048 * 25088
    total = fg.train_flops_per_token(c) * 8192
    assert total == pytest.approx(4.073e13, rel=1e-3)
    assert total / 197e12 == pytest.approx(0.2068, rel=1e-3)
    share = {k: 3 * v * 8192 / total for k, v in per.items()}
    assert share["mlp"] == pytest.approx(0.607, abs=1e-3)
    assert share["mamba_proj"] == pytest.approx(0.280, abs=1e-3)
    assert share["mamba_scan"] == pytest.approx(0.017, abs=1e-3)
    assert share["head"] == pytest.approx(0.062, abs=1e-3)


def test_kernel_costs_and_their_least_times():
    """The scan's bytes count ``B`` and ``C`` once a group (256 lanes a
    token, not 2,048) and do not move with the chunk that runs."""
    import dataclasses
    c = _mcfg()
    scan = fg.ssm_scan_train_cost(c, 8192)
    assert scan["flops"] == 9 * 8192 * 3 * 3_178_496
    row = (4096 + 256) * 2 + 64 * 4
    forward = row + 4096 * 2
    states = 8192 / 256 * 4096 * 128 * 4 * 2
    assert scan["bytes"] == 9 * (8192 * (forward + forward + 4096 * 2 + row)
                                 + states)
    assert fg.ssm_scan_train_cost(dataclasses.replace(c, chunk=128),
                                  8192) == scan
    least = flops.roofline(scan["flops"], scan["bytes"], 197e12, 819e9)
    assert least["bound"] == "memory"
    assert least["least_s"] == pytest.approx(6.11e-3, rel=1e-2)
    core = fg.flash_core_train_cost(c, 1)
    assert core == flops.flash_attention_train_cost(1, 32, 8192, 64, 1)
    assert flops.roofline(core["flops"], core["bytes"], 197e12,
                          819e9)["bound"] == "compute"


# -- the readers on a synthetic profile ----

L = "jit(step)/jit(main)/jvp(Granite)/"
B = "jit(step)/jit(main)/transpose(jvp(Granite))/"
R = "blocks/checkpoint/rematted_computation/Granite/blocks/"
OP_NAMES = {
    "fusion.1": L + "blocks/h_0/mamba/in_proj/dot_general",
    "fusion.2": L + "blocks/h_0/mamba/conv/mul",
    "ssd.3": L + "blocks/h_0/mamba/scan/jit(_ssd_fwd)/pallas_call",
    "fusion.4": L + "blocks/h_0/mamba/out_proj/dot_general",
    "fusion.5": B + R + "h_0/mamba/in_proj/dot_general",
    "ssd.6": B + "blocks/checkpoint/h_0/mamba/scan/jit(_ssd_bwd)/pallas_call",
    "fusion.7": B + "blocks/checkpoint/h_0/mamba/out_proj/dot_general",
    "fusion.8": L + "blocks/h_5/attn/qkv/q/dot_general",
    "fusion.9": L + "blocks/h_5/attn/repeat/broadcast",
    "flash.10": L + "blocks/h_5/attn/core/jit(_flash_fwd)/pallas_call",
    "flash.11": B + "blocks/checkpoint/h_5/attn/core/jit(_flash_bwd)/"
                    "pallas_call",
    "fusion.12": L + "blocks/h_5/mlp/gate_up/dot_general",
    "fusion.13": B + "blocks/checkpoint/h_5/mlp/down/dot_general",
    "fusion.14": L + "loss/loss/while/body",
    "fusion.15": "jit(step)/optimizer/mul",
}
US = [14, 6, 30, 10, 14, 70, 12, 5, 3, 20, 20, 40, 50, 40, 100]


def _xspace(op_names=None) -> bytes:
    from jax.profiler import ProfileData
    op_names = op_names or OP_NAMES
    names = {
        n: (f"%{name} = bf16[8]{{0}} custom-call(bf16[8]{{0}} %x)"
            if name.split(".")[0] in ("flash", "ssd") else
            f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, "
            f"calls=%f{n}")
        for n, name in enumerate(op_names, start=1)}
    names[30] = "jit_step(1)"

    def device(n):
        at, events = 1000, []
        for i, us in enumerate(US, start=1):
            events.append(tp._event(i, at, us))
            at += us
        return tp._plane(f"/device:TPU:{n}", names, [
            tp._line("XLA Modules", [tp._event(30, 900, 1100)]),
            tp._line("XLA Ops", events)])

    octal = "".join(f"\\{b:03o}" for b in tp._hlo_proto(op_names, {}))
    meta = ('planes { name: "/host:metadata" '
            'stat_metadata { key: 1 value { id: 1 name: "Hlo Proto" } } '
            'event_metadata { key: 1 value { id: 1 name: "jit_step(1)" '
            f'stats {{ metadata_id: 1 bytes_value: "{octal}" }} }} }} }}')
    return ProfileData.text_proto_to_serialized_xspace(
        tp.HOST + device(0) + device(1) + meta)


def _run(tmp_path, raw: bytes, traced=True):
    man = mf.load_manifest()
    facts = {
        **tp._fit_in_ring(tmp_path, raw), "kind": "TPU v5 lite",
        "kernel_cost_per_step": {"flops": 197e12 * 8e-6, "bytes": 1.0},
        "shapes": {"ssm_cost_per_step": {"flops": 1.0,
                                         "bytes": 819e9 * 5e-6}}}
    return report.Run(mf.find_cell(man, CELL), facts, {}, {},
                      {"steps": 2} if traced else None)


def _note(**attributes):
    """A ``train.compile`` span of kind ``trace`` in the newest fit."""
    from ray_tpu.util import tracing
    root = max((s for s in tracing.get_spans() if s.name == "train.fit"),
               key=lambda s: s.mono_end)
    tracing.get_tracer().add_spans([tracing.Span(
        name="train.compile", trace_id=root.trace_id,
        span_id=os.urandom(8).hex(), parent_id=root.span_id,
        start=root.mono_start + 42, end=root.mono_start + 43,
        attributes={"kind": "trace", **attributes},
        mono_start=root.mono_start + 42,
        mono_end=root.mono_start + 43).to_dict()])


def test_every_reader_of_the_cell_reads(tmp_path):
    run = _run(tmp_path, _xspace())
    got = {name: mf.load_reader(name)(run)
           for name in NEW[:1] + JOINED}
    assert got == {
        "model.mamba_proj_ms_per_step": pytest.approx(0.050 / 2),
        "model.attention_ms_per_step": pytest.approx(0.048 / 2),
        "model.mlp_ms_per_step": pytest.approx(0.090 / 2),
        "kernel.attn_flash_ms_per_step": pytest.approx(0.040 / 2),
        "attn_flash_roofline": pytest.approx(40.0),     # 8 us over 20
        "model.attn_repeat_ms_per_step": pytest.approx(0.003 / 2),
        "model.mamba_ms_per_step": pytest.approx(0.156 / 2),
        "model.ssm_scan_ms_per_step": pytest.approx(0.100 / 2),
        "ssm_scan_roofline": pytest.approx(10.0),       # 5 us over 50
    }


def test_the_counter_reads_the_note_off_the_newest_trace_span(tmp_path):
    run = _run(tmp_path, _xspace())
    counter = mf.load_reader("ssm.score_squares_per_group")
    assert counter(run) is None             # a program without the note
    _note(ssm_blocks_per_group=8, ssm_groups=1)
    assert counter(run) == 8.0


def test_new_readers_are_none_with_nothing_to_read(tmp_path, monkeypatch):
    """No trace; a step without the scopes (the parent's program on
    another cell); no ``train.fit`` span. A reader returns None and does
    not raise."""
    proj = mf.load_reader(NEW[0])
    counter = mf.load_reader(NEW[1])
    run = _run(tmp_path / "a", _xspace(), traced=False)
    assert proj(run) is None and counter(run) is None
    other = {k: v.replace("/mamba/", "/attn/") for k, v in OP_NAMES.items()}
    assert proj(_run(tmp_path / "b", _xspace(other))) is None
    from ray_tpu.util import tracing
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    run = _run(tmp_path / "c", _xspace())
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    assert proj(run) is None and counter(run) is None


# -- the manifest and the configuration file ----

def test_the_manifest_lists_the_configuration_the_cell_and_the_metrics():
    """Wherever the entries stand in their lists (a later PR appends
    behind them)."""
    man = mf.load_manifest()
    assert mf.check_manifest(man) == []
    assert len(man["workloads"]) >= 14 and len(man["configs"]) >= 13
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1
    config = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["source"] == _cfg()["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
        "config.json")
    assert len(config["why"]) <= 200
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "b1-t8192", 1)
    assert len(cell["why"]) <= 200
    per_layer = {m["name"]: m for m in man["per_layer"]}
    assert (per_layer[NEW[0]]["source"], per_layer[NEW[0]]["layer"]) == (
        "device_trace", "model")
    assert (per_layer[NEW[1]]["source"], per_layer[NEW[1]]["unit"],
            per_layer[NEW[1]]["better"]) == ("program_counter", "x", "lower")
    for name in NEW + JOINED:
        assert CELL in per_layer[name]["workloads"]
    assert all(per_layer[n]["moves"] == "step_ms_p90" for n in NEW)
    e2e = [m["name"] for m in mf.metrics_of(man, "end_to_end", CELL)]
    assert e2e == ["tokens_per_s_per_chip", "step_ms_p90", "setup_s"]


def test_the_configuration_runs_every_published_width():
    cfg = _cfg()
    pub, model = cfg["published"], cfg["model"]
    changed = {k for k in pub if cfg[k] != pub[k]}
    assert changed == {"num_hidden_layers", "vocab_size"}
    assert (pub["num_hidden_layers"], pub["vocab_size"]) == (40, 100352)
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (10, 25088)
    assert len(cfg["reduced"]) == 2
    for key, was in (("num_hidden_layers", "40"), ("vocab_size", "100352")):
        assert any(r.startswith(f"{key} {was} ->") for r in cfg["reduced"])
    assert model["layer_types"] == pub["layer_types"][:10] == (
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4)
    assert (model["ssm_groups"], model["chunk"]) == (
        pub["mamba_n_groups"], pub["mamba_chunk_size"]) == (1, 256)
    assert model["remat"] is True and model["seq_len"] == 8192
    assert model["vocab_size"] == cfg["loss"]["uniform_over"] == 25088
    assert model["vocab_size"] >= pub["vocab_size"] / 8
    assert model["vocab_size"] % 128 == 0
    assert cfg["sample_unit"] == "tokens" and cfg["ce_chunk"] == 2048
    assert "four chips" in cfg["cut"]["deployment"]
    assert "four pipeline stages" in cfg["cut"]["deployment"]
    assert "797,850,560" in cfg["cut"]["memory"]
    assert "13.15 GB" in cfg["cut"]["memory"]
    assert {"stack", "mlp", "mamba", "attention"} == set(cfg["layers"])
    assert {"gated_norm", "initial_values", "compute_dtype", "optimizer",
            "sequence", "weights", "tokens", "ce_chunk", "unused_keys",
            "described_from_memory"} <= set(cfg["assumed"])
    assert cfg["kernel"] == {
        "tpu_custom_call": True, "ssm_path": "pallas_chunked",
        "flash_path": "multi_block", "gate_norm_path": "pallas",
        "conv_path": "pallas"}
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog_file):
        with open(catalog_file) as f:
            rows = [json.loads(line) for line in f]
        catalog = next(r for r in rows if r["source_url"] == cfg["source"])
        assert pub == catalog["config"]
    builder = mf.load_builder(cfg["builder"])
    with pytest.raises(ValueError, match="not the configuration file's"):
        builder.model_config(
            {**cfg, "model": {**model, "mlp_width": 4096}}, tiny=False)
    with pytest.raises(ValueError, match="`published` differ"):
        builder.model_config(
            {**cfg, "published": {**pub, "residual_multiplier": 1.0}},
            tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config({**cfg, "logits_scaling": 1}, tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config({**cfg, "mamba_n_groups": 8}, tiny=False)


def test_the_limit_lies_between_the_programs_readings_and_the_float8s():
    """``reference.rtol`` against the readings the file records (the
    cell's own runs and ``tools/granite_limit.py`` took them on the
    v5e)."""
    ref = _cfg()["reference"]
    rtol, got = ref["rtol"], ref["readings"]
    assert set(ref["grad_groups"]) == KEYS - {"loss", "grad_norm",
                                              "update_norm", "mamba_out_rms"}
    assert set(got["program_largest"]) == KEYS
    assert got["seeds"] >= 8
    assert all(0 <= v < rtol for v in got["program_largest"].values())
    low = got["float8"]
    assert low["fails"] is True and low["smallest"] > rtol
    assert low["by"] in got["program_largest"]
    assert got["unchanged_state_update_norm"] == 1.0 > rtol


def test_the_builder_refuses_a_step_whose_mixers_ran_otherwise(monkeypatch):
    import jax
    from ray_tpu.parallel import make_mesh
    builder = mf.load_builder("granite")
    cfg = _cfg()
    good = dict(ssm_path="pallas_chunked", ssm_chunk=256, ssm_groups=1,
                conv_path="pallas", gate_norm_path="pallas",
                flash_path="multi_block")
    builder.refuse_unless_the_files_kernels(good, cfg["kernel"], cfg["model"])
    for bad in [{**good, "ssm_path": "chunked_xla"},
                {**good, "ssm_chunk": 128}, {**good, "ssm_groups": 8},
                {**good, "conv_path": "xla"},
                {**good, "gate_norm_path": "xla"},
                {**good, "flash_path": "xla"}, {}]:
        with pytest.raises(RuntimeError, match="this cell measures"):
            builder.refuse_unless_the_files_kernels(bad, cfg["kernel"],
                                                    cfg["model"])
    traffic = mf.effective_traffic(
        mf.load_json(mf.traffic_path("b1-t8192")), True)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    real = builder._other
    monkeypatch.setattr(
        builder, "_other", lambda name: types.SimpleNamespace(
            step_notes=lambda: {"ssm_path": "chunked_xla"})
        if name == "joyai" else real(name))
    # a rehearsal is let through: it runs on the CPU by design
    assert callable(builder.build(cfg, traffic, mesh, 0, tiny=True)[
        "reference"])
    tiny = builder.model_config
    monkeypatch.setattr(builder, "model_config",
                        lambda cfg, _: tiny(cfg, True))
    built = builder.build(cfg, traffic, mesh, 0, tiny=False)
    with pytest.raises(RuntimeError, match="this cell measures"):
        built["reference"]({"params": None, "batch": None})
    assert built["shapes"]["ssm_cost_per_step"]["bytes"] > 0
    assert built["kernel_cost_per_step"]["flops"] > 0


def test_the_limit_tool_reads_every_key_and_every_leaf_at_the_tiny_preset(
        tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = tmp_path / "limit.json"
    p = subprocess.run(
        [sys.executable, os.path.join(mf.BENCH_DIR, "tools",
                                      "granite_limit.py"),
         "--seeds", "11", "--low-seeds", "1", "--tiny", "--leaves", "--out",
         str(out)], capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(out.read_text())
    assert got["cell"] == CELL
    line = got["seeds"]["11"]
    assert set(line["reference"]) == KEYS - {"update_norm"}
    assert line["program_correct"] is True and line["low_correct"] is False
    assert max(line["program"].values()) < 1e-5
    assert {"h_0/mamba/A_log", "h_2/attn/q/kernel", "h_0/mlp/gate_up/kernel",
            "wte/embedding"} <= set(line["leaves"])


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_runs_the_cell_end_to_end_and_is_correct(
        trace, tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")}
    env.pop("RAY_TPU_CHIPS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(mf.BENCH_DIR, "run.py"), "--workload",
         CELL, "--seed", "3000000001", "--seconds", "1", "--trace",
         str(trace), "--rehearse", "--out", str(tmp_path / "out")],
        capture_output=True, text=True, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, p.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    group = "per_layer" if trace else "end_to_end"
    assert set(line["metrics"]) == {
        m["name"] for m in mf.metrics_of(mf.load_manifest(), group, CELL)}
    assert all(m["value"] is None for m in line["metrics"].values())
    worker = json.loads((tmp_path / "out" / CELL /
                         f"seed3000000001.trace{trace}" /
                         "worker.json").read_text())
    got = worker["reference"]
    assert set(got["plain_f32"]) == KEYS
    assert got["program_from"] == "first dispatch"
    assert got["program"]["update_norm"] == pytest.approx(
        got["plain_f32"]["update_norm"], rel=1e-4)
    assert "granite reference done" in p.stderr
