"""What the Qwen3-Next cell adds to the benchmark: ``flops_qwen3_next.py``
against counts by hand at the cell's shapes, the three new readers and the
ones the cell joins on a small synthetic profile whose numbers are known
(built with ``test_program_trace.py``'s helpers) and on runs with nothing
to read, the manifest's entries wherever they stand in their lists, the
configuration file against the catalog's keys and the cut's floors, the
limit against its readings, the builder's refusals, and the rehearsal of
the cell end to end."""

import json
import os
import subprocess
import sys
import types

import pytest

import test_program_trace as tp
from benchlib import flops, flops_qwen3_next as fq
from benchlib import manifest as mf, report

CELL = "qwen3-next-80b-a3b.b1-t16384"
CONFIG = "qwen3-next-80b-a3b"
NEW = ["model.gdn_ms_per_step", "model.gdn_scan_ms_per_step",
       "gdn_scan_roofline"]
JOINED = ["model.attention_ms_per_step", "model.mlp_ms_per_step",
          "model.moe_route_ms_per_step", "model.moe_experts_ms_per_step",
          "moe_experts_roofline", "model.moe_router_ms_per_step",
          "model.moe_shared_ms_per_step", "moe.held_route_share",
          "kernel.attn_flash_ms_per_step", "attn_flash_roofline",
          "model.attn_gate_ms_per_step", "model.attn_repeat_ms_per_step",
          "device.hbm_held_gb", "model.recompute_ms_per_step"]
KEYS = {"loss", "grad_norm", "update_norm", "moe_absent_route_share",
        "gdn_out_rms", "grad_norm_gdn_gates", "grad_norm_attn_qk"}


def _cfg():
    return mf.find_cell(mf.load_manifest(), CELL)["config_file"]


def _mcfg():
    return mf.load_builder("qwen3_next").model_config(_cfg(), tiny=False)


# -- flops_qwen3_next.py against counts by hand ----

def test_parameters_of_each_part_and_of_the_cut():
    cut = _mcfg()
    per = fq.layer_params(cut)
    assert per == cut.layer_params()
    assert per["gdn"] == (2048 * 12288 + 2048 * 64 + 4 * 8192 + 64 + 128
                          + 4096 * 2048) == 33_718_464
    assert per["attn"] == (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
                           + 512) == 27_263_488
    assert per["moe"] == 2048 * 512 + 3 * 2048 * 512 + 2048 == 4_196_352
    assert per["expert"] == 3 * 2048 * 512 == 3_145_728
    period = (3 * per["gdn"] + per["attn"]
              + 4 * (per["moe"] + 32 * per["expert"] + per["norms"]))
    assert period == 547_873_856
    assert fq.num_params(cut) == cut.num_params() == 625_994_816
    assert fq.num_params(cut) * 14 == pytest.approx(8.76e9, rel=1e-3)


def test_required_operations_per_token_and_per_step():
    c = _mcfg()
    assert fq.layers_of(c) == (3, 1)
    per = fq.step_forward_flops_per_token(c)
    assert per["gdn_proj"] == 3 * 2 * (2048 * 12288 + 2048 * 64
                                       + 4096 * 2048)
    # a chunk of 64: the two score products' lower halves once a key
    # head; a value head the solve, three products with the state, the
    # outputs
    a_key, a_value = 64 * 64 * 128, (64 * 64 * 128 + 3 * 64 * 128 * 128
                                     + 64 * 64 * 128 // 2)
    assert fq.gdn_recurrence_macs_per_token(c) == (
        16 * a_key + 32 * a_value) / 64 == 2_097_152
    assert per["gdn_scan"] == 3 * 2 * 2_097_152
    assert per["attn_core"] == 2 * 16384 * 16 * 2 * 256 * 0.5 == 134_217_728
    assert per["held_experts"] == 4 * (10 * 32 / 512) * 2 * 3 * 2048 * 512
    assert per["head"] == 2 * 2048 * 19072
    matmuls = sum(v for k, v in per.items()
                  if k not in ("attn_core", "gdn_scan"))
    assert matmuls == pytest.approx(384e6, rel=5e-3)
    total = fq.train_flops_per_token(c) * 16384
    assert total == pytest.approx(2.609e13, rel=1e-3)
    assert total / 197e12 == pytest.approx(0.1325, rel=1e-3)


def test_kernel_costs_and_their_least_times():
    """The recurrence's bytes count ``q`` and ``k`` at the key heads'
    width and the decay one float a row a head; the attention core at
    256 lanes; the experts at 32 held of 512."""
    c = _mcfg()
    scan = fq.gdn_scan_train_cost(c, 1)
    assert scan["flops"] == 3 * 16384 * 3 * 2 * 2_097_152
    assert scan["bytes"] == 3 * 16384 * (
        (4 * 2048 + 4 * 4096) * 2 + 4 * 32 * 4)
    least = flops.roofline(scan["flops"], scan["bytes"], 197e12, 819e9)
    assert least["least_s"] == pytest.approx(3.14e-3, rel=1e-2)
    # g, beta and their cotangents: 512 bytes a token, where a decay a
    # channel and its cotangent alone would be 32,768
    assert 4 * 32 * 4 * 64 == 2 * 4096 * 4
    core = fq.flash_core_train_cost(c, 1)
    assert core == flops.flash_attention_train_cost(1, 16, 16384, 256, 1)
    assert flops.roofline(core["flops"], core["bytes"], 197e12,
                          819e9)["bound"] == "compute"
    experts = fq.held_experts_train_cost(c, 16384)
    rows = 16384 * 10 * 32 / 512
    assert rows == 10240
    assert experts["flops"] == 4 * 6.0 * rows * 3 * 2048 * 512


# -- the readers on a synthetic profile ----

L = "jit(step)/jit(main)/jvp(Qwen3Next)/"
B = "jit(step)/jit(main)/transpose(jvp(Qwen3Next))/"
R = "blocks/checkpoint/rematted_computation/Qwen3Next/blocks/"
OP_NAMES = {
    "fusion.1": L + "blocks/h_0/gdn/qkvz/dot_general",
    "conv.2": L + "blocks/h_0/gdn/checkpoint/conv/jit(_conv_fwd)/pallas_call",
    "gdn.3": L + "blocks/h_0/gdn/checkpoint/scan/jit(_gdn_fwd)/pallas_call",
    "fusion.4": L + "blocks/h_0/gdn/out/dot_general",
    "fusion.5": B + R + "h_0/gdn/checkpoint/rematted_computation/decay/exp",
    "gdn.6": B + "blocks/checkpoint/h_0/gdn/checkpoint/scan/jit(_gdn_bwd)/"
                 "pallas_call",
    "fusion.7": B + "blocks/checkpoint/h_0/gdn/out/dot_general",
    "fusion.8": L + "blocks/h_3/attn/qkv/q/dot_general",
    "fusion.9": L + "blocks/h_3/attn/repeat/broadcast",
    "flash.10": L + "blocks/h_3/attn/core/jit(_flash_fwd)/pallas_call",
    "flash.11": B + "blocks/checkpoint/h_3/attn/core/jit(_flash_bwd)/"
                    "pallas_call",
    "fusion.12": L + "blocks/h_3/attn/gate/mul",
    "fusion.13": L + "blocks/h_3/mlp/router/dot_general",
    "gmm.14": L + "blocks/h_3/mlp/experts/jit(gmm)/pallas_call",
    "fusion.15": L + "blocks/h_3/mlp/shared/gate/dot_general",
    "fusion.16": L + "blocks/h_3/mlp/shared_gate/mul",
    "fusion.17": L + "loss/loss/while/body",
    "fusion.18": "jit(step)/optimizer/mul",
}
US = [14, 6, 30, 10, 4, 70, 12, 5, 3, 20, 20, 7, 9, 40, 11, 2, 40, 100]


def _xspace(op_names=None) -> bytes:
    from jax.profiler import ProfileData
    op_names = op_names or OP_NAMES
    names = {
        n: (f"%{name} = bf16[8]{{0}} custom-call(bf16[8]{{0}} %x)"
            if name.split(".")[0] in ("flash", "gdn", "conv", "gmm") else
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
        "reference": {"program": {"moe_absent_route_share": 0.9375}},
        "shapes": {"gdn_scan_cost_per_step": {"flops": 1.0,
                                              "bytes": 819e9 * 5e-6},
                   "moe_cost_per_step": {"flops": 197e12 * 4e-6,
                                         "bytes": 1.0}}}
    return report.Run(mf.find_cell(man, CELL), facts, {}, {},
                      {"steps": 2} if traced else None)


def test_every_reader_of_the_cell_reads(tmp_path):
    run = _run(tmp_path, _xspace())
    skip = {"device.hbm_held_gb", "model.recompute_ms_per_step"}
    got = {name: mf.load_reader(name)(run)
           for name in NEW + [n for n in JOINED if n not in skip]}
    assert got == {
        "model.gdn_ms_per_step": pytest.approx(0.146 / 2),
        "model.gdn_scan_ms_per_step": pytest.approx(0.100 / 2),
        "gdn_scan_roofline": pytest.approx(10.0),       # 5 us over 50
        "model.attention_ms_per_step": pytest.approx(0.055 / 2),
        "model.mlp_ms_per_step": pytest.approx(0.062 / 2),
        "model.moe_route_ms_per_step": pytest.approx(0.009 / 2),
        "model.moe_experts_ms_per_step": pytest.approx(0.040 / 2),
        "moe_experts_roofline": pytest.approx(20.0),    # 4 us over 20
        "model.moe_router_ms_per_step": pytest.approx(0.009 / 2),
        "model.moe_shared_ms_per_step": pytest.approx(0.011 / 2),
        "moe.held_route_share": pytest.approx(6.25),
        "kernel.attn_flash_ms_per_step": pytest.approx(0.040 / 2),
        "attn_flash_roofline": pytest.approx(40.0),     # 8 us over 20
        "model.attn_gate_ms_per_step": pytest.approx(0.007 / 2),
        "model.attn_repeat_ms_per_step": pytest.approx(0.003 / 2),
    }


def test_new_readers_are_none_with_nothing_to_read(tmp_path, monkeypatch):
    """No trace; a step without the module (the parent's program on
    another cell); no ``train.fit`` span. A reader returns None and does
    not raise."""
    readers = [mf.load_reader(name) for name in NEW]
    run = _run(tmp_path / "a", _xspace(), traced=False)
    assert [r(run) for r in readers] == [None] * 3
    other = {k: v.replace("/gdn/", "/kda/") for k, v in OP_NAMES.items()}
    run = _run(tmp_path / "b", _xspace(other))
    assert [r(run) for r in readers] == [None] * 3
    from ray_tpu.util import tracing
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    run = _run(tmp_path / "c", _xspace())
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    assert [r(run) for r in readers] == [None] * 3


# -- the manifest and the configuration file ----

def test_the_manifest_lists_the_configuration_the_cell_and_the_metrics():
    """Wherever the entries stand in their lists (a later PR appends
    behind them)."""
    man = mf.load_manifest()
    assert mf.check_manifest(man) == []
    assert len(man["workloads"]) >= 15 and len(man["configs"]) >= 14
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1
    config = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["source"] == _cfg()["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
        "config.json")
    assert len(config["why"]) <= 200
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "b1-t16384", 1)
    assert len(cell["why"]) <= 200
    per_layer = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        assert per_layer[name]["source"] == "device_trace"
        assert per_layer[name]["moves"] == "step_ms_p90"
    assert per_layer["gdn_scan_roofline"]["unit"] == "%"
    assert per_layer["gdn_scan_roofline"]["layer"] == "kernel"
    for name in NEW + JOINED:
        assert CELL in per_layer[name]["workloads"]
    e2e = [m["name"] for m in mf.metrics_of(man, "end_to_end", CELL)]
    assert e2e == ["tokens_per_s_per_chip", "step_ms_p90", "setup_s"]


def test_the_configuration_runs_every_published_width_inside_the_floors():
    cfg = _cfg()
    pub, model = cfg["published"], cfg["model"]
    changed = {k for k in pub if cfg[k] != pub[k]}
    assert changed == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (48, 512, 151936)
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 32, 19072)
    assert len(cfg["reduced"]) == 3
    for key, was in (("num_hidden_layers", "48"), ("num_experts", "512"),
                     ("vocab_size", "151936")):
        assert any(r.startswith(f"{key} {was} ->") for r in cfg["reduced"])
    # the floors: a whole period, at least 8 experts, an eighth of the rows
    assert model["n_layer"] == pub["full_attention_interval"] == 4
    assert model["experts_held"] == [0, 32] and 32 >= 8
    assert model["num_experts"] == 512 and model["top_k"] == 10
    assert model["vocab_size"] == cfg["loss"]["uniform_over"] == 19072
    assert model["vocab_size"] >= pub["vocab_size"] / 8
    assert model["vocab_size"] % 128 == 0
    assert model["remat"] is True and model["seq_len"] == 16384
    assert (model["gdn_key_heads"], model["gdn_value_heads"],
            model["gdn_head_dim"], model["head_dim"]) == (16, 32, 128, 256)
    assert cfg["sample_unit"] == "tokens" and cfg["ce_chunk"] == 2048
    assert "16 chips share each layer" in cfg["cut"]["deployment"]
    assert "pipeline stages" in cfg["cut"]["deployment"]
    assert "625,994,816" in cfg["cut"]["memory"]
    assert "320 routes" in cfg["cut"]["load"]
    assert {"norms", "block", "gdn", "attn", "moe", "head"} == set(
        cfg["layers"])
    assert {"mtp", "zero_centred_norm", "qkvz_columns", "q_proj_columns",
            "conv", "decay", "rope", "router", "shared_gate", "optimizer",
            "sequence", "weights", "tokens", "ce_chunk", "unused_keys",
            "described_from_memory"} <= set(cfg["assumed"])
    assert cfg["kernel"] == {
        "tpu_custom_call": True, "gdn_path": "pallas_chunked",
        "gdn_gate_path": "pallas", "conv_path": "pallas",
        "flash_path": "multi_block", "flash_lanes_per_block": 256}
    assert cfg["loss"]["declines"] is False
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog_file):
        with open(catalog_file) as f:
            rows = [json.loads(line) for line in f]
        catalog = next(r for r in rows if r["source_url"] == cfg["source"])
        assert pub == catalog["config"]
    builder = mf.load_builder(cfg["builder"])
    with pytest.raises(ValueError, match="not the configuration file's"):
        builder.model_config(
            {**cfg, "model": {**model, "expert_width": 1024}}, tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config({**cfg, "head_dim": 128}, tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config({**cfg, "num_experts": 64}, tiny=False)


def test_the_limit_lies_between_the_programs_readings_and_the_float8s():
    """``reference.rtol`` against the readings the file records (the
    cell's own runs and ``tools/qwen3_next_limit.py`` took them on the
    v5e)."""
    ref = _cfg()["reference"]
    rtol, got = ref["rtol"], ref["readings"]
    assert rtol in (2.0 ** -10, 2.0 ** -9)
    assert set(ref["grad_groups"]) == {"grad_norm_gdn_gates",
                                       "grad_norm_attn_qk"}
    assert set(got["program_largest"]) == KEYS
    assert got["seeds"] >= 10
    assert all(0 <= v < rtol for v in got["program_largest"].values())
    low = got["float8"]
    assert low["fails"] is True and low["smallest"] > rtol
    assert low["by"] in got["program_largest"]
    assert got["unchanged_state_update_norm"] == 1.0 > rtol


def test_the_builder_refuses_a_step_whose_mixers_ran_otherwise(monkeypatch):
    import jax
    from ray_tpu.parallel import make_mesh
    builder = mf.load_builder("qwen3_next")
    cfg = _cfg()
    good = dict(gdn_path="pallas_chunked", gdn_gate_path="pallas",
                conv_path="pallas", flash_path="multi_block",
                flash_lanes_per_block=256)
    builder.refuse_unless_the_files_kernels(good, cfg["kernel"])
    for bad in [{**good, "gdn_path": "xla_chunked"},
                {**good, "gdn_gate_path": "xla"},
                {**good, "conv_path": "xla"}, {**good, "flash_path": "xla"},
                {**good, "flash_lanes_per_block": 128}, {}]:
        with pytest.raises(RuntimeError, match="this cell measures"):
            builder.refuse_unless_the_files_kernels(bad, cfg["kernel"])
    traffic = mf.effective_traffic(
        mf.load_json(mf.traffic_path("b1-t16384")), True)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    real = builder._other
    monkeypatch.setattr(
        builder, "_other", lambda name: types.SimpleNamespace(
            step_notes=lambda: {"gdn_path": "xla_chunked"})
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
    assert built["shapes"]["gdn_scan_cost_per_step"]["bytes"] > 0
    assert built["kernel_cost_per_step"]["flops"] > 0


def test_the_limit_tool_reads_every_key_at_the_tiny_preset(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = tmp_path / "limit.json"
    p = subprocess.run(
        [sys.executable, os.path.join(mf.BENCH_DIR, "tools",
                                      "qwen3_next_limit.py"),
         "--seeds", "11", "--low-seeds", "1", "--tiny", "--out", str(out)],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(out.read_text())
    assert got["cell"] == CELL
    line = got["seeds"]["11"]
    assert set(line["reference"]) == KEYS - {"update_norm"}
    assert line["program_correct"] is True and line["low_correct"] is False
    assert max(line["program"].values()) < 1e-5


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
    assert "qwen3_next reference done" in p.stderr
