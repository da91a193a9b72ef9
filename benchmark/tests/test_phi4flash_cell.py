"""What the Phi-4-mini-flash cell adds to the benchmark:
``flops_phi4flash.py`` against counts by hand at the cell's shapes, the
three new readers and the ones the cell joins on a small synthetic profile
whose numbers are known (built with ``test_program_trace.py``'s helpers) and
on runs with nothing to read, the manifest's entries wherever they stand in
their lists, the configuration file against the catalog's keys, the limit
against its readings, the builder's refusal by the step's notes, and the
rehearsal of the cell end to end."""

import json
import os
import subprocess
import sys
import types

import pytest

import test_program_trace as tp
from benchlib import flops, flops_phi4flash as fp
from benchlib import manifest as mf, report

CELL = "phi-4-mini-flash-reasoning.b1-t4096"
CONFIG = "phi-4-mini-flash-reasoning"
NEW = ["model.attn_diff_ms_per_step", "model.attn_cross_ms_per_step",
       "model.gmu_ms_per_step"]
JOINED = ["model.attention_ms_per_step", "model.mlp_ms_per_step",
          "kernel.attn_flash_ms_per_step", "attn_flash_roofline",
          "model.attn_window_ms_per_step", "attn_window_roofline",
          "model.mamba_ms_per_step", "model.ssm_scan_ms_per_step",
          "ssm_scan_roofline"]
KEYS = {"loss", "grad_norm", "update_norm", "mamba_out_rms",
        "grad_norm_mamba_ssm", "grad_norm_attn_diff", "grad_norm_yoco_kv"}


def _cfg():
    return mf.find_cell(mf.load_manifest(), CELL)["config_file"]


def _mcfg(**kw):
    import dataclasses
    mcfg = mf.load_builder("phi4flash").model_config(_cfg(), tiny=False)
    return dataclasses.replace(mcfg, **kw)


# -- flops_phi4flash.py against counts by hand ----

def test_parameters_of_each_part_and_of_the_cut():
    cut = _mcfg()
    per = fp.layer_params(cut)
    assert per == cut.layer_params() and per["F"] == per["S"]
    assert per["M"] == (2560 * 10240 + 5 * 5120 + 5120 * 192 + 161 * 5120
                        + 5120 * 16 + 5120 + 5120 * 2560)       # 41.24 M
    assert per["S"] == (2561 * 5120 + 4 * 64 + 128 + 2561 * 2560)  # 19.67 M
    assert per["G"] == 2 * 2560 * 5120                           # 26.21 M
    assert per["X"] == 2 * 2561 * 2560 + 4 * 64 + 128            # 13.11 M
    assert per["mlp"] == 3 * 2560 * 10240                        # 78.64 M
    assert fp.layers_of(cut) == {"M": 3, "S": 2, "F": 1, "G": 1, "X": 1}
    mixers = 3 * per["M"] + 3 * per["S"] + per["G"] + per["X"]
    assert mixers == pytest.approx(222.05e6, rel=1e-4)
    assert 8 * per["mlp"] == pytest.approx(629.15e6, rel=1e-4)
    assert fp.num_params(cut) == cut.num_params() == 915_516_416
    assert fp.num_params(cut) * 14 == pytest.approx(12.82e9, rel=1e-3)
    published = _mcfg(n_layer=32, vocab_size=200064)
    assert fp.layers_of(published) == {"M": 9, "S": 8, "F": 1, "G": 7,
                                       "X": 7}
    assert fp.num_params(published) == pytest.approx(3.85e9, rel=1e-2)
    # N = 12, the next stack the rule allows: 18.6 GB of state
    assert fp.num_params(_mcfg(n_layer=12)) * 14 == pytest.approx(
        18.6e9, rel=1e-2)


def test_required_operations_per_token_and_per_step():
    c = _mcfg()
    per = fp.forward_flops_per_token(c)
    assert per["mamba_proj"] == 2 * (2560 * 10240 + 5120 * 192 + 160 * 5120
                                     + 5120 * 2560)
    assert per["mamba_scan"] == 7 * 5120 * 16 + 3 * 5120
    assert per["attn_proj"] == 2 * (2560 * 5120 + 2560 * 2560)
    assert per["cross_proj"] == 2 * 2 * 2560 * 2560
    assert per["gmu"] == 2 * 2 * 2560 * 5120
    assert per["mlp"] == 2 * 3 * 2560 * 10240
    assert per["head"] == 2 * 2560 * 25088
    # two maps a pair: a 64-wide score and a 128-wide product each
    seen_band = 512 * 513 // 2 + (4096 - 512) * 512
    assert fp.seen_entries(4096, 512) == seen_band == 1_966_336
    assert per["core_window"] == 20 * 2 * (2 * 64 + 2 * 128) * seen_band \
        / 4096
    assert per["core_full"] == 20 * 2 * (2 * 64 + 2 * 128) * (
        4096 * 4097 // 2) / 4096
    step = fp.step_forward_flops_per_token(c)
    assert step["mlp"] == 8 * per["mlp"]
    assert step["mamba_scan"] == 3 * per["mamba_scan"]
    assert step["core_window"] == 2 * per["core_window"]
    assert step["core_full"] == 2 * per["core_full"]      # F and X
    total = fp.train_flops_per_token(c) * 4096
    assert total == pytest.approx(2.346e13, rel=1e-3)
    assert total / 197e12 == pytest.approx(0.1191, rel=1e-3)
    share = {k: 3 * v * 4096 / total for k, v in step.items()}
    assert share["mlp"] == pytest.approx(0.659, abs=1e-3)
    assert share["head"] == pytest.approx(0.067, abs=1e-3)
    assert (share["mamba_proj"] + share["attn_proj"] + share["cross_proj"]
            + share["gmu"]) == pytest.approx(0.232, abs=1e-3)
    assert share["mamba_scan"] < 1e-3


def test_kernel_costs_and_their_least_times():
    c = _mcfg()
    rows, wide, narrow = 4096, 4096 * 2560 * 2, 4096 * 1280 * 2
    core = wide * 6 + narrow * 6 + 3 * rows * 40 * 4
    window = fp.window_cores_train_cost(c, 1)
    assert window["flops"] == 2 * 3 * 20 * 2 * 384 * 1_966_336
    assert window["bytes"] == 2 * core
    every = fp.flash_cores_train_cost(c, 1)
    assert every["flops"] == window["flops"] + 2 * 3 * 20 * 2 * 384 * (
        4096 * 4097 // 2)
    assert every["bytes"] == 4 * core
    least = flops.roofline(every["flops"], every["bytes"], 197e12, 819e9)
    assert least["bound"] == "compute"
    assert least["least_s"] == pytest.approx(4.85e-3, rel=1e-2)
    scan = fp.ssm_scan_train_cost(c, 1)
    assert scan["flops"] == 3 * 4096 * 3 * (7 * 5120 * 16 + 3 * 5120)
    row = 5120 * 2 + 5120 * 4 + 2 * 16 * 2      # x, dt, B and C
    assert scan["bytes"] == 3 * 4096 * ((row + 5120 * 2)
                                        + (row + 5120 * 2 + row))
    least = flops.roofline(scan["flops"], scan["bytes"], 197e12, 819e9)
    assert least["bound"] == "memory"
    assert least["least_s"] == pytest.approx(1.69e-3, rel=1e-2)


# -- the readers on a synthetic profile ----

L = "jit(step)/jit(main)/jvp(Phi4Flash)/"
B = "jit(step)/jit(main)/transpose(jvp(Phi4Flash))/"
R = "blocks/checkpoint/rematted_computation/Phi4Flash/blocks/"
OP_NAMES = {
    "fusion.1": L + "blocks/h_0/mamba/in_proj/dot_general",
    "fusion.2": L + "blocks/h_0/mamba/scan/while/body/closed_call/"
                    "checkpoint/mul",
    "fusion.3": B + R + "h_0/mamba/scan/while/body/closed_call/checkpoint/"
                        "rematted_computation/exp",
    "fusion.4": B + R + "h_0/mamba/gate/mul",
    "fusion.5": L + "blocks/h_1/attn/qkv/dot_general",
    "flash.6": L + "blocks/h_1/attn/window/jit(_flash_fwd)/pallas_call",
    "flash.7": B + R + "h_1/attn/window/jit(_flash_bwd)/pallas_call",
    "fusion.8": B + R + "h_1/attn/diff/mul",
    "flash.9": L + "blocks/h_5/attn/core/jit(_flash_fwd)/pallas_call",
    "fusion.10": L + "blocks/h_6/gmu/in_proj/dot_general",
    "fusion.11": B + R + "h_6/gmu/gate/mul",
    "flash.12": B + R + "h_7/attn/cross/jit(_flash_bwd)/pallas_call",
    "fusion.13": L + "blocks/h_7/attn/diff/sub",
    "fusion.14": L + "blocks/h_7/mlp/gate_up/dot_general",
    "fusion.15": L + "loss/loss/while/body",
    "fusion.16": "jit(step)/optimizer/mul",
}
US = [14, 30, 70, 6, 10, 8, 12, 5, 20, 9, 3, 40, 7, 50, 40, 100]


def _xspace(op_names=None) -> bytes:
    from jax.profiler import ProfileData
    op_names = op_names or OP_NAMES
    names = {
        n: (f"%{name} = bf16[8]{{0}} custom-call(bf16[8]{{0}} %x)"
            if name.split(".")[0] == "flash" else
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
        "shapes": {"window_cost_per_step": {"flops": 197e12 * 2e-6,
                                            "bytes": 1.0},
                   "ssm_cost_per_step": {"flops": 1.0,
                                         "bytes": 819e9 * 5e-6}}}
    return report.Run(mf.find_cell(man, CELL), facts, {}, {},
                      {"steps": 2} if traced else None)


def test_every_reader_of_the_cell_reads(tmp_path):
    run = _run(tmp_path, _xspace())
    got = {name: mf.load_reader(name)(run) for name in NEW + JOINED}
    assert got == {
        "model.attn_diff_ms_per_step": pytest.approx(0.012 / 2),   # 5 + 7
        "model.attn_cross_ms_per_step": pytest.approx(0.040 / 2),
        "model.gmu_ms_per_step": pytest.approx(0.012 / 2),         # 9 + 3
        # 10 + 8 + 12 + 5 + 20 + 40 + 7: nothing under mamba or gmu
        "model.attention_ms_per_step": pytest.approx(0.102 / 2),
        "model.mlp_ms_per_step": pytest.approx(0.050 / 2),
        "kernel.attn_flash_ms_per_step": pytest.approx(0.080 / 2),
        "attn_flash_roofline": pytest.approx(20.0),     # 8 us over 40
        "model.attn_window_ms_per_step": pytest.approx(0.020 / 2),
        "attn_window_roofline": pytest.approx(20.0),    # 2 us over 10
        "model.mamba_ms_per_step": pytest.approx(0.120 / 2),
        "model.ssm_scan_ms_per_step": pytest.approx(0.100 / 2),
        "ssm_scan_roofline": pytest.approx(10.0),       # 5 us over 50
    }


def test_new_readers_are_none_with_nothing_to_read(tmp_path, monkeypatch):
    """No trace; a step without the scopes (any other cell's, or the
    parent's program); no ``train.fit`` span. A reader returns None and
    does not raise."""
    run = _run(tmp_path / "a", _xspace(), traced=False)
    assert [mf.load_reader(n)(run) for n in NEW] == [None, None, None]
    other = {k: v.replace("/gmu/", "/mlp/").replace("/diff/", "/out/")
             .replace("/cross/", "/core/") for k, v in OP_NAMES.items()}
    run = _run(tmp_path / "b", _xspace(other))
    assert [mf.load_reader(n)(run) for n in NEW] == [None, None, None]
    from ray_tpu.util import tracing
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    run = _run(tmp_path / "c", _xspace())
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    assert [mf.load_reader(n)(run) for n in NEW] == [None, None, None]


# -- the manifest and the configuration file ----

def test_the_manifest_lists_the_configuration_the_cell_and_the_metrics():
    """Wherever the entries stand in their lists (a later PR appends
    behind them)."""
    man = mf.load_manifest()
    assert mf.check_manifest(man) == []
    assert len(man["workloads"]) >= 10 and len(man["configs"]) >= 9
    config = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(config["why"]) <= 200
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "b1-t4096", 1)
    assert len(cell["why"]) <= 200
    per_layer = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        assert CELL in per_layer[name]["workloads"]
        assert per_layer[name]["moves"] == "step_ms_p90"
        assert per_layer[name]["source"] == "device_trace"
        assert per_layer[name]["layer"] == "model"
    for name in JOINED:
        assert CELL in per_layer[name]["workloads"]
    e2e = [m["name"] for m in mf.metrics_of(man, "end_to_end", CELL)]
    assert e2e == ["tokens_per_s_per_chip", "step_ms_p90", "setup_s"]


def test_the_configuration_runs_every_published_width():
    cfg = _cfg()
    pub, model = cfg["published"], cfg["model"]
    changed = {k for k in pub if cfg[k] != pub[k]}
    assert changed == {"num_hidden_layers", "vocab_size"}
    assert (pub["num_hidden_layers"], pub["vocab_size"]) == (32, 200064)
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (8, 25088)
    assert len(cfg["reduced"]) == 2
    for key, was in (("num_hidden_layers", "32"), ("vocab_size", "200064")):
        assert any(r.startswith(f"{key} {was} ->") for r in cfg["reduced"])
    assert (model["n_embd"], model["n_head"], model["n_kv_head"],
            model["head_dim"], model["window"], model["mlp_width"]) == (
                2560, 40, 20, 64, 512, 10240)
    assert (model["mamba_inner"], model["ssm_state"], model["conv_kernel"],
            model["dt_rank"]) == (5120, 16, 4, 160)
    assert model["layer_kinds"] == "MSMSMFGX" and model["n_layer"] == 8
    assert model["remat"] is True and model["seq_len"] == 4096
    assert model["vocab_size"] == cfg["loss"]["uniform_over"] == 25088
    assert model["vocab_size"] >= pub["vocab_size"] / 8
    assert model["vocab_size"] % 128 == 0
    # what report.py reads of a configuration outside a rehearsal
    assert cfg["sample_unit"] == "tokens" and cfg["ce_chunk"] == 2048
    assert "8 chips" in cfg["cut"]["deployment"]
    assert "3 : 2 : 1 : 1 : 1" in cfg["cut"]["depth"]
    assert "915.5 M" in cfg["cut"]["memory"]
    assert "12.87 GB" in cfg["cut"]["memory"]       # 4,096 rows, compiled
    assert "14.67 GB" in cfg["cut"]["memory"]       # 8,192 rows, compiled
    assert {"stack", "block", "mamba", "gmu", "diff_attention",
            "cross_attention"} == set(cfg["layers"])
    assert {"mamba_sizes", "mamba_initial_values", "layer_rule", "biases",
            "pairing", "lambda", "compute_dtype", "optimizer", "sequence",
            "weights", "tokens", "ce_chunk", "ssm_chunk",
            "described_from_memory"} <= set(cfg["assumed"])
    assert cfg["kernel"] == {"tpu_custom_call": True,
                             "flash_path": "multi_block",
                             "flash_window": 512, "ssm_path": "chunked"}
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    catalog = next(r for r in rows if r["source_url"] == cfg["source"])
    assert pub == catalog["config"]
    builder = mf.load_builder(cfg["builder"])
    with pytest.raises(ValueError, match="not the configuration file's"):
        builder.model_config(
            {**cfg, "model": {**model, "mlp_width": 8192}}, tiny=False)
    with pytest.raises(ValueError, match="not the configuration file's"):
        builder.model_config(
            {**cfg, "model": {**model, "layer_kinds": "MSMSMSMS"}},
            tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config({**cfg, "sliding_window": 4096}, tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config({**cfg, "tie_word_embeddings": False},
                             tiny=False)


def test_the_limit_lies_between_the_programs_readings_and_the_float8s():
    """``reference.rtol`` against the readings the file records (the
    cell's own runs and ``tools/limit.py`` took them on the v5e)."""
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
    # every planted fault moved one of the cell's keys past twice the limit
    faults = {k: v for k, v in got["faults"].items() if k != "what"}
    assert len(faults) == 12
    for name, moved in faults.items():
        assert moved["key"] in KEYS
        assert moved["smallest_over_seeds"] > 2 * rtol, name


def test_the_builder_refuses_a_step_whose_mixers_ran_otherwise(monkeypatch):
    import jax
    from ray_tpu.parallel import make_mesh
    builder = mf.load_builder("phi4flash")
    cfg = _cfg()
    kernel = cfg["kernel"]
    good = dict(flash_path="multi_block", flash_window=512,
                ssm_path="xla_chunked")
    builder.refuse_unless_band_and_chunked(good, kernel)
    builder.refuse_unless_band_and_chunked(
        {**good, "ssm_path": "pallas_chunked"}, kernel)
    for bad in [{**good, "flash_path": "xla"},
                {**good, "flash_path": "single_block"},
                {**good, "flash_window": "none"},
                {**good, "flash_window": 4096},
                {**good, "ssm_path": "per_token"},
                {"flash_path": "multi_block", "flash_window": 512}, {}]:
        with pytest.raises(RuntimeError, match="this cell measures"):
            builder.refuse_unless_band_and_chunked(bad, kernel)
    traffic = mf.effective_traffic(
        mf.load_json(mf.traffic_path("b1-t4096")), True)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    notes = dict(attn_kind="differential", flash_path="xla",
                 flash_layout="unequal_shapes", ssm_path="xla_chunked")
    real = builder._other
    monkeypatch.setattr(
        builder, "_other", lambda name: types.SimpleNamespace(
            step_notes=lambda: notes) if name == "joyai" else real(name))
    # a rehearsal is let through: it runs on the CPU by design
    assert callable(builder.build(cfg, traffic, mesh, 0, tiny=True)[
        "reference"])
    tiny = builder.model_config
    monkeypatch.setattr(builder, "model_config",
                        lambda cfg, _: tiny(cfg, True))
    built = builder.build(cfg, traffic, mesh, 0, tiny=False)
    with pytest.raises(RuntimeError, match="not the 'multi_block'"):
        built["reference"]({"params": None, "batch": None})
    assert set(built["shapes"]) >= {"window_cost_per_step",
                                    "ssm_cost_per_step"}
    assert built["kernel_cost_per_step"]["flops"] > 0


def test_reference_returns_the_keys_the_step_reports():
    """``loop.py`` holds every key the reference returns against the
    first dispatch's metric of that name: each has to be one; the
    parameters may wait on the host; and the low reading is another
    number (the rounder bites)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    builder = mf.load_builder("phi4flash")
    ref = mf.load_reference("phi4flash")
    mcfg, model, loss_fn = builder.program(_cfg(), tiny=True)
    params = builder.make_params(model, 0)
    toks = np.random.default_rng(0).integers(0, 256, (2, 32), dtype=np.int32)
    batch = {"tokens": jnp.asarray(toks),
             "targets": jnp.asarray(np.roll(toks, -1, 1))}
    spec = builder.reference_spec(mcfg)
    out = ref.loss_and_grad_norm(params, batch, spec)
    loss, report_ = loss_fn(params, batch)
    assert set(out) == {"loss", "grad_norm", "mamba_out_rms"}
    assert all(v.ndim == 0 for v in report_.values())
    assert out["loss"] == pytest.approx(float(loss), rel=1e-5)
    assert out["mamba_out_rms"] == pytest.approx(
        float(report_["mamba_out_rms"]), rel=1e-4)
    groups = _cfg()["reference"]["grad_groups"]
    on_host = ref.loss_and_grad_norm(
        jax.device_get(params), batch,
        {**spec, "adamw": _cfg()["optimizer"], "grad_groups": groups})
    assert set(on_host) == KEYS
    assert on_host["grad_norm"] == pytest.approx(out["grad_norm"], rel=1e-6)
    assert 0 < on_host["update_norm"] < 1
    assert all(0 < on_host[name] < on_host["grad_norm"] for name in groups)
    low = ref.loss_and_grad_norm(
        params, batch, {**spec, "operand_dtype": "float8_e4m3fn"})
    assert low["grad_norm"] != out["grad_norm"]
    assert low["grad_norm"] == pytest.approx(out["grad_norm"], rel=0.05)


def test_the_limit_tool_reads_every_key_and_every_leaf_at_the_tiny_preset(
        tmp_path):
    """``tools/phi4flash_limit.py --tiny``: the program's float32 preset
    is the reference's to rounding on every key, groups among them; the
    float8 reading is not correct; ``--leaves`` gives a distance a
    gradient leaf on both sides."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = tmp_path / "limit.json"
    p = subprocess.run(
        [sys.executable, os.path.join(mf.BENCH_DIR, "tools",
                                      "phi4flash_limit.py"),
         "--seeds", "11", "--low-seeds", "1", "--tiny", "--leaves", "--out",
         str(out)], capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(out.read_text())
    line = got["seeds"]["11"]
    assert set(line["reference"]) == KEYS - {"update_norm"}
    assert line["program_correct"] is True and line["low_correct"] is False
    assert max(line["program"].values()) < 1e-5
    assert got["largest"]["low"]["grad_norm"] > got["rtol"]
    leaves = line["leaves"]
    assert {"h_0/mamba/A_log", "h_1/attn/lambda_q1", "h_5/attn/qkv/kernel",
            "h_6/gmu/in_proj/kernel", "wte/embedding"} <= set(leaves)
    assert all(set(v) == {"reference", "program", "low"}
               for v in leaves.values())


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
    # the step's own first update against the reference's AdamW step
    assert got["program_from"] == "first dispatch"
    assert got["program"]["update_norm"] == pytest.approx(
        got["plain_f32"]["update_norm"], rel=1e-4)
    assert "phi4flash reference done" in p.stderr
