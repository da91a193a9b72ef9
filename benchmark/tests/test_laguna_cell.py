"""What the Laguna-XS.2 cell adds to the benchmark: ``flops_laguna.py``
against counts by hand at the cell's shapes, the two new readers and the
ones the cell joins on a small synthetic profile whose numbers are known
(built with ``test_program_trace.py``'s helpers) and on runs with nothing
to read, the manifest's entries wherever they stand in their lists, the
configuration file against the catalog's keys, the limit against its
readings, the builder's refusals by the step's notes, and the rehearsal of
the cell end to end."""

import json
import os
import subprocess
import sys
import types

import pytest

import test_program_trace as tp
from benchlib import flops, flops_laguna as fl
from benchlib import manifest as mf, report

CELL = "laguna-xs.2.b1-t16384"
NEW = ["model.attn_gate_ms_per_step", "model.attn_repeat_ms_per_step"]
JOINED = ["model.attention_ms_per_step", "model.mlp_ms_per_step",
          "model.moe_route_ms_per_step", "model.moe_experts_ms_per_step",
          "model.moe_shared_ms_per_step", "model.moe_router_ms_per_step",
          "moe.held_route_share", "moe_experts_roofline",
          "kernel.attn_flash_ms_per_step", "attn_flash_roofline",
          "model.attn_window_ms_per_step", "model.attn_global_ms_per_step",
          "attn_window_roofline"]
KEYS = {"loss", "grad_norm", "moe_absent_route_share", "update_norm",
        "attn_window_out_rms"}


def _cfg():
    return mf.find_cell(mf.load_manifest(), CELL)["config_file"]


def _mcfg(**kw):
    import dataclasses
    mcfg = mf.load_builder("laguna").model_config(_cfg(), tiny=False)
    return dataclasses.replace(mcfg, **kw)


# -- flops_laguna.py against counts by hand ----

def test_parameters_of_each_layer_and_of_the_cut():
    cut, whole = _mcfg(), _mcfg(experts_held=None)
    for i in range(5):
        assert fl.layer_params(cut, i) == cut.layer_params(i)
    full = 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48        # 29.46 M
    sliding = 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64     # 37.88 M
    assert [fl.attn_weights(cut, i) for i in range(5)] == [
        full, sliding, sliding, sliding, full]
    assert fl.layer_params(cut, 0)["dense"] == 3 * 2048 * 8192
    routed = fl.layer_params(cut, 1)
    assert routed["router"] == 2048 * 256 + 256
    assert routed["shared"] == 3 * 2048 * 512
    assert routed["experts"] == 32 * 3 * 2048 * 512             # 100.7 M
    assert sum(fl.layer_params(whole, 1).values()) == pytest.approx(
        846.9e6, rel=1e-3)
    assert sum(fl.layer_params(whole, 4).values()) == pytest.approx(
        838.4e6, rel=1e-3)
    assert fl.num_params(cut) == cut.num_params()
    assert fl.num_params(cut) == pytest.approx(691.62e6, rel=1e-4)
    assert fl.num_params(cut) * 14 == pytest.approx(9.68e9, rel=1e-3)
    published = _mcfg(experts_held=None, vocab_size=100352, n_layer=40)
    assert fl.num_params(published) == pytest.approx(33.4e9, rel=5e-3)
    assert fl.routed_layers(cut) == 4 and fl.routed_layers(published) == 39


def test_required_operations_per_token_and_per_step():
    c = _mcfg()
    step = fl.step_forward_flops_per_token(c)
    assert step["attn_proj"] == 2.0 * (2 * (2 * 2048 * 6144 + 2048 * 48)
                                       + 3 * (2 * 2048 * 8192 + 2048 * 64)
                                       + 5 * 2 * 2048 * 1024)
    # a row under a window of 512 sees 504.0 keys on average, a full
    # row 8,192.5
    band = 512 * 513 // 2 + (16384 - 512) * 512
    assert fl.seen_entries(16384, 512) == band
    assert band / 16384 == pytest.approx(504.0, abs=0.05)
    assert step["core_window"] == 3 * 2.0 * 64 * 2 * 128 * band / 16384
    assert step["core_full"] == 2 * 2.0 * 48 * 2 * 128 * 8192.5
    assert step["dense_mlp"] == 2.0 * 3 * 2048 * 8192
    assert step["shared"] == 4 * 2.0 * 3 * 2048 * 512
    assert step["held_experts"] == 4 * (8 * 32 / 256) * 2.0 * 3 * 2048 * 512
    assert step["router"] == 4 * 2.0 * 2048 * 256
    assert step["head"] == 2.0 * 2048 * 12544
    total = sum(step.values())
    assert total == pytest.approx(1003.9e6, rel=1e-4)
    assert fl.train_flops_per_token(c) == 3 * total
    # the configuration file's `cut.consequence`
    assert 16384 * 3 * total == pytest.approx(4.934e13, rel=1e-3)
    assert step["core_full"] / total == pytest.approx(0.401, abs=2e-3)
    assert step["attn_proj"] / total == pytest.approx(0.344, abs=2e-3)
    assert step["core_window"] / total == pytest.approx(0.049, abs=1e-3)
    assert step["dense_mlp"] / total == pytest.approx(0.100, abs=1e-3)
    assert step["held_experts"] / total == pytest.approx(0.025, abs=1e-3)
    attention = step["attn_proj"] + step["core_window"] + step["core_full"]
    assert attention / total == pytest.approx(0.794, abs=2e-3)


def test_kernel_costs_and_their_least_times():
    c = _mcfg()
    band = fl.seen_entries(16384, 512)
    window = fl.window_cores_train_cost(c, 1)
    assert window["flops"] == 3 * 64 * 6 * 2.0 * band * 128
    tensor = 64 * 16384 * 128 * 2
    assert window["bytes"] == 3 * (12 * tensor + 3 * 64 * 16384 * 4)
    least = flops.roofline(window["flops"], window["bytes"], 197e12, 819e9)
    assert least["bound"] == "compute"
    assert least["least_s"] == pytest.approx(12.36e-3, rel=1e-2)
    full = fl.full_cores_train_cost(c, 1)
    assert full["flops"] == 2 * 48 * 6 * 2.0 * (16384 * 16385 // 2) * 128
    assert flops.roofline(full["flops"], full["bytes"], 197e12,
                          819e9)["least_s"] == pytest.approx(100.5e-3,
                                                             rel=1e-2)
    both = fl.flash_cores_train_cost(c, 1)
    assert both == {k: window[k] + full[k] for k in ("flops", "bytes")}
    # a full layer of 48 heads is SmallThinker's global layer of 28, by
    # its rule, times 48/28
    from benchlib import flops_smallthinker as fs
    small = mf.load_builder("smallthinker").model_config(
        mf.find_cell(mf.load_manifest(),
                     "smallthinker-21b-a3b.b1-t16384")["config_file"],
        tiny=False)
    assert full["flops"] / 2 == pytest.approx(
        fs.global_cores_train_cost(small, 1)["flops"] * 48 / 28)
    experts = fl.held_experts_train_cost(c, 16384)
    rows = 16384 * 8 // 8             # an eighth of the routes
    assert experts["flops"] == 4 * 6.0 * rows * 3 * 2048 * 512
    assert experts["bytes"] == 4 * 9 * 2 * (rows * 2048 + rows * 512
                                            + 32 * 2048 * 512)


# -- the readers on a synthetic profile ----

L = "jit(step)/jit(main)/jvp(Laguna)/"
B = "jit(step)/jit(main)/transpose(jvp(Laguna))/"
R = "blocks/jvp(Laguna)/blocks/checkpoint/rematted_computation/"
OP_NAMES = {
    "fusion.1": L + "blocks/h_0/attn/qkv/q/dot_general",
    "fusion.2": L + "blocks/h_0/attn/rope/mul",
    "fusion.3": L + "blocks/h_0/attn/repeat/broadcast_in_dim",
    "flash.4": L + "blocks/h_0/attn/core/jit(_flash_fwd)/pallas_call",
    "fusion.5": L + "blocks/h_0/attn/gate/g/dot_general",
    "fusion.6": B + R + "h_1/attn/gate/mul",
    "fusion.7": B + R + "h_1/attn/repeat/broadcast_in_dim",
    "flash.8": B + R + "h_1/attn/window/jit(_flash_fwd)/pallas_call",
    "flash.9": B + "blocks/jvp(Laguna)/blocks/checkpoint/h_1/attn/window/"
                   "jit(_flash_bwd)/pallas_call",
    "fusion.10": L + "blocks/h_1/attn/out/out/dot_general",
    "fusion.11": L + "blocks/h_0/mlp/up/dot_general",
    "fusion.12": L + "blocks/h_1/mlp/router/dot_general",
    "fusion.13": L + "blocks/h_1/mlp/dispatch/sort",
    "gmm.14": L + "blocks/h_1/mlp/experts/jit(gmm)/pallas_call",
    "fusion.15": L + "blocks/h_1/mlp/shared/up/dot_general",
    "fusion.16": L + "loss/loss/while/body",
    "fusion.17": "jit(step)/optimizer/mul",
}
US = [14, 6, 8, 40, 3, 5, 12, 10, 20, 10, 30, 4, 9, 50, 7, 40, 100]


def _xspace(op_names=None) -> bytes:
    from jax.profiler import ProfileData
    op_names = op_names or OP_NAMES
    names = {
        n: (f"%{name} = bf16[8]{{0}} custom-call(bf16[8]{{0}} %x)"
            if name.split(".")[0] in ("gmm", "flash") else
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
        "kernel_cost_per_step": {"flops": 197e12 * 35e-6, "bytes": 1.0},
        "shapes": {"moe_cost_per_step": {"flops": 1.0,
                                         "bytes": 819e9 * 5e-6},
                   "window_cost_per_step": {"flops": 197e12 * 3e-6,
                                            "bytes": 1.0}},
        "reference": {"program": {"moe_absent_route_share": 0.875}}}
    return report.Run(mf.find_cell(man, CELL), facts, {}, {},
                      {"steps": 2} if traced else None)


def test_every_reader_of_the_cell_reads(tmp_path):
    run = _run(tmp_path, _xspace())
    got = {name: mf.load_reader(name)(run) for name in NEW + JOINED}
    assert got == {
        # the gate's projection in layer 0's forward and its product in
        # layer 1's recomputed block; the copies in both
        "model.attn_gate_ms_per_step": pytest.approx(0.008 / 2),
        "model.attn_repeat_ms_per_step": pytest.approx(0.020 / 2),
        "model.attention_ms_per_step": pytest.approx(0.128 / 2),
        "model.mlp_ms_per_step": pytest.approx(0.100 / 2),
        "model.moe_route_ms_per_step": pytest.approx(0.013 / 2),
        "model.moe_experts_ms_per_step": pytest.approx(0.050 / 2),
        "model.moe_shared_ms_per_step": pytest.approx(0.007 / 2),
        "model.moe_router_ms_per_step": pytest.approx(0.004 / 2),
        "moe.held_route_share": pytest.approx(12.5),
        "moe_experts_roofline": pytest.approx(20.0),    # 5 us over 25
        "kernel.attn_flash_ms_per_step": pytest.approx(0.070 / 2),
        "attn_flash_roofline": pytest.approx(100.0),    # 35 us over 35
        "model.attn_window_ms_per_step": pytest.approx(0.030 / 2),
        "model.attn_global_ms_per_step": pytest.approx(0.040 / 2),
        "attn_window_roofline": pytest.approx(20.0),    # 3 us over 15
    }


def test_new_readers_are_none_with_nothing_to_read(tmp_path, monkeypatch):
    """No trace; a step without the scopes (any other model's, or the
    parent's program asked for another cell); no ``train.fit`` span. A
    reader returns None and does not raise."""
    run = _run(tmp_path / "a", _xspace(), traced=False)
    assert [mf.load_reader(n)(run) for n in NEW] == [None, None]
    other = {k: v.replace("/gate/", "/out/").replace("/repeat/", "/rope/")
             for k, v in OP_NAMES.items()}
    run = _run(tmp_path / "b", _xspace(other))
    assert [mf.load_reader(n)(run) for n in NEW] == [None, None]
    from ray_tpu.util import tracing
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    run = _run(tmp_path / "c", _xspace())
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    assert [mf.load_reader(n)(run) for n in NEW] == [None, None]


# -- the manifest and the configuration file ----

def test_the_manifest_lists_the_configuration_the_cell_and_the_metrics():
    """That the entries are present, wherever they stand in their lists
    (a later PR appends behind them, and may list its cell beside this
    one)."""
    man = mf.load_manifest()
    assert mf.check_manifest(man) == []
    config = next(c for c in man["configs"] if c["name"] == "laguna-xs.2")
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["file"] == "benchmark/configs/laguna-xs.2.json"
    assert config["source"] == _cfg()["source"]
    assert len(config["why"]) <= 200
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna-xs.2", "b1-t16384", 1)
    assert "1/8" in cell["why"] and "8x" in cell["why"]
    per_layer = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        assert CELL in per_layer[name]["workloads"]
        assert per_layer[name]["moves"] == "step_ms_p90"
        assert (per_layer[name]["unit"], per_layer[name]["source"],
                per_layer[name]["layer"]) == ("ms", "device_trace", "model")
    assert "smallthinker-21b-a3b.b1-t16384" in per_layer[
        "model.attn_repeat_ms_per_step"]["workloads"]
    for name in JOINED:
        assert CELL in per_layer[name]["workloads"]
    e2e = [m["name"] for m in mf.metrics_of(man, "end_to_end", CELL)]
    assert e2e == ["tokens_per_s_per_chip", "step_ms_p90", "setup_s"]


def test_the_configuration_runs_every_published_width():
    cfg = _cfg()
    pub, model = cfg["published"], cfg["model"]
    changed = {k for k in pub if cfg[k] != pub[k]}
    assert changed == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (40, 256, 100352)
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 32, 12544)
    assert len(cfg["reduced"]) == 3
    for key, was in (("num_hidden_layers", "40"), ("num_experts", "256"),
                     ("vocab_size", "100352")):
        assert any(r.startswith(f"{key} {was} ->") for r in cfg["reduced"])
    # the three lists as published, their first five entries run
    assert model["layer_types"] == pub["layer_types"][:5] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert model["heads_per_layer"] == pub[
        "num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert model["mlp_layer_types"] == pub["mlp_layer_types"][:5] == [
        "dense", "sparse", "sparse", "sparse", "sparse"]
    assert (model["n_embd"], model["n_kv_head"], model["head_dim"],
            model["window"]) == (2048, 8, 128, 512)
    full = pub["rope_parameters"]["full_attention"]
    assert (model["full_theta"], model["yarn_factor"],
            model["yarn_original_len"], model["yarn_beta_fast"],
            model["yarn_beta_slow"], model["yarn_attention_factor"],
            model["full_rotary"]) == (
        full["rope_theta"], full["factor"],
        full["original_max_position_embeddings"], full["beta_fast"],
        full["beta_slow"], full["attention_factor"],
        full["partial_rotary_factor"]) == (
        500000, 64, 4096, 64, 1, 1.4158883083359672, 0.5)
    assert model["sliding_theta"] == pub["rope_parameters"][
        "sliding_attention"]["rope_theta"] == 10000
    assert (model["num_experts"], model["top_k"], model["route_scale"],
            model["expert_width"], model["shared_width"],
            model["dense_width"]) == (256, 8, 2.5, 512, 512, 8192)
    assert model["experts_held"] == [0, 32] and model["n_layer"] == 5
    assert model["remat"] is True and model["seq_len"] == 16384
    assert cfg["max_position_embeddings"] == 262144
    assert model["vocab_size"] == cfg["loss"]["uniform_over"] == 12544
    assert model["vocab_size"] * 8 == pub["vocab_size"]
    assert model["vocab_size"] % 128 == 0
    # what report.py reads of a configuration outside a rehearsal
    assert cfg["sample_unit"] == "tokens" and cfg["ce_chunk"] == 2048
    assert "8 chips" in cfg["cut"]["deployment"]
    assert "an eighth" in cfg["cut"]["load"]
    assert "13.04 GB" in cfg["cut"]["memory"]
    assert "16.64 GB" in cfg["cut"]["memory"]       # without remat: refused
    assert {"gating", "router_scores", "qk_norm", "rope", "aux_loss",
            "window_count", "weights", "sequence", "optimizer", "tokens",
            "ce_chunk"} <= set(cfg["assumed"])
    # each of the two unsettled readings names the other one
    assert "element-wise" in cfg["assumed"]["gating"]
    assert "14%" in cfg["assumed"]["gating"]
    assert "softmax" in cfg["assumed"]["router_scores"]
    assert cfg["kernel"] == {"tpu_custom_call": True,
                             "flash_path": "multi_block",
                             "flash_window": 512}
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    catalog = next(r for r in rows if r["source_url"] == cfg["source"])
    assert pub == catalog["config"]
    builder = mf.load_builder(cfg["builder"])
    with pytest.raises(ValueError, match="not the configuration file's"):
        builder.model_config(
            {**cfg, "model": {**model, "expert_width": 768}}, tiny=False)
    with pytest.raises(ValueError, match="not the configuration file's"):
        builder.model_config(
            {**cfg, "model": {**model,
                              "heads_per_layer": [48, 48, 48, 48, 48]}},
            tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config({**cfg, "sliding_window": 4096}, tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config({**cfg, "gating": "per-lane"}, tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config(
            {**cfg, "layer_types": ["sliding_attention"] * 40}, tiny=False)


def test_the_limit_lies_between_the_programs_readings_and_the_float8s():
    """``reference.rtol`` against the readings the file records (the
    cell's own runs and ``tools/limit.py`` took them on the v5e)."""
    ref = _cfg()["reference"]
    rtol, got = ref["rtol"], ref["readings"]
    assert rtol == 2.0 ** -10
    assert set(got["program_largest"]) == KEYS
    assert all(0 <= v < rtol for v in got["program_largest"].values())
    assert got["seeds"] >= 6
    low = got["float8"]
    assert low["fails"] is True and low["smallest"] > rtol
    assert low["by"] in got["program_largest"] and low["seeds"] >= 1
    assert got["unchanged_state_update_norm"] == 1.0 > rtol
    assert "float8_e4m3fn" in ref["rtol_why"]


def test_the_builder_refuses_a_step_that_is_not_the_files(monkeypatch):
    import jax
    from ray_tpu.parallel import make_mesh
    builder = mf.load_builder("laguna")
    cfg = _cfg()
    mcfg = _mcfg()
    good = dict(attn_layers="FSSSF", attn_heads="48,64,64,64,48",
                attn_window=512, attn_gate="headwise_sigmoid",
                moe_router="sigmoid", moe_experts_held=[0, 32])
    builder.refuse_unless_the_files_stack(good, mcfg, cfg)
    for bad in [{**good, "moe_experts_held": [0, 16]},
                {**good, "attn_heads": "48,48,48,48,48"},
                {**good, "attn_layers": "FFFFF"},
                {**good, "moe_router": "softmax"}, {}]:
        with pytest.raises(RuntimeError, match="the file says"):
            builder.refuse_unless_the_files_stack(bad, mcfg, cfg)
    traffic = mf.effective_traffic(
        mf.load_json(mf.traffic_path("b1-t16384")), True)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    band = dict(flash_path="multi_block", flash_window=512,
                flash_band_blocks=31, attn_kind="window_global")
    notes = {**good, **band, "flash_band_blocks": 136}  # the causal grid's
    real = builder._builder
    monkeypatch.setattr(builder, "_builder", lambda name: (
        types.SimpleNamespace(step_notes=lambda: notes) if name == "joyai"
        else real(name)))
    # a rehearsal is let through: it runs on the CPU by design
    assert callable(builder.build(cfg, traffic, mesh, 0, tiny=True)[
        "reference"])
    tiny = builder.model_config
    monkeypatch.setattr(builder, "model_config",
                        lambda cfg, _: tiny(cfg, True))
    built = builder.build(cfg, traffic, mesh, 0, tiny=False)
    with pytest.raises(RuntimeError, match="this cell measures that kernel"):
        built["reference"]({"params": None, "batch": None})
    assert set(built["shapes"]) >= {"moe_cost_per_step",
                                    "window_cost_per_step",
                                    "global_cost_per_step"}
    assert built["kernel_cost_per_step"]["flops"] > 0


def test_reference_returns_the_keys_the_step_reports():
    """``loop.py`` holds every key the reference returns against the
    first dispatch's metric of that name: each has to be one; the
    parameters may wait on the host; and the low reading is another
    number (the rounder bites)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    builder = mf.load_builder("laguna")
    ref = mf.load_reference("laguna")
    mcfg, model, loss_fn = builder.program(_cfg(), tiny=True)
    params = builder.make_params(model, 0)
    toks = np.random.default_rng(0).integers(0, 256, (2, 64), dtype=np.int32)
    batch = {"tokens": jnp.asarray(toks),
             "targets": jnp.asarray(np.roll(toks, -1, 1))}
    spec = builder.reference_spec(mcfg)
    out = ref.loss_and_grad_norm(params, batch, spec)
    loss, report_ = jax.jit(loss_fn)(params, batch)
    assert set(out) == KEYS - {"update_norm"}
    assert all(v.ndim == 0 for v in report_.values())
    assert out["loss"] == pytest.approx(float(loss), rel=1e-5)
    assert out["moe_absent_route_share"] == pytest.approx(
        float(report_["moe_absent_route_share"]))
    assert out["attn_window_out_rms"] == pytest.approx(
        float(report_["attn_window_out_rms"]), rel=1e-4)
    on_host = ref.loss_and_grad_norm(
        jax.device_get(params), batch,
        {**spec, "adamw": _cfg()["optimizer"]})
    assert set(on_host) == KEYS
    assert on_host["grad_norm"] == pytest.approx(out["grad_norm"], rel=1e-6)
    assert 0 < on_host["update_norm"] < 1
    low = ref.loss_and_grad_norm(
        params, batch, {**spec, "operand_dtype": "float8_e4m3fn"})
    assert low["grad_norm"] != out["grad_norm"]
    assert low["grad_norm"] == pytest.approx(out["grad_norm"], rel=0.05)


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
    assert "laguna routes by routed layer" in p.stderr
