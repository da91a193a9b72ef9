"""What the Xing4.0 cell adds to the benchmark: ``flops_xing.py`` against
counts by hand at the cell's shapes (``hc_train_cost`` among them), the
three new readers and the ones the cell joins on a small synthetic
profile whose numbers are known (built with ``test_program_trace.py``'s
helpers) and on runs with nothing to read, the manifest's entries
wherever they stand in their lists, the configuration file against the
catalog's keys, the limit against its readings, the builder's refusals
by the step's notes, and the rehearsal of the cell end to end."""

import json
import os
import subprocess
import sys
import types

import pytest

import test_program_trace as tp
from benchlib import flops, flops_mla, flops_xing as fx
from benchlib import manifest as mf, report

CELL = "xing4.0-29b-a4b.b1-t4096"
NEW = ["model.hc_ms_per_step", "model.hc_maps_ms_per_step",
       "hc_mix_roofline"]
JOINED = ["model.attention_ms_per_step", "model.mlp_ms_per_step",
          "model.moe_route_ms_per_step", "model.moe_experts_ms_per_step",
          "moe_experts_roofline", "kernel.attn_flash_ms_per_step",
          "attn_flash_roofline", "model.moe_shared_ms_per_step",
          "moe.held_route_share", "model.mla_proj_ms_per_step"]
KEYS = {"loss", "lm_loss", "grad_norm", "moe_absent_route_share",
        "update_norm", "hc_stream_spread"}


def _cfg():
    return mf.find_cell(mf.load_manifest(), CELL)["config_file"]


def _mcfg(**kw):
    import dataclasses
    mcfg = mf.load_builder("xing").model_config(_cfg(), tiny=False)
    return dataclasses.replace(mcfg, **kw)


# -- flops_xing.py against counts by hand ----

def test_parameters_of_each_layer_and_of_the_cut():
    c = _mcfg()
    per = fx.layer_params(c)
    assert per == {**c.layer_params(), "hc": c.hc_params()}
    maps = (4 * 3584 + 1) * 24 + 3          # phi, b and three gates
    assert fx.hc_params_per_sub_layer(c) == maps and per["hc"] == 2 * maps
    mla = (3584 * 768 + 768 + 768 * 32 * 192 + 3584 * 576 + 512
           + 512 * 32 * 256 + 4096 * 3584)
    assert per["mla"] == mla == 28411136
    assert per["dense"] == mla + 2 * 3584 + 3 * 3584 * 9216 + 2 * maps
    assert per["routed"] == (mla + 2 * 3584 + 3584 * 64 + 64
                             + 9 * 3 * 3584 * 1024 + 2 * maps)
    assert fx.num_params(c) == c.num_params() == (
        per["dense"] + 4 * per["routed"] + 2 * 16384 * 3584 + 3584)
    assert fx.num_params(c) * 14 == pytest.approx(10.63e9, rel=1e-3)
    with_mtp = _mcfg(mtp_depth=1)
    assert fx.num_params(with_mtp) == with_mtp.num_params()
    assert fx.num_params(with_mtp) * 14 == pytest.approx(12.79e9, rel=1e-3)
    # one stream: flops_mla's counts, no maps
    one = _mcfg(hc_mult=1)
    assert fx.layer_params(one)["hc"] == 0
    assert fx.num_params(one) == flops_mla.num_params(one) == one.num_params()


def test_required_operations_per_token_and_per_step():
    c = _mcfg()
    per = fx.step_forward_flops_per_token(c)
    base = flops_mla.step_forward_flops_per_token(c)
    assert {k: per[k] for k in base} == base
    assert per["hc_maps"] == 10 * 2.0 * 14336 * 24
    assert per["hc_mix"] == 10 * 2.0 * 3584 * (4 + 16 + 4)
    total = sum(per.values())
    assert fx.train_flops_per_token(c) == 3.0 * total
    step = 4096 * fx.train_flops_per_token(c)
    assert step == pytest.approx(1.17e13, rel=5e-3)
    assert step / 197e12 == pytest.approx(59.4e-3, rel=2e-3)
    share = {k: v / total for k, v in per.items()}
    # what the cell's `why` and the file's `cut.consequence` say
    assert share["mla_proj"] == pytest.approx(0.298, abs=2e-3)
    assert share["attn_core"] == pytest.approx(0.220, abs=2e-3)
    assert share["dense_mlp"] == pytest.approx(0.208, abs=2e-3)
    assert share["head"] == pytest.approx(0.123, abs=2e-3)
    assert share["hc_maps"] + share["hc_mix"] < 0.01
    assert share["mtp_proj"] == 0.0
    assert fx.step_forward_flops_per_token(_mcfg(hc_mult=1))["hc_maps"] == 0


def test_the_residual_paths_cost_and_its_least_time():
    c = _mcfg()
    cost = fx.hc_train_cost(c, 4096)
    # (6 n + 5) d elements a token a sub-layer in bfloat16, ten sub-layers
    assert cost["bytes"] == 10 * 4096 * 29 * 3584 * 2 == 8514437120
    per = fx.step_forward_flops_per_token(c)
    assert cost["flops"] == 3.0 * 4096 * (per["hc_maps"] + per["hc_mix"])
    least = flops.roofline(cost["flops"], cost["bytes"], 197e12, 819e9)
    assert least["bound"] == "memory"
    assert least["least_s"] == pytest.approx(10.40e-3, rel=1e-3)
    assert fx.hc_train_cost(c, 4096, bytes_per_el=4)["bytes"] \
        == 2 * cost["bytes"]
    assert fx.hc_train_cost(_mcfg(mtp_depth=1), 4096)["bytes"] \
        == cost["bytes"] * 12 // 10
    assert fx.hc_train_cost(_mcfg(hc_mult=1), 4096) == {"flops": 0.0,
                                                        "bytes": 0}
    # the shared kernels' costs are flops_mla's at this config
    assert fx.latent_attention_train_cost(c, 1) \
        == flops_mla.latent_attention_train_cost(c, 1)
    assert fx.held_experts_train_cost(c, 4096)["flops"] \
        == 4 * 6.0 * (4096 * 4 // 8) * 3 * 3584 * 1024


# -- the readers on a synthetic profile ----

L = "jit(step)/jit(main)/jvp(JoyAI)/"
B = "jit(step)/jit(main)/transpose(jvp(JoyAI))/"
R = "blocks/jvp(JoyAI)/blocks/checkpoint/rematted_computation/"
OP_NAMES = {
    "fusion.1": L + "embed/hc_expand/concatenate",
    "fusion.2": L + "blocks/h_0/hc_attn/maps/dot_general",
    "fusion.3": L + "blocks/h_0/hc_attn/pre/mul",
    "fusion.4": L + "blocks/h_0/attn/q_down/proj/dot_general",
    "flash.5": L + "blocks/h_0/attn/core/jit(mla_flash_fwd)/pallas_call",
    "fusion.6": L + "blocks/h_0/hc_attn/post/add",
    "fusion.7": B + R + "h_1/hc_mlp/maps/div",
    "fusion.8": B + R + "h_1/hc_mlp/pre/mul",
    "fusion.9": B + "blocks/jvp(JoyAI)/blocks/checkpoint/h_1/hc_mlp/post/"
                    "mul",
    "fusion.10": L + "blocks/h_1/mlp/router/dot_general",
    "fusion.11": L + "blocks/h_1/mlp/dispatch/sort",
    "gmm.12": L + "blocks/h_1/mlp/experts/jit(gmm)/pallas_call",
    "fusion.13": L + "blocks/h_1/mlp/shared/up/dot_general",
    "fusion.14": L + "blocks/hc_collapse/add",
    "fusion.15": L + "blocks/h_1/attn_norm/mul",
    "fusion.16": L + "loss/loss/while/body",
    "fusion.17": "jit(step)/optimizer/mul",
}
US = [2, 14, 6, 8, 40, 10, 12, 4, 9, 5, 7, 50, 3, 1, 20, 40, 100]


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
        "kernel_cost_per_step": {"flops": 197e12 * 10e-6, "bytes": 1.0},
        "shapes": {"moe_cost_per_step": {"flops": 1.0,
                                         "bytes": 819e9 * 5e-6},
                   "hc_cost_per_step": {"flops": 1.0,
                                        "bytes": 819e9 * 5.8e-6}},
        "reference": {"program": {"moe_absent_route_share": 0.875}}}
    return report.Run(mf.find_cell(man, CELL), facts, {}, {},
                      {"steps": 2} if traced else None)


def test_every_reader_of_the_cell_reads(tmp_path):
    run = _run(tmp_path, _xspace())
    got = {name: mf.load_reader(name)(run) for name in NEW + JOINED}
    assert got == {
        # expand 2, maps 14 + 12, pre 6 + 4, post 10 + 9, collapse 1: the
        # forward's and the recomputed block's, none of it under attn,
        # mlp or the block's norms
        "model.hc_ms_per_step": pytest.approx(0.058 / 2),
        "model.hc_maps_ms_per_step": pytest.approx(0.026 / 2),
        "hc_mix_roofline": pytest.approx(20.0),     # 5.8 us over 29
        "model.attention_ms_per_step": pytest.approx(0.048 / 2),
        "model.mlp_ms_per_step": pytest.approx(0.065 / 2),
        "model.moe_route_ms_per_step": pytest.approx(0.012 / 2),
        "model.moe_experts_ms_per_step": pytest.approx(0.050 / 2),
        "moe_experts_roofline": pytest.approx(20.0),    # 5 us over 25
        "kernel.attn_flash_ms_per_step": pytest.approx(0.040 / 2),
        "attn_flash_roofline": pytest.approx(50.0),     # 10 us over 20
        "model.moe_shared_ms_per_step": pytest.approx(0.003 / 2),
        "moe.held_route_share": pytest.approx(12.5),
        "model.mla_proj_ms_per_step": pytest.approx(0.008 / 2),
    }


def test_new_readers_are_none_with_nothing_to_read(tmp_path, monkeypatch):
    """No trace; a step with one residual stream (any other model's, or
    the parent's program asked for another cell); no ``train.fit`` span.
    A reader returns None and does not raise."""
    run = _run(tmp_path / "a", _xspace(), traced=False)
    assert [mf.load_reader(n)(run) for n in NEW] == [None] * 3
    other = {k: v.replace("/hc_attn/", "/attn/").replace(
        "/hc_mlp/", "/mlp/").replace("/hc_expand/", "/wte/").replace(
        "/hc_collapse/", "/norm_f/") for k, v in OP_NAMES.items()}
    run = _run(tmp_path / "b", _xspace(other))
    assert [mf.load_reader(n)(run) for n in NEW] == [None] * 3
    from ray_tpu.util import tracing
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    run = _run(tmp_path / "c", _xspace())
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    assert [mf.load_reader(n)(run) for n in NEW] == [None] * 3


# -- the manifest and the configuration file ----

def test_the_manifest_lists_the_configuration_the_cell_and_the_metrics():
    """That the entries are present, wherever they stand in their lists
    (a later PR appends behind them, and may list its cell beside this
    one)."""
    man = mf.load_manifest()
    assert mf.check_manifest(man) == []
    config = next(c for c in man["configs"]
                  if c["name"] == "xing4.0-29b-a4b")
    assert config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert config["file"] == "benchmark/configs/xing4.0-29b-a4b.json"
    assert config["source"] == _cfg()["source"]
    assert len(config["why"]) <= 200
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "xing4.0-29b-a4b", "b1-t4096", 1)
    assert "no MTP module" in cell["why"] and len(cell["why"]) <= 200
    per_layer = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["source"] == "device_trace"
    assert [(per_layer[n]["unit"], per_layer[n]["layer"],
             per_layer[n]["moves"]) for n in NEW] == [
        ("ms", "model", "step_ms_p90"), ("ms", "model", "step_ms_p90"),
        ("%", "kernel", "tokens_per_s_per_chip")]
    for name in JOINED:
        assert CELL in per_layer[name]["workloads"]
    # the cell runs no MTP module, so it does not report the module's time
    assert CELL not in per_layer["model.mtp_ms_per_step"]["workloads"]
    e2e = [m["name"] for m in mf.metrics_of(man, "end_to_end", CELL)]
    assert e2e == ["tokens_per_s_per_chip", "step_ms_p90", "setup_s"]


def test_the_configuration_runs_every_published_width():
    cfg = _cfg()
    pub, model = cfg["published"], cfg["model"]
    changed = {k for k in pub if cfg[k] != pub[k]}
    assert changed == {"num_hidden_layers", "first_k_dense_replace",
                       "n_routed_experts", "vocab_size",
                       "num_nextn_predict_layers"}
    assert [pub[k] for k in sorted(changed)] == [2, 64, 40, 1, 131072]
    assert [cfg[k] for k in sorted(changed)] == [1, 8, 5, 0, 16384]
    assert len(cfg["reduced"]) == 5
    for key, was in (("num_hidden_layers", "40"),
                     ("first_k_dense_replace", "2"),
                     ("n_routed_experts", "64"), ("vocab_size", "131072"),
                     ("num_nextn_predict_layers", "1")):
        assert any(r.startswith(f"{key} {was} ->") for r in cfg["reduced"])
    assert (model["n_embd"], model["q_rank"], model["kv_rank"],
            model["n_head"], model["nope_dim"], model["rope_dim"],
            model["v_dim"], model["dense_width"], model["expert_width"],
            model["shared_width"]) == (
        3584, 768, 512, 32, 128, 64, 128, 9216, 1024, 1024)
    assert (model["num_experts"], model["top_k"], model["route_scale"],
            model["norm_topk_prob"]) == (64, 4, 2.0, True)
    assert (model["hc_mult"], model["hc_sinkhorn_iters"], model["hc_eps"],
            model["hc_res_clamp"]) == (
        pub["hc_mult"], pub["hc_sinkhorn_iters"], pub["hc_eps"],
        pub["mhc_h_res_clamp_max"]) == (4, 20, 1e-6, 30)
    assert pub["mhc_h_res_clamp_min"] == -30
    assert cfg["rope_scaling"] == pub["rope_scaling"]       # copied whole
    yarn, scaling = model["rope_scaling"], pub["rope_scaling"]
    assert (yarn["factor"], yarn["original_len"], yarn["beta_fast"],
            yarn["beta_slow"], yarn["mscale"], yarn["mscale_all_dim"]) == (
        scaling["factor"], scaling["original_max_position_embeddings"],
        scaling["beta_fast"], scaling["beta_slow"], scaling["mscale"],
        scaling["mscale_all_dim"]) == (64, 4096, 32, 1, 1, 1)
    assert model["rope_theta"] == pub["rope_theta"] == 10000
    import math
    m = 0.1 * math.log(64) + 1
    assert model["mla_scale"] == pytest.approx(192 ** -0.5 * m * m,
                                               rel=1e-12)
    assert model["experts_held"] == [0, 8] and model["n_layer"] == 5
    assert model["dense_layers"] == 1 and model["mtp_depth"] == 0
    assert model["remat"] is True
    assert model["seq_len"] == scaling["original_max_position_embeddings"]
    assert model["vocab_size"] == cfg["loss"]["uniform_over"] == 16384
    assert model["vocab_size"] * 8 == pub["vocab_size"]
    # what report.py reads of a configuration outside a rehearsal
    assert cfg["sample_unit"] == "tokens" and cfg["ce_chunk"] == 2048
    assert "8-chip deployment" in cfg["cut"]["deployment"]
    assert "an eighth" in cfg["cut"]["load"]
    # the memory step taken, with both readings
    assert "14.73 GB" in cfg["cut"]["memory"]
    assert "12.03 GB" in cfg["cut"]["memory"]
    assert {"hc_map_norm", "hc_eps", "hc_clamp", "hc_ends", "hc_mtp",
            "hc_init", "hc_dtypes", "hc_mult_1", "yarn", "rope",
            "weights", "sequence", "optimizer", "tokens", "ce_chunk"} <= set(
        cfg["assumed"])
    for key in ("hc_map_norm", "hc_eps", "hc_clamp", "hc_ends", "hc_mtp",
                "yarn"):        # each names its other reading
        assert "other reading" in cfg["assumed"][key], key
    assert cfg["kernel"] == {"tpu_custom_call": True,
                             "flash_path": "mla_multi_block"}
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
            {**cfg, "model": {**model, "hc_sinkhorn_iters": 10}}, tiny=False)
    with pytest.raises(ValueError, match="not the configuration file's"):
        builder.model_config(
            {**cfg, "model": {**model, "rope_scaling": {
                **yarn, "mscale_all_dim": 0.0}}}, tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config({**cfg, "hc_mult": 2}, tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config({**cfg, "mhc_h_res_clamp_min": -10},
                             tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config(
            {**cfg, "rope_scaling": {**scaling, "factor": 32}}, tiny=False)


def test_the_limit_lies_between_the_programs_readings_and_the_float8s():
    """``reference.rtol`` against the readings the file records (the
    cell's own runs and ``tools/limit.py`` took them on the v5e)."""
    ref = _cfg()["reference"]
    rtol, got = ref["rtol"], ref["readings"]
    assert rtol == 2.0 ** -10
    assert set(got["program_largest"]) == KEYS
    assert all(0 <= v < rtol for v in got["program_largest"].values())
    assert got["seeds"] >= 8
    low = got["float8"]
    assert low["fails"] is True and low["smallest"] > rtol
    assert low["by"] in got["program_largest"] and low["seeds"] >= 1
    assert got["unchanged_state_update_norm"] == 1.0 > rtol
    assert "float8_e4m3fn" in ref["rtol_why"]
    # the maps' own gradient norm: reported, not compared, and why
    assert set(ref["reported_grad_groups"]) == {"grad_norm_hc"}
    assert max(got["grad_norm_hc_off"]) > 10 * rtol
    assert "grad_norm_hc" in ref["rtol_why"]


def test_the_builder_refuses_a_step_that_is_not_the_files(monkeypatch):
    import jax
    from ray_tpu.parallel import make_mesh
    builder = mf.load_builder("xing")
    cfg = _cfg()
    good = dict(flash_path="mla_multi_block", rope_kind="yarn", hc_mult=4,
                hc_sinkhorn_iters=20, hc_state_dtype="bfloat16",
                mla_scale=cfg["model"]["mla_scale"])
    builder.refuse_unless_as_the_file_says(good, cfg)
    for bad in [{**good, "flash_path": "xla"}, {**good, "hc_mult": 2},
                {**good, "hc_state_dtype": "float32"},
                {**good, "mla_scale": 192 ** -0.5},
                {k: v for k, v in good.items() if k != "rope_kind"}, {}]:
        with pytest.raises(RuntimeError, match="this cell measures those"):
            builder.refuse_unless_as_the_file_says(bad, cfg)
    traffic = mf.effective_traffic(
        mf.load_json(mf.traffic_path("b1-t4096")), True)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    real = builder._other
    monkeypatch.setattr(builder, "_other", lambda name: (
        types.SimpleNamespace(
            step_notes=lambda: {**good, "flash_path": "xla"},
            SPARE_DISPATCHES=32) if name == "joyai" else real(name)))
    # a rehearsal is let through: it runs on the CPU by design
    assert callable(builder.build(cfg, traffic, mesh, 0, tiny=True)[
        "reference"])
    tiny = builder.model_config
    monkeypatch.setattr(builder, "model_config",
                        lambda cfg, _: tiny(cfg, True))
    built = builder.build(cfg, traffic, mesh, 0, tiny=False)
    with pytest.raises(RuntimeError, match="this cell measures those"):
        built["reference"]({"params": None, "batch": None})
    assert set(built["shapes"]) >= {"moe_cost_per_step", "hc_cost_per_step"}
    assert built["shapes"]["hc_cost_per_step"]["bytes"] > 0
    assert built["kernel_cost_per_step"]["flops"] > 0


def test_reference_returns_the_keys_the_step_reports():
    """``loop.py`` holds every key the reference returns against the
    first dispatch's metric of that name: each has to be one; the
    parameters may wait on the host; asked for a gradient group the
    reference gives it (the tests and the tools ask; the cell does not);
    and the low reading is another number (the rounder bites)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    builder = mf.load_builder("xing")
    ref = mf.load_reference("xing")
    cfg = _cfg()
    mcfg, model, loss_fn = builder.program(cfg, tiny=True)
    assert mcfg.mtp_depth == 1      # the rehearsal keeps the module
    params = builder.make_params(model, 0)
    toks = np.random.default_rng(0).integers(0, 256, (2, 64), dtype=np.int32)
    batch = {"tokens": jnp.asarray(toks),
             "targets": jnp.asarray(np.roll(toks, -1, 1))}
    spec = builder.reference_spec(mcfg)
    out = ref.loss_and_grad_norm(params, batch, spec)
    loss, report_ = jax.jit(loss_fn)(params, batch)
    assert set(out) == (KEYS | {"mtp_loss"}) - {"update_norm"}
    assert all(v.ndim == 0 for v in report_.values())
    assert out["loss"] == pytest.approx(float(loss), rel=1e-5)
    assert out["moe_absent_route_share"] == pytest.approx(
        float(report_["moe_absent_route_share"]))
    assert out["hc_stream_spread"] == pytest.approx(
        float(report_["hc_stream_spread"]), rel=1e-4)
    assert float(report_["hc_res_row_err"]) < 1e-4
    on_host = ref.loss_and_grad_norm(
        jax.device_get(params), batch,
        {**spec, "adamw": cfg["optimizer"],
         "grad_groups": cfg["reference"]["reported_grad_groups"]})
    assert set(on_host) == KEYS | {"mtp_loss", "grad_norm_hc"}
    assert on_host["grad_norm"] == pytest.approx(out["grad_norm"], rel=1e-6)
    assert 0 < on_host["grad_norm_hc"] < 0.1 * on_host["grad_norm"]
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
    assert set(got["plain_f32"]) == KEYS | {"mtp_loss"}
    # the step's own first update against the reference's AdamW step
    assert got["program_from"] == "first dispatch"
    assert got["program"]["update_norm"] == pytest.approx(
        got["plain_f32"]["update_norm"], rel=1e-4)
    assert got["program"]["hc_stream_spread"] == pytest.approx(
        got["plain_f32"]["hc_stream_spread"], rel=1e-4)
    assert "xing reference done" in p.stderr
