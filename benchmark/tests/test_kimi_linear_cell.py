"""What the Kimi-Linear cell adds to the benchmark: ``flops_kimi_linear.py``
against counts by hand at the cell's shapes, the three new readers on a
small synthetic profile whose numbers are known (built with
``test_program_trace.py``'s helpers) and on runs with nothing to read, the
manifest's entries wherever they stand in their lists, the configuration
file against the catalog's keys, the limit against its readings, the
builder's refusal by the step's notes, and the rehearsal of the cell end to
end."""

import json
import os
import subprocess
import sys
import types

import pytest

import test_program_trace as tp
from benchlib import flops, flops_kimi_linear as fk, kda_trace
from benchlib import manifest as mf, report

CELL = "kimi-linear-48b-a3b.b1-t16384"
NEW = ["model.kda_ms_per_step", "model.kda_scan_ms_per_step",
       "kda_scan_roofline"]
JOINED = ["model.attention_ms_per_step", "model.mlp_ms_per_step",
          "model.moe_route_ms_per_step", "model.moe_experts_ms_per_step",
          "moe_experts_roofline", "model.moe_shared_ms_per_step",
          "moe.held_route_share", "model.mla_proj_ms_per_step",
          "kernel.attn_flash_ms_per_step", "attn_flash_roofline"]


def _cfg():
    return mf.find_cell(mf.load_manifest(), CELL)["config_file"]


def _mcfg(**kw):
    import dataclasses
    mcfg = mf.load_builder("kimi_linear").model_config(_cfg(), tiny=False)
    return dataclasses.replace(mcfg, **kw)


# -- flops_kimi_linear.py against counts by hand ----

def test_parameters_of_each_part_and_of_the_cut():
    cut, whole = _mcfg(), _mcfg(experts_held=None)
    per = fk.layer_params(cut)
    assert per == cut.layer_params()
    assert per["kda"] == (4 * 2304 * 4096 + 3 * 4 * 4096
                          + 2 * (2304 * 128 + 128 * 4096) + 2 * 4096 + 32
                          + 2304 * 32 + 128)                    # 39.52 M
    assert per["mla"] == (2304 * 32 * 192 + 2304 * 576 + 512
                          + 512 * 32 * 256 + 4096 * 2304)       # 29.11 M
    assert per["dense"] == 3 * 2304 * 9216
    assert per["routed"] == (2304 * 256 + 256 + 3 * 2304 * 1024
                             + 8 * 3 * 2304 * 1024)
    assert fk.layer_params(whole)["routed"] == pytest.approx(1819.6e6,
                                                             rel=1e-3)
    assert fk.num_params(cut) == cut.num_params()
    assert fk.num_params(cut) == pytest.approx(602.45e6, rel=1e-4)
    assert fk.num_params(cut) * 14 == pytest.approx(8.43e9, rel=1e-3)
    published = _mcfg(experts_held=None, vocab_size=163840, n_layer=27)
    assert fk.num_params(published) == pytest.approx(49.12e9, rel=1e-3)
    assert fk.layers_of(cut) == (4, 1) and fk.layers_of(published) == (20, 7)


def test_required_operations_per_token_and_per_step():
    c = _mcfg()
    per = fk.forward_flops_per_token(c)
    assert per["kda_proj"] == 2.0 * (4 * 2304 * 4096
                                     + 2 * (2304 * 128 + 128 * 4096)
                                     + 2304 * 32)               # 78.9 M
    # a chunk of 64 rows a head: key-key and query-key products under the
    # diagonal, the solve against 256 columns, three state products, the
    # outputs inside the chunk
    chunk = (64 * 64 * 128 + 64 * 64 * 128 + 3 * 64 * 128 * 128
             + 64 * 64 * 64)
    assert fk.kda_recurrence_macs_per_token(c) == 32 * chunk / 64
    assert per["kda_scan"] == pytest.approx(4.46e6, rel=1e-2)
    assert per["attn_core"] == 2.0 * 16384 * 32 * 320 * 0.5     # 167.8 M
    assert per["dense_mlp"] == 2.0 * 3 * 2304 * 9216
    assert per["held_experts"] == 8 * 8 / 256 * 2.0 * 3 * 2304 * 1024
    assert per["head"] == 2.0 * 2304 * 20480
    step = fk.step_forward_flops_per_token(c)
    assert step["kda_proj"] == 4 * per["kda_proj"]
    assert step["kda_scan"] == 4 * per["kda_scan"]
    assert step["mla_proj"] == per["mla_proj"]
    assert step["shared"] == 4 * per["shared"]
    assert step["dense_mlp"] == per["dense_mlp"]
    total = sum(step.values())
    assert total == pytest.approx(856.8e6, rel=1e-3)
    assert fk.train_flops_per_token(c) == 3 * total
    # the configuration file's `cut.consequence`
    assert 16384 * 3 * total == pytest.approx(4.21e13, rel=2e-3)
    assert (step["kda_proj"] + step["kda_scan"]) / total == pytest.approx(
        0.389, abs=2e-3)
    assert step["attn_core"] / total == pytest.approx(0.196, abs=2e-3)
    assert step["kda_scan"] / total == pytest.approx(0.021, abs=1e-3)


def test_kernel_costs_and_their_least_times():
    c = _mcfg()
    scan = fk.kda_scan_train_cost(c, 1)
    assert scan["flops"] == 4 * 16384 * 3 * fk.forward_flops_per_token(
        c)["kda_scan"]
    # q, k, v, o, dO, dq, dk, dv at two bytes; g, dg, beta, dbeta at four
    assert scan["bytes"] == 4 * 16384 * (8 * 4096 * 2 + 2 * 4096 * 4
                                         + 2 * 32 * 4)
    least = flops.roofline(scan["flops"], scan["bytes"], 197e12, 819e9)
    assert least["bound"] == "memory"
    assert least["least_s"] == pytest.approx(7.89e-3, rel=1e-2)
    core = fk.mla_core_train_cost(c, 1)
    assert core["flops"] == 32 * 2.0 * 16384 * 16384 * (3 * 192 + 3 * 128) \
        * 0.5
    # one layer of JoyAI's six cores a row of twice the length: by its rule
    joyai = mf.load_builder("joyai").model_config(
        mf.find_cell(mf.load_manifest(),
                     "joyai-llm-flash.b1-t8192")["config_file"], tiny=False)
    from benchlib import flops_mla
    assert core["flops"] == pytest.approx(
        flops_mla.latent_attention_train_cost(joyai, 1)["flops"] / 6 * 4)
    experts = fk.held_experts_train_cost(c, 16384)
    rows = 16384 * 8 // 32            # 1/32 of the routes
    assert experts["flops"] == 4 * 6.0 * rows * 3 * 2304 * 1024
    assert experts["bytes"] == 4 * 9 * 2 * (rows * 2304 + rows * 1024
                                            + 8 * 2304 * 1024)


# -- the readers on a synthetic profile ----

L = "jit(step)/jit(main)/jvp(KimiLinear)/"
B = "jit(step)/jit(main)/transpose(jvp(KimiLinear))/"
R = "blocks/checkpoint/rematted_computation/"
OP_NAMES = {
    "fusion.1": L + "blocks/h_0/kda/qkv/q/dot_general",
    "fusion.2": L + "blocks/h_0/kda/checkpoint/conv/checkpoint/mul",
    "fusion.3": L + "blocks/h_0/kda/checkpoint/scan/while/body/"
                    "closed_call/checkpoint/dot_general",
    "fusion.4": B + R + "h_1/kda/checkpoint/rematted_computation/scan/"
                        "while/body/closed_call/checkpoint/exp",
    "fusion.5": B + R + "h_1/kda/checkpoint/out_gate/checkpoint/"
                        "rematted_computation/div",
    "fusion.6": L + "blocks/h_1/kda/out/out/dot_general",
    "fusion.7": L + "blocks/h_3/attn/q_up/dot_general",
    "fusion.8": L + "blocks/h_3/attn/kv_down/proj/dot_general",
    "flash.9": L + "blocks/h_3/attn/core/jit(mla_flash_fwd)/pallas_call",
    "flash.10": B + R + "h_3/attn/core/jit(mla_flash_bwd)/pallas_call",
    "fusion.11": L + "blocks/h_1/mlp/dispatch/sort",
    "gmm.12": L + "blocks/h_1/mlp/experts/jit(gmm)/pallas_call",
    "fusion.13": L + "blocks/h_1/mlp/shared/up/dot_general",
    "fusion.14": L + "loss/loss/while/body",
    "fusion.15": "jit(step)/optimizer/mul",
}
US = [14, 30, 60, 40, 6, 10, 50, 10, 20, 40, 9, 50, 7, 40, 100]


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
        "kernel_cost_per_step": {"flops": 197e12 * 30e-6, "bytes": 1.0},
        "shapes": {"moe_cost_per_step": {"flops": 1.0,
                                         "bytes": 819e9 * 5e-6},
                   "kda_scan_cost_per_step": {"flops": 1.0,
                                              "bytes": 819e9 * 5e-6}},
        "reference": {"program": {"moe_absent_route_share": 0.96875}}}
    return report.Run(mf.find_cell(man, CELL), facts, {}, {},
                      {"steps": 2} if traced else None)


def test_the_scope_beneath_kda_skips_the_checkpoints_names():
    """On ``path_trace``'s paths: the scopes above an operation."""
    under = kda_trace.scope_under
    assert under(("blocks", "h_0", "kda", "qkv", "q")) == "qkv"
    assert under(("blocks", "h_0", "kda", "checkpoint", "scan", "while",
                  "body")) == "scan"
    assert under(("blocks", "checkpoint", "rematted_computation", "h_1",
                  "kda", "checkpoint", "rematted_computation", "out_gate",
                  "checkpoint")) == "out_gate"
    assert under(("blocks", "h_0", "kda")) == ""
    assert under(("blocks", "h_3", "attn", "core")) is None
    assert under(("loss", "kda", "scan")) is None


def test_every_reader_of_the_cell_reads(tmp_path):
    run = _run(tmp_path, _xspace())
    got = {name: mf.load_reader(name)(run) for name in NEW + JOINED}
    assert got == {
        # 14 + 30 + 60 + 40 + 6 + 10 under kda, 60 + 40 of them under scan
        "model.kda_ms_per_step": pytest.approx(0.160 / 2),
        "model.kda_scan_ms_per_step": pytest.approx(0.100 / 2),
        "kda_scan_roofline": pytest.approx(10.0),       # 5 us over 50
        # the MLA layer alone: nothing under kda counts as attention
        "model.attention_ms_per_step": pytest.approx(0.120 / 2),
        "model.mlp_ms_per_step": pytest.approx(0.066 / 2),
        "model.moe_route_ms_per_step": pytest.approx(0.009 / 2),
        "model.moe_experts_ms_per_step": pytest.approx(0.050 / 2),
        "moe_experts_roofline": pytest.approx(20.0),    # 5 us over 25
        "model.moe_shared_ms_per_step": pytest.approx(0.007 / 2),
        "moe.held_route_share": pytest.approx(3.125),
        "model.mla_proj_ms_per_step": pytest.approx(0.060 / 2),
        "kernel.attn_flash_ms_per_step": pytest.approx(0.060 / 2),
        "attn_flash_roofline": pytest.approx(100.0),    # 30 us over 30
    }


def test_new_readers_are_none_with_nothing_to_read(tmp_path, monkeypatch):
    """No trace; a step without the module (any other cell's, or the
    parent's program); no ``train.fit`` span; a worker that reported no
    cost. A reader returns None and does not raise."""
    run = _run(tmp_path / "a", _xspace(), traced=False)
    assert [mf.load_reader(n)(run) for n in NEW] == [None, None, None]
    other = {k: v.replace("/kda/", "/attn/") for k, v in OP_NAMES.items()}
    run = _run(tmp_path / "b", _xspace(other))
    assert [mf.load_reader(n)(run) for n in NEW] == [None, None, None]
    from ray_tpu.util import tracing
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    run = _run(tmp_path / "c", _xspace())
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    assert [mf.load_reader(n)(run) for n in NEW] == [None, None, None]
    run = _run(tmp_path / "d", _xspace())
    del run.worker["shapes"]["kda_scan_cost_per_step"]
    assert mf.load_reader("kda_scan_roofline")(run) is None
    assert mf.load_reader("model.kda_scan_ms_per_step")(run) is not None


# -- the manifest and the configuration file ----

def test_the_manifest_lists_the_configuration_the_cell_and_the_metrics():
    """Wherever the entries stand in their lists (a later PR appends
    behind them)."""
    man = mf.load_manifest()
    assert mf.check_manifest(man) == []
    config = next(c for c in man["configs"]
                  if c["name"] == "kimi-linear-48b-a3b")
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["file"] == "benchmark/configs/kimi-linear-48b-a3b.json"
    assert len(config["why"]) <= 200
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-linear-48b-a3b", "b1-t16384", 1)
    per_layer = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
    assert per_layer["kda_scan_roofline"]["moves"] == "tokens_per_s_per_chip"
    assert per_layer["kda_scan_roofline"]["unit"] == "%"
    for name in NEW[:2]:
        assert per_layer[name]["moves"] == "step_ms_p90"
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
            pub["vocab_size"]) == (27, 256, 163840)
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 8, 20480)
    assert len(cfg["reduced"]) == 3
    for key, was in (("num_hidden_layers", "27"), ("num_experts", "256"),
                     ("vocab_size", "163840")):
        assert any(r.startswith(f"{key} {was} ->") for r in cfg["reduced"])
    assert (model["n_embd"], model["kda_heads"], model["kda_head_dim"],
            model["conv_kernel"]) == (2304, 32, 128, 4)
    assert (model["n_head"], model["nope_dim"], model["rope_dim"],
            model["v_dim"], model["kv_rank"]) == (32, 128, 64, 128, 512)
    assert pub["q_lora_rank"] is None and pub["mla_use_nope"] is True
    assert (model["num_experts"], model["top_k"], model["route_scale"],
            model["expert_width"], model["shared_width"],
            model["dense_width"]) == (256, 8, 2.446, 1024, 1024, 9216)
    assert model["mla_layers"] == pub["linear_attn_config"][
        "full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert cfg["linear_attn_config"] == pub["linear_attn_config"]
    assert model["experts_held"] == [0, 8] and model["n_layer"] == 5
    assert model["remat"] is True and model["seq_len"] == 16384
    assert model["vocab_size"] == cfg["loss"]["uniform_over"] == 20480
    assert model["vocab_size"] * 8 == pub["vocab_size"]
    assert model["vocab_size"] % 128 == 0
    # what report.py reads of a configuration outside a rehearsal
    assert cfg["sample_unit"] == "tokens" and cfg["ce_chunk"] == 2048
    assert "32 chips" in cfg["cut"]["deployment"]
    assert "1/32" in cfg["cut"]["load"]
    assert "13.17 GB" in cfg["cut"]["memory"]
    assert {"conv", "qk_norm", "decay_pair", "gate_pair", "beta", "mla_nope",
            "selection_bias", "sequence", "optimizer", "weights", "tokens",
            "ce_chunk", "described_from_memory"} <= set(cfg["assumed"])
    assert cfg["kernel"] == {"tpu_custom_call": True,
                             "flash_path": "mla_multi_block",
                             "kda_path": "chunked"}
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    catalog = next(r for r in rows if r["source_url"] == cfg["source"])
    assert pub == catalog["config"]
    builder = mf.load_builder(cfg["builder"])
    with pytest.raises(ValueError, match="not the configuration file's"):
        builder.model_config(
            {**cfg, "model": {**model, "expert_width": 768}}, tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config({**cfg, "kv_lora_rank": 256}, tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config({**cfg, "q_lora_rank": 1536}, tiny=False)


def test_the_limit_lies_between_the_programs_readings_and_the_float8s():
    """``reference.rtol`` against the readings the file records (the
    cell's own runs and ``tools/limit.py`` took them on the v5e)."""
    ref = _cfg()["reference"]
    rtol, got = ref["rtol"], ref["readings"]
    assert rtol == 2.0 ** -10
    assert set(got["program_largest"]) == {
        "loss", "grad_norm", "moe_absent_route_share", "update_norm",
        "kda_out_rms", "grad_norm_kda_gates"}
    assert all(0 <= v < rtol for v in got["program_largest"].values())
    low = got["float8"]
    assert low["fails"] is True and low["smallest"] > rtol
    assert low["by"] in got["program_largest"]
    assert got["unchanged_state_update_norm"] == 1.0 > rtol
    # every planted fault moved one of the two KDA keys past the limit
    assert set(got["faults"]) - {"what"} == {
        "decay_left_out_inside_the_chunk",
        "a_heads_mean_decay_for_its_channels",
        "beta_left_out_of_the_correction",
        "the_correction_reads_the_undecayed_state",
        "a_chunks_state_handed_on_one_chunk_late"}
    for name, moved in got["faults"].items():
        if name != "what":
            assert max(moved["kda_out_rms"],
                       moved["grad_norm_kda_gates"]) > 2 * rtol, name


def test_the_builder_refuses_a_step_whose_mixers_ran_otherwise(monkeypatch):
    import jax
    from ray_tpu.parallel import make_mesh
    builder = mf.load_builder("kimi_linear")
    cfg = _cfg()
    kernel = cfg["kernel"]
    good = dict(flash_path="mla_multi_block", kda_path="xla_chunked")
    builder.refuse_unless_kernel_and_chunked(good, kernel)
    builder.refuse_unless_kernel_and_chunked(
        {**good, "kda_path": "pallas_chunked"}, kernel)
    for bad in [{**good, "flash_path": "xla"},
                {**good, "kda_path": "recurrent"},
                {"flash_path": "mla_multi_block"}, {}]:
        with pytest.raises(RuntimeError, match="this cell measures"):
            builder.refuse_unless_kernel_and_chunked(bad, kernel)
    traffic = mf.effective_traffic(
        mf.load_json(mf.traffic_path("b1-t16384")), True)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    notes = dict(attn_kind="kda_mla", flash_path="xla",
                 flash_layout="concatenated", kda_path="xla_chunked")
    monkeypatch.setattr(builder, "_joyai", lambda: types.SimpleNamespace(
        step_notes=lambda: notes))
    # a rehearsal is let through: it runs on the CPU by design
    assert callable(builder.build(cfg, traffic, mesh, 0, tiny=True)[
        "reference"])
    tiny = builder.model_config
    monkeypatch.setattr(builder, "model_config",
                        lambda cfg, _: tiny(cfg, True))
    built = builder.build(cfg, traffic, mesh, 0, tiny=False)
    with pytest.raises(RuntimeError, match="not the 'mla_multi_block'"):
        built["reference"]({"params": None, "batch": None})
    assert set(built["shapes"]) >= {"moe_cost_per_step",
                                    "kda_scan_cost_per_step"}


def test_reference_returns_the_keys_the_step_reports():
    """``loop.py`` holds every key the reference returns against the
    first dispatch's metric of that name: each has to be one; the
    parameters may wait on the host; and the low reading is another
    number (the rounder bites)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    builder = mf.load_builder("kimi_linear")
    ref = mf.load_reference("kimi_linear")
    mcfg, model, loss_fn = builder.program(_cfg(), tiny=True)
    params = builder.make_params(model, 0)
    toks = np.random.default_rng(0).integers(0, 256, (2, 64), dtype=np.int32)
    batch = {"tokens": jnp.asarray(toks),
             "targets": jnp.asarray(np.roll(toks, -1, 1))}
    spec = builder.reference_spec(mcfg)
    out = ref.loss_and_grad_norm(params, batch, spec)
    loss, report_ = loss_fn(params, batch)
    assert set(out) == {"loss", "grad_norm", "moe_absent_route_share",
                        "kda_out_rms"}
    assert all(v.ndim == 0 for v in report_.values())
    assert out["loss"] == pytest.approx(float(loss), rel=1e-5)
    assert out["moe_absent_route_share"] == pytest.approx(
        float(report_["moe_absent_route_share"]))
    assert out["kda_out_rms"] == pytest.approx(
        float(report_["kda_out_rms"]), rel=1e-4)
    groups = _cfg()["reference"]["grad_groups"]
    on_host = ref.loss_and_grad_norm(
        jax.device_get(params), batch,
        {**spec, "adamw": _cfg()["optimizer"], "grad_groups": groups})
    assert set(on_host) == set(out) | {"update_norm", *groups}
    assert on_host["grad_norm"] == pytest.approx(out["grad_norm"], rel=1e-6)
    assert 0 < on_host["update_norm"] < 1
    assert all(0 < on_host[name] < on_host["grad_norm"] for name in groups)
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
    assert set(got["plain_f32"]) == {
        "loss", "grad_norm", "moe_absent_route_share", "update_norm",
        "kda_out_rms", "grad_norm_kda_gates"}
    # the step's own first update against the reference's AdamW step
    assert got["program_from"] == "first dispatch"
    assert got["program"]["update_norm"] == pytest.approx(
        got["plain_f32"]["update_norm"], rel=1e-4)
    assert "kimi_linear routes by routed layer" in p.stderr
