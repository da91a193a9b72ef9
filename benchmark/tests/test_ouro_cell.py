"""What the Ouro-2.6B cell adds to the benchmark: ``flops_ouro.py`` against
counts by hand at the cell's shapes (applications counted, not
parameters), the two new readers and the ones the cell joins on a small
synthetic profile whose numbers are known (built with
``test_program_trace.py``'s helpers) and on runs with nothing to read, the
manifest's entries wherever they stand in their lists, the configuration
file against the catalog's keys, the limit against its readings, the
builder's refusals by the step's notes, and the rehearsal of the cell end
to end."""

import json
import os
import subprocess
import sys
import types

import pytest

import test_program_trace as tp
from benchlib import flops, flops_ouro as fo
from benchlib import manifest as mf, report

CELL = "ouro-2.6b.b1-t4096"
NEW = ["model.exit_gate_ms_per_step", "model.sandwich_norm_ms_per_step"]
JOINED = ["model.attention_ms_per_step", "model.mlp_ms_per_step",
          "kernel.attn_flash_ms_per_step", "attn_flash_roofline"]
PASSES = [f"lm_loss_ut_{t}" for t in (1, 2, 3, 4)]
KEYS = {"loss", "grad_norm", *PASSES, "exit_mean_step", "exit_entropy",
        "grad_norm_blocks", "grad_norm_head", "update_norm"}


def _cfg():
    return mf.find_cell(mf.load_manifest(), CELL)["config_file"]


def _mcfg(**kw):
    import dataclasses
    mcfg = mf.load_builder("ouro").model_config(_cfg(), tiny=False)
    return dataclasses.replace(mcfg, **kw)


# -- flops_ouro.py against counts by hand ----

def test_parameters_of_a_layer_and_of_the_cut():
    c = _mcfg()
    per = fo.layer_params(c)
    assert per == c.layer_params() == {
        "attn": 4 * 2048 * 2048, "mlp": 3 * 2048 * 5632, "norms": 4 * 2048}
    assert sum(per.values()) == 51388416
    assert fo.num_params(c) == c.num_params() == (
        8 * 51388416 + 2 * 49152 * 2048 + 2048 + 2049) == 612438017
    assert fo.num_params(c) * 14 == pytest.approx(8.57e9, rel=1e-3)
    # the tree does not grow with the passes; the published depth is 2.67 B
    assert fo.num_params(_mcfg(ut_steps=1)) == fo.num_params(c)
    assert fo.num_params(_mcfg(n_layer=48)) == pytest.approx(2.668e9,
                                                             rel=1e-3)


def test_required_operations_count_applications_not_parameters():
    c = _mcfg()
    assert fo.applications(c) == {"blocks": 32, "cores": 32, "heads": 4}
    per = fo.step_forward_flops_per_token(c)
    weights = 4 * 2048 * 2048 + 3 * 2048 * 5632         # a block's matmuls
    assert per["attn_proj"] + per["mlp"] == 32 * 2.0 * weights
    assert per["head"] == 4 * 2.0 * 2048 * 49152
    assert per["core"] == 32 * 2.0 * 16 * 128 * 4096
    assert per["gate"] == 4 * 2.0 * 2048
    total = sum(per.values())
    assert fo.train_flops_per_token(c) == 3.0 * total
    step = 4096 * fo.train_flops_per_token(c)
    assert step == pytest.approx(5.69e13, rel=2e-3)
    assert step / 197e12 == pytest.approx(0.289, rel=3e-3)
    share = {k: v / total for k, v in per.items()}
    # what the cell's `why` and the file's `cut.consequence` say
    assert share["attn_proj"] + share["mlp"] == pytest.approx(0.710, abs=2e-3)
    assert share["head"] == pytest.approx(0.174, abs=2e-3)
    assert share["core"] == pytest.approx(0.116, abs=2e-3)
    # 2.05 G matmul parameter-applications a token against 612 M
    # parameters: "6 x parameters x tokens" undercounts 3.3 times, 3.8
    # with the cores
    applied = (per["attn_proj"] + per["mlp"] + per["head"]) / 2.0
    assert applied == pytest.approx(2.05e9, rel=3e-3)
    assert applied / fo.num_params(c) == pytest.approx(3.3, abs=0.06)
    assert total / 2.0 / fo.num_params(c) == pytest.approx(3.8, abs=0.06)
    # deployed, 192 applications beside the same four passes
    deployed = fo.step_forward_flops_per_token(_mcfg(n_layer=48))
    assert deployed["head"] / sum(deployed.values()) == pytest.approx(
        0.034, abs=1e-3)
    # one pass of the same stack is a quarter of everything
    assert sum(fo.step_forward_flops_per_token(
        _mcfg(ut_steps=1)).values()) == pytest.approx(total / 4)


def test_the_cores_and_the_norms_cost():
    c = _mcfg()
    cost = fo.flash_cores_train_cost(c, 1)
    assert cost == flops.flash_attention_train_cost(1, 16, 4096, 128, 32)
    assert cost["flops"] == pytest.approx(0.66e13, rel=5e-3)
    assert cost["flops"] == 4096 * 3.0 * fo.step_forward_flops_per_token(
        c)["core"]
    norms = fo.norms_train_cost(c, 4096)
    # 4 x 32 + 4 = 132 norm passes forward a step, 16.8 MB in and out each
    assert norms["bytes"] == 132 * 5 * 4096 * 2048 * 2
    assert 4096 * 2048 * 2 == pytest.approx(16.8e6, rel=2e-3)
    assert flops.roofline(norms["flops"], norms["bytes"], 197e12,
                          819e9)["bound"] == "memory"


# -- the readers on a synthetic profile ----

L = "jit(step)/jit(main)/jvp(Ouro)/"
B = "jit(step)/jit(main)/transpose(jvp(Ouro))/"
LOOP = "blocks/while/body/"
R = LOOP + "checkpoint/rematted_computation/"
OP_NAMES = {
    "fusion.1": L + "embed/wte/gather",
    "fusion.2": L + LOOP + "checkpoint/h_0/attn_norm/mul",
    "fusion.3": L + LOOP + "checkpoint/h_0/attn/qkv/q/dot_general",
    "flash.4": L + LOOP + "checkpoint/h_0/attn/core/jit(_flash_fwd)/"
                          "pallas_call",
    "fusion.5": L + LOOP + "checkpoint/h_0/attn_post_norm/mul",
    "fusion.6": L + LOOP + "checkpoint/h_0/mlp_norm/mul",
    "fusion.7": L + LOOP + "checkpoint/h_0/mlp/gate/dot_general",
    "fusion.8": L + LOOP + "checkpoint/h_0/mlp_post_norm/mul",
    "fusion.9": L + LOOP + "norm_f/mul",
    "fusion.10": L + LOOP + "exit_gate/reduce_sum",
    "fusion.11": B + R + "h_1/mlp_post_norm/mul",
    "fusion.12": B + LOOP + "checkpoint/h_1/attn_post_norm/mul",
    "flash.13": B + LOOP + "checkpoint/h_1/attn/core/jit(_flash_bwd)/"
                           "pallas_call",
    "fusion.14": B + LOOP + "exit_gate/mul",
    "fusion.15": L + "loss/exit/exp",
    "fusion.16": B + "loss/exit/mul",
    "fusion.17": L + "loss/loss/while/body/dot_general",
    "fusion.18": "jit(step)/optimizer/mul",
}
US = [2, 3, 8, 40, 5, 4, 12, 6, 7, 1, 9, 11, 60, 2, 3, 4, 50, 100]


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
        "kernel_cost_per_step": {"flops": 197e12 * 10e-6, "bytes": 1.0}}
    return report.Run(mf.find_cell(man, CELL), facts, {}, {},
                      {"steps": 2} if traced else None)


def test_every_reader_of_the_cell_reads(tmp_path):
    """A path is matched by its parts: the loop's ``while/body`` and a
    recomputed block's ``checkpoint/rematted_computation`` stand between
    ``blocks`` and the module."""
    run = _run(tmp_path, _xspace())
    got = {name: mf.load_reader(name)(run) for name in NEW + JOINED}
    assert got == {
        # exit_gate 1 + 2 under blocks, loss/exit 3 + 4
        "model.exit_gate_ms_per_step": pytest.approx(0.010 / 2),
        # attn_norm 3, attn_post_norm 5 + 11, mlp_norm 4, mlp_post_norm
        # 6 + 9, norm_f 7
        "model.sandwich_norm_ms_per_step": pytest.approx(0.045 / 2),
        "model.attention_ms_per_step": pytest.approx(0.108 / 2),
        "model.mlp_ms_per_step": pytest.approx(0.012 / 2),
        "kernel.attn_flash_ms_per_step": pytest.approx(0.100 / 2),
        "attn_flash_roofline": pytest.approx(20.0),     # 10 us over 50
    }


def test_new_readers_are_none_with_nothing_to_read(tmp_path, monkeypatch):
    """No trace; a two-norm stack with no gate (any other model's, or the
    parent's program asked for another cell); no ``train.fit`` span. A
    reader returns None and does not raise."""
    run = _run(tmp_path / "a", _xspace(), traced=False)
    assert [mf.load_reader(n)(run) for n in NEW] == [None] * 2
    other = {k: v.replace("_post_norm/", "_proj/").replace(
        "/exit_gate/", "/ln/").replace("loss/exit/", "loss/")
        for k, v in OP_NAMES.items()}
    run = _run(tmp_path / "b", _xspace(other))
    assert [mf.load_reader(n)(run) for n in NEW] == [None] * 2
    from ray_tpu.util import tracing
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    run = _run(tmp_path / "c", _xspace())
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    assert [mf.load_reader(n)(run) for n in NEW] == [None] * 2


# -- the manifest and the configuration file ----

def test_the_manifest_lists_the_configuration_the_cell_and_the_metrics():
    """That the entries are present, wherever they stand in their lists
    (a later PR appends behind them, and may list its cell beside this
    one)."""
    man = mf.load_manifest()
    assert mf.check_manifest(man) == []
    config = next(c for c in man["configs"] if c["name"] == "ouro-2.6b")
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["file"] == "benchmark/configs/ouro-2.6b.json"
    assert config["source"] == _cfg()["source"]
    assert len(config["why"]) <= 200
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b", "b1-t4096", 1)
    assert "32 block applications" in cell["why"] and len(cell["why"]) <= 200
    per_layer = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        assert CELL in per_layer[name]["workloads"]
        assert (per_layer[name]["unit"], per_layer[name]["layer"],
                per_layer[name]["moves"], per_layer[name]["source"]) == (
            "ms", "model", "step_ms_p90", "device_trace")
    for name in JOINED:
        assert CELL in per_layer[name]["workloads"]
    # no routed layer, no window: the cell joins none of those lists
    for name in ("model.moe_route_ms_per_step", "moe_experts_roofline",
                 "model.attn_window_ms_per_step"):
        assert CELL not in per_layer[name]["workloads"]
    e2e = [m["name"] for m in mf.metrics_of(man, "end_to_end", CELL)]
    assert e2e == ["tokens_per_s_per_chip", "step_ms_p90", "setup_s"]


def test_the_configuration_runs_every_published_width():
    cfg = _cfg()
    pub, model = cfg["published"], cfg["model"]
    changed = {k for k in pub if cfg[k] != pub[k]}
    assert changed == {"num_hidden_layers"}
    assert (pub["num_hidden_layers"], cfg["num_hidden_layers"]) == (48, 8)
    assert len(cfg["reduced"]) == 1
    assert cfg["reduced"][0].startswith("num_hidden_layers 48 -> 8")
    assert (model["n_embd"], model["n_head"], model["n_kv_head"],
            model["head_dim"], model["intermediate"], model["vocab_size"],
            model["ut_steps"], model["rope_theta"], model["rms_eps"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"],
        pub["intermediate_size"], pub["vocab_size"], pub["total_ut_steps"],
        pub["rope_theta"], pub["rms_norm_eps"]) == (
        2048, 16, 16, 128, 5632, 49152, 4, 1000000, 1e-6)
    assert model["n_layer"] == 8 and model["remat"] is True
    assert model["seq_len"] == 4096 and model["exit_beta"] == 0.05
    assert cfg["loss"]["uniform_over"] == model["vocab_size"]   # whole
    assert cfg["layer_types"] == pub["layer_types"]     # copied whole
    # what report.py reads of a configuration outside a rehearsal
    assert cfg["sample_unit"] == "tokens" and cfg["ce_chunk"] == 2048
    assert "six-stage pipeline" in cfg["cut"]["deployment"]
    assert "11.46 GB" in cfg["cut"]["memory"]        # as compiled
    assert "17.4%" in cfg["cut"]["consequence"]
    assert "3.4% deployed" in cfg["cut"]["consequence"]
    assert {"described_from_memory", "sandwich_norm", "final_norm_every_pass",
            "no_bias", "no_pass_embedding", "exit_beta", "stage",
            "optimizer", "weights", "sequence", "tokens", "ce_chunk",
            "unused_keys", "remat"} <= set(cfg["assumed"])
    for key in ("sandwich_norm", "final_norm_every_pass"):
        assert "other reading" in cfg["assumed"][key], key
    assert set(cfg["layers"]) == {"stack", "block", "exit", "loss"}
    assert cfg["optimizer"]["learning_rate"] == 3e-4
    assert cfg["kernel"] == {"tpu_custom_call": True,
                             "flash_path": "multi_block", "ut_path": "scan"}
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    catalog = next(r for r in rows if r["source_url"] == cfg["source"])
    assert pub == catalog["config"]
    builder = mf.load_builder(cfg["builder"])
    mcfg = builder.model_config(cfg, tiny=False)
    assert mcfg.num_params() == 612438017
    with pytest.raises(ValueError, match="not the configuration file's"):
        builder.model_config(
            {**cfg, "model": {**model, "intermediate": 4096}}, tiny=False)
    with pytest.raises(ValueError, match="not the configuration file's"):
        builder.model_config(
            {**cfg, "model": {**model, "ut_steps": 2}}, tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config({**cfg, "total_ut_steps": 2}, tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config({**cfg, "tie_word_embeddings": True},
                             tiny=False)


def test_the_limit_lies_between_the_programs_readings_and_the_float8s():
    """``reference.rtol`` against the readings the file records (the
    cell's own runs and ``tools/ouro_limit.py`` took them on the v5e)."""
    ref = _cfg()["reference"]
    rtol, got = ref["rtol"], ref["readings"]
    assert set(got["program_largest"]) == KEYS
    assert all(0 <= v < rtol for v in got["program_largest"].values())
    assert got["seeds"] >= 8
    low = got["float8"]
    assert low["fails"] is True and low["smallest"] > rtol
    assert low["by"] in got["program_largest"] and low["seeds"] >= 3
    assert got["unchanged_state_update_norm"] == 1.0 > rtol
    assert "float8_e4m3fn" in ref["rtol_why"]
    # the limit lies between its two readings, with room on both sides
    assert max(got["program_largest"].values()) * 1.25 < rtol
    assert rtol * 1.25 < low["smallest"]
    # the gate's own gradient norm: reported, not compared, and why
    assert set(ref["grad_groups"]) == {"grad_norm_blocks", "grad_norm_head"}
    assert set(ref["reported_grad_groups"]) == {"grad_norm_exit_gate"}
    assert "grad_norm_exit_gate" in ref["rtol_why"]


def test_the_builder_refuses_a_step_that_is_not_the_files(monkeypatch):
    import jax
    from ray_tpu.parallel import make_mesh
    builder = mf.load_builder("ouro")
    cfg = _cfg()
    good = dict(flash_path="multi_block", attn_kind="looped_full",
                ut_steps=4, ut_path="scan", ce_rows=16384)
    builder.refuse_unless_as_the_file_says(good, cfg)
    for bad in [{**good, "flash_path": "xla"}, {**good, "ut_steps": 3},
                {**good, "ut_path": "unrolled"}, {**good, "ce_rows": 4096},
                {k: v for k, v in good.items() if k != "attn_kind"}, {}]:
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
    assert built["shapes"]["applications"] == {"blocks": 8, "cores": 8,
                                               "heads": 4}
    assert built["shapes"]["norm_cost_per_step"]["bytes"] > 0
    assert built["kernel_cost_per_step"]["flops"] > 0


def test_reference_returns_the_keys_the_step_reports():
    """``loop.py`` holds every key the reference returns against the
    first dispatch's metric of that name: each has to be one; the
    parameters may wait on the host; asked for the reported group the
    reference gives it (the tool asks; the cell does not); and the low
    reading is another number (the rounder bites)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    builder = mf.load_builder("ouro")
    ref = mf.load_reference("ouro")
    cfg = _cfg()
    mcfg, model, loss_fn = builder.program(cfg, tiny=True)
    params = builder.make_params(model, 0)
    toks = np.random.default_rng(0).integers(0, 256, (2, 64), dtype=np.int32)
    batch = {"tokens": jnp.asarray(toks),
             "targets": jnp.asarray(np.roll(toks, -1, 1))}
    held = cfg["reference"]["grad_groups"]
    spec = {**builder.reference_spec(mcfg), "grad_groups": held}
    out = ref.loss_and_grad_norm(params, batch, spec)
    loss, report_ = jax.jit(loss_fn)(params, batch)
    assert set(out) == KEYS - {"update_norm"}
    assert set(report_) == {*PASSES, "exit_mean_step", "exit_entropy"}
    assert all(v.ndim == 0 for v in report_.values())
    assert out["loss"] == pytest.approx(float(loss), rel=1e-5)
    for k in report_:
        assert out[k] == pytest.approx(float(report_[k]), rel=1e-5)
    on_host = ref.loss_and_grad_norm(
        jax.device_get(params), batch,
        {**spec, "adamw": cfg["optimizer"], "grad_groups": {
            **held, **cfg["reference"]["reported_grad_groups"]}})
    assert set(on_host) == KEYS | {"grad_norm_exit_gate"}
    assert on_host["grad_norm"] == pytest.approx(out["grad_norm"], rel=1e-6)
    assert 0 < on_host["grad_norm_exit_gate"] < 0.2 * on_host["grad_norm"]
    assert 0 < on_host["update_norm"] < 1
    low = ref.loss_and_grad_norm(
        params, batch, {**spec, "operand_dtype": "float8_e4m3fn"})
    assert low["grad_norm"] != out["grad_norm"]
    assert low["grad_norm"] == pytest.approx(out["grad_norm"], rel=0.2)


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
    for key in ("update_norm", "exit_mean_step", "grad_norm_blocks"):
        assert got["program"][key] == pytest.approx(
            got["plain_f32"][key], rel=1e-4)
    assert "ouro reference done" in p.stderr
