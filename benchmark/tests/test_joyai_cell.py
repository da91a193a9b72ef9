"""What the JoyAI-LLM-Flash cell adds to the benchmark: ``flops_mla.py``
against counts by hand at the cell's shapes, ``path_trace.py`` and the two
new readers on a small synthetic profile whose numbers are known (built
with ``test_program_trace.py``'s helpers) and on runs with nothing to
read, the manifest's entries, the configuration file against the
catalog's keys, the builder's refusal by the ``flash_path`` note, and the
rehearsal of the cell end to end."""

import json
import os
import subprocess
import sys
import types

import pytest

import test_program_trace as tp
from benchlib import flops, flops_mla as fm, manifest as mf, report
from benchlib import path_trace

CELL = "joyai-llm-flash.b1-t8192"
NEW = ["model.mla_proj_ms_per_step", "model.mtp_ms_per_step"]
JOINED = ["model.attention_ms_per_step", "model.mlp_ms_per_step",
          "model.moe_route_ms_per_step", "model.moe_experts_ms_per_step",
          "moe_experts_roofline", "model.moe_shared_ms_per_step",
          "moe.held_route_share", "kernel.attn_flash_ms_per_step",
          "attn_flash_roofline"]


def _cfg():
    return mf.find_cell(mf.load_manifest(), CELL)["config_file"]


def _mcfg(**kw):
    import dataclasses
    mcfg = mf.load_builder("joyai").model_config(_cfg(), tiny=False)
    return dataclasses.replace(mcfg, **kw)


# -- flops_mla.py against counts by hand --------------------------------------

def test_parameters_of_each_part_and_of_the_cut():
    cut, whole = _mcfg(), _mcfg(experts_held=None)
    per = fm.layer_params(cut)
    assert per == cut.layer_params()
    # W_qa 3.146 + W_qb 9.437 + W_kva 1.180 + W_kvb 4.194 + W_o 8.389 M
    assert fm.mla_matmul_weights(cut) == (
        2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
        + 4096 * 2048)
    assert per["mla"] == pytest.approx(26.35e6, rel=1e-3)
    assert per["dense"] == pytest.approx((26.35 + 44.04) * 1e6, rel=1e-3)
    # the router 0.52 M, the shared expert 4.72 M, 16 held x 4.72 M
    assert per["routed"] == pytest.approx(
        (26.35 + 0.52 + 4.72 + 16 * 4.72) * 1e6, rel=1e-3)
    assert per["mtp"] - per["routed"] == 2 * 2048 * 2048 + 3 * 2048
    assert fm.layer_params(whole)["routed"] == pytest.approx(1240e6,
                                                             rel=1e-3)
    assert fm.num_params(cut) == cut.num_params()
    assert fm.num_params(cut) == pytest.approx(681.4e6, rel=1e-4)
    assert fm.num_params(cut) * 14 == pytest.approx(9.54e9, rel=1e-3)
    published = _mcfg(experts_held=None, vocab_size=129280, n_layer=40)
    assert fm.num_params(published) == pytest.approx(50.2e9, rel=2e-3)


def test_required_operations_per_token_and_per_step():
    c = _mcfg()
    per = fm.forward_flops_per_token(c)
    assert per["mla_proj"] == pytest.approx(2 * 26.345e6, rel=1e-4)
    assert per["attn_core"] == 8192 * 32 * (192 + 128)      # half the square
    assert per["dense_mlp"] == 2 * 3 * 2048 * 7168
    assert per["shared"] == 2 * 3 * 2048 * 768
    assert per["held_experts"] == 0.5 * per["shared"]   # 8 x 16 / 256 routes
    assert per["head"] == 2 * 2048 * 16384
    assert per["mtp_proj"] == 2 * 4096 * 2048
    step = {k: 3 * 8192 * v
            for k, v in fm.step_forward_flops_per_token(c).items()}
    total = fm.train_flops_per_token(c) * 8192
    assert total == pytest.approx(sum(step.values()))
    # the issue's arithmetic: 2.78e13 a step, 141 ms at the peak
    assert total == pytest.approx(2.78e13, rel=5e-3)
    assert total / 197e12 == pytest.approx(0.141, rel=5e-3)
    assert step["attn_core"] == pytest.approx(1.24e13, rel=5e-3)
    assert step["mla_proj"] == pytest.approx(0.78e13, rel=5e-3)
    assert step["head"] == pytest.approx(0.33e13, rel=5e-3)
    assert step["dense_mlp"] == pytest.approx(0.22e13, rel=2e-2)
    assert step["shared"] == pytest.approx(0.12e13, rel=4e-2)
    assert step["held_experts"] == pytest.approx(0.06e13, rel=4e-2)
    assert step["mtp_proj"] == pytest.approx(0.04e13, rel=4e-2)
    assert (step["attn_core"] + step["mla_proj"]) / total == pytest.approx(
        0.73, abs=0.01)


def test_attention_and_held_experts_costs_and_their_rooflines():
    c = _mcfg()
    attn = fm.latent_attention_train_cost(c, 1)
    # a layer: 32 heads x T^2 x 960 = 2.06e12, 1.25 times a 128-wide one
    assert attn["flops"] / 6 == 32 * 8192 ** 2 * 960
    assert attn["flops"] / 6 == pytest.approx(2.06e12, rel=2e-3)
    like_128 = flops.flash_attention_train_cost(1, 32, 8192, 128, 6)
    assert attn["flops"] / like_128["flops"] == 1.25
    wide, rope, key, rows = (8192 * 4096 * 2, 8192 * 2048 * 2, 8192 * 64 * 2,
                             32 * 8192 * 4)
    assert attn["bytes"] == 6 * (11 * wide + 3 * rope + 3 * key + 3 * rows)
    # the rotary key once, not 32 times: repeated it would be one more
    # head-wide array of 64 lanes in each of its three places
    assert 6 * 3 * key * 32 == 6 * 3 * rope
    roof = flops.roofline(attn["flops"], attn["bytes"], 197e12, 819e9)
    assert roof["bound"] == "compute"
    assert roof["least_s"] == pytest.approx(62.8e-3, rel=2e-3)
    held = fm.held_experts_train_cost(c, 8192)
    rows = 8192 * 8 * 16 / 256                      # 4,096 at an even load
    assert held["flops"] == 5 * 6 * rows * 3 * 2048 * 768
    assert held["bytes"] == 5 * 9 * 2 * (rows * (2048 + 768)
                                         + 16 * 2048 * 768)
    roof = flops.roofline(held["flops"], held["bytes"], 197e12, 819e9)
    assert roof["bound"] == "memory"
    assert roof["least_s"] == pytest.approx(4.03e-3, rel=0.01)


# -- path_trace.py and the readers on a synthetic profile ----------------------

L = "jit(step)/jvp(JoyAI)/"
T = "jit(step)/transpose(jvp(JoyAI))/"
OP_NAMES = {
    "fusion.1": L + "blocks/h_1/attn/q_down/proj/dot_general",
    "fusion.2": L + "blocks/h_1/attn/q_down/norm/mul",
    "fusion.3": T + "blocks/h_1/attn/jvp(q_up)/dot_general",    # recomputed
    "fusion.4": T + "blocks/h_1/attn/transpose(jvp(kv_up))/dot_general",
    "fusion.5": T + "blocks/h_1/attn/jvp(rope)/mul",
    "mla.6": L + "blocks/h_1/attn/core/jit(mla_flash_fwd)/pallas_call",
    "fusion.7": L + "blocks/h_1/attn/out_proj/dot_general",
    "fusion.8": L + "blocks/mtp/proj/eh_proj/dot_general",
    "mla.9": T + "blocks/mtp/h/attn/core/jit(mla_flash_bwd)/pallas_call",
    "fusion.10": L + "blocks/mtp/h/attn/kv_down/proj/dot_general",
    "fusion.11": L + "blocks/mtp/h/mlp/shared/up/dot_general",
    "gmm.12": L + "blocks/mtp/h/mlp/experts/jit(gmm)/pallas_call",
    "fusion.13": L + "loss/mtp/mtp_norm/mul",
    "fusion.14": "jit(step)/jvp(loss)/mtp/loss/loss/while/body/dot_general",
    "fusion.15": "jit(step)/jvp(loss)/loss/while/body/dot_general",
    "fusion.16": L + "blocks/h_0/mlp/up/dot_general",
    "fusion.17": "jit(step)/optimizer/mul",
}
DEVICE_NAMES = {
    n: (f"%{name} = bf16[8]{{0}} custom-call(bf16[8]{{0}} %x)"
        if name.split(".")[0] in ("gmm", "mla") else
        f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, "
        f"calls=%f{n}")
    for n, name in enumerate(OP_NAMES, start=1)}
US = [30, 4, 20, 10, 6, 100, 16, 12, 60, 8, 14, 10, 2, 40, 44, 50, 100]


def _device(n: int) -> str:
    names = {**DEVICE_NAMES, 30: "jit_step(1)"}
    at, events = 1000, []
    for i, us in enumerate(US, start=1):
        events.append(tp._event(i, at, us))
        at += us
    return tp._plane(f"/device:TPU:{n}", names, [
        tp._line("XLA Modules", [tp._event(30, 900, 1100)]),
        tp._line("XLA Ops", events)])


def _xspace(op_names=None) -> bytes:
    from jax.profiler import ProfileData
    octal = "".join(f"\\{b:03o}" for b in tp._hlo_proto(
        op_names or OP_NAMES, {}))
    meta = ('planes { name: "/host:metadata" '
            'stat_metadata { key: 1 value { id: 1 name: "Hlo Proto" } } '
            'event_metadata { key: 1 value { id: 1 name: "jit_step(1)" '
            f'stats {{ metadata_id: 1 bytes_value: "{octal}" }} }} }} }}')
    return ProfileData.text_proto_to_serialized_xspace(
        tp.HOST + _device(0) + _device(1) + meta)


def test_self_time_by_scope_path_and_under_the_mtp_modules_two():
    from jax.profiler import ProfileData
    raw = _xspace()
    got = path_trace.reduce_profile(
        ProfileData.from_serialized_xspace(raw),
        path_trace.program_trace.op_names(raw), steps=2)
    assert got["devices"] == 2 and got["steps"] == 2
    us = {k: round(v * 1e6, 6) for k, v in got["under_s"].items()}
    assert us["blocks/h_1/attn/q_down/proj"] == 30
    assert us["blocks/mtp/h/attn/core/jit(mla_flash_bwd)"] == 60
    assert us["loss/mtp/loss/loss/while/body"] == 40
    assert us["optimizer"] == 100 and sum(us.values()) == sum(US)

    def under(*prefixes):
        return pytest.approx(sum(path_trace.under(got, prefixes)) * 1e6)
    # proj 12 + its block 60 + 8 + 14 + 10; its norm 2 + its loss 40
    assert under("blocks/mtp") == 104 and under("loss/mtp") == 42
    assert under("blocks/mtp", "loss/mtp") == 146
    assert under("blocks/h_1/attn") == 186 and under("blocks/h") == 0


def _run(tmp_path, raw: bytes, traced=True):
    man = mf.load_manifest()
    facts = {
        **tp._fit_in_ring(tmp_path, raw), "kind": "TPU v5 lite",
        "kernel_cost_per_step": {"flops": 197e12 * 20e-6, "bytes": 1.0},
        "shapes": {"moe_cost_per_step": {"flops": 1.0,
                                         "bytes": 819e9 * 1e-6}},
        "reference": {"program": {"moe_absent_route_share": 0.9375}}}
    return report.Run(mf.find_cell(man, CELL), facts, {}, {},
                      {"steps": 2} if traced else None)


def test_every_reader_of_the_cell_reads(tmp_path):
    run = _run(tmp_path, _xspace())
    got = {name: mf.load_reader(name)(run) for name in NEW + JOINED}
    assert got == {
        # q_down 34, q_up 20, kv_up 10, rope 6 in h_1; kv_down 8 in mtp/h
        "model.mla_proj_ms_per_step": pytest.approx(0.078 / 2),
        "model.mtp_ms_per_step": pytest.approx(0.146 / 2),
        # h_1's 186 and the MTP block's 68: its ``attn`` counts with them
        "model.attention_ms_per_step": pytest.approx(0.254 / 2),
        "model.mlp_ms_per_step": pytest.approx(0.074 / 2),
        "model.moe_route_ms_per_step": pytest.approx(0.0),
        "model.moe_experts_ms_per_step": pytest.approx(0.010 / 2),
        "moe_experts_roofline": pytest.approx(20.0),    # 1 us over 5
        "model.moe_shared_ms_per_step": pytest.approx(0.014 / 2),
        "moe.held_route_share": pytest.approx(6.25),
        "kernel.attn_flash_ms_per_step": pytest.approx(0.160 / 2),
        "attn_flash_roofline": pytest.approx(25.0),     # 20 us over 80
    }


def test_new_readers_are_none_with_nothing_to_read(tmp_path, monkeypatch):
    """No trace; a step with no latent attention and no MTP module (any
    other cell's, or the parent's program); no ``train.fit`` span."""
    run = _run(tmp_path / "a", _xspace(), traced=False)
    assert [mf.load_reader(n)(run) for n in NEW] == [None, None]
    other = {
        k: v.replace("/mtp/", "/h_2/").replace("q_down", "q").replace(
            "q_up", "q").replace("kv_up", "k").replace("kv_down", "k").replace(
                "rope", "rotate")
        for k, v in OP_NAMES.items()}
    run = _run(tmp_path / "b", _xspace(other))
    assert [mf.load_reader(n)(run) for n in NEW] == [None, None]
    from ray_tpu.util import tracing
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    run = _run(tmp_path / "c", _xspace())
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    assert [mf.load_reader(n)(run) for n in NEW] == [None, None]


# -- the manifest and the configuration file ----------------------------------

def test_the_manifest_lists_the_configuration_the_cell_and_the_metrics():
    man = mf.load_manifest()
    assert mf.check_manifest(man) == []
    cell = {w["name"]: w for w in man["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "joyai-llm-flash", "b1-t8192", 1)
    per_layer = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert (per_layer[name]["layer"], per_layer[name]["moves"],
                per_layer[name]["source"]) == ("model", "step_ms_p90",
                                               "device_trace")
    got = {m["name"] for m in mf.metrics_of(man, "per_layer", CELL)}
    assert {*NEW, *JOINED} <= got
    assert not {"kernel.flash_ms_per_step", "flash_attention_roofline",
                "collective.ms_per_step", "moe.load_max_over_mean",
                "model.mamba_ms_per_step", "ssm_scan_roofline"} & got
    assert [m["name"] for m in mf.metrics_of(man, "end_to_end", CELL)] == [
        "tokens_per_s_per_chip", "step_ms_p90", "setup_s"]
    entry = {c["name"]: c for c in man["configs"]}["joyai-llm-flash"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["file"] == "benchmark/configs/joyai-llm-flash.json"


def test_the_configuration_runs_every_published_width():
    cfg = _cfg()
    pub, model = cfg["published"], cfg["model"]
    changed = {k: cfg[k] for k in pub if cfg[k] != pub[k]}
    assert changed == {"num_hidden_layers": 5, "n_routed_experts": 16,
                       "vocab_size": 16384}
    assert len(cfg["reduced"]) == 3
    assert sorted(r.split()[0] for r in cfg["reduced"]) == sorted(changed)
    assert (model["n_embd"], model["n_head"], model["q_rank"],
            model["kv_rank"], model["nope_dim"], model["rope_dim"],
            model["v_dim"], model["rope_theta"], model["rms_eps"],
            model["dense_layers"], model["dense_width"],
            model["num_experts"], model["top_k"], model["expert_width"],
            model["shared_width"], model["norm_topk_prob"],
            model["route_scale"], model["mtp_depth"]) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["q_lora_rank"],
        pub["kv_lora_rank"], pub["qk_nope_head_dim"],
        pub["qk_rope_head_dim"], pub["v_head_dim"], pub["rope_theta"],
        pub["rms_norm_eps"], pub["first_k_dense_replace"],
        pub["intermediate_size"], pub["n_routed_experts"],
        pub["num_experts_per_tok"], pub["moe_intermediate_size"],
        pub["n_shared_experts"] * pub["moe_intermediate_size"],
        pub["norm_topk_prob"], pub["routed_scaling_factor"],
        pub["num_nextn_predict_layers"])
    assert pub["qk_head_dim"] == model["nope_dim"] + model["rope_dim"]
    assert model["experts_held"] == [0, cfg["n_routed_experts"]]
    assert model["n_layer"] == cfg["num_hidden_layers"]
    assert model["vocab_size"] == cfg["vocab_size"] \
        == cfg["loss"]["uniform_over"]
    assert model["vocab_size"] % 128 == 0
    assert model["vocab_size"] >= pub["vocab_size"] / 8
    assert "16 chips" in cfg["cut"]["deployment"]
    assert "1/16" in cfg["cut"]["load"]
    assert {"mtp_concatenation", "mtp_stream", "mtp_weight", "mtp_mean",
            "score_correction_bias"} <= set(cfg["assumed"])
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    catalog = next(r for r in rows if r["source_url"] == cfg["source"])
    assert pub == catalog["config"]
    builder = mf.load_builder(cfg["builder"])
    with pytest.raises(ValueError, match="not the configuration file's"):
        builder.model_config(
            {**cfg, "model": {**model, "expert_width": 384}}, tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config({**cfg, "kv_lora_rank": 256}, tiny=False)


def test_the_limit_lies_between_its_two_readings():
    """``reference.rtol`` against the readings the file records
    (``tools/limit.py`` and the cell's own runs took them on the chip):
    every number of the program under it on every seed, the float8
    reading over it by at least one number on every seed, with the room
    ``rtol_why`` states on each side; the update's distance, whose other
    reading is 1 (a state left unchanged), far under it."""
    ref = _cfg()["reference"]
    got = ref["readings"]
    assert ref["module"] == "joyai"
    assert set(got["program_largest"]) == {
        "loss", "lm_loss", "mtp_loss", "grad_norm",
        "moe_absent_route_share", "update_norm"}
    nearest = max(got["program_largest"].values())
    assert nearest == got["program_largest"]["grad_norm"]
    assert nearest * 1.5 < ref["rtol"]
    assert got["grad_norm_mean"] + 3 * got["grad_norm_sd"] < ref["rtol"]
    assert ref["rtol"] * 1.25 < got["float8_smallest_failing"]
    assert got["program_largest"]["update_norm"] * 10 < ref["rtol"] < 1
    assert got["seeds"] >= 13 and got["float8_seeds"] >= 6
    assert "float8_e4m3fn" in ref["rtol_why"]


# -- the optimizer's first step -------------------------------------------------

def _tiny_step(learning_rate=None, **adamw):
    """(step, state, batch, kept, the reference's spec) at the tiny
    preset, the optimizer the configuration's but for ``adamw``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from ray_tpu import train
    builder = mf.load_builder("joyai")
    cfg = _cfg()
    mcfg, model, loss_fn = builder.program(cfg, tiny=True)
    o = {**cfg["optimizer"], **adamw}
    if learning_rate is not None:
        o["learning_rate"] = learning_rate
    opt = optax.chain(
        optax.clip_by_global_norm(o["clip_global_norm"]),
        optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                    weight_decay=o["weight_decay"],
                    mu_dtype=jnp.dtype(o["mu_dtype"])))
    state = train.init_train_state(builder.make_params(model, 7), opt, None)
    toks = np.random.default_rng(7).integers(0, 256, (2, 64), dtype=np.int32)
    batch = {"tokens": jnp.asarray(toks),
             "targets": jnp.asarray(np.roll(toks, -1, 1))}
    kept = {"params": jax.tree_util.tree_map(jnp.copy, state.params)}
    step = builder.with_first_change(train.make_train_step(loss_fn, opt),
                                     kept)
    spec = {**builder.reference_spec(mcfg), "adamw": cfg["optimizer"]}
    return step, state, batch, kept, spec


def test_the_first_dispatch_reports_what_it_changed_and_no_other_does():
    import jax
    import optax
    from ray_tpu import train
    step, state, batch, kept, spec = _tiny_step()
    assert train.compile_count(step) == 0 and callable(step.lower)
    state, first = step(state, batch)
    assert float(first["update_norm"]) == pytest.approx(float(
        optax.global_norm(jax.tree_util.tree_map(
            lambda a, b: b - a, kept["params"], state.params))), rel=1e-6)
    assert float(first["update_norm"]) > 0
    state, second = step(state, batch)
    assert "update_norm" not in second and set(second) < set(first)
    assert train.compile_count(step) >= 1
    want = mf.load_reference("joyai").loss_and_grad_norm(
        kept["params"], batch, spec)
    rtol = _cfg()["reference"]["rtol"]
    for key, value in want.items():
        assert float(first[key]) == pytest.approx(value, rel=rtol / 8), key


@pytest.mark.parametrize("fault", [
    {"learning_rate": 0.0}, {"learning_rate": 2.2e-5}, {"eps": 1e-3},
    {"clip_global_norm": 1e-6, "eps": 1e-7}, "sgd", "unchanged"],
    ids=["rate_0", "rate_a_tenth_off", "eps", "clip", "sgd", "unchanged"])
def test_an_update_that_is_not_the_configurations_fails_the_limit(fault):
    """What ``declines`` would have seen and this cell's rate hides from
    it: each of these passes every other number of the comparison (they
    are taken before the update) and is ``update_norm`` alone off by
    more than ``rtol``."""
    import jax.numpy as jnp
    import optax
    from ray_tpu import train
    if isinstance(fault, dict):
        step, state, batch, kept, spec = _tiny_step(**fault)
        _, first = step(state, batch)
        got = float(first["update_norm"])
    else:
        step, state, batch, kept, spec = _tiny_step()
        builder = mf.load_builder("joyai")
        _, _, loss_fn = builder.program(_cfg(), tiny=True)
        opt = (optax.sgd(_cfg()["optimizer"]["learning_rate"])
               if fault == "sgd" else optax.set_to_zero())
        kept = {"params": kept["params"]}
        state = train.init_train_state(kept["params"], opt, None)
        step = builder.with_first_change(
            train.make_train_step(loss_fn, opt, donate=False), kept)
        got = float(step(state, batch)[1]["update_norm"])
    want = mf.load_reference("joyai").loss_and_grad_norm(
        kept["params"], batch, spec)["update_norm"]
    assert abs(got - want) > 8 * _cfg()["reference"]["rtol"] * want


# -- the builder's refusal by the note -----------------------------------------

def test_the_builder_refuses_a_step_whose_attention_was_not_the_kernel(
        monkeypatch):
    """``checks.py`` counts custom calls, and the experts' grouped
    matmuls are custom calls too: the builder reads the step's
    ``flash_path`` note from the fit's ``train.compile`` span."""
    import jax
    from ray_tpu.parallel import make_mesh
    from ray_tpu.train import session
    builder = mf.load_builder("joyai")
    cfg = _cfg()
    traffic = mf.effective_traffic(mf.load_json(mf.traffic_path("b1-t8192")),
                                   True)

    def span(name, **attributes):
        return types.SimpleNamespace(name=name, attributes=attributes)
    spans = [span("train.compile", kind="trace", fun_name="init"),
             span("train.compile", kind="trace", attn_kind="mla",
                  flash_path="xla", flash_layout="concatenated"),
             span("train.compile", kind="lower")]
    monkeypatch.setattr(session, "get_session",
                        lambda: types.SimpleNamespace(spans=spans))
    assert builder.step_notes()["flash_path"] == "xla"
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    # a rehearsal is let through: it runs on the CPU by design
    built = builder.build(cfg, traffic, mesh, 0, tiny=True)
    assert callable(built["reference"])
    want = cfg["kernel"]["flash_path"]
    assert want == "mla_multi_block"

    # at the cell's size the reference is not reached: refused first
    real = mf.load_builder("joyai")
    monkeypatch.setattr(real, "step_notes", lambda: spans[1].attributes)
    monkeypatch.setattr(real, "model_config",
                        lambda cfg, tiny: builder.model_config(cfg, True))
    built = real.build(cfg, traffic, mesh, 0, tiny=False)
    with pytest.raises(RuntimeError, match="not the 'mla_multi_block'"):
        built["reference"]({"params": None, "batch": None})


# -- the plain reference and the rehearsal -------------------------------------

def test_reference_returns_the_keys_the_step_reports():
    """``loop.py`` holds every key the reference returns against the
    first dispatch's metric of that name: each has to be one."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.joyai import JoyAI, JoyAIConfig, joyai_loss_fn
    ref = mf.load_reference("joyai")
    builder = mf.load_builder("joyai")
    cfg = JoyAIConfig.tiny(dtype=jnp.float32)
    model = JoyAI(cfg)
    params = jax.jit(model.init_params)(jax.random.key(0))
    toks = np.random.default_rng(0).integers(0, 256, (2, 64), dtype=np.int32)
    batch = {"tokens": jnp.asarray(toks),
             "targets": jnp.asarray(np.roll(toks, -1, 1))}
    out = ref.loss_and_grad_norm(params, batch, builder.reference_spec(cfg))
    loss, report_ = joyai_loss_fn(model, ce_chunk=32)(params, batch)
    assert set(out) == {"loss", "lm_loss", "mtp_loss", "grad_norm",
                        "moe_absent_route_share"}
    assert set(out) - {"loss", "grad_norm"} <= set(report_)
    assert out["loss"] == pytest.approx(float(loss), rel=1e-4)
    assert out["loss"] == pytest.approx(
        out["lm_loss"] + 0.3 * out["mtp_loss"], rel=1e-6)
    assert out["moe_absent_route_share"] == pytest.approx(
        float(report_["moe_absent_route_share"]))
    # the low reading is another number (the rounder bites)
    low = ref.loss_and_grad_norm(
        params, batch, {**builder.reference_spec(cfg),
                        "operand_dtype": "float8_e4m3fn"})
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
        capture_output=True, text=True, env=env, timeout=600)
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
        "loss", "lm_loss", "mtp_loss", "grad_norm",
        "moe_absent_route_share", "update_norm"}
    # the step's own first update against the reference's AdamW step
    assert got["program_from"] == "first dispatch"
    assert got["program"]["update_norm"] == pytest.approx(
        got["plain_f32"]["update_norm"], rel=1e-4)
