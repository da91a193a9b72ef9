"""What the OLMoE cell adds to the benchmark: ``flops_moe.py`` against
hand-worked numbers, ``moe_trace.py`` on a small synthetic profile whose
numbers are known (built with ``test_program_trace.py``'s helpers), the
new readers on it and on runs with nothing to read, and the plain
reference's experts against a loop over experts written here."""

import numpy as np
import pytest

import test_program_trace as tp
from benchlib import flops, flops_moe, manifest as mf, moe_trace, report

CELL = "olmoe-1b-7b.b4-t4096"
NEW = ["model.moe_route_ms_per_step", "model.moe_experts_ms_per_step",
       "moe_experts_roofline", "moe.load_max_over_mean",
       "kernel.attn_flash_ms_per_step", "attn_flash_roofline"]
OLMOE = dict(n_layer=1, d=2048, n_head=16, head_dim=128, n_kv_head=16,
             num_experts=64, expert_width=1024, vocab_size=50304)


# -- flops_moe.py against the issue's hand-worked numbers ------------------

def test_parameters_of_a_layer_and_of_the_cut_model():
    p = flops_moe.routed_decoder_params(**OLMOE)
    assert p["experts"] == 64 * 3 * 2048 * 1024 == 402_653_184
    assert p["attention"] == 4 * 2048 * 2048 and p["router"] == 131_072
    assert p["layer"] == pytest.approx(419.6e6, rel=2e-4)
    assert p["table"] == pytest.approx(103.0e6, rel=3e-4)
    assert p["total"] == pytest.approx(625.7e6, rel=2e-4)
    assert p["total"] * 14 == pytest.approx(8.8e9, rel=6e-3)   # bytes held
    sixteen = flops_moe.routed_decoder_params(**{**OLMOE, "n_layer": 16})
    assert sixteen["total"] == pytest.approx(6.92e9, rel=1e-3)  # "1B-7B"


def test_required_operations_per_token_and_per_step():
    per_token = flops_moe.routed_decoder_train_flops_per_token(
        top_k=8, seq_len=4096, **OLMOE)
    # 6 x (attention 16.78 M + 8 x 6.29 M + router 0.13 M + head 103.0 M)
    # + causal attention 3 * 2 * 2 * T * d / 2
    by_hand = 6 * (16.78e6 + 8 * 6.29e6 + 0.13e6 + 103.0e6) \
        + 3 * 2 * 2 * 4096 * 2048 / 2
    assert per_token == pytest.approx(by_hand, rel=1e-3)
    assert per_token == pytest.approx(1.07e9, rel=2e-3)
    step = per_token * 16384
    assert step == pytest.approx(17.6e12, rel=3e-3)
    assert step / 197e12 == pytest.approx(0.089, rel=3e-3)
    head = 6 * 103.0e6 / per_token              # of one layer of 16: 55%
    assert head == pytest.approx(0.577, abs=0.01)


def test_grouped_matmul_cost_and_its_roofline():
    cost = flops_moe.grouped_matmul_train_cost(
        16384, 8, 2048, 1024, 64, n_layer=1)
    assert cost["flops"] == 6 * 16384 * 8 * 3 * 2048 * 1024
    # nine grouped matmuls: 131,072 rows in (2,048 or 1,024 wide), out
    # (1,024 or 2,048), and 64 experts' 2,048 x 1,024 matrices, in bf16
    assert cost["bytes"] == 9 * 2 * (131072 * (2048 + 1024)
                                     + 64 * 2048 * 1024)
    roof = flops.roofline(cost["flops"], cost["bytes"], 197e12, 819e9)
    assert roof["bound"] == "compute"
    assert roof["least_s"] == pytest.approx(25.1e-3, rel=2e-3)


# -- moe_trace.py on a synthetic profile ------------------------------------

L = "jit(step)/jvp(Llama)/blocks/h_0/"
T = "jit(step)/transpose(jvp(Llama))/blocks/h_0/"
OP_NAMES = {
    "fusion.1": L + "attn/q/dot_general",
    "flash.2": T + "attn/pallas_call",
    "fusion.3": L + "mlp/jvp(router)/top_k",
    "sort.4": L + "mlp/jvp(dispatch)/sort",
    "gmm.5": L + "mlp/jvp(experts)/jit(gmm)/pallas_call",
    "tgmm.6": T + "mlp/transpose(jvp(experts))/jit(tgmm)/pallas_call",
    "fusion.7": T + "mlp/transpose(jvp(combine))/gather",
    "fusion.8": L + "mlp_norm/mul",
    "fusion.9": "jit(step)/optimizer/mul",
    "fusion.10": T + "mlp/convert_element_type",    # under mlp, no scope
}
DEVICE_NAMES = {
    1: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput, calls=%f1",
    2: "%flash.2 = (bf16[8]{0}, f32[8]{0}) custom-call(bf16[8]{0} %q)",
    3: "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f3",
    4: "%sort.4 = (s32[8]{0}, s32[8]{0}) sort(s32[8]{0} %a, s32[8]{0} %b)",
    5: "%gmm.5 = bf16[8]{0} custom-call(bf16[8]{0} %x, bf16[8]{0} %w)",
    6: "%tgmm.6 = bf16[8]{0} custom-call(bf16[8]{0} %x, bf16[8]{0} %g)",
    7: "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f7",
    8: "%fusion.8 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f8",
    9: "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f9",
    10: "%fusion.10 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fa",
}


def _device(n: int) -> str:
    # the window is 1000..2000 us; everything runs in module jit_step(1)
    names = {**DEVICE_NAMES, 20: "jit_step(1)"}
    return tp._plane(f"/device:TPU:{n}", names, [
        tp._line("XLA Modules", [tp._event(20, 900, 1100)]),
        tp._line("XLA Ops", [
            tp._event(1, 1000, 40),         # attn, a matmul
            tp._event(2, 1040, 60),         # attn, the kernel
            tp._event(3, 1100, 30),         # mlp / router
            tp._event(4, 1130, 20),         # mlp / dispatch
            tp._event(5, 1150, 200),        # mlp / experts, a kernel
            tp._event(6, 1350, 100),        # mlp / experts, a kernel
            tp._event(7, 1450, 50),         # mlp / combine
            tp._event(8, 1500, 10),         # blocks, neither
            tp._event(10, 1510, 6),         # mlp, none of the four
            tp._event(9, 1600, 100),        # optimizer
        ])])


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


def test_routed_scopes_and_kernels_by_module():
    from jax.profiler import ProfileData
    raw = _xspace()
    got = moe_trace.reduce_profile(
        ProfileData.from_serialized_xspace(raw),
        moe_trace.program_trace.op_names(raw), steps=2)
    assert got["devices"] == 2 and got["steps"] == 2
    us = {k: v * 1e6 for k, v in got["mlp_s"].items()}
    assert us == {"router": pytest.approx(30), "dispatch": pytest.approx(20),
                  "experts": pytest.approx(300), "combine": pytest.approx(50),
                  "other": pytest.approx(6)}
    kernels = {k: v * 1e6 for k, v in got["kernel_s"].items()}
    assert kernels == {"attn": pytest.approx(60), "mlp": pytest.approx(300)}
    # the four and the rest are what program_trace puts under ``mlp``
    whole = moe_trace.program_trace.reduce_profile(
        ProfileData.from_serialized_xspace(raw),
        moe_trace.program_trace.op_names(raw), steps=2)
    assert sum(got["mlp_s"].values()) == pytest.approx(
        whole["blocks_s"]["mlp"])


def _run(tmp_path, raw: bytes, traced=True):
    man = mf.load_manifest()
    facts = {
        **tp._fit_in_ring(tmp_path, raw), "kind": "TPU v5 lite",
        "kernel_cost_per_step": {"flops": 197e12 * 12e-6, "bytes": 1.0},
        "shapes": {"moe_cost_per_step": {"flops": 197e12 * 30e-6,
                                         "bytes": 1.0}},
        "reference": {"program": {"moe_load_max_over_mean": 1.0625}}}
    return report.Run(mf.find_cell(man, CELL), facts, {}, {},
                      {"steps": 2} if traced else None)


def test_the_six_readers_on_a_fit_and_its_profile(tmp_path):
    run = _run(tmp_path, _xspace())
    got = {name: mf.load_reader(name)(run) for name in NEW}
    assert got == {
        "model.moe_route_ms_per_step": pytest.approx(0.100 / 2),
        "model.moe_experts_ms_per_step": pytest.approx(0.300 / 2),
        # least 30 us of operations over 150 us a step under ``experts``
        "moe_experts_roofline": pytest.approx(20.0),
        "moe.load_max_over_mean": 1.0625,
        "kernel.attn_flash_ms_per_step": pytest.approx(0.060 / 2),
        "attn_flash_roofline": pytest.approx(40.0),     # 12 us over 30
    }


def test_device_readers_are_none_with_nothing_to_read(tmp_path, monkeypatch):
    """No trace; a dense ``mlp`` (the GPT-2 step: no routed scope, its
    only custom calls under ``attn``); no ``train.fit`` span (a program
    from before them, as the parent is for this cell's metrics)."""
    device = [n for n in NEW if n != "moe.load_max_over_mean"]
    run = _run(tmp_path / "a", _xspace(), traced=False)
    assert [mf.load_reader(n)(run) for n in device] == [None] * 5
    dense = {k: v.replace("jvp(experts)/", "fc/").replace(
        "transpose(jvp(experts))/", "fc/") for k, v in OP_NAMES.items()}
    run = _run(tmp_path / "b", _xspace(dense))
    assert [mf.load_reader(n)(run) for n in device[:3]] == [None] * 3
    assert mf.load_reader("kernel.attn_flash_ms_per_step")(run) == \
        pytest.approx(0.030)
    from ray_tpu.util import tracing
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    assert [mf.load_reader(n)(run) for n in device] == [None] * 5
    bare = report.Run(run.cell, {"shapes": {}, "kernel_cost_per_step": {}},
                      {}, {}, None)
    assert mf.load_reader("moe.load_max_over_mean")(bare) is None


def test_the_manifest_lists_the_cell_and_its_metrics():
    man = mf.load_manifest()
    assert mf.check_manifest(man) == []
    assert man["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in man["per_layer"]][-6:] == NEW
    assert all(m["workloads"] == [CELL] for m in man["per_layer"][-6:])
    got = {m["name"] for m in mf.metrics_of(man, "per_layer", CELL)}
    assert {"model.attention_ms_per_step", "model.mlp_ms_per_step",
            *NEW} <= got
    assert not {"kernel.flash_ms_per_step", "flash_attention_roofline",
                "collective.ms_per_step"} & got
    assert [m["name"] for m in mf.metrics_of(man, "end_to_end", CELL)] == [
        "tokens_per_s_per_chip", "step_ms_p90", "setup_s"]


def test_the_configuration_runs_every_published_width():
    cfg = mf.find_cell(mf.load_manifest(), CELL)["config_file"]
    pub, model = cfg["published"], cfg["model"]
    changed = {k for k in pub if cfg[k] != pub[k]}
    assert changed == {"num_hidden_layers"} and cfg["num_hidden_layers"] == 1
    assert (model["n_embd"], model["n_head"], model["n_kv_head"],
            model["num_experts"], model["top_k"], model["expert_width"],
            model["seq_len"], model["vocab_size"], model["n_layer"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["num_experts"],
        pub["num_experts_per_tok"], pub["intermediate_size"],
        pub["max_position_embeddings"], pub["vocab_size"], 1)
    assert model["head_dim"] * model["n_head"] == pub["hidden_size"]
    builder = mf.load_builder(cfg["builder"])
    mcfg = builder.model_config(cfg, tiny=False)     # refuses a mismatch
    assert mcfg.num_params() == flops_moe.routed_decoder_params(
        **OLMOE)["total"]
    with pytest.raises(ValueError, match="not the configuration file's"):
        builder.model_config(
            {**cfg, "model": {**model, "expert_width": 512}}, tiny=False)


# -- the plain reference ------------------------------------------------------

def test_reference_experts_are_a_loop_over_experts():
    """``references/olmoe.py`` walks the experts eight at a time under
    a checkpoint; written out one expert at a time it is this."""
    import jax
    import jax.numpy as jnp
    ref = mf.load_reference("olmoe")
    ks = jax.random.split(jax.random.key(0), 5)
    e, d, f, k = 16, 12, 20, 3
    h = jax.random.normal(ks[0], (2, 7, d))
    gate, up = (jax.random.normal(ks[i], (e, d, f)) for i in (1, 2))
    down = jax.random.normal(ks[3], (e, f, d))
    probs = jax.nn.softmax(jax.random.normal(ks[4], (2, 7, e)), -1)
    top, chosen = jax.lax.top_k(probs, k)
    mix = (jax.nn.one_hot(chosen, e) * top[..., None]).sum(-2)
    want = np.zeros((2, 7, d), np.float32)
    for b in range(2):
        for t in range(7):
            for weight, i in zip(np.asarray(top[b, t]),
                                 np.asarray(chosen[b, t])):
                x = np.asarray(h[b, t])
                a = x @ np.asarray(gate[i])
                a = a / (1 + np.exp(-a)) * (x @ np.asarray(up[i]))
                want[b, t] += weight * (a @ np.asarray(down[i]))
    with jax.default_matmul_precision("highest"):
        got = ref._experts(h, mix, gate, up, down)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_reference_returns_the_six_keys_the_step_reports():
    """``loop.py`` holds every key the reference returns against the
    first dispatch's metric of that name."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.llama import Llama, LlamaConfig
    ref = mf.load_reference("olmoe")
    cfg = LlamaConfig.tiny_olmoe(dtype=jnp.float32)
    params = jax.jit(Llama(cfg).init_params)(jax.random.key(0))
    toks = np.random.default_rng(0).integers(0, 256, (2, 64), dtype=np.int32)
    out = ref.loss_and_grad_norm(
        params, {"tokens": jnp.asarray(toks),
                 "targets": jnp.asarray(np.roll(toks, -1, 1))},
        {"n_layer": 2, "n_head": 4, "top_k": 2, "norm_topk_prob": False,
         "rms_eps": 1e-5, "rope_theta": 1e4, "aux_loss_coef": 0.01,
         "z_loss_coef": 0.001})
    assert set(out) == {"loss", "grad_norm", "lm_loss", "moe_aux_loss",
                        "moe_z_loss", "moe_load_max_over_mean"}
    assert out["loss"] == pytest.approx(
        out["lm_loss"] + 0.01 * out["moe_aux_loss"]
        + 0.001 * out["moe_z_loss"])
    assert out["moe_aux_loss"] == pytest.approx(2.0, rel=0.1)   # ~ top_k
    assert out["moe_z_loss"] == pytest.approx(np.log(8) ** 2, rel=0.1)


def test_the_low_reading_rounds_operands_and_nothing_else():
    """``spec["operand_dtype"]``: operands on the narrow type's grid
    (scaled so that the largest element is the type's largest), the
    gradient straight through; absent, the reference is untouched."""
    import jax
    import jax.numpy as jnp

    ref = mf.load_reference("olmoe")
    assert ref._rounder(None) is ref._same
    rnd = ref._rounder("float8_e4m3fn")
    x = jnp.asarray(np.random.default_rng(0).normal(size=257) * 0.02,
                    jnp.float32)
    got = rnd(x)
    assert float(jnp.abs(got).max()) == float(jnp.abs(x).max())
    err = np.abs(np.asarray(got - x))
    # three mantissa bits: half a step of 2^-3 on normal numbers, and
    # the subnormal step 2^-9 under a scale of 448 / max|x|
    step = 2.0 ** -9 * float(jnp.abs(x).max()) / 448
    assert (err <= np.maximum(2.0 ** -4 * np.abs(x), step / 2) * 1.001).all()
    assert len(np.unique(np.asarray(got))) < 200
    np.testing.assert_array_equal(
        jax.grad(lambda v: rnd(v).sum())(x), np.ones(257, np.float32))
    bf = ref._rounder("bfloat16")(x)
    np.testing.assert_array_equal(bf, x.astype(jnp.bfloat16).astype(x.dtype))
