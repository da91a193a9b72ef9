"""What the Nemotron-H cell adds to the benchmark: ``flops_nemotron.py``
against the issue's hand-worked numbers, ``scope_trace.py`` on a small
synthetic profile whose numbers are known (built with
``test_program_trace.py``'s helpers), the new readers on it and on runs
with nothing to read, the configuration file against the catalog's keys,
and the plain reference's recurrence against a loop written here."""

import json

import numpy as np
import pytest

import test_program_trace as tp
from benchlib import flops, flops_nemotron as fn, manifest as mf, report
from benchlib import scope_trace

CELL = "nemotron-3-nano-30b-a3b.b1-t8192"
NEW = ["model.mamba_ms_per_step", "model.ssm_scan_ms_per_step",
       "ssm_scan_roofline", "model.moe_shared_ms_per_step",
       "moe.held_route_share"]
JOINED = ["model.attention_ms_per_step", "model.mlp_ms_per_step",
          "model.moe_route_ms_per_step", "model.moe_experts_ms_per_step",
          "moe_experts_roofline", "kernel.attn_flash_ms_per_step",
          "attn_flash_roofline"]


def _cfg():
    return mf.find_cell(mf.load_manifest(), CELL)["config_file"]


def _mcfg(**kw):
    import dataclasses
    mcfg = mf.load_builder("nemotron_h").model_config(_cfg(), tiny=False)
    return dataclasses.replace(mcfg, **kw)


# -- flops_nemotron.py against the issue's hand-worked numbers -------------

def test_parameters_of_each_kind_of_layer_and_of_the_cut():
    cut, whole = _mcfg(), _mcfg(experts_held=None)
    per = fn.layer_params(cut)
    assert per["M"] == pytest.approx(38.74e6, rel=1e-3)
    assert per["*"] == pytest.approx(23.40e6, rel=1e-3)
    # 8 x 9.98 M held + the shared expert 19.96 M + the router 0.34 M
    assert per["E"] == pytest.approx((8 * 9.98 + 19.96 + 0.34) * 1e6,
                                     rel=1e-3)
    assert fn.layer_params(whole)["E"] == pytest.approx(1297e6, rel=1e-3)
    assert fn.num_params(cut) == cut.num_params()
    assert fn.num_params(cut) == pytest.approx(667.0e6, rel=1e-3)
    assert fn.num_params(cut) * 14 == pytest.approx(9.34e9, rel=1e-3)
    published = _mcfg(
        experts_held=None, vocab_size=131072,
        pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    assert fn.num_params(published) == pytest.approx(31.58e9, rel=1e-3)


def test_required_operations_per_token_and_per_step():
    c = _mcfg()
    per = fn.forward_flops_per_token(c)
    # in_proj 27.70 M + out_proj 11.01 M weights, twice, plus the scan
    assert fn.scan_forward_flops_per_token(c) == pytest.approx(2.75e6,
                                                               rel=2e-3)
    assert per["M"] == pytest.approx(2 * 38.71e6 + 2.75e6, rel=1e-3)
    # shared 39.9, the 0.375 local routes 7.5, router 0.7
    assert per["E"] == pytest.approx((39.9 + 7.5 + 0.7) * 1e6, rel=2e-3)
    assert per["*"] == pytest.approx(113.9e6, rel=1e-3)
    assert per["head"] == pytest.approx(88.1e6, rel=1e-3)
    per_token = fn.train_flops_per_token(c)
    assert per_token == pytest.approx(2.145e9, rel=1e-3)
    step = per_token * 8192
    assert step == pytest.approx(17.6e12, rel=3e-3)
    assert step / 197e12 == pytest.approx(0.0892, rel=3e-3)
    total = sum(per[k] for k in c.pattern) + per["head"]
    assert 4 * per["M"] / total == pytest.approx(0.45, abs=0.01)
    assert per["head"] / total == pytest.approx(0.12, abs=0.01)


def test_scan_and_held_experts_costs_and_their_rooflines():
    c = _mcfg()
    scan = fn.ssm_scan_train_cost(c, 8192)
    assert scan["flops"] == 4 * 8192 * 3 * fn.scan_forward_flops_per_token(c)
    row = (4096 + 2048) * 2 + 64 * 4        # x, B, C in bf16, dt in f32
    boundary = 8192 / 128 * 4096 * 128 * 4 * 2
    assert scan["bytes"] == 4 * (8192 * (3 * row + 3 * 4096 * 2) + boundary)
    roof = flops.roofline(scan["flops"], scan["bytes"], 197e12, 819e9)
    assert roof["bound"] == "memory"
    assert roof["least_s"] == pytest.approx(3.8e-3, rel=0.01)
    held = fn.held_experts_train_cost(c, 8192)
    rows = 8192 * 6 * 8 / 128                       # 3,072 at an even load
    assert held["flops"] == 4 * 6 * rows * 2 * 2688 * 1856
    assert held["bytes"] == 4 * 6 * 2 * (rows * (2688 + 1856)
                                         + 8 * 2688 * 1856)
    roof = flops.roofline(held["flops"], held["bytes"], 197e12, 819e9)
    assert roof["bound"] == "compute"
    assert roof["least_s"] == pytest.approx(3.73e-3, rel=0.01)


# -- scope_trace.py on a synthetic profile ----------------------------------

L = "jit(step)/jvp(NemotronH)/blocks/"
T = "jit(step)/transpose(jvp(NemotronH))/blocks/"
OP_NAMES = {
    "fusion.1": L + "h_0/mamba/in_proj/dot_general",
    "fusion.2": L + "h_0/mamba/scan/checkpoint/dot_general",
    "fusion.3": T + "h_0/mamba/scan/checkpoint/rematted_computation/exp",
    "fusion.4": T + "h_0/mamba/gate_norm/checkpoint/mul",
    "fusion.5": L + "h_0/mamba/mul",                    # the skip, no scope
    "fusion.6": L + "h_1/mlp/shared/up/dot_general",
    "gmm.7": L + "h_1/mlp/checkpoint/experts/jit(gmm)/pallas_call",
    "fusion.8": T + "h_1/mlp/checkpoint/router/top_k",
    "flash.9": L + "h_7/attn/pallas_call",
    "fusion.10": L + "h_0/norm/mul",
    "fusion.11": "jit(step)/optimizer/mul",
}
DEVICE_NAMES = {
    n: (f"%{name} = bf16[8]{{0}} custom-call(bf16[8]{{0}} %x)"
        if name.split(".")[0] in ("gmm", "flash") else
        f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, "
        f"calls=%f{n}")
    for n, name in enumerate(OP_NAMES, start=1)}
US = [40, 100, 60, 20, 6, 80, 30, 10, 50, 4, 100]     # by event, in order


def _device(n: int) -> str:
    names = {**DEVICE_NAMES, 20: "jit_step(1)"}
    at, events = 1000, []
    for i, us in enumerate(US, start=1):
        events.append(tp._event(i, at, us))
        at += us
    return tp._plane(f"/device:TPU:{n}", names, [
        tp._line("XLA Modules", [tp._event(20, 900, 1100)]),
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


def test_self_time_by_module_and_the_scope_beneath():
    from jax.profiler import ProfileData
    raw = _xspace()
    got = scope_trace.reduce_profile(
        ProfileData.from_serialized_xspace(raw),
        scope_trace.program_trace.op_names(raw), steps=2)
    assert got["devices"] == 2 and got["steps"] == 2
    us = {k: pytest.approx(v * 1e6) for k, v in got["under_s"].items()}
    assert us == {"mamba/in_proj": 40, "mamba/scan": 160,
                  "mamba/gate_norm": 20, "mamba/": 6, "mlp/shared": 80,
                  "mlp/checkpoint": 40, "attn/": 50}
    # what program_trace puts under ``mlp`` and ``attn`` is the same time
    whole = scope_trace.program_trace.reduce_profile(
        ProfileData.from_serialized_xspace(raw),
        scope_trace.program_trace.op_names(raw), steps=2)
    assert whole["blocks_s"]["mlp"] == pytest.approx(120e-6)
    assert whole["blocks_s"]["attn"] == pytest.approx(50e-6)


def _run(tmp_path, raw: bytes, traced=True):
    man = mf.load_manifest()
    facts = {
        **tp._fit_in_ring(tmp_path, raw), "kind": "TPU v5 lite",
        "kernel_cost_per_step": {"flops": 197e12 * 10e-6, "bytes": 1.0},
        "shapes": {"moe_cost_per_step": {"flops": 197e12 * 3e-6,
                                         "bytes": 1.0},
                   "ssm_cost_per_step": {"flops": 1.0,
                                         "bytes": 819e9 * 20e-6}},
        "reference": {"program": {"moe_absent_route_share": 0.9375}}}
    return report.Run(mf.find_cell(man, CELL), facts, {}, {},
                      {"steps": 2} if traced else None)


def test_every_reader_of_the_cell_on_a_fit_and_its_profile(tmp_path):
    run = _run(tmp_path, _xspace())
    got = {name: mf.load_reader(name)(run) for name in NEW + JOINED}
    assert got == {
        "model.mamba_ms_per_step": pytest.approx(0.226 / 2),
        "model.ssm_scan_ms_per_step": pytest.approx(0.160 / 2),
        # least 20 us of bytes over 80 us a step under ``scan``
        "ssm_scan_roofline": pytest.approx(25.0),
        "model.moe_shared_ms_per_step": pytest.approx(0.080 / 2),
        "moe.held_route_share": pytest.approx(6.25),
        "model.attention_ms_per_step": pytest.approx(0.050 / 2),
        "model.mlp_ms_per_step": pytest.approx(0.120 / 2),
        "model.moe_route_ms_per_step": pytest.approx(0.010 / 2),
        "model.moe_experts_ms_per_step": pytest.approx(0.030 / 2),
        "moe_experts_roofline": pytest.approx(20.0),    # 3 us over 15
        "kernel.attn_flash_ms_per_step": pytest.approx(0.050 / 2),
        "attn_flash_roofline": pytest.approx(40.0),     # 10 us over 25
    }


def test_new_readers_are_none_with_nothing_to_read(tmp_path, monkeypatch):
    """No trace; a step with no ``mamba`` module and no ``shared``
    expert (OLMoE's, or the parent's program for this cell's metrics);
    no ``train.fit`` span; a worker that recorded no reference."""
    device = NEW[:4]
    run = _run(tmp_path / "a", _xspace(), traced=False)
    assert [mf.load_reader(n)(run) for n in device] == [None] * 4
    other = {k: v.replace("/mamba/", "/attn/").replace("/shared/", "/fc/")
             for k, v in OP_NAMES.items()}
    run = _run(tmp_path / "b", _xspace(other))
    assert [mf.load_reader(n)(run) for n in device] == [None] * 4
    from ray_tpu.util import tracing
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    run = _run(tmp_path / "c", _xspace())
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    assert [mf.load_reader(n)(run) for n in device] == [None] * 4
    bare = report.Run(run.cell, {"shapes": {}, "kernel_cost_per_step": {}},
                      {}, {}, None)
    assert mf.load_reader("moe.held_route_share")(bare) is None


# -- the manifest and the configuration file ---------------------------------

def test_the_manifest_lists_the_cell_and_its_metrics():
    man = mf.load_manifest()
    assert mf.check_manifest(man) == []
    cell = man["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "nemotron-3-nano-30b-a3b", "b1-t8192", 1)
    assert [m["name"] for m in man["per_layer"]][-5:] == NEW
    assert all(m["workloads"] == [CELL] for m in man["per_layer"][-5:])
    got = {m["name"] for m in mf.metrics_of(man, "per_layer", CELL)}
    assert {*NEW, *JOINED} <= got
    assert not {"kernel.flash_ms_per_step", "flash_attention_roofline",
                "collective.ms_per_step", "moe.load_max_over_mean"} & got
    assert [m["name"] for m in mf.metrics_of(man, "end_to_end", CELL)] == [
        "tokens_per_s_per_chip", "step_ms_p90", "setup_s"]
    entry = man["configs"][-1]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]


def test_the_configuration_runs_every_published_width():
    cfg = _cfg()
    pub, model = cfg["published"], cfg["model"]
    changed = {k: cfg[k] for k in pub if cfg[k] != pub[k]}
    assert changed == {"num_hidden_layers": 9, "n_routed_experts": 8,
                       "vocab_size": 16384}
    assert len(cfg["reduced"]) == 3
    assert sorted(r.split()[0] for r in cfg["reduced"]) == sorted(changed)
    start, end = model["layers"]
    assert pub["hybrid_override_pattern"][start:end + 1] \
        == model["pattern"] == "MEMEMEM*E"
    assert (model["n_embd"], model["mamba_heads"], model["mamba_head_dim"],
            model["ssm_state"], model["ssm_groups"], model["conv_kernel"],
            model["chunk"], model["n_head"], model["n_kv_head"],
            model["head_dim"], model["num_experts"], model["top_k"],
            model["expert_width"], model["shared_width"],
            model["norm_topk_prob"], model["route_scale"]) == (
        pub["hidden_size"], pub["mamba_num_heads"], pub["mamba_head_dim"],
        pub["ssm_state_size"], pub["n_groups"], pub["conv_kernel"],
        pub["chunk_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"],
        pub["n_routed_experts"], pub["num_experts_per_tok"],
        pub["moe_intermediate_size"],
        pub["moe_shared_expert_intermediate_size"], pub["norm_topk_prob"],
        pub["routed_scaling_factor"])
    assert model["experts_held"] == [0, cfg["n_routed_experts"]]
    assert model["vocab_size"] == cfg["vocab_size"] \
        == cfg["loss"]["uniform_over"]
    assert "16 chips" in cfg["cut"]["deployment"]
    assert "1/16" in cfg["cut"]["load"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    catalog = next(r for r in rows if r["source_url"] == cfg["source"])
    assert pub == catalog["config"]
    builder = mf.load_builder(cfg["builder"])
    with pytest.raises(ValueError, match="not the configuration file's"):
        builder.model_config(
            {**cfg, "model": {**model, "expert_width": 928}}, tiny=False)


# -- the plain reference --------------------------------------------------------

def test_reference_recurrence_is_a_loop_over_time():
    """``references/nemotron_h.py`` scans time in checkpointed blocks;
    written out a step at a time in numpy it is this."""
    import jax
    ref = mf.load_reference("nemotron_h")
    rng = np.random.default_rng(0)
    rows, t, h, p, n = 2, 12, 3, 4, 5
    x = rng.normal(size=(rows, t, h, p)).astype(np.float32)
    dt = rng.uniform(0.01, 0.5, size=(rows, t, h)).astype(np.float32)
    a = -rng.uniform(1, 4, size=(h,)).astype(np.float32)
    b = rng.normal(size=(rows, t, h, n)).astype(np.float32)
    c = rng.normal(size=(rows, t, h, n)).astype(np.float32)
    want = np.zeros((rows, t, h, p), np.float32)
    for r in range(rows):
        for i in range(h):
            state = np.zeros((p, n), np.float32)
            for s in range(t):
                state = (np.exp(dt[r, s, i] * a[i]) * state
                         + dt[r, s, i] * np.outer(x[r, s, i], b[r, s, i]))
                want[r, s, i] = state @ c[r, s, i]
    with jax.default_matmul_precision("highest"):
        got = ref._recurrence(x, dt, a, b, c)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_reference_returns_the_keys_the_step_reports():
    """``loop.py`` holds every key the reference returns against the
    first dispatch's metric of that name: each has to be one."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.nemotron_h import (
        NemotronH, NemotronHConfig, nemotron_h_loss_fn)
    ref = mf.load_reference("nemotron_h")
    builder = mf.load_builder("nemotron_h")
    cfg = NemotronHConfig.tiny(dtype=jnp.float32)
    model = NemotronH(cfg)
    params = jax.jit(model.init_params)(jax.random.key(0))
    toks = np.random.default_rng(0).integers(0, 256, (2, 64), dtype=np.int32)
    batch = {"tokens": jnp.asarray(toks),
             "targets": jnp.asarray(np.roll(toks, -1, 1))}
    out = ref.loss_and_grad_norm(params, batch, builder.reference_spec(cfg))
    _, report = nemotron_h_loss_fn(model, ce_chunk=32)(params, batch)
    assert set(out) == {"loss", "lm_loss", "grad_norm",
                        "moe_absent_route_share"}
    assert set(out) - {"loss", "grad_norm"} <= set(report)
    assert out["moe_absent_route_share"] == pytest.approx(
        float(report["moe_absent_route_share"]))
    assert float(report["moe_held_route_share"]) == pytest.approx(
        1.0 - out["moe_absent_route_share"], abs=1e-6)
    assert out["loss"] == pytest.approx(np.log(256), abs=1.0)
