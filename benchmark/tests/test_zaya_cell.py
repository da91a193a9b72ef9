"""What the ZAYA1-8B cell adds to the benchmark: ``flops_zaya.py`` against
counts by hand at the cell's shapes, the three new readers on a small
synthetic profile whose numbers are known (built with
``test_program_trace.py``'s helpers) and on runs with nothing to read, the
manifest's entries wherever they stand in their lists, the configuration
file against the catalog's keys, the limit against its readings, the
builder's refusal by the ``flash_path`` note, and the rehearsal of the cell
end to end."""

import json
import os
import subprocess
import sys
import types

import pytest

import test_program_trace as tp
from benchlib import flops, flops_zaya as fz, manifest as mf, report

CELL = "zaya1-8b.b2-t8192"
NEW = ["model.cca_mix_ms_per_step", "model.moe_router_ms_per_step",
       "cca_mix_roofline"]
JOINED = ["model.attention_ms_per_step", "model.mlp_ms_per_step",
          "model.moe_route_ms_per_step", "model.moe_experts_ms_per_step",
          "moe_experts_roofline", "kernel.attn_flash_ms_per_step",
          "attn_flash_roofline", "moe.held_route_share"]


def _cfg():
    return mf.find_cell(mf.load_manifest(), CELL)["config_file"]


def _mcfg(**kw):
    import dataclasses
    mcfg = mf.load_builder("zaya").model_config(_cfg(), tiny=False)
    return dataclasses.replace(mcfg, **kw)


# -- flops_zaya.py against counts by hand ----------------------------------------

def test_parameters_of_each_part_and_of_the_cut():
    cut, whole = _mcfg(), _mcfg(experts_held=None)
    per = fz.layer_params(cut)
    assert per == cut.layer_params()
    # W_q|W_k 2.621 + W_v 0.524 + W_o 2.097 M; the convolutions 0.33 M
    assert per["cca"] == (2048 * 1280 + 2048 * 256 + 1024 * 2048
                          + 3 * 1280 + 2 * 10 * 128 * 128 + 1280 + 2)
    assert per["router"] == (2048 * 256 + 256 + 256 + 2 * (256 * 256 + 256)
                             + 256 * 16 + 16 + 16)
    assert per["experts"] == 8 * 3 * 2048 * 2048
    assert sum(per.values()) == pytest.approx(106.9e6, rel=1e-3)
    assert sum(fz.layer_params(whole).values()) == pytest.approx(207.6e6,
                                                                 rel=1e-3)
    assert fz.num_params(cut) == cut.num_params()
    assert fz.num_params(cut) == pytest.approx(602.0e6, rel=1e-4)
    assert fz.num_params(cut) * 14 == pytest.approx(8.43e9, rel=1e-3)
    published = _mcfg(experts_held=None, vocab_size=262272, n_layer=40)
    assert fz.num_params(published) == pytest.approx(8.84e9, rel=2e-3)
    # what works on a token: everything but 15 of the 16 experts
    at_work = (fz.num_params(published)
               - 40 * 15 * 3 * 2048 * 2048)
    assert at_work == pytest.approx(1.29e9, rel=1e-2)   # 0.75 B + the table


def test_required_operations_per_token_and_per_step():
    c = _mcfg()
    per = fz.forward_flops_per_token(c)
    assert per["cca_proj"] == 2 * (2048 * 1280 + 2048 * 256 + 1024 * 2048)
    assert per["cca_conv"] == 2 * 2 * 10 * 128 * 128
    assert per["attn_core"] == 2 * 8192 * 8 * 256 * 0.5
    assert per["held_experts"] == 0.5 * 2 * 3 * 2048 * 2048
    assert per["head"] == 2 * 2048 * 32896
    step = fz.step_forward_flops_per_token(c)
    assert step["head"] == per["head"]
    assert step["attn_core"] == 5 * per["attn_core"]
    total = sum(step.values())
    assert fz.train_flops_per_token(c) == 3 * total
    # the file's ``cut.consequence``: head and loss ~39% of the operations
    assert step["head"] / total == pytest.approx(0.39, abs=0.02)
    matmuls = total - step["attn_core"]
    assert step["head"] / matmuls == pytest.approx(0.52, abs=0.02)
    # a step of 16,384 tokens: 1.7e13 operations, 88 ms at the peak
    assert 16384 * fz.train_flops_per_token(c) == pytest.approx(1.7e13,
                                                                rel=0.03)


def test_kernel_costs_and_their_least_times():
    c = _mcfg()
    cores = fz.flash_cores_train_cost(c, 2)
    assert cores == flops.flash_attention_train_cost(2, 8, 8192, 128, 5)
    assert cores["flops"] == 5 * 2 * 8 * 6 * 2.0 * 8192 * 8192 * 128 * 0.5
    experts = fz.held_experts_train_cost(c, 16384)
    rows = 8192                       # half of 16,384 top-1 routes
    assert experts["flops"] == 5 * 6.0 * rows * 3 * 2048 * 2048
    assert experts["bytes"] == 5 * 9 * 2 * (2 * rows * 2048
                                            + 8 * 2048 * 2048)
    mix = fz.cca_mix_train_cost(c, 16384)
    assert mix["bytes"] == 5 * 16384 * 5 * 1280 * 2
    assert mix["flops"] == 5 * 16384 * 3 * 2 * 2 * 10 * 128 * 128
    least = flops.roofline(mix["flops"], mix["bytes"], 197e12, 819e9)
    assert least["bound"] == "memory"
    assert least["least_s"] == pytest.approx(1.28e-3, rel=0.01)


# -- the readers on a synthetic profile ------------------------------------------

L = "jit(step)/jit(main)/jvp(Zaya)/"
OP_NAMES = {
    "fusion.1": L + "blocks/h_0/attn/qkv/qk/dot_general",
    "fusion.2": L + "blocks/h_0/attn/conv/mul",
    "fusion.3": L + "blocks/h_0/attn/mix/rsqrt",
    "fusion.4": L + "blocks/h_0/attn/rope/concatenate",
    "flash.5": L + "blocks/h_0/attn/core/jit(flash_fwd)/pallas_call",
    "fusion.6": "jit(step)/jit(main)/transpose(jvp(Zaya))/blocks/h_0/attn/"
                "conv/transpose",
    "fusion.7": L + "blocks/h_0/attn/out/out/dot_general",
    "fusion.8": L + "blocks/h_0/attn_res/scale/add",
    "fusion.9": L + "blocks/h_0/mlp/router/fc1/dot_general",
    "fusion.10": L + "blocks/h_0/mlp/router/scatter-add",
    "fusion.11": L + "blocks/h_0/mlp/dispatch/sort",
    "gmm.12": L + "blocks/h_0/mlp/experts/jit(gmm)/pallas_call",
    "fusion.13": L + "blocks/h_0/mlp/combine/scatter-add",
    "fusion.14": L + "loss/loss/while/body",
    "fusion.15": "jit(step)/optimizer/mul",
}
US = [30, 8, 6, 4, 100, 12, 20, 5, 14, 2, 9, 50, 7, 40, 100]


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
        "kernel_cost_per_step": {"flops": 197e12 * 20e-6, "bytes": 1.0},
        "shapes": {"moe_cost_per_step": {"flops": 1.0,
                                         "bytes": 819e9 * 5e-6},
                   "cca_mix_cost_per_step": {"flops": 1.0,
                                             "bytes": 819e9 * 3e-6}},
        "reference": {"program": {"moe_absent_route_share": 0.5}}}
    return report.Run(mf.find_cell(man, CELL), facts, {}, {},
                      {"steps": 2} if traced else None)


def test_every_reader_of_the_cell_reads(tmp_path):
    run = _run(tmp_path, _xspace())
    got = {name: mf.load_reader(name)(run) for name in NEW + JOINED}
    assert got == {
        # conv 8 + 12 (its backward), mix 6, rope 4
        "model.cca_mix_ms_per_step": pytest.approx(0.030 / 2),
        # the router's MLP 14 and its count of routes 2, not the sort
        "model.moe_router_ms_per_step": pytest.approx(0.016 / 2),
        "cca_mix_roofline": pytest.approx(20.0),        # 3 us over 15
        "model.attention_ms_per_step": pytest.approx(0.180 / 2),
        "model.mlp_ms_per_step": pytest.approx(0.082 / 2),
        "model.moe_route_ms_per_step": pytest.approx(0.032 / 2),
        "model.moe_experts_ms_per_step": pytest.approx(0.050 / 2),
        "moe_experts_roofline": pytest.approx(20.0),    # 5 us over 25
        "kernel.attn_flash_ms_per_step": pytest.approx(0.100 / 2),
        "attn_flash_roofline": pytest.approx(40.0),     # 20 us over 50
        "moe.held_route_share": pytest.approx(50.0),
    }
    # ``loop.py`` keeps of the first dispatch's report only the keys the
    # reference returns, and a max over 16 loads is no number to hold to
    # one rtol: the cell stays off ``moe.load_max_over_mean``'s list
    per_layer = {m["name"]: m for m in mf.load_manifest()["per_layer"]}
    assert CELL not in per_layer["moe.load_max_over_mean"]["workloads"]


def test_new_readers_are_none_with_nothing_to_read(tmp_path, monkeypatch):
    """No trace; a step without CCA's scopes and with a one-matrix
    router's scopes only (any other cell's, or the parent's program); no
    ``train.fit`` span. A reader returns None and does not raise."""
    run = _run(tmp_path / "a", _xspace(), traced=False)
    assert [mf.load_reader(n)(run) for n in NEW] == [None, None, None]
    other = {
        k: v.replace("/conv/", "/q/").replace("/mix/", "/q_norm/").replace(
            "/rope/", "/rotate/").replace("/mlp/router/", "/mlp/gate/")
        for k, v in OP_NAMES.items()}
    run = _run(tmp_path / "b", _xspace(other))
    assert [mf.load_reader(n)(run) for n in NEW] == [None, None, None]
    from ray_tpu.util import tracing
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    run = _run(tmp_path / "c", _xspace())
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    assert [mf.load_reader(n)(run) for n in NEW] == [None, None, None]
    # a cost the worker did not report (the parent's builder): no share
    run = _run(tmp_path / "d", _xspace())
    del run.worker["shapes"]["cca_mix_cost_per_step"]
    assert mf.load_reader("cca_mix_roofline")(run) is None


# -- the manifest and the configuration file -----------------------------------

def test_the_manifest_lists_the_configuration_the_cell_and_the_metrics():
    """Wherever the entries stand in their lists (a later PR appends
    behind them)."""
    man = mf.load_manifest()
    assert mf.check_manifest(man) == []
    config = next(c for c in man["configs"] if c["name"] == "zaya1-8b")
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["file"] == "benchmark/configs/zaya1-8b.json"
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "zaya1-8b", "b2-t8192", 1)
    per_layer = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
    assert per_layer["cca_mix_roofline"]["moves"] == "tokens_per_s_per_chip"
    assert per_layer["cca_mix_roofline"]["unit"] == "%"
    for name in JOINED:
        assert CELL in per_layer[name]["workloads"]
    e2e = [m["name"] for m in mf.metrics_of(man, "end_to_end", CELL)]
    assert e2e == ["tokens_per_s_per_chip", "step_ms_p90", "setup_s"]
    traffic = mf.load_json(mf.traffic_path("b2-t8192"))
    like = mf.load_json(mf.traffic_path("b1-t8192"))
    assert traffic["batch_per_chip"] == 2
    assert {k: v for k, v in traffic.items()
            if k not in ("about", "batch_per_chip")} == {
        k: v for k, v in like.items() if k not in ("about", "batch_per_chip")}


def test_the_configuration_runs_every_published_width():
    cfg = _cfg()
    pub, model = cfg["published"], cfg["model"]
    changed = {k for k in pub if cfg[k] != pub[k]}
    assert changed == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (40, 16, 262272)
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 8, 32896)
    assert len(cfg["reduced"]) == 3
    for key, was in (("num_hidden_layers", "40"), ("num_experts", "16"),
                     ("vocab_size", "262272")):
        assert any(r.startswith(f"{key} {was} ->") for r in cfg["reduced"])
    assert (model["n_embd"], model["n_head"], model["n_kv_head"],
            model["head_dim"]) == (2048, 8, 2, 128)
    assert (model["expert_width"], model["router_width"],
            model["num_experts"], model["top_k"]) == (2048, 256, 16, 1)
    assert model["conv_taps"] == [2, 2] and model["rotary_dim"] == 64
    assert model["rope_theta"] == 5e6 and model["tied"] is True
    assert model["experts_held"] == [0, 8] and model["n_layer"] == 5
    assert model["vocab_size"] == cfg["loss"]["uniform_over"] == 32896
    assert model["vocab_size"] % 128 == 0
    assert model["vocab_size"] >= pub["vocab_size"] / 8
    # what report.py reads of a configuration outside a rehearsal
    assert cfg["sample_unit"] == "tokens" and cfg["ce_chunk"] == 2048
    assert "8 chips" in cfg["cut"]["deployment"]
    assert "deployed load" in cfg["cut"]["load"]
    assert {"value_halves", "conv_grouping", "l2_norm", "rope",
            "router_state", "router_mlp", "balance_bias", "aux_loss",
            "residual_scaling", "projection_columns", "sequence",
            "optimizer", "weights", "tokens", "ce_chunk",
            "described_from_memory"} <= set(cfg["assumed"])
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    catalog = next(r for r in rows if r["source_url"] == cfg["source"])
    assert pub == catalog["config"]
    builder = mf.load_builder(cfg["builder"])
    with pytest.raises(ValueError, match="not the configuration file's"):
        builder.model_config(
            {**cfg, "model": {**model, "expert_width": 1024}}, tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config({**cfg, "router_hidden_size": 128}, tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config({**cfg, "cca_time1": 4}, tiny=False)


def test_the_limit_lies_between_its_two_readings():
    """``reference.rtol`` against the readings the file records
    (``tools/limit.py`` and the cell's own runs took them on the chip):
    every number of the program under it on every seed, the float8
    reading over it by at least one number on every seed, with room on
    both sides; the update's distance, whose other reading is 1 (a state
    left unchanged), far under it."""
    ref = _cfg()["reference"]
    got = ref["readings"]
    assert ref["module"] == "zaya"
    assert set(got["program_largest"]) == {
        "loss", "grad_norm", "moe_absent_route_share", "update_norm"}
    nearest = max(got["program_largest"].values())
    assert nearest * 1.3 < ref["rtol"]
    assert ref["rtol"] * 1.3 < got["float8_smallest_failing"]
    assert got["program_largest"]["update_norm"] * 10 < ref["rtol"] < 1
    assert got["seeds"] >= 8 and got["float8_seeds"] >= 3
    assert "float8_e4m3fn" in ref["rtol_why"]


# -- what the builder does at set-up ---------------------------------------------

def test_the_builder_balances_the_routers_once_and_the_reference_reads_it():
    """``make_params``: the initialisers' parameters but for each
    layer's ``balance_bias``, moved by the load until the 8 experts of
    the tiny preset draw near the mean on the sequence it was balanced
    on; the same seed gives the same tree."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.zaya import Zaya, ZayaConfig
    builder = mf.load_builder("zaya")
    # rows long enough for a load to mean something: 256 routes an expert
    mcfg = ZayaConfig.tiny(seq_len=2048, dtype=jnp.float32)
    model = Zaya(mcfg)
    plain = jax.jit(model.init_params)(jax.random.key(5))
    params = builder.make_params(model, 5)
    again = builder.make_params(model, 5)
    tokens = jax.random.randint(jax.random.key(6), (1, mcfg.seq_len), 0,
                                mcfg.vocab_size)

    def worst(p):
        _, sown = model.apply({"params": p}, tokens, return_hidden=True,
                              mutable=["moe"])
        loads = np.stack([np.asarray(sown["moe"][f"h_{i}"]["mlp"]["load"][0])
                          for i in range(mcfg.n_layer)])
        return float((loads.max(-1) / loads.mean(-1)).max())

    assert worst(params) < 0.9 * worst(plain)
    for i in range(mcfg.n_layer):
        name = f"h_{i}"
        bias = params[name]["mlp"]["router"]["balance_bias"]
        assert np.any(np.asarray(bias))
        assert float(np.abs(bias).max()) < 0.1      # probabilities' scale
        np.testing.assert_array_equal(
            bias, again[name]["mlp"]["router"]["balance_bias"])
        for leaf, was in zip(
                jax.tree_util.tree_leaves(params[name]["attn"]),
                jax.tree_util.tree_leaves(plain[name]["attn"])):
            np.testing.assert_array_equal(leaf, was)


# -- the builder's refusal by the note, the reference's keys -------------------

def test_the_builder_refuses_a_step_whose_attention_was_not_the_kernel(
        monkeypatch):
    import jax
    from ray_tpu.parallel import make_mesh
    builder = mf.load_builder("zaya")
    cfg = _cfg()
    traffic = mf.effective_traffic(mf.load_json(mf.traffic_path("b2-t8192")),
                                   True)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    notes = dict(attn_kind="cca", flash_path="xla",
                 flash_layout="unequal_shapes")
    joyai = types.SimpleNamespace(
        step_notes=lambda: notes,
        with_first_change=mf.load_builder("joyai").with_first_change)
    monkeypatch.setattr(builder, "_joyai", lambda: joyai)
    # a rehearsal is let through: it runs on the CPU by design
    assert callable(builder.build(cfg, traffic, mesh, 0, tiny=True)[
        "reference"])
    assert cfg["kernel"]["flash_path"] == "multi_block"
    tiny = builder.model_config
    monkeypatch.setattr(builder, "model_config",
                        lambda cfg, _: tiny(cfg, True))
    built = builder.build(cfg, traffic, mesh, 0, tiny=False)
    with pytest.raises(RuntimeError, match="not the 'multi_block'"):
        built["reference"]({"params": None, "batch": None})
    assert set(built["shapes"]) >= {"moe_cost_per_step",
                                    "cca_mix_cost_per_step"}


def test_reference_returns_the_keys_the_step_reports():
    """``loop.py`` holds every key the reference returns against the
    first dispatch's metric of that name: each has to be one; and the
    low reading is another number (the rounder bites)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    builder = mf.load_builder("zaya")
    ref = mf.load_reference("zaya")
    mcfg, model, loss_fn = builder.program(_cfg(), tiny=True)
    params = builder.make_params(model, 0)
    toks = np.random.default_rng(0).integers(0, 256, (2, 64), dtype=np.int32)
    batch = {"tokens": jnp.asarray(toks),
             "targets": jnp.asarray(np.roll(toks, -1, 1))}
    spec = builder.reference_spec(mcfg)
    out = ref.loss_and_grad_norm(params, batch, spec)
    loss, report_ = loss_fn(params, batch)
    assert set(out) == {"loss", "grad_norm", "moe_absent_route_share"}
    assert all(v.ndim == 0 for v in report_.values())
    from ray_tpu.models.zaya import zaya_loss_fn
    assert "moe_load" in zaya_loss_fn(model, ce_chunk=32)(params, batch)[1]
    assert out["loss"] == pytest.approx(float(loss), rel=1e-5)
    assert out["moe_absent_route_share"] == pytest.approx(
        float(report_["moe_absent_route_share"]))
    with_step = ref.loss_and_grad_norm(
        params, batch, {**spec, "adamw": _cfg()["optimizer"]})
    assert set(with_step) == set(out) | {"update_norm"}
    assert 0 < with_step["update_norm"] < 1
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
        "loss", "grad_norm", "moe_absent_route_share", "update_norm"}
    # the step's own first update against the reference's AdamW step
    assert got["program_from"] == "first dispatch"
    assert got["program"]["update_norm"] == pytest.approx(
        got["plain_f32"]["update_norm"], rel=1e-4)
