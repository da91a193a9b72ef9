"""What the SmallThinker cell adds to the benchmark: ``flops_smallthinker.py``
against counts by hand at the cell's shapes (the band counted exactly), the
three new readers on a small synthetic profile whose numbers are known (built
with ``test_program_trace.py``'s helpers) and on runs with nothing to read,
the manifest's entries wherever they stand in their lists, the configuration
file against the catalog's keys, the limit against its readings, the builder's
refusal by the kernel's notes, and the rehearsal of the cell end to end."""

import json
import os
import subprocess
import sys
import types

import pytest

import test_program_trace as tp
from benchlib import flops, flops_smallthinker as fs, manifest as mf, report

CELL = "smallthinker-21b-a3b.b1-t16384"
NEW = ["model.attn_window_ms_per_step", "model.attn_global_ms_per_step",
       "attn_window_roofline"]
JOINED = ["model.attention_ms_per_step", "model.mlp_ms_per_step",
          "model.moe_route_ms_per_step", "model.moe_experts_ms_per_step",
          "moe_experts_roofline", "kernel.attn_flash_ms_per_step",
          "attn_flash_roofline", "moe.held_route_share"]


def _cfg():
    return mf.find_cell(mf.load_manifest(), CELL)["config_file"]


def _mcfg(**kw):
    import dataclasses
    mcfg = mf.load_builder("smallthinker").model_config(_cfg(), tiny=False)
    return dataclasses.replace(mcfg, **kw)


# -- flops_smallthinker.py against counts by hand ----

def test_parameters_of_each_part_and_of_the_cut():
    cut, whole = _mcfg(), _mcfg(experts_held=None)
    per = fs.layer_params(cut)
    assert per == cut.layer_params()
    assert per["attn"] == 2 * 2560 * 3584 + 2 * 2560 * 512      # 20.97 M
    assert per["router"] == 2560 * 64
    assert per["experts"] == 16 * 3 * 2560 * 768
    assert sum(per.values()) - per["experts"] == pytest.approx(21.14e6,
                                                               rel=1e-3)
    assert sum(fs.layer_params(whole).values()) == pytest.approx(398.6e6,
                                                                 rel=1e-3)
    assert fs.num_params(cut) == cut.num_params()
    assert fs.num_params(cut) == pytest.approx(559.7e6, rel=1e-4)
    assert fs.num_params(cut) * 14 == pytest.approx(7.84e9, rel=1e-3)
    published = _mcfg(experts_held=None, vocab_size=151936, n_layer=52)
    assert fs.num_params(published) == pytest.approx(21.5e9, rel=2e-3)
    assert fs.layers_of(cut) == (3, 1) and fs.layers_of(published) == (39, 13)


def test_the_band_is_counted_exactly():
    """A row sees ``min(r + 1, window)`` keys: counted one by one at a
    small size, and the cell's average at its own."""
    import numpy as np
    for t, w in [(64, 24), (64, 1), (64, 64), (64, 100), (10, 3)]:
        r, c = np.arange(t)[:, None], np.arange(t)[None, :]
        assert fs.seen_entries(t, w) == ((c <= r) & (c > r - w)).sum()
        assert fs.seen_entries(t, None) == (c <= r).sum()
    assert fs.seen_entries(16384, 4096) / 16384 == pytest.approx(3584.1,
                                                                 abs=0.05)
    assert fs.seen_entries(16384, None) / 16384 == 8192.5
    # the band against the blocks a kernel of 1,024-row blocks walks: 70
    # for 56 blocks' worth
    assert fs.seen_entries(16384, 4096) / 1024 ** 2 == pytest.approx(56.0,
                                                                     abs=0.01)


def test_required_operations_per_token_and_per_step():
    c = _mcfg()
    per = fs.forward_flops_per_token(c)
    assert per["attn_proj"] == 2 * (2 * 2560 * 3584 + 2 * 2560 * 512)
    assert per["core_window"] == pytest.approx(4 * 3584.125 * 3584)
    assert per["core_global"] == pytest.approx(4 * 8192.5 * 3584)
    assert per["held_experts"] == 6 * 0.25 * 2 * 3 * 2560 * 768
    assert per["head"] == 2 * 2560 * 19072
    step = fs.step_forward_flops_per_token(c)
    assert step["head"] == per["head"]
    assert step["core_window"] == 3 * per["core_window"]
    assert step["core_global"] == per["core_global"]
    assert step["attn_proj"] == 4 * per["attn_proj"]
    total = sum(step.values())
    assert total == pytest.approx(609e6, rel=2e-3)
    assert fs.train_flops_per_token(c) == 3 * total
    # the file's ``cut.consequence``: the cores 45% (windowed 25, global 19)
    cores = step["core_window"] + step["core_global"]
    assert cores / total == pytest.approx(0.45, abs=0.01)
    assert step["core_window"] / total == pytest.approx(0.25, abs=0.01)
    assert step["attn_proj"] / total == pytest.approx(0.28, abs=0.01)
    assert step["head"] / total == pytest.approx(0.16, abs=0.01)
    assert step["held_experts"] / total == pytest.approx(0.12, abs=0.01)
    # without the window the cores would be 469.6 M a token
    assert 4 * per["core_global"] == pytest.approx(469.6e6, rel=1e-3)
    # a step of 16,384 tokens: 2.99e13 operations, 152 ms at the peak
    assert 16384 * fs.train_flops_per_token(c) == pytest.approx(2.99e13,
                                                                rel=3e-3)


def test_kernel_costs_and_their_least_times():
    c = _mcfg()
    window = fs.window_cores_train_cost(c, 1)
    glob_ = fs.global_cores_train_cost(c, 1)
    both = fs.flash_cores_train_cost(c, 1)
    assert window["flops"] == 3 * 28 * 6 * 2.0 * fs.seen_entries(
        16384, 4096) * 128
    assert glob_["flops"] == 28 * 6 * 2.0 * fs.seen_entries(16384, None) * 128
    # the global core is flops.py's causal cost but for the diagonal's
    # other half and o, which the backward reads too
    plain = flops.flash_attention_train_cost(1, 28, 16384, 128, 1)
    assert glob_["flops"] == pytest.approx(plain["flops"], rel=1e-3)
    tensor = 28 * 16384 * 128 * 2
    assert glob_["bytes"] == plain["bytes"] + tensor
    assert window["bytes"] == 3 * glob_["bytes"]
    assert both == {k: window[k] + glob_[k] for k in ("flops", "bytes")}
    least = flops.roofline(window["flops"], window["bytes"], 197e12, 819e9)
    assert least["bound"] == "compute"
    assert least["least_s"] == pytest.approx(38.46e-3, rel=1e-3)
    experts = fs.held_experts_train_cost(c, 16384)
    rows = 16384 * 6 // 4             # a quarter of the routes
    assert experts["flops"] == 4 * 6.0 * rows * 3 * 2560 * 768
    assert experts["bytes"] == 4 * 9 * 2 * (rows * 2560 + rows * 768
                                            + 16 * 2560 * 768)


# -- the readers on a synthetic profile ----

L = "jit(step)/jit(main)/jvp(SmallThinker)/"
B = "jit(step)/jit(main)/transpose(jvp(SmallThinker))/"
OP_NAMES = {
    "fusion.1": L + "blocks/h_0/mlp/h_0/router/dot_general",
    "fusion.2": L + "blocks/h_0/attn/qkv/q/dot_general",
    "flash.3": L + "blocks/h_0/attn/core/jit(_flash_fwd)/pallas_call",
    "fusion.4": L + "blocks/h_1/attn/rope/concatenate",
    "fusion.5": L + "blocks/h_1/attn/repeat/broadcast_in_dim",
    "flash.6": L + "blocks/h_1/attn/window/jit(_flash_fwd)/pallas_call",
    "flash.7": B + "blocks/h_1/attn/window/jit(_flash_bwd)/pallas_call",
    "fusion.8": B + "blocks/h_1/attn/window/convert_element_type",
    "fusion.9": L + "blocks/h_1/attn/out/out/dot_general",
    "fusion.10": L + "blocks/h_1/mlp/router/scatter-add",
    "fusion.11": L + "blocks/h_1/mlp/dispatch/sort",
    "gmm.12": L + "blocks/h_1/mlp/experts/jit(gmm)/pallas_call",
    "fusion.13": L + "blocks/h_1/mlp/combine/scatter-add",
    "fusion.14": L + "loss/loss/while/body",
    "fusion.15": "jit(step)/optimizer/mul",
}
US = [14, 30, 60, 4, 6, 40, 50, 10, 20, 2, 9, 50, 7, 40, 100]


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
                   "window_cost_per_step": {"flops": 197e12 * 20e-6,
                                            "bytes": 1.0}},
        "reference": {"program": {"moe_absent_route_share": 0.75}}}
    return report.Run(mf.find_cell(man, CELL), facts, {}, {},
                      {"steps": 2} if traced else None)


def test_every_reader_of_the_cell_reads(tmp_path):
    run = _run(tmp_path, _xspace())
    got = {name: mf.load_reader(name)(run) for name in NEW + JOINED}
    assert got == {
        # the windowed layer's two kernels 40 + 50 and the cast beside them
        "model.attn_window_ms_per_step": pytest.approx(0.100 / 2),
        "model.attn_global_ms_per_step": pytest.approx(0.060 / 2),
        "attn_window_roofline": pytest.approx(40.0),    # 20 us over 50
        "model.attention_ms_per_step": pytest.approx(0.220 / 2),
        # the routes made before attention count with the routed layer
        "model.mlp_ms_per_step": pytest.approx(0.082 / 2),
        "model.moe_route_ms_per_step": pytest.approx(0.032 / 2),
        "model.moe_experts_ms_per_step": pytest.approx(0.050 / 2),
        "moe_experts_roofline": pytest.approx(20.0),    # 5 us over 25
        "kernel.attn_flash_ms_per_step": pytest.approx(0.150 / 2),
        "attn_flash_roofline": pytest.approx(40.0),     # 30 us over 75
        "moe.held_route_share": pytest.approx(25.0),
    }


def test_new_readers_are_none_with_nothing_to_read(tmp_path, monkeypatch):
    """No trace; a step without the two scopes (any other cell's, or the
    parent's program); no ``train.fit`` span; a worker that reported no
    cost. A reader returns None and does not raise."""
    run = _run(tmp_path / "a", _xspace(), traced=False)
    assert [mf.load_reader(n)(run) for n in NEW] == [None, None, None]
    other = {k: v.replace("/attn/window/", "/attn/").replace(
        "/attn/core/", "/attn/") for k, v in OP_NAMES.items()}
    run = _run(tmp_path / "b", _xspace(other))
    assert [mf.load_reader(n)(run) for n in NEW] == [None, None, None]
    from ray_tpu.util import tracing
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    run = _run(tmp_path / "c", _xspace())
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    assert [mf.load_reader(n)(run) for n in NEW] == [None, None, None]
    run = _run(tmp_path / "d", _xspace())
    del run.worker["shapes"]["window_cost_per_step"]
    assert mf.load_reader("attn_window_roofline")(run) is None
    assert mf.load_reader("model.attn_window_ms_per_step")(run) is not None


# -- the manifest and the configuration file ----

def test_the_manifest_lists_the_configuration_the_cell_and_the_metrics():
    """Wherever the entries stand in their lists (a later PR appends
    behind them)."""
    man = mf.load_manifest()
    assert mf.check_manifest(man) == []
    config = next(c for c in man["configs"]
                  if c["name"] == "smallthinker-21b-a3b")
    assert config["reduced"] == ["num_hidden_layers",
                                 "moe_num_primary_experts", "vocab_size"]
    assert config["file"] == "benchmark/configs/smallthinker-21b-a3b.json"
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21b-a3b", "b1-t16384", 1)
    per_layer = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
    assert per_layer["attn_window_roofline"]["moves"] \
        == "tokens_per_s_per_chip"
    assert per_layer["attn_window_roofline"]["unit"] == "%"
    for name in NEW[:2]:
        assert per_layer[name]["moves"] == "step_ms_p90"
    for name in JOINED:
        assert CELL in per_layer[name]["workloads"]
    e2e = [m["name"] for m in mf.metrics_of(man, "end_to_end", CELL)]
    assert e2e == ["tokens_per_s_per_chip", "step_ms_p90", "setup_s"]
    traffic = mf.load_json(mf.traffic_path("b1-t16384"))
    like = mf.load_json(mf.traffic_path("b1-t8192"))
    assert {k: v for k, v in traffic.items() if k != "about"} == {
        k: v for k, v in like.items() if k != "about"}


def test_the_configuration_runs_every_published_width():
    cfg = _cfg()
    pub, model = cfg["published"], cfg["model"]
    changed = {k for k in pub if cfg[k] != pub[k]}
    assert changed == {"num_hidden_layers", "moe_num_primary_experts",
                       "vocab_size"}
    assert (pub["num_hidden_layers"], pub["moe_num_primary_experts"],
            pub["vocab_size"]) == (52, 64, 151936)
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"],
            cfg["vocab_size"]) == (4, 16, 19072)
    assert len(cfg["reduced"]) == 3
    for key, was in (("num_hidden_layers", "52"),
                     ("moe_num_primary_experts", "64"),
                     ("vocab_size", "151936")):
        assert any(r.startswith(f"{key} {was} ->") for r in cfg["reduced"])
    assert (model["n_embd"], model["n_head"], model["n_kv_head"],
            model["head_dim"]) == (2560, 28, 4, 128)
    assert (model["expert_width"], model["num_experts"], model["top_k"]) == (
        768, 64, 6)
    assert model["window"] == 4096 and model["rope_theta"] == 1.5e6
    assert model["window_period"] == model["rope_period"] == [0, 1, 1, 1]
    assert pub["sliding_window_layout"] == pub["rope_layout"] \
        == [0, 1, 1, 1] * 13
    assert model["experts_held"] == [0, 16] and model["n_layer"] == 4
    assert model["tied"] is False and model["seq_len"] == 16384
    assert model["vocab_size"] == cfg["loss"]["uniform_over"] == 19072
    assert model["vocab_size"] % 128 == 0
    assert model["vocab_size"] >= pub["vocab_size"] / 8
    # what report.py reads of a configuration outside a rehearsal
    assert cfg["sample_unit"] == "tokens" and cfg["ce_chunk"] == 2048
    assert "8 chips" in cfg["cut"]["deployment"]
    assert "a quarter" in cfg["cut"]["load"]
    assert {"router_input", "rope", "window_count", "topk_then_softmax",
            "aux_loss", "not_built", "balance", "sequence", "optimizer",
            "weights", "tokens", "ce_chunk", "described_from_memory"} <= set(
        cfg["assumed"])
    assert cfg["kernel"] == {"tpu_custom_call": True,
                             "flash_path": "multi_block",
                             "flash_window": 4096}
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    catalog = next(r for r in rows if r["source_url"] == cfg["source"])
    assert pub == catalog["config"]
    builder = mf.load_builder(cfg["builder"])
    with pytest.raises(ValueError, match="not the configuration file's"):
        builder.model_config(
            {**cfg, "model": {**model, "expert_width": 1024}}, tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config({**cfg, "sliding_window_size": 2048}, tiny=False)
    with pytest.raises(ValueError, match="own keys disagree"):
        builder.model_config({**cfg, "rope_layout": [1, 1, 1, 1] * 13},
                             tiny=False)


def test_the_limit_lies_between_the_programs_readings_and_the_float8s():
    """``reference.rtol`` against the readings the file records
    (``tools/smallthinker_limit.py`` and the cell's own runs took them
    on the chip). Float8 operands hardly move the whole gradient's norm
    (the head's and the tables' before anything else), so the cell
    compares two numbers of the new mechanism beside it: the root mean
    square of what a whole window hands the output projection
    (``attn_window_out_rms``), which the float8 reading fails on every
    seed with the limit between the two readings and room on each side,
    and the norm of the q and k projections' gradients
    (``reference.grad_groups``), which every planted fault of the band
    fails, those of the backward kernels alone among them. Every number
    of the program has 1.8 times of room under the limit, the float8
    reading two and a half over it; ``update_norm`` stands between its
    reading and 1."""
    ref = _cfg()["reference"]
    got = ref["readings"]
    rtol = ref["rtol"]
    assert ref["module"] == "smallthinker" and rtol == 2 ** -10
    accepted = {mf.load_json(os.path.join(mf.ROOT, c["file"]))[
        "reference"]["rtol"] for c in mf.load_manifest()["configs"]
        if c["name"] != "smallthinker-21b-a3b"}
    assert rtol in accepted
    assert set(ref["grad_groups"]) == {"grad_norm_attn_qk"}
    assert set(got["program_largest"]) == {
        "loss", "grad_norm", "moe_absent_route_share", "update_norm",
        "attn_window_out_rms", "grad_norm_attn_qk"}
    assert max(got["program_largest"].values()) * 1.8 < rtol
    assert got["program_largest"]["update_norm"] * 100 < rtol
    assert rtol * 100 < got["unchanged_state_update_norm"]
    assert got["seeds"] >= 8
    low = got["float8"]
    assert low["seeds"] >= 4 and low["fails"] is True
    assert low["by"] == "attn_window_out_rms"
    assert got["program_largest"][low["by"]] * 1.8 < rtol \
        < low["smallest"] / 2.5
    assert max(low["other_keys_largest"].values()) < rtol   # said, not hidden
    assert low["smallest"] <= low["largest"]
    assert set(got["faults"]) >= {
        "causal_only", "window_plus_block", "window_minus_block",
        "rope_in_layer_0", "backward_window_plus_block"}
    for name, fault in got["faults"].items():
        if name != "what":
            assert fault["grad_norm_attn_qk"] > 10 * rtol, name
    # the backward kernels alone: no number of the forward pass moves
    behind = got["faults"]["backward_window_plus_block"]
    assert max(behind["loss"], behind["attn_window_out_rms"]) * 10 < rtol
    assert "float8_e4m3fn" in ref["rtol_why"]
    loss = _cfg()["loss"]
    assert loss["declines"] is False and "update_norm" in loss["why"]


def test_the_limit_tool_rehearses_at_the_tiny_size(tmp_path):
    """``tools/smallthinker_limit.py --tiny``: the reference, the
    program, the float8 reading and a planted fault, each with the
    file's groups and every leaf; at float32 and this size the program
    is the reference and the other two are not."""
    out = tmp_path / "limit.json"
    p = subprocess.run(
        [sys.executable, os.path.join(mf.BENCH_DIR, "tools",
                                      "smallthinker_limit.py"), "--tiny",
         "--seeds", "5", "--low-seeds", "1", "--faults", "causal_only",
         "--out", str(out)],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-3000:]
    lines = {line["reading"]: line
             for line in json.loads(out.read_text())["lines"]}
    assert list(lines) == ["reference", "program", "low",
                           "fault:causal_only"]
    groups = _cfg()["reference"]["grad_groups"]
    assert set(lines["program"]["compared"]) == {
        "loss", "grad_norm", "moe_absent_route_share",
        "attn_window_out_rms", *groups}
    assert lines["program"]["correct"] is True
    assert max(lines["program"]["off"].values()) < 1e-5
    assert lines["low"]["correct"] is False
    assert lines["fault:causal_only"]["correct"] is False
    assert lines["fault:causal_only"]["compared"]["grad_norm_attn_qk"] > 0.01
    assert lines["low"]["compared"]["attn_window_out_rms"] > 2 ** -10
    assert sum(k.startswith("leaf:") for k in lines["program"]["off"]) == 43


# -- what the builder does at set-up ----

def test_the_builder_makes_the_embedding_at_unit_scale_and_nothing_else():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.smallthinker import SmallThinker, SmallThinkerConfig
    builder = mf.load_builder("smallthinker")
    model = SmallThinker(SmallThinkerConfig.tiny(dtype=jnp.float32))
    plain = jax.jit(model.init_params)(jax.random.key(5))
    params = builder.make_params(model, 5)
    again = builder.make_params(model, 5)
    assert float(jnp.std(params["wte"]["embedding"])) == pytest.approx(
        1.0, rel=0.05)
    np.testing.assert_allclose(params["wte"]["embedding"],
                               plain["wte"]["embedding"] * 50, rtol=1e-6)
    rest = [{k: v for k, v in p.items() if k != "wte"}
            for p in (params, plain, again)]
    for a, b, c in zip(*(jax.tree_util.tree_leaves(r) for r in rest)):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
    per_layer = builder.routes_by_layer(
        np.array([[6., 2., 4., 4.], [0., 0., 8., 8.]]), (0, 2))
    assert per_layer[0] == {"held_route_share": 0.5,
                            "load_max_over_mean": 1.5,
                            "held_max_over_mean": 1.5}
    assert per_layer[1]["held_route_share"] == 0.0
    assert per_layer[1]["held_max_over_mean"] is None


# -- the builder's refusal by the notes, the reference's keys ----

def test_the_builder_refuses_a_step_whose_windows_did_not_skip_the_band(
        monkeypatch):
    import jax
    from ray_tpu.parallel import make_mesh
    builder = mf.load_builder("smallthinker")
    cfg = _cfg()
    kernel = cfg["kernel"]
    good = dict(flash_path="multi_block", flash_window=4096,
                flash_band_blocks=70)
    builder.refuse_unless_band_skipped(good, kernel, 136)
    for bad, why in [
            ({**good, "flash_path": "xla"}, "the XLA path"),
            ({**good, "flash_window": "none"}, "no window reached it"),
            ({**good, "flash_window": 2048}, "another window"),
            ({**good, "flash_band_blocks": 136}, "masked, not skipped"),
            ({"flash_path": "multi_block"}, "no window's notes")]:
        with pytest.raises(RuntimeError, match="this cell measures"):
            builder.refuse_unless_band_skipped(bad, kernel, 136)
        del why
    traffic = mf.effective_traffic(
        mf.load_json(mf.traffic_path("b1-t16384")), True)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    notes = dict(attn_kind="window_global", flash_path="xla",
                 flash_layout="unequal_shapes")
    joyai = types.SimpleNamespace(
        step_notes=lambda: notes,
        with_first_change=mf.load_builder("joyai").with_first_change)
    monkeypatch.setattr(builder, "_joyai", lambda: joyai)
    # a rehearsal is let through: it runs on the CPU by design
    assert callable(builder.build(cfg, traffic, mesh, 0, tiny=True)[
        "reference"])
    tiny = builder.model_config
    monkeypatch.setattr(builder, "model_config",
                        lambda cfg, _: tiny(cfg, True))
    built = builder.build(cfg, traffic, mesh, 0, tiny=False)
    with pytest.raises(RuntimeError, match="not the 'multi_block' kernel"):
        built["reference"]({"params": None, "batch": None})
    assert set(built["shapes"]) >= {"moe_cost_per_step",
                                    "window_cost_per_step",
                                    "global_cost_per_step"}


def test_reference_returns_the_keys_the_step_reports():
    """``loop.py`` holds every key the reference returns against the
    first dispatch's metric of that name: each has to be one; and the
    low reading is another number (the rounder bites)."""
    import jax.numpy as jnp
    import numpy as np
    builder = mf.load_builder("smallthinker")
    ref = mf.load_reference("smallthinker")
    mcfg, model, loss_fn = builder.program(_cfg(), tiny=True)
    params = builder.make_params(model, 0)
    toks = np.random.default_rng(0).integers(0, 256, (2, 64), dtype=np.int32)
    batch = {"tokens": jnp.asarray(toks),
             "targets": jnp.asarray(np.roll(toks, -1, 1))}
    spec = builder.reference_spec(mcfg)
    out = ref.loss_and_grad_norm(params, batch, spec)
    loss, report_ = loss_fn(params, batch)
    assert set(out) == {"loss", "grad_norm", "moe_absent_route_share",
                        "attn_window_out_rms"}
    assert all(v.ndim == 0 for v in report_.values())
    from ray_tpu.models.smallthinker import smallthinker_loss_fn
    assert "moe_load" in smallthinker_loss_fn(model, ce_chunk=32)(
        params, batch)[1]
    assert out["loss"] == pytest.approx(float(loss), rel=1e-5)
    assert out["moe_absent_route_share"] == pytest.approx(
        float(report_["moe_absent_route_share"]))
    assert out["attn_window_out_rms"] == pytest.approx(
        float(report_["attn_window_out_rms"]), rel=1e-5)
    groups = _cfg()["reference"]["grad_groups"]
    with_step = ref.loss_and_grad_norm(
        params, batch, {**spec, "adamw": _cfg()["optimizer"],
                        "grad_groups": groups})
    assert set(with_step) == set(out) | {"update_norm", *groups}
    assert 0 < with_step["update_norm"] < 1
    assert all(0 < with_step[name] < with_step["grad_norm"]
               for name in groups)
    low = ref.loss_and_grad_norm(
        params, batch, {**spec, "operand_dtype": "float8_e4m3fn"})
    assert low["grad_norm"] != out["grad_norm"]
    assert low["grad_norm"] == pytest.approx(out["grad_norm"], rel=0.05)
    # the mask is the definition's
    rows, cols = np.arange(6), np.arange(6)
    assert ref.seen(rows, cols, 2).sum(1).tolist() == [1, 2, 2, 2, 2, 2]
    assert ref.seen(rows, cols, None).sum(1).tolist() == [1, 2, 3, 4, 5, 6]


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
        "loss", "grad_norm", "moe_absent_route_share", "update_norm",
        "attn_window_out_rms", "grad_norm_attn_qk"}
    # the step's own first update against the reference's AdamW step
    assert got["program_from"] == "first dispatch"
    assert got["program"]["update_norm"] == pytest.approx(
        got["plain_f32"]["update_norm"], rel=1e-4)
    assert "smallthinker routes by layer" in p.stderr
