"""granite-4.0-h-micro (``models/granite.py``): the system against the
benchmark's plain reference on seeded random weights; the published
config key for key; the four multipliers, the attention scale, "no
positions" and the tied table's two paths as planted faults that a key of
the cell's comparison catches; the one Mamba-2 mixer shared with
Nemotron-H. What a recomputed block keeps, the step's notes and scopes
are ``test_granite_remat.py``'s."""

import dataclasses
import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu import train
from ray_tpu.models import Granite, GraniteHybridConfig
from ray_tpu.models import granite as model_file
from ray_tpu.models import gpt2, nemotron_h
from ray_tpu.models.granite import granite_loss_fn

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmark"))
from benchlib import manifest as mf  # noqa: E402

F32 = dict(dtype=jnp.float32)
RTOL = 2.0 ** -9    # the cell's limit (configs/granite-4.0-h-micro.json)
CONFIG_FILE = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "granite-4.0-h-micro.json")
GROUPS = mf.load_json(CONFIG_FILE)["reference"]["grad_groups"]
KEYS = ("loss", "grad_norm", "mamba_out_rms", *GROUPS)


@pytest.fixture(scope="module")
def ref():
    return mf.load_reference("granite")


def _spec(cfg, **kw):
    return {**mf.load_builder("granite").reference_spec(cfg), **kw}


def _jittered(params, seed, by=0.1):
    """Every leaf moved off its initial value, so that the norms' scales
    and the biases say something."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return tree.unflatten([
        x + by * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


def _batch(seed, cfg, rows=2):
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, cfg.seq_len), dtype=np.int32)
    return {"tokens": jnp.asarray(toks),
            "targets": jnp.asarray(np.roll(toks, -1, 1))}


def _leaves_with_names(tree):
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in
            jax.tree_util.tree_leaves_with_path(tree)]


# -- the system against the plain reference ----

@pytest.mark.parametrize("seed, overrides", [
    (1, {"remat": True}),
    (2, {"seq_len": 50, "layer_types": ("mamba", "attention")}),
    (3, {"layer_types": ("attention", "mamba"), "ssm_groups": 2,
         "n_kv_head": 1})],
    ids=["four_layers_recomputed", "rows_not_whole_chunks",
         "two_groups_one_kv_head"])
def test_loss_and_every_gradient_leaf_are_the_references(ref, seed,
                                                         overrides):
    """At 1e-5 on the loss and 2e-4 of a leaf's largest entry: both
    sides are float32 at the highest matmul precision, and differ by
    the order of float32 sums (the chunked scan against the recurrence
    a token at a time)."""
    cfg = GraniteHybridConfig.tiny(**F32, **overrides)
    model = Granite(cfg)
    params = _jittered(model.init_params(jax.random.key(seed)), seed)
    batch = _batch(seed, cfg)
    spec = _spec(cfg)
    with jax.default_matmul_precision("highest"):
        (loss, report), grads = jax.jit(jax.value_and_grad(
            granite_loss_fn(model, ce_chunk=16), has_aux=True))(
                params, batch)
        logits = jax.jit(lambda p, t: model.apply({"params": p}, t))(
            params, batch["tokens"])
    want, want_grads = ref.loss_and_grads(params, batch, spec)
    want_logits, out_sq = jax.jit(
        lambda p, t: ref.forward(p, t, spec))(params, batch["tokens"])
    assert float(loss) == pytest.approx(want["loss"], rel=1e-5)
    assert float(optax.global_norm(grads)) == pytest.approx(
        want["grad_norm"], rel=1e-4)
    np.testing.assert_allclose(logits, want_logits, atol=5e-5)
    assert float(report["mamba_out_rms"]) == pytest.approx(
        want["mamba_out_rms"], rel=1e-5)
    assert out_sq.shape == (cfg.layer_types.count("mamba"),)
    want_leaves = dict(_leaves_with_names(want_grads))
    for name, got in _leaves_with_names(grads):
        scale = max(float(np.abs(want_leaves[name]).max()), 1e-3)
        np.testing.assert_allclose(got, want_leaves[name],
                                   atol=2e-4 * scale, err_msg=name)
    assert len(want_leaves) == len(jax.tree_util.tree_leaves(grads))


def test_update_norm_is_the_references_first_adamw_step(ref):
    cfg = GraniteHybridConfig.tiny(**F32)
    model = Granite(cfg)
    params = _jittered(model.init_params(jax.random.key(4)), 4)
    batch = _batch(4, cfg)
    o = mf.load_json(CONFIG_FILE)["optimizer"]
    opt = optax.chain(
        optax.clip_by_global_norm(o["clip_global_norm"]),
        optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                    weight_decay=o["weight_decay"]))
    step = train.make_train_step(granite_loss_fn(model, ce_chunk=16), opt)
    with jax.default_matmul_precision("highest"):
        new, _ = step(train.init_train_state(
            jax.tree_util.tree_map(jnp.copy, params), opt, None), batch)
    got = float(optax.global_norm(jax.tree_util.tree_map(
        lambda a, b: b - a, params, new.params)))
    want = ref.loss_and_grad_norm(params, batch, _spec(cfg, adamw=o))
    assert got == pytest.approx(want["update_norm"], rel=1e-4)


# -- the published configuration ----

def test_the_preset_carries_every_value_of_the_published_config():
    """``hf_config()`` of the preset against the file's ``published``
    (the catalog row's ``config``), key for key; the cut gives the
    file's own top-level keys and 797,850,560 parameters."""
    file = mf.load_json(CONFIG_FILE)
    full = GraniteHybridConfig.granite_4_0_h_micro()
    assert full.hf_config() == file["published"]
    assert len(file["published"]) == 33
    assert full.n_layer == 40 and full.head_dim == 64
    assert [i for i, k in enumerate(full.layer_types)
            if k == "attention"] == [5, 15, 25, 35]
    assert (full.mamba_inner, full.conv_width) == (4096, 4096 + 2 * 128)
    per = full.layer_params()
    assert per["mamba"] == 25_847_232 and per["attention"] == 10_485_760
    assert per["mlp"] == 50_331_648 and per["norms"] == 4096
    assert full.num_params() == pytest.approx(3.19e9, rel=0.01)
    cut = mf.load_builder("granite").model_config(file, tiny=False)
    assert cut.layer_types == full.layer_types[:10] and cut.remat
    assert cut.num_params() == 797_850_560
    assert file["reduced"][0].startswith("num_hidden_layers 40 -> 10")
    assert file["reduced"][1].startswith("vocab_size 100352 -> 25088")
    assert len(file["reduced"]) == 2


def test_parameters_are_the_configs_count():
    cfg = GraniteHybridConfig.tiny()
    params = jax.eval_shape(Granite(cfg).init_params, jax.random.key(0))
    assert sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(
        params)) == cfg.num_params()
    assert set(params) == {"wte", "norm_f", "h_0", "h_1", "h_2", "h_3"}
    assert "lm_head" not in params          # the table is tied


@pytest.mark.parametrize("field, value, error", [
    ("positions", "rope", NotImplementedError),
    ("num_experts", 8, NotImplementedError),
    ("layer_types", ("mamba", "moe"), ValueError),
    ("mamba_heads", 12, ValueError), ("n_kv_head", 3, ValueError)])
def test_what_the_stack_does_not_hold_is_refused_by_name(field, value, error):
    with pytest.raises(error):
        GraniteHybridConfig.tiny(**{field: value})


# -- one Mamba-2 mixer ----

def test_there_is_one_mamba2_mixer_and_both_configs_run_it():
    """``models/granite.py`` imports Nemotron's mixer; both configs
    carry every field ``Mamba2Dims`` lists; the Nemotron tree is what it
    was (a Mamba layer's leaves and their shapes at the tiny preset)."""
    assert model_file.Mamba2Mixer is nemotron_h.Mamba2Mixer
    from ray_tpu.models import NemotronH, NemotronHConfig
    fields = nemotron_h.Mamba2Dims.__annotations__
    for cfg in (NemotronHConfig.tiny(), GraniteHybridConfig.tiny()):
        assert isinstance(cfg, nemotron_h.Mamba2Dims)
        assert all(hasattr(cfg, f) for f in fields)
    n = NemotronHConfig.tiny()
    params = jax.eval_shape(NemotronH(n).init_params, jax.random.key(0))
    assert {k: v.shape for k, v in _leaves_with_names(
        params["h_0"]["mamba"])} == {
        "['A_log']": (8,), "['D']": (8,), "['dt_bias']": (8,),
        "['conv']['bias']": (128,), "['conv']['kernel']": (4, 128),
        "['gate_norm']['scale']": (64,),
        "['in_proj']['kernel']": (64, 64 + 128 + 8),
        "['out_proj']['kernel']": (64, 64)}
    g = GraniteHybridConfig.tiny()
    ours = jax.eval_shape(Granite(g).init_params, jax.random.key(0))
    assert set(ours["h_0"]["mamba"]) == set(params["h_0"]["mamba"])


def test_the_mixers_names_are_the_identity_for_nemotron(monkeypatch):
    """``Mamba2Mixer`` names the three parts of ``in_proj``'s product
    for a recomputed block's policy (``ops/remat.py::IN_PROJ_PARTS``).
    Nemotron's blocks are under no policy: with the names in place its
    loss and every gradient leaf on one seed are bit for bit what they
    are with the names taken out, its jaxpr differs by those ``name``
    equations alone (no ``checkpoint`` more, and none whose policy could
    read them), and the lowered program is the same text but for the
    counters in its private functions' symbols (``@silu_105``: lowering
    numbers them by the equations traced so far, a ``name`` among them,
    and XLA inlines them)."""
    import re
    from conftest import equations
    from ray_tpu.models import NemotronH, NemotronHConfig
    from ray_tpu.models.nemotron_h import nemotron_h_loss_fn
    from ray_tpu.ops.remat import IN_PROJ_PARTS
    cfg = NemotronHConfig.tiny(**F32)
    params = _jittered(NemotronH(cfg).init_params(jax.random.key(3)), 3,
                       by=0.02)
    batch = _batch(3, cfg)

    def program():
        fn = jax.value_and_grad(
            nemotron_h_loss_fn(NemotronH(cfg), ce_chunk=16), has_aux=True)
        eqns = list(equations(jax.make_jaxpr(fn)(params, batch).jaxpr))
        return (eqns, jax.jit(fn).lower(params, batch).as_text(),
                jax.jit(fn)(params, batch))

    eqns, text, numbers = program()
    named = [e.params["name"] for e in eqns if e.primitive.name == "name"]
    layers = cfg.pattern.count("M")
    assert layers and sorted(set(named) & set(IN_PROJ_PARTS)) == sorted(
        IN_PROJ_PARTS)
    monkeypatch.setattr(nemotron_h, "checkpoint_name", lambda x, _: x)
    bare_eqns, bare_text, bare_numbers = program()
    assert not {e.params["name"] for e in bare_eqns
                if e.primitive.name == "name"} & set(IN_PROJ_PARTS)
    assert [e.primitive.name for e in eqns if not (
        e.primitive.name == "name"
        and e.params["name"] in IN_PROJ_PARTS)] == [
            e.primitive.name for e in bare_eqns]
    policies = [e.params["policy"] for e in eqns
                if e.primitive.name == "remat2" and e.params["policy"]]
    assert len(policies) == layers      # the XLA scan's own, one a layer
    unnumbered = functools.partial(re.sub, r"@(\w+?)_\d+\b", r"@\1")
    assert unnumbered(text) == unnumbered(bare_text)
    got, want = map(jax.tree_util.tree_leaves, (numbers, bare_numbers))
    assert len(got) == len(want) > 20       # (a router's bias leaf is 0)
    assert all(bool((a == b).all()) for a, b in zip(got, want))


def test_nemotrons_loss_sows_nothing_and_granites_reports_the_scans_rms():
    """``out_sq`` is sown only where ``stats`` is mutable: Nemotron's
    traced loss holds no mean of ``y``'s square."""
    from ray_tpu.models import NemotronH, NemotronHConfig
    from ray_tpu.models.nemotron_h import nemotron_h_loss_fn
    n = NemotronHConfig.tiny(**F32)
    model = NemotronH(n)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    _, sown = jax.eval_shape(
        lambda p, t: model.apply({"params": p}, t, return_hidden=True,
                                 mutable=["moe"]),
        params, jax.ShapeDtypeStruct((2, n.seq_len), jnp.int32))
    assert set(sown) == {"moe"}
    batch = {k: jax.ShapeDtypeStruct((2, n.seq_len), jnp.int32)
             for k in ("tokens", "targets")}
    _, report = jax.eval_shape(nemotron_h_loss_fn(model, ce_chunk=16),
                               params, batch)
    assert "mamba_out_rms" not in report


# -- planted faults ----

def _numbers(cfg, params, batch):
    """The keys the cell compares, from the program's own step."""
    model = Granite(cfg)
    opt = optax.sgd(0.0)
    step = train.make_train_step(granite_loss_fn(model, ce_chunk=16), opt,
                                 grad_groups=GROUPS)
    with jax.default_matmul_precision("highest"):
        # the step donates its state: a copy of the parameters goes in
        _, metrics = step(train.init_train_state(
            jax.tree_util.tree_map(jnp.copy, params), opt, None), batch)
    return {k: float(metrics[k]) for k in KEYS}


def _pushed(params, by=4.0):
    """The jittered parameters with the attention layers' ``q`` and ``k``
    kernels ``by`` times as large: at the initialisers' values the scores
    are near zero, every softmax is near uniform, and neither the scale
    nor a rotation of ``q`` and ``k`` moves anything."""
    params = jax.tree_util.tree_map(lambda x: x, params)
    for block in params.values():
        if "attn" in block:
            for name in ("q", "k"):
                block["attn"][name]["kernel"] *= by
    return params


def _rotated(orig):
    """RoPE applied to ``q`` and ``k`` in front of the core."""
    from ray_tpu.models.llama import apply_rope_half, rope_freqs

    def attn_fn(mesh, scale):
        core = orig(mesh, scale)

        def rotating(q, k, v):
            angles = rope_freqs(q.shape[-1], q.shape[1], 10000.0)
            return core(apply_rope_half(q, angles),
                        apply_rope_half(k, angles), v)
        return rotating
    return attn_fn


def _head_path_cut(orig):
    """The tied leaf fed by the lookup alone: the head reads a copy of
    the table that hands no gradient back."""
    return lambda hidden, table, *a, **kw: orig(
        hidden, jax.lax.stop_gradient(table), *a, **kw)


def _lookup_path_cut(orig):
    """The tied leaf fed by the head alone."""
    class Cut(orig):
        def __call__(self, tokens):
            return jax.lax.stop_gradient(super().__call__(tokens))
    return Cut


# fault -> the config's fields it changes, or (where it is planted, the
# name there, old -> new)
FAULTS = {
    "embedding_multiplier_1": {"embedding_multiplier": 1.0},
    "residual_multiplier_1": {"residual_multiplier": 1.0},
    "logits_scaling_1": {"logits_scaling": 1.0},
    "the_default_attention_scale": {"attention_multiplier": 16 ** -0.5},
    "rope_applied": (model_file, "_attn_fn", _rotated),
    "the_tied_leaf_fed_by_the_lookup_alone": (
        gpt2, "chunked_cross_entropy", _head_path_cut),
    "the_tied_leaf_fed_by_the_head_alone": (
        model_file.nn, "Embed", _lookup_path_cut),
}


@pytest.fixture(scope="module")
def fault_case():
    cfg = GraniteHybridConfig.tiny(**F32)
    params = _pushed(_jittered(
        Granite(cfg).init_params(jax.random.key(11)), 11))
    batch = _batch(11, cfg)
    return cfg, params, batch, _numbers(cfg, params, batch)


def test_the_sound_program_is_inside_the_limit_on_every_key(ref, fault_case):
    cfg, params, batch, got = fault_case
    want = ref.loss_and_grad_norm(params, batch,
                                  _spec(cfg, grad_groups=GROUPS))
    assert set(want) == set(KEYS)
    for key, value in got.items():
        assert abs(value - want[key]) <= RTOL * abs(want[key]), key
    assert min(want.values()) > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_moves_a_key_past_twice_the_limit(
        fault, fault_case, monkeypatch):
    """Each fault of ISSUE 63's list, planted while the program is
    traced: at least one of the cell's keys leaves the limit by a factor
    of two (the sound program's numbers stand in for the reference's,
    which the test above holds them to)."""
    cfg, params, batch, sound = fault_case
    if isinstance(FAULTS[fault], dict):
        cfg = dataclasses.replace(cfg, **FAULTS[fault])
    else:
        where, name, make = FAULTS[fault]
        monkeypatch.setattr(where, name, make(getattr(where, name)))
    got = _numbers(cfg, params, batch)
    off = {k: abs(got[k] - sound[k]) / abs(sound[k]) for k in KEYS}
    assert max(off.values()) > 2 * RTOL, off


def test_the_tied_leafs_gradient_is_the_sum_of_its_two_paths(monkeypatch):
    """The table's gradient with both paths live is the lookup's alone
    plus the head's alone, and neither is zero."""
    cfg = GraniteHybridConfig.tiny(layer_types=("mamba", "attention"), **F32)
    params = _jittered(Granite(cfg).init_params(jax.random.key(6)), 6)
    batch = _batch(6, cfg)

    def table_grad():
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.grad(lambda p: granite_loss_fn(
                Granite(cfg), ce_chunk=16)(p, batch)[0]))(
                    params)["wte"]["embedding"]

    both = table_grad()
    with monkeypatch.context() as patch:
        patch.setattr(gpt2, "chunked_cross_entropy", _head_path_cut(
            gpt2.chunked_cross_entropy))
        lookup = table_grad()
    with monkeypatch.context() as patch:
        patch.setattr(model_file.nn, "Embed",
                      _lookup_path_cut(model_file.nn.Embed))
        head = table_grad()
    assert float(jnp.abs(lookup).max()) > 0 and float(jnp.abs(head).max()) > 0
    np.testing.assert_allclose(lookup + head, both,
                               atol=1e-5 * float(jnp.abs(both).max()))


def test_float8_operands_fail_at_least_one_key_of_the_cells(ref):
    """The reference with its matmul operands rounded to
    ``float8_e4m3fn``, the precision under the configuration's bfloat16,
    is not correct at the cell's limit."""
    cfg = GraniteHybridConfig.tiny(**F32)
    params = _jittered(Granite(cfg).init_params(jax.random.key(12)), 12)
    batch = _batch(12, cfg)
    spec = _spec(cfg, grad_groups=GROUPS)
    want = ref.loss_and_grad_norm(params, batch, spec)
    low = ref.loss_and_grad_norm(
        params, batch, {**spec, "operand_dtype": "float8_e4m3fn"})
    off = {k: abs(low[k] - want[k]) / abs(want[k]) for k in want}
    assert max(off.values()) > RTOL, off
