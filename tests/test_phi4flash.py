"""Phi-4-mini-flash (``models/phi4flash.py``): the system against the
benchmark's plain reference on seeded random weights, the layer kinds the
architecture's rule gives, differential attention against two masked
softmaxes, the gradient that reaches layer ``F`` from the cross-decoder,
and what the keys of the cell's comparison see of a planted fault."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import matmuls, same_bits

from ray_tpu import train
from ray_tpu.models import Phi4Flash, Phi4FlashConfig
from ray_tpu.models import phi4flash as model_file
from ray_tpu.models.phi4flash import phi4flash_loss_fn
from ray_tpu.ops import attention, mamba1
from ray_tpu.util import tracing

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmark"))
from benchlib import manifest as mf  # noqa: E402

F32 = dict(dtype=jnp.float32)
RTOL = 2.0 ** -9    # the cell's limit (configs/phi-4-mini-flash-reasoning.json)
GROUPS = {
    "grad_norm_mamba_ssm": "^h_[0-9]+/mamba/",
    "grad_norm_attn_diff": "^h_[0-9]+/attn/(lambda_[qk][12]|subln|out/kernel)$",
    "grad_norm_yoco_kv": "^h_5/attn/qkv/kernel$"}
KEYS = ("loss", "grad_norm", "mamba_out_rms", *GROUPS)


@pytest.fixture(scope="module")
def ref():
    return mf.load_reference("phi4flash")


def _spec(cfg, **kw):
    return {**mf.load_builder("phi4flash").reference_spec(cfg), **kw}


def _jittered(params, seed, by=0.1):
    """Every leaf moved off its initial value, so that the norms'
    scales and the biases say something."""
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
    (0, {}), (1, {"remat": True}), (2, {"seq_len": 28}),
    (3, {"n_layer": 12, "window": 5})],
    ids=["eight_layers", "blocks_recomputed", "rows_not_whole_chunks",
         "twelve_layers_an_odd_window"])
def test_loss_and_every_gradient_leaf_are_the_references(ref, seed,
                                                         overrides):
    cfg = Phi4FlashConfig.tiny(**F32, **overrides)
    model = Phi4Flash(cfg)
    params = _jittered(model.init_params(jax.random.key(seed)), seed)
    batch = _batch(seed, cfg)
    with jax.default_matmul_precision("highest"):
        (loss, report), grads = jax.jit(jax.value_and_grad(
            phi4flash_loss_fn(model, ce_chunk=16), has_aux=True))(
                params, batch)
        logits = model.apply({"params": params}, batch["tokens"])
    spec = _spec(cfg)
    want, want_grads = ref.loss_and_grads(params, batch, spec)
    want_logits, out_sq = ref.forward(params, batch["tokens"], spec)
    assert float(loss) == pytest.approx(want["loss"], rel=1e-5)
    assert float(optax.global_norm(grads)) == pytest.approx(
        want["grad_norm"], rel=1e-4)
    np.testing.assert_allclose(logits, want_logits, atol=5e-5)
    assert float(report["mamba_out_rms"]) == pytest.approx(
        want["mamba_out_rms"], rel=1e-5)
    assert out_sq.shape == (cfg.layer_kinds.count("M"),)
    want_leaves = dict(_leaves_with_names(want_grads))
    for name, got in _leaves_with_names(grads):
        scale = max(float(np.abs(want_leaves[name]).max()), 1e-3)
        np.testing.assert_allclose(got, want_leaves[name],
                                   atol=2e-4 * scale, err_msg=name)
    assert len(want_leaves) == len(jax.tree_util.tree_leaves(grads))


def test_the_layer_kinds_are_the_rules_at_eight_and_at_thirty_two(ref):
    small = Phi4FlashConfig.phi_4_mini_flash_reasoning(n_layer=8)
    assert small.layer_kinds == "MSMSMFGX"
    assert (small.memory_layer, small.kv_layer) == (4, 5)
    full = Phi4FlashConfig.phi_4_mini_flash_reasoning()
    assert full.layer_kinds == "MS" * 8 + "MF" + "GX" * 7
    assert (full.memory_layer, full.kv_layer) == (16, 17)
    assert {k: full.layer_kinds.count(k) for k in "MSFGX"} == {
        "M": 9, "S": 8, "F": 1, "G": 7, "X": 7}
    for cfg in (small, full, Phi4FlashConfig.tiny()):
        assert cfg.layer_kinds == "".join(
            ref.kind_of({"n_layer": cfg.n_layer}, i)
            for i in range(cfg.n_layer))
    assert full.lambda_init(0) == pytest.approx(0.2)
    assert full.lambda_init(5) == pytest.approx(0.8 - 0.6 * math.exp(-1.5))
    assert ref.lambda_init(5) == full.lambda_init(5)
    with pytest.raises(ValueError, match="pairs in both halves"):
        Phi4FlashConfig.tiny(n_layer=6)
    with pytest.raises(ValueError, match="do not pair"):
        Phi4FlashConfig.tiny(n_head=6, n_kv_head=4)


def test_parameters_are_the_configs_count_and_the_published_models():
    cfg = Phi4FlashConfig.tiny()
    params = jax.eval_shape(Phi4Flash(cfg).init_params, jax.random.key(0))
    assert sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(
        params)) == cfg.num_params()
    full = Phi4FlashConfig.phi_4_mini_flash_reasoning()
    per = full.layer_params()
    assert per["M"] == 41_241_600 and per["S"] == 19_668_864
    assert per["G"] == 26_214_400 and per["X"] == 13_112_704
    assert per["mlp"] == 78_643_200
    # "3.8B": 9 M, 8 S + F, 7 G, 7 X, 32 MLPs, the tied table once
    assert full.num_params() == pytest.approx(3.85e9, rel=0.01)
    cut = Phi4FlashConfig.phi_4_mini_flash_reasoning(n_layer=8,
                                                     vocab_size=25088)
    assert cut.num_params() == 915_516_416


# -- differential attention ----

def _two_softmaxes(q, k, v, lam, lam_init, scale, window, eps=1e-5):
    """The definition, a query pair at a time: pair ``j`` reads
    key/value pair ``j // rep``."""
    b, t, heads, d = q.shape
    pairs, kv_pairs = heads // 2, k.shape[2] // 2
    rows, keys = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = keys <= rows
    if window is not None:
        seen &= keys > rows - window
    out = []
    for j in range(pairs):
        i = j // (pairs // kv_pairs)
        v12 = jnp.concatenate([v[:, :, 2 * i], v[:, :, 2 * i + 1]], -1)

        def weights(qh, kh):
            s = jnp.einsum("btd,bsd->bts", qh, kh) / math.sqrt(d)
            return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        a1 = weights(q[:, :, 2 * j], k[:, :, 2 * i])
        a2 = weights(q[:, :, 2 * j + 1], k[:, :, 2 * i + 1])
        o = (a1 - lam * a2) @ v12
        o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
        out.append(o * scale * (1 - lam_init))
    return jnp.concatenate(out, -1)


@pytest.mark.parametrize("window", [None, 5, 8, 64],
                         ids=["causal", "window_5", "window_8",
                              "window_past_the_row"])
def test_differential_attention_is_two_masked_softmaxes_a_pair(window,
                                                               monkeypatch):
    ks = jax.random.split(jax.random.key(0), 5)
    b, t, d = 2, 24, 8
    q = jax.random.normal(ks[0], (b, t, 12, d))     # 6 query pairs
    k = jax.random.normal(ks[1], (b, t, 6, d))      # 3 key/value pairs
    v = jax.random.normal(ks[2], (b, t, 6, d))
    scale = 1.0 + 0.1 * jax.random.normal(ks[3], (2 * d,))
    lam, lam_init = jnp.float32(0.37), 0.61
    said = {}
    monkeypatch.setattr(tracing, "note_trace", said.update)
    with jax.default_matmul_precision("highest"):
        got = attention.differential_attention(
            q, k, v, lam, lam_init, scale, window=window)
        want = _two_softmaxes(q, k, v, lam, lam_init, scale, window)
    assert got.shape == (b, t, 6 * 2 * d)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert said["attn_pairs"] == [6, 3] and said["attn_products"] == 4
    assert said["attn_calls"] == 1
    with pytest.raises(ValueError, match="pairs adjacent heads"):
        attention.differential_attention(q[:, :, :5], k, v, lam, lam_init,
                                         scale)


def test_the_products_run_in_the_flash_kernels_with_their_window(
        monkeypatch):
    """On a TPU backend the four products are one multi-block flash
    call over twice the heads (here: interpreted, at a block of 64
    rows), under the window's band."""
    from ray_tpu.ops.pallas import flash_attention as kernel_fn
    ks = jax.random.split(jax.random.key(1), 4)
    b, t, d = 1, 256, 64
    q = jax.random.normal(ks[0], (b, t, 4, d))
    k = jax.random.normal(ks[1], (b, t, 2, d))
    v = jax.random.normal(ks[2], (b, t, 2, d))
    scale = jnp.ones((2 * d,))
    calls = []

    def flash(q, k, v, window=None, **kw):
        calls.append((q.shape, window))
        return kernel_fn(q, k, v, window=window, block=64, interpret=True)
    monkeypatch.setattr(attention, "causal_attention", flash)
    with jax.default_matmul_precision("highest"):
        got = attention.differential_attention(q, k, v, 0.5, 0.7, scale,
                                               window=100)
        want = _two_softmaxes(q, k, v, 0.5, 0.7, scale, 100)
    assert calls == [((b, t, 8, d), 100)]
    np.testing.assert_allclose(got, want, atol=3e-3)


# -- what crosses blocks ----

def _numbers(cfg, params, batch):
    """The keys the cell compares, from the program's own step."""
    model = Phi4Flash(cfg)
    opt = optax.sgd(0.0)
    step = train.make_train_step(phi4flash_loss_fn(model, ce_chunk=16),
                                 opt, grad_groups=GROUPS)
    with jax.default_matmul_precision("highest"):
        # the step donates its state: a copy of the parameters goes in
        _, metrics = step(train.init_train_state(
            jax.tree_util.tree_map(jnp.copy, params), opt, None), batch)
    return {k: float(metrics[k]) for k in KEYS}


def _shifted_rows(z):
    return jnp.pad(z, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def _one_chunk_late(orig):
    def walk(one, state, rows):
        def late(carry, r):
            older, newer = carry
            _, y = one(older, r)            # reads the state a chunk late
            return (newer, one(newer, r)[0]), y
        (_, last), y = jax.lax.scan(late, (state, state), rows)
        return last, y
    return walk


def _attention_with(**changes):
    """``differential_attention`` as the model calls it, with some of
    its arguments changed by functions of the call's own."""
    def make(orig):
        def attend(q, k, v, lam, lam_init, subln, **kw):
            a = dict(q=q, k=k, v=v, lam=lam, lam_init=lam_init, kw=kw)
            for name, change in changes.items():
                if name in kw:
                    kw[name] = change(a)
                else:
                    a[name] = change(a)
            return orig(a["q"], a["k"], a["v"], a["lam"], a["lam_init"],
                        subln, **kw)
        return attend
    return make


def _cut_when_cross(name):
    return lambda a: (jax.lax.stop_gradient(a[name])
                      if a["kw"]["scope"] == "cross" else a[name])


# (module, attribute, what replaces it given the original)
FAULTS = {
    "the_decay_from_the_previous_tokens_delta": (
        mamba1, "_mamba1_decay",
        lambda orig: lambda dt, A: orig(_shifted_rows(dt), A)),
    "the_input_term_without_delta": (
        mamba1, "_mamba1_write",
        lambda orig: lambda dt, x, B: orig(jnp.ones_like(dt), x, B)),
    "a_chunks_state_handed_on_one_chunk_late": (
        mamba1, "_mamba1_walk", _one_chunk_late),
    "the_skip_left_out": (
        mamba1, "mamba1_scan",
        lambda orig: lambda x, dt, A, B, C, D, **kw: orig(
            x, dt, A, B, C, jnp.zeros_like(D), **kw)),
    "the_memory_of_layer_2_for_the_last_scans": (
        Phi4FlashConfig, "memory_layer", lambda orig: property(lambda c: 2)),
    "the_windowed_layers_k_and_v_for_the_full_layers": (
        Phi4FlashConfig, "kv_layer",
        lambda orig: property(lambda c: c.n_layer // 2 - 1)),
    "the_cross_layers_gradient_cut_off_from_the_full_layer": (
        model_file, "differential_attention",
        _attention_with(k=_cut_when_cross("k"), v=_cut_when_cross("v"))),
    "lambda_without_its_exponentials": (
        model_file, "_lambda",
        lambda orig: lambda vec, lam_init: (
            jnp.sum(vec["q1"] * vec["k1"]) - jnp.sum(vec["q2"] * vec["k2"])
            + lam_init)),
    "one_less_lambda_init_left_out": (
        model_file, "differential_attention",
        _attention_with(lam_init=lambda a: 0.0)),
    "query_pair_j_reads_pair_j_mod_the_pairs": (
        attention, "_to_query_pairs",
        lambda orig: lambda x, rep: jnp.tile(
            x, (1, 1, rep) + (1,) * (x.ndim - 3))),
    "a_window_one_key_short": (
        model_file, "differential_attention",
        _attention_with(window=lambda a: a["kw"]["window"]
                        and a["kw"]["window"] - 1)),
    "a_window_one_key_long": (
        model_file, "differential_attention",
        _attention_with(window=lambda a: a["kw"]["window"]
                        and a["kw"]["window"] + 1)),
}


def _pushed(params, by=3.0):
    """The jittered parameters with ``W_x`` (so ``B``, ``C`` and
    ``delta``), ``W_dt`` and the lambda vectors ``by`` times as large:
    where the state is a real part of ``y``, ``Delta`` differs from
    token to token and ``exp`` is not its own first-order term. At the
    initialisers' values a scan's output is nearly ``D * x`` and a
    fault in the recurrence moves little of anything."""
    params = jax.tree_util.tree_map(lambda x: x, params)
    for block in params.values():
        if "mamba" in block:
            for name in ("x_proj", "dt_proj"):
                block["mamba"][name]["kernel"] *= by
        if "attn" in block:
            for name in ("q1", "k1", "q2", "k2"):
                block["attn"][f"lambda_{name}"] *= by
    return params


@pytest.fixture(scope="module")
def fault_case():
    cfg = Phi4FlashConfig.tiny(**F32)
    params = _pushed(_jittered(
        Phi4Flash(cfg).init_params(jax.random.key(11)), 11))
    batch = _batch(11, cfg)
    return cfg, params, batch, _numbers(cfg, params, batch)


def test_the_sound_program_is_inside_the_limit_on_every_key(ref, fault_case):
    cfg, params, batch, got = fault_case
    want = ref.loss_and_grad_norm(params, batch,
                                  _spec(cfg, grad_groups=GROUPS))
    assert set(want) == set(KEYS)
    for key, value in got.items():
        assert abs(value - want[key]) <= RTOL * abs(want[key]), key
    assert want["grad_norm_yoco_kv"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_moves_a_key_past_twice_the_limit(
        fault, fault_case, monkeypatch):
    """Each fault of ISSUE 48's list, planted while the program is
    traced: at least one of the cell's keys leaves the limit by a factor
    of two (the sound program's numbers stand in for the reference's,
    which the test above holds them to)."""
    cfg, params, batch, sound = fault_case
    where, name, make = FAULTS[fault]
    monkeypatch.setattr(where, name, make(getattr(where, name)))
    got = _numbers(cfg, params, batch)
    off = {k: abs(got[k] - sound[k]) / abs(sound[k]) for k in KEYS}
    assert max(off.values()) > 2 * RTOL, off


def test_the_full_layers_qkv_gets_gradient_from_the_cross_layer(
        fault_case, monkeypatch):
    """``X`` reads ``F``'s ``K, V``: cut off, the key and value columns
    of ``F``'s ``W_qkv`` lose that part of their gradient, the query
    columns (which only ``F`` itself reads) lose nothing they had."""
    cfg, params, batch, _ = fault_case
    q_w = cfg.n_head * cfg.head_dim

    def f_qkv_grad():
        loss_fn = phi4flash_loss_fn(Phi4Flash(cfg), ce_chunk=16)
        with jax.default_matmul_precision("highest"):
            g = jax.grad(lambda p: loss_fn(p, batch)[0])(params)
        return np.asarray(g[f"h_{cfg.kv_layer}"]["attn"]["qkv"]["kernel"])
    whole = f_qkv_grad()
    where, name, make = FAULTS[
        "the_cross_layers_gradient_cut_off_from_the_full_layer"]
    monkeypatch.setattr(where, name, make(getattr(where, name)))
    cut = f_qkv_grad()
    from_x = whole - cut
    assert np.abs(from_x[:, q_w:]).max() > 1e-2 * np.abs(whole[:, q_w:]).max()
    # the query columns change only through the stream, never vanish
    assert np.abs(cut[:, :q_w]).max() > 0


def test_float8_operands_fail_at_least_one_key_of_the_cells(ref):
    """The reference with its matmul operands rounded to
    ``float8_e4m3fn``, the precision under the configuration's bfloat16,
    is not correct at the cell's limit."""
    cfg = Phi4FlashConfig.tiny(**F32)
    params = _jittered(Phi4Flash(cfg).init_params(jax.random.key(12)), 12)
    batch = _batch(12, cfg)
    spec = _spec(cfg, grad_groups=GROUPS)
    want = ref.loss_and_grad_norm(params, batch, spec)
    low = ref.loss_and_grad_norm(
        params, batch, {**spec, "operand_dtype": "float8_e4m3fn"})
    off = {k: abs(low[k] - want[k]) / abs(want[k]) for k in want}
    assert max(off.values()) > RTOL, off


def test_the_references_recurrence_is_the_tests_own(ref):
    """``references/phi4flash.py::recurrence`` (nested blocks under
    ``jax.checkpoint``) against a plain Python loop over the tokens."""
    ks = jax.random.split(jax.random.key(3), 5)
    b, t, c, n = 2, 20, 6, 3
    x = jax.random.normal(ks[0], (b, t, c))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, c)))
    A = -jnp.exp(jax.random.normal(ks[2], (c, n)))
    B, C = (jax.random.normal(k, (b, t, n)) for k in ks[3:])
    h, want = jnp.zeros((b, c, n)), []
    for i in range(t):
        h = (jnp.exp(dt[:, i, :, None] * A) * h
             + (dt[:, i] * x[:, i])[..., None] * B[:, i, None, :])
        want.append(jnp.sum(h * C[:, i, None, :], -1))
    np.testing.assert_allclose(ref.recurrence(x, dt, A, B, C),
                               jnp.stack(want, 1), rtol=1e-5, atol=1e-5)


# -- the step, its notes and its scopes ----

def test_the_step_reports_the_keys_and_notes_what_the_layers_are(
        monkeypatch):
    cfg = Phi4FlashConfig.tiny(**F32, remat=True)
    model = Phi4Flash(cfg)
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    opt = optax.adamw(1e-3)
    step = train.make_train_step(phi4flash_loss_fn(model, ce_chunk=16), opt,
                                 grad_groups=GROUPS)
    state = train.init_train_state(model.init_params(jax.random.key(0)), opt,
                                   None)
    _, metrics = step(state, _batch(0, cfg))
    assert set(KEYS) | {"lm_loss"} <= set(metrics)
    assert all(np.isfinite(float(metrics[k])) for k in KEYS)
    assert notes["layer_pattern"] == "MSMSMFGX"
    assert notes["attn_kind"] == "differential" and notes["attn_window"] == 8
    assert notes["ssm_kind"] == "mamba1" and notes["ssm_tokens"] == 2 * 32
    assert (notes["ssm_inner"], notes["ssm_state"], notes["ssm_dt_rank"],
            notes["ssm_chunk"]) == (64, 4, 2, 8)
    assert notes["ssm_path"] == "xla_chunked"
    assert notes["attn_pairs"] == [4, 2] and notes["attn_products"] == 4
    assert (notes["yoco_memory_layer"], notes["yoco_kv_layer"]) == (4, 5)
    assert notes["blocks_remat"] is True


@pytest.mark.parametrize("remat, keeps", [
    (True, "mlp_gate_up,mixer_in_proj,mixer_stream,attn_q,attn_k,attn_v,"
     "mamba1_scan_out,mamba1_scan_states,attn_out,attn_lse"),
    (False, "")],
    ids=["recomputed", "kept_whole"])
def test_a_recomputed_block_says_what_its_policy_keeps(remat, keeps,
                                                       monkeypatch):
    """``blocks_remat_keeps`` beside ``blocks_remat``: the names a
    recomputed block's policy keeps (the MLP's ``gate_up`` product, then
    the attention cores' output and row statistics), and every block's
    checkpoint carries a policy; nothing where the blocks are not
    recomputed."""
    cfg = Phi4FlashConfig.tiny(remat=remat, **F32)
    model = Phi4Flash(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    traced = jax.make_jaxpr(lambda p, t: model.apply(
        {"params": p}, t, return_hidden=True,
        mutable=["moe", "stats"])[0])(params, _batch(0, cfg)["tokens"])
    assert notes["blocks_remat"] is remat
    assert notes["blocks_remat_keeps"] == keeps
    with_policy = [e for e in traced.jaxpr.eqns
                   if e.primitive.name == "remat2" and e.params["policy"]]
    assert len(with_policy) == (cfg.n_layer if remat else 0)


def _mlp_forwards(remat) -> int:
    """The MLPs' forward matmuls (``gate_up``, ``down``) in the traced
    loss and gradient of the tiny model, at an MLP width no other
    projection of it has."""
    cfg = Phi4FlashConfig.tiny(remat=remat, mlp_width=48, **F32)
    model = Phi4Flash(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    t, d, f = cfg.seq_len, cfg.n_embd, cfg.mlp_width
    traced = jax.make_jaxpr(jax.value_and_grad(
        phi4flash_loss_fn(model, ce_chunk=16), has_aux=True))(
            params, _batch(0, cfg))
    return matmuls(traced, ((2, t, d), (d, 2 * f)), ((2, t, f), (f, d)))


def test_a_recomputed_block_runs_its_mlps_matmuls_once(monkeypatch):
    """With ``remat`` the gradient holds as many of the MLPs' forward
    matmuls as without it: the policy keeps ``gate_up``'s product
    (``_BLOCK_KEEPS``), and nothing in the backward pass reads ``down``'s,
    which is added to the stream as it is. Under the policy without that
    name each block holds one more ``gate_up``, and still no second
    ``down``."""
    n = Phi4FlashConfig.tiny().n_layer
    assert (_mlp_forwards(False), _mlp_forwards(True)) == (2 * n, 2 * n)
    monkeypatch.setattr(model_file, "_BLOCK_KEEPS", tuple(
        n for n in model_file._BLOCK_KEEPS if n != "mlp_gate_up"))
    assert _mlp_forwards(True) == 3 * n


def test_a_recomputed_stack_gives_the_bits_of_the_one_kept_whole():
    """Loss, report and every gradient leaf with ``remat`` are the same
    bits as without: kept and recomputed products come from the same
    matmuls (``conftest.same_bits``: no ``jit``)."""
    got = {}
    for remat in (False, True):
        cfg = Phi4FlashConfig.tiny(remat=remat, **F32)
        model = Phi4Flash(cfg)
        params = _jittered(model.init_params(jax.random.key(5)), 5)
        got[remat] = jax.value_and_grad(
            phi4flash_loss_fn(model, ce_chunk=16), has_aux=True)(
                params, _batch(5, cfg))
    assert len(jax.tree_util.tree_leaves(got[False])) > 100
    assert same_bits(got[True], got[False])


@pytest.fixture
def on_the_scans_kernels(monkeypatch):
    """The tiny stack at widths the Mamba-1 kernels tile (1,024 channels,
    8 states; rows of 32 tokens, padded to a block of 64) with the scans
    on ``ops/pallas/mamba1_scan.py``'s kernels, interpreted:
    ``mamba1_path`` is told what a TPU would answer. -> remat -> (model,
    loss function)."""
    import functools

    from ray_tpu.ops.pallas import mamba1_scan as kernels
    monkeypatch.setattr(mamba1, "mamba1_path",
                        lambda *a, **kw: "pallas_chunked")
    monkeypatch.setattr(kernels, "mamba1_scan", functools.partial(
        kernels.mamba1_scan, interpret=True))

    def made(remat):
        model = Phi4Flash(Phi4FlashConfig.tiny(
            mamba_inner=1024, ssm_state=8, remat=remat, **F32))
        return model, phi4flash_loss_fn(model, ce_chunk=16)
    return made


def test_recomputed_blocks_on_the_scans_kernels_give_the_kept_blocks_numbers(
        on_the_scans_kernels):
    """Loss, report and every gradient leaf with ``remat`` (PR 70: the
    blocks keep the scan's ``y`` and entering states, which only the
    kernels' forward rule names, beside their projections' products) against
    without: the same kernels on the same operands."""
    got = {}
    for remat in (False, True):
        model, loss_fn = on_the_scans_kernels(remat)
        params = _jittered(model.init_params(jax.random.key(5)), 5)
        got[remat] = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
            params, _batch(5, model.config, rows=1))
    ((want, want_report), want_grads), ((loss, report), grads) = (
        got[False], got[True])
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    assert float(report["mamba_out_rms"]) == pytest.approx(
        float(want_report["mamba_out_rms"]), rel=1e-6)
    want_leaves = dict(_leaves_with_names(want_grads))
    assert len(want_leaves) == len(jax.tree_util.tree_leaves(grads)) > 100
    for name, leaf in _leaves_with_names(grads):
        scale = max(float(np.abs(want_leaves[name]).max()), 1e-3)
        np.testing.assert_allclose(leaf, want_leaves[name],
                                   atol=1e-5 * scale, err_msg=name)


@pytest.mark.parametrize("remat, listed, forwards", [
    (True, True, 1), (True, False, 2), (False, True, 1)],
    ids=["recomputed", "recomputed_without_the_scans_names", "kept_whole"])
def test_the_scans_forward_kernel_runs_once_a_layer_under_remat(
        on_the_scans_kernels, monkeypatch, remat, listed, forwards):
    """In the gradient's jaxpr, with what nothing reads taken out as
    lowering takes it out: a recomputed block runs the scan's forward
    kernel once a Mamba layer (2 results: ``y`` and the entering states)
    and its backward once (6); a policy without the kernels' two names
    runs the forward twice, as before PR 70."""
    from conftest import live_kernel_calls
    if not listed:
        monkeypatch.setattr(model_file, "_BLOCK_KEEPS", tuple(
            n for n in model_file._BLOCK_KEEPS
            if not n.startswith("mamba1_scan")))
    model, loss_fn = on_the_scans_kernels(remat)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    traced = jax.make_jaxpr(jax.value_and_grad(loss_fn, has_aux=True))(
        params, _batch(0, model.config, rows=1))
    layers = model.config.layer_kinds.count("M")
    assert layers == 3
    assert live_kernel_calls(traced) == [2] * forwards * layers + [6] * layers


def test_every_kind_of_layer_has_its_own_scopes():
    cfg = Phi4FlashConfig.tiny(**F32)
    model = Phi4Flash(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    batch = {k: jax.ShapeDtypeStruct((2, cfg.seq_len), jnp.int32)
             for k in ("tokens", "targets")}
    text = jax.jit(jax.grad(
        lambda p, b: phi4flash_loss_fn(model, ce_chunk=16)(p, b)[0])).lower(
            params, batch).as_text(debug_info=True)
    for scope in (
            "h_0/mamba/in_proj", "h_0/mamba/conv", "h_0/mamba/x_proj",
            "h_0/mamba/dt", "h_0/mamba/scan", "h_0/mamba/gate",
            "h_0/mamba/out_proj", "h_1/attn/qkv", "h_1/attn/repeat",
            "h_1/attn/window", "h_1/attn/diff", "h_1/attn/out",
            "h_5/attn/core", "h_6/gmu/in_proj", "h_6/gmu/gate",
            "h_6/gmu/out_proj", "h_7/attn/q/", "h_7/attn/cross",
            "h_7/attn/diff", "h_7/mlp/gate_up", "h_7/mlp/down"):
        assert scope in text, scope
    assert "h_7/attn/qkv" not in text and "h_5/attn/window" not in text
    assert "h_6/mamba" not in text and "h_4/gmu" not in text


def test_a_mesh_over_the_batch_gives_the_one_device_loss_and_sp_is_refused():
    from ray_tpu.parallel.mesh import make_mesh
    cfg = Phi4FlashConfig.tiny(**F32)
    params = Phi4Flash(cfg).init_params(jax.random.key(0))
    batch = _batch(0, cfg)
    with jax.default_matmul_precision("highest"):
        want = phi4flash_loss_fn(Phi4Flash(cfg), ce_chunk=16)(params, batch)
        mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
        got = jax.jit(phi4flash_loss_fn(Phi4Flash(cfg, mesh=mesh),
                                        ce_chunk=16))(params, batch)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    sp = make_mesh({"sp": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="sp=2"):
        phi4flash_loss_fn(Phi4Flash(cfg, mesh=sp), ce_chunk=16)(params,
                                                                batch)
