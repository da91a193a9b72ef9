"""Model + sharded train-step tests on the 8-device CPU mesh."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import GPT2, GPT2Config, ResNet, ResNet50Config
from ray_tpu.models.gpt2 import gpt2_loss_fn
from ray_tpu.models.resnet import resnet_loss_fn
from ray_tpu.parallel import make_mesh
from ray_tpu.train import (
    init_train_state, make_train_step, shard_batch,
)


def _gpt_batch(cfg, batch=8, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size,
                          (batch, cfg.seq_len)).astype(np.int32)
    return {"tokens": tokens[:, :], "targets": np.roll(tokens, -1, 1)}


def test_gpt2_forward_shapes():
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init_params(jax.random.key(0))
    batch = _gpt_batch(cfg, batch=2)
    logits = model.apply({"params": params}, batch["tokens"])
    assert logits.shape == (2, cfg.seq_len, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_gpt2_train_step_loss_decreases():
    cfg = GPT2Config.tiny()
    mesh = make_mesh({"dp": 4, "tp": 2})
    model = GPT2(cfg, mesh=mesh)
    params = model.init_params(jax.random.key(0))
    opt = optax.adamw(1e-2)
    state = init_train_state(params, opt, mesh)
    step = make_train_step(gpt2_loss_fn(model), opt)
    batch = shard_batch(_gpt_batch(cfg), mesh)

    state, m0 = step(state, batch)
    for _ in range(10):
        state, m = step(state, batch)
    assert float(m["loss"]) < float(m0["loss"])
    assert int(state.step) == 11


def test_gpt2_ring_attention_model_matches_dense():
    mesh = make_mesh({"dp": 2, "sp": 4})
    cfg_d = GPT2Config.tiny(attn_impl="dense")
    cfg_r = GPT2Config.tiny(attn_impl="ring")
    m_dense = GPT2(cfg_d)
    m_ring = GPT2(cfg_r, mesh=mesh)
    params = m_dense.init_params(jax.random.key(0))
    batch = _gpt_batch(cfg_d, batch=4)

    logits_d = m_dense.apply({"params": params}, batch["tokens"])
    sharded = shard_batch(batch, mesh, seq_sharded=True)
    logits_r = jax.jit(
        lambda p, t: m_ring.apply({"params": p}, t)
    )(params, sharded["tokens"])
    np.testing.assert_allclose(np.asarray(logits_r),
                               np.asarray(logits_d),
                               atol=2e-2, rtol=2e-2)


def test_gpt2_fsdp_sharding_runs():
    mesh = make_mesh({"fsdp": 8})
    cfg = GPT2Config.tiny()
    model = GPT2(cfg, mesh=mesh)
    params = model.init_params(jax.random.key(0))
    opt = optax.adamw(1e-3)
    state = init_train_state(params, opt, mesh)
    # params actually sharded: wte embed dim split over fsdp
    wte = state.params["wte"]["embedding"]
    assert "fsdp" in str(wte.sharding.spec)
    step = make_train_step(gpt2_loss_fn(model), opt)
    batch = shard_batch(_gpt_batch(cfg), mesh)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_resnet_train_step():
    cfg = ResNet50Config.tiny()
    mesh = make_mesh({"dp": 8})
    model = ResNet(cfg)
    variables = model.init_variables(jax.random.key(0), image_size=32)
    opt = optax.sgd(0.1, momentum=0.9)
    state = init_train_state(variables["params"], opt, mesh,
                             extra=variables["batch_stats"])

    raw = resnet_loss_fn(model)

    def loss_fn(params, extra, batch):
        return raw(params, extra, batch)

    step = make_train_step(loss_fn, opt, has_extra=True)
    rng = np.random.default_rng(0)
    batch = shard_batch({
        "image": rng.standard_normal((16, 32, 32, 3)).astype(np.float32),
        "label": rng.integers(0, cfg.num_classes, (16,)).astype(np.int32),
    }, mesh)
    l0 = None
    for i in range(5):
        state, metrics = step(state, batch)
        if l0 is None:
            l0 = float(metrics["loss"])
    assert float(metrics["loss"]) < l0


def test_gpt2_remat_matches():
    cfg = GPT2Config.tiny()
    cfg_r = GPT2Config.tiny(remat=True)
    model = GPT2(cfg)
    model_r = GPT2(cfg_r)
    params = model.init_params(jax.random.key(0))
    batch = _gpt_batch(cfg, batch=2)
    l1 = gpt2_loss_fn(model)(params, batch)
    l2 = gpt2_loss_fn(model_r)(params, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


def test_chunked_ce_custom_vjp_matches_dense():
    """chunked_cross_entropy (hand-written VJP reusing saved LSE)
    must match full-logits cross-entropy in value AND gradients."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt2 import (
        chunked_cross_entropy, cross_entropy_loss,
    )

    B, S, E, V = 2, 64, 32, 128
    hidden = jax.random.normal(jax.random.key(0), (B, S, E))
    emb = jax.random.normal(jax.random.key(1), (V, E)) * 0.1
    tgt = jax.random.randint(jax.random.key(2), (B, S), 0, V)
    tgt = tgt.at[0, :5].set(-1)      # ignored positions

    def loss_chunked(h, e):
        return chunked_cross_entropy(h, e, tgt, chunk_size=32)

    def loss_plain(h, e):
        return cross_entropy_loss(
            jnp.einsum("bse,ve->bsv", h, e), tgt)

    l1, (gh1, ge1) = jax.value_and_grad(
        loss_chunked, argnums=(0, 1))(hidden, emb)
    l2, (gh2, ge2) = jax.value_and_grad(
        loss_plain, argnums=(0, 1))(hidden, emb)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gh1), np.asarray(gh2),
                               rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ge1), np.asarray(ge2),
                               rtol=1e-3, atol=1e-5)


# -- the chunked cross-entropy on a mesh: each chip scans its own rows ----

# 8 x 24 tokens: on four chips 48 rows each, which chunks of 32 do not
# divide, so every chip pads; masked positions on two chips.
_CE_B, _CE_S, _CE_E, _CE_V, _CE_CHUNK = 8, 24, 32, 128, 32


def _ce_inputs(batch=_CE_B):
    hidden = jax.random.normal(jax.random.key(0), (batch, _CE_S, _CE_E))
    emb = jax.random.normal(jax.random.key(1), (_CE_V, _CE_E)) * 0.1
    tgt = jax.random.randint(jax.random.key(2), (batch, _CE_S), 0, _CE_V)
    tgt = tgt.at[0, :5].set(-1).at[batch - 1, 3:9].set(-1)
    return hidden, emb, tgt


def _ce_value_and_grad(mesh, chunk=_CE_CHUNK):
    from ray_tpu.models.gpt2 import chunked_cross_entropy

    def loss(h, e, t):
        return chunked_cross_entropy(h, e, t, chunk_size=chunk, mesh=mesh)

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))


def _ce_on_mesh(mesh, hidden, emb, tgt, emb_spec=None):
    """The three inputs placed as a train step has them: tokens over
    dp/fsdp and sp (``batch_spec``), the head as ``emb_spec`` says."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ray_tpu.train.step import batch_spec

    tokens = NamedSharding(mesh, batch_spec(mesh, seq_sharded=True))
    head = NamedSharding(mesh, emb_spec or PartitionSpec())
    return (jax.device_put(hidden, tokens), jax.device_put(emb, head),
            jax.device_put(tgt, tokens))


def _mesh_of(axes):
    n = int(np.prod(list(axes.values())))
    return make_mesh(axes, devices=jax.devices()[:n])


@pytest.mark.parametrize("axes", [
    {"dp": 4}, {"dp": 2, "fsdp": 2}, {"dp": 2, "sp": 2}],
    ids=["dp4", "dp2-fsdp2", "dp2-sp2"])
def test_chunked_ce_on_a_mesh_equals_the_unsharded(axes):
    """Loss, d hidden and d embedding of the per-chip scan equal the
    global scan's: the same mean over the global count of unmasked
    tokens, no gradient scaled by an axis size or reduced twice."""
    hidden, emb, tgt = _ce_inputs()
    want_l, (want_dh, want_de) = _ce_value_and_grad(None)(hidden, emb, tgt)
    mesh = _mesh_of(axes)
    placed = _ce_on_mesh(mesh, hidden, emb, tgt)
    got_l, (got_dh, got_de) = _ce_value_and_grad(mesh)(*placed)
    assert float(got_l) == float(want_l)
    np.testing.assert_array_equal(np.asarray(got_dh), np.asarray(want_dh))
    np.testing.assert_allclose(np.asarray(got_de), np.asarray(want_de),
                               rtol=0, atol=1e-7)
    # d hidden stays where its rows are
    assert got_dh.sharding.is_equivalent_to(placed[0].sharding, 3)


_HLO_LINE = re.compile(
    r"^\s*(?:ROOT )?%?(?P<name>[\w.\-]+) = (?P<type>.*?) "
    r"(?P<op>[a-z][\w\-]*)\((?P<rest>.*)$")
_COLLECTIVES = {"all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast"}


def _hlo_computations(text):
    """computation name -> its instructions as (name, type, opcode,
    op_name, whole line); the entry computation under ``ENTRY``."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$", line)
        if head:
            cur = comps.setdefault(
                "ENTRY" if head.group(1) else head.group(2), [])
            continue
        m = _HLO_LINE.match(line)
        if m and cur is not None:
            op = m.group("op").removesuffix("-start").removesuffix("-done")
            name = re.search(r'op_name="([^"]*)"', line)
            cur.append((m.group("name"), m.group("type"), op,
                        name.group(1) if name else "", line))
    return comps


def _crosses_devices(line) -> bool:
    """False for a collective whose groups hold one device each: the
    sum over a mesh axis of size 1, which the CPU compiler leaves in
    the text (the TPU's removes it) and which moves nothing."""
    groups = re.search(r"replica_groups=(\{\{[\d,{}]*\}\}|\[\d+,(\d+)\])",
                       line)
    if groups.group(2):
        return int(groups.group(2)) > 1
    return "," in groups.group(1).replace("},{", "")


def _under_loss(instruction) -> bool:
    from ray_tpu.observability import xplane
    return xplane.scope_path(instruction[3]).split("/")[0] == "loss"


def test_chunked_ce_on_dp4_scans_a_quarter_of_the_rows():
    """The guard that fails where the chunk axis is sharded and the
    partitioner gathers it (two all-gathers of ``[n, chunk, E]`` and
    both ``while``s over all ``n`` chunks on every chip): here both
    loops carry ``[n/4, chunk, E]``, nothing is gathered, and the only
    collectives are the scalar sums and one all-reduce of the
    embedding's gradient, outside any loop body."""
    batch, chunk = 16, 32           # 384 rows: n = 12, three a chip
    n = batch * _CE_S // chunk
    hidden, emb, tgt = _ce_inputs(batch)
    mesh = _mesh_of({"dp": 4})
    fn = _ce_value_and_grad(mesh)
    text = fn.lower(*_ce_on_mesh(mesh, hidden, emb, tgt)).compile().as_text()
    comps = _hlo_computations(text)
    rows_global = f"f32[{n},{chunk},{_CE_E}]"
    rows_local = f"f32[{n // 4},{chunk},{_CE_E}]"

    everything = [i for body in comps.values() for i in body]
    assert not [i for i in everything if i[2] == "all-gather"]
    assert not [i for i in everything if rows_global in i[1]]
    whiles = [i for i in everything if i[2] == "while"]
    assert len(whiles) == 2                     # forward and backward
    for w in whiles:
        assert rows_local in w[1], w[4]
        assert f'"known_trip_count":{{"n":"{n // 4}"}}' in w[4]
        assert "/loss/" in w[3]

    collectives = [i for i in everything
                   if i[2] in _COLLECTIVES and _crosses_devices(i[4])]
    assert collectives and all(c in comps["ENTRY"] for c in collectives)
    big = [c for c in collectives if "[]" not in c[1].split("{")[0]]
    assert [(c[2], c[1].split("{")[0]) for c in big] == [
        ("all-reduce", f"f32[{_CE_V},{_CE_E}]")], big
    assert "transpose(" in big[0][3]
    for c in collectives:                       # the rest: scalar sums
        assert c[2] == "all-reduce" and _under_loss(c), c[4]
        if c is not big[0]:
            assert set(re.findall(r"[a-z]\d+\[(\d*)\]", c[1])) == {""}, c[4]


@pytest.mark.parametrize("case", ["no-mesh", "one-device", "batch-of-1",
                                  "vocab-over-tp"])
def test_chunked_ce_fall_through_keeps_the_global_scan(case):
    """Where there is nothing to split (no mesh, a one-device mesh),
    where the batch does not divide the axes (init-time tracing), and
    where the head is sharded on its vocabulary, the function takes the
    global scan: still the dense loss, no ``shard_map`` in the lowering,
    and on one device the very program of ``mesh=None``."""
    from jax.sharding import PartitionSpec

    from ray_tpu.models.gpt2 import cross_entropy_loss

    hidden, emb, tgt = _ce_inputs(1 if case == "batch-of-1" else _CE_B)
    mesh = {"no-mesh": lambda: None,
            "one-device": lambda: _mesh_of({"dp": 1}),
            "batch-of-1": lambda: _mesh_of({"dp": 4}),
            "vocab-over-tp": lambda: _mesh_of({"dp": 2, "tp": 2})}[case]()
    args = (hidden, emb, tgt)
    if case == "vocab-over-tp":
        args = _ce_on_mesh(mesh, hidden, emb, tgt,
                           emb_spec=PartitionSpec("tp", None))
    fn = _ce_value_and_grad(mesh)
    loss, (dh, de) = fn(*args)

    def dense(h, e):
        return cross_entropy_loss(jnp.einsum("bse,ve->bsv", h, e), tgt)

    want, (want_dh, want_de) = jax.value_and_grad(
        dense, argnums=(0, 1))(hidden, emb)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(dh), np.asarray(want_dh),
                               rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(de), np.asarray(want_de),
                               rtol=1e-3, atol=1e-5)

    lowered = fn.lower(*args)
    assert "shard_map" not in lowered.as_text(debug_info=True)
    if case == "vocab-over-tp":
        # the head stays sharded: no chip is handed all of it
        assert de.sharding.is_equivalent_to(args[1].sharding, 2)
    if case == "one-device":
        # the text without locations, which is what jit compiles and
        # the compile cache keys on
        plain = _ce_value_and_grad(None).lower(hidden, emb, tgt)
        assert lowered.as_text() == plain.as_text()


def test_gpt2_loss_on_dp4_scans_local_rows_and_says_so():
    """``gpt2_loss_fn`` hands the model's mesh on: a dp=4 step has no
    all-gather under ``loss``, and the trace's ``train.compile`` span
    carries the rows one chip scans and the axes they were mapped over
    (absent where the function fell through)."""
    from ray_tpu.util import tracing

    cfg = GPT2Config.tiny()
    batch = _gpt_batch(cfg, batch=8)

    def traced_step(mesh):
        model = GPT2(cfg, mesh=mesh)
        opt = optax.adamw(1e-3)
        state = init_train_state(
            model.init_params(jax.random.key(0)), opt, mesh)
        step = make_train_step(gpt2_loss_fn(model, ce_chunk=64), opt)
        b = shard_batch(batch, mesh) if mesh is not None else batch
        before = len(tracing.get_spans())
        text = step.lower(state, b).compile().as_text()
        traces = [s.attributes for s in tracing.get_spans()[before:]
                  if s.name == "train.compile"
                  and s.attributes["kind"] == "trace"
                  and s.attributes["fun_name"] == "step"]
        return text, traces

    text, traces = traced_step(_mesh_of({"dp": 4}))
    assert [(t["ce_rows_local"], t["ce_axes"]) for t in traces] == [
        (8 * cfg.seq_len // 4, ["dp"])]
    under_loss = [i for body in _hlo_computations(text).values()
                  for i in body if _under_loss(i)]
    assert under_loss
    assert not [i for i in under_loss if i[2] == "all-gather"]

    _, traces = traced_step(_mesh_of({"dp": 1}))
    assert len(traces) == 1
    assert not {"ce_rows_local", "ce_axes"} & set(traces[0])


def test_the_mlps_names_are_the_identity_under_llamas_own_remat(monkeypatch):
    """``SwiGLU``'s three products carry ``checkpoint_name``s for the
    models whose recomputed blocks keep them (``models/ouro.py``). A
    stack whose policy lists none of them — this file's own ``remat``,
    ``nothing_saveable`` — lowers to the text it lowers to with the names
    taken out, forward and backward."""
    from ray_tpu.models import Llama, LlamaConfig
    from ray_tpu.models import llama as llama_file
    cfg = LlamaConfig.tiny(remat=True)
    model = Llama(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((2, cfg.seq_len), jnp.int32)

    def lowered():
        text = jax.jit(jax.grad(lambda p, t: model.apply(
            {"params": p}, t).astype(jnp.float32).sum())).lower(
                params, tokens).as_text()
        # a private function's number counts the functions before it
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)
    named = lowered()
    assert "gate" in named
    monkeypatch.setattr(llama_file, "checkpoint_name", lambda x, name: x)
    assert lowered() == named
