"""``ops/kda.py``'s chunked gated delta rule against the recurrence it is
a rearrangement of, token by token: outputs and the gradients of every
operand."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import equations, live_kernel_calls

from ray_tpu.ops import kda
from ray_tpu.ops.remat import KDA_SCAN_OUT, KDA_SCAN_STATES


def recurrence(q, k, v, g, beta):
    """``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t
    v_t^T``; ``o_t = S_t^T q_t``, one row at a time, float32."""
    b, t, h, kd = q.shape

    def step(S, row):
        q, k, v, g, beta = row              # [b, H, .]; beta [b, H]
        S = jnp.exp(g)[..., None] * S
        read = jnp.sum(k[..., None] * S, -2)            # S^T k
        S = S + k[..., None] * (beta[..., None] * (v - read))[..., None, :]
        return S, jnp.sum(q[..., None] * S, -2)

    rows = tuple(jnp.moveaxis(z.astype(jnp.float32), 1, 0)
                 for z in (q, k, v, g, beta))
    _, o = jax.lax.scan(
        step, jnp.zeros((b, h, kd, v.shape[-1]), jnp.float32), rows)
    return jnp.moveaxis(o, 0, 1)


def operands(seed, b, t, h, kd, vd, decay=1.0, beta=None):
    """q scaled, k of unit length, v, g = -decay * softplus(.), beta."""
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(b, t, h, kd))) * kd ** -0.5
    k = unit(rng.normal(size=(b, t, h, kd)))
    v = rng.normal(size=(b, t, h, vd))
    g = -decay * np.log1p(np.exp(rng.normal(size=(b, t, h, kd))))
    bt = (1 / (1 + np.exp(-rng.normal(size=(b, t, h)))) if beta is None
          else np.full((b, t, h), beta))
    return tuple(jnp.asarray(z, jnp.float32) for z in (q, k, v, g, bt))


def outputs_and_gradients(args, *fns):
    """(outputs, gradients) of each function, the gradients those of one
    fixed random projection of the output."""
    weight = jnp.asarray(np.random.default_rng(7).normal(
        size=args[2].shape), jnp.float32)

    def of(fn):
        def loss(*a):
            o = fn(*a)
            return jnp.sum(o * weight), o
        (_, o), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
        return o, grads
    return tuple(of(fn) for fn in fns)


def both(args, chunk):
    """Of the chunked form, of the recurrence."""
    return outputs_and_gradients(
        args, lambda *a: kda.kda_scan(*a, chunk=chunk), recurrence)


def close(got, want, rtol=2e-4):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    np.testing.assert_allclose(np.asarray(got) / scale,
                               np.asarray(want) / scale, atol=rtol, rtol=0)


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("t", [128, 150])        # whole chunks, and not
@pytest.mark.parametrize("heads", [1, 3])
def test_chunked_form_is_the_recurrence(chunk, t, heads):
    args = operands(0, 2, t, heads, 32, 24)
    (o, grads), (o_ref, grads_ref) = both(args, chunk)
    close(o, o_ref)
    for got, want in zip(grads, grads_ref):
        close(got, want)


@pytest.mark.parametrize("chunk", [16, 64])
def test_decays_that_overflow_exp_of_minus_the_running_sum(chunk):
    """At ``decay`` 8 a chunk's running sum passes -100 in some channel
    inside one chunk of 16 and -400 inside one of 64: ``exp(-G)`` is
    infinite in float32 (past 88.7), so a form that scaled keys by it
    would give nan or inf. The differences are what is exponentiated."""
    args = operands(1, 1, 128, 2, 32, 32, decay=8.0)
    G = np.cumsum(np.asarray(args[3])[:, :chunk], axis=1)
    with np.errstate(over="ignore"):
        assert np.isinf(np.exp(-G, dtype=np.float32)).any()
    (o, grads), (o_ref, grads_ref) = both(args, chunk)
    assert np.isfinite(np.asarray(o)).all()
    close(o, o_ref)
    for got, want in zip(grads, grads_ref):
        assert np.isfinite(np.asarray(got)).all()
        close(got, want)


@pytest.mark.parametrize("case", ["no_decay", "no_write"])
def test_the_plain_delta_rule_and_a_layer_that_writes_nothing(case):
    if case == "no_decay":      # alpha = 1: the delta rule without a gate
        args = operands(2, 1, 96, 2, 32, 32, decay=0.0)
        (o, grads), (o_ref, grads_ref) = both(args, 16)
        close(o, o_ref)
        for got, want in zip(grads, grads_ref):
            close(got, want)
    else:                       # beta = 0: the state stays zero
        args = operands(3, 1, 96, 2, 32, 32, beta=0.0)
        o = kda.kda_scan(*args, chunk=16)
        assert not np.asarray(o).any()


def test_a_state_carried_across_exactly_one_chunk_boundary():
    """Two chunks: the second's outputs read what the first wrote, and
    differ from the same rows run alone from an empty state."""
    chunk = 16
    args = operands(4, 1, 2 * chunk, 2, 32, 32)
    o = kda.kda_scan(*args, chunk=chunk)
    close(o, recurrence(*args))
    alone = kda.kda_scan(*(a[:, chunk:] for a in args), chunk=chunk)
    assert float(jnp.max(jnp.abs(o[:, chunk:] - alone))) > 1e-3


def test_groups_of_chunks_hand_the_state_on(monkeypatch):
    """More rows than one recomputed group holds: the outer scan's
    carry is the state."""
    monkeypatch.setattr(kda, "GROUP_ROWS", 32)
    args = operands(5, 1, 100, 2, 16, 16)
    (o, grads), (o_ref, grads_ref) = both(args, 16)
    close(o, o_ref)
    for got, want in zip(grads, grads_ref):
        close(got, want)


@pytest.mark.parametrize("axis", ["sp", "tp"])
def test_kda_path_refuses_a_split_sequence_or_split_heads(axis):
    from ray_tpu.parallel import make_mesh
    mesh = make_mesh({axis: 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match=f"{axis}=2"):
        kda.kda_path((1, 64, 2, 16), 16, mesh)
    assert kda.kda_path((1, 64, 2, 16), 16, None) == "xla_chunked"
    dp = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    assert kda.kda_path((2, 64, 2, 16), 16, dp) == "xla_chunked"


# ---------------------------------------------------------------------------
# the kernel pair (``ops/pallas/kda_scan.py``), interpreted on the CPU
# ---------------------------------------------------------------------------

from ray_tpu.ops.pallas import kda_scan as kernels  # noqa: E402


def three_ways(args):
    """Of the kernels, of ``xla_chunked``, of the recurrence."""
    return outputs_and_gradients(
        args, lambda *a: kernels.kda_scan(*a, interpret=True),
        lambda *a: kda.kda_scan(*a, chunk=64), recurrence)


def all_close(got, *wants):
    (o, grads) = got
    for o_ref, grads_ref in wants:
        close(o, o_ref)
        for g, want in zip(grads, grads_ref):
            assert np.isfinite(np.asarray(g)).all()
            close(g, want)


@pytest.mark.parametrize("t", [128, 150])   # two chunks; not whole chunks
@pytest.mark.parametrize("heads", [1, 3])
def test_the_kernels_are_the_chunked_form_and_the_recurrence(t, heads):
    """``o`` and ``dq, dk, dv, dg, dbeta``; 150 rows are three chunks in
    two grid cells, so the state and its cotangent cross a cell."""
    got, xla, ref = three_ways(operands(10 + heads, 1, t, heads, 128, 128))
    all_close(got, xla, ref)


def test_the_kernels_at_decays_that_overflow_exp_of_minus_the_running_sum():
    args = operands(1, 1, 128, 2, 128, 128, decay=8.0)
    G = np.cumsum(np.asarray(args[3])[:, :64], axis=1)
    with np.errstate(over="ignore"):
        assert np.isinf(np.exp(-G, dtype=np.float32)).any()
    got, xla, ref = three_ways(args)
    assert np.isfinite(np.asarray(got[0])).all()
    all_close(got, xla, ref)


def test_the_kernels_carry_a_state_across_exactly_one_chunk_boundary():
    """One grid cell of two chunks (and of four heads): the second's
    outputs read what the first wrote."""
    args = operands(4, 1, 128, 4, 128, 128)
    o = kernels.kda_scan(*args, interpret=True)
    close(o, recurrence(*args))
    alone = kernels.kda_scan(*(a[:, 64:] for a in args), interpret=True)
    assert float(jnp.max(jnp.abs(o[:, 64:] - alone))) > 1e-3


def test_the_kernels_take_bfloat16_values_and_return_their_cotangent_so():
    q, k, v, g, beta = operands(6, 1, 128, 1, 128, 128)
    v = v.astype(jnp.bfloat16)

    def loss(fn, v):
        return jnp.sum(jnp.sin(fn(q, k, v, g, beta)))
    dv = jax.grad(lambda v: loss(
        lambda *a: kernels.kda_scan(*a, interpret=True), v))(v)
    want = jax.grad(lambda v: loss(
        lambda *a: kda.kda_scan(*a, chunk=64), v))(v)
    assert dv.dtype == jnp.bfloat16
    close(dv.astype(jnp.float32), want.astype(jnp.float32), rtol=1e-2)


# --- un-normalised q and k: the norm in the kernels' cells ------------------

def raw_operands(seed, b, t, h, zero_rows=()):
    """``operands`` at the kernels' widths with q and k as a mixer's
    convolutions leave them: no unit length, no scale, each head its own
    size; ``zero_rows``: rows of q and of k that are all zeros."""
    _, _, v, g, beta = operands(seed, b, t, h, 128, 128)
    rng = np.random.default_rng(100 + seed)
    q, k = (rng.normal(size=(b, t, h, 128)) * rng.uniform(
        0.1, 3.0, size=(1, 1, h, 1)) for _ in range(2))
    for row in zero_rows:
        q[:, row], k[:, row] = 0.0, 0.0
    return jnp.asarray(q, jnp.float32), jnp.asarray(k, jnp.float32), v, g, beta


def normed_three_ways(args):
    """Of the kernels given the raw rows, of ``unit_rows`` + the XLA
    chunked form (``kda_scan``'s own other path), of ``unit_rows`` + the
    recurrence."""
    def by_row(q, k, *rest):
        return recurrence(kda.unit_rows(q) * q.shape[-1] ** -0.5,
                          kda.unit_rows(k), *rest)
    return outputs_and_gradients(
        args,
        lambda *a: kernels.kda_scan(*a, normalize_qk=True, interpret=True),
        lambda *a: kda.kda_scan(*a, chunk=64, normalize_qk=True), by_row)


@pytest.mark.parametrize("t, heads, zero_rows", [
    (128, 1, ()), (128, 3, ()), (150, 1, ()), (150, 3, ()),
    (200, 2, ()),               # a cell and 72 rows: the tail is padded
    (128, 4, (5, 70)),          # a row of zeros in each chunk
])
def test_the_kernels_bring_raw_q_and_k_to_unit_length_themselves(
        t, heads, zero_rows):
    """``o`` and all five cotangents, ``dq`` and ``dk`` those of the
    rows as they came. A row of zeros has the unit row zero and a
    cotangent ``rsqrt(1e-6)`` times the unit row's: compared apart, so
    that it does not set the scale for the others."""
    args = raw_operands(20 + heads, 1, t, heads, zero_rows)
    got, xla, ref = normed_three_ways(args)
    if zero_rows:
        rows = np.asarray(zero_rows)
        assert not np.asarray(kda.unit_rows(args[1]))[:, rows].any()

        def apart(result):
            o, (dq, dk, *others) = result
            live = jnp.ones((t,), bool).at[rows].set(False)[:, None, None]
            return o, (*(jnp.where(live, z, 0.0) for z in (dq, dk)),
                       dq[:, rows], dk[:, rows], *others)
        got, xla, ref = apart(got), apart(xla), apart(ref)
        assert float(jnp.max(jnp.abs(got[1][2]))) > 0
    all_close(got, xla, ref)


def test_bfloat16_q_and_k_come_back_as_bfloat16_cotangents():
    """As the cell's convolutions hand them over: the kernels read the
    bfloat16 rows, and the cotangents of those rows leave in bfloat16
    (XLA's transpose of ``x.astype(float32)`` rounds at the same
    point)."""
    q, k, v, g, beta = raw_operands(7, 1, 128, 2)
    q, k = q.astype(jnp.bfloat16), k.astype(jnp.bfloat16)

    def grads(fn):
        return jax.grad(lambda q, k: jnp.sum(jnp.sin(
            fn(q, k, v, g, beta, normalize_qk=True))), argnums=(0, 1))(q, k)
    got = grads(functools.partial(kernels.kda_scan, interpret=True))
    want = grads(functools.partial(kda.kda_scan, chunk=64))
    for z, ref in zip(got, want):
        assert z.dtype == ref.dtype == jnp.bfloat16
        close(z.astype(jnp.float32), ref.astype(jnp.float32), rtol=1e-2)


def _behind(eqn, made_by):
    """The equations behind ``eqn``, nearest first: each one's first
    operand that an equation made, back to a load."""
    out = []
    while True:
        makers = [made_by[id(v)] for v in eqn.invars if id(v) in made_by]
        if not makers:
            return out
        eqn = makers[0]
        out.append(eqn)


@pytest.mark.parametrize("normalize", [False, True])
def test_every_product_and_exp_in_both_kernels_is_float32(normalize):
    """No bfloat16 operand inside the recurrence, no matmul below
    ``HIGHEST`` (Mosaic has nothing between it and one bfloat16 pass),
    read from the kernels' own jaxprs, both passes. Given raw bfloat16
    ``q, k`` (``normalize``) the norm is there too, in float32: a square,
    its sum along the lanes, plus 1e-6, ``rsqrt``; and the only bfloat16
    values are the operands as loaded and the cotangents as stored."""
    args = operands(0, 1, 128, 1, 128, 128)
    if normalize:
        args = tuple(z.astype(jnp.bfloat16) for z in args[:2]) + args[2:]
    traced = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(kernels.kda_scan(*a, normalize_qk=normalize)),
        argnums=(0, 1, 2, 3, 4)))(*args)
    calls = [e for e in equations(traced.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 2                      # forward, backward
    for call in calls:
        inside = list(equations(call.params["jaxpr"]))
        dots = [e for e in inside if e.primitive.name == "dot_general"]
        exps = [e for e in inside if e.primitive.name == "exp"]
        assert len(dots) >= 20 and len(exps) >= 8
        for e in dots:
            assert {v.aval.dtype for v in e.invars} == {jnp.dtype("float32")}
            assert e.outvars[0].aval.dtype == jnp.float32
            assert e.params["precision"] is not None and all(
                p == jax.lax.Precision.HIGHEST for p in e.params["precision"])
        for e in exps:
            assert e.invars[0].aval.dtype == jnp.float32
        half = [(e, v) for e in inside for v in e.outvars
                if getattr(v.aval, "dtype", None) == jnp.bfloat16]
        roots = [e for e in inside if e.primitive.name == "rsqrt"]
        if not normalize:
            assert not half and not roots
            continue
        made_by = {id(v): e for e in inside for v in e.outvars}
        assert len(roots) == 2                      # q's and k's, one head
        for e in roots:
            assert e.invars[0].aval.dtype == e.outvars[0].aval.dtype \
                == jnp.float32 and e.outvars[0].aval.shape == (128, 1)
            before = _behind(e, made_by)
            names = [b.primitive.name for b in before]
            square = names.index("mul")
            assert names[0] == "add" and "reduce_sum" in names[1:square]
            assert names[square + 1:] == ["convert_element_type", "get"]
            assert all(b.outvars[0].aval.dtype == jnp.float32
                       for b in before[:-1])
            assert [float(v.val) for v in before[0].invars
                    if hasattr(v, "val")] == [pytest.approx(1e-6)]
            assert before[square].invars[0] is before[square].invars[1]
        # bfloat16: loaded and cast up at once, or cast down to be stored
        # (a store hands back what it overwrote, which nothing reads)
        used_by = {}
        for e in inside:
            for v in e.invars:
                used_by.setdefault(id(v), []).append(e.primitive.name)
        kinds = sorted((e.primitive.name, *used_by.get(id(v), []))
                       for e, v in half)
        loaded = [("get", "convert_element_type")] * 2          # q, k
        stored = [("convert_element_type", "swap")] * 2 + [("swap",)] * 2
        assert kinds == (loaded if call is calls[0]
                         else sorted(stored + loaded))


@pytest.mark.parametrize("case, shape, chunk, want", [
    ("tpu_tiles", (1, 16384, 32, 128), 64, "pallas_chunked"),
    ("tpu_chunk_16", (1, 64, 2, 128), 16, "xla_chunked"),
    ("tpu_keys_64", (1, 128, 2, 64), 64, "xla_chunked"),
    ("tpu_values_64", (1, 128, 2, 128), 64, "xla_chunked"),
    ("tpu_two_devices", (2, 128, 2, 128), 64, "xla_chunked"),
    ("tpu_dp_mesh", (2, 128, 2, 128), 64, "xla_chunked"),
    ("cpu_tiles", (1, 128, 2, 128), 64, "xla_chunked"),
])
def test_kda_path_takes_the_kernels_where_it_observes_they_fit(
        monkeypatch, case, shape, chunk, want):
    from ray_tpu.parallel import make_mesh
    backend = case.split("_")[0]
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count",
                        lambda: 2 if case == "tpu_two_devices" else 1)
    mesh = (make_mesh({"dp": 2}, devices=jax.devices()[:2])
            if case == "tpu_dp_mesh" else None)
    values = 64 if case == "tpu_values_64" else None
    assert kda.kda_path(shape, chunk, mesh, values=values) == want
    if want == "pallas_chunked":        # a mesh of one device is one device
        one = make_mesh({"dp": 1}, devices=jax.devices()[:1])
        assert kda.kda_path(shape, chunk, one) == want
        for axis in ("sp", "tp"):
            split = make_mesh({axis: 2}, devices=jax.devices()[:2])
            with pytest.raises(NotImplementedError, match=f"{axis}=2"):
                kda.kda_path(shape, chunk, split)


# --- what a recomputing caller keeps of the forward kernel -------------------

def _recomputed(kept):
    def loss(*a):
        return jnp.sum(jnp.sin(kernels.kda_scan(*a, interpret=True)))
    if kept is None:
        return loss
    return jax.checkpoint(
        loss, policy=jax.checkpoint_policies.save_only_these_names(*kept))


@pytest.mark.parametrize("kept, forwards", [
    (None, 1), ((), 2), ((KDA_SCAN_OUT,), 2), ((KDA_SCAN_STATES,), 2),
    ((KDA_SCAN_OUT, KDA_SCAN_STATES), 1)],
    ids=["not_recomputed", "keeps_nothing", "keeps_o_alone",
         "keeps_the_states_alone", "keeps_both"])
def test_a_caller_that_keeps_both_named_results_runs_the_forward_once(
        kept, forwards):
    """The forward rule names ``o`` and the chunk-entering states before
    they part into primal and residuals (``kda_scan_out``,
    ``kda_scan_states``, exported by ``ops/kda.py``): a checkpoint whose
    policy keeps both does not run the forward kernel in its backward
    pass, one that keeps either alone still does (the other result has
    to be made again, and the kernel with it)."""
    assert (KDA_SCAN_OUT, KDA_SCAN_STATES) == (
        "kda_scan_out", "kda_scan_states")
    traced = jax.make_jaxpr(jax.value_and_grad(
        _recomputed(kept), argnums=(0, 1, 2, 3, 4)))(
            *operands(0, 1, 128, 1, 128, 128))
    # the forward kernel has 2 results under differentiation (``o``, the
    # states entering the chunks), the backward 5
    assert live_kernel_calls(traced) == [2] * forwards + [5]


def test_the_kept_results_give_the_gradients_of_the_recomputed_ones():
    """Keeping ``o`` and the states changes what runs, not what comes
    out: the same kernels on the same operands, to the bit."""
    args = operands(3, 1, 150, 2, 128, 128)
    want, got = (jax.jit(jax.value_and_grad(
        _recomputed(kept), argnums=(0, 1, 2, 3, 4)))(*args)
        for kept in ((), (KDA_SCAN_OUT, KDA_SCAN_STATES)))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# a decay a head (``gdn_scan``): Gated DeltaNet's recurrence, key heads
# under value heads
# ---------------------------------------------------------------------------

def head_operands(seed, b, t, key_heads, heads, kd, vd, decay=1.0):
    """``operands`` with q and k over ``key_heads`` heads, v over
    ``heads``, and ``g`` one number a row a value head."""
    q, k, _, _, _ = operands(seed, b, t, key_heads, kd, vd)
    _, _, v, g, beta = operands(seed + 50, b, t, heads, kd, vd, decay=decay)
    return q, k, v, g[..., 0], beta


def widened(fn):
    """``fn`` (a decay a channel, as many key heads as value heads)
    given ``gdn_scan``'s operands: value head ``j`` reads key head ``j
    // rep``, and every channel the head's decay."""
    def wide(q, k, v, g, beta, **kw):
        rep = v.shape[2] // q.shape[2]
        q, k = (jnp.repeat(z, rep, axis=2) for z in (q, k))
        return fn(q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta,
                  **kw)
    return wide


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("t, key_heads, heads", [
    (128, 1, 1), (150, 2, 4),       # whole chunks, and a tail; 2 under 4
    (96, 3, 3)])
def test_a_decay_a_head_is_the_recurrence_and_kda_fed_the_broadcast_decay(
        chunk, t, key_heads, heads):
    args = head_operands(30, 2, t, key_heads, heads, 32, 24)
    got, fed, ref = outputs_and_gradients(
        args, lambda *a: kda.gdn_scan(*a, chunk=chunk),
        widened(functools.partial(kda.kda_scan, chunk=chunk)),
        widened(recurrence))
    all_close(got, fed, ref)


def test_a_decay_a_head_that_overflows_exp_of_minus_the_running_sum():
    args = head_operands(31, 1, 128, 2, 2, 32, 32, decay=8.0)
    G = np.cumsum(np.asarray(args[3])[:, :64], axis=1)
    with np.errstate(over="ignore"):
        assert np.isinf(np.exp(-G, dtype=np.float32)).any()
    got, ref = outputs_and_gradients(
        args, lambda *a: kda.gdn_scan(*a, chunk=64), widened(recurrence))
    assert np.isfinite(np.asarray(got[0])).all()
    all_close(got, ref)


def test_gdn_scan_refuses_a_decay_a_channel_and_heads_that_do_not_divide():
    q, k, v, g, beta = head_operands(32, 1, 32, 2, 4, 16, 16)
    with pytest.raises(ValueError, match="a decay a head"):
        kda.gdn_scan(q, k, v, jnp.zeros(v.shape), beta, chunk=16)
    with pytest.raises(ValueError, match="a decay a head"):
        kda.gdn_scan(q, k, v[:, :, :3], g[..., :3], beta[..., :3], chunk=16)


def test_gdn_scan_makes_nothing_as_wide_as_the_keys_of_its_decay():
    """No ``[.., K]``-wide decay on the XLA path: no ``exp`` in the traced
    gradient has a result as large as ``q`` widened to the value heads."""
    b, t, hk, h, kd = 1, 128, 2, 4, 32
    args = head_operands(33, b, t, hk, h, kd, kd)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(kda.gdn_scan(*a, chunk=64)),
        argnums=(0, 1, 2, 3, 4)))(*args)
    exps = [e for e in equations(jaxpr) if e.primitive.name == "exp"]
    assert exps
    assert max(int(np.prod(e.outvars[0].aval.shape)) for e in exps) \
        <= b * t * h * 64            # a chunk's [C, C] matrix a head
    assert jaxpr.out_avals[3].shape == (b, t, h)        # dg a row a head


@pytest.mark.parametrize("axis", ["sp", "tp"])
def test_gdn_path_refuses_a_split_sequence_or_split_heads(axis):
    from ray_tpu.parallel import make_mesh
    mesh = make_mesh({axis: 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match=f"{axis}=2"):
        kda.gdn_path((1, 64, 2, 16), 16, mesh, heads=4)
    assert kda.gdn_path((1, 64, 2, 16), 16, None, heads=4) == "xla_chunked"


@pytest.mark.parametrize("case, shape, heads, chunk, want", [
    ("the cell's", (1, 16384, 16, 128), 32, 64, "pallas_chunked"),
    ("one key head a value head", (1, 256, 4, 128), 4, 64, "pallas_chunked"),
    ("a cell's two heads under three", (1, 256, 2, 128), 6, 64,
     "xla_chunked"),
    ("narrow keys", (1, 256, 2, 64), 4, 64, "xla_chunked"),
    ("the tiny preset's chunk", (1, 256, 2, 128), 4, 16, "xla_chunked")])
def test_gdn_path_takes_the_kernels_where_it_observes_they_fit(
        monkeypatch, case, shape, heads, chunk, want):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert kda.gdn_path(shape, chunk, None, heads=heads) == want


def head_three_ways(args, **kw):
    """Of the kernels, of ``xla_chunked``, of the recurrence."""
    return outputs_and_gradients(
        args, lambda *a: kernels.gdn_scan(*a, interpret=True, **kw),
        lambda *a: kda.gdn_scan(*a, chunk=64, **kw), widened(recurrence))


@pytest.mark.parametrize("t, key_heads, heads", [
    (128, 1, 1), (150, 2, 4),       # three chunks in two cells; 2 under 4
    (128, 1, 2)])                   # a cell of two heads on one key head
def test_the_kernels_with_a_decay_a_head_are_the_chunked_form_and_the_rows(
        t, key_heads, heads):
    got, xla, ref = head_three_ways(
        head_operands(40 + heads, 1, t, key_heads, heads, 128, 128))
    assert got[1][0].shape == (1, t, key_heads, 128)        # dq a key head
    assert got[1][3].shape == (1, t, heads)                 # dg a row a head
    all_close(got, xla, ref)


def test_the_kernels_with_a_decay_a_head_at_decays_that_overflow():
    args = head_operands(41, 1, 128, 2, 2, 128, 128, decay=8.0)
    got, xla, ref = head_three_ways(args)
    assert np.isfinite(np.asarray(got[0])).all()
    all_close(got, xla, ref)


def test_the_kernels_with_a_decay_a_head_norm_raw_q_and_k_a_key_head():
    q, k, v, g, beta = raw_operands(42, 1, 200, 2)
    _, _, v, g, beta = head_operands(42, 1, 200, 2, 4, 128, 128)

    def by_row(q, k, *rest):
        return widened(recurrence)(
            kda.unit_rows(q) * q.shape[-1] ** -0.5, kda.unit_rows(k), *rest)
    got, xla, ref = outputs_and_gradients(
        (q, k, v, g, beta),
        lambda *a: kernels.gdn_scan(*a, normalize_qk=True, interpret=True),
        lambda *a: kda.gdn_scan(*a, chunk=64, normalize_qk=True), by_row)
    all_close(got, xla, ref)


def test_the_kernels_with_a_decay_a_head_hold_no_decay_as_wide_as_the_keys():
    """What HBM sees of the decay: ``g`` in and ``dg`` out of both
    ``pallas_call``s are [b, H, T / 128, 1, 128], a float a row a head,
    and ``q``, ``k`` and their cotangents the key heads' alone."""
    b, t, hk, h = 1, 256, 2, 4
    args = head_operands(43, b, t, hk, h, 128, 128)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(kernels.gdn_scan(*a, interpret=True)),
        argnums=(0, 1, 2, 3, 4)))(*args)
    calls = [e for e in equations(jaxpr) if e.primitive.name == "pallas_call"]
    assert len(calls) == 2
    steps, keys, values = (b, h, t // 128, 1, 128), (b, t, hk * 128), \
        (b, t, h * 128)
    fwd, bwd = calls
    assert [v.aval.shape for v in fwd.invars] == [
        keys, keys, steps, values, steps]
    assert [v.aval.shape for v in bwd.outvars] == [
        keys, keys, steps, values, steps]
