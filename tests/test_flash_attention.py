"""Pallas flash-attention kernel numerics (interpret mode on CPU).

The kernel itself runs on TPU; ``interpret=True`` executes the same
program through the Pallas interpreter so block logic, masking, and
the custom VJP are validated in CI without a chip.
"""

import importlib

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.pallas.flash_attention import (
    flash_attention,
    flash_attention_shapes_ok,
)
from ray_tpu.ops.remat import ATTN_LSE, ATTN_OUT, remat_keeps, remat_policy
from ray_tpu.util import tracing

# the package exports the function under the module's name
fa = importlib.import_module("ray_tpu.ops.pallas.flash_attention")


def _rand_qkv(b=2, t=256, h=4, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return tuple(jax.random.normal(k, (b, t, h, d), dtype) for k in ks)


def test_forward_matches_dense():
    q, k, v = _rand_qkv()
    ref = jax.nn.dot_product_attention(q, k, v, is_causal=True)
    out = flash_attention(q, k, v, causal=True, block=64,
                          interpret=True)
    assert float(jnp.abs(out - ref).max()) < 2e-5


def test_forward_non_causal():
    q, k, v = _rand_qkv(t=128)
    ref = jax.nn.dot_product_attention(q, k, v, is_causal=False)
    out = flash_attention(q, k, v, causal=False, block=64,
                          interpret=True)
    assert float(jnp.abs(out - ref).max()) < 2e-5


def test_gradients_match_dense():
    q, k, v = _rand_qkv(t=128)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, block=64,
                                interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (jax.nn.dot_product_attention(
            q, k, v, is_causal=True) ** 2).sum()

    g1 = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.abs(a - b).max()) < 5e-4


def _folded(q, k, v, *, causal, block):
    """The same kernels on [B*H, T, D], one head a block: the layout
    every shape took before the kernels indexed [B, T, H*D], and the
    one a shape that cannot be blocked on 128 lanes still takes."""
    b, t, h, d = q.shape
    static = fa._Static(d ** -0.5, causal, block, d, hpb=1,
                        interpret=True)
    out = fa._flash_core(*(x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
                           for x in (q, k, v)), static)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _out_and_grads(attn, q, k, v):
    def loss(q, k, v):
        o = attn(q, k, v)
        return jnp.sum(o * jnp.cos(o)), o
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return (out, *grads)


@pytest.fixture
def the_square(monkeypatch):
    """Runs what it is given with the single-block bodies held to one
    slab (the square, whatever ``_causal_slabs`` would say): what a
    slabbed run is compared with. The rule is read when the jitted
    kernel functions are traced, so their cached traces go before and
    after."""
    def run(fn):
        with monkeypatch.context() as m:
            m.setattr(fa, "_causal_slabs", lambda t, causal: 1)
            jax.clear_caches()
            try:
                return fn()
            finally:
                jax.clear_caches()
    return run


def _backward_notes(t, block):
    """What a row of several blocks adds to the notes: its backward
    kernel holds dq's ``t`` rows resident (every shape here fits the
    budget); a row of one block says nothing."""
    return {} if t == block else {"flash_bwd_resident_rows": t}


@pytest.mark.parametrize("causal", [True, False],
                         ids=["causal", "full"])
@pytest.mark.parametrize("t,block", [(128, 128), (256, 128), (768, 768)],
                         ids=["single", "multi", "slabs"])
@pytest.mark.parametrize("h,d", [(4, 64), (2, 128)],
                         ids=["two_heads_a_block", "one_head_a_block"])
def test_direct_layout_matches_dense_and_folded(h, d, t, block, causal,
                                                the_square):
    """The kernels on the projections' [B, T, H*D] (two heads in a
    128-lane block at D=64, one at D=128; one block a row, the
    streaming path, and one block a row long enough to be walked as
    causal slabs) against XLA's dense attention and against the same
    kernels on the folded layout: output, dq, dk, dv. Where the body
    walks slabs, also against the same call computing the square."""
    q, k, v = _rand_qkv(b=2, t=t, h=h, d=d, seed=3)
    assert fa._heads_per_block(h, d) == 128 // d
    slabs = 3 if (t == 768 and causal) else 1

    def direct():
        return _out_and_grads(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, block=block, interpret=True),
            q, k, v)
    tracing.take_trace_notes()
    got = direct()
    assert tracing.take_trace_notes() == {
        "flash_layout": "bthd", "flash_lanes_per_block": 128,
        "flash_path": "single_block" if t == block else "multi_block",
        "flash_causal_slabs": slabs, **_backward_notes(t, block)}
    dense = _out_and_grads(
        lambda q, k, v: jax.nn.dot_product_attention(
            q, k, v, is_causal=causal), q, k, v)
    folded = _out_and_grads(
        lambda q, k, v: _folded(q, k, v, causal=causal, block=block),
        q, k, v)
    square = the_square(direct) if slabs > 1 else got
    for name, a, ref, f, sq in zip(("out", "dq", "dk", "dv"), got, dense,
                                   folded, square):
        assert float(jnp.abs(a - ref).max()) < 5e-4, name
        assert float(jnp.abs(a - f).max()) < 2e-5, name
        assert float(jnp.abs(a - sq).max()) < 2e-5, name


@pytest.mark.parametrize("h,d", [(2, 64), (1, 128), (3, 64)],
                         ids=["two_heads_a_block", "one_head_a_block",
                              "folded"])
def test_slabs_leave_the_rows_log_sum_exp_the_squares(h, d, the_square):
    """``lse``, which the backward reads in place of the softmax's
    statistics: a slab's rows written into their own lanes of the
    [hpb, T] rows, equal to the square's and to the dense scores'."""
    t = 768
    q, k, v = _rand_qkv(b=1, t=t, h=h, d=d, seed=5)
    hpb = fa._heads_per_block(h, d) or 1
    if fa._heads_per_block(h, d):
        args = [x.reshape(1, t, h * d) for x in (q, k, v)]
    else:
        args = [x.transpose(0, 2, 1, 3).reshape(h, t, d) for x in (q, k, v)]
    assert fa._causal_slabs(t, True) == 3

    def run():
        return fa._flash_fwd(*args, scale=d ** -0.5, causal=True, blk=t,
                             d=d, hpb=hpb, interpret=True)[1]
    lse = run()
    assert float(jnp.abs(lse - the_square(run)).max()) < 2e-6
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    want = jax.nn.logsumexp(s, axis=-1)                    # [1, H, T]
    assert float(jnp.abs(lse.reshape(h, t) - want[0]).max()) < 2e-5


@pytest.mark.parametrize("t,causal,slabs", [
    (128, True, 1), (512, True, 1), (768, False, 1), (1000, True, 1),
    (896, True, 1), (768, True, 3), (1024, True, 4), (1024, False, 1),
], ids=["t128", "two_slabs_do_not_pay", "t768_not_causal",
        "rows_off_the_tiles", "t896_not_whole_slabs", "t768", "t1024",
        "t1024_not_causal"])
def test_slabs_are_chosen_from_causal_and_the_rows_alone(t, causal, slabs):
    """One rule (``_causal_slabs``), and what it decided in the
    trace's notes: 1 where the square is computed — not causal, or a
    row that three or more slabs of whole tiles do not tile."""
    x = jax.ShapeDtypeStruct((1, t, 2, 64), jnp.bfloat16)
    tracing.take_trace_notes()
    jax.eval_shape(
        lambda q, k, v: flash_attention(q, k, v, causal=causal), x, x, x)
    notes = tracing.take_trace_notes()
    assert notes["flash_path"] == "single_block"
    assert notes["flash_causal_slabs"] == slabs == fa._causal_slabs(
        t, causal)


@pytest.mark.parametrize("h,d,lanes", [(3, 64, 64), (4, 32, 32),
                                       (2, 192, 192)],
                         ids=["odd_heads", "d32", "d192"])
def test_shapes_off_the_lane_tiles_take_the_fold_and_say_so(h, d, lanes):
    """An odd head count at D=64 (H*D is not a multiple of 128) and a
    head width that is neither 64 nor a multiple of 128: decided from
    the shapes, noted for the trace span, and still right."""
    q, k, v = _rand_qkv(b=1, t=128, h=h, d=d, seed=4)
    assert fa._heads_per_block(h, d) == 0
    tracing.take_trace_notes()
    out = flash_attention(q, k, v, causal=True, interpret=True)
    assert tracing.take_trace_notes() == {
        "flash_layout": "folded", "flash_lanes_per_block": lanes,
        "flash_path": "single_block", "flash_causal_slabs": 1}
    ref = jax.nn.dot_product_attention(q, k, v, is_causal=True)
    assert float(jnp.abs(out - ref).max()) < 2e-5


def test_rejects_non_blockable_seq():
    q, k, v = _rand_qkv(t=100)
    with pytest.raises(ValueError, match="not divisible"):
        flash_attention(q, k, v, block=64, interpret=True)


def test_shapes_ok_helper():
    assert flash_attention_shapes_ok(1024, 64)
    assert not flash_attention_shapes_ok(100, 64)   # seq too odd
    assert not flash_attention_shapes_ok(1024, 50)  # head dim % 8


def test_causal_attention_dispatch_cpu_fallback():
    # On the CPU test backend flash never fires; the dense path must
    # serve any shape.
    q, k, v = _rand_qkv(t=100)
    out = causal_attention(q, k, v)
    ref = jax.nn.dot_product_attention(q, k, v, is_causal=True)
    assert float(jnp.abs(out - ref).max()) < 1e-6


@pytest.mark.parametrize("backend, force_flash, t, kernel", [
    ("tpu", True, 256, True),       # a one-device program, shapes block
    ("tpu", False, 256, False),     # the tests' process holds 8 devices
    ("tpu", True, 100, False),      # 100 rows do not block
    ("cpu", True, 256, False),
], ids=["one_device_program", "process_of_many_devices",
        "rows_do_not_block", "no_tpu"])
def test_causal_attention_decides_from_backend_shapes_and_devices(
        monkeypatch, backend, force_flash, t, kernel):
    """The whole decision, traced and not run: the kernel where the
    backend is a TPU, the shapes block and the program is one
    device's; XLA's attention otherwise. The kernel says that it was
    traced in the trace's notes."""
    assert jax.device_count() > 1
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    x = jax.ShapeDtypeStruct((2, t, 4, 64), jnp.bfloat16)
    tracing.take_trace_notes()
    out = jax.eval_shape(
        lambda q, k, v: causal_attention(q, k, v, force_flash=force_flash),
        x, x, x)
    assert (out.shape, out.dtype) == (x.shape, x.dtype)
    assert ("flash_path" in tracing.take_trace_notes()) == kernel


@pytest.mark.parametrize("shape, layout, lanes, path, slabs", [
    ((32, 1024, 12, 64), "bthd", 128, "single_block", 4),
    ((4, 4096, 16, 128), "bthd", 128, "multi_block", 1),
    ((8, 2048, 32, 128), "bthd", 128, "multi_block", 1),
    ((1, 1024, 3, 64), "folded", 64, "single_block", 4),
], ids=["gpt2_cells", "olmoe_cell", "d128_t2048", "odd_heads_t1024"])
def test_the_cells_shapes_choose_what_their_trace_notes_say(
        shape, layout, lanes, path, slabs):
    """The benchmark's cells at their real shapes (a chip's share of
    the batch), by nothing but the shapes: the notes that every
    ``train.compile`` span of a cell's step carries (PERF.md section
    3), and one folded shape at the cells' length. The GPT-2 cells'
    one block a row is walked as causal slabs; the streaming path
    skips whole blocks through its grid and says 1. Traced only: no
    kernel runs."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    tracing.take_trace_notes()
    out = jax.eval_shape(
        lambda q, k, v: flash_attention(q, k, v, causal=True), x, x, x)
    assert (out.shape, out.dtype) == (shape, jnp.bfloat16)
    assert tracing.take_trace_notes() == {
        "flash_layout": layout, "flash_lanes_per_block": lanes,
        "flash_path": path, "flash_causal_slabs": slabs,
        **_backward_notes(shape[1], 1024)}


# -- the backward pass of a row of several blocks --------------------------

@pytest.fixture
def notes(monkeypatch):
    """What ``flash_attention`` says of itself, caught at the call (a
    train step's listener, once a test of this process has built one,
    takes the thread's notes away at the next trace)."""
    said = {}
    monkeypatch.setattr(tracing, "note_trace", said.update)
    return said


def _dense(q, k, v, causal):
    return jax.nn.dot_product_attention(q, k, v, is_causal=causal)


def _grads(attn, q, k, v):
    return _out_and_grads(attn, q, k, v)[1:]


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("blocks", [2, 3, 4])
@pytest.mark.parametrize("h,d", [(1, 128), (2, 64)],
                         ids=["one_head_a_block", "two_heads_a_block"])
def test_multi_block_backward_is_the_dense_softmaxs(h, d, blocks, causal,
                                                    notes):
    """``_bwd_kernel``: dq, dk, dv of a row of 2, 3 and 4 blocks against
    the dense softmax's. ``dq`` rides in scratch across the key blocks:
    not causal it leaves in the last key block's cells, causal at its
    diagonal."""
    q, k, v = _rand_qkv(b=2, t=64 * blocks, h=h, d=d, seed=7)
    got = _grads(lambda *a: flash_attention(
        *a, causal=causal, block=64, interpret=True), q, k, v)
    assert notes["flash_path"] == "multi_block"
    assert notes["flash_bwd_resident_rows"] == 64 * blocks
    dense = _grads(lambda *a: _dense(*a, causal), q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, dense):
        assert float(jnp.abs(a - b).max()) < 5e-5, name


@pytest.mark.parametrize("window", [None, 70], ids=["causal", "window"])
def test_the_backward_kernels_gradients_are_the_same_run_to_run(window):
    """What ``correct`` leans on: the sums into ``dq``, ``dk`` and ``dv``
    run in the grid's order, so two compiles of one program give the
    same bits (three blocks, two rows of the batch, two heads a lane
    block; under a window the band's cells alone)."""
    q, k, v = _rand_qkv(b=2, t=192, h=2, d=64, seed=13)

    def run():
        jax.clear_caches()
        return _grads(lambda *a: flash_attention(
            *a, block=64, window=window, interpret=True), q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), run(), run()):
        assert bool((a == b).all()), name


MiB = 1 << 20
HELD = 30_998_528   # _bwd_bytes(16384, 1024, 128): 544 a row + 22.1 MB a cell


@pytest.mark.parametrize(
    "t, block, d, window, budget, fits", [
        (16384, None, 128, None, None, True),   # the SmallThinker cell's row
        (16384, None, 128, 4096, None, True),   # and its windowed layers'
        (8192, None, 128, None, None, True),    # ZAYA's and Nemotron's
        (4096, None, 128, None, None, True),    # OLMoE's
        (65536, None, 128, None, None, True),   # 34 MiB of dq
        (131072, None, 128, None, None, False),     # 68: the rows decide
        (131072, None, 128, 4096, None, False),     # a window holds as much
        (131072, None, 64, None, None, False),      # two heads a lane block
        (131072, None, 32, None, None, False),  # folded: 32 lanes pad to 128
        (16384, None, 128, None, 24 * MiB, False),  # the budget decides
        (16384, 512, 128, None, 24 * MiB, True),    # the blocks decide
        (16384, None, 128, None, HELD, True),       # to the byte
        (16384, None, 128, None, HELD - 1, False),
        (4096, None, 64, None, None, True),     # two heads a lane block
        (4096, None, 32, None, None, True),     # folded
        (1024, None, 128, None, 0, None),   # one block: the fused body
    ], ids=lambda v: str(v))
def test_a_row_past_the_backward_kernels_budget_is_refused_by_name(
        t, block, d, window, budget, fits, notes, monkeypatch):
    """A row of several blocks whose resident rows fit ``_BWD_VMEM``
    takes ``_bwd_kernel`` and says how many rows it holds; one past it
    raises ``NotImplementedError`` with the rows, the bytes and the
    budget, before anything is noted or handed to the jitted functions
    (a ``ValueError`` would send a caller to the dense path); a row of
    one block never meets the budget. Traced only: no kernel runs."""
    assert fa._bwd_bytes(16384, 1024, 128) == HELD
    handed = []
    monkeypatch.setattr(fa, "_flash_core",
                        lambda q, k, v, static: handed.append(static) or q)
    if budget is not None:
        monkeypatch.setattr(fa, "_BWD_VMEM", budget)
    x = jax.ShapeDtypeStruct((1, t, 2, d), jnp.bfloat16)

    def trace():
        jax.eval_shape(lambda q, k, v: flash_attention(
            q, k, v, block=block, window=window), x, x, x)
    if fits is False:
        asked = fa._bwd_bytes(t, block or 1024, d)
        with pytest.raises(NotImplementedError) as refused:
            trace()
        said = str(refused.value)
        lanes = 32 if d == 32 else 128      # a folded head's own lanes
        for part in (f"{t} rows", f"{lanes} lanes", f"{asked} bytes",
                     f"budget of {fa._BWD_VMEM}", "`sp` mesh axis",
                     "ROADMAP C4"):
            assert part in said, (part, said)
        assert not handed and not notes
        return
    trace()
    [static] = handed
    assert static.blk == (block or 1024) == static.bk
    assert notes.get("flash_bwd_resident_rows") == (t if fits else None)
    assert notes["flash_path"] == (
        "multi_block" if fits else "single_block")


def test_nothing_in_a_signature_chooses_a_backward():
    """One block size and no switch: the shapes decide, and what they
    cannot hold is refused."""
    import inspect
    assert list(inspect.signature(flash_attention).parameters) == [
        "q", "k", "v", "causal", "scale", "block", "interpret", "window"]
    assert list(inspect.signature(fa.mla_flash_static).parameters) == [
        "t", "dn", "dr", "scale", "block", "interpret"]
    assert fa._Static._fields == (
        "scale", "causal", "blk", "d", "hpb", "interpret", "window")
    assert fa._MlaStatic._fields == (
        "scale", "block", "dn", "dr", "interpret")


@pytest.mark.parametrize("window", [None, 4096], ids=["causal", "window"])
@pytest.mark.parametrize("sharded", [False, True], ids=["bare", "dp_mesh"])
def test_causal_attention_surfaces_the_refusal_and_takes_no_dense_path(
        sharded, window, monkeypatch):
    """The dispatch above the kernel: where the kernel is eligible and
    the row is past its budget, ``causal_attention`` (bare, and as
    ``make_sharded_causal_attention`` maps it over ``dp``) raises the
    kernel's refusal; XLA's dense attention, whose ``[T, T]`` scores at
    131,072 rows are 69 GB a head, is never asked."""
    from jax.sharding import Mesh
    from ray_tpu.ops import attention
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def dense(*a, **kw):
        raise AssertionError("the dense path was taken")
    monkeypatch.setattr(jax.nn, "dot_product_attention", dense)
    if sharded:
        attn = attention.make_sharded_causal_attention(
            Mesh(jax.devices()[:2], ("dp",)), window=window)
    else:
        def attn(q, k, v):
            return causal_attention(q, k, v, force_flash=True,
                                    window=window)
    x = jax.ShapeDtypeStruct((2, 131072, 2, 128), jnp.bfloat16)
    with pytest.raises(NotImplementedError, match="131072 rows"):
        jax.eval_shape(attn, x, x, x)


def test_the_backward_kernel_under_a_shard_map_over_dp(notes):
    """Each chip's own rows of the batch through the kernels (what
    ``make_sharded_causal_attention`` does on a dp mesh): the gradients
    of the sharded call are those of the whole batch in one call."""
    from jax.sharding import Mesh, PartitionSpec as P
    q, k, v = _rand_qkv(b=4, t=192, h=2, d=64, seed=11)

    def flash(q, k, v):
        return flash_attention(q, k, v, block=64, interpret=True)
    mesh = Mesh(jax.devices()[:4], ("dp",))
    sharded = jax.shard_map(flash, mesh=mesh, in_specs=P("dp"),
                            out_specs=P("dp"), check_vma=False)
    got = _grads(sharded, q, k, v)
    assert notes["flash_bwd_resident_rows"] == 192
    for name, a, b in zip(("dq", "dk", "dv"), got,
                          _grads(flash, q, k, v)):
        assert float(jnp.abs(a - b).max()) < 1e-6, name


def test_the_benchmarks_fault_tool_reaches_the_backward_by_static(notes):
    """``benchmark/tools/smallthinker_limit.py`` plants a fault by calling
    ``_flash_bwd(*res, g, **static._asdict())`` with the window a key
    block (``static.bk``) wider: the names it reads stay, and the wider
    band moves the gradients."""
    q, k, v = _rand_qkv(b=1, t=256, h=1, d=128, seed=17)
    static = fa._Static(128 ** -0.5, True, 64, 128, 1, True, window=70)
    x = [a.reshape(1, 256, 128) for a in (q, k, v)]
    out, lse = fa._flash_fwd(*x, **static._asdict())
    wider = static._replace(window=static.window + static.bk)
    right = fa._flash_bwd(*x, out, lse, out, **static._asdict())
    wrong = fa._flash_bwd(*x, out, lse, out, **wider._asdict())
    assert wider.window == 134
    assert float(jnp.abs(right[0] - wrong[0]).max()) > 1e-3


# ---------------------------------------------------------------------------
# a recomputed block keeps its core's output and row statistics
# ---------------------------------------------------------------------------

def _kernel_calls(jaxpr) -> int:
    """``pallas_call`` equations in a jaxpr, the jaxprs its equations
    hold (a jit's, a checkpoint's, a custom rule's) among them."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _kernel_calls(sub)
    return n


def _block_of(core):
    """A block round an attention core, as the models build it: the
    core's operands are projections of the block's input, made inside
    it, and its output goes through a gate and a matmul, so a
    recomputed block has to make q, k and v again and reads ``out``
    twice. Returns (block(x, w) -> a number, x, w)."""
    t, e = 256, 128
    kx, kw = jax.random.split(jax.random.key(23))
    x = jax.random.normal(kx, (2, t, e))
    if core.startswith("latent"):
        from ray_tpu.models.llama import rope_freqs
        from ray_tpu.ops import mla
        ks = jax.random.split(kw, 6)
        angles = rope_freqs(64, t, 10000.0)
        w = (mla.UpProjections(*(
            jax.random.normal(k, (r, 2 * d)) * 0.2
            for k, r, d in zip(ks, (32, 32, 16, 16), (128, 64, 128, 128)))),
            jax.random.normal(ks[4], (e, 32 + 16 + 64)) * 0.1,
            jax.random.normal(ks[5], (2 * 128, e)) * 0.1)

        def block(x, w):
            up, down, out_w = w
            c = x @ down
            o = mla.latent_attention(
                c[..., :32], c[..., 32:48], c[..., 48:], up, angles,
                n_head=2, saved=core.removeprefix("latent_"),
                interpret=True)
            return ((o * jax.nn.sigmoid(o)) @ out_w * x).sum()
        return block, x, w
    window = {"causal": None, "window": 70}[core]
    ks = jax.random.split(kw, 2)
    w = (jax.random.normal(ks[0], (e, 3 * 2 * 64)) * 0.1,
         jax.random.normal(ks[1], (2 * 64, e)) * 0.1)

    def block(x, w):
        qkv_w, out_w = w
        q, k, v = jnp.split((x @ qkv_w).reshape(2, t, 6, 64), 3, axis=2)
        o = flash_attention(q, k, v, block=64, interpret=True,
                            window=window).reshape(2, t, -1)
        return ((o * jax.nn.sigmoid(o)) @ out_w * x).sum()
    return block, x, w


def test_the_policy_keeps_a_models_own_names_first():
    assert remat_keeps() == (ATTN_OUT, ATTN_LSE) == ("attn_out", "attn_lse")
    assert remat_keeps("kda_gated_out") == (
        "kda_gated_out", "attn_out", "attn_lse")


@pytest.mark.parametrize(
    "core", ["causal", "window", "latent_latents", "latent_expanded"])
def test_a_recomputed_block_runs_the_forward_kernel_once(core):
    """Under ``jax.checkpoint`` with ``remat_policy()`` the gradient of
    a block holds two kernel calls, the forward and the backward, where
    a checkpoint without a policy holds three (the forward again, for
    an ``out`` and an ``lse`` the first pass made); the loss and the
    gradients are the same bits as without any checkpoint, since the
    backward kernel reads the same ``out`` and ``lse``; and outside a
    checkpoint the names are the identity: two calls, as before them.
    The gradients are run operation by operation: under a ``jit`` the
    three are three programs, and XLA's CPU compiler fuses the latent
    core's float32 rotation differently in each (an ulp, with the
    policy or without any), which says nothing of what the operations
    are."""
    block, x, w = _block_of(core)

    def grad(f):
        return jax.value_and_grad(f, argnums=(0, 1))
    kept = grad(jax.checkpoint(block, policy=remat_policy()))
    more = grad(jax.checkpoint(block, policy=remat_policy("another")))
    again = grad(jax.checkpoint(block))
    plain = grad(block)
    calls = {name: _kernel_calls(jax.make_jaxpr(f)(x, w).jaxpr)
             for name, f in [("kept", kept), ("more", more),
                             ("again", again), ("plain", plain)]}
    assert calls == {"kept": 2, "more": 2, "again": 3, "plain": 2}
    want = jax.tree_util.tree_leaves(plain(x, w))
    got = jax.tree_util.tree_leaves(kept(x, w))
    assert len(got) == len(want) > 3
    assert all(bool((a == b).all()) for a, b in zip(got, want))
    assert all(float(jnp.abs(a).max()) > 0 for a in want)
