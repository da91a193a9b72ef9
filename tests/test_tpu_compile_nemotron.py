"""The Nemotron-H cell's new pieces compile for the real chip, with no
chip here (as ``test_tpu_compile.py``: the TPU compiler for a described
v5e; nothing runs, so nothing here is a result or a time)."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def _arg(device):
    one = SingleDeviceSharding(device)
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)


def test_held_experts_compile_for_v5e_at_the_cells_widths(v5e, monkeypatch):
    """8 of 128 relu^2 experts at 2,688 x 1,856 (neither a multiple of
    the kernel's 1,024 tile; 1,856 is 14.5 lane tiles) over a slab of
    6,144 of the 49,152 sorted routes, forward and backward: six
    megablox custom calls (two matrices, each forward, for its input
    and for its weights) inside the slab loop. The sigmoid router over
    the 128 experts chooses by ``ops/pallas/router_choice.py``'s pair."""
    from ray_tpu.ops import moe
    from ray_tpu.util import tracing

    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.held_rows(8192 * 6, 8, 128) == 6144
    arg = _arg(v5e[0])

    def loss(x, router, up, down, bias):
        y, _, _, load = moe.routed_ffn(
            x, router, None, up, down, top_k=6, norm_topk_prob=True,
            router="sigmoid", select_bias=bias, route_scale=2.5,
            expert="relu2", experts_held=(0, 8))
        return y.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        arg((1, 8192, 2688), jnp.bfloat16), arg((2688, 128), jnp.float32),
        arg((8, 2688, 1856), jnp.float32), arg((8, 1856, 2688), jnp.float32),
        arg((128,), jnp.float32)).compile().as_text()
    assert text.count("tpu_custom_call") >= 6
    # the router's choice is the kernel pair, once each, under the
    # layer's ``router`` scope, and no ``top_k`` or gather beside it
    assert notes["moe_router_path"] == "pallas"
    for kernel in ("_choice_fwd", "_choice_bwd"):
        assert len(re.findall(
            r"custom-call\(.*router\)*/jit\(%s\)\)*/pallas_call" % kernel,
            text)) == 1, kernel
    assert not re.search(r"router\)*/(top_k|gather|scatter)", text)


def test_chunked_scan_compiles_for_v5e_at_the_cells_shape(v5e):
    """One sequence of 8,192 tokens, 64 heads of 64, state 128 in 8
    groups, chunks of 128, in bfloat16, forward and backward: the
    program keeps no ``[128, 128]`` square of any chunk for the backward
    and fits a chip many times over."""
    from ray_tpu.ops import ssm
    arg = _arg(v5e[0])

    def loss(x, dt, a, b, c, d):
        return ssm.mamba2_scan(x, dt, a, b, c, d, chunk=128).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        arg((1, 8192, 64, 64), jnp.bfloat16), arg((1, 8192, 64), jnp.float32),
        arg((64,), jnp.float32), arg((1, 8192, 8, 128), jnp.bfloat16),
        arg((1, 8192, 8, 128), jnp.bfloat16), arg((64,), jnp.float32)
    ).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 4e9


def test_kernel_scan_compiles_for_v5e_at_the_cells_shape(v5e, monkeypatch):
    """The same shape where the backend is a TPU: ``mamba2_scan`` takes
    the Pallas kernels, one custom call a pass, and no ``[128, 128]``
    square of any chunk is an array of the program (268 MB each in
    float32 on the XLA path): the temporaries are the boundary states
    and the small per-step vectors."""
    from ray_tpu.ops import ssm
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)   # the cell's chip
    assert ssm.scan_path((1, 8192, 64, 64), (1, 8192, 8, 128),
                         128) == "pallas_chunked"
    arg = _arg(v5e[0])

    def loss(x, dt, a, b, c, d):
        return ssm.mamba2_scan(x, dt, a, b, c, d, chunk=128).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        arg((1, 8192, 64, 64), jnp.bfloat16), arg((1, 8192, 64), jnp.float32),
        arg((64,), jnp.float32), arg((1, 8192, 8, 128), jnp.bfloat16),
        arg((1, 8192, 8, 128), jnp.bfloat16), arg((64,), jnp.float32)
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


def _dp(v5e):
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.array(v5e), ("dp",))
    rows = NamedSharding(mesh, PartitionSpec("dp"))
    whole = NamedSharding(mesh, PartitionSpec())
    return mesh, (lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=rows)), (
        lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, dtype, sharding=whole))


@pytest.mark.parametrize("given_the_mesh", [True, False],
                         ids=["given_the_mesh", "given_no_mesh"])
def test_scan_compiles_for_four_chips_with_the_batch_over_dp(
        v5e, monkeypatch, given_the_mesh):
    """Four sequences at the cell's shape, one a chip of the described
    2x2, forward and backward. A ``pallas_call`` has no SPMD rule
    ("Mosaic kernels cannot be automatically partitioned"): given the
    mesh, the kernels run under a ``shard_map`` over ``dp``, a custom
    call a pass on each chip; given none, in a process that sees more
    than one device, the scan is XLA's. Either way no chip gathers
    another's rows: the one collective is the sum of ``A``'s and
    ``D``'s gradients."""
    from ray_tpu.ops import ssm
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert jax.device_count() > 1
    mesh, rows, whole = _dp(v5e)
    given = mesh if given_the_mesh else None

    def loss(x, dt, a, b, c, d):
        return ssm.mamba2_scan(x, dt, a, b, c, d, chunk=128,
                               mesh=given).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        rows((4, 8192, 64, 64), jnp.bfloat16),
        rows((4, 8192, 64), jnp.float32), whole((64,), jnp.float32),
        rows((4, 8192, 8, 128), jnp.bfloat16),
        rows((4, 8192, 8, 128), jnp.bfloat16), whole((64,), jnp.float32)
    ).compile()
    text = compiled.as_text()
    assert (text.count("tpu_custom_call") >= 2) == given_the_mesh
    assert "all-gather" not in text
    assert "all-reduce" in text
    assert compiled.memory_analysis().temp_size_in_bytes < (
        1e9 if given_the_mesh else 4e9)


@pytest.mark.parametrize("given_the_mesh", [True, False],
                         ids=["given_the_mesh", "given_no_mesh"])
def test_a_mamba_layer_compiles_for_four_chips_with_the_batch_over_dp(
        v5e, monkeypatch, given_the_mesh):
    """The model hands its mesh to the scan: an ``M`` layer whose
    shapes the kernels tile, loss and gradients, the batch over ``dp``
    on the described 2x2. The trace's ``ssm_path`` note says which scan
    each program got."""
    from ray_tpu.models.nemotron_h import (
        NemotronH, NemotronHConfig, nemotron_h_loss_fn,
    )
    from ray_tpu.util import tracing
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh, rows, whole = _dp(v5e)
    cfg = NemotronHConfig.tiny(
        pattern="M", seq_len=256, mamba_heads=8, mamba_head_dim=16,
        ssm_state=128, ssm_groups=1, chunk=128)
    model = NemotronH(cfg, mesh=mesh if given_the_mesh else None)
    params = jax.tree.map(
        lambda z: whole(z.shape, z.dtype),
        jax.eval_shape(NemotronH(cfg).init_params, jax.random.key(0)))
    batch = {k: rows((4, cfg.seq_len), jnp.int32)
             for k in ("tokens", "targets")}
    loss = nemotron_h_loss_fn(model, ce_chunk=64)
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    text = jax.jit(jax.grad(lambda p, b: loss(p, b)[0])).lower(
        params, batch).compile().as_text()
    want = "pallas_chunked" if given_the_mesh else "chunked_xla"
    assert notes["ssm_path"] == want
    assert (text.count("tpu_custom_call") >= 2) == given_the_mesh


@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip", "dp_on_the_2x2"])
def test_a_mamba_layer_at_the_cells_shape_keeps_the_kernels_layout(
        v5e, monkeypatch, chips):
    """One ``M`` layer at the cell's widths and 8,192 tokens, loss and
    gradients, on one chip and with a sequence a chip over ``dp``: the
    scan's two custom calls, the gated norm's two, the convolution's two
    (PR 55) and the loss's one (under the ``shard_map`` over ``dp`` as on
    one chip), the notes name the kernels, and under ``gate_norm`` no
    float32 ``[rows, 8192, 4096]`` array is left in the optimised HLO:
    the XLA norm behind the scan's custom call relaid one out three times
    a layer (PERF.md section 6, PR 37); the kernels read and write
    bfloat16."""
    import re

    import numpy as np
    from jax.sharding import Mesh

    from ray_tpu.models.nemotron_h import (
        NemotronH, NemotronHConfig, nemotron_h_loss_fn,
    )
    from ray_tpu.util import tracing
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if chips == 1:
        mesh = Mesh(np.array(v5e[:1]), ("dp",))
        rows = whole = _arg(v5e[0])
    else:
        mesh, rows, whole = _dp(v5e)
    cfg = NemotronHConfig.nemotron_3_nano_30b_a3b(pattern="M",
                                                  vocab_size=16384)
    model = NemotronH(cfg, mesh=mesh)
    params = jax.tree.map(
        lambda z: whole(z.shape, z.dtype),
        jax.eval_shape(lambda k: NemotronH(cfg).init_params(k, 1),
                       jax.random.key(0)))
    batch = {k: rows((chips, cfg.seq_len), jnp.int32)
             for k in ("tokens", "targets")}
    loss = nemotron_h_loss_fn(model)
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    text = jax.jit(jax.grad(lambda p, b: loss(p, b)[0])).lower(
        params, batch).compile().as_text()
    assert notes["ssm_path"] == "pallas_chunked"
    assert notes["gate_norm_path"] == "pallas"
    assert notes["conv_path"] == "pallas"
    assert (notes["conv_taps"], notes["conv_cols"]) == (4, 6144)
    # and the head's forward, one more (PR 51: ``ops/pallas/ce_lse.py``)
    assert notes["ce_path"] == "pallas_lse"
    assert text.count("tpu_custom_call") == 7
    for kernel in ("_conv_fwd", "_conv_bwd"):
        assert len(re.findall(
            r"custom-call\(.*/conv/.*jit\(%s\)" % kernel, text)) == 1
    assert len(re.findall(r"custom-call\(.*jit\(_ce_lse_fwd\)", text)) == 1
    assert len(re.findall(r"custom-call\(.*gate_norm", text)) == 2
    under = [ln for ln in text.splitlines() if "gate_norm" in ln]
    assert under
    assert not [ln for ln in under
                if re.search(r"= \(?f32\[\d+,8192,4096\]", ln)]


def test_layers_at_one_shape_trace_each_scan_kernel_once(monkeypatch):
    """What holds ``setup_s`` (PERF.md section 6, PR 28: a kernel's
    price is per ``pallas_call`` equation): the two functions that hold
    the ``pallas_call``s are jitted, so two layers of one step trace
    each kernel's body once and lower to one function a kernel, called
    twice; a second trace of the step finds the first one's.
    Interpreted, on the CPU: counting traces needs nothing of the
    chip."""
    from ray_tpu.ops.pallas import ssd_scan
    bodies = []
    sums = ssd_scan._running_sums       # each kernel's body, once
    monkeypatch.setattr(
        ssd_scan, "_running_sums",
        lambda *a: bodies.append(1) or sums(*a))
    x = jax.ShapeDtypeStruct((1, 128, 8, 32), jnp.float32)  # no other test's
    dt = jax.ShapeDtypeStruct((1, 128, 8), jnp.float32)
    bc = jax.ShapeDtypeStruct((1, 128, 1, 128), jnp.float32)
    head = jax.ShapeDtypeStruct((8,), jnp.float32)

    def two_layers(x, dt, a, b, c, d):
        for i in range(2):
            with jax.named_scope(f"h_{i}"):
                x = ssd_scan.ssd_scan(x, dt, a, b, c, d, chunk=128,
                                      interpret=True)
        return x.sum()

    text = jax.jit(jax.grad(two_layers, argnums=(0, 1))).lower(
        x, dt, head, bc, bc, head).as_text()
    assert text.count("call @_ssd_fwd") == 2
    assert text.count("call @_ssd_bwd") == 2
    assert len(bodies) == 2             # forward's and backward's

    def again(*args):                   # a new function: a new trace
        return two_layers(*args) * 2.0

    jax.jit(jax.grad(again, argnums=(0, 1))).lower(x, dt, head, bc, bc, head)
    assert len(bodies) == 2


def test_layers_at_one_shape_trace_each_norm_kernel_once(monkeypatch):
    """The same for the gated norm's two kernels."""
    from ray_tpu.ops.pallas import gated_norm
    bodies = []
    strips = gated_norm._strips         # each kernel's body, once
    monkeypatch.setattr(
        gated_norm, "_strips",
        lambda *a: bodies.append(1) or strips(*a))
    rows = jax.ShapeDtypeStruct((1, 48, 384), jnp.float32)  # no other test's
    scale = jax.ShapeDtypeStruct((384,), jnp.float32)

    def two_layers(y, z, scale):
        for i in range(2):
            with jax.named_scope(f"h_{i}"):
                y = gated_norm.gated_norm(y, z, scale, groups=3, eps=1e-5,
                                          interpret=True)
        return (y ** 2).sum()       # so the last layer's value is used

    text = jax.jit(jax.grad(two_layers, argnums=(0, 1, 2))).lower(
        rows, rows, scale).as_text()
    assert text.count("call @_norm_fwd") == 2
    assert text.count("call @_norm_bwd") == 2
    assert len(bodies) == 2             # forward's and backward's

    def again(*args):                   # a new function: a new trace
        return two_layers(*args) * 2.0

    jax.jit(jax.grad(again, argnums=(0, 1, 2))).lower(rows, rows, scale)
    assert len(bodies) == 2


def test_layers_at_one_shape_trace_each_conv_kernel_once(monkeypatch):
    """The same for the convolution's two kernels (PR 55: 36 calls a
    step in the Kimi-Linear cell)."""
    from ray_tpu.ops.pallas import causal_conv
    bodies = []
    steps = causal_conv._steps          # each kernel's body, once
    monkeypatch.setattr(
        causal_conv, "_steps",
        lambda *a, **kw: bodies.append(1) or steps(*a, **kw))
    rows = jax.ShapeDtypeStruct((1, 48, 640), jnp.float32)  # no other test's
    taps = jax.ShapeDtypeStruct((4, 640), jnp.float32)

    def two_layers(x, w):
        for i in range(2):
            with jax.named_scope(f"h_{i}"):
                x = causal_conv.causal_conv(x, w, interpret=True)
        return (x ** 2).sum()

    text = jax.jit(jax.grad(two_layers, argnums=(0, 1))).lower(
        rows, taps).as_text()
    assert text.count("call @_conv_fwd") == 2
    assert text.count("call @_conv_bwd") == 2
    assert len(bodies) == 2             # forward's and backward's

    def again(*args):                   # a new function: a new trace
        return two_layers(*args) * 2.0

    jax.jit(jax.grad(again, argnums=(0, 1))).lower(rows, taps)
    assert len(bodies) == 2
