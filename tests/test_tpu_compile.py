"""The main path's kernels compile for the real chip, with no chip here.

The TPU compiler is installed in the sandbox and compiles for a chip
that is described and not attached (on-chip-measurement guide, §2,
third rehearsal): it refuses what interpret mode lets through — a
slice off the tiling, too much VMEM, a kernel that cannot be
partitioned. Nothing runs, so nothing here is a result or a time.
"""

import importlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import (  # noqa: E402
    Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding,
)

from ray_tpu.ops.pallas.flash_attention import flash_attention  # noqa: E402

# [batch, seq, heads, head_dim]: chip_smoke.py's GPT-2 124M shape, one
# head-dim-128 shape (multi-block path: 2048 = 2 x 1024 blocks), and the
# OLMoE cell's (olmoe-1b-7b.b4-t4096: four 1,024-blocks at D=128).
SHAPES = [(32, 1024, 12, 64), (8, 2048, 32, 128), (4, 4096, 16, 128)]


def _loss(q, k, v):
    return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("shape", SHAPES,
                         ids=["x".join(map(str, s)) for s in SHAPES])
def test_flash_kernel_compiles_for_v5e(v5e, shape, grad):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e[0]))
    fn = jax.grad(_loss, argnums=(0, 1, 2)) if grad else _loss
    text = jax.jit(fn).lower(x, x, x).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape, slabs", [
    ((4, 1024, 3, 64), 4), ((8, 1024, 16, 128), 4), ((8, 768, 12, 64), 3),
], ids=["folded_one_head_a_block", "d128_one_head_a_block", "three_slabs"])
def test_causal_slabs_compile_for_v5e_off_the_cells_shapes(
        v5e, shape, slabs):
    """The single-block bodies walked as causal row slabs where no cell
    runs them (the GPT-2 cells' shape is above): one head a block,
    folded and at D=128, and three slabs. The slabs' slices fall on
    whole tiles, and the float32 dk and dv sums fit VMEM."""
    fa = importlib.import_module("ray_tpu.ops.pallas.flash_attention")
    assert fa._causal_slabs(shape[1], True) == slabs
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e[0]))
    text = jax.jit(jax.grad(_loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2


LOCATIONS = re.compile(r"loc\(.*?\)$|^#loc.*$", re.M)


@pytest.mark.parametrize("shape, window, used_mib", [
    ((1, 16384, 28, 128), None, (8, 24)),
    ((1, 16384, 28, 128), 4096, (8, 24)),
    ((2, 8192, 8, 128), None, (8, 24)),
    ((1, 65536, 2, 128), None, (34, 64)),
    ((1, 65536, 2, 128), 4096, (34, 64)),
    ((1, 16384, 64, 128), 512, (4, 24)),
    ((1, 4096, 80, 64), 512, (1, 24))],
    ids=["smallthinker_global", "smallthinker_window", "zaya",
         "longest_row_that_fits", "longest_row_under_a_window",
         "laguna_window", "phi4flash_window"])
def test_the_multi_block_backward_compiles_as_one_kernel_in_its_vmem(
        v5e, monkeypatch, shape, window, used_mib):
    """The SmallThinker cell's two layers (28 heads of 128 over 16,384
    rows, causal and under the window of 4,096), ZAYA's (2 x 8,192
    rows, 8 heads), and the longest power of two ``_bwd_fits`` accepts,
    65,536 rows (34 MiB of ``dq``; no cell runs it): forward and
    backward are **two** custom calls, the backward one kernel whose
    resident ``dq`` (8.4 MB of float32 at 16,384 rows) fits the VMEM it
    asks for, ``_BWD_VMEM`` in the call's configuration — the compile
    succeeding is the proof, the compiler refusing a kernel that holds
    more than it was granted. ``_bwd_fits`` counts more than the
    compiler takes: held to what the compiler used, the same row is
    refused. Laguna's sliding layer (64 heads of 128 under 512 keys)
    and Phi-4-mini-flash's (80 heads of 64 at 4,096 rows) run in blocks
    of their window (``_window_block``: 512 rows), the others in
    ``_pick_block``'s 1,024."""
    fa = importlib.import_module("ray_tpu.ops.pallas.flash_attention")
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e[0]))

    def loss(q, k, v):
        return flash_attention(q, k, v, window=window).astype(
            jnp.float32).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    calls = "\n".join(line for line in text.splitlines()
                      if 'custom_call_target="tpu_custom_call"' in line)
    granted, used = ([int(size) for size in re.findall(
        '"' + key + r'":\[\{"memory_space":"1","offset":"0","size":"(\d+)"',
        calls)] for key in ("scoped_memory_configs",
                            "used_scoped_memory_configs"))
    print(f"VMEM used, MiB: {[round(u / 2**20, 2) for u in used]}")
    assert len(used) == 2 and max(granted) == 64 << 20
    assert used_mib[0] << 20 < max(used) < used_mib[1] << 20
    monkeypatch.setattr(fa, "_BWD_VMEM", max(used))
    assert not fa._bwd_fits(
        shape[1], fa._window_block(shape[1], window), 128)
    with pytest.raises(NotImplementedError, match=f"{shape[1]} rows"):
        jax.eval_shape(loss, x, x, x)
    assert f"{shape[1]},{shape[1]}" not in text


def test_a_row_of_one_block_lowers_as_it_did_whatever_the_budget(
        v5e, monkeypatch):
    """The GPT-2 cells' shape (one block a row: the fused single-block
    backward) never meets the budget: its lowered text, locations
    stripped, is the same with ``_BWD_VMEM`` at its value and at
    nothing, two custom calls, and neither asks for VMEM."""
    fa = importlib.import_module("ray_tpu.ops.pallas.flash_attention")
    x = jax.ShapeDtypeStruct(SHAPES[0], jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e[0]))

    texts = []
    for budget in (fa._BWD_VMEM, 0):
        monkeypatch.setattr(fa, "_BWD_VMEM", budget)
        jax.clear_caches()
        # from one line both times: a Mosaic body carries its callers'
        texts.append(LOCATIONS.sub("", jax.jit(jax.grad(
            lambda q, k, v: flash_attention(q, k, v).astype(
                jnp.float32).sum(), argnums=(0, 1, 2))).lower(
                    x, x, x).as_text()))
    with_budget, without = texts
    assert without == with_budget
    assert with_budget.count("tpu_custom_call") == 2
    assert "vmem_limit_bytes" not in with_budget


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("shape", SHAPES,
                         ids=["x".join(map(str, s)) for s in SHAPES])
def test_flash_kernel_compiles_under_shard_map_on_dp4(
        v5e, monkeypatch, shape, grad):
    """The same three shapes as the four-chip cell runs them: batch
    over dp=4, the kernel under shard_map, its grid over a chip's own
    rows."""
    from ray_tpu.ops.attention import make_sharded_causal_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(v5e, ("dp",))
    attn = make_sharded_causal_attention(mesh)

    def loss(q, k, v):
        return attn(q, k, v).astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("dp")))
    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else loss
    text = jax.jit(fn).lower(x, x, x).compile().as_text()
    # the backward is one kernel: the fused single-block body at 1,024
    # rows, ``_bwd_kernel`` over a chip's own rows at 2,048 and 4,096
    assert text.count('custom_call_target="tpu_custom_call"') == (
        2 if grad else 1)


MOVES = re.compile(r"copy|transpose|pad|slice|concatenate|bitcast")
ENTRY_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
SEES_THROUGH = ("get-tuple-element", "bitcast")


def _moves_next_to_the_kernels(hlo: str) -> list[str]:
    """Instructions of the entry computation that only move data (a
    copy, transpose, pad, slice or concatenate, bare or as a fusion
    XLA named after one) and that write a kernel's operand or read a
    kernel's result, tuple plumbing and bitcasts seen through."""
    entry = hlo[hlo.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    made, calls = {}, {}
    for line in entry.splitlines():
        m = ENTRY_INSTRUCTION.match(line)
        if m:
            name, result, opcode, rest = m.groups()
            operands, _, attributes = rest.partition("), ")
            made[name] = (opcode, result,
                          re.findall(r"%([\w.\-]+)", operands))
            if opcode == "fusion":
                calls[name] = re.search(r"calls=%([\w.\-]+)",
                                        attributes).group(1)

    def is_move(name):
        opcode = made[name][0]
        if opcode != "fusion":
            return bool(MOVES.fullmatch(opcode))
        # XLA names a fusion after what it holds: a matmul that also
        # bitcasts is no move
        body = hlo[hlo.index(f"\n%{calls[name]} "):]
        return bool(MOVES.match(name)) and " convolution(" not in body[
            :body.index("\n}")]

    def source(name):
        while made[name][0] in SEES_THROUGH:
            name = made[name][2][0]
        return name

    kernels = {n for n, (op, _, _) in made.items() if op == "custom-call"}
    found = set()
    for kernel in kernels:
        found |= {source(o) for o in made[kernel][2] if is_move(source(o))}
    for name, (opcode, _, operands) in made.items():
        if opcode not in SEES_THROUGH and is_move(name) and any(
                source(o) in kernels for o in operands):
            found.add(name)
    return sorted(f"{n} = {made[n][1]} {made[n][0]}" for n in found)


@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip", "dp4"])
def test_nothing_is_copied_between_the_projections_and_the_kernel(
        v5e, monkeypatch, chips):
    """The GPT-2 cells' attention block (32 x 1,024 tokens a chip, 12
    heads of 64), forward and backward, compiled for the chip: the qkv
    projection's matmuls write what the forward kernel reads, the
    kernel writes what the output projection reads, and the backward
    kernel's three gradients are the operands of the projections'
    backward matmuls — bare on one chip, and on dp=4 across the
    shard_map's boundary. Before the kernel indexed [B, T, H*D] there
    were a transpose and a copy a tensor a pass (PERF.md section 6,
    PR 29): 36.6 ms of a 250 ms step."""
    from ray_tpu.models.gpt2 import CausalSelfAttention, GPT2Config
    from ray_tpu.ops.attention import make_sharded_causal_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = GPT2Config.small()
    block = CausalSelfAttention(cfg)
    mesh = Mesh(v5e[:chips], ("dp",))
    attn = make_sharded_causal_attention(mesh)
    x = jax.ShapeDtypeStruct((32 * chips, 1024, cfg.n_embd), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("dp")))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=NamedSharding(mesh, P())),
        jax.eval_shape(
            lambda: block.init(jax.random.key(0),
                               jnp.zeros((1, 128, cfg.n_embd), cfg.dtype),
                               jax.nn.dot_product_attention))["params"])

    def loss(params, x, g):     # g: the cotangent the next layer sends
        y = block.apply({"params": params}, x, attn)
        return (y.astype(jnp.float32) * g.astype(jnp.float32)).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x, x).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    assert _moves_next_to_the_kernels(hlo) == []


def test_twelve_layers_lower_each_kernel_once_and_a_second_trace_none(
        v5e, monkeypatch):
    """The price of a kernel in warm set-up is its trace and its
    lowering to Mosaic (PR 28 paid both 24 times a trace of the step,
    +5.15 s of ``step.trace_lower_s``, and was refused for it). The
    functions that hold the pallas_calls are jitted: twelve layers at
    one shape lower to one function a kernel, called twelve times, and
    the step's second trace (its donated outputs come back in other
    layouts) finds the first one's."""
    fa = importlib.import_module("ray_tpu.ops.pallas.flash_attention")
    bodies = []
    scores = fa._slab_scores    # the single-block bodies', once a slab
    monkeypatch.setattr(
        fa, "_slab_scores",
        lambda *a, **kw: bodies.append(1) or scores(*a, **kw))

    def stack(x, scales):
        for i in range(12):
            with jax.named_scope(f"h_{i}"):
                # a scale no other test of this process has traced
                x = flash_attention(x * scales[i], x, x, scale=0.1171875)
        return x.astype(jnp.float32).sum()

    one = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct(SHAPES[0], jnp.bfloat16, sharding=one)
    scales = jax.ShapeDtypeStruct((12,), jnp.bfloat16, sharding=one)
    text = jax.jit(jax.grad(stack)).lower(x, scales).as_text()
    assert 2 <= text.count("tpu_custom_call") <= 3
    assert text.count("call @_flash_fwd") == 12
    assert text.count("call @_flash_bwd") == 12
    traced = len(bodies)
    assert traced > 0

    def again(x, scales):       # a new function: a new trace
        return stack(x, scales) * 2.0

    text = jax.jit(jax.grad(again)).lower(x, scales).as_text()
    assert len(bodies) == traced
    assert 2 <= text.count("tpu_custom_call") <= 3


@pytest.mark.parametrize("n_devices", [4, 1], ids=["dp4", "one_of_four"])
def test_mesh_dispatch_keeps_the_kernel(v5e, monkeypatch, n_devices):
    """What a model given a mesh dispatches: on dp=4 the kernel under
    shard_map, batch over four chips; on a one-device mesh of a
    four-chip host the bare kernel — decided from the mesh, while this
    process counts eight devices. The dispatch asks
    jax.default_backend(); steered here, in the test."""
    from ray_tpu.ops.attention import make_sharded_causal_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert jax.device_count() > 1
    mesh = Mesh(v5e[:n_devices], ("dp",))
    attn = make_sharded_causal_attention(mesh)
    x = jax.ShapeDtypeStruct(SHAPES[0], jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("dp")))
    text = jax.jit(attn).lower(x, x, x).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("family", ["gpt2", "llama", "moe"])
def test_every_model_takes_its_attention_from_the_mesh(
        v5e, monkeypatch, family):
    """No model file decides the kernel for itself: each one, given a
    one-device mesh on a many-device host, compiles the kernel."""
    from ray_tpu import models

    cls, cfg = {"gpt2": (models.GPT2, models.GPT2Config),
                "llama": (models.Llama, models.LlamaConfig),
                "moe": (models.MoETransformer, models.MoEConfig)}[family]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(v5e[:1], ("dp",))
    attn = cls(cfg.tiny(), mesh=mesh)._attn_fn()
    x = jax.ShapeDtypeStruct(SHAPES[0], jnp.bfloat16,
                             sharding=NamedSharding(mesh, P()))
    text = jax.jit(attn).lower(x, x, x).compile().as_text()
    assert "tpu_custom_call" in text


def test_routed_experts_compile_for_v5e(v5e, monkeypatch):
    """The OLMoE cell's routed layer at the published widths (16,384
    tokens, 64 experts x 1,024, top-8), forward and backward: on a TPU
    the grouped matmuls are the megablox Pallas kernel, which Mosaic has
    to take at the tile ``ops/moe.py`` chose (nine custom calls: three
    matrices, each forward, for its input and for its weights). The
    softmax router over the 64 experts, both its sums read by the loss,
    chooses by ``ops/pallas/router_choice.py``'s pair."""
    from ray_tpu.ops import moe
    from ray_tpu.util import tracing

    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.grouped_matmul_path() == "megablox_gmm"
    one = SingleDeviceSharding(v5e[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def loss(x, router, gate, up, down):
        y, aux, z, _ = moe.routed_ffn(x, router, gate, up, down, top_k=8)
        return y.astype(jnp.float32).sum() + aux + z

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        arg((4, 4096, 2048), jnp.bfloat16), arg((2048, 64), jnp.float32),
        arg((64, 2048, 1024), jnp.float32), arg((64, 2048, 1024), jnp.float32),
        arg((64, 1024, 2048), jnp.float32)).compile().as_text()
    assert text.count("tpu_custom_call") >= 9
    # the router's choice is the kernel pair, once each, under the
    # layer's ``router`` scope, and no ``top_k`` or gather beside it
    assert notes["moe_router_path"] == "pallas"
    for kernel in ("_choice_fwd", "_choice_bwd"):
        assert len(re.findall(
            r"custom-call\(.*router\)*/jit\(%s\)\)*/pallas_call" % kernel,
            text)) == 1, kernel
    assert not re.search(r"router\)*/(top_k|gather|scatter)", text)


@pytest.mark.parametrize("router", ["softmax", "sigmoid"])
def test_the_routers_choice_compiles_under_shard_map_on_dp4(
        v5e, monkeypatch, router):
    """A router over a batch that four chips share (dp=4): the choice's
    kernel pair a shard at a time under ``shard_map``, over a chip's own
    4,096 tokens, forward and backward, and one global program over the
    four chips (a batch they do not divide) on XLA's lines."""
    from ray_tpu.ops import moe
    from ray_tpu.util import tracing

    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(v5e, ("dp",))

    def arg(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def loss(x, w, bias):
        if router == "softmax":
            return moe.route_softmax(x, w, top_k=8, mesh=mesh)[0].sum()
        shards = moe._token_shards(mesh, x)
        route = jax.shard_map(
            lambda x, w, bias: moe._route_sigmoid(
                x.reshape(-1, x.shape[-1]), w, bias, 8, True, 2.5,
                "pallas")[0],
            mesh=mesh, in_specs=(shards.spec, P(), P()),
            out_specs=P(shards.axes), check_vma=False)
        return route(x, w, bias).sum()

    args = (arg((256, 256), jnp.float32), arg((256,), jnp.float32))
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        arg((4, 4096, 256), jnp.bfloat16, P("dp")), *args
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "f32[256,4096]" in text              # a chip's own tokens
    if router == "softmax":
        assert notes.pop("moe_router_path") == "pallas"
        jax.jit(loss).trace(arg((3, 4096, 256), jnp.bfloat16), *args)
        assert notes["moe_router_path"] == "xla"


# hidden [B, S, E] and the rows of the table, the mesh the batch is over
HEADS = {
    "gpt2": ((32, 1024, 768), 50304, None),
    "gpt2_dp4_a_chips_rows": ((128, 1024, 768), 50304, {"dp": 4}),
    "zaya": ((2, 8192, 2048), 32896, None),
}


@pytest.mark.parametrize("head", list(HEADS), ids=list(HEADS))
def test_the_lm_heads_forward_is_one_custom_call_under_loss(
        v5e, monkeypatch, head):
    """The chunked cross-entropy at the GPT-2 cells' head (32,768 rows a
    chip against 50,304 = 3 x 131 lane tiles: a ragged last tile) and
    ZAYA's (32,896 = 257 tiles, a prime), forward and backward: the
    forward is ONE custom call, ``ops/pallas/ce_lse.py``'s, under
    ``loss`` (under the ``shard_map`` on dp=4), inside the VMEM it asks
    for (64 MiB); no float32 ``[rows, vocab]`` logits of all a chip's rows
    exist, and the only ``[2048, vocab]`` float32 chunks are the
    backward's."""
    from ray_tpu.models import gpt2
    from ray_tpu.ops.pallas import ce_lse
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.util import tracing

    shape, vocab, axes = HEADS[head]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1 if not axes else 4)
    mesh = make_mesh(axes, devices=v5e) if axes else None
    rows_at = (NamedSharding(mesh, P("dp")) if axes
               else SingleDeviceSharding(v5e[0]))
    whole = (NamedSharding(mesh, P()) if axes
             else SingleDeviceSharding(v5e[0]))
    hidden = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=rows_at)
    table = jax.ShapeDtypeStruct((vocab, shape[-1]), jnp.float32,
                                 sharding=whole)
    targets = jax.ShapeDtypeStruct(shape[:2], jnp.int32, sharding=rows_at)
    notes = {}
    monkeypatch.setattr(tracing, "note_trace", notes.update)

    def loss(h, w, t):
        return gpt2.chunked_cross_entropy(h, w, t, mesh=mesh)
    compiled = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        hidden, table, targets).compile()
    local = shape[0] * shape[1] // (4 if axes else 1)
    block_rows, tile = ce_lse.blocks(local, shape[-1], vocab)
    assert notes["ce_path"] == "pallas_lse"
    assert (notes["ce_fwd_rows"], notes["ce_fwd_tile"]) == (block_rows, tile)
    assert vocab % tile, "these two tables have a ragged last tile"
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    assert re.search(r'op_name="[^"]*loss/[^"]*jit\(_ce_lse_fwd\)', calls[0])
    # the compile succeeding is the proof that the blocks fit what is
    # asked for: the compiler refuses a kernel that holds more
    assert int(re.search(
        r'(?<!used_)scoped_memory_configs":\[\{"memory_space":"1",'
        r'"offset":"\d+","size":"(\d+)"', calls[0]).group(1)
               ) == ce_lse._VMEM_LIMIT
    assert f"f32[{local},{vocab}]" not in text
    assert "exponential_reduce" not in text
