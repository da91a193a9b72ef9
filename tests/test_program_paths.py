"""Which program a bare kernel may run in, consumer by consumer and mesh
by mesh: the answers and the refusals the path functions gave when each
spelled the rule out itself (``ops/ssm.py::_kernel_batch_axes`` and its
four re-writings at PR 69's parent, where this table was written and
passed first), held in one place so that the one home of the rule,
``ops/pallas/program.py``, is seen to give them all.

Everything here is a decision: no kernel is traced. The backend is said
to be a TPU and every shape is one its kernels tile (the cells' own), so
that the mesh alone decides."""

import math

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import gpt2
from ray_tpu.ops import (
    cca, conv1d, gated_norm, hyper_connections as hc, kda, mamba1, mla, moe,
    ssm)
from ray_tpu.ops.pallas import program
from ray_tpu.parallel.mesh import make_mesh

REFUSED = NotImplementedError

# mesh -> (its axes or None, the devices of the process where there is no
# mesh, the batch)
MESHES = {
    "none_on_one_device": (None, 1, 4),
    "none_on_four": (None, 4, 4),
    "one_device": ({"dp": 1}, 8, 4),
    "dp4": ({"dp": 4}, 8, 4),
    "dp2_fsdp2": ({"dp": 2, "fsdp": 2}, 8, 4),
    "sp2": ({"sp": 2}, 8, 4),
    "tp2": ({"tp": 2}, 8, 4),
    "ep2": ({"ep": 2}, 8, 4),
    "dp4_batch_of_2": ({"dp": 4}, 8, 2),
}
T = 4096

# consumer -> its decision for a batch of ``b`` rows on ``mesh``
CONSUMERS = {
    "rule": lambda mesh, b: program.batch_axes(mesh, b),
    "scan_path": lambda mesh, b: ssm.scan_path(
        (b, T, 64, 64), (b, T, 8, 128), 128, mesh),
    "norm_path": lambda mesh, b: gated_norm.norm_path((b, T, 4096), 8, mesh),
    "conv_path": lambda mesh, b: conv1d.conv_path((b, T, 6144), 4, mesh),
    "mamba1_path": lambda mesh, b: mamba1.mamba1_path(
        (b, T, 5120), 16, 4, mesh),
    "kda_path": lambda mesh, b: kda.kda_path((b, T, 32, 128), 64, mesh),
    "gdn_path": lambda mesh, b: kda.gdn_path(
        (b, T, 16, 128), 64, mesh, values=128, heads=32),
    "cca_path": lambda mesh, b: cca.cca_path((b, T, 1280), 8, 2, (2, 2), mesh),
    "hc_maps_path": lambda mesh, b: hc.hc_maps_path((b, T, 4 * 2048), 4, mesh),
    "mla_path": lambda mesh, b: mla.mla_path(b, T, 16, 128, 64, 128, mesh),
    "routed_layer": lambda mesh, b: _routed(mesh, b),
    "loss": lambda mesh, b: gpt2._mapped_over(gpt2._loss_axes(mesh, b, T)),
}


def _routed(mesh, b):
    """(the axes the routed layer maps its tokens over, whether it is one
    global program over several devices)."""
    shards = moe._token_shards(mesh, jax.ShapeDtypeStruct((b, T, 64),
                                                          jnp.bfloat16))
    return shards.axes, shards.one_program


KERNEL, XLA = ("kernel", "xla")
# what each consumer calls its two paths
NAMES = {"scan_path": ("pallas_chunked", "chunked_xla"),
         "mamba1_path": ("pallas_chunked", "xla_chunked"),
         "kda_path": ("pallas_chunked", "xla_chunked"),
         "gdn_path": ("pallas_chunked", "xla_chunked"),
         **{c: ("pallas", "xla") for c in (
             "norm_path", "conv_path", "cca_path", "hc_maps_path")}}
# the kernels that are mapped over the batch's axes, and the two
# recurrences that take theirs in a one-device program alone
MAPPED = ("scan_path", "norm_path", "conv_path", "cca_path", "hc_maps_path")
BARE_ONLY = ("mamba1_path", "kda_path", "gdn_path")

# mesh -> what the rule says: the axes to map over, None where no kernel
# may run
RULE = {"none_on_one_device": (), "none_on_four": None, "one_device": (),
        "dp4": ("dp",), "dp2_fsdp2": ("dp", "fsdp"), "sp2": None,
        "tp2": None, "ep2": None, "dp4_batch_of_2": None}


def _expected(consumer: str, mesh: str):
    axes = RULE[mesh]
    if consumer == "rule":
        return axes
    if consumer in MAPPED:
        return NAMES[consumer][axes is None]
    if consumer in BARE_ONLY:
        if mesh in ("sp2", "tp2"):
            return REFUSED
        return NAMES[consumer][axes != ()]
    if consumer == "mla_path":
        if mesh in ("sp2", "tp2", "ep2", "none_on_four"):
            return REFUSED
        return (XLA, ()) if axes is None else (KERNEL, axes)
    if consumer == "routed_layer":
        if mesh in ("tp2", "ep2"):
            return REFUSED
        if mesh == "sp2":
            return ("sp",), False
        # one global program over several devices: a mesh of more than
        # one whose axes do not shard these tokens
        return axes or (), mesh == "dp4_batch_of_2"
    if consumer == "loss":     # tp shards the head's vocabulary
        return ("sp",) if mesh == "sp2" else axes or ()
    raise KeyError(consumer)


@pytest.mark.parametrize("mesh_name", list(MESHES))
@pytest.mark.parametrize("consumer", list(CONSUMERS))
def test_every_consumer_decides_as_it_did(consumer, mesh_name, monkeypatch):
    axes, devices, batch = MESHES[mesh_name]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    mesh = axes and make_mesh(
        axes, devices=jax.devices()[:math.prod(axes.values())])
    want = _expected(consumer, mesh_name)
    if want is REFUSED:
        with pytest.raises(NotImplementedError) as refused:
            CONSUMERS[consumer](mesh, batch)
        if mesh is not None:    # the axis by name and its size
            (axis, size), = axes.items()
            assert f"{axis}={size}" in str(refused.value)
    else:
        assert CONSUMERS[consumer](mesh, batch) == want


# -- the map and the refusal --------------------------------------------------

def _rows_and_weights(x, w):
    """A stand-in for a kernel's core: each row's product and a map of
    it with the batch second, as ``hc_maps`` returns its own."""
    y = jnp.tanh(x @ w)
    return y, jnp.moveaxis(y, 0, 1)


@pytest.mark.parametrize("axes", [("dp",), ("dp", "fsdp")],
                         ids=["dp", "dp_and_fsdp"])
def test_over_batch_is_the_bare_call_on_each_devices_rows(axes):
    """Under ``over_batch`` every device runs ``fn`` on its rows: the
    results are the bare call's, sharded on the dimension each spec
    names, and a weight held whole has its cotangent summed over the
    axes (what the six mapped kernels' own tests hold them to, through
    this one function since PR 69)."""
    mesh = make_mesh(dict.fromkeys(axes, 2),
                     devices=jax.devices()[:2 ** len(axes)])
    x = jax.random.normal(jax.random.key(0), (4, 8, 16))
    w = jax.random.normal(jax.random.key(1), (16, 16))
    mapped = program.over_batch(_rows_and_weights, mesh, axes,
                                in_specs=(0, None), out_specs=(0, 1))

    def loss(fn):
        return lambda x, w: sum(jnp.sum(jnp.square(o)) for o in fn(x, w))

    (y, turned), want = jax.jit(mapped)(x, w), _rows_and_weights(x, w)
    assert y.sharding.spec[0] in (axes, axes[0])
    assert turned.sharding.spec[:2] in ((None, axes), (None, axes[0]))
    for got, ref in zip((y, turned), want):
        assert float(jnp.abs(got - ref).max()) < 1e-6
    got = jax.jit(jax.grad(loss(mapped), argnums=(0, 1)))(x, w)
    for g, ref in zip(got, jax.grad(loss(_rows_and_weights), (0, 1))(x, w)):
        assert float(jnp.abs(g - ref).max()) < 1e-4 * float(
            jnp.abs(ref).max())


def test_over_batch_without_axes_is_the_function_itself():
    assert program.over_batch(_rows_and_weights, None, (), (0, None),
                              (0, 1)) is _rows_and_weights


def test_refuse_names_who_met_which_axis_and_what_is_missing():
    program.refuse(None, "a layer", sp="a halo")
    program.refuse(make_mesh({"dp": 2}, devices=jax.devices()[:2]),
                   "a layer", sp="a halo", tp="split heads")
    mesh = make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    with pytest.raises(NotImplementedError) as refused:
        program.refuse(mesh, "a layer", sp="a halo", tp="split heads")
    assert str(refused.value) == (
        "a layer on a mesh with tp=2: split heads is not implemented for "
        "it; dp and fsdp shard the batch and need nothing")


def test_token_axes_are_batch_specs_axes_where_the_shapes_divide():
    """dp and fsdp on the batch, sp on the sequence, as
    ``train.step.batch_spec`` places a batch; none where a shape does
    not divide or nothing shards the tokens."""
    from ray_tpu.train.step import batch_spec
    mesh = make_mesh({"dp": 2, "sp": 2}, devices=jax.devices()[:4])
    assert program.token_axes(mesh, 4, 64) == (("dp",), "sp")
    assert tuple(batch_spec(mesh, seq_sharded=True)) in (
        (("dp",), "sp"), ("dp", "sp"))
    assert program.token_axes(mesh, 4, 63) == ((), None)
    assert program.token_axes(mesh, 3, 64) == ((), None)
    assert program.token_axes(None, 4, 64) == ((), None)
    pp = make_mesh({"pp": 2}, devices=jax.devices()[:2])
    assert program.token_axes(pp, 4, 64) == ((), None)
    assert program.batch_axes(pp, 4) is None
