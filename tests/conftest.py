"""Test configuration.

Multi-device tests run on a virtual 8-device CPU mesh — the analog of
the reference's multi-node-on-one-machine pattern (SURVEY.md §4.2:
``ray.cluster_utils.Cluster``): N simulated devices on the XLA CPU
backend let all sharding/collective invariants run without TPU
hardware. These env vars must be set before jax is first imported
anywhere in the test process.
"""

import os

# Force CPU: tests must never grab the chip, whatever JAX_PLATFORMS
# the caller's shell exported. The env var is for the subprocesses we
# spawn; jax.config pins this process.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# Persistent XLA compilation cache: the model tests compile the same
# tiny graphs every run — warm runs skip straight to execution. Worker
# subprocesses get the same directory from the runtime.
from ray_tpu.util import compile_cache  # noqa: E402

compile_cache.enable()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import pytest  # noqa: E402


# -- host-contention gate (tests import this from conftest) ------------
# Perf floors measured on an idle box are meaningless under load: the
# documented runner must stay green on a busy 1-core host. Floors
# divide by ``relax`` when the load factor crosses SOFT; tests skip
# outright past HARD (a number measured at 6x oversubscription guards
# nothing).

LOAD_SOFT, LOAD_HARD = 1.5, 4.0


def host_load_factor() -> float:
    """1-minute loadavg per core (0.0 where unavailable)."""
    try:
        return os.getloadavg()[0] / max(1, os.cpu_count() or 1)
    except (OSError, AttributeError):
        return 0.0


def perf_floor_gate():
    """-> relax divisor for perf floors; skips the calling test on a
    hopelessly contended host."""
    load = host_load_factor()
    if load > LOAD_HARD:
        pytest.skip(f"host load factor {load:.1f} > {LOAD_HARD}: "
                    f"perf floors are meaningless here")
    return 4.0 if load > LOAD_SOFT else 1.0


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running learning/e2e test")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection run (ResourceKiller / drain / "
        "preemption)")
    config.addinivalue_line(
        "markers",
        "partition: network-fault run (ChaosTransport frame faults "
        "/ silent partitions)")
    config.addinivalue_line(
        "markers",
        "scale: full-N scale-envelope run (scripts/run_scale.sh; "
        "tier-1 runs the small-N variants)")


# -- the suite's clock ---------------------------------------------------
# The driver cuts the whole run at a time limit and counts a cut run only
# as far as it got. A test that grows past the ceiling fails under its
# own name, in the PR that grew it.

CALL_CEILING_S = 180.0


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    report = (yield).get_result()
    if (report.when == "call" and report.passed
            and report.duration > CALL_CEILING_S
            and "slow" not in item.keywords):
        report.outcome = "failed"
        report.longrepr = (
            f"took {report.duration:.0f} s: make it smaller or mark it "
            f"slow (see README, Running it)")


# -- programs for a described chip (tests/test_tpu_compile*.py) ----------

@pytest.fixture(scope="module")
def v5e():
    """The four described chips of a v5e:2x2, persistent cache off: a
    compile for a described chip is written to the cache but cannot be
    read back without the chip (it would warn and recompile)."""
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, no description
        pytest.skip(f"cannot describe a v5e:2x2 here: {e}")
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def on_device(device):
    """-> ``arg(shape, dtype)``: an abstract array on ``device``."""
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(device)
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)


def lower_real_size_step(device, model, loss_fn, batch_shape, **step_kw):
    """A cell's step as its builder makes it (clipped adamw with a bf16
    first moment, abstract state and batch on the one described chip),
    traced and lowered for the chip, not compiled: -> (the trace's notes,
    the lowered program)."""
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.util import tracing
    arg = on_device(device)
    opt = optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(2e-5, b1=0.9, b2=0.95, weight_decay=0.1,
                    mu_dtype=jnp.bfloat16))
    step = train.make_train_step(loss_fn, opt, **step_kw)
    state = jax.tree.map(
        lambda z: arg(z.shape, z.dtype),
        jax.eval_shape(lambda: train.init_train_state(
            model.init_params(jax.random.key(0)), opt, None)))
    batch = {k: arg(batch_shape, jnp.int32) for k in ("tokens", "targets")}
    notes = {}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        patch.setattr(jax, "device_count", lambda: 1)   # the cell's chip
        patch.setattr(tracing, "note_trace", notes.update)
        lowered = step.lower(state, batch)
    return notes, lowered


def kernel_calls(lowered) -> list[str]:
    """The lowered program's ``tpu_custom_call``s, one line each as the
    compiled text would name it: the scope path down to the primitive
    (``.../h_0/attn/core/jit(_flash_fwd)/pallas_call``), then operand and
    result shapes (``bf16[1,16384,6144]``). A jitted kernel is lowered
    once and called a layer: each call site counts, under its own path."""
    import re
    text = lowered.as_text(debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    bodies = {}
    for head in re.finditer(r"^  func\.func \w+ @(\w+)\(", text, re.M):
        bodies[head.group(1)] = re.findall(
            r"(?:(?<![\w.])(?:func\.)?call @(\w+)|custom_call @tpu_custom_call)"
            r"\(.* : (\(.*\) -> .*) loc\((#loc\d+)\)$",
            text[head.end():text.index("\n  }", head.end())], re.M)

    def walk(fn, path):
        for callee, types, loc in bodies[fn]:
            here = f"{path}/{names.get(loc, '')}".strip("/")
            if callee:
                yield from walk(callee, here)
            else:
                shapes = (f"{t.split('x')[-1]}[{','.join(t.split('x')[:-1])}]"
                          for t in re.findall(r"tensor<(\w+)>", types))
                yield f"{here} {' '.join(shapes)}"

    return list(walk("main", ""))


def kernel_kinds(lines) -> list[str]:
    """Which jitted kernel each custom call is (``_flash_fwd``, ``gmm``):
    of :func:`kernel_calls`' lines, or of the compiled text's."""
    import re
    return [re.search(r"jit\((\w+)\)/pallas_call", line).group(1)
            for line in lines]


def scope_primitives(lowered, scope: str) -> set[str]:
    """The primitives the lowered program runs right under ``scope``
    (``/mlp/router/``): the last piece of every operation's name stack
    that holds it (``top_k``, ``gather``, ``scatter-add``,
    ``jit(_choice_fwd)``, ``dot_general``), whatever layer and pass."""
    import re
    return set(re.findall(
        r'^#loc\d+ = loc\("[^"]*%s([^"/]*)"' % re.escape(scope),
        lowered.as_text(debug_info=True), re.M))


def router_choice_calls(lowered, layers: int, *shapes: str,
                        counts_scattered: bool = False) -> None:
    """The routers' choice of a lowered step on the ``pallas`` path
    (``ops/pallas/router_choice.py``): the forward and the backward
    kernel under ``.../mlp/router`` once a routed layer each, on the
    transposed product ``shapes[0]`` (``f32[512,16384]``) and the
    ``[k, T]`` arrays ``shapes[1:]``, none under ``rematted_computation``
    (a recomputed block keeps what the forward kernel made), and no
    ``top_k``, gather or scatter left beside them (``counts_scattered``:
    but the counts' scatter-add, where the routes reach the experts
    through ``routed_experts``, which counts them itself)."""
    calls = kernel_calls(lowered)
    choice = [(kind, line) for kind, line in zip(kernel_kinds(calls), calls)
              if kind.startswith("_choice_")]
    assert sorted(kind for kind, _ in choice) == (
        ["_choice_bwd"] * layers + ["_choice_fwd"] * layers)
    for kind, line in choice:
        assert "/mlp/router/jit(%s)/pallas_call" % kind in line, line
        assert "rematted_computation" not in line, line
        assert all(shape in line for shape in shapes), line
    left = scope_primitives(lowered, "/mlp/router/")
    assert {"jit(_choice_fwd)", "jit(_choice_bwd)", "dot_general"} <= left
    assert [p for p in left if "top_k" in p or "gather" in p
            or "scatter" in p or "sort" in p] == (
                ["scatter-add"] if counts_scattered else []), left


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations(sub)


def matmuls(traced, *shapes) -> int:
    """The ``dot_general``s of a traced function (``make_jaxpr``'s), the
    jaxprs its equations hold among them, whose operands have one of
    ``shapes`` (``(lhs shape, rhs shape)``: the rows' trailing axis
    against the weight's leading one, a ``Dense``'s forward): how often a
    recomputing program runs a layer's forward matmul. A loop's body
    counts once."""
    forward = (((len(shapes[0][0]) - 1,), (0,)), ((), ()))
    return sum(
        e.params["dimension_numbers"] == forward
        and tuple(v.aval.shape for v in e.invars) in shapes
        for e in equations(traced.jaxpr) if e.primitive.name == "dot_general")


def primitives(traced, name: str) -> int:
    """How many equations of a traced function (``make_jaxpr``'s), the
    jaxprs its equations hold among them, are the primitive ``name``
    (``top_k``): how often a recomputing program makes a choice."""
    return sum(e.primitive.name == name for e in equations(traced.jaxpr))


def same_bits(got, want) -> bool:
    """Are two trees' leaves the same bits, leaf for leaf, with something
    in every leaf of ``want``? For a recomputing program's loss and
    gradients against the plain one's, run operation by operation: under
    a ``jit`` the two are two programs, which XLA's CPU compiler may fuse
    differently."""
    import jax.numpy as jnp
    got, want = (jax.tree_util.tree_leaves(t) for t in (got, want))
    return (len(got) == len(want)
            and all(bool((a == b).all()) for a, b in zip(got, want))
            and all(float(jnp.abs(b).max()) > 0 for b in want))


def live_kernel_calls(traced) -> list[int]:
    """The ``pallas_call``s left in a traced function (``make_jaxpr``'s)
    once what nothing reads is taken out, as lowering takes it out, each
    by its number of results, sorted: where no chip's compiler is asked,
    how often a recomputing program runs a kernel."""
    from jax.interpreters import partial_eval as pe
    live, _ = pe.dce_jaxpr(traced.jaxpr, [True] * len(traced.jaxpr.outvars))
    return sorted(len(e.outvars) for e in equations(live)
                  if e.primitive.name == "pallas_call")


def program_bytes(compiled):
    """-> (``memory_analysis()``, arguments + temporaries + unaliased
    outputs): what the program asks of the chip's memory."""
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + max(0, m.output_size_in_bytes - m.alias_size_in_bytes))
    print(f"program {total / 1e9:.2f} GB: arguments "
          f"{m.argument_size_in_bytes / 1e9:.2f}, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.2f}")
    return m, total


@pytest.fixture
def rt():
    """A fresh multiprocess runtime per test."""
    import ray_tpu
    ray_tpu.init(num_cpus=4, ignore_reinit_error=False)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def rt_local():
    """In-process (local_mode) runtime — fast, for API-shape tests."""
    import ray_tpu
    ray_tpu.init(local_mode=True)
    yield ray_tpu
    ray_tpu.shutdown()
