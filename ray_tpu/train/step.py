"""Sharded train-step machinery.

The hot path of the framework: everything here compiles to ONE XLA
program per step — forward, backward, the data-parallel gradient
reduction (psum over ``dp``/``fsdp`` inserted by sharding propagation,
riding ICI), optimizer update, all fused. The reference's equivalent
path is user torch code + NCCL allreduce orchestrated per-step from
Python (SURVEY.md §3.4); here the collective IS part of the program.
"""

from __future__ import annotations

import re
import threading
import time
from typing import Any, Callable

import jax
import jax.monitoring
import jax.numpy as jnp
from flax import struct

from ray_tpu.parallel.sharding import place_params
from ray_tpu.train import session
from ray_tpu.util import tracing


@struct.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any
    extra: Any = None          # e.g. batch_stats for BN models

    def num_params(self) -> int:
        return sum(x.size for x in jax.tree_util.tree_leaves(self.params))


def init_train_state(params, optimizer, mesh=None, extra=None,
                     patterns=None) -> TrainState:
    """Place params per the sharding rule table and build matching
    optimizer state (jit propagates the param shardings into the Adam
    moments — optimizer-state sharding, the ZeRO analog, for free)."""
    if mesh is not None:
        params = place_params(params, mesh, patterns)
    opt_state = jax.jit(optimizer.init)(params)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=opt_state, extra=extra)


def _group_norms(grads, groups: dict[str, str]) -> dict:
    """{name: the global norm of the gradient leaves whose path (the
    tree's keys joined by ``/``: ``h_1/attn/q/kernel``) the group's
    regular expression finds}; a group that finds no leaf is an error."""
    import optax
    leaves = {"/".join(str(getattr(k, "key", k)) for k in path): g
              for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]}
    norms = {}
    for name, pattern in groups.items():
        found = [g for path, g in leaves.items() if re.search(pattern, path)]
        if not found:
            raise ValueError(f"grad_groups[{name!r}] = {pattern!r} finds "
                             f"none of {sorted(leaves)}")
        norms[name] = optax.global_norm(found)
    return norms


def _step_body(loss_fn, optimizer, has_extra, grad_norm, grad_groups=None):
    def loss_and_report(params, batch):
        """A loss function may return ``(loss, report)``: the report's
        scalars ride beside the loss in the step's metrics. A scalar
        loss has the empty report, which adds nothing to the traced
        program."""
        out = loss_fn(params, batch)
        return out if isinstance(out, tuple) else (out, {})

    def step(state: TrainState, batch) -> tuple[TrainState, dict]:
        if has_extra:
            (loss, new_extra), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, state.extra, batch)
            report = {}
        else:
            (loss, report), grads = jax.value_and_grad(
                loss_and_report, has_aux=True)(state.params, batch)
            new_extra = state.extra
        import optax
        metrics = {"loss": loss, **report}
        # The forward and backward carry the model's scopes; everything
        # after them is the ``optimizer`` scope (docs/observability.md).
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(grads, state.opt_state,
                                                state.params)
            new_params = optax.apply_updates(state.params, updates)
            if grad_norm:
                metrics["grad_norm"] = optax.global_norm(grads)
            if grad_groups:
                metrics.update(_group_norms(grads, grad_groups))
            new_state = TrainState(step=state.step + 1,
                                   params=new_params, opt_state=new_opt,
                                   extra=new_extra)
        return new_state, metrics
    return step


# jax.monitoring events that make up a compile, by the ``kind`` of the
# ``train.compile`` span each becomes. ``backend`` is the XLA compile or,
# on a persistent-cache hit, the load that ``cache_load`` times alone.
_COMPILE_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
# Told just before a ``cache_load``: the original compile's seconds less
# this load's.
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_thread = threading.local()     # depth of open phases; cache outcome
_listening = False              # and load interval awaiting their backend


def _take(name: str):
    """This thread's pending value of that name, handed over once."""
    value = getattr(_thread, name, None)
    setattr(_thread, name, None)
    return value


def _on_compile_begins(event: str, _start: float, **_) -> None:
    if event in _COMPILE_KINDS:     # jax tells the three phases' starts
        _thread.depth = getattr(_thread, "depth", 0) + 1
        if _thread.depth == 1 and _COMPILE_KINDS[event] == "trace":
            tracing.take_trace_notes()      # an earlier trace's, unread


def _on_compile_seconds(event: str, seconds: float, **kw) -> None:
    kind = _COMPILE_KINDS.get(event)
    if kind is None:
        if event == _SAVED_EVENT:
            _thread.saved = seconds
        return
    now = time.monotonic()
    if kind == "cache_load":
        # jax does not say whose load it is: the backend phase that
        # holds it does, when it ends. ``compiled_in_s``: what the
        # compile took when the entry was written (whole seconds, as
        # the cache keeps it), to hold against the load: a load may
        # cost more than its compile.
        _thread.load = (now - seconds, now,
                        (_take("saved") or 0.0) + seconds)
        return
    # Every jitted function called while another is traced or lowered
    # (the jnp functions) reports a trace of its own: the outermost
    # span holds them all.
    _thread.depth = max(0, getattr(_thread, "depth", 1) - 1)
    if _thread.depth:
        return
    attributes = {"kind": kind, "fun_name": kw.get("fun_name", "")}
    if kind == "trace":
        # What the traced code said of itself (``ce_rows_local``,
        # ``ce_axes`` of a cross-entropy scanned per chip).
        attributes.update(tracing.take_trace_notes())
    target = session.trace_target()
    if kind == "backend":
        outcome, load = _take("cache"), _take("load")
        if outcome:
            attributes["cache"] = outcome
        if load:
            start, end, compiled_in_s = load
            tracing.record_train_span(
                "train.compile", start, end,
                {**attributes, "kind": "cache_load",
                 "compiled_in_s": compiled_in_s}, **target)
    tracing.record_train_span("train.compile", now - seconds, now,
                              attributes, **target)


def _on_cache_event(event: str, **_) -> None:
    if event in _CACHE_EVENTS:      # told on the backend span that follows
        _thread.cache = _CACHE_EVENTS[event]


def _listen_for_compiles() -> None:
    """Installed once a process, the first time a step is built: from
    then on every trace, lowering, compile and cache load of the
    process is a ``train.compile`` span (a dozen a fit; a step that
    recompiles in the middle of a run shows up by name)."""
    global _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_scalar_listener(_on_compile_begins)
        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_seconds)
        jax.monitoring.register_event_listener(_on_cache_event)


def make_train_step(loss_fn: Callable, optimizer,
                    has_extra: bool = False,
                    donate: bool = True,
                    grad_norm: bool = True,
                    grad_groups: dict[str, str] | None = None) -> Callable:
    """Build the jitted step: forward, backward, gradient psum (via
    sharding propagation) and the optimizer update fused into ONE
    compiled program with the param/opt-state buffers donated — the
    update happens in place in HBM, no re-materialized param copy.

    loss_fn: (params, batch) -> loss, or (loss, report) with a dict
             of scalars that the step adds to its metrics (the
             routed experts' losses and load)      (has_extra=False)
             (params, extra, batch) -> (loss, new_extra)  (True)
    Returns step(state, batch) -> (state, metrics).
    ``grad_norm=False`` skips the global-norm metric (a full f32 read
    of every gradient leaf — measurable on HBM-bound steps).
    ``grad_groups``: {metric name: regular expression over a leaf's
    path, ``h_1/attn/q/kernel``}: the global norm of the gradient
    leaves each finds rides in the metrics under its name (the
    attention projections' alone, one layer's: where the whole norm is
    dominated by other leaves). None adds nothing to the program.
    """
    _listen_for_compiles()
    step = _step_body(loss_fn, optimizer, has_extra, grad_norm, grad_groups)
    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_multi_train_step(loss_fn: Callable, optimizer,
                          has_extra: bool = False,
                          donate: bool = True,
                          grad_norm: bool = True) -> Callable:
    """Scan variant: one compiled program runs K optimizer steps over
    a batch stack whose leaves carry a leading [K, ...] axis. Same
    math as K calls of the single step — the scan just amortizes
    per-dispatch overhead (host round-trip, arg handling) across K
    steps, exactly like queueing K async dispatches. Returns
    (state, metrics_of_last_step)."""
    _listen_for_compiles()
    body = _step_body(loss_fn, optimizer, has_extra, grad_norm)

    def multi(state: TrainState, batches):
        state, ms = jax.lax.scan(body, state, batches)
        last = jax.tree_util.tree_map(lambda x: x[-1], ms)
        return state, last

    return jax.jit(multi, donate_argnums=(0,) if donate else ())


def compile_count(step_fn: Callable) -> int | None:
    """Number of distinct executables compiled for a jitted step fn
    (``None`` when the jax runtime doesn't expose it).

    The fused-step contract after warmup is a STABLE count: one
    compile for the initial input layouts plus at most one relayout
    compile once donated outputs (whose layouts the compiler picks)
    feed back as inputs — the count must never keep growing with
    steps (a growing count means every dispatch pays a compile).
    """
    size = getattr(step_fn, "_cache_size", None)
    if size is None:
        return None
    try:
        return int(size())
    except Exception:  # noqa: BLE001 — introspection must never raise
        return None


def buffers_donated(tree) -> bool:
    """True when every jax array leaf of ``tree`` was consumed by a
    donating dispatch (``is_deleted``) — the observable proof that a
    donated step really took ownership of its input buffers."""
    leaves = [x for x in jax.tree_util.tree_leaves(tree)
              if hasattr(x, "is_deleted")]
    return bool(leaves) and all(x.is_deleted() for x in leaves)


def batch_spec(mesh, *, seq_sharded: bool = False,
               batch_dim: int = 0):
    """PartitionSpec for a [..., batch, ...] array on this mesh;
    ``batch_dim`` leading axes (e.g. a multi-step scan stack) stay
    unsharded."""
    from jax.sharding import PartitionSpec as P

    batch_axes = tuple(a for a in ("dp", "fsdp")
                       if mesh.shape.get(a, 1) > 1)
    first = batch_axes if batch_axes else None
    lead = (None,) * batch_dim
    if seq_sharded and mesh.shape.get("sp", 1) > 1:
        return P(*lead, first, "sp")
    return P(*lead, first)


def shard_batch(batch, mesh, seq_sharded: bool = False,
                batch_dim: int = 0):
    """device_put a host batch across the mesh: batch dim over dp/fsdp,
    optionally seq dim over sp (for ring attention). ``batch_dim``
    marks how many leading axes precede the batch axis (scan stacks)."""
    from jax.sharding import NamedSharding

    def put(x):
        spec = batch_spec(
            mesh,
            seq_sharded=seq_sharded and x.ndim >= 2 + batch_dim,
            batch_dim=batch_dim)
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(put, batch)
