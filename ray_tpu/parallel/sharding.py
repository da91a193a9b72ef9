"""Logical-axis sharding rules.

Models annotate arrays with *logical* axis names ("batch", "embed",
"mlp", "heads", "seq", "vocab", "experts"); a rule table maps logical
axes to mesh axes. This is the pjit/partitioning idiom (t5x/maxtext
style) and is the ZeRO/FSDP analog of the reference's delegated model
sharding (SURVEY.md §2.4 row 2): parameter + optimizer-state sharding
fall out of the same rule table for free.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

from ray_tpu.parallel.mesh import (
    AXIS_DP, AXIS_EP, AXIS_FSDP, AXIS_SP, AXIS_TP,
)


@dataclass
class LogicalAxisRules:
    """Ordered map logical-axis -> mesh axis (or None = replicated).

    A logical axis may list several mesh axes in preference order; the
    first one present in the mesh (size > 1 or declared) is used.
    """

    rules: dict[str, tuple[str, ...]] = field(default_factory=dict)

    def mesh_axis(self, logical: str, mesh) -> str | None:
        for candidate in self.rules.get(logical, ()):  # pref order
            if candidate in mesh.shape and mesh.shape[candidate] > 1:
                return candidate
        return None


DEFAULT_RULES = LogicalAxisRules(rules={
    # activations
    "batch": (AXIS_DP, AXIS_FSDP),
    "seq": (AXIS_SP,),
    "act_embed": (AXIS_TP,),
    # params
    "embed": (AXIS_FSDP,),
    "mlp": (AXIS_TP,),
    "heads": (AXIS_TP,),
    "kv": (),
    "vocab": (AXIS_TP,),
    "experts": (AXIS_EP,),
    # conv / vision
    "conv_out": (AXIS_TP,),
    "conv_in": (),
})


def logical_to_mesh(logical_axes: tuple[str | None, ...],
                    mesh, rules: LogicalAxisRules = DEFAULT_RULES):
    """Translate logical axis names to a PartitionSpec for ``mesh``.

    Duplicate mesh axes are dropped (an axis can shard one dim only).
    """
    from jax.sharding import PartitionSpec

    used: set[str] = set()
    out = []
    for name in logical_axes:
        axis = rules.mesh_axis(name, mesh) if name else None
        if axis is not None and axis not in used:
            used.add(axis)
            out.append(axis)
        else:
            out.append(None)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def named_sharding(mesh, *logical_axes,
                   rules: LogicalAxisRules = DEFAULT_RULES):
    from jax.sharding import NamedSharding
    return NamedSharding(mesh, logical_to_mesh(logical_axes, mesh, rules))


def constrain(x, mesh, *logical_axes,
              rules: LogicalAxisRules = DEFAULT_RULES):
    """In-jit sharding constraint by logical axes.

    Axes that don't divide the array dim are dropped (e.g. a tiny
    init-time batch smaller than dp) — a constraint is an optimization
    hint, never a shape requirement.
    """
    import jax
    import math
    from jax.sharding import NamedSharding, PartitionSpec

    spec = logical_to_mesh(logical_axes, mesh, rules)
    fixed = []
    for dim, entry in enumerate(spec):
        if entry is None:
            fixed.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = math.prod(mesh.shape[a] for a in axes)
        fixed.append(entry if x.shape[dim] % size == 0 else None)
    while fixed and fixed[-1] is None:
        fixed.pop()
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*fixed)))


# --------------------------------------------------------------------------
# Parameter-tree sharding by path pattern
# --------------------------------------------------------------------------

# Pattern table: regex over the flattened param path -> logical axes per
# dim. Matched FIRST wins. Used by shard_params for models that don't
# carry explicit partitioning metadata.
DEFAULT_PARAM_PATTERNS: list[tuple[str, tuple[str | None, ...]]] = [
    # GPT-style transformer (see models/gpt2.py param naming).
    # Order matters: wpe before the generic embedding rule (its param
    # path also contains "embedding" but dim0 is positions, not vocab).
    (r"pos_embed", (None, None, "embed")),       # ViT [1, P, E]
    (r"wpe|pos_emb", (None, "embed")),
    (r"wte|embedding", ("vocab", "embed")),
    # The routed experts of models/llama.py (OLMoE: ops/moe.py's
    # dropless routed_ffn): mlp.gate is the router, mlp.experts the
    # stacked [E, d, f] / [E, f, d] weights. Listed before the dense
    # mlp rules, whose "gate" would take the router. routed_ffn
    # replicates the experts inside its shard_map (ep or tp > 1 raises).
    (r"mlp.*experts.*(gate_proj|up_proj)", ("experts", "embed", "mlp")),
    (r"mlp.*experts.*down_proj", ("experts", "mlp", "embed")),
    (r"mlp/gate/kernel", ("embed", None)),
    # The top-1 switch experts (models/moe.py): expert dim -> ep axis
    (r"moe.*router", ("embed", None)),
    (r"moe.*w_up", ("experts", "embed", "mlp")),
    (r"moe.*w_down", ("experts", "mlp", "embed")),
    # GPT-2 head-structured projections ([E,3,H,D] / [H,D,E] einsum
    # kernels — the head split lives in the param layout so attention
    # inputs need no transpose copies):
    (r"(attn|attention).*qkv_kernel", ("embed", None, "heads", None)),
    (r"(attn|attention).*qkv_bias", (None, "heads", None)),
    (r"(attn|attention).*proj_kernel", ("heads", None, "embed")),
    (r"(attn|attention).*(q|k|v|qkv).*kernel", ("embed", "heads")),
    (r"(attn|attention).*(out|proj).*kernel", ("heads", "embed")),
    (r"mlp.*(fc|up|gate).*kernel", ("embed", "mlp")),
    (r"mlp.*(down|out|proj).*kernel", ("mlp", "embed")),
    (r"lm_head.*kernel", ("embed", "vocab")),
    # conv kernels (H, W, Cin, Cout)
    (r"conv.*kernel", (None, None, "conv_in", "conv_out")),
    # norms / biases / scales: replicated
    (r".*", ()),
]


def _path_str(path) -> str:
    import jax
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            parts.append(p.name)
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts).lower()


def spec_for_path(path, ndim: int, mesh,
                  patterns=None, rules: LogicalAxisRules = DEFAULT_RULES):
    from jax.sharding import PartitionSpec

    patterns = patterns or DEFAULT_PARAM_PATTERNS
    s = _path_str(path)
    for pattern, logical in patterns:
        if re.search(pattern, s):
            if len(logical) != ndim:
                # rank mismatch (e.g. fused kernels): replicate rather
                # than mis-shard
                return PartitionSpec()
            return logical_to_mesh(logical, mesh, rules)
    return PartitionSpec()


def shard_params(params, mesh, patterns=None,
                 rules: LogicalAxisRules = DEFAULT_RULES):
    """Build a NamedSharding pytree for a parameter pytree by matching
    param paths against the pattern table."""
    import jax
    from jax.sharding import NamedSharding

    def leaf_sharding(path, leaf):
        ndim = getattr(leaf, "ndim", 0)
        return NamedSharding(
            mesh, spec_for_path(path, ndim, mesh, patterns, rules))

    return jax.tree_util.tree_map_with_path(leaf_sharding, params)


def place_params(params, mesh, patterns=None,
                 rules: LogicalAxisRules = DEFAULT_RULES):
    """device_put a parameter pytree according to the rule table."""
    import jax
    shardings = shard_params(params, mesh, patterns, rules)
    return jax.device_put(params, shardings)
