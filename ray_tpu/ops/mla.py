"""Multi-head latent attention (MLA) for training.

DeepSeek-V2/V3's attention, which JoyAI-LLM-Flash carries: queries and
keys/values are projected **down** to a narrow latent, normed, and
projected **up** again to the heads; a small rotary part is kept apart
from the latent ("decoupled RoPE"), and the key's rotary part is one
head that every query head shares. With ``h`` the normed block input:

    c_q          = RMSNorm(h W_qa)                  [rq]     ``q_down``
    q_nope|q_rope = c_q W_qb, per head [dn | dr]             ``q_up``
    c_kv | k_r   = h W_kva, c_kv = RMSNorm(c_kv)    [rkv | dr] ``kv_down``
    k_nope | v   = c_kv W_kvb, per head [dn | dv]            ``kv_up``
    q_rope, k_r  take RoPE over interleaved pairs            ``rope``
    s_i = (q_nope_i k_nope_i^T + q_rope_i k_r^T) * scale
    o_i = causal_softmax(s_i) v_i                            ``core``
    y   = concat_i(o_i) W_o                                  ``out_proj``

``scale`` is ``(dn + dr)^-1/2`` unless the caller gives one. **Under
YaRN** (``models/joyai.py::YarnScaling``; Xing4.0-29B-A4B is the second
model on this function) the DeepSeek-V3 code has two factors and both
come through arguments: ``rope_amplitude``, on the rotated lanes of
``q_rope`` and ``k_r`` alike (YaRN's factor on cos and sin), and
``scale``, which carries ``m^2`` on the whole score, nope and rope
parts alike. The scale is one of the kernels' static arguments
(``mla_flash_static(t, dn, dr, scale)``), a constant inside their
bodies: scaling ``q`` in front instead would cost a pass over it in
each direction.

The down projections and the output projection are the model's own
dense layers (``models/joyai.py``); this file holds everything between
the latents and ``concat_i(o_i)``: ``latent_attention``.

Kimi-Linear's global layers (``models/kimi_linear.py``) are the same
attention with **no query latent and no rotation**: ``q_nope | q_rope =
h W_q`` straight from the block's normed input, which the caller hands
as ``c_q`` (no ``q_down`` scope opens; ``q_up`` is all of ``W_q``), and
``angles=None``: ``q_rope`` and ``k_r`` enter the scores as they are and
no ``rope`` scope opens. Same kernels, same shapes a head.

**The up-projections' columns.** ``W_qb`` and ``W_kvb`` are held as two
arrays each, the heads' 128-wide parts side by side in one (``[rq,
H*dn]``, ``[rkv, H*dn]``, ``[rkv, H*dv]``) and the heads' rotary parts
in the other (``[rq, H*dr]``): a permutation of the published matrices'
columns. Each matmul then writes ``[B, T, H*128]`` with one head a
128-lane block, which is what the kernel indexes
(``ops/pallas/flash_attention.py``'s last section); nothing is sliced,
concatenated or transposed between a projection and the kernel.

**What is kept for the backward pass** (``saved``): ``"expanded"``
keeps what the kernel read, as any attention does: ``q``, ``k_nope``,
``v`` and the rotated parts, ``H*(dn + dr) + H*(dn + dv) + dr`` values a
token (14,400 at the published widths) beside the output. ``"latents"``
keeps ``c_q``, ``c_kv`` and ``k_r`` (``rq + rkv + dr`` = 2,112) and the
backward pass runs the up-projections and the rotation again before
the kernels' backward: 2 x 13.6 M more operations a token a layer, a
sixth of the projections' own, for 24.6 KB a token a layer (201 MB a
layer at 8,192 tokens; PERF.md section 6, PR 34). The kernel's own
forward is not run again: its output and log-sum-exp are kept.

**Which attention runs** (``mla_path``; the trace's notes say:
``flash_path``). On a TPU where the shapes tile, the Pallas kernels:
bare in a one-device program, under a ``shard_map`` over the batch on a
mesh whose only real axes are ``dp`` / ``fsdp``. ``sp``, ``tp`` and
``ep`` are refused by name here, before a routed layer is built (which refuses
``ep`` and ``tp`` itself). Elsewhere (the CPU; shapes that do not
tile; a batch the mesh does not divide, as at init) a masked softmax
over the concatenated keys in XLA, ``flash_path`` = ``"xla"``: at 8,192
tokens its float32 scores are 8.6 GB a layer, so a benchmark refuses a
cell whose note says so.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.pallas import program
from ray_tpu.ops.remat import name_core_results
from ray_tpu.util import tracing


class UpProjections(NamedTuple):
    """``W_qb`` and ``W_kvb``, columns grouped by part (see above)."""
    q_nope: jax.Array       # [rq, H*dn]
    q_rope: jax.Array       # [rq, H*dr]
    k_nope: jax.Array       # [rkv, H*dn]
    v: jax.Array            # [rkv, H*dv]


def mla_path(batch: int, t: int, n_head: int, dn: int, dr: int, dv: int,
             mesh=None, interpret: bool = False) -> tuple[str, tuple]:
    """(``"kernel"`` or ``"xla"``, the mesh axes the batch is mapped
    over). Decided from the backend, the shapes and the mesh, as
    ``ops/attention.py`` decides for equal widths; raises where a TPU
    program with shapes that tile cannot reach the kernel."""
    from ray_tpu.ops.pallas.flash_attention import mla_flash_shapes_ok

    program.refuse(
        mesh, "latent attention",
        sp="the sequence split over chips (keys passed round a ring)",
        tp="the heads split over chips",
        ep="attention operands replicated over an expert axis")
    if not interpret and (
            jax.default_backend() != "tpu"
            or not mla_flash_shapes_ok(t, dn, dr, dv, n_head)):
        return "xla", ()
    if mesh is None and not interpret and jax.device_count() != 1:
        raise NotImplementedError(
            "latent attention in a process of "
            f"{jax.device_count()} devices and no mesh: a bare Pallas "
            "kernel cannot be partitioned; give the model its mesh")
    # no mesh: interpreted kernels (tests) run bare whatever the process
    # holds; None: e.g. the tiny batch of init-time tracing
    axes = () if mesh is None else program.batch_axes(mesh, batch)
    return ("xla", ()) if axes is None else ("kernel", axes)


def _xla_core(qn, qr, kn, kr, v, n_head: int, scale: float):
    """The same attention as one masked softmax over the concatenated
    keys, float32 scores: the path of the CPU and of shapes that do not
    tile."""
    b, t, _ = qn.shape
    q = jnp.concatenate([qn.reshape(b, t, n_head, -1),
                         qr.reshape(b, t, n_head, -1)], -1)
    k = jnp.concatenate([
        kn.reshape(b, t, n_head, -1),
        jnp.broadcast_to(kr[:, :, None], (b, t, n_head, kr.shape[-1]))], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1).astype(v.dtype),
                   v.reshape(b, t, n_head, -1),
                   preferred_element_type=jnp.float32)
    return o.reshape(b, t, -1).astype(v.dtype)


def latent_attention(c_q, c_kv, k_r, up: UpProjections, angles, *,
                     n_head: int, saved: str = "latents", mesh=None,
                     interpret: bool = False, scale: float | None = None,
                     rope_amplitude: float = 1.0):
    """``concat_i(o_i)``, [B, T, H*dv], from the normed latents ``c_q``
    [B, T, rq] and ``c_kv`` [B, T, rkv], the unrotated shared key
    ``k_r`` [B, T, dr], the up-projections and the rotation's
    ``angles`` [T, dr/2] (``models/llama.py::rope_freqs``).

    **A caller without a query latent** (``q_lora_rank`` null:
    ``models/kimi_linear.py``) hands the block's normed input as
    ``c_q`` and its one query projection's columns as ``up.q_nope`` /
    ``up.q_rope``: ``q_up`` is then the whole of ``W_q``, which the
    backward pass runs again as it runs any ``q_up`` again, and the
    caller opens no ``q_down``. **``angles=None``** is attention with
    no positions (``mla_use_nope``): the 64 "rotary" lanes stay in both
    score products unrotated, no ``rope`` scope opens, in either pass,
    and the note ``mla_positions`` says ``none`` (``rope`` otherwise).

    ``scale`` multiplies the whole score, both products alike (None:
    ``(dn + dr)^-1/2``); it is one of the kernels' static arguments and
    costs no pass (the note ``mla_scale``). ``rope_amplitude``
    multiplies the rotated lanes of ``q_rope`` and ``k_r`` (YaRN's
    factor on cos and sin; 1 leaves the rotation as it is traced
    without it).

    ``saved``: what the backward pass keeps (module docstring). The
    model takes the default; ``"expanded"`` is what the tests hold its
    gradients against, and the note ``mla_saved`` says which ran.
    ``interpret`` runs the kernels in interpret mode wherever this is
    (tests). Opens the scopes ``q_up``, ``kv_up``, ``rope`` and ``core``
    under its caller's, in both passes."""
    from ray_tpu.models.llama import apply_rope
    from ray_tpu.ops.pallas.flash_attention import (
        mla_flash_bwd, mla_flash_core, mla_flash_fwd, mla_flash_static)

    if saved not in ("latents", "expanded"):
        raise ValueError(f"saved={saved!r}: 'latents' or 'expanded'")
    tracing.note_trace(mla_saved=saved,
                       mla_positions="none" if angles is None else "rope")
    b, t, _ = c_q.shape
    dt = c_q.dtype
    dr = k_r.shape[-1]
    dn, dv = up.q_nope.shape[-1] // n_head, up.v.shape[-1] // n_head
    scale = float((dn + dr) ** -0.5 if scale is None else scale)
    tracing.note_trace(mla_scale=scale)
    path, axes = mla_path(b, t, n_head, dn, dr, dv, mesh, interpret)

    def expand(angles, c_q, c_kv, k_r, *up):
        """The kernel's five operands from the latents."""
        q_nope_w, q_rope_w, k_nope_w, v_w = up
        with jax.named_scope("q_up"):
            qn, qr = c_q @ q_nope_w.astype(dt), c_q @ q_rope_w.astype(dt)
        with jax.named_scope("kv_up"):
            kn, v = c_kv @ k_nope_w.astype(dt), c_kv @ v_w.astype(dt)
        if angles is None:      # no positions: the lanes stay as they are
            return qn, qr, kn, k_r, v
        with jax.named_scope("rope"):
            # in float32: apply_rope rounds the cosines to its operand's
            # type, and a rotation by bf16 cosines is another function,
            # not a rounding of this one (the gradient norm's distance
            # from the float32 reference halves; PERF.md 6, PR 34)
            f32 = jnp.float32
            def amp(x):     # YaRN's factor on cos and sin
                return x if rope_amplitude == 1.0 else x * rope_amplitude
            qr = amp(apply_rope(qr.reshape(b, t, n_head, dr).astype(f32),
                                angles[:t])).reshape(
                                    b, t, n_head * dr).astype(dt)
            kr = amp(apply_rope(k_r[:, :, None].astype(f32),
                                angles[:t]))[:, :, 0].astype(dt)
        return qn, qr, kn, kr, v

    operands = (c_q, c_kv, k_r, *up)
    if path == "xla":
        tracing.note_trace(flash_path="xla", flash_layout="concatenated")

        def attend(*operands):
            qkv = expand(angles, *operands)
            with jax.named_scope("core"):
                return _xla_core(*qkv, n_head, scale)
        if saved == "latents":
            attend = jax.checkpoint(attend)
        return attend(*operands)

    static = mla_flash_static(t, dn, dr, scale, interpret=interpret)

    def kernel(fn, n_in):
        # every operand and result carries the batch first
        return jax.named_scope("core")(program.over_batch(
            functools.partial(fn, static=static), mesh, axes,
            in_specs=(0,) * n_in, out_specs=0))

    if saved == "expanded":     # the kernels' own custom_vjp keeps them
        return kernel(mla_flash_core, 5)(*expand(angles, *operands))
    fwd, bwd = kernel(mla_flash_fwd, 5), kernel(mla_flash_bwd, 8)

    # the angles enter as an operand of their own (None: a tree of no
    # leaves), not through ``expand``'s closure: the backward
    # rule is traced when the cotangent arrives, which under a
    # recomputed block (``nn.remat``) is after the trace that made the
    # angles has ended
    @jax.custom_vjp
    def attend(angles, *operands):
        return fwd(*expand(angles, *operands))[0]

    def attend_fwd(angles, *operands):
        out, lse = name_core_results(*fwd(*expand(angles, *operands)))
        return out, (angles, operands, out, lse)

    def attend_bwd(res, g):
        angles, operands, out, lse = res
        # as jax.checkpoint does: without the barrier XLA finds the
        # forward pass's identical matmuls and keeps their results
        # instead (common subexpressions), and nothing is saved
        operands, g = jax.lax.optimization_barrier((operands, g))
        qkv, pull = jax.vjp(functools.partial(expand, angles), *operands)
        return (None if angles is None else jnp.zeros_like(angles),
                *pull(bwd(*qkv, out, lse, g)))
    attend.defvjp(attend_fwd, attend_bwd)
    return attend(angles, *operands)
