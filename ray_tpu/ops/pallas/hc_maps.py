"""The residual maps of mHC (``ops/hyper_connections.py::hc_maps``) on a
Pallas TPU kernel pair, forward and backward, under one ``custom_vjp``.

The mathematics, the precisions and the order of the operations are that
function's docstring to the letter. What differs is where the chain from
the state's product with ``phi`` to the three maps runs. As
``jax.numpy`` it is ~45 XLA fusions a sub-layer forward (a reduction
roots its own fusion, so each of the 20 Sinkhorn normalisations is a
column sum, a divide, a row sum and a divide over ``[n, n, 1, T]``
float32, whose one batch row pads to eight sublanes) and twice that
backward, a few microseconds each whatever its size: 16.5 ms of the
Xing4.0 cell's 203.4 ms step for 16 floats a token (PERF.md section 6,
PRs 54 and 60). Here the chain is one kernel a pass, in VMEM.

**The kernels' edge.** The state is read by XLA, as before: the norm's
factor ``r = rsqrt(mean(x^2) + 1e-6)``, a float32 reduction over the ``n
d`` lanes, and the one product ``m = x phi`` (the state's type against
``phi`` rounded to it, accumulated in float32), turned so that the
**tokens lie in the lanes** and folded to whole vector registers: ``m``
``[n^2 + 2n, R, 128]``, ``r`` ``[R, 128]`` with ``R = B T / 128``. The
forward kernel takes those 25 floats a token, ``b`` and ``alpha``
(scalars in SMEM) and writes ``H_pre``, ``H_post`` ``[n, R, 128]`` and
``H_res`` ``[n^2, R, 128]``; the wrapper reshapes them to ``[n, B, T]``
and ``[n, n, B, T]``, which moves nothing. Backward, the kernel takes
the three cotangents with ``m`` and ``r`` and writes the cotangents of
``m`` and ``r`` and, eight sublanes of partial sums a lane in one
resident block, those of ``b`` and ``alpha``; XLA then makes ``dx`` and
``dphi`` (the product's two transposes as autodiff writes them, and the
norm's term ``-r^3 dr x / (n d)``). No pass over ``[n, n, T]`` is left in
HBM.

**Why the edge stops there** (PERF.md section 6, PR 60). A wider pair
was built and timed: the state read once forward (the sum of squares and
``phi^T x^T`` in one kernel, 0.160 ms where the bytes take 0.143) and
once backward (``dx`` and ``dphi`` from one block in VMEM). Alone a
sub-layer's forward + backward fell from 0.87 to 0.54 ms; in the Xing4.0
step it rose, because XLA fuses the state's reads with their neighbours
(``dx``'s product takes the other cotangents of the state as its
epilogue, ``post`` rides on ``out_proj``'s) and a custom call is a wall
to that: the cotangent's sum became a pass of its own, 0.59 ms a
sub-layer. So the state stays XLA's.

Grid (both passes): blocks of ``BLOCK_ROWS`` x 128 tokens (8 rows, so
every entry of a token block's ``M`` is one vector register and the
sixteen stay in registers through the loop). The forward's loop is a
``lax.fori_loop`` over the iterations with the ``n^2`` entries as its
carry: the body is one column and one row normalisation, ``n^2`` exact
divisions each (no approximate reciprocal), whatever ``iters`` is. The
backward kernel runs the same loop once more, writing every iterate
(``2 iters + 1`` of them, 2.7 MB a block of 1,024 tokens at 20
iterations) to VMEM scratch, then walks them in reverse: a
normalisation ``N = M / D``, ``D = sum(M) + eps``, has ``dM = (dN -
sum(dN N)) / D`` with the sums over the same axis, so a reverse step
reads the iterate before it (for ``D``) and its own (``N``). The clamp
passes a cotangent where it did not bite. A last block that the rows do
not fill reads past the arrays: tokens are independent, their results
are dropped on the way out, and they are masked out of ``b``'s and
``alpha``'s sums.

**Kept by name.** The forward rule names the kernel's three results
(``MAPS_PRE``, ``MAPS_POST``, ``MAPS_RES``) and the two arrays the
backward kernel reads (``MAPS_M``, ``MAPS_R``), 41 floats a token, 0.67
MB a sub-layer at 4,096 tokens, before they part into primal and
residuals. A recomputed block whose policy keeps the five
(``models/joyai.py::_block`` at ``hc_mult`` > 1) runs ``pre`` and
``post`` again from the kept maps and neither the norm, the product nor
the forward kernel; outside such a policy a name is the identity.

Set-up and devices as ``gated_norm.py``: the two functions that hold the
``pallas_call``s are jitted (``_hc_maps_fwd``, ``_hc_maps_bwd``: what a
trace's operations are called), so a model's sub-layers trace and lower
each kernel once; a ``pallas_call`` has no SPMD partitioning rule, so
``hc_maps`` takes the mesh and the axes the batch is sharded over and
maps everything over them. Which programs get the kernels is
``ops/hyper_connections.py::hc_maps_path``'s decision.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ray_tpu.ops.pallas import program
from ray_tpu.ops.remat import MAPS_KEEPS

_F32 = jnp.float32
_LANES = 128
# Rows of 128 tokens a grid cell: one vector register an entry.
BLOCK_ROWS = 8
_VMEM_LIMIT = 32 << 20


def shapes_ok(t: int) -> bool:
    """Whether the kernels tile sequences of ``t`` tokens: whole
    128-lane tiles (the state's own lanes are XLA's to read)."""
    return t % _LANES == 0


class _Static(NamedTuple):
    """What the kernels are specialised on, besides their shapes."""
    n: int
    iters: int
    eps: float
    clamp: float
    norm_eps: float
    interpret: bool

    def kernel(self) -> dict:
        """What a kernel's body takes."""
        return dict(n=self.n, iters=self.iters, eps=self.eps,
                    clamp=self.clamp)


# ---------------------------------------------------------------------------
# the chain, one block of tokens: entries are [rows, 128] float32
# ---------------------------------------------------------------------------

def _line(v, n, k, columns):
    """Column ``k``'s (``i`` runs) or row ``k``'s (``j`` runs) entries
    of ``v``; ``v[n i + j]`` is entry ``[i, j]``."""
    return ([v[n * i + k] for i in range(n)] if columns
            else [v[n * k + j] for j in range(n)])


def _normalise(m, n, eps, *, columns):
    """``M / (sum(M) + eps)``, the sum over each column or each row."""
    over = [sum(_line(m, n, k, columns)) + eps for k in range(n)]
    return tuple(m[n * i + j] / over[j if columns else i]
                 for i in range(n) for j in range(n))


def _normalise_back(d, out, before, n, eps, *, columns):
    """The cotangent of ``_normalise``'s operand from that of its result
    ``d``, its result ``out`` and its operand ``before``."""
    over = [sum(_line(before, n, k, columns)) + eps for k in range(n)]
    dot = [sum(a * b for a, b in zip(_line(d, n, k, columns),
                                     _line(out, n, k, columns)))
           for k in range(n)]
    return tuple((d[n * i + j] - dot[j if columns else i])
                 / over[j if columns else i]
                 for i in range(n) for j in range(n))


def _pre_activations(s_ref, m_ref, r, n):
    """``m r`` and ``z = gate * (m r) + b`` an entry, ``b`` and
    ``alpha`` (one gate an entry) from SMEM; and the gates."""
    width = n * n + 2 * n
    gate = [s_ref[0, width + min(c // n, 2)] for c in range(width)]
    s = [m_ref[c] * r for c in range(width)]
    return s, [gate[c] * s[c] + s_ref[0, c] for c in range(width)], gate


def _fwd_kernel(s_ref, m_ref, r_ref, pre_ref, post_ref, res_ref, *,
                n, iters, eps, clamp):
    _, z, _ = _pre_activations(s_ref, m_ref, r_ref[...], n)
    for i in range(n):
        pre_ref[i] = jax.nn.sigmoid(z[i])
        post_ref[i] = 2.0 * jax.nn.sigmoid(z[n + i])
    m = tuple(jnp.exp(jnp.clip(z[2 * n + e], -clamp, clamp))
              for e in range(n * n))

    def step(_, m):
        return _normalise(_normalise(m, n, eps, columns=True), n, eps,
                          columns=False)

    m = lax.fori_loop(0, iters, step, m)
    for e in range(n * n):
        res_ref[e] = m[e]


def _bwd_kernel(s_ref, m_ref, r_ref, dpre_ref, dpost_ref, dres_ref,
                dm_ref, dr_ref, acc_ref, it_ref, *,
                n, iters, eps, clamp, total_rows):
    """One block. ``acc_ref`` [2, n^2 + 2n, 8, 128] is the call's: it
    stays in VMEM across the blocks, which add into it their ``dz``
    (``b``'s cotangent) and ``dz * m r`` (``alpha``'s) an entry, a
    token a place. ``it_ref`` [2 iters + 1, n^2, 8, 128] holds the
    loop's iterates."""
    @pl.when(pl.program_id(0) == 0)
    def _first():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    r = r_ref[...]
    nn = n * n
    s, z, gate = _pre_activations(s_ref, m_ref, r, n)
    zr = z[2 * n:]

    def keep(k, m):
        for e in range(nn):
            it_ref[k, e] = m[e]

    def kept(k):
        return tuple(it_ref[k, e] for e in range(nn))

    # the loop again, every iterate kept
    def step(t, m):
        m = _normalise(m, n, eps, columns=True)
        keep(2 * t + 1, m)
        m = _normalise(m, n, eps, columns=False)
        keep(2 * t + 2, m)
        return m

    m0 = tuple(jnp.exp(jnp.clip(v, -clamp, clamp)) for v in zr)
    keep(0, m0)
    lax.fori_loop(0, iters, step, m0)

    # and back through it
    def back(i, d):
        t = iters - 1 - i
        between = kept(2 * t + 1)
        d = _normalise_back(d, kept(2 * t + 2), between, n, eps,
                            columns=False)
        return _normalise_back(d, between, kept(2 * t), n, eps,
                               columns=True)

    d = lax.fori_loop(0, iters, back,
                      tuple(dres_ref[e] for e in range(nn)))

    # down to the pre-activations
    dz = []
    for i in range(n):
        p = jax.nn.sigmoid(z[i])
        dz.append(dpre_ref[i] * (p * (1.0 - p)))
    for i in range(n):
        q = jax.nn.sigmoid(z[n + i])
        dz.append(dpost_ref[i] * (2.0 * (q * (1.0 - q))))
    for e in range(nn):     # exp's cotangent, where the clamp let it by
        inside = (zr[e] > -clamp) & (zr[e] < clamp)
        dz.append(jnp.where(inside, d[e] * it_ref[0, e], 0.0))

    ragged = total_rows % BLOCK_ROWS != 0
    if ragged:      # rows past the arrays hold anything
        live = (pl.program_id(0) * BLOCK_ROWS + lax.broadcasted_iota(
            jnp.int32, r.shape, 0)) < total_rows

    def summed(v):
        return jnp.where(live, v, 0.0) if ragged else v

    dr = jnp.zeros_like(r)
    for c, dz_c in enumerate(dz):
        ds = dz_c * gate[c]
        dm_ref[c] = ds * r
        dr = dr + ds * m_ref[c]
        acc_ref[0, c] += summed(dz_c)
        acc_ref[1, c] += summed(dz_c * s[c])
    dr_ref[...] = dr


# ---------------------------------------------------------------------------
# the two calls
# ---------------------------------------------------------------------------

def _specs(width, total_rows):
    from jax.experimental.pallas import tpu as pltpu
    scalars = pl.BlockSpec((1, width + 3), lambda i: (0, 0),
                           memory_space=pltpu.SMEM)

    def entries(k):
        return pl.BlockSpec((k, BLOCK_ROWS, _LANES), lambda i: (0, i, 0))

    return ((pl.cdiv(total_rows, BLOCK_ROWS),), scalars, entries,
            pl.BlockSpec((BLOCK_ROWS, _LANES), lambda i: (i, 0)))


def _compiler_params(semantics):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=(semantics,),
                                vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnames=("static",))
def _hc_maps_fwd(scalars, m, r, *, static: _Static):
    """(H_pre [n, R, 128], H_post [n, R, 128], H_res [n^2, R, 128]) from
    ``scalars`` [1, n^2 + 2n + 3] (``b``, then ``alpha``), ``m`` [n^2 +
    2n, R, 128] and ``r`` [R, 128]. Jitted so that a model's sub-layers
    share one trace and one Mosaic lowering."""
    n = static.n
    width, total_rows, _ = m.shape
    grid, scalar, entries, row = _specs(width, total_rows)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **static.kernel()),
        grid=grid,
        in_specs=[scalar, entries(width), row],
        out_specs=[entries(n), entries(n), entries(n * n)],
        out_shape=[jax.ShapeDtypeStruct((k, total_rows, _LANES), _F32)
                   for k in (n, n, n * n)],
        compiler_params=_compiler_params("parallel"),
        interpret=static.interpret,
    )(scalars, m, r)


@functools.partial(jax.jit, static_argnames=("static",))
def _hc_maps_bwd(scalars, m, r, dpre, dpost, dres, *, static: _Static):
    """(dm as ``m``, dr as ``r``, dscalars as ``scalars``); jitted for
    the reason ``_hc_maps_fwd`` is."""
    from jax.experimental.pallas import tpu as pltpu
    n, iters = static.n, static.iters
    width, total_rows, _ = m.shape
    grid, scalar, entries, row = _specs(width, total_rows)
    dm, dr, acc = pl.pallas_call(
        functools.partial(_bwd_kernel, total_rows=total_rows,
                          **static.kernel()),
        grid=grid,
        in_specs=[scalar, entries(width), row,
                  entries(n), entries(n), entries(n * n)],
        out_specs=[entries(width), row,
                   pl.BlockSpec((2, width, BLOCK_ROWS, _LANES),
                                lambda i: (0, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(m.shape, _F32),
                   jax.ShapeDtypeStruct(r.shape, _F32),
                   jax.ShapeDtypeStruct((2, width, BLOCK_ROWS, _LANES),
                                        _F32)],
        scratch_shapes=[pltpu.VMEM((2 * iters + 1, n * n, BLOCK_ROWS, _LANES),
                                   _F32)],
        compiler_params=_compiler_params("arbitrary"),
        interpret=static.interpret,
    )(scalars, m, r, dpre, dpost, dres)
    db, dgate = acc.sum((-1, -2))
    dalpha = jnp.stack([dgate[:n].sum(), dgate[n:2 * n].sum(),
                        dgate[2 * n:].sum()])
    return dm, dr, jnp.concatenate([db, dalpha])[None]


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------

def _product(x, phi):
    """``x phi`` [B, T, n^2 + 2n] in float32, ``phi`` rounded to the
    state's type: ``hc_maps``'s one matmul."""
    return jnp.einsum("btk,kc->btc", x, phi.astype(x.dtype),
                      preferred_element_type=_F32)


def _forward(x, phi, b, alpha, static: _Static):
    """The three maps as the callers read them, and what the backward
    rule keeps; the five arrays of ``MAPS_KEEPS`` under their names."""
    b_, t, _ = x.shape
    n, shape = static.n, (b_ * t // _LANES, _LANES)
    r = lax.rsqrt(jnp.mean(jnp.square(x.astype(_F32)), -1)
                  + static.norm_eps).reshape(shape)
    m = jnp.moveaxis(_product(x, phi), -1, 0).reshape(-1, *shape)
    scalars = jnp.concatenate([b.astype(_F32), alpha.astype(_F32)])[None]
    pre, post, res = _hc_maps_fwd(scalars, m, r, static=static)
    # all five named before they part into primal and residuals (the
    # trap ``ops/remat.py::name_core_results`` records)
    pre, post, res, m, r = (checkpoint_name(v, name) for v, name in zip(
        (pre, post, res, m, r), MAPS_KEEPS))
    maps = (pre.reshape(n, b_, t), post.reshape(n, b_, t),
            res.reshape(n, n, b_, t))
    return maps, (x, phi, b, alpha, scalars, m, r)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _maps_core(x, phi, b, alpha, static: _Static):
    return _forward(x, phi, b, alpha, static)[0]


def _maps_core_bwd(static, res, cotangents):
    x, phi, b, alpha, scalars, m, r = res
    b_, t, width = x.shape
    dpre, dpost, dres = (d.astype(_F32).reshape(-1, *r.shape)
                         for d in cotangents)
    dm, dr, dscalars = _hc_maps_bwd(scalars, m, r, dpre, dpost, dres,
                                    static=static)
    # the product's two transposes as autodiff writes them, and the
    # norm's term: ``dr d(rsqrt(mean x^2 + eps)) = -r^3 dr x / (n d)``,
    # which needs no second mean
    _, product_vjp = jax.vjp(_product, x, phi)
    dx, dphi = product_vjp(jnp.moveaxis(dm.reshape(-1, b_, t), 0, -1))
    coef = (dr * r * r * r * (-1.0 / width)).reshape(b_, t, 1)
    dx = dx + (coef * x.astype(_F32)).astype(x.dtype)
    dscalars = dscalars[0]
    return (dx, dphi,
            dscalars[:b.shape[0]].astype(b.dtype),
            dscalars[b.shape[0]:].astype(alpha.dtype))


_maps_core.defvjp(_forward, _maps_core_bwd)


def hc_maps(x, phi, b, alpha, *, n: int, iters: int, eps: float,
            clamp: float, norm_eps: float = 1e-6, interpret: bool = False,
            mesh=None, batch_axes=()):
    """``ops/hyper_connections.py::hc_maps`` on the kernels: the state
    ``x`` [B, T, n d], ``phi`` [n d, n^2 + 2n], ``b`` [n^2 + 2n],
    ``alpha`` [3]; the same three float32 maps, differentiable in all
    four. ``T`` must pass ``shapes_ok``; ``mesh`` and ``batch_axes``
    are ``program.over_batch``'s (a token's maps need nothing of
    another's, the parameters are whole on every device)."""
    if not shapes_ok(x.shape[1]):
        raise ValueError(
            f"the residual maps' kernels do not tile a state {x.shape}")
    core = functools.partial(_maps_core, static=_Static(
        n, iters, float(eps), float(clamp), float(norm_eps), interpret))
    # the maps come back streams first: [n, B, T] and [n, n, B, T]
    core = program.over_batch(core, mesh, batch_axes,
                              in_specs=(0, None, None, None),
                              out_specs=(1, 1, 2))
    return core(x, phi, b, alpha)
