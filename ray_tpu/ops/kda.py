"""Kimi Delta Attention's recurrence for training: the gated delta rule
with **a decay a channel**, in its chunked form, both passes.

The recurrence (arXiv:2510.26692, equation 1), a head, with a state
``S`` of ``[K keys, V values]`` that starts at zero, a key ``k_t`` of
unit length, a step size ``beta_t`` in (0, 1) and a decay ``alpha_t =
exp(g_t)`` in (0, 1]^K::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

The decay is applied first and the delta correction reads the decayed
state, so with ``u_t = beta_t (v_t - (Diag(alpha_t) S_{t-1})^T k_t)``
the step is ``S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T``: a decay and a
rank-one write of a *corrected* value.

**The chunked form** (``kda_scan``). Inside a chunk of ``C`` rows, with
``G_t`` the running sum of ``g`` from the chunk's first row to row
``t`` (inclusive, <= 0) and ``S`` the state that entered the chunk::

    A_tj = sum_d k_td k_jd exp(G_td - G_jd)   (j <  t)   key-key products
    B_tj = sum_d q_td k_jd exp(G_td - G_jd)   (j <= t)   query-key products
    (I + Diag(beta) A) U = Diag(beta) (V - (exp(G) * K) S)
    o_t = (exp(G_t) * q_t)^T S + sum_{j<=t} B_tj u_j
    S  <- Diag(exp(G_C)) S + (exp(G_C - G) * K)^T U

- ``A`` and ``B`` are **formed from differences of the running sums**,
  never from ``exp(-G_j)`` by itself, which overflows float32 inside
  one chunk at decays this parameterisation reaches (``_scores``): a
  chunk is cut into sub-blocks of 16 rows; against the rows of an
  earlier sub-block both operands are scaled to the later sub-block's
  first row, each by a factor of at most 1, and the product is a plain
  matmul; only the 16 x 16 squares on the diagonal take the explicit
  ``[16, 16, K]`` form.
- ``T = (I + Diag(beta) A)^-1`` is a unit lower-triangular solve a chunk
  a head (the WY / UT form), which Mamba-2's scan has no counterpart
  of: forward substitution by rows inside each 16 x 16 diagonal block,
  then by blocks (``_solve``). With ``Uv = T Diag(beta) V`` and ``W = T
  Diag(beta) (exp(G) * K)``, ``U = Uv - W S``: everything but ``S`` is
  known before the chunks are walked.
- the chunks are walked in order, two ``[K, V]`` products a chunk to
  hand the state on (``_carry``), and the outputs read from the states
  that entered (``_read_out``).

Everything here is float32, the matmuls at ``Precision.HIGH`` (three
bfloat16 passes an operand pair; the kernels' at ``HIGHEST``, Mosaic
having nothing between that and one pass): the recurrence is 2% of a
Kimi-Linear step's operations (PERF.md section 5).

**What is kept for the backward pass** (``xla_chunked``). The rows are
walked in groups of ``GROUP_ROWS`` (a ``lax.scan`` over groups; each
group's chunks are made together and walked by an inner scan); a group
is a ``jax.checkpoint``, so the backward pass keeps ``q, k, v, g, beta``
and the state that entered each group (``[T / GROUP_ROWS, H, K, V]``
float32) and recomputes a group's inner quantities, its chunk-boundary
states among them, when it reaches it.

**Which path runs** (``kda_path``; the trace's note ``kda_path`` says).
``pallas_chunked``: ``ops/pallas/kda_scan.py``'s kernel pair, the same
five equations with a chunk's arrays and the state in VMEM (no groups:
the backward keeps the state entering every chunk and recomputes a
chunk's squares from it, and the forward rule names ``o`` and those
states, ``ops/remat.py::KDA_SCAN_*``, for the policy of a recomputed
block that would keep them; given a mixer's un-normalised ``q`` and ``k``
it also makes their unit rows there, where ``xla_chunked`` makes float32
arrays of them first: ``kda_scan``'s ``normalize_qk``), on a TPU
backend where keys and values are one 128-lane tile a head, the chunk
is 64 and the program is one device's (a ``pallas_call`` has no
partitioning rule; a mesh of several devices, ``dp`` or ``fsdp``, takes
the XLA path). ``xla_chunked``, the
above, everywhere else: the CPU, the tiny preset's chunks of 16, other
widths; it is also what the tests hold the kernels to. Nothing but
what ``kda_path`` observes chooses. A sequence or the heads split over
chips (``sp``, ``tp``) would need the state or the heads passed between
chips: refused by name.

**A decay a head** (``gdn_scan``, at the file's end: Gated DeltaNet,
arXiv:2412.06464). The same recurrence with ``alpha_t`` one number a
head and a row, and ``Hk`` key heads serving ``H`` value heads (value
head ``j`` reads q and k of key head ``j // (H / Hk)``). The decay then
leaves the chunk's products: ``A = (K K^T) * D`` and ``B = (Q K^T) * D``
with ``D_tj = exp(G_t - G_j)`` one ``[C, C]`` matrix a head, so there
are no sub-blocks, no ``[16, 16, K]`` squares and no array of decays
``K`` wide anywhere, in HBM or out of it; ``_solve``, ``_carry`` and
``_read_out`` are shared with the form above, and so are the two paths
and what chooses between them (``gdn_path``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.pallas import kda_scan as kernels, program
from ray_tpu.util import tracing

SUB = 16            # rows of a sub-block of a chunk (``_scores``, ``_solve``)
GROUP_ROWS = 512    # rows of one recomputed group of chunks
# float32 operands as three bfloat16 passes (``HIGH``): 2^-16 a product
_matmul = functools.partial(jnp.einsum, precision=lax.Precision.HIGH,
                            preferred_element_type=jnp.float32)


def kda_path(shape, chunk: int, mesh=None, *, values: int | None = None
             ) -> str:
    """Which recurrence ``kda_scan`` compiles for ``q`` [b, T, H, K]
    and values ``values`` wide (as the keys, if not said) at this chunk
    on this mesh:
    ``pallas_chunked`` on a TPU where the kernels tile the widths and
    the chunk and the program is one device's, else ``xla_chunked``.
    Raises where the program spans chips in a way that would split a
    sequence or its heads."""
    program.refuse(
        mesh, "kda",
        sp="the sequence split over chips (a recurrent state passed from "
           "chip to chip)",
        tp="the heads split over chips")
    if chunk % min(SUB, chunk):
        raise ValueError(f"chunk {chunk} is not whole sub-blocks of {SUB}")
    # the kernels where the rule finds a one-device program (no axis to
    # map them over: ``program.over_batch`` is not carried over)
    if (jax.default_backend() == "tpu"
            and kernels.shapes_ok(shape[-1], values or shape[-1], chunk)
            and program.batch_axes(mesh, shape[0]) == ()):
        return "pallas_chunked"
    return "xla_chunked"


def _scores(q, k, G, sub: int):
    """(``A`` [.., C, C], zero on and above the diagonal; ``B``, zero
    above it) from q, k and the running sums G, all [.., C, K]. Every
    exponent is a difference of running sums and at most 0."""
    *lead, c, kd = k.shape
    n = c // sub
    q4, k4, G4 = (z.reshape(*lead, n, sub, kd) for z in (q, k, G))
    first = G4[..., :1, :]                      # G at each sub-block's start
    # rows, scaled down to their own sub-block's first row
    to_first = jnp.exp(G4 - first)
    q_rows, k_rows = q4 * to_first, k4 * to_first
    # columns of the sub-blocks before I, scaled down to I's first row
    before = (jnp.arange(c) < jnp.arange(n)[:, None] * sub)[..., None]
    reach = first - G[..., None, :, :]                      # [.., n, C, K]
    k_cols = k[..., None, :, :] * jnp.exp(jnp.where(before, reach, -jnp.inf))
    a_off = _matmul("...icd,...ijd->...icj", k_rows, k_cols)
    b_off = _matmul("...icd,...ijd->...icj", q_rows, k_cols)
    # the diagonal squares, explicitly
    row, col = jnp.arange(sub)[:, None], jnp.arange(sub)
    between = jnp.exp(jnp.where(
        (col <= row)[..., None],
        G4[..., :, None, :] - G4[..., None, :, :], -jnp.inf))  # [..,n,s,s,K]
    kk = jnp.sum(k4[..., :, None, :] * k4[..., None, :, :] * between, -1)
    qk = jnp.sum(q4[..., :, None, :] * k4[..., None, :, :] * between, -1)
    kk = jnp.where(col < row, kk, 0.0)
    on_diag = jnp.eye(n, dtype=k.dtype)[:, None, :, None]   # [n, 1, n, 1]

    def whole(off, diag):
        return (off.reshape(*lead, n, sub, n, sub)
                + diag[..., :, :, None, :] * on_diag).reshape(*lead, c, c)
    return whole(a_off, kk), whole(b_off, qk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _solve(A, beta, sub: int):
    """``(I + Diag(beta) A)^-1`` for ``A`` [.., C, C] strictly lower
    triangular: forward substitution, by rows inside each ``sub``-row
    diagonal block and then by blocks. Its backward pass is the
    inverse's own, ``dM = -T^T dT T^T`` under the diagonal: two products
    a chunk instead of the substitution's steps walked back."""
    *lead, c, _ = A.shape
    n = c // sub
    L = beta[..., None] * A
    blocks = L.reshape(*lead, n, sub, n, sub)
    # the diagonal blocks: row i of the inverse's strict part is
    # -L_i - sum_{j<i} L_ij X_j, the rows above it already final
    X = -jnp.stack([blocks[..., i, :, i, :] for i in range(n)], -3)
    for i in range(1, sub):
        X = X.at[..., i, :].add(
            _matmul("...j,...jk->...k", X[..., i, :], X))
    X = X + jnp.eye(sub, dtype=A.dtype)
    inv = jnp.zeros_like(L)
    for i in range(n):
        r = slice(i * sub, (i + 1) * sub)
        inv = inv.at[..., r, r].set(X[..., i, :, :])
        if i:
            lead_ = slice(0, i * sub)
            below = _matmul("...ij,...jk->...ik", L[..., r, lead_],
                            inv[..., lead_, lead_])
            inv = inv.at[..., r, lead_].set(
                -_matmul("...ij,...jk->...ik", X[..., i, :, :], below))
    return inv


def _solve_fwd(A, beta, sub):
    T = _solve(A, beta, sub)
    return T, (T, A, beta)


def _solve_bwd(sub, res, dT):
    T, A, beta = res
    dM = -_matmul("...ji,...jk,...lk->...il", T, dT, T)
    dL = jnp.tril(dM, -1)
    return beta[..., None] * dL, jnp.sum(dL * A, -1)


_solve.defvjp(_solve_fwd, _solve_bwd)


def _state_read(k, G):
    """The key as the correction reads the state that entered the
    chunk: decayed down to the key's own row (``Diag(alpha)`` first,
    then the delta correction)."""
    return k * jnp.exp(G)


def _chunk_terms(q, k, v, g, beta, sub: int):
    """What a chunk needs that does not depend on the state entering
    it. q, k, g [.., C, K]; v [.., C, V]; beta [.., C]."""
    G = jnp.cumsum(g, axis=-2)
    A, B = _scores(q, k, G, sub)
    T = _solve(A, beta, sub)
    end = G[..., -1:, :]
    bv, bk = beta[..., None] * v, beta[..., None] * _state_read(k, G)
    return {"B": B,
            "Uv": _matmul("...ij,...jv->...iv", T, bv),
            "W": _matmul("...ij,...jd->...id", T, bk),
            "q_in": q * jnp.exp(G),             # reads the entering state
            "k_out": k * jnp.exp(end - G),      # writes to the chunk's end
            "keep": jnp.exp(end[..., 0, :])}    # what of a state survives


def _carry(S, terms):
    """The states entering each chunk and each chunk's corrected values
    ``U``, from the state ``S`` [b, H, K, V] entering the first; the
    terms carry the chunks on axis 2."""
    def step(S, t):
        U = t["Uv"] - _matmul("...id,...dv->...iv", t["W"], S)
        new = (t["keep"][..., None] * S
               + _matmul("...id,...iv->...dv", t["k_out"], U))
        return new, (S, U)

    per_chunk = {k: jnp.moveaxis(terms[k], 2, 0)
                 for k in ("Uv", "W", "k_out", "keep")}
    S, (entering, U) = lax.scan(step, S, per_chunk)
    return S, jnp.moveaxis(entering, 0, 2), jnp.moveaxis(U, 0, 2)


def _read_out(terms, entering, U):
    """``o`` of every chunk from the state that entered it and its
    corrected values."""
    return (_matmul("...id,...dv->...iv", terms["q_in"], entering)
            + _matmul("...ij,...jv->...iv", terms["B"], U))


def _group(S, rows, *, chunk: int):
    """One group of whole chunks: (the state it leaves, its outputs
    [b, rows, H, V]). rows: q, k, v, g [b, rows, H, .], beta [b, rows,
    H], as the caller holds them; the heads come in front of the rows
    here, a group at a time."""
    b, n, h, _ = rows["q"].shape

    def by_chunk(z):        # [b, rows, H, ...] -> [b, H, chunks, C, ...]
        z = z.astype(jnp.float32).reshape(b, n // chunk, chunk, *z.shape[2:])
        return jnp.moveaxis(z, 3, 1)

    terms = _chunk_terms(*(by_chunk(rows[name]) for name in "qkvg"),
                         by_chunk(rows["beta"]), min(SUB, chunk))
    S, entering, U = _carry(S, terms)
    o = _read_out(terms, entering, U)                   # [b, H, chunks, C, V]
    return S, jnp.moveaxis(o, 1, 3).reshape(b, n, h, -1)


def _xla_chunked(q, k, v, g, beta, *, chunk: int, group=_group):
    """``kda_scan`` in XLA: groups of chunks under a ``lax.scan``, each a
    ``jax.checkpoint`` (``group``: ``_group``, or ``gdn_scan``'s)."""
    b, t, kd = q.shape[0], q.shape[1], q.shape[-1]
    h = v.shape[2]
    per_group = chunk * max(1, min(GROUP_ROWS, t + (-t) % chunk) // chunk)
    pad = (-t) % per_group
    # [groups, b, rows a group, H, .]: no row moves for a batch of one
    rows = {name: jnp.moveaxis(
        jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2)).reshape(
            b, (t + pad) // per_group, per_group, *z.shape[2:]), 1, 0)
        for name, z in (("q", q), ("k", k), ("v", v), ("g", g),
                        ("beta", beta))}
    group = jax.checkpoint(functools.partial(group, chunk=chunk))
    _, o = lax.scan(group, jnp.zeros((b, h, kd, v.shape[-1]), jnp.float32),
                    rows)
    return jnp.moveaxis(o, 0, 1).reshape(b, t + pad, h, -1)[:, :t]


def unit_rows(x):
    """``x`` [.., K] to unit length along its last axis, float32: ``x /
    sqrt(sum x^2 + 1e-6)`` (fla's ``l2norm``; a row of zeros stays
    zero). The kernels' ``_unit`` is this on a head's square in VMEM."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                         + kernels.NORM_EPS)


def kda_scan(q, k, v, g, beta, *, chunk: int = 64, mesh=None,
             normalize_qk: bool = False):
    """The recurrence above over whole sequences, chunked, by the path
    ``kda_path`` names.

    q, k: [batch, T, H, K]  queries (already scaled) and unit keys; or,
                            with ``normalize_qk``, both as the mixer's
                            convolutions left them, in any dtype
    v:    [batch, T, H, V]
    g:    [batch, T, H, K]  log-decays, <= 0, float32
    beta: [batch, T, H]     step sizes, float32
    Returns ``o`` [batch, T, H, V] float32. ``T`` need not be whole
    chunks: the tail is padded with rows that neither decay nor write
    the state. ``mesh`` is the mesh the program is sharded over, if the
    caller knows one: ``kda_path`` decides from it.

    ``normalize_qk`` says what the caller hands over, not how it is
    run: ``unit_rows(q) * K^-1/2`` and ``unit_rows(k)`` are what the
    recurrence reads either way. ``xla_chunked`` makes them here, in
    float32 arrays under the scope ``qk_norm``; ``pallas_chunked`` hands
    the rows to the kernels as they are, which make a head's unit square
    in VMEM and carry ``dq, dk`` back through the norm there. The
    recurrence itself is under the scope ``scan`` on both paths."""
    path = kda_path(q.shape, chunk, mesh, values=v.shape[-1])
    tracing.note_trace(kda_path=path, kda_chunk=chunk, kda_heads=q.shape[2],
                       kda_state=[q.shape[-1], v.shape[-1]])
    if path == "pallas_chunked":
        with jax.named_scope("scan"):
            return kernels.kda_scan(q, k, v, g, beta,
                                    normalize_qk=normalize_qk)
    if normalize_qk:
        with jax.named_scope("qk_norm"):
            q, k = unit_rows(q) * q.shape[-1] ** -0.5, unit_rows(k)
    with jax.named_scope("scan"):
        return _xla_chunked(q, k, v, g, beta, chunk=chunk)


# ---------------------------------------------------------------------------
# a decay a head: Gated DeltaNet (arXiv:2412.06464)
# ---------------------------------------------------------------------------

def gdn_path(shape, chunk: int, mesh=None, *, values: int | None = None,
             heads: int | None = None) -> str:
    """``kda_path`` for the recurrence with a decay a head, ``shape``
    that of ``q`` [b, T, Hk, K] under ``heads`` value heads (as many as
    key heads, if not said): the same observations (backend, widths,
    chunk, the devices the program spans) and that a kernel cell's
    value heads are whole key heads' groups; the same two names, the
    same refusals of ``sp`` and ``tp`` by name."""
    path = kda_path(shape, chunk, mesh, values=values)
    if path == "pallas_chunked" and not kernels.heads_ok(
            heads or shape[2], shape[2]):
        return "xla_chunked"
    return path


def _scalar_chunk_terms(q, k, v, g, beta, sub: int):
    """``_chunk_terms`` where the decay is one number a row: q, k
    [.., C, K]; v [.., C, V]; g, beta [.., C]. The decay leaves the
    products: ``A = (K K^T) * D`` and ``B = (Q K^T) * D`` with ``D_tj =
    exp(G_t - G_j)`` one ``[C, C]`` matrix a chunk (every exponent a
    difference of running sums, at most 0 under the mask), no
    sub-blocks and no ``[.., K]`` array of decays anywhere
    (arXiv:2412.06464, section 3.3)."""
    G = jnp.cumsum(g, axis=-1)                              # [.., C]
    c = G.shape[-1]
    row, col = jnp.arange(c)[:, None], jnp.arange(c)
    D = jnp.exp(jnp.where(col <= row, G[..., :, None] - G[..., None, :],
                          -jnp.inf))
    A = jnp.where(col < row, _matmul("...id,...jd->...ij", k, k) * D, 0.0)
    B = _matmul("...id,...jd->...ij", q, k) * D
    T = _solve(A, beta, sub)
    end = G[..., -1:]
    to_row = jnp.exp(G)[..., None]
    bv, bk = beta[..., None] * v, beta[..., None] * k * to_row
    return {"B": B,
            "Uv": _matmul("...ij,...jv->...iv", T, bv),
            "W": _matmul("...ij,...jd->...id", T, bk),
            "q_in": q * to_row,
            "k_out": k * jnp.exp(end - G)[..., None],
            "keep": jnp.exp(end)}           # [.., 1]: every channel's


def _for_value_heads(z, rep: int):
    """A key head's rows [b, Hk, ...] for each of its ``rep`` value
    heads, [b, Hk * rep, ...]: value head ``j`` reads key head ``j //
    rep`` (not ``j % Hk``: a key head's value heads are neighbours)."""
    return jnp.repeat(z, rep, axis=1)


def _scalar_group(S, rows, *, chunk: int, rep: int):
    """``_group`` for a decay a head and ``rep`` value heads a key head:
    q, k [b, rows, H / rep, K]; v [b, rows, H, V]; g, beta [b, rows, H].
    A key head's q and k are repeated here, a group's rows at a time
    (value head ``j`` reads key head ``j // rep``); the caller never
    holds the copies."""
    b, n, h, _ = rows["v"].shape

    def by_chunk(z):        # [b, rows, H, ...] -> [b, H, chunks, C, ...]
        z = z.astype(jnp.float32).reshape(b, n // chunk, chunk, *z.shape[2:])
        return jnp.moveaxis(z, 3, 1)

    q, k = (_for_value_heads(by_chunk(rows[name]), rep) for name in "qk")
    terms = _scalar_chunk_terms(
        q, k, *(by_chunk(rows[name]) for name in ("v", "g", "beta")),
        min(SUB, chunk))
    S, entering, U = _carry(S, terms)
    o = _read_out(terms, entering, U)                   # [b, H, chunks, C, V]
    return S, jnp.moveaxis(o, 1, 3).reshape(b, n, h, -1)


def gdn_scan(q, k, v, g, beta, *, chunk: int = 64, mesh=None,
             normalize_qk: bool = False):
    """The gated delta rule with **a decay a head** (Gated DeltaNet,
    arXiv:2412.06464): the recurrence of this file's head with
    ``alpha_t`` one number a head and a row, over whole sequences,
    chunked, by the path ``gdn_path`` names.

    q, k: [batch, T, Hk, K]  as ``kda_scan``'s, over ``Hk`` key heads
    v:    [batch, T, H, V]   ``H`` a multiple of ``Hk``: value head ``j``
                             reads q and k of key head ``j // (H / Hk)``
    g:    [batch, T, H]      log-decays, <= 0, float32: rank 3
    beta: [batch, T, H]      step sizes, float32
    Returns ``o`` [batch, T, H, V] float32; ``T`` need not be whole
    chunks. ``normalize_qk`` as ``kda_scan``'s.

    Nothing ``K`` wide is made of the decay on either path, and ``q``
    and ``k`` stay ``Hk`` heads wide in HBM: ``xla_chunked`` repeats a
    key head's rows a group of chunks at a time inside the recomputed
    group (``_scalar_group``); ``pallas_chunked`` hands the kernels'
    grid cell the key heads of its value heads (a ``BlockSpec``), reads
    ``g`` as it reads ``beta`` and writes ``dg`` one float a row a
    head. The kernels' forward rule names its results as ``kda_scan``'s
    does (``KDA_SCAN_OUT``, ``KDA_SCAN_STATES``). The recurrence is under the
    scope ``scan`` on both paths, ``qk_norm`` as ``kda_scan``'s."""
    heads, key_heads = v.shape[2], q.shape[2]
    if g.ndim != 3 or heads % key_heads or k.shape != q.shape:
        raise ValueError(
            f"gdn_scan: a decay a head is g [b, T, H], and H a multiple "
            f"of the key heads: q {q.shape}, k {k.shape}, v {v.shape}, "
            f"g {g.shape}")
    rep = heads // key_heads
    path = gdn_path(q.shape, chunk, mesh, values=v.shape[-1], heads=heads)
    tracing.note_trace(gdn_path=path, gdn_chunk=chunk,
                       gdn_heads=[key_heads, heads],
                       gdn_state=[q.shape[-1], v.shape[-1]])
    if path == "pallas_chunked":
        with jax.named_scope("scan"):
            return kernels.gdn_scan(q, k, v, g, beta,
                                    normalize_qk=normalize_qk)
    if normalize_qk:
        with jax.named_scope("qk_norm"):
            q, k = unit_rows(q) * q.shape[-1] ** -0.5, unit_rows(k)
    with jax.named_scope("scan"):
        return _xla_chunked(
            q, k, v, g, beta, chunk=chunk,
            group=functools.partial(_scalar_group, rep=rep))
