"""The chunked gated delta rule (KDA) as Pallas TPU kernels.

The mathematics is ``models.kimi_linear.kda_chunked``'s (its docstring
and the module's have the recurrence); what changes is where a tile's
matrices live. Per (head, tile of ``C = 64`` positions) the kernel
builds, in registers and VMEM and nowhere else,

    G = cumsum(g)                          [C, Dk]  float32
    A, B (pairs weighed by exp(G_r - G_i))  [C, C]
    T = (I + beta A)^-1                    [C, C]   float32
    [u, wk] = T [v, k e^G] beta            [C, Dv + Dk]
    w = u - wk S,   o = (q e^G) S + B w
    S <- e^(G_end) S + (k e^(G_end - G))^T w

and carries ``S`` (held TRANSPOSED, ``[Dv, Dk]``: the decay then runs
along lanes and every product with it is the MXU's native ``a b^T``) in
a float32 VMEM scratch across the sequential tile axis of the grid.

**Layout.** A grid step is one tile of EVERY head: the blocks are the
tile's rows of ``[B, L * H, D]``, which is ``[B, L, H, D]`` as it lies
in memory (a bitcast), and head ``h``'s ``[C, D]`` is every ``H``-th
row of the block, read and written by strided loads and stores. The
other choice, ``[C, hb * D]`` lane slabs out of ``[B, L, H * D]``, runs
the kernels 5% faster and the train step 1.2% slower (XLA then moves
the model's per-head normalisations into a 4-D layout and pays for it
around them: PERF.md, PR 30). ``beta`` comes as its own ``[C, H]``.

**In-tile pairs** keep every exponent <= 0 by the halving of
``_kda_intra``: the pair (row r, key i < r) belongs to the one block
size ``b`` at which r lies in the upper and i in the lower half of the
same block, and is factored around that lower half's last position m,
``exp(G_r - G_m) exp(G_m - G_i)``. ``log2(C)`` products of ``[2C, Dk] x
[Dk, C]`` (k rows and q rows stacked), each masked to its level.

**The solve** is a forward elimination on the vector unit
(:func:`_eliminate`): rank-one updates of float32 8-row tiles give the
inverse exactly as a row-by-row substitution would; its derivative is
``-T^T dT T^T``.

**Backward**: tiles in reverse with ``dS`` in VMEM; the tile's forward
is recomputed from ``q, k, v, g, beta`` and what the forward kernel
saved (the state at the tile's start, and ``T``, so the elimination is
not run twice), and its derivative is ``jax.vjp`` of the SAME per-tile
function the forward kernel traces. Matrix products are ``custom_vjp``
so that the backward products take their operands in the compute dtype
too, as XLA's do on the chip.

Precision is ``kda_chunked``'s: products take ``compute_dtype``
operands and accumulate in float32; ``G``, every ``exp``, the solve and
the carried state are float32 (float32 products contract at
``HIGHEST``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.dtype("float32")
# Positions a grid step: 64 reads 6.4 / 14.8 ms a call forward / forward
# + backward at the cell's shapes and 32 reads 8.5 / 21.6 (PERF.md, PR 30).
_TILE = 64
# Heads worked on side by side inside the loop over a tile's heads
# (1 / 2 / 4: 6.8 / 6.4 / 6.2 ms forward; 4 reads no more tokens/s
# in the cell than 2 and compiles twice the code).
_UNROLL = 2
# A step's blocks, double-buffered (a tile of every head: 1 MB an
# operand at 64 x 32 x 128, the tile's states 2 MB: 25 MB backward),
# of a v5e's 128 MiB.
_VMEM_LIMIT = 64 << 20
# contracting dimensions of ``a b``, ``a b^T``, ``a^T b``
_FORMS = {"nn": ((1,), (0,)), "nt": ((1,), (1,)), "tn": ((0,), (0,))}


def _dot(a, b, form, dt):
    """One 2-D product, operands in ``dt``, float32 out; float32
    operands contract at ``HIGHEST`` (a float32 product means one)."""
    return jax.lax.dot_general(
        a.astype(dt), b.astype(dt), (_FORMS[form], ((), ())),
        precision=_HI if dt == _F32 else None,
        preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _mm(a, b, form, dt):
    """``_dot`` whose cotangents are ``_dot``s in the same ``dt``."""
    return _dot(a, b, form, dt)


def _mm_fwd(a, b, form, dt):
    return _dot(a, b, form, dt), (a, b)


def _mm_bwd(form, dt, res, dc):
    a, b = res
    if form == "nn":
        return _dot(dc, b, "nt", dt), _dot(a, dc, "tn", dt)
    if form == "nt":
        return _dot(dc, b, "nn", dt), _dot(dc, a, "tn", dt)
    return _dot(b, dc, "nt", dt), _dot(a, dc, "nn", dt)


_mm.defvjp(_mm_fwd, _mm_bwd)


def _iota(c, axis):
    return jax.lax.broadcasted_iota(jnp.int32, (c, c), axis)


def _tril(c):
    """Lower-triangular ones with the diagonal, bfloat16 (exact)."""
    return (_iota(c, 0) >= _iota(c, 1)).astype(jnp.bfloat16)


def _ones_dot(ones, x, form):
    """A 0/1 matrix times float32 ``x``, float32-faithful in three
    bfloat16 passes: ``x`` is split into three bfloat16 parts that add
    up to it and the ones are exact, so every product is exact and the
    sum is float32's (half the MXU passes of a ``HIGHEST`` product)."""
    bf = jnp.bfloat16
    hi = x.astype(bf)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(bf)
    low = (rest - mid.astype(jnp.float32)).astype(bf)
    return (_dot(ones, low, form, bf) + _dot(ones, mid, form, bf)
            + _dot(ones, hi, form, bf))


@jax.custom_vjp
def _cumsum(g):
    """Inclusive sum down the rows (``jnp.cumsum`` does not lower in
    Mosaic): a product with lower-triangular ones."""
    return _ones_dot(_tril(g.shape[0]), g, "nn")


def _cumsum_fwd(g):
    return _cumsum(g), None


def _cumsum_bwd(_, dG):
    return (_ones_dot(_tril(dG.shape[0]), dG, "tn"),)


_cumsum.defvjp(_cumsum_fwd, _cumsum_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _roll(x, shift):
    """Rows rolled down by ``shift`` (``pltpu.roll`` has no derivative
    rule of its own: its transpose is the roll back)."""
    return pltpu.roll(x, shift, 0)


_roll.defvjp(lambda x, shift: (_roll(x, shift), None),
             lambda shift, _, d: (_roll(d, (-shift) % d.shape[0]),))


def _mid(G, b):
    """``G`` at the last position of the lower half of each row's block
    of ``b`` rows, laid over the block's rows. Blocks of whole 8-row
    groups take a sublane broadcast; smaller ones are put together from
    rolled copies."""
    c = G.shape[0]
    half = b // 2
    if b % 8 == 0:
        return jnp.concatenate([
            jnp.broadcast_to(G[j * b + half - 1:j * b + half], (b, G.shape[1]))
            for j in range(c // b)], axis=0)
    at = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) % b
    out = G                                # rows at the middle itself
    for off in range(half - 1, -half - 1, -1):
        if off:                            # row r reads row r + off
            out = jnp.where(at == half - 1 - off,
                            _roll(G, (-off) % c), out)
    return out


def _levels(c):
    """The halving's constants, made once a grid step and shared by its
    heads: per block size ``b = C, C/2, .., 2`` a column that is +1 on
    the rows in the upper half of their block and -1 on the others, and
    the mask of the pairs that belong to the level."""
    row, col = _iota(c, 0), _iota(c, 1)
    apart = row ^ col      # its highest set bit is the pair's half size
    at = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    out, b = [], c
    while b >= 2:
        half = b // 2
        out.append((b, jnp.where(at % b >= half, 1.0, -1.0),
                    (row > col) & (apart >= half) & (apart < b)))
        b = half
    return out


def _pairs(q, k, G, dt, levels):
    """``A`` (strictly lower: ``k_r . Diag(exp(G_r - G_i)) k_i``) and
    ``B`` (lower with its diagonal: the same with ``q_r``), ``[C, C]``
    float32, by halving. The exponents are <= 0 but for ``G``'s own
    rounding, and both factors of a pair leave the SAME middle, so
    their product is the pair's weight whatever the rounding."""
    c = q.shape[0]
    A = jnp.zeros((c, c), jnp.float32)
    B = jnp.where(_iota(c, 0) == _iota(c, 1),
                  jnp.sum(q * k, axis=-1, keepdims=True), 0.0)
    for b, sign, mine in levels:
        # rows above the middle: G_r - G_m; keys at or below: G_m - G_i
        fac = jnp.exp((G - _mid(G, b)) * sign)
        kf = k * fac
        level = _mm(jnp.concatenate([kf, q * fac], axis=0), kf, "nt", dt)
        A = jnp.where(mine, level[:c], A)
        B = jnp.where(mine, level[c:], B)
    return A, B


def _eliminate(ns):
    """``(I + n)^-1`` for each strictly lower ``n [m, m]`` float32, by
    forward elimination on the vector unit: step ``i`` subtracts ``n[r,
    i]`` times the finished row ``i`` from the rows ``r > i`` (``n`` is
    zero on and above its diagonal: no mask is needed), and only the
    8-row groups (a register's sublanes) that hold such rows are
    touched. Exact float32: what a row-by-row substitution computes.
    The matrices advance in lockstep, so that their chains stand side
    by side in the instruction stream."""
    m = ns[0].shape[0]
    groups = range(0, m, 8)
    eye = (_iota(m, 0) == _iota(m, 1)).astype(jnp.float32)
    xs = [[eye[r:r + 8] for r in groups] for _ in ns]
    cols = [[n[r:r + 8] for r in groups] for n in ns]
    for i in range(m - 1):
        for x, col in zip(xs, cols):
            row = x[i // 8][i % 8:i % 8 + 1]
            for t in range(i // 8, len(x)):
                x[t] = x[t] - col[t][:, i:i + 1] * row
    return [jnp.concatenate(x, axis=0) for x in xs]


def _inverse(n):
    """``(I + n)^-1``, ``n [C, C]`` strictly lower, ``C`` 32 or 64: at
    64 the two diagonal blocks of 32 are eliminated side by side and
    the lower-left block is ``-Q^-1 L P^-1`` (two float32 products):
    0.77 against 1.30 us a matrix for 63 steps on the whole of it, one
    matrix at a time (PERF.md, PR 30)."""
    c = n.shape[0]
    if c <= 32:
        return _eliminate([n])[0]
    h = c // 2
    p, q = _eliminate([n[:h, :h], n[h:, h:]])
    low = -_dot(_dot(q, n[h:, :h], "nn", _F32), p, "nn", _F32)
    zero = jnp.zeros((h, h), jnp.float32)
    return jnp.concatenate([jnp.concatenate([p, zero], axis=1),
                            jnp.concatenate([low, q], axis=1)], axis=0)


@jax.custom_vjp
def _unit_lower_inverse(n, t):
    """``(I + n)^-1`` for strictly lower ``n``; ``t`` is that inverse
    where the caller has it already (the backward kernel reads the one
    the forward kernel saved) and ``None`` where it is to be computed.
    Its derivative needs the inverse alone: ``-T^T dT T^T``."""
    return _inverse(n) if t is None else t


def _unit_lower_inverse_fwd(n, t):
    t = _unit_lower_inverse(n, t)
    return t, t


def _unit_lower_inverse_bwd(t, dt):
    # d(m^-1) = -m^-1 dm m^-1; only the strictly lower part of m is read
    c = t.shape[0]
    dn = -_dot(_dot(t, dt, "tn", _F32), t, "nt", _F32)
    return jnp.where(_iota(c, 0) > _iota(c, 1), dn, 0.0), None


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _tile(q, k, v, g, beta, st, *, dt, levels, t=None):
    """One head's tile. ``q, k, g [C, Dk]``, ``v [C, Dv]``, ``beta
    [C, 1]``, ``st [Dv, Dk]`` (the state, transposed), all float32 ->
    ``(o [C, Dv], the next st, T [C, C])``. Pure: the forward kernel
    traces it and the backward kernel takes its ``jax.vjp`` (with the
    ``t`` the forward saved)."""
    c, dv = v.shape
    G = _cumsum(g)
    A, B = _pairs(q, k, G, dt, levels)
    T = _unit_lower_inverse(beta * A, t)
    eG = jnp.exp(G)
    sol = _mm(T, jnp.concatenate([v, k * eG], axis=-1) * beta, "nn", _F32)
    u, wk = sol[:, :dv], sol[:, dv:]
    through = _mm(jnp.concatenate([wk, q * eG], axis=0), st, "nt", dt)
    w = u - through[:c]
    o = through[c:] + _mm(B, w, "nn", dt)
    g_end = G[c - 1:c]
    st = st * jnp.exp(g_end) + _mm(w, k * jnp.exp(g_end - G), "tn", dt)
    return o, st, T


def _head(refs, beta_ref, h, c, nh):
    """Head ``h``'s operands out of a tile's blocks: rows ``h, h + nh,
    ..`` of the ``[C * nh, D]`` tiles (position-major, as ``[B, L, H,
    D]`` lies in memory) and lane ``h`` of ``beta``'s ``[C, nh]``."""
    rows = pl.ds(h, c, stride=nh)
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, nh), 1)
    beta = jnp.sum(jnp.where(lane == h, beta_ref[0].astype(jnp.float32), 0.0),
                   axis=1, keepdims=True)
    return tuple(r[0, rows, :].astype(jnp.float32) for r in refs) + (beta,)


def _each_head(nh, body):
    """``body(h)`` for every head: a loop over ``_UNROLL`` heads at a
    time, whose independent chains the scheduler interleaves."""
    u = _UNROLL if nh % _UNROLL == 0 else 1

    def step(i, carry):
        for j in range(u):
            body(i * u + j)
        return carry

    jax.lax.fori_loop(0, nh // u, step, 0)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest, c, nh,
                dt):
    """Grid ``(B, tiles)``, the tile axis sequential; every head of
    the tile in one step. ``rest``: where the backward will want them,
    the blocks of the state at the tile's start and of the tile's
    ``T``; and the state scratch ``[H, Dv, Dk]``."""
    st_scr = rest[-1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        st_scr[...] = jnp.zeros(st_scr.shape, jnp.float32)

    levels = _levels(c)

    def head(h):
        st = st_scr[h]
        o, st_next, t = _tile(
            *_head((q_ref, k_ref, v_ref, g_ref), beta_ref, h, c, nh), st,
            dt=dt, levels=levels)
        if len(rest) == 3:
            rest[0][0, 0, h] = st
            rest[1][0, 0, h] = t
        o_ref[0, pl.ds(h, c, stride=nh), :] = o.astype(o_ref.dtype)
        st_scr[h] = st_next

    _each_head(nh, head)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, st_ref, t_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dst_scr, *, c, nh,
                dt):
    """The same grid with the tile axis reversed by the index maps:
    ``dst_scr`` carries the state's cotangent back through the tiles."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        dst_scr[...] = jnp.zeros(dst_scr.shape, jnp.float32)

    lane = jax.lax.broadcasted_iota(jnp.int32, (c, nh), 1)
    levels = _levels(c)

    def head(h):
        rows = pl.ds(h, c, stride=nh)
        (_, _, t), vjp = jax.vjp(
            functools.partial(_tile, dt=dt, levels=levels, t=t_ref[0, 0, h]),
            *_head((q_ref, k_ref, v_ref, g_ref), beta_ref, h, c, nh),
            st_ref[0, 0, h])
        grads = vjp((do_ref[0, rows, :].astype(jnp.float32), dst_scr[h],
                     jnp.zeros_like(t)))
        for ref, d in zip((dq_ref, dk_ref, dv_ref, dg_ref), grads):
            ref[0, rows, :] = d
        dbeta_ref[0] = jnp.where(lane == h, grads[4], dbeta_ref[0])
        dst_scr[h] = grads[5]

    _each_head(nh, head)


def _specs(c, nh, dk, dv, nc, reverse):
    """Block specs over grid ``(B, tiles)``: a tile's rows of every
    head, ``[C * H, D]`` out of ``[B, L * H, D]`` (``[B, L, H, D]`` as
    it lies in memory: a bitcast of it), ``beta``'s
    ``[C, H]``, the tile's states in ``[B, tiles, H, Dv, Dk]`` and
    its ``T`` in ``[B, tiles, H, C, C]``."""
    at = (lambda ci: nc - 1 - ci) if reverse else (lambda ci: ci)
    return (
        pl.BlockSpec((1, c * nh, dk), lambda b, ci: (b, at(ci), 0)),
        pl.BlockSpec((1, c * nh, dv), lambda b, ci: (b, at(ci), 0)),
        pl.BlockSpec((1, c, nh), lambda b, ci: (b, at(ci), 0)),
        pl.BlockSpec((1, 1, nh, dv, dk), lambda b, ci: (b, at(ci), 0, 0, 0)),
        pl.BlockSpec((1, 1, nh, c, c), lambda b, ci: (b, at(ci), 0, 0, 0)),
    )


def _call(kernel, name, shape, c, dt, interpret, **kw):
    b, l, h, dk, dv = shape
    return pl.pallas_call(
        functools.partial(kernel, c=c, nh=h, dt=dt),
        grid=(b, l // c),
        scratch_shapes=[pltpu.VMEM((h, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name, **kw)


def _forward(q, k, v, g, beta, c, dt, interpret, save):
    b, l, h, dk = q.shape
    dv = v.shape[-1]
    nc = l // c
    qk_spec, v_spec, beta_spec, st_spec, t_spec = _specs(
        c, h, dk, dv, nc, False)
    out_shape = [jax.ShapeDtypeStruct((b, l * h, dv), jnp.float32)]
    out_specs = [v_spec]
    if save:
        out_shape += [jax.ShapeDtypeStruct((b, nc, h, dv, dk), jnp.float32),
                      jax.ShapeDtypeStruct((b, nc, h, c, c), jnp.float32)]
        out_specs += [st_spec, t_spec]
    rows = lambda a: a.reshape(b, l * h, -1)  # noqa: E731
    out = _call(
        _fwd_kernel, "kda_fwd", (b, l, h, dk, dv), c, dt, interpret,
        in_specs=[qk_spec, qk_spec, v_spec, qk_spec, beta_spec],
        out_specs=out_specs, out_shape=out_shape,
    )(rows(q), rows(k), rows(v), rows(g), beta)
    return out[0].reshape(b, l, h, dv), tuple(out[1:])


def _backward(q, k, v, g, beta, states, ts, do, c, dt, interpret):
    b, l, h, dk = q.shape
    dv = v.shape[-1]
    qk_spec, v_spec, beta_spec, st_spec, t_spec = _specs(
        c, h, dk, dv, l // c, True)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    rows = lambda a: a.reshape(b, l * h, -1)  # noqa: E731
    dq, dkk, dvv, dg, dbeta = _call(
        _bwd_kernel, "kda_bwd", (b, l, h, dk, dv), c, dt, interpret,
        in_specs=[qk_spec, qk_spec, v_spec, qk_spec, beta_spec, st_spec,
                  t_spec, v_spec],
        out_specs=[qk_spec, qk_spec, v_spec, qk_spec, beta_spec],
        out_shape=[f32(b, l * h, dk), f32(b, l * h, dk), f32(b, l * h, dv),
                   f32(b, l * h, dk), f32(b, l, h)],
    )(rows(q), rows(k), rows(v), rows(g), beta, states, ts, rows(do))
    return (dq.reshape(q.shape), dkk.reshape(k.shape), dvv.reshape(v.shape),
            dg.reshape(g.shape), dbeta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kda(q, k, v, g, beta, c, dt, interpret):
    return _forward(q, k, v, g, beta, c, dt, interpret, save=False)[0]


# What a recomputing caller should keep of a differentiated call: the
# forward kernel's three results (``jax.checkpoint`` with
# ``save_only_these_names(*REMAT_NAMES)`` then runs ``kda_fwd`` once a
# step). The operands are not named: they are cheap to make again.
REMAT_NAMES = ("kda.o", "kda.states", "kda.t")


def _kda_fwd(q, k, v, g, beta, c, dt, interpret):
    o, saved = _forward(q, k, v, g, beta, c, dt, interpret, save=True)
    o, *saved = map(checkpoint_name, (o, *saved), REMAT_NAMES)
    return o, (q, k, v, g, beta, *saved)


def _kda_bwd(c, dt, interpret, res, do):
    grads = _backward(*res, do, c, dt, interpret)
    return tuple(g.astype(a.dtype) for g, a in zip(grads, res))


_kda.defvjp(_kda_fwd, _kda_bwd)


def _step_bytes(h, dk, dv):
    """VMEM the backward kernel's grid step needs (the heavier of the
    two): its double-buffered blocks (q, k, g and their cotangents; v,
    dO, dv; the tile's states and ``T``) and the ``dS`` scratch."""
    rows = _TILE * h
    blocks = 6 * rows * dk + 3 * rows * dv + h * (dv * dk + _TILE * _TILE)
    return 4 * (2 * blocks + h * dv * dk)


def takes(q, v) -> bool:
    """Whether the kernels take these operands, from shapes alone:
    heads that are whole 128-lane slabs (the published ``head_dim``
    128) and a tile of every head that fits the VMEM limit."""
    h, dk, dv = q.shape[2], q.shape[3], v.shape[3]
    return (dk % 128 == 0 and dv % 128 == 0
            and _step_bytes(h, dk, dv) <= 3 * _VMEM_LIMIT // 4)


def kda_kernels(q, k, v, g, beta, *, compute_dtype="float32",
                interpret: bool = False):
    """The gated delta rule with a decay per channel, by the kernels.
    ``q, k, g``: ``[B, L, H, Dk]``; ``v``: ``[B, L, H, Dv]``; ``beta``:
    ``[B, L, H]``; ``g <= 0``; ``Dk, Dv`` multiples of 128
    (:func:`takes`). Returns ``o [B, L, H, Dv]`` float32,
    differentiable in all five. Any ``L``: the tail is padded to whole
    tiles of ``_TILE`` positions that write nothing."""
    l = q.shape[1]
    pad = -l % _TILE
    if pad:   # beta 0 and no decay: the state passes unchanged
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    o = _kda(q, k, v, g, beta, _TILE, jnp.dtype(compute_dtype), interpret)
    return o[:, :l]
