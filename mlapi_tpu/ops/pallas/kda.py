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

**Layout.** Operands and results are the projections' own ``[B, L,
H * D]`` (``beta``: ``[B, L, H]``). A grid step is one tile of every
head, a ``[C, H * D]`` lane slab that lies in memory as one piece, and
head ``h``'s ``[C, D]`` is lanes ``h * D .. (h + 1) * D`` of it: whole
128-lane columns of the block, read and written in place. What needs a
head's channels TOGETHER in the layer around the rule happens here too,
on the tile that is in VMEM anyway (:func:`_normed_tile`): q's and k's
L2 normalisation before the rule, the RMS norm of ``o`` times the
output gate after it. That is why lane slabs no longer lose: while those
reductions were XLA's, XLA put a 4-D layout somewhere around them and
paid for the change whichever layout the kernels took (lane slabs ran
the kernels 5% faster and the train step 1.2% slower: PERF.md, PR 30);
with them in here everything XLA sees between the projections and the
output projection is elementwise on ``[B, L, H * D]`` and nothing is
laid out (PERF.md, PR 35). The 4-D entry :func:`kda_kernels` (the bare
rule, what the oracle tests hold against the literal recurrence)
reshapes to slabs and runs the same kernels without that head and tail.

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
is recomputed from the call's operands and what the forward kernel
saved (the state at the tile's start, and ``T``, so the elimination is
not run twice; the output before its norm exists in VMEM only), and its
derivative is ``jax.vjp`` of the SAME per-tile function the forward
kernel traces. Matrix products are ``custom_vjp``
so that the backward products take their operands in the compute dtype
too, as XLA's do on the chip.

Precision is ``kda_chunked``'s: products take ``compute_dtype``
operands and accumulate in float32; ``G``, every ``exp``, the solve and
the carried state are float32 (float32 products contract at
``HIGHEST``), and so are both normalisations and the gate.
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
# operand at 64 x 32 x 128, the tile's states 2 MB: 29 MB backward),
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


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _normed_tile(q, k, v, g, beta, gate, scale, st, *, eps, **kw):
    """:func:`_tile` inside the layer's per-head normalisations, the
    formulas of ``models.kimi_linear`` (``kda_plain``): raw ``q, k`` are
    L2-normalised (q scaled ``Dk ** -0.5``) on the way in, and what
    leaves is ``y = RMSNorm(o) * scale * gate`` (``gate [C, Dv]``,
    ``scale [1, Dv]``). All float32; ``o`` itself never leaves VMEM."""
    o, st, T = _tile(_l2(q) * q.shape[-1] ** -0.5, _l2(k), v, g, beta, st,
                     **kw)
    inv = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * inv * scale * gate, st, T


def _tile_fn(eps, **kw):
    """The per-tile function of a call: the bare rule (five operands
    and the state), or with ``eps`` the normed one (seven)."""
    if eps is None:
        return functools.partial(_tile, **kw)
    return functools.partial(_normed_tile, eps=eps, **kw)


def _lanes(ref, h, nh):
    """Head ``h``'s lanes of a ``[.., C, H * D]`` block."""
    d = ref.shape[-1] // nh
    return pl.ds(pl.multiple_of(h * d, 128), d)


def _head(ins, h, c, nh):
    """Head ``h``'s operands out of a tile's blocks, float32, in the
    per-tile function's order: its lanes of the ``[C, H * D]`` slabs
    (``q, k, v, g``), lane ``h`` of ``beta``'s ``[C, H]``, and for the
    normed call its lanes of ``gate`` and the ``[1, Dv]`` scale."""
    q, k, v, g, beta_ref, *tail = ins
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, nh), 1)
    beta = jnp.sum(jnp.where(lane == h, beta_ref[0].astype(jnp.float32), 0.0),
                   axis=1, keepdims=True)

    def wide(r):
        return r[0, :, _lanes(r, h, nh)].astype(jnp.float32)

    out = (wide(q), wide(k), wide(v), wide(g), beta)
    if tail:
        out += (wide(tail[0]), tail[1][...].astype(jnp.float32))
    return out


def _each_head(nh, unroll, body):
    """``body(h)`` for every head: a loop over ``unroll`` heads at a
    time, whose independent chains the scheduler interleaves."""
    u = unroll if nh % unroll == 0 else 1

    def step(i, carry):
        for j in range(u):
            body(i * u + j)
        return carry

    jax.lax.fori_loop(0, nh // u, step, 0)


def _fwd_kernel(*refs, n, c, nh, dt, eps, unroll):
    """Grid ``(B, tiles)``, the tile axis sequential; every head of
    the tile in one step. ``refs``: the call's ``n`` operands (five, or
    seven with ``eps``), the result, where the backward will want them
    the blocks of the state at the tile's start and of the tile's
    ``T``, and the state scratch ``[H, Dv, Dk]``."""
    ins, (y_ref, *saved), st_scr = refs[:n], refs[n:-1], refs[-1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        st_scr[...] = jnp.zeros(st_scr.shape, jnp.float32)

    tile = _tile_fn(eps, dt=dt, levels=_levels(c))

    def head(h):
        st = st_scr[h]
        y, st_next, t = tile(*_head(ins, h, c, nh), st)
        if saved:
            saved[0][0, 0, h] = st
            saved[1][0, 0, h] = t
        y_ref[0, :, _lanes(y_ref, h, nh)] = y.astype(y_ref.dtype)
        st_scr[h] = st_next

    _each_head(nh, unroll, head)


def _bwd_kernel(*refs, n, c, nh, dt, eps, unroll):
    """The same grid with the tile axis reversed by the index maps:
    ``dst_scr`` carries the state's cotangent back through the tiles.
    ``refs``: the ``n`` operands, the saved states and ``T``, the
    result's cotangent; then a cotangent for every operand (the scale's
    summed over heads and tiles in its one revisited block)."""
    ins, (st_ref, t_ref, dy_ref) = refs[:n], refs[n:n + 3]
    outs, dst_scr = refs[n + 3:-1], refs[-1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        dst_scr[...] = jnp.zeros(dst_scr.shape, jnp.float32)
        for ref in outs[6:]:
            ref[...] = jnp.zeros(ref.shape, jnp.float32)

    lane = jax.lax.broadcasted_iota(jnp.int32, (c, nh), 1)
    tile = _tile_fn(eps, dt=dt, levels=_levels(c))

    def head(h):
        (_, _, t), vjp = jax.vjp(
            functools.partial(tile, t=t_ref[0, 0, h]),
            *_head(ins, h, c, nh), st_ref[0, 0, h])
        *grads, dst = vjp((
            dy_ref[0, :, _lanes(dy_ref, h, nh)].astype(jnp.float32),
            dst_scr[h], jnp.zeros_like(t)))
        for i, (ref, d) in enumerate(zip(outs, grads)):
            if i == 4:      # beta: this head's lane
                ref[0] = jnp.where(lane == h, d, ref[0])
            elif i == 6:    # the scale: every head's and tile's, summed
                ref[0] += d
            else:
                ref[0, :, _lanes(ref, h, nh)] = d
        dst_scr[h] = dst

    _each_head(nh, unroll, head)


def _spec(nc, reverse, block):
    """A tile's block of an array whose second axis is the tiles':
    ``[B, L, ..]`` in blocks of ``C`` positions, or the saved ``[B,
    tiles, H, ..]`` a tile at a time; the backward walks them in
    reverse."""
    at = (lambda ci: nc - 1 - ci) if reverse else (lambda ci: ci)
    zeros = (0,) * (len(block) - 2)
    return pl.BlockSpec(block, lambda b, ci: (b, at(ci), *zeros))


def _slabs(spec, shapes, c):
    """Specs of a tile of every head of the operands that have
    positions (``q, k, v, g``, ``beta``'s ``[C, H]``, ``gate``), or of
    their cotangents."""
    return [spec((1, c, s[-1])) for s in shapes[:6]]


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _call(kernel, name, shapes, spec, rest_specs, out_specs, out_shape, *,
          c, dt, eps, interpret, unroll):
    """One ``pallas_call`` over grid ``(B, tiles)`` on operands of
    ``shapes`` (``q, k, v, g [B, L, H * D]``, ``beta [B, L, H]``, and
    with ``eps`` ``gate`` and the scale as ``[1, Dv]``, whole every
    step); ``rest_specs`` are the further inputs'."""
    b, l, h = shapes[4]
    dk, dv = shapes[0][-1] // h, shapes[2][-1] // h
    whole = [pl.BlockSpec(s, lambda b, ci: (0, 0)) for s in shapes[6:]]
    return pl.pallas_call(
        functools.partial(kernel, n=len(shapes), c=c, nh=h, dt=dt, eps=eps,
                          unroll=unroll),
        grid=(b, l // c),
        in_specs=_slabs(spec, shapes, c) + whole + rest_specs,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((h, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name)


# The two calls are BUILT once for each set of shapes and statics: a
# model's layers then share one jitted callable, so its kernel body is
# traced once a program and not once a layer (the elimination unrolls
# to thousands of operations: 0.3 to 1 s of tracing a call).
@functools.lru_cache(maxsize=None)
def _forward_call(shapes, save, **kw):
    b, l, h = shapes[4]
    dk, dv = shapes[0][-1] // h, shapes[2][-1] // h
    c = kw["c"]
    nc = l // c
    spec = functools.partial(_spec, nc, False)
    out = [(b, l, h * dv)]
    if save:   # the state at every tile's start, and every tile's T
        out += [(b, nc, h, dv, dk), (b, nc, h, c, c)]
    return _call(
        _fwd_kernel, "kda_fwd", shapes, spec, [],
        [spec((1, c, h * dv))] + [spec((1, 1) + s[2:]) for s in out[1:]],
        [_f32(*s) for s in out], **kw)


@functools.lru_cache(maxsize=None)
def _backward_call(shapes, **kw):
    """Takes the operands, the saved states and ``T`` and the result's
    cotangent; a cotangent for every operand, the scale's a row a
    batch row (summed over heads and tiles)."""
    b, l, h = shapes[4]
    dk, dv = shapes[0][-1] // h, shapes[2][-1] // h
    c = kw["c"]
    spec = functools.partial(_spec, l // c, True)
    out_specs = _slabs(spec, shapes, c)
    out_shape = [_f32(*s) for s in shapes[:6]]
    for s in shapes[6:]:
        out_specs.append(pl.BlockSpec((1, *s), lambda b, ci: (b, 0, 0)))
        out_shape.append(_f32(b, *s))
    return _call(
        _bwd_kernel, "kda_bwd", shapes, spec,
        [spec((1, 1, h, dv, dk)), spec((1, 1, h, c, c)),
         spec((1, c, h * dv))],
        out_specs, out_shape, **kw)


def _statics(ops, c, dt, eps, interpret):
    return tuple(a.shape for a in ops), dict(
        c=c, dt=dt, eps=eps, interpret=interpret, unroll=_UNROLL)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _kda(ops, c, dt, eps, interpret):
    shapes, kw = _statics(ops, c, dt, eps, interpret)
    return _forward_call(shapes, False, **kw)(*ops)[0]


# What a recomputing caller should keep of a differentiated call: the
# forward kernel's three results (``jax.checkpoint`` with
# ``save_only_these_names(*REMAT_NAMES)`` then runs ``kda_fwd`` once a
# step). The operands are not named: they are cheap to make again.
REMAT_NAMES = ("kda.o", "kda.states", "kda.t")


def _kda_fwd(ops, c, dt, eps, interpret):
    shapes, kw = _statics(ops, c, dt, eps, interpret)
    y, *saved = map(checkpoint_name, _forward_call(shapes, True, **kw)(*ops),
                    REMAT_NAMES)
    return y, (ops, *saved)


def _kda_bwd(c, dt, eps, interpret, res, dy):
    ops, states, ts = res
    shapes, kw = _statics(ops, c, dt, eps, interpret)
    grads = list(_backward_call(shapes, **kw)(*ops, states, ts, dy))
    grads = grads[:6] + [jnp.sum(g, axis=0) for g in grads[6:]]
    return (tuple(g.astype(a.dtype) for g, a in zip(grads, ops)),)


_kda.defvjp(_kda_fwd, _kda_bwd)


def _step_bytes(h, dk, dv):
    """VMEM the backward kernel's grid step needs (the heavier of the
    two): its double-buffered blocks (q, k, g and their cotangents; v,
    gate, dy and the first two's cotangents; the tile's states and
    ``T``) and the ``dS`` scratch."""
    rows = _TILE * h
    blocks = 6 * rows * dk + 5 * rows * dv + h * (dv * dk + _TILE * _TILE)
    return 4 * (2 * blocks + h * dv * dk)


def takes(h: int, dk: int, dv: int) -> bool:
    """Whether the kernels take a call of ``h`` heads ``dk`` / ``dv``
    wide, from shapes alone: heads that are whole 128-lane slabs (the
    published ``head_dim`` 128) and a tile of every head that fits the
    VMEM limit."""
    return (dk % 128 == 0 and dv % 128 == 0
            and _step_bytes(h, dk, dv) <= 3 * _VMEM_LIMIT // 4)


def _run(ops, eps, compute_dtype, interpret):
    """The kernels on slab operands of any ``L``: the tail is padded to
    whole tiles of ``_TILE`` positions that write nothing (beta 0 and no
    decay: the state passes unchanged)."""
    l = ops[0].shape[1]
    pad = -l % _TILE
    if pad:
        ops = tuple(
            jnp.pad(a, ((0, 0), (0, pad), (0, 0))) if a.ndim == 3 else a
            for a in ops)
    y = _kda(ops, _TILE, jnp.dtype(compute_dtype), eps, interpret)
    return y[:, :l]


def kda_layer(q, k, v, g, beta, gate, o_scale, *, eps: float,
              compute_dtype="float32", interpret: bool = False):
    """A KDA layer between its projections, by the kernels: the gated
    delta rule on L2-normalised ``q`` (scaled ``Dk ** -0.5``) and ``k``,
    its output RMS-normed per head (``o_scale [Dv]``, ``eps``) and
    gated. ``q, k, g``: ``[B, L, H * Dk]`` (``q, k`` raw, ``g <= 0``);
    ``v, gate``: ``[B, L, H * Dv]``; ``beta``: ``[B, L, H]``; ``Dk, Dv``
    multiples of 128 (:func:`takes`). Returns ``y [B, L, H * Dv]``
    float32, differentiable in all seven. Any ``L``."""
    return _run((q, k, v, g, beta, gate, o_scale.reshape(1, -1)), eps,
                compute_dtype, interpret)


def kda_kernels(q, k, v, g, beta, *, compute_dtype="float32",
                interpret: bool = False):
    """The bare gated delta rule with a decay per channel, by the same
    kernels (no normalisation, no gate). ``q, k, g``: ``[B, L, H,
    Dk]``; ``v``: ``[B, L, H, Dv]``; ``beta``: ``[B, L, H]``; ``g <=
    0``. Returns ``o [B, L, H, Dv]`` float32, differentiable in all
    five. Any ``L``. What the oracle tests hold against the literal
    recurrence; the reshapes to and from slabs are XLA's to lay out."""
    b, l, h, _ = q.shape
    slab = lambda a: a.reshape(b, l, -1)  # noqa: E731
    o = _run((slab(q), slab(k), slab(v), slab(g), beta), None,
             compute_dtype, interpret)
    return o.reshape(b, l, h, -1)
