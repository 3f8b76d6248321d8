"""The backward of the held experts' grouped product as a Pallas TPU
kernel.

The mathematics is ``models.experts._grouped_bwd``'s: for ``y[t] = sum
over the pairs (t, j) routed here of w[t, j] * E_e(x[t])``, ``E(x) =
(silu(x wg) * (x wu)) wd``, over the tiles that ``experts.plan`` laid
out (the pairs sorted by expert, each expert's group padded to whole
tiles, ``n_tiles`` of them in use), the gradients of ``x``, of the
routing weights and of the three weights. What changes is where a
tile's rows and an expert's gradient live. The forward stays the XLA
loop: a kernel for it, built the same way, was measured on the chip no
faster than the loop, and its token-major copies cost the step more
(PERF.md).

**The grid walks the plan.** Grid ``(I // bi, M // tile)``: the
intermediate width in blocks (one where an expert's blocks fit VMEM
whole, as at Laguna's 2048 x 512) and every tile the plan could hold.
Each row's token, each tile's valid rows, the expert of each step and
``n_tiles`` are scalar-prefetched; a step whose tile is not in use
computes nothing, and the index maps of the weights repeat the last
tile's expert, so it fetches nothing either. Tiles are sorted by
expert, so an expert's weights come from HBM once a pass however many
tiles it has.

**Rows move by DMA.** The chip's DMA moves whole ``(8, 128)`` float32
tiles of a row-major ``[T, H]`` array, and a token's row is one sublane
of ``H / 128`` of them, so ``x`` and ``dy`` are handed over (and ``dx``
given back) TOKEN-MAJOR: ``[T * H / 128, 128]`` float32, a token's row
as ``H / 128`` consecutive 128-lane rows, whole tiles where ``H`` is a
multiple of 1024 (:func:`takes`). XLA lays that out around the call,
a token's row of ``x`` beside its row of ``dy``, and ``dx`` back. A
tile's rows come by one DMA a valid row, ``x`` and ``dy`` together
(padding is a group's tail and is never fetched), the next tile's while
this one computes; a strided load a 128-lane chunk makes each ``[tile,
H]`` operand of the products. What a tile adds to ``dx`` goes back by
read-add-write: the tile's rows of the float32 result are
fetched while the tile computes, its part is added in VMEM and they are
written back. The grid runs in order on the one TensorCore and a tile's
tokens are distinct (a token meets an expert once), so it is enough
that a tile's writes land before the next tile reads.

**Weight gradients stay in VMEM.** ``dwg``, ``dwu``, ``dwd`` of the
step's expert are summed in float32 scratch over the expert's
consecutive tiles and written once, when the expert changes: the
revisited-output pattern of ``jax.experimental.pallas.ops.tpu.megablox``
``tgmm``. The steps after the last tile in use visit, one each, the
held experts that have no tile, so that they get zeros. ``dw`` of the
routing weights is written a row per tile in the plan's sorted order and
put back in pair order by one scatter of unique indices (each pair has
one row; padding rows go to distinct indices past the end and are
dropped).

Precision is ``_grouped_bwd``'s: the forward is recomputed as the loop
computes it (products of compute-dtype operands accumulating in
float32, ``silu(a) * b`` rounded to the compute dtype), the backward
products take compute-dtype operands, ``dx`` and the weight gradients
are summed in float32; the weight gradients leave in the weights'
dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.dtype("float32")
_LANE = 128
# A DMA moves whole float32 tiles of 8 x 128: a token's row of H / 128
# chunks is whole tiles when H is a multiple of this.
_ROW_UNIT = 8 * _LANE
# Of a v5e's 128 MiB of VMEM; the intermediate width is cut into blocks
# whose step needs at most three quarters of it.
_VMEM_LIMIT = 100 << 20


def _step_bytes(h: int, bi: int, tile: int, size: int) -> int:
    """VMEM a grid step needs at an intermediate block of ``bi`` and
    weights of ``size`` bytes: the three weight blocks and their results
    double-buffered, their float32 sums; the rows of ``x``, ``dy`` (two
    tiles each) and ``dx`` token-major; the tile's operands and
    intermediates."""
    weights = 3 * h * bi
    return (weights * (4 * size + 4) + 5 * tile * h * 4 + tile * h * 16
            + tile * bi * 28)


def _block(h: int, i: int, tile: int, size: int) -> int | None:
    """The widest block of the intermediate width (a multiple of 128
    that divides it) whose step fits; None if none does."""
    for n in range(1, i // _LANE + 1):
        bi = i // n
        if i % n == 0 and bi % _LANE == 0 and (
                _step_bytes(h, bi, tile, size) <= 3 * _VMEM_LIMIT // 4):
            return bi
    return None


def takes(h: int, i: int, tile: int, dtype="bfloat16") -> bool:
    """Whether the kernel takes experts ``h`` wide with an intermediate
    width ``i`` at ``tile`` rows a tile and weights in ``dtype``, from
    shapes alone: a token's row of whole float32 tiles (``h`` a
    multiple of 1024: at other widths the token-major copies would
    carry padding, and at kimi's 2304 they cost its cell more than the
    kernel saves, PERF.md), an intermediate width of whole
    128-lane columns, a tile of whole 8-row groups, and a block of the
    intermediate width that fits the VMEM limit."""
    return (h % _ROW_UNIT == 0 and i % _LANE == 0 and tile % 8 == 0
            and _block(h, i, tile, jnp.dtype(dtype).itemsize) is not None)


def _dot(a, b, dims):
    """A 2-D product, float32 out; float32 operands at ``HIGHEST``."""
    return jax.lax.dot_general(
        a, b, (dims, ((), ())),
        precision=_HI if a.dtype == _F32 else None,
        preferred_element_type=jnp.float32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


class _Rows:
    """One DMA a valid row of a tile between a token-major HBM array
    and a ``[tile * hc, 128]`` VMEM buffer; a copy's completion is
    counted on ``sem`` and waited for one row at a time."""

    def __init__(self, tok_ref, tile, hc):
        self.tok, self.tile, self.hc = tok_ref, tile, hc

    def _copy(self, hbm, vmem, i, tok, sem, into_vmem):
        src = hbm.at[pl.ds(pl.multiple_of(tok * self.hc, 8), self.hc)]
        dst = vmem.at[pl.ds(pl.multiple_of(i * self.hc, 8), self.hc)]
        if not into_vmem:
            src, dst = dst, src
        return pltpu.make_async_copy(src, dst, sem)

    def start(self, hbm, vmem, t, n, sem, into_vmem=True):
        def body(i, c):
            tok = self.tok[t * self.tile + i]
            self._copy(hbm, vmem, i, tok, sem, into_vmem).start()
            return c

        jax.lax.fori_loop(0, n, body, 0)

    def wait(self, hbm, vmem, n, sem, into_vmem=True):
        def body(i, c):
            self._copy(hbm, vmem, 0, 0, sem, into_vmem).wait()
            return c

        jax.lax.fori_loop(0, n, body, 0)


def _operand(buf, tile, per, first, h, n):
    """A tile's ``[tile, h]`` rows out of its token-major buffer, ``per``
    128-lane rows a token, the operand's from row ``first`` on (a
    strided load a 128-lane chunk); the rows past the ``n`` valid ones
    zero."""
    x = jnp.concatenate([buf[pl.ds(first + c, tile, stride=per), :]
                         for c in range(h // _LANE)], axis=1)
    valid = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) < n
    return jnp.where(valid, x, 0.0)


def _accumulate(buf, part, tile, hc):
    """Add ``part [tile, h]`` to the tile's token-major rows in VMEM."""
    for c in range(part.shape[1] // _LANE):
        at = pl.ds(c, tile, stride=hc)
        buf[at, :] = buf[at, :] + part[:, c * _LANE:(c + 1) * _LANE]


def _column(row):
    """``[1, tile]`` -> ``[tile, 1]`` (a transpose of whole tiles)."""
    return jnp.transpose(jnp.broadcast_to(row, (_LANE, row.shape[1])))[:, :1]


def _row(col):
    """``[tile, 1]`` -> ``[1, tile]``."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], _LANE)))[:1]


def _bwd_kernel(tok_ref, nval_ref, wexp_ref, dwexp_ref, nt_ref, xdy_hbm,
                w_ref, wg_ref, wu_ref, wd_ref, _, dx_hbm, dwf_ref, dwg_ref,
                dwu_ref, dwd_ref, xdybuf, dxbuf, acc_g, acc_u, acc_d, sems, *,
                tile, hc, h, steps):
    """Grid ``(intermediate blocks, tiles)``, both sequential; the
    tile's forward is recomputed. A token's row of ``x`` and of ``dy``
    come together, ``2 hc`` 128-lane rows by one DMA. ``dwg``, ``dwu``,
    ``dwd`` blocks follow ``dwexp`` (the tile's expert, then the experts
    with no tile, then the last again): summed in the ``acc_*`` scratch
    over a run of one expert, written at its end. ``sems``: the rows of
    ``x`` and ``dy`` (two buffers), reads and writes of ``dx``."""
    del wexp_ref
    t, n = pl.program_id(1), nt_ref[0]
    slot = t % 2
    both, rows = _Rows(tok_ref, tile, 2 * hc), _Rows(tok_ref, tile, hc)
    cdt = wg_ref.dtype
    e = dwexp_ref[t]

    @pl.when((t == 0) | (dwexp_ref[jnp.maximum(t - 1, 0)] != e))
    def _():
        for acc in (acc_g, acc_u, acc_d):
            acc[...] = jnp.zeros(acc.shape, jnp.float32)

    @pl.when((t == 0) & (n > 0))
    def _():
        both.start(xdy_hbm, xdybuf.at[0], 0, nval_ref[0], sems.at[0])

    @pl.when(t < n)
    def _():
        nv = nval_ref[t]
        both.wait(xdy_hbm, xdybuf.at[slot], nv, sems.at[slot])

        @pl.when(t + 1 < n)
        def _():
            both.start(xdy_hbm, xdybuf.at[1 - slot], t + 1, nval_ref[t + 1],
                       sems.at[1 - slot])

        @pl.when(t > 0)
        def _():
            rows.wait(dx_hbm, dxbuf, nval_ref[t - 1], sems.at[3], False)

        rows.start(dx_hbm, dxbuf, t, nv, sems.at[2])
        xs = _operand(xdybuf.at[slot], tile, 2 * hc, 0, h, nv).astype(cdt)
        dys = _operand(xdybuf.at[slot], tile, 2 * hc, hc, h, nv)
        wg, wu, wd = wg_ref[0], wu_ref[0], wd_ref[0]
        a, b = _dot(xs, wg, _NN), _dot(xs, wu, _NN)
        hh = (jax.nn.silu(a) * b).astype(cdt)
        o = _dot(hh, wd, _NN)
        dwf_ref[0, 0] = _row(jnp.sum(o * dys, axis=1, keepdims=True))
        do = (dys * _column(w_ref[0])).astype(cdt)
        dh = _dot(do, wd, _NT)
        sig = jax.nn.sigmoid(a)
        da = (dh * b * sig * (1.0 + a * (1.0 - sig))).astype(cdt)
        db = (dh * a * sig).astype(cdt)
        dxt = _dot(da, wg, _NT) + _dot(db, wu, _NT)
        acc_g[...] += _dot(xs, da, _TN)
        acc_u[...] += _dot(xs, db, _TN)
        acc_d[...] += _dot(hh, do, _TN)
        rows.wait(dx_hbm, dxbuf, nv, sems.at[2])
        _accumulate(dxbuf, dxt, tile, hc)
        rows.start(dx_hbm, dxbuf, t, nv, sems.at[3], into_vmem=False)

        @pl.when(t == n - 1)
        def _():
            rows.wait(dx_hbm, dxbuf, nv, sems.at[3], False)

    @pl.when((t == steps - 1) | (dwexp_ref[jnp.minimum(t + 1, steps - 1)] != e))
    def _():
        for ref, acc in ((dwg_ref, acc_g), (dwu_ref, acc_u), (dwd_ref, acc_d)):
            ref[0] = acc[...].astype(ref.dtype)


def _weight_specs(h, bi, table):
    """The tile's expert's blocks of ``wg``, ``wu`` ``[n, H, I]`` and
    ``wd [n, I, H]``, the expert read from the prefetched ``table``."""
    at = lambda j, t, *s: s[table][t]  # noqa: E731
    return [pl.BlockSpec((1, h, bi), lambda j, t, *s: (at(j, t, *s), 0, j)),
            pl.BlockSpec((1, h, bi), lambda j, t, *s: (at(j, t, *s), 0, j)),
            pl.BlockSpec((1, bi, h), lambda j, t, *s: (at(j, t, *s), j, 0))]


def _live(t, nt_ref):
    """Tile ``t``, or the last tile in use after it (0 with none)."""
    return jnp.maximum(jnp.minimum(t, nt_ref[0] - 1), 0)


# Built once for each set of shapes and statics, so that a model's
# expert layers share one callable and the kernel is not traced again
# a layer (that cost the delta-rule kernels 10-17% of the set-up).
@functools.lru_cache(maxsize=None)
def _backward_call(t, g, tile, h, bi, i, count, cdt, interpret):
    hc = h // _LANE
    nj = i // bi
    nt = lambda s: s[-1]  # noqa: E731
    anyspace = pl.BlockSpec(memory_space=pl.ANY)
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(nj, g),
        in_specs=[anyspace,
                  pl.BlockSpec((1, 1, tile), lambda j, t, *s: (
                      _live(t, nt(s)), 0, 0)),
                  *_weight_specs(h, bi, 2),
                  anyspace],
        out_specs=[anyspace,
                   pl.BlockSpec((1, 1, 1, tile), lambda j, t, *s: (
                       j, _live(t, nt(s)), 0, 0)),
                   *_weight_specs(h, bi, 3)],
        scratch_shapes=[pltpu.VMEM((2, tile * 2 * hc, _LANE), jnp.float32),
                        pltpu.VMEM((tile * hc, _LANE), jnp.float32),
                        pltpu.VMEM((h, bi), jnp.float32),
                        pltpu.VMEM((h, bi), jnp.float32),
                        pltpu.VMEM((bi, h), jnp.float32),
                        pltpu.SemaphoreType.DMA((4,))])
    return pl.pallas_call(
        functools.partial(_bwd_kernel, tile=tile, hc=hc, h=h, steps=g),
        grid_spec=grid,
        out_shape=[jax.ShapeDtypeStruct((t * hc, _LANE), jnp.float32),
                   jax.ShapeDtypeStruct((nj, g, 1, tile), jnp.float32),
                   jax.ShapeDtypeStruct((count, h, i), cdt),
                   jax.ShapeDtypeStruct((count, h, i), cdt),
                   jax.ShapeDtypeStruct((count, i, h), cdt)],
        input_output_aliases={10: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="grouped_ffn_bwd")


def _tables(wflat, rows, tile_expert, n_tiles, tile, k, count):
    """What the kernel prefetches, from the plan: the token of every row
    (0 for padding, never fetched), the valid rows of each tile (a
    prefix: padding is a group's tail), the expert whose weights each
    grid step holds (after the last tile in use, that tile's), the
    expert whose weight gradients it writes (the tile's, then each held
    expert with no tile, then the last again) and ``n_tiles``; and each
    row's routing weight, ``[tiles, 1, tile]``."""
    g = rows.size // tile
    step = jnp.arange(g)
    live = step < n_tiles
    valid = rows >= 0
    tok = jnp.where(valid, rows // k, 0).astype(jnp.int32)
    nval = jnp.sum(valid.reshape(g, tile), axis=1).astype(jnp.int32)
    # tiles are sorted by expert: the last in use has the largest
    last = jnp.maximum(jnp.max(jnp.where(live, tile_expert, 0)), 0)
    wexp = jnp.where(live, tile_expert, last).astype(jnp.int32)
    has = jnp.any((jnp.arange(count)[:, None] == tile_expert[None, :])
                  & live[None, :], axis=1)
    # the j-th held expert with no tile: the experts before which fewer
    # than j + 1 have none, counted
    none_before = jnp.cumsum(~has)
    n_empty = none_before[-1]
    j = jnp.clip(step - n_tiles, 0, jnp.maximum(n_empty - 1, 0))
    after = jnp.sum(none_before[None, :] <= j[:, None], axis=1)
    dwexp = jnp.where(live, tile_expert,
                      jnp.where(n_empty > 0, after, last)).astype(jnp.int32)
    nt = jnp.reshape(n_tiles, (1,)).astype(jnp.int32)
    wrow = jnp.where(valid, wflat[jnp.maximum(rows, 0)], 0.0)
    return (tok, nval, wexp, dwexp, nt), wrow.reshape(g, 1, tile)


def grouped_ffn_bwd(x, dy, wflat, wg, wu, wd, rows, tile_expert, n_tiles, *,
                    tile: int, k: int, interpret: bool = False):
    """The backward of ``models.experts._grouped`` by the kernel, from
    ``x [T, H]`` (the layer's input in any float dtype; its rows are
    rounded to the weights' dtype for the products), the cotangent
    ``dy [T, H]`` of its result, ``wflat [T * k]``, the held experts'
    ``wg, wu [n, H, I]`` and ``wd [n, I, H]`` in the compute dtype, and
    the plan (``rows``, ``tile_expert``, ``n_tiles``): ``dx [T, H]``
    float32, ``dwflat [T * k]`` float32 and ``dwg``, ``dwu``, ``dwd`` in
    the weights' dtype. :func:`takes` says which widths it takes."""
    count, h, i = wg.shape
    t = x.shape[0]
    g = rows.size // tile
    tables, wrow = _tables(wflat, rows, tile_expert, n_tiles, tile, k, count)
    # token-major: a token's row of x, then its row of dy, in 128-lane
    # rows; one DMA fetches both
    rows3 = lambda a: a.astype(_F32).reshape(t, h // _LANE, _LANE)  # noqa: E731
    xdy = jnp.concatenate([rows3(x), rows3(dy)], axis=1).reshape(-1, _LANE)
    dxr, dwf, dwg, dwu, dwd = _backward_call(
        t, g, tile, h, _block(h, i, tile, wg.dtype.itemsize), i, count,
        jnp.dtype(wg.dtype), interpret)(
        *tables, xdy, wrow, wg, wu, wd,
        jnp.zeros((t * h // _LANE, _LANE), jnp.float32))
    # sorted rows -> pairs: each pair has one row; padding rows go to
    # distinct indices past the end and are dropped
    at = jnp.where(rows >= 0, rows, wflat.size + jnp.arange(rows.size))
    dwflat = jnp.zeros(wflat.shape, jnp.float32).at[at].set(
        jnp.sum(dwf, axis=0).reshape(rows.size), mode="drop",
        unique_indices=True)
    return dxr.reshape(t, h), dwflat, dwg, dwu, dwd
