"""The sparse-expert FFN that the decoders with routed experts share
(``kimi_linear_lm``, ``laguna_lm``): routing, the held experts' grouped
product and the shared expert.

:func:`moe`: a sigmoid router over ALL ``router`` outputs in float32,
the top ``k`` of the scores (of ``s + b`` where the layer has a
selection bias ``router_bias``, a leaf with no gradient), weights
renormalised over the chosen and scaled by ``scale``, applied to the
experts' OUTPUTS, plus the shared expert. ``held = (first, count)``
makes this the layer expert parallelism needs: parameters exist for the
held experts only, the router keeps its published width, and the layer
adds its own experts' part; what the absent experts would add is left
out and the partial sum goes on (docs/DESIGN.md section 30). No token
is dropped: the pairs routed here are sorted by expert into tiles of
``tile`` rows (:func:`plan`) and only the tiles IN USE are computed, so
the work grows with the pairs here while every shape stays static
(:func:`grouped_ffn`: a loop whose trip count is the number of tiles in
use). Where ``ops/pallas/grouped_ffn.py`` ``takes`` the widths, its
Pallas kernel, which walks the plan's tiles, runs the backward in place
of the loop's, which stays as its reference (``moe.calls_traced`` /
``moe.calls_kernel`` in ``utils.metrics.REGISTRY`` say once a trace
which was chosen).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from mlapi_tpu.ops.pallas import grouped_ffn as kernels
from mlapi_tpu.utils.metrics import REGISTRY
from mlapi_tpu.utils.platform import pallas_interpret

_HI = jax.lax.Precision.HIGHEST
# What moe() names for a recomputing block to keep: the router's scores
# and the routing plan (a HIGHEST product, a top_k over every expert, a
# stable sort and a scatter of every pair; none of it differentiated but
# the sigmoid, 9 MB a layer at the published widths).
ROUTE_NAMES = ("moe.s", "moe.idx", "moe.rows", "moe.tile_expert",
               "moe.n_tiles", "moe.counts")


def mm(x, w, cdt):
    """``x @ w`` with operands in the compute dtype, float32 out."""
    return jnp.dot(x.astype(cdt), w.astype(cdt),
                   preferred_element_type=jnp.float32)


def ffn(p, x, cdt):
    """SwiGLU: a dense MLP, or the shared expert."""
    h = jax.nn.silu(mm(x, p["gate"], cdt)) * mm(x, p["up"], cdt)
    return mm(h, p["down"], cdt)


def plan(idx, first: int, count: int, tile: int):
    """Sort the (token, choice) pairs routed to experts ``first ..
    first + count - 1`` by expert, each expert's group padded to whole
    tiles. Returns ``rows`` (``[M]``: the pair a padded row holds, -1
    for padding), ``tile_expert`` (``[M // tile]``), ``n_tiles`` (tiles
    in use) and ``counts`` (pairs a held expert). ``M`` is static: all
    pairs plus a tile's padding an expert."""
    p = idx.size
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(key, stable=True)
    skey = key[order]
    counts = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0)
    padded = -(-counts // tile) * tile
    ends = jnp.cumsum(padded)
    slot = jnp.minimum(skey, count - 1)
    rank = jnp.arange(p) - (jnp.cumsum(counts) - counts)[slot]
    m = -(-(p + count * tile) // tile) * tile
    dest = jnp.where(skey < count, (ends - padded)[slot] + rank, m)
    rows = jnp.full((m,), -1, jnp.int32).at[dest].set(
        order.astype(jnp.int32), mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(m // tile) * tile, side="right"),
        count - 1,
    ).astype(jnp.int32)
    return rows, tile_expert, ends[-1] // tile, counts


def _tile_rows(rows, wflat, t, tile, k):
    sl = jax.lax.dynamic_slice_in_dim(rows, t * tile, tile)
    valid = sl >= 0
    pair = jnp.where(valid, sl, 0)
    return valid, pair, pair // k, jnp.where(valid, wflat[pair], 0.0)


def _expert_tile(xs, wg, wu, wd, e):
    pick = lambda w: jax.lax.dynamic_index_in_dim(w, e, 0, False)  # noqa: E731
    wg, wu, wd = pick(wg), pick(wu), pick(wd)
    f32 = dict(preferred_element_type=jnp.float32)
    a, b = jnp.dot(xs, wg, **f32), jnp.dot(xs, wu, **f32)
    h = (jax.nn.silu(a) * b).astype(xs.dtype)
    return (wg, wu, wd), (a, b, h), jnp.dot(h, wd, **f32)


def _grouped(x, wflat, wg, wu, wd, rows, tile_expert, n_tiles, tile, k,
             kernel):
    """``y[t] = sum over the pairs (t, j) in rows of wflat[pair] *
    E_e(x[t])``, ``E(x) = (silu(x wg) * (x wu)) wd``: the held experts'
    part of the layer. ``x [T, H]`` and ``wg, wu [n, H, I]``, ``wd [n,
    I, H]`` in the compute dtype; ``wflat [T * k]`` float32. The loop
    runs ``n_tiles`` tiles (a value, not a shape): the work follows the
    pairs that are here. ``kernel``: the backward by the Pallas kernel
    of ``ops/pallas/grouped_ffn.py`` (the forward is this loop either
    way)."""
    def body(t, y):
        _, _, tok, wt = _tile_rows(rows, wflat, t, tile, k)
        _, _, o = _expert_tile(x[tok], wg, wu, wd, tile_expert[t])
        return y.at[tok].add(o * wt[:, None])

    return jax.lax.fori_loop(
        0, n_tiles, body, jnp.zeros(x.shape, jnp.float32))


grouped_ffn = jax.custom_vjp(_grouped, nondiff_argnums=(8, 9, 10))


def _grouped_fwd(x, wflat, wg, wu, wd, rows, tile_expert, n_tiles, tile, k,
                 kernel):
    y = _grouped(x, wflat, wg, wu, wd, rows, tile_expert, n_tiles, tile, k,
                 kernel)
    return y, (x, wflat, wg, wu, wd, rows, tile_expert, n_tiles)


def _grouped_bwd(tile, k, kernel, res, dy):
    x, wflat, wg, wu, wd, rows, tile_expert, n_tiles = res
    cdt = x.dtype
    if kernel:
        dx, dwf, dwg, dwu, dwd = kernels.grouped_ffn_bwd(
            x, dy, wflat, wg, wu, wd, rows, tile_expert, n_tiles, tile=tile,
            k=k, interpret=pallas_interpret())
        return (dx.astype(cdt), dwf, dwg, dwu, dwd, None, None, None)
    f32 = dict(preferred_element_type=jnp.float32)

    def body(t, carry):
        dx, dwf, dwg, dwu, dwd = carry
        valid, pair, tok, wt = _tile_rows(rows, wflat, t, tile, k)
        e = tile_expert[t]
        xs = x[tok]
        (wg_e, wu_e, wd_e), (a, b, h), o = _expert_tile(xs, wg, wu, wd, e)
        dys = dy[tok]
        dwf = dwf.at[jnp.where(valid, pair, wflat.size)].add(
            jnp.sum(o * dys, axis=-1), mode="drop")
        do = (dys * wt[:, None]).astype(cdt)
        dh = jnp.dot(do, wd_e.T, **f32)
        sig = jax.nn.sigmoid(a)
        da = (dh * b * sig * (1.0 + a * (1.0 - sig))).astype(cdt)
        db = (dh * a * sig).astype(cdt)
        dx = dx.at[tok].add(
            jnp.dot(da, wg_e.T, **f32) + jnp.dot(db, wu_e.T, **f32))
        return (
            dx, dwf,
            dwg.at[e].add(jnp.dot(xs.T, da, **f32)),
            dwu.at[e].add(jnp.dot(xs.T, db, **f32)),
            dwd.at[e].add(jnp.dot(h.T, do, **f32)),
        )

    zeros = lambda a: jnp.zeros(a.shape, jnp.float32)  # noqa: E731
    dx, dwf, dwg, dwu, dwd = jax.lax.fori_loop(
        0, n_tiles, body,
        (zeros(x), zeros(wflat), zeros(wg), zeros(wu), zeros(wd)))
    return (dx.astype(cdt), dwf, dwg.astype(wg.dtype), dwu.astype(wu.dtype),
            dwd.astype(wd.dtype), None, None, None)


grouped_ffn.defvjp(_grouped_fwd, _grouped_bwd)


def moe(p, x, *, k: int, held: tuple, tile: int, scale: float,
        compute_dtype):
    """The held experts' part plus the shared expert of ``x [B, L, H]``,
    and the layer's ``(pairs here, fullest held expert's pairs, tiles
    the grouped product runs)``.
    ``p``: ``router [H, E]``, ``experts`` (``gate``, ``up`` ``[n, H,
    I]``, ``down [n, I, H]`` of the ``held = (first, n)`` experts),
    ``shared`` (a SwiGLU), and ``router_bias [E]`` where the family
    selects by ``s + b``."""
    cdt = jnp.dtype(compute_dtype)
    b, l, hid = x.shape
    x2 = x.reshape(b * l, hid)
    first, count = held
    with jax.named_scope("moe.route"):
        s = jax.nn.sigmoid(jnp.dot(
            x2, p["router"].astype(jnp.float32), precision=_HI))
        pick = s
        if "router_bias" in p:
            pick = s + jax.lax.stop_gradient(p["router_bias"])
        _, idx = jax.lax.top_k(pick, k)
        s, idx, rows, tile_expert, n_tiles, counts = map(
            checkpoint_name, (s, idx, *plan(idx, first, count, tile)),
            ROUTE_NAMES)
        chosen = jnp.take_along_axis(s, idx, axis=1)
        w = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scale
    with jax.named_scope("moe.experts"):
        e = p["experts"]
        REGISTRY.counter("moe.calls_traced").inc()
        kernel = kernels.takes(hid, e["gate"].shape[-1], tile, cdt)
        if kernel:
            REGISTRY.counter("moe.calls_kernel").inc()
        y = grouped_ffn(
            x2.astype(cdt), w.reshape(-1), e["gate"].astype(cdt),
            e["up"].astype(cdt), e["down"].astype(cdt), rows,
            tile_expert, n_tiles, tile, k, kernel)
    with jax.named_scope("moe.shared"):
        y = y.reshape(b, l, hid) + ffn(p["shared"], x, cdt)
    return y, (jnp.sum(counts), jnp.max(counts), n_tiles)


def load_stats(loads, count: int, routed: int, tile: int) -> dict:
    """A step's expert load as device scalars, from every layer's
    ``(pairs here, fullest held expert's pairs, tiles run)`` (a dense
    layer's is ``(0, 0, 0)``): ``moe.pairs_routed`` (``routed``: tokens
    x experts a token x expert layers), ``moe.pairs_here`` (those whose
    expert is held here), ``moe.expert_load_max`` (the fullest held
    expert's pairs in any layer), ``moe.load_max_over_mean`` (the least
    even layer's fullest held expert over its mean held expert: 1 even,
    at most ``count``, the number held), ``moe.tiles_run`` (the trips of
    the grouped loops, one forward's: the recomputation and the
    backward run the same) and ``moe.rows_run`` (``tiles_run x tile``:
    the rows their products compute, ``pairs_here`` of them a pair and
    the rest padding paid for in full)."""
    here = fullest = tiles = jnp.zeros((), jnp.int32)
    uneven = jnp.zeros((), jnp.float32)
    for pairs, top, trips in loads:
        here, fullest = here + pairs, jnp.maximum(fullest, top)
        uneven = jnp.maximum(uneven, top * count / jnp.maximum(pairs, 1))
        tiles = tiles + trips
    return {
        "moe.pairs_routed": jnp.asarray(routed, jnp.int32),
        "moe.pairs_here": here.astype(jnp.int32),
        "moe.expert_load_max": fullest.astype(jnp.int32),
        "moe.load_max_over_mean": uneven.astype(jnp.float32),
        "moe.tiles_run": tiles.astype(jnp.int32),
        "moe.rows_run": (tiles * tile).astype(jnp.int32),
    }
