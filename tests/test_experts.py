"""``models/experts.py``, the sparse-expert layer that ``kimi_linear_lm``
and ``laguna_lm`` share: the routing plan's invariants, the grouped
product against a dense sum over experts (values and gradients), and
the optional selection bias."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlapi_tpu.models import experts, kimi_linear, laguna


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def test_one_expert_layer_in_the_tree():
    """Both models run the module's layer, neither a copy of it."""
    for mod in (kimi_linear, laguna):
        assert mod.experts is experts
        assert not hasattr(mod, "grouped_ffn") and not hasattr(mod, "_plan")


@pytest.mark.parametrize("first,count,tile", [(0, 4, 8), (4, 4, 8), (2, 6, 16),
                                              (0, 16, 4)])
def test_plan_sorts_the_pairs_here_into_whole_tiles(first, count, tile):
    """Every pair routed to a held expert appears once, in a tile of
    its expert alone, groups padded to whole tiles with -1; the tiles
    in use are exactly those groups; nothing else is planned."""
    idx = jnp.asarray(np.random.default_rng(tile).integers(0, 16, (40, 4)))
    rows, tile_expert, n_tiles, counts = jax.jit(
        lambda i: experts.plan(i, first, count, tile))(idx)
    rows, tile_expert = np.asarray(rows), np.asarray(tile_expert)
    flat = np.asarray(idx).reshape(-1)
    here = (flat >= first) & (flat < first + count)
    assert np.array_equal(np.asarray(counts),
                          np.bincount(flat[here] - first, minlength=count))
    assert int(n_tiles) == int(np.sum(-(-np.asarray(counts) // tile)))
    used = rows[:int(n_tiles) * tile]
    assert sorted(used[used >= 0]) == sorted(np.flatnonzero(here))
    assert np.all(rows[int(n_tiles) * tile:] == -1)
    for t in range(int(n_tiles)):
        pairs = used[t * tile:(t + 1) * tile]
        assert np.all(flat[pairs[pairs >= 0]] - first == tile_expert[t])


@pytest.mark.parametrize("cdt", ["float32", "bfloat16"])
def test_grouped_product_is_the_dense_sum_over_held_experts(cdt):
    """``grouped_ffn`` over the planned tiles against every token
    through every held expert weighted by the routing weights: values,
    and the gradients of x, the weights and the three kernels."""
    k, first, count, tile = 4, 4, 4, 8
    rng = jax.random.split(jax.random.key(0), 6)
    x = jax.random.normal(rng[0], (48, 32))
    idx = jax.random.randint(rng[1], (48, k), 0, 16)
    w = jax.random.uniform(rng[2], (48, k))
    wg, wu = (0.2 * jax.random.normal(r, (count, 32, 16)) for r in rng[3:5])
    wd = 0.2 * jax.random.normal(rng[5], (count, 16, 32))
    dt = jnp.dtype(cdt)

    def grouped(x, w, wg, wu, wd):
        rows, tile_expert, n_tiles, _ = experts.plan(idx, first, count, tile)
        return jnp.sum(jnp.sin(experts.grouped_ffn(
            x.astype(dt), w.reshape(-1), wg.astype(dt), wu.astype(dt),
            wd.astype(dt), rows, tile_expert, n_tiles, tile, k)))

    def dense(x, w, wg, wu, wd):
        y = 0.0
        for e in range(count):
            weight = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=1)
            h = jax.nn.silu(x @ wg[e]) * (x @ wu[e])
            y = y + weight[:, None] * (h @ wd[e])
        return jnp.sum(jnp.sin(y))

    args = (x, w, wg, wu, wd)
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(grouped, argnums=range(5)))(*args)
        want, g_want = jax.jit(jax.value_and_grad(dense, argnums=range(5)))(*args)
    tol = 1e-5 if cdt == "float32" else 3e-2
    assert abs(float(got) - float(want)) < tol * max(1.0, abs(float(want)))
    for a, b in zip(g_got, g_want):
        assert _rel(a, b) < tol


@pytest.mark.parametrize("with_bias", [False, True])
def test_selection_bias_is_optional_and_never_a_weight(with_bias):
    """A layer with ``router_bias`` selects by ``s + b`` and weighs by
    ``s``; one without selects by ``s``. A bias that lifts experts 4..7
    above every score sends every token's four choices to the held
    experts; the bias itself gets no gradient."""
    rng = jax.random.split(jax.random.key(1), 5)
    p = {"router": 0.3 * jax.random.normal(rng[0], (32, 16)),
         "experts": {"gate": 0.2 * jax.random.normal(rng[1], (4, 32, 16)),
                     "up": 0.2 * jax.random.normal(rng[2], (4, 32, 16)),
                     "down": 0.2 * jax.random.normal(rng[3], (4, 16, 32))},
         "shared": {"gate": jnp.zeros((32, 16)), "up": jnp.zeros((32, 16)),
                    "down": jnp.zeros((16, 32))}}
    if with_bias:
        p["router_bias"] = jnp.zeros((16,)).at[4:8].set(5.0)
    x = jax.random.normal(rng[4], (2, 24, 32))
    kw = dict(k=4, held=(4, 4), tile=8, scale=2.5, compute_dtype="float32")
    y, (pairs, fullest, tiles) = experts.moe(p, x, **kw)
    if with_bias:
        assert int(pairs) == 2 * 24 * 4 and int(fullest) == 2 * 24
        assert int(tiles) == 4 * 2 * 24 // 8
        g = jax.grad(lambda p: jnp.sum(experts.moe(p, x, **kw)[0] ** 2))(p)
        assert float(jnp.max(jnp.abs(g["router_bias"]))) == 0.0
        assert float(jnp.max(jnp.abs(g["router"]))) > 0.0
    else:
        assert 0 < int(pairs) < 2 * 24 * 4
    assert y.shape == x.shape and bool(jnp.all(jnp.isfinite(y)))


def test_load_stats_names_and_bounds():
    stats = experts.load_stats(
        [(jnp.int32(0), jnp.int32(0), jnp.int32(0)),
         (jnp.int32(40), jnp.int32(25), jnp.int32(7)),
         (jnp.int32(64), jnp.int32(16), jnp.int32(8))], 4, 400, 8)
    got = {k: float(v) for k, v in stats.items()}
    assert got == {"moe.pairs_routed": 400.0, "moe.pairs_here": 104.0,
                   "moe.expert_load_max": 25.0,
                   "moe.load_max_over_mean": 2.5, "moe.tiles_run": 15.0,
                   "moe.rows_run": 120.0}


@pytest.mark.parametrize("per_expert,tile,tiles", [
    ([257, 0, 5], 256, 3),   # 257 pairs: two tiles; an empty expert: none
    ([0, 0, 0], 256, 0),
    ([256, 1, 0], 256, 2),
    ([8, 9, 16], 8, 5),
])
def test_tiles_run_is_the_plans_closed_form(per_expert, tile, tiles):
    """``moe.tiles_run`` of a layer planned from hand-built ``idx`` is
    the sum over held experts of ``ceil(pairs / tile)`` and
    ``moe.rows_run`` that times ``tile``; pairs routed to experts not
    held (ids 3 and up here) plan nothing."""
    flat = np.concatenate([np.full(n, e) for e, n in enumerate(per_expert)]
                          + [np.full(11, 3), np.full(6, 7)])
    idx = jnp.asarray(np.random.default_rng(tile).permutation(
        np.pad(flat, (0, -len(flat) % 4), constant_values=5)).reshape(-1, 4))
    _, _, n_tiles, counts = jax.jit(
        lambda i: experts.plan(i, 0, 3, tile))(idx)
    assert list(np.asarray(counts)) == per_expert
    stats = experts.load_stats(
        [(jnp.sum(counts), jnp.max(counts), n_tiles)], 3, idx.size, tile)
    assert int(stats["moe.tiles_run"]) == tiles
    assert int(stats["moe.rows_run"]) == tiles * tile
