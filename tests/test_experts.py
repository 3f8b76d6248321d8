"""``models/experts.py``, the sparse-expert layer that ``kimi_linear_lm``
and ``laguna_lm`` share: the routing plan's invariants, the grouped
product against a dense sum over experts (values and gradients), the
backward's Pallas kernel (``ops/pallas/grouped_ffn.py``) against the XLA
loop's (interpreter), the choice between them by shapes, and the
optional selection bias."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlapi_tpu.models import experts, kimi_linear, laguna
from mlapi_tpu.ops.pallas import grouped_ffn as gk
from mlapi_tpu.utils.metrics import REGISTRY


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
            wd.astype(dt), rows, tile_expert, n_tiles, tile, k, False)))

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


# Widths the kernel takes (a token's row of whole float32 tiles), small
# enough for the interpreter: 512 tokens x 4 choices of 32 experts, 8
# held (ids 8-15), tiles of 128.
T, K, E, FIRST, COUNT, HID, INTER, TILE = 512, 4, 32, 8, 8, 1024, 128, 128
LOADS = {
    # pairs a held expert: one over three tiles whose last is mostly
    # padding (44 of 128), one over two (the second holds ONE pair), two
    # with no pair, one with a single pair
    "mixed": [300, 0, 129, 5, 64, 0, 1, 200],
    "collapse": [0, 0, 0, T, 0, 0, 0, 0],     # every pair here on one expert
    "none": [0] * COUNT,                      # n_tiles 0
    "random": None,                           # distinct uniform choices
}


def _routed(load, seed=3):
    """``idx [T, K]``: each held expert gets the tokens ``LOADS[load]``
    says (distinct tokens), every other choice an expert not held."""
    rng = np.random.default_rng(seed)
    per = LOADS[load]
    if per is None:
        return jnp.asarray(np.stack([rng.permutation(E)[:K] for _ in range(T)]))
    choices = [[] for _ in range(T)]
    for e, c in enumerate(per):
        for t in rng.choice(T, c, replace=False):
            choices[t].append(FIRST + e)
    others = [e for e in range(E) if not FIRST <= e < FIRST + COUNT]
    for ch in choices:
        assert len(ch) <= K
        ch += [int(o) for o in rng.choice(others, K - len(ch), replace=False)]
        rng.shuffle(ch)
    return jnp.asarray(choices, jnp.int32)


def _operands(seed, inter):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (T, HID))
    w = jax.random.uniform(ks[1], (T * K,))
    wg, wu = (0.1 * jax.random.normal(r, (COUNT, HID, inter)) for r in ks[2:4])
    wd = 0.1 * jax.random.normal(ks[4], (COUNT, inter, HID))
    return (x, w, wg, wu, wd), jax.random.normal(ks[5], (T, HID))


def _both_backwards(load, cdt, inter, seed):
    """``y`` and the gradients of ``x``, the routing weights and the
    three kernels, by the kernel's backward and by the loop's, over the
    plan of ``load``."""
    idx = _routed(load)
    rows, tile_expert, n_tiles, counts = experts.plan(idx, FIRST, COUNT, TILE)
    if LOADS[load] is not None:
        assert list(np.asarray(counts)) == LOADS[load]
    ops, probe = _operands(seed, inter)
    dt = jnp.dtype(cdt)

    def run(kernel):
        def f(x, w, wg, wu, wd):
            return experts.grouped_ffn(
                x.astype(dt), w, wg.astype(dt), wu.astype(dt), wd.astype(dt),
                rows, tile_expert, n_tiles, TILE, K, kernel)

        y, vjp = jax.vjp(f, *ops)
        return (y,) + vjp(probe)

    return (jax.jit(lambda: run(True))(), jax.jit(lambda: run(False))(),
            int(n_tiles))


@pytest.mark.parametrize("cdt", ["float32", "bfloat16"])
@pytest.mark.parametrize("load", list(LOADS))
def test_grouped_kernel_backward_matches_the_loop(load, cdt):
    """The backward by the Pallas kernel (``ops/pallas/grouped_ffn.py``,
    interpreter) against the XLA loop's over the same plan: the
    gradients of ``x``, the routing weights and the three kernels, as
    norm of the difference over the loop's norm (``y`` is the loop's
    either way). With no pair here every result is zero."""
    got, want, n_tiles = _both_backwards(load, cdt, INTER, seed=4)
    names = ("y", "dx", "dw", "dwg", "dwu", "dwd")
    if load == "none":
        assert n_tiles == 0
        for name, a in zip(names, got):
            assert not np.any(np.asarray(a, np.float32)), name
        return
    tol = 1e-5 if cdt == "float32" else 5e-3
    for name, a, b in zip(names, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.all(np.isfinite(np.asarray(a, np.float32))), name
        assert _rel(a, b) < tol, (name, _rel(a, b))


def test_grouped_kernel_splits_the_intermediate_width(monkeypatch):
    """Where an expert's blocks would not fit VMEM whole, the kernel runs
    the intermediate width in blocks, one pass over the tiles a block:
    ``dx`` gathers each pass's part in its float32 rows, the routing
    weights' gradient is summed over the passes, each block of the
    weight gradients is its own. Here two blocks of 128 against the
    loop, float32, on the mixed load."""
    monkeypatch.setattr(gk, "_block", lambda h, i, tile, size: i // 2)
    got, want, _ = _both_backwards("mixed", "float32", 256, seed=8)
    for a, b in zip(got, want):
        assert _rel(a, b) < 1e-5


def test_shapes_choose_between_the_kernel_and_the_loop(monkeypatch):
    """``moe`` takes the kernel's backward where a token's row is whole
    float32 tiles (hidden a multiple of 1024, Laguna's 2048), the
    intermediate width whole 128-lane columns and the tile whole 8-row
    groups, and the XLA loop's elsewhere: at the rehearsal widths
    (hidden 64, tile 16) and at kimi's hidden 2304. Read from
    ``moe.calls_traced`` / ``moe.calls_kernel``, counted once a trace;
    both paths' gradients agree."""
    def counts():
        c = REGISTRY.snapshot()["counters"]
        return c.get("moe.calls_traced", 0), c.get("moe.calls_kernel", 0)

    assert gk.takes(HID, INTER, TILE) and gk.takes(2048, 512, 256)
    assert not gk.takes(2304, 1024, 256)
    assert not gk.takes(64, 32, 16) and not gk.takes(1024, 128, 12)
    for hid, inter, tile, kernel in ((HID, INTER, TILE, 1), (64, 32, 16, 0)):
        rng = jax.random.split(jax.random.key(6), 5)
        p = {"router": 0.3 * jax.random.normal(rng[0], (hid, E)),
             "experts": {
                 "gate": 0.1 * jax.random.normal(rng[1], (COUNT, hid, inter)),
                 "up": 0.1 * jax.random.normal(rng[2], (COUNT, hid, inter)),
                 "down": 0.1 * jax.random.normal(rng[3], (COUNT, inter, hid))},
             "shared": {"gate": jnp.zeros((hid, inter)),
                        "up": jnp.zeros((hid, inter)),
                        "down": jnp.zeros((inter, hid))}}
        x = jax.random.normal(rng[4], (2, 128, hid))
        kw = dict(k=K, held=(FIRST, COUNT), tile=tile, scale=2.5,
                  compute_dtype="float32")
        before = counts()
        fn = jax.jit(jax.grad(lambda p: jnp.sum(experts.moe(p, x, **kw)[0] ** 2)))
        got = fn(p)
        fn(p)  # a second call of the same trace counts nothing
        after = counts()
        assert (after[0] - before[0], after[1] - before[1]) == (1, kernel)
        with monkeypatch.context() as m:
            m.setattr(gk, "takes", lambda *a: False)
            want = jax.jit(jax.grad(
                lambda p: jnp.sum(experts.moe(p, x, **kw)[0] ** 2)))(p)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert _rel(a, b) < 1e-4
