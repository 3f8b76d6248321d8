"""``kimi_linear_lm`` against its plain reference
(``benchmark/reference/kimi_linear.py``: float32, ``Precision.HIGHEST``,
KDA as the literal recurrence, every token through every held expert):
each layer kind alone and the whole model, values and every gradient
leaf; the chunked delta rule against the recurrence; flash attention
with value heads narrower than the query/key heads; the expert layer's
share; ``fit``, the checkpoint and the serving refusal.

Tiny widths that keep every ratio of the published model: five layers
of the same kinds (KDA + dense, KDA, KDA, MLA, KDA; the last four with
the expert FFN), 16 experts with 4 a token of which 4 are held,
``d_k = d_v = 16``, query/key heads (16 + 8) wider than value heads
(16), at least two chunks.
"""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlapi_tpu.models import get_model
from mlapi_tpu.models import kimi_linear as kl
from mlapi_tpu.utils.metrics import REGISTRY

BENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from reference import kimi_linear as ref  # noqa: E402

VOCAB = 300
KW = dict(
    vocab_size=VOCAB, hidden_size=64, num_layers=5,
    kda_layers=[1, 2, 3, 5, 6, 7], full_attn_layers=[4, 8],
    first_k_dense_replace=1, intermediate_size=128, num_heads=4,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
    kda_num_heads=4, kda_head_dim=16, kda_chunk=32, num_experts=16,
    num_experts_per_token=4, moe_intermediate_size=32, experts_held=[4, 4],
    moe_tile=8, compute_dtype="float32",
)
# the same model as the reference reads it (the configuration file's keys)
CFG = {
    "vocab_size": VOCAB, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_experts_per_token": 4,
    "num_shared_experts": 1, "routed_scaling_factor": 2.446,
    "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 32, "rms_norm_eps": 1e-5,
    "num_hidden_layers": 5, "first_k_dense_replace": 1, "num_experts": 4,
    "router_width": 16, "experts_held": [4, 4],
    "linear_attn_config": {
        "kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
        "num_heads": 4, "head_dim": 16, "short_conv_kernel_size": 4},
}
L = 80  # 2.5 chunks of 32


@pytest.fixture(scope="module")
def flat():
    return ref.make_params(7, CFG)


@pytest.fixture(scope="module")
def ids():
    x = np.random.default_rng(0).integers(1, VOCAB, (2, L)).astype(np.int32)
    x[1, -9:] = 0  # a padded tail: masked in the loss
    return x


def rel(a, b):
    """Norm of the difference over the reference's norm."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def flatten(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flatten(v, f"{prefix}{k}."))
        else:
            out[f"{prefix}{k}"] = v
    return out


def program_loss(model, params, ids):
    """``make_train_step(task="lm")``'s loss, written out."""
    logits = model.apply(params, ids)
    t = ids[:, 1:]
    keep = (t != 0).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    ce = -jnp.take_along_axis(logp, t[..., None], axis=-1)[..., 0]
    return jnp.sum(ce * keep) / jnp.maximum(jnp.sum(keep), 1.0)


# -- each layer kind alone ------------------------------------------------
# Tolerance 2e-4 of the reference's norm, values and gradients: both
# sides are float32, but the program's products run at the backend's
# default precision in another order (chunks against one position at a
# time: 2.5 chunks re-associate up to 80 decays), a rounding of 1e-6 a
# product that the delta rule's solve carries forward.
KIND_TOL = 2e-4


@pytest.mark.parametrize("kind", ["kda", "mla", "moe", "dense"])
def test_layer_kind_matches_reference(flat, kind):
    model = get_model("kimi_linear_lm", **KW)
    c = ref.settings(CFG)
    p = ref.nested(flat)
    layer = {"kda": 1, "mla": 3, "moe": 1, "dense": 0}[kind]
    name = {"kda": "kda", "mla": "mla", "moe": "moe", "dense": "mlp"}[kind]
    lp = p[f"layer_{layer}"][name]
    x = jax.random.normal(jax.random.key(3), (2, L, 64), jnp.float32)
    probe = jax.random.normal(jax.random.key(4), (2, L, 64), jnp.float32)

    prog = {"kda": model._kda, "mla": model._mla, "dense": model._ffn,
            "moe": lambda lp, x: model._moe(lp, x)[0]}[kind]
    plain = {"kda": lambda lp, x: ref._kda(lp, x, c, "float32"),
             "mla": lambda lp, x: ref._mla(lp, x, c, "float32"),
             "dense": lambda lp, x: ref._ffn(lp, x, "float32"),
             "moe": lambda lp, x: ref.moe(lp, x, c)[0]}[kind]

    def both(f):
        y, grads = jax.value_and_grad(
            lambda lp, x: jnp.sum(f(lp, x) * probe), argnums=(0, 1))(lp, x)
        return f(lp, x), grads

    with jax.default_matmul_precision("highest"):
        y, (gp, gx) = jax.jit(lambda: both(prog))()
    y_ref, (gp_ref, gx_ref) = jax.jit(lambda: both(plain))()
    assert rel(y, y_ref) < KIND_TOL
    assert rel(gx, gx_ref) < KIND_TOL
    for k, g in flatten(gp_ref).items():
        if k == "router_bias":  # the selection bias has no gradient
            assert float(jnp.max(jnp.abs(flatten(gp)[k]))) == 0.0
            continue
        assert rel(flatten(gp)[k], g) < KIND_TOL, k


# -- the whole model ------------------------------------------------------
def test_whole_model_matches_reference(flat, ids):
    """Logits, loss and EVERY gradient leaf, float32 against float32.
    Tolerances: logits 1e-5 absolute (values of 0.6: five blocks of
    float32 rounding); loss 1e-6; gradient leaves 5e-4 of the leaf's
    norm (the loss's gradient passes four chunked scans backward, each
    re-associating its sums)."""
    model = get_model("kimi_linear_lm", **KW)
    params = ref.nested(flat)
    with jax.default_matmul_precision("highest"):
        logits, stats = jax.jit(model.apply_with_stats)(params, ids)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: program_loss(model, p, ids)))(params)
    r_logits, r_here = ref.forward(flat, jnp.asarray(ids), CFG)
    r_loss, _, r_grads = ref._loss_and_grad(
        flat, jnp.asarray(ids), ref.hashable(ref.settings(CFG)), "float32")
    assert float(jnp.max(jnp.abs(logits - r_logits))) < 1e-5
    assert abs(float(loss) - float(r_loss)) < 1e-6
    assert int(stats["moe.pairs_here"]) == int(r_here)
    assert int(stats["moe.pairs_routed"]) == ids.size * 4 * 4
    got = flatten(grads)
    assert set(got) == set(r_grads)
    for k, g in r_grads.items():
        if k.endswith("router_bias"):
            assert float(jnp.max(jnp.abs(got[k]))) == 0.0
            continue
        assert rel(got[k], g) < 5e-4, k


def test_stats_count_the_expert_loops_tiles(flat, ids):
    """``apply_with_stats`` counts the grouped loops' trips and rows:
    ``moe.rows_run == moe.tiles_run x moe_tile >= moe.pairs_here``, and
    the padding is less than a tile for each held expert of each of the
    four expert layers."""
    model = get_model("kimi_linear_lm", **KW)
    _, stats = jax.jit(model.apply_with_stats)(ref.nested(flat), ids)
    tiles, rows = int(stats["moe.tiles_run"]), int(stats["moe.rows_run"])
    here = int(stats["moe.pairs_here"])
    assert rows == tiles * KW["moe_tile"] >= here > 0
    assert rows - here < 4 * KW["experts_held"][1] * KW["moe_tile"]


def test_bfloat16_program_is_told_from_8_bit_products(flat, ids):
    """The configuration's precision (bfloat16 products, float32
    accumulation) against the reference, beside the reference's own
    CONTROL one precision down (``int8_all``: every projection's
    product on the int8 grid). The number is the benchmark's: the
    median over leaves of the part of the gradient's error that stands
    perpendicular to the reference, over the reference's norm. At these
    widths bfloat16 reads 0.03 and the control 0.11, so the limit 0.06
    passes the one and fails the other: a program whose
    products were 8-bit would fail it."""
    model = get_model("kimi_linear_lm", **{**KW, "compute_dtype": "bfloat16"})
    grads = flatten(jax.jit(jax.grad(
        lambda p: program_loss(model, p, ids)))(ref.nested(flat)))

    def of(precision):
        return ref._loss_and_grad(
            flat, jnp.asarray(ids), ref.hashable(ref.settings(CFG)),
            precision)[2]

    r, control = of("float32"), of("int8_all")

    def turn(got):
        out = []
        for k, g in r.items():
            g = np.asarray(g, np.float64).ravel()
            if not np.any(g):
                continue
            e = np.asarray(got[k], np.float64).ravel() - g
            out.append(np.linalg.norm(e - (e @ g) / (g @ g) * g)
                       / np.linalg.norm(g))
        return float(np.median(out))

    assert turn(grads) < 0.06 < turn(control)


# -- the chunked delta rule -----------------------------------------------
def _delta_rule_operands(length, decay, b=2, h=2, d=128):
    ks = jax.random.split(jax.random.key(length), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    return (unit(jax.random.normal(ks[0], (b, length, h, d))) * d ** -0.5,
            unit(jax.random.normal(ks[1], (b, length, h, d))),
            jax.random.normal(ks[2], (b, length, h, d)),
            -decay * jax.random.uniform(ks[3], (b, length, h, d), maxval=2.0),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, length, h))),
            jax.random.normal(ks[5], (b, length, h, d)))


@pytest.mark.parametrize("length,decay", [
    (80, 1.0),    # not a multiple of the chunk (32), one group
    (200, 1.0),   # three groups of two chunks, the last one padded
    (80, 40.0),   # a chunk decays by far more than e^88: no overflow
])
def test_chunked_kda_matches_literal_recurrence(monkeypatch, length, decay):
    """``kda_chunked`` against ``reference.delta_rule`` (one position at
    a time), output and all five gradients. 1e-4 of the norm: float32
    both sides; the chunked form inverts a unit-triangular matrix a
    chunk and carries the state across chunks and groups."""
    monkeypatch.setattr(kl, "_GROUP", 64)
    q, k, v, g, beta, probe = _delta_rule_operands(length, decay, h=3, d=16)

    def run(f):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(f(*a) * probe), argnums=(0, 1, 2, 3, 4)))(
                q, k, v, g, beta)

    with jax.default_matmul_precision("highest"):
        y, grads = run(lambda *a: kl.kda_chunked(*a, chunk=32))
    y_ref, grads_ref = run(ref.delta_rule)
    assert np.isfinite(float(y))
    assert abs(float(y) - float(y_ref)) < 1e-4 * abs(float(y_ref)) + 1e-5
    for got, want in zip(grads, grads_ref):
        assert np.all(np.isfinite(np.asarray(got)))
        assert rel(got, want) < 1e-4


@pytest.mark.parametrize("length,decay,cdt,tol", [
    (200, 1.0, "float32", 1e-4),   # no multiple of the tile (32)
    (520, 1.0, "float32", 1e-4),   # 17 tiles: 16 carried states
    (80, 40.0, "float32", 1e-4),   # a tile decays by far more than e^88
    # bfloat16 products, float32 accumulation: the kernels read 0.0026-
    # 0.0033 and kda_chunked 0.0026-0.0033 against the same oracle
    (200, 1.0, "bfloat16", 1e-2),
])
def test_kda_kernels_match_literal_recurrence(length, decay, cdt, tol):
    """The Pallas kernels (``ops/pallas/kda.py``, interpreter) at 2 rows
    x 2 heads of 128 against ``reference.delta_rule``: the output and
    all five gradients, as norm of the difference over the oracle's
    norm. float32 operands: rounding only. ``compute_dtype="bfloat16"``:
    the tolerance ``kda_chunked`` meets against the same oracle, and
    the kernels within a quarter more than what it reads."""
    from mlapi_tpu.ops.pallas import kda as kk

    *args, probe = _delta_rule_operands(length, decay)

    def run(f):
        y, vjp = jax.vjp(f, *args)
        return (y,) + vjp(probe)

    got = jax.jit(lambda: run(lambda *a: kk.kda_kernels(
        *a, compute_dtype=cdt, interpret=True)))()
    want = jax.jit(lambda: run(ref.delta_rule))()
    errs = [rel(a, w) for a, w in zip(got, want)]
    for a, e in zip(got, errs):
        assert np.all(np.isfinite(np.asarray(a)))
        assert e < tol, errs
    if cdt != "float32":
        xla = jax.jit(lambda: run(lambda *a: kl.kda_chunked(
            *a, chunk=32, compute_dtype=cdt)))()
        for e, a, w in zip(errs, xla, want):
            assert rel(a, w) < tol
            assert e < 1.25 * rel(a, w) + 1e-4, (errs, rel(a, w))


def _layer_operands(length, b=2, h=2, d=128, decay=1.0):
    """The seven operands of a KDA layer's call as the projections
    leave them (``[B, L, H * D]`` slabs, raw q and k, ``beta [B, L,
    H]``, the output norm's scale ``[D]``) and a probe for ``y``."""
    ks = jax.random.split(jax.random.key(1000 + length), 8)

    def wide(i):
        return jax.random.normal(ks[i], (b, length, h * d))

    return (wide(0), wide(1), wide(2),
            -decay * jax.random.uniform(ks[3], (b, length, h * d), maxval=2.0),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, length, h))),
            jax.nn.sigmoid(wide(5)),
            1.0 + 0.1 * jax.random.normal(ks[6], (d,)), wide(7))


def test_shapes_choose_between_the_kernels_and_the_xla_scan():
    """Heads of 128 take the kernels, 16-wide heads the plain path
    (``kda_chunked`` between the normalisations): read from
    ``kda.calls_traced`` / ``kda.calls_kernel``, counted once a trace
    where ``models.kimi_linear.kda`` chooses. Both answers are the
    recurrence's, between the layer's normalisations."""
    def counts():
        c = REGISTRY.snapshot()["counters"]
        return c.get("kda.calls_traced", 0), c.get("kda.calls_kernel", 0)

    def literal(q, k, v, g, beta, gate, scale):
        b, l, h = beta.shape
        heads = lambda a: a.reshape(b, l, h, -1)  # noqa: E731
        unit = lambda a: a * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(a * a, -1, keepdims=True) + 1e-6)
        q, k = heads(q), heads(k)
        o = ref.delta_rule(unit(q) * q.shape[-1] ** -0.5, unit(k), heads(v),
                           heads(g), beta)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5)
        return (o * scale * heads(gate)).reshape(b, l, -1)

    for d, kernel in ((16, 0), (128, 1)):
        *args, _ = _layer_operands(40, b=1, d=d)
        before = counts()
        fn = jax.jit(lambda *a: kl.kda(*a, eps=1e-5, chunk=32))
        y = fn(*args)
        fn(*args)  # a second call of the same trace counts nothing
        after = counts()
        assert (after[0] - before[0], after[1] - before[1]) == (1, kernel)
        assert rel(y, literal(*args)) < 1e-4


@pytest.mark.parametrize("length,cdt,tol", [
    (200, "float32", 1e-4),    # no multiple of the tile (64)
    (1100, "float32", 1e-4),   # 18 tiles: 17 carried states
    # bfloat16 products, float32 accumulation and normalisations: the
    # kernels and the plain path each against the float32 plain path
    (200, "bfloat16", 1e-2),
])
def test_kda_layer_kernels_match_the_plain_path(length, cdt, tol):
    """The kernels' seven-operand call (``kda_layer``, interpreter: the
    L2 normalisations of q and k, the rule, the output's RMS norm times
    the gate, all on a tile in VMEM) at 2 rows x 2 heads of 128 against
    the plain ``jax.numpy`` path of the same entry point
    (``kda_plain``, float32): ``y`` and all seven gradients, as norm of
    the difference over the oracle's norm. float32 operands: rounding
    only. ``compute_dtype="bfloat16"``: the tolerance the plain path
    meets at that dtype against the same oracle, and the kernels within
    a quarter more than what it reads."""
    from mlapi_tpu.ops.pallas import kda as kk

    *args, probe = _layer_operands(length)

    def run(f):
        y, vjp = jax.vjp(f, *args)
        return (y,) + vjp(probe)

    def plain(cdt):
        return jax.jit(lambda: run(lambda *a: kl.kda_plain(
            *a, eps=1e-5, chunk=32, compute_dtype=cdt)))()

    got = jax.jit(lambda: run(lambda *a: kk.kda_layer(
        *a, eps=1e-5, compute_dtype=cdt, interpret=True)))()
    with jax.default_matmul_precision("highest"):
        want = plain("float32")
    assert len(got) == 8 and got[7].shape == (128,)
    errs = [rel(a, w) for a, w in zip(got, want)]
    for a, e in zip(got, errs):
        assert np.all(np.isfinite(np.asarray(a)))
        assert e < tol, errs
    if cdt != "float32":
        for e, a, w in zip(errs, plain(cdt), want):
            assert rel(a, w) < tol
            assert e < 1.25 * rel(a, w) + 1e-4, (errs, rel(a, w))


def test_kda_block_by_the_kernels_matches_the_plain_block(monkeypatch):
    """A KDA block of the model (norm, projections, convolutions, the
    call, output projection, dense FFN) at ``head_dim`` 128, where the
    entry point chooses the kernels, against the same block with
    ``kda_plain`` called directly: the loss and every parameter's
    gradient, float32."""
    kw = dict(KW, hidden_size=32, num_layers=1, kda_layers=[1],
              full_attn_layers=[], intermediate_size=64, kda_num_heads=2,
              kda_head_dim=128, remat=False)
    model = get_model("kimi_linear_lm", **kw)
    params = model.init(jax.random.key(5))
    x = np.random.default_rng(2).integers(1, VOCAB, (2, 100)).astype(np.int32)

    def loss_and_grads():
        before = REGISTRY.snapshot()["counters"].get("kda.calls_kernel", 0)
        with jax.default_matmul_precision("highest"):
            out = jax.jit(jax.value_and_grad(
                lambda p: program_loss(model, p, x)))(params)
        return out, REGISTRY.snapshot()["counters"].get(
            "kda.calls_kernel", 0) - before

    (loss, grads), chose = loss_and_grads()
    monkeypatch.setattr(kl, "kda", kl.kda_plain)
    (want_loss, want), chose_plain = loss_and_grads()
    assert (chose, chose_plain) == (1, 0)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * abs(float(want_loss))
    for name, g in flatten(want).items():
        assert rel(flatten(grads)[name], g) < KIND_TOL, name


def test_unit_lower_inverse_is_a_stable_blocked_substitution():
    """The doubling inverse on the matrix that breaks a power series:
    every entry under the diagonal 1 (identical keys, no decay, full
    write strength). Its powers grow like binomials (1e17 at 64), its
    inverse is the bidiagonal (1, -1): exact here, and 1e-5 of the
    inverse's norm on a random well-scaled matrix, values and
    gradient."""
    c = 64
    ones = jnp.tril(jnp.ones((c, c), jnp.float32))
    want = jnp.eye(c) - jnp.eye(c, k=-1)
    assert float(jnp.max(jnp.abs(kl._unit_lower_inverse(ones) - want))) == 0.0
    m = jnp.eye(c) + jnp.tril(
        0.3 * jax.random.normal(jax.random.key(1), (3, c, c)), -1)
    probe = jax.random.normal(jax.random.key(2), (3, c, c))
    y, g = jax.value_and_grad(
        lambda m: jnp.sum(kl._unit_lower_inverse(m) * probe))(m)
    y_ref, g_ref = jax.value_and_grad(lambda m: jnp.sum(
        jnp.linalg.inv(jnp.eye(c) + jnp.tril(m, -1)) * probe))(m)
    assert abs(float(y) - float(y_ref)) < 1e-5 * abs(float(y_ref)) + 1e-5
    assert rel(g, g_ref) < 1e-5


# -- what a block keeps across its recomputation ---------------------------
def _gradient_programs(head_dim, count_primitives):
    """The loss's gradient of a three-layer model (KDA + dense, MLA +
    experts, KDA + experts) with and without ``remat``: the primitive
    counts of its jaxpr and its value."""
    kw = dict(KW, hidden_size=32, num_layers=3, kda_layers=[1, 3],
              full_attn_layers=[2], intermediate_size=64, num_heads=2,
              kv_lora_rank=16, kda_num_heads=1, kda_head_dim=head_dim,
              num_experts=8, num_experts_per_token=2,
              moe_intermediate_size=16, experts_held=[2, 4])
    x = np.random.default_rng(1).integers(1, VOCAB, (1, 64)).astype(np.int32)
    out = {}
    for remat in (True, False):
        model = get_model("kimi_linear_lm", **kw, remat=remat)
        params = model.init(jax.random.key(3))
        grad = jax.grad(lambda p, m=model: program_loss(m, p, x))
        out[remat] = (count_primitives(jax.make_jaxpr(grad)(params).jaxpr),
                      flatten(jax.jit(grad)(params)))
    return out


@pytest.fixture(scope="module")
def wide_heads(count_primitives):
    return _gradient_programs(128, count_primitives)


@pytest.mark.parametrize("remat", [True, False])
def test_recomputed_block_keeps_kernels_and_routing(wide_heads, remat):
    """Heads of whole 128-lane slabs (the kernels, interpreted): in the
    gradient's jaxpr ``kda_fwd`` runs once a KDA layer (2), the flash
    forward once an MLA layer (1), ``top_k`` and ``sort`` once an expert
    layer (2), whether or not the blocks are recomputed: the checkpoint
    keeps what their producers name. What is not named IS made again:
    a recomputing gradient holds more products. The two gradients agree
    leaf by leaf."""
    counts, grads = wide_heads[remat]
    assert (counts["kda_fwd"], counts["kda_bwd"]) == (2, 2)
    assert (counts["flash_attention_fwd"], counts["flash_attention_dq"],
            counts["flash_attention_dkv"]) == (1, 1, 1)
    assert (counts["top_k"], counts["sort"]) == (2, 2)
    plain, want = wide_heads[False]
    assert (counts["dot_general"] > plain["dot_general"]) == remat
    assert grads.keys() == want.keys()
    for name, g in grads.items():
        assert np.all(np.isfinite(np.asarray(g))), name
        assert rel(g, want[name]) < KIND_TOL or not np.any(want[name]), name


def test_narrow_heads_still_recompute_the_xla_scan(count_primitives):
    """16-wide heads go to ``kda_chunked``, which names nothing: no
    ``kda_*`` kernel in the gradient, and under ``remat`` its scans
    (groups, and chunks inside a group) run once more a KDA layer, as
    before; the routing is kept at any width."""
    both = _gradient_programs(16, count_primitives)
    (counts, grads), (plain, want) = both[True], both[False]
    for c in (counts, plain):
        assert c["kda_fwd"] == c["kda_bwd"] == 0
        assert (c["top_k"], c["sort"], c["flash_attention_fwd"]) == (2, 2, 1)
    assert counts["scan"] == plain["scan"] + 2 * 2
    for name, g in grads.items():
        assert rel(g, want[name]) < KIND_TOL or not np.any(want[name]), name


# -- flash attention, value heads narrower than query/key heads -----------
@pytest.mark.parametrize("length", [96, 640])
def test_flash_unequal_head_widths(length):
    """``flash_attention`` (interpreter) with scores over 24 and values
    over 16 against the plain causal softmax, forward and the three
    gradients: 96 positions would fit one tile but stream (the one-tile
    kernels slice every operand by one width), 640 stream in two tiles
    of 512 with a padded tail. float32 operands: 2e-5 of the norm."""
    from mlapi_tpu.ops.pallas import flash_attention

    b, h, dq, dv = 2, 2, 24, 16
    ks = jax.random.split(jax.random.key(length), 4)
    q = jax.random.normal(ks[0], (b, length, h, dq))
    k = jax.random.normal(ks[1], (b, length, h, dq))
    v = jax.random.normal(ks[2], (b, length, h, dv))
    probe = jax.random.normal(ks[3], (b, length, h, dv))

    def plain(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       precision="highest") * dq ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((length, length), bool)), s, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                          precision="highest")

    def run(f):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(f(*a) * probe), argnums=(0, 1, 2)))(q, k, v)

    y, grads = run(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=True))
    y_ref, grads_ref = run(plain)
    assert flash_attention(q, k, v, causal=True, interpret=True).shape == (
        b, length, h, dv)
    assert abs(float(y) - float(y_ref)) < 2e-5 * abs(float(y_ref)) + 1e-5
    for got, want in zip(grads, grads_ref):
        assert rel(got, want) < 2e-5


def test_flash_refuses_unequal_query_and_key_widths():
    from mlapi_tpu.ops.pallas import flash_attention

    q = jnp.zeros((1, 16, 2, 24))
    with pytest.raises(ValueError, match="q and k head widths"):
        flash_attention(q, jnp.zeros((1, 16, 2, 16)), q, interpret=True)


# -- the share is the model's ---------------------------------------------
def test_four_shares_add_up_to_the_uncut_layer(flat):
    """Four chips of four experts each: the parts their expert layers
    give, with the shared expert (which every chip computes alike)
    counted once, add up to the UNCUT reference's layer output (all 16
    experts, dense). 1e-5 of the norm: float32, sums in another order."""
    c_all = ref.settings({**CFG, "num_experts": 16, "experts_held": [0, 16]})
    rng = jax.random.split(jax.random.key(11), 4)
    router = {"router": 0.5 * jax.random.normal(rng[0], (64, 16)),
              "router_bias": 0.02 * jax.random.normal(rng[1], (16,))}
    experts = {k: 0.1 * jax.random.normal(r, (16, *s)) for (k, s), r in zip(
        {"gate": (64, 32), "up": (64, 32), "down": (32, 64)}.items(),
        jax.random.split(rng[2], 3))}
    shared = ref.nested(flat)["layer_1"]["moe"]["shared"]
    x = jax.random.normal(rng[3], (2, L, 64))
    whole, pairs = ref.moe(
        {**router, "experts": experts, "shared": shared}, x, c_all)
    assert int(pairs) == x.shape[0] * L * 4
    with jax.default_matmul_precision("highest"):
        shared_part = get_model("kimi_linear_lm", **KW)._ffn(shared, x)
        total, here = shared_part, 0
        for s in range(4):
            model = get_model(
                "kimi_linear_lm", **{**KW, "experts_held": [4 * s, 4]})
            held = {k: v[4 * s:4 * s + 4] for k, v in experts.items()}
            y, (pairs_here, _, _) = model._moe(
                {**router, "experts": held, "shared": shared}, x)
            total = total + (y - shared_part)
            here += int(pairs_here)
    assert here == int(pairs)  # every pair is some share's
    assert rel(total, whole) < 1e-5


def test_no_token_is_dropped_under_the_worst_imbalance(flat):
    """Every token routed to ONE held expert (its other three choices
    go to experts that are not here): that expert gets as many pairs as
    there are tokens, 20 tiles of 8 rows, the three others none, and the
    layer still gives the reference's result, which drops nothing by
    construction (dense, no capacity)."""
    model = get_model("kimi_linear_lm", **KW)  # holds experts 4..7
    c = ref.settings(CFG)
    lp = dict(ref.nested(flat)["layer_1"]["moe"])
    bias = np.zeros(16, np.float32)
    bias[[5, 0, 1, 2]] = [9.0, 8.0, 7.0, 6.0]
    lp["router_bias"] = jnp.asarray(bias)
    x = jax.random.normal(jax.random.key(5), (2, L, 64))
    with jax.default_matmul_precision("highest"):
        y, (pairs, fullest, _) = jax.jit(model._moe)(lp, x)
    y_ref, pairs_ref = ref.moe(lp, x, c)
    assert int(pairs) == int(fullest) == int(pairs_ref) == 2 * L
    assert rel(y, y_ref) < 1e-5


def test_layer_kinds_follow_the_published_lists():
    model = get_model("kimi_linear_lm", **KW)
    assert model.layer_kinds == (
        ("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe"),
        ("kda", "moe"))
    with pytest.raises(ValueError, match="neither"):
        get_model("kimi_linear_lm", **{**KW, "kda_layers": [1, 2]})
    with pytest.raises(ValueError, match="experts_held"):
        get_model("kimi_linear_lm", **{**KW, "experts_held": [14, 4]})
    with pytest.raises(ValueError, match="power of two"):
        get_model("kimi_linear_lm", **{**KW, "kda_chunk": 48})


# -- fit, the checkpoint, the CLIs ----------------------------------------
@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """``python -m mlapi_tpu.train --preset docs-kimi-linear`` (a few
    steps), in this process: its closing JSON and its checkpoint."""
    from mlapi_tpu.train.__main__ import main

    out = str(tmp_path_factory.mktemp("kimi") / "ckpt")
    buf = io.StringIO()
    with redirect_stdout(buf):
        main(["--preset", "docs-kimi-linear", "--steps", "30", "--out", out])
    return json.loads(buf.getvalue().strip().splitlines()[-1]), out


def test_fit_trains_the_preset_and_reports_expert_load(trained):
    report, _ = trained
    assert report["final_loss"] < report["first_loss"] - 0.5
    stats = report["model_stats"]
    snap = REGISTRY.snapshot()
    for name in ("moe.pairs_routed", "moe.pairs_here", "moe.expert_load_max",
                 "moe.load_max_over_mean", "moe.tiles_run", "moe.rows_run"):
        assert snap["gauges"][name] == stats[name] > 0
    assert stats["moe.rows_run"] == 32 * stats["moe.tiles_run"]  # the preset's tile
    # 16 rows x 128 tokens x 4 experts a token x 4 expert layers
    assert stats["moe.pairs_routed"] == 16 * 128 * 4 * 4
    assert stats["moe.expert_load_max"] <= stats["moe.pairs_here"] \
        <= stats["moe.pairs_routed"]
    # the least even layer's fullest over its mean held expert (4 held)
    assert 1.0 <= stats["moe.load_max_over_mean"] <= 4.0
    assert snap["counters"]["fit.stats_n"] >= 1


def test_checkpoint_round_trip_and_serving_refusal(trained):
    from mlapi_tpu.checkpoint import load_checkpoint
    from mlapi_tpu.serving.engine import InferenceEngine, NotServable

    _, out = trained
    params, meta = load_checkpoint(out)
    assert meta.config["model"] == "kimi_linear_lm"
    model = get_model("kimi_linear_lm", **meta.config["model_kwargs"])
    ids = np.random.default_rng(1).integers(1, 260, (1, 48)).astype(np.int32)
    logits = jax.jit(model.apply)(params, ids)
    assert logits.shape == (1, 48, 260) and bool(jnp.all(jnp.isfinite(logits)))
    with pytest.raises(NotServable, match="cannot be served yet"):
        InferenceEngine.from_checkpoint(out)


def test_serving_cli_refuses_in_one_sentence(trained, capsys):
    from mlapi_tpu.serving.__main__ import main

    with pytest.raises(SystemExit) as e:
        main(["--checkpoint", trained[1], "--port", "0"])
    assert e.value.code == 2
    err = capsys.readouterr().err.strip().splitlines()[-1]
    assert "kimi_linear_lm checkpoint trains but cannot be served yet" in err


@pytest.mark.parametrize("with_stats", [False, True])
def test_step_has_a_fourth_output_only_for_a_model_with_stats(with_stats):
    """A model without ``apply_with_stats`` gets the three-output step
    it always had (``benchmark/train_child.py`` unpacks three values);
    this family's step hands its statistics on as a fourth."""
    import optax

    from mlapi_tpu.train.loop import make_train_step

    if with_stats:
        model = get_model("kimi_linear_lm", **KW)
        kw = {"stats_apply": model.apply_with_stats}
    else:
        model = get_model(
            "gpt_lm", vocab_size=VOCAB, hidden_size=32, num_layers=1,
            num_heads=2, max_positions=32, compute_dtype="float32")
        kw = {}
    params = model.init(jax.random.key(0))
    tx = optax.adamw(1e-3)
    step = make_train_step(model.apply, tx, task="lm", **kw)
    x = np.random.default_rng(2).integers(1, VOCAB, (2, 32)).astype(np.int32)
    out = step(params, tx.init(params), x, x)
    assert len(out) == (4 if with_stats else 3)
    assert np.isfinite(float(out[2]))
    if with_stats:
        assert set(out[3]) == {
            "moe.pairs_routed", "moe.pairs_here", "moe.expert_load_max",
            "moe.load_max_over_mean", "moe.tiles_run", "moe.rows_run"}
