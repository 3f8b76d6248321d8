"""``laguna_lm`` against its plain reference
(``benchmark/reference/laguna.py``: float32, ``Precision.HIGHEST``,
attention as an explicit masked softmax, every token through every held
expert): each layer kind alone and the whole model, values and every
gradient leaf; the window; the head groups; the partial YaRN rotation
against the literal per-position formula; the gate; the expert layer's
share; ``fit``, the checkpoint and the serving refusal.

Tiny widths that keep every ratio of the published model: five layers
of the same kinds (full + dense, three sliding and one full over
experts), 6 / 8 query heads over 2 K/V heads of 16, a window of 32 in a
row of 96 (and of 160 in a row of 640, where the kernels stream), 16
experts with 4 a token of which 4 are held.
"""

import io
import json
import math
import os
import sys
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlapi_tpu.models import get_model
from mlapi_tpu.models import laguna
from mlapi_tpu.models.llama import rotate
from mlapi_tpu.utils.metrics import REGISTRY

BENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from reference import laguna as ref  # noqa: E402

VOCAB = 300
TYPES = ["full_attention", "sliding_attention", "sliding_attention",
         "sliding_attention", "full_attention", "sliding_attention"]
HEADS = [6, 8, 8, 8, 6, 8]
MLPS = ["dense", "sparse", "sparse", "sparse", "sparse", "sparse"]
ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 64, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1},
}
KW = dict(
    vocab_size=VOCAB, hidden_size=64, num_layers=5, layer_types=TYPES,
    heads_per_layer=HEADS, mlp_layer_types=MLPS, num_kv_heads=2, head_dim=16,
    sliding_window=32, rope_full=ROPE["full_attention"],
    rope_sliding=ROPE["sliding_attention"], intermediate_size=128,
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, experts_held=[4, 4], moe_tile=8,
    compute_dtype="float32",
)
# the same model as the reference reads it (the configuration file's keys)
CFG = {
    "vocab_size": VOCAB, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 5, "num_key_value_heads": 2, "head_dim": 16,
    "rms_norm_eps": 1e-6, "sliding_window": 32, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "moe_routed_scaling_factor": 2.5, "num_experts": 4, "router_width": 16,
    "experts_held": [4, 4], "layer_types": TYPES,
    "num_attention_heads_per_layer": HEADS, "mlp_layer_types": MLPS,
    "rope_parameters": ROPE, "weights_seed": 7,
}
L = 96


@pytest.fixture(scope="module")
def flat():
    return ref.make_params(0, CFG)


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


def value_and_grads(f, lp, x, probe):
    y, grads = jax.value_and_grad(
        lambda lp, x: jnp.sum(f(lp, x) * probe), argnums=(0, 1))(lp, x)
    return f(lp, x), grads


# -- each layer kind alone ------------------------------------------------
# Tolerance 2e-4 of the reference's norm, values and gradients: both
# sides are float32 at the highest precision; the kernels sum a row's
# keys tile by tile with a running maximum where the reference takes
# one softmax over the whole row.
KIND_TOL = 2e-4


@pytest.mark.parametrize("kind,length,window", [
    ("sliding", L, 32),     # 8 / 2 heads, one tile, the window in the tile
    ("full", L, 32),        # 6 / 2 heads, partial YaRN rotary
    ("sliding", 640, 160),  # the streaming kernels' windowed grids
    ("full", 640, 160),     # the streaming kernels, causal, 6 / 2
    ("moe", L, 32),
    ("dense", L, 32),
])
def test_layer_kind_matches_reference(flat, kind, length, window):
    model = get_model("laguna_lm", **{**KW, "sliding_window": window})
    c = ref.settings({**CFG, "sliding_window": window})
    p = ref.nested(flat)
    layer, name = {"sliding": (1, "attn"), "full": (4, "attn"),
                   "moe": (1, "moe"), "dense": (0, "mlp")}[kind]
    lp = p[f"layer_{layer}"][name]
    x = jax.random.normal(jax.random.key(3), (2, length, 64), jnp.float32)
    probe = jax.random.normal(jax.random.key(4), (2, length, 64), jnp.float32)
    prog = {
        "sliding": lambda lp, x: model._attn(laguna.SLIDING, lp, x),
        "full": lambda lp, x: model._attn(laguna.FULL, lp, x),
        "dense": model._ffn, "moe": lambda lp, x: model._moe(lp, x)[0]}[kind]
    plain = {
        "sliding": lambda lp, x: ref.attention(lp, x, c, "s"),
        "full": lambda lp, x: ref.attention(lp, x, c, "f"),
        "dense": lambda lp, x: ref._ffn(lp, x, "float32"),
        "moe": lambda lp, x: ref.moe(lp, x, c)[0]}[kind]
    with jax.default_matmul_precision("highest"):
        y, (gp, gx) = jax.jit(lambda: value_and_grads(prog, lp, x, probe))()
    y_ref, (gp_ref, gx_ref) = jax.jit(
        lambda: value_and_grads(plain, lp, x, probe))()
    assert rel(y, y_ref) < KIND_TOL
    assert rel(gx, gx_ref) < KIND_TOL
    for k, g in flatten(gp_ref).items():
        assert rel(flatten(gp)[k], g) < KIND_TOL, k


def test_streaming_calls_are_counted_as_windowed_and_grouped():
    """``flash.calls_windowed`` / ``flash.calls_gqa`` count, once a
    TRACE, the calls that carry a window and the calls whose K/V heads
    are fewer than the query heads, beside ``flash.calls_traced``."""
    from mlapi_tpu.ops.pallas import flash_attention

    def counts():
        c = REGISTRY.snapshot()["counters"]
        return tuple(c.get("flash.calls_" + k, 0)
                     for k in ("traced", "windowed", "gqa"))

    q = jnp.ones((1, 32, 4, 16))
    before = counts()
    flash_attention(q, q[:, :, :2], q[:, :, :2], causal=True, window=8,
                    interpret=True)
    flash_attention(q, q, q, causal=True, interpret=True)
    after = counts()
    assert tuple(a - b for a, b in zip(after, before)) == (2, 1, 1)


# -- the whole model ------------------------------------------------------
def test_whole_model_matches_reference_in_float32(flat, ids):
    """Logits, loss and EVERY gradient leaf, float32 against float32.
    Tolerances: logits 1e-5 absolute (five blocks of float32 rounding);
    loss 1e-6; gradient leaves 5e-4 of the leaf's norm."""
    model = get_model("laguna_lm", **KW)
    params = ref.nested(flat)
    with jax.default_matmul_precision("highest"):
        logits, stats = jax.jit(model.apply_with_stats)(params, ids)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: program_loss(model, p, ids)))(params)
    r_logits, r_here = ref.forward(flat, jnp.asarray(ids), CFG)
    r_loss, _, r_grads = ref._loss_and_grad(
        flat, jnp.asarray(ids), ref.hashable(ref.settings(CFG)), "float32",
        None)
    assert float(jnp.max(jnp.abs(logits - r_logits))) < 1e-5
    assert abs(float(loss) - float(r_loss)) < 1e-6
    assert int(stats["moe.pairs_here"]) == int(r_here)
    assert int(stats["moe.pairs_routed"]) == ids.size * 4 * 4
    got = flatten(grads)
    assert set(got) == set(r_grads)
    for k, g in r_grads.items():
        assert rel(got[k], g) < 5e-4, k


def test_stats_count_the_expert_loops_tiles(flat, ids):
    """``apply_with_stats`` counts the grouped loops' trips and rows:
    ``moe.rows_run == moe.tiles_run x moe_tile >= moe.pairs_here``, and
    the padding is less than a tile for each held expert of each of the
    four expert layers."""
    model = get_model("laguna_lm", **KW)
    _, stats = jax.jit(model.apply_with_stats)(ref.nested(flat), ids)
    tiles, rows = int(stats["moe.tiles_run"]), int(stats["moe.rows_run"])
    here = int(stats["moe.pairs_here"])
    assert rows == tiles * KW["moe_tile"] >= here > 0
    assert rows - here < 4 * KW["experts_held"][1] * KW["moe_tile"]


def _grad_turn(got, r, worst=False):
    """The benchmark's number: the median (or the worst) over leaves of
    the part of the gradient's error that stands perpendicular to the
    reference, over the reference's norm."""
    out = []
    for k, g in r.items():
        g = np.asarray(g, np.float64).ravel()
        if not np.any(g):
            continue
        e = np.asarray(got[k], np.float64).ravel() - g
        out.append(np.linalg.norm(e - (e @ g) / (g @ g) * g)
                   / np.linalg.norm(g))
    return float(np.max(out) if worst else np.median(out))


@pytest.fixture(scope="module")
def reference_grads(flat, ids):
    def of(precision, fault=None):
        return ref._loss_and_grad(
            flat, jnp.asarray(ids), ref.hashable(ref.settings(CFG)),
            precision, fault)[2]
    return of


def test_bfloat16_program_is_told_from_8_bit_products(
        flat, ids, reference_grads):
    """The configuration's precision (bfloat16 products, float32
    accumulation) against the reference, beside the reference's own
    CONTROL one precision down (``int8_all``). At these widths bfloat16
    reads 0.02 and the control 0.08: a limit between them passes the
    one and fails the other."""
    model = get_model("laguna_lm", **{**KW, "compute_dtype": "bfloat16"})
    grads = flatten(jax.jit(jax.grad(
        lambda p: program_loss(model, p, ids)))(ref.nested(flat)))
    r = reference_grads("float32")
    mine, control = _grad_turn(grads, r), _grad_turn(
        reference_grads("int8_all"), r)
    assert mine < 0.04 < control, (mine, control)


@pytest.mark.parametrize("fault,least", [
    ("no_window", 0.2), ("no_gate", 0.2), ("plain_rope", 0.2)])
def test_a_left_out_mechanism_turns_the_gradient(
        flat, ids, reference_grads, fault, least):
    """The model's own planted faults, as the reference's ``fault``
    computes them in float32 (no rounding on either side: any gap is
    the mechanism's): the worst leaf's first gradient (a projection of
    a layer whose mechanism was left out) turns by a quarter of its
    length or more (0.42 without the window, 0.29 without the gate,
    0.27 with plain rotary in YaRN's place). The MEDIAN leaf hardly
    moves (token vectors of unit RMS carry most of a
    position's state past an attention layer), which is why the cell's
    limits hold these faults by a worst-leaf number."""
    r = reference_grads("float32")
    assert _grad_turn(reference_grads("float32", fault), r, worst=True) > least


# -- the window, the head groups ------------------------------------------
def test_window_shorter_than_the_row_differs_from_the_layer_run_full(flat):
    """A sliding layer with 32 of 96 positions in its window against
    the SAME layer (same weights, same plain rotary) run without one:
    they part from position 32 on and agree before it; the windowless
    run is the reference's ``no_window`` fault."""
    lp = ref.nested(flat)["layer_1"]["attn"]
    x = jax.random.normal(jax.random.key(5), (1, L, 64), jnp.float32)
    c = ref.settings(CFG)
    with jax.default_matmul_precision("highest"):
        win = get_model("laguna_lm", **KW)._attn(laguna.SLIDING, lp, x)
        full = get_model("laguna_lm", **{**KW, "sliding_window": L})._attn(
            laguna.SLIDING, lp, x)
    assert rel(win[:, :32], full[:, :32]) < 1e-5
    assert rel(win[:, 32:], full[:, 32:]) > 0.1
    assert rel(full, ref.attention(lp, x, c, "s", fault="no_window")) < KIND_TOL
    assert rel(win, ref.attention(lp, x, c, "s")) < KIND_TOL


@pytest.mark.parametrize("heads", [8, 6])
def test_query_head_reads_its_groups_kv_head(heads):
    """8 / 2 and 6 / 2 (the published 64 / 8 and 48 / 8): with K/V
    head 1 zeroed, exactly the query heads of group 0 (``h // (H / 2)
    == 0``) keep a non-zero output, in the layer's own head count."""
    kind = laguna.SLIDING if heads == 8 else laguna.FULL
    model = get_model("laguna_lm", **KW)
    lp = model.init(jax.random.key(2))["layer_1" if heads == 8 else "layer_4"][
        "attn"]
    assert lp["q"].shape == (64, heads * 16)
    v = lp["v"].at[:, 16:].set(0.0)
    x = jax.random.normal(jax.random.key(6), (1, L, 64), jnp.float32)
    for head in range(heads):
        o = jnp.zeros((heads * 16, 64)).at[head * 16:head * 16 + 16, :16].set(
            jnp.eye(16))
        y = model._attn(kind, {**lp, "v": v, "o": o}, x)
        assert bool(jnp.any(y != 0)) == (head // (heads // 2) == 0), head


# -- rotary ----------------------------------------------------------------
@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention",
                                  "plain_partial"])
def test_rotation_matches_the_literal_per_position_formula(kind):
    """``llama.rotate`` fed ``laguna.rope_table``'s data against loops
    over positions and lane pairs written from the HF formulas
    (``_compute_yarn_parameters``, ``rotate_half``): YaRN over half the
    lanes, plain rotary over all, plain rotary over half."""
    rope = dict(ROPE.get(kind) or {"rope_type": "default", "rope_theta": 10000,
                                   "partial_rotary_factor": 0.5})
    d, n = 16, 40
    x = np.asarray(jax.random.normal(jax.random.key(8), (1, n, 2, d)))
    inv_freq, dims, scale = laguna.rope_table(rope, d)
    pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (1, n))
    got = np.asarray(rotate(jnp.asarray(x), pos, inv_freq, rot_dims=dims,
                            scale=scale))

    r = int(d * rope["partial_rotary_factor"])
    theta = rope["rope_theta"]
    want = x.copy()
    for i in range(r // 2):
        f = theta ** (-2 * i / r)
        factor = 1.0
        if rope["rope_type"] == "yarn":
            orig = rope["original_max_position_embeddings"]

            def dim_of(rot):
                return r * math.log(orig / (rot * 2 * math.pi)) / (
                    2 * math.log(theta))

            lo = max(math.floor(dim_of(rope["beta_fast"])), 0)
            hi = min(math.ceil(dim_of(rope["beta_slow"])), r - 1)
            ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
            f = f / rope["factor"] * ramp + f * (1 - ramp)
            factor = rope["attention_factor"]
        for t in range(n):
            c, s = factor * math.cos(t * f), factor * math.sin(t * f)
            a, b = x[0, t, :, i], x[0, t, :, i + r // 2]
            want[0, t, :, i] = a * c - b * s
            want[0, t, :, i + r // 2] = b * c + a * s
    assert dims == r
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the lanes beyond the rotated ones pass untouched
    assert np.array_equal(got[..., r:], x[..., r:])
    # and the reference's own rotation is the same one
    c = ref.settings(CFG)
    if kind in ROPE:
        np.testing.assert_allclose(
            np.asarray(ref.rope(jnp.asarray(x), c, kind[0])), want, atol=2e-5)


# -- the gate ---------------------------------------------------------------
def test_gate_is_one_sigmoid_a_head_from_the_normed_input(flat):
    """``W_g = 0`` makes every head's gate 1/2: the layer then gives
    half of what the reference gives with its gate left out; with the
    drawn ``W_g`` it gives the reference's gated output and differs from
    the ungated one."""
    lp = ref.nested(flat)["layer_4"]["attn"]
    c = ref.settings(CFG)
    x = jax.random.normal(jax.random.key(9), (1, L, 64), jnp.float32)
    model = get_model("laguna_lm", **KW)
    ungated = ref.attention(lp, x, c, "f", fault="no_gate")
    with jax.default_matmul_precision("highest"):
        halved = model._attn(
            laguna.FULL, {**lp, "gate": jnp.zeros_like(lp["gate"])}, x)
        gated = model._attn(laguna.FULL, {**lp, "gate": 50 * lp["gate"]}, x)
    assert lp["gate"].shape == (64, 6)
    assert rel(halved, 0.5 * ungated) < KIND_TOL
    assert rel(gated, ref.attention(
        {**lp, "gate": 50 * lp["gate"]}, x, c, "f")) < KIND_TOL
    assert rel(gated, 0.5 * ungated) > 0.05


# -- the share is the model's ---------------------------------------------
def test_four_shares_add_up_to_the_uncut_layer(flat):
    """Four chips of four experts each (the configuration's eight of
    32, at tiny size): the parts their expert layers give, with the
    shared expert (which every chip computes alike) counted once, add
    up to the UNCUT reference's layer output (all 16 experts, dense).
    1e-5 of the norm: float32, sums in another order."""
    c_all = ref.settings({**CFG, "num_experts": 16, "experts_held": [0, 16]})
    rng = jax.random.split(jax.random.key(11), 4)
    router = {"router": 0.5 * jax.random.normal(rng[0], (64, 16))}
    experts = {k: 0.1 * jax.random.normal(r, (16, *s)) for (k, s), r in zip(
        {"gate": (64, 32), "up": (64, 32), "down": (32, 64)}.items(),
        jax.random.split(rng[2], 3))}
    shared = ref.nested(flat)["layer_1"]["moe"]["shared"]
    x = jax.random.normal(rng[3], (2, L, 64))
    whole, pairs = ref.moe(
        {**router, "experts": experts, "shared": shared}, x, c_all)
    assert int(pairs) == x.shape[0] * L * 4
    with jax.default_matmul_precision("highest"):
        shared_part = get_model("laguna_lm", **KW)._ffn(shared, x)
        total, here = shared_part, 0
        for s in range(4):
            model = get_model(
                "laguna_lm", **{**KW, "experts_held": [4 * s, 4]})
            held = {k: v[4 * s:4 * s + 4] for k, v in experts.items()}
            y, (pairs_here, _, _) = model._moe(
                {**router, "experts": held, "shared": shared}, x)
            total = total + (y - shared_part)
            here += int(pairs_here)
    assert here == int(pairs)  # every pair is some share's
    assert rel(total, whole) < 1e-5


def test_weights_are_the_configurations_not_the_runs():
    """``make_params`` and ``draw`` take ``weights_seed`` and ignore
    the run's seed or key: two runs' weights are the same arrays,
    another ``weights_seed`` gives others."""
    a, b = ref.make_params(1, CFG), ref.make_params(2 ** 31 + 5, CFG)
    other = ref.make_params(1, {**CFG, "weights_seed": 8})
    drawn = ref.draw(ref.param_spec(CFG), ref.seed_key(*ref.split_seed(99)))
    for k in a:
        assert np.array_equal(a[k], b[k])
        # eager against jitted: the same draw to the last bit or two
        np.testing.assert_allclose(drawn[k], a[k], atol=1e-7)
    assert not np.array_equal(a["embed"], other["embed"])
    assert set(a) == set(flatten(get_model("laguna_lm", **KW).init(
        jax.random.key(0))))


def test_layer_lists_are_checked():
    model = get_model("laguna_lm", **KW)
    assert model.layer_types[:5] == tuple(TYPES[:5]) and model.held == (4, 4)
    with pytest.raises(ValueError, match="fewer than"):
        get_model("laguna_lm", **{**KW, "heads_per_layer": [6, 8]})
    with pytest.raises(ValueError, match="kv heads"):
        get_model("laguna_lm", **{**KW, "heads_per_layer": [6, 7, 8, 8, 6]})
    with pytest.raises(ValueError, match="layer type"):
        get_model("laguna_lm", **{**KW, "layer_types": ["chunked"] * 5})
    with pytest.raises(ValueError, match="experts_held"):
        get_model("laguna_lm", **{**KW, "experts_held": [14, 4]})


# -- fit, the checkpoint, the CLIs ----------------------------------------
@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """``python -m mlapi_tpu.train --preset docs-laguna`` (a few
    steps), in this process: its closing JSON and its checkpoint."""
    from mlapi_tpu.train.__main__ import main

    out = str(tmp_path_factory.mktemp("laguna") / "ckpt")
    buf = io.StringIO()
    with redirect_stdout(buf):
        main(["--preset", "docs-laguna", "--steps", "30", "--out", out])
    return json.loads(buf.getvalue().strip().splitlines()[-1]), out


def test_fit_trains_the_preset_and_reports_expert_load(trained):
    report, _ = trained
    assert report["final_loss"] < report["first_loss"] - 0.5
    stats = report["model_stats"]
    # 16 rows x 128 tokens x 4 experts a token x 4 expert layers
    assert stats["moe.pairs_routed"] == 16 * 128 * 4 * 4
    assert stats["moe.expert_load_max"] <= stats["moe.pairs_here"] \
        <= stats["moe.pairs_routed"]
    assert 1.0 <= stats["moe.load_max_over_mean"] <= 8.0


def test_checkpoint_round_trip_and_serving_refusal(trained, capsys):
    from mlapi_tpu.checkpoint import load_checkpoint
    from mlapi_tpu.serving.__main__ import main
    from mlapi_tpu.serving.engine import InferenceEngine, NotServable

    _, out = trained
    params, meta = load_checkpoint(out)
    assert meta.config["model"] == "laguna_lm"
    model = get_model("laguna_lm", **meta.config["model_kwargs"])
    ids = np.random.default_rng(1).integers(1, 260, (1, 48)).astype(np.int32)
    logits = jax.jit(model.apply)(params, ids)
    assert logits.shape == (1, 48, 260) and bool(jnp.all(jnp.isfinite(logits)))
    with pytest.raises(NotServable, match="cannot be served yet"):
        InferenceEngine.from_checkpoint(out)
    with pytest.raises(SystemExit) as e:
        main(["--checkpoint", out, "--port", "0"])
    assert e.value.code == 2
    err = capsys.readouterr().err.strip().splitlines()[-1]
    assert "laguna_lm checkpoint trains but cannot be served yet" in err
