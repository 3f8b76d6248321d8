"""The main path's Pallas kernels, handed to the TPU's own compiler
for a DESCRIBED v5e (``jax.experimental.topologies``): nothing runs
and no chip is attached, but what Mosaic refuses on the chip — a
block whose minor dims break the tiling, too much VMEM, a kernel
GSPMD cannot partition — is refused here, at BERT-base and
GPT-2-small geometry, before any chip time is spent.

This is the ONE file that touches libtpu in tier-1. The topology is
described inside a module-scoped, non-autouse fixture, never at
import, in a ``skipif``, in ``parametrize`` or in ``conftest.py``:
one process at a time may load the library, every xdist worker
imports every test file, and only the worker that is dealt this file
may reach for it. A compile against a described device is not a chip
run and proves nothing about results or speed — ``chip_smoke.py``
does that on the chip.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from mlapi_tpu.ops.pallas import (
    decode_attention,
    decode_attention_tp,
    extend_attention,
    flash_attention,
    flash_attention_on_mesh,
    paged_decode_attention,
    paged_extend_attention,
)

# BERT-base: 12 heads x 64, the sst2-bert preset's batch and length.
BERT_B, BERT_L, HEADS, HEAD_DIM = 32, 128, 12, 64
# The benchmark cell bert-base.finetune: 128 rows of 128 tokens.
CELL_B = 128
# GPT-2 small serving: 12 KV heads x 64, 1024 positions, 16-token
# pages (what the verify skill and chip_smoke drive), a 16-token
# extend span, 4 rows.
GEN_B, GEN_L, PAGE, SPAN = 4, 1024, 16, 16
POOL_PAGES = GEN_B * GEN_L // PAGE + 1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    """(data=1, model=4): the generative-serving TP layout."""
    return Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but can never be read back without a chip; keep these
    compiles out of it (and silent)."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _cache(shape, fmt, sharding, scale_sharding=None):
    """A KV operand in one of the two stored formats."""
    if fmt == "int8":
        return {
            "q": _shape(shape, jnp.int8, sharding),
            "scale": _shape(
                shape[:-1] + (1,), jnp.float32, scale_sharding or sharding
            ),
        }
    return _shape(shape, jnp.bfloat16, sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("batch", [BERT_B, CELL_B])
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_flash_attention_bert_base(one_chip, direction, batch):
    """The sst2-bert preset's attention: forward, and the custom-VJP
    backward kernels, at the preset's batch and at the benchmark
    cell's (128 x 128 tokens). One tile a sequence, so the row
    statistics are ``[B, H, L]`` along lanes: no float32 array with a
    trailing dimension of 1 (a 128-lane tile a number) is left."""
    qkv = _shape((batch, BERT_L, HEADS, HEAD_DIM), jnp.bfloat16, one_chip)
    mask = _shape((batch, BERT_L), jnp.float32, one_chip)

    def fwd(q, k, v, m):
        return flash_attention(q, k, v, m, interpret=False)

    if direction == "forward":
        fn = fwd
    else:
        def fn(q, k, v, m):
            loss = lambda q, k, v: jnp.sum(  # noqa: E731
                fwd(q, k, v, m).astype(jnp.float32) ** 2
            )
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    txt = _compile(fn, qkv, qkv, qkv, mask).as_text()
    assert txt.count('custom_call_target="tpu_custom_call"') == (
        1 if direction == "forward" else 3
    )
    assert not re.findall(r"f32\[[\d,]+,1\]", txt)


_KERNEL = r"^\s*%?([a-z_]+?)[.\d]* = .*custom_call_target=\"tpu_custom_call\""
# a differentiated streaming call's three kernels, as the chip names them
_FLASH = ["flash_attention_dkv", "flash_attention_dq", "flash_attention_fwd"]
# a differentiated expert layer's kernel (the backward); the name of the
# program's operation carries the transforms around it
_EXPERTS = ["grouped_ffn_bwd"]


def _kernels(txt):
    """The Pallas kernels of a compiled program by the names they were
    given (``name=`` of the ``pallas_call``), sorted."""
    found = re.findall(_KERNEL, txt, re.M)
    return sorted(next((k for k in _FLASH + _EXPERTS if k in n), n)
                  for n in found)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_flash_attention_latent_heads_stream(one_chip, direction):
    """The latent-attention call of ``kimi_linear_lm`` at the cell
    ``kimi-linear.pretrain_8k``: one row of 8,192 positions, 32 heads,
    scores over 192 (128 + the 64-wide shared key part), values over
    128, causal. The streaming kernels take it (16 tiles of 512), with
    a 192-wide block for q and k and a 128-wide one for v and the
    output."""
    b, l, h = 1, 8192, 32
    qk = _shape((b, l, h, 192), jnp.bfloat16, one_chip)
    v = _shape((b, l, h, 128), jnp.bfloat16, one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    if direction == "forward":
        fn = fwd
    else:
        def fn(q, k, v):
            loss = lambda q, k, v: jnp.sum(  # noqa: E731
                fwd(q, k, v).astype(jnp.float32) ** 2
            )
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    compiled = _compile(fn, qk, qk, v)
    # each pass under its own name, within the scoped VMEM it is given
    # (a kernel over its limit is refused here)
    assert _kernels(compiled.as_text()) == (
        ["flash_attention_fwd"] if direction == "forward" else _FLASH)
    out = jax.eval_shape(fn, qk, qk, v)
    shapes = [o.shape for o in jax.tree.leaves(out)]
    assert shapes == (
        [(b, l, h, 128)] if direction == "forward"
        else [(b, l, h, 192), (b, l, h, 192), (b, l, h, 128)]
    )


def _in_scope(txt, scope):
    """The instructions of a compiled program whose ``op_name`` holds
    ``scope`` (a ``jax.named_scope``)."""
    return [line for line in txt.splitlines()
            if re.search(rf'op_name="[^"]*{re.escape(scope)}[/"]', line)]


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("heads,window", [(64, 512), (48, None)])
def test_flash_attention_laguna_calls_stream(one_chip, heads, window,
                                             direction):
    """The two attention calls of ``laguna_lm`` at the cell
    ``laguna-xs2.pretrain_8k``: one row of 8,192 positions, heads of
    128, 8 K/V heads under 64 query heads with a window of 512 (the
    windowed grids: 2 of 16 k-tiles a q-tile, 2 of 16 q-tiles a k-tile)
    and under 48 causal. The streaming kernels take both, K/V indexed
    ``h // group`` and never repeated."""
    b, l, kvh, d = 1, 8192, 8, 128
    q = _shape((b, l, heads, d), jnp.bfloat16, one_chip)
    kv = _shape((b, l, kvh, d), jnp.bfloat16, one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               interpret=False)

    if direction == "forward":
        fn = fwd
    else:
        def fn(q, k, v):
            loss = lambda q, k, v: jnp.sum(  # noqa: E731
                fwd(q, k, v).astype(jnp.float32) ** 2
            )
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    txt = _compile(fn, q, kv, kv).as_text()
    assert _kernels(txt) == (
        ["flash_attention_fwd"] if direction == "forward" else _FLASH)
    if direction == "forward":  # no K or V made as wide as the queries
        assert not re.findall(rf"\[{b},{heads},{l},{d}\]\S* broadcast\(", txt)
    shapes = [o.shape for o in jax.tree.leaves(jax.eval_shape(fn, q, kv, kv))]
    assert shapes == ([(b, l, heads, d)] if direction == "forward" else
                      [(b, l, heads, d), (b, l, kvh, d), (b, l, kvh, d)])


def _laguna_kwargs():
    import json

    with open(os.path.join(os.path.dirname(os.path.dirname(__file__)),
                           "benchmark", "configs",
                           "laguna-xs2-ep8.json")) as f:
        return json.load(f)["program"]["model_kwargs"]


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_laguna_block_at_published_widths(one_chip, monkeypatch, kind):
    """One whole block of ``laguna_lm`` at the published widths (1 x
    8192; norm, projections, rotary, the flash call, headwise gate,
    output projection, router, 32 held experts, shared expert) under
    the model's ``jax.checkpoint`` policy, gradient in the parameters:
    three flash kernels (forward once, dq, dk/dv), the expert layer's
    backward kernel, a rotation with no gather, and in the expert
    layer's backward (``moe.experts`` under ``transpose``) no ``while``
    loop and no scatter into a ``[8192, 2048]`` operand: the rows move
    inside the kernel."""
    from mlapi_tpu.models import experts, get_model, laguna

    # code that asks the backend sees the CPU here: steer it in the test
    monkeypatch.setattr(laguna, "pallas_interpret", lambda: False)
    monkeypatch.setattr(experts, "pallas_interpret", lambda: False)
    model = get_model("laguna_lm", **dict(
        _laguna_kwargs(), vocab_size=1024, num_layers=1, layer_types=[kind],
        heads_per_layer=[64 if kind == "sliding_attention" else 48],
        mlp_layer_types=["sparse"]))
    assert model.remat
    params = jax.tree.map(
        lambda a: _shape(a.shape, a.dtype, one_chip),
        jax.eval_shape(model.init, jax.random.key(0)))
    ids = _shape((1, 8192), jnp.int32, one_chip)
    txt = _compile(
        jax.grad(lambda p, x: jnp.mean(model.apply(p, x))), params, ids
    ).as_text()
    assert _kernels(txt) == sorted(_FLASH + _EXPERTS)
    assert not re.findall(r"\[[\d,]*8192,\d+,128\]\S* gather\(", txt)
    back = [line for line in _in_scope(txt, "moe.experts")
            if "transpose(" in line]
    assert any("tpu_custom_call" in line for line in back)
    assert not [line for line in back if " while(" in line]
    assert not [line for line in back
                if re.search(r"= \w+\[8192,2048\]\S* scatter\(", line)]


def test_laguna_step_fits_the_chip(one_chip, monkeypatch):
    """The cell ``laguna-xs2.pretrain_8k``'s whole step as ``fit``
    builds it (``make_train_step``, AdamW, 1 x 8192, all five layers at
    the published widths): the chip's compiler takes it, it holds 15
    flash kernels (five layers x forward, dq, dk/dv: no forward runs
    twice) and the four expert layers' backward kernels, and weights +
    AdamW state + the step's temporaries stay under the 15.75 GB a v5e
    reports as its limit (read here: 8.30 GB of arguments, all aliased
    to the outputs, + 4.32 GB)."""
    import optax

    from mlapi_tpu.models import experts, get_model, laguna
    from mlapi_tpu.train.loop import make_train_step

    monkeypatch.setattr(laguna, "pallas_interpret", lambda: False)
    monkeypatch.setattr(experts, "pallas_interpret", lambda: False)
    model = get_model("laguna_lm", **_laguna_kwargs())
    tx = optax.adamw(1e-4)
    shaped = lambda t: jax.tree.map(  # noqa: E731
        lambda a: _shape(a.shape, a.dtype, one_chip), t)
    params = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(params)) == 691_623_936
    step = make_train_step(model.apply, tx, task="lm",
                           stats_apply=model.apply_with_stats)
    ids = _shape((1, 8192), jnp.int32, one_chip)
    compiled = step.lower(shaped(params), shaped(jax.eval_shape(
        tx.init, params)), ids, ids).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    assert mem.alias_size_in_bytes > 0.99 * mem.argument_size_in_bytes
    assert _kernels(compiled.as_text()) == sorted(_FLASH * 5 + _EXPERTS * 4)


def _sized_f32(txt, ops, elements):
    """The float32 results of ``ops`` instructions (a regular
    expression over HLO opcodes) that hold ``elements`` numbers or more.
    A ``bitcast`` moves nothing and is none of them."""
    return [m for m in re.findall(rf"= f32\[([\d,]+)\]\S* (?:{ops})\(", txt)
            if np.prod([int(n) for n in m.split(",")]) >= elements]


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_kda_kernels_at_the_cell_call(one_chip, direction):
    """A KDA layer's call of ``kimi_linear_lm`` at the cell
    ``kimi-linear.pretrain_8k``, ``1 x 8192`` positions, 32 heads of
    128: the seven operands as the model's projections leave them
    (``[B, L, H * D]``, ``beta [B, L, H]``, the norm's scale ``[D]``),
    forward and the gradient of a sum in all seven. Mosaic takes the
    kernels (one custom call forward; the forward that saves the states
    and the backward in the gradient), no ``while`` of the XLA scan is
    left, and XLA lays NOTHING out around them: the blocks are lane
    slabs of the projections' own tiling and the per-head reductions are
    the kernels', so no operand-sized ``copy``, ``transpose``, pad or
    ``reshape`` is in the program (the rows layout paid 5 / 9
    ``reshape``s here: PERF.md, PR 30)."""
    from mlapi_tpu.ops.pallas.kda import kda_layer

    b, l, h, d = 1, 8192, 32, 128
    x = _shape((b, l, h * d), jnp.float32, one_chip)
    beta = _shape((b, l, h), jnp.float32, one_chip)
    scale = _shape((d,), jnp.float32, one_chip)
    args = (x, x, x, x, beta, x, scale)

    def fwd(*a):
        return kda_layer(*a, eps=1e-5, compute_dtype="bfloat16",
                         interpret=False)

    if direction == "forward":
        fn = fwd
    else:
        def fn(*a):
            return jax.grad(lambda *a: jnp.sum(fwd(*a)),
                            argnums=tuple(range(7)))(*a)

    txt = _compile(fn, *args).as_text()
    assert txt.count('custom_call_target="tpu_custom_call"') == (
        1 if direction == "forward" else 2
    )
    moved = _sized_f32(txt, "copy|transpose|pad|reshape", b * l * h * d)
    assert not moved, moved
    assert " while(" not in txt
    shapes = [o.shape for o in jax.tree.leaves(jax.eval_shape(fn, *args))]
    assert shapes == ([(b, l, h * d)] if direction == "forward" else
                      [(b, l, h * d)] * 4 + [(b, l, h), (b, l, h * d), (d,)])


def _kimi_blocks(one_chip, monkeypatch, **layers):
    """The compiled gradient, in the parameters, of ``kimi_linear_lm``
    at the published widths cut to the given layers (1 x 8192, each
    block under the model's ``jax.checkpoint`` policy), as text."""
    import json

    from mlapi_tpu.models import get_model, kimi_linear

    # code that asks the backend sees the CPU here: steer it in the test
    monkeypatch.setattr(kimi_linear, "pallas_interpret", lambda: False)
    with open(os.path.join(os.path.dirname(os.path.dirname(__file__)),
                           "benchmark", "configs",
                           "kimi-linear-48b-a3b-ep32.json")) as f:
        kw = json.load(f)["program"]["model_kwargs"]
    model = get_model("kimi_linear_lm", **dict(kw, vocab_size=1024, **layers))
    assert model.remat
    params = jax.tree.map(
        lambda a: _shape(a.shape, a.dtype, one_chip),
        jax.eval_shape(model.init, jax.random.key(0)))
    ids = _shape((1, 8192), jnp.int32, one_chip)
    return _compile(
        jax.grad(lambda p, x: jnp.mean(model.apply(p, x))), params, ids
    ).as_text()



def test_recomputed_blocks_run_each_forward_kernel_once(one_chip, monkeypatch):
    """Two blocks of ``kimi_linear_lm`` at the published widths (KDA +
    experts, MLA + experts; 1 x 8192), each under the model's
    ``jax.checkpoint``: the compiled gradient holds ONE ``kda_fwd``, one
    ``kda_bwd`` and three flash kernels (forward, dq, dk/dv). A bare
    checkpoint made each forward kernel again in the backward pass (two
    and four); the policy keeps what the producers name, and the chip's
    compiler honours it at these shapes."""
    txt = _kimi_blocks(one_chip, monkeypatch, num_layers=2, kda_layers=[1],
                       full_attn_layers=[2], first_k_dense_replace=0)
    kernels = re.findall(_KERNEL, txt, re.M)
    assert sorted(kernels) == _FLASH + ["kda_bwd", "kda_fwd"]


def test_kda_layer_has_no_layout_copy(one_chip, monkeypatch):
    """One KDA block of ``kimi_linear_lm`` at the published widths (32
    heads of 128, 1 x 8192; norm, projections, convolutions, gates, the
    call, output projection, dense FFN) under the model's checkpoint
    policy, gradient in the parameters. Between the projections and the
    output projection everything XLA sees is elementwise on ``[B, L, H
    * D]`` and the per-head reductions are the kernels', so the
    compiled block holds no ``copy``, ``transpose`` or ``reshape`` of
    an operand-sized float32 tensor around ``kda_fwd`` / ``kda_bwd``
    (the parent of PR 35 compiled 16 ``copy`` and 18 ``reshape`` here),
    and no ``[.., 32, 128]`` tensor at all."""
    txt = _kimi_blocks(one_chip, monkeypatch, num_layers=1, kda_layers=[1],
                       full_attn_layers=[], first_k_dense_replace=1)
    assert sorted(re.findall(_KERNEL, txt, re.M)) == ["kda_bwd", "kda_fwd"]
    moved = _sized_f32(txt, "copy|transpose|reshape", 8192 * 4096)
    assert not moved, moved
    assert not re.findall(r"\w+\[[\d,]*8192,32,128\]", txt)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_grouped_ffn_kernel_at_the_laguna_cell(one_chip, monkeypatch,
                                               direction):
    """The expert layer of the cell ``laguna-xs2.pretrain_8k``'s grouped
    product (``models.experts.grouped_ffn`` with the kernel's backward):
    8,192 tokens x 8 choices, tiles of 256, planned over 32 held experts
    of 2048 x 512, forward and the gradient of a sum in ``x``, the
    routing weights and the three kernels. The forward is the XLA loop
    and holds no kernel; the gradient is the kernel alone (the sum's
    gradient needs nothing the forward makes), Mosaic takes it in the
    VMEM limit with the intermediate width in one block, and no XLA
    ``while`` loop is left outside the plan. Kimi's hidden 2304 is no
    whole number of float32 tiles a token's row: it keeps the loop's
    backward."""
    from mlapi_tpu.models import experts
    from mlapi_tpu.ops.pallas import grouped_ffn as gk

    t, k, tile, count, hid, inter = 8192, 8, 256, 32, 2048, 512
    assert gk._block(hid, inter, tile, 2) == inter
    assert not gk.takes(2304, 1024, tile)

    def fwd(x, idx, w, wg, wu, wd):
        rows, tile_expert, n_tiles, _ = jax.named_call(
            experts.plan, name="plan")(idx, 0, count, tile)
        return experts.grouped_ffn(x, w, wg, wu, wd, rows, tile_expert,
                                   n_tiles, tile, k, True)

    fn = fwd
    if direction == "backward":
        def fn(x, idx, *a):
            return jax.grad(lambda *a: jnp.sum(fwd(a[0], idx, *a[1:])),
                            argnums=(0, 1, 2, 3, 4))(x, *a)

    args = (_shape((t, hid), jnp.bfloat16, one_chip),
            _shape((t, k), jnp.int32, one_chip),
            _shape((t * k,), jnp.float32, one_chip),
            _shape((count, hid, inter), jnp.bfloat16, one_chip),
            _shape((count, hid, inter), jnp.bfloat16, one_chip),
            _shape((count, inter, hid), jnp.bfloat16, one_chip))
    monkeypatch.setattr(experts, "pallas_interpret", lambda: False)
    txt = _compile(fn, *args).as_text()
    assert _kernels(txt) == ([] if direction == "forward" else _EXPERTS)
    outside_plan = [line for line in txt.splitlines()
                    if " while(" in line and "plan" not in line]
    assert bool(outside_plan) == (direction == "forward")
    shapes = [o.shape for o in jax.tree.leaves(jax.eval_shape(fn, *args))]
    assert shapes == ([(t, hid)] if direction == "forward" else
                      [(t, hid), (t * k,), (count, hid, inter),
                       (count, hid, inter), (count, inter, hid)])


def test_flash_attention_layer_has_no_layout_copy(one_chip):
    """An attention layer as ``models/bert.py`` writes it (projections,
    ``[B, L, H, D]`` reshapes, flash, output projection) at the cell's
    shapes, forward and backward: the kernels read and write the
    model's own ``[B, L, H*D]`` layout, so the compiled layer holds no
    ``copy`` or ``transpose`` of an attention-sized tensor around the
    three custom calls."""
    hidden = HEADS * HEAD_DIM
    x = _shape((CELL_B, BERT_L, hidden), jnp.bfloat16, one_chip)
    w = _shape((hidden, hidden), jnp.bfloat16, one_chip)
    mask = _shape((CELL_B, BERT_L), jnp.float32, one_chip)

    def layer(x, wq, wk, wv, wo, m):
        q, k, v = (
            (x @ p).reshape(CELL_B, BERT_L, HEADS, HEAD_DIM)
            for p in (wq, wk, wv)
        )
        ctx = flash_attention(q, k, v, m, interpret=False)
        out = ctx.reshape(CELL_B, BERT_L, hidden) @ wo
        return jnp.sum(out.astype(jnp.float32) ** 2)

    txt = _compile(
        jax.grad(layer, argnums=(0, 1, 2, 3, 4)), x, w, w, w, w, mask
    ).as_text()
    assert txt.count('custom_call_target="tpu_custom_call"') == 3
    sized = rf"\[{CELL_B},{BERT_L},(?:{HEADS},{HEAD_DIM}|{hidden})\]"
    moved = re.findall(rf"= \w+{sized}\S* (?:copy|transpose)\(", txt)
    assert not moved, moved
    assert not re.findall(r"f32\[[\d,]+,1\]", txt)


@pytest.mark.parametrize("shape", [(4, 1), (1, 4)])
def test_flash_attention_on_mesh_bert_base(topo, shape):
    """The same attention on four chips, data- and tensor-parallel:
    sharded operands reach the kernel only through ``shard_map``
    (handed to GSPMD, Mosaic answers "cannot be automatically
    partitioned"), forward and backward, with no all-gather put
    around it."""
    mesh4 = Mesh(np.array(topo.devices).reshape(shape), ("data", "model"))
    on = NamedSharding(mesh4, P("data", None, "model", None))
    qkv = _shape((BERT_B, BERT_L, HEADS, HEAD_DIM), jnp.bfloat16, on)
    mask = _shape((BERT_B, BERT_L), jnp.float32,
                  NamedSharding(mesh4, P("data", None)))

    def fn(q, k, v, m):
        loss = lambda q, k, v: jnp.sum(  # noqa: E731
            flash_attention_on_mesh(
                mesh4, q, k, v, m, interpret=False
            ).astype(jnp.float32) ** 2
        )
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    txt = _compile(fn, qkv, qkv, qkv, mask).as_text()
    assert "tpu_custom_call" in txt
    assert "all-gather" not in txt


@pytest.mark.parametrize("fmt", ["bf16", "int8"])
@pytest.mark.parametrize(
    "kernel", ["decode", "extend", "paged_decode", "paged_extend"]
)
def test_cache_read_kernels_gpt2_small(one_chip, kernel, fmt):
    """The four cache-read kernels x both stored formats. The paged
    pair's k-tile IS the 16-token page (below the 128-lane width, and
    below int8's 32-row sublane tile): the layouts Mosaic must take."""
    u = 1 if "decode" in kernel else SPAN
    q = _shape((GEN_B, u, HEADS, HEAD_DIM), jnp.bfloat16, one_chip)
    mask_shape = (GEN_B, GEN_L) if u == 1 else (GEN_B, u, GEN_L)
    mask = _shape(mask_shape, jnp.float32, one_chip)
    if kernel.startswith("paged"):
        pool = _cache((POOL_PAGES, PAGE, HEADS, HEAD_DIM), fmt, one_chip)
        table = _shape((GEN_B, GEN_L // PAGE), jnp.int32, one_chip)
        fn = paged_decode_attention if u == 1 else paged_extend_attention
        compiled = _compile(
            functools.partial(fn, interpret=False), q, pool, pool, table, mask
        )
    else:
        cache = _cache((GEN_B, GEN_L, HEADS, HEAD_DIM), fmt, one_chip)
        fn = decode_attention if u == 1 else extend_attention
        compiled = _compile(
            functools.partial(fn, interpret=False), q, cache, cache, mask
        )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("fmt", ["bf16", "int8"])
def test_decode_attention_tp_runs_per_shard(mesh, fmt):
    """The open question of ROADMAP S3: under a model-axis mesh, does
    the compiled kernel run PER SHARD on its local heads, or does
    GSPMD all-gather the head-sharded cache around the opaque custom
    call? ``decode_attention_tp``'s shard_map must leave the kernel in
    the program with no all-gather of a cache-sized operand."""
    heads = NamedSharding(mesh, P(None, None, "model", None))
    rep = NamedSharding(mesh, P())
    q = _shape((GEN_B, 1, HEADS, HEAD_DIM), jnp.bfloat16, heads)
    cache = _cache((GEN_B, GEN_L, HEADS, HEAD_DIM), fmt, heads)
    mask = _shape((GEN_B, GEN_L), jnp.float32, rep)

    compiled = _compile(
        lambda q, k, v, m: decode_attention_tp(
            mesh, q, k, v, m, interpret=False
        ),
        q, cache, cache, mask,
    )
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt
    # Per-shard operands: 12 heads over 4 = 3 local KV heads.
    local = f"[{GEN_B},{GEN_L},{HEADS // 4},{HEAD_DIM}]"
    assert local in txt.replace(" ", ""), "kernel does not see local heads"
    gathers = [
        line for line in txt.splitlines()
        if "all-gather" in line and f"{GEN_L}" in line
    ]
    assert not gathers, gathers
