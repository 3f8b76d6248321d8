"""FSDP (ZeRO-style parameter + optimizer-state sharding) over the
third mesh axis.

The contract under test (ISSUE 1, docs/DESIGN.md §12):

- every ≥1-D param leaf above the size threshold is actually sharded
  over ``fsdp`` (largest free divisible dim), small leaves replicate;
- optimizer moments take the SAME layout as their params (that is the
  memory win — AdamW moments are 2x the params);
- the loss trajectory is the plain-DP trajectory to printed digits
  (FSDP changes where state lives, not the math);
- checkpoints round-trip sharded state and resume bit-exact;
- FSDP composes with TP, LoRA masking, and the sparse criteo path.
"""

import os

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from mlapi_tpu.datasets import get_dataset
from mlapi_tpu.models import get_model
from mlapi_tpu.parallel import (
    FSDP_MIN_SIZE,
    create_mesh,
    fsdp_spec_tree,
    params_for_model,
    shard_batch_for_mesh,
    state_shardings_like,
)
from mlapi_tpu.train import fit

MLP_KW = dict(num_features=64, num_classes=10, hidden_dims=[256, 128])
TINY_BERT = dict(
    num_classes=2, vocab_size=256, hidden_size=32, num_layers=2,
    num_heads=2, intermediate_size=64, max_positions=64,
)


@pytest.fixture(scope="module")
def mesh_fsdp8():
    """(data=1, fsdp=8, model=1): pure FSDP over all 8 virtual devices."""
    return create_mesh((1, 8, 1))


@pytest.fixture(scope="module")
def mesh_2x2x2():
    return create_mesh((2, 2, 2))


def _specs(tree):
    return {
        jax.tree_util.keystr(path): tuple(leaf.sharding.spec)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


def test_three_dim_mesh_gets_fsdp_axis():
    mesh = create_mesh((1, 8, 1))
    assert mesh.axis_names == ("data", "fsdp", "model")
    assert mesh.shape["fsdp"] == 8


def test_every_large_leaf_sharded_over_fsdp(mesh_fsdp8):
    """The spec rule, end to end through placement: every leaf at or
    above the threshold with a free divisible dim carries ``fsdp``;
    every leaf below the threshold does not."""
    for name, kwargs in (("mlp", MLP_KW), ("bert_classifier", TINY_BERT)):
        model = get_model(name, **kwargs)
        placed = params_for_model(
            model, model.init(jax.random.key(0)), mesh_fsdp8
        )
        for key, leaf in jax.tree_util.tree_leaves_with_path(placed):
            spec = tuple(leaf.sharding.spec)
            free_divisible = any(
                (i >= len(spec) or spec[i] is None) and d % 8 == 0
                for i, d in enumerate(leaf.shape)
            ) or "fsdp" in spec
            if leaf.size >= FSDP_MIN_SIZE and free_divisible:
                assert "fsdp" in spec, (
                    f"{name}{jax.tree_util.keystr(key)} {leaf.shape} "
                    f"not fsdp-sharded: {spec}"
                )
            else:
                assert "fsdp" not in spec, (
                    f"{name}{jax.tree_util.keystr(key)} {leaf.shape} "
                    f"sharded below threshold: {spec}"
                )


def test_fsdp_composes_with_tp_specs():
    """On a (1, 2, 4) mesh a TP model's specs keep their ``model``
    placement and gain ``fsdp`` on a DIFFERENT dim of the same leaf."""
    mesh = create_mesh((1, 2, 4))
    model = get_model("bert_classifier", **TINY_BERT)
    params = model.init(jax.random.key(0))
    specs = fsdp_spec_tree(
        params, model.param_shardings(), mesh.shape["fsdp"]
    )
    ffn_up = specs["layer_0"]["ffn_up"]["kernel"]
    assert tuple(ffn_up) == ("fsdp", "model")
    word = specs["embeddings"]["word"]
    assert tuple(word) == ("model", "fsdp")
    # No leaf ever uses one axis twice.
    for spec in jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P)):
        named = [a for a in spec if a is not None]
        assert len(named) == len(set(named)), spec


def test_moments_shard_like_params(mesh_fsdp8):
    """state_shardings_like mirrors param shardings onto adam moments
    (exact-shape match) and keeps step counters replicated."""
    import optax

    model = get_model("mlp", **MLP_KW)
    placed = params_for_model(
        model, model.init(jax.random.key(0)), mesh_fsdp8
    )
    tx = optax.adamw(1e-3)
    opt_sh = state_shardings_like(
        jax.eval_shape(tx.init, placed), placed, mesh_fsdp8
    )
    opt = jax.jit(tx.init, out_shardings=opt_sh)(placed)
    p_specs = _specs(placed)
    for key, leaf in jax.tree_util.tree_leaves_with_path(opt):
        ks = jax.tree_util.keystr(key)
        for p_key, p_spec in p_specs.items():
            if ks.endswith(p_key) and leaf.ndim:
                assert tuple(leaf.sharding.spec) == p_spec, (ks, p_key)
                break
        else:
            assert tuple(leaf.sharding.spec) == (), ks  # counters


def test_batch_shards_over_data_and_fsdp(mesh_2x2x2):
    x = np.zeros((8, 3), np.float32)
    placed = shard_batch_for_mesh(x, mesh_2x2x2)
    assert tuple(placed.sharding.spec)[0] == ("data", "fsdp")
    with pytest.raises(ValueError, match="not divisible"):
        shard_batch_for_mesh(np.zeros((6, 3), np.float32), mesh_2x2x2)


def test_trajectory_matches_plain_dp_digits_mlp(mesh_fsdp8):
    """The equivalence bar, stated honestly: the first steps are
    BIT-IDENTICAL to plain DP (the loss/grad math is unchanged), and
    a 100-step trajectory stays on the same path to the precision the
    collective allows — reduce-scatter sums partial gradients in a
    different order than all-reduce, so a single-ulp rounding
    difference enters within a few steps and amplifies chaotically
    (measured: bit-exact through step 2, ~1e-4 relative at step 40,
    ~1e-2 at step 120 — with identical eval accuracies throughout).
    docs/DESIGN.md §12 records the caveat."""
    splits = get_dataset("digits")
    kw = dict(
        batch_size=64, learning_rate=1e-3, optimizer="adamw",
        seed=0, eval_every=1,
    )
    r_dp = fit(get_model("mlp", **MLP_KW), splits,
               mesh=create_mesh((8, 1)), steps=2, **kw)
    r_fsdp = fit(get_model("mlp", **MLP_KW), splits,
                 mesh=mesh_fsdp8, steps=2, **kw)
    for h_dp, h_f in zip(r_dp.history, r_fsdp.history):
        # Reduce-scatter sums in a different order than all-reduce:
        # equal to float32 rounding (bit-exact under older XLA:CPU).
        assert h_dp["loss"] == pytest.approx(h_f["loss"], rel=1e-5)

    kw = dict(
        steps=100, batch_size=64, learning_rate=1e-3,
        optimizer="adamw", seed=0, eval_every=20,
    )
    r_dp = fit(get_model("mlp", **MLP_KW), splits,
               mesh=create_mesh((8, 1)), **kw)
    r_fsdp = fit(get_model("mlp", **MLP_KW), splits,
                 mesh=mesh_fsdp8, **kw)
    for h_dp, h_f in zip(r_dp.history, r_fsdp.history):
        assert h_f["loss"] == pytest.approx(h_dp["loss"], rel=2e-2)
        assert abs(h_dp["test_accuracy"] - h_f["test_accuracy"]) <= 0.02


def test_trajectory_matches_plain_dp_small_bert(mesh_fsdp8):
    splits = get_dataset("sst2", max_len=32)
    kw = dict(
        steps=8, batch_size=32, learning_rate=1e-3, optimizer="adamw",
        seed=0,
    )
    r_dp = fit(get_model("bert_classifier", **TINY_BERT), splits,
               mesh=create_mesh((8, 1)), **kw)
    r_fsdp = fit(get_model("bert_classifier", **TINY_BERT), splits,
                 mesh=mesh_fsdp8, **kw)
    assert r_dp.final_loss == pytest.approx(r_fsdp.final_loss, abs=5e-6)


def test_checkpoint_roundtrip_resume_exact_2x2x2(mesh_2x2x2, tmp_path):
    """Sharded save -> restore -> resume replays the uninterrupted
    trajectory bit-for-bit, and restored leaves land back on the mesh
    in their FSDP layout."""
    splits = get_dataset("digits")
    kw = dict(
        batch_size=64, learning_rate=1e-3, optimizer="adamw", seed=0,
        mesh=mesh_2x2x2, async_save=False,
    )
    ck = os.fspath(tmp_path / "state")
    ref = fit(get_model("mlp", **MLP_KW), splits, steps=16, **kw)
    fit(get_model("mlp", **MLP_KW), splits, steps=8,
        checkpoint_dir=ck, save_every=4, **kw)
    resumed = fit(get_model("mlp", **MLP_KW), splits, steps=16,
                  checkpoint_dir=ck, save_every=4, **kw)
    for a, b in zip(
        jax.tree.leaves(ref.params), jax.tree.leaves(resumed.params)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    kernel = resumed.params["dense_0"]["kernel"]
    assert "fsdp" in tuple(kernel.sharding.spec)


def test_fsdp_tp_lora_train_step(mesh_2x2x2):
    """LoRA under FSDP x TP: masked optimizer state (moments only for
    adapters) builds, places, and trains finite."""
    from mlapi_tpu.models.lora import LoraModel

    inner = get_model(
        "gpt_lm", vocab_size=260, hidden_size=32, num_layers=1,
        num_heads=2, max_positions=32, compute_dtype="float32",
    )
    model = LoraModel(inner, rank=4)
    splits = get_dataset("docs_text", seq_len=32)
    r = fit(
        model, splits, steps=3, batch_size=16, learning_rate=1e-3,
        optimizer="adamw", mesh=mesh_2x2x2,
        init_params=model.init(jax.random.key(0)),
    )
    assert np.isfinite(r.final_loss)


def test_sparse_criteo_fsdp_matches_plain_mesh():
    """The r05 sparse-embedding scatter keeps its [F, V]-native update
    exact when the dense leaves are FSDP-sharded: same losses as the
    (2, 4) DP x TP reference, per printed digits."""
    wd_kw = dict(
        num_dense=3, vocab_sizes=[64] * 4, embed_dim=8,
        hidden_dims=[32], num_classes=2,
    )
    splits = get_dataset(
        "criteo", num_dense=3, num_categorical=4, vocab_size=64,
        n_train=512, n_test=64,
    )
    kw = dict(
        steps=5, batch_size=64, learning_rate=1e-3,
        optimizer="recsys-sparse-adamw", seed=0,
    )
    r_ref = fit(get_model("wide_deep", **wd_kw), splits,
                mesh=create_mesh((2, 4)), **kw)
    r_fsdp = fit(get_model("wide_deep", **wd_kw), splits,
                 mesh=create_mesh((1, 2, 4)), **kw)
    assert f"{r_ref.final_loss:.6f}" == f"{r_fsdp.final_loss:.6f}"


def test_fsdp_state_bytes_per_device_drop_by_the_axis(mesh_fsdp8):
    """The committed memory number: FSDP (1, 8, 1) must hold a
    multiple less per-device param+moment bytes than replicated DP
    (8, 1, 1) on the same config (digits-mlp: two large kernels over
    8 devices -> ~6x; bert-base reaches ~8x), at the same losses.
    Params are what ``fit`` returns on each mesh; the moments are
    placed by ``place_train_state``, the one placement ``fit`` uses."""
    import optax

    from mlapi_tpu.parallel import place_train_state
    from mlapi_tpu.parallel.layout import bytes_per_device

    splits = get_dataset("digits")
    kw = dict(steps=2, batch_size=64, learning_rate=1e-3,
              optimizer="adamw", seed=0)
    state_bytes, losses = [], []
    for mesh in (create_mesh((8, 1, 1)), mesh_fsdp8):
        model = get_model("mlp", **MLP_KW)
        r = fit(model, splits, mesh=mesh, **kw)
        _, opt, _ = place_train_state(
            model, jax.device_get(r.params), optax.adamw(1e-3).init, mesh
        )
        state_bytes.append(bytes_per_device(r.params) + bytes_per_device(opt))
        losses.append(r.final_loss)
    dp_bytes, f_bytes = state_bytes
    assert dp_bytes > 0 and f_bytes > 0
    ratio = dp_bytes / f_bytes
    assert ratio >= 4.0, (
        f"FSDP per-device state only {ratio:.2f}x below replicated "
        f"({dp_bytes} vs {f_bytes})"
    )
    # Same program, same math: the losses agree to float32 rounding
    # (reduce-scatter sums in another order than all-reduce).
    assert losses[0] == pytest.approx(losses[1], rel=1e-5)


def test_serving_loads_fsdp_trained_checkpoint(tmp_path, mesh_fsdp8):
    """The train->serve handoff: a final checkpoint written from
    FSDP-sharded params restores on a single device (serve-anywhere
    contract of checkpoint/io.py)."""
    from mlapi_tpu.checkpoint import load_checkpoint, save_checkpoint

    splits = get_dataset("digits")
    model = get_model("mlp", **MLP_KW)
    r = fit(model, splits, steps=4, batch_size=64, learning_rate=1e-3,
            optimizer="adamw", mesh=mesh_fsdp8)
    out = tmp_path / "ckpt"
    save_checkpoint(out, r.params, step=4, config={"model": "mlp"})
    abstract = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    params, meta = load_checkpoint(out, abstract)
    for leaf in jax.tree.leaves(params):
        assert len(leaf.sharding.device_set) == 1
    ref = np.asarray(
        jax.jit(model.apply)(
            jax.device_get(r.params), np.asarray(splits.x_test[:8])
        )
    )
    got = np.asarray(
        jax.jit(model.apply)(params, np.asarray(splits.x_test[:8]))
    )
    np.testing.assert_allclose(got, ref, atol=1e-6)
