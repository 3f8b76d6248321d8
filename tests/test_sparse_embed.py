"""True sparse embedding updates (train/sparse_embed.py): the
touched-rows-only step must be NUMERICALLY EQUIVALENT to the dense
recsys path it replaces — same rowwise-AdaGrad math per unique row,
duplicate ids aggregated exactly like gather autodiff does, untouched
rows bit-frozen — while never materializing the dense table cotangent
or the full-table optimizer sweep (the criteo step's dominant HBM
traffic)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlapi_tpu.datasets import get_dataset
from mlapi_tpu.models import get_model
from mlapi_tpu.train import fit
from mlapi_tpu.train.loop import _make_optimizer, make_train_step
from mlapi_tpu.train.sparse_embed import make_sparse_recsys_step

SMALL = dict(
    num_dense=4,
    vocab_sizes=[64] * 6,   # tiny vocab: duplicate ids guaranteed
    embed_dim=8,
    hidden_dims=[32],
    num_classes=2,
)


@pytest.fixture(scope="module")
def model():
    return get_model("wide_deep", **SMALL)


@pytest.fixture(scope="module")
def batch(model):
    rng = np.random.default_rng(3)
    b = 256
    x = np.concatenate(
        [
            rng.normal(size=(b, SMALL["num_dense"])).astype(np.float32),
            rng.integers(0, 64, size=(b, 6)).astype(np.float32),
        ],
        axis=1,
    )
    y = rng.integers(0, 2, size=(b,)).astype(np.int32)
    # b=256 over vocab 64: every table sees many duplicate ids per
    # batch — the aggregation path is exercised on every step.
    return x, y


def _run_dense(model, params, x, y, steps, lr):
    tx = _make_optimizer("recsys-adamw", lr, model=model, params=params)
    opt_state = tx.init(params)
    step = make_train_step(model.apply, tx)
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, x, y)
    return params, float(loss)


def _run_sparse(model, params, x, y, steps, lr):
    base = _make_optimizer("adamw", lr)
    init, step = make_sparse_recsys_step(model, base, lr)
    opt_state = init(params)
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, x, y)
    return params, opt_state, float(loss)


def test_sparse_step_matches_dense_recsys_exactly(model, batch):
    """5 steps of the sparse path == 5 steps of the dense
    recsys-adamw path, leaf for leaf: the sparse scatter update is
    the same rowwise-AdaGrad trajectory, not an approximation."""
    x, y = batch
    p0 = model.init(jax.random.key(0))
    dense_p, dense_loss = _run_dense(model, p0, x, y, 5, 3e-3)
    p0 = model.init(jax.random.key(0))
    sparse_p, _, sparse_loss = _run_sparse(model, p0, x, y, 5, 3e-3)
    assert np.isclose(dense_loss, sparse_loss, rtol=1e-5)
    dl, treedef = jax.tree.flatten(dense_p)
    # flatten_up_to validates sparse_p's structure AGAINST dense_p's
    # treedef, so the zipped leaves are guaranteed aligned.
    sl = treedef.flatten_up_to(sparse_p)
    paths = [str(k) for k, _ in jax.tree_util.tree_flatten_with_path(
        dense_p)[0]]
    for path, a, b in zip(paths, dl, sl):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b),
            rtol=2e-5, atol=2e-6, err_msg=path,
        )


def test_untouched_rows_are_bit_frozen(model, batch):
    """Rows no batch id referenced must be BITWISE unchanged — the
    defining property of the sparse update (the dense path rewrites
    them with identical values; the sparse path never touches them)."""
    x, y = batch
    params = model.init(jax.random.key(1))
    before = np.asarray(params["deep_tables"]).copy()
    ids = np.asarray(model.embedding_ids(jnp.asarray(x)))
    p2, _, _ = _run_sparse(model, params, x, y, 3, 3e-3)
    after = np.asarray(p2["deep_tables"])
    touched = np.zeros((6, 64), bool)
    touched[np.arange(6)[None, :], ids] = True
    assert (before[~touched] == after[~touched]).all()
    assert not np.allclose(before[touched], after[touched])


def test_fit_integration_matches_dense_and_learns(model):
    """fit(optimizer="recsys-sparse-adamw") reproduces the dense
    recsys-adamw run EXACTLY (same minibatch sequence, same rowwise-
    AdaGrad trajectory — measured identical to the printed digits)
    and learns the planted structure well above chance. The dense
    baseline is run here, not assumed: plain adam reaches ~0.75 on
    this config but AdaGrad-on-tables converges slower — the sparse
    path's contract is equivalence with ITS dense counterpart."""
    splits = get_dataset(
        "criteo", num_dense=4, num_categorical=6, vocab_size=512,
        n_train=8192, n_test=1024,
    )
    big = get_model("wide_deep", **dict(SMALL, vocab_sizes=[512] * 6))
    dense = fit(big, splits, steps=150, batch_size=512,
                learning_rate=3e-3, optimizer="recsys-adamw")
    sparse = fit(big, splits, steps=150, batch_size=512,
                 learning_rate=3e-3, optimizer="recsys-sparse-adamw")
    assert sparse.test_accuracy == pytest.approx(
        dense.test_accuracy, abs=1e-3
    )
    assert np.isclose(sparse.final_loss, dense.final_loss, rtol=1e-4)
    assert sparse.test_accuracy > 0.58  # planted structure, 0.5 chance


def test_sharded_fit_on_2x4_mesh(model, mesh_2x4):
    """The scatter update composes with model-axis-sharded tables
    (GSPMD handles cross-shard ids); params keep the declared
    layout."""
    splits = get_dataset(
        "criteo", num_dense=4, num_categorical=6, vocab_size=512,
        n_train=4096, n_test=512,
    )
    big = get_model("wide_deep", **dict(SMALL, vocab_sizes=[512] * 6))
    r = fit(big, splits, steps=60, batch_size=512, learning_rate=3e-3,
            optimizer="recsys-sparse-adamw", mesh=mesh_2x4)
    assert np.isfinite(r.final_loss)
    spec = tuple(r.params["deep_tables"].sharding.spec)
    assert spec in ((None, "model", None), (None, "model"))


def test_guards_are_loud(model):
    base = _make_optimizer("adamw", 1e-3)
    with pytest.raises(ValueError, match="weight_decay"):
        make_sparse_recsys_step(model, base, 1e-3, weight_decay=0.1)
    with pytest.raises(ValueError, match="classification"):
        make_sparse_recsys_step(model, base, 1e-3, task="lm")
    lm = get_model(
        "gpt_lm", vocab_size=64, hidden_size=16, num_layers=1,
        num_heads=2, max_positions=32,
    )
    with pytest.raises(ValueError, match="protocol"):
        make_sparse_recsys_step(lm, base, 1e-3)


@pytest.mark.requires_tpu
def test_sparse_matches_dense_on_tpu(model, batch):
    """The sparse scatter pipeline on REAL Mosaic lowering: TPU
    scatter/segment-sum must reproduce the dense trajectory exactly
    like the CPU run does (the on-chip check for the r05 flagship)."""
    x, y = batch
    p0 = model.init(jax.random.key(0))
    dense_p, dense_loss = _run_dense(model, p0, x, y, 3, 3e-3)
    p0 = model.init(jax.random.key(0))
    sparse_p, _, sparse_loss = _run_sparse(model, p0, x, y, 3, 3e-3)
    assert np.isclose(dense_loss, sparse_loss, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(dense_p["deep_tables"]),
        np.asarray(sparse_p["deep_tables"]),
        rtol=2e-5, atol=2e-6,
    )


def test_sparse_resume_matches_uninterrupted_run(model, tmp_path):
    """The criteo preset trains 2000 steps with --save-every as a real
    workflow: a sparse-optimizer run resumed from its train-state
    checkpoint (custom {"base", "acc"} opt_state pytree through orbax)
    must land on the uninterrupted trajectory."""
    splits = get_dataset(
        "criteo", num_dense=4, num_categorical=6, vocab_size=64,
        n_train=1024, n_test=128,
    )
    kwargs = dict(batch_size=128, learning_rate=3e-3, seed=3,
                  optimizer="recsys-sparse-adamw")
    full = fit(model, splits, steps=40, **kwargs)

    ck = tmp_path / "train_state"
    fit(model, splits, steps=20, checkpoint_dir=str(ck), save_every=10,
        **kwargs)
    resumed = fit(model, splits, steps=40, checkpoint_dir=str(ck),
                  save_every=10, **kwargs)
    for a, b in zip(jax.tree.leaves(full.params),
                    jax.tree.leaves(resumed.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        )
