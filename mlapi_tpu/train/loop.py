"""Optax training loop.

TPU-native replacement for the reference's training pipeline
(``Logistic Regression.ipynb``: pandas CSV → ``train_test_split`` →
``LogisticRegression().fit`` via scipy lbfgs → ``pickle.dump``). Here
the step is a pure jit-compiled function (one traced XLA computation:
forward, softmax-CE loss, grad, optimizer update — all fused), and
data parallelism is expressed by sharding the batch over the ``data``
axis of a device mesh: XLA inserts the gradient all-reduce over ICI
automatically, no hand-written collectives (see
``mlapi_tpu.parallel``).

L2 regularisation matches sklearn's convention (penalty on weights,
not intercept; strength ``1/C`` over the *sum* of example losses —
we fold that into ``weight_decay`` on the mean loss).
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax

from mlapi_tpu.utils.logging import get_logger
from mlapi_tpu.utils.metrics import REGISTRY, span

_log = get_logger("train.loop")

# ``fit(profile_dir=)`` traces this many steps, starting this many
# steps after the first one the call runs: never the compile, and a
# trace small enough to read.
PROFILE_SKIP_STEPS = 5
PROFILE_STEPS = 20


@dataclass
class TrainResult:
    params: Any
    final_loss: float
    test_accuracy: float | None
    steps: int
    wall_seconds: float
    history: list[dict] = field(default_factory=list)
    # Loss of the first step this call ran (before any update it made
    # took effect) — with final_loss, "did it learn at all".
    first_loss: float | None = None
    # The host's milliseconds a step by part, from this call's share
    # of the registry's ``fit.*`` sums: ``batch`` (``batch_at`` plus
    # the mesh placement), ``dispatch`` (the ``step_fn`` call, which
    # holds whatever the runtime makes the host wait for the device),
    # ``sync`` (every ``float(loss)`` the loop read).
    host_ms_per_step: dict = field(default_factory=dict)
    # The last step's model statistics (a sparse-expert model's
    # ``moe.*`` load); empty for a model that reports none.
    model_stats: dict = field(default_factory=dict)


def _host_ms_per_step(before: dict) -> dict:
    """The registry's ``fit.*`` sums since the snapshot ``before``, as
    milliseconds a step (``{}`` when no step ran)."""
    now = REGISTRY.snapshot()["counters"]
    ran = now.get("fit.step_n", 0) - before.get("fit.step_n", 0)
    return {
        part: (
            now.get(f"fit.{part}_us", 0) - before.get(f"fit.{part}_us", 0)
        ) / 1e3 / ran
        for part in ("batch", "dispatch", "sync")
    } if ran else {}


def _stop_trace(loss) -> None:
    """End ``fit(profile_dir=)``'s trace once the device has finished
    the traced steps (the last loss is ready)."""
    try:
        jax.block_until_ready(loss)
    finally:
        jax.profiler.stop_trace()


def make_train_step(
    apply_fn: Callable,
    tx: optax.GradientTransformation,
    *,
    weight_decay: float = 0.0,
    debug_checks: bool = False,
    task: str = "classify",
    teacher: tuple | None = None,
    distill_temperature: float = 2.0,
    distill_alpha: float = 0.5,
    state_shardings: tuple | None = None,
    stats_apply: Callable | None = None,
) -> Callable:
    """Build a jit-compiled SGD step ``(params, opt_state, x, y) ->
    (params, opt_state, loss)``.

    ``stats_apply`` (a model's ``apply_with_stats``: ``(params, x) ->
    (logits, {name: device scalar})``) takes ``apply_fn``'s place in the
    loss and the step returns a fourth value, that dictionary, computed
    in the same program with no sync of its own (a sparse-expert
    model's load a step). Without it the step is the three-output
    program it has always been.

    ``params`` and ``opt_state`` are donated — the optimizer update
    happens in-place in device memory, no copies.

    ``state_shardings=(param_shardings, opt_shardings)`` (sharding
    pytrees mirroring the two state args) pins the step's OUTPUT
    layouts to them. Without the pin GSPMD is free to re-shard the
    updated state (measured on the FSDP mesh: a replicated bias came
    back fsdp-sharded), which both breaks donation aliasing and makes
    the next call recompile against the drifted input layout. Meshed
    training passes the placed state's own shardings; single-device
    callers leave it None.

    ``task`` selects the objective: ``"classify"`` (softmax CE against
    ``y`` class ids) or ``"lm"`` (next-token CE — ``y`` is the same
    ``[B, L]`` id sequence as ``x``, targets are ``y`` shifted one
    left, pad positions (id 0) masked out of the loss).

    ``teacher=(teacher_apply, teacher_params)`` enables knowledge
    DISTILLATION (Hinton et al.): the loss becomes ``alpha * hard_CE
    + (1 - alpha) * T^2 * KL(teacher_T || student_T)`` with both
    distributions softened by ``distill_temperature``. The teacher
    forward runs inside the same jitted step under ``stop_gradient``
    (its params an undonated argument, re-passed each call), so
    distilling costs one extra forward — no second program, no host
    round trip. This is what trains a speculative-decoding DRAFT that
    actually matches its target's distribution: a draft trained on
    hard labels alone agrees with the target only where the data
    does; a distilled draft matches the target's own probabilities,
    which is the quantity acceptance sampling tests.

    ``debug_checks=True`` compiles the step through ``checkify`` with
    float checks (SURVEY §5 sanitizers row): NaN/inf produced anywhere
    inside the step — a grad, an optimizer moment, the loss — raises
    with the location of the first bad op, instead of surfacing N
    steps later as a non-finite loss. Costs a host sync per step, so
    it is a debug mode, not the default.
    """
    if task not in ("classify", "lm"):
        raise ValueError(f"unknown task {task!r}")
    t_apply, t_params = teacher if teacher is not None else (None, None)

    def soft_kl(t_logits, s_logits):
        """Per-position KL(teacher_T || student_T), both softened by
        the distillation temperature — ONE definition for both tasks
        (they differ only in how positions are masked/averaged)."""
        t = distill_temperature
        return jnp.sum(
            jax.nn.softmax(t_logits / t)
            * (jax.nn.log_softmax(t_logits / t)
               - jax.nn.log_softmax(s_logits / t)),
            axis=-1,
        )

    def blend(hard, soft):
        t = distill_temperature
        return distill_alpha * hard + (1.0 - distill_alpha) * (t * t) * soft

    def loss_fn(params, x, y, tp):
        if stats_apply is not None:
            logits, stats = stats_apply(params, x)
        else:
            logits = apply_fn(params, x)
        if task == "lm":
            targets = y[:, 1:]
            keep = (targets != 0).astype(jnp.float32)
            denom = jnp.maximum(jnp.sum(keep), 1.0)
            s = logits[:, :-1]
            ce = optax.softmax_cross_entropy_with_integer_labels(
                s, targets
            )
            loss = jnp.sum(ce * keep) / denom
            if t_apply is not None:
                t_logits = jax.lax.stop_gradient(
                    t_apply(tp, x)
                )[:, :-1]
                soft = jnp.sum(soft_kl(t_logits, s) * keep) / denom
                loss = blend(loss, soft)
        else:
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()
            if t_apply is not None:
                t_logits = jax.lax.stop_gradient(t_apply(tp, x))
                loss = blend(loss, soft_kl(t_logits, logits).mean())
        if weight_decay:
            # Penalise weight matrices only (ndim >= 2), never biases —
            # sklearn's LogisticRegression convention.
            l2 = sum(
                jnp.sum(jnp.square(p))
                for p in jax.tree.leaves(params)
                if p.ndim >= 2
            )
            loss = loss + 0.5 * weight_decay * l2
        return loss if stats_apply is None else (loss, stats)

    def step(params, opt_state, x, y, tp):
        out, grads = jax.value_and_grad(
            loss_fn, has_aux=stats_apply is not None
        )(params, x, y, tp)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        if stats_apply is None:
            return params, opt_state, out
        return params, opt_state, *out

    if debug_checks:
        from jax.experimental import checkify

        checked = checkify.checkify(step, errors=checkify.float_checks)
        # Donation shifts under checkify: the wrapped signature is the
        # same, but outputs gain the error prefix — jit still donates
        # the (params, opt_state) inputs safely.
        jitted_c = jax.jit(checked, donate_argnums=(0, 1))

        def checked_step(params, opt_state, x, y):
            err, out = jitted_c(params, opt_state, x, y, t_params)
            checkify.check_error(err)  # throws with the first bad op
            return out

        return checked_step

    out_shardings = None
    if state_shardings is not None:
        p_sh, o_sh = state_shardings
        mesh_of = next(
            s for s in jax.tree.leaves(p_sh)
            if hasattr(s, "mesh")
        ).mesh
        scalar = jax.sharding.NamedSharding(
            mesh_of, jax.sharding.PartitionSpec()
        )
        out_shardings = (p_sh, o_sh, scalar)
        if stats_apply is not None:
            out_shardings += (scalar,)  # a prefix: every stat replicated

    jitted = jax.jit(step, donate_argnums=(0, 1), out_shardings=out_shardings)

    def run_step(params, opt_state, x, y):
        # Teacher params ride as an ordinary (undonated) argument —
        # NOT a closure constant, which would bake the whole teacher
        # tree into the executable as literals.
        return jitted(params, opt_state, x, y, t_params)

    # The bench introspects the compiled program (cost_analysis);
    # keep a .lower that binds the teacher like a call does.
    run_step.lower = lambda p, o, x, y: jitted.lower(p, o, x, y, t_params)
    return run_step


@functools.lru_cache(maxsize=64)
def _jitted(apply_fn: Callable) -> Callable:
    """One jit wrapper (and trace cache) per apply_fn object."""
    return jax.jit(apply_fn)


def evaluate(
    apply_fn: Callable, params, x, y, *, batch_size: int = 4096
) -> float:
    """Held-out accuracy (the reference's single metric: ``.score``).

    Evaluates in ``batch_size`` chunks — one whole-test-set jit call
    OOMs once the eval set or model stops being tiny. The tail chunk
    pads up to a full batch (one compiled shape, not two) with the pad
    rows' predictions discarded."""
    x = np.asarray(x)
    y = np.asarray(y)
    n = len(x)
    if n == 0:
        return float("nan")
    fn = _jitted(apply_fn)
    if n <= batch_size:
        logits = fn(params, jnp.asarray(x))
        return float(jnp.mean(jnp.argmax(logits, axis=-1) == jnp.asarray(y)))
    correct = 0
    for s in range(0, n, batch_size):
        chunk = x[s : s + batch_size]
        m = len(chunk)
        if m < batch_size:
            chunk = np.concatenate(
                [chunk, np.repeat(chunk[-1:], batch_size - m, axis=0)]
            )
        pred = jnp.argmax(fn(params, jnp.asarray(chunk)), axis=-1)[:m]
        correct += int(jnp.sum(pred == jnp.asarray(y[s : s + m])))
    return correct / n


def evaluate_lm(
    apply_fn: Callable, params, x, *, batch_size: int = 256
) -> float:
    """Held-out next-token top-1 accuracy over ``[N, L]`` sequences
    (pad id 0 positions excluded) — the LM counterpart of
    :func:`evaluate`, batched for the same OOM reason."""
    x = np.asarray(x)
    n = len(x)
    if n == 0:
        return float("nan")
    fn = _jitted(apply_fn)
    correct = total = 0
    for s in range(0, n, batch_size):
        chunk = x[s : s + batch_size]
        m = len(chunk)
        if m < batch_size and s > 0:
            chunk = np.concatenate(
                [chunk, np.repeat(chunk[-1:], batch_size - m, axis=0)]
            )
        pred = np.asarray(
            jnp.argmax(fn(params, jnp.asarray(chunk)), axis=-1)
        )[:m, :-1]
        targets = chunk[:m, 1:]
        keep = targets != 0
        correct += int(((pred == targets) & keep).sum())
        total += int(keep.sum())
    return correct / max(total, 1)


def _save_train_state(
    root, state: dict, step: int, run_config: dict, keep_last: int = 0
) -> None:
    """Checkpoint FULL train state (params + optimizer moments) so a
    resumed run continues the same trajectory, not a fresh-optimizer
    approximation of it. With ``keep_last``, older committed steps are
    collected after the new one commits."""
    from mlapi_tpu.checkpoint import gc_checkpoints, save_checkpoint
    from mlapi_tpu.checkpoint.io import step_dir

    save_checkpoint(
        step_dir(root, step),
        state,
        step=step,
        config={"kind": "train_state", **run_config},
    )
    if keep_last and jax.process_index() == 0:
        gc_checkpoints(root, keep_last)


def _maybe_resume(root, params, opt_state, run_config: dict):
    """Restore the newest committed train-state checkpoint under
    ``root``, if any. Returns (params, opt_state, start_step).

    The checkpoint's recorded hyperparameters must match this run's —
    silently continuing an lr=1e-2 trajectory with lr=1e-3 (or a
    different seed/optimizer with identical state shapes) produces a
    run matching neither config.
    """
    from mlapi_tpu.checkpoint import latest_step, load_checkpoint
    from mlapi_tpu.checkpoint.io import read_manifest
    from mlapi_tpu.utils.logging import get_logger

    log = get_logger("train.loop")
    newest = latest_step(root)
    if newest is None:
        return params, opt_state, 0

    # Validate hyperparameters from the manifest alone, BEFORE paying
    # for the tensor restore (gigabytes of tensorstore I/O for sharded
    # models). Keys absent from the checkpoint (written by an older
    # framework version) can't be checked — warn, don't reject, so
    # legacy checkpoints stay resumable.
    meta = read_manifest(newest)
    diff = {
        k: (meta.config[k], run_config[k])
        for k in run_config
        if k in meta.config and meta.config[k] != run_config[k]
    }
    if diff:
        raise ValueError(
            f"refusing to resume from {newest}: checkpoint was written "
            f"with different hyperparameters (checkpoint vs requested: "
            f"{diff}). Match the original config, or pass resume=False "
            "/ --no-resume to start fresh."
        )
    unchecked = [k for k in run_config if k not in meta.config]
    if unchecked:
        log.warning(
            "resuming from %s: checkpoint predates hyperparameter "
            "recording; cannot verify %s match the original run",
            newest, unchecked,
        )

    log.info("resuming from %s", newest)
    # Mirror the save-side structure EXACTLY (no list()/tuple()
    # conversions): jax.tree.map preserves tuple/namedtuple treedefs,
    # and optax states rely on their namedtuple types surviving the
    # round trip (multi_transform's update does state.inner_states).
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=getattr(a, "sharding", None)
        ),
        {"params": params, "opt_state": opt_state},
    )
    state, meta = load_checkpoint(newest, abstract)
    return state["params"], state["opt_state"], meta.step


def _make_optimizer(
    name: str, learning_rate: float, *, model=None, params=None
) -> optax.GradientTransformation:
    """``name`` is an optax factory (``"adam"``, ``"adamw"``, …) or
    ``"recsys-<base>"``: embedding tables (as labelled by the model's
    ``optimizer_partitions``) take rowwise AdaGrad, the rest ``<base>``
    — see ``mlapi_tpu.train.optimizers``."""
    if name.startswith("recsys-sparse-"):
        # Not an optax transform: the sparse path changes the GRADIENT
        # representation (row cotangents + scatter), so it is built at
        # the STEP level — fit/bench branch to
        # train/sparse_embed.make_sparse_recsys_step before reaching
        # here.
        raise ValueError(
            f"{name!r} is a step-level optimizer (sparse embedding "
            "updates), not an optax transform; use train.fit / the "
            "train CLI, or make_sparse_recsys_step directly"
        )
    if name.startswith("recsys-"):
        if model is None or not hasattr(model, "optimizer_partitions"):
            raise ValueError(
                f"optimizer {name!r} needs a model with "
                "optimizer_partitions(); "
                f"{type(model).__name__ if model else 'no model'} has none"
            )
        from mlapi_tpu.train.optimizers import partitioned

        base = _make_optimizer(name[len("recsys-"):], learning_rate)
        return partitioned(model, params, base, learning_rate)
    try:
        factory = getattr(optax, name)
    except AttributeError:
        raise ValueError(f"unknown optax optimizer {name!r}") from None
    return factory(learning_rate)


def fit(
    model,
    splits,
    *,
    steps: int = 500,
    batch_size: int | None = None,
    learning_rate: float = 0.1,
    weight_decay: float = 0.0,
    optimizer: str = "adam",
    seed: int = 0,
    mesh: jax.sharding.Mesh | None = None,
    eval_every: int = 0,
    checkpoint_dir: str | None = None,
    save_every: int = 0,
    keep_last: int = 0,
    async_save: bool = True,
    resume: bool = True,
    profile_dir: str | None = None,
    debug_checks: bool = False,
    task: str = "auto",
    init_params=None,
    distill_from: str | None = None,
    distill_temperature: float = 2.0,
    distill_alpha: float = 0.5,
) -> TrainResult:
    """Train ``model`` on ``splits``.

    ``task="auto"`` infers the objective from the label shape:
    ``[B, L]`` sequence labels (LM datasets set ``y == x``) train
    next-token prediction with pad masking; ``[B]`` class ids train
    classification. ``test_accuracy`` is next-token top-1 accuracy
    for LM runs.

    ``batch_size=None`` runs full-batch steps (right for tiny convex
    problems like Iris). With ``mesh`` set, the batch is sharded over
    the mesh's ``data`` axis and params follow the model's declared
    layout, which makes the jitted step data-parallel (ICI all-reduce
    on gradients) and — for sharded models — tensor-parallel too.

    Fault tolerance (SURVEY §5 failure-detection row): with
    ``checkpoint_dir`` + ``save_every``, full train state (params AND
    optimizer moments) is checkpointed periodically; a rerun resumes
    from the newest committed step and — because minibatch selection
    is a pure function of (seed, step) — replays the exact schedule a
    never-interrupted run would have seen. ``keep_last=N`` retains
    only the newest N committed step dirs (older ones are collected
    after each commit). ``async_save`` (single-process runs) copies
    state to host synchronously — the step donates those device
    buffers, so they cannot outlive the loop iteration — then writes
    to disk on a background thread, keeping the device busy through
    the tensorstore I/O; at most one save is in flight, and a failed
    save surfaces on the next save point (or at the end of the run).

    ``profile_dir`` takes a ``jax.profiler`` trace of
    ``PROFILE_STEPS`` (20) steps, starting ``PROFILE_SKIP_STEPS`` (5)
    steps after the first step this call runs, so never the compile;
    the device is drained before the trace starts and before it stops,
    so it holds exactly those steps' executions. A shorter run traces
    what is left after the skipped steps (view with TensorBoard/XProf,
    or read with ``jax.profiler.ProfileData``).

    The loop's own clock: every step runs inside ``span("fit.step",
    step_num=i)`` with ``fit.batch``, ``fit.dispatch``, ``fit.sync``,
    ``fit.eval`` and ``fit.checkpoint`` nested where that work happens
    (``utils/metrics.py``): host spans in any profiler trace, and
    ``<name>_us`` / ``<name>_n`` sums in the process-wide ``REGISTRY``
    always, kept when the loop leaves by an exception.
    """
    from mlapi_tpu.parallel import (
        model_on_mesh,
        params_for_model,
        shard_batch_for_mesh,
    )

    model = model_on_mesh(model, mesh)
    if task == "auto":
        # Prefer the dataset's explicit marker (extras["task"], set by
        # LM loaders); fall back to the label-shape heuristic.
        task = getattr(splits, "extras", {}).get(
            "task",
            "lm" if np.asarray(splits.y_train).ndim == 2 else "classify",
        )

    # ``init_params`` seeds training from existing weights (pretrained
    # fine-tune, LoRA base) instead of a fresh random init.
    params = (
        init_params if init_params is not None
        else model.init(jax.random.key(seed))
    )
    # TRUE sparse embedding updates (recsys-sparse-<base>): gradients
    # w.r.t. gathered rows + scatter updates of touched rows only —
    # the dense [F, V, D] cotangent and full-table optimizer sweep
    # never materialize (train/sparse_embed.py). Orthogonal features
    # that would force dense table traffic are rejected there or here.
    sparse_embed = optimizer.startswith("recsys-sparse-")
    if sparse_embed:
        from mlapi_tpu.train.sparse_embed import make_sparse_recsys_step

        if distill_from is not None:
            raise ValueError(
                "recsys-sparse-* cannot distill: the teacher loss "
                "needs the full forward's dense gradient path"
            )
        if debug_checks:
            raise ValueError(
                "recsys-sparse-* does not support --debug-checks; "
                "use the dense recsys-<base> path to checkify"
            )
        if hasattr(model, "trainable_mask"):
            # A LoRA wrapper delegates the sparse-embedding protocol
            # to its inner model, so the step would silently train the
            # frozen base with full moments and ignore the adapters.
            raise ValueError(
                "recsys-sparse-* cannot train a parameter-efficient "
                "(LoRA) wrapper: the sparse step bypasses "
                "trainable_mask; fine-tune with the dense "
                "recsys-<base> path instead"
            )
        base = _make_optimizer(
            optimizer[len("recsys-sparse-"):], learning_rate
        )
        sparse_init, sparse_step = make_sparse_recsys_step(
            model, base, learning_rate, task=task,
            weight_decay=weight_decay,
        )
        tx = None
    else:
        tx = _make_optimizer(
            optimizer, learning_rate, model=model, params=params
        )
        if hasattr(model, "trainable_mask"):
            # Parameter-efficient fine-tuning (LoRA): frozen leaves
            # get no update and — the part that matters for memory —
            # no optimizer state at all (adamw moments exist only for
            # the adapters).
            tx = optax.masked(tx, model.trainable_mask(params))

    init_opt = sparse_init if sparse_embed else tx.init
    state_shardings = None
    if mesh is not None:
        # Model-declared layout (e.g. Wide&Deep's sharded embedding
        # tables), augmented with ZeRO-style ``fsdp``-axis sharding
        # when the mesh has one, or fully replicated. The optimizer
        # state is placed EXPLICITLY in the matching layout — jit-
        # initialising from placed params does not inherit their
        # shardings, see parallel.mesh.place_train_state (the one
        # shared implementation).
        from mlapi_tpu.parallel import place_train_state

        params, opt_state, state_shardings = place_train_state(
            model, params, init_opt, mesh
        )
    else:
        opt_state = init_opt(params)

    # The hyperparameters that define the optimisation trajectory; a
    # resumed run must match them exactly (steps may grow — extending
    # a finished run is legitimate).
    # Knowledge distillation: load the teacher once, place it like the
    # student (same mesh), and hand its (apply, params) to the step.
    teacher = None
    teacher_hash = None
    if distill_from is not None:
        from mlapi_tpu.checkpoint import load_checkpoint, read_manifest
        from mlapi_tpu.models import get_model as _get_model

        t_meta = read_manifest(distill_from)
        t_model = _get_model(
            t_meta.config["model"], **t_meta.config.get("model_kwargs", {})
        )
        t_abstract = jax.eval_shape(lambda: t_model.init(jax.random.key(0)))
        t_params, t_meta = load_checkpoint(distill_from, t_abstract)
        if mesh is not None:
            t_params = params_for_model(t_model, t_params, mesh)
        teacher = (t_model.apply, t_params)
        teacher_hash = t_meta.config_hash

    run_config = {
        "optimizer": optimizer,
        "learning_rate": learning_rate,
        "weight_decay": weight_decay,
        "batch_size": batch_size,
        "seed": seed,
        "task": task,
        # The distillation target defines the optimisation trajectory
        # as much as the optimizer does — a resume must match it.
        **(
            {
                "distill_from_hash": teacher_hash,
                "distill_temperature": distill_temperature,
                "distill_alpha": distill_alpha,
            }
            if teacher is not None
            else {}
        ),
    }

    start_step = 0
    if checkpoint_dir and resume:
        params, opt_state, start_step = _maybe_resume(
            checkpoint_dir, params, opt_state, run_config
        )
        if start_step >= steps:
            raise ValueError(
                f"resumed train state is already at step {start_step}, past "
                f"the requested {steps} steps — raise --steps or pass "
                "resume=False / --no-resume"
            )

    if sparse_embed:
        step_fn = sparse_step
        if state_shardings is not None:
            # Rebuild with the placed state's shardings pinned on the
            # step outputs (the build above ran before placement and
            # exists for its loud validation errors; jit is lazy, so
            # only this step ever compiles).
            _, step_fn = make_sparse_recsys_step(
                model, base, learning_rate, task=task,
                weight_decay=weight_decay,
                state_shardings=state_shardings,
            )
    else:
        step_fn = make_train_step(
            model.apply, tx, weight_decay=weight_decay,
            debug_checks=debug_checks, task=task, teacher=teacher,
            distill_temperature=distill_temperature,
            distill_alpha=distill_alpha,
            state_shardings=state_shardings,
            # the class's own: a wrapper (LoRA) hands unknown names to
            # its inner model, whose forward is not the wrapper's
            stats_apply=(
                model.apply_with_stats
                if hasattr(type(model), "apply_with_stats") else None
            ),
        )

    def eval_fn(p):
        if task == "lm":
            return evaluate_lm(model.apply, p, splits.x_test)
        return evaluate(model.apply, p, splits.x_test, splits.y_test)

    # Async checkpointing: one background writer, one save in flight.
    save_pool = None
    pending_save = None
    if checkpoint_dir and save_every and async_save and jax.process_count() == 1:
        import concurrent.futures

        save_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="ckpt-save"
        )

    # Preserve the dataset's feature dtype: float32 for tabular rows,
    # int32 token ids for text models.
    x_all = np.asarray(splits.x_train)
    y_all = np.asarray(splits.y_train, dtype=np.int32)
    n = len(x_all)

    def batch_at(i: int):
        """Minibatch for step ``i`` — a pure function of (seed, i), so a
        resumed run replays the identical batch sequence."""
        if batch_size is None or batch_size >= n:
            return x_all, y_all
        idx = np.random.default_rng((seed, i)).choice(n, size=batch_size, replace=False)
        return x_all[idx], y_all[idx]

    def read_loss() -> float:
        """The loss on the host, inside the loop: where a step waits
        for the device."""
        with span("fit.sync", "fit.sync"):
            value = float(loss)
        read_stats()
        return value

    def read_stats() -> dict:
        """The newest step's model statistics into ``REGISTRY`` gauges
        of their names: only where the loop already waits for the
        device (after a loss readback, at the loop's end)."""
        if not stats:
            return {}
        with span("fit.stats", "fit.stats"):
            read = {name: v.item() for name, v in stats[0].items()}
        for name, value in read.items():
            REGISTRY.gauge(name).set(value)
        return read

    sums0 = REGISTRY.snapshot()["counters"]
    trace_from = start_step + PROFILE_SKIP_STEPS if profile_dir else -1
    tracing = False
    if profile_dir and steps <= trace_from:
        _log.warning(
            "profile_dir: nothing traced, the run ends within the %d "
            "skipped steps", PROFILE_SKIP_STEPS,
        )
    t0 = time.perf_counter()
    history: list[dict] = []
    loss = float("nan")
    first_loss = None
    stats: tuple = ()  # the newest step's, as device arrays
    try:
        for i in range(start_step, steps):
            if i == trace_from:
                jax.block_until_ready(loss)  # earlier steps stay out
                jax.profiler.start_trace(profile_dir)
                tracing = True
            with span("fit.step", "fit.step", step_num=i):
                with span("fit.batch", "fit.batch"):
                    x, y = batch_at(i)
                    if mesh is not None:
                        x, y = shard_batch_for_mesh((x, y), mesh)
                with span("fit.dispatch", "fit.dispatch"):
                    params, opt_state, loss, *stats = step_fn(
                        params, opt_state, x, y
                    )
                if first_loss is None:
                    first_loss = loss  # device scalar; read after the loop
                if eval_every and (i + 1) % eval_every == 0:
                    loss_now = read_loss()
                    if not np.isfinite(loss_now):
                        raise FloatingPointError(
                            f"non-finite loss {loss_now} at step {i + 1}"
                        )
                    with span("fit.eval", "fit.eval"):
                        acc = eval_fn(params)
                    history.append(
                        {"step": i + 1, "loss": loss_now,
                         "test_accuracy": acc}
                    )
                if (
                    checkpoint_dir
                    and save_every
                    and (i + 1) % save_every == 0
                    and (i + 1) < steps
                ):
                    loss_now = read_loss()
                    if not np.isfinite(loss_now):
                        raise FloatingPointError(
                            f"refusing to checkpoint non-finite loss "
                            f"{loss_now} at step {i + 1}"
                        )
                    # The opt_state pytree is stored AS-IS: converting
                    # the top level to a list would strip namedtuple
                    # types (optax.multi_transform's state is one) and
                    # break the restore-side structure match.
                    state = {"params": params, "opt_state": opt_state}
                    with span("fit.checkpoint", "fit.checkpoint"):
                        if save_pool is not None:
                            if pending_save is not None:
                                # one in flight; fail loud
                                pending_save.result()
                            # Host copy NOW (the next step donates these
                            # device buffers); disk write overlaps
                            # training.
                            host_state = jax.device_get(state)
                            pending_save = save_pool.submit(
                                _save_train_state, checkpoint_dir,
                                host_state, i + 1, run_config, keep_last,
                            )
                        else:
                            _save_train_state(
                                checkpoint_dir, state, i + 1, run_config,
                                keep_last,
                            )
            if tracing and i + 1 == trace_from + PROFILE_STEPS:
                tracing = False
                _stop_trace(loss)
    finally:
        try:
            if tracing:
                # A run (or an exception) that ended inside the traced
                # steps: what ran is in the trace.
                _stop_trace(loss)
        finally:
            # Join the in-flight save even when the loop raises — a
            # failed background save must never be silently dropped (if
            # both failed, the loop's exception stays chained as
            # __context__).
            if save_pool is not None:
                try:
                    if pending_save is not None:
                        pending_save.result()
                finally:
                    save_pool.shutdown(wait=True)
    wall = time.perf_counter() - t0
    final_loss = float(loss)  # after the last step: no span of the loop's
    model_stats = read_stats()
    if steps > start_step and not np.isfinite(final_loss):
        raise FloatingPointError(
            f"training ended with non-finite loss {final_loss}"
        )

    if len(splits.x_test):
        with span("fit.eval", "fit.eval"):
            test_acc = eval_fn(params)
    else:
        test_acc = None
    return TrainResult(
        params=params,
        final_loss=final_loss,
        test_accuracy=test_acc,
        steps=steps,
        wall_seconds=wall,
        history=history,
        first_loss=None if first_loss is None else float(first_loss),
        host_ms_per_step=_host_ms_per_step(sums0),
        model_stats=model_stats,
    )
