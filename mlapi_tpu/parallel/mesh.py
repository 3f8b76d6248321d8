"""Device mesh construction and basic sharding helpers.

Idiom (modern JAX, GSPMD): build one logical mesh with named axes,
annotate arrays with ``NamedSharding``, and let ``jax.jit`` insert the
collectives. Scales from 1 chip to multi-host pods without changing
application code; multi-host initialisation is
``jax.distributed.initialize`` before mesh creation.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
MODEL_AXIS = "model"


def create_mesh(
    shape: tuple[int, ...] | None = None,
    axis_names: tuple[str, ...] | None = None,
    *,
    devices=None,
) -> Mesh:
    """Build a named device mesh.

    Defaults to putting every visible device on the ``data`` axis with
    a trivial ``model`` axis — right for pure data-parallel configs.
    Pass an explicit ``shape`` (e.g. ``(2, 4)``) for configs that
    shard params over ``model`` (Criteo embeddings, BERT TP).

    A THREE-dimensional ``shape`` names the axes ``(data, fsdp,
    model)``: the middle axis is a second data-parallel axis over
    which parameters and optimizer state are ZeRO-sharded
    (``layout.fsdp_spec_tree``) — GSPMD turns the gradient all-reduce
    over it into reduce-scatter + all-gather, cutting per-device state
    memory by the axis size at equal math.
    """
    devices = list(jax.devices() if devices is None else devices)
    if shape is not None and int(np.prod(shape)) < len(devices):
        # An explicit shape smaller than the host takes the first
        # devices (e.g. a (1, 4) TP mesh on an 8-device host): the
        # deployment decides the slice, not the host size.
        devices = devices[: int(np.prod(shape))]
    n = len(devices)
    if axis_names is None:
        axis_names = (
            (DATA_AXIS, FSDP_AXIS, MODEL_AXIS)
            if shape is not None and len(shape) == 3
            else (DATA_AXIS, MODEL_AXIS)
        )
    if shape is None:
        shape = (n,) + (1,) * (len(axis_names) - 1)
    if int(np.prod(shape)) != n:
        raise ValueError(f"mesh shape {shape} does not cover {n} devices")
    mesh_devices = mesh_utils.create_device_mesh(shape, devices=devices)
    return Mesh(mesh_devices, axis_names)


def mesh_for_config(shape, *, devices=None) -> Mesh | None:
    """The mesh a training config's ``mesh_shape`` gets on this host.

    ``None`` ⇒ no mesh. A shape that fits ⇒ that mesh. A shape that
    does NOT fit: on ONE visible device the run is unsharded (the
    ladder presets name pod-slice meshes; one chip is the degenerate
    slice), but with several devices visible it is an error — quietly
    training on the first device would leave the rest idle."""
    if shape is None:
        return None
    devices = list(jax.devices() if devices is None else devices)
    need = int(np.prod(shape))
    if need <= len(devices):
        return create_mesh(tuple(shape), devices=devices)
    if len(devices) == 1:
        return None
    raise ValueError(
        f"mesh {tuple(shape)} needs {need} devices but {len(devices)} "
        "are visible: pass --mesh-shape with a shape that fits (e.g. "
        f"'{len(devices)},1')"
    )


def model_on_mesh(model, mesh):
    """``model`` with ``mesh`` pinned on it when its attention runs as
    a Pallas kernel (``attention_impl`` / ``decode_attn_impl`` ==
    ``"flash"``): GSPMD cannot partition the opaque kernel, so the
    model wraps it in ``shard_map`` over the mesh it is told about
    (``flash_attention_on_mesh``, the ``*_tp`` cache-read wrappers).
    The field already exists (ring attention uses it) and program
    factories key on it for free. Models that are not dataclasses with
    a ``mesh`` field (wrappers) come back unchanged."""
    import dataclasses

    if mesh is None or getattr(model, "mesh", None) is not None or "flash" not in (
        getattr(model, "attention_impl", None),
        getattr(model, "decode_attn_impl", None),
    ):
        return model
    try:
        return dataclasses.replace(model, mesh=mesh)
    except TypeError:
        return model


def replicate_for_mesh(pytree, mesh: Mesh):
    """Fully replicate every leaf across the mesh (params, opt state)."""
    sharding = NamedSharding(mesh, P())
    return jax.device_put(pytree, sharding)


def fit_spec(shape, spec, mesh: Mesh) -> P:
    """``spec`` with every axis dropped whose mesh size does not
    divide its dimension. Published vocabularies are not round
    (bert-base 30522, gpt2 50257): on a 4-way model axis their
    vocab-sharded tables stay whole on every device instead of
    failing placement; GSPMD partitions the rest as declared."""
    if spec is None:
        return P()
    axes = []
    for dim, axis in zip(shape, tuple(spec)):
        names = axis if isinstance(axis, tuple) else (axis,)
        size = int(np.prod([mesh.shape[a] for a in names if a]))
        axes.append(axis if dim % size == 0 else None)
    return P(*axes)


def place_params(params, mesh: Mesh, spec_tree=None):
    """Place a param pytree on the mesh per a PartitionSpec pytree.

    ``spec_tree`` mirrors ``params`` (models provide it via
    ``param_shardings()``); missing/None spec ⇒ replicated. This is
    the moment sharded training/serving actually happens: after
    placement, ``jax.jit`` sees the shardings on its inputs and GSPMD
    partitions the whole step — gathers, all-to-alls, gradient
    reductions — with no further annotation.

    Weight-only-quantized trees compose transparently: where a float
    leaf became ``{"q": int8, "scale": f32}`` (``ops/quant.py``), the
    float leaf's spec applies to ``q`` verbatim, and ``scale`` — whose
    reduced axes have length 1 — keeps only the LAST axis's placement
    (per-channel scales live on the channel axis; a length-1 axis
    cannot shard). The dequantized product then carries exactly the
    float layout, so every downstream program partitions identically.
    """
    if spec_tree is None:
        return replicate_for_mesh(params, mesh)

    from mlapi_tpu.ops.quant import _is_quant_leaf

    def put(leaf, spec):
        if _is_quant_leaf(leaf):
            q, scale = leaf["q"], leaf["scale"]
            full = tuple(fit_spec(q.shape, spec, mesh))
            full = full + (None,) * (q.ndim - len(full))
            sspec = P(
                *((None,) * (scale.ndim - 1) + (full[q.ndim - 1],))
            )
            return {
                "q": jax.device_put(q, NamedSharding(mesh, P(*full))),
                "scale": jax.device_put(scale, NamedSharding(mesh, sspec)),
            }
        return jax.device_put(
            leaf, NamedSharding(mesh, fit_spec(leaf.shape, spec, mesh))
        )

    return jax.tree.map(put, params, spec_tree, is_leaf=_is_quant_leaf)


def params_for_model(model, params, mesh: Mesh, layout=None):
    """Place ``params`` using the model's own layout when it has one
    (``param_shardings``), else fully replicated.

    ``layout`` (a ``SpecLayout``) renames the mesh axes consistently
    across every model — pass it when the mesh doesn't use the default
    ``data``/``model`` axis names.

    On a mesh with a non-trivial ``fsdp`` axis the model's TP specs
    (or the replicated default) are augmented leaf-by-leaf with
    ZeRO-style parameter sharding (``layout.fsdp_spec_tree``): every
    large-enough leaf gets its largest still-unsharded dimension
    partitioned over ``fsdp``. Models need no FSDP awareness — the
    derivation composes with whatever TP layout they declare."""
    spec_fn = getattr(model, "param_shardings", None)
    spec_tree = spec_fn(layout) if spec_fn else None
    fsdp_axis = layout.fsdp_axis if layout is not None else FSDP_AXIS
    if mesh.shape.get(fsdp_axis, 1) > 1:
        from mlapi_tpu.parallel.layout import fsdp_spec_tree

        spec_tree = fsdp_spec_tree(
            params, spec_tree, mesh.shape[fsdp_axis], fsdp_axis=fsdp_axis
        )
    return place_params(params, mesh, spec_tree)


def state_shardings_like(opt_abstract, params, mesh: Mesh):
    """Shardings for an optimizer-state pytree, mirrored from placed
    ``params`` — the piece that makes ZeRO sharding cover the moments,
    which for AdamW are 2x the params.

    ``jax.jit(tx.init)(placed_params)`` does NOT inherit the param
    shardings (measured: the zeros have no data dependence on the
    inputs, so GSPMD assigns them the default device) — the moments
    must be placed explicitly. Optax states mirror the param tree's
    dict structure under their namedtuple/tuple wrappers, so each
    state leaf is matched to its param by the trailing run of dict
    keys in its path (``.mu['dense_0']['kernel']`` →
    ``['dense_0']['kernel']``), longest suffix first:

    - exact shape match → the param's own sharding (adam mu/nu);
    - leading-dims match → the param's spec truncated to the leaf's
      rank (rowwise-AdaGrad accumulators: ``[F, V]`` for a
      ``[F, V, D]`` table keeps the table's vocab sharding);
    - no match (step counters, ``optax.MaskedNode``) → replicated.
    """
    from jax.tree_util import DictKey, tree_leaves_with_path

    # Param index: every dict-key path suffix → (shape, sharding);
    # ambiguous suffixes (two params sharing a trailing key) drop out
    # — their leaves fall back through shorter suffixes or replication.
    index: dict = {}
    collisions: set = set()
    for path, leaf in tree_leaves_with_path(params):
        keys = tuple(
            k.key for k in path if isinstance(k, DictKey)
        )
        for i in range(len(keys)):
            suffix = keys[i:]
            if suffix in index:
                collisions.add(suffix)
            else:
                index[suffix] = (tuple(leaf.shape), leaf.sharding)
    replicated = NamedSharding(mesh, P())

    def match(path, leaf):
        if not hasattr(leaf, "shape"):
            return replicated  # defensive: unshaped leaf
        shape = tuple(leaf.shape)
        keys = [k.key for k in path if isinstance(k, DictKey)]
        # The trailing run of dict keys (state wrappers are tuples/
        # namedtuples; dicts inside the run that are NOT param path
        # segments — e.g. a state dict {'acc': ...} — are shed as the
        # suffix shortens).
        for i in range(len(keys)):
            suffix = tuple(keys[i:])
            if suffix in collisions or suffix not in index:
                continue
            p_shape, p_sharding = index[suffix]
            if shape == p_shape:
                return p_sharding
            if shape == p_shape[: len(shape)]:
                spec = tuple(p_sharding.spec)[: len(shape)]
                return NamedSharding(mesh, P(*spec))
        return replicated

    return jax.tree_util.tree_map_with_path(match, opt_abstract)


def place_train_state(model, params, init_opt, mesh: Mesh, layout=None):
    """Place a full train state on ``mesh``: params in the model's
    (FSDP-augmented) layout, optimizer state EXPLICITLY in the
    mirrored layout, and the sharding trees a train step needs to pin
    its outputs.

    Returns ``(params, opt_state, state_shardings)`` with
    ``state_shardings = (param_shardings, opt_shardings)`` — the ONE
    implementation of the "moments must be placed explicitly"
    invariant, shared by ``fit`` and the multichip dryrun
    so they cannot measure different memory layouts than training
    uses.
    """
    params = params_for_model(model, params, mesh, layout)
    opt_sh = state_shardings_like(
        jax.eval_shape(init_opt, params), params, mesh
    )
    opt_state = jax.jit(init_opt, out_shardings=opt_sh)(params)
    return params, opt_state, (
        jax.tree.map(lambda a: a.sharding, params), opt_sh
    )


def batch_shard_axes(mesh: Mesh) -> tuple[str, ...]:
    """The mesh axes a batch dimension shards over: ``data``, plus
    ``fsdp`` when present — the FSDP axis is a second data-parallel
    axis (each of its shards sees different examples; what it changes
    is where the *state* lives, not the math)."""
    axes = tuple(
        a for a in (DATA_AXIS, FSDP_AXIS) if a in mesh.axis_names
    )
    return axes or (DATA_AXIS,)


def batch_shard_size(mesh: Mesh) -> int:
    """Product of the batch-sharding axis sizes (divisibility unit
    for batch/bucket dimensions on this mesh)."""
    n = 1
    for a in batch_shard_axes(mesh):
        n *= mesh.shape[a]
    return n


def shard_batch_for_mesh(pytree, mesh: Mesh, axis: str | tuple = DATA_AXIS):
    """Shard each leaf's leading (batch) dimension over ``axis``.

    Leading dims must be divisible by the axis size — callers pad
    (the serving batcher pads to bucket sizes for exactly this
    reason, and to avoid recompilation).

    When the mesh carries an ``fsdp`` axis and the default ``data``
    axis is requested, the batch shards over BOTH ``(data, fsdp)`` —
    on an FSDP mesh every device holds distinct examples, and the
    divisibility unit grows to ``data * fsdp``
    (:func:`batch_shard_size`).
    """
    if axis == DATA_AXIS:
        axes = batch_shard_axes(mesh)
    elif isinstance(axis, (tuple, list)):
        axes = tuple(axis)
    else:
        axes = (axis,)
    axis_size = 1
    for a in axes:
        axis_size *= mesh.shape[a]
    dim0 = axes if len(axes) > 1 else axes[0]

    def put(leaf):
        arr = np.asarray(leaf)
        if arr.shape[0] % axis_size:
            raise ValueError(
                f"batch dim {arr.shape[0]} not divisible by mesh axes "
                f"{axes!r} of total size {axis_size}; pad first"
            )
        spec = P(dim0, *(None,) * (arr.ndim - 1))
        return jax.device_put(arr, NamedSharding(mesh, spec))

    return jax.tree.map(put, pytree)
