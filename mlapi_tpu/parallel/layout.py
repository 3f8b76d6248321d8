"""Canonical PartitionSpec layouts per parameter family.

One place that answers "how is this tensor laid out on the mesh" for
every config in the ladder, so models annotate params by *role* and
the mesh shape can change without touching model code. (Pattern after
public TPU sharding idioms — a frozen dataclass of named-axis specs.)
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from mlapi_tpu.parallel.mesh import DATA_AXIS, FSDP_AXIS, MODEL_AXIS

# Leaves with fewer elements than this stay replicated over the fsdp
# axis: sharding a layernorm scale or a bias saves bytes nobody is
# short of, while adding an all-gather per use. 2048 elements keeps
# every scale/small-bias replicated and shards everything matrix-like
# (the smallest sharded leaf in the ladder is digits-mlp's [64, 256]).
FSDP_MIN_SIZE = 2048


@dataclass(frozen=True)
class SpecLayout:
    """Canonical PartitionSpecs for params and activations."""

    data_axis: str = DATA_AXIS
    model_axis: str = MODEL_AXIS
    fsdp_axis: str = FSDP_AXIS

    # --- activations -----------------------------------------------------
    def batch(self) -> P:
        """Activations: batch dim sharded over data, features replicated."""
        return P(self.data_axis)

    # --- dense layers ----------------------------------------------------
    def replicated(self) -> P:
        """Small params (linear classifier W/b, layernorm scales)."""
        return P()

    def dense_col(self) -> P:
        """[in, out] weight, output features sharded over model (TP
        column-parallel: each chip computes a slice of the outputs)."""
        return P(None, self.model_axis)

    def dense_row(self) -> P:
        """[in, out] weight, input features sharded over model (TP
        row-parallel: follows a col-parallel layer; XLA inserts the
        psum on the output)."""
        return P(self.model_axis, None)

    # --- embeddings ------------------------------------------------------
    def embedding_rows(self) -> P:
        """[vocab, dim] table sharded over vocab rows — each chip owns
        a shard of the vocab/hash space and lookups become an XLA
        gather + all-to-all."""
        return P(self.model_axis, None)

    def embedding_tables(self) -> P:
        """[fields, vocab, dim] stacked tables (Criteo Wide&Deep):
        sharded over the per-field vocab dim, fields replicated."""
        return P(None, self.model_axis, None)

    def bias_col(self) -> P:
        """Bias of a column-parallel layer: sharded like its outputs."""
        return P(self.model_axis)

    # --- attention -------------------------------------------------------
    def attn_qkv(self) -> P:
        """[d_model, heads*head_dim]: heads sharded over model."""
        return P(None, self.model_axis)

    def attn_out(self) -> P:
        """[heads*head_dim, d_model]: contraction dim sharded over model."""
        return P(self.model_axis, None)


# --- FSDP (ZeRO-style parameter + optimizer-state sharding) -----------
def add_fsdp_to_spec(
    spec: P | None,
    shape: tuple[int, ...],
    fsdp_size: int,
    *,
    fsdp_axis: str = FSDP_AXIS,
    min_size: int = FSDP_MIN_SIZE,
) -> P:
    """One leaf's FSDP spec: shard the LARGEST still-unsharded,
    divisible dimension over the ``fsdp`` axis, on top of whatever TP
    layout ``spec`` already declares.

    Rules (docs/DESIGN.md §12):
    - leaves with fewer than ``min_size`` elements stay as-is
      (replicated over fsdp) — sharding a layernorm scale buys bytes
      nobody needs at the price of a collective per use;
    - only dimensions the TP spec leaves unsharded are eligible (an
      axis can appear once per spec), and only those divisible by the
      fsdp axis size (``jax.device_put`` needs even shards);
    - among eligible dims, the largest wins (ties → first), which
      maximises the bytes actually partitioned;
    - a leaf with NO eligible dim stays as-is — correct (GSPMD treats
      it as replicated over fsdp) and loud in the bench numbers rather
      than an error, since e.g. a [3, V, D] stacked table with V taken
      by TP and 3 < fsdp_size has nowhere to split.
    """
    full = tuple(spec) if spec is not None else ()
    full = full + (None,) * (len(shape) - len(full))
    size = int(np.prod(shape, dtype=np.int64)) if shape else 0
    if size < min_size:
        return P(*full)
    candidates = [
        d
        for d in range(len(shape))
        if full[d] is None and shape[d] % fsdp_size == 0
    ]
    if not candidates:
        return P(*full)
    best = max(candidates, key=lambda d: shape[d])
    new = list(full)
    new[best] = fsdp_axis
    return P(*new)


def fsdp_spec_tree(
    params,
    spec_tree,
    fsdp_size: int,
    *,
    fsdp_axis: str = FSDP_AXIS,
    min_size: int = FSDP_MIN_SIZE,
):
    """Derive the full FSDP spec pytree for ``params``.

    ``spec_tree`` is the model's TP layout (``param_shardings()``) or
    ``None`` for models without one (linear, MLP — everything starts
    replicated). The result feeds ``place_params`` unchanged;
    optimizer moments then mirror the PLACED params' shardings via
    ``mesh.state_shardings_like`` (jit-initialising from placed
    params does not inherit them — the moments must be placed
    explicitly).
    """
    from mlapi_tpu.ops.quant import _is_quant_leaf

    if spec_tree is None:
        spec_tree = jax.tree.map(
            lambda _: P(), params, is_leaf=_is_quant_leaf
        )

    def one(leaf, spec):
        shape = (
            leaf["q"].shape if _is_quant_leaf(leaf) else np.shape(leaf)
        )
        return add_fsdp_to_spec(
            spec, tuple(shape), fsdp_size,
            fsdp_axis=fsdp_axis, min_size=min_size,
        )

    return jax.tree.map(one, params, spec_tree, is_leaf=_is_quant_leaf)


def bytes_per_device(tree) -> int:
    """Max-over-devices of the bytes a pytree's shards occupy locally
    (``addressable_shards[...].data.nbytes``) — the committed,
    deterministic measure of the FSDP memory win (wall-clock on this
    box swings ±25-30%; byte counts do not). A replicated leaf costs
    its full ``nbytes`` on EVERY device; an fsdp-sharded leaf 1/axis
    of it. Host numpy leaves count once (single-device placement)."""
    per_dev: dict = {}
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is not None:
            for s in shards:
                per_dev[s.device] = per_dev.get(s.device, 0) + s.data.nbytes
        elif hasattr(leaf, "nbytes"):
            per_dev[None] = per_dev.get(None, 0) + leaf.nbytes
    return max(per_dev.values(), default=0)
