"""LoRA (low-rank adaptation) fine-tuning for any model family.

``LoraModel(inner, rank=r)`` trains two small matrices per target
kernel — ``a [in, r]`` and ``b [r, out]`` — while the base weights
stay frozen (``stop_gradient`` in the merge + a masked optimizer, so
base weights get no gradient math and NO optimizer moments: for adamw
that is the difference between 3x and ~1.01x parameter memory during
fine-tuning, which is what lets a big pretrained model fine-tune on
hardware that could only just serve it).

TPU-first shape discipline: the merge ``W_eff = W + (alpha/r)·a@b``
happens INSIDE the traced step, so the train step stays one fused XLA
program with static shapes; ``b`` initializes to zero, so step 0 is
byte-identical to the base model (the standard LoRA guarantee).

Single-tenant serving never sees LoRA: ``merge_params`` folds the
adaptation back into a plain parameter tree that checkpoints and
serves through the unchanged engines. MANY-tenant serving keeps the
base un-merged instead and applies per-request adapters from a
device slot pool (``serving/adapter_store.py``); the serving-side
helpers at the bottom of this module — :func:`lora_apply` inside the
traced blocks, :func:`export_adapter` / :func:`merge_adapter` at the
edges — carry that path.

The reference (`/root/reference`) has no fine-tuning story at all —
this exists for the framework's own pretrained-model scale.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

# Kernel-holding nodes adapted by default: every dense projection the
# decoder/encoder families register under these names. GPT/BERT store
# them as ``{"kernel", "bias"}`` dicts; Llama as bare 2-D arrays —
# both shapes are matched.
DEFAULT_TARGETS = (
    "qkv", "attn_out", "ffn_up", "ffn_down",              # gpt / bert
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",   # llama
)


def _kernel_of(node):
    """The 2-D kernel held by a target node, or None."""
    if isinstance(node, dict) and getattr(
        node.get("kernel"), "ndim", 0
    ) == 2:
        return node["kernel"]
    if getattr(node, "ndim", 0) == 2:
        return node
    return None


def _walk_targets(tree, targets, path=()):
    """Yield (path, kernel) for every adapted kernel."""
    if isinstance(tree, dict):
        for k, v in tree.items():
            kernel = _kernel_of(v) if k in targets else None
            if kernel is not None:
                yield path + (k,), kernel
            else:
                yield from _walk_targets(v, targets, path + (k,))


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


@dataclass(frozen=True)
class LoraModel:
    """Low-rank adapter over any registered model family."""

    inner: object
    rank: int = 8
    alpha: float = 16.0
    targets: tuple = DEFAULT_TARGETS

    def __getattr__(self, name):
        return getattr(self.inner, name)

    @property
    def scale(self) -> float:
        return self.alpha / self.rank

    # -- parameters -----------------------------------------------------
    def init(self, rng, base_params=None):
        """``{"base": <inner params>, "lora": {<joined path>: {a, b}}}``.
        ``base_params`` lets a pretrained checkpoint seed the frozen
        part; ``b`` starts at zero so the adapted model initially
        equals the base exactly."""
        base = self.inner.init(rng) if base_params is None else base_params
        lora = {}
        # Deterministic per-adapter streams: fold by enumeration order
        # (dict order is construction order, which init() fixes) —
        # never by Python string hash, which is salted per process.
        for i, (path, kernel) in enumerate(
            _walk_targets(base, self.targets)
        ):
            d_in, d_out = kernel.shape
            key = jax.random.fold_in(rng, i)
            lora["/".join(path)] = {
                "a": (1.0 / d_in**0.5)
                * jax.random.normal(key, (d_in, self.rank)),
                "b": jnp.zeros((self.rank, d_out)),
            }
        if not lora:
            raise ValueError(
                f"no LoRA targets found in {type(self.inner).__name__} "
                f"params (targets={self.targets})"
            )
        return {"base": base, "lora": lora}

    def merge_params(self, params, *, stop_base_gradient: bool = False):
        """Fold the adapters into a PLAIN inner-model tree:
        ``W + (alpha/rank)·a@b`` per target. Traced (used inside the
        train step) or eager (export for serving — the result
        checkpoints and serves like any base-model tree)."""
        base, lora = params["base"], params["lora"]
        if stop_base_gradient:
            base = jax.lax.stop_gradient(base)
        merged = jax.tree.map(lambda x: x, base)  # fresh containers

        for joined, ab in lora.items():
            path = tuple(joined.split("/"))
            parent = _get(merged, path[:-1])
            node = parent[path[-1]]
            w = _kernel_of(node)
            delta = (self.scale * ab["a"] @ ab["b"]).astype(w.dtype)
            if isinstance(node, dict):
                node = dict(node)
                node["kernel"] = w + delta
                parent[path[-1]] = node
            else:
                parent[path[-1]] = w + delta
        return merged

    # -- model protocol -------------------------------------------------
    def apply(self, params, *args, **kwargs):
        return self.inner.apply(
            self.merge_params(params, stop_base_gradient=True),
            *args, **kwargs,
        )

    def generate(self, params, prompt_ids, **kwargs):
        return self.inner.generate(
            self.merge_params(params), prompt_ids, **kwargs
        )

    def trainable_mask(self, params) -> dict:
        """Pytree of bools matching ``params``: only the adapters
        train. The train loop hands this to ``optax.masked`` so the
        frozen base gets no update AND no optimizer state."""
        return {
            "base": jax.tree.map(lambda _: False, params["base"]),
            "lora": jax.tree.map(lambda _: True, params["lora"]),
        }

    def param_shardings(self, layout=None) -> dict:
        """Adapters are tiny — replicate them; the base keeps the
        inner model's layout."""
        from mlapi_tpu.parallel import SpecLayout

        lo = layout or SpecLayout()
        if not hasattr(self.inner, "param_shardings"):
            raise NotImplementedError(
                f"{type(self.inner).__name__} has no param_shardings"
            )
        # eval_shape: tree structure only, no parameter allocation —
        # the base may be large.
        probe = jax.eval_shape(
            lambda: self.inner.init(jax.random.key(0))
        )
        lora = {
            "/".join(p): {"a": lo.replicated(), "b": lo.replicated()}
            for p, _ in _walk_targets(probe, self.targets)
        }
        return {
            "base": self.inner.param_shardings(layout),
            "lora": lora,
        }


# -- serving-side application (many-adapter slot pool) -----------------
def lora_apply(layer, target, x, y):
    """``y + adapter delta`` for a block matmul ``y = x @ W[target]``
    when the layer dict carries serving adapter state, else ``y``
    ITSELF — the presence check is a static Python branch at trace
    time, so a build with no adapter traffic traces byte-identical
    programs (no masked zero-delta ops riding every batch).

    The state (installed by ``AdapterSlots.batch_params``) is
    ``layer["lora"] = {target: {"a": [S, d_in, r], "b": [S, r,
    d_out]}, ...}`` plus ONE marker: scalar ``"slot"`` (grouped batch
    — a single tenant, one plain ``x @ A @ B`` per target) or int32
    ``"rows"`` ``[B]`` (mixed tenants — the gathered BGMV path,
    ``ops/bgmv.py``; base rows index the all-zero NULL slot 0)."""
    lora = layer.get("lora") if isinstance(layer, dict) else None
    if lora is None:
        return y
    ab = lora.get(target)
    if ab is None:
        return y
    a, b = ab["a"], ab["b"]
    rows = lora.get("rows")
    if rows is not None:
        from mlapi_tpu.ops.bgmv import bgmv

        return y + bgmv(x, a, b, rows)
    slot = lora["slot"]
    return y + (x @ a[slot].astype(x.dtype)) @ b[slot].astype(x.dtype)


def export_adapter(lora_params: dict, scale: float) -> dict:
    """A trained adapter tree (``params["lora"]``: ``{"layer_0/qkv":
    {a, b}}`` joined paths) → the CANONICAL serving payload
    ``{layer: {target: {a, b}}}`` with ``b`` pre-scaled by
    alpha/rank, so the serving delta is exactly ``x @ a @ b`` and no
    scale rides the wire, the store, or the slot pool."""
    import numpy as np

    out: dict = {}
    for joined, ab in lora_params.items():
        path = joined.split("/")
        out.setdefault(path[0], {})[path[-1]] = {
            "a": np.asarray(ab["a"]),
            "b": np.asarray(scale * ab["b"]),
        }
    return out


def merge_adapter(params: dict, payload: dict) -> dict:
    """Eagerly fold a serving payload into a fresh plain params tree:
    ``W + a @ b`` per target (``b`` already carries the scale). The
    merged-weights REFERENCE for the slot-path token-identity pins
    (tests) — and the escape hatch for serving one tenant on
    an engine built without adapter slots."""
    merged = jax.tree.map(lambda x: x, params)  # fresh containers
    for ln, layer in payload.items():
        for target, ab in layer.items():
            node = merged[ln][target]
            w = _kernel_of(node)
            delta = (jnp.asarray(ab["a"]) @ jnp.asarray(ab["b"])).astype(
                w.dtype
            )
            if isinstance(node, dict):
                node = dict(node)
                node["kernel"] = w + delta
                merged[ln][target] = node
            else:
                merged[ln][target] = w + delta
    return merged
