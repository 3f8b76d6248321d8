"""Laguna-style decoder: attention layers that differ in mask, head
count and positions inside one model, over sparse experts.

The published model (``poolside/Laguna-XS.2``, ``model_type: laguna``)
repeats one full-attention layer and three sliding-window layers. The
kind of layer ``n`` (0-indexed, as published) is ``layer_types[n]``, its
query-head count ``heads_per_layer[n]`` (the config's
``num_attention_heads_per_layer``: 48 on full layers, 64 on sliding
ones) over ``num_kv_heads`` shared K/V heads, its FFN
``mlp_layer_types[n]`` (``dense`` | ``sparse``). Pre-norm residual
blocks (``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``),
final RMSNorm, untied head, no biases.

**Attention**, layer ``n`` with ``H`` query heads of ``d = head_dim``:
``q = x W_q [L, H, d]``, ``k, v = x W_k, x W_v [L, KV, d]``; query head
``h`` reads K/V head ``h // (H / KV)`` (never repeated in HBM:
``flash_attention`` is GQA-native in both passes). Positions by layer
kind (``rope_sliding`` / ``rope_full``, the config's
``rope_parameters`` entries under their own keys): a ``default`` entry
rotates ``partial_rotary_factor * d`` lanes at ``rope_theta``; a
``yarn`` entry rotates them with the interpolated table
(``llama.yarn_inv_freq``) and scales ``cos`` and ``sin`` by
``attention_factor``; both are data to ONE rotation
(``llama.rotate``). Causal softmax over ``q k^T / sqrt(d)``; on sliding
layers also ``q_pos - k_pos < sliding_window`` (a position sees itself
and the ``window - 1`` before it). ``gating``: ``g = sigmoid(x W_g)``,
``W_g [hidden, H]``, one number a head from the layer's normed input,
scales head ``h``'s output before ``W_o``.

**FFN**: a SwiGLU of ``intermediate_size`` on dense layers; on sparse
ones the expert layer of ``models/experts.py`` (sigmoid router over all
``num_experts``, the top ``num_experts_per_tok``, renormalised and
scaled by ``moe_routed_scaling_factor`` on the experts' outputs, no
selection bias, one shared SwiGLU of
``shared_expert_intermediate_size``), with ``experts_held = (first,
count)`` the chip's share of an expert-parallel layer.

Training only: the serving cache and decode kernels have no window and
one head count for every layer; the serving CLI refuses such a
checkpoint (``serving_refusal``). ``apply_with_stats`` hands
``make_train_step`` the six ``moe.*`` device scalars a step that
``kimi_linear_lm`` does.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from mlapi_tpu.models import experts, register_model
from mlapi_tpu.models.experts import mm as _mm
from mlapi_tpu.models.llama import rope_inv_freq, rotate, yarn_inv_freq
from mlapi_tpu.ops.pallas.flash_attention import REMAT_NAMES as _FLASH_NAMES
from mlapi_tpu.ops.pallas.flash_attention import flash_attention_on_mesh
from mlapi_tpu.utils.platform import pallas_interpret

FULL, SLIDING = "full_attention", "sliding_attention"
_SCOPE = {FULL: "attn.full", SLIDING: "attn.sliding"}


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return x32 * inv * scale.astype(jnp.float32)


def rope_table(rope: dict, head_dim: int):
    """``(inv_freq, rotated lanes, scale)`` of one ``rope_parameters``
    entry: what :func:`mlapi_tpu.models.llama.rotate` takes."""
    dims = int(head_dim * rope.get("partial_rotary_factor", 1))
    theta = rope["rope_theta"]
    if rope.get("rope_type", "default") == "default":
        return rope_inv_freq(theta, dims), dims, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    inv_freq = yarn_inv_freq(
        theta, dims, factor=rope["factor"],
        original_max=rope["original_max_position_embeddings"],
        beta_fast=rope.get("beta_fast", 32), beta_slow=rope.get("beta_slow", 1))
    scale = rope.get("attention_factor") or 0.1 * math.log(rope["factor"]) + 1
    return inv_freq, dims, float(scale)


@register_model("laguna_lm")
@dataclass(frozen=True)
class LagunaLM:
    """Decoder-only causal LM, Laguna architecture (training)."""

    input_kind = "text"
    serving_refusal = (
        "a laguna_lm checkpoint trains but cannot be served yet: the "
        "engine's cache and decode kernels have no sliding window and "
        "one head count for every layer"
    )

    vocab_size: int = 512
    hidden_size: int = 64
    num_layers: int = 5
    # 0-indexed, as published; may run past num_layers (the published
    # lists with a cut depth)
    layer_types: tuple = (FULL, SLIDING, SLIDING, SLIDING, FULL)
    heads_per_layer: tuple = (6, 8, 8, 8, 6)
    mlp_layer_types: tuple = ("dense", "sparse", "sparse", "sparse", "sparse")
    num_kv_heads: int = 2
    head_dim: int = 16
    sliding_window: int = 32
    # the config's rope_parameters entries, under their own keys
    rope_full: dict | tuple = (
        ("rope_type", "yarn"), ("rope_theta", 500000.0), ("factor", 64.0),
        ("original_max_position_embeddings", 64), ("beta_fast", 64.0),
        ("beta_slow", 1.0), ("partial_rotary_factor", 0.5))
    rope_sliding: dict | tuple = (
        ("rope_type", "default"), ("rope_theta", 10000.0),
        ("partial_rotary_factor", 1))
    intermediate_size: int = 256
    # expert FFN
    num_experts: int = 16
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 32
    shared_expert_intermediate_size: int = 32
    moe_routed_scaling_factor: float = 2.5
    # (first id, count) of the routed experts this model holds;
    # None: all of them
    experts_held: tuple | None = None
    moe_tile: int = 256
    rms_norm_eps: float = 1e-6
    compute_dtype: str = "bfloat16"
    # every block under jax.checkpoint; a block's recomputation keeps
    # what the flash forward kernel and the router made (the names their
    # producers set), everything else is made again from its input
    remat: bool = True
    mesh: object = None

    def __post_init__(self):
        for name in ("layer_types", "heads_per_layer", "mlp_layer_types",
                     "experts_held"):
            val = getattr(self, name)
            if isinstance(val, list):
                object.__setattr__(self, name, tuple(val))
        for name in ("rope_full", "rope_sliding"):
            val = getattr(self, name)
            if isinstance(val, dict):
                object.__setattr__(self, name, tuple(sorted(val.items())))
        n = self.num_layers
        for name in ("layer_types", "heads_per_layer", "mlp_layer_types"):
            if len(getattr(self, name)) < n:
                raise ValueError(f"{name} names fewer than {n} layers")
        for kind, heads in zip(self.layer_types[:n], self.heads_per_layer):
            if kind not in _SCOPE:
                raise ValueError(f"layer type {kind!r}")
            if heads % self.num_kv_heads:
                raise ValueError(
                    f"{heads} query heads over {self.num_kv_heads} kv heads")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(
                f"experts_held {self.experts_held} outside 0.."
                f"{self.num_experts}")

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    # ------------------------------------------------------------------
    def init(self, rng: jax.Array) -> dict:
        h, v, d = self.hidden_size, self.vocab_size, self.head_dim
        keys = iter(jax.random.split(rng, 4 + 16 * self.num_layers))

        def w(*shape, scale=0.02):
            return scale * jax.random.normal(next(keys), shape, jnp.float32)

        ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731

        def ffn(i):
            return {"gate": w(h, i), "up": w(h, i), "down": w(i, h)}

        kv, ie = self.num_kv_heads * d, self.moe_intermediate_size
        params = {"embed": w(v, h), "final_norm": ones(h), "lm_head": w(h, v)}
        for n in range(self.num_layers):
            nh = self.heads_per_layer[n]
            layer = {
                "attn_norm": ones(h), "ffn_norm": ones(h),
                "attn": {"q": w(h, nh * d), "k": w(h, kv), "v": w(h, kv),
                         "gate": w(h, nh), "o": w(nh * d, h)},
            }
            if self.mlp_layer_types[n] == "dense":
                layer["mlp"] = ffn(self.intermediate_size)
            else:
                layer["moe"] = {
                    "router": w(h, self.num_experts),
                    "experts": {"gate": w(self.held[1], h, ie),
                                "up": w(self.held[1], h, ie),
                                "down": w(self.held[1], ie, h)},
                    "shared": ffn(self.shared_expert_intermediate_size),
                }
            params[f"layer_{n}"] = layer
        return params

    # ------------------------------------------------------------------
    def _attn(self, kind, p, x):
        """One attention layer on its normed input ``x [B, L, hidden]``;
        the head count is the projections' own."""
        cdt = jnp.dtype(self.compute_dtype)
        b, l, _ = x.shape
        d, kvh = self.head_dim, self.num_kv_heads
        nh = p["gate"].shape[-1]
        sliding = kind == SLIDING
        q = _mm(x, p["q"], cdt).reshape(b, l, nh, d)
        k = _mm(x, p["k"], cdt).reshape(b, l, kvh, d)
        v = _mm(x, p["v"], cdt).reshape(b, l, kvh, d)
        with jax.named_scope("attn.rope"):
            inv_freq, dims, scale = rope_table(
                dict(self.rope_sliding if sliding else self.rope_full), d)
            pos = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32), (b, l))
            q, k = (rotate(a, pos, inv_freq, rot_dims=dims, scale=scale)
                    for a in (q, k))
        with jax.named_scope(_SCOPE[kind] + ".core"):
            ctx = flash_attention_on_mesh(
                self.mesh, q.astype(cdt), k.astype(cdt), v.astype(cdt),
                causal=True, window=self.sliding_window if sliding else None,
                interpret=pallas_interpret())
        with jax.named_scope("attn.gate"):
            g = jax.nn.sigmoid(_mm(x, p["gate"], cdt))
            ctx = ctx.astype(jnp.float32) * g[..., None]
        return _mm(ctx.reshape(b, l, nh * d), p["o"], cdt)

    def _ffn(self, p, x):
        return experts.ffn(p, x, jnp.dtype(self.compute_dtype))

    def _moe(self, p, x):
        """The held experts' part plus the shared expert, and the
        layer's ``(pairs here, fullest held expert's pairs, tiles
        run)``."""
        return experts.moe(
            p, x, k=self.num_experts_per_tok, held=self.held,
            tile=self.moe_tile, scale=self.moe_routed_scaling_factor,
            compute_dtype=self.compute_dtype)

    def _block(self, n, layer, x):
        kind = self.layer_types[n]
        xn = _rms_norm(x, layer["attn_norm"], self.rms_norm_eps)
        with jax.named_scope(_SCOPE[kind]):
            x = x + self._attn(kind, layer["attn"], xn)
        xn = _rms_norm(x, layer["ffn_norm"], self.rms_norm_eps)
        zero = jnp.zeros((), jnp.int32)
        if self.mlp_layer_types[n] == "dense":
            return x + self._ffn(layer["mlp"], xn), (zero, zero, zero)
        y, load = self._moe(layer["moe"], xn)
        return x + y, load

    def apply_with_stats(self, params: dict, token_ids):
        """``[B, L]`` ids -> ``[B, L, V]`` float32 logits, and the
        step's expert load as ``kimi_linear_lm`` reports it:
        ``moe.pairs_routed``, ``moe.pairs_here``,
        ``moe.expert_load_max``, ``moe.load_max_over_mean``,
        ``moe.tiles_run``, ``moe.rows_run``."""
        cdt = jnp.dtype(self.compute_dtype)
        x = params["embed"][token_ids].astype(jnp.float32)
        loads = []
        keep = jax.checkpoint_policies.save_only_these_names(
            *_FLASH_NAMES, *experts.ROUTE_NAMES)
        for n in range(self.num_layers):
            block = functools.partial(self._block, n)
            if self.remat:
                block = jax.checkpoint(block, policy=keep)
            x, load = block(params[f"layer_{n}"], x)
            loads.append(load)
        with jax.named_scope("lm_head"):
            logits = _mm(
                _rms_norm(x, params["final_norm"], self.rms_norm_eps),
                params["lm_head"], cdt)
        sparse = sum(kind == "sparse"
                     for kind in self.mlp_layer_types[:self.num_layers])
        routed = token_ids.size * self.num_experts_per_tok * sparse
        return logits, experts.load_stats(loads, self.held[1], routed,
                                          self.moe_tile)

    def apply(self, params: dict, token_ids) -> jax.Array:
        return self.apply_with_stats(params, token_ids)[0]
