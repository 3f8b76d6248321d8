"""Llama-style decoder: RMSNorm, rotary positions, SwiGLU, GQA.

Second decoder family in the zoo (reference repo has none —
`/root/reference` is a serving-only sklearn tutorial; this family
exists because a complete framework serves the architectures users
actually deploy). Differences from :class:`mlapi_tpu.models.gpt.GptLM`
and why they matter on TPU:

- **Rotary position embeddings** instead of a learned ``wpe`` table:
  positions enter as a per-row rotation of q/k, so the KV cache stores
  *rotated* keys and decode needs no position-table lookup. Left-pad
  bucketing composes exactly: row ``b``'s effective position is
  ``idx - n_pad[b]`` (clamped), the same shift discipline the GPT
  path proves bucket-invariance with.
- **Grouped-query attention** (``num_kv_heads < num_heads``): the
  cache shrinks by the group factor — the serving cache is HBM-
  resident state per concurrent request, so GQA directly raises the
  max decode batch. K/V heads are broadcast to query heads with a
  reshape-free ``jnp.repeat`` at attention time (XLA fuses it).
- **RMSNorm + SwiGLU, no biases** — fewer, larger fused ops.

The incremental-decoding machinery (prefill program, chunked
``lax.scan`` decode, per-row sampling streams, top-k/top-p) is SHARED
with the GPT family via the model-generic helpers in ``gpt.py``
(``_generate_fn``, ``prefill_fn``, ``decode_chunk_fn``): this class
plugs in through ``prefill_core``/``decode_step``/``init_cache``, so
the serving engine (`serving/engine.py::TextGenerationEngine`) works
unchanged.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from mlapi_tpu.models import register_model
from mlapi_tpu.utils.platform import pallas_interpret


def _rms_norm(x, scale, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (x32 * inv * scale.astype(jnp.float32)).astype(x.dtype)


def rope_inv_freq(theta: float, dims: int):
    """Plain rotary frequencies of ``dims`` rotated lanes:
    ``theta ** (-2i / dims)``, ``i < dims / 2``."""
    half = dims // 2
    return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def yarn_inv_freq(theta: float, dims: int, *, factor: float,
                  original_max: int, beta_fast: float, beta_slow: float):
    """YaRN frequencies of ``dims`` rotated lanes (HF
    ``_compute_yarn_parameters``, its floor / ceil and clamp included):
    with ``f_i`` the plain frequencies, ``lo`` / ``hi`` the lane pairs
    whose wavelength makes ``beta_fast`` / ``beta_slow`` rotations in
    ``original_max`` positions and ``ramp_i = clip((i - lo) / (hi -
    lo), 0, 1)``: ``f_i / factor * ramp_i + f_i * (1 - ramp_i)``. The
    attention factor is the caller's ``scale`` of :func:`rotate`."""
    def pair_of(rotations):
        return dims * math.log(original_max / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    lo = max(math.floor(pair_of(beta_fast)), 0)
    hi = min(math.ceil(pair_of(beta_slow)), dims - 1)
    if lo == hi:
        hi += 0.001
    f = rope_inv_freq(theta, dims)
    ramp = jnp.clip(
        (jnp.arange(dims // 2, dtype=jnp.float32) - lo) / (hi - lo), 0, 1)
    return f / factor * ramp + f * (1 - ramp)


def rotate(x, positions, inv_freq, *, rot_dims: int | None = None,
           scale: float = 1.0):
    """Rotate the first ``rot_dims`` lanes (default: all) of ``x [B, L,
    H, D]`` by per-row-and-position angles ``positions * inv_freq``
    (``inv_freq [rot_dims / 2]``: plain, YaRN or any other table is
    data here); ``cos`` and ``sin`` are multiplied by ``scale`` (YaRN's
    attention factor); the lanes beyond ``rot_dims`` pass unrotated.

    ``positions``: ``[B, L]`` int32 effective positions (already
    n_pad-shifted and clamped by callers). rotate-half convention:
    lane ``i < rot_dims / 2`` pairs with lane ``i + rot_dims / 2``.

    Written as ``x * cos + rotate_half(x) * sin`` over the FULL lane
    dim, with ``rotate_half`` a product with a constant signed
    permutation matrix — deliberately NOT the textbook
    slice-halves-and-concatenate. Under GSPMD, slice+concat over a dim
    the ``model`` axis shards finer than one KV head (GQA: ``wk`` is
    ``[h, kvh*hd]``; a TP degree above ``kvh`` splits heads)
    MISCOMPILES on this jax/XLA version — the partitioner returns
    scrambled values, wrong by O(1) even at position 0 where rope is
    the identity (repro pinned in
    tests/test_llama.py::test_rope_is_identity_at_position_zero_tp).
    A product partitions correctly under every layout and is exact:
    every output lane is plus or minus ONE input lane, at ``HIGHEST``
    precision in any dtype. (A constant-index ``take`` is as exact,
    but the TPU compiler lowers it to a gather between two transposes
    of the whole operand: described-v5e compile at ``[1, 8192, 64,
    128]``, PERF.md, PR 36.)
    """
    d = x.shape[-1]
    r = d if rot_dims is None else rot_dims
    half = r // 2
    lane = jnp.arange(d)
    turned = lane < r
    # Per-lane angle: lane j pairs with lane (j + half) % r and both
    # use frequency j % half.
    ang = positions.astype(jnp.float32)[..., None] * jnp.where(
        turned, inv_freq[lane % half], 0.0)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    cos = jnp.where(turned, cos, 1.0)[:, :, None, :].astype(x.dtype)
    sin = jnp.where(turned, sin, 0.0)[:, :, None, :].astype(x.dtype)
    # rotate_half(x)[j] = -x[j + half] (j < half), x[j - half]
    # (half <= j < r), nothing beyond r.
    src = jnp.where(lane < half, lane + half, lane - half)
    sign = jnp.where(lane < half, -1.0, jnp.where(turned, 1.0, 0.0))
    perm = (lane[:, None] == src[None, :]) * sign[None, :]
    xr = jnp.einsum("blhd,de->blhe", x, perm.astype(x.dtype),
                    precision=jax.lax.Precision.HIGHEST)
    return x * cos + xr * sin


def _rope(x, positions, theta: float):
    """Plain rotary over all lanes of ``x [B, L, H, D]``."""
    return rotate(x, positions, rope_inv_freq(theta, x.shape[-1]))


@register_model("llama_lm")
@dataclass(frozen=True)
class LlamaLM:
    """Decoder-only causal LM, Llama-family architecture."""

    input_kind = "text"

    vocab_size: int = 512
    hidden_size: int = 256
    num_layers: int = 4
    num_heads: int = 4
    num_kv_heads: int | None = None  # None -> MHA (== num_heads)
    intermediate_size: int | None = None  # None -> 8/3 * h, 128-rounded
    max_positions: int = 256
    rope_theta: float = 10_000.0
    compute_dtype: str = "bfloat16"
    # "full" | "flash" | "ring" — same contract as GptLM.apply.
    attention_impl: str = "full"
    mesh: object = None
    seq_axis: str = "seq"
    ring_block_impl: str = "einsum"
    ring_zigzag: bool = False
    # KV-cache storage format — same contract as ``GptLM.kv_quant``
    # ("none" | "int8"); composes with GQA (the int8 payload shrinks
    # the ALREADY-grouped [B, L, KVH, D] cache a further ~2x).
    kv_quant: str = "none"
    # Cache-read attention — same contract as
    # ``GptLM.decode_attn_impl`` ("einsum" | "flash"; "flash" covers
    # single-token decode AND multi-token extend spans). The flash
    # kernels are GQA-native: scales and payload index per KV head,
    # queries grouped in-register — the repeated K/V tensor the
    # einsum path broadcasts (``_repeat_kv``) never exists.
    decode_attn_impl: str = "einsum"

    def __post_init__(self):
        from mlapi_tpu.ops.quant import KV_FORMATS

        if self.kv_quant not in KV_FORMATS:
            raise ValueError(
                f"unknown kv_quant {self.kv_quant!r}; one of {KV_FORMATS}"
            )
        if self.decode_attn_impl not in ("einsum", "flash"):
            raise ValueError(
                f"unknown decode_attn_impl {self.decode_attn_impl!r}; "
                'one of ("einsum", "flash")'
            )
        if self.attention_impl not in ("full", "flash", "ring"):
            raise ValueError(f"unknown attention_impl {self.attention_impl!r}")
        if self.attention_impl == "ring" and self.mesh is None:
            raise ValueError('attention_impl="ring" requires a mesh')
        if self.ring_zigzag and self.ring_block_impl != "flash":
            raise ValueError('ring_zigzag needs ring_block_impl="flash"')
        if self.num_kv_heads is not None and self.num_kv_heads < 1:
            raise ValueError(f"num_kv_heads must be >= 1, got {self.num_kv_heads}")
        if self.hidden_size % self.num_heads:
            raise ValueError("hidden_size must divide evenly into heads")
        if self.num_heads % self.kv_heads:
            raise ValueError(
                f"num_heads ({self.num_heads}) must be a multiple of "
                f"num_kv_heads ({self.kv_heads})"
            )
        if self.head_dim % 2:
            raise ValueError("rotary embeddings need an even head_dim")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.num_heads if self.num_kv_heads is None else self.num_kv_heads

    @property
    def ffn_size(self) -> int:
        if self.intermediate_size is not None:
            return self.intermediate_size
        return max(128, (8 * self.hidden_size // 3 + 127) // 128 * 128)

    # ------------------------------------------------------------------
    def init(self, rng: jax.Array) -> dict:
        h, f, v = self.hidden_size, self.ffn_size, self.vocab_size
        kvh, hd = self.kv_heads, self.head_dim
        keys = iter(jax.random.split(rng, 2 + 7 * self.num_layers))

        def w(k, shape, scale=0.02):
            return scale * jax.random.normal(k, shape)

        params = {
            "wte": w(next(keys), (v, h)),
            "lm_head": w(next(keys), (h, v)),
            "rms_f_scale": jnp.ones((h,)),
        }
        for n in range(self.num_layers):
            params[f"layer_{n}"] = {
                "wq": w(next(keys), (h, h)),
                "wk": w(next(keys), (h, kvh * hd)),
                "wv": w(next(keys), (h, kvh * hd)),
                "wo": w(next(keys), (h, h)),
                "rms1_scale": jnp.ones((h,)),
                "w_gate": w(next(keys), (h, f)),
                "w_up": w(next(keys), (h, f)),
                "w_down": w(next(keys), (f, h)),
                "rms2_scale": jnp.ones((h,)),
            }
        return jax.tree.map(lambda a: a.astype(jnp.float32), params)

    # ------------------------------------------------------------------
    def _qkv(self, layer, xn, positions):
        """Project + rope one block's q/k/v. ``positions`` is the
        per-row effective position of every residual-stream slot."""
        from mlapi_tpu.models.lora import lora_apply

        cdt = jnp.dtype(self.compute_dtype)
        b, l, _ = xn.shape
        nh, kvh, hd = self.num_heads, self.kv_heads, self.head_dim
        q = lora_apply(
            layer, "wq", xn, xn @ layer["wq"].astype(cdt)
        ).reshape(b, l, nh, hd)
        k = lora_apply(
            layer, "wk", xn, xn @ layer["wk"].astype(cdt)
        ).reshape(b, l, kvh, hd)
        v = lora_apply(
            layer, "wv", xn, xn @ layer["wv"].astype(cdt)
        ).reshape(b, l, kvh, hd)
        return _rope(q, positions, self.rope_theta), _rope(
            k, positions, self.rope_theta
        ), v

    def _block(self, layer, x, positions, attend):
        # lora_apply: per-tenant serving delta — static no-op unless
        # the dispatch augmented this layer with a "lora" sub-dict
        # (serving/adapter_store.py slot pool).
        from mlapi_tpu.models.lora import lora_apply

        cdt = jnp.dtype(self.compute_dtype)
        xn = _rms_norm(x, layer["rms1_scale"]).astype(cdt)
        q, k, v = self._qkv(layer, xn, positions)
        ctx = attend(q, k, v).reshape(x.shape[0], x.shape[1], -1)
        wo = lora_apply(layer, "wo", ctx, ctx @ layer["wo"].astype(cdt))
        x = x + wo.astype(jnp.float32)

        xn = _rms_norm(x, layer["rms2_scale"]).astype(cdt)
        gate = jax.nn.silu(
            lora_apply(
                layer, "w_gate", xn, xn @ layer["w_gate"].astype(cdt)
            ).astype(jnp.float32)
        ).astype(cdt)
        up = lora_apply(layer, "w_up", xn, xn @ layer["w_up"].astype(cdt))
        gu = gate * up
        down = lora_apply(
            layer, "w_down", gu, gu @ layer["w_down"].astype(cdt)
        )
        return x + down.astype(jnp.float32)

    def _repeat_kv(self, k):
        group = self.num_heads // self.kv_heads
        return k if group == 1 else jnp.repeat(k, group, axis=2)

    def apply(self, params: dict, token_ids) -> jax.Array:
        """``[B, L]`` ids → ``[B, L, V]`` next-token logits (causal)."""
        from mlapi_tpu.ops import full_attention

        b, l = token_ids.shape
        x = params["wte"][token_ids]
        positions = jnp.broadcast_to(jnp.arange(l)[None], (b, l))

        if self.attention_impl == "flash":
            from mlapi_tpu.ops.pallas import flash_attention_on_mesh

            def attend(q, k, v):
                # The kernel is GQA-native: raw kv heads go straight
                # in, no repeated K/V tensor in HBM.
                return flash_attention_on_mesh(
                    self.mesh, q, k, v, causal=True,
                    interpret=pallas_interpret(),
                )
        elif self.attention_impl == "ring":
            from mlapi_tpu.ops import ring_self_attention

            def attend(q, k, v):
                return ring_self_attention(
                    self.mesh, q, self._repeat_kv(k), self._repeat_kv(v),
                    causal=True, seq_axis=self.seq_axis, head_axis="model",
                    block_impl=self.ring_block_impl,
                    zigzag=self.ring_zigzag,
                )
        else:
            def attend(q, k, v):
                return full_attention(
                    q, self._repeat_kv(k), self._repeat_kv(v), causal=True
                )

        for n in range(self.num_layers):
            x = self._block(params[f"layer_{n}"], x, positions, attend)
        x = _rms_norm(x, params["rms_f_scale"])
        return x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)

    # -- incremental decoding (shared engine contract) -----------------
    def init_cache(self, batch: int, max_len: int) -> dict:
        """``[B, max_len, KVH, D]`` per layer — GQA shrinks this by
        ``num_heads / num_kv_heads`` vs the query-head count; under
        ``kv_quant="int8"`` the layer holds int8 payload + f32 scales
        instead (``ops/quant.init_kv_cache``)."""
        from mlapi_tpu.ops.quant import init_kv_cache

        cdt = jnp.dtype(self.compute_dtype)
        return {
            f"layer_{n}": init_kv_cache(
                batch, max_len, self.kv_heads, self.head_dim, cdt,
                self.kv_quant,
            )
            for n in range(self.num_layers)
        }

    def prefill_core(self, params, prompt_ids, n_pad, total_len: int,
                     cache=None, pos0=None):
        """Full causal forward over a left-padded ``[B, P]`` prompt,
        writing ROTATED K (and V) into a fresh cache — the dispatch
        target of ``gpt._prefill_core`` (see that docstring for the
        padding/alignment contract, and ``GptLM.prefill_core`` for the
        page-native ``cache``/``pos0`` variant: rotary phases key on
        effective positions, which the caller's virtual-slot ``n_pad``
        keeps invariant under the offset, so the stored rotated K is
        identical wherever the block lands)."""
        from mlapi_tpu.ops import full_attention
        from mlapi_tpu.ops.quant import kv_cache_append

        b, p = prompt_ids.shape
        cache = self.init_cache(b, total_len) if cache is None else dict(cache)
        if pos0 is None:
            pos0 = jnp.int32(0)
        cdt = jnp.dtype(self.compute_dtype)

        positions = jnp.maximum(jnp.arange(p)[None, :] - n_pad[:, None], 0)
        mask = (jnp.arange(p)[None, :] >= n_pad[:, None]).astype(jnp.float32)
        x = params["wte"][prompt_ids]
        for n in range(self.num_layers):
            layer = params[f"layer_{n}"]
            kv_seen = {}

            def attend(q, k, v, *, _kv=kv_seen):
                _kv["k"], _kv["v"] = k, v
                return full_attention(
                    q, self._repeat_kv(k), self._repeat_kv(v),
                    mask=mask, causal=True,
                )

            x = self._block(layer, x, positions, attend)
            # Rotated K / raw V quantize at the append, exactly like
            # the GPT family (the prompt block itself attended
            # full-precision above).
            cache[f"layer_{n}"] = kv_cache_append(
                cache[f"layer_{n}"], kv_seen["k"], kv_seen["v"],
                pos0, cdt,
            )
        x = _rms_norm(x, params["rms_f_scale"])
        last_logits = x[:, -1].astype(jnp.float32) @ params["lm_head"].astype(
            jnp.float32
        )
        return cache, last_logits

    def decode_step(self, params, cache, token_ids, pos, n_pad=None,
                    prefix_len=None, prefix_lo=None):
        """One cached decode step — same contract as
        ``GptLM.decode_step`` (``[B, 1]`` ids at traced cache position
        ``pos``; per-row ``n_pad`` shifts rotary positions and masks
        pad keys; ``prefix_len``/``prefix_lo`` describe a shared
        prefix-cache region). The cache write + masked attention is
        the shared ``gpt.cached_attend``, with GQA's kv-head broadcast
        plugged in.
        """
        from mlapi_tpu.models.gpt import cached_attend, decode_valid_and_shift
        from mlapi_tpu.ops.quant import kv_cache_seq_len

        cdt = jnp.dtype(self.compute_dtype)
        b = token_ids.shape[0]
        max_len = kv_cache_seq_len(cache)
        if n_pad is None:
            n_pad = jnp.zeros((b,), jnp.int32)

        valid, shift = decode_valid_and_shift(
            max_len, pos, n_pad, prefix_len, prefix_lo
        )
        positions = jnp.maximum(pos - shift, 0)[:, None]  # [B, 1]
        x = params["wte"][token_ids]
        new_cache = {}

        for n in range(self.num_layers):
            layer = params[f"layer_{n}"]

            def attend(q, k_new, v_new, *, _n=n):
                out, new_cache[f"layer_{_n}"] = cached_attend(
                    cache[f"layer_{_n}"], q, k_new, v_new, pos, valid,
                    cdt, self.head_dim, expand=self._repeat_kv,
                    impl=self.decode_attn_impl, mesh=self.mesh,
                )
                return out

            x = self._block(layer, x, positions, attend)

        x = _rms_norm(x, params["rms_f_scale"])
        logits = x[:, 0].astype(jnp.float32) @ params["lm_head"].astype(
            jnp.float32
        )
        return logits, new_cache

    def extend_core(self, params, cache, token_ids, pos0, n_pad,
                    prefix_len, prefix_lo, all_logits: bool = False):
        """Fused block forward against an existing cache — same
        contract as ``GptLM.extend_core`` (rotary positions per row,
        GQA kv broadcast via the shared ``cached_attend``; under
        ``decode_attn_impl="flash"`` the block reads the cache through
        the GQA-native flash-extend kernel, where the repeated K/V
        tensor the einsum path broadcasts never exists)."""
        from mlapi_tpu.models.gpt import (
            cached_attend, extend_positions_and_mask,
        )
        from mlapi_tpu.ops.quant import kv_cache_seq_len

        cdt = jnp.dtype(self.compute_dtype)
        max_len = kv_cache_seq_len(cache)
        posq, mask = extend_positions_and_mask(
            max_len, token_ids.shape[1], pos0, n_pad, prefix_len,
            prefix_lo,
        )
        x = params["wte"][token_ids]
        new_cache = {}

        for n in range(self.num_layers):
            layer = params[f"layer_{n}"]

            def attend(q, k_new, v_new, *, _n=n):
                out, new_cache[f"layer_{_n}"] = cached_attend(
                    cache[f"layer_{_n}"], q, k_new, v_new, pos0, mask,
                    cdt, self.head_dim, expand=self._repeat_kv,
                    impl=self.decode_attn_impl, mesh=self.mesh,
                )
                return out

            x = self._block(layer, x, posq, attend)

        x = _rms_norm(x, params["rms_f_scale"])
        if not all_logits:
            x = x[:, -1]
        logits = x.astype(jnp.float32) @ params["lm_head"].astype(
            jnp.float32
        )
        return new_cache, logits

    def generate(self, params, prompt_ids, **kwargs):
        """Same surface as ``GptLM.generate`` (the whole prefill +
        chunked-scan + sampling pipeline is the shared machinery in
        ``gpt.py``)."""
        from mlapi_tpu.models.gpt import run_generate

        return run_generate(self, params, prompt_ids, **kwargs)

    # ------------------------------------------------------------------
    def param_shardings(self, layout=None) -> dict:
        """Megatron TP: q/k/v/gate/up column-sharded, wo/w_down
        row-sharded, embeddings + head vocab-sharded."""
        from mlapi_tpu.parallel import SpecLayout

        lo = layout or SpecLayout()
        specs = {
            "wte": lo.embedding_rows(),
            "lm_head": lo.attn_qkv(),  # [h, V]: column(vocab)-sharded
            "rms_f_scale": lo.replicated(),
        }
        for n in range(self.num_layers):
            specs[f"layer_{n}"] = {
                "wq": lo.attn_qkv(),
                "wk": lo.attn_qkv(),
                "wv": lo.attn_qkv(),
                "wo": lo.attn_out(),
                "rms1_scale": lo.replicated(),
                "w_gate": lo.attn_qkv(),
                "w_up": lo.attn_qkv(),
                "w_down": lo.attn_out(),
                "rms2_scale": lo.replicated(),
            }
        return specs
