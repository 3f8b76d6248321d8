"""Decoder-only (GPT-style) causal LM — the generative model family.

The reference serves only classifiers (``main.py:16-27``); this goes
past parity: same TPU-first recipe as the BERT encoder (one flat param
pytree, explicit einsum attention, bf16 hidden compute / f32 softmax
+ layernorm stats, Megatron TP layout over the ``model`` mesh axis)
plus what decoding actually needs on a TPU:

- **Causal attention** through the shared ops (`full_attention` /
  Pallas ``flash_attention`` / sequence-parallel ``ring_attention``
  all take ``causal=True``).
- **KV-cache decode under ``lax.scan``**: generation is one compiled
  XLA while-program — fixed-shape cache ``[B, max_len, H, D]`` per
  layer, one token per step, no per-token Python dispatch.

Pre-norm blocks (GPT-2 style: ln -> attn -> residual, ln -> mlp ->
residual, final ln), learned positions, weight-tied LM head.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mlapi_tpu.models import register_model
from mlapi_tpu.utils.platform import pallas_interpret

_LN_EPS = 1e-5


def _layer_norm(x, scale, bias):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + _LN_EPS) * scale + bias


@register_model("gpt_lm")
@dataclass(frozen=True)
class GptLM:
    """Decoder-only causal language model with weight-tied head."""

    input_kind = "text"

    vocab_size: int = 512
    hidden_size: int = 256
    num_layers: int = 4
    num_heads: int = 4
    max_positions: int = 256
    compute_dtype: str = "bfloat16"
    # "full" | "flash" (Pallas kernel) | "ring" (sequence-parallel
    # over mesh's seq axis; requires ``mesh``) — all causal. Ring
    # applies to ``apply`` (training/scoring, where the whole sequence
    # is live); ``generate`` decodes one token at a time against the
    # KV cache, where there is no sequence dimension to shard.
    attention_impl: str = "full"
    mesh: object = None  # jax.sharding.Mesh for attention_impl="ring"
    seq_axis: str = "seq"
    # Ring options: per-block attention ("einsum" | "flash") and the
    # zigzag stripe layout (flash-only; balances causal work to two
    # half-block units per ring step on every device — ~2x wall time).
    ring_block_impl: str = "einsum"
    ring_zigzag: bool = False
    # KV-cache storage format: "none" keeps the compute dtype;
    # "int8" stores symmetric per-token-per-head int8 payload + f32
    # scales (ops/quant.py) — ~2x less decode HBM per cached token,
    # ~2x the serving cache budget per chip. A dataclass field (not a
    # method argument) so every lru_cache'd program factory
    # (prefill_fn, decode_chunk_fn, ...) keys on the cache format for
    # free.
    kv_quant: str = "none"
    # Cache-read attention: "einsum" (the reference oracle — one
    # [B,U,H,D] x [B,L,H,D] einsum over the dequantized cache) or
    # "flash" (the Pallas split-K kernels,
    # ops/pallas/decode_attention.py, which read int8 cache tiles
    # in-kernel — the 2x HBM saving reaches the READ, not just
    # storage). A MODEL field like kv_quant, so every cached program
    # factory keys on the impl for free. "flash" covers BOTH span
    # widths: single-token decode steps take the flash-decode kernel
    # and multi-token blocks (extend_core — chunked prefill,
    # admission, speculative verify) its U-token flash-extend twin.
    decode_attn_impl: str = "einsum"

    def __post_init__(self):
        from mlapi_tpu.ops.quant import KV_FORMATS

        if self.attention_impl not in ("full", "flash", "ring"):
            raise ValueError(f"unknown attention_impl {self.attention_impl!r}")
        if self.attention_impl == "ring" and self.mesh is None:
            raise ValueError('attention_impl="ring" requires a mesh')
        if self.ring_zigzag and self.ring_block_impl != "flash":
            raise ValueError('ring_zigzag needs ring_block_impl="flash"')
        if self.hidden_size % self.num_heads:
            raise ValueError("hidden_size must divide evenly into heads")
        if self.kv_quant not in KV_FORMATS:
            raise ValueError(
                f"unknown kv_quant {self.kv_quant!r}; one of {KV_FORMATS}"
            )
        if self.decode_attn_impl not in ("einsum", "flash"):
            raise ValueError(
                f"unknown decode_attn_impl {self.decode_attn_impl!r}; "
                'one of ("einsum", "flash")'
            )

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def intermediate_size(self) -> int:
        return 4 * self.hidden_size

    # ------------------------------------------------------------------
    def init(self, rng: jax.Array) -> dict:
        h, i, v = self.hidden_size, self.intermediate_size, self.vocab_size
        keys = iter(jax.random.split(rng, 2 + 6 * self.num_layers))

        def dense(k, shape, scale=0.02):
            return {
                "kernel": scale * jax.random.normal(k, shape),
                "bias": jnp.zeros((shape[-1],)),
            }

        params = {
            "wte": 0.02 * jax.random.normal(next(keys), (v, h)),
            "wpe": 0.01 * jax.random.normal(next(keys), (self.max_positions, h)),
            "ln_f_scale": jnp.ones((h,)),
            "ln_f_bias": jnp.zeros((h,)),
        }
        for n in range(self.num_layers):
            params[f"layer_{n}"] = {
                "qkv": dense(next(keys), (h, 3 * h)),
                "attn_out": dense(next(keys), (h, h)),
                "ln1_scale": jnp.ones((h,)),
                "ln1_bias": jnp.zeros((h,)),
                "ffn_up": dense(next(keys), (h, i)),
                "ffn_down": dense(next(keys), (i, h)),
                "ln2_scale": jnp.ones((h,)),
                "ln2_bias": jnp.zeros((h,)),
            }
        return jax.tree.map(lambda a: a.astype(jnp.float32), params)

    # ------------------------------------------------------------------
    def _block(self, layer, x, attend):
        """One pre-norm transformer block; ``attend(q, k, v)`` supplies
        the attention so the full-sequence and cached-decode paths
        share every other op."""
        cdt = jnp.dtype(self.compute_dtype)
        b, l, h = x.shape
        nh, hd = self.num_heads, self.head_dim

        # lora_apply: the per-tenant serving delta (adapter slot pool,
        # serving/adapter_store.py) — a static no-op returning its
        # ``y`` argument unchanged unless the dispatch augmented this
        # layer dict with a "lora" sub-dict.
        from mlapi_tpu.models.lora import lora_apply

        xn = _layer_norm(x, layer["ln1_scale"], layer["ln1_bias"]).astype(cdt)
        qkv = xn @ layer["qkv"]["kernel"].astype(cdt) + layer["qkv"][
            "bias"
        ].astype(cdt)
        qkv = lora_apply(layer, "qkv", xn, qkv)
        q, k, v = jnp.split(qkv.reshape(b, l, 3 * nh, hd), 3, axis=2)
        ctx = attend(q, k, v).reshape(b, l, -1)
        attn = ctx @ layer["attn_out"]["kernel"].astype(cdt) + layer[
            "attn_out"
        ]["bias"].astype(cdt)
        attn = lora_apply(layer, "attn_out", ctx, attn)
        x = x + attn.astype(jnp.float32)

        xn = _layer_norm(x, layer["ln2_scale"], layer["ln2_bias"]).astype(cdt)
        up = xn @ layer["ffn_up"]["kernel"].astype(cdt) + layer["ffn_up"][
            "bias"
        ].astype(cdt)
        up = lora_apply(layer, "ffn_up", xn, up)
        up = jax.nn.gelu(up.astype(jnp.float32), approximate=True).astype(cdt)
        down = up @ layer["ffn_down"]["kernel"].astype(cdt) + layer[
            "ffn_down"
        ]["bias"].astype(cdt)
        down = lora_apply(layer, "ffn_down", up, down)
        return x + down.astype(jnp.float32)

    def apply(self, params: dict, token_ids) -> jax.Array:
        """``[B, L]`` ids → ``[B, L, V]`` next-token logits (causal)."""
        from mlapi_tpu.ops import full_attention

        b, l = token_ids.shape
        x = params["wte"][token_ids] + params["wpe"][jnp.arange(l)][None]

        if self.attention_impl == "flash":
            from mlapi_tpu.ops.pallas import flash_attention_on_mesh

            def attend(q, k, v):
                return flash_attention_on_mesh(
                    self.mesh, q, k, v, causal=True,
                    interpret=pallas_interpret(),
                )
        elif self.attention_impl == "ring":
            from mlapi_tpu.ops import ring_self_attention

            def attend(q, k, v):
                return ring_self_attention(
                    self.mesh, q, k, v, causal=True,
                    seq_axis=self.seq_axis, head_axis="model",
                    block_impl=self.ring_block_impl,
                    zigzag=self.ring_zigzag,
                )
        else:
            def attend(q, k, v):
                return full_attention(q, k, v, causal=True)

        for n in range(self.num_layers):
            x = self._block(params[f"layer_{n}"], x, attend)
        x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])
        # Weight-tied head; logits in f32 for a stable softmax/loss.
        return x.astype(jnp.float32) @ params["wte"].T.astype(jnp.float32)

    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int) -> dict:
        """Fixed-shape KV cache: ``[B, max_len, H, D]`` per layer in
        the compute dtype, or the int8 payload+scale layout under
        ``kv_quant="int8"`` (see ``ops/quant.init_kv_cache``)."""
        from mlapi_tpu.ops.quant import init_kv_cache

        cdt = jnp.dtype(self.compute_dtype)
        return {
            f"layer_{n}": init_kv_cache(
                batch, max_len, self.num_heads, self.head_dim, cdt,
                self.kv_quant,
            )
            for n in range(self.num_layers)
        }

    def prefill_core(self, params, prompt_ids, n_pad, total_len: int,
                     cache=None, pos0=None):
        """Full causal forward over a left-padded ``[B, P]`` prompt,
        writing K/V into a fresh ``[B, total_len, H, D]`` cache — this
        model family's implementation of the decoder protocol (see
        :func:`_prefill_core` for the shared contract).

        ``cache``/``pos0`` (page-native prefill): write the prompt's
        K/V into an EXISTING cache pytree at traced slot offset
        ``pos0`` instead of building a fresh one (``total_len`` is
        then ignored). With a paged cache this is what makes prefill
        write pool pages ONCE — the block's attention is unchanged
        (full-precision in-register over ``kv_seen``), only the
        append's destination moves, so token streams are pinned
        identical to the fresh-cache path.
        """
        b, p = prompt_ids.shape
        cache = self.init_cache(b, total_len) if cache is None else dict(cache)
        if pos0 is None:
            pos0 = jnp.int32(0)
        cdt = jnp.dtype(self.compute_dtype)

        from mlapi_tpu.ops import full_attention
        from mlapi_tpu.ops.quant import kv_cache_append

        pos_idx = jnp.maximum(jnp.arange(p)[None, :] - n_pad[:, None], 0)
        x = params["wte"][prompt_ids] + params["wpe"][pos_idx]
        mask = (jnp.arange(p)[None, :] >= n_pad[:, None]).astype(jnp.float32)
        for n in range(self.num_layers):
            layer = params[f"layer_{n}"]
            kv_seen = {}

            def attend(q, k, v, *, _kv=kv_seen):
                _kv["k"], _kv["v"] = k, v
                return full_attention(q, k, v, mask=mask, causal=True)

            x = self._block(layer, x, attend)
            # The prompt block attends full-precision in-register
            # (kv_seen); only the STORED cache is quantized — the
            # append fuses the quantize into this write (ops/quant).
            cache[f"layer_{n}"] = kv_cache_append(
                cache[f"layer_{n}"], kv_seen["k"], kv_seen["v"],
                pos0, cdt,
            )
        x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])
        last_logits = x[:, -1].astype(jnp.float32) @ params["wte"].T.astype(
            jnp.float32
        )
        return cache, last_logits

    def decode_step(self, params, cache, token_ids, pos, n_pad=None,
                    prefix_len=None, prefix_lo=None):
        """One decode step: ``[B, 1]`` ids at position ``pos`` (traced
        scalar) → (``[B, V]`` logits, updated cache). The KV for the
        new token is written into the fixed-shape cache; attention
        reads the full cache with positions ``> pos`` masked out —
        static shapes, so the scan body compiles once.

        ``n_pad`` (``[B]`` int32) is the per-row count of left-pad
        positions in the cache: those keys are masked out and the
        position embedding is shifted so row ``b``'s real tokens sit
        at effective positions ``0..pos-n_pad[b]`` — a prompt's output
        is identical whichever pad bucket it landed in.
        ``prefix_len``/``prefix_lo`` describe a shared prefix-cache
        region ahead of the per-row pads (see
        :func:`decode_valid_and_shift`).
        """
        from mlapi_tpu.ops.quant import kv_cache_seq_len

        cdt = jnp.dtype(self.compute_dtype)
        b = token_ids.shape[0]
        hd = self.head_dim
        max_len = kv_cache_seq_len(cache)
        if n_pad is None:
            n_pad = jnp.zeros((b,), jnp.int32)

        valid, shift = decode_valid_and_shift(
            max_len, pos, n_pad, prefix_len, prefix_lo
        )
        posq = jnp.maximum(pos - shift, 0)
        x = params["wte"][token_ids] + params["wpe"][posq][:, None, :]
        new_cache = {}

        for n in range(self.num_layers):
            layer = params[f"layer_{n}"]

            def attend(q, k_new, v_new, *, _n=n):
                out, new_cache[f"layer_{_n}"] = cached_attend(
                    cache[f"layer_{_n}"], q, k_new, v_new, pos, valid,
                    cdt, hd, impl=self.decode_attn_impl,
                    mesh=self.mesh,
                )
                return out

            x = self._block(layer, x, attend)

        x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])
        logits = x[:, 0].astype(jnp.float32) @ params["wte"].T.astype(
            jnp.float32
        )
        return logits, new_cache

    def extend_core(self, params, cache, token_ids, pos0, n_pad,
                    prefix_len, prefix_lo, all_logits: bool = False):
        """Fused BLOCK forward of ``[B, U]`` tokens at cache slots
        ``[pos0, pos0+U)`` against an existing cache — the multi-token
        generalization of :meth:`decode_step` (one weight pass over
        the whole block instead of U serial steps; this is what makes
        prefix-cache suffix prefill MXU-bound, not bandwidth-bound).
        Queries attend to every earlier valid cache slot plus the
        causal part of their own block, under the same
        prefix-region/pad-hole layout as
        :func:`decode_valid_and_shift`. Returns
        ``(cache, last_logits [B, V])`` — or, with ``all_logits=True``
        (speculative-decoding verification), logits at EVERY block
        position ``[B, U, V]``.

        Under ``decode_attn_impl="flash"`` the block attends through
        the U-token flash-extend kernel (``cached_attend`` routes on
        the query width), so chunked prefill, admission mini-prefills
        and speculative verify read the cache at its stored byte
        format — the einsum read stays the oracle.
        """
        from mlapi_tpu.ops.quant import kv_cache_seq_len

        cdt = jnp.dtype(self.compute_dtype)
        b, u = token_ids.shape
        hd = self.head_dim
        max_len = kv_cache_seq_len(cache)

        posq, mask = extend_positions_and_mask(
            max_len, u, pos0, n_pad, prefix_len, prefix_lo
        )
        x = params["wte"][token_ids] + params["wpe"][posq]
        new_cache = {}

        for n in range(self.num_layers):
            layer = params[f"layer_{n}"]

            def attend(q, k_new, v_new, *, _n=n):
                out, new_cache[f"layer_{_n}"] = cached_attend(
                    cache[f"layer_{_n}"], q, k_new, v_new, pos0, mask,
                    cdt, hd, impl=self.decode_attn_impl,
                    mesh=self.mesh,
                )
                return out

            x = self._block(layer, x, attend)

        x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])
        if not all_logits:
            x = x[:, -1]
        logits = x.astype(jnp.float32) @ params["wte"].T.astype(
            jnp.float32
        )
        return new_cache, logits

    def generate(
        self,
        params,
        prompt_ids,
        *,
        max_new_tokens: int,
        temperature=0.0,
        rng: jax.Array | None = None,
        pad_lens=None,
        top_k=0,
        top_p=1.0,
    ):
        """Greedy (``temperature=0``) or sampled generation.

        ``prompt_ids``: ``[B, P]`` int32. Returns ``[B, max_new_tokens]``.
        Prefill runs the full forward once; decode is a ``lax.scan``
        over single-token steps against the KV cache — one jitted
        program end to end, compiled per (shape, max_new_tokens).

        ``temperature`` may be a float or a per-row ``[B]`` array; it
        is a *traced* argument, so a client cycling temperatures never
        forces recompilation. ``top_k``/``top_p`` (scalar or per-row,
        traced likewise) restrict sampling to the k highest logits /
        the smallest nucleus reaching cumulative probability p —
        ``0``/``1.0`` disable them. ``pad_lens`` (``[B]`` int) marks how many
        left-pad tokens each row carries: pads are masked out of
        attention and position embeddings are shifted, so bucketed
        serving produces bucket-invariant outputs. Sampling uses one
        PRNG stream per row (``fold_in(rng, row)``), making each row's
        tokens independent of its batch position.
        """
        return run_generate(
            self, params, prompt_ids, max_new_tokens=max_new_tokens,
            temperature=temperature, rng=rng, pad_lens=pad_lens,
            top_k=top_k, top_p=top_p,
        )

    # ------------------------------------------------------------------
    def param_shardings(self, layout=None) -> dict:
        """Megatron TP: qkv/ffn-up column-sharded, attn-out/ffn-down
        row-sharded, embeddings vocab-sharded. Axis names come from
        the shared ``SpecLayout`` (mesh renames touch one place)."""
        from mlapi_tpu.parallel import SpecLayout

        lo = layout or SpecLayout()
        col = {"kernel": lo.attn_qkv(), "bias": lo.bias_col()}
        row = {"kernel": lo.attn_out(), "bias": lo.replicated()}
        specs = {
            "wte": lo.embedding_rows(),
            "wpe": lo.replicated(),
            "ln_f_scale": lo.replicated(),
            "ln_f_bias": lo.replicated(),
        }
        for n in range(self.num_layers):
            specs[f"layer_{n}"] = {
                "qkv": dict(col),
                "attn_out": dict(row),
                "ln1_scale": lo.replicated(), "ln1_bias": lo.replicated(),
                "ffn_up": dict(col),
                "ffn_down": dict(row),
                "ln2_scale": lo.replicated(), "ln2_bias": lo.replicated(),
            }
        return specs


_FILTERED = -1e30  # finite stand-in for -inf (f32-safe; prob == 0)


def _filter_top_k_top_p(scaled, top_k, top_p):
    """Per-row nucleus filtering on temperature-scaled logits
    ``[B, V]``: keep the ``top_k[b]`` highest logits (``<= 0`` or
    ``>= V`` disables), then the smallest prefix of the sorted
    distribution whose cumulative probability reaches ``top_p[b]``
    (``<= 0`` or ``>= 1`` disables; the argmax token always
    survives). Both are traced vectors, so no program is keyed on
    them; cost is two per-row sorts — noise next to the decode
    matmuls."""
    v = scaled.shape[-1]

    def _one(lg, k, p):
        s = jnp.sort(lg)[::-1]  # descending — the ONE sort per row
        k_eff = jnp.clip(k, 1, v)
        kth = jax.lax.dynamic_index_in_dim(s, k_eff - 1, keepdims=False)
        apply_k = (k > 0) & (k < v)
        lg = jnp.where(apply_k, jnp.where(lg >= kth, lg, _FILTERED), lg)
        # The k-filtered sorted vector is s with positions >= k_eff
        # masked — no second sort. (Ties at the kth logit: lg keeps
        # all tied tokens while the positional mask counts exactly k
        # toward the nucleus — the same keep-the-ties behavior a
        # re-sort would give, since thr only tightens.)
        s2 = jnp.where(
            apply_k & (jnp.arange(v) >= k_eff), _FILTERED, s
        )
        probs = jax.nn.softmax(s2)
        cum = jnp.cumsum(probs)
        keep = (cum - probs) < p  # prefix mask; index 0 always kept
        thr = jnp.min(jnp.where(keep, s2, jnp.inf))
        apply_p = (p > 0.0) & (p < 1.0)
        return jnp.where(
            apply_p, jnp.where(lg >= thr, lg, _FILTERED), lg
        )

    return jax.vmap(_one)(scaled, top_k, top_p)


def _pick_token(temps, logits, key_data, step, top_k=None, top_p=None):
    """Next token per row: greedy where ``temps[b] <= 0``, else sampled
    from ``logits / temps[b]`` — optionally top-k/top-p (nucleus)
    filtered — with the row's own PRNG stream
    (``fold_in(row_key, step)``): a row's tokens do not depend on
    which batch slot it landed in. ``step`` may be a scalar or a
    per-row ``[B]`` vector — rows admitted into a running batch
    (continuous batching) sample at their OWN token index, so the
    stream matches a solo run exactly."""
    b = logits.shape[0]
    step = jnp.broadcast_to(jnp.asarray(step, jnp.int32), (b,))
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.where(temps > 0.0, temps, 1.0)
    scaled = logits / safe_t[:, None]
    if top_k is None:
        top_k = jnp.zeros((b,), jnp.int32)
    if top_p is None:
        top_p = jnp.ones((b,), jnp.float32)
    v = logits.shape[-1]
    need = jnp.any((top_k > 0) & (top_k < v)) | jnp.any(
        (top_p > 0.0) & (top_p < 1.0)
    )
    # cond, not where: batches with no filtering requested (greedy /
    # plain temperature) skip the per-row sorts at runtime.
    scaled = jax.lax.cond(
        need,
        lambda s: _filter_top_k_top_p(s, top_k, top_p),
        lambda s: s,
        scaled,
    )
    keys = jax.vmap(
        lambda kd, s: jax.random.fold_in(jax.random.wrap_key_data(kd), s)
    )(key_data, step)
    sampled = jax.vmap(
        lambda k, lg: jax.random.categorical(k, lg)
    )(keys, scaled).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)


def run_generate(
    model,
    params,
    prompt_ids,
    *,
    max_new_tokens: int,
    temperature=0.0,
    rng: jax.Array | None = None,
    pad_lens=None,
    top_k=0,
    top_p=1.0,
):
    """Model-generic generation entry (every decoder family's
    ``generate`` delegates here) — see ``GptLM.generate`` for the full
    argument semantics."""
    b, p = prompt_ids.shape
    if p + max_new_tokens > model.max_positions:
        raise ValueError(
            f"prompt ({p}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_positions ({model.max_positions})"
        )
    rng = jax.random.key(0) if rng is None else rng
    # The key crosses the jit boundary as raw uint32 data: a typed
    # key array as a jit argument trips a fastpath buffer-count
    # bug in this JAX version once other executables exist on a
    # multi-device host (second identical call INVALID_ARGUMENT).
    row_keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(
        jnp.arange(b)
    )
    temps = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (b,))
    n_pad = (
        jnp.zeros((b,), jnp.int32)
        if pad_lens is None
        else jnp.asarray(pad_lens, jnp.int32)
    )
    top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (b,))
    top_p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (b,))
    return _generate_fn(model, max_new_tokens)(
        params, prompt_ids, jax.random.key_data(row_keys), temps, n_pad,
        top_k, top_p,
    )


def decode_valid_and_shift(max_len, pos, n_pad, prefix_len=None,
                           prefix_lo=None):
    """Shared decode-time key mask + per-row position shift, for both
    the plain left-padded layout and the prefix-cache layout.

    Cache-slot layout (per row ``b``):
    ``[prefix_lo .. prefix_len)`` real PREFIX tokens (shared across
    the batch, scattered from the prefix KV cache; empty when
    ``prefix_len == 0``), ``[prefix_len .. prefix_len + n_pad[b])``
    this row's suffix pad slots (masked), then real suffix/generated
    tokens. Valid keys: ``idx <= pos`` (written so far), ``idx >=
    prefix_lo`` (prefix's own left-pad), and NOT inside the per-row
    pad hole. With ``prefix_len == prefix_lo == 0`` this reduces
    exactly to the original ``(idx <= pos) & (idx >= n_pad[b])``.

    The position shift maps slot ``s`` to effective position
    ``s - prefix_lo - n_pad[b]`` (prefix real count + suffix index),
    which likewise reduces to ``s - n_pad[b]``.
    Returns ``(valid [B,1,1,L], shift [B])``.

    ``pos`` may be a traced scalar (all rows at the same slot — the
    serving decode loop) or a per-row ``[B]`` vector (rows at
    DESYNCHRONIZED slots — batched speculation, where per-row
    acceptance lengths advance each row's cache independently).
    ``prefix_lo`` likewise: scalar for a batch sharing ONE prefix, or
    per-row ``[B]`` when rows carry DIFFERENT prefixes right-aligned
    to the common region end ``prefix_len`` (cross-batch prefix
    sharing — each row's real prefix occupies ``[lo_b, prefix_len)``;
    ``lo_b == prefix_len`` is an empty region).
    """
    if prefix_len is None:
        prefix_len = jnp.int32(0)
    if prefix_lo is None:
        prefix_lo = jnp.int32(0)
    idx = jnp.arange(max_len)[None, :]
    posk = pos[:, None] if jnp.ndim(pos) else pos
    lok = prefix_lo[:, None] if jnp.ndim(prefix_lo) else prefix_lo
    valid = (
        (idx <= posk)
        & (idx >= lok)
        & ((idx < prefix_len) | (idx >= prefix_len + n_pad[:, None]))
    )[:, None, None, :]
    shift = prefix_lo + n_pad
    return valid, shift


def extend_positions_and_mask(max_len, u, pos0, n_pad, prefix_len=None,
                              prefix_lo=None):
    """Block-extend variant of :func:`decode_valid_and_shift`: for U
    queries at cache slots ``[pos0, pos0+U)``, per-row effective
    positions ``[B, U]`` (clipped at 0 for pad slots) and the
    ``[B, 1, U, L]`` key mask — earlier valid slots plus the causal
    part of the block itself, minus the prefix pad and the per-row
    suffix pad hole. ``pos0``: traced scalar, or per-row ``[B]`` for
    desynchronized rows (batched speculation). ``prefix_lo``: scalar,
    or per-row ``[B]`` for cross-batch prefix sharing (see
    :func:`decode_valid_and_shift`)."""
    if prefix_len is None:
        prefix_len = jnp.int32(0)
    if prefix_lo is None:
        prefix_lo = jnp.int32(0)
    idx = jnp.arange(max_len)
    pos0k = pos0[:, None] if jnp.ndim(pos0) else pos0
    lok = prefix_lo[:, None] if jnp.ndim(prefix_lo) else prefix_lo
    qpos = pos0k + jnp.arange(u)[None, :]          # [B|1, U] slot ids
    shift = prefix_lo + n_pad                          # [B]
    posq = jnp.maximum(qpos - shift[:, None], 0)
    valid_k = (idx[None, :] >= lok) & (
        (idx[None, :] < prefix_len)
        | (idx[None, :] >= prefix_len + n_pad[:, None])
    )                                                  # [B, L]
    causal = idx[None, None, :] <= qpos[:, :, None]  # [B|1, U, L]
    mask = (valid_k[:, None, :] & causal)[:, None, :, :]
    return posq, mask


def cached_attend(
    cache_layer, q, k_new, v_new, pos, valid, cdt, head_dim, expand=None,
    impl: str = "einsum", mesh=None,
):
    """One decode-time attention over a fixed-shape KV cache, shared
    by every decoder family: write the new K/V at ``pos``, attend the
    ``[B, 1]`` query against the whole cache under the ``valid`` mask.
    ``expand`` broadcasts kv-heads to query heads (GQA families pass
    their repeat; MHA passes nothing). Returns ``(ctx, new_layer)``.

    ``pos`` scalar: one fused slice-update writes every row at the
    same slot (the serving layout). ``pos`` per-row ``[B]``: the
    write vmaps over rows so each lands at its own slot — the layout
    batched speculation needs, where per-row acceptance lengths
    desynchronize row positions. Scalar callers compile the exact
    HLO they always did.

    Both cache formats route through here. The write always goes
    through ``ops.quant.kv_cache_append`` (quantize fused into the
    append for int8 layers). The READ depends on ``impl``:

    - ``"einsum"`` (default, the reference oracle): ``kv_cache_kv``
      dequantizes at the read seam and a ``[B,1,H,D] x [B,L,H,D]``
      einsum attends — the full-precision operand materializes
      between the dequant and the einsum, so the int8 format saves
      storage but not read traffic.
    - ``"flash"``: the Pallas split-K kernels
      (``ops/pallas/decode_attention``) read the STORED tiles — int8
      payload + scales dequantized per tile in registers — so int8
      is what crosses HBM on the read. Single-token queries take the
      flash-decode kernel; multi-token blocks (``extend_core``:
      chunked prefill, admission mini-prefills, prefix suffixes,
      speculative verify) take its U-token flash-extend twin, whose
      ``[B, U, L]`` mask (``extend_positions_and_mask``) carries the
      causal intra-span structure — every token the server processes
      reads the cache at its stored byte format.

    PAGED cache layers (``ops/quant.kv_is_paged_layer``: pool +
    page-table) route through the same two impls: the einsum path
    gathers pages into the contiguous oracle layout inside
    ``kv_cache_kv`` (the reference), while the flash path hands the
    pools and the table to ``paged_decode_attention`` — the page
    table becomes the kernel's BlockSpec index map and no contiguous
    cache ever materializes.

    ``mesh`` (optional): when it carries a ``model`` axis of size > 1
    that divides the cache's KV-head count, the flash kernel runs
    under an explicit ``shard_map`` over that axis
    (``decode_attention_tp`` / ``paged_decode_attention_tp``) so
    GSPMD cannot all-gather head-sharded cache operands around the
    opaque ``pallas_call``. Indivisible head counts fall back to the
    unwrapped kernel (GSPMD decides, as before).
    """
    from mlapi_tpu.ops.attention import NEG
    from mlapi_tpu.ops.quant import (
        kv_cache_append, kv_cache_kv, kv_is_paged_layer,
        kv_is_quantized_layer,
    )

    expand = expand or (lambda t: t)
    new_layer = kv_cache_append(cache_layer, k_new, v_new, pos, cdt)
    if impl == "flash":
        from mlapi_tpu.ops.pallas import (
            decode_attention, decode_attention_tp,
            extend_attention, extend_attention_tp,
            paged_decode_attention, paged_decode_attention_tp,
            paged_extend_attention, paged_extend_attention_tp,
        )

        u = q.shape[1]
        paged = kv_is_paged_layer(new_layer)
        if kv_is_quantized_layer(new_layer):
            k = {"q": new_layer["k_q"], "scale": new_layer["k_scale"]}
            v = {"q": new_layer["v_q"], "scale": new_layer["v_scale"]}
            kvh = new_layer["k_q"].shape[2]
        else:
            k, v = new_layer["k"], new_layer["v"]
            kvh = new_layer["k"].shape[2]
        # Single-token steps carry a [B, 1, 1, L] validity; extends a
        # [B, 1, U, L] one. Both collapse the same way: drop the
        # broadcast head axis, keep one mask row per query row.
        if u == 1:
            mask2 = valid[:, 0, 0, :].astype(jnp.float32)  # [B, L]
        else:
            mask2 = valid[:, 0].astype(jnp.float32)        # [B, U, L]
        scale = 1.0 / head_dim**0.5
        interp = pallas_interpret()
        tp = (
            mesh.shape["model"]
            if mesh is not None and "model" in getattr(
                mesh, "axis_names", ()
            )
            else 1
        )
        use_tp = tp > 1 and kvh % tp == 0 and q.shape[2] % tp == 0
        if paged:
            table = new_layer["table"]
            fn_tp = (
                paged_decode_attention_tp if u == 1
                else paged_extend_attention_tp
            )
            fn = (
                paged_decode_attention if u == 1
                else paged_extend_attention
            )
            if use_tp:
                ctx = fn_tp(
                    mesh, q, k, v, table, mask2, scale=scale,
                    interpret=interp,
                )
            else:
                ctx = fn(
                    q, k, v, table, mask2, scale=scale,
                    interpret=interp,
                )
        elif use_tp:
            fn_tp = decode_attention_tp if u == 1 else extend_attention_tp
            ctx = fn_tp(
                mesh, q, k, v, mask2, scale=scale, interpret=interp,
            )
        else:
            fn = decode_attention if u == 1 else extend_attention
            ctx = fn(
                q, k, v, mask2, scale=scale, interpret=interp,
            )
        return ctx, new_layer
    ck, cv = kv_cache_kv(new_layer, cdt)
    scores = (
        jnp.einsum(
            "bqhd,bkhd->bhqk", q, expand(ck),
            preferred_element_type=jnp.float32,
        )
        / head_dim**0.5
    )
    scores = jnp.where(valid, scores, NEG)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    ctx = jnp.einsum(
        "bhqk,bkhd->bqhd", probs, expand(cv),
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
    return ctx, new_layer


def _prefill_core(model, params, prompt_ids, n_pad, total_len: int):
    """Decoder-protocol prefill dispatch: every model family
    implements ``prefill_core`` (full forward over a left-padded
    ``[B, P]`` prompt → ``(cache, last_logits)``); everything
    downstream (``_decode_scan``, ``prefill_fn``, ``decode_chunk_fn``,
    ``_generate_fn``) is model-generic.

    Contract (see ``GptLM.prefill_core`` for the canonical
    implementation): per-row ``n_pad`` pad positions are masked out of
    attention and positions are shifted so real tokens occupy
    effective positions ``0..P-1-n_pad[b]``; every row's last real
    token sits at index ``P-1`` (right-aligned), so the next-token
    logits are one static slice. One batched forward + cache build is
    a single fused program — prefilling via P decode-shaped steps
    would cost P dispatches.
    """
    return model.prefill_core(params, prompt_ids, n_pad, total_len)


def _decode_scan(
    model, params, cache, tok, pos, n_pad, temps, key_data,
    n_steps: int, step0, top_k=None, top_p=None,
    prefix_len=None, prefix_lo=None,
):
    """``n_steps`` cached decode steps under one ``lax.scan``.

    ``tok`` ``[B]`` is the last emitted token (fed back in), ``pos``
    the traced cache position it occupies + 1 is written next;
    ``step0`` the traced sampling-stream offset — scalar or per-row
    ``[B]`` (so chunked decoding reproduces the single-scan token
    stream exactly, including rows admitted mid-batch at a different
    token index than their neighbours). Returns
    ``(tokens [B, n_steps], cache, last_tok)``.
    """
    b = tok.shape[0]
    step0 = jnp.broadcast_to(jnp.asarray(step0, jnp.int32), (b,))

    def step(carry, i):
        cache, tok, pos = carry
        logits, cache = model.decode_step(
            params, cache, tok[:, None], pos, n_pad,
            prefix_len, prefix_lo,
        )
        nxt = _pick_token(temps, logits, key_data, i + step0, top_k, top_p)
        return (cache, nxt, pos + 1), nxt

    (cache, tok, _), toks = jax.lax.scan(
        step, (cache, tok, pos), jnp.arange(n_steps)
    )
    return toks.T, cache, tok


@functools.lru_cache(maxsize=256)
def _generate_fn(model, max_new_tokens: int):
    """One jitted end-to-end generation program per (model config,
    token count); temperature, pad widths, and PRNG keys are traced
    arguments (the key as raw uint32 data — see ``generate``)."""

    def _run(params, prompt_ids, key_data, temps, n_pad, top_k, top_p):
        p = prompt_ids.shape[1]
        cache, first_logits = _prefill_core(
            model, params, prompt_ids, n_pad, p + max_new_tokens
        )
        first = _pick_token(temps, first_logits, key_data, 0, top_k, top_p)
        if max_new_tokens == 1:
            return first[:, None]
        rest, _, _ = _decode_scan(
            model, params, cache, first, jnp.int32(p), n_pad, temps,
            key_data, max_new_tokens - 1, jnp.int32(1), top_k, top_p,
        )
        return jnp.concatenate([first[:, None], rest], axis=1)

    return jax.jit(_run)


@functools.lru_cache(maxsize=64)
def prefill_fn(model, total_len: int):
    """Jitted prefill + first-token program for incremental decoding:
    ``(params, prompt_ids [B,P], key_data, temps, n_pad)`` →
    ``(first_tok [B], cache)``. Compiled per (model, B, P, total_len);
    any ``max_new_tokens`` then reuses it via ``decode_chunk_fn`` —
    the serving engine's compile count stays bounded by shape buckets,
    not by request parameters."""

    def _run(params, prompt_ids, key_data, temps, n_pad, top_k, top_p):
        cache, logits = _prefill_core(
            model, params, prompt_ids, n_pad, total_len
        )
        return _pick_token(temps, logits, key_data, 0, top_k, top_p), cache

    return jax.jit(_run)


@functools.cache
def admit_scatter_fn():
    """Jitted continuous-batching admission scatter: place a joiner's
    prompt K/V (a ``[1, bucket]``-shaped cache pytree from
    ``prefill_fn(model, bucket)``) into row ``r`` of a RUNNING batch's
    ``[B, total]`` cache, ending at the batch's current decode
    position ``pos`` (``r`` and ``pos - bucket`` are traced scalars —
    one compile covers every admission point). Splitting admission
    into (bucket-keyed prefill) + (this scatter) keeps the EXPENSIVE
    compile keyed on the prompt bucket alone; the scatter is pure
    data movement and compiles per (bucket, cache, batch) shape in
    negligible time, which is what makes admission viable at every
    cache tier, not just the warmed default.

    Cache-slot layout for the admitted row: real prompt tokens land in
    slots ``[pos - used, pos)`` and everything earlier is masked via
    ``n_pad_row = pos - used``, so the next decode step (which writes
    at ``pos``) sees exactly the joiner's prompt at effective
    positions ``0..used-1`` — byte-identical semantics to a row that
    was in the batch from its own prefill.
    """

    def _run(cache, mini, r, off):
        def scatter(big, small):
            start = (r,) + (off,) + (0,) * (big.ndim - 2)
            return jax.lax.dynamic_update_slice(
                big, small.astype(big.dtype), start
            )

        return jax.tree.map(scatter, cache, mini)

    return jax.jit(_run, donate_argnums=(0,))


@functools.cache
def realign_fn():
    """Jitted per-row cache ROLL for batched-speculation handoff:
    shift row ``b``'s slots right by ``delta[b]`` (``new[b, i] =
    old[b, i - delta_b]``, clamped reads below 0 land on slot 0 and
    are garbage). Callers bump ``n_pad[b] += delta_b`` so the rolled
    rows' effective positions (``slot - n_pad``) are UNCHANGED —
    wpe indices and stored rotary phases both key on effective
    position, so the roll is exact for every decoder family. This is
    what lets desynchronized per-row speculative positions rejoin
    the scalar-``pos`` chunk loop (and its admission machinery) at a
    round boundary."""

    def _run(cache, delta):
        def roll(a):
            L = a.shape[1]
            idx = jnp.arange(L)[None, :] - delta[:, None]  # [B, L]
            idx = jnp.clip(idx, 0, L - 1)
            return jnp.take_along_axis(
                a, idx.reshape(idx.shape + (1,) * (a.ndim - 2)), axis=1
            )

        return jax.tree.map(roll, cache)

    return jax.jit(_run, donate_argnums=(0,))


@functools.lru_cache(maxsize=64)
def decode_chunk_fn(model, chunk: int):
    """Jitted ``chunk``-step decode program:
    ``(params, cache, tok, pos, n_pad, temps, key_data, step0)`` →
    ``(tokens [B, chunk], cache, last_tok)``. The cache is donated —
    each chunk updates it in place (no per-chunk HBM copy); callers
    must use the returned cache handle."""

    def _run(params, cache, tok, pos, n_pad, temps, key_data, step0,
             top_k, top_p, prefix_len, prefix_lo):
        return _decode_scan(
            model, params, cache, tok, pos, n_pad, temps, key_data,
            chunk, step0, top_k, top_p, prefix_len, prefix_lo,
        )

    return jax.jit(_run, donate_argnums=(1,))


@functools.lru_cache(maxsize=64)
def extend_chunk_fn(model, width: int, total: int):
    """Jitted chunked-prefill program: one ``[B, width]`` block of a
    long prompt forwarded against the cache at traced offset ``pos0``
    (``extend_core``). Because the offset is traced, ONE compile
    serves every chunk of every prompt padded to a ``width`` multiple
    — a 4096-token prompt costs ceil(4096/width) dispatches of this
    same program instead of a bespoke exact-length compile per prompt
    length (the compile-count story that makes long-context serving
    predictable). Returns ``(cache, last_logits)``; the caller samples
    from the FINAL chunk's logits only."""

    def _run(params, cache, chunk_ids, pos0, n_pad):
        return model.extend_core(
            params, cache, chunk_ids, pos0, n_pad,
            jnp.int32(0), jnp.int32(0),
        )

    return jax.jit(_run, donate_argnums=(1,))


@functools.lru_cache(maxsize=64)
def paged_extend_fn(model, width: int):
    """Jitted ``[B, width]`` block forward against a PAGED cache at
    traced offset ``pos0`` with traced prefix-region parameters — the
    paged serving lifecycle's one prefill workhorse. It covers what
    took two contiguous programs: chunked long-prompt prefill
    (``prefix_len = 0``, the ``extend_chunk_fn`` role) and
    shared-prefix suffix prefill (``pos0 = prefix_len = P`` with the
    region's ``lo``, the ``prefix_prefill_fn`` role) — because a paged
    cache arrives with its page TABLE already describing the rows
    (shared prefix pages included), there is no per-variant cache
    construction left to fuse in. Callers sample the final block's
    logits with ``sample_fn`` (stream index 0 — byte-identical to the
    contiguous programs' draws). The cache is donated: pool updates
    are in place."""

    def _run(params, cache, chunk_ids, pos0, n_pad, prefix_len, lo):
        return model.extend_core(
            params, cache, chunk_ids, pos0, n_pad, prefix_len, lo
        )

    return jax.jit(_run, donate_argnums=(1,))


@functools.lru_cache(maxsize=64)
def paged_prefill_fn(model, width: int):
    """Page-native prefill + first token: the SAME full causal forward
    as ``prefill_fn`` (prompt block attends full-precision
    in-register), but the K/V append lands straight in pool pages —
    ``cache`` is a paged pytree (pool leaves + ``[R, NP]`` table
    mirrors) and ``off`` the traced VIRTUAL slot of the row's bucket
    start, so formation and admission write the prefill bytes exactly
    once (``generate.prefill_adopt_bytes`` reads 0 on this path where
    the contiguous-then-``paged_scatter_fn`` adopt paid one full extra
    copy). ``n_pad`` stays the row's LOCAL pad count (``bucket -
    used``): effective positions are ``local_slot - n_pad``, invariant
    under ``off``, which is what pins the token stream identical to
    the adopt path. ``(params, cache, prompt_ids [R, width], off,
    key_data, temps, n_pad, top_k, top_p) → (first_tok [R], cache)``;
    the cache is donated (pool updates in place)."""

    def _run(params, cache, prompt_ids, off, key_data, temps, n_pad,
             top_k, top_p):
        cache, logits = model.prefill_core(
            params, prompt_ids, n_pad, 0, cache=cache, pos0=off
        )
        return _pick_token(temps, logits, key_data, 0, top_k, top_p), cache

    return jax.jit(_run, donate_argnums=(1,))


@functools.cache
def paged_scatter_fn():
    """Jitted paged ADOPT: copy a contiguous ``[R, W]``-shaped cache
    pytree (a prefill's output, a joiner's mini cache, a prefix
    entry's KV) into pool pages at virtual offset ``off`` of the
    ``[R, NP]`` page-table rows ``table`` — one scatter per leaf, the
    coordinates shared with ``ops/quant``'s paged append. This is the
    page-granular replacement for ``admit_scatter_fn`` (no whole-row
    cache object to write into) and the bridge by which contiguous
    prefill programs feed the paged pool; formation pays one extra
    copy of the bytes prefill just wrote (page-native prefill is a
    noted follow-up), while ADMISSION keeps the contiguous path's
    shape: bucket-keyed prefill + a trivial scatter."""

    def _run(cache, mini, table, off):
        from mlapi_tpu.ops.quant import kv_layer_page_size

        out = {}
        for ln, layer in cache.items():
            page = kv_layer_page_size(layer)
            small = mini[ln]
            w = next(iter(small.values())).shape[1]
            r = table.shape[0]
            vpos = off + jnp.arange(w)  # [W] virtual slots
            pids = jnp.take_along_axis(
                table, jnp.broadcast_to((vpos // page)[None], (r, w)),
                axis=1,
            )
            offs = jnp.broadcast_to((vpos % page)[None], (r, w))
            new_layer = {"table": layer["table"]}
            for name in small:
                new_layer[name] = layer[name].at[pids, offs].set(
                    small[name].astype(layer[name].dtype)
                )
            out[ln] = new_layer
        return out

    return jax.jit(_run, donate_argnums=(0,))


@functools.cache
def paged_cow_fn():
    """Jitted copy-on-write page copy: duplicate pool pages ``src``
    into freshly-allocated pages ``dst`` (both ``int32 [R]``) across
    every layer's pools — the device half of COW. One gather+scatter
    of R pages, independent of sequence length or batch size: this is
    what lets a shared prefix's last partial page diverge per row
    without copying anyone's cache. The caller rewrites the HOST page
    table; the pools are donated."""

    def _run(cache, src, dst):
        out = {}
        for ln, layer in cache.items():
            new_layer = {"table": layer["table"]}
            for name in layer:
                if name == "table":
                    continue
                pool = layer[name]
                new_layer[name] = pool.at[dst].set(pool[src])
            out[ln] = new_layer
        return out

    return jax.jit(_run, donate_argnums=(0,))


@functools.cache
def paged_realign_fn():
    """Jitted paged fallback for the batched-speculation handoff when
    a row's realign delta is NOT a page multiple (the page-aligned
    case is a pure HOST table shift — see ``BatchRun._paged_realign``).
    Row gather + write-back THROUGH the tables: every row's virtual
    window is gathered at the shifted coordinates
    (``new[b, v] = old[b, v - delta_b]``, clamped like
    :func:`realign_fn`) and scattered back into the row's OWN mapped
    pages (unmapped tiles route through the never-read null page).
    Cost is one pass over the rows' whole VIRTUAL window — bounded by
    the cache tier, same order as the contiguous ``realign_fn`` roll
    it replaces (keying the program on the live extent would compile
    per handoff width) — a loud, counted repack
    (``generate.spec_realign_repacks``), kept only for the sub-page
    case page identity cannot express. Rows must not share pages
    (``p_len == 0`` batches — the only ones batched spec takes).
    The cache is donated."""

    def _run(cache, delta):
        from mlapi_tpu.ops.quant import kv_layer_page_size

        out = {}
        for ln, layer in cache.items():
            page = kv_layer_page_size(layer)
            table = layer["table"]
            b, npv = table.shape
            L = npv * page
            vdst = jnp.arange(L)[None, :]                     # [1, L]
            vsrc = jnp.clip(vdst - delta[:, None], 0, L - 1)  # [B, L]
            pd = jnp.take_along_axis(
                table, jnp.broadcast_to(vdst // page, (b, L)), axis=1
            )
            od = jnp.broadcast_to(vdst % page, (b, L))
            ps = jnp.take_along_axis(table, vsrc // page, axis=1)
            os_ = vsrc % page
            new_layer = {"table": table}
            for name, pool in layer.items():
                if name == "table":
                    continue
                new_layer[name] = pool.at[pd, od].set(pool[ps, os_])
            out[ln] = new_layer
        return out

    return jax.jit(_run, donate_argnums=(0,))


@functools.lru_cache(maxsize=16)
def sample_fn(model):
    """Jitted standalone sampler for the chunked-prefill path: the
    final chunk's logits → each row's first token at stream index 0
    (identical draw to the fused prefill programs)."""

    def _run(logits, key_data, temps, top_k, top_p):
        return _pick_token(temps, logits, key_data, 0, top_k, top_p)

    return jax.jit(_run)


@functools.lru_cache(maxsize=64)
def prefix_prefill_fn(model, suffix_len: int, total: int):
    """Jitted prefix-cache prefill + first-token program: scatter a
    shared prompt prefix's precomputed KV (``prefix_kv``, a
    ``[1, P]``-shaped cache pytree from ``prefill_fn(model, P)``)
    into slots ``[0, P)`` of EVERY row of a fresh ``[B, total]``
    cache, then run a teacher-forced scan over the left-padded
    ``[B, suffix_len]`` suffix block at slots ``[P, P+suffix_len)``.
    The prefix forward is never recomputed — that is the entire
    point: time-to-first-token for a request with an S-token shared
    prefix drops from O(P + U) to O(U) forward work.

    Per-row suffix pads (``hole [B]``) are masked via the pad hole in
    :func:`extend_positions_and_mask`; ``lo`` is the prefix's OWN
    left-pad inside its bucket. Cross-batch prefix sharing rides the
    same program shapes: ``prefix_kv`` may be a per-row ``[B, P]``
    stack (each row's own prefix, right-aligned to the common region
    end ``P``) with ``lo`` a per-row ``[B]`` vector — the broadcast
    becomes the identity and the mask helpers handle the vector. The suffix runs as ONE fused block
    forward (``extend_core``) — a single weight pass, like the plain
    prefill, so the KV path beats re-prefilling the concatenation for
    every nonempty prefix. Sampling draws at each row's stream index
    0, so the emitted stream is byte-identical to the same prompt
    served without prefix caching. Returns ``(first_tok [B], cache)``.
    """

    def _run(params, prefix_kv, suffix_ids, hole, lo, key_data, temps,
             top_k, top_p):
        b = suffix_ids.shape[0]
        p_len = jax.tree.leaves(prefix_kv)[0].shape[1]
        cache = model.init_cache(b, total)
        cache = jax.tree.map(
            lambda big, small: jax.lax.dynamic_update_slice(
                big,
                jnp.broadcast_to(
                    small, (b,) + small.shape[1:]
                ).astype(big.dtype),
                (0, 0, 0, 0),
            ),
            cache, prefix_kv,
        )
        cache, logits = model.extend_core(
            params, cache, suffix_ids, jnp.int32(p_len), hole,
            jnp.int32(p_len), lo,
        )
        first = _pick_token(temps, logits, key_data, 0, top_k, top_p)
        return first, cache

    return jax.jit(_run)
