"""Kimi-Linear-style decoder: layers of different kinds in one model.

The published model (``moonshotai/Kimi-Linear-48B-A3B``) interleaves
three gated delta-rule linear-attention layers (KDA) with one latent
attention layer (MLA, no positions), and follows its first, dense
layer with sparse-expert FFNs (256 experts, 8 a token, one shared
expert). The kind of each layer comes from the config's own
``kda_layers`` / ``full_attn_layers`` / ``first_k_dense_replace``,
1-indexed as published; ``params["layer_n"]`` is published layer
``n + 1``. Pre-norm residual blocks (``h = x + Mix(RMSNorm(x))``,
``y = h + FFN(RMSNorm(h))``), final RMSNorm, untied head, no biases.

**KDA** (per head, ``d_k = d_v``): q, k, v projections, a causal
depthwise convolution of width ``conv_kernel`` over time and SiLU on
each, q and k L2-normalised per head (q scaled ``d_k ** -0.5``). A
log-decay per channel ``g_t = -exp(A_log[h]) * softplus(W_f2 W_f1 x_t +
dt_bias)`` and a write strength ``beta_t = sigmoid(W_b x_t)`` drive

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t

and the output is ``W_o [RMSNorm_head(o_t) * sigmoid(W_g2 W_g1 x_t)]``.
The program computes it in CHUNKS of ``kda_chunk`` positions
(:func:`kda_chunked`): inside a chunk by matrix products and the
inverse of one unit lower-triangular matrix, across chunks by a
``lax.scan`` that carries ``S``; plain XLA, differentiated by JAX. At
heads of 128 the same computation, with the per-head normalisations
around it, runs as Pallas kernels (``ops/pallas/kda.py``, forward and
backward; :func:`kda` chooses by shapes and :func:`kda_plain`, which is
``kda_chunked`` between those normalisations, is their oracle). The
in-chunk products weigh keys by ratios of cumulative decays, ``exp(G_r -
G_i)``, which a factorisation ``exp(G_r) * exp(-G_i)`` overflows in any
float format once a chunk decays by more than e^88. So the chunk is SPLIT by
halving: a pair (row ``r``, key ``i < r``) belongs to the one block
size at which ``r`` lies in the upper and ``i`` in the lower half of the
same block, and factors around that lower half's last position ``m``:
``exp(G_r - G_m) * exp(G_m - G_i)``, both factors <= 1, exact in any
decay. ``log2(chunk)`` masked products of ``[C, D] x [D, C]`` a chunk.

**MLA** (``mla_use_nope``: no rotary part is rotated): queries of
``qk_nope + qk_rope`` per head, a ``kv_lora_rank`` latent (RMSNorm'd)
expanded to per-head ``k_nope`` and ``v``, plus a ``qk_rope``-wide key
part shared by all heads; causal softmax over ``q k^T / sqrt(192)``
through ``flash_attention`` (value heads narrower than query/key heads).
Training uses this un-absorbed form.

**Expert FFN** (``models/experts.py``, shared with ``laguna_lm``):
sigmoid router over ALL ``num_experts`` in float32, the top
``num_experts_per_token`` of ``s + b`` (``b``: the selection bias, a
leaf with no gradient), weights renormalised over the chosen and scaled
by ``routed_scaling_factor``, plus the shared expert; ``experts_held =
(first, count)`` is the chip's share of an expert-parallel layer.

Training only: there is no ``prefill_core`` / ``decode_step`` /
``init_cache`` for a recurrent state and a latent cache (ROADMAP A3,
A4); the serving CLI refuses such a checkpoint (``serving_refusal``).
``apply_with_stats`` hands ``make_train_step`` six device scalars a
step beside the logits (``moe.pairs_routed``, ``moe.pairs_here``,
``moe.expert_load_max``, ``moe.load_max_over_mean``, ``moe.tiles_run``,
``moe.rows_run``).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from mlapi_tpu.models import experts, register_model
from mlapi_tpu.models.experts import mm as _mm
from mlapi_tpu.ops.pallas import kda as kda_kernels
from mlapi_tpu.ops.pallas.flash_attention import REMAT_NAMES as _FLASH_NAMES
from mlapi_tpu.utils.metrics import REGISTRY
from mlapi_tpu.utils.platform import pallas_interpret

# Positions whose in-chunk matrices are built at once (kda_chunked). On a
# v5e at the published widths the step reads 765 / 682 / 618 ms at 1024 /
# 512 / 128 positions a group with chunks of 64, and 605 at 128 with
# chunks of 32 (PERF.md, PR 29): a group's temporaries should stay small.
_GROUP = 128
_HI = jax.lax.Precision.HIGHEST


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return x32 * inv * scale.astype(jnp.float32)


def _short_conv(x, w):
    """Causal depthwise convolution over time: ``x [B, L, C]``,
    ``w [K, C]``, ``y_t = sum_j w[j] * x_(t - K + 1 + j)``."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    l = x.shape[1]
    return sum(xp[:, j:j + l] * w[j] for j in range(k))


# -- KDA ---------------------------------------------------------------


def _kda_intra(q, k, g, cdt):
    """The in-chunk matrices. ``q, k, g``: ``[..., C, D]`` float32 (``g``
    the log-decay, <= 0; ``C`` a power of two). Returns ``G`` (the
    cumulative log-decay in the chunk), ``A`` (``[..., C, C]``,
    strictly lower: ``k_r . Diag(exp(G_r - G_i)) k_i``) and ``B``
    (lower with its diagonal: the same with ``q_r``).

    Halving (module docstring): at block size ``b`` every pair whose
    row lies in the upper half of a block and whose key lies in the
    lower half of the SAME block is one product of rows weighed by
    ``exp(G_r - G_m)`` and keys weighed by ``exp(G_m - G_i)``, ``m`` the
    lower half's last position: both exponents are <= 0. The
    ``log2(C)`` block sizes are stacked along one axis, so each of
    ``A`` and ``B`` is one batched product and one masked sum."""
    c, d = q.shape[-2:]
    lead = q.shape[:-2]
    G = jnp.cumsum(g, axis=-2)
    pos = jnp.arange(c)
    sizes = [c >> i for i in range(c.bit_length() - 1)]   # C, C/2, .., 2

    def from_half(b):
        Gb = G.reshape(*lead, c // b, b, d)
        return (Gb - Gb[..., b // 2 - 1:b // 2, :]).reshape(*lead, c, d)

    rel = jnp.stack([from_half(b) for b in sizes], axis=-3)  # [.., n, C, D]
    size = jnp.asarray(sizes)[:, None]
    upper = (pos % size >= size // 2)[..., None]             # [n, C, 1]
    row = jnp.where(upper, jnp.exp(jnp.minimum(rel, 0.0)), 0.0)
    key = (k[..., None, :, :] * jnp.where(
        upper, 0.0, jnp.exp(jnp.minimum(-rel, 0.0)))).astype(cdt)
    block = pos // size                                      # [n, C]
    same = block[:, :, None] == block[:, None, :]            # [n, C, C]

    def lower(rows):
        pairs = jnp.einsum(
            "...nrd,...nid->...nri",
            (rows[..., None, :, :] * row).astype(cdt), key,
            preferred_element_type=jnp.float32)
        return jnp.sum(jnp.where(same, pairs, 0.0), axis=-3)

    diag = jnp.eye(c, dtype=jnp.float32) * jnp.sum(q * k, -1)[..., None]
    return G, lower(k), lower(q) + diag


@jax.custom_vjp
def _unit_lower_inverse(m):
    """``m^-1`` for unit lower-triangular ``m [..., C, C]`` (``C`` a
    power of two), by doubling: with ``x`` the inverse of the diagonal
    blocks of size ``b``, the inverse of those of size ``2b`` is ``x -
    x L x``, ``L`` the lower-left quarter of each ``2b`` block (``[[P,
    0], [L, Q]]^-1 = [[P^-1, 0], [-Q^-1 L P^-1, Q^-1]]``): two products
    of whole matrices a level and no slicing, each block's inverse built
    from smaller exact ones as a blocked forward substitution builds it.
    What ``triangular_solve`` lowers to on the chip (a custom call that
    inverts diagonal blocks) took a seventh of the train step (PERF.md,
    PR 29)."""
    c = m.shape[-1]
    pos = jnp.arange(c)
    mm = functools.partial(jnp.einsum, "...ij,...jk->...ik", precision=_HI)
    x = jnp.broadcast_to(jnp.eye(c, dtype=m.dtype), m.shape)
    b = 1
    while b < c:
        quarter = ((pos // (2 * b))[:, None] == (pos // (2 * b))[None, :]) \
            & (pos % (2 * b) >= b)[:, None] & (pos % (2 * b) < b)[None, :]
        x = x - mm(mm(x, jnp.where(quarter, m, 0.0)), x)
        b *= 2
    return x


def _unit_lower_inverse_fwd(m):
    x = _unit_lower_inverse(m)
    return x, x


def _unit_lower_inverse_bwd(x, dx):
    # d(m^-1) = -m^-1 dm m^-1; only the strictly lower part of m is read
    mm = functools.partial(jnp.einsum, precision=_HI)
    dm = -mm("...ji,...jk,...lk->...il", x, dx, x)
    return (jnp.tril(dm, -1),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def kda_chunked(q, k, v, g, beta, *, chunk: int, compute_dtype="float32"):
    """The gated delta rule with a decay per channel, in chunks.

    ``q, k, g``: ``[B, L, H, Dk]``; ``v``: ``[B, L, H, Dv]``; ``beta``:
    ``[B, L, H]``; ``g <= 0`` is the log of the decay. Returns ``o``
    ``[B, L, H, Dv]`` float32. Any ``L`` (the tail is padded with
    positions that write nothing). Products take operands in
    ``compute_dtype`` and accumulate in float32; decays, the in-chunk
    solve and the carried state are float32. Chunks run in groups of
    ``_GROUP`` positions: a group's in-chunk matrices are built at once,
    its chunks scanned, and its inside recomputed in the backward pass,
    so the temporaries are a group's and not the sequence's.
    """
    cdt = jnp.dtype(compute_dtype)
    b, l, h, dk = q.shape
    dv = v.shape[-1]
    c = chunk
    n = -(-l // c)
    per = min(n, max(1, _GROUP // c))                # chunks a group
    groups = -(-n // per)
    pad = groups * per * c - l

    def grouped(a):
        a = a.astype(jnp.float32)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape(b, groups, per, c, *a.shape[2:])
        # [groups, per, B, H, C, ...]: the scans' axes lead, heads batch
        return jnp.transpose(a, (1, 2, 0, 4, 3, *range(5, a.ndim)))

    ein = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)

    def step(S, xs):
        u, wk, B, qg, kd, decay = xs
        Sc = S.astype(cdt)
        w = u - ein("bhck,bhkv->bhcv", wk, Sc)
        o = ein("bhck,bhkv->bhcv", qg, Sc) + ein(
            "bhci,bhiv->bhcv", B, w.astype(cdt))
        S = decay[..., None] * S + ein("bhck,bhcv->bhkv", kd, w.astype(cdt))
        return S, o

    @jax.checkpoint
    def group(S, xs):
        q, k, v, g, beta = xs                        # [per, B, H, C, ...]
        G, A, B = _kda_intra(q, k, g, cdt)
        m = jnp.eye(c, dtype=jnp.float32) + beta[..., None] * A
        rhs = jnp.concatenate(
            [v, k * jnp.exp(G)], axis=-1) * beta[..., None]
        sol = jnp.einsum("...ij,...jk->...ik", _unit_lower_inverse(m), rhs,
                         precision=_HI)
        g_end = G[..., -1:, :]
        return jax.lax.scan(step, S, (
            sol[..., :dv], sol[..., dv:].astype(cdt), B.astype(cdt),
            (q * jnp.exp(G)).astype(cdt),
            (k * jnp.exp(g_end - G)).astype(cdt),
            jnp.exp(g_end[..., 0, :]),
        ))

    S0 = jnp.zeros((b, h, dk, dv), jnp.float32)
    _, o = jax.lax.scan(
        group, S0, tuple(grouped(a) for a in (q, k, v, g, beta)))
    # [groups, per, B, H, C, Dv] -> [B, L, H, Dv]
    o = jnp.transpose(o, (2, 0, 1, 4, 3, 5))
    return o.reshape(b, groups * per * c, h, dv)[:, :l]


def kda_plain(q, k, v, g, beta, gate, o_scale, *, eps: float, chunk: int,
              compute_dtype="float32"):
    """A KDA layer between its projections in plain ``jax.numpy``: the
    slabs split into heads, q and k L2-normalised (q scaled ``Dk **
    -0.5``), :func:`kda_chunked`, the output RMS-normed per head
    (``o_scale [Dv]``) and gated. ``q, k, g``: ``[B, L, H * Dk]``
    (``q, k`` raw, ``g <= 0``); ``v, gate``: ``[B, L, H * Dv]``;
    ``beta``: ``[B, L, H]``. Returns ``y [B, L, H * Dv]`` float32. What
    narrow heads run, and the oracle of the kernels' normed call."""
    b, l, h = beta.shape

    def heads(a):
        return a.reshape(b, l, h, -1)

    def l2(a):
        return a * jax.lax.rsqrt(
            jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6)

    q, k = heads(q), heads(k)
    o = kda_chunked(l2(q) * q.shape[-1] ** -0.5, l2(k), heads(v), heads(g),
                    beta, chunk=chunk, compute_dtype=compute_dtype)
    return (_rms_norm(o, o_scale, eps) * heads(gate)).reshape(b, l, -1)


def kda(q, k, v, g, beta, gate, o_scale, *, eps: float, chunk: int,
        compute_dtype="float32"):
    """A KDA layer between its projections, by whichever implementation
    the shapes allow: heads that are whole 128-lane slabs (the
    published ``head_dim`` 128) run the Pallas kernels
    (``ops/pallas/kda.py``: the chunk's matrices, the state and the
    per-head normalisations stay in VMEM, operands in the projections'
    own ``[B, L, H * D]``; they tile the sequence themselves and the
    result does not depend on ``chunk``); anything narrower runs
    :func:`kda_plain`. Same arguments and result as :func:`kda_plain`.
    Counted once a TRACE in ``utils.metrics.REGISTRY``:
    ``kda.calls_traced``, and ``kda.calls_kernel`` when the kernels are
    chosen."""
    REGISTRY.counter("kda.calls_traced").inc()
    h = beta.shape[-1]
    if not kda_kernels.takes(h, q.shape[-1] // h, v.shape[-1] // h):
        return kda_plain(q, k, v, g, beta, gate, o_scale, eps=eps,
                         chunk=chunk, compute_dtype=compute_dtype)
    REGISTRY.counter("kda.calls_kernel").inc()
    return kda_kernels.kda_layer(
        q, k, v, g, beta, gate, o_scale, eps=eps,
        compute_dtype=compute_dtype, interpret=pallas_interpret())


# -- the model ---------------------------------------------------------


@register_model("kimi_linear_lm")
@dataclass(frozen=True)
class KimiLinearLM:
    """Decoder-only causal LM, Kimi-Linear architecture (training)."""

    input_kind = "text"
    serving_refusal = (
        "a kimi_linear_lm checkpoint trains but cannot be served yet: "
        "the engine has no recurrent-state or latent cache "
        "(ROADMAP A3, A4)"
    )

    vocab_size: int = 512
    hidden_size: int = 64
    num_layers: int = 5
    # 1-indexed, as published
    kda_layers: tuple = (1, 2, 3, 5)
    full_attn_layers: tuple = (4,)
    first_k_dense_replace: int = 1
    intermediate_size: int = 256
    # latent attention
    num_heads: int = 4
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    kv_lora_rank: int = 32
    # gated delta-rule linear attention
    kda_num_heads: int = 4
    kda_head_dim: int = 16
    conv_kernel: int = 4
    kda_gate_rank: int | None = None  # None -> kda_head_dim
    kda_chunk: int = 32  # what the chip prefers (_GROUP's note)
    # expert FFN
    num_experts: int = 16
    num_experts_per_token: int = 4
    moe_intermediate_size: int = 32
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    # (first id, count) of the routed experts this model holds;
    # None: all of them
    experts_held: tuple | None = None
    moe_tile: int = 256
    rms_norm_eps: float = 1e-5
    compute_dtype: str = "bfloat16"
    # every block under jax.checkpoint (what 8,192 positions at the
    # published widths need beside 9.6 GB of state). A block's
    # recomputation keeps what the delta-rule and flash forward kernels
    # and the router made (the names their producers set), so those run
    # once a step; everything else is made again from the block's input.
    remat: bool = True
    mesh: object = None

    def __post_init__(self):
        for name in ("kda_layers", "full_attn_layers", "experts_held"):
            val = getattr(self, name)
            if isinstance(val, list):
                object.__setattr__(self, name, tuple(val))
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(
                f"experts_held {self.experts_held} outside 0.."
                f"{self.num_experts}")
        if self.kda_chunk < 2 or self.kda_chunk & (self.kda_chunk - 1):
            raise ValueError("kda_chunk must be a power of two")
        _ = self.layer_kinds  # every layer has a kind, or this raises

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @property
    def gate_rank(self) -> int:
        return self.kda_gate_rank or self.kda_head_dim

    @property
    def layer_kinds(self) -> tuple:
        """``(mixer, ffn)`` of every layer: ``("kda" | "mla", "dense" |
        "moe")``."""
        kinds = []
        for i in range(1, self.num_layers + 1):
            if i in self.kda_layers:
                mixer = "kda"
            elif i in self.full_attn_layers:
                mixer = "mla"
            else:
                raise ValueError(
                    f"layer {i} is in neither kda_layers nor "
                    "full_attn_layers")
            kinds.append(
                (mixer, "dense" if i <= self.first_k_dense_replace else "moe"))
        return tuple(kinds)

    # ------------------------------------------------------------------
    def init(self, rng: jax.Array) -> dict:
        h, v = self.hidden_size, self.vocab_size
        keys = iter(jax.random.split(rng, 4 + 32 * self.num_layers))

        def w(*shape, scale=0.02):
            return scale * jax.random.normal(next(keys), shape, jnp.float32)

        ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
        nk, dk, r = self.kda_num_heads, self.kda_head_dim, self.gate_rank
        ck = nk * dk
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        nh, lat = self.num_heads, self.kv_lora_rank
        ie, n_held = self.moe_intermediate_size, self.held[1]

        def ffn(i):
            return {"gate": w(h, i), "up": w(h, i), "down": w(i, h)}

        params = {"embed": w(v, h), "final_norm": ones(h), "lm_head": w(h, v)}
        for n, (mixer, kind) in enumerate(self.layer_kinds):
            layer = {"attn_norm": ones(h), "ffn_norm": ones(h)}
            if mixer == "kda":
                layer["kda"] = {
                    "q": w(h, ck), "k": w(h, ck), "v": w(h, ck),
                    "conv_q": w(self.conv_kernel, ck, scale=0.3),
                    "conv_k": w(self.conv_kernel, ck, scale=0.3),
                    "conv_v": w(self.conv_kernel, ck, scale=0.3),
                    "f_a": w(h, r), "f_b": w(r, ck),
                    # softplus(dt_bias) in [0.001, 0.1], exp(A_log) in
                    # [1, 16]: a step forgets 0.1% to 80% of a channel
                    "dt_bias": jnp.log(jnp.expm1(jnp.geomspace(
                        1e-3, 1e-1, ck, dtype=jnp.float32))),
                    "A_log": jnp.log(jnp.linspace(
                        1.0, 16.0, nk, dtype=jnp.float32)),
                    "b": w(h, nk),
                    "g_a": w(h, r), "g_b": w(r, ck),
                    "o_norm": ones(dk), "o": w(ck, h),
                }
            else:
                layer["mla"] = {
                    "q": w(h, nh * qk),
                    "kv_a": w(h, lat + self.qk_rope_head_dim),
                    "kv_norm": ones(lat),
                    "kv_b": w(lat, nh * (self.qk_nope_head_dim
                                         + self.v_head_dim)),
                    "o": w(nh * self.v_head_dim, h),
                }
            if kind == "dense":
                layer["mlp"] = ffn(self.intermediate_size)
            else:
                layer["moe"] = {
                    "router": w(h, self.num_experts),
                    "router_bias": w(self.num_experts),
                    "experts": {"gate": w(n_held, h, ie),
                                "up": w(n_held, h, ie),
                                "down": w(n_held, ie, h)},
                    "shared": ffn(ie * self.num_shared_experts),
                }
            params[f"layer_{n}"] = layer
        return params

    # ------------------------------------------------------------------
    def _kda(self, p, x):
        """Everything here is elementwise on ``[B, L, H * D]`` or a
        product: what needs a head's channels together (the L2 norms of
        q and k, the output's RMS norm) is :func:`kda`'s, so XLA has no
        4-D tensor to lay out."""
        cdt = jnp.dtype(self.compute_dtype)

        def qkv(name):
            return jax.nn.silu(
                _short_conv(_mm(x, p[name], cdt), p["conv_" + name]))

        g = -jnp.repeat(jnp.exp(p["A_log"]), self.kda_head_dim) * (
            jax.nn.softplus(
                _mm(_mm(x, p["f_a"], cdt), p["f_b"], cdt) + p["dt_bias"]))
        beta = jax.nn.sigmoid(_mm(x, p["b"], cdt))
        gate = jax.nn.sigmoid(_mm(_mm(x, p["g_a"], cdt), p["g_b"], cdt))
        q, k, v = qkv("q"), qkv("k"), qkv("v")
        with jax.named_scope("kda.core"):
            y = kda(q, k, v, g, beta, gate, p["o_norm"],
                    eps=self.rms_norm_eps, chunk=self.kda_chunk,
                    compute_dtype=self.compute_dtype)
        return _mm(y, p["o"], cdt)

    def _mla(self, p, x):
        cdt = jnp.dtype(self.compute_dtype)
        b, l, _ = x.shape
        nh, nope, rope = (self.num_heads, self.qk_nope_head_dim,
                          self.qk_rope_head_dim)
        lat, vd = self.kv_lora_rank, self.v_head_dim
        q = _mm(x, p["q"], cdt).reshape(b, l, nh, nope + rope)
        c = _mm(x, p["kv_a"], cdt)
        c_kv = _rms_norm(c[..., :lat], p["kv_norm"], self.rms_norm_eps)
        kv = _mm(c_kv, p["kv_b"], cdt).reshape(b, l, nh, nope + vd)
        k_pe = jnp.broadcast_to(c[..., None, lat:], (b, l, nh, rope))
        k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
        q, k, v = q.astype(cdt), k.astype(cdt), kv[..., nope:].astype(cdt)
        from mlapi_tpu.ops.pallas import flash_attention_on_mesh

        ctx = flash_attention_on_mesh(
            self.mesh, q, k, v, causal=True, interpret=pallas_interpret())
        return _mm(ctx.reshape(b, l, nh * vd), p["o"], cdt)

    def _ffn(self, p, x):
        return experts.ffn(p, x, jnp.dtype(self.compute_dtype))

    def _moe(self, p, x):
        """The held experts' part plus the shared expert, and the
        layer's ``(pairs here, fullest held expert's pairs, tiles
        run)``."""
        return experts.moe(
            p, x, k=self.num_experts_per_token, held=self.held,
            tile=self.moe_tile, scale=self.routed_scaling_factor,
            compute_dtype=self.compute_dtype)

    def _block(self, kinds, layer, x):
        mixer, kind = kinds
        xn = _rms_norm(x, layer["attn_norm"], self.rms_norm_eps)
        with jax.named_scope(mixer):
            mix = (self._kda if mixer == "kda" else self._mla)(
                layer[mixer], xn)
        x = x + mix
        xn = _rms_norm(x, layer["ffn_norm"], self.rms_norm_eps)
        zero = jnp.zeros((), jnp.int32)
        if kind == "dense":
            return x + self._ffn(layer["mlp"], xn), (zero, zero, zero)
        y, load = self._moe(layer["moe"], xn)
        return x + y, load

    def apply_with_stats(self, params: dict, token_ids):
        """``[B, L]`` ids -> ``[B, L, V]`` float32 logits, and the
        step's expert load as device scalars (``experts.load_stats``:
        ``moe.pairs_routed``, ``moe.pairs_here``,
        ``moe.expert_load_max``, ``moe.load_max_over_mean``,
        ``moe.tiles_run``, ``moe.rows_run``)."""
        cdt = jnp.dtype(self.compute_dtype)
        x = params["embed"][token_ids].astype(jnp.float32)
        loads = []
        # a recomputed block keeps what its kernels and its router made
        # (the producers name it; docs/DESIGN.md section 30)
        keep = jax.checkpoint_policies.save_only_these_names(
            *kda_kernels.REMAT_NAMES, *_FLASH_NAMES, *experts.ROUTE_NAMES)
        for n, kinds in enumerate(self.layer_kinds):
            block = functools.partial(self._block, kinds)
            if self.remat:
                block = jax.checkpoint(block, policy=keep)
            x, load = block(params[f"layer_{n}"], x)
            loads.append(load)
        with jax.named_scope("lm_head"):
            logits = _mm(
                _rms_norm(x, params["final_norm"], self.rms_norm_eps),
                params["lm_head"], cdt)
        moe_layers = sum(kind == "moe" for _, kind in self.layer_kinds)
        routed = token_ids.size * self.num_experts_per_token * moe_layers
        return logits, experts.load_stats(loads, self.held[1], routed,
                                          self.moe_tile)

    def apply(self, params: dict, token_ids) -> jax.Array:
        return self.apply_with_stats(params, token_ids)[0]
