"""Plain reference for the ``kimi-linear-48b-a3b-ep32`` configuration.

Kimi-Linear (``moonshotai/Kimi-Linear-48B-A3B-Instruct``, config.json):
a decoder whose layers differ in kind. Published layer ``i`` (1-indexed)
mixes with KDA if ``i`` is in ``linear_attn_config.kda_layers`` and with
MLA if in ``full_attn_layers``; its FFN is dense for ``i <=
first_k_dense_replace`` and the sparse-expert FFN after. Pre-norm
residual blocks, RMSNorm (eps ``rms_norm_eps``), untied head, no biases.
Forward, next-token loss over the non-pad targets, gradients
(``jax.grad`` of this file's own forward) and AdamW written out here:
float32 ``jax.numpy`` at ``Precision.HIGHEST``, no kernels, no chunks,
no sorting. Imports nothing of ``mlapi_tpu``; the weights come from
:func:`make_params`, which the harness also hands to the program.

The equations, as read from the config's keys and the family's
description (catalog row of the ``model-configs`` guide):

- **KDA**, the LITERAL recurrence, one position at a time. ``q~, k~, v~
  = W x``; a causal depthwise convolution of width
  ``short_conv_kernel_size`` over time on each, then SiLU; q and k
  L2-normalised per head, q scaled ``d_k ** -0.5``. Per channel
  ``g_t = -exp(A_log[h]) * softplus(W_f2 W_f1 x_t + dt_bias)``,
  ``beta_t = sigmoid(W_b x_t)``; per head, ``S`` in R^(d_k x d_v):
  ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_(t-1) + beta_t k_t
  v_t^T``, ``o_t = S_t^T q_t``; out ``W_o [RMSNorm_head(o_t) *
  sigmoid(W_g2 W_g1 x_t)]``. The scan over time is cut into segments
  whose inside is recomputed in the backward pass, so 8,192 positions
  keep 128 states and not 8,192.
- **MLA** without positions (``mla_use_nope``, ``q_lora_rank`` null):
  ``q = W_q x`` (heads of ``qk_nope + qk_rope``); ``c = W_kva x``,
  ``c_kv = RMSNorm(c[:kv_lora_rank])``, ``k_pe = c[kv_lora_rank:]``
  shared by all heads and not rotated; ``[k_nope, v] = W_kvb c_kv`` per
  head; causal softmax of ``q [k_nope, k_pe]^T / sqrt(qk_nope +
  qk_rope)`` over ``v``, in blocks of query rows.
- **Expert FFN**: ``s = sigmoid(W_r x)`` over all ``router_width``
  experts; the top ``num_experts_per_token`` of ``s + b``; weights
  ``s_i / sum_chosen s_j * routed_scaling_factor``; every token goes
  through EVERY held expert and the result is weighted by whether (and
  how) the token chose it: a dense mask, nothing sorted, nothing
  dropped. ``experts_first``/``num_experts`` say which experts are held
  (the chip's share of an expert-parallel layer): what the absent ones
  would add is left out, here as in the program. Plus the shared expert.

Departures and sizes the config does not settle (the configuration
file's ``assumed``): the two low-rank gates have rank ``head_dim``;
``A_log`` is one number a head and ``dt_bias`` one a channel; the
selection bias ``b`` is a fixed leaf (no gradient, no balance loss); the
output norm's weight is one vector of ``d_v`` shared by the heads;
documents packed into a row are not separated in attention or state.

``precision`` (``numerics.py``): ``"float32"`` is the reference;
``"int8_all"`` puts every projection's product on the int8 grid,
forward and backward: the CONTROL, one step under the configuration's
bfloat16 products. The router and the recurrence stay float32 in every
precision (a deployment keeps them so).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference import numerics
from reference.numerics import (  # noqa: F401 (seed_key, split_seed: re-exported)
    einsum, hashable, matmul, seed_key, split_seed,
)

NEG = -1e30
SEGMENT = 64      # positions of the recurrence between two kept states
QUERY_BLOCK = 256

# AdamW as the configuration states it (optax.adamw's defaults at the
# configuration's learning rate; fit's own weight_decay, an L2 term in
# the loss, is 0).
ADAMW = dict(lr=1e-4, b1=0.9, b2=0.999, eps=1e-8, weight_decay=1e-4)


def settings(cfg: dict) -> dict:
    """The configuration's file as the flat scalars this file reads
    (``numerics.hashable`` keeps scalars only): the published keys
    under their own names, the nested ``linear_attn_config`` flattened,
    the layers' kinds as one string (``kd`` KDA + dense, ``km`` KDA +
    experts, ``mm`` MLA + experts, ...)."""
    if "layers" in cfg:
        return cfg
    lin = cfg["linear_attn_config"]
    kinds = []
    for i in range(1, cfg["num_hidden_layers"] + 1):
        mixer = ("k" if i in lin["kda_layers"] else
                 "m" if i in lin["full_attn_layers"] else None)
        if mixer is None:
            raise ValueError(f"layer {i} has no kind")
        kinds.append(mixer + ("d" if i <= cfg["first_k_dense_replace"]
                              else "m"))
    held = cfg.get("experts_held") or [0, cfg["num_experts"]]
    keep = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_experts_per_token",
            "num_shared_experts", "routed_scaling_factor",
            "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "kv_lora_rank", "rms_norm_eps")
    return {
        **{k: cfg[k] for k in keep},
        "layers": ",".join(kinds),
        "kda_heads": lin["num_heads"], "kda_head_dim": lin["head_dim"],
        "conv_kernel": lin["short_conv_kernel_size"],
        "gate_rank": cfg.get("kda_gate_rank") or lin["head_dim"],
        "router_width": cfg.get("router_width", cfg["num_experts"]),
        "experts_first": held[0], "experts_count": held[1],
    }


def param_spec(cfg: dict) -> dict:
    """Flat ``name -> (shape, init)``; names are dotted paths, the
    program's own tree flattened."""
    c = settings(cfg)
    h, v = c["hidden_size"], c["vocab_size"]
    ck, r = c["kda_heads"] * c["kda_head_dim"], c["gate_rank"]
    nh, lat = c["num_attention_heads"], c["kv_lora_rank"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    ie, held = c["moe_intermediate_size"], c["experts_count"]
    w = "normal:0.02"
    spec = {"embed": ((v, h), w), "final_norm": ((h,), "scale:0.05"),
            "lm_head": ((h, v), w)}

    def ffn(p, i):
        spec.update({p + "gate": ((h, i), w), p + "up": ((h, i), w),
                     p + "down": ((i, h), w)})

    for n, kind in enumerate(c["layers"].split(",")):
        p = f"layer_{n}."
        spec[p + "attn_norm"] = spec[p + "ffn_norm"] = ((h,), "scale:0.05")
        if kind[0] == "k":
            a = p + "kda."
            for name in "qkv":
                spec[a + name] = ((h, ck), w)
                spec[a + "conv_" + name] = ((c["conv_kernel"], ck), "normal:0.3")
            spec.update({
                a + "f_a": ((h, r), w), a + "f_b": ((r, ck), w),
                # softplus(dt_bias) in [0.001, 0.1], exp(A_log) in [1, 16]
                a + "dt_bias": ((ck,), "inv_softplus_geom:0.001:0.1"),
                a + "A_log": ((c["kda_heads"],), "log_lin:1:16"),
                a + "b": ((h, c["kda_heads"]), w),
                a + "g_a": ((h, r), w), a + "g_b": ((r, ck), w),
                a + "o_norm": ((c["kda_head_dim"],), "scale:0.05"),
                a + "o": ((ck, h), w),
            })
        else:
            a = p + "mla."
            spec.update({
                a + "q": ((h, nh * qk), w),
                a + "kv_a": ((h, lat + c["qk_rope_head_dim"]), w),
                a + "kv_norm": ((lat,), "scale:0.05"),
                a + "kv_b": ((lat, nh * (c["qk_nope_head_dim"]
                                         + c["v_head_dim"])), w),
                a + "o": ((nh * c["v_head_dim"], h), w),
            })
        if kind[1] == "d":
            ffn(p + "mlp.", c["intermediate_size"])
        else:
            a = p + "moe."
            spec.update({
                # logits of spread ~1 over unit-RMS inputs and a bias a
                # fiftieth of that: the eighth and ninth scores of a
                # token are 0.02 apart in the mean, never tied
                a + "router": ((h, c["router_width"]), w),
                a + "router_bias": ((c["router_width"],), w),
                a + "experts.gate": ((held, h, ie), w),
                a + "experts.up": ((held, h, ie), w),
                a + "experts.down": ((held, ie, h), w),
            })
            ffn(a + "shared.", ie * c["num_shared_experts"])
    return spec


def draw(spec: dict, key) -> dict:
    """``numerics.draw`` plus the two ramps of the decay's leaves."""
    plain = {k: v for k, v in spec.items()
             if v[1].split(":")[0] in ("normal", "scale")}
    out = numerics.draw(plain, key)
    for name, (shape, init) in spec.items():
        kind, _, args = init.partition(":")
        if name in out:
            continue
        lo, hi = (float(a) for a in args.split(":"))
        if kind == "log_lin":
            out[name] = jnp.log(jnp.linspace(lo, hi, shape[0],
                                             dtype=jnp.float32))
        elif kind == "inv_softplus_geom":
            out[name] = jnp.log(jnp.expm1(jnp.geomspace(
                lo, hi, shape[0], dtype=jnp.float32)))
        else:
            raise ValueError(init)
    return out


@functools.lru_cache(maxsize=8)
def _make_params_fn(cfg_items: tuple):
    spec = param_spec(dict(cfg_items))
    return jax.jit(lambda lo, hi: draw(spec, seed_key(lo, hi)))


def make_params(seed: int, cfg: dict) -> dict:
    """Every weight, on the device, in one jitted call from the seed."""
    lo, hi = split_seed(seed)
    return _make_params_fn(hashable(settings(cfg)))(
        jnp.int32(lo), jnp.int32(hi))


# ---- the layers -----------------------------------------------------------


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _conv(x, w):
    k, l = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + l] * w[j] for j in range(k))


def delta_rule(q, k, v, g, beta):
    """The recurrence itself. ``q, k, g``: ``[B, L, H, Dk]``, ``v``:
    ``[B, L, H, Dv]``, ``beta``: ``[B, L, H]`` -> ``o [B, L, H, Dv]``."""
    b, l, h, dk = q.shape
    seg = min(SEGMENT, l)
    pad = -l % seg

    def time_major(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((l + pad) // seg, seg, *a.shape[1:])

    def position(S, x):
        q, k, v, g, be = x
        S = jnp.exp(g)[..., None] * S
        kS = jnp.einsum("bhk,bhkv->bhv", k, S, precision=numerics._HI)
        S = S + (be[..., None] * k)[..., None] * (v - kS)[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q, precision=numerics._HI)

    @jax.checkpoint
    def segment(S, xs):
        return jax.lax.scan(position, S, xs)

    S0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(segment, S0,
                        tuple(time_major(a) for a in (q, k, v, g, beta)))
    o = o.reshape(l + pad, b, h, -1)[:l]
    return jnp.moveaxis(o, 0, 1)


def _kda(p, x, c, precision):
    b, l, _ = x.shape
    nk, dk = c["kda_heads"], c["kda_head_dim"]
    mm = functools.partial(matmul, precision=precision)

    def qkv(name):
        return jax.nn.silu(_conv(mm(x, p[name]), p["conv_" + name])
                           ).reshape(b, l, nk, dk)

    def l2(a):
        return a * jax.lax.rsqrt(
            jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6)

    q, k, v = l2(qkv("q")) * dk ** -0.5, l2(qkv("k")), qkv("v")
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        (mm(mm(x, p["f_a"]), p["f_b"]) + p["dt_bias"]).reshape(b, l, nk, dk))
    beta = jax.nn.sigmoid(mm(x, p["b"]))
    o = delta_rule(q, k, v, g, beta)
    gate = jax.nn.sigmoid(mm(mm(x, p["g_a"]), p["g_b"])).reshape(b, l, nk, dk)
    o = _rms_norm(o, p["o_norm"], c["rms_norm_eps"]) * gate
    return mm(o.reshape(b, l, nk * dk), p["o"])


def _mla(p, x, c, precision):
    b, l, _ = x.shape
    nh, nope, rope = (c["num_attention_heads"], c["qk_nope_head_dim"],
                      c["qk_rope_head_dim"])
    lat, vd = c["kv_lora_rank"], c["v_head_dim"]
    mm = functools.partial(matmul, precision=precision)
    q = mm(x, p["q"]).reshape(b, l, nh, nope + rope)
    ckv = mm(x, p["kv_a"])
    kv = mm(_rms_norm(ckv[..., :lat], p["kv_norm"], c["rms_norm_eps"]),
            p["kv_b"]).reshape(b, l, nh, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(ckv[..., None, lat:], (b, l, nh, rope))], axis=-1)
    v = kv[..., nope:]
    blk = min(QUERY_BLOCK, l)
    pad = -l % blk
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, (l + pad) // blk, blk, nh, nope + rope)

    @jax.checkpoint
    def rows(_, xs):
        qi, start = xs
        s = einsum("bqhd,bkhd->bhqk", qi, k, precision) * (nope + rope) ** -0.5
        seen = (start + jnp.arange(blk))[:, None] >= jnp.arange(l)[None, :]
        pr = jax.nn.softmax(jnp.where(seen, s, NEG), axis=-1)
        return None, einsum("bhqk,bkhd->bqhd", pr, v, precision)

    _, ctx = jax.lax.scan(
        rows, None, (jnp.moveaxis(qb, 1, 0), jnp.arange(qb.shape[1]) * blk))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, l + pad, nh * vd)[:, :l]
    return mm(ctx, p["o"])


def _ffn(p, x, precision):
    mm = functools.partial(matmul, precision=precision)
    return mm(jax.nn.silu(mm(x, p["gate"])) * mm(x, p["up"]), p["down"])


def route(p, x, c):
    """``[T, router_width]`` combine weights: ``s_i / sum_chosen *
    routed_scaling_factor`` where expert ``i`` is among the token's
    top ``num_experts_per_token`` of ``s + b``, else 0."""
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"], precision=numerics._HI))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(p["router_bias"]),
                           c["num_experts_per_token"])
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=s.dtype), axis=1)
    w = s * chosen
    return w / jnp.sum(w, axis=-1, keepdims=True) * c["routed_scaling_factor"]


def moe(p, x, c, precision="float32"):
    """The held experts' part of the layer plus the shared expert, and
    the layer's pairs routed to a held expert."""
    b, l, h = x.shape
    w = route(p, x.reshape(b * l, h), c)
    first, count = c["experts_first"], c["experts_count"]
    y = _ffn(p["shared"], x, precision)
    for e in range(count):
        expert = {k: p["experts"][k][e] for k in ("gate", "up", "down")}
        y = y + (w[:, first + e].reshape(b, l, 1)
                 * _ffn(expert, x, precision))
    return y, jnp.sum(w[:, first:first + count] > 0)


def nested(params: dict) -> dict:
    """``{"a.b": x}`` -> ``{"a": {"b": x}}``."""
    out: dict = {}
    for k, v in params.items():
        d = out
        *path, last = k.split(".")
        for part in path:
            d = d.setdefault(part, {})
        d[last] = v
    return out


def forward(params: dict, ids, cfg: dict, precision: str = "float32"):
    """``[B, L]`` token ids -> ``[B, L, vocab]`` float32 logits, and
    the pairs routed to a held expert (all expert layers)."""
    c = settings(cfg)
    p = nested(params)
    eps = c["rms_norm_eps"]
    x = p["embed"][ids]
    here = jnp.zeros((), jnp.int32)
    for n, kind in enumerate(c["layers"].split(",")):
        layer = p[f"layer_{n}"]

        @jax.checkpoint
        def block(layer, x, kind=kind):
            xn = _rms_norm(x, layer["attn_norm"], eps)
            x = x + (_kda(layer["kda"], xn, c, precision) if kind[0] == "k"
                     else _mla(layer["mla"], xn, c, precision))
            xn = _rms_norm(x, layer["ffn_norm"], eps)
            if kind[1] == "d":
                return x + _ffn(layer["mlp"], xn, precision), jnp.int32(0)
            y, pairs = moe(layer["moe"], xn, c, precision)
            return x + y, pairs.astype(jnp.int32)

        x, pairs = block(layer, x)
        here = here + pairs
    logits = matmul(_rms_norm(x, p["final_norm"], eps), p["lm_head"],
                    precision)
    return logits, here


def loss_fn(params, ids, cfg, precision):
    """Mean next-token cross-entropy over the targets that are not
    padding (id 0), as ``make_train_step(task="lm")`` has it."""
    logits, here = forward(params, ids, cfg, precision)
    targets = ids[:, 1:]
    keep = (targets != 0).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    ce = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(ce * keep) / jnp.maximum(jnp.sum(keep), 1.0), here


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _loss_and_grad(params, ids, cfg_items, precision):
    (loss, here), g = jax.value_and_grad(loss_fn, has_aux=True)(
        params, ids, dict(cfg_items), precision)
    return loss, here, g


def _adamw_step(params, mu, nu, grads, t, hp_items):
    hp = dict(hp_items)
    out_p, out_m, out_v = {}, {}, {}
    for k in params:
        g = grads[k]
        m = hp["b1"] * mu[k] + (1 - hp["b1"]) * g
        v = hp["b2"] * nu[k] + (1 - hp["b2"]) * g * g
        mh = m / (1 - hp["b1"] ** t)
        vh = v / (1 - hp["b2"] ** t)
        upd = mh / (jnp.sqrt(vh) + hp["eps"]) + hp["weight_decay"] * params[k]
        out_p[k] = params[k] - hp["lr"] * upd
        out_m[k], out_v[k] = m, v
    return out_p, out_m, out_v


_adamw = jax.jit(_adamw_step, static_argnames=("hp_items",))
# the state updated in place: at the published widths two copies of
# weights and moments do not fit beside the gradient
_adamw_in_place = jax.jit(_adamw_step, static_argnames=("hp_items",),
                          donate_argnums=(0, 1, 2))


@jax.jit
def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def leaf_samples(tree: dict) -> dict:
    return {k: numerics.sample(v.astype(jnp.float32))
            for k, v in tree.items()}


@jax.jit
def _delta_norms(p, p0):
    return {k: jnp.sqrt(jnp.sum(jnp.square(p[k] - p0[k]))) for k in p}


def train_steps(params, batches, cfg, *, precision="float32", block=None,
                hp=None, fault=None, seed=None):
    """Follow ``len(batches)`` AdamW steps from ``params``. Returns
    what ``reference/bert.py``'s does (``losses``, ``grad_norms``,
    ``grad_sample``, ``delta_norms``) and ``pairs_here`` a step.
    ``block`` is not used: a row is the unit, and the layers block
    themselves. With ``seed`` (the one ``params`` were made from) the
    state is updated in place, ``params`` are consumed, the moments
    wait on the host between two steps, and the change is measured
    against a fresh draw.

    ``fault`` puts a planted fault in the program's place (never in a
    benchmark run): ``"drop_half"`` trains on half of the rows (with
    one row: on the first half of its positions);
    ``"state_unchanged"`` returns the state as it came."""
    hp = tuple(sorted(dict(ADAMW if hp is None else hp).items()))
    cfg_items = hashable(settings(cfg))
    in_place = seed is not None
    adamw = _adamw_in_place if in_place else _adamw
    p, mu, nu = params, None, None
    losses, here, grad_norms, grad_sample = [], [], None, None
    for t, (ids, _) in enumerate(batches, start=1):
        ids = jnp.asarray(ids)
        if fault == "drop_half":
            ids = (ids[:ids.shape[0] // 2] if ids.shape[0] > 1
                   else ids[:, :ids.shape[1] // 2])
        elif fault not in (None, "state_unchanged"):
            raise ValueError(fault)
        loss, pairs, g = _loss_and_grad(p, ids, cfg_items, precision)
        if grad_norms is None:
            grad_norms = leaf_norms(g)
            grad_sample = jax.device_get(leaf_samples(g))
        if fault != "state_unchanged":
            if mu is None:
                mu = jax.tree.map(jnp.zeros_like, g)
                nu = jax.tree.map(jnp.zeros_like, g)
            p, mu, nu = adamw(p, mu, nu, g, jnp.float32(t), hp)
            if in_place and t < len(batches):
                # the moments wait on the host while the next gradient
                # is computed: weights, gradient and the step's
                # temporaries fill the chip without them
                mu, nu = jax.device_get((mu, nu))
        del g
        losses.append(float(loss))
        here.append(int(pairs))
    del mu, nu
    if seed is not None and fault != "state_unchanged":
        params = make_params(seed, cfg)
    delta = _delta_norms(p, params)
    return {
        "losses": losses,
        "grad_norms": {k: float(v) for k, v in grad_norms.items()},
        "grad_sample": grad_sample,
        "delta_norms": {k: float(v) for k, v in delta.items()},
        "pairs_here": here,
    }
