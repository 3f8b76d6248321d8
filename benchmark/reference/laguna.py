"""Plain reference for the ``laguna-xs2-ep8`` configuration.

Laguna-XS.2 (``poolside/Laguna-XS.2``, config.json, ``model_type:
laguna``): a decoder whose attention layers differ in mask, head count
and positions. Forward, next-token loss over the non-pad targets,
gradients (``jax.grad`` of this file's own forward) and AdamW written
out here: float32 ``jax.numpy`` at ``Precision.HIGHEST``, no kernel, no
grouped product, attention as an explicit masked softmax. Imports
nothing of ``mlapi_tpu``; written from the equations below, which are
the configuration's keys read literally.

Pre-norm residual blocks, ``h = x + Attn_l(RMSNorm(x))``, ``y = h +
FFN_l(RMSNorm(h))``, final RMSNorm, untied head, no biases. Layer ``l``
(0-indexed as published) has kind ``layer_types[l]``, ``H_l =
num_attention_heads_per_layer[l]`` query heads, ``num_key_value_heads``
K/V heads, ``d = head_dim``:

- ``q = x W_q [L, H_l, d]``, ``k = x W_k``, ``v = x W_v [L, KV, d]``;
  query head ``h`` reads K/V head ``h // (H_l / KV)``.
- Positions 0 .. L-1 of the row, ``rope_parameters[kind]``. Rotated are
  the first ``r = partial_rotary_factor * d`` dims, dim ``i < r / 2``
  paired with ``i + r / 2`` (HF ``rotate_half``); the others pass.
  ``default``: ``inv_freq_i = theta^(-2i / r)``. ``yarn`` (HF
  ``_compute_yarn_parameters``, with ``dim = r``): ``f_i = theta^(-2i /
  r)``; ``c(n) = r ln(original_max / (2 pi n)) / (2 ln theta)``; ``lo =
  max(floor(c(beta_fast)), 0)``, ``hi = min(ceil(c(beta_slow)), r -
  1)``; ``ramp_i = clip((i - lo) / (hi - lo), 0, 1)``; ``inv_freq_i =
  f_i / factor * ramp_i + f_i * (1 - ramp_i)``; ``cos`` and ``sin``
  times ``attention_factor``.
- Scores ``q k^T / sqrt(d)``, causal; on ``sliding_attention`` layers
  also ``q_pos - k_pos < sliding_window`` (a position sees itself and
  the ``window - 1`` before it); softmax; times ``v``. In blocks of
  query rows, every block against all the keys.
- ``gating``: ``g = sigmoid(x W_g)``, ``W_g [hidden, H_l]``, from the
  layer's normed input; head ``h``'s output times ``g_h``; then ``W_o``.
- FFN. ``dense``: SwiGLU of ``intermediate_size``. ``sparse``: ``s =
  sigmoid(x W_r)`` over all ``router_width`` experts, the top
  ``num_experts_per_tok`` of ``s``, weights ``s_chosen / sum(s_chosen)
  * moe_routed_scaling_factor`` on the experts' OUTPUTS; every token
  goes through EVERY held expert and the result is weighted by whether
  (and how) the token chose it: a dense mask, nothing sorted, nothing
  dropped. ``experts_held`` says which experts are held (the chip's
  share of an expert-parallel layer): what the absent ones would add is
  left out, here as in the program. Plus one shared SwiGLU of
  ``shared_expert_intermediate_size`` for every token, ungated.

What the config does not settle is the configuration file's
``assumed``: the gate is one number a head; the router scores by
sigmoid and renormalises over the chosen, with no selection bias and
no balance loss; no q/k norm; documents packed into a row are not
separated.

**The weights are the configuration's, not the run's.**
:func:`make_params` and :func:`draw` take their key from the
configuration's ``weights_seed`` and IGNORE the seed or key they are
handed (the harness hands them ``--seed``): the router, which decides
how many pairs stay on this chip and so how much work a step is, is
then the same object in every run, and ``--seed`` deals the rows only.

``precision`` (``numerics.py``): ``"float32"`` is the reference;
``"int8_all"`` puts every projection's product on the int8 grid,
forward and backward: the CONTROL. The router stays float32 in every
precision. ``fault`` puts a planted fault in the program's place
(never in a benchmark run): ``drop_half``, ``state_unchanged``, and
this model's own ``no_window`` (the sliding layers attend causally
without their window), ``plain_rope`` (the full layers' YaRN table
replaced by plain rotary at the same theta and lanes, attention factor
1) and ``no_gate`` (the attention output is not gated). The model's own
three are also taken under ``precision`` (``tools/control_train_lm.py``
passes every name it does not know that way).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from reference import numerics
# AdamW written out, the per-leaf norms and samples, and the dotted
# names' nesting are the kimi reference's own (plain, family-free)
from reference.kimi_linear import (
    _adamw, _adamw_in_place, _delta_norms, leaf_norms, leaf_samples, nested,
)
from reference.numerics import (  # noqa: F401 (seed_key, split_seed: re-exported)
    einsum, hashable, matmul, seed_key, split_seed,
)

NEG = -1e30
QUERY_BLOCK = 256
MODEL_FAULTS = ("no_window", "plain_rope", "no_gate")

# AdamW as the configuration states it (optax.adamw's defaults; the
# learning rate is the configuration's ``program.learning_rate``; fit's
# own weight_decay, an L2 term in the loss, is 0).
ADAMW = dict(lr=1e-4, b1=0.9, b2=0.999, eps=1e-8, weight_decay=1e-4)


def settings(cfg: dict) -> dict:
    """The configuration's file as the flat scalars this file reads
    (``numerics.hashable`` keeps scalars only): the published keys
    under their own names, the two ``rope_parameters`` entries
    flattened, the layers held as one string (``f48d``: full attention,
    48 query heads, dense FFN; ``s64s``: sliding, 64, sparse)."""
    if "layers" in cfg:
        return cfg
    n = cfg["num_hidden_layers"]
    layers = ",".join(
        f"{kind[0]}{heads}{mlp[0]}" for kind, heads, mlp in zip(
            cfg["layer_types"][:n], cfg["num_attention_heads_per_layer"][:n],
            cfg["mlp_layer_types"][:n]))
    held = cfg.get("experts_held") or [0, cfg["num_experts"]]
    keep = ("vocab_size", "hidden_size", "intermediate_size",
            "num_key_value_heads", "head_dim", "rms_norm_eps",
            "sliding_window", "num_experts_per_tok", "moe_intermediate_size",
            "shared_expert_intermediate_size", "moe_routed_scaling_factor")
    out = {**{k: cfg[k] for k in keep}, "layers": layers,
           "router_width": cfg.get("router_width", cfg["num_experts"]),
           "experts_first": held[0], "experts_count": held[1],
           "weights_seed": cfg.get("weights_seed", 0)}
    for kind in ("full_attention", "sliding_attention"):
        for k, v in cfg["rope_parameters"][kind].items():
            out[f"{kind[0]}_{k}"] = v
    return out


def layers_of(c: dict) -> list:
    """``[(kind, query heads, ffn)]``: ``("f" | "s", H, "d" | "s")``."""
    return [(s[0], int(s[1:-1]), s[-1]) for s in c["layers"].split(",")]


class Spec(dict):
    """``name -> (shape, init)`` and the seed the weights are drawn
    from, whatever key :func:`draw` is handed."""

    weights_seed = 0


def param_spec(cfg: dict) -> Spec:
    """Flat ``name -> (shape, init)``; names are dotted paths, the
    program's own tree flattened."""
    c = settings(cfg)
    h, v, d = c["hidden_size"], c["vocab_size"], c["head_dim"]
    kv = c["num_key_value_heads"] * d
    ie, held = c["moe_intermediate_size"], c["experts_count"]
    w = "normal:0.02"
    # Token vectors of unit RMS, as a trained model's residual stream
    # has them beside what its layers add: with N(0, 0.02) here every
    # position's state was nine tenths the SAME vector (the attention
    # layers' mean over the context, 0.18 an element against 0.02) and
    # every token chose the same experts (PERF.md, PR 36).
    spec = Spec({"embed": ((v, h), "normal:1.0"),
                 "final_norm": ((h,), "scale:0.05"), "lm_head": ((h, v), w)})
    spec.weights_seed = c["weights_seed"]

    def ffn(p, i):
        spec.update({p + "gate": ((h, i), w), p + "up": ((h, i), w),
                     p + "down": ((i, h), w)})

    for n, (_, nh, mlp) in enumerate(layers_of(c)):
        p = f"layer_{n}."
        spec[p + "attn_norm"] = spec[p + "ffn_norm"] = ((h,), "scale:0.05")
        a = p + "attn."
        spec.update({a + "q": ((h, nh * d), w), a + "k": ((h, kv), w),
                     a + "v": ((h, kv), w), a + "gate": ((h, nh), w),
                     a + "o": ((nh * d, h), w)})
        if mlp == "d":
            ffn(p + "mlp.", c["intermediate_size"])
        else:
            a = p + "moe."
            spec.update({
                # logits of spread ~1 over unit-RMS inputs
                a + "router": ((h, c["router_width"]), w),
                a + "experts.gate": ((held, h, ie), w),
                a + "experts.up": ((held, h, ie), w),
                a + "experts.down": ((held, ie, h), w),
            })
            ffn(a + "shared.", c["shared_expert_intermediate_size"])
    return spec


def draw(spec: Spec, key=None) -> dict:
    """Every weight of ``spec`` from ITS ``weights_seed``; ``key`` is
    what the harness derives from ``--seed`` and is not used (module
    docstring)."""
    return numerics.draw(spec, seed_key(*split_seed(spec.weights_seed)))


@functools.lru_cache(maxsize=8)
def _make_params_fn(cfg_items: tuple):
    spec = param_spec(dict(cfg_items))
    return jax.jit(lambda: draw(spec))


def make_params(seed: int, cfg: dict) -> dict:
    """Every weight, on the device, in one jitted call, from the
    configuration's ``weights_seed`` (``seed``, the run's, is not
    used)."""
    del seed
    return _make_params_fn(hashable(settings(cfg)))()


# ---- the layers -----------------------------------------------------------


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def inv_freq(c: dict, kind: str, fault=None):
    """``(inv_freq [r / 2], r, factor on cos and sin)`` of layer kind
    ``"f"`` or ``"s"``."""
    r = int(c["head_dim"] * c.get(f"{kind}_partial_rotary_factor", 1))
    theta = c[f"{kind}_rope_theta"]
    i = jnp.arange(r // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / r)
    if c.get(f"{kind}_rope_type", "default") == "default" or (
            fault == "plain_rope"):
        return f, r, 1.0
    orig = c[f"{kind}_original_max_position_embeddings"]

    def dim_of(rotations):
        return r * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    lo = max(math.floor(dim_of(c[f"{kind}_beta_fast"])), 0)
    hi = min(math.ceil(dim_of(c[f"{kind}_beta_slow"])), r - 1)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip((i - lo) / (hi - lo), 0.0, 1.0)
    return (f / c[f"{kind}_factor"] * ramp + f * (1 - ramp), r,
            c[f"{kind}_attention_factor"])


def rope(x, c: dict, kind: str, fault=None):
    """``x [B, L, H, d]`` at positions 0 .. L-1."""
    f, r, factor = inv_freq(c, kind, fault)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * f[None, :]
    cos = (jnp.cos(ang) * factor)[None, :, None, :]
    sin = (jnp.sin(ang) * factor)[None, :, None, :]
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(p, x, c, kind, precision="float32", fault=None):
    """One attention layer on its normed input ``x [B, L, hidden]``."""
    b, l, _ = x.shape
    d, kvh = c["head_dim"], c["num_key_value_heads"]
    nh = p["gate"].shape[-1]
    group = nh // kvh
    mm = functools.partial(matmul, precision=precision)
    q = rope(mm(x, p["q"]).reshape(b, l, nh, d), c, kind, fault)
    k = rope(mm(x, p["k"]).reshape(b, l, kvh, d), c, kind, fault)
    v = mm(x, p["v"]).reshape(b, l, kvh, d)
    window = (c["sliding_window"]
              if kind == "s" and fault != "no_window" else None)
    blk = min(QUERY_BLOCK, l)
    pad = -l % blk
    # [blocks, B, blk, KV, group, d]: query head h = kv * group + g
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, (l + pad) // blk, blk, kvh, group, d)

    @jax.checkpoint
    def rows(_, xs):
        qi, start = xs
        s = einsum("bqkgd,blkd->bkgql", qi, k, precision) * d ** -0.5
        dist = (start + jnp.arange(blk))[:, None] - jnp.arange(l)[None, :]
        seen = dist >= 0
        if window is not None:
            seen = seen & (dist < window)
        pr = jax.nn.softmax(jnp.where(seen, s, NEG), axis=-1)
        return None, einsum("bkgql,blkd->bqkgd", pr, v, precision)

    _, ctx = jax.lax.scan(
        rows, None, (jnp.moveaxis(qb, 1, 0), jnp.arange(qb.shape[1]) * blk))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, l + pad, nh, d)[:, :l]
    if fault != "no_gate":
        ctx = ctx * jax.nn.sigmoid(mm(x, p["gate"]))[..., None]
    return mm(ctx.reshape(b, l, nh * d), p["o"])


def _ffn(p, x, precision):
    mm = functools.partial(matmul, precision=precision)
    return mm(jax.nn.silu(mm(x, p["gate"])) * mm(x, p["up"]), p["down"])


def route(p, x, c):
    """``[T, router_width]`` combine weights: ``s_i / sum_chosen *
    moe_routed_scaling_factor`` where expert ``i`` is among the token's
    top ``num_experts_per_tok`` of ``s``, else 0."""
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"], precision=numerics._HI))
    _, idx = jax.lax.top_k(s, c["num_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=s.dtype), axis=1)
    w = s * chosen
    return (w / jnp.sum(w, axis=-1, keepdims=True)
            * c["moe_routed_scaling_factor"])


def moe(p, x, c, precision="float32"):
    """The held experts' part of the layer plus the shared expert, and
    the layer's pairs routed to a held expert. One held expert at a
    time over ALL the tokens."""
    b, l, h = x.shape
    w = route(p, x.reshape(b * l, h), c)
    first, count = c["experts_first"], c["experts_count"]
    here = w[:, first:first + count]

    @jax.checkpoint
    def one(y, e):
        expert, col = e
        return y + col.reshape(b, l, 1) * _ffn(expert, x, precision), None

    y, _ = jax.lax.scan(one, _ffn(p["shared"], x, precision),
                        (p["experts"], here.T))
    return y, jnp.sum(here > 0)


def forward(params: dict, ids, cfg: dict, precision: str = "float32",
            fault=None):
    """``[B, L]`` token ids -> ``[B, L, vocab]`` float32 logits, and
    the pairs routed to a held expert (all expert layers)."""
    c = settings(cfg)
    p = nested(params)
    eps = c["rms_norm_eps"]
    x = p["embed"][ids]
    here = jnp.zeros((), jnp.int32)
    for n, (kind, _, mlp) in enumerate(layers_of(c)):

        @jax.checkpoint
        def block(layer, x, kind=kind, mlp=mlp):
            xn = _rms_norm(x, layer["attn_norm"], eps)
            x = x + attention(layer["attn"], xn, c, kind, precision, fault)
            xn = _rms_norm(x, layer["ffn_norm"], eps)
            if mlp == "d":
                return x + _ffn(layer["mlp"], xn, precision), jnp.int32(0)
            y, pairs = moe(layer["moe"], xn, c, precision)
            return x + y, pairs.astype(jnp.int32)

        x, pairs = block(p[f"layer_{n}"], x)
        here = here + pairs
    logits = matmul(_rms_norm(x, p["final_norm"], eps), p["lm_head"],
                    precision)
    return logits, here


def loss_fn(params, ids, cfg, precision, fault=None):
    """Mean next-token cross-entropy over the targets that are not
    padding (id 0), as ``make_train_step(task="lm")`` has it."""
    logits, here = forward(params, ids, cfg, precision, fault)
    targets = ids[:, 1:]
    keep = (targets != 0).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    ce = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(ce * keep) / jnp.maximum(jnp.sum(keep), 1.0), here


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision", "fault"))
def _loss_and_grad(params, ids, cfg_items, precision, fault):
    (loss, here), g = jax.value_and_grad(loss_fn, has_aux=True)(
        params, ids, dict(cfg_items), precision, fault)
    return loss, here, g


def train_steps(params, batches, cfg, *, precision="float32", fault=None,
                seed=None):
    """Follow ``len(batches)`` AdamW steps from ``params``. Returns
    what ``reference/kimi_linear.py``'s does (``losses``,
    ``grad_norms``, ``grad_sample``, ``delta_norms``, ``pairs_here`` a
    step). With ``seed`` (any: the weights are the
    configuration's) the state is updated in place, ``params`` are
    consumed, the moments wait on the host between two steps, and the
    change is measured against a fresh draw. ``fault`` and the names
    ``precision`` also takes: module docstring."""
    if precision in MODEL_FAULTS:
        fault, precision = precision, "float32"
    if fault not in (None, "drop_half", "state_unchanged", *MODEL_FAULTS):
        raise ValueError(fault)
    lr = cfg.get("program", {}).get("learning_rate", ADAMW["lr"])
    hp = tuple(sorted(dict(ADAMW, lr=lr).items()))
    cfg_items = hashable(settings(cfg))
    in_place = seed is not None
    adamw = _adamw_in_place if in_place else _adamw
    model_fault = fault if fault in MODEL_FAULTS else None
    p, mu, nu = params, None, None
    losses, here, grad_norms, grad_sample = [], [], None, None
    for t, (ids, _) in enumerate(batches, start=1):
        ids = jnp.asarray(ids)
        if fault == "drop_half":
            ids = (ids[:ids.shape[0] // 2] if ids.shape[0] > 1
                   else ids[:, :ids.shape[1] // 2])
        loss, pairs, g = _loss_and_grad(p, ids, cfg_items, precision,
                                        model_fault)
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
