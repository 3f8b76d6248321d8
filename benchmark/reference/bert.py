"""Plain reference for the ``bert-base-sst2`` configuration.

BERT encoder (Devlin et al. 2018; HF ``bert-base-uncased``) with the
tanh-pooled [CLS] head of ``BertForSequenceClassification``: forward,
mean softmax cross-entropy, gradients (``jax.grad`` of this file's own
forward) and an AdamW step written out here. float32 ``jax.numpy``
at ``Precision.HIGHEST``, no kernels, no
mesh, no optimizer library. Imports nothing of ``mlapi_tpu`` and takes
nothing it has made: the weights come from :func:`make_params`, which
the harness also hands to the program as its initial weights.

Departures from the published model, each shared with the program's
configuration: no dropout (the preset trains without it); token type
ids are all 0; the attention mask is ``ids != 0``.

``precision`` (see ``numerics.py``) is ``"float32"`` for the
reference. The configuration states bfloat16 matrix products over
float32 master weights and optimizer state, so the CONTROL of the
training cell is this file one step down from the products:
``train_steps(..., precision="int8_all")`` (every product of the step
on the int8 grid) and ``"int8"`` (the forward products only). Norms
and losses do not tell 8-bit products from the program (rounding
noise hardly moves a norm); the first gradient's direction does
(``grad_dir_gap_median``, PERF.md, PR 26).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference import numerics
from reference.numerics import (  # noqa: F401 (draw, seed_key, split_seed: re-exported)
    draw, einsum, hashable, matmul, seed_key, split_seed,
)

LN_EPS = 1e-12
NEG = -1e9

# AdamW as the configuration states it (optax.adamw defaults at
# learning rate 2e-5): decay on every leaf, bias-corrected moments.
ADAMW = dict(lr=2e-5, b1=0.9, b2=0.999, eps=1e-8, weight_decay=1e-4)


def param_spec(cfg: dict) -> dict:
    """Flat ``name -> (shape, init)``; names are dotted paths."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    spec = {
        "embeddings.word": ((cfg["vocab_size"], h), "normal:0.02"),
        "embeddings.position": ((cfg["max_position_embeddings"], h), "normal:0.02"),
        "embeddings.token_type": ((cfg["type_vocab_size"], h), "normal:0.02"),
        "embeddings.ln_scale": ((h,), "scale:0.05"),
        "embeddings.ln_bias": ((h,), "normal:0.02"),
        "pooler.kernel": ((h, h), "normal:0.02"),
        "pooler.bias": ((h,), "normal:0.02"),
        "classifier.kernel": ((h, cfg["num_labels"]), "normal:0.02"),
        "classifier.bias": ((cfg["num_labels"],), "normal:0.02"),
    }
    for n in range(cfg["num_hidden_layers"]):
        p = f"layer_{n}."
        for name, shape in (("q", (h, h)), ("k", (h, h)), ("v", (h, h)),
                            ("attn_out", (h, h)), ("ffn_up", (h, i)),
                            ("ffn_down", (i, h))):
            spec[p + name + ".kernel"] = (shape, "normal:0.02")
            spec[p + name + ".bias"] = ((shape[1],), "normal:0.02")
        for ln in ("ln1", "ln2"):
            spec[p + ln + "_scale"] = ((h,), "scale:0.05")
            spec[p + ln + "_bias"] = ((h,), "normal:0.02")
    return spec


def make_params(seed: int, cfg: dict) -> dict:
    """Every weight, on the device, in one jitted call from the seed."""
    return numerics.make_params(param_spec, seed, cfg)


def _layer_norm(x, scale, bias):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * scale + bias


def forward(params: dict, ids, cfg: dict, precision: str = "float32"):
    """``[B, L]`` token ids -> ``[B, num_labels]`` float32 logits."""
    nh = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // nh
    b, l = ids.shape

    def dense(x, prefix):
        return (matmul(x, params[prefix + ".kernel"], precision)
                + params[prefix + ".bias"])

    mask = ids != 0
    x = (params["embeddings.word"][ids]
         + params["embeddings.position"][jnp.arange(l)][None]
         + params["embeddings.token_type"][0][None, None])
    x = _layer_norm(x, params["embeddings.ln_scale"],
                    params["embeddings.ln_bias"])
    for n in range(cfg["num_hidden_layers"]):
        p = f"layer_{n}."
        q = dense(x, p + "q").reshape(b, l, nh, hd)
        k = dense(x, p + "k").reshape(b, l, nh, hd)
        v = dense(x, p + "v").reshape(b, l, nh, hd)
        s = einsum("bqhd,bkhd->bhqk", q, k, precision) / (hd ** 0.5)
        s = jnp.where(mask[:, None, None, :], s, NEG)
        pr = jax.nn.softmax(s, axis=-1)
        ctx = einsum("bhqk,bkhd->bqhd", pr, v, precision)
        attn = dense(ctx.reshape(b, l, -1), p + "attn_out")
        x = _layer_norm(x + attn, params[p + "ln1_scale"],
                        params[p + "ln1_bias"])
        up = jax.nn.gelu(dense(x, p + "ffn_up"), approximate=False)
        x = _layer_norm(x + dense(up, p + "ffn_down"),
                        params[p + "ln2_scale"], params[p + "ln2_bias"])
    pooled = jnp.tanh(dense(x[:, 0, :], "pooler"))
    return dense(pooled, "classifier")


def loss_sum(params, ids, labels, cfg, precision):
    logits = forward(params, ids, cfg, precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=1))


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision",
                                             "block"))
def _loss_and_grad(params, ids, labels, cfg_items, precision, block):
    """Mean loss and its gradient over the batch, in blocks of rows so
    that float32 activations fit on one chip."""
    cfg = dict(cfg_items)
    n = ids.shape[0]
    g0 = jax.tree.map(jnp.zeros_like, params)

    def body(carry, xs):
        tot, g = carry
        v, gi = jax.value_and_grad(loss_sum)(params, *xs, cfg, precision)
        return (tot + v, jax.tree.map(jnp.add, g, gi)), None

    (tot, g), _ = jax.lax.scan(
        body, (jnp.float32(0), g0),
        (ids.reshape(n // block, block, -1),
         labels.reshape(n // block, block)))
    return tot / n, jax.tree.map(lambda a: a / n, g)


@functools.partial(jax.jit, static_argnames=("hp_items",))
def _adamw(params, mu, nu, grads, t, hp_items):
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


@jax.jit
def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def leaf_samples(tree: dict) -> dict:
    """``numerics.sample`` of every leaf (float32)."""
    return {k: numerics.sample(v.astype(jnp.float32))
            for k, v in tree.items()}


@jax.jit
def _delta_norms(p, p0):
    return {k: jnp.sqrt(jnp.sum(jnp.square(p[k] - p0[k]))) for k in p}


def train_steps(params, batches, cfg, *, precision="float32", block=32,
                hp=None, fault=None):
    """Follow ``len(batches)`` AdamW steps from ``params``.

    Returns ``losses`` (one per step), ``grad_norms`` (per leaf, first
    step, as the optimizer gets it), ``grad_sample`` (per leaf, the
    elements of that gradient at ``numerics.sample``'s places, numpy)
    and ``delta_norms`` (per leaf, the norm of the parameters' change
    after the last step).

    ``fault`` plants one of the faults of "How correct is decided" in
    the reference put in the program's place, to read what it does to
    the numbers (never in a benchmark run): ``"drop_half"`` leaves
    half of each batch out and takes the mean over the rest,
    ``"quarter_batch"`` keeps the first quarter of each batch (one
    replica's shard of a four-way data-parallel step whose gradient is
    not all-reduced); ``"state_unchanged"`` returns the state as it
    came."""
    hp = tuple(sorted(dict(ADAMW if hp is None else hp).items()))
    cfg_items = hashable(cfg)
    p = params
    mu = jax.tree.map(jnp.zeros_like, p)
    nu = jax.tree.map(jnp.zeros_like, p)
    losses, grad_norms, grad_sample = [], None, None
    for t, (ids, labels) in enumerate(batches, start=1):
        ids, labels = jnp.asarray(ids), jnp.asarray(labels)
        if fault in ("drop_half", "quarter_batch"):
            keep = ids.shape[0] // (2 if fault == "drop_half" else 4)
            ids, labels = ids[:keep], labels[:keep]
        elif fault not in (None, "state_unchanged"):
            raise ValueError(fault)
        blk = min(block, ids.shape[0])
        loss, g = _loss_and_grad(p, ids, labels, cfg_items, precision, blk)
        if grad_norms is None:
            grad_norms = leaf_norms(g)
            grad_sample = jax.device_get(leaf_samples(g))
        if fault != "state_unchanged":
            p, mu, nu = _adamw(p, mu, nu, g, jnp.float32(t), hp)
        losses.append(loss)
    delta = _delta_norms(p, params)
    return {
        "losses": [float(x) for x in losses],
        "grad_norms": {k: float(v) for k, v in grad_norms.items()},
        "grad_sample": grad_sample,
        "delta_norms": {k: float(v) for k, v in delta.items()},
    }
