"""Plain reference for the ``gpt2-124m`` configuration.

GPT-2 (Radford et al. 2019; HF ``gpt2`` ``config.json``): learned
positions, pre-norm blocks, ``gelu_new``, weight-tied head. One full
causal forward pass in float32 ``jax.numpy`` at ``Precision.HIGHEST``:
no cache, no kernels, no batching tricks. Imports nothing of
``mlapi_tpu`` and takes nothing it has made: the weights come from
:func:`make_params`, which the harness also writes out as the
checkpoint the server loads.

``precision`` (see ``numerics.py``): ``"float32"`` is the reference;
the configuration states bfloat16 products, so the serving cells'
CONTROL is this file at ``"int8"`` or ``"float8_e4m3fn"``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference import numerics
from reference.numerics import (  # noqa: F401 (draw, seed_key, split_seed: re-exported)
    draw, einsum, hashable, matmul, seed_key, split_seed,
)

NEG = -1e9


def param_spec(cfg: dict) -> dict:
    h = cfg["n_embd"]
    spec = {
        "wte": ((cfg["vocab_size"], h), "normal:0.02"),
        "wpe": ((cfg["n_positions"], h), "normal:0.01"),
        "ln_f_scale": ((h,), "scale:0.05"),
        "ln_f_bias": ((h,), "normal:0.02"),
    }
    for n in range(cfg["n_layer"]):
        p = f"layer_{n}."
        for name, shape in (("qkv", (h, 3 * h)), ("attn_out", (h, h)),
                            ("ffn_up", (h, 4 * h)), ("ffn_down", (4 * h, h))):
            spec[p + name + ".kernel"] = (shape, "normal:0.02")
            spec[p + name + ".bias"] = ((shape[1],), "normal:0.02")
        for ln in ("ln1", "ln2"):
            spec[p + ln + "_scale"] = ((h,), "scale:0.05")
            spec[p + ln + "_bias"] = ((h,), "normal:0.02")
    return spec


def make_params(seed: int, cfg: dict) -> dict:
    """Every weight, on the device, in one jitted call from the seed."""
    return numerics.make_params(param_spec, seed, cfg)


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def forward(params: dict, ids, cfg: dict, precision: str = "float32"):
    """``[B, T]`` token ids -> ``[B, T, vocab]`` float32 logits; row
    ``t`` scores the token that follows position ``t``."""
    nh = cfg["n_head"]
    hd = cfg["n_embd"] // nh
    eps = cfg["layer_norm_epsilon"]
    b, t = ids.shape

    def dense(x, prefix):
        return (matmul(x, params[prefix + ".kernel"], precision)
                + params[prefix + ".bias"])

    causal = jnp.tril(jnp.ones((t, t), bool))
    x = params["wte"][ids] + params["wpe"][jnp.arange(t)][None]
    for n in range(cfg["n_layer"]):
        p = f"layer_{n}."
        xn = _layer_norm(x, params[p + "ln1_scale"], params[p + "ln1_bias"],
                         eps)
        q, k, v = jnp.split(dense(xn, p + "qkv"), 3, axis=-1)
        q, k, v = (a.reshape(b, t, nh, hd) for a in (q, k, v))
        s = einsum("bqhd,bkhd->bhqk", q, k, precision) / (hd ** 0.5)
        s = jnp.where(causal[None, None], s, NEG)
        ctx = einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                     precision)
        x = x + dense(ctx.reshape(b, t, -1), p + "attn_out")
        xn = _layer_norm(x, params[p + "ln2_scale"], params[p + "ln2_bias"],
                         eps)
        up = jax.nn.gelu(dense(xn, p + "ffn_up"), approximate=True)
        x = x + dense(up, p + "ffn_down")
    x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"], eps)
    return matmul(x, params["wte"].T, precision)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _gaps(params, ids, cfg_items, precision):
    """Per position: the reference's best logit minus its logit of the
    token that follows in ``ids`` (``served``), and minus its logit of
    the token that ``precision`` puts first (``control``)."""
    cfg = dict(cfg_items)
    ref = forward(params, ids, cfg, "float32")[:, :-1]
    best = jnp.max(ref, axis=-1)
    nxt = ids[:, 1:]
    served = best - jnp.take_along_axis(ref, nxt[..., None], axis=-1)[..., 0]
    if precision == "float32":
        return served, jnp.zeros_like(served)
    low = jnp.argmax(forward(params, ids, cfg, precision)[:, :-1], axis=-1)
    control = best - jnp.take_along_axis(ref, low[..., None], axis=-1)[..., 0]
    return served, control


def served_gaps(params, rows, cfg, *, pad_to: int, block: int = 8,
                control: str | None = None):
    """``rows``: list of ``(prompt_ids, served_ids)``. Runs the
    reference once over each prompt with its served tokens (right-
    padded to ``pad_to``, in blocks of ``block`` rows) and returns,
    per row, the widest gap by which a served token's logit lies below
    the reference's best — and, with ``control``, the same for the
    tokens that precision would have put first at those positions."""
    import numpy as np

    out_s, out_c = [], []
    cfg_items = hashable(cfg)
    for i in range(0, len(rows), block):
        chunk = rows[i:i + block]
        ids = np.zeros((block, pad_to), np.int32)
        for j, (pr, sv) in enumerate(chunk):
            seq = list(pr) + list(sv)
            if len(seq) > pad_to:
                raise ValueError(f"row of {len(seq)} tokens > pad_to {pad_to}")
            ids[j, :len(seq)] = seq
        s, c = _gaps(params, jnp.asarray(ids), cfg_items,
                     control or "float32")
        s, c = np.asarray(s), np.asarray(c)
        for j, (pr, sv) in enumerate(chunk):
            lo, hi = len(pr) - 1, len(pr) - 1 + len(sv)
            out_s.append(float(s[j, lo:hi].max()) if sv else 0.0)
            out_c.append(float(c[j, lo:hi].max()) if sv else 0.0)
    return out_s, out_c
