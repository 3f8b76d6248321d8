"""Matrix products of the plain references, in the precision asked for.

``float32`` is the reference proper: float32 inputs, float32
accumulation, ``Precision.HIGHEST`` (on a TPU a float32 product
otherwise runs as one bfloat16 pass). The others exist for the
CONTROLS of "How correct is decided": the same mathematics one step
of precision down, which the comparison has to tell from the program.

- ``bfloat16``: inputs rounded to bfloat16, float32 accumulation.
- ``int8``: W8A8 as deployed: activations scaled per row and weights
  per output column to the int8 range, rounded, multiplied exactly,
  rescaled (straight-through gradient, so training can follow it).
- ``int8_all``: the same W8A8 grid in EVERY product of a training
  step: the two backward products (``dy @ w.T``, ``x.T @ dy``) take
  their inputs on the int8 grid as well, each scaled along the
  dimension that is not summed over, and the integers are multiplied
  exactly (float32 accumulation) and rescaled.
- ``float8_e4m3fn``: both inputs scaled per tensor to the e4m3 range
  and rounded to it, and the product handed on in e4m3 as well, as a
  deployment that keeps its activations in fp8 does (straight-through
  gradient). Rounding the inputs alone is no step down from the
  programs measured here: they round every activation between two
  products to bfloat16, and read as far from float32 as fp8 inputs
  with float32 activations do (PERF.md, PR 26).

Imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "int8", "int8_all", "float8_e4m3fn")
_HI = jax.lax.Precision.HIGHEST


def _ste(x, q):
    return x + jax.lax.stop_gradient(q - x)


def _int8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return _ste(x, jnp.clip(jnp.round(x / scale), -127, 127) * scale)


def _q8(x, axis):
    """``x`` on the int8 grid along ``axis``: whole numbers in
    [-127, 127] (exact in bfloat16) and the scale they stand for."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127), scale


def _dot8(a, sa, b, sb):
    y = jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    return y * sa * sb


@jax.custom_vjp
def _matmul_int8_all(x, w):
    """``x [M, K] @ w [K, N]`` with forward and backward on int8."""
    return _dot8(*_q8(x, 1), *_q8(w, 0))


def _int8_all_fwd(x, w):
    return _matmul_int8_all(x, w), (x, w)


def _int8_all_bwd(res, dy):
    x, w = res
    qd, sd = _q8(dy, 1)            # dx = dy @ w.T sums over N
    qw, sw = _q8(w, 1)
    dx = _dot8(qd, sd, qw.T, sw.T)
    qx, sx = _q8(x, 0)             # dw = x.T @ dy sums over M
    qd, sd = _q8(dy, 0)
    dw = _dot8(qx.T, sx.T, qd, sd)
    return dx, dw


_matmul_int8_all.defvjp(_int8_all_fwd, _int8_all_bwd)


def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return _ste(x, q)


def matmul(x, w, precision: str = "float32"):
    """``x [..., K] @ w [K, N]`` -> float32."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "float32":
        return jnp.matmul(x, w, precision=_HI)
    if precision == "int8_all":
        y = _matmul_int8_all(x.reshape(-1, x.shape[-1]), w)
        return y.reshape(*x.shape[:-1], w.shape[1])
    if precision == "int8":
        x, w = _int8(x, -1), _int8(w, 0)
    elif precision == "float8_e4m3fn":
        x, w = _fp8(x), _fp8(w)
    elif precision != "bfloat16":
        raise ValueError(f"unknown precision {precision!r}")
    y = jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    return _fp8(y) if precision == "float8_e4m3fn" else y


def einsum(spec: str, a, b, precision: str = "float32"):
    """Attention's two products: float32/HIGHEST in the reference,
    bfloat16 inputs in every control (a W8A8 or fp8 deployment keeps
    attention in bfloat16)."""
    if precision == "float32":
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=_HI)
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def sample(a, n: int = 4096):
    """At most ``n`` elements of ``a``, at places fixed by its size
    alone (a multiplicative hash of 0..n-1, so neither rows nor
    columns repeat in step): what the program's first gradient and
    the reference's are compared on, element by element, without
    moving a whole tree off the device."""
    size = int(np.prod(a.shape))
    if size <= n:
        return a.reshape(-1)
    idx = (np.arange(n, dtype=np.uint64) * np.uint64(2654435761)) % np.uint64(size)
    return a.reshape(-1)[idx.astype(np.int32)]


def split_seed(seed: int) -> tuple[int, int]:
    """A seed of up to 62 bits as two non-negative int32."""
    return int(seed) & 0x7FFFFFFF, (int(seed) >> 31) & 0x7FFFFFFF


def seed_key(seed_lo, seed_hi):
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.key(0), seed_lo), seed_hi)


def draw(spec: dict, key, dtype=jnp.float32) -> dict:
    """One array per ``name -> (shape, init)`` entry of ``spec``;
    ``init`` is ``normal:<std>`` or ``scale:<std>`` (1 + std * z)."""
    out = {}
    for n, (name, (shape, init)) in enumerate(sorted(spec.items())):
        kind, _, std = init.partition(":")
        z = float(std) * jax.random.normal(
            jax.random.fold_in(key, n), shape, jnp.float32)
        if kind == "scale":
            z = 1.0 + z
        elif kind != "normal":
            raise ValueError(init)
        out[name] = z.astype(dtype)
    return out


@functools.lru_cache(maxsize=8)
def _make_params_fn(param_spec, cfg_items: tuple):
    spec = param_spec(dict(cfg_items))
    return jax.jit(lambda lo, hi: draw(spec, seed_key(lo, hi)))


def make_params(param_spec, seed: int, cfg: dict) -> dict:
    """Every weight of ``param_spec(cfg)``, on the device, in one
    jitted call from the seed."""
    lo, hi = split_seed(seed)
    return _make_params_fn(param_spec, hashable(cfg))(
        jnp.int32(lo), jnp.int32(hi))


def hashable(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))
