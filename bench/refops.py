"""Plain jax.numpy building blocks of the reference models.

Nothing here imports the program.  Every matrix product goes through
``mm`` (or ``einsum``), which computes in float32 at HIGHEST precision,
or, for the control, rounds both operands to float8 (e4m3, one scale per
tensor) first: the nearest precision below the bfloat16 that the
configurations state for their activations.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "fp8")
_HI = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0


def qdq_fp8(x):
    """Round to float8 e4m3 with one scale per tensor, back to float32."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _operands(precision: str, *xs):
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    xs = [x.astype(jnp.float32) for x in xs]
    return [qdq_fp8(x) for x in xs] if precision == "fp8" else xs


def mm(a, b, precision: str):
    a, b = _operands(precision, a, b)
    return jnp.matmul(a, b, precision=_HI)


def einsum(spec: str, a, b, precision: str):
    a, b = _operands(precision, a, b)
    return jnp.einsum(spec, a, b, precision=_HI)


def layer_norm(x, scale, bias, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def rope(x, positions, theta: float):
    """Rotary embedding, halves convention.  x: (b, t, h, hd)."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, :, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, *, causal: bool, precision: str):
    """q: (b, t, h, hd); k, v: (b, t, kv, hd), h a multiple of kv."""
    b, t, h, hd = q.shape
    groups = h // k.shape[2]
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)
    s = einsum("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(hd)
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return einsum("bhqk,bkhd->bqhd", p, v, precision)


def normal(key, shape, std: float):
    return jax.random.normal(key, shape, jnp.float32) * std


def xent(logits, labels):
    """Mean next-token cross-entropy over every position."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
