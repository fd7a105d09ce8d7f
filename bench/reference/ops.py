"""Numerics shared by the plain references: float32 matmuls at the highest
precision, and the float8 control that stands in for them.

``mm(x, w, mode)`` is every weight matmul of a reference.  ``mode="f32"``
is the reference proper.  ``mode="fp8"`` is the control: both operands are
rounded to float8 e4m3 first (the weight per tensor, the activation per
row, each scaled so that its largest magnitude lands at the format's
largest finite value), then multiplied in float32.  Everything else stays
float32 in both modes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fp8(a, axis):
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (a / scale).astype(F8).astype(jnp.float32) * scale


def mm(x, w, mode: str = "f32"):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if mode == "fp8":
        x = _fp8(x, axis=-1)
        w = _fp8(w, axis=None)
    elif mode != "f32":
        raise ValueError(f"unknown precision mode {mode!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * inv * scale.astype(jnp.float32)


def logits_rows(hidden, embed, mode: str = "f32"):
    """Tied output head: ``hidden (R, D) @ embed (V, D)^T``."""
    return mm(hidden, embed.T, mode)
