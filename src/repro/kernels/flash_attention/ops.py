"""Jit'd public wrapper for the flash_attention Pallas kernel (compiled for
the TPU; ``interpret=True`` runs it through the Pallas interpreter)."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(jax.jit, static_argnames=(
    "causal", "q_offset", "block_q", "block_kv", "scale", "interpret",
    "force_ref"))
def flash_attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
                    block_q: int = 128, block_kv: int = 128,
                    scale: Optional[float] = None,
                    interpret: bool = False,
                    force_ref: bool = False) -> jax.Array:
    Sq, Skv = q.shape[2], k.shape[2]
    bq, bk = min(block_q, Sq), min(block_kv, Skv)
    if force_ref or Sq % bq or Skv % bk:
        return attention_ref(q, k, v, causal=causal, q_offset=q_offset,
                             scale=scale)
    return flash_attention_pallas(
        q, k, v, causal=causal, q_offset=q_offset, block_q=bq, block_kv=bk,
        scale=scale, interpret=interpret)
