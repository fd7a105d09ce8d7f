"""Pallas TPU kernel: streaming-softmax (flash) attention with GQA + causal
masking + query-offset for context-parallel blocks.

Same VMEM-reuse principle as cannon_mm applied to attention: K/V tiles are
streamed HBM->VMEM once per query block while the running (max, denom, acc)
statistics stay resident in VMEM scratch, so the S^2 score matrix never
touches HBM.  ``q_offset`` is the global position of this shard's first query
row — the SHMEM grid shards the sequence over grid rows (mx), and each PE
runs this kernel on its local query block against gathered K/V, with causal
masking computed in *global* coordinates.

Grid: (batch, q_heads, nq, nkv), kv innermost ("arbitrary").  Causal blocks
strictly above the diagonal are skipped via ``pl.when`` (no MXU work, no
VMEM traffic beyond the prefetch).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  nkv: int, bq: int, bk: int, q_offset: int, causal: bool,
                  scale: float, out_dtype):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    iq = pl.program_id(2)
    # Skip kv blocks fully in the causal future of every query in this block.
    # Last (global) query position in the block:
    last_q = q_offset + (iq + 1) * bq - 1
    first_kv = ik * bk
    visible = (last_q >= first_kv) if causal else True

    @pl.when(visible)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_offset + iq * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            kv_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= kv_pos, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                        # masked -> exp(-big)=0
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nkv - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(out_dtype)


def flash_attention_pallas(
    q: jax.Array,            # (B, Hq, Sq, D)
    k: jax.Array,            # (B, Hkv, Skv, D)
    v: jax.Array,            # (B, Hkv, Skv, D)
    *,
    causal: bool = True,
    q_offset: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    bq, bk = min(block_q, Sq), min(block_kv, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, Skv, bq, bk)
    nq, nkv = Sq // bq, Skv // bk
    scale = scale if scale is not None else D ** -0.5

    kernel = functools.partial(
        _flash_kernel, nkv=nkv, bq=bq, bk=bk, q_offset=q_offset,
        causal=causal, scale=scale, out_dtype=q.dtype)
    return pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret, name="flash_attention",
    )(q, k, v)
