"""Pallas TPU kernel: VMEM-blocked matmul — the intra-chip Cannon analogue.

The paper's mechanism on Epiphany is *data reuse in core-local memory*: read a
block from slow global memory once, keep it in the 32 KB scratchpad, and let
it serve many FLOPs.  Inside one TPU chip the identical hierarchy exists
(HBM 819 GB/s -> VMEM ~20 TB/s -> MXU), and the identical remedy applies:
this kernel stages (bm, bk)/(bk, bn) operand tiles into VMEM via BlockSpecs
and accumulates C tiles in fp32 VMEM scratch across the K sweep, so every
HBM byte is reused bm (resp. bn) times — versus a naive streaming matmul
whose operands are re-fetched from HBM for every output tile.

Grid layout: (nm, nn, nk) with K innermost and marked "arbitrary" so the
accumulator tile stays resident while K blocks stream through — the VMEM
residency plays the role of the Epiphany core hoarding its submatrix between
NoC shifts.

MXU alignment: block shapes default to multiples of 128 in both matmul dims
(the systolic array is 128x128); bf16 inputs hit the native MXU path with
fp32 accumulation via ``preferred_element_type``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int, out_dtype):
    """One (i, j, k) grid step: acc[i,j] += A[i,k] @ B[k,j]."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def matmul_pallas(
    a: jax.Array,                      # (M, K)
    b: jax.Array,                      # (K, N)
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 256,
    out_dtype: Optional[jnp.dtype] = None,
    interpret: bool = False,
) -> jax.Array:
    """C = A @ B with explicit VMEM tiling.

    VMEM working set = bm*bk + bk*bn (operands, input dtype) + bm*bn*4
    (fp32 accumulator); defaults (256,256,256) give 0.5 MB of operands in
    bf16 + 0.25 MB accumulator — comfortably double-bufferable within the
    ~16 MB/core VMEM budget of a v5e, with all dims MXU-aligned.
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (
        f"shape ({M},{K})x({K},{N}) not divisible by blocks ({bm},{bn},{bk})")
    nm, nn, nk = M // bm, N // bn, K // bk

    kernel = functools.partial(_matmul_kernel, nk=nk, out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="cannon_mm",
    )(a, b)
