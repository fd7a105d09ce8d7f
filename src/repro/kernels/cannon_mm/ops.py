"""Jit'd public wrapper for the cannon_mm Pallas kernel.

``blocked_matmul`` compiles the Pallas kernel for the TPU; ``interpret=True``
runs the same kernel body through the Pallas interpreter instead, which
validates BlockSpec indexing and numerics on hosts without a chip.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.cannon_mm.kernel import matmul_pallas
from repro.kernels.cannon_mm.ref import matmul_ref


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "out_dtype", "interpret"))
def blocked_matmul(a: jax.Array, b: jax.Array, *, block_m: int = 256,
                   block_n: int = 256, block_k: int = 256,
                   out_dtype: Optional[jnp.dtype] = None,
                   interpret: bool = False) -> jax.Array:
    M, K = a.shape
    _, N = b.shape
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    if (M % bm or N % bn or K % bk):
        # Ragged shapes: oracle path (padding would waste MXU cycles; the
        # framework always feeds aligned shapes).
        return matmul_ref(a, b, out_dtype)
    return matmul_pallas(a, b, block_m=bm, block_n=bn, block_k=bk,
                         out_dtype=out_dtype,
                         interpret=interpret)
