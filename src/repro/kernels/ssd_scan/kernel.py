"""Pallas TPU kernels for the Mamba2 SSD chunked scan.

The SSD ("state-space dual") form splits the sequence into chunks of length L
and computes, per chunk, (a) the intra-chunk output via an attention-like
masked matmul and (b) the chunk's contribution to the running state — both
dense MXU work over VMEM-resident tiles.  The only sequential dependence left
is a tiny per-chunk affine recurrence over (H, N, P) states, which ops.py
runs as an associative scan (and, across SHMEM grid rows, as a ppermute
affine exchange — see models/ssm.py).

Two kernels:
  pass 1 ``_chunk_kernel``: x,dt,B,C -> y_intra, chunk_state, cumexp
  pass 2 ``_apply_kernel``: y_intra, C, cumexp, state_in -> y

Grid: (batch, n_chunks, heads); each grid cell owns one head of one chunk,
so every tile is 2-D — ``(L, P)`` inputs, ``(L, N)`` B/C of the head's
group, ``(1, L)`` step rows — which is what Mosaic lowers.  The wrappers
move heads ahead of the chunk positions; ``ssd_chunk_pallas`` returns that
head-major layout and ``ssd_apply_pallas`` takes it back.

Within-chunk cumulative decays are masked sums in log space (a lower-
triangular reduction, not ``cumsum``, which Mosaic cannot lower); all decay
exponents are <= 0 by construction (A < 0, dt > 0), so exp() never
overflows.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32


def _col(row, eye):
    """(1, L) row -> (L, 1) column through the identity mask (exact)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _chunk_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
                  y_ref, state_ref, cumexp_ref):
    x = x_ref[0, 0, 0].astype(_F32)           # (L, P)
    dt = dt_ref[0, 0, 0].astype(_F32)         # (1, L)
    a = a_ref[0].astype(_F32)                 # (1, 1)
    Bm = b_ref[0, 0, 0].astype(_F32)          # (L, N) this head's group
    Cm = c_ref[0, 0, 0].astype(_F32)          # (L, N)
    L = x.shape[0]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = t_idx >= s_idx
    eye = t_idx == s_idx

    dtA = dt * a                              # (1, L), <= 0
    dtA_col = _col(dtA, eye)                  # (L, 1)
    # inclusive prefix sums of dtA, as a column (indexed t) and a row
    # (indexed s): masked reductions over the lower triangle
    cum_col = jnp.sum(jnp.where(causal, dtA, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(t_idx <= s_idx, dtA_col, 0.0), axis=0,
                      keepdims=True)
    cumexp_ref[0, 0, 0] = jnp.exp(cum_row)

    # Intra-chunk: y[t] = sum_{s<=t} (C_t . B_s) * exp(cum_t - cum_s) * dt_s * x_s
    # decay[t, s] = exp(cum[t] - cum[s]) for t >= s else 0; clamp masked
    # entries before exp (they are positive and would overflow).
    ldecay = cum_col - cum_row                # (L, L)
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, ldecay, -60.0)), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=_F32)   # (L, L)
    w = scores * decay * dt
    y_ref[0, 0, 0] = jnp.dot(w, x, preferred_element_type=_F32
                             ).astype(y_ref.dtype)

    # Chunk state: state[n, p] = sum_s exp(cum_last - cum_s) * dt_s * B_s[n] * x_s[p]
    cum_last = jnp.sum(dtA, axis=1, keepdims=True)              # (1, 1)
    sdecay = jnp.exp(cum_last - cum_col) * _col(dt, eye)        # (L, 1)
    bw = (Bm * sdecay).T                                        # (N, L)
    state_ref[0, 0, 0] = jnp.dot(bw, x, preferred_element_type=_F32
                                 ).astype(state_ref.dtype)


def _apply_kernel(y_ref, c_ref, cumexp_ref, sin_ref, o_ref):
    y = y_ref[0, 0, 0].astype(_F32)           # (L, P)
    Cm = c_ref[0, 0, 0].astype(_F32)          # (L, N)
    ce = cumexp_ref[0, 0, 0].astype(_F32)     # (1, L)
    sin = sin_ref[0, 0, 0].astype(_F32)       # (N, P)
    L = y.shape[0]
    eye = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) == \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    y_inter = jnp.dot(Cm, sin, preferred_element_type=_F32) * _col(ce, eye)
    o_ref[0, 0, 0] = (y + y_inter).astype(o_ref.dtype)


def _groups_major(m, nc, L):
    """(B, S, G, N) -> (B, nc, G, L, N)."""
    B, _, G, N = m.shape
    return m.reshape(B, nc, L, G, N).transpose(0, 1, 3, 2, 4)


def ssd_chunk_pallas(x, dt, A, Bm, Cm, *, chunk: int, interpret: bool = False
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pass 1 over all chunks.  x (B,S,H,P) -> head-major
    (y_intra (B,nc,H,L,P), chunk_states (B,nc,H,N,P), cumexp (B,nc,H,1,L))."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    L = chunk
    assert S % L == 0
    nc = S // L
    rep = H // G
    xh = x.reshape(B, nc, L, H, P).transpose(0, 1, 3, 2, 4)
    dth = dt.reshape(B, nc, L, H).transpose(0, 1, 3, 2)[:, :, :, None, :]
    a3 = A.astype(_F32).reshape(H, 1, 1)

    return pl.pallas_call(
        _chunk_kernel,
        grid=(B, nc, H),
        in_specs=[
            pl.BlockSpec((1, 1, 1, L, P), lambda b, c, h: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, L), lambda b, c, h: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, c, h: (h, 0, 0)),
            # head h reads its group's B/C: group = h // (H / G)
            pl.BlockSpec((1, 1, 1, L, N),
                         lambda b, c, h: (b, c, h // rep, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, N),
                         lambda b, c, h: (b, c, h // rep, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, L, P), lambda b, c, h: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, N, P), lambda b, c, h: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, L), lambda b, c, h: (b, c, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nc, H, L, P), x.dtype),
            jax.ShapeDtypeStruct((B, nc, H, N, P), _F32),
            jax.ShapeDtypeStruct((B, nc, H, 1, L), _F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(xh, dth, a3, _groups_major(Bm, nc, L), _groups_major(Cm, nc, L))


def ssd_apply_pallas(y_intra, Cm, cumexp, states_in, *, interpret: bool = False
                     ) -> jax.Array:
    """Pass 2: add each chunk's contribution from the incoming state.
    Head-major y_intra (B,nc,H,L,P) / cumexp (B,nc,H,1,L) and Cm (B,S,G,N)
    -> y (B, S, H, P)."""
    B, nc, H, L, P = y_intra.shape
    G, N = Cm.shape[2], Cm.shape[3]
    rep = H // G
    y = pl.pallas_call(
        _apply_kernel,
        grid=(B, nc, H),
        in_specs=[
            pl.BlockSpec((1, 1, 1, L, P), lambda b, c, h: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, N),
                         lambda b, c, h: (b, c, h // rep, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, L), lambda b, c, h: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, N, P), lambda b, c, h: (b, c, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, L, P),
                               lambda b, c, h: (b, c, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nc, H, L, P), y_intra.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(y_intra, _groups_major(Cm, nc, L), cumexp, states_in)
    return y.transpose(0, 1, 3, 2, 4).reshape(B, nc * L, H, P)
