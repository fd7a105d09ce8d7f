"""Public SSD scan op: chunked algorithm with two interchangeable backends.

  * ``backend="jnp"`` — pure-jnp chunked SSD (differentiable; used in
    training; identical math to the Pallas kernels, one fused XLA graph).
  * ``backend="pallas"`` — the two Pallas kernels from kernel.py (serving /
    prefill fast path; validated against ref in tests).

Cross-chunk state passing is an affine recurrence s' = d * s + u over tiny
(H, N, P) tensors, run as ``jax.lax.associative_scan`` (log-depth).  The
same affine pair (total decay, contribution) is what models/ssm.py exchanges
across SHMEM grid rows via ppermute when the sequence is sharded.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import ssd_apply_pallas, ssd_chunk_pallas


def _chunk_math_jnp(x, dt, A, Bm, Cm):
    """Per-chunk intra output + state contribution, batched over (B, nc).

    x (B,nc,L,H,P), dt (B,nc,L,H), Bm/Cm (B,nc,L,G,N) ->
    y_intra (B,nc,L,H,P), states (B,nc,H,N,P), cumexp (B,nc,L,H)
    """
    rep = x.shape[3] // Bm.shape[3]
    x32 = x.astype(jnp.float32)
    dt32 = dt.astype(jnp.float32)
    B32, C32 = Bm.astype(jnp.float32), Cm.astype(jnp.float32)
    dtA = dt32 * A.astype(jnp.float32)                    # (B,nc,L,H)
    cum = jnp.cumsum(dtA, axis=2)
    cumexp = jnp.exp(cum)
    scores = jnp.einsum("bctgn,bcsgn->bcgts", C32, B32)
    scores = jnp.repeat(scores, rep, axis=2)              # (B,nc,H,L,L)
    L = x.shape[2]
    causal = jnp.tril(jnp.ones((L, L), jnp.bool_))
    ldecay = cum.transpose(0, 1, 3, 2)[..., :, None] - \
        cum.transpose(0, 1, 3, 2)[..., None, :]           # (B,nc,H,L,L)
    # clamp BEFORE exp: masked (t < s) entries have ldecay > 0 and would
    # overflow to inf, poisoning the where() gradient with 0 * inf = NaN.
    decay = jnp.exp(jnp.where(causal[None, None, None], ldecay, -60.0))
    decay = jnp.where(causal[None, None, None], decay, 0.0)
    w = scores * decay * dt32.transpose(0, 1, 3, 2)[..., None, :]
    y_intra = jnp.einsum("bchts,bcshp->bcthp", w, x32)
    sdecay = jnp.exp(cum[:, :, -1:, :] - cum) * dt32      # (B,nc,L,H)
    b_h = jnp.repeat(B32, rep, axis=3)                    # (B,nc,L,H,N)
    states = jnp.einsum("bclh,bclhn,bclhp->bchnp", sdecay, b_h, x32)
    return y_intra.astype(x.dtype), states, cumexp


def _apply_math_jnp(y_intra, Cm, cumexp, states_in):
    rep = y_intra.shape[3] // Cm.shape[3]
    c_h = jnp.repeat(Cm.astype(jnp.float32), rep, axis=3)
    y_inter = jnp.einsum("bclhn,bchnp->bclhp", c_h, states_in) \
        * cumexp[..., None]
    return (y_intra.astype(jnp.float32) + y_inter).astype(y_intra.dtype)


def _state_passing(states, chunk_decay, init_state):
    """Affine prefix over chunks: in_state[c] = prod-decay * init + sum contrib.

    states (B,nc,H,N,P) fp32, chunk_decay (B,nc,H) fp32.
    Returns (states_in (B,nc,H,N,P), final_state (B,H,N,P)).
    """
    d = chunk_decay[..., None, None]                      # (B,nc,H,1,1)

    def combine(a, b):
        da, sa = a
        db, sb = b
        return da * db, db * sa + sb

    # inclusive scan over chunks of (decay, contribution)
    dacc, sacc = jax.lax.associative_scan(combine, (d, states), axis=1)
    # state entering chunk c is the inclusive result of chunk c-1 applied to init
    init = init_state[:, None].astype(jnp.float32)
    s_after = dacc * init + sacc                          # state AFTER chunk c
    states_in = jnp.concatenate(
        [init.astype(jnp.float32), s_after[:, :-1]], axis=1)
    return states_in, s_after[:, -1]


@functools.partial(jax.jit, static_argnames=("chunk", "backend", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, init_state: Optional[jax.Array] = None, *,
             chunk: int = 128, backend: str = "jnp",
             interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.  Returns (y (B,S,H,P), final_state (B,H,N,P))."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    nc = S // L
    if init_state is None:
        init_state = jnp.zeros((B, H, N, P), jnp.float32)

    if backend == "pallas":
        y_h, states, cumexp = ssd_chunk_pallas(
            x, dt, A, Bm, Cm, chunk=L, interpret=interpret)
        states_in, final = _state_passing(states, cumexp[:, :, :, 0, -1],
                                          init_state)
        return ssd_apply_pallas(y_h, Cm, cumexp, states_in,
                                interpret=interpret), final
    xr = x.reshape(B, nc, L, H, P)
    dtr = dt.reshape(B, nc, L, H)
    br = Bm.reshape(B, nc, L, G, N)
    cr = Cm.reshape(B, nc, L, G, N)
    y_intra, states, cumexp = _chunk_math_jnp(xr, dtr, A, br, cr)
    states_in, final = _state_passing(states, cumexp[:, :, -1, :], init_state)
    y = _apply_math_jnp(y_intra, cr, cumexp, states_in)
    return y.reshape(B, S, H, P), final


def ssd_decode_step(x, dt, A, Bm, Cm, state):
    """Single-token recurrence (serve decode).  x (B,H,P), dt (B,H),
    Bm/Cm (B,G,N), state (B,H,N,P) -> (y (B,H,P), new state)."""
    rep = x.shape[1] // Bm.shape[1]
    dA = jnp.exp(dt.astype(jnp.float32) * A.astype(jnp.float32))   # (B,H)
    b_h = jnp.repeat(Bm.astype(jnp.float32), rep, axis=1)          # (B,H,N)
    c_h = jnp.repeat(Cm.astype(jnp.float32), rep, axis=1)
    state = (dA[:, :, None, None] * state
             + (dt.astype(jnp.float32)[:, :, None] * b_h)[..., None]
             * x.astype(jnp.float32)[:, :, None, :])
    y = jnp.einsum("bhn,bhnp->bhp", c_h, state)
    return y.astype(x.dtype), state
