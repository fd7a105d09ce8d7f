"""Mamba2 (SSD) block on the SHMEM grid.

Sequence is sharded over grid rows, channels/heads over grid cols.  Two
communication patterns, both pure SHMEM neighbor/collective exchanges:

  * conv halo — the depthwise causal conv needs (k-1) trailing timesteps of
    the previous row's shard: one ``shmem_put`` down-row (ppermute), masked
    to zeros on row 0.
  * state relay — the SSD recurrence across row shards is affine in the
    state: each row publishes (total_decay, contribution); rows fcollect the
    q summaries and locally prefix-compose what entered their shard, then
    add the correction term C_t * exp(cumdecay_t) * state_in.  Exact (the
    recurrence is linear), no serialization across rows.

Head/channel alignment: col j owns heads [j*H/r, (j+1)*H/r) and the matching
d_inner slice; B/C (tiny, G groups * N states) are col-gathered to full width
after the conv since every head needs its group's full state vector.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import resolve_kernel_backend
from repro.kernels.ssd_scan import ssd_decode_step, ssd_scan
from repro.models.layers import (ParallelContext, col_slice, dense,
                                 fused_dense, rms_norm)


def _ssd_backend_kwargs(cfg, backend: Optional[str]) -> Dict:
    """Resolve the threaded kernel-backend name (defaulted from
    ``cfg.ssd_backend``) into ``ssd_scan``'s (backend, interpret) pair."""
    use_pallas, interpret = resolve_kernel_backend(
        backend if backend is not None else cfg.ssd_backend)
    return {"backend": "pallas" if use_pallas else "jnp",
            "interpret": interpret}


def _conv_param_slice(pctx: ParallelContext, w: jax.Array, di: int, gn: int,
                      r: int) -> jax.Array:
    """Slice conv weights/bias to this col's LOCAL channel order.

    The local conv input is [xc_j | B_j | C_j] (one col block per segment),
    while the global channel order is [all xc | all B | all C]; a plain
    contiguous col_slice would mix segments.  w: (..., di + 2*gn) global.
    """
    _, j = pctx.grid.my_coords()
    di_loc, gn_loc = di // r, gn // r
    xs = lax.dynamic_slice_in_dim(w[..., :di], j * di_loc, di_loc, axis=-1)
    bs = lax.dynamic_slice_in_dim(w[..., di:di + gn], j * gn_loc, gn_loc,
                                  axis=-1)
    cs = lax.dynamic_slice_in_dim(w[..., di + gn:], j * gn_loc, gn_loc,
                                  axis=-1)
    return jnp.concatenate([xs, bs, cs], axis=-1)


def _slice_groups(bc: jax.Array, G: int, r: int, j: jax.Array, axis: int
                  ) -> jax.Array:
    """Select the B/C group slice covering this col's heads.

    G >= r: col j owns G/r whole groups.  G < r (requires r % G == 0): the
    r/G consecutive cols sharing a group each take that single group.
    """
    if G >= r:
        assert G % r == 0, (G, r)
        gpc = G // r
        return lax.dynamic_slice_in_dim(bc, j * gpc, gpc, axis=axis)
    assert r % G == 0, (G, r)
    return lax.dynamic_slice_in_dim(bc, j // (r // G), 1, axis=axis)


def _conv1d_causal(x: jax.Array, w: jax.Array, b: Optional[jax.Array],
                   halo: jax.Array) -> jax.Array:
    """Depthwise causal conv.  x (B, S, C), halo (B, k-1, C), w (k, C)."""
    k = w.shape[0]
    xp = jnp.concatenate([halo, x], axis=1)                  # (B, S+k-1, C)
    out = sum(xp[:, i:i + x.shape[1]] * w[i][None, None] for i in range(k))
    if b is not None:
        out = out + b[None, None]
    return out.astype(x.dtype)


def mamba_block(pctx: ParallelContext, p: Dict, x: jax.Array, cfg,
                backend: Optional[str] = None) -> Tuple[jax.Array, Tuple]:
    """x (B, S_loc, D_loc) -> (y (B, S_loc, D_loc), (conv_state, ssm_state)).
    ``backend`` selects the SSD scan kernel (default: ``cfg.ssd_backend``)."""
    grid = pctx.grid
    i, j = grid.my_coords()
    B, S_loc, _ = x.shape
    H, P, G, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_groups, cfg.ssm_state
    H_loc = H // pctx.r
    di_loc = H_loc * P
    gn_loc = G * N // pctx.r
    kconv = cfg.conv_kernel

    # in_proj consumes the residual layout (arot under cannon_opt); every
    # internal tensor below is NATURAL (col j owns head/channel slice j).
    z, xc, Bc, Cc, dt = fused_dense(
        pctx, x, [p["wz"], p["wx"], p["wb"], p["wc"], p["wdt"]])
    pctx = pctx.with_(act_layout="blocked") \
        if pctx.act_layout == "skewed" else pctx

    # --- depthwise causal conv over [x, B, C] with a row halo exchange -----
    xBC = jnp.concatenate([xc, Bc, Cc], axis=-1)             # (B,S_loc,conv_loc)
    tail = xBC[:, -(kconv - 1):, :]
    halo = grid.put(tail, grid.row_shift_pairs(-1))          # from row i-1
    halo = jnp.where(i == 0, jnp.zeros_like(halo), halo)     # seq start
    conv_w = _conv_param_slice(pctx, p["conv_w"], di=cfg.d_inner,
                               gn=G * N, r=pctx.r)           # (k, conv_loc)
    conv_b = _conv_param_slice(pctx, p["conv_b"], di=cfg.d_inner,
                               gn=G * N, r=pctx.r)
    xBC = _conv1d_causal(xBC, conv_w, conv_b, halo)
    xBC = jax.nn.silu(xBC.astype(jnp.float32)).astype(x.dtype)
    xc, Bc, Cc = (xBC[..., :di_loc], xBC[..., di_loc:di_loc + gn_loc],
                  xBC[..., di_loc + gn_loc:])

    # --- assemble SSD operands --------------------------------------------
    B_full = grid.all_gather_cols(Bc, axis=-1).reshape(B, S_loc, G, N)
    C_full = grid.all_gather_cols(Cc, axis=-1).reshape(B, S_loc, G, N)
    xh = xc.reshape(B, S_loc, H_loc, P)
    A_loc = col_slice(pctx, p["A"], n_loc=H_loc).astype(jnp.float32)
    dtb = col_slice(pctx, p["dt_bias"], n_loc=H_loc)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + dtb)       # (B,S_loc,H_loc)

    # Group alignment: col j owns heads [j*H_loc, (j+1)*H_loc); global head h
    # belongs to group h // (H/G).  Slice the groups covering local heads so
    # the kernel's local rep (= heads per group) matches the global mapping.
    Bg = _slice_groups(B_full, G, pctx.r, j, axis=2)
    Cg = _slice_groups(C_full, G, pctx.r, j, axis=2)

    y0, contrib = ssd_scan(xh, dt, A_loc, Bg, Cg, chunk=cfg.ssd_chunk,
                           **_ssd_backend_kwargs(cfg, backend))

    # --- cross-row state relay (affine prefix over row shards) -------------
    sumdtA = jnp.sum(dt * A_loc[None, None], axis=1)         # (B, H_loc)
    decay_tot = jnp.exp(sumdtA)[..., None, None]             # (B,H_loc,1,1)
    decays = grid.all_gather_rows(decay_tot[None], axis=0)   # (q,B,H_loc,1,1)
    contribs = grid.all_gather_rows(contrib[None], axis=0)   # (q,B,H_loc,N,P)
    state_in = jnp.zeros_like(contrib)
    prefixes = [state_in]
    for s in range(grid.q - 1):
        state_in = decays[s] * state_in + contribs[s]
        prefixes.append(state_in)
    sel = jax.nn.one_hot(i, grid.q, dtype=jnp.float32)
    state_in = jnp.einsum("s,sbhnp->bhnp", sel, jnp.stack(prefixes))
    final_state = decays[grid.q - 1] * prefixes[-1] + contribs[grid.q - 1]

    # correction: y += exp(cumsum dtA)_t * C_t . state_in
    cumexp = jnp.exp(jnp.cumsum(dt * A_loc[None, None], axis=1))  # (B,S,H_loc)
    rep = xh.shape[2] // Bg.shape[2]
    c_h = jnp.repeat(Cg.astype(jnp.float32), rep, axis=2)    # (B,S,H_loc,N)
    y_corr = jnp.einsum("bshn,bhnp->bshp", c_h, state_in) * cumexp[..., None]
    y = y0.astype(jnp.float32) + y_corr

    # --- skip, gated norm, out projection ----------------------------------
    Dskip = col_slice(pctx, p["D"], n_loc=H_loc).astype(jnp.float32)
    y = y + Dskip[None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(B, S_loc, di_loc)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(pctx, y.astype(x.dtype), p["ssm_norm"],
                 eps=cfg.rms_norm_eps)
    out = dense(pctx, y, p["wo"], kind="crot")   # back to the residual layout
    # Decode conv cache wants the PRE-conv raw tail; row q-1 holds the
    # sequence-final one (serve/prefill selects it when building the cache).
    return out, (tail, final_state)


def mamba_chunk_step(pctx: ParallelContext, p: Dict, x: jax.Array,
                     state: Tuple, cfg, n_valid: jax.Array,
                     backend: Optional[str] = None
                     ) -> Tuple[jax.Array, Tuple]:
    """Multi-token state advance for chunked prefill (gemv layout).

    x (B, L, D_loc) row-replicated; state = (conv_state (B, k-1, conv_loc)
    PRE-activation, ssm_state (B, H_loc, N, P) fp32).  Slot b consumes chunk
    positions [0, n_valid[b]): padding columns are state-neutral (their
    ``dt`` is zeroed, so the SSD recurrence is the identity there, and the
    conv window gathers the last k-1 inputs *before* ``n_valid``), which
    lets one compiled executable serve every partial chunk — the same
    ``n_valid`` contract the paged-attention chunk path uses.  At
    ``n_valid == 1`` this computes :func:`mamba_decode_step`'s update, so
    decode-phase slots ride through chunked launches unchanged.
    ``backend`` selects the SSD scan kernel (jnp / pallas /
    pallas-interpret; default ``cfg.ssd_backend``) — the serving engine
    threads its ``kernel_backend`` through here.
    """
    conv_state, ssm_state = state
    B, L = x.shape[:2]
    H_loc = cfg.ssm_heads // pctx.r
    P, G, N = cfg.ssm_headdim, cfg.ssm_groups, cfg.ssm_state
    di_loc = H_loc * P
    gn_loc = G * N // pctx.r
    kconv = cfg.conv_kernel
    _, j = pctx.grid.my_coords()

    z, xc, Bc, Cc, dt = fused_dense(
        pctx, x, [p["wz"], p["wx"], p["wb"], p["wc"], p["wdt"]])
    xBC = jnp.concatenate([xc, Bc, Cc], axis=-1)             # (B, L, conv_loc)
    halo = conv_state.astype(xBC.dtype)
    conv_w = _conv_param_slice(pctx, p["conv_w"], di=cfg.d_inner,
                               gn=G * N, r=pctx.r)
    conv_b = _conv_param_slice(pctx, p["conv_b"], di=cfg.d_inner,
                               gn=G * N, r=pctx.r)
    out = _conv1d_causal(xBC, conv_w, conv_b, halo)
    # new conv window: the last (k-1) PRE-activation inputs at positions
    # strictly before n_valid (n_valid = 0 leaves the state untouched)
    full = jnp.concatenate([halo, xBC], axis=1)              # (B, k-1+L, C)
    gidx = n_valid[:, None] + jnp.arange(kconv - 1)[None, :]
    new_conv_state = jnp.take_along_axis(full, gidx[..., None], axis=1)
    xBC_a = jax.nn.silu(out.astype(jnp.float32)).astype(x.dtype)
    xc_a, Bc_a, Cc_a = (xBC_a[..., :di_loc],
                        xBC_a[..., di_loc:di_loc + gn_loc],
                        xBC_a[..., di_loc + gn_loc:])

    B_full = pctx.grid.all_gather_cols(Bc_a, axis=-1).reshape(B, L, G, N)
    C_full = pctx.grid.all_gather_cols(Cc_a, axis=-1).reshape(B, L, G, N)
    Bg = _slice_groups(B_full, G, pctx.r, j, axis=2)
    Cg = _slice_groups(C_full, G, pctx.r, j, axis=2)

    A_loc = col_slice(pctx, p["A"], n_loc=H_loc).astype(jnp.float32)
    dtb = col_slice(pctx, p["dt_bias"], n_loc=H_loc)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + dtb)       # (B, L, H_loc)
    valid = jnp.arange(L)[None, :] < n_valid[:, None]        # (B, L)
    dt = jnp.where(valid[..., None], dt, 0.0)   # dt=0: identity recurrence
    xh = xc_a.reshape(B, L, H_loc, P)
    y, new_ssm = ssd_scan(xh, dt, A_loc, Bg, Cg,
                          init_state=ssm_state.astype(jnp.float32),
                          chunk=L, **_ssd_backend_kwargs(cfg, backend))

    Dskip = col_slice(pctx, p["D"], n_loc=H_loc).astype(jnp.float32)
    y = y.astype(jnp.float32) + Dskip[None, None, :, None] \
        * xh.astype(jnp.float32)
    y = y.reshape(B, L, di_loc) * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(pctx, y.astype(x.dtype), p["ssm_norm"],
                 eps=cfg.rms_norm_eps)
    out = dense(pctx, y, p["wo"])
    return out, (new_conv_state, new_ssm)


def mamba_decode_step(pctx: ParallelContext, p: Dict, x: jax.Array,
                      state: Tuple, cfg) -> Tuple[jax.Array, Tuple]:
    """Single-token decode.  x (B_loc, 1, D_loc); state = (conv_state
    (B_loc, k-1, conv_loc) PRE-activation, ssm_state (B_loc, H_loc, N, P))."""
    conv_state, ssm_state = state
    B = x.shape[0]
    H_loc = cfg.ssm_heads // pctx.r
    P, G, N = cfg.ssm_headdim, cfg.ssm_groups, cfg.ssm_state
    di_loc = H_loc * P
    gn_loc = G * N // pctx.r
    kconv = cfg.conv_kernel
    _, j = pctx.grid.my_coords()

    z, xc, Bc, Cc, dt = fused_dense(
        pctx, x, [p["wz"], p["wx"], p["wb"], p["wc"], p["wdt"]])
    xBC = jnp.concatenate([xc, Bc, Cc], axis=-1)[:, 0]       # (B, conv_loc)
    window = jnp.concatenate([conv_state, xBC[:, None]], axis=1)  # (B,k,conv)
    conv_w = _conv_param_slice(pctx, p["conv_w"], di=cfg.d_inner,
                               gn=G * N, r=pctx.r)
    conv_b = _conv_param_slice(pctx, p["conv_b"], di=cfg.d_inner,
                               gn=G * N, r=pctx.r)
    out = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                     conv_w.astype(jnp.float32)) + conv_b
    xBC_t = jax.nn.silu(out).astype(x.dtype)
    new_conv_state = window[:, 1:]

    xc_t = xBC_t[:, :di_loc]
    Bc_t = xBC_t[:, di_loc:di_loc + gn_loc]
    Cc_t = xBC_t[:, di_loc + gn_loc:]
    B_full = pctx.grid.all_gather_cols(Bc_t, axis=-1).reshape(B, G, N)
    C_full = pctx.grid.all_gather_cols(Cc_t, axis=-1).reshape(B, G, N)
    B_full = _slice_groups(B_full, G, pctx.r, j, axis=1)
    C_full = _slice_groups(C_full, G, pctx.r, j, axis=1)

    A_loc = col_slice(pctx, p["A"], n_loc=H_loc).astype(jnp.float32)
    dtb = col_slice(pctx, p["dt_bias"], n_loc=H_loc)
    dt_t = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + dtb)  # (B, H_loc)
    xh = xc_t.reshape(B, H_loc, P)
    y, new_ssm = ssd_decode_step(xh, dt_t, A_loc, B_full, C_full, ssm_state)

    Dskip = col_slice(pctx, p["D"], n_loc=H_loc).astype(jnp.float32)
    y = y.astype(jnp.float32) + Dskip[None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(B, 1, di_loc) * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(pctx, y.astype(x.dtype), p["ssm_norm"],
                 eps=cfg.rms_norm_eps)
    out = dense(pctx, y, p["wo"])
    return out, (new_conv_state, new_ssm)
