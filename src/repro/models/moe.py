"""Mixture-of-Experts on the SHMEM grid: EP over the flat PE space.

Expert parallelism is the paper's PGAS story at its purest: experts are
symmetric objects distributed over the flat OpenSHMEM PE space (e // E_loc
owns expert e — flat PE arithmetic), and dispatch/combine are all_to_all
exchanges over the NoC/ICI.

Token hidden states are feature-sharded over grid cols (D_loc per PE), and
routing decisions are bit-identical across the row (router logits are
col-psummed), so each PE ships only its own D_loc slice; after the flat
all_to_all, slices from the r cols of a source row reassemble into full-D
tokens on the expert owner.  Per-PE wire volume is T*k*D/16 — the minimum
possible (each routed token's hidden crosses the wire exactly once).

Expert compute: tokens sorted by local expert id, one grouped GEMM via
``lax.ragged_dot`` (MegaBlocks-style, differentiable), swiglu, second
grouped GEMM, inverse exchange, weighted scatter-add combine.  This
grid-wide layer (training) sends each PE at most ``capacity_factor`` times
its even share of the assignments: the overflow is dropped (counted and
returned for the aux metrics).

:func:`moe_share` is the serving layer of one chip's share of an
expert-parallel deployment (``ModelConfig.experts_held``): it routes every
token over all ``n_experts`` and computes the experts it holds for every
assignment they receive, dropless, through the same grouped FFN
(:func:`expert_ffn`; the megablox ``gmm`` kernel on the pallas backends).
The assignments of the experts it does not hold contribute nothing here:
the chips that hold them add theirs.  One chip runs it with no exchange.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import ParallelContext, col_slice

GMM_ROWS = 128          # the grouped-matmul kernel's row tile


def _router(pctx: ParallelContext, x2d: jax.Array, wr: jax.Array, cfg):
    """x2d (T, D_loc) -> (probs (T, E) fp32, logits fp32); wr replicated (D, E).
    The row slice follows the residual layout (skewed under cannon_opt)."""
    i, j = pctx.grid.my_coords()
    d_loc = x2d.shape[-1]
    idx = (i + j) % pctx.q if pctx.act_layout == "skewed" else j
    wr_j = lax.dynamic_slice_in_dim(wr, idx * d_loc, d_loc, axis=0)
    part = x2d.astype(jnp.float32) @ wr_j.astype(jnp.float32)
    logits = pctx.grid.psum_cols(part)
    return jax.nn.softmax(logits, axis=-1), logits


def _gmm_tile(n: int) -> int:
    """The widest kernel tile of at most 1024 that divides ``n``."""
    for t in (1024, 768, 512, 384, 256, 128):
        if n % t == 0:
            return t
    return n


def _gmm(lhs, rhs, group_sizes, pallas: bool, interpret: bool):
    """Grouped matmul: rows ``[off_g, off_g + group_sizes[g])`` of ``lhs``
    times ``rhs[g]``.  Rows past the last group are left undefined.

    On the chip the expert weights stay in HBM at the kernel's boundary:
    left free, XLA prefetches whole layers of them (113 MB) into VMEM
    ahead of the kernel, so that the grouped matmul's device time leaves
    out most of its weight reads and the reads contend with the layers
    before it."""
    if not pallas:
        return lax.ragged_dot(lhs, rhs, group_sizes)
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    if not interpret:
        rhs = pltpu.with_memory_space_constraint(rhs, pltpu.MemorySpace.HBM)
    k, n = rhs.shape[1:]
    return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
               tiling=(GMM_ROWS, 512 if k % 512 == 0 else k, _gmm_tile(n)),
               interpret=interpret)


def expert_ffn(xs: jax.Array, w1: jax.Array, w2: jax.Array,
               group_sizes: jax.Array, pallas: bool = False,
               interpret: bool = False) -> jax.Array:
    """SwiGLU experts over rows sorted by expert: xs (M, D), w1 (E, D, 2F)
    (gate then up), w2 (E, F, D), group_sizes (E,) -> (M, D).  ``pallas``
    runs the megablox grouped-matmul kernel (``gmm``; ``interpret`` through
    the Pallas interpreter), which visits only the row tiles the groups
    cover; else ``lax.ragged_dot``."""
    h = _gmm(xs, w1, group_sizes, pallas, interpret)        # (M, 2F)
    F = w2.shape[1]
    h = (jax.nn.silu(h[:, :F].astype(jnp.float32)).astype(h.dtype)
         * h[:, F:])
    return _gmm(h, w2, group_sizes, pallas, interpret)      # (M, D)


def expert_rows(x: jax.Array, le: jax.Array, n_local: int, w1: jax.Array,
                w2: jax.Array, n_rows: int, pallas: bool = False,
                interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """The per-PE step of an expert layer: sort the assignments by local
    expert, run them through :func:`expert_ffn`, and read each one's
    output row back.

    ``le`` (T,) or (T, k) gives each assignment's local expert (``n_local``
    = none: sorted last and never computed); assignment ``le[t, ...]``
    takes its input row from ``x[t]``.  The buffer holds ``n_rows`` rows,
    at least every assignment to a local expert.  Returns ``(rows
    le.shape + (D,), group_sizes (n_local,) int32)``; the row of an
    assignment to no local expert is row 0's."""
    fl = le.reshape(-1)
    N, per_row = fl.shape[0], fl.shape[0] // le.shape[0]
    order = jnp.argsort(fl, stable=True)
    if n_rows > N:
        order = jnp.concatenate(
            [order, jnp.full((n_rows - N,), N - 1, order.dtype)])
    xs = x[order[:n_rows] // per_row]                          # (n_rows, D)
    sizes = jnp.zeros((n_local,), jnp.int32).at[fl].add(1, mode="drop")
    ye = expert_ffn(xs, w1, w2, sizes, pallas, interpret)
    rank = jnp.zeros((N,), jnp.int32).at[order[:N]].set(
        jnp.arange(N, dtype=jnp.int32)).reshape(le.shape)
    return jnp.take(ye, jnp.where(le < n_local, rank, 0), axis=0), sizes


def moe_share(pctx: ParallelContext, p: Dict, x: jax.Array, cfg,
              live: Optional[jax.Array] = None, pallas: bool = False,
              interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """One chip's share of an expert-parallel layer, dropless.

    x (B, L, D_loc) in a natural (unskewed) layout; ``live`` (B, L) bool
    marks the positions that feed a token (chunk padding and idle slots
    route to no expert; None = every position).  Every live position is
    routed over all ``cfg.n_experts``: the top ``cfg.top_k`` of its float32
    router logits, gated by a softmax over those k.  The held experts
    ``[cfg.expert_first, + cfg.experts_held)`` compute every assignment
    they receive: the row buffer holds ``T * min(top_k, experts_held)``
    rows, all a position can send, so none is ever dropped.  ``pallas`` /
    ``interpret`` select the grouped-matmul kernel (:func:`expert_ffn`).

    Returns ``(y (B, L, D_loc), counts (experts_held + 2,) int32)``: the
    rows each held expert computed, the busiest one's rows, then the
    positions routed."""
    B, L, D_loc = x.shape
    T = B * L
    k, Eh, e0 = cfg.top_k, cfg.experts_held, cfg.expert_first
    x2d = pctx.grid.all_gather_cols(x.reshape(T, D_loc), axis=-1)  # (T, D)
    logits = jnp.dot(x2d.astype(jnp.float32),
                     p["router"].astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)           # (T, E)
    top_l, top_e = lax.top_k(logits, k)                        # (T, k)
    gate = jax.nn.softmax(top_l, axis=-1)
    use = (top_e >= e0) & (top_e < e0 + Eh)
    routed = jnp.ones((T,), bool) if live is None else live.reshape(T)
    use = use & routed[:, None]
    le = jnp.where(use, top_e - e0, Eh)                        # Eh = none
    # the buffer holds every held assignment; its tail never reaches a
    # held expert, so the kernel's group sizes leave it out
    M = -(-T * min(k, Eh) // GMM_ROWS) * GMM_ROWS
    rows, counts = expert_rows(x2d, le, Eh, p["w1"], p["w2"], M,
                               pallas=pallas, interpret=interpret)
    y = jnp.sum(jnp.where(use[..., None], rows.astype(jnp.float32)
                          * gate[..., None], 0.0), axis=1)     # (T, D)
    y = col_slice(pctx, y, n_loc=D_loc, layout="blocked")
    n_routed = jnp.sum(routed.astype(jnp.int32))
    return (y.astype(x.dtype).reshape(B, L, D_loc),
            jnp.concatenate([counts, jnp.max(counts)[None], n_routed[None]]))


def moe_block(pctx: ParallelContext, p: Dict, x: jax.Array, cfg
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x (B, S_loc, D_loc) -> (y same shape, metrics {aux_loss, dropped}).

    The grid-wide layer: each PE receives at most ``capacity_factor`` x
    ``T * top_k / n_pes`` assignments; the rest are DROPPED (they add
    nothing to their token's output) and counted in ``moe_dropped``.
    Serving a share of an expert-parallel layer without drops is
    :func:`moe_share`."""
    grid = pctx.grid
    n_pes = grid.n_pes
    B, S_loc, D_loc = x.shape
    T = B * S_loc
    E, k = cfg.n_experts, cfg.top_k
    E_loc = E // n_pes
    cap = int(math.ceil(T * k / n_pes * cfg.capacity_factor))

    x2d = x.reshape(T, D_loc)
    probs, logits = _router(pctx, x2d, p["router"], cfg)  # router is replicated
    top_w, top_e = lax.top_k(probs, k)                      # (T, k)
    if cfg.router_renorm:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # ---- dispatch bookkeeping (identical across the grid row) -------------
    fe = top_e.reshape(-1)                                  # (T*k,)
    fw = top_w.reshape(-1).astype(jnp.float32)
    ft = jnp.repeat(jnp.arange(T), k)
    dest = fe // E_loc                                      # owner PE, flat
    oh = jax.nn.one_hot(dest, n_pes, dtype=jnp.int32)
    pos = jnp.cumsum(oh, axis=0) - oh                       # exclusive rank
    pos = jnp.sum(pos * oh, axis=-1)                        # (T*k,)
    valid = pos < cap
    slot = jnp.where(valid, pos, cap)                       # cap -> dropped
    dropped = jnp.sum(1 - valid.astype(jnp.int32))

    send_x = jnp.zeros((n_pes, cap + 1, D_loc), x.dtype
                       ).at[dest, slot].set(x2d[ft])[:, :cap]
    send_le = jnp.zeros((n_pes, cap + 1), jnp.int32
                        ).at[dest, slot].set(fe % E_loc)[:, :cap]

    # ---- flat all_to_all + full-D reassembly ------------------------------
    # int8 wire option (DeepSeek-style low-precision dispatch): per-slot
    # block quantization; scales (1/D_loc of the payload) ride along fp32.
    int8_wire = cfg.moe_wire_dtype == "int8"

    def _a2a(t):
        return lax.all_to_all(t, grid.axis, split_axis=0, concat_axis=0,
                              tiled=True)

    if int8_wire:
        sc = jnp.max(jnp.abs(send_x.astype(jnp.float32)), axis=-1,
                     keepdims=True) / 127.0 + 1e-12
        q8 = jnp.clip(jnp.round(send_x.astype(jnp.float32) / sc),
                      -127, 127).astype(jnp.int8)
        recv_x = (_a2a(q8).astype(jnp.float32)
                  * _a2a(sc.astype(jnp.float32))).astype(x.dtype)
    else:
        recv_x = _a2a(send_x)                               # (n_pes, cap, D_loc)
    recv_le = _a2a(send_le)
    q, r = grid.q, grid.r
    # source PE s = (i_s, j_s) sent its residual slice of row i_s's tokens:
    # D_{j_s} naturally, D_{(i_s+j_s)%q} under the skewed layout — roll each
    # source row's pieces back into natural feature order before reassembly.
    xs = recv_x.reshape(q, r, cap, D_loc)
    skewed = pctx.act_layout == "skewed"
    if skewed:
        xs = jnp.stack([jnp.roll(xs[i], i, axis=0) for i in range(q)])
    xs = xs.transpose(0, 2, 1, 3)
    xs = xs.reshape(q * cap, r * D_loc)                     # (M, D) full hidden
    les = recv_le.reshape(q, r, cap)[:, 0].reshape(q * cap)

    # ---- grouped expert FFN: the per-PE step of moe_share ---------------
    ys, _ = expert_rows(xs, les, E_loc, p["w1"][0], p["w2"][0], q * cap)

    # ---- inverse exchange + weighted combine ------------------------------
    yd = ys.reshape(q, cap, r, D_loc).transpose(0, 2, 1, 3)
    if skewed:   # restore each destination row's skewed slice order
        yd = jnp.stack([jnp.roll(yd[i], -i, axis=0) for i in range(q)])
    yd = yd.reshape(n_pes, cap, D_loc)
    if int8_wire:
        sc = jnp.max(jnp.abs(yd.astype(jnp.float32)), axis=-1,
                     keepdims=True) / 127.0 + 1e-12
        q8 = jnp.clip(jnp.round(yd.astype(jnp.float32) / sc),
                      -127, 127).astype(jnp.int8)
        back = (_a2a(q8).astype(jnp.float32)
                * _a2a(sc.astype(jnp.float32))).astype(yd.dtype)
    else:
        back = _a2a(yd)                                     # (n_pes, cap, D_loc)
    gathered = back[dest, slot]                             # (T*k, D_loc)
    gathered = jnp.where(valid[:, None], gathered, 0)
    contrib = gathered.astype(jnp.float32) * fw[:, None]
    y = jnp.zeros((T, D_loc), jnp.float32).at[ft].add(contrib)

    # ---- aux losses (switch-style load balance + router z) ----------------
    # frac/pmean must be averaged over ALL token shards (grid rows + data)
    # BEFORE the product — mean-of-products != product-of-means.
    frac = jnp.mean(jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=(0, 1))
    pmean = jnp.mean(probs, axis=0)
    frac = grid.psum_rows(frac) / grid.q
    pmean = grid.psum_rows(pmean) / grid.q
    for ax in pctx.data_axes:
        frac = lax.pmean(frac, ax)
        pmean = lax.pmean(pmean, ax)
    aux = E * jnp.sum(frac * pmean) * cfg.moe_aux_coef
    zloss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * cfg.moe_z_coef

    metrics = {"moe_aux": aux + zloss,
               "moe_dropped": dropped.astype(jnp.float32) / (T * k)}
    return y.astype(x.dtype).reshape(B, S_loc, D_loc), metrics
