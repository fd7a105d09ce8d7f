"""Serving: prefill + decode steps on the SHMEM grid.

Two decode layouts, chosen by batch size (see DESIGN.md §Parallelism):

  * ``batched``  — batch sharded over (data, grid rows), heads over cols.
    KV cache fully PE-local: decode attention needs ZERO communication;
    projections run the normal Cannon path with M = local batch.
    (decode_32k: B=128 over 16 data x 4 rows -> 2 seqs/PE.)

  * ``longctx``  — batch too small to shard (B=1, 500k context).  Weights
    stored UNSKEWED; projections via gemv2d (stationary weights, tiny
    activations move).  KV cache *sequence*-sharded over (data x grid rows):
    each PE scores its cache chunk and partials merge with a log-sum-exp
    reduction (flash-decoding as a SHMEM collective).  SSM archs carry O(1)
    state instead — this is why long_500k is an SSM/hybrid-only cell.

Cache boundary layout: every leaf is (groups, n_pes, ...local) with dim 1
sharded over MODEL and (batched mode) the local batch dim over DATA.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import default_kernel_backend, resolve_kernel_backend
from repro.kernels.paged_attention import gather_pages, paged_attention
from repro.models import params as pm
from repro.models.attention import attention_partial, combine_partials
from repro.models.config import ModelConfig, attn_static
from repro.models.layers import (ParallelContext, apply_rope, col_slice,
                                 dense, fused_dense, rms_norm_local,
                                 rope_tables)
from repro.models.moe import moe_block
from repro.models.ssm import mamba_chunk_step, mamba_decode_step
from repro.models.transformer import (_norm, apply_layer, embed_tokens,
                                      forward, mlp_apply, param_specs)
from repro.partition import DATA, MODEL, POD, MeshPlan
from repro.serve.state import (ModelStateSpecs, layer_state_specs,
                               pattern_pspecs)
from repro.train.step import make_pctx


# ---------------------------------------------------------------------------
# Cache specs.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PagedKV:
    """Physically paged KV arena layout.

    One arena `(G, n_pes, ceil(n_blocks/q), block_pos_stride, kvh, hd)` is
    shared by every batch bucket: physical page ``p`` lives on grid row
    ``p % q`` at local index ``p // q`` (columns shard kv heads as usual).
    Step kernels address it through a per-slot **block table** operand
    ``(B, s_max // block_pos_stride)`` of physical page ids (-1 =
    unallocated), so sequence identity lives entirely in host-built tables —
    slot migration, prefix sharing and ``fork()`` never touch device KV.
    """

    n_blocks: int                # physical pages across the whole arena
    block_pos_stride: int        # cache positions per page

    def __post_init__(self):
        if self.n_blocks < 1:
            raise ValueError("n_blocks must be >= 1")
        if self.block_pos_stride < 1:
            raise ValueError("block_pos_stride must be >= 1")

    def blocks_local(self, q: int) -> int:
        """Per-PE page count (rows shard the physical id space)."""
        return -(-self.n_blocks // q)


def paged_cache_specs(cfg: ModelConfig, plan: MeshPlan, paged: PagedKV, *,
                      n_dense_slots: int = 0) -> Any:
    """ShapeDtypeStruct pytree for the bucket-independent engine state arena.

    Spec-driven (:mod:`repro.serve.state`): attention layers contribute
    paged K/V leaves, SSM layers contribute dense per-slot ``conv``/``ssm``
    leaves (``n_dense_slots`` rows; required > 0 when any layer is dense).
    """
    specs = layer_state_specs(cfg, plan, stride=paged.block_pos_stride)
    if specs.has_dense and n_dense_slots < 1:
        raise ValueError(
            f"{cfg.name}: dense-state layers need n_dense_slots >= 1")
    return specs.arena_specs(paged.n_blocks, n_dense_slots)


def paged_cache_pspecs(cfg: ModelConfig) -> Any:
    """Arena boundary specs: pages AND dense slots are sharded *inside* the
    flat MODEL axis (dim 1), never batch-sharded — the arena is
    bucket-independent (see ``repro.serve.state.pattern_pspecs``)."""
    return pattern_pspecs(cfg)


def cache_specs(cfg: ModelConfig, plan: MeshPlan, batch: int, s_max: int,
                mode: str) -> Any:
    """ShapeDtypeStruct pytree for the decode cache (dry-run + init)."""
    q, r = plan.grid_q, plan.grid_r
    n_pes = q * r
    G = cfg.n_groups()
    dshards = plan.data_size * (plan.pod_size if plan.has_pod else 1)
    has_attn = any(mixer == "attn" for mixer, _ in cfg.pattern())
    kvh = cfg.kv_stored(r)[0] // r if has_attn else 0
    hd = cfg.hd() if has_attn else 0
    dt = cfg.compute_dtype

    if mode == "batched":
        assert batch % (dshards * q) == 0, (batch, dshards, q)
        # boundary dim 2 is sharded over DATA: global-over-data size batch//q
        kv_shape = (G, n_pes, batch // q, s_max, kvh, hd)
    elif mode == "gemv":
        # weights-stationary decode: batch over DATA only, cache sequence
        # sharded over grid ROWS (flash-decode merge over rows)
        assert batch % dshards == 0, (batch, dshards)
        kv_shape = (G, n_pes, batch, s_max // q, kvh, hd)
    else:  # longctx: sequence-sharded cache over (data x rows), batch repl.
        s_loc = s_max // (dshards * q)
        kv_shape = (G, n_pes, batch, s_loc, kvh, hd)

    entries = []
    for (mixer, ffn) in cfg.pattern():
        if mixer == "attn":
            e = {
                "k": jax.ShapeDtypeStruct(kv_shape, dt),
                "v": jax.ShapeDtypeStruct(kv_shape, dt),
            }
            if cfg.enc_layers:   # whisper: cached encoder cross K/V
                cross = (G, n_pes, kv_shape[2], cfg.enc_seq, kvh, hd)
                e["cross_k"] = jax.ShapeDtypeStruct(cross, dt)
                e["cross_v"] = jax.ShapeDtypeStruct(cross, dt)
            entries.append(e)
        else:
            H_loc = cfg.ssm_heads // r
            conv_ch = (cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state) // r
            b_here = kv_shape[2]
            entries.append({
                "conv": jax.ShapeDtypeStruct(
                    (G, n_pes, b_here, cfg.conv_kernel - 1, conv_ch), dt),
                "ssm": jax.ShapeDtypeStruct(
                    (G, n_pes, b_here, H_loc, cfg.ssm_state, cfg.ssm_headdim),
                    jnp.float32),
            })
    return entries


def cache_pspecs(cfg: ModelConfig, mode: str, data_axes) -> Any:
    lead = tuple(data_axes) if len(data_axes) > 1 else data_axes[0]
    batch_ax = lead if mode in ("batched", "gemv") else None

    def spec_for(leaf_name):
        return P(None, MODEL, batch_ax)

    entries = []
    for (mixer, ffn) in cfg.pattern():
        if mixer == "attn":
            e = {"k": P(None, MODEL, batch_ax), "v": P(None, MODEL, batch_ax)}
            if cfg.enc_layers:
                e["cross_k"] = P(None, MODEL, batch_ax)
                e["cross_v"] = P(None, MODEL, batch_ax)
            entries.append(e)
        else:
            entries.append({"conv": P(None, MODEL, batch_ax),
                            "ssm": P(None, MODEL, batch_ax)})
    return entries


# ---------------------------------------------------------------------------
# Decode-mode attention.
# ---------------------------------------------------------------------------
#
# ``pos`` may be a scalar (single-shot serving: every sequence at the same
# position) or a vector (B_loc,) (continuous batching: each slot at its own
# position).  The vector path writes the new K/V with a one-hot scatter and
# masks attention per slot; at equal positions it computes the same values as
# the scalar path, which the engine parity test relies on.


def _rope_decode(q, k, pos, hd, theta):
    """Rotate the single new q/k at ``pos`` (scalar or per-slot vector)."""
    if jnp.ndim(pos) == 0:
        cos, sin = rope_tables(jnp.reshape(pos, (1,)), hd, theta)
        return apply_rope(q, cos[None], sin[None]), \
            apply_rope(k, cos[None], sin[None])
    cos, sin = rope_tables(pos[:, None], hd, theta)      # (B, 1, hd/2)
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin)


def _scatter_kv(kc, vc, k, v, local_pos, valid=None):
    """Write k/v (B, 1, kvh, hd) into the cache at per-slot ``local_pos``.

    ``valid`` (B,) optionally masks slots whose position falls outside this
    PE's cache shard (sequence-sharded layouts)."""
    S = kc.shape[1]
    hit = jnp.arange(S)[None, :] == jnp.clip(local_pos, 0, S - 1)[:, None]
    if valid is not None:
        hit = hit & valid[:, None]
    sel = hit[..., None, None]
    return (jnp.where(sel, k.astype(kc.dtype), kc),
            jnp.where(sel, v.astype(vc.dtype), vc))


def _attn_decode_batched(pctx, p, x, cfg, kc, vc, pos):
    """x (B_pe, 1, D_loc); kc/vc (B_pe, S_max, kvh_loc, hd) local; pos traced.
    Returns (y, new kc, new vc).  Zero-communication attention."""
    B = x.shape[0]
    hq_loc = cfg.n_heads_padded // pctx.r
    hkv_loc = cfg.n_kv_stored // pctx.r
    hd = cfg.head_dim
    biases = [p.get("bq"), p.get("bk"), p.get("bv")] if cfg.qkv_bias else None
    q, k, v = fused_dense(pctx, x, [p["wq"], p["wk"], p["wv"]], biases=biases)
    q = q.reshape(B, 1, hq_loc, hd)
    k = k.reshape(B, 1, hkv_loc, hd)
    v = v.reshape(B, 1, hkv_loc, hd)
    if cfg.qk_norm:
        q = rms_norm_local(q, p["q_norm"])
        k = rms_norm_local(k, p["k_norm"])
    q, k = _rope_decode(q, k, pos, hd, cfg.rope_theta)
    kv_pos = jnp.arange(kc.shape[1])
    if jnp.ndim(pos) == 0:
        kc = lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), pos,
                                             axis=1)
        vc = lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), pos,
                                             axis=1)
        part = attention_partial(
            q.transpose(0, 2, 1, 3), kc.transpose(0, 2, 1, 3),
            vc.transpose(0, 2, 1, 3), kv_pos=kv_pos,
            q_pos=jnp.reshape(pos, (1,)))
    else:
        kc, vc = _scatter_kv(kc, vc, k, v, pos)
        part = attention_partial(
            q.transpose(0, 2, 1, 3), kc.transpose(0, 2, 1, 3),
            vc.transpose(0, 2, 1, 3), kv_pos=kv_pos, q_pos=pos[:, None])
    out = (part.acc / jnp.maximum(part.l, 1e-30)[..., None])
    out = out.transpose(0, 2, 1, 3).reshape(B, 1, hq_loc * hd)
    y = dense(pctx, out.astype(x.dtype), p["wo"])
    return y, kc, vc


def _attn_decode_longctx(pctx, p, x, cfg, kc, vc, pos, shard_offset,
                         reduce_data: bool = True):
    """x (B, 1, D_loc) replicated over rows (+data); cache seq-sharded:
    kc/vc (B, S_loc, kvh_loc, hd), this PE covering global positions
    [shard_offset, shard_offset + S_loc).  Flash-decoding LSE merge."""
    B = x.shape[0]
    grid = pctx.grid
    hq_loc = cfg.n_heads_padded // pctx.r
    hkv_loc = cfg.n_kv_stored // pctx.r
    hd = cfg.head_dim
    q, k, v = fused_dense(pctx, x, [p["wq"], p["wk"], p["wv"]])
    q = q.reshape(B, 1, hq_loc, hd)
    k = k.reshape(B, 1, hkv_loc, hd)
    v = v.reshape(B, 1, hkv_loc, hd)
    if cfg.qk_norm:
        q = rms_norm_local(q, p["q_norm"])
        k = rms_norm_local(k, p["k_norm"])
    q, k = _rope_decode(q, k, pos, hd, cfg.rope_theta)
    # write the new KV into its owner shard (masked dynamic update)
    S_loc = kc.shape[1]
    kv_pos = shard_offset + jnp.arange(S_loc)
    if jnp.ndim(pos) == 0:
        local_pos = jnp.clip(pos - shard_offset, 0, S_loc - 1)
        mine = (pos >= shard_offset) & (pos < shard_offset + S_loc)
        k_old = lax.dynamic_slice_in_dim(kc, local_pos, 1, axis=1)
        v_old = lax.dynamic_slice_in_dim(vc, local_pos, 1, axis=1)
        k_new = jnp.where(mine, k.astype(kc.dtype), k_old)
        v_new = jnp.where(mine, v.astype(vc.dtype), v_old)
        kc = lax.dynamic_update_slice_in_dim(kc, k_new, local_pos, axis=1)
        vc = lax.dynamic_update_slice_in_dim(vc, v_new, local_pos, axis=1)
        part = attention_partial(
            q.transpose(0, 2, 1, 3), kc.transpose(0, 2, 1, 3),
            vc.transpose(0, 2, 1, 3), kv_pos=kv_pos,
            q_pos=jnp.reshape(pos, (1,)))
    else:
        mine = (pos >= shard_offset) & (pos < shard_offset + S_loc)
        kc, vc = _scatter_kv(kc, vc, k, v, pos - shard_offset, valid=mine)
        part = attention_partial(
            q.transpose(0, 2, 1, 3), kc.transpose(0, 2, 1, 3),
            vc.transpose(0, 2, 1, 3), kv_pos=kv_pos, q_pos=pos[:, None])

    # reduce over grid ROWS (+ the data axes when the cache shards there):
    def reduce_max(t):
        groups = [[i * grid.r + j for i in range(grid.q)]
                  for j in range(grid.r)]
        t = lax.pmax(t, grid.axis, axis_index_groups=groups)
        if reduce_data:
            for ax in pctx.data_axes:
                t = lax.pmax(t, ax)
        return t

    def reduce_sum(t):
        t = grid.psum_rows(t)
        if reduce_data:
            for ax in pctx.data_axes:
                t = lax.psum(t, ax)
        return t

    out = combine_partials(part, reduce_max, reduce_sum)
    out = out.transpose(0, 2, 1, 3).reshape(B, 1, hq_loc * hd)
    y = dense(pctx, out.astype(x.dtype), p["wo"])
    return y, kc, vc


def _rows_pmax(grid):
    """pmax over grid rows (the axis paged KV pages shard on)."""
    groups = [[i * grid.r + j for i in range(grid.q)] for j in range(grid.r)]
    return lambda t: lax.pmax(t, grid.axis, axis_index_groups=groups)


def _paged_partial(q, kc, vc, table, q_pos, kv_len, stride, row, qrows,
                   backend):
    """Per-row paged-attention partials, backend-dispatched.

    ``backend="jnp"`` materializes the gathered per-slot K/V runs
    (:func:`repro.kernels.paged_attention.gather_pages`) and scores them with ``attention_partial`` —
    the bit-exact reference.  The pallas backends hand the arena shard and
    the table straight to the fused kernel
    (:mod:`repro.kernels.paged_attention`): the page gather happens inside
    the kernel's DMA index maps, so no ``(B, T * stride, ...)`` gathered
    copy ever exists, and each slot's walk stops at ``kv_len`` (one past
    its last valid query position).  Both return LSE partials, so the
    SHMEM row-merge downstream (``combine_partials``) is backend-blind."""
    if backend == "jnp":
        kg, vg, kv_pos = gather_pages(kc, vc, table, stride=stride, row=row,
                                      qrows=qrows)
        return attention_partial(
            q, kg.transpose(0, 2, 1, 3), vg.transpose(0, 2, 1, 3),
            kv_pos=kv_pos, q_pos=q_pos)
    _, interpret = resolve_kernel_backend(backend)
    if q_pos.ndim == 1:      # scalar-pos decode: shared across the batch
        q_pos = jnp.broadcast_to(q_pos[None, :],
                                 (q.shape[0], q_pos.shape[0]))
    return paged_attention(q, kc, vc, table, q_pos, kv_len, stride=stride,
                           row=row, qrows=qrows, backend="pallas",
                           interpret=interpret)


def _attn_decode_paged(pctx, p, x, cfg, kc, vc, pos, table, stride,
                       backend="jnp"):
    """Paged-arena decode attention (gemv projections, weights stationary).

    x (B, 1, D_loc) replicated over rows; kc/vc (n_blocks_local, stride,
    kvh_loc, hd) — this PE (row i) owns physical pages ``p % q == i``.
    ``table`` (B, T) holds each slot's physical page ids (-1 = unallocated).
    The new token's K/V scatters into ``table[pos // stride]`` at offset
    ``pos % stride`` on the owner row; attention reads each slot's pages
    (gathered copies under ``backend="jnp"``, in place inside the fused
    kernel under the pallas backends) and the per-row partials merge with
    the flash-decoding LSE reduction (each position is owned by exactly one
    row).  ``pos`` may be scalar (single-shot) or (B,) (continuous
    batching)."""
    B = x.shape[0]
    grid = pctx.grid
    i, _ = grid.my_coords()
    qrows = pctx.q
    hq_loc = cfg.n_heads_padded // pctx.r
    hkv_loc = cfg.n_kv_stored // pctx.r
    hd = cfg.head_dim
    biases = [p.get("bq"), p.get("bk"), p.get("bv")] if cfg.qkv_bias else None
    q, k, v = fused_dense(pctx, x, [p["wq"], p["wk"], p["wv"]], biases=biases)
    q = q.reshape(B, 1, hq_loc, hd)
    k = k.reshape(B, 1, hkv_loc, hd)
    v = v.reshape(B, 1, hkv_loc, hd)
    if cfg.qk_norm:
        q = rms_norm_local(q, p["q_norm"])
        k = rms_norm_local(k, p["k_norm"])
    q, k = _rope_decode(q, k, pos, hd, cfg.rope_theta)

    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    n_loc = kc.shape[0]
    # scatter the new K/V into its table page (owner row only; slots whose
    # write page lives elsewhere — or idle slots with table entry -1 — are
    # routed out of bounds and dropped)
    pid_w = jnp.take_along_axis(table, (posv // stride)[:, None], axis=1)[:, 0]
    mine_w = (pid_w >= 0) & (pid_w % qrows == i)
    li_w = jnp.where(mine_w, pid_w // qrows, n_loc)
    off_w = posv % stride
    kc = kc.at[li_w, off_w].set(k[:, 0].astype(kc.dtype), mode="drop")
    vc = vc.at[li_w, off_w].set(v[:, 0].astype(vc.dtype), mode="drop")

    q_pos = jnp.reshape(pos, (1,)) if jnp.ndim(pos) == 0 else pos[:, None]
    # an idle slot (table all -1) walks nothing
    kv_len = jnp.where(jnp.any(table >= 0, axis=1), posv + 1, 0)
    part = _paged_partial(q.transpose(0, 2, 1, 3), kc, vc, table, q_pos,
                          kv_len, stride, i, qrows, backend)
    out = combine_partials(part, _rows_pmax(grid), grid.psum_rows)
    out = out.transpose(0, 2, 1, 3).reshape(B, 1, hq_loc * hd)
    y = dense(pctx, out.astype(x.dtype), p["wo"])
    return y, kc, vc


def _attn_prefill_chunk_paged(pctx, p, x, cfg, kc, vc, pos, n_valid, table,
                              stride, backend="jnp"):
    """Chunked-prefill attention against the paged arena (gemv projections).

    x (B, L, D_loc) replicated over rows: each slot advances up to L
    positions in ONE launch.  Slot b's chunk covers global positions
    [pos[b], pos[b] + n_valid[b]); chunk columns past ``n_valid`` are
    padding — their K/V writes are dropped and their outputs never read
    (the body extracts the last valid position only), so one compiled
    ``prefill_bs{N}_len{L}`` executable serves every partial chunk.  All
    valid positions' K/V scatter into the slot's block-table pages in one
    shot; the gather + blocked causal mask (q_pos (B, L) against per-slot
    kv_pos labels) makes chunk position j attend to exactly [0, pos+j], so
    the chunk reproduces the per-token path position for position."""
    B, L = x.shape[:2]
    grid = pctx.grid
    i, _ = grid.my_coords()
    qrows = pctx.q
    hq_loc = cfg.n_heads_padded // pctx.r
    hkv_loc = cfg.n_kv_stored // pctx.r
    hd = cfg.head_dim
    biases = [p.get("bq"), p.get("bk"), p.get("bv")] if cfg.qkv_bias else None
    q, k, v = fused_dense(pctx, x, [p["wq"], p["wk"], p["wv"]], biases=biases)
    q = q.reshape(B, L, hq_loc, hd)
    k = k.reshape(B, L, hkv_loc, hd)
    v = v.reshape(B, L, hkv_loc, hd)
    if cfg.qk_norm:
        q = rms_norm_local(q, p["q_norm"])
        k = rms_norm_local(k, p["k_norm"])
    pos2 = pos[:, None] + jnp.arange(L)[None, :]            # (B, L) global
    cos, sin = rope_tables(pos2, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    valid = jnp.arange(L)[None, :] < n_valid[:, None]       # (B, L)
    n_loc = kc.shape[0]
    T = table.shape[1]
    # scatter every valid chunk position's K/V into its table page (owner
    # row only; padding columns, idle slots and out-of-owner writes are
    # routed out of bounds and dropped)
    tidx = jnp.clip(pos2 // stride, 0, T - 1)
    pid_w = jnp.take_along_axis(table, tidx, axis=1)        # (B, L)
    mine_w = valid & (pid_w >= 0) & (pid_w % qrows == i)
    li_w = jnp.where(mine_w, pid_w // qrows, n_loc)
    off_w = pos2 % stride
    kc = kc.at[li_w, off_w].set(k.astype(kc.dtype), mode="drop")
    vc = vc.at[li_w, off_w].set(v.astype(vc.dtype), mode="drop")

    part = _paged_partial(q.transpose(0, 2, 1, 3), kc, vc, table, pos2,
                          pos + n_valid, stride, i, qrows, backend)
    out = combine_partials(part, _rows_pmax(grid), grid.psum_rows)
    out = out.transpose(0, 2, 1, 3).reshape(B, L, hq_loc * hd)
    y = dense(pctx, out.astype(x.dtype), p["wo"])
    return y, kc, vc


def _dense_slot_gather(arena_leaves, slots):
    """Gather each batch lane's dense state rows from the slot arena.

    ``arena_leaves`` maps name -> (n_slots, ...) local arena; ``slots`` (B,)
    holds each lane's slot id (-1 = idle lane, which reads slot 0 as a dummy
    and never writes back).  The dense analogue of the paged ``gather_pages`` —
    sequence identity lives in the host-built slot vector, so fork /
    migration / preemption never reorder arena rows."""
    n_slots = next(iter(arena_leaves.values())).shape[0]
    idx = jnp.clip(slots, 0, n_slots - 1)
    return {name: jnp.take(a, idx, axis=0) for name, a in arena_leaves.items()}


def _dense_slot_scatter(arena_leaves, new_leaves, slots):
    """Write advanced per-lane dense state back to its slot row (idle lanes,
    slots == -1, are routed out of bounds and dropped)."""
    n_slots = next(iter(arena_leaves.values())).shape[0]
    li = jnp.where(slots >= 0, slots, n_slots)
    return {name: arena_leaves[name].at[li].set(
        new_leaves[name].astype(arena_leaves[name].dtype), mode="drop")
        for name in arena_leaves}


# ---------------------------------------------------------------------------
# Decode layer + step.
# ---------------------------------------------------------------------------

def _cross_decode(pctx, p, x, cfg, ck, cv):
    """Cross attention against the cached encoder K/V (whisper decode).
    ck/cv (B_pe, S_enc, kvh_loc, hd) fully local; non-causal."""
    B = x.shape[0]
    hq_loc = cfg.n_heads_padded // pctx.r
    hd = cfg.head_dim
    q = dense(pctx, x, p["wq"]).reshape(B, 1, hq_loc, hd)
    S_enc = ck.shape[1]
    part = attention_partial(
        q.transpose(0, 2, 1, 3), ck.transpose(0, 2, 1, 3),
        cv.transpose(0, 2, 1, 3), kv_pos=jnp.zeros((S_enc,), jnp.int32),
        q_pos=jnp.zeros((1,), jnp.int32))   # q_pos >= kv_pos always: no mask
    out = (part.acc / jnp.maximum(part.l, 1e-30)[..., None])
    out = out.transpose(0, 2, 1, 3).reshape(B, 1, hq_loc * hd)
    return dense(pctx, out.astype(x.dtype), p["wo"])


def _decode_layer(pctx, cfg, mixer, ffn, p, x, cache, pos, shard_offset, mode,
                  table=None, paged=None, n_valid=None, slots=None,
                  backend="jnp"):
    ast = attn_static(cfg, pctx.r) if mixer == "attn" else None
    if mixer == "attn":
        h = _norm(pctx, cfg, p["norm1"], x)
        if paged is not None and n_valid is not None:
            h, kc, vc = _attn_prefill_chunk_paged(pctx, p["mixer"], h, ast,
                                                  cache["k"], cache["v"],
                                                  pos, n_valid, table,
                                                  paged.block_pos_stride,
                                                  backend=backend)
        elif paged is not None:
            h, kc, vc = _attn_decode_paged(pctx, p["mixer"], h, ast,
                                           cache["k"], cache["v"], pos,
                                           table, paged.block_pos_stride,
                                           backend=backend)
        elif mode == "batched":
            h, kc, vc = _attn_decode_batched(pctx, p["mixer"], h, ast,
                                             cache["k"], cache["v"], pos)
        else:
            h, kc, vc = _attn_decode_longctx(pctx, p["mixer"], h, ast,
                                             cache["k"], cache["v"], pos,
                                             shard_offset,
                                             reduce_data=(mode == "longctx"))
        x = x + h
        new_cache = {"k": kc, "v": vc}
    elif slots is not None:
        # engine path (DenseSpec): per-slot state rides in a dense slot
        # arena addressed through the ``slots`` operand — the O(1)-state
        # sibling of the block-table indirection above
        h = _norm(pctx, cfg, p["norm1"], x)
        st = _dense_slot_gather(cache, slots)
        if n_valid is not None:
            h, (conv, ssm) = mamba_chunk_step(pctx, p["mixer"], h,
                                              (st["conv"], st["ssm"]), cfg,
                                              n_valid, backend=backend)
        else:
            h, (conv, ssm) = mamba_decode_step(pctx, p["mixer"], h,
                                               (st["conv"], st["ssm"]), cfg)
        x = x + h
        new_cache = _dense_slot_scatter(cache, {"conv": conv, "ssm": ssm},
                                        slots)
    else:
        h = _norm(pctx, cfg, p["norm1"], x)
        h, (conv, ssm) = mamba_decode_step(pctx, p["mixer"], h,
                                           (cache["conv"], cache["ssm"]), cfg)
        x = x + h
        new_cache = {"conv": conv, "ssm": ssm}
    if "cross" in p:
        h = _norm(pctx, cfg, p["norm_cross"], x)
        x = x + _cross_decode(pctx, p["cross"], h, ast,
                              cache["cross_k"], cache["cross_v"])
        new_cache = dict(new_cache, cross_k=cache["cross_k"],
                         cross_v=cache["cross_v"])
    if ffn == "mlp":
        h = _norm(pctx, cfg, p["norm2"], x)
        x = x + mlp_apply(pctx, cfg, p["ffn"], h)
    elif ffn == "moe":
        h = _norm(pctx, cfg, p["norm2"], x)
        y, _ = moe_block(pctx, p["ffn"], h, cfg)
        x = x + y
    return x, new_cache


def _embed_decode(pctx, embed_blk, tokens, mode, compute_dtype):
    """tokens: batched -> (B_data,) replicated over model (each row takes its
    slice); longctx -> (B,) replicated everywhere; chunked prefill feeds
    (B, L) token blocks (gemv layout only) and gets (B, L, D_loc) back."""
    vb = embed_blk[0]
    V_loc = vb.shape[0]
    grid = pctx.grid
    i, _ = grid.my_coords()
    loc = tokens - i * V_loc
    hit = (loc >= 0) & (loc < V_loc)
    part = jnp.take(vb, jnp.clip(loc, 0, V_loc - 1), axis=0)
    part = jnp.where(hit[..., None], part, 0).astype(compute_dtype)
    if mode == "batched":
        # sum over vocab row-blocks AND scatter the batch dim to rows
        return grid.reduce_scatter_rows(part, axis=0)[:, None, :]
    out = grid.psum_rows(part)                  # gemv/longctx: repl. rows
    return out if tokens.ndim == 2 else out[:, None, :]


def _last_logits(pctx, lm_head_blk, x, gather_rows: bool):
    """x (B_loc, 1, D_loc) -> logits (B, 1, V) gathered to a boundary-clean
    layout (full vocab per PE; batch re-gathered over rows when the rows
    shard it).  The (rows x cols) 2D use of the flat model axis cannot cross
    the shard_map boundary in one PartitionSpec."""
    logits = dense(pctx, x, lm_head_blk, out_dtype=jnp.float32)
    logits = pctx.grid.all_gather_cols(logits, axis=-1)     # full vocab
    if gather_rows:
        logits = pctx.grid.all_gather_rows(logits, axis=0)  # full local batch
    return logits


def make_decode_body(cfg: ModelConfig, mesh: Mesh, plan: MeshPlan, *,
                     batch: int, s_max: int, mode: str = "batched",
                     tp_strategy: Optional[str] = None,
                     per_slot: bool = False,
                     paged: Optional[PagedKV] = None,
                     kernel_backend: Optional[str] = None):
    """Device-level decode step body + boundary specs (un-mapped).

    Returns ``(body, in_specs, out_specs, specs, pctx)`` so callers can either
    ``shard_map`` it directly (:func:`make_decode_step`) or wrap it as a
    :class:`repro.core.hybrid.HybridKernel` and enqueue it on a
    ``CommandQueue`` (the serving engine).

    With ``per_slot=True`` the step takes vector ``pos`` (B,) operands: each
    batch slot advances from its own position.  Dense per-slot steps
    additionally take a ``reset`` (B,) operand wiping recycled slots
    in-kernel; paged steps don't need it — a fresh slot simply points its
    block table at freshly allocated pages, and stale page contents beyond
    the slot's position are causally masked.

    With ``paged`` set (gemv mode only) the cache operand is the engine
    state arena of :func:`paged_cache_specs` and the step's trailing
    operands derive from the per-layer state specs
    (:func:`repro.serve.state.layer_state_specs`): a block-table operand
    ``(B, s_max // block_pos_stride)`` of physical page ids when any layer
    pages KV, then a ``(B,)`` dense slot-id operand when any layer carries
    O(1) dense state; ``pos`` may be scalar or per-slot.  Attention-only
    models keep the exact pre-StateSpec ABI
    ``(params, arena, tokens, pos, table)``.

    ``kernel_backend`` (default: :func:`repro.kernels.default_kernel_backend`,
    i.e. ``"jnp"`` unless ``REPRO_KERNEL_BACKEND`` overrides it) selects the
    attention kernels on the PAGED path: ``"jnp"`` keeps the materialized
    per-slot gather; the pallas backends read KV pages in place inside the
    fused paged-attention kernel.  Non-paged modes (batched/longctx) always
    use the jnp attention paths.
    """
    kernel_backend = kernel_backend if kernel_backend is not None \
        else default_kernel_backend()
    resolve_kernel_backend(kernel_backend)      # validate eagerly
    if tp_strategy is None:
        tp_strategy = "cannon" if mode == "batched" else "gemv"
    act_layout = "blocked" if mode == "batched" else "repl_rows"
    pctx = make_pctx(plan, "cannon" if mode == "batched" else "allgather",
                     remat=False, compute_dtype=cfg.compute_dtype)
    pctx = dataclasses.replace(pctx, act_layout=act_layout,
                               preskewed=(mode == "batched"))
    # "gemv": weights stationary (unskewed, gemv2d), batch over DATA only,
    # cache sequence-sharded over grid rows — kills the per-step weight
    # ppermute traffic of Cannon-style decode (EXPERIMENTS.md §Perf).
    specs = param_specs(cfg, plan.grid_q, plan.grid_r,
                        preskew=pctx.preskewed)
    q, r = plan.grid_q, plan.grid_r
    dshards = plan.data_size * (plan.pod_size if plan.has_pod else 1)
    pattern = cfg.pattern()

    if per_slot and mode == "longctx":
        raise NotImplementedError(
            "per-slot decode needs a data-sharded batch dim "
            "(modes: batched, gemv)")
    sspecs: Optional[ModelStateSpecs] = None
    if paged is not None:
        if mode != "gemv":
            raise NotImplementedError(
                "paged KV rides the gemv layout (weights stationary, "
                f"pages over grid rows): mode={mode!r}")
        if s_max % paged.block_pos_stride:
            raise ValueError(
                f"s_max={s_max} must be a multiple of "
                f"block_pos_stride={paged.block_pos_stride}")
        sspecs = layer_state_specs(cfg, plan, stride=paged.block_pos_stride)

    def body(params, cache, tokens, pos, *extra):
        table = reset = slots = None
        if sspecs is not None:
            it = iter(extra)
            if sspecs.has_paged:
                table = next(it)
            if sspecs.has_dense:
                slots = next(it)
        elif per_slot:
            reset = extra[0]
        grid = pctx.grid
        i, _ = grid.my_coords()
        x = _embed_decode(pctx, params["embed"], tokens, mode,
                          cfg.compute_dtype)
        if per_slot and mode == "batched":
            # the embed reduce-scatter gave row i batch chunk i; slice the
            # per-slot operands to match
            B_pe = x.shape[0]
            pos = lax.dynamic_slice_in_dim(pos, i * B_pe, B_pe)
            reset = lax.dynamic_slice_in_dim(reset, i * B_pe, B_pe)
        if mode == "longctx":
            # this PE's cache shard covers [shard_offset, +S_loc)
            didx = jnp.zeros((), jnp.int32)
            for ax in pctx.data_axes:
                didx = didx * lax.axis_size(ax) + lax.axis_index(ax)
            s_loc = s_max // (dshards * q)
            shard_offset = (didx * q + i) * s_loc
        elif mode == "gemv":
            shard_offset = i * (s_max // q)    # rows only; batch over data
        else:
            shard_offset = 0

        def group_body(carry, xs):
            x = carry
            group_params, group_cache = xs
            new_caches = []
            for posn, (mixer, ffn) in enumerate(pattern):
                x, nc = _decode_layer(pctx, cfg, mixer, ffn,
                                      group_params[posn], x,
                                      group_cache[posn], pos, shard_offset,
                                      mode, table=table, paged=paged,
                                      slots=slots, backend=kernel_backend)
                new_caches.append(nc)
            return x, new_caches

        # strip the n_pes dim (shard_map gives local (G, 1, ...) leaves)
        local_cache = jax.tree.map(lambda c: c[:, 0], cache)
        if per_slot and paged is None:
            # recycled slots start from a clean cache (slot-reset is folded
            # into the step so each bucket keeps a single executable).
            # Paged steps need no reset: slot identity lives in the table.
            def _wipe(c):
                sel = reset.reshape((1, -1) + (1,) * (c.ndim - 2)) > 0
                return jnp.where(sel, jnp.zeros((), c.dtype), c)
            local_cache = jax.tree.map(_wipe, local_cache)
        x, new_cache = lax.scan(group_body, x,
                                (params["layers"], local_cache))
        x = _norm(pctx, cfg, params["final_norm"], x)
        logits = _last_logits(pctx, params["lm_head"], x,
                              gather_rows=(mode == "batched"))
        new_cache = jax.tree.map(lambda c: c[:, None], new_cache)
        return logits, new_cache

    pspecs = pm.param_pspecs(specs)
    cpspecs = sspecs.arena_pspecs() if sspecs is not None \
        else cache_pspecs(cfg, mode, pctx.data_axes)
    lead = tuple(pctx.data_axes) if len(pctx.data_axes) > 1 \
        else pctx.data_axes[0]
    tok_spec = P() if mode == "longctx" else P(lead)
    logit_spec = P() if mode == "longctx" else P(lead, None, None)
    if sspecs is not None:
        pos_spec = tok_spec if per_slot else P()
        in_specs = (pspecs, cpspecs, tok_spec, pos_spec) \
            + sspecs.operand_pspecs(lead)
    elif per_slot:
        in_specs = (pspecs, cpspecs, tok_spec, tok_spec, tok_spec)
    else:
        in_specs = (pspecs, cpspecs, tok_spec, P())
    return body, in_specs, (logit_spec, cpspecs), specs, pctx


def make_decode_step(cfg: ModelConfig, mesh: Mesh, plan: MeshPlan, *,
                     batch: int, s_max: int, mode: str = "batched",
                     tp_strategy: Optional[str] = None,
                     per_slot: bool = False,
                     paged: Optional[PagedKV] = None,
                     kernel_backend: Optional[str] = None):
    """serve_step(params, cache, tokens, pos[, reset|table]) -> (logits, cache).

    ``mode="batched"``: tokens (B,) sharded over data; Cannon projections.
    ``mode="longctx"``: tokens (B,) replicated; gemv2d projections over
    UNSKEWED weights (pass tp_strategy="allgather"-storage params).
    ``per_slot=True``: ``pos``/``reset`` are (B,) vectors sharded like
    ``tokens`` (continuous-batching step; see :func:`make_decode_body`).
    ``paged``: the cache operand is the physically paged arena and the
    trailing operand is the (B, T) block table (see :class:`PagedKV`).
    ``kernel_backend``: paged-path kernel selection (see
    :func:`make_decode_body`).
    """
    body, in_specs, out_specs, specs, pctx = make_decode_body(
        cfg, mesh, plan, batch=batch, s_max=s_max, mode=mode,
        tp_strategy=tp_strategy, per_slot=per_slot, paged=paged,
        kernel_backend=kernel_backend)
    mapped = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    return jax.jit(mapped, donate_argnums=(1,)), specs, pctx


def make_prefill_chunk_body(cfg: ModelConfig, mesh: Mesh, plan: MeshPlan, *,
                            batch: int, s_max: int, chunk: int,
                            paged: PagedKV,
                            kernel_backend: Optional[str] = None,
                            all_logits: bool = False):
    """Chunked multi-token prefill body: up to L tokens per slot per launch.

    The ``prefill_bs{N}_len{L}`` ABI (gemv layout, engine state arena):

        body(params, arena, tokens (B, L), pos (B,), n_valid (B,),
             *state_operands) -> (logits (B, 1, V), arena)

    where ``state_operands`` derive from the per-layer StateSpecs exactly
    like the decode step's: ``table (B, T)`` when any layer pages KV, then
    ``slots (B,)`` when any layer carries dense state.  Slot b consumes
    ``tokens[b, :n_valid[b]]`` at cache positions
    ``[pos[b], pos[b] + n_valid[b])``: the whole chunk embeds as one (B, L)
    block, paged layers scatter all valid positions' K/V into the slot's
    block-table pages inside the SAME kernel (blocked causal attention over
    the gathered pages reproduces the token-stepped prefill position for
    position), and dense layers advance their slot state through the whole
    valid prefix in one :func:`mamba_chunk_step`.  The returned logits
    belong to chunk position ``n_valid - 1`` — exactly the sampling logits
    when the chunk contains the slot's final known token (``n_valid`` may
    be 1, so a mixed batch can carry decode-phase slots through the same
    launch).  Prompt ingestion drops from O(prompt) to O(prompt / L)
    enqueues — the paper's amortize-the-offload rule applied to
    time-to-first-token.

    ``kernel_backend`` (default: :func:`repro.kernels.default_kernel_backend`)
    selects both the paged-attention kernel (gathered copy vs fused
    in-place page reads) AND the SSD scan backend used by
    :func:`repro.models.ssm.mamba_chunk_step` for dense layers.

    ``all_logits=True`` is the speculative-decoding **verify** variant of
    the same ABI: logits come back ``(B, L, V)`` — one distribution per
    chunk position — instead of the single last-valid row.  Position j's
    logits are the target model's distribution over the token at position
    ``pos + j + 1`` having attended to everything through ``pos + j``,
    which is exactly what accept/reject sampling needs to judge draft
    token j+1 (and row ``n_valid - 1`` is the bonus distribution).
    Everything else — K/V scatter, causal masking, dense-state advance,
    padding semantics past ``n_valid`` — is byte-for-byte the prefill
    path; the default ``all_logits=False`` body is unchanged, so the
    non-speculative executables stay bit-identical.
    """
    kernel_backend = kernel_backend if kernel_backend is not None \
        else default_kernel_backend()
    resolve_kernel_backend(kernel_backend)      # validate eagerly
    if not 1 <= chunk <= s_max:
        raise ValueError(f"chunk must be in [1, s_max={s_max}], got {chunk}")
    if s_max % paged.block_pos_stride:
        raise ValueError(
            f"s_max={s_max} must be a multiple of "
            f"block_pos_stride={paged.block_pos_stride}")
    pctx = make_pctx(plan, "allgather", remat=False,
                     compute_dtype=cfg.compute_dtype)
    pctx = dataclasses.replace(pctx, act_layout="repl_rows", preskewed=False)
    specs = param_specs(cfg, plan.grid_q, plan.grid_r, preskew=False)
    pattern = cfg.pattern()
    sspecs = layer_state_specs(cfg, plan, stride=paged.block_pos_stride)

    def body(params, cache, tokens, pos, n_valid, *extra):
        it = iter(extra)
        table = next(it) if sspecs.has_paged else None
        slots = next(it) if sspecs.has_dense else None
        x = _embed_decode(pctx, params["embed"], tokens, "gemv",
                          cfg.compute_dtype)

        def group_body(carry, xs):
            x = carry
            group_params, group_cache = xs
            new_caches = []
            for posn, (mixer, ffn) in enumerate(pattern):
                x, nc = _decode_layer(pctx, cfg, mixer, ffn,
                                      group_params[posn], x,
                                      group_cache[posn], pos, 0, "gemv",
                                      table=table, paged=paged,
                                      n_valid=n_valid, slots=slots,
                                      backend=kernel_backend)
                new_caches.append(nc)
            return x, new_caches

        local_cache = jax.tree.map(lambda c: c[:, 0], cache)
        x, new_cache = lax.scan(group_body, x,
                                (params["layers"], local_cache))
        if not all_logits:
            # extract each slot's last VALID chunk position before the final
            # norm + lm_head (both are pointwise over positions, so the
            # gather commutes and the vocab projection runs on 1 position,
            # not L)
            idx = jnp.clip(n_valid - 1, 0, x.shape[1] - 1)
            x = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        x = _norm(pctx, cfg, params["final_norm"], x)
        logits = _last_logits(pctx, params["lm_head"], x, gather_rows=False)
        new_cache = jax.tree.map(lambda c: c[:, None], new_cache)
        return logits, new_cache

    pspecs = pm.param_pspecs(specs)
    cpspecs = sspecs.arena_pspecs()
    lead = tuple(pctx.data_axes) if len(pctx.data_axes) > 1 \
        else pctx.data_axes[0]
    in_specs = (pspecs, cpspecs, P(lead, None), P(lead), P(lead)) \
        + sspecs.operand_pspecs(lead)
    out_specs = (P(lead, None, None), cpspecs)
    return body, in_specs, out_specs, specs, pctx


def make_prefill(cfg: ModelConfig, mesh: Mesh, plan: MeshPlan, *,
                 tp_strategy: str = "cannon",
                 extra_batch_keys: Tuple[str, ...] = ()):
    """prefill(params, batch) -> last-position logits (B, 1, V_loc blocked).

    Runs the full training-style forward (Cannon path, flash attention) and
    extracts the final position's logits; cache export for decode handoff is
    a reshard pass (batched mode) documented in DESIGN.md.
    """
    pctx = make_pctx(plan, tp_strategy, remat=False,
                     compute_dtype=cfg.compute_dtype)
    specs = param_specs(cfg, plan.grid_q, plan.grid_r, preskew=pctx.preskewed)

    def body(params, batch):
        x, aux, caches = forward(pctx, cfg, params, batch,
                                 collect_cache=False)
        grid = pctx.grid
        i, _ = grid.my_coords()
        last = x[:, -1:, :]
        last = grid.psum_rows(
            jnp.where(i == pctx.q - 1, last, jnp.zeros_like(last)))
        # `last` is row-replicated: Cannon treats it as 4 stacked copies of
        # the M block — redundant but correct; vocab gathered for a clean
        # boundary layout.
        return _last_logits(pctx, params["lm_head"], last, gather_rows=False)

    pspecs = pm.param_pspecs(specs)
    lead = tuple(pctx.data_axes) if len(pctx.data_axes) > 1 \
        else pctx.data_axes[0]
    example = {k: 0 for k in ("tokens",) + tuple(extra_batch_keys)}
    bspec = jax.tree.map(lambda _: P(lead), example)
    mapped = jax.shard_map(body, mesh=mesh, in_specs=(pspecs, bspec),
                           out_specs=P(lead, None, None), check_vma=False)
    return jax.jit(mapped), specs, pctx
