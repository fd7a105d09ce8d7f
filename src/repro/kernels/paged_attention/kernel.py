"""Pallas TPU kernel: fused paged-attention flash decode over block tables.

The paper's rule — read remote memory *in place* over the NoC instead of
staging redundant copies through the slow standard path — applied to the
serving engine's hottest loop.  The jnp paged path materializes, per layer
and per launch, a gathered ``(B, T * stride, kvh, hd)`` K/V copy
(``jnp.take`` over the page arena) before attending; this kernel instead
takes the **arena shard and the block table directly** and performs the
page gather inside the kernel: the K/V BlockSpec index maps read the
scalar-prefetched local page index, so each grid step DMAs exactly one
physical page HBM->VMEM and streams it through the running flash-decode
statistics.  No gathered intermediate ever exists.

Grid: ``(B, T)`` — slot-major, table entries innermost ("arbitrary": the
running (m, l, acc) scratch carries across t).  Per (b, t) the kernel

  * skips the *compute* for pages this grid row does not own
    (``own[b, t] == 0``: entry is unallocated, or the physical id routes
    to another row) via ``pl.when`` — no MXU/VPU work and no accumulator
    update; the block pipeline still prefetches the (clipped) page 0 pair
    for those steps, a known cost of the dense ``(B, T)`` grid;
  * masks positions causally in *global* coordinates: page t covers
    positions ``[t * stride, (t+1) * stride)`` regardless of which
    physical page backs it (tables may be scrambled arbitrarily);
  * accumulates streaming-softmax partials, flushed as ``(m, l, acc)``
    **LSE partial outputs** — NOT normalized attention — so the SHMEM
    row-merge (``repro.models.attention.combine_partials``) composes
    unchanged across the grid rows that shard the physical page space.

The same kernel serves one-position decode (L = 1) and chunked prefill
(L = chunk): chunk columns past a slot's ``n_valid`` produce garbage
partials that the caller never reads (the prefill body extracts the last
valid position only), exactly like the jnp path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_flash_kernel(lidx_ref, own_ref, q_ref, k_ref, v_ref, qpos_ref,
                        m_ref, l_ref, acc_ref, m_s, l_s, acc_s, *,
                        n_entries: int, stride: int, group: int,
                        scale: float):
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(own_ref[b, t] > 0)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale      # (Hq, L, hd)
        k = k_ref[0].astype(jnp.float32)              # (stride, kvh, hd)
        v = v_ref[0].astype(jnp.float32)
        # GQA: q head h attends stored kv head h // group
        kr = jnp.repeat(k.transpose(1, 0, 2), group, axis=0)  # (Hq, stride, hd)
        vr = jnp.repeat(v.transpose(1, 0, 2), group, axis=0)
        s = jnp.einsum("hld,hsd->hls", q, kr)         # (Hq, L, stride)
        L = q.shape[1]
        # table entry t labels positions [t*stride, (t+1)*stride) no matter
        # which physical page backs it — the causal mask runs on the LABELS
        kv_pos = t * stride + jax.lax.broadcasted_iota(
            jnp.int32, (L, stride), 1)
        mask = qpos_ref[0, 0][:, None] >= kv_pos
        s = jnp.where(mask[None], s, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=-1)
        acc_s[...] = acc_s[...] * alpha[..., None] + jnp.einsum(
            "hls,hsd->hld", p, vr)
        m_s[...] = m_new

    @pl.when(t == n_entries - 1)
    def _flush():
        m_ref[0] = m_s[...]
        l_ref[0] = l_s[...]
        acc_ref[0] = acc_s[...]


def paged_attention_pallas(
    q: jax.Array,            # (B, Hq, L, hd)
    kc: jax.Array,           # (n_blocks_local, stride, kvh, hd) arena shard
    vc: jax.Array,           # (n_blocks_local, stride, kvh, hd)
    lidx: jax.Array,         # (B, T) int32 local page index (clipped; see ops)
    own: jax.Array,          # (B, T) int32 1 = this row owns the entry
    q_pos: jax.Array,        # (B, L) int32 global query positions
    *,
    stride: int,
    scale=None,
    interpret: bool = False,
):
    """Fused paged flash-decode partials: ``(m, l, acc)`` fp32.

    ``lidx``/``own`` are the scalar-prefetch form of the block table (one
    integer pair per table entry, computed by :func:`ops.table_routing`);
    the K/V index maps read ``lidx`` so the page gather happens in the DMA
    engine, never as a materialized copy.
    """
    B, Hq, L, hd = q.shape
    _, _, kvh, _ = kc.shape
    T = lidx.shape[1]
    assert Hq % kvh == 0, (Hq, kvh)
    scale = scale if scale is not None else hd ** -0.5
    kernel = functools.partial(
        _paged_flash_kernel, n_entries=T, stride=stride, group=Hq // kvh,
        scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, T),
        in_specs=[
            pl.BlockSpec((1, Hq, L, hd), lambda b, t, lidx, own: (b, 0, 0, 0)),
            # the in-kernel gather: entry (b, t)'s page is DMA'd straight
            # from the arena at the scalar-prefetched local index
            pl.BlockSpec((1, stride, kvh, hd),
                         lambda b, t, lidx, own: (lidx[b, t], 0, 0, 0)),
            pl.BlockSpec((1, stride, kvh, hd),
                         lambda b, t, lidx, own: (lidx[b, t], 0, 0, 0)),
            # (B, 1, L): a (1, L) block of a 2-D (B, L) array breaks the
            # TPU rule that a block's last two dims tile by (8, 128) or
            # span the array
            pl.BlockSpec((1, 1, L), lambda b, t, lidx, own: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Hq, L), lambda b, t, lidx, own: (b, 0, 0)),
            pl.BlockSpec((1, Hq, L), lambda b, t, lidx, own: (b, 0, 0)),
            pl.BlockSpec((1, Hq, L, hd), lambda b, t, lidx, own: (b, 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((Hq, L), jnp.float32),
            pltpu.VMEM((Hq, L), jnp.float32),
            pltpu.VMEM((Hq, L, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, L), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, L), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, L, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="paged_attention",
    )(lidx, own, q, kc, vc, q_pos[:, None, :])
