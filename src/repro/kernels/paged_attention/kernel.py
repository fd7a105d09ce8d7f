"""Pallas TPU kernel: fused paged-attention flash decode over block tables.

The paper's rule — read remote memory *in place* over the NoC instead of
staging redundant copies through the slow standard path — applied to the
serving engine's hottest loop.  The jnp paged path materializes, per layer
and per launch, a gathered ``(B, T * stride, kvh, hd)`` K/V copy
(``jnp.take`` over the page arena) before attending; this kernel instead
takes the **arena shard and the block table directly** and performs the
page gather inside the kernel: the K/V BlockSpec index maps read the
scalar-prefetched local page index of the step's table entry, so each
grid step DMAs exactly the physical page it needs HBM->VMEM (the pipeline
double-buffers it behind the step before).  No gathered intermediate ever
exists.

The walk.  Tile ``(b, qi)`` — ``tq`` chunk columns of slot b — reads
positions below its bound

    bound = min(kv_len[b], q_start[b] + (qi + 1) * tq)

(one past the tile's last causal position, cut at one past the slot's last
valid query position).  The grid is one dimension whose length is set on
the device (:func:`_work_list`): one step per table entry each tile reads,
tiles in order, entries 0 .. ``ceil(bound / stride) - 1``.  So

  * a tile at or past the slot's valid columns (``qi * tq >= kv_len -
    q_start``: chunk padding, or an idle slot with ``kv_len = 0``) takes no
    step at all, and no entry at or past a tile's bound is fetched: the
    work follows each slot's real length, not ``s_max / stride``;
  * each step copies its page into a block of ``P`` pages held in VMEM,
    and the block is scored once, at its last page: one ``(kvh, tq *
    group, P * stride)`` product per block (query heads grouped by kv
    head, no ``repeat`` of K/V), and the running ``(m, l, acc)`` rescaled
    once per block, not once per page;
  * positions mask causally in *global* coordinates: entry t covers
    positions ``[t * stride, (t+1) * stride)`` regardless of which physical
    page backs it (tables may be scrambled arbitrarily); keys at or past
    the bound, or on entries this grid row does not own (``own[b, t] ==
    0``: unallocated, or routed to another row), are zeroed and masked, so
    whatever a page holds there is never read into a result;
  * ``(m, l, acc)`` flush as **LSE partial outputs** — NOT normalized
    attention — so the SHMEM row-merge
    (``repro.models.attention.combine_partials``) composes unchanged across
    the grid rows that shard the physical page space.

The same kernel serves one-position decode (L = 1) and chunked prefill
(L = chunk); the tile sizes follow the call's static shapes
(:func:`walk_shape`).  Chunk columns past a slot's ``n_valid`` are padding
the caller never reads (the prefill body extracts the last valid position
only): they attend to the slot's valid keys where their tile is walked,
and read ``(m, l, acc) = (-1e30, 0, 0)`` where it is not — finite either
way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
KEYS_PER_BLOCK = 256    # positions of one scored block (P = this / stride)
QUERY_TILE = 64         # chunk columns per query tile, where L divides


def walk_shape(L: int, stride: int, T: int):
    """``(tq, P)``: query columns per tile and table entries per key block,
    from the call's static shapes.  ``tq`` divides ``L`` and ``P``
    divides ``T``, so no tile or block runs off its array; ``P <= 32``, so
    a block's ownership fits one int32 of bits."""
    tq = QUERY_TILE if L > QUERY_TILE and L % QUERY_TILE == 0 else L
    P = max(1, min(T, KEYS_PER_BLOCK // stride, 32))
    while T % P:
        P -= 1
    return tq, P


def _work_list(own, q_start, kv_len, *, tq, stride, pages, nQ):
    """The walk as a list of grid steps, one table entry each, built on the
    device.

    Tile ``(b, qi)`` reads positions below ``bound = min(kv_len[b],
    q_start[b] + (qi + 1) * tq)``: it steps through entries 0 ..
    ``ceil(bound / stride) - 1`` of slot b's table, and a tile at or past
    the slot's valid columns (chunk padding, an idle slot) takes no step.
    Returns ``(n_steps, steps, walked, owned)``: the step count (at least
    1); ``steps`` (B * nQ * T,) int32, ``tile * T + entry`` per step with
    ``tile = b * nQ + qi`` (the entries past the step count are never
    read); ``walked`` (B, nQ), the tiles that step; and ``owned`` (B,
    T / pages), bit i set where entry ``block * pages + i`` is this
    row's."""
    B, T = own.shape
    nK = T // pages
    i32 = jnp.int32
    qi = lax.broadcasted_iota(i32, (B, nQ), 1)
    q0 = lax.broadcast_in_dim(q_start, (B, nQ), (0,))
    kl = lax.broadcast_in_dim(kv_len, (B, nQ), (0,))
    bound = lax.min(lax.min(kl, q0 + (qi + 1) * tq),
                    lax.full_like(kl, T * stride))
    walked = qi * tq < kl - q0
    n_ent = lax.select(walked, lax.div(bound + stride - 1, stride),
                       lax.full_like(kl, 0)).reshape(B * nQ)
    end = lax.cumsum(n_ent)
    # step s belongs to the first tile whose entries end past it (the one
    # step of a launch in which no tile walks names the last tile, which
    # it only initializes)
    s = lax.broadcasted_iota(i32, (B * nQ * T, B * nQ), 0)
    ends = lax.broadcast_in_dim(end, s.shape, (1,))
    before = ends <= s
    tile = lax.min(jnp.sum(before, axis=1, dtype=i32),
                   lax.full((B * nQ * T,), B * nQ - 1, i32))
    start = jnp.max(lax.select(before, ends, lax.full_like(ends, 0)), axis=1)
    steps = tile * T + s[:, 0] - start
    bits = lax.shift_left(lax.full((B, nK, pages), 1, i32),
                          lax.broadcasted_iota(i32, (B, nK, pages), 2))
    owned = jnp.sum(lax.select(own.reshape(B, nK, pages) > 0, bits,
                               lax.full_like(bits, 0)), axis=-1, dtype=i32)
    return lax.max(end[-1], 1), steps, walked, owned


def _paged_flash_kernel(steps_ref, lidx_ref, owned_ref, q0_ref, kv_len_ref,
                        q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                        kblk, vblk, *, stride: int, pages: int,
                        n_entries: int, nQ: int, tq: int, group: int,
                        scale: float):
    T, S = n_entries, pages * stride
    step = steps_ref[pl.program_id(0)]
    # (lax.div / lax.rem: every operand is >= 0, and they lower to one op)
    tile, e = lax.div(step, T), lax.rem(step, T)
    b, qi = lax.div(tile, nQ), lax.rem(tile, nQ)
    j, i = lax.div(e, pages), lax.rem(e, pages)
    walked = qi * tq < kv_len_ref[b] - q0_ref[b]
    bound = jnp.minimum(jnp.minimum(kv_len_ref[b], q0_ref[b] + (qi + 1) * tq),
                        T * stride)
    last = (i == pages - 1) | ((e + 1) * stride >= bound)

    @pl.when(e == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    # gather the block's pages; it is scored once, at its last page
    kblk[i] = k_ref[0]
    vblk[i] = v_ref[0]

    # (the one step of a launch in which no tile walks computes nothing)
    @pl.when(walked & last)
    def _block():
        owned = owned_ref[b, j]

        def key_ok(shape, axis):
            """Keys of block j this row owns, inside the tile's bound."""
            k = lax.broadcasted_iota(jnp.int32, shape, axis)
            bits = lax.shift_right_logical(lax.full(shape, owned, jnp.int32),
                                           lax.div(k, stride))
            return ((bits & 1) > 0) & (k < bound - j * S)

        rows = q_ref.shape[3]
        f32 = jnp.float32
        q = q_ref[0, 0].astype(f32) * scale                # (kvh, rows, hd)
        # whatever an unread key holds (a NaN past the bound, a page left
        # from another block) stays out
        full = (S,) + kblk.shape[2:]
        ok_rows = jnp.broadcast_to(key_ok((S, 1, 1), 0), full)
        k, v = (lax.select(ok_rows, blk[...].reshape(full).astype(f32),
                           lax.full(full, 0.0, f32)).transpose(1, 0, 2)
                for blk in (kblk, vblk))                   # (kvh, S, hd)
        s = jnp.einsum("hrd,hsd->hrs", q, k, preferred_element_type=f32)
        q_pos = q0_ref[b] + qi * tq + lax.div(lax.broadcasted_iota(
            jnp.int32, (1, rows, S), 1), group)            # row r: column r // group
        kv_pos = j * S + lax.broadcasted_iota(jnp.int32, (1, rows, S), 2)
        mask = jnp.broadcast_to(key_ok((1, rows, S), 2) & (kv_pos <= q_pos),
                                s.shape)
        s = lax.select(mask, s, lax.full(s.shape, NEG_INF, f32))
        m_prev = m_ref[0, 0]
        m_new = lax.max(m_prev, jnp.max(s, axis=-1))
        p = lax.select(mask, jnp.exp(s - m_new[..., None]),
                       lax.full(s.shape, 0.0, f32))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[0, 0] = l_ref[0, 0] * alpha + jnp.sum(p, axis=-1)
        acc_ref[0, 0] = acc_ref[0, 0] * alpha[..., None] + jnp.einsum(
            "hrs,hsd->hrd", p, v, preferred_element_type=f32)
        m_ref[0, 0] = m_new


def paged_attention_pallas(
    q: jax.Array,            # (B, Hq, L, hd)
    kc: jax.Array,           # (n_blocks_local, stride, kvh, hd) arena shard
    vc: jax.Array,           # (n_blocks_local, stride, kvh, hd)
    lidx: jax.Array,         # (B, T) int32 local page index (clipped; see ops)
    own: jax.Array,          # (B, T) int32 1 = this row owns the entry
    q_start: jax.Array,      # (B,) int32 global position of query column 0
    kv_len: jax.Array,       # (B,) int32 one past the last valid query
    *,
    stride: int,
    scale=None,
    interpret: bool = False,
):
    """Fused paged flash-decode partials: ``(m, l, acc)`` fp32, shaped
    ``(B, Hq, L)``, ``(B, Hq, L)``, ``(B, Hq, L, hd)``.

    Query column j of slot b sits at global position ``q_start[b] + j``;
    columns at or past ``kv_len[b] - q_start[b]`` are padding.
    ``lidx``/``own`` are the block table resolved against this grid row
    (one integer pair per table entry, computed by
    :func:`ops.table_routing`); ``lidx`` rides in SMEM with the step list,
    each block's ownership bits, ``q_start`` and ``kv_len``, and the K/V
    index maps read it, so the page gather happens in the DMA engine,
    never as a materialized copy.
    """
    B, Hq, L, hd = q.shape
    _, _, kvh, _ = kc.shape
    T = lidx.shape[1]
    assert Hq % kvh == 0, (Hq, kvh)
    group = Hq // kvh
    scale = scale if scale is not None else hd ** -0.5
    tq, P = walk_shape(L, stride, T)
    nQ, rows = L // tq, tq * group
    # (B, Hq, L, hd) -> (B, nQ, kvh, tq * group, hd): a tile's query heads
    # grouped under their kv head, row = column * group + g
    qt = q.reshape(B, kvh, group, nQ, tq, hd).transpose(0, 3, 1, 4, 2, 5
                                                        ).reshape(
        B, nQ, kvh, rows, hd)
    n_steps, steps, walked, owned = _work_list(
        own, q_start.astype(jnp.int32), kv_len.astype(jnp.int32), tq=tq,
        stride=stride, pages=P, nQ=nQ)
    kernel = functools.partial(_paged_flash_kernel, stride=stride, pages=P,
                               n_entries=T, nQ=nQ, tq=tq, group=group,
                               scale=scale)

    def page_index(s, steps_ref, lidx_ref, *_):
        st = steps_ref[s]
        return lidx_ref[lax.div(st, T * nQ), lax.rem(st, T)], 0, 0, 0

    def tile_index(s, steps_ref, *_):
        tile = lax.div(steps_ref[s], T)
        return lax.div(tile, nQ), lax.rem(tile, nQ), 0, 0

    page = pl.BlockSpec((1, stride, kvh, hd), page_index)
    stats = pl.BlockSpec((1, 1, kvh, rows), tile_index)
    tile = pl.BlockSpec((1, 1, kvh, rows, hd),
                        lambda *a: tile_index(*a) + (0,))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_steps,),
        in_specs=[tile, page, page],
        out_specs=[stats, stats, tile],
        scratch_shapes=[pltpu.VMEM((P, stride, kvh, hd), kc.dtype),
                        pltpu.VMEM((P, stride, kvh, hd), vc.dtype)],
    )
    m, l, acc = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, nQ, kvh, rows), jnp.float32),
            jax.ShapeDtypeStruct((B, nQ, kvh, rows), jnp.float32),
            jax.ShapeDtypeStruct((B, nQ, kvh, rows, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="paged_attention",
    )(steps, lidx, owned, q_start.astype(jnp.int32),
      kv_len.astype(jnp.int32), qt, kc, vc)

    # a tile that took no step was never written: it reads as a row that
    # owns no key
    walked = walked[:, :, None, None]
    m = jnp.where(walked, m, NEG_INF)
    l = jnp.where(walked, l, 0.0)
    acc = jnp.where(walked[..., None], acc, 0.0)

    def untile(x):           # (B, nQ, kvh, rows, ...) -> (B, Hq, L, ...)
        tail = x.shape[4:]
        x = x.reshape(B, nQ, kvh, tq, group, *tail)
        x = jnp.moveaxis(x, (1, 3), (3, 4))              # (B,kvh,g,nQ,tq,..)
        return x.reshape(B, Hq, L, *tail)

    return untile(m), untile(l), untile(acc)
