"""Materialized-gather reference for the fused paged-attention kernel.

This is exactly the computation the serving engine's jnp backend performs
per attention layer (:func:`gather_pages` + ``attention_partial``):
``jnp.take`` every table entry's page out of the arena into a gathered
``(B, T * stride, kvh, hd)`` copy, label each position, then run one
masked softmax partial over the run.  The fused kernel must reproduce its
row-merged output bit-closely WITHOUT ever materializing the copy — this
module is the oracle for that claim, and the thing the gather-vs-fused
microbenchmark prices.
"""

from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30
UNOWNED_POS = 2 ** 30   # past any q_pos: causally masked out (a Python
#                        int: importing this module touches no device)


def gather_pages(kc, vc, table, *, stride, row, qrows):
    """Materialize this row's pages of every slot (the copy the fused
    kernel eliminates).  Returns (kg, vg, kv_pos) with kg/vg
    ``(B, T * stride, kvh, hd)`` and kv_pos ``(B, T * stride)`` global
    position labels (unowned/unallocated entries pushed past any query).
    Routing goes through :func:`ops.table_routing` — the same mapping the
    fused kernel prefetches — so the oracle can never drift from it."""
    from repro.kernels.paged_attention.ops import table_routing
    B, T = table.shape
    kvh, hd = kc.shape[-2:]
    lidx, own = table_routing(table, row, qrows)
    own = own.astype(bool)
    lg = lidx.reshape(-1)
    kg = jnp.take(kc, lg, axis=0).reshape(B, T * stride, kvh, hd)
    vg = jnp.take(vc, lg, axis=0).reshape(B, T * stride, kvh, hd)
    pos_grid = jnp.arange(T)[:, None] * stride + jnp.arange(stride)[None, :]
    kv_pos = jnp.where(own[:, :, None], pos_grid[None],
                       UNOWNED_POS).reshape(B, T * stride)
    return kg, vg, kv_pos


def paged_attention_ref(q, kc, vc, table, q_pos, *, stride, row, qrows,
                        scale=None):
    """Gathered-copy paged attention partials ``(m, l, acc)``, fp32.

    q (B, Hq, L, hd); kc/vc (n_blocks_local, stride, kvh, hd);
    table (B, T) physical page ids (-1 unallocated); q_pos (B, L) global.
    """
    B, Hq, L, hd = q.shape
    kvh = kc.shape[-2]
    scale = scale if scale is not None else hd ** -0.5
    kg, vg, kv_pos = gather_pages(kc, vc, table, stride=stride, row=row,
                                  qrows=qrows)
    group = Hq // kvh
    kr = jnp.repeat(kg.transpose(0, 2, 1, 3), group, axis=1
                    ).astype(jnp.float32)
    vr = jnp.repeat(vg.transpose(0, 2, 1, 3), group, axis=1
                    ).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * scale, kr)
    mask = (q_pos[:, :, None] >= kv_pos[:, None, :])[:, None]
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p, vr)
    return m, l, acc


def merge_rows(partials):
    """Host-side LSE merge over per-row partials — the numpy-level mirror
    of ``combine_partials`` over the SHMEM grid rows, for oracle checks."""
    ms = jnp.stack([m for m, _, _ in partials])
    m_glob = jnp.max(ms, axis=0)
    w = jnp.exp(ms - m_glob)
    l_glob = sum(l * w[i] for i, (_, l, _) in enumerate(partials))
    acc_glob = sum(a * w[i][..., None] for i, (_, _, a) in enumerate(partials))
    return acc_glob / jnp.maximum(l_glob, 1e-30)[..., None]
