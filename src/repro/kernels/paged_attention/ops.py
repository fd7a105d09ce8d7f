"""Public paged-attention op: in-place page reads with two backends.

  * ``backend="jnp"``     — the materialized-gather reference (ref.py):
    identical math to the serving engine's historical paged path; keeps a
    gathered ``(B, T * stride, kvh, hd)`` K/V copy alive per call.
  * ``backend="pallas"``  — the fused kernel (kernel.py): the block table
    and each slot's ``kv_len`` ride as scalar-prefetch operands and every
    page a query tile needs is DMA'd from the arena once, in place;
    compiled for the TPU, or run through the Pallas interpreter with
    ``interpret=True`` (CPU CI).

Both return **LSE partials** ``(m, l, acc)`` over this grid row's pages, so
the SHMEM row-merge (``combine_partials``) downstream is backend-blind.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.ref import paged_attention_ref


class PagedPartial(NamedTuple):
    """Per-shard softmax partials (attribute-compatible with
    ``repro.models.attention.AttnPartial``)."""
    m: jax.Array      # (B, Hq, L)
    l: jax.Array      # (B, Hq, L)
    acc: jax.Array    # (B, Hq, L, hd)


def table_routing(table: jax.Array, row, qrows: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """Resolve a ``(B, T)`` block table against one grid row.

    Physical page ``p`` lives on row ``p % qrows`` at local index
    ``p // qrows`` — the single source of truth for gather routing
    (:func:`ref.gather_pages` — which the serving jnp path calls — uses
    this too; the K/V *scatter* in
    the decode bodies must keep using the same mapping).  Returns
    ``(lidx, own)`` int32: the clipped local index (unowned entries read
    page 0, which the mask discards) and the ownership flag.
    """
    own = (table >= 0) & (table % qrows == row)
    lidx = jnp.where(own, table // qrows, 0).astype(jnp.int32)
    return lidx, own.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("stride", "qrows", "scale",
                                             "backend", "interpret"))
def paged_attention(q: jax.Array, kc: jax.Array, vc: jax.Array,
                    table: jax.Array, q_pos: jax.Array, kv_len: jax.Array,
                    *, stride: int,
                    row, qrows: int, scale: Optional[float] = None,
                    backend: str = "jnp",
                    interpret: bool = False) -> PagedPartial:
    """Paged flash-decode partials of q against this row's arena shard.

    q (B, Hq, L, hd) — L = 1 for decode, L = chunk for chunked prefill;
    kc/vc (n_blocks_local, stride, kvh, hd) local page arena;
    table (B, T) physical page ids (-1 = unallocated);
    q_pos (B, L) global query positions, ``q_pos[b, j] = q_pos[b, 0] + j``;
    kv_len (B,) one past each slot's last valid query position (0 for an
    idle slot): the fused kernel walks no table entry at or past it, and
    columns at or past it are padding whose partials the caller never
    reads (the jnp reference ignores it); ``row`` may be traced (the grid
    row index under shard_map).
    """
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"unknown paged_attention backend {backend!r}: "
                         f"valid values are ('jnp', 'pallas')")
    if backend == "jnp":
        m, l, acc = paged_attention_ref(q, kc, vc, table, q_pos,
                                        stride=stride, row=row, qrows=qrows,
                                        scale=scale)
        return PagedPartial(m, l, acc)
    lidx, own = table_routing(table, row, qrows)
    m, l, acc = paged_attention_pallas(q, kc, vc, lidx, own, q_pos[:, 0],
                                       kv_len, stride=stride, scale=scale,
                                       interpret=interpret)
    return PagedPartial(m, l, acc)
