"""Kernels: share of the listed block-table entries that the paged-attention
kernel walks (``100 x EngineStats.kv_pages_walked / kv_pages_listed`` over
the window), in percent.  Each launch of a paged model lists bucket x
``s_max / stride`` entries and walks ``ceil(kv_len / stride)`` of each
occupied slot's, ``kv_len`` one past its last fed position."""


def read(ctx):
    n, k = ctx.stats.get("kv_pages_listed"), ctx.stats.get("kv_pages_walked")
    return 100.0 * k / n if n and k is not None else None
