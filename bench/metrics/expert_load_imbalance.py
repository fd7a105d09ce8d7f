"""Experts: how far the busiest held expert's rows stand above an even
split, ``100 x moe_rows_max x experts_held / moe_rows - 100`` over the
window, in percent (``EngineStats``: ``moe_rows`` the rows the held
experts computed, ``moe_rows_max`` in each MoE layer of a launch the
most rows one held expert computed, both summed over layers and
launches).  0 where every held expert gets the same rows; None on a
program without the counters."""


def read(ctx):
    rows, top = ctx.stats.get("moe_rows"), ctx.stats.get("moe_rows_max")
    held = ctx.s.get("experts_held")
    if not rows or top is None or not held:
        return None
    return 100.0 * top * held / rows - 100.0
