"""Scheduler: occupied slots over the slots of the buckets launched
(``EngineStats.slots_occupied / slots_launched`` over the window), in
percent."""


def read(ctx):
    n, k = ctx.stats.get("slots_launched"), ctx.stats.get("slots_occupied")
    return 100.0 * k / n if n and k is not None else None
