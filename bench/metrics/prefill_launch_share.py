"""Scheduler: share of the window's launches that ran a prefill-chunk
program (``EngineStats.prefill_chunk_launches / launches``), in percent.
Decode slots that ride such a launch wait for its whole chunk."""


def read(ctx):
    n = ctx.stats["launches"]
    return 100.0 * ctx.stats["prefill_chunk_launches"] / n if n else None
