"""Step programs: share of the positions of prefill-chunk launches (bucket x
chunk length) that fed no token (``1 - EngineStats.chunk_positions_fed /
chunk_positions`` over the window), in percent.  Decode slots riding a
chunk feed one position of it."""


def read(ctx):
    n, k = ctx.stats.get("chunk_positions"), \
        ctx.stats.get("chunk_positions_fed")
    return 100.0 * (1.0 - k / n) if n and k is not None else None
