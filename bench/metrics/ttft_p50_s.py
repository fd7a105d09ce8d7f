"""Time to first token, median, over every request offered in the window,
each timed from the moment it was due (open loop).  A request that never
got a token counts as waiting until the clients stopped.  With 41 requests
a window, the 75th percentile read far off in one run of six in each set,
so the median is the number bounded (PERF.md)."""

from harness.stats import quantile


def read(ctx):
    return quantile([r.times[0] - r.due_t if r.times else ctx.t_stop - r.due_t
                     for r in ctx.recs], 0.5)
