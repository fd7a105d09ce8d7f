"""Gap between consecutive tokens as each client stream receives them,
95th percentile over every gap that closes inside the window."""

from harness.stats import quantile


def read(ctx):
    gaps = [b - a for r in ctx.recs for a, b in zip(r.times, r.times[1:])
            if b <= ctx.t_end]
    return quantile(gaps, 0.95)
