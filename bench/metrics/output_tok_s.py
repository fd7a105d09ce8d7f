"""Output tokens delivered to clients inside the window, over the window."""


def read(ctx):
    n = sum(1 for r in ctx.recs for t in r.times if ctx.t0 <= t <= ctx.t_end)
    return n / (ctx.t_end - ctx.t0)
