"""Service layer: submit to first admission (the program's own
``Request.submit_t`` / ``admit_t`` stamps), 75th percentile over the
requests of the window that were admitted."""

from harness.stats import quantile


def read(ctx):
    return quantile([r.queue_wait_s for r in ctx.recs], 0.75)
