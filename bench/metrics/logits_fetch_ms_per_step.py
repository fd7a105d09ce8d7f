"""Drive loop: host time copying each launch's logits, cut to the
vocabulary, from the device to the host once they are ready, per launch
(the program's ``serve/fetch`` phase, ``EngineStats.fetch_ns``), in ms."""


def read(ctx):
    n, t = ctx.stats["launches"], ctx.stats.get("fetch_ns")
    return t / n / 1e6 if n and t is not None else None
