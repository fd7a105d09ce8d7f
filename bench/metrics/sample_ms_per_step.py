"""Drive loop (sampling): host time sampling each slot's token from its
logits row and advancing its request, per launch (the program's
``serve/sample`` phase, ``EngineStats.sample_ns``), in ms."""


def read(ctx):
    n, t = ctx.stats["launches"], ctx.stats.get("sample_ns")
    return t / n / 1e6 if n and t is not None else None
