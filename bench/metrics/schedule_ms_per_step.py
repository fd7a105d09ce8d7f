"""Scheduler: host time in ``scheduler.schedule()`` per launch, admission
included (the program's ``serve/schedule`` phase, ``EngineStats.schedule_ns``
over the window), in ms."""


def read(ctx):
    n, t = ctx.stats["launches"], ctx.stats.get("schedule_ns")
    return t / n / 1e6 if n and t is not None else None
