"""Drive loop: host time building a launch's operand arrays, putting them on
the device and enqueueing the step program, per launch (the program's
``serve/operands`` phase, ``EngineStats.operand_ns``), in ms."""


def read(ctx):
    n, t = ctx.stats["launches"], ctx.stats.get("operand_ns")
    return t / n / 1e6 if n and t is not None else None
