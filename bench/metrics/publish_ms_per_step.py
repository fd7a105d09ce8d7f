"""Prefix cache: host time publishing filled prompt pages to the radix cache
and dense state snapshots, per launch (the program's ``serve/publish``
phase, ``EngineStats.publish_ns``), in ms."""


def read(ctx):
    n, t = ctx.stats["launches"], ctx.stats.get("publish_ns")
    return t / n / 1e6 if n and t is not None else None
