"""Set-up time: process start to the first timed request (loading, making
the weights, compiling or loading every step program, warming up)."""


def read(ctx):
    return ctx.setup_s
