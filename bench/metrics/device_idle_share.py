"""Device: 1 - (union of device-operation intervals / traced window), in
percent."""


def read(ctx):
    if not ctx.red:
        return None
    return 100.0 * (1.0 - ctx.red["busy_ns"] / ctx.red["window_ns"])
