"""Whole step: model FLOPs of the positions actually fed (2 per weight per
position, the SSM recurrence, attention over each position's real context,
the output head where a slot samples) over the summed wall time of the
traced steps times the chip's peak, in percent."""

from harness import flops


def read(ctx):
    st = [s for s in ctx.red["steps"] if s["seq"] is not None] \
        if ctx.red else []
    if not st:
        return None
    work = sum(flops.launch_model_flops(ctx.s, ctx.launches[s["seq"]]["slots"])
               for s in st)
    wall = sum(s["end"] - s["start"] for s in st) / 1e9
    return 100.0 * work / (wall * ctx.peak["flops"])
