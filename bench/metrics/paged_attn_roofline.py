"""Paged-attention kernel: the least time the chip needs for the work the
traced launches need (each slot's K/V up to its last fed position, its
queries and outputs, and the QK/PV FLOPs over its real context), over the
device time of the kernel's operations in those launches, in percent."""

from harness import flops, trace

KERNEL = r"^paged_attention$"


def read(ctx):
    st = [s for s in ctx.red["steps"] if s["seq"] is not None] \
        if ctx.red else []
    spent = trace.kernel_ns(ctx.red, KERNEL, st) / 1e9 if st else 0.0
    if not spent:
        return None
    need = sum(ctx.s["n_layers"] * flops.least_time(
        *flops.paged_attention_need(ctx.s, ctx.launches[s["seq"]]["slots"]),
        ctx.peak) for s in st)
    return 100.0 * need / spent
