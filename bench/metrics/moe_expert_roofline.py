"""Kernels: the held experts' grouped GEMMs (the megablox ``gmm`` kernel,
``%gmm.N`` in the trace).  The least time the chip needs for the expert
work of the traced launches, over the device time of the kernel's
operations in those launches, in percent.

The rows of a launch are its fed positions times the MoE layers times the
window's rows a position sends a layer (``EngineStats.moe_rows /
moe_positions``): the counters are per launch, the trace per step.  A row
costs ``2 x 3 x d_model x d_ff_expert`` FLOPs (gate, up, down); the bytes
are each held expert's three matrices read once a layer and launch, and
each row's hidden state in and out, in bf16.  The program holds the
expert weights in HBM at the kernel's boundary, so the kernel reads them
itself and its device time holds those reads (a weight prefetched into
VMEM by a copy beside other ops would leave them out).  None on a program
without the counters."""

from harness import flops, trace

KERNEL = r"^gmm$"


def moe_layers(s) -> int:
    pat = s["layer_pattern"]
    return s["n_layers"] // len(pat) * sum(1 for _, f in pat if f == "moe")


def expert_need(s, fed: int, rows_per_position: float):
    """(FLOPs, bytes) of one launch's held experts, every MoE layer."""
    D, F = s["d_model"], s["d_ff_expert"]
    L = moe_layers(s)
    rows = fed * rows_per_position * L
    return (rows * 2.0 * 3 * D * F,
            L * s["experts_held"] * 3.0 * D * F * flops.BF16
            + rows * 2.0 * D * flops.BF16)


def read(ctx):
    st = [s for s in ctx.red["steps"] if s["seq"] is not None] \
        if ctx.red else []
    n, rows = ctx.stats.get("moe_positions"), ctx.stats.get("moe_rows")
    if not st or not n or rows is None:
        return None
    spent = trace.kernel_ns(ctx.red, KERNEL, st) / 1e9
    if not spent:
        return None
    per = rows / n
    need = sum(flops.least_time(*expert_need(
        ctx.s, sum(f for _, f, _ in ctx.launches[s["seq"]]["slots"]), per),
        ctx.peak) for s in st)
    return 100.0 * need / spent
