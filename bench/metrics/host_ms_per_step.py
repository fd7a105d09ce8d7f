"""Drive loop: host time per step, the wall time of each traced
``bench/step`` span less the device-busy time inside it, as a mean."""


def read(ctx):
    st = [s for s in ctx.red["steps"] if s["kind"]] if ctx.red else []
    if not st:
        return None
    return sum(s["end"] - s["start"] - s["busy"] for s in st) / len(st) / 1e6
