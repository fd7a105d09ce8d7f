"""Step programs: device-busy time inside each traced pure-decode step
(``serve_step_bs{N}``), as a mean."""


def read(ctx):
    st = [s for s in ctx.red["steps"] if s["kind"] == "decode"] \
        if ctx.red else []
    return sum(s["busy"] for s in st) / len(st) / 1e6 if st else None
