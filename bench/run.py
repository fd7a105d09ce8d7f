#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and makes its weights from the seed, warms
up every step program, offers the cell's traffic for ``--seconds`` through
the program's ``GenerateService``, checks what was served against the
plain reference, and prints one JSON line last on standard output:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared beside its limit (also the last lines on standard error).

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.  ``--rehearse`` runs on the CPU against the
program's tiny sibling of the model with interpreted kernels: it checks
the harness end to end and reports no device number.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

# what a CPU rehearsal offers instead of the mix's lengths and load
REHEARSAL_TRAFFIC = {"prompt": {"dist": "uniform", "min": 20, "max": 60},
                     "output": {"dist": "uniform", "min": 8, "max": 24},
                     "rate_per_s": 20.0, "requests": 12}
FINISH_S = 60.0     # how long a first token due in the window may take


class Compiles:
    """Counts backend compilations and persistent-cache hits and misses."""

    def __init__(self):
        import jax
        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, _secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the model's tiny sibling")
    ap.add_argument("--control", action="store_true",
                    help="score the float8 reference's first choices in "
                         "place of the served tokens: must come out not "
                         "correct (never part of a benchmark run)")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler trace into this directory")
    ap.add_argument("--dump", default=None,
                    help="write every request's times (seconds from the "
                         "window's start) to this JSON file")
    return ap.parse_args(argv)


def device_info(jax, chips):
    devs = jax.devices()[:chips]
    peaks = [d.memory_stats() or {} for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(p.get("peak_bytes_in_use", 0)
                                     for p in peaks)}


def setup(args, patch=None):
    """Everything before the window.  ``patch(eng)`` lets a test break
    the timed path underneath."""
    import jax
    from harness import files, program
    cell = files.cell(args.workload)
    chips = cell["chips"]
    devs = jax.devices()
    if not args.rehearse and (devs[0].platform != "tpu"
                              or len(devs) < chips):
        raise SystemExit(f"JAX finds {len(devs)} {devs[0].platform} "
                         f"device(s); the cell needs {chips} TPU chip(s)")
    conf = files.config(cell["config"])
    traffic = dict(files.traffic(cell["traffic"]))
    if args.rehearse:
        traffic.update({k: v for k, v in REHEARSAL_TRAFFIC.items()
                        if k in traffic or k in ("prompt", "output")})
    cfg = program.model_config(conf, args.rehearse)
    s = program.sizes(cfg, conf, args.rehearse)
    params = program.make_weights(conf, cfg, s, program.mesh_for(chips),
                                  args.seed)
    eng = program.build(conf, cfg, chips, params, args.rehearse)
    if patch is not None:
        patch(eng)
    program.warm_up(eng)
    log = program.LaunchLog(eng) if args.trace else None
    gen = files.module("traffic", traffic["generator"])
    specs = gen.make(traffic, args.seed, cfg.vocab_size, args.seconds)
    next_turn = functools.partial(gen.next_turn, traffic) \
        if hasattr(gen, "next_turn") else None
    return types.SimpleNamespace(cell=cell, conf=conf, traffic=traffic,
                                 cfg=cfg, s=s, params=params, eng=eng,
                                 log=log, specs=specs, chips=chips,
                                 next_turn=next_turn)


async def serve(st, args, counter):
    """The measured window, and the tracer beside it."""
    import jax
    from harness import drive, program
    from repro.serve.service import GenerateService, ServiceConfig
    marks = {}
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None

    async def side(t0, _recs):
        loop = asyncio.get_running_loop()
        await asyncio.sleep(max(0.0, t0 - time.perf_counter()))
        marks["stats0"], marks["compiles0"] = program.stats(st.eng), \
            counter.compiles
        if args.trace:
            w = min(6.0, 0.5 * args.seconds)
            await asyncio.sleep(max(0.0, t0 + (args.seconds - w) / 2
                                    - time.perf_counter()))
            await loop.run_in_executor(None, jax.profiler.start_trace,
                                       trace_dir)
            await asyncio.sleep(w)
            await loop.run_in_executor(None, jax.profiler.stop_trace)
        await asyncio.sleep(max(0.0, t0 + args.seconds
                                - time.perf_counter()))
        marks["stats1"], marks["compiles1"] = program.stats(st.eng), \
            counter.compiles

    svc = GenerateService(st.eng, ServiceConfig(
        max_pending=len(st.specs) + 8))
    async with svc:
        t0, t_end, recs = await drive.window(
            svc, st.specs, args.seconds, st.traffic["after_window"],
            FINISH_S, extra=side, next_turn=st.next_turn)
        t_stop = time.perf_counter()
    delta = {k: marks["stats1"][k] - marks["stats0"][k]
             for k in marks["stats0"]}
    return types.SimpleNamespace(
        t0=t0, t_end=t_end, t_stop=t_stop, recs=recs, stats=delta,
        compiles_in_window=marks["compiles1"] - marks["compiles0"],
        trace_dir=trace_dir)


def reduce_trace(w, args):
    import glob
    from harness import trace
    paths = glob.glob(os.path.join(w.trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise RuntimeError("the profiler wrote no trace")
    if args.keep_trace:
        os.makedirs(args.keep_trace, exist_ok=True)
        shutil.copy(paths[0], os.path.join(
            args.keep_trace, f"{args.workload}.{args.seed}.xplane.pb"))
    try:
        red = trace.reduce(trace.load(paths[0]))
    except ValueError:
        if not args.rehearse:       # a CPU trace has no device plane
            raise
        red = None
    shutil.rmtree(w.trace_dir, ignore_errors=True)
    return red


def verdict(st, w, args):
    """The comparison with the plain reference, and the request checks."""
    from harness import check
    vocab = st.cfg.vocab_size
    bad = 0
    for r in w.recs:
        in_window = r.spec["due"] < args.seconds
        if r.error is not None:
            bad += 1
        elif r.finish is not None:
            if r.finish != "length" or len(r.tokens) != r.spec["max_tokens"]:
                bad += 1
        elif not r.times and in_window \
                and st.traffic["after_window"] == "first_token":
            bad += 1            # due in the window, and no first token
        if any(not 0 <= t < vocab for t in r.tokens):
            bad += 1
    limits = st.cell["rehearsal_limits" if args.rehearse else "limits"]
    chosen = check.sample(w.recs, st.cell["check_sample"], args.seed)
    ref = st.conf["reference"]
    got = check.gaps(ref, st.s, check.reference_weights(ref, st.s, args.seed),
                     chosen, control=args.control) if chosen else {}
    checks = {
        "logit_gap": {"value": got.get("logit_gap"),
                      "limit": limits["logit_gap"]},
        "bad_requests": {"value": bad, "limit": 0},
        "requests_compared": {"value": len(chosen), "limit": 1,
                              "at_least": True},
    }
    ok = all(c["value"] is not None and (
        c["value"] >= c["limit"] if c.get("at_least")
        else c["value"] <= c["limit"]) for c in checks.values())
    extra = {"tokens_compared": got.get("tokens_compared", 0)}
    if "served_logit_gap" in got:
        extra["served_logit_gap"] = got["served_logit_gap"]
    return ok, checks, extra


def main(argv=None, patch=None) -> int:
    args = parse(argv)
    if not args.rehearse:
        # JAX's persistent compilation cache, at a fixed path inside the
        # checkout; the program takes the directory it is given here
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    import jax
    from harness import files, flops, trace
    counter = Compiles()
    st = setup(args, patch)
    setup_hits, setup_misses = counter.hits, counter.misses
    w = asyncio.run(serve(st, args, counter))
    dev = device_info(jax, st.chips)
    red = reduce_trace(w, args) if args.trace else None
    st.eng = st.params = None       # the program's state, freed
    gc.collect()
    ok, checks, extra = verdict(st, w, args)
    if args.dump:
        dump(w, args.dump)

    ctx = types.SimpleNamespace(
        recs=[r for r in w.recs if r.spec["due"] < args.seconds],
        t0=w.t0, t_end=w.t_end, t_stop=w.t_stop, seconds=args.seconds,
        setup_s=w.t0 - T_START, stats=w.stats, red=red,
        launches=st.log.launches if st.log else None, s=st.s,
        peak=None if args.rehearse else flops.peak(dev["kind"]))
    metrics = {}
    for m in files.metrics_for(args.workload, bool(args.trace)):
        v = files.reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if red is not None:
        dev["busy_s"] = red["busy_ns"] / 1e9
        dev["window_s"] = red["window_ns"] / 1e9
    n_tok = sum(len(r.tokens) for r in ctx.recs)
    print(json.dumps({
        "setup_s": ctx.setup_s, "compiles_in_window": w.compiles_in_window,
        "setup_cache_hits": setup_hits, "setup_cache_misses": setup_misses,
        "requests_offered": len(ctx.recs), "tokens_served": n_tok,
        "generator_late_p99_s": _late(w.recs), **extra}), file=sys.stderr)
    result = {"correct": ok, "attempted": len(ctx.recs),
              "failed": sum(1 for r in ctx.recs if r.error is not None
                            or (not r.times and st.traffic["after_window"]
                                == "first_token")),
              "metrics": metrics, "device": dev}
    if args.rehearse:
        # not device numbers: shown on standard error, never in the result
        print("rehearsal (CPU) metrics: " + json.dumps(metrics),
              file=sys.stderr)
        result["metrics"], result["rehearsal"] = {}, True
    if red is not None:
        result["breakdown"] = trace.breakdown(red)
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def dump(w, path):
    rel = lambda t: t - w.t0 if t else None
    with open(path, "w") as f:
        json.dump({"t_end": rel(w.t_end), "t_stop": rel(w.t_stop), "recs": [
            {"due": r.spec["due"], "submit": rel(r.submit_t),
             "admit": rel(getattr(r.request, "admit_t", None)),
             "prompt": len(r.spec["prompt"]), "max_tokens": r.spec["max_tokens"],
             "greedy": r.spec["temperature"] == 0.0, "finish": r.finish,
             "error": r.error, "times": [rel(t) for t in r.times]}
            for r in w.recs]}, f)


def _late(recs):
    from harness.stats import quantile
    return quantile([r.submit_t - r.due_t for r in recs if r.submit_t], 0.99)


if __name__ == "__main__":
    sys.exit(main())
