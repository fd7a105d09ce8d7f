#!/usr/bin/env python3
"""Find a cell's knee once: offer a ladder of fixed rates in one process,
and report for each whether the system kept up.

    python3 bench/sweep.py --workload <cell> --rates 1,2,3,4 --seconds 40 \\
        [--write]

A rate is sustained when neither the queue of requests that are due and
have no first token nor the count of streams in flight (first token
delivered, not finished) grows over the window (each one's mean over the
last quarter is at most 1.5 times its mean over the second, plus two) and
every request due in the window got its first token within it plus 30 s.
``--warm-s`` leaves the window's first seconds out of the quarters: a mix of
sessions fills with live sessions for about one session's lifetime, and
its streams in flight grow over that time at any rate.  The knee is the
highest sustained rate; ``--write`` sets the cell's traffic file to four
fifths of it.  Each rate is offered with a seed of its own, after set-up's
``warm`` prompts of that seed, as ``run.py`` does.  Runs on the chip, like
``run.py``.

    python3 bench/sweep.py --workload qwen2-0.5b.prefix \
        --rates 0.2,0.3,0.4,0.5,0.6,0.8 --seconds 102 --warm-s 30
"""

import argparse
import asyncio
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import run  # noqa: E402


async def offer(st, rate, seconds, seed, warm_s):
    from harness import drive, files, program
    from harness.stats import quantile
    from repro.serve.service import GenerateService, ServiceConfig
    mix = dict(st.traffic, rate_per_s=rate)
    gen = files.module("traffic", mix["generator"])
    specs = gen.make(mix, seed, st.cfg.vocab_size, seconds)
    if hasattr(gen, "warm"):
        program.prime(st.eng, gen.warm(mix, specs))
    queue, inflight = [], []

    async def sample(t0, recs):
        while time.perf_counter() < t0 + seconds:
            now = time.perf_counter()
            queue.append(sum(1 for r in recs if r.due_t <= now
                             and not r.times))
            inflight.append(sum(1 for r in recs if r.times
                                and not (r.finish or r.error)))
            await asyncio.sleep(0.5)

    async with GenerateService(st.eng, ServiceConfig(
            max_pending=len(specs) + 8)) as svc:
        t0, t_end, recs = await drive.window(svc, specs, seconds,
                                             "first_token", 30.0,
                                             extra=sample,
                                             next_turn=st.next_turn)
    skip = int(warm_s / 0.5)
    queue, inflight = queue[skip:], inflight[skip:]
    q = len(queue) // 4
    quarters = lambda v: (sum(v[q:2 * q]) / q, sum(v[3 * q:]) / len(v[3 * q:]))
    early, late = quarters(queue)
    fly_early, fly_late = quarters(inflight)
    ttft = [r.times[0] - r.due_t if r.times else None for r in recs]
    itl = [b - a for r in recs for a, b in zip(r.times, r.times[1:])
           if b <= t_end]
    out_tok = sum(1 for r in recs for t in r.times if t <= t_end)
    ok = late <= 1.5 * early + 2 and fly_late <= 1.5 * fly_early + 2 \
        and all(x is not None for x in ttft)
    return {"rate_per_s": rate, "requests": len(recs), "warm_s": warm_s,
            "sustained": ok, "queue_q2": early, "queue_q4": late,
            "inflight_q2": fly_early, "inflight_q4": fly_late,
            "itl_p95_s": quantile(itl, 0.95),
            "ttft_p50_s": quantile(ttft, 0.5),
            "ttft_p90_s": quantile(ttft, 0.9),
            "output_tok_s": out_tok / (t_end - t0)}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--warm-s", type=float, default=0.0,
                    help="seconds at the window's start that the growth "
                         "test leaves out")
    ap.add_argument("--write", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the model's tiny sibling")
    args = ap.parse_args()
    extra = ["--rehearse"] if args.rehearse else []
    if not args.rehearse:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    st = run.setup(run.parse(["--workload", args.workload, "--seed",
                              str(args.seed), "--seconds",
                              str(args.seconds)] + extra))
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        # a seed of its own per rate: prompts the cache has not seen
        rows.append(asyncio.run(offer(st, rate, args.seconds,
                                      args.seed + i, args.warm_s)))
        print(json.dumps(rows[-1]), flush=True)
    knee = max([r["rate_per_s"] for r in rows if r["sustained"]],
               default=None)
    fixed = None if knee is None else round(0.8 * knee, 2)
    print(json.dumps({"knee_per_s": knee, "fixed_rate_per_s": fixed}))
    if args.write and fixed is not None:
        from harness import files
        path = os.path.join(BENCH, "traffic", st.cell["traffic"] + ".json")
        mix = files.traffic(st.cell["traffic"])
        mix["rate_per_s"] = fixed
        with open(path, "w") as f:
            json.dump(mix, f, indent=2)
            f.write("\n")


if __name__ == "__main__":
    main()
