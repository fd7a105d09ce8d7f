"""Open-loop service latency: Poisson arrivals through GenerateService.

``serve_throughput.py`` measures the engine closed-loop (submit everything,
drain); this bench measures what a CLIENT sees: requests arrive on a seeded
Poisson process at ``--rate`` arrivals/sec — open loop, so arrivals do NOT
wait for completions and an overloaded service shows up as a growing TTFT
tail instead of a silently throttled workload.  Each arrival is one asyncio
client streaming its own tokens; the record is the latency DISTRIBUTION
(p50/p99 TTFT, inter-token latency, queue wait) plus the admission
outcomes (completed / shed / rejected).

The default full run sweeps one under-capacity and one over-capacity rate
under ``fifo`` admission, then repeats the over-capacity rate under
``deadline`` admission with the same seed and a TTFT SLO on every request:
the paired records show load shedding converting an unbounded fifo tail
into a bounded accepted-request tail (deadline p99 TTFT < fifo p99 TTFT at
the same arrival rate).

``BENCH_serve.json`` is the same append-only trajectory
``serve_throughput.py`` writes: full runs append one record per
(rate, policy) cell; explicit single-rate runs (CI's service-smoke) leave
it alone unless ``--json`` is passed.

Standalone:
  XLA_FLAGS=--xla_force_host_platform_device_count=16 \\
  PYTHONPATH=src python benchmarks/serve_service.py \\
      [--rate 4 --requests 16 --seed 0]          # smoke (CI) form
"""

from __future__ import annotations

import argparse
import asyncio
import datetime
import os
import sys

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402

from repro.partition import DATA, MODEL, MeshPlan  # noqa: E402
from repro.runtime.compile_cache import use_compile_cache  # noqa: E402
from repro.serve.engine import (EngineConfig, SamplingParams,  # noqa: E402
                                build_engine, generate)
from repro.serve.service import (AdmissionRejected,  # noqa: E402
                                 GenerateService, ServiceConfig)

from serve_throughput import (JSON_PATH, S_MAX,  # noqa: E402
                              _append_trajectory, _bench_config)


def _workload(rng, vocab, n):
    """Seeded prompts + decode lengths (distinct from the arrival process
    so rate sweeps at one seed serve the SAME requests)."""
    prompts = [rng.integers(0, vocab, size=int(rng.integers(2, 12))).tolist()
               for _ in range(n)]
    n_toks = [int(rng.integers(4, 12)) for _ in range(n)]
    return prompts, n_toks


async def _drive(eng, *, admission, est_ttft_s, prompts, n_toks, rate,
                 arrival_seed, ttft_slo_s, max_pending):
    """One open-loop pass: Poisson arrivals, every client drains its own
    stream concurrently.  Returns the service metrics snapshot."""
    svc_cfg = ServiceConfig(max_pending=max_pending, admission=admission,
                            est_ttft_s=est_ttft_s)
    gaps = np.random.default_rng(arrival_seed).exponential(
        1.0 / rate, size=len(prompts))

    async def client(prompt, max_tokens):
        try:
            stream = await svc.submit(prompt, max_tokens=max_tokens,
                                      ttft_deadline_s=ttft_slo_s)
        except AdmissionRejected:
            return None
        return await stream.drain()

    async with GenerateService(eng, svc_cfg) as svc:
        tasks = []
        for prompt, max_tokens, gap in zip(prompts, n_toks, gaps):
            await asyncio.sleep(gap)            # open loop: arrivals don't
            tasks.append(asyncio.create_task(   # wait for completions
                client(prompt, max_tokens)))
        results = await asyncio.gather(*tasks)
        snap = svc.metrics.snapshot()
    return results, snap


def _check_invariants(results, snap):
    """The service-smoke gate: every ACCEPTED request ran to completion
    (finish_reason length/stop, or an explicit policy shed — never hung or
    errored) and, when anything produced a token, p99 TTFT is finite."""
    accepted = [r for r in results if r is not None]
    for toks, comp in accepted:
        if comp.finish_reason not in ("stop", "length", "shed"):
            raise RuntimeError(
                f"accepted request ended '{comp.finish_reason}'")
        if comp.finish_reason == "shed" and toks:
            raise RuntimeError("shed request emitted tokens")
    n_done = snap["completed"] + snap["shed"]
    if n_done != len(accepted):
        raise RuntimeError(
            f"{len(accepted)} accepted but {n_done} reached a terminal "
            f"metrics record")
    p99 = snap["ttft_s"]["p99"]
    if snap["completed"] and not (p99 is not None and np.isfinite(p99)):
        raise RuntimeError(f"p99 TTFT not finite: {p99}")


def run(report, *, rate=None, requests=64, seed=0, admission=None,
        config=None, ttft_slo_s=0.5, json_path="auto", timestamp=None):
    # explicit --rate = a smoke/spot run: never touches the committed
    # trajectory unless --json asks; the default sweep appends
    if json_path == "auto":
        json_path = None if rate is not None else JSON_PATH
    cfg = _bench_config(config)
    mesh = jax.make_mesh((1, 16), (DATA, MODEL),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    plan = MeshPlan((DATA, MODEL), (1, 16), 4, 4)
    ec = EngineConfig(s_max=S_MAX, buckets=(1, 2, 4, 8), block_pos_stride=8)
    eng = build_engine(cfg, mesh, plan, engine_cfg=ec, seed=0)

    rng = np.random.default_rng(seed)
    prompts, n_toks = _workload(rng, cfg.vocab_size, requests)

    # warm every bucket executable (prefills warm the chunk kernels too),
    # then one untimed service pass so mixed prefill/decode bucket combos
    # only reachable under staggered arrivals are compiled too: an
    # open-loop latency record must not charge XLA compiles to TTFT
    for b in ec.buckets:
        generate(eng, prompts[:b], SamplingParams(max_tokens=1))
    asyncio.run(_drive(
        eng, admission="fifo", est_ttft_s=0.0, prompts=prompts[:16],
        n_toks=n_toks[:16], rate=8.0, arrival_seed=seed,
        ttft_slo_s=None, max_pending=requests))

    if rate is not None:
        cells = [(float(rate), admission or "fifo")]
    else:
        # under-capacity fifo, over-capacity fifo, over-capacity deadline:
        # the last two pair up as the shed-vs-tail comparison
        over = 100.0
        cells = [(2.0, admission)] if admission else \
            [(2.0, "fifo"), (over, "fifo"), (over, "deadline")]

    ts = timestamp or datetime.datetime.now(
        datetime.timezone.utc).isoformat(timespec="seconds")
    p99_by_cell = {}
    for cell_rate, cell_admission in cells:
        results, snap = asyncio.run(_drive(
            eng, admission=cell_admission, est_ttft_s=0.05,
            prompts=prompts, n_toks=n_toks, rate=cell_rate,
            arrival_seed=seed + 1, ttft_slo_s=ttft_slo_s,
            max_pending=max(1, requests)))
        _check_invariants(results, snap)
        tag = f"rate{cell_rate:g}.{cell_admission}"
        p99_by_cell[(cell_rate, cell_admission)] = snap["ttft_s"]["p99"]
        report(f"service.{tag}.accepted", snap["submitted"],
               f"of {requests} offered ({snap['rejected']} rejected)")
        report(f"service.{tag}.completed", snap["completed"],
               f"{snap['shed']} shed by admission policy")
        for key in ("ttft_s", "itl_s", "queue_wait_s"):
            st = snap[key]
            if st["n"]:
                report(f"service.{tag}.{key}.p50", f"{st['p50']:.4f}",
                       f"p99 {st['p99']:.4f} over {st['n']}")
        if json_path:
            n = _append_trajectory(json_path, {
                "bench": "serve_service",
                "config": cfg.name,
                "admission": cell_admission,
                "rate_per_s": cell_rate,
                "requests": requests,
                "seed": seed,
                "ttft_slo_s": ttft_slo_s,
                "timestamp": ts,
                "accepted": snap["submitted"],
                "completed": snap["completed"],
                "shed": snap["shed"],
                "rejected": snap["rejected"],
                "tokens": snap["tokens"],
                "preemptions": eng.scheduler.n_preemptions,
                **{key: {s: (round(v, 5) if isinstance(v, float) else v)
                         for s, v in snap[key].items()}
                   for key in ("ttft_s", "itl_s", "queue_wait_s")},
            })
            report(f"service.{tag}.json", os.path.relpath(json_path),
                   f"trajectory appended ({n} records)")

    fifo_p99 = p99_by_cell.get((100.0, "fifo"))
    edf_p99 = p99_by_cell.get((100.0, "deadline"))
    if fifo_p99 is not None and edf_p99 is not None:
        report("service.overload.p99_ttft_fifo_vs_deadline",
               f"{fifo_p99:.4f}/{edf_p99:.4f}",
               "deadline sheds infeasible requests; accepted tail stays "
               "under the SLO")
    return p99_by_cell


def run_speculation(report, *, requests=8, rate=16.0, seed=0, config=None,
                    json_path="auto", timestamp=None, smoke=False):
    """Paired open-loop passes (speculation off / n-gram drafter) over one
    repetitive workload: the SERVICE-level view of speculative decoding.

    ``serve_throughput.py --speculation`` owns the closed-loop >= 2x claim;
    this pass shows what concurrent streaming clients see — the metrics
    snapshot's ``speculation`` counters (proposed / accepted / accept_rate,
    folded in per pump from EngineStats deltas) and the per-request decode
    token rate — and appends one record per cell so the trajectory holds
    the off/ngram pair under identical Poisson arrivals."""
    from serve_throughput import _spec_workload
    from repro.kernels import default_kernel_backend
    from repro.serve.spec import SpeculationConfig
    if json_path == "auto":
        json_path = None if smoke else JSON_PATH
    kernel_backend = default_kernel_backend()
    cfg = _bench_config("spec-bench" if config in (None, "srv-bench")
                        else config)
    mesh = jax.make_mesh((1, 16), (DATA, MODEL),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    plan = MeshPlan((DATA, MODEL), (1, 16), 4, 4)
    spec_k, ngram_max = 6, 3
    plen, tail = (12, 8) if smoke else (32, 24)
    max_tokens = 16 if smoke else 32
    s_max = -(-(4 + plen + max_tokens + 8) // 16) * 16
    prompts = _spec_workload(cfg, mesh, plan, kernel_backend,
                             np.random.default_rng(seed), requests, plen,
                             tail, spec_k, ngram_max, s_max)
    n_toks = [max_tokens] * requests

    ts = timestamp or datetime.datetime.now(
        datetime.timezone.utc).isoformat(timespec="seconds")
    outs = {}
    for label, speculation in (
            ("off", None),
            ("ngram", SpeculationConfig(drafter="ngram", k=spec_k,
                                        ngram_max=ngram_max))):
        ec = EngineConfig(s_max=s_max, buckets=(1, 2, 4, 8),
                          block_pos_stride=8, speculation=speculation)
        eng = build_engine(cfg, mesh, plan, engine_cfg=ec, seed=0)
        # untimed closed-loop pass compiles every executable (incl.
        # verify_bs{N}) so the open-loop pass doesn't charge XLA to TTFT
        generate(eng, prompts, SamplingParams(max_tokens=max_tokens))
        results, snap = asyncio.run(_drive(
            eng, admission="fifo", est_ttft_s=0.0, prompts=prompts,
            n_toks=n_toks, rate=rate, arrival_seed=seed + 1,
            ttft_slo_s=None, max_pending=requests))
        _check_invariants(results, snap)
        comps = [comp for r in results if r is not None for _, comp in [r]]
        # request ids keep counting across engines; pair streams by prompt
        outs[label] = sorted((tuple(c.prompt), tuple(c.tokens))
                             for c in comps)
        dec = [c.decode_tok_s for c in comps if c.decode_tok_s is not None]
        dec_mean = float(np.mean(dec)) if dec else 0.0
        spec_snap = snap["speculation"]
        tag = f"service.spec.{label}"
        report(f"{tag}.decode_tok_s_mean", f"{dec_mean:.1f}",
               f"per-request decode rate over {len(dec)} streaming clients")
        if speculation is not None:
            ar = spec_snap["accept_rate"]
            report(f"{tag}.accept_rate",
                   f"{ar:.2f}" if ar is not None else "n/a",
                   f"{spec_snap['accepted']}/{spec_snap['proposed']} draft "
                   f"tokens accepted (service metrics snapshot)")
        if json_path:
            n = _append_trajectory(json_path, {
                "bench": "serve_service",
                "mode": "speculation",
                "speculation": label,
                "config": cfg.name,
                "admission": "fifo",
                "rate_per_s": rate,
                "requests": requests,
                "seed": seed,
                "timestamp": ts,
                "completed": snap["completed"],
                "tokens": snap["tokens"],
                "decode_tok_s_mean": round(dec_mean, 2),
                "proposed_tokens": spec_snap["proposed"],
                "accepted_tokens": spec_snap["accepted"],
                "rejected_tokens": spec_snap["rejected"],
                "accept_rate": round(spec_snap["accept_rate"], 4)
                if spec_snap["accept_rate"] is not None else None,
                **{key: {s: (round(v, 5) if isinstance(v, float) else v)
                         for s, v in snap[key].items()}
                   for key in ("ttft_s", "itl_s")},
            })
            report(f"{tag}.json", os.path.relpath(json_path),
                   f"trajectory appended ({n} records)")
    # same engine seed + greedy sampling: the streams must pair up exactly
    if outs["off"] != outs["ngram"]:
        raise RuntimeError("speculative service streams diverged from "
                           "non-speculative greedy streams")
    report("service.spec.greedy_parity", "ok",
           "streamed tokens identical with speculation off/ngram")


def run_failover(report, *, requests=12, kills=2, seed=0, config=None,
                 json_path="auto", timestamp=None, smoke=False):
    """Supervised-replica failover under SIGKILL: the serving stack's
    crash-recovery record.

    One :class:`ReplicaSupervisor` drives the workload while the bench
    hard-kills the worker process ``kills`` times mid-generation (at
    evenly spaced delivered-token thresholds).  The gate is the failover
    contract: every stream's tokens equal the uninterrupted reference
    token for token — ``tokens_lost == 0`` AND ``tokens_duplicated == 0``
    — with a 100% completion rate; the record adds the measured recovery
    time (crash detected -> fresh process restored) per failover."""
    import tempfile

    from repro.serve.supervisor import EngineSpec, ReplicaSupervisor, \
        SupervisorConfig
    if json_path == "auto":
        json_path = None if smoke else JSON_PATH
    cfg = _bench_config(config)
    plan = MeshPlan((DATA, MODEL), (1, 16), 4, 4)
    ec = EngineConfig(s_max=S_MAX, buckets=(1, 2, 4, 8), block_pos_stride=8)
    spec = EngineSpec(model_cfg=cfg, plan=plan, engine_cfg=ec, seed=0)

    rng = np.random.default_rng(seed)
    prompts, n_toks = _workload(rng, cfg.vocab_size, requests)
    sampling = [SamplingParams(max_tokens=n) for n in n_toks]
    # no eos: every request runs to max_tokens.  The uninterrupted
    # reference runs only after the supervisor's workers have exited, so
    # this process stays off the devices while they own them.
    total_expected = sum(n_toks)
    thresholds = [total_expected * (i + 1) // (kills + 1)
                  for i in range(kills)]

    sup_cfg = SupervisorConfig(
        checkpoint_path=os.path.join(tempfile.mkdtemp(prefix="failover-"),
                                     "replica.ckpt"),
        checkpoint_every_steps=4, fsync=True, max_pending=requests,
        max_respawns=kills + 2)

    async def drive():
        async with ReplicaSupervisor(spec, sup_cfg) as sup:
            streams = [await sup.submit(p, max_tokens=n)
                       for p, n in zip(prompts, n_toks)]
            streamed = {s.request_id: [] for s in streams}
            comps = {}

            async def consume(s):
                async for tok in s:
                    streamed[s.request_id].append(tok)
                comps[s.request_id] = s.completion

            tasks = [asyncio.create_task(consume(s)) for s in streams]

            async def killer():
                for i, threshold in enumerate(thresholds):
                    while sum(len(v) for v in streamed.values()) < threshold:
                        await asyncio.sleep(0.01)
                    await sup.kill_replica()
                    while sup.n_spawns < i + 2:
                        await asyncio.sleep(0.05)

            await asyncio.gather(killer(), *tasks)
            snap = sup.metrics.snapshot()
            return ([streamed[s.request_id] for s in streams],
                    [comps[s.request_id] for s in streams],
                    snap, sup.n_failovers)

    streamed, comps, snap, n_failovers = asyncio.run(drive())
    expect = generate(spec.build(), prompts, sampling)

    lost = dup = 0
    completed = 0
    for got, comp, e in zip(streamed, comps, expect):
        ok = 0
        for a, b in zip(got, e.tokens):
            if a != b:
                break
            ok += 1
        lost += len(e.tokens) - ok
        dup += len(got) - ok
        if comp is not None and comp.finish_reason in ("stop", "length"):
            completed += 1
    rate_done = completed / requests
    rec = snap["failover"]["recovery_s"]

    report("service.failover.kills", n_failovers,
           f"{kills} requested at delivered-token thresholds {thresholds}")
    report("service.failover.tokens_lost", lost,
           f"of {total_expected} expected (dup {dup}) — gate: 0/0")
    report("service.failover.completion_rate", f"{rate_done:.3f}",
           f"{completed}/{requests} finished stop/length")
    if rec["n"]:
        report("service.failover.recovery_s_mean", f"{rec['mean']:.3f}",
               f"max {rec['max']:.3f} over {rec['n']} failovers "
               "(detect -> respawn + restore + re-queue)")
    report("service.failover.checkpoints", snap["failover"]["checkpoints"],
           f"cadence {sup_cfg.checkpoint_every_steps} steps, fsync on")

    if json_path:
        ts = timestamp or datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds")
        n = _append_trajectory(json_path, {
            "bench": "serve_service",
            "mode": "failover",
            "config": cfg.name,
            "requests": requests,
            "kills": n_failovers,
            "seed": seed,
            "timestamp": ts,
            "completed": completed,
            "completion_rate": round(rate_done, 4),
            "tokens_expected": total_expected,
            "tokens_lost": lost,
            "tokens_duplicated": dup,
            "checkpoints": snap["failover"]["checkpoints"],
            "recovery_s": {s: (round(v, 5) if isinstance(v, float) else v)
                           for s, v in rec.items()},
        })
        report("service.failover.json", os.path.relpath(json_path),
               f"trajectory appended ({n} records)")

    if n_failovers < kills:
        raise RuntimeError(
            f"only {n_failovers} of {kills} kills landed")
    if lost or dup:
        raise RuntimeError(
            f"failover broke the token contract: {lost} lost, "
            f"{dup} duplicated")
    if completed != requests:
        raise RuntimeError(
            f"only {completed}/{requests} requests completed")
    report("service.failover.contract", "ok",
           "zero lost, zero duplicated, all streams completed")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rate", type=float, default=None,
                    help="single arrival rate (requests/sec); default: the "
                         "full under/over-capacity sweep")
    ap.add_argument("--requests", type=int, default=64,
                    help="offered load per cell")
    ap.add_argument("--seed", type=int, default=0,
                    help="workload + arrival-process seed")
    ap.add_argument("--admission", default=None,
                    choices=["fifo", "deadline", "fair_share"],
                    help="single policy to bench (default: the sweep's "
                         "fifo/fifo/deadline cells)")
    ap.add_argument("--config", default="srv-bench",
                    help="registry architecture (reduced smoke sibling), "
                         "e.g. qwen2-0.5b")
    ap.add_argument("--ttft-slo", type=float, default=0.5, dest="ttft_slo",
                    help="per-request TTFT deadline in seconds (enforced "
                         "only by the deadline policy)")
    ap.add_argument("--timestamp", default=None,
                    help="timestamp recorded in trajectory entries")
    ap.add_argument("--json", default=None,
                    help="append records to this path (default: "
                         "BENCH_serve.json on full sweeps; single-rate "
                         "runs don't touch the trajectory)")
    ap.add_argument("--failover", action="store_true",
                    help="run the supervised-replica SIGKILL pass instead "
                         "of the admission sweep: kill the worker process "
                         "--kills times mid-generation, gate on zero "
                         "lost/duplicated tokens and 100%% completion "
                         "(--rate or --requests<=8 makes it a "
                         "trajectory-free smoke)")
    ap.add_argument("--kills", type=int, default=2,
                    help="worker kills in the --failover pass")
    ap.add_argument("--speculation", action="store_true",
                    help="run the paired off/ngram open-loop pass instead "
                         "of the admission sweep: same repetitive workload "
                         "as serve_throughput --speculation, records the "
                         "service metrics snapshot's speculation counters "
                         "(--rate makes it a trajectory-free smoke)")
    args = ap.parse_args()
    use_compile_cache()
    print("name,value,derived")

    def report(name, value, derived=""):
        print(f"{name},{value},{derived}", flush=True)

    if args.failover:
        run_failover(
            report, kills=args.kills, seed=args.seed, config=args.config,
            json_path=args.json or "auto", timestamp=args.timestamp,
            requests=args.requests if args.requests != 64 else 12,
            smoke=args.rate is not None or args.requests not in (64, 12))
        return
    if args.speculation:
        run_speculation(
            report, rate=args.rate or 16.0, seed=args.seed,
            config=args.config, json_path=args.json or "auto",
            timestamp=args.timestamp,
            # --requests keeps its sweep default of 64, far too many for
            # the paired pass; only an explicit override applies
            requests=args.requests if args.requests != 64 else 8,
            smoke=args.rate is not None)
        return
    run(report, rate=args.rate, requests=args.requests, seed=args.seed,
        admission=args.admission, config=args.config,
        ttft_slo_s=args.ttft_slo, json_path=args.json or "auto",
        timestamp=args.timestamp)


if __name__ == "__main__":
    main()
