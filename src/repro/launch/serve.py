"""Serving launcher: batched greedy decoding with the SHMEM-grid server.

Serves the architecture at its published widths over every device present
(one chip: a 1x1 PE grid; four chips: 2x2; see ``repro.launch.mesh``).
``--reduced`` serves the tiny smoke sibling instead, for CPU runs:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --tokens 32
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=16 \\
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced

With ``--engine`` the same model is served through the continuous-batching
engine (mixed-length workload, bucketed executables, paged-KV admission —
see docs/serving.md).

With ``--service`` the workload instead arrives through the asyncio
``GenerateService`` front-end — concurrent streaming clients over one
engine thread, pluggable admission (``--admission fifo|deadline|
fair_share``) — and the run ends by printing the ``ServiceMetrics``
snapshot (p50/p99 TTFT, inter-token and queue-wait latencies, shed and
reject counters).
"""

from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_NAMES, get_config
from repro.configs.registry import reduced
from repro.launch.mesh import device_mesh
from repro.models import params as pm
from repro.partition import DATA
from repro.runtime.compile_cache import use_compile_cache
from repro.serve.decode import cache_pspecs, cache_specs, make_decode_step


def main():
    ap = argparse.ArgumentParser()
    # --config is an alias (underscores accepted: mamba2_780m == mamba2-780m)
    ap.add_argument("--arch", "--config", dest="arch", required=True,
                    type=lambda s: s.replace("_", "-"),
                    choices=list(ARCH_NAMES))
    ap.add_argument("--reduced", action="store_true",
                    help="serve the tiny smoke sibling of --arch (fp32, "
                         "d_model 64, vocab 256) instead of its published "
                         "widths")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--s-max", type=int, default=64)
    ap.add_argument("--mode", default="gemv",
                    choices=["batched", "gemv", "longctx"])
    ap.add_argument("--engine", action="store_true",
                    help="serve a mixed-length workload through the "
                         "continuous-batching engine")
    ap.add_argument("--service", action="store_true",
                    help="serve through the asyncio GenerateService "
                         "front-end (concurrent streaming clients, "
                         "SLO-aware admission) and print its metrics "
                         "snapshot")
    ap.add_argument("--supervised", action="store_true",
                    help="serve through the crash-safe ReplicaSupervisor: "
                         "the engine drive loop runs in a child worker "
                         "process taking periodic drain checkpoints, and "
                         "--kills SIGKILLs it mid-generation to "
                         "demonstrate zero-token-loss failover")
    ap.add_argument("--kills", type=int, default=1,
                    help="worker kills to inject under --supervised")
    ap.add_argument("--admission", default="fifo",
                    choices=["fifo", "deadline", "fair_share"],
                    help="admission policy for --service")
    ap.add_argument("--rate", type=float, default=8.0,
                    help="Poisson arrival rate (req/s) for --service")
    ap.add_argument("--ttft-slo", type=float, default=None, dest="ttft_slo",
                    help="per-request TTFT deadline in seconds for "
                         "--service (sheds infeasible requests under "
                         "--admission deadline)")
    ap.add_argument("--requests", type=int, default=8,
                    help="workload size for --engine / --service")
    ap.add_argument("--prefill-chunks", default="16,64,256",
                    help="chunked-prefill length ladder for --engine "
                         "(comma-separated; empty string disables chunking)")
    ap.add_argument("--kernel-backend", default=None,
                    choices=["jnp", "pallas", "pallas-interpret"],
                    help="step-kernel backend for --engine (default: "
                         "REPRO_KERNEL_BACKEND or jnp); pallas reads KV "
                         "pages in place inside the fused kernel")
    ap.add_argument("--speculation", default="off",
                    choices=["off", "ngram", "draft_model"],
                    help="speculative decoding for --engine/--service: "
                         "draft k tokens per slot (prompt-lookup or a "
                         "second draft-model CommandQueue) and verify them "
                         "in one verify_bs{N} launch")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens per slot per verify launch")
    ap.add_argument("--prefix-cache", default="on", choices=["on", "off"],
                    help="cross-request radix prefix cache for "
                         "--engine/--service: on = shared token-block "
                         "prefixes adopt resident KV pages at admission; "
                         "off = pure free-list allocation (the parity "
                         "baseline)")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if cfg.enc_layers:
        raise SystemExit("whisper serving needs an encoder pass; see "
                         "tests/test_decode.py for the full harness")
    use_compile_cache()

    if args.engine or args.service or args.supervised:
        if args.mode != "gemv":
            print(f"note: --engine serves via the per-slot gemv decode "
                  f"layout; --mode {args.mode} ignored")
        if args.supervised:
            # the worker owns the devices: this process never touches them
            return _main_supervised(cfg, args)
        mesh, plan = device_mesh()
        if args.service:
            return _main_service(cfg, mesh, plan, args)
        return _main_engine(cfg, mesh, plan, args)

    mesh, plan = device_mesh()
    step, specs, pctx = make_decode_step(
        cfg, mesh, plan, batch=args.batch, s_max=args.s_max, mode=args.mode)
    params = pm.init_params(specs, seed=0)
    pspecs = pm.param_pspecs(specs)
    params = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, pspecs)
    cs = cache_specs(cfg, plan, args.batch, args.s_max, args.mode)
    cps = cache_pspecs(cfg, args.mode, pctx.data_axes)
    cache = jax.tree.map(
        lambda sd, sp: jax.device_put(jnp.zeros(sd.shape, sd.dtype),
                                      NamedSharding(mesh, sp)), cs, cps)

    rng = np.random.default_rng(0)
    tok = jnp.asarray(rng.integers(0, min(cfg.vocab_size, 256),
                                   size=(args.batch,)), jnp.int32)
    tok_spec = P() if args.mode == "longctx" else P(DATA)
    out_tokens = [np.asarray(tok)]
    t0 = time.time()
    for t in range(args.tokens):
        logits, cache = step(params,
                             cache,
                             jax.device_put(tok, NamedSharding(mesh, tok_spec)),
                             jnp.int32(t))
        tok = jnp.argmax(logits[:, 0, :cfg.vocab_size], axis=-1).astype(
            jnp.int32)
        out_tokens.append(np.asarray(tok))
    dt = (time.time() - t0) / args.tokens
    seqs = np.stack(out_tokens, 1)
    dev = jax.devices()[0]
    print(f"decoded {args.tokens} tokens x batch {args.batch} "
          f"({dt*1e3:.1f} ms/token on {len(jax.devices())} x "
          f"{dev.platform} {dev.device_kind}, compile included)")
    for b in range(min(args.batch, 2)):
        print(f"  seq[{b}]: {seqs[b][:16].tolist()} ...")


def _engine_cfg(args):
    from repro.serve.engine import EngineConfig
    stride = 16
    s_max = -(-max(args.s_max, args.tokens + 12) // stride) * stride
    buckets = tuple(b for b in (1, 2, 4, 8) if b <= max(args.batch, 1))
    chunks = tuple(int(c) for c in args.prefill_chunks.split(",") if c)
    ec_kw = {} if args.kernel_backend is None \
        else {"kernel_backend": args.kernel_backend}
    ec_kw["prefix_cache"] = getattr(args, "prefix_cache", "on") != "off"
    if getattr(args, "speculation", "off") != "off":
        from repro.serve.spec import SpeculationConfig
        ec_kw["speculation"] = SpeculationConfig(
            drafter=args.speculation, k=args.spec_k,
            # self-drafting default: the reduced target config itself runs
            # on the draft queue (vocabs match by construction)
            draft_config=args.arch if args.speculation == "draft_model"
            else None)
    return EngineConfig(s_max=s_max, buckets=buckets,
                        block_pos_stride=stride, prefill_chunks=chunks,
                        **ec_kw)


def _build_engine(cfg, mesh, plan, args):
    from repro.serve.engine import build_engine
    # every mixer maps to a StateSpec (paged KV for attn, dense slots for
    # SSM), so dense/moe/hybrid/ssm families all serve through the engine
    return build_engine(cfg, mesh, plan, seed=0, engine_cfg=_engine_cfg(args))


def _workload(cfg, args):
    rng = np.random.default_rng(0)
    vocab = min(cfg.vocab_size, 256)
    return [rng.integers(0, vocab,
                         size=int(rng.integers(2, 12))).tolist()
            for _ in range(args.requests)]


def _main_engine(cfg, mesh, plan, args):
    from repro.serve.engine import SamplingParams, generate
    eng = _build_engine(cfg, mesh, plan, args)
    prompts = _workload(cfg, args)
    outs = generate(eng, prompts, SamplingParams(max_tokens=args.tokens))
    for c in outs[:4]:
        print(f"  {c.request_id}: prompt[{len(c.prompt)}] -> "
              f"{c.tokens[:12]} ({c.finish_reason})")
    ev = eng.kernel_events()
    st = eng.stats
    ttfts = [c.ttft_s for c in outs if c.ttft_s is not None]
    kinds = ["paged KV" if eng.store.needs_pages else None,
             "dense slots" if eng.store.has_dense else None]
    print(f"served {len(outs)} requests / {st.tokens_generated} tokens "
          f"({cfg.family}: {' + '.join(k for k in kinds if k)}): "
          f"{eng.throughput_tok_s():.1f} tok/s over {len(ev)} executables "
          f"{sorted(ev)}")
    # launches != tokens since chunked prefill: one prefill_bs{N}_len{L}
    # enqueue ingests up to L prompt tokens per slot
    ttft_ms = f"{np.mean(ttfts) * 1e3:.1f} ms" if ttfts else "n/a"
    tpl = st.tokens_generated / max(st.launches, 1)
    print(f"  prefill: {st.prompt_tokens_ingested} prompt tokens ingested "
          f"in {st.prefill_launches} launches "
          f"({st.prefill_chunk_launches} chunked); "
          f"decode: {st.decode_launches} launches; "
          f"{tpl:.2f} tokens/launch; mean TTFT {ttft_ms}")
    if st.spec_launches:
        print(f"  speculation: {st.spec_launches} verify launches, "
              f"{st.spec_proposed_tokens} proposed / "
              f"{st.spec_accepted_tokens} accepted "
              f"(accept rate {st.spec_accept_rate:.2f}, "
              f"{st.spec_rollbacks} rollbacks)")
    if st.prefix_hits or st.prefix_evictions:
        print(f"  prefix cache: {st.prefix_hits} page hits, "
              f"{st.prefix_tokens_reused} prompt tokens reused "
              f"(hit rate {st.prefix_hit_rate:.2f}), "
              f"{st.prefix_evictions} evictions")


def _main_service(cfg, mesh, plan, args):
    import asyncio
    import json

    from repro.serve.service import (AdmissionRejected, GenerateService,
                                     ServiceConfig)
    eng = _build_engine(cfg, mesh, plan, args)
    prompts = _workload(cfg, args)
    gaps = np.random.default_rng(1).exponential(1.0 / args.rate,
                                                size=args.requests)

    async def client(svc, prompt):
        try:
            stream = await svc.submit(prompt, max_tokens=args.tokens,
                                      ttft_deadline_s=args.ttft_slo)
        except AdmissionRejected as e:
            print(f"  rejected: {e.reason}")
            return None
        toks, comp = await stream.drain()
        return comp

    async def drive():
        svc_cfg = ServiceConfig(admission=args.admission)
        async with GenerateService(eng, svc_cfg) as svc:
            tasks = []
            for prompt, gap in zip(prompts, gaps):
                await asyncio.sleep(gap)    # open loop: Poisson arrivals
                tasks.append(asyncio.create_task(client(svc, prompt)))
            comps = await asyncio.gather(*tasks)
            return comps, svc.metrics.snapshot()

    comps, snap = asyncio.run(drive())
    for c in [c for c in comps if c is not None][:4]:
        print(f"  {c.request_id}: prompt[{len(c.prompt)}] -> "
              f"{c.tokens[:12]} ({c.finish_reason})")
    print(f"service ({args.admission} admission, rate {args.rate:g}/s): "
          f"{snap['completed']} completed, {snap['shed']} shed, "
          f"{snap['rejected']} rejected, {snap['tokens']} tokens")
    print(json.dumps(snap, indent=2))


def _main_supervised(cfg, args):
    import asyncio
    import json
    import os
    import tempfile

    from repro.serve.supervisor import (EngineSpec, ReplicaSupervisor,
                                        SupervisorConfig)
    spec = EngineSpec(model_cfg=cfg, plan=None,
                      engine_cfg=_engine_cfg(args), seed=0)
    prompts = _workload(cfg, args)
    sup_cfg = SupervisorConfig(
        checkpoint_path=os.path.join(
            tempfile.mkdtemp(prefix="serve-supervised-"), "replica.ckpt"),
        checkpoint_every_steps=4, max_respawns=args.kills + 2)
    total = args.tokens * len(prompts)
    thresholds = [total * (i + 1) // (args.kills + 1)
                  for i in range(max(0, args.kills))]

    async def drive():
        async with ReplicaSupervisor(spec, sup_cfg) as sup:
            streams = [await sup.submit(p, max_tokens=args.tokens)
                       for p in prompts]
            delivered = {s.request_id: 0 for s in streams}
            comps = {}

            async def consume(s):
                async for _ in s:
                    delivered[s.request_id] += 1
                comps[s.request_id] = s.completion

            tasks = [asyncio.create_task(consume(s)) for s in streams]

            async def killer():
                for i, threshold in enumerate(thresholds):
                    while sum(delivered.values()) < threshold:
                        await asyncio.sleep(0.01)
                    print(f"  SIGKILL worker #{i + 1} "
                          f"({sum(delivered.values())} tokens delivered)")
                    await sup.kill_replica()
                    while sup.n_spawns < i + 2:
                        await asyncio.sleep(0.05)

            await asyncio.gather(killer(), *tasks)
            return ([comps[s.request_id] for s in streams],
                    sup.metrics.snapshot(), sup.n_failovers)

    comps, snap, n_failovers = asyncio.run(drive())
    for c in [c for c in comps if c is not None][:4]:
        print(f"  {c.request_id}: prompt[{len(c.prompt)}] -> "
              f"{c.tokens[:12]} ({c.finish_reason})")
    fo = snap["failover"]
    rec = fo["recovery_s"]["mean"]
    print(f"supervised replica: {snap['completed']} completed / "
          f"{snap['tokens']} tokens across {n_failovers} failovers "
          f"({fo['checkpoints']} checkpoints"
          + (f", mean recovery {rec:.2f}s" if rec is not None else "")
          + ") — streams resumed with zero duplicated/dropped tokens")
    print(json.dumps(snap, indent=2))


if __name__ == "__main__":
    main()
