"""Serving-engine throughput: continuous batching vs the naive fixed batch.

Drives a mixed-length request workload through ``ServingEngine`` and reports
tokens/sec derived from the CommandQueue's ``KernelEvent`` timestamps (the
OpenCL-event view of the run), per-bucket launch/flop/collective stats,
paged-KV residency (peak block-pool occupancy + bytes resident), and — since
chunked prefill — time-to-first-token plus the prefill launches-vs-tokens
split (one ``prefill_bs{N}_len{L}`` enqueue ingests up to L prompt tokens
per slot, so launches < tokens ingested by construction).

``BENCH_serve.json`` at the repo root is a **trajectory**: a list of run
records (config name + CLI-passed timestamp + the metric payload), appended
to — never overwritten — so regressions are visible across PRs.  Full runs
append by default; smoke runs leave it alone unless ``--json`` is passed
explicitly.

Standalone:
  XLA_FLAGS=--xla_force_host_platform_device_count=16 \\
  PYTHONPATH=src python benchmarks/serve_throughput.py \\
      [--config mamba2_780m] [--timestamp 2026-07-28T00:00:00Z]

``--kernel-backend`` selects the step-kernel implementation (jnp
materialized-gather reference vs the fused Pallas paged-attention path);
running the bench once per backend appends PAIRED trajectory entries.
``pallas`` compiles the kernels for the TPU and refuses any other backend;
on CPU hosts pass ``pallas-interpret``, whose pairing is a parity/ABI
record, not a speed: the interpreter is expected to be slower.

``--config`` serves a reduced registry architecture instead of the built-in
dense bench model — including SSM/hybrid families, which exercise the dense
StateSpec path end to end.  ``--steps N`` runs a smoke pass: the workload is
submitted but only N engine steps execute (one bucket executable compiles,
no warm-up) — CI uses this to keep the benchmark path from rotting without
paying a full run, and it asserts the chunked-prefill amortization
invariant (strictly fewer prefill launches than prompt tokens ingested).
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.models.config import ModelConfig  # noqa: E402
from repro.partition import DATA, MODEL, MeshPlan  # noqa: E402
from repro.runtime.compile_cache import use_compile_cache  # noqa: E402
from repro.serve.engine import (EngineConfig, EngineStats,  # noqa: E402
                                SamplingParams, build_engine, generate)

N_REQUESTS = 16
S_MAX = 64
JSON_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")


def _workload(rng, vocab):
    prompts = [rng.integers(0, vocab, size=int(rng.integers(2, 12))).tolist()
               for _ in range(N_REQUESTS)]
    sampling = [SamplingParams(max_tokens=int(rng.integers(4, 12)))
                for _ in range(N_REQUESTS)]
    return prompts, sampling


def _bench_config(name):
    if name in (None, "srv-bench"):
        return ModelConfig(name="srv-bench", family="dense", d_model=128,
                           n_layers=4, n_heads=8, n_kv_heads=4, d_ff=512,
                           vocab_size=1024, param_dtype=jnp.float32,
                           compute_dtype=jnp.float32, attn_block_kv=32)
    if name == "spec-bench":
        # the speculation pair's default: small enough that greedy decode
        # falls into short token cycles (see _spec_workload), and small
        # enough that a verify_bs{N}_len{k+1} launch costs about what a
        # decode launch costs (launch overhead, not per-position compute,
        # dominates) — so launch reduction shows up as wall-clock speedup
        return ModelConfig(name="spec-bench", family="dense", d_model=32,
                           n_layers=1, n_heads=4, n_kv_heads=2, d_ff=64,
                           vocab_size=64, param_dtype=jnp.float32,
                           compute_dtype=jnp.float32, attn_block_kv=32)
    from repro.configs import get_config
    from repro.configs.registry import reduced
    return reduced(get_config(name.replace("_", "-")))


def _append_trajectory(json_path, record):
    """BENCH_serve.json holds a LIST of run records; append, never clobber
    (a pre-trajectory single-record file is adopted as the list head).  An
    unreadable file is preserved under ``<path>.corrupt`` instead of being
    silently overwritten — the trajectory is the cross-PR record."""
    history = []
    if os.path.exists(json_path):
        try:
            with open(json_path) as f:
                prev = json.load(f)
            history = prev if isinstance(prev, list) else [prev]
        except (json.JSONDecodeError, OSError):
            os.replace(json_path, json_path + ".corrupt")
            print(f"warning: unreadable trajectory moved to "
                  f"{json_path}.corrupt", file=sys.stderr)
    history.append(record)
    with open(json_path, "w") as f:
        json.dump(history, f, indent=2, sort_keys=True)
        f.write("\n")
    return len(history)


def run(report, steps=None, json_path="auto", config=None, timestamp=None,
        kernel_backend=None, seed=0):
    # "auto": full runs append to the committed BENCH_serve.json trajectory;
    # smoke (--steps) runs never touch it unless --json asks explicitly
    if json_path == "auto":
        json_path = None if steps is not None else JSON_PATH
    if kernel_backend is None:     # same env-honoring default as the engine
        from repro.kernels import default_kernel_backend
        kernel_backend = default_kernel_backend()
    cfg = _bench_config(config)
    mesh = jax.make_mesh((1, 16), (DATA, MODEL),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    plan = MeshPlan((DATA, MODEL), (1, 16), 4, 4)
    ec = EngineConfig(s_max=S_MAX, buckets=(1, 2, 4, 8),
                      block_pos_stride=8,     # default chunk ladder -> (16, 64)
                      kernel_backend=kernel_backend)
    eng = build_engine(cfg, mesh, plan, engine_cfg=ec, seed=0)

    prompts, sampling = _workload(np.random.default_rng(seed),
                                  cfg.vocab_size)
    ttfts = []
    if steps is not None:
        # smoke pass: submit everything, run exactly `steps` step kernels
        for p, s in zip(prompts, sampling):
            eng.submit(p, s)
        for _ in range(steps):
            if not eng.step():
                break
        # the whole point of chunked prefill: launches amortize over tokens.
        # CI's bench-smoke job relies on this tripwire (an explicit raise,
        # not an assert, so `python -O` cannot strip the gate).
        if steps > 0 and eng.prefill_chunk_ladder and \
                eng.stats.prefill_launches >= eng.stats.prompt_tokens_ingested:
            raise RuntimeError(
                "chunked prefill must use strictly fewer launches than "
                f"prompt tokens ingested: {eng.stats.prefill_launches} "
                f"launches for {eng.stats.prompt_tokens_ingested} tokens")
    else:
        # warm EVERY bucket executable (the prefills warm the chunk kernels
        # too), then zero all counters so the timed pass reports
        # steady-state work only
        for b in ec.buckets:
            generate(eng, prompts[:b], SamplingParams(max_tokens=1))
        eng.stats = EngineStats()
        eng.queue.max_depth = 0
        for ev in eng.kernel_events().values():
            ev.launches = 0

        outs = generate(eng, prompts, sampling)
        assert all(len(c.tokens) == s.max_tokens
                   for c, s in zip(outs, sampling))
        ttfts = [c.ttft_s for c in outs if c.ttft_s is not None]

    st = eng.stats
    tok_s = eng.throughput_tok_s()
    report("serve.engine.kernel_backend", kernel_backend,
           "jnp = materialized gather; pallas = fused in-place page reads")
    report("serve.engine.tokens_per_sec", f"{tok_s:.1f}",
           f"{st.tokens_generated} tokens, {st.steps} launches")
    report("serve.engine.executables", eng.queue.n_executables,
           "one per (bucket, chunk-length) used")
    report("serve.engine.queue_max_depth", eng.queue.max_depth, "")
    report("serve.engine.prefill_launches", st.prefill_launches,
           f"of which {st.prefill_chunk_launches} chunked "
           f"(ladder {list(eng.prefill_chunk_ladder)})")
    report("serve.engine.prompt_tokens_ingested", st.prompt_tokens_ingested,
           "launches < tokens: chunked prefill amortizes enqueue overhead")
    report("serve.engine.decode_launches", st.decode_launches, "")
    if ttfts:
        report("serve.engine.ttft_mean_ms", f"{np.mean(ttfts) * 1e3:.2f}",
               f"over {len(ttfts)} requests")
        report("serve.engine.ttft_max_ms", f"{np.max(ttfts) * 1e3:.2f}", "")
    report("serve.engine.migrations", st.migrations,
           "host-side table permutations (no device KV copies)")
    report("serve.engine.peak_kv_blocks_used", st.peak_blocks_used,
           f"of {eng.pool.n_blocks} pool blocks "
           f"(stride {eng.pool.block_pos_stride})")
    report("serve.engine.peak_kv_bytes_resident", eng.peak_kv_bytes(),
           f"{eng.pool.layout.bytes_per_block} B/page arena footprint")
    for name, ev in sorted(eng.kernel_events().items()):
        report(f"serve.event.{name}.launches", ev.launches, "")
        report(f"serve.event.{name}.gflops_per_launch",
               f"{ev.flops / 1e9:.3f}", "from XLA cost analysis")
        report(f"serve.event.{name}.collective_mb_per_launch",
               f"{ev.collective_bytes / 1e6:.3f}", "from HLO")

    if json_path:
        payload = {
            "bench": "serve_throughput",
            "config": cfg.name,
            "kernel_backend": kernel_backend,
            "seed": seed,
            "timestamp": timestamp or datetime.datetime.now(
                datetime.timezone.utc).isoformat(timespec="seconds"),
            "mode": "smoke" if steps is not None else "full",
            "tokens_per_sec": round(tok_s, 2),
            "tokens_generated": st.tokens_generated,
            "steps": st.steps,
            "prefill_launches": st.prefill_launches,
            "prefill_chunk_launches": st.prefill_chunk_launches,
            "prompt_tokens_ingested": st.prompt_tokens_ingested,
            "decode_launches": st.decode_launches,
            "ttft_s_mean": round(float(np.mean(ttfts)), 4) if ttfts else None,
            "ttft_s_max": round(float(np.max(ttfts)), 4) if ttfts else None,
            "prefill_chunk_ladder": list(eng.prefill_chunk_ladder),
            "executables": sorted(eng.kernel_events()),
            "peak_kv_blocks_used": st.peak_blocks_used,
            "peak_kv_bytes_resident": eng.peak_kv_bytes(),
            "peak_dense_slots_used": st.peak_dense_slots_used,
            "migrations": st.migrations,
        }
        n = _append_trajectory(json_path, payload)
        report("serve.engine.json", os.path.relpath(json_path),
               f"trajectory appended ({n} records)")
    return tok_s


# The degraded-mode fault profile (--faults): every injection site lit at
# a rate low enough that the retry budget usually covers a streak, so the
# paired record shows graceful degradation, not collapse.
FAULT_RATES = {"launch": 0.08, "device": 0.06, "nan_logits": 0.03,
               "pool": 0.06, "stall": 0.02}


def run_faults(report, json_path="auto", config=None, timestamp=None,
               kernel_backend=None, seed=0, smoke=False):
    """Paired fault-free vs degraded-mode full passes over one workload;
    appends BOTH records (``fault_profile`` "off" / "chaos") to the
    trajectory.  The degraded pass serves the same seeded workload under a
    deterministic :class:`FaultInjector` (launch raises, device failures,
    NaN logits, pool steals, stalls) with the default retry/quarantine
    policy, and the record carries tokens/sec, the completion rate, and
    the engine's fault counters — the serving analogue of running the
    board with a flaky link and reporting how much of the traffic still
    lands.

    Two explicit raises gate the pair: every request must reach a
    TERMINAL state (no hang under chaos — the soak-test invariant), and
    pool/slot accounting must drain to zero after both passes (injected
    faults never leak pages)."""
    from repro.serve.resilience import FaultInjector, ResilienceConfig
    if json_path == "auto":
        json_path = None if smoke else JSON_PATH
    if kernel_backend is None:
        from repro.kernels import default_kernel_backend
        kernel_backend = default_kernel_backend()
    cfg = _bench_config(config)
    mesh = jax.make_mesh((1, 16), (DATA, MODEL),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    plan = MeshPlan((DATA, MODEL), (1, 16), 4, 4)
    rng = np.random.default_rng(seed)
    if smoke:
        prompts = [rng.integers(0, cfg.vocab_size,
                                size=int(rng.integers(2, 8))).tolist()
                   for _ in range(4)]
        sampling = [SamplingParams(max_tokens=4)] * 4
    else:
        prompts, sampling = _workload(rng, cfg.vocab_size)

    # the smoke pass is short, so it runs the profile hot (and capped) to
    # guarantee the guard path actually executes in CI
    rates = {k: min(1.0, 4 * v) for k, v in FAULT_RATES.items()} \
        if smoke else FAULT_RATES
    results = {}
    for label in ("off", "chaos"):
        inj = None if label == "off" else FaultInjector(
            seed + 1, rates, stall_s=0.001,
            max_faults=20 if smoke else None)
        ec = EngineConfig(s_max=S_MAX, buckets=(1, 2, 4, 8),
                          block_pos_stride=8, kernel_backend=kernel_backend,
                          max_steps=20_000,      # hang valve under chaos
                          fault_injector=inj,
                          resilience=None if inj is None
                          else ResilienceConfig())
        eng = build_engine(cfg, mesh, plan, engine_cfg=ec, seed=0)
        if not smoke:
            # warm every bucket executable, then zero the counters so the
            # timed pass (and its fault counters) reports steady state;
            # the injector keeps its deterministic schedule across both
            # passes, so counts() below is cumulative — the per-pass
            # fault_* numbers come from the reset EngineStats
            for b in ec.buckets:
                generate(eng, prompts[:b], SamplingParams(max_tokens=1))
            eng.stats = EngineStats()
        outs = generate(eng, prompts, sampling)
        st = eng.stats
        if any(c.finish_reason is None for c in outs):
            raise RuntimeError(
                f"[{label}] request left non-terminal under the fault "
                f"profile: chaos must never hang a request")
        if eng.pool.n_free != eng.pool.n_blocks:
            raise RuntimeError(
                f"[{label}] pool accounting leaked: "
                f"{eng.pool.n_blocks - eng.pool.n_free} pages still held")
        ok = sum(c.finish_reason in ("stop", "length") for c in outs)
        results[label] = {
            "tok_s": eng.throughput_tok_s(),
            "completion_rate": ok / len(outs),
            "quarantined": sum(c.finish_reason == "error" for c in outs),
            "stats": st,
            "injector_counts": inj.counts() if inj is not None else {},
            "n_fired": inj.n_fired if inj is not None else 0,
        }
        r = results[label]
        report(f"serve.faults.{label}.tokens_per_sec", f"{r['tok_s']:.1f}",
               f"{st.tokens_generated} tokens, {st.steps} launches")
        report(f"serve.faults.{label}.completion_rate",
               f"{r['completion_rate']:.2f}",
               f"{ok}/{len(outs)} requests finished stop|length")
        if inj is not None:
            report("serve.faults.chaos.injected", inj.n_fired,
                   " ".join(f"{k}={v}" for k, v in
                            sorted(inj.counts().items()) if v))
            report("serve.faults.chaos.retries", st.fault_retries,
                   f"launch_failures={st.fault_launch_failures} "
                   f"nonfinite={st.fault_nonfinite}")
            report("serve.faults.chaos.quarantined", r["quarantined"],
                   "requests finished as error")
            report("serve.faults.chaos.pool_steals", st.fault_pool_steals,
                   f"stalls={st.fault_stalls}")

    if results["chaos"]["n_fired"] == 0:
        raise RuntimeError(
            "the chaos pass injected zero faults: the degraded-mode "
            "record would be vacuous (rates/workload too small)")
    degradation = (results["chaos"]["tok_s"] / results["off"]["tok_s"]
                   if results["off"]["tok_s"] else 0.0)
    report("serve.faults.throughput_ratio", f"{degradation:.2f}",
           "chaos / fault-free tokens per sec (graceful degradation)")

    if json_path:
        stamp = timestamp or datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds")
        for label, r in results.items():
            st = r["stats"]
            payload = {
                "bench": "serve_throughput",
                "config": cfg.name,
                "kernel_backend": kernel_backend,
                "seed": seed,
                "timestamp": stamp,
                "mode": "faults",
                "fault_profile": label,
                "fault_rates": rates if label == "chaos" else None,
                "tokens_per_sec": round(r["tok_s"], 2),
                "throughput_ratio_vs_off": round(degradation, 3)
                if label == "chaos" else None,
                "completion_rate": round(r["completion_rate"], 4),
                "quarantined": r["quarantined"],
                "tokens_generated": st.tokens_generated,
                "steps": st.steps,
                "fault_injected": r["injector_counts"],
                "fault_launch_failures": st.fault_launch_failures,
                "fault_retries": st.fault_retries,
                "fault_nonfinite": st.fault_nonfinite,
                "fault_quarantined": st.fault_quarantined,
                "fault_pool_steals": st.fault_pool_steals,
                "fault_stalls": st.fault_stalls,
            }
            n = _append_trajectory(json_path, payload)
        report("serve.faults.json", os.path.relpath(json_path),
               f"paired records appended ({n} total)")
    return degradation


def run_prefix(report, json_path="auto", config=None, timestamp=None,
               kernel_backend=None, seed=0, requests=8, smoke=False):
    """Paired cache-off/cache-on full passes over one shared-prefix
    workload; appends BOTH records (``prefix_cache`` "off" / "on") to the
    trajectory.

    The workload is the radix cache's home turf: every request is a shared
    system prefix (whole KV pages) plus a short distinct tail, served twice
    — a warm pass (which for the cache-on engine also populates the tree)
    and a timed pass.  Three explicit raises (not asserts) gate the pair:

      * greedy parity — cache-on must emit token-for-token what cache-off
        emits (adopted pages ARE the KV the off engine recomputes);
      * the cache-on pass must land prefix hits (smoke: hit rate > 0; full
        runs: >= 0.5 — the shared prefix dominates each prompt);
      * strictly fewer prefill launches cache-on than cache-off (adopted
        pages skip prefill entirely, not just kernel work).
    """
    if json_path == "auto":
        json_path = None if smoke else JSON_PATH
    if kernel_backend is None:
        from repro.kernels import default_kernel_backend
        kernel_backend = default_kernel_backend()
    cfg = _bench_config(config)
    mesh = jax.make_mesh((1, 16), (DATA, MODEL),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    plan = MeshPlan((DATA, MODEL), (1, 16), 4, 4)
    rng = np.random.default_rng(seed)
    if smoke:
        requests, sys_tokens, tail, max_tokens = 4, 16, 8, 4
    else:
        sys_tokens, tail, max_tokens = 48, 8, 8
    sys_prefix = rng.integers(0, cfg.vocab_size, size=sys_tokens).tolist()
    prompts = [sys_prefix
               + rng.integers(0, cfg.vocab_size, size=tail).tolist()
               for _ in range(requests)]
    sampling = [SamplingParams(max_tokens=max_tokens)] * requests

    results = {}
    for label in ("off", "on"):
        ec = EngineConfig(s_max=S_MAX, buckets=(1, 2, 4, 8),
                          block_pos_stride=8, kernel_backend=kernel_backend,
                          prefix_cache=(label == "on"))
        eng = build_engine(cfg, mesh, plan, engine_cfg=ec, seed=0)
        # warm pass: compiles every executable AND (cache-on) populates the
        # radix tree, so the timed pass measures steady-state serving with
        # a resident shared prefix; counters reset in between
        generate(eng, prompts, sampling)
        eng.stats = EngineStats()
        eng.queue.max_depth = 0
        for ev in eng.kernel_events().values():
            ev.launches = 0
        outs = generate(eng, prompts, sampling)
        st = eng.stats
        results[label] = {
            "outs": [c.tokens for c in outs],
            "stats": st,
            "tok_s": eng.throughput_tok_s(),
            "n_blocks": eng.pool.n_blocks,
        }
        report(f"serve.prefix.{label}.tokens_per_sec",
               f"{results[label]['tok_s']:.1f}",
               f"{st.tokens_generated} tokens, {st.steps} launches")
        report(f"serve.prefix.{label}.prefill_launches",
               st.prefill_launches,
               f"{st.prompt_tokens_ingested} prompt tokens ingested")
        if label == "on":
            report("serve.prefix.on.hit_rate", f"{st.prefix_hit_rate:.3f}",
                   f"{st.prefix_tokens_reused} tokens reused via "
                   f"{st.prefix_hits} page hits, "
                   f"{st.prefix_evictions} evictions")

    if results["off"]["outs"] != results["on"]["outs"]:
        raise RuntimeError(
            "prefix-cache greedy decode must match cache-off greedy "
            "token-for-token on the same seed")
    report("serve.prefix.greedy_parity", "ok",
           "cache-on == cache-off token-for-token")
    st_on, st_off = results["on"]["stats"], results["off"]["stats"]
    hit_rate = st_on.prefix_hit_rate
    floor = 0.0 if smoke else 0.5
    if not st_on.prefix_hits or hit_rate <= floor:
        raise RuntimeError(
            f"shared-prefix workload must hit the radix cache "
            f"(hit rate {hit_rate:.3f} <= {floor}, "
            f"{st_on.prefix_hits} hits)")
    launch_delta = st_off.prefill_launches - st_on.prefill_launches
    if launch_delta <= 0:
        raise RuntimeError(
            f"adopted prefix pages must eliminate prefill launches: "
            f"on={st_on.prefill_launches} vs off={st_off.prefill_launches}")
    report("serve.prefix.prefill_launches_saved", launch_delta,
           f"{st_off.prompt_tokens_ingested - st_on.prompt_tokens_ingested}"
           f" prompt tokens never re-prefilled")

    if json_path:
        stamp = timestamp or datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds")
        for label, r in results.items():
            st = r["stats"]
            payload = {
                "bench": "serve_throughput",
                "config": cfg.name,
                "kernel_backend": kernel_backend,
                "seed": seed,
                "timestamp": stamp,
                "mode": "prefix",
                "prefix_cache": label,
                "requests": requests,
                "sys_tokens": sys_tokens,
                "tokens_per_sec": round(r["tok_s"], 2),
                "tokens_generated": st.tokens_generated,
                "steps": st.steps,
                "prefill_launches": st.prefill_launches,
                "prefill_launch_delta_vs_off": launch_delta
                if label == "on" else None,
                "prompt_tokens_ingested": st.prompt_tokens_ingested,
                "decode_launches": st.decode_launches,
                "prefix_hits": st.prefix_hits,
                "prefix_tokens_reused": st.prefix_tokens_reused,
                "prefix_evictions": st.prefix_evictions,
                "prefix_hit_rate": round(hit_rate, 4)
                if label == "on" else None,
                "peak_kv_blocks_used": st.peak_blocks_used,
            }
            n = _append_trajectory(json_path, payload)
        report("serve.prefix.json", os.path.relpath(json_path),
               f"paired records appended ({n} total)")
    return hit_rate


def _oracle_rounds(prefix, cont, k, ngram_max, ngram_min=1):
    """Verify launches a prompt-lookup drafter needs to emit ``cont`` after
    ``prefix`` (greedy parity makes the token stream drafter-independent, so
    this replays the exact accept/advance loop the engine will run)."""
    from repro.serve.spec.drafter import _find_continuation
    hist = list(prefix)
    i = rounds = 0
    while i < len(cont):
        n_ok = 0
        for j, d in enumerate(_find_continuation(hist, k, ngram_max,
                                                 ngram_min)):
            if i + j < len(cont) and d == cont[i + j]:
                n_ok += 1
            else:
                break
        rounds += 1
        i += n_ok + 1          # accepted run + the launch's own sampled token
        hist = list(prefix) + cont[:i]
    return rounds


def _spec_workload(cfg, mesh, plan, kernel_backend, rng, n_requests, plen,
                   tail, k, ngram_max, s_max):
    """Repetitive-prompt workload: self-continuation prompts selected for
    cyclic greedy output.

    Greedy decode of a tiny random-weight model falls into short token
    cycles — the regime prompt-lookup drafting is built for — but not from
    every starting point.  So: warm-generate ``plen + tail`` tokens from a
    pool of random 4-token seeds, take ``seed + first plen tokens`` as the
    prompt, and keep the ``n_requests`` candidates whose *next* ``tail``
    tokens (under greedy parity, exactly how the bench decode starts) need
    the fewest oracle verify launches.  The selection uses only the plain
    engine's own output — no speculative pass runs until the timed pair."""
    pool = 6 * n_requests
    eng = build_engine(cfg, mesh, plan, seed=0,
                       engine_cfg=EngineConfig(s_max=s_max,
                                               buckets=(1, 2, 4, 8),
                                               block_pos_stride=8,
                                               kernel_backend=kernel_backend))
    seeds = [rng.integers(0, cfg.vocab_size, size=4).tolist()
             for _ in range(pool)]
    warm = generate(eng, seeds, SamplingParams(max_tokens=plen + tail))

    def rounds(c):
        full = list(c.prompt) + list(c.tokens)
        cut = len(c.prompt) + plen
        return _oracle_rounds(full[:cut], full[cut:], k, ngram_max)

    order = sorted(range(pool), key=lambda i: rounds(warm[i]))
    return [list(warm[i].prompt) + list(warm[i].tokens)[:plen]
            for i in order[:n_requests]]


def run_speculation(report, json_path="auto", config=None, timestamp=None,
                    kernel_backend=None, seed=0, requests=8, max_tokens=32,
                    smoke=False):
    """Paired speculative/non-speculative full passes over one repetitive
    greedy workload; appends BOTH records to the trajectory.

    Two explicit raises (not asserts) gate the pair:

      * greedy parity — the speculative engine must emit token-for-token
        what the plain engine emits (CI's bench-smoke invariant);
      * >= 2x mean per-request decode tokens/sec with the n-gram drafter
        on this workload (full runs only — smoke passes check parity but
        skip the timing claim on shared CI hosts).
    """
    from repro.serve.spec import SpeculationConfig
    if json_path == "auto":
        json_path = None if smoke else JSON_PATH
    if kernel_backend is None:
        from repro.kernels import default_kernel_backend
        kernel_backend = default_kernel_backend()
    # default model: the spec-bench sibling (smaller than srv-bench).  Its
    # greedy dynamics have stronger cyclic attractors, which is the regime
    # the prompt-lookup drafter targets; srv-bench's outputs are too chaotic
    # for an n-gram oracle to predict (~1.4x launch reduction ceiling).
    cfg = _bench_config("spec-bench" if config in (None, "srv-bench")
                        else config)
    mesh = jax.make_mesh((1, 16), (DATA, MODEL),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    plan = MeshPlan((DATA, MODEL), (1, 16), 4, 4)
    spec_k, ngram_max = 6, 3
    plen, tail = (12, 8) if smoke else (32, 24)
    s_max = -(-(4 + plen + max_tokens + 8) // 16) * 16
    prompts = _spec_workload(cfg, mesh, plan, kernel_backend,
                             np.random.default_rng(seed), requests, plen,
                             tail, spec_k, ngram_max, s_max)
    sampling = [SamplingParams(max_tokens=max_tokens)] * requests

    results = {}
    for label, speculation in (
            ("off", None),
            ("ngram", SpeculationConfig(drafter="ngram", k=spec_k,
                                        ngram_max=ngram_max))):
        ec = EngineConfig(s_max=s_max, buckets=(1, 2, 4, 8),
                          block_pos_stride=8, kernel_backend=kernel_backend,
                          speculation=speculation)
        eng = build_engine(cfg, mesh, plan, engine_cfg=ec, seed=0)
        # warm pass: full workload once (compiles every executable the
        # timed pass uses, incl. verify_bs{N}), then reset all counters
        generate(eng, prompts, sampling)
        eng.stats = EngineStats()
        eng.queue.max_depth = 0
        for ev in eng.kernel_events().values():
            ev.launches = 0
        outs = generate(eng, prompts, sampling)
        st = eng.stats
        dec = [c.decode_tok_s for c in outs if c.decode_tok_s is not None]
        results[label] = {
            "outs": [c.tokens for c in outs],
            "decode_tok_s_mean": float(np.mean(dec)) if dec else 0.0,
            "stats": st,
            "tok_s": eng.throughput_tok_s(),
            "executables": sorted(eng.kernel_events()),
        }
        report(f"serve.spec.{label}.decode_tok_s_mean",
               f"{results[label]['decode_tok_s_mean']:.1f}",
               f"per-request decode rate over {len(dec)} requests")
        report(f"serve.spec.{label}.launches", st.launches,
               f"decode {st.decode_launches} + prefill {st.prefill_launches}"
               f" + verify {st.spec_launches}")
        if speculation is not None:
            report("serve.spec.ngram.accept_rate",
                   f"{st.spec_accept_rate:.2f}",
                   f"{st.spec_accepted_tokens}/{st.spec_proposed_tokens} "
                   f"draft tokens accepted")

    if results["off"]["outs"] != results["ngram"]["outs"]:
        raise RuntimeError(
            "speculative greedy decode must match non-speculative greedy "
            "token-for-token on the same seed")
    report("serve.spec.greedy_parity", "ok",
           "speculative == non-speculative token-for-token")
    off, on = (results["off"]["decode_tok_s_mean"],
               results["ngram"]["decode_tok_s_mean"])
    speedup = on / off if off else 0.0
    report("serve.spec.decode_speedup", f"{speedup:.2f}x",
           "mean per-request decode tokens/sec, ngram vs off")
    if not smoke and speedup < 2.0:
        raise RuntimeError(
            f"speculative decode speedup {speedup:.2f}x < 2x on the "
            f"repetitive-prompt workload (accept rate "
            f"{results['ngram']['stats'].spec_accept_rate:.2f})")

    if json_path:
        stamp = timestamp or datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds")
        for label, r in results.items():
            st = r["stats"]
            payload = {
                "bench": "serve_throughput",
                "config": cfg.name,
                "kernel_backend": kernel_backend,
                "seed": seed,
                "timestamp": stamp,
                "mode": "speculation",
                "speculation": label,
                "tokens_per_sec": round(r["tok_s"], 2),
                "decode_tok_s_mean": round(r["decode_tok_s_mean"], 2),
                "decode_speedup_vs_off": round(speedup, 2)
                if label == "ngram" else None,
                "tokens_generated": st.tokens_generated,
                "steps": st.steps,
                "launches": st.launches,
                "decode_launches": st.decode_launches,
                "prefill_launches": st.prefill_launches,
                "spec_launches": st.spec_launches,
                "proposed_tokens": st.spec_proposed_tokens,
                "accepted_tokens": st.spec_accepted_tokens,
                "accept_rate": round(st.spec_accept_rate, 4)
                if st.spec_proposed_tokens else None,
                "spec_rollbacks": st.spec_rollbacks,
                "executables": r["executables"],
            }
            n = _append_trajectory(json_path, payload)
        report("serve.spec.json", os.path.relpath(json_path),
               f"paired records appended ({n} total)")
    return speedup


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=None,
                    help="smoke mode: run only N engine steps")
    ap.add_argument("--config", default="srv-bench",
                    help="registry architecture to serve (reduced smoke "
                         "sibling), e.g. mamba2_780m; default: the built-in "
                         "dense bench model")
    ap.add_argument("--seed", type=int, default=0,
                    help="workload sampling seed (prompt lengths, token "
                         "ids, per-request max_tokens); recorded in the "
                         "trajectory entry for reproducible comparisons")
    ap.add_argument("--timestamp", default=None,
                    help="timestamp recorded in the trajectory entry "
                         "(default: current UTC time)")
    ap.add_argument("--json", default=None,
                    help="append machine-readable results to this path "
                         "(default: BENCH_serve.json on full runs only; "
                         "smoke runs don't touch the trajectory)")
    ap.add_argument("--kernel-backend", default=None,
                    choices=["jnp", "pallas", "pallas-interpret"],
                    help="step-kernel backend: jnp materializes gathered "
                         "K/V copies; pallas reads pages in place inside "
                         "the fused paged-attention kernel (paired runs "
                         "give the trajectory a before/after comparison); "
                         "default: REPRO_KERNEL_BACKEND or jnp")
    ap.add_argument("--speculation", action="store_true",
                    help="run the PAIRED speculative/non-speculative pass "
                         "(repetitive greedy workload, n-gram drafter) "
                         "instead of the standard bench; appends two "
                         "records and enforces greedy parity + the >= 2x "
                         "decode-rate claim (--steps downgrades it to a "
                         "parity-only smoke)")
    ap.add_argument("--faults", action="store_true",
                    help="run the PAIRED fault-free/degraded pass: the "
                         "same workload served plain and under the seeded "
                         "chaos profile (launch/device/NaN/pool/stall "
                         "faults with the default retry + quarantine "
                         "policy); appends two records with completion "
                         "rate and fault counters (--steps downgrades it "
                         "to a terminality-only smoke)")
    ap.add_argument("--spec-requests", type=int, default=8,
                    help="workload size for --speculation")
    ap.add_argument("--spec-tokens", type=int, default=32,
                    help="per-request max_tokens for --speculation")
    ap.add_argument("--prefix-workload", action="store_true",
                    help="run the PAIRED cache-off/cache-on pass over a "
                         "shared-system-prefix workload; appends two "
                         "records and enforces greedy parity, a radix "
                         "cache hit-rate floor, and strictly fewer "
                         "prefill launches cache-on (--steps downgrades "
                         "the hit-rate floor to > 0)")
    ap.add_argument("--prefix-requests", type=int, default=8,
                    help="workload size for --prefix-workload")
    args = ap.parse_args()
    use_compile_cache()
    print("name,value,derived")

    def report(name, value, derived=""):
        print(f"{name},{value},{derived}", flush=True)

    if args.faults:
        run_faults(report, json_path=args.json or "auto",
                   config=args.config, timestamp=args.timestamp,
                   kernel_backend=args.kernel_backend, seed=args.seed,
                   smoke=args.steps is not None)
        return
    if args.prefix_workload:
        run_prefix(report, json_path=args.json or "auto",
                   config=args.config, timestamp=args.timestamp,
                   kernel_backend=args.kernel_backend, seed=args.seed,
                   requests=args.prefix_requests,
                   smoke=args.steps is not None)
        return
    if args.speculation:
        run_speculation(report, json_path=args.json or "auto",
                        config=args.config, timestamp=args.timestamp,
                        kernel_backend=args.kernel_backend, seed=args.seed,
                        requests=args.spec_requests,
                        max_tokens=args.spec_tokens,
                        smoke=args.steps is not None)
        return
    run(report, steps=args.steps, json_path=args.json or "auto",
        config=args.config, timestamp=args.timestamp,
        kernel_backend=args.kernel_backend, seed=args.seed)


if __name__ == "__main__":
    main()
