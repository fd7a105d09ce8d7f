#!/usr/bin/env python3
"""Bring-up smoke of the serving path on TPU v5e, at published widths.

Run from the repository root:

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # the 2x2 PE grid of a four-chip host

One seeded process drives the normal entry points and prints one JSON line
per phase, then, only when every phase passed, the final line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Phases of the default run:

* ``supervised`` — qwen2-0.5b behind ``ReplicaSupervisor`` with one worker
  SIGKILLed mid-generation.  Every stream must end ``length``/``stop``
  having delivered each token exactly once (0 lost, 0 duplicated against
  the worker's completion).  Runs first: until it ends, this process never
  touches a device, so the worker can own the chip.
* ``service`` — qwen2-0.5b on the 1x1 grid through ``GenerateService``,
  8 requests of 128-1024 prompt tokens, once per kernel backend (``jnp``,
  then the compiled ``pallas``).  All logits must be finite and the two
  backends' logits for each request's prefill and first decode step must
  agree within ``LOGIT_TOL``.
* ``ssm`` — mamba2-780m through the engine, 4 requests, both backends, the
  same checks.  Random-weight bf16 mamba2 amplifies rounding with depth
  (two jnp runs that only chunk the prompt differently already differ by
  ~20% at 48 layers), so the gated logits comparison runs the published
  widths cut to ``SSM_CHECK_LAYERS`` layers; the full-depth difference is
  reported beside it.

``--four-chips`` runs only the multi-chip path: qwen2-0.5b on the 2x2 grid,
with the 1x1 engine's weights re-blocked (``reblock_params``), against the
1x1 engine on device 0, with the same prompts and logits check.

The script exits non-zero without the ``ok`` line when JAX finds no TPU,
when a phase raises, or when a check fails.  It sets no ``JAX_PLATFORMS``
or ``XLA_FLAGS``.  Compiled executables go to the persistent compilation
cache (``repro.runtime.compile_cache``), so a second run reports hits.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import gc
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402

from repro.ckpt.checkpoint import reblock_params  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch.mesh import device_mesh  # noqa: E402
from repro.models import params as pm  # noqa: E402
from repro.models.transformer import param_specs  # noqa: E402
from repro.runtime.compile_cache import use_compile_cache  # noqa: E402
from repro.serve.engine import (EngineConfig, SamplingParams,  # noqa: E402
                                build_engine, generate)
from repro.serve.service import GenerateService, ServiceConfig  # noqa: E402
from repro.serve.supervisor import (EngineSpec,  # noqa: E402
                                    ReplicaSupervisor, SupervisorConfig)

SEED = 0
S_MAX = 2048
PREFILL_LADDER = (256,)
# Two bf16 forward passes that differ only in the attention / SSD kernels
# (or in the PE grid): per logits row, ||a - b|| / ||a|| against the
# reference row a (jnp, or the 1x1 grid).  The largest |a - b| over the
# largest |a| is reported beside it.
LOGIT_TOL = 0.05
SSM_CHECK_LAYERS = 4
DONE = ("length", "stop")

_PROBE = ("import json, jax; d = jax.devices(); print(json.dumps("
          "{'platform': d[0].platform, 'kind': d[0].device_kind, "
          "'count': len(d)}))")


class CheckFailed(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


# -- compile-cache and device accounting ------------------------------------

_CACHE = {"hits": 0, "misses": 0}


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _CACHE["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _CACHE["misses"] += 1


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _compile_s(engine) -> float:
    return round(sum(ev.build_time_s for ev in engine.queue.events.values()),
                 3)


class _Phase:
    """Wall time and compile-cache events of one phase."""

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.cache0 = dict(_CACHE)
        return self

    def __exit__(self, *exc):
        self.wall_s = round(time.perf_counter() - self.t0, 3)
        self.cache = {k: _CACHE[k] - self.cache0[k] for k in _CACHE}


# -- workloads and logits ----------------------------------------------------

def workload(seed: int, n: int, vocab: int, prompt_len, out_len):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, size=int(rng.integers(*prompt_len)))
               .tolist() for _ in range(n)]
    max_tokens = [int(rng.integers(*out_len)) for _ in range(n)]
    return prompts, max_tokens


class LogitTap:
    """Keeps, per prompt, the logits rows its first two tokens were sampled
    from (the end of its prefill and its first decode step), and counts
    non-finite rows over every committed step."""

    def __init__(self, engine):
        self.rows = {}
        self.nonfinite = 0
        commit = engine._commit

        def tapped(sd, rows, fed, skip=frozenset()):
            for s, r in enumerate(sd.slots):
                if r is None or s in skip:
                    continue
                if not np.isfinite(rows[s]).all():
                    self.nonfinite += 1
                samples = r.num_cached + fed[s] == len(r.seq_tokens)
                if samples and len(r.output_tokens) < 2:
                    self.rows.setdefault(tuple(r.prompt), []).append(
                        np.asarray(rows[s], np.float32))
            return commit(sd, rows, fed, skip)

        engine._commit = tapped


def compare_logits(ref: LogitTap, got: LogitTap, tol=LOGIT_TOL) -> dict:
    """Largest relative row error at prefill and at the first decode step,
    checked against ``tol`` (None: reported only); a decode row is compared
    only where both runs fed it the same first token (greedy argmax
    near-ties may flip with random weights)."""
    errs = {"prefill": [], "decode": []}
    skipped = 0
    for key, rows_r in ref.rows.items():
        rows_g = got.rows[key]
        for i, (a, b) in enumerate(zip(rows_r, rows_g)):
            if i == 1 and np.argmax(rows_r[0]) != np.argmax(rows_g[0]):
                skipped += 1
                continue
            errs["prefill" if i == 0 else "decode"].append((
                float(np.linalg.norm(a - b) / np.linalg.norm(a)),
                float(np.abs(a - b).max() / np.abs(a).max())))
    check(len(errs["prefill"]) == len(ref.rows),
          "a request has no prefill logits")
    check(errs["decode"], "no decode row could be compared")
    out = {"tol": tol, "decode_rows_skipped": skipped}
    for kind, e in errs.items():
        out[f"{kind}_rel_l2"] = max(x for x, _ in e)
        out[f"{kind}_rel_maxabs"] = max(y for _, y in e)
        out[f"{kind}_rows"] = len(e)
    check(tol is None or max(out["prefill_rel_l2"],
                             out["decode_rel_l2"]) <= tol,
          f"logits disagree: {out}")
    return out


def check_completions(comps, max_tokens, tap: LogitTap) -> int:
    for c, n in zip(comps, max_tokens):
        check(c is not None and c.finish_reason in DONE,
              f"request ended {getattr(c, 'finish_reason', None)!r}")
        check(len(c.tokens) == n or c.finish_reason == "stop",
              f"{len(c.tokens)} of {n} tokens")
    check(tap.nonfinite == 0, f"{tap.nonfinite} non-finite logits rows")
    return sum(len(c.tokens) for c in comps)


def engine_config(backend: str, buckets) -> EngineConfig:
    return EngineConfig(s_max=S_MAX, buckets=buckets,
                        prefill_chunks=PREFILL_LADDER,
                        kernel_backend=backend)


# -- phases -------------------------------------------------------------------

def run_supervised(cfg, prompts, max_tokens) -> dict:
    """Serve behind a supervisor; SIGKILL the worker once mid-stream."""
    spec = EngineSpec(model_cfg=cfg, plan=None,
                      engine_cfg=engine_config("pallas", (1, 8)), seed=SEED)
    half = sum(max_tokens) // 2

    async def drive(ckpt):
        sup_cfg = SupervisorConfig(checkpoint_path=ckpt,
                                   checkpoint_every_steps=1, max_respawns=2,
                                   ready_timeout_s=900.0)
        async with ReplicaSupervisor(spec, sup_cfg) as sup:
            streams = [await sup.submit(p, max_tokens=n)
                       for p, n in zip(prompts, max_tokens)]
            got = {s.request_id: [] for s in streams}

            async def consume(s):
                async for tok in s:
                    got[s.request_id].append(tok)

            async def killer():
                while sum(map(len, got.values())) < half:
                    await asyncio.sleep(0.01)
                await sup.kill_replica()
                while sup.n_spawns < 2:
                    await asyncio.sleep(0.05)

            await asyncio.gather(killer(), *map(consume, streams))
            return ([got[s.request_id] for s in streams],
                    [s.completion for s in streams], sup.n_failovers,
                    sup.metrics.snapshot()["failover"])

    with _Phase() as ph, tempfile.TemporaryDirectory() as tmp:
        delivered, comps, failovers, fo = asyncio.run(
            drive(os.path.join(tmp, "replica.ckpt")))
    lost = dup = 0
    for got, comp in zip(delivered, comps):
        check(comp is not None and comp.finish_reason in DONE,
              f"supervised request ended "
              f"{getattr(comp, 'finish_reason', None)!r}")
        ok = 0
        for a, b in zip(got, comp.tokens):
            if a != b:
                break
            ok += 1
        lost += len(comp.tokens) - ok
        dup += len(got) - ok
    check(failovers == 1, f"{failovers} failovers, 1 kill injected")
    check(lost == 0 and dup == 0, f"{lost} tokens lost, {dup} duplicated")
    return {"phase": "supervised", "config": cfg.name,
            "kernel_backend": "pallas", "requests": len(prompts),
            "tokens": sum(map(len, delivered)), "kills": 1,
            "failovers": failovers, "tokens_lost": lost,
            "tokens_duplicated": dup,
            "recovery_s": fo["recovery_s"]["mean"], "wall_s": ph.wall_s,
            "compile_s": None, "peak_bytes_in_use": None,
            "compile_cache": "in the worker process; not counted here",
            "delivered": delivered}


def _record(eng, comps, max_tokens, tap: LogitTap, ph: _Phase) -> dict:
    return {"requests": len(comps),
            "tokens": check_completions(comps, max_tokens, tap),
            "compile_s": _compile_s(eng), "wall_s": ph.wall_s,
            "peak_bytes_in_use": _peak_bytes(), "compile_cache": ph.cache}


def run_service(cfg, mesh, plan, backend, prompts, max_tokens):
    """One GenerateService pass; returns (record, tap, engine)."""
    with _Phase() as ph:
        eng = build_engine(cfg, mesh, plan, seed=SEED,
                           engine_cfg=engine_config(backend, (1, 8)))
        tap = LogitTap(eng)

        async def drive():
            async with GenerateService(
                    eng, ServiceConfig(max_pending=len(prompts))) as svc:
                streams = [await svc.submit(p, max_tokens=n)
                           for p, n in zip(prompts, max_tokens)]
                return [(await s.drain())[1] for s in streams]

        comps = asyncio.run(drive())
    return _record(eng, comps, max_tokens, tap, ph), tap, eng


def run_engine(cfg, mesh, plan, backend, prompts, max_tokens, buckets,
               params=None):
    """One ``generate()`` pass; returns (record, tap, engine)."""
    with _Phase() as ph:
        eng = build_engine(cfg, mesh, plan, seed=SEED, params=params,
                           engine_cfg=engine_config(backend, buckets))
        tap = LogitTap(eng)
        comps = generate(eng, prompts,
                         [SamplingParams(max_tokens=n) for n in max_tokens])
    return _record(eng, comps, max_tokens, tap, ph), tap, eng


def service_phase(cfg, sup: dict, sup_prompts, sup_max) -> dict:
    mesh, plan = device_mesh()
    prompts, max_tokens = workload(SEED + 1, 8, cfg.vocab_size,
                                   (128, 1025), (32, 65))
    rec_j, tap_j, eng = run_service(cfg, mesh, plan, "jnp", prompts,
                                    max_tokens)
    del eng
    gc.collect()
    rec_p, tap_p, eng = run_service(cfg, mesh, plan, "pallas", prompts,
                                    max_tokens)
    # the supervised streams against the same executables, uninterrupted
    ref = generate(eng, sup_prompts,
                   [SamplingParams(max_tokens=n) for n in sup_max])
    same = sum(r.tokens == d for r, d in zip(ref, sup["delivered"]))
    return {"phase": "service", "config": cfg.name, "grid": "1x1",
            "jnp": rec_j, "pallas": rec_p,
            "logits": compare_logits(tap_j, tap_p),
            "supervised_streams_equal_uninterrupted":
            f"{same}/{len(ref)}"}


def ssm_phase() -> dict:
    cfg = get_config("mamba2-780m")
    cut = dataclasses.replace(cfg, n_layers=SSM_CHECK_LAYERS)
    mesh, plan = device_mesh()
    prompts, max_tokens = workload(SEED + 2, 4, cfg.vocab_size,
                                   (128, 1025), (32, 33))
    recs, taps, cut_taps = {}, {}, {}
    for backend in ("jnp", "pallas"):
        recs[backend], taps[backend], eng = run_engine(
            cfg, mesh, plan, backend, prompts, max_tokens, (4,))
        del eng
        _, cut_taps[backend], eng = run_engine(
            cut, mesh, plan, backend, prompts, max_tokens, (4,))
        del eng
        gc.collect()
    return {"phase": "ssm", "config": cfg.name, "grid": "1x1", **recs,
            "logits": {"n_layers": cut.n_layers, **compare_logits(
                cut_taps["jnp"], cut_taps["pallas"])},
            "logits_full_depth": {"n_layers": cfg.n_layers, **compare_logits(
                taps["jnp"], taps["pallas"], tol=None)}}


def four_chip_phase() -> dict:
    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, "
          f"JAX found {len(devices)}")
    cfg = get_config("qwen2-0.5b")
    prompts, max_tokens = workload(SEED + 1, 8, cfg.vocab_size,
                                   (128, 1025), (32, 65))
    mesh1, plan1 = device_mesh(devices[:1])
    rec1, tap1, eng1 = run_engine(cfg, mesh1, plan1, "pallas", prompts,
                                  max_tokens, (1, 8))
    mesh4, plan4 = device_mesh(devices[:4])
    q, r = plan4.grid_q, plan4.grid_r
    specs4 = param_specs(cfg, q, r, preskew=False)
    params4 = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh4, s)),
        reblock_params(eng1.params, eng1.specs, 1, 1, q, r),
        pm.param_pspecs(specs4))
    del eng1
    gc.collect()
    rec4, tap4, _ = run_engine(cfg, mesh4, plan4, "pallas", prompts,
                               max_tokens, (1, 8), params=params4)
    return {"phase": "four_chips", "config": cfg.name,
            "kernel_backend": "pallas", "grid_1x1": rec1,
            f"grid_{q}x{r}": rec4, "logits": compare_logits(tap1, tap4)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-grid path and its 1x1 reference")
    args = ap.parse_args()
    use_compile_cache()
    jax.monitoring.register_event_listener(_on_event)

    if not args.four_chips:
        # which devices are there, asked by a child that exits at once: this
        # process must stay off the chip until the supervised phase is over
        probe = json.loads(subprocess.run(
            [sys.executable, "-c", _PROBE], capture_output=True, text=True,
            timeout=600, check=True).stdout.strip().splitlines()[-1])
        check(probe["platform"] == "tpu",
              f"JAX finds no TPU (platform {probe['platform']!r})")
        cfg = get_config("qwen2-0.5b")
        sup_prompts, sup_max = workload(SEED, 4, cfg.vocab_size,
                                        (128, 513), (32, 33))
        sup = run_supervised(cfg, sup_prompts, sup_max)
        emit({k: v for k, v in sup.items() if k != "delivered"})

    dev = jax.devices()[0]
    check(dev.platform == "tpu", f"JAX finds no TPU (platform "
          f"{dev.platform!r})")
    if args.four_chips:
        emit(four_chip_phase())
    else:
        emit(service_phase(cfg, sup, sup_prompts, sup_max))
        emit(ssm_phase())
    emit({"ok": True, "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
