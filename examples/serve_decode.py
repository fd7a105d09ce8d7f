"""Batched serving example: greedy decode on the SHMEM grid with the
weights-stationary gemv decode path (EXPERIMENTS.md §Perf hillclimb 3),
comparing decode modes.

  XLA_FLAGS=--xla_force_host_platform_device_count=16 \\
  PYTHONPATH=src python examples/serve_decode.py

With ``--config <arch>`` (e.g. ``--config mamba2_780m``) the script instead
serves that architecture's reduced smoke sibling through the
continuous-batching engine — the StateSpec ABI makes SSM and hybrid
families first-class engine citizens (dense per-slot state rides alongside
paged KV).
"""

import argparse  # noqa: E402
import os
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

import time  # noqa: E402

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.models import params as pm  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.partition import DATA, MeshPlan, MODEL  # noqa: E402
from repro.serve.decode import (cache_pspecs, cache_specs,  # noqa: E402
                                make_decode_step)

ap = argparse.ArgumentParser()
ap.add_argument("--config", default=None,
                help="registry arch for an engine smoke run (reduced "
                     "sibling), e.g. mamba2_780m; underscores accepted")
ARGS = ap.parse_args()

mesh = jax.make_mesh((1, 16), (DATA, MODEL),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
plan = MeshPlan((DATA, MODEL), (1, 16), 4, 4)

if ARGS.config:
    # engine smoke on a registry architecture (SSM/hybrid included)
    from repro.configs import get_config  # noqa: E402
    from repro.configs.registry import reduced  # noqa: E402
    from repro.serve.engine import (EngineConfig, SamplingParams,  # noqa: E402
                                    build_engine, generate)
    smoke = reduced(get_config(ARGS.config.replace("_", "-")))
    eng = build_engine(smoke, mesh, plan, seed=0,
                       engine_cfg=EngineConfig(s_max=64, buckets=(1, 2, 4),
                                               block_pos_stride=16))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, min(smoke.vocab_size, 256),
                            size=int(rng.integers(2, 9))).tolist()
               for _ in range(4)]
    outs = generate(eng, prompts, SamplingParams(max_tokens=8))
    for c in outs:
        print(f"{smoke.name} {c.request_id}: prompt[{len(c.prompt)}] -> "
              f"{c.tokens} ({c.finish_reason})")
    print(f"{smoke.name} ({smoke.family}): "
          f"state operands {eng.state_specs.step_operands()}, "
          f"{eng.stats.tokens_generated} tokens, "
          f"{eng.queue.n_executables} executables, "
          f"peak state bytes {eng.peak_kv_bytes()}")
    raise SystemExit(0)

cfg = ModelConfig(name="srv", family="dense", d_model=256, n_layers=4,
                  n_heads=8, n_kv_heads=4, d_ff=1024, vocab_size=4096,
                  param_dtype=jnp.float32, compute_dtype=jnp.float32,
                  attn_block_kv=64)
B, S_MAX, N_TOK = 4, 128, 24

for mode in ("batched", "gemv"):
    step, specs, pctx = make_decode_step(cfg, mesh, plan, batch=B,
                                         s_max=S_MAX, mode=mode)
    params = pm.init_params(specs, seed=0)
    pspecs = pm.param_pspecs(specs)
    params = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, pspecs)
    cs = cache_specs(cfg, plan, B, S_MAX, mode)
    cps = cache_pspecs(cfg, mode, pctx.data_axes)
    cache = jax.tree.map(
        lambda sd, sp: jax.device_put(jnp.zeros(sd.shape, sd.dtype),
                                      NamedSharding(mesh, sp)), cs, cps)
    tok = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(B,)), jnp.int32)
    seq = [np.asarray(tok)]
    t0 = None
    for t in range(N_TOK):
        logits, cache = step(params,
                             cache,
                             jax.device_put(tok, NamedSharding(mesh, P(DATA))),
                             jnp.int32(t))
        if t == 0:
            jax.block_until_ready(logits)
            t0 = time.time()
        tok = jnp.argmax(logits[:, 0, :cfg.vocab_size], -1).astype(jnp.int32)
        seq.append(np.asarray(tok))
    jax.block_until_ready(tok)
    dt = (time.time() - t0) / (N_TOK - 1) * 1e3
    print(f"mode={mode:8s} {dt:7.1f} ms/token (host CPU)  "
          f"first seq: {np.stack(seq, 1)[0][:10].tolist()}")
print("note: the two modes use different weight-storage skews, so the same"
      " seed yields different logical models — per-mode correctness vs the"
      " oracle is proven in tests/test_decode.py")

# --- continuous-batching engine on the same model -------------------------
# Mixed-length prompts served through the CommandQueue: one step executable
# per batch bucket, per-slot positions, paged-KV admission (docs/serving.md).
from repro.serve.engine import (EngineConfig, SamplingParams,  # noqa: E402
                                build_engine, generate)

eng = build_engine(cfg, mesh, plan,
                   engine_cfg=EngineConfig(s_max=S_MAX, buckets=(1, 2, 4),
                                           block_pos_stride=16), seed=0)
rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab_size,
                        size=int(rng.integers(2, 9))).tolist()
           for _ in range(6)]
outs = generate(eng, prompts, SamplingParams(max_tokens=8))
for c in outs[:3]:
    print(f"engine {c.request_id}: prompt[{len(c.prompt)}] -> {c.tokens}")
print(f"engine: {eng.stats.tokens_generated} tokens, "
      f"{eng.queue.n_executables} executables "
      f"(buckets {sorted(eng.kernel_events())}), "
      f"{eng.throughput_tok_s():.1f} tok/s over the summed step time")
print(f"engine: chunked prefill ingested "
      f"{eng.stats.prompt_tokens_ingested} prompt tokens in "
      f"{eng.stats.prefill_launches} launches "
      f"({eng.stats.prefill_chunk_launches} chunked)")

# streaming front-end: tokens arrive as they are sampled
stream_prompt = prompts[0]
print("engine stream:", end=" ", flush=True)
for tok in eng.stream(stream_prompt, SamplingParams(max_tokens=6)):
    print(tok, end=" ", flush=True)
print()
