"""End-to-end serving-engine tests on the CPU mesh.

The load-bearing assertion is token parity: ``engine.generate()`` must emit
exactly the tokens the pre-existing single-shot decode path emits for the
same prompts/params — the continuous-batching machinery (per-slot positions,
slot resets, bucket migration) must be invisible to the math.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import params as pm
from repro.models.config import ModelConfig
from repro.partition import DATA
from repro.serve.decode import cache_pspecs, cache_specs, make_decode_step
from repro.serve.engine import (EngineConfig, RequestState, SamplingParams,
                                build_engine, generate)

CFG = ModelConfig(name="eng", family="dense", d_model=64, n_layers=2,
                  n_heads=8, n_kv_heads=4, d_ff=128, vocab_size=128,
                  param_dtype=jnp.float32, compute_dtype=jnp.float32,
                  attn_block_kv=32)
S_MAX = 32


def _device_params(mesh, specs):
    params = pm.init_params(specs, seed=0)
    pspecs = pm.param_pspecs(specs)
    return jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        params, pspecs)


def _single_shot_greedy(mesh, plan, prompts, n_tok):
    """The pre-existing serving path: one fixed batch, scalar position."""
    B, plen = prompts.shape
    step, specs, pctx = make_decode_step(CFG, mesh, plan, batch=B,
                                         s_max=S_MAX, mode="gemv")
    params_d = _device_params(mesh, specs)
    cs = cache_specs(CFG, plan, B, S_MAX, "gemv")
    cps = cache_pspecs(CFG, "gemv", pctx.data_axes)
    cache = jax.tree.map(
        lambda sd, sp: jax.device_put(jnp.zeros(sd.shape, sd.dtype),
                                      NamedSharding(mesh, sp)), cs, cps)
    out = [[] for _ in range(B)]
    tok = prompts[:, 0]
    for t in range(plen + n_tok - 1):
        logits, cache = step(params_d, cache,
                             jax.device_put(jnp.asarray(tok),
                                            NamedSharding(mesh, P(DATA))),
                             jnp.int32(t))
        nxt = np.asarray(jnp.argmax(logits[:, 0, :CFG.vocab_size], -1))
        if t + 1 < plen:
            tok = prompts[:, t + 1]
        else:
            tok = nxt.astype(np.int32)
            for b in range(B):
                out[b].append(int(nxt[b]))
    return out, params_d


def test_generate_matches_single_shot_decode(mesh16, plan16):
    B, plen, n_tok = 4, 5, 8
    prompts = np.random.default_rng(0).integers(
        0, CFG.vocab_size, size=(B, plen)).astype(np.int32)
    expect, params_d = _single_shot_greedy(mesh16, plan16, prompts, n_tok)

    ec = EngineConfig(s_max=S_MAX, buckets=(1, 2, 4), block_pos_stride=4)
    eng = build_engine(CFG, mesh16, plan16, engine_cfg=ec, params=params_d)
    outs = generate(eng, [p.tolist() for p in prompts],
                    SamplingParams(max_tokens=n_tok))
    for b, c in enumerate(outs):
        assert c.tokens == expect[b], (b, c.tokens, expect[b])
        assert c.finish_reason == "length"


def test_mixed_length_workload_one_executable_per_bucket(mesh16, plan16):
    """16 requests of mixed prompt/output lengths share bucketed
    executables: no per-request (or per-shape) recompiles.  Since chunked
    prefill the invariant is one executable per (bucket, chunk-length)
    actually used — and prefill launches amortize over prompt tokens."""
    ec = EngineConfig(s_max=S_MAX, buckets=(1, 2, 4, 8), block_pos_stride=4)
    eng = build_engine(CFG, mesh16, plan16, engine_cfg=ec, seed=0)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, CFG.vocab_size,
                            size=int(rng.integers(2, 10))).tolist()
               for _ in range(16)]
    sampling = [SamplingParams(max_tokens=int(rng.integers(3, 8)))
                for _ in range(16)]
    outs = generate(eng, prompts, sampling)
    assert len(outs) == 16
    for c, sp in zip(outs, sampling):
        assert c.finish_reason == "length"
        assert len(c.tokens) == sp.max_tokens
    # at most one compiled executable per (bucket, chunk-length) used
    used = set(eng.kernel_events())
    assert eng.queue.n_executables == len(used)
    decode_used = {n for n in used if n.startswith("serve_step_bs")}
    chunk_used = {n for n in used if n.startswith("prefill_bs")}
    assert used == decode_used | chunk_used
    assert len(decode_used) <= len(ec.buckets)
    assert 0 < len(chunk_used) <= \
        len(ec.buckets) * len(eng.prefill_chunk_ladder)
    # launches != tokens: chunked prefill amortizes prompt ingestion
    assert eng.stats.prefill_chunk_launches > 0
    assert eng.stats.prefill_launches < eng.stats.prompt_tokens_ingested
    assert eng.stats.prompt_tokens_ingested == sum(len(p) for p in prompts)
    assert eng.stats.tokens_generated == sum(len(c.tokens) for c in outs)
    # throughput reads the step counter: tokens over summed step() time
    assert eng.stats.step_ns > 0
    assert eng.throughput_tok_s() == pytest.approx(
        eng.stats.tokens_generated * 1e9 / eng.stats.step_ns)
    assert eng.stats.prefill_launches > 0 and eng.stats.decode_launches > 0
    # the paged arena is ONE bucket-invariant allocation: every leaf keeps
    # the (G, n_pes, n_blocks_local, stride, kvh, hd) shape across the whole
    # mixed-bucket run, and bucket churn was host-side table permutations
    q = plan16.grid_q
    n_loc = -(-eng.pool.n_blocks // q)
    for entry in eng._arena:
        for leaf in entry.values():
            assert leaf.shape[2:4] == (n_loc, ec.block_pos_stride)
    assert eng.stats.migrations > 0      # buckets shrank as requests finished
    assert eng.stats.peak_blocks_used > 0
    assert eng.peak_kv_bytes() == eng.stats.peak_blocks_used * \
        eng.pool.layout.bytes_per_block


def test_preemption_under_tiny_pool_still_completes(mesh16, plan16):
    # pool holds 12 positions total; three 4-token prompts generating 6
    # tokens each cannot coexist -> scheduler must preempt and recompute
    ec = EngineConfig(s_max=S_MAX, buckets=(1, 2, 4), block_pos_stride=2,
                      n_kv_blocks=6, max_steps=400, prefill_chunks=())
    eng = build_engine(CFG, mesh16, plan16, engine_cfg=ec, seed=0)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, CFG.vocab_size, size=4).tolist()
               for _ in range(3)]
    outs = generate(eng, prompts, SamplingParams(max_tokens=6))
    assert all(len(c.tokens) == 6 for c in outs)
    assert eng.scheduler.n_preemptions > 0
    assert sum(c.n_preemptions for c in outs) == eng.scheduler.n_preemptions
    assert eng.pool.n_free == eng.pool.n_blocks     # everything released


def test_preemption_recompute_preserves_greedy_tokens(mesh16, plan16):
    """Recompute-style preemption must not change greedy outputs."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, CFG.vocab_size, size=4).tolist()
               for _ in range(3)]
    big = EngineConfig(s_max=S_MAX, buckets=(1, 2, 4), block_pos_stride=2,
                       prefill_chunks=())
    eng_big = build_engine(CFG, mesh16, plan16, engine_cfg=big, seed=0)
    baseline = generate(eng_big, prompts, SamplingParams(max_tokens=6))

    tiny = EngineConfig(s_max=S_MAX, buckets=(1, 2, 4), block_pos_stride=2,
                        n_kv_blocks=6, max_steps=400, prefill_chunks=())
    eng_tiny = build_engine(CFG, mesh16, plan16, engine_cfg=tiny, seed=0)
    preempted = generate(eng_tiny, prompts, SamplingParams(max_tokens=6))
    assert eng_tiny.scheduler.n_preemptions > 0
    for b, p in zip(baseline, preempted):
        assert b.tokens == p.tokens


def test_eos_and_cancellation(mesh16, plan16):
    ec = EngineConfig(s_max=S_MAX, buckets=(1, 2, 4), block_pos_stride=4,
                      prefill_chunks=())
    eng = build_engine(CFG, mesh16, plan16, engine_cfg=ec, seed=0)
    prompt = [3, 14, 15]
    [probe] = generate(eng, [prompt], SamplingParams(max_tokens=4))
    first = probe.tokens[0]

    # same prompt with that token as EOS stops immediately ("stop", not
    # "length"), still reporting the EOS token
    [stopped] = generate(eng, [prompt],
                         SamplingParams(max_tokens=4, eos_token_id=first))
    assert stopped.finish_reason == "stop" and stopped.tokens == [first]

    # cancellation mid-flight frees the slot and marks the request
    r1 = eng.submit(prompt, SamplingParams(max_tokens=8))
    r2 = eng.submit(prompt, SamplingParams(max_tokens=8))
    eng.step()
    assert eng.cancel(r1.request_id)
    eng.drain()
    assert r1.state == RequestState.FINISHED \
        and r1.finish_reason == "cancelled"
    assert r2.finish_reason == "length" and len(r2.output_tokens) == 8
    assert eng.pool.n_free == eng.pool.n_blocks


def test_identical_prompts_share_physical_pages(mesh16, plan16):
    """Two identical prompts must share prompt KV pages in the arena: the
    second request's block table adopts the first one's published pages, so
    peak pool occupancy stays strictly under 2x the solo footprint — and
    the adopted (never recomputed) KV yields identical greedy tokens."""
    stride, plen, n_tok = 4, 9, 4
    ec = EngineConfig(s_max=S_MAX, buckets=(1, 2), block_pos_stride=stride,
                      prefill_chunks=())
    eng = build_engine(CFG, mesh16, plan16, engine_cfg=ec, seed=0)
    prompt = np.random.default_rng(7).integers(
        0, CFG.vocab_size, size=plen).tolist()
    solo = eng.pool.blocks_for(plen + n_tok + 1)          # 4 pages

    a = eng.submit(prompt, SamplingParams(max_tokens=n_tok))
    for _ in range(plen):          # prefill a fully: both full pages publish
        eng.step()
    b = eng.submit(prompt, SamplingParams(max_tokens=n_tok))
    eng.drain()
    assert a.output_tokens == b.output_tokens
    shared = (plen - 1) // stride                         # 2 full pages
    assert eng.stats.peak_blocks_used <= 2 * solo - shared < 2 * solo
    assert eng.pool.n_free == eng.pool.n_blocks


def test_fork_shares_prompt_pages_and_matches_greedy(mesh16, plan16):
    """Request.fork() for n>1 sampling from one prompt: the fork adopts the
    parent's prompt pages (device memory dedupe) and, under greedy
    sampling, reproduces the parent's tokens exactly."""
    stride, plen, n_tok = 4, 9, 4
    ec = EngineConfig(s_max=S_MAX, buckets=(1, 2), block_pos_stride=stride,
                      prefill_chunks=())
    eng = build_engine(CFG, mesh16, plan16, engine_cfg=ec, seed=0)
    prompt = np.random.default_rng(8).integers(
        0, CFG.vocab_size, size=plen).tolist()
    parent = eng.submit(prompt, SamplingParams(max_tokens=n_tok))
    for _ in range(plen):
        eng.step()
    child = eng.fork(parent)
    assert child.prompt == parent.prompt
    assert child.request_id != parent.request_id
    eng.drain()
    assert child.output_tokens == parent.output_tokens
    solo = eng.pool.blocks_for(plen + n_tok + 1)
    assert eng.stats.peak_blocks_used <= 2 * solo - (plen - 1) // stride


def test_rngs_are_dropped_on_finish_and_cancel(mesh16, plan16):
    """Per-request sampling RNGs must not outlive their request (a leak
    here grows host memory unboundedly in a long-running server)."""
    ec = EngineConfig(s_max=S_MAX, buckets=(1, 2), block_pos_stride=4,
                      prefill_chunks=())
    eng = build_engine(CFG, mesh16, plan16, engine_cfg=ec, seed=0)
    rng = np.random.default_rng(9)
    p1 = rng.integers(0, CFG.vocab_size, size=3).tolist()
    p2 = rng.integers(0, CFG.vocab_size, size=3).tolist()
    r1 = eng.submit(p1, SamplingParams(max_tokens=2, temperature=0.8, seed=1))
    r2 = eng.submit(p2, SamplingParams(max_tokens=8, temperature=0.8, seed=2))
    while not r1.is_finished:
        eng.step()
    assert r1.request_id not in eng._rngs     # dropped on natural completion
    assert r2.request_id in eng._rngs         # still sampling
    eng.cancel(r2.request_id)
    assert r2.request_id not in eng._rngs     # dropped on cancellation
    eng.drain()
    assert eng._rngs == {}


def test_engine_config_rejects_bad_prefill_chunks():
    """Regression for silent ladder drops: entries < 2 or out-of-order
    ladders used to be silently discarded by the s_max cap; they are user
    errors and must raise."""
    with pytest.raises(ValueError, match="must be >= 2"):
        EngineConfig(prefill_chunks=(1, 16))
    with pytest.raises(ValueError, match="must be >= 2"):
        EngineConfig(prefill_chunks=(0,))
    with pytest.raises(ValueError, match="ascending"):
        EngineConfig(prefill_chunks=(64, 16))
    with pytest.raises(ValueError, match="ascending"):
        EngineConfig(prefill_chunks=(16, 16, 64))
    # legal ladders: strictly ascending >= 2; () disables chunking; entries
    # above s_max remain legal (they are capped by geometry, not rejected)
    assert EngineConfig(prefill_chunks=()).prefill_chunks == ()
    assert EngineConfig(s_max=32, prefill_chunks=(16, 64, 256)) is not None


def test_submit_validation(mesh16, plan16):
    ec = EngineConfig(s_max=S_MAX, buckets=(1, 2, 4), block_pos_stride=4)
    eng = build_engine(CFG, mesh16, plan16, engine_cfg=ec, seed=0)
    with pytest.raises(ValueError):
        eng.submit(list(range(30)), SamplingParams(max_tokens=8))  # > s_max
    with pytest.raises(ValueError):
        eng.submit([], SamplingParams(max_tokens=1))


def test_stream_generator_exit_under_pallas_interpret(mesh16, plan16):
    """Abandoning stream() mid-flight (GeneratorExit) under the explicit
    pallas-interpret backend cancels the request and frees its pages — the
    interpreted fused-kernel path shares the XLA path's lifecycle hooks —
    and the engine keeps serving, with the abandoned stream's tokens being
    a prefix of a clean run's."""
    ec = EngineConfig(s_max=S_MAX, buckets=(1, 2), block_pos_stride=4,
                      kernel_backend="pallas-interpret")
    eng = build_engine(CFG, mesh16, plan16, engine_cfg=ec, seed=0)
    prompt = list(range(1, 6))
    gen = eng.stream(prompt, SamplingParams(max_tokens=10))
    got = [next(gen), next(gen), next(gen)]
    gen.close()
    assert not eng.scheduler.has_work
    assert eng.pool.n_free == eng.pool.n_blocks
    ref = generate(eng, [prompt], SamplingParams(max_tokens=10))[0]
    assert ref.tokens[:3] == got


def test_two_interleaved_stream_consumers_match_generate(mesh16, plan16):
    """Two stream() generators consumed in strict alternation: each
    next() drives the WHOLE engine, so both requests batch together and
    still emit exactly the single-shot reference tokens."""
    B, plen, n_tok = 2, 5, 6
    prompts = np.random.default_rng(7).integers(
        0, CFG.vocab_size, size=(B, plen)).astype(np.int32)
    expect, params_d = _single_shot_greedy(mesh16, plan16, prompts, n_tok)

    ec = EngineConfig(s_max=S_MAX, buckets=(1, 2, 4), block_pos_stride=4)
    eng = build_engine(CFG, mesh16, plan16, engine_cfg=ec, params=params_d)
    g0 = eng.stream(prompts[0].tolist(), SamplingParams(max_tokens=n_tok))
    g1 = eng.stream(prompts[1].tolist(), SamplingParams(max_tokens=n_tok))
    out = [[], []]
    for _ in range(n_tok):
        out[0].append(next(g0))
        out[1].append(next(g1))
    for g in (g0, g1):
        with pytest.raises(StopIteration):
            next(g)
    assert out[0] == expect[0]
    assert out[1] == expect[1]
    assert eng.pool.n_free == eng.pool.n_blocks
