"""One chip's share of an expert-parallel layer (``ModelConfig.experts_held``)
and the granite-4.0-h-small tiny sibling it serves, on the CPU.

* The engine's prefill, then decode through the paged KV and dense SSM
  state, matches the plain float32 reference's full forward pass
  (``bench/reference/granite.py``) in logits, on seeded weights.
* The shares of one expert layer, each holding its range of experts, add up
  to the uncut layer, the shared expert counted once.
* Routing is dropless: a router forced onto one held expert computes every
  assignment.
* Chunk padding and idle slots route to no expert: they add nothing to the
  layer's output rows and nothing to ``EngineStats.moe_rows``.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import check, files, program  # noqa: E402
from reference import granite  # noqa: E402
from repro.launch.mesh import device_mesh  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.models.moe import expert_ffn, moe_share  # noqa: E402
from repro.models.transformer import mlp_apply  # noqa: E402
from repro.serve.decode import (_scan_layers, _step_logits,  # noqa: E402
                                read_expert_counts)
from repro.serve.engine import (EngineConfig, SamplingParams,  # noqa: E402
                                ServingEngine)
from repro.train.step import make_pctx  # noqa: E402

SEED = 4200000011


@pytest.fixture(scope="module")
def tiny():
    """The tiny sibling as the benchmark's rehearsal serves it, its sizes
    and its weights in the program's layout on one device."""
    conf = files.config("granite-4.0-h-small")
    cfg = program.model_config(conf, True)
    s = program.sizes(cfg, conf, True)
    mesh, plan = device_mesh(jax.devices()[:1])
    params = program.make_weights(conf, cfg, s, mesh, SEED)
    return cfg, s, mesh, plan, params


def test_the_tiny_sibling_keeps_the_share(tiny):
    cfg, s, *_ = tiny
    assert 0 < cfg.experts_held < cfg.top_k < cfg.n_experts
    assert cfg.d_ff_shared and not cfg.rope and cfg.attn_scale == 1 / 128
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.rms_norm_eps) == (12.0, 0.22, 16.0, 1e-5)
    assert [m for m, _ in cfg.pattern()] == ["mamba"] * 5 + ["attn"] \
        + ["mamba"] * 4


def test_engine_matches_the_reference_in_logits(tiny):
    """Prefill in chunks of 16 over two pages of 8, then decode, three
    requests in a bucket of four (one lane idle): every logits row the
    engine sampled from against the reference's.  Both sides are float32
    and differ only in the order of their sums (chunked SSD scan against
    the reference's own chunking, grouped expert rows against dense
    experts), over a residual stream of rms about 100 through ten layers:
    they read 3.6e-6 apart at most, on logits whose spread is about 1.
    The tolerance, 1e-4, leaves room for another summation order and
    fails a step computed in bfloat16 (errors near 1e-2)."""
    cfg, s, mesh, plan, params = tiny
    ec = EngineConfig(s_max=64, buckets=(4,), block_pos_stride=8,
                      prefill_chunks=(16,), n_dense_slots=4,
                      kernel_backend="jnp")
    eng = ServingEngine(cfg, mesh, plan, params=params, engine_cfg=ec)
    rows = {}
    launch = eng._launch

    def spy(sd, chunk):
        out, fed = launch(sd, chunk)
        for lane, r in enumerate(sd.slots):
            if r is not None and r.num_cached + fed[lane] == len(r.seq_tokens):
                rows.setdefault(r.request_id, []).append(out[lane].copy())
        return out, fed
    eng._launch = spy
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (5, 23, 11)]
    reqs = [eng.submit(p, SamplingParams(max_tokens=6)) for p in prompts]
    eng.drain()
    w = check.reference_weights("granite", s, SEED)
    for r, p in zip(reqs, prompts):
        got = np.stack(rows[r.request_id])
        want = check.reference_logits("granite", s, w, p, r.output_tokens)
        assert got.shape == want.shape
        assert want.std() > 0.5
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)


def _layer(cfg, seed=0):
    """One expert layer's reference weights: the router, every expert and
    the shared expert, float32."""
    s = {"d_model": cfg.d_model, "n_experts": cfg.n_experts,
         "top_k": cfg.top_k, "d_ff_expert": cfg.d_ff_expert,
         "d_ff_shared": cfg.d_ff_shared, "experts_held": cfg.n_experts,
         "expert_first": 0}
    D, E, F, Fs = cfg.d_model, cfg.n_experts, cfg.d_ff_expert, cfg.d_ff_shared
    ks = jax.random.split(jax.random.key(seed), 7)
    n = lambda k, shp, fan: jax.random.normal(k, shp) / np.sqrt(fan)
    w = {"router": 2.0 * n(ks[0], (D, E), D),
         "w_gate": n(ks[1], (E, D, F), D), "w_up": n(ks[2], (E, D, F), D),
         "w_down": n(ks[3], (E, F, D), F), "s_gate": n(ks[4], (D, Fs), D),
         "s_up": n(ks[5], (D, Fs), D), "s_down": n(ks[6], (Fs, D), Fs)}
    return s, w


def _program_layer(w, first, held):
    """The share holding experts [first, first + held), and the shared
    expert, in the program's stored layout on a 1x1 grid."""
    sl = slice(first, first + held)
    blk = lambda a: a[None]
    return ({"router": w["router"],
             "w1": jnp.concatenate([w["w_gate"][sl], w["w_up"][sl]], -1),
             "w2": w["w_down"][sl]},
            {"w_gate": blk(w["s_gate"]), "w_up": blk(w["s_up"]),
             "w_down": blk(w["s_down"])})


def _run(mesh, fn, *args):
    """Run ``fn(pctx, *args)`` inside the grid's shard_map on one device."""
    plan = device_mesh(jax.devices()[:1])[1]
    pctx = dataclasses.replace(make_pctx(plan, "allgather", remat=False,
                                         compute_dtype=jnp.float32),
                               act_layout="repl_rows", preskewed=False)
    mapped = jax.shard_map(lambda *a: fn(pctx, *a), mesh=mesh,
                           in_specs=P(), out_specs=P(), check_vma=False)
    return jax.jit(mapped)(*args)


CFG = ModelConfig(name="share", family="moe", d_model=64, n_layers=1,
                  n_experts=12, top_k=4, d_ff_expert=32, d_ff_shared=48,
                  param_dtype=jnp.float32, compute_dtype=jnp.float32)


@pytest.mark.parametrize("backend", ["jnp", "pallas-interpret"])
def test_the_shares_add_up_to_the_uncut_layer(backend):
    """Four shares of three experts each, over a router of twelve picking
    four: their outputs, with the shared expert added once, are the uncut
    layer's; their rows add up to every assignment."""
    mesh = device_mesh(jax.devices()[:1])[0]
    s, w = _layer(CFG)
    x = jax.random.normal(jax.random.key(1), (2, 24, CFG.d_model))
    want = granite.moe(s, w, x.reshape(-1, CFG.d_model))
    held = 3
    total, rows = 0.0, 0
    for first in range(0, CFG.n_experts, held):
        cfg = dataclasses.replace(CFG, experts_held=held, expert_first=first)
        moe_p, _ = _program_layer(w, first, held)
        y, counts = _run(mesh, lambda pctx, p, x: moe_share(
            pctx, p, x, cfg, pallas=backend != "jnp",
            interpret=backend != "jnp"), moe_p, x)
        total = total + y
        rows += int(counts[:held].sum())
        assert int(counts[held]) == int(counts[:held].max())
        assert int(counts[-1]) == x.shape[0] * x.shape[1]
    _, shared_p = _program_layer(w, 0, held)
    total = total + _run(mesh, lambda pctx, p, x: mlp_apply(pctx, CFG, p, x),
                         shared_p, x)
    np.testing.assert_allclose(np.asarray(total).reshape(want.shape), want,
                               rtol=1e-4, atol=1e-4)
    assert rows == x.shape[0] * x.shape[1] * CFG.top_k


def test_a_router_forced_onto_one_held_expert_drops_nothing():
    """Every token's first choice is expert 5, held here with 6 and 7: it
    computes all 48 tokens' rows, four times an even split of the 48 x 4
    assignments over twelve experts, where a capacity-bound layer would
    drop."""
    mesh = device_mesh(jax.devices()[:1])[0]
    s, w = _layer(CFG)
    w["router"] = w["router"].at[:, 5].set(0.0)
    x = jax.random.normal(jax.random.key(2), (4, 12, CFG.d_model))
    x = x.at[..., 0].set(50.0)
    w["router"] = w["router"].at[0, 5].set(10.0)
    cfg = dataclasses.replace(CFG, experts_held=3, expert_first=5)
    moe_p, _ = _program_layer(w, 5, 3)
    y, counts = _run(mesh, lambda pctx, p, x: moe_share(pctx, p, x, cfg),
                     moe_p, x)
    T = x.shape[0] * x.shape[1]
    assert int(counts[0]) == T
    s_held = dict(s, experts_held=3, expert_first=5)
    w_held = dict(w, w_gate=w["w_gate"][5:8], w_up=w["w_up"][5:8],
                  w_down=w["w_down"][5:8], s_down=0 * w["s_down"])
    want = granite.moe(s_held, w_held, x.reshape(T, -1))
    np.testing.assert_allclose(np.asarray(y).reshape(want.shape), want,
                               rtol=1e-4, atol=1e-4)


def test_the_busiest_expert_is_counted_in_each_layer():
    """Two share layers whose routers send every token to a different one
    of the held experts 5-7 (5, then 6) and none to the other two, summed
    over the layers as a step program sums them and read back from its
    logits: each layer's busiest expert computes all T rows, so the
    busiest rows read 2T, where the busiest of the rows summed over the
    layers would read T."""
    mesh = device_mesh(jax.devices()[:1])[0]
    _, w = _layer(CFG)
    cfg = dataclasses.replace(CFG, n_layers=2, experts_held=3,
                              expert_first=5,
                              layer_pattern=(("attn", "moe"),) * 2)
    x = jax.random.normal(jax.random.key(5), (4, 12, CFG.d_model))
    x = x.at[..., 0].set(50.0)
    T = x.shape[0] * x.shape[1]
    layers = []
    for e in (5, 6):
        gain = jnp.where(jnp.arange(12) == e, 10.0, -10.0)[5:8]
        forced = dict(w, router=w["router"].at[:, 5:8].set(0.0)
                      .at[0, 5:8].set(gain))
        layers.append(_program_layer(forced, 5, 3)[0])
    params = jax.tree.map(lambda a: a[None], layers)
    cache = [{"z": jnp.zeros((1, 1))}] * 2

    def step(pctx, params, x):
        def layer(mixer, ffn, p, x, c):
            return x, c, moe_share(pctx, p, x, cfg)[1]
        _, _, cnt = _scan_layers(cfg, params, cache, x, layer)
        return cnt, _step_logits(cfg, jnp.zeros((1, 1, 8)), cnt)
    cnt, logits = _run(mesh, step, params, x)
    rows, rows_max, positions = read_expert_counts(cfg, logits)
    assert (rows_max, positions) == (2 * T, 2 * T)
    np.testing.assert_array_equal(np.asarray(cnt)[:3], [T, T, 0])
    assert rows == 2 * T


def test_positions_that_feed_nothing_route_to_no_expert():
    """A ``live`` mask (chunk padding, idle slots) leaves those positions'
    output at zero and their rows out of the counts."""
    mesh = device_mesh(jax.devices()[:1])[0]
    _, w = _layer(CFG)
    x = jax.random.normal(jax.random.key(3), (3, 8, CFG.d_model))
    live = jnp.arange(8)[None, :] < jnp.asarray([5, 0, 8])[:, None]
    cfg = dataclasses.replace(CFG, experts_held=4, expert_first=4)
    moe_p, _ = _program_layer(w, 4, 4)
    run = lambda live: _run(mesh, lambda pctx, p, x, m: moe_share(
        pctx, p, x, cfg, live=m), moe_p, x, live)
    y, counts = run(live)
    y_all, counts_all = run(jnp.ones_like(live))
    np.testing.assert_array_equal(np.asarray(y)[~np.asarray(live)], 0.0)
    np.testing.assert_allclose(np.asarray(y)[np.asarray(live)],
                               np.asarray(y_all)[np.asarray(live)])
    assert int(counts[-1]) == 13 and int(counts_all[-1]) == 24
    assert 0 < int(counts[:-2].sum()) < int(counts_all[:-2].sum())


def test_padding_and_idle_slots_add_no_rows_in_the_engine(tiny):
    """One request in a bucket of four (three lanes idle), prefilled in
    chunks of 16 (the last one mostly padding): the positions the step
    programs route are exactly the fed ones, in every MoE layer."""
    cfg, s, mesh, plan, params = tiny
    ec = EngineConfig(s_max=64, buckets=(4,), block_pos_stride=8,
                      prefill_chunks=(16,), n_dense_slots=4,
                      kernel_backend="jnp")
    eng = ServingEngine(cfg, mesh, plan, params=params, engine_cfg=ec)
    fed = []
    launch = eng._launch

    def spy(sd, chunk):
        out, f = launch(sd, chunk)
        fed.append(sum(f))
        return out, f
    eng._launch = spy
    eng.submit(list(range(3, 22)), SamplingParams(max_tokens=4))
    eng.drain()
    st = eng.stats
    assert st.moe_positions == sum(fed) * cfg.n_layers == (19 + 3) * 10
    assert 0 < st.moe_rows_max <= st.moe_rows <= \
        st.moe_rows_max * cfg.experts_held
    assert st.moe_rows <= st.moe_positions * min(cfg.top_k,
                                                 cfg.experts_held)


def test_expert_ffn_kernel_matches_ragged_dot():
    """The grouped-matmul kernel (interpreted) against ``lax.ragged_dot``
    on rows sorted by expert, with a tail past the last group that
    neither reads."""
    k = jax.random.split(jax.random.key(4), 3)
    xs = jax.random.normal(k[0], (256, 64))
    w1 = jax.random.normal(k[1], (3, 64, 64)) / 8
    w2 = jax.random.normal(k[2], (3, 32, 64)) / 6
    gs = jnp.asarray([40, 0, 77], jnp.int32)
    got = expert_ffn(xs, w1, w2, gs, pallas=True, interpret=True)
    want = expert_ffn(xs, w1, w2, gs)
    np.testing.assert_allclose(np.asarray(got)[:117], np.asarray(want)[:117],
                               rtol=1e-5, atol=1e-5)
