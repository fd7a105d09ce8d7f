"""Fused paged-attention kernel family: pallas(interpret) vs jnp parity.

Three altitudes, mirroring how the kernel is consumed:

  * **op level** — ``paged_attention`` partials from the fused kernel merge
    (LSE, per grid row) to the same output as the materialized-gather
    reference, across scrambled block tables, unallocated entries, GQA and
    multi-row page sharding — no mesh involved;
  * **body level** — ``make_decode_body`` / ``make_prefill_chunk_body``
    under ``kernel_backend="pallas-interpret"`` reproduce the jnp bodies'
    logits through shard_map, including partial chunks (``n_valid < L``)
    and mixed decode+prefill launches;
  * **engine level** — greedy ``generate()``/``stream()`` under the pallas
    backend is token-for-token identical to the jnp backend for an
    attention config AND the reduced mamba2-780m (whose chunked prefill
    exercises the Pallas SSD scan).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.configs.registry import reduced
from repro.kernels import KERNEL_BACKENDS
from repro.kernels.paged_attention import merge_rows, paged_attention
from repro.models import params as pm
from repro.models.config import ModelConfig
from repro.partition import DATA
from repro.serve.decode import (PagedKV, make_decode_step,
                                make_prefill_chunk_body, paged_cache_pspecs,
                                paged_cache_specs)
from repro.serve.engine import (EngineConfig, SamplingParams, build_engine,
                                generate)

F32 = dict(param_dtype=jnp.float32, compute_dtype=jnp.float32,
           attn_block_kv=32)
ATTN = ModelConfig(name="pk-attn", family="dense", d_model=64, n_layers=2,
                   n_heads=8, n_kv_heads=4, d_ff=128, vocab_size=128,
                   qk_norm=True, **F32)
S_MAX = 32


# ---------------------------------------------------------------------------
# Op level (no mesh): fused kernel vs materialized gather.
# ---------------------------------------------------------------------------

def _rand_case(rng, *, B, T, stride, kvh, hd, Hq, qrows, L, holes=True):
    n_blocks = B * T
    n_loc = -(-n_blocks // qrows)
    table = np.arange(n_blocks, dtype=np.int32)
    rng.shuffle(table)                       # pages are position-agnostic
    table = table.reshape(B, T)
    if holes:
        table[-1, -1] = -1                   # unallocated tail entry
    arenas = [(rng.normal(size=(n_loc, stride, kvh, hd)).astype(np.float32),
               rng.normal(size=(n_loc, stride, kvh, hd)).astype(np.float32))
              for _ in range(qrows)]
    q = rng.normal(size=(B, Hq, L, hd)).astype(np.float32)
    pos = rng.integers(0, T * stride - L + 1, size=B).astype(np.int32)
    q_pos = pos[:, None] + np.arange(L, dtype=np.int32)[None]
    return table, arenas, q, q_pos


def _merged(backend, table, arenas, q, q_pos, stride, qrows, kv_len=None):
    if kv_len is None:
        kv_len = q_pos[:, -1] + 1
    parts = []
    for row, (kc, vc) in enumerate(arenas):
        parts.append(paged_attention(
            jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
            jnp.asarray(table), jnp.asarray(q_pos), jnp.asarray(kv_len),
            stride=stride, row=row, qrows=qrows, backend=backend,
            interpret=True))
    return np.asarray(merge_rows(parts))


# The walk's geometry at stride 32: 8 table entries (256 positions) per key
# block, 16 entries per slot, so a slot spans two blocks; chunks of 128
# columns run as two query tiles of 64.
WALK = dict(T=16, stride=32, kvh=2, hd=16, Hq=4)


def _walk_case(rng, *, pos, n_valid, L, qrows, holes=False, idle=()):
    """Explicit slot positions, valid columns and idle slots on the walk
    geometry; returns the case plus each slot's ``kv_len``."""
    B = len(pos)
    table, arenas, q, _ = _rand_case(rng, B=B, L=L, qrows=qrows, holes=holes,
                                     **WALK)
    pos = np.asarray(pos, np.int32)
    kv_len = pos + np.asarray(n_valid, np.int32)
    for b in idle:
        table[b] = -1
        kv_len[b] = 0
    q_pos = pos[:, None] + np.arange(L, dtype=np.int32)[None]
    return table, arenas, q, q_pos, kv_len


def _poison_unread(table, arenas, kv_len, stride, qrows):
    """NaN into every arena position the walk must never read: positions
    at or past each slot's ``kv_len``, and pages no table lists."""
    arenas = [(kc.copy(), vc.copy()) for kc, vc in arenas]
    live = np.zeros((len(arenas), arenas[0][0].shape[0], stride), bool)
    for b, row in enumerate(table):
        for t, pid in enumerate(row):
            if pid >= 0:
                n = int(np.clip(kv_len[b] - t * stride, 0, stride))
                live[pid % qrows, pid // qrows, :n] = True
    for r, (kc, vc) in enumerate(arenas):
        kc[~live[r]] = np.nan
        vc[~live[r]] = np.nan
    return arenas


SCRAMBLED = {
    # the original cases: stride 8, four entries (one block), qrows 2
    "decode": dict(L=1),
    "chunk": dict(L=8),
    # kv_len 0 (idle slot, table all -1), 1, exactly a block boundary, one
    # past it, and T * stride
    "kv-len-edges": dict(pos=[0, 0, 255, 256, 511], n_valid=[1] * 5, L=1,
                         qrows=2, idle=(0,)),
    "holes-qrows1": dict(pos=[3, 300, 0], n_valid=[1] * 3, L=1, qrows=1,
                         holes=True),
    "holes-qrows2": dict(pos=[200, 31, 262], n_valid=[1] * 3, L=1, qrows=2,
                         holes=True),
    # chunk columns valid: 1 (a decode rider), a partial second tile, all
    "chunk-n-valid": dict(pos=[300, 17, 0], n_valid=[1, 70, 128], L=128,
                          qrows=2),
    "nan-past-kv-len": dict(pos=[300, 17, 0], n_valid=[1, 70, 128], L=128,
                            qrows=2, poison=True),
}


@pytest.mark.parametrize("case", list(SCRAMBLED), ids=list(SCRAMBLED))
def test_fused_kernel_matches_gather_ref_scrambled(case):
    """The load-bearing claim: in-place page reads == materialized gather,
    after the LSE row merge, for scrambled tables + holes + GQA, over each
    slot's valid columns, wherever its walk stops.  Padding columns stay
    finite, and a NaN in any position the walk must not read never reaches
    a result."""
    rng = np.random.default_rng(0)
    spec = dict(SCRAMBLED[case])
    if "pos" not in spec:
        stride, qrows = 8, 2
        table, arenas, q, q_pos = _rand_case(
            rng, B=3, T=4, stride=stride, kvh=2, hd=16, Hq=4, qrows=qrows,
            L=spec["L"])
        kv_len = q_pos[:, -1] + 1
    else:
        poison = spec.pop("poison", False)
        stride, qrows = WALK["stride"], spec["qrows"]
        table, arenas, q, q_pos, kv_len = _walk_case(rng, **spec)
    o_ref = _merged("jnp", table, arenas, q, q_pos, stride, qrows, kv_len)
    if "pos" in spec and poison:
        arenas = _poison_unread(table, arenas, kv_len, stride, qrows)
    o_pal = _merged("pallas", table, arenas, q, q_pos, stride, qrows, kv_len)
    assert np.isfinite(o_pal).all()
    valid = q_pos < kv_len[:, None]                  # (B, L) read columns
    a, b = (np.moveaxis(o, 2, 1)[valid] for o in (o_ref, o_pal))
    rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-9)
    assert rel < 1e-5, rel


@pytest.mark.parametrize("kv_len", [[0, 0, 0], [0, 37, 1]],
                         ids=["all-idle", "mixed"])
def test_work_list_steps_name_entries_inside_the_table(kv_len):
    """The device-built step list of an 8-column chunk in tiles of 4: each
    step names a tile and a table entry inside the kernel's arrays, and
    the steps visit exactly the entries below each tile's bound.  The
    all-idle launch (the engine's warm-up) still takes one step, on the
    last tile: the interpreter clamps an index past an array, the chip
    halts on it."""
    from repro.kernels.paged_attention.kernel import _work_list
    B, T, stride, pages, tq, nQ = 3, 16, 4, 4, 4, 2
    kv_len = np.asarray(kv_len, np.int32)
    q_start = np.asarray([0, 30, 0], np.int32)
    n, steps, walked, _ = _work_list(
        jnp.ones((B, T), jnp.int32), jnp.asarray(q_start),
        jnp.asarray(kv_len), tq=tq, stride=stride, pages=pages, nQ=nQ)
    got = [divmod(int(x), T) for x in np.asarray(steps)[:int(n)]]
    want = []
    for b in range(B):
        for qi in range(nQ):
            if qi * tq < kv_len[b] - q_start[b]:
                bound = min(kv_len[b], q_start[b] + (qi + 1) * tq)
                want += [(b * nQ + qi, e) for e in range(-(-bound // stride))]
    assert got == (want or [(B * nQ - 1, 0)])
    assert np.asarray(walked).sum() == len({t for t, _ in want})


def test_fused_kernel_single_row_identity_table():
    """qrows=1 (every page local), identity table, no holes — the simplest
    geometry must also agree, per-slot positions staggered."""
    rng = np.random.default_rng(1)
    stride, qrows = 4, 1
    table, arenas, q, q_pos = _rand_case(
        rng, B=4, T=8, stride=stride, kvh=4, hd=8, Hq=8, qrows=qrows, L=1,
        holes=False)
    o_ref = _merged("jnp", table, arenas, q, q_pos, stride, qrows)
    o_pal = _merged("pallas", table, arenas, q, q_pos, stride, qrows)
    rel = np.abs(o_ref - o_pal).max() / (np.abs(o_ref).max() + 1e-9)
    assert rel < 1e-5, rel


def test_paged_attention_rejects_unknown_backend():
    rng = np.random.default_rng(2)
    table, arenas, q, q_pos = _rand_case(
        rng, B=1, T=2, stride=4, kvh=2, hd=8, Hq=2, qrows=1, L=1)
    with pytest.raises(ValueError, match="backend"):
        paged_attention(jnp.asarray(q), *map(jnp.asarray, arenas[0]),
                        jnp.asarray(table), jnp.asarray(q_pos),
                        jnp.asarray(q_pos[:, -1] + 1), stride=4, row=0,
                        qrows=1, backend="cuda")


# ---------------------------------------------------------------------------
# Body level (mesh16): shard_map'd steps, both backends.
# ---------------------------------------------------------------------------

def _device_params(mesh, specs):
    params = pm.init_params(specs, seed=0)
    pspecs = pm.param_pspecs(specs)
    return jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        params, pspecs)


def _fresh_arena(mesh, cfg, plan, paged, n_dense_slots=0):
    return jax.tree.map(
        lambda sd, sp: jax.device_put(jnp.zeros(sd.shape, sd.dtype),
                                      NamedSharding(mesh, sp)),
        paged_cache_specs(cfg, plan, paged, n_dense_slots=n_dense_slots),
        paged_cache_pspecs(cfg))


def test_decode_body_backend_parity_scrambled_table(mesh16, plan16):
    """Per-slot paged decode steps: pallas-interpret logits match jnp on a
    scrambled table through the full shard_map body (projections, RoPE,
    in-kernel scatter, row merge)."""
    cfg, B, stride, steps = ATTN, 4, 8, 6
    T = S_MAX // stride
    paged = PagedKV(n_blocks=B * T, block_pos_stride=stride)
    kw = dict(batch=B, s_max=S_MAX, mode="gemv", per_slot=True, paged=paged)
    step_j, specs, _ = make_decode_step(cfg, mesh16, plan16,
                                        kernel_backend="jnp", **kw)
    step_p, _, _ = make_decode_step(cfg, mesh16, plan16,
                                    kernel_backend="pallas-interpret", **kw)
    params_d = _device_params(mesh16, specs)
    aj, ap = (_fresh_arena(mesh16, cfg, plan16, paged) for _ in range(2))
    table = np.arange(B * T, dtype=np.int32)
    np.random.default_rng(5).shuffle(table)
    table_d = jax.device_put(jnp.asarray(table.reshape(B, T)),
                             NamedSharding(mesh16, P(DATA, None)))
    toks = np.random.default_rng(1).integers(
        0, cfg.vocab_size, size=(B, steps)).astype(np.int32)
    for t in range(steps):
        tok = jax.device_put(jnp.asarray(toks[:, t]),
                             NamedSharding(mesh16, P(DATA)))
        pos = jax.device_put(jnp.full((B,), t, jnp.int32),
                             NamedSharding(mesh16, P(DATA)))
        lj, aj = step_j(params_d, aj, tok, pos, table_d)
        lp, ap = step_p(params_d, ap, tok, pos, table_d)
        a, b = np.asarray(lj), np.asarray(lp)
        rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-9)
        assert rel < 1e-5, (t, rel)


def test_prefill_chunk_body_backend_parity_partial_chunks(mesh16, plan16):
    """Chunked-prefill bodies agree across backends with n_valid < L partial
    chunks AND n_valid = 1 decode riders in the same launch (the mixed-step
    ABI), on a scrambled table."""
    cfg, B, stride, L = ATTN, 4, 4, 8
    T = S_MAX // stride
    paged = PagedKV(n_blocks=B * T, block_pos_stride=stride)
    lead = DATA
    bodies = {}
    for be in ("jnp", "pallas-interpret"):
        body, in_specs, out_specs, specs, _ = make_prefill_chunk_body(
            cfg, mesh16, plan16, batch=B, s_max=S_MAX, chunk=L, paged=paged,
            kernel_backend=be)
        bodies[be] = jax.jit(jax.shard_map(
            body, mesh=mesh16, in_specs=in_specs, out_specs=out_specs,
            check_vma=False))
    params_d = _device_params(mesh16, specs)
    table = np.arange(B * T, dtype=np.int32)
    np.random.default_rng(9).shuffle(table)
    table_d = jax.device_put(jnp.asarray(table.reshape(B, T)),
                             NamedSharding(mesh16, P(lead, None)))
    rng = np.random.default_rng(3)
    toks = rng.integers(0, cfg.vocab_size, size=(B, L)).astype(np.int32)
    # slot 0: full chunk; 1-2: partial prefill; 3: decode rider (n_valid=1)
    n_valid = np.array([L, 5, 3, 1], np.int32)
    pos = np.array([0, 0, 2, 7], np.int32)      # staggered slot positions
    dev = lambda a, s: jax.device_put(jnp.asarray(a),
                                      NamedSharding(mesh16, s))
    args = (dev(toks, P(lead, None)), dev(pos, P(lead)),
            dev(n_valid, P(lead)), table_d)
    aj, ap = (_fresh_arena(mesh16, cfg, plan16, paged) for _ in range(2))
    lj, aj = bodies["jnp"](params_d, aj, *args)
    lp, ap = bodies["pallas-interpret"](params_d, ap, *args)
    a, b = np.asarray(lj), np.asarray(lp)
    rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-9)
    assert rel < 1e-5, rel
    # the arenas the two backends wrote must agree too (same scatter)
    for ej, ep in zip(jax.tree.leaves(aj), jax.tree.leaves(ap)):
        assert np.allclose(np.asarray(ej), np.asarray(ep), atol=1e-6)


# ---------------------------------------------------------------------------
# Engine level (mesh16): token-for-token greedy parity.
# ---------------------------------------------------------------------------

def _engine_pair(cfg, mesh, plan, **ec_kw):
    ej = build_engine(cfg, mesh, plan, seed=0, engine_cfg=EngineConfig(
        kernel_backend="jnp", **ec_kw))
    ep = build_engine(cfg, mesh, plan, params=ej.params,
                      engine_cfg=EngineConfig(
                          kernel_backend="pallas-interpret", **ec_kw))
    return ej, ep


def test_engine_greedy_parity_attn(mesh16, plan16):
    """Mixed-length attn workload (chunked prefill + decode + bucket churn):
    pallas-interpret tokens == jnp tokens, and the pallas engine really
    launched chunked prefill executables (mixed steps included)."""
    ej, ep = _engine_pair(ATTN, mesh16, plan16, s_max=S_MAX,
                          buckets=(1, 2, 4), block_pos_stride=4,
                          prefill_chunks=(4, 16))
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, ATTN.vocab_size, size=n).tolist()
               for n in (9, 3, 6, 2)]
    sampling = [SamplingParams(max_tokens=m) for m in (6, 4, 5, 7)]
    oj = generate(ej, prompts, sampling)
    op = generate(ep, prompts, sampling)
    for a, b in zip(oj, op):
        assert a.tokens == b.tokens
    assert ep.stats.prefill_chunk_launches > 0
    assert ep.stats.decode_launches > 0


def test_engine_greedy_parity_mamba2(mesh16, plan16):
    """The reduced mamba2-780m serves identically under both backends —
    this is the path that flips the engine's chunked prefill from
    ``ssd_scan(backend="jnp")`` to the Pallas SSD kernels."""
    cfg = reduced(get_config("mamba2-780m"))
    ej, ep = _engine_pair(cfg, mesh16, plan16, s_max=S_MAX,
                          buckets=(1, 2, 4), block_pos_stride=4,
                          prefill_chunks=(4, 16))
    rng = np.random.default_rng(22)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (9, 3, 6)]
    oj = generate(ej, prompts, SamplingParams(max_tokens=5))
    op = generate(ep, prompts, SamplingParams(max_tokens=5))
    for a, b in zip(oj, op):
        assert a.tokens == b.tokens
    assert ep.stats.prefill_chunk_launches > 0


def test_engine_stream_parity_backends(mesh16, plan16):
    """stream() under pallas-interpret yields exactly generate()'s tokens
    under jnp (the streaming front-end is backend-blind)."""
    ej, ep = _engine_pair(ATTN, mesh16, plan16, s_max=S_MAX, buckets=(1, 2),
                          block_pos_stride=4, prefill_chunks=(4,))
    prompt = np.random.default_rng(23).integers(
        0, ATTN.vocab_size, size=7).tolist()
    [cj] = generate(ej, [prompt], SamplingParams(max_tokens=6))
    streamed = list(ep.stream(prompt, SamplingParams(max_tokens=6)))
    assert streamed == cj.tokens


# ---------------------------------------------------------------------------
# Config validation.
# ---------------------------------------------------------------------------

def test_engine_config_rejects_unknown_kernel_backend():
    """Unknown backends must raise at config time, naming the valid set —
    the ``prefill_chunks`` validation precedent."""
    for bad in ("cuda", "triton", "Pallas", ""):
        with pytest.raises(ValueError, match="kernel_backend"):
            EngineConfig(kernel_backend=bad)
    for ok in KERNEL_BACKENDS:
        assert EngineConfig(kernel_backend=ok).kernel_backend == ok
    assert EngineConfig().kernel_backend in KERNEL_BACKENDS


def test_decode_body_rejects_unknown_kernel_backend(mesh16, plan16):
    paged = PagedKV(n_blocks=8, block_pos_stride=4)
    with pytest.raises(ValueError, match="kernel_backend"):
        make_decode_step(ATTN, mesh16, plan16, batch=2, s_max=S_MAX,
                         mode="gemv", per_slot=True, paged=paged,
                         kernel_backend="nope")
    with pytest.raises(ValueError, match="kernel_backend"):
        make_prefill_chunk_body(ATTN, mesh16, plan16, batch=2, s_max=S_MAX,
                                chunk=4, paged=paged, kernel_backend="nope")
