"""The drive loop's own spans and counters (``repro.serve.engine.phases``).

A tiny engine drains mixed prompts; its phase counters must tile the step
counter, its work counters must agree with what the launches fed, a
profiler trace must hold one ``serve/step`` span per step with its phases
inside, and the compiled step programs must carry their kernels' names.
"""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.hybrid import HybridKernel
from repro.core.shmem import ShmemGrid
from repro.models.config import ModelConfig
from repro.partition import MODEL
from repro.serve.engine import EngineConfig, SamplingParams, build_engine
from repro.serve.engine.phases import COUNTERS
from repro.serve.spec.config import SpeculationConfig

CFG = ModelConfig(name="trc", family="dense", d_model=64, n_layers=2,
                  n_heads=8, n_kv_heads=4, d_ff=128, vocab_size=128,
                  param_dtype=jnp.float32, compute_dtype=jnp.float32,
                  attn_block_kv=32)
PHASES = ("schedule", "operands", "wait", "fetch", "publish", "sample")


def _engine(mesh, plan, **kw):
    ec = EngineConfig(s_max=32, buckets=(1, 2, 4), block_pos_stride=4,
                      prefill_chunks=(4, 16), **kw)
    return build_engine(CFG, mesh, plan, engine_cfg=ec, seed=0)


def _drain_mixed(eng, seed=3):
    """Staggered arrivals, so chunk launches carry decode slots riding
    along with one position each."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, CFG.vocab_size,
                            size=int(rng.integers(3, 14))).tolist()
               for _ in range(5)]
    eng.submit(prompts[0], SamplingParams(max_tokens=9))
    eng.step()
    eng.step()
    for i, p in enumerate(prompts[1:]):
        eng.submit(p, SamplingParams(max_tokens=3 + i,
                                     temperature=0.7 * (i % 2), seed=i))
    eng.drain()


def _phase_sum(st):
    return sum(getattr(st, COUNTERS[p]) for p in PHASES)


def test_counters_agree_with_the_launches(mesh16, plan16):
    eng = _engine(mesh16, plan16)
    launches = []
    launch = eng._launch

    def spy(sd, chunk):
        rows, fed = launch(sd, chunk)
        launches.append((sd.bucket, chunk,
                         sum(r is not None for r in sd.slots), list(fed)))
        return rows, fed

    eng._launch = spy
    _drain_mixed(eng)
    st = eng.stats
    chunks = [(b, c, fed) for b, c, _, fed in launches if c is not None]
    # decode slots rode some chunk launch with their single position
    assert any(1 in fed for _, _, fed in chunks)
    assert st.chunk_positions == sum(b * c for b, c, _ in chunks)
    assert st.chunk_positions_fed == sum(sum(fed) for _, _, fed in chunks)
    assert 0 < st.chunk_positions_fed <= st.chunk_positions
    assert st.slots_launched == sum(b for b, _, _, _ in launches)
    assert st.slots_occupied == sum(n for _, _, n, _ in launches)
    assert 0 < st.slots_occupied <= st.slots_launched
    assert st.launches == len(launches)
    for p in PHASES:
        assert getattr(st, COUNTERS[p]) > 0, p
    assert 0 < _phase_sum(st) <= st.step_ns


def test_verify_launch_records_operands_wait_and_fetch(mesh16, plan16):
    eng = _engine(mesh16, plan16, speculation=SpeculationConfig(k=2))
    st = eng.stats
    read = lambda: (st.operand_ns, st.wait_ns, st.fetch_ns,
                    st.slots_launched)
    grew = []
    launch = eng.spec.launch

    def spy(rnd):
        before = read()
        rows = launch(rnd)
        grew.append(all(a > b for a, b in zip(read(), before)))
        return rows

    eng.spec.launch = spy
    eng.submit([5, 6, 7, 8] * 3, SamplingParams(max_tokens=12))
    eng.drain()
    assert st.spec_launches == len(grew) > 0
    assert all(grew)
    assert 0 < st.slots_occupied <= st.slots_launched
    assert _phase_sum(st) <= st.step_ns


def _host_spans(path):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            {k: v for k, v in e.stats}
                            if e.name == "serve/step" else {}))
    return out


def test_trace_holds_step_spans_with_their_phases(mesh16, plan16, tmp_path):
    eng = _engine(mesh16, plan16)
    _drain_mixed(eng, seed=4)            # compile outside the trace
    n0 = eng.stats.launches
    with jax.profiler.trace(str(tmp_path)):
        _drain_mixed(eng, seed=5)
    launched = eng.stats.launches - n0
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    spans = _host_spans(path)
    assert not [n for n, *_ in spans if n.startswith("bench/")]
    steps = [s for s in spans if s[0] == "serve/step"]
    phases = [s for s in spans if s[0].startswith("serve/")
              and s[0] not in ("serve/step", "serve/build")]
    busy = [s for s in steps if "bucket" in s[3]]
    assert len(busy) == launched
    for _, a, b, args in busy:
        inside = {n for n, s, e, _ in phases if a <= s and e <= b}
        assert inside == {"serve/" + p for p in PHASES}, inside
        assert 0 < int(args["occupied"]) <= int(args["bucket"])
    # every phase span lies inside some step span
    for n, s, e, _ in phases:
        assert any(a <= s and e <= b for _, a, b, _ in steps), n
    # the phases of one step never overlap: they are siblings
    for _, a, b, _ in busy:
        iv = sorted((s, e) for _, s, e, _ in phases if a <= s and e <= b)
        assert all(e0 <= s1 for (_, e0), (s1, _) in zip(iv, iv[1:]))


def test_step_programs_carry_their_kernel_names(mesh16, plan16):
    eng = _engine(mesh16, plan16)
    _drain_mixed(eng)
    modules = {name: c.as_text().split(",", 1)[0].split()[-1]
               for name, c in eng.queue._compiled.items()}
    assert any(n.startswith("prefill_bs") for n in modules)
    assert any(n.startswith("serve_step_bs") for n in modules)
    for name, module in modules.items():
        assert module == f"jit_{name}", (name, module)


@pytest.mark.parametrize("name", ["serve_step_bs2", "prefill_bs4_len16",
                                  "verify_bs2_len3"])
def test_named_kernel_lowers_under_its_name(mesh16, name):
    k = HybridKernel(lambda grid, a: a * 2, grid=ShmemGrid(MODEL, 4, 4),
                     in_specs=(P(MODEL),), out_specs=P(MODEL), name=name)
    text = k.bind(mesh16).lower(jnp.ones((16, 4))).as_text()
    assert f"module @jit_{name} " in text


def test_paged_walk_counters_match_a_hand_count(mesh16, plan16):
    """Two slots of different lengths, greedy, stride 4 (table width 8):
    each launch walks ceil(kv_len / 4) pages per occupied slot and lists
    bucket x 8 entries.  By hand, per launch (bucket; kv_len per slot):

      1. chunk of 4, bucket 2: a feeds 0-3 (kv 4); b feeds its whole
         prompt 0-2 (kv 3) and samples -> 1 + 1 pages
      2. chunk of 4 (the ladder's smallest covers a's 2 left), bucket 2:
         a feeds 4-5 (kv 6) and samples; b feeds 3 (kv 4) and finishes
         -> 2 + 1
      3. decode, bucket 1: a feeds 6 (kv 7) and finishes -> 2
    """
    eng = _engine(mesh16, plan16)
    eng.submit(list(range(1, 7)), SamplingParams(max_tokens=2))
    eng.submit([7, 8, 9], SamplingParams(max_tokens=2))
    eng.drain()
    st = eng.stats
    assert (st.prefill_chunk_launches, st.launches) == (2, 3)
    assert st.kv_pages_walked == 2 + 3 + 2
    assert st.kv_pages_listed == (2 + 2 + 1) * 8


def test_paged_walk_counters_stay_zero_without_pages(mesh16, plan16):
    from repro.configs import get_config
    from repro.configs.registry import reduced
    ec = EngineConfig(s_max=32, buckets=(1, 2), block_pos_stride=4,
                      prefill_chunks=(4,))
    eng = build_engine(reduced(get_config("mamba2-780m")), mesh16, plan16,
                       engine_cfg=ec, seed=0)
    eng.submit([1, 2, 3, 4, 5], SamplingParams(max_tokens=3))
    eng.drain()
    assert eng.stats.launches > 0
    assert eng.stats.kv_pages_walked == eng.stats.kv_pages_listed == 0
