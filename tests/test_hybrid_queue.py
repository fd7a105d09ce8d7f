"""CommandQueue / KernelEvent unit tests (cl_command_queue analogue)."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from repro.core.hybrid import CommandQueue, HybridKernel
from repro.core.shmem import ShmemGrid
from repro.models.config import ModelConfig
from repro.partition import MODEL
from repro.serve.engine import EngineConfig, SamplingParams, build_engine

GRID = ShmemGrid(MODEL, 4, 4)


def _add_kernel():
    return HybridKernel(lambda grid, a, b: a + b, grid=GRID,
                        in_specs=(P(MODEL), P(MODEL)), out_specs=P(MODEL),
                        name="addk")


def test_build_stamps_cost_stats_on_first_build_only(mesh16):
    """Regression: a rebuild must keep cumulative build_time_s but must NOT
    overwrite the per-launch cost stats recorded at first build."""
    queue = CommandQueue(mesh16)
    kern = _add_kernel()
    a = jnp.ones((16, 8), jnp.float32)
    b = jnp.full((16, 8), 2.0, jnp.float32)
    queue.build(kern, a, b)
    ev = queue.events["addk"]
    t1 = ev.build_time_s
    assert t1 > 0.0
    # simulate stats a consumer is aggregating against, then rebuild
    ev.flops, ev.bytes_accessed, ev.collective_bytes = 123.5, 7.0, 3.0
    queue.build(kern, a, b)
    assert (ev.flops, ev.bytes_accessed, ev.collective_bytes) == \
        (123.5, 7.0, 3.0)
    assert ev.build_time_s > t1          # build time stays cumulative


def test_enqueue_finish_event_lifecycle(mesh16):
    queue = CommandQueue(mesh16)
    kern = _add_kernel()
    a = jnp.ones((16, 8), jnp.float32)
    b = jnp.full((16, 8), 2.0, jnp.float32)
    out = queue.enqueue(kern, a, b)      # implicit first build
    assert queue.depth == 1
    queue.finish()
    assert queue.depth == 0
    np.testing.assert_allclose(np.asarray(out), 3.0)
    ev = queue.events["addk"]
    assert ev.launches == 1
    # the event is a cost-and-count record: host time around launches is
    # the drive loop's phase counters, so the event carries no timestamps
    assert {f.name for f in dataclasses.fields(ev)} == {
        "name", "flops", "bytes_accessed", "collective_bytes", "launches",
        "build_time_s"}


def test_max_depth_tracks_inflight_high_water(mesh16):
    """``max_depth`` is the enqueued-but-not-drained high-water mark, not
    the current occupancy — it must survive the drain."""
    queue = CommandQueue(mesh16)
    kern = _add_kernel()
    a = jnp.ones((16, 8), jnp.float32)
    queue.enqueue(kern, a, a)
    queue.enqueue(kern, a, a)
    queue.enqueue(kern, a, a)
    assert queue.depth == 3 and queue.max_depth == 3
    queue.finish()
    assert queue.depth == 0 and queue.max_depth == 3
    queue.enqueue(kern, a, a)
    queue.finish()
    assert queue.max_depth == 3          # high-water, not last depth
    assert queue.events["addk"].launches == 4


def test_event_accounting_under_mixed_prefill_decode_traffic(mesh16, plan16):
    """KernelEvent invariants under real mixed engine traffic: staggered
    submits force prefill chunk launches to interleave with decode-phase
    slots, and every event record must stay consistent — the step counter
    holds the wall time the launches were driven over, launches partition
    across executables, ``n_executables`` matches the distinct kernels
    actually used, and the engine's finish()-per-step discipline keeps the
    queue's high-water depth at exactly 1."""
    cfg = ModelConfig(name="q", family="dense", d_model=64, n_layers=2,
                      n_heads=8, n_kv_heads=4, d_ff=128, vocab_size=128,
                      param_dtype=jnp.float32, compute_dtype=jnp.float32,
                      attn_block_kv=32)
    ec = EngineConfig(s_max=32, buckets=(1, 2, 4), block_pos_stride=4,
                      prefill_chunks=(4, 16))
    eng = build_engine(cfg, mesh16, plan16, engine_cfg=ec, seed=0)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(5, 12))).tolist()
               for _ in range(4)]
    # staggered arrivals: r0 reaches decode while later prompts prefill,
    # so chunk launches carry mixed n_valid (decode slots ride along)
    eng.submit(prompts[0], SamplingParams(max_tokens=10))
    eng.step()
    eng.step()
    for p in prompts[1:]:
        eng.submit(p, SamplingParams(max_tokens=4))
    eng.drain()

    events = eng.kernel_events()
    assert events and set(events) == set(eng.queue.events)
    # mixed traffic really happened: both executable kinds were used
    assert any(n.startswith("prefill_bs") for n in events)
    assert any(n.startswith("serve_step_bs") for n in events)
    # one compiled executable per distinct kernel name, nothing orphaned
    assert eng.queue.n_executables == len(events)
    # launches partition exactly across events
    assert sum(ev.launches for ev in events.values()) == eng.stats.steps
    for name, ev in events.items():
        assert ev.launches > 0, name
    # the wall time the launches were driven over is the step counter,
    # tiled by the phases inside it; throughput reads it
    st = eng.stats
    phases = (st.schedule_ns + st.operand_ns + st.wait_ns + st.fetch_ns
              + st.sample_ns + st.publish_ns)
    assert 0 < phases <= st.step_ns
    assert eng.throughput_tok_s() == pytest.approx(
        st.tokens_generated * 1e9 / st.step_ns)
    # finish()-per-step discipline: never more than one in-flight enqueue
    assert eng.queue.max_depth == 1
    assert eng.queue.depth == 0
