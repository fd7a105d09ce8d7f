"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

Nothing runs: each test lowers one Pallas kernel at the published widths of
the model that uses it and compiles it for a v5e chip that is described, not
attached.  The TPU compiler refuses what the interpreter accepts (block
shapes off the (8, 128) tiling, primitives Mosaic cannot lower), so these
guard the compiled path on hosts without a chip.

The topology is described inside a module fixture: describing it loads the
TPU library, which one process at a time may hold.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.ssd_scan.kernel import ssd_apply_pallas, ssd_chunk_pallas


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an AOT compile for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, sharding, kernel):
    """Compile and check the Mosaic call is there under the kernel's own
    ``name=``: a profiler trace shows the op as ``%<kernel>.N``, and the
    benchmark finds the paged-attention kernel by that name."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert f"%{kernel}." in text, kernel
    return text


# qwen2-0.5b on one chip, as the chat cell serves it: 14 query heads over 2
# kv heads of width 64, pages of 16 positions, s_max 4096 -> 256 table
# entries over an 8192-page arena; decode, and each prefill chunk length.
HQ, KVH, HD, STRIDE, T, N_PAGES = 14, 2, 64, 16, 256, 8192


@pytest.mark.parametrize("L", [1, 16, 64, 256],
                         ids=["decode", "prefill16", "prefill64", "prefill"])
def test_paged_attention_compiles_for_v5e(one_chip, L):
    B = 16
    _compile(
        lambda q, kc, vc, lidx, own, q0, kv_len: paged_attention_pallas(
            q, kc, vc, lidx, own, q0, kv_len, stride=STRIDE),
        ((B, HQ, L, HD), jnp.bfloat16),
        ((N_PAGES, STRIDE, KVH, HD), jnp.bfloat16),
        ((N_PAGES, STRIDE, KVH, HD), jnp.bfloat16),
        ((B, T), jnp.int32), ((B, T), jnp.int32), ((B,), jnp.int32),
        ((B,), jnp.int32), sharding=one_chip, kernel="paged_attention")


# mamba2-780m on one chip: 48 heads of width 64, state 128, one group; the
# scan's own chunk (128) and the engine's prefill chunk (256).
H, P, N, G = 48, 64, 128, 1


@pytest.mark.parametrize("L", [128, 256])
def test_ssd_kernels_compile_for_v5e(one_chip, L):
    B, nc = 2, 2
    S = nc * L
    _compile(
        lambda x, dt, A, Bm, Cm: ssd_chunk_pallas(x, dt, A, Bm, Cm, chunk=L),
        ((B, S, H, P), jnp.bfloat16), ((B, S, H), jnp.float32),
        ((H,), jnp.float32), ((B, S, G, N), jnp.bfloat16),
        ((B, S, G, N), jnp.bfloat16), sharding=one_chip, kernel="ssd_chunk")
    _compile(
        lambda y, Cm, ce, sin: ssd_apply_pallas(y, Cm, ce, sin),
        ((B, nc, H, L, P), jnp.bfloat16), ((B, S, G, N), jnp.bfloat16),
        ((B, nc, H, 1, L), jnp.float32), ((B, nc, H, N, P), jnp.float32),
        sharding=one_chip, kernel="ssd_apply")


# granite-4.0-h-small on one chip: NoPE attention of 32 query heads over 8
# kv heads of width 128, scored at its published scale (1/128, not
# 128 ** -0.5), over the offline cell's 8192-page arena (s_max 2048).
G_HQ, G_KVH, G_HD, G_T = 32, 8, 128, 128


@pytest.mark.parametrize("L", [1, 256], ids=["decode", "prefill"])
def test_granite_paged_attention_compiles_for_v5e(one_chip, L):
    B = 64
    _compile(
        lambda q, kc, vc, lidx, own, q0, kv_len: paged_attention_pallas(
            q, kc, vc, lidx, own, q0, kv_len, stride=STRIDE,
            scale=0.0078125),
        ((B, G_HQ, L, G_HD), jnp.bfloat16),
        ((N_PAGES, STRIDE, G_KVH, G_HD), jnp.bfloat16),
        ((N_PAGES, STRIDE, G_KVH, G_HD), jnp.bfloat16),
        ((B, G_T), jnp.int32), ((B, G_T), jnp.int32), ((B,), jnp.int32),
        ((B,), jnp.int32), sharding=one_chip, kernel="paged_attention")


# its share of an expert layer: 9 held experts of width 768 over d_model
# 4096, the rows of a 64-slot decode step (64 x min(10, 9), rounded to the
# kernel's row tile) through both grouped matmuls of the SwiGLU experts.
def test_grouped_expert_gemm_compiles_for_v5e(one_chip):
    from repro.models.moe import expert_ffn
    _compile(
        lambda xs, w1, w2, gs: expert_ffn(xs, w1, w2, gs, pallas=True),
        ((640, 4096), jnp.bfloat16), ((9, 4096, 2 * 768), jnp.bfloat16),
        ((9, 768, 4096), jnp.bfloat16), ((9,), jnp.int32),
        sharding=one_chip, kernel="gmm")


# two of those layers back to back: left free, XLA prefetches the second
# layer's gate/up weights into VMEM ahead of its kernel (the device time of
# the kernel then leaves out their reads); held in HBM, every weight reaches
# its kernel from HBM and the kernel streams it itself.
def test_expert_weights_reach_the_grouped_gemm_from_hbm(one_chip):
    import re

    from repro.models.moe import expert_ffn

    def two_layers(xs, w1a, w2a, w1b, w2b, gs):
        y = expert_ffn(xs, w1a, w2a, gs, pallas=True)
        return expert_ffn(jnp.tanh(y) * xs, w1b, w2b, gs, pallas=True)

    w1, w2 = ((9, 4096, 2 * 768), jnp.bfloat16), ((9, 768, 4096), jnp.bfloat16)
    text = _compile(two_layers, ((640, 4096), jnp.bfloat16), w1, w2, w1, w2,
                    ((9,), jnp.int32), sharding=one_chip, kernel="gmm")
    defs = dict(re.findall(r"^\s*(%[\w.-]+) = (.*)$", text, re.M))
    weights = [ops.split(", ")[-1].split("*/")[-1] for ops in re.findall(
        r"%gmm(?:\.\d+)? = .*?custom-call\(([^)]*)\)", text)]
    assert len(weights) == 4
    for w in weights:
        shape = defs[w].split(" ", 1)[0]
        assert "S(1)" not in shape, (w, defs[w][:120])
